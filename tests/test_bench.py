"""bench.py harness tests: the inline main() and its JSON contract, the
no-device refusal, dataset provenance labeling, OOM classification, FLOP
accounting.

The reference has no benchmark harness at all (BASELINE.md: "published:
{}"); bench.py is the measurement artifact, so what it refuses to print is
tested as first-class behavior: a run that finds no TPU and was not told
``--device=cpu`` exits non-zero with NO metric line.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench
from distributed_tensorflow_tpu import data

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(**extra):
    env = dict(os.environ)
    env.update({"DTTPU_BENCH_SMOKE": "1",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
               **{k: str(v) for k, v in extra.items()})
    return env


def _run(args, env, timeout=600):
    proc = subprocess.run([sys.executable, BENCH] + args, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=timeout, cwd=REPO)
    return proc


class TestInlineMain:
    """Whole-bench subprocess runs (slow tier, tests/conftest.py)."""

    def test_smoke_run_single_json_line(self):
        """--device=cpu runs main() inline in the one process; stdout
        carries exactly one JSON line with the full field contract."""
        proc = _run(["--device=cpu"], _env())
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1, lines
        r = json.loads(lines[0])
        assert r["value"] > 0
        assert r["metric"].startswith("mnist_mlp_train_examples_per_sec")
        assert r["fingerprint"]["backend"] == "cpu"   # and says so
        assert r["data"] == "synthetic"
        assert r["unit"] == "examples/sec/chip"
        assert r["vs_baseline"] > 0
        # XLA:CPU reports flops, so the FLOP accounting fields must appear.
        assert r.get("flops_per_example", 0) > 0
        # telemetry fields (default-on): barrier-closed per-update
        # latency percentiles + the host-timeline trace file, whose
        # dispatch spans and jit_compile instants must parse as Chrome
        # trace JSON (docs/OBSERVABILITY.md)
        assert r["step_time_p50_ms"] > 0
        assert r["step_time_p95_ms"] >= r["step_time_p50_ms"]
        assert os.path.exists(r["trace_file"])
        trace = json.load(open(r["trace_file"]))
        names = {e["name"] for e in trace["traceEvents"]}
        assert "dispatch" in names and "jit_compile" in names

    def test_telemetry_off_drops_fields(self):
        """DTTPU_BENCH_TELEMETRY=0: no trace file, no latency fields —
        the schema change is strictly opt-out."""
        proc = _run(["--device=cpu"], _env(DTTPU_BENCH_TELEMETRY=0))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        r = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        assert r["value"] > 0
        assert "step_time_p50_ms" not in r
        assert "step_time_p95_ms" not in r
        assert "trace_file" not in r


class TestNoDevice:
    def test_no_tpu_and_no_device_flag_prints_no_metric(self):
        """No fallback: without --device, a platform other than tpu is a
        non-zero exit with a one-line reason and NOTHING on stdout — a CPU
        rate can never arrive under a device metric's name."""
        env = _env()
        env.pop("DTTPU_BENCH_DEVICE", None)
        env["JAX_PLATFORMS"] = "cpu"
        proc = _run([], env, timeout=120)
        assert proc.returncode != 0
        assert proc.stdout.decode().strip() == ""
        err = proc.stderr.decode()
        assert "no TPU" in err and "--device=cpu" in err


class TestPromoteLevers:
    """scripts/promote_levers.py selection rule: a PURE lever arm must
    beat base by >= 2% measured tokens/sec to become a bench default."""

    def _promote(self, rows):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import promote_levers
        finally:
            sys.path.pop(0)
        return promote_levers.promote(rows)

    def test_winning_levers_promote(self):
        rows = [
            {"model": "gpt", "arm": "base", "tokens_per_sec": 100.0},
            {"model": "gpt", "arm": "loss_chunk", "tokens_per_sec": 110.0},
            {"model": "gpt", "arm": "remat_dots", "tokens_per_sec": 101.0},
            {"model": "bert", "arm": "base", "tokens_per_sec": 200.0},
            {"model": "bert", "arm": "mlm_gather", "tokens_per_sec": 230.0},
        ]
        env, evidence = self._promote(rows)
        # loss_chunk (+10%) and mlm_gather (+15%) promote; remat_dots
        # (+1%, under the 2% bar) does not
        assert env == {"DTTPU_BENCH_LOSS_CHUNK": "512",
                       "DTTPU_BENCH_MLM_GATHER": "1"}
        assert {e["model"] for e in evidence} == {"gpt", "bert"}

    def test_bert_remat_dots_promotes(self):
        # the 08-01 hardware table's shape: bert remat_dots is a pure
        # +12% lever and must map onto DTTPU_BENCH_BERT_REMAT
        rows = [
            {"model": "bert", "arm": "base", "tokens_per_sec": 131123.0},
            {"model": "bert", "arm": "remat_dots",
             "tokens_per_sec": 147351.0},
        ]
        env, _ = self._promote(rows)
        assert env == {"DTTPU_BENCH_BERT_REMAT": "dots"}

    def test_composite_arms_never_promote(self):
        # a composite arm can WIN the table without promoting env levers:
        # its batch move has no env knob
        rows = [
            {"model": "gpt", "arm": "base", "tokens_per_sec": 100.0},
            {"model": "gpt", "arm": "loss_chunk_b192",
             "tokens_per_sec": 150.0},
        ]
        env, evidence = self._promote(rows)
        assert env == {}
        assert evidence[0]["best"]["arm"] == "loss_chunk_b192"

    def test_no_base_row_promotes_nothing(self):
        rows = [{"model": "gpt", "arm": "loss_chunk",
                 "tokens_per_sec": 1e9}]
        env, _ = self._promote(rows)
        assert env == {}

    def test_parse_rejects_smoke_and_cpu_rows(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import promote_levers
        finally:
            sys.path.pop(0)
        mk = lambda **kw: json.dumps(dict(
            model="gpt", arm="base", tokens_per_sec=1.0, **kw))
        lines = [mk(backend="cpu", smoke=True),
                 mk(backend="cpu", smoke=False),
                 mk(backend="tpu", smoke=True),
                 mk(backend="tpu", smoke=False)]
        assert len(promote_levers.parse(lines)) == 1
        assert len(promote_levers.parse(lines, allow_any=True)) == 4


class TestPromotedDefaults:
    """bench._load_promoted_defaults: PROMOTED.json is a DEFAULT layer —
    explicit env wins, SMOKE runs ignore it, absence is silent."""

    def test_setdefault_env_wins_and_smoke_skips(self, monkeypatch,
                                                 tmp_path):
        f = tmp_path / "PROMOTED.json"
        f.write_text(json.dumps(
            {"env": {"DTTPU_TEST_PROMOTED_KNOB": "5"}}))
        monkeypatch.setattr(bench, "_PROMOTED", str(f))
        monkeypatch.setattr(bench, "SMOKE", False)
        # seed-then-delete so monkeypatch records an undo for the key —
        # _load_promoted_defaults writes os.environ directly, and an
        # unrecorded setdefault would leak past teardown
        monkeypatch.setenv("DTTPU_TEST_PROMOTED_KNOB", "seed")
        monkeypatch.delenv("DTTPU_TEST_PROMOTED_KNOB")
        bench._load_promoted_defaults()
        assert os.environ["DTTPU_TEST_PROMOTED_KNOB"] == "5"
        monkeypatch.setenv("DTTPU_TEST_PROMOTED_KNOB", "9")
        bench._load_promoted_defaults()
        assert os.environ["DTTPU_TEST_PROMOTED_KNOB"] == "9"
        monkeypatch.delenv("DTTPU_TEST_PROMOTED_KNOB")
        monkeypatch.setattr(bench, "SMOKE", True)
        bench._load_promoted_defaults()
        assert "DTTPU_TEST_PROMOTED_KNOB" not in os.environ

    def test_absent_and_corrupt_files_are_tolerated(self, monkeypatch,
                                                    tmp_path):
        monkeypatch.setattr(bench, "SMOKE", False)
        monkeypatch.setattr(bench, "_PROMOTED",
                            str(tmp_path / "missing.json"))
        bench._load_promoted_defaults()          # no raise
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        monkeypatch.setattr(bench, "_PROMOTED", str(bad))
        bench._load_promoted_defaults()          # warns, no raise


class TestRecovery:
    """bench.py --config=recovery: the resilience smoke's JSON contract
    (docs/RESILIENCE.md).  Run in-process — the row is tiny by design
    (XOR MLP) and a subprocess would mostly measure jax import time."""

    def test_recovery_schema_and_one_injected_kill(self):
        result = bench.bench_recovery()
        assert result["metric"] == "recovery_restore_ms"
        assert result["unit"] == "ms"
        assert result["value"] > 0
        assert result["restore_ms"] == result["value"]
        # the kill lands between two save intervals: 0 < lost <= interval
        assert 0 <= result["recovery_steps_lost"] <= 5
        assert result["restarts"] >= 1
        assert result["faults_injected"] == 1
        assert result["final_step"] == 24
        # watchdog smoke: the injected stall (1.0 s) was detected at the
        # first post-stall check — detection latency sits just above the
        # stall itself, never an unbounded wait — and the quarantined
        # replica's requests migrated to the survivor
        assert result["watchdog_quarantined"] == 1
        assert result["watchdog_detect_ms"] is not None
        stall_ms = result["watchdog_stall_s"] * 1e3
        assert stall_ms < result["watchdog_detect_ms"] < stall_ms + 5e3
        assert result["watchdog_migrations"] >= 1
        # goodput ledger satellite: the recovery row carries the full
        # wall-clock split, and the buckets sum to wall within 1%
        gp = result["goodput"]
        assert result["goodput_pct"] == gp["goodput_pct"]
        assert 0.0 < gp["goodput_pct"] <= 100.0
        assert sum(gp["buckets_s"].values()) == pytest.approx(
            gp["wall_s"], rel=0.01)
        for bucket in ("step", "checkpoint_save", "checkpoint_restore",
                       "restart_backoff", "fault_recovery"):
            assert gp["buckets_s"][bucket] > 0.0, (bucket, gp)
        json.dumps(result)                      # one-line-JSON safe


class TestIdentityStamp:
    """Every bench line carries run identity (obs/ledger.py schema):
    run_id, git_sha, backend/mesh fingerprint — anonymous rows can only
    be compared by filename convention."""

    def test_stamp_identity_fields(self):
        from distributed_tensorflow_tpu.obs import ledger as ledger_lib
        r = bench._stamp_identity({"value": 1.0}, "mnist_mlp")
        assert r["schema_version"] == ledger_lib.SCHEMA_VERSION
        assert len(r["run_id"]) == 16
        assert r["config"] == "mnist_mlp"
        assert r["timestamp"] > 0
        fp = r["fingerprint"]
        assert fp["backend"] == "cpu"
        assert fp["device_count"] >= 1
        assert fp["process_count"] >= 1
        assert "device_kind" in fp
        # two runs never share a run_id
        r2 = bench._stamp_identity({"value": 1.0}, "mnist_mlp")
        assert r2["run_id"] != r["run_id"]
        # a stamped line is directly convertible to a ledger row
        ledger_lib.validate_row(ledger_lib.row_from_bench(r))

    def test_git_sha_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("DTTPU_GIT_SHA", "cafe1234babe")
        assert bench._git_sha() == "cafe1234babe"
        monkeypatch.delenv("DTTPU_GIT_SHA")
        sha = bench._git_sha()       # this repo IS a git checkout
        assert sha and sha != "unknown" and "\n" not in sha

    @pytest.mark.slow
    def test_smoke_line_is_stamped_and_ledgered(self, tmp_path):
        """Subprocess contract: the printed line carries the stamps, and
        DTTPU_BENCH_LEDGER appends one valid row.  A full bench
        subprocess, so slow-tier like the other smokes."""
        from distributed_tensorflow_tpu.obs import ledger as ledger_lib
        ledger_path = str(tmp_path / "ledger.jsonl")
        proc = _run(["--device=cpu"],
                    _env(DTTPU_BENCH_LEDGER=ledger_path,
                         DTTPU_GIT_SHA="feedbeef0123"))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        r = json.loads(proc.stdout.decode().strip().splitlines()[-1])
        assert r["git_sha"] == "feedbeef0123"
        assert r["config"] == "mnist_mlp"
        assert len(r["run_id"]) == 16
        assert r["fingerprint"]["backend"] == "cpu"
        rows = ledger_lib.PerfLedger(ledger_path).rows()
        assert len(rows) == 1
        row = rows[0]
        assert row["run_id"] == r["run_id"]
        assert row["git_sha"] == "feedbeef0123"
        assert row["measured"]["value"] == r["value"]
        assert row["knobs"].get("DTTPU_BENCH_SMOKE") == "1"


class TestHelpers:
    def test_is_oom(self):
        assert bench._is_oom(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1 bytes"))
        assert bench._is_oom(RuntimeError("Ran out of memory on device"))
        assert not bench._is_oom(ValueError("shape mismatch"))

    def test_transformer_flops_per_token(self):
        params = {"w": np.zeros((1000,), np.float32)}
        f = bench._transformer_flops_per_token(params, num_layers=2,
                                               hidden=8, seq=16)
        assert f == 6 * 1000 + 12 * 2 * 8 * 16

    def test_decode_eval_weights_device_resident(self, monkeypatch):
        """The trained decode-row params must stay DEVICE-resident: a
        host (numpy) tree makes every later generate() re-upload the full
        weight set per call (builder-measured 2026-08-01: fp decode 991
        tok/s from a host tree vs 23.6k device-resident)."""
        import jax
        from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

        monkeypatch.setattr(bench, "SMOKE", True)
        monkeypatch.delenv("DTTPU_BENCH_DECODE_TRAIN", raising=False)
        config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                           num_heads=2, intermediate_size=32,
                           max_position=16, dropout_rate=0.0)
        params, steps, sample = bench._decode_eval_weights(GPT(config),
                                                           config)
        assert steps > 0
        for leaf in jax.tree.leaves(params):
            assert isinstance(leaf, jax.Array), type(leaf)
        toks = sample(np.random.default_rng(0), 2, 8)
        assert toks.shape == (2, 8) and toks.max() < 64

    def test_decode_eval_weights_disable_knob(self, monkeypatch):
        monkeypatch.setenv("DTTPU_BENCH_DECODE_TRAIN", "0")
        from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
        config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                           num_heads=2, intermediate_size=32,
                           max_position=16, dropout_rate=0.0)
        _, steps, _ = bench._decode_eval_weights(GPT(config), config)
        assert steps == 0

    def test_attach_mfu_with_peak_override(self, monkeypatch):
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        r = bench._attach_mfu({"metric": "m"}, rate_per_chip=1e6,
                              flops_per_example=1e5)
        assert r["mfu"] == pytest.approx(0.1)
        assert r["flops_source"] == "xla"

    def test_attach_mfu_analytic_fallback(self, monkeypatch):
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        r = bench._attach_mfu({"metric": "m"}, 1e6, None, analytic=2e5)
        assert r["mfu"] == pytest.approx(0.2)
        assert r["flops_source"] == "analytic"

    def test_attach_mfu_scan_undercount_flips_to_analytic(self, monkeypatch):
        # XLA counts a lax.scan body once, so a scanned 12-layer LM's
        # compiled-step flops land at ~1/3 of the 6N analytic figure;
        # the analytic model must win and the raw XLA number be recorded
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        r = bench._attach_mfu({"metric": "m"}, 1e3,
                              flops_per_example=2.9e8, analytic=7.7e8,
                              scanned=True)
        assert r["flops_source"] == "analytic"
        assert r["flops_per_example"] == pytest.approx(7.7e8)
        assert r["flops_xla_scan_undercount"] == pytest.approx(2.9e8)
        assert r["mfu"] == pytest.approx(0.77)

    def test_attach_mfu_honest_xla_kept(self, monkeypatch):
        # resnet-shaped case: XLA ~= 3x the forward-only analytic constant
        # — the compiled-step figure is honest and must keep priority
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        r = bench._attach_mfu({"metric": "m"}, 1e3,
                              flops_per_example=3.6e10, analytic=1.23e10,
                              scanned=True)
        assert r["flops_source"] == "xla"
        assert "flops_xla_scan_undercount" not in r

    def test_attach_mfu_unscanned_never_flips(self, monkeypatch):
        # an unscanned row whose honest XLA figure is below a rough
        # hard-coded analytic constant must NOT be replaced — the flip is
        # scoped to programs where the scan-body undercount can occur
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        r = bench._attach_mfu({"metric": "m"}, 1e3,
                              flops_per_example=7e7, analytic=1.53e8)
        assert r["flops_source"] == "xla"
        assert r["flops_per_example"] == pytest.approx(7e7)
        assert "flops_xla_scan_undercount" not in r


class TestProvenance:
    def test_no_dir_is_synthetic(self):
        assert data.provenance("mnist", None) == "synthetic"
        assert data.provenance("cifar10", "") == "synthetic"

    def test_unknown_dataset_raises(self):
        with pytest.raises(ValueError):
            data.provenance("imagenet", "/tmp")

    def test_real_mnist_npz(self, tmp_path):
        x = np.zeros((8, 28, 28), np.uint8)
        y = np.zeros((8,), np.uint8)
        np.savez(tmp_path / "mnist.npz", x_train=x, y_train=y,
                 x_test=x, y_test=y)
        assert data.provenance("mnist", str(tmp_path)) == "real"
        (xt, yt), (xe, ye) = data.mnist(str(tmp_path), flatten=True)
        assert xt.shape == (8, 784) and yt.dtype == np.int32

    def test_partial_idx_files_stay_synthetic(self, tmp_path):
        (tmp_path / "train-images-idx3-ubyte").write_bytes(b"x")
        assert data.provenance("mnist", str(tmp_path)) == "synthetic"

    def test_real_cifar_npz(self, tmp_path):
        x = np.zeros((4, 32, 32, 3), np.uint8)
        y = np.zeros((4,), np.uint8)
        np.savez(tmp_path / "cifar10.npz", x_train=x, y_train=y,
                 x_test=x, y_test=y)
        assert data.provenance("cifar10", str(tmp_path)) == "real"


class TestGptLong:
    def test_gpt_long_metric_and_seq_pinned_against_env(self):
        """gpt_long is the gpt row pinned at seq 2048 (the flash-dispatch
        operating point).  Round-5 advisor fix: the row's EXPLICIT seq
        now beats DTTPU_BENCH_SEQ — an exported env var must not
        silently retarget a named row's defining parameter (the SMOKE
        config keeps the run cheap on CPU despite the 2048 label)."""
        proc = _run(["--config=gpt_long", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=128))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"].startswith("gpt_long_lm_train_tokens_per_sec")
        assert r["seq_len"] == 2048
        assert r["value"] > 0

    def test_gpt_decode_int8_smoke(self):
        """int8 decode measures both paths in one run and reports their
        greedy-token agreement; on the smoke model the two paths must
        agree on nearly every token or the quant path is broken."""
        proc = _run(["--config=gpt_decode_int8", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=64))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"].startswith("gpt_decode_int8_tokens_per_sec")
        assert r["value"] > 0 and r["fp_value"] > 0
        assert r["greedy_token_match"] > 0.9

    def test_gpt_decode_spec_smoke(self):
        """Speculative decode: trains the target, distills the truncated
        draft (the donation-sensitive deep-copy path — a dropped copy
        deletes the target's shared embedding/head buffers and crashes
        here), and must keep the exactness guarantee: spec output ==
        plain greedy output."""
        proc = _run(["--config=gpt_decode_spec", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=64))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"].startswith("gpt_decode_spec_tokens_per_sec")
        assert r["value"] > 0 and r["plain_value"] > 0
        assert r["greedy_token_match"] > 0.9
        assert 0.0 <= r["acceptance"] <= 1.0
        assert r["trained_steps"] > 0

    def test_gpt_moe_smoke(self):
        proc = _run(["--config=gpt_moe", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=64))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        r = json.loads(lines[0])
        assert r["metric"].startswith("gpt_moe_lm_train_tokens_per_sec")
        assert r["moe_experts"] == 8
        assert r["value"] > 0

    def test_gpt_serve_smoke_schema(self):
        """Continuous-batching row: the seeded mixed-length arrival
        trace runs on the CPU mesh and the JSON carries the serving
        schema — engine tokens/s, TTFT percentiles, a vs_lockstep ratio
        against the in-process lock-step baseline, plus two more phases: the
        shared-prefix trace (radix-cache reuse vs the prefix_cache=False
        ablation) and the fixed-HBM concurrency measurement.
        Admission/retirement must never recompile the hot executables:
        after warmup the sanitizer sees zero violations, so
        retrace_warnings must be absent."""
        proc = _run(["--config=gpt_serve", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=128))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"].startswith("gpt_serve_tokens_per_sec")
        assert r["tokens_per_sec"] > 0
        assert r["lockstep_tokens_per_sec"] > 0
        assert r["vs_lockstep"] == r["vs_baseline"]
        assert r["vs_lockstep_paged"] > 0
        # the fused page-walk kernel leg: same paged layout read
        # through the Pallas kernel (interpret mode on CPU, so the
        # ratio vs the gather path is informational off-TPU — the
        # fields just have to exist and be sane)
        assert r["kernel_tokens_per_sec"] > 0
        assert r["vs_lockstep_paged_kernel"] > 0
        assert r["paged_kernel_vs_gather"] > 0
        assert 0 < r["ttft_p50_ms"] <= r["ttft_p95_ms"]
        assert r["requests"] > 0 and r["num_slots"] > 0
        assert r["page_size"] > 0
        assert r.get("retrace_warnings", 0) == 0
        # the acceptance bar: strictly better than lock-step batching
        # on the mixed-length trace (CPU smoke margin is ~1.2-1.4x)
        assert r["vs_lockstep"] > 1.0
        # paged-KV phase 1: the shared-prefix trace.  The radix cache
        # must actually fire (hits, skipped windows) and pay for
        # itself: tokens/s AND TTFT p50 strictly better than the same
        # engine with reuse ablated.
        sp = r["shared_prefix"]
        assert sp["requests"] > 0
        assert sp["prefix_hit_rate"] > 0
        assert sp["prefill_windows_skipped"] > 0
        assert sp["prefix_tokens_reused"] > 0
        assert sp["vs_no_reuse"] > 1.0
        assert 0 < sp["ttft_p50_ms"] < sp["no_reuse_ttft_p50_ms"]
        assert sp["lockstep_tokens_per_sec"] > 0
        assert sp["kernel_tokens_per_sec"] > 0
        assert sp["kernel_vs_gather"] > 0
        # paged-KV phase 2: at the contiguous layout's HBM budget the
        # paged engine runs strictly more concurrent slots
        assert r["slots_at_fixed_mem"] > r["slots_at_fixed_mem_contiguous"]

    def test_fleet_smoke_schema(self):
        """Fleet row: the adversarial three-tenant block burst routed
        over 2 CPU replicas under the deficit fair-share policy with a
        LoRA adapter on one tenant's traffic.  The JSON carries fleet
        tokens/s, per-tenant TTFT p50/p95, and fairness_ratio — the
        weight-normalized admitted-token min/max over the contended
        window, where plain FIFO on this trace measures 0.0.  Placement,
        failover, and adapter swaps must never recompile: zero
        retrace_warnings."""
        proc = _run(["--config=fleet", "--device=cpu"],
                    _env(DTTPU_BENCH_SEQ=128))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"] == "fleet_tokens_per_sec"
        assert r["tokens_per_sec"] > 0
        assert r["replicas"] == 2
        for tenant in ("free", "pro", "batch"):
            p50 = r["tenant_ttft_p50_ms"][tenant]
            p95 = r["tenant_ttft_p95_ms"][tenant]
            assert 0 < p50 <= p95
        assert 0 < r["ttft_p50_ms"] <= r["ttft_p95_ms"]
        assert r.get("retrace_warnings", 0) == 0
        # the fair-share bar: the deficit queue must interleave the
        # per-tenant blocks FIFO would serialize (FIFO scores 0.0; the
        # CPU smoke converges well above half)
        assert r["fairness_ratio"] > 0.5
        # migration leg: drain-by-migration frees the replica without
        # waiting out its decodes, and the kill leg salvages decode
        # work through snapshots (ratio in (0, 1]: the migrated
        # requests were mid-decode, not finished)
        assert 0 < r["drain_migrate_ms"] < r["drain_wait_ms"]
        assert 0 < r["tokens_preserved_ratio"] <= 1.0
        assert r["migrations"] >= 1

    def test_fleet_sim_smoke_schema(self):
        """Fleet-simulator row (docs/FLEET_SIM.md): the seeded
        diurnal+burst trace with two scheduled correlated kills through
        the REAL router on virtual time, autoscaler-vs-static scored as
        attainment per replica-second, the SLO-vs-replicas capacity
        curve, and the stub-validation leg (sim within 25% of a real
        serve.Engine replay, asserted in-process)."""
        proc = _run(["--config=fleet_sim", "--device=cpu"], _env())
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert len(lines) == 1
        r = json.loads(lines[0])
        assert r["metric"] == "fleet_sim_requests_per_sec"
        assert r["value"] > 0
        assert r["simulated_requests"] == (2 * r["requests_main"]
                                           + 4 * r["requests_curve"])
        assert r["sim_wall_s"] < 60.0
        # every leg accounts for every request, and the chaos events
        # actually fired
        for leg in (r["autoscaler"], r["static"]):
            assert (leg["completed"] + leg["deadline_exceeded"]
                    + leg["lost"] == r["requests_main"])
            assert leg["correlated_kills_armed"] == 2
            assert 0 < leg["slo_attainment"] <= 1.0
        assert r["autoscaler"]["scale_outs"] >= 1
        # the acceptance bar: the SLO policy buys attainment with
        # capacity at the right moments — never worse per replica-second
        # than always-on peak provisioning
        assert r["autoscaler_vs_static"] >= 1.0
        curve = r["slo_vs_replicas"]
        assert set(curve) == {"2", "3", "4", "6"}
        for c in curve.values():
            assert 0 < c["slo_attainment"] <= 1.0
            assert c["ttft_p99_ms"] > 0
        assert (curve["6"]["slo_attainment"]
                >= curve["2"]["slo_attainment"])
        assert r["cost_model"]["provenance"] == "analytic"
        v = r["validation"]
        assert abs(v["tokens_per_sec_ratio"] - 1.0) <= 0.25
        assert abs(v["ttft_p50_ratio"] - 1.0) <= 0.25
        assert v["calibrated"]["decode_tick_s"] > 0
        assert r.get("retrace_warnings", 0) == 0
        # prefix-affinity ablation (docs/SERVING.md §Fleet affinity
        # policy): same fingerprinted Zipf trace both arms, affinity
        # wins on throughput AND hit rate
        abl = r["ablation"]
        assert abl["trace_fingerprint"] and abl["requests"] >= 2000
        assert r["affinity_vs_blind"] > 1.0
        assert (abl["affinity"]["fleet_prefix_hit_rate"]
                > abl["blind"]["fleet_prefix_hit_rate"])
        assert r["fleet_prefix_hit_rate"] \
            == abl["affinity"]["fleet_prefix_hit_rate"]
        for arm in abl["affinity"], abl["blind"]:
            assert 0 < arm["ttft_p50_ms"] <= arm["ttft_p95_ms"]
        # the real 2-replica CPU leg: affinity beats blind on actual
        # radix-cache hits, and the affinity placements really fired
        ra = r["real_affinity"]
        assert (ra["affinity"]["fleet_prefix_hit_rate"]
                > ra["blind"]["fleet_prefix_hit_rate"])
        assert ra["affinity"]["affinity_hits"] >= 1

    @pytest.mark.slow
    def test_fleet_sim_full_scale_acceptance(self):
        """The headline claim at FULL size (no smoke shrink): at least
        one million simulated requests through the real router in under
        60 s of CPU wall-clock, with the autoscaler no worse than
        static provisioning per replica-second."""
        env = _env()
        env.pop("DTTPU_BENCH_SMOKE", None)
        proc = _run(["--config=fleet_sim", "--device=cpu"], env)
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        r = json.loads(lines[-1])
        assert r["simulated_requests"] >= 1_000_000
        assert r["sim_wall_s"] < 60.0
        assert r["autoscaler_vs_static"] >= 1.0
        # the 10⁶-request prefix-affinity ablation at full size: the
        # headline affinity_vs_blind > 1.0 must hold off-smoke too
        assert r["ablation"]["requests"] >= 1_000_000
        assert r["affinity_vs_blind"] > 1.0
        assert (r["ablation"]["affinity"]["fleet_prefix_hit_rate"]
                > r["ablation"]["blind"]["fleet_prefix_hit_rate"])


class TestAnalytical:
    """The graph-tier static cost model riding the bench JSON
    (``analytical_flops``/``analytical_bytes``/``analytical_mfu``):
    every measured perf claim gets a same-program static roofline next
    to it (docs/ANALYSIS.md §graph tier)."""

    def test_attach_analytical_exact_on_a_matmul(self, monkeypatch):
        monkeypatch.setenv("DTTPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("DTTPU_PEAK_BW", "1e10")
        import jax
        import jax.numpy as jnp
        step = jax.jit(lambda a, b: a @ b)
        args = (jax.ShapeDtypeStruct((4, 8), jnp.float32),
                jax.ShapeDtypeStruct((8, 16), jnp.float32))
        r = bench._attach_analytical({"metric": "m"}, step, args,
                                     tokens_per_step=4)
        assert r["analytical_flops"] == 2 * 4 * 8 * 16
        assert r["analytical_bytes"] == (4 * 8 + 8 * 16 + 4 * 16) * 4
        assert r["analytical_flops_per_token"] == pytest.approx(
            2 * 8 * 16)
        intensity = r["analytical_flops"] / r["analytical_bytes"]
        assert r["analytical_mfu"] == pytest.approx(
            min(1.0, 1e10 * intensity / 1e12), abs=1e-4)

    def test_attach_analytical_without_peak_omits_mfu(self, monkeypatch):
        # CPU mesh, no override: flops/bytes still land (they're
        # hardware-independent), the roofline field does not
        monkeypatch.delenv("DTTPU_PEAK_FLOPS", raising=False)
        monkeypatch.delenv("DTTPU_PEAK_BW", raising=False)
        import jax
        import jax.numpy as jnp
        step = jax.jit(lambda a: a + 1.0)
        r = bench._attach_analytical(
            {"metric": "m"}, step,
            (jax.ShapeDtypeStruct((8,), jnp.float32),))
        assert r["analytical_flops"] == 8
        assert "analytical_mfu" not in r

    def test_gpt_smoke_analytical_schema_and_roofline_bound(self):
        """--config=gpt carries the graph-tier fields, and the measured
        mfu sits below the static roofline ceiling — the sanity bound
        that makes a too-good-to-be-true number fail loudly."""
        proc = _run(["--config=gpt", "--device=cpu"],
                    _env(DTTPU_PEAK_FLOPS="1e15", DTTPU_PEAK_BW="1e13"))
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        lines = [l for l in proc.stdout.decode().splitlines()
                 if l.strip()]
        r = json.loads(lines[-1])
        assert r["analytical_flops"] > 0
        assert r["analytical_bytes"] > 0
        assert r["analytical_flops_per_token"] > 0
        assert 0 < r["analytical_mfu"] <= 1.0
        # the cost model counts scan bodies times their trip count, so
        # the static figure must not fall below XLA's scan-undercounted
        # per-token number
        assert r["analytical_flops_per_token"] >= r["flops_per_example"]
        # measured <= static roofline ceiling
        assert r["mfu"] <= r["analytical_mfu"]
