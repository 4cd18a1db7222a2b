"""The shortcut-expert / latent-attention family's cell, rehearsed on the
CPU at a toy size through ``benchmark/run.py`` (``longcat_bench.py`` builds
it from the real family and traffic files and a toy CUT configuration): it
reads ``correct: true`` with the probe going prefill -> shared pages ->
prefill from an unaligned depth -> decode; a fault in what the cell exists
to measure reads ``correct: false``; the three new readers read hand-made
spans; and the family's counts at the published configuration are the
issue's arithmetic."""
import io
import json
from types import SimpleNamespace

import pytest

import bench_paths
import longcat_bench
import run as bench_run
from harness import program_spans as ps
from harness import spec
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.obs.trace import SpanRecord

# importing program_spans is a traced run's on-switch; collecting this file
# must not leave tracing on for the tests that follow
trace_lib.deactivate(ps.ACTIVATED)
reqtrace.reset()

BENCH = spec.Benchmark(bench_paths.ROOT)
CELL = "longcat-flash-chat.agent_sessions_64"


@pytest.fixture(scope="module")
def longcat_root(tmp_path_factory):
    return longcat_bench.build(tmp_path_factory.mktemp("longcatbench_root"))


def _run(root, trace: int, seconds: float):
    out = io.StringIO()
    code = bench_run.main(
        ["--workload", longcat_bench.CELL, "--seed", str(2 ** 31 + 17),
         "--seconds", str(seconds), "--trace", str(trace)],
        out, root=root, rehearse_on_cpu=True)
    assert code == 0
    return [json.loads(line) for line in out.getvalue().splitlines()]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsed_cell_reads_correct(longcat_root, trace):
    lines = _run(longcat_root, trace, 2)
    assert lines[0]["family"] == "longcat_toy"
    last = lines[-1]
    checks = next(line for line in lines if "checks" in line)
    assert last["correct"] is True, checks
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(checks["checks"].values()), checks
    # float32 on both sides at a toy size: the probe's logits after
    # prefill -> shared pages -> prefill -> decode are the reference's
    assert checks["logit_max_abs_err"] < 1e-4
    assert checks["token_positions"] > 0
    # three pinned programs (no per-slot state), none with a kernel
    assert set(checks["kernel_in_program"]) == {
        "prefill_window", "admit", "decode_tick"}
    assert not any(checks["kernel_in_program"].values())
    assert checks["use_paged_kernel"] is False
    names = set(last["metrics"])
    if not trace:
        assert names == {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p50_ms",
                         "setup_s"}
        return
    # the readers of the program's spans read the chip's: on a CPU
    # rehearsal they report nothing, and raise nothing
    assert names == {"compiles_in_window.serve", "tick_ms_p50", "slow_ticks",
                     "prefix_hit_pct", "slot_occupancy_pct",
                     "mosaic_dev_pct.serve", "idle_pct.serve"}
    assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
    # every turn after a session's first maps its own history's pages
    assert last["metrics"]["prefix_hit_pct"]["value"] > 60


def test_a_router_that_weighs_by_the_biased_score_reads_not_correct(
        longcat_root, monkeypatch):
    """The weights taken from ``p + bias`` where the rule says ``p``: the
    probe's logits leave the reference's; the run still ends and prints its
    line."""
    import jax
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.ops import moe as moe_lib

    def biased(kernel, bias, x, *, top_k, scale):
        p = jax.nn.softmax(x.astype(jnp.float32)
                           @ kernel.astype(jnp.float32), axis=-1)
        weight, choice = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
        return choice.astype(jnp.int32), weight * scale

    monkeypatch.setattr(moe_lib, "route_top_k", biased)
    lines = _run(longcat_root, 0, 1)
    last = lines[-1]
    assert last["correct"] is False
    checks = next(line for line in lines if "checks" in line)["checks"]
    assert not checks["logits_match_reference"]
    assert checks["no_turn_failed"] and checks["hot_programs_were_dispatched"]
    compared = last["compared"]
    assert compared["logit_max_abs_err"]["value"] > \
        10 * compared["logit_max_abs_err"]["limit"]


def test_the_balanced_choice_bias_evens_the_routers_load():
    """The weight recipe's ``choice_bias_balance``: ``build_model``'s
    ``init`` is the program's with every router's columns centred over each
    chip's group of experts and its bias refitted, nothing else touched,
    the same under ``jit`` and for the same key; on fresh tokens the
    outputs of the router are then chosen about equally often (loads within
    a third of their mean, a tenth of it the sample's own noise), where
    under the seeded bias they spread by more than the mean."""
    import copy
    import jax
    import numpy as np
    from distributed_tensorflow_tpu.models.longcat_flash import LongcatFlash

    cell = BENCH.cell(CELL)
    family = BENCH.family(cell)
    with open(longcat_bench.TOY_CONFIG) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["cut"]["published"]["n_routed_experts"] = 56   # + 4 identity
    config["moe_topk"] = 6
    config["assumed"]["choice_bias_balance"] = {"rows": 16, "tokens": 64}
    key = jax.random.PRNGKey(5)
    model = family.build_model(config)
    params = jax.jit(model.init)(key)
    seeded = LongcatFlash(family.model_config(config)).init(key)
    again = model.init(key)
    changed = []
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(params),
                               jax.tree.leaves(seeded),
                               jax.tree.leaves(again)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            changed.append(jax.tree_util.keystr(path))
    assert len(changed) == 2 * config["num_layers"]
    assert all("router" in name for name in changed)
    for layer in params["layers"]:
        groups = np.asarray(layer["moe"]["router"]["kernel"],
                            np.float32)[:, :56].reshape(-1, 14, 4)
        assert np.abs(groups.sum(-1)).max() < 1e-5

    ids = jax.random.randint(jax.random.PRNGKey(6), (16, 64), 0,
                             config["vocab_size"])

    def unevenness(tree):
        logits = np.asarray(family.reference.router_logits(tree, ids, config))
        worst = []
        for i, layer in enumerate(tree["layers"]):
            z = logits[:, i].reshape(-1, logits.shape[-1])
            p = np.exp(z - z.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            biased = p + np.asarray(layer["moe"]["router"]["choice_bias"])
            picks = np.argsort(-biased, axis=-1)[:, :config["moe_topk"]]
            load = np.bincount(picks.ravel(), minlength=z.shape[-1])
            worst.append(load.std() / load.mean())
        return max(worst)

    assert unevenness(seeded) > 1.0
    assert unevenness(params) < 0.5


# ------------------------------------------- the family at published widths

def test_the_family_counts_the_issues_arithmetic():
    cell = BENCH.cell(CELL)
    family, config = BENCH.family(cell), cell.config
    counts = family._counts(config)
    assert counts["attention"] == 90_572_800
    assert counts["dense_ffn"] == 226_492_416
    assert counts["router"] == 4_718_592 + 768
    assert counts["expert"] == 37_748_736
    assert counts["router_outputs"] == 768
    assert counts["cache_token"] * 2 == 9_216
    assert family.share(config) == (512, 16, 0)
    # 10.35 GB of weights in bf16: 5.17 B parameters held
    assert round(2 * family.total_params(config) / 1e9, 2) == 10.35
    step = family.decode_step_bytes(config, 64, 64 * 2500)
    assert set(step) == {"weights", "expert_weights", "latent_cache"}
    assert round(step["weights"] / 1e9, 1) == 5.3     # 5.1 + 0.2 of head
    touched = 16 * (1 - (63 / 64) ** 64)
    assert step["expert_weights"] == pytest.approx(
        75_497_472 * 4 * touched, rel=1e-12)
    assert 0.62 < touched / 16 < 0.64                 # the issue's ~63 %
    assert step["latent_cache"] == 9_216 * (64 * 2500 + 64)
    assert 9.5e9 < sum(step.values()) < 10.1e9        # the issue's ~9.8 GB
    # a token: 2 x 2.76 B outside the experts and in the head's slice, a
    # quarter of an expert a layer, 40,960 operations a cached position
    # and sublayer
    bare = family.serve_flops_per_token(config, 0, head=False)
    assert bare == 2.0 * (4 * counts["layer_outside_experts"] + 6144) \
        + 2.0 * 37_748_736 * 0.25 * 4
    assert (family.serve_flops_per_token(config, 0) - bare
            == 2 * 16_384 * 6_144)
    assert (family.serve_flops_per_token(config, 600)
            - family.serve_flops_per_token(config, 100)
            == 2 * 64 * (192 + 128) * 8 * 500)
    assert family.kernel_expected(config, "decode_tick") is False


def test_the_configuration_is_the_source_but_for_three_keys():
    """Every number of the catalog's entry under its key, the three reduced
    keys apart; the router built from it has 768 outputs."""
    config = BENCH.cell(CELL).config
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert config["cut"]["published"] == {k: published[k] for k in differs}
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)       # at the floors
    model = BENCH.family(BENCH.cell(CELL)).build_model(config)
    assert model.config.router_outputs == 768
    assert model.config.experts_held == 16
    traffic = BENCH.cell(CELL).traffic["params"]
    assert traffic["clients"] == config["serve"]["num_slots"]
    # ``agent_sessions`` with twice the clients — and twice the grid
    # points: the generator deals a grid in equal hands and 64 does not
    # divide 96
    agent = BENCH.cell("granite-4.0-h-micro.agent_sessions").traffic
    want = json.loads(json.dumps(agent["params"]))
    want["clients"] = 64
    for grid in ("user_message_tokens", "output_tokens"):
        assert want[grid]["points"] == 96
        want[grid]["points"] = 192
    assert traffic == want


# ------------------------------------------- the readers, on hand-made spans

ROWS = [[3, 0, 1, 0], [2, 2, 0, 0]]       # [expert layer][held expert]


def _case(monkeypatch, window_ticks):
    """Two fill ticks, the window's ticks, one traced tick.  A window tick
    is ``(touched, could, live_steps, decode picks, first-read picks)``, a
    pick triple ``(picks, identity, held)`` or None for a span without the
    count; the fill and traced ticks carry counts that must not be read."""
    rows, lengths, at = [], [], 0.0
    loud = (16, 16, 8, (900, 900, 0), (900, 0, 900))
    plan = [loud, loud] + list(window_ticks) + [loud]

    def picks(triple):
        if triple is None:
            return {}
        return {"router_picks": triple[0], "router_picks_identity": triple[1],
                "router_picks_held": triple[2], "expert_tokens": ROWS}

    for n, (touched, could, live, decode, first) in enumerate(plan):
        tick, start = len(rows), at
        rows.append(None)
        prefill = len(rows)
        rows.append(SpanRecord("serve.prefill", (at + 0.1) * 1e3,
                               (at + 0.4) * 1e3, tick, {}, 1))
        rows.append(SpanRecord("serve.first_token_read", (at + 0.2) * 1e3,
                               (at + 0.3) * 1e3, prefill, picks(first), 1))
        rows.append(SpanRecord("serve.decode_dispatch", (at + 0.4) * 1e3,
                               (at + 0.5) * 1e3, tick,
                               {"steps": 4, "active": 8}, 1))
        args = dict(picks(decode), live_steps=live)
        if could is not None:
            args.update(experts_touched=touched, experts_held_steps=could)
        rows.append(SpanRecord("serve.decode_fetch", (at + 0.5) * 1e3,
                               (at + 20.0 + n) * 1e3, tick, args, 1))
        at += 20.2 + n
        rows[tick] = SpanRecord("serve.tick", start * 1e3, at * 1e3, None,
                                {"tick": n + 1}, 1)
        lengths.append((at - start) / 1e3)
        at += 0.5
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    record = {"kind": "serve", "platform": "tpu", "root": bench_paths.ROOT,
              "cell": CELL, "config": "longcat-flash-chat",
              "family": "longcat_flash",
              "tick_seconds": [s + 0.0003 for s in lengths[2:-1]]}
    return record, SimpleNamespace(window_s=lengths[-1] + 0.0004)


WINDOW = [(40, 4 * 4 * 16, 4 * 64, (100, 30, 10), (60, 20, 2)),
          (24, 4 * 4 * 16, 4 * 16, (80, 30, 0), None),
          (0, 4 * 4 * 16, 0, (0, 0, 0), (20, 10, 0))]


@pytest.mark.parametrize("name,want", [
    ("experts_touched_pct", 100.0 * 64 / 768),
    ("identity_picks_pct", 100.0 * 90 / 260),
    ("expert_load_max_over_mean", 3 / 1.0),
])
def test_the_new_readers_on_hand_made_spans(monkeypatch, name, want):
    record, traced = _case(monkeypatch, WINDOW)
    read = BENCH.layer_reader(name)
    assert read(record, traced) == pytest.approx(want)
    # another run's ticks: the window is not verified, nothing is read
    record, traced = _case(monkeypatch, WINDOW)
    record["tick_seconds"][1] += 0.01
    assert read(record, traced) is None


@pytest.mark.parametrize("name", ["experts_touched_pct",
                                  "identity_picks_pct",
                                  "expert_load_max_over_mean"])
def test_the_new_readers_report_nothing_without_the_counts(monkeypatch,
                                                           name):
    """A parent commit's spans, or a model without experts: the decode
    fetches carry ``live_steps`` alone."""
    bare = [(None, None, 4 * 8, None, None)] * 3
    record, traced = _case(monkeypatch, bare)
    assert BENCH.layer_reader(name)(record, traced) is None


def test_experts_touched_reports_the_familys_model_beside_the_count(
        monkeypatch, capfd):
    record, traced = _case(monkeypatch, WINDOW[:1] * 3)
    BENCH.layer_reader("experts_touched_pct")(record, traced)
    line = next(json.loads(l.split("program_spans: ", 1)[1])
                for l in capfd.readouterr().err.splitlines()
                if '"metric": "experts_touched_pct"' in l)
    assert line["mean_live_slots"] == pytest.approx(64.0)
    assert line["modelled_pct"] == pytest.approx(
        100 * (1 - (63 / 64) ** 64))
    assert line["experts_held_steps"] == 3 * 256


def test_the_new_metrics_are_declared_for_the_new_cell_alone():
    doc = BENCH.doc
    for name, layer, moves in [
            ("experts_touched_pct", "kernels", "tpot_p50_ms"),
            ("identity_picks_pct", "model", "serve_tokens_per_s"),
            ("expert_load_max_over_mean", "model", "serve_tokens_per_s")]:
        entry = next(m for m in doc["per_layer"] if m["name"] == name)
        assert (entry["layer"], entry["moves"]) == (layer, moves)
        assert entry["workloads"] == [CELL]
    listed = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {"decode_hbm_roofline_pct", "serve_mfu_pct",
                      "idle_pct.serve", "hbm_peak_pct.serve"}
    assert not listed & {"recurrent_state_bytes_pct", "snapshot_resume_pct",
                         "decode_pages_walked_pct"}
