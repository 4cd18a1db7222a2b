"""The state-space / attention family's cell, rehearsed on the CPU at a toy
size through ``benchmark/run.py`` (``hybrid_bench.py`` builds it from the
real family, configuration and traffic files): it reads ``correct: true``
with the probe going prefill -> snapshot -> restore -> decode, and a fault in
what the cell exists to measure reads ``correct: false``."""
import io
import json

import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on the path)
import hybrid_bench
import run as bench_run


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    return hybrid_bench.build(tmp_path_factory.mktemp("hybridbench_root"))


def _run(root, trace: int, seconds: float):
    out = io.StringIO()
    code = bench_run.main(
        ["--workload", hybrid_bench.CELL, "--seed", str(2 ** 31 + 17),
         "--seconds", str(seconds), "--trace", str(trace)],
        out, root=root, rehearse_on_cpu=True)
    assert code == 0
    return [json.loads(line) for line in out.getvalue().splitlines()]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsed_cell_reads_correct(hybrid_root, trace):
    lines = _run(hybrid_root, trace, 2)
    assert lines[0]["family"] == "hybrid_tiny"
    last = lines[-1]
    checks = next(line for line in lines if "checks" in line)
    assert last["correct"] is True, checks
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(checks["checks"].values()), checks
    # float32 on both sides at a toy size: the probe's logits after
    # prefill -> snapshot -> restore -> decode are the reference's
    assert checks["logit_max_abs_err"] < 1e-4
    assert checks["token_positions"] > 0
    # five pinned programs, none with a kernel, each dispatched
    assert set(checks["kernel_in_program"]) == {
        "prefill_window", "admit", "decode_tick", "state_snapshot",
        "state_restore"}
    assert not any(checks["kernel_in_program"].values())
    assert checks["use_paged_kernel"] is False
    names = set(last["metrics"])
    if not trace:
        assert names == {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p50_ms",
                         "setup_s"}
        return
    # the three new readers read the chip's spans: on a CPU rehearsal they
    # report nothing, and raise nothing
    assert names == {"compiles_in_window.serve", "tick_ms_p50", "slow_ticks",
                     "prefix_hit_pct", "slot_occupancy_pct",
                     "mosaic_dev_pct.serve", "idle_pct.serve"}
    assert last["metrics"]["compiles_in_window.serve"]["value"] == 0
    # every turn after a session's first resumes after its own history
    assert last["metrics"]["prefix_hit_pct"]["value"] > 60


def test_a_state_dropped_at_a_window_boundary_reads_not_correct(
        hybrid_root, monkeypatch):
    """Every prefill window starts from a zero state, whatever the slot
    holds: the probe's logits leave the reference's and the engine's tokens
    its argmax; the run still ends and prints its line."""
    import jax
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.hybrid import HybridDecoder
    real = HybridDecoder.decode_window_paged

    def forgetful(self, *args, state, **kwargs):
        return real(self, *args, state=jax.tree.map(jnp.zeros_like, state),
                    **kwargs)

    monkeypatch.setattr(HybridDecoder, "decode_window_paged", forgetful)
    lines = _run(hybrid_root, 0, 1)
    last = lines[-1]
    assert last["correct"] is False
    checks = next(line for line in lines if "checks" in line)["checks"]
    assert not checks["logits_match_reference"]
    assert checks["no_turn_failed"] and checks["hot_programs_were_dispatched"]
    compared = last["compared"]
    assert compared["logit_max_abs_err"]["value"] > \
        10 * compared["logit_max_abs_err"]["limit"]


def test_the_readers_of_the_new_metrics_find_the_family_and_its_bytes(
        hybrid_root):
    """``decode_step_bytes`` at the published configuration: the arithmetic
    of the issue (6.38 GB of weights, 76.4 MB of state a slot, 8,192 B of
    K/V a token), and the reader's path to it."""
    from harness import spec
    bench = spec.Benchmark(bench_paths.ROOT)
    cell = bench.cell("granite-4.0-h-micro.agent_sessions")
    family = bench.family(cell)
    assert family.total_params(cell.config) == 3_191_396_096
    step = family.decode_step_bytes(cell.config, 32, 32 * 2500)
    assert round(step["weights"] / 1e9, 2) == 6.38
    assert round(step["recurrent_state"] / 2 / 32 / 1e6, 1) == 76.4
    assert step["kv"] == 8192 * (32 * 2500 + 32)
    share = step["recurrent_state"] / sum(step.values())
    assert 0.38 < share < 0.44                       # the issue's ~41 %
    per_token = family.serve_flops_per_token(cell.config, 2500)
    assert 6.3e9 < per_token < 6.8e9
    # the deployment's bytes, as the configuration file states them
    from distributed_tensorflow_tpu.serve import pages
    model = family.build_model(cell.config)
    assert pages.state_bytes_per_slot(model) == 76_437_504
    # the reader's path to it: the family of the cells that list the metric
    from layer_metrics import decode_hbm_roofline_pct as reader
    found, config = reader.cell_family()
    assert found.name == family.name and config == cell.config


# ------------------------------ the three new readers on hand-made spans

def _spans(with_args: bool):
    """Two window ticks and one traced tick: each a ``serve.admit`` (one
    resumed, one not, one backpressured), a decode dispatch, the read of an
    admitting window's token behind it (in the second tick the host came
    too late to wait for it) and the decode fetch."""
    from distributed_tensorflow_tpu.obs.trace import SpanRecord
    rows, lengths = [], []
    for n, (start, resumed, outcome, live_steps, read_from) in enumerate(
            [(0.0, True, "ok", 8, 12.0), (100.0, False, "ok", 6, 19.9),
             (200.0, True, "backpressure", 8, 12.0),
             (300.0, True, "ok", 8, 12.0)]):
        tick = len(rows)
        end = start + 60.0
        rows.append(SpanRecord("serve.tick", start * 1e3, end * 1e3, None,
                               {"tick": n + 1}, 1))
        admit = {"outcome": outcome}
        dispatch, fetch = {"steps": 4, "active": 2}, {}
        if with_args:
            admit["resumed"] = resumed
            dispatch["cached_tokens"] = 1000
            fetch["live_steps"] = live_steps
        rows.append(SpanRecord("serve.admit", (start + 1) * 1e3,
                               (start + 2) * 1e3, tick, admit, 1))
        rows.append(SpanRecord("serve.decode_dispatch", (start + 10) * 1e3,
                               (start + 11) * 1e3, tick, dispatch, 1))
        prefill = len(rows)
        rows.append(SpanRecord("serve.prefill", (start + 11.5) * 1e3,
                               (start + 20) * 1e3, tick, {}, 1))
        if with_args:
            rows.append(SpanRecord(
                "serve.first_token_read", (start + read_from) * 1e3,
                (start + 20) * 1e3, prefill, {}, 1))
        rows.append(SpanRecord("serve.decode_fetch", (start + 20) * 1e3,
                               (start + 50) * 1e3, tick, fetch, 1))
        lengths.append((end - start) / 1e3)
    return rows, lengths


@pytest.fixture
def readers_case(monkeypatch):
    from types import SimpleNamespace
    from harness import program_spans as ps
    from harness import spec

    fake = SimpleNamespace(decode_step_bytes=lambda config, live, cached: {
        "weights": 1000.0, "recurrent_state": 100.0 * live,
        "kv": 1.0 * cached})
    bench = spec.Benchmark(bench_paths.ROOT)
    read = {m: bench.layer_reader(m) for m in (
        "decode_hbm_roofline_pct", "recurrent_state_bytes_pct",
        "snapshot_resume_pct")}
    found = {"family": (fake, {"any": "thing"})}
    # the byte count's module is loaded twice: by its name, and by the
    # reader that imports it
    monkeypatch.setitem(read["decode_hbm_roofline_pct"].__globals__,
                        "cell_family", lambda: found["family"])
    monkeypatch.setattr(
        read["recurrent_state_bytes_pct"].__globals__["_bytes"],
        "cell_family", lambda: found["family"])

    def case(with_args=True):
        rows, lengths = _spans(with_args)
        monkeypatch.setattr(ps, "_program_spans", lambda: rows)
        monkeypatch.setattr(ps, "_LAST", [None, None])
        record = {"kind": "serve", "platform": "tpu",
                  "device_kind": "TPU v5 lite",
                  "tick_seconds": [s + 0.0003 for s in lengths[:3]]}
        return record, SimpleNamespace(window_s=0.0601)

    return read, case, found


def test_the_new_readers_on_hand_made_spans(readers_case):
    read, case, _ = readers_case
    record, traced = case()
    # three window ticks: 8, 6 and 8 live slot-steps of 4 steps x 2 slots;
    # the decode program ran from the read's return to the fetch's, 30 ms,
    # in the two ticks whose read waited: only those are measured
    weights = 2 * 4 * 1000.0
    state = 100.0 * (8 + 8)
    kv = 1.0 * 1000 * (8 + 8) / 2          # a slot's share, a live step
    assert read["decode_hbm_roofline_pct"](record, traced) == pytest.approx(
        100 * (weights + state + kv) / (2 * 0.030 * 819e9), rel=1e-9)
    # the share of the bytes is over every dispatch, timed or not
    weights, state = 3 * 4 * 1000.0, 100.0 * (8 + 6 + 8)
    kv = 1.0 * 1000 * (8 + 6 + 8) / 2
    assert read["recurrent_state_bytes_pct"](record, traced) == \
        pytest.approx(100 * state / (weights + state + kv), rel=1e-9)
    # of the window's two admissions that ended ok, one resumed
    assert read["snapshot_resume_pct"](record, traced) == pytest.approx(50.0)
    assert all(r(dict(record, platform="cpu"), traced) is None
               for r in read.values())


def test_the_new_readers_read_nothing_from_a_program_without_their_spans(
        readers_case):
    """Laid over the parent commit (no ``cached_tokens`` / ``live_steps`` /
    ``resumed``, no read behind the decode dispatch), or in a cell whose
    family counts no decode bytes: None, and nothing raised."""
    read, case, found = readers_case
    record, traced = case(with_args=False)
    assert all(r(record, traced) is None for r in read.values())
    record, traced = case()
    found["family"] = None
    assert read["decode_hbm_roofline_pct"](record, traced) is None
    assert read["recurrent_state_bytes_pct"](record, traced) is None
    assert read["snapshot_resume_pct"](record, traced) == pytest.approx(50.0)
