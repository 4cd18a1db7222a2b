"""The decoder-hybrid-decoder family's cell, rehearsed on the CPU at a toy
size through ``benchmark/run.py`` (``phi4flash_bench.py`` builds it from the
real family, configuration and traffic files): it reads ``correct: true``
with the probe going prefill -> snapshot -> restore -> decode over a context
that wraps every ring, and rings that keep nothing read ``correct: false``.
The family's counts at the published configuration are the arithmetic of the
configuration file, the cell steers clear of the harness's two traps for a
window model, and ``shared_kv_bytes_pct`` reads hand-made spans."""
import io
import json
from types import SimpleNamespace

import pytest

import bench_paths
import phi4flash_bench
import run as bench_run
from harness import grids
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
CELL = phi4flash_bench.REAL_CELL


@pytest.fixture(scope="module")
def phi_root(tmp_path_factory):
    return phi4flash_bench.build(tmp_path_factory.mktemp("phi4flash_root"))


def _run(root, trace: int, seconds: float):
    out = io.StringIO()
    code = bench_run.main(
        ["--workload", phi4flash_bench.CELL, "--seed", str(2 ** 31 + 23),
         "--seconds", str(seconds), "--trace", str(trace)],
        out, root=root, rehearse_on_cpu=True)
    assert code == 0
    return [json.loads(line) for line in out.getvalue().splitlines()]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsed_cell_reads_correct(phi_root, trace):
    cell = spec.Benchmark(phi_root).cell(phi4flash_bench.CELL)
    model = spec.Benchmark(phi_root).family(cell).build_model(cell.config)
    # the probe's context lies past the window and wraps the rings
    assert cell.config["serve"]["check_context_tokens"] > model.config.ring
    lines = _run(phi_root, trace, 2)
    assert lines[0]["family"] == "phi4flash_tiny"
    last = lines[-1]
    checks = next(line for line in lines if "checks" in line)
    assert last["correct"] is True, checks
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(checks["checks"].values()), checks
    assert checks["logit_max_abs_err"] < 1e-4
    assert checks["token_positions"] > 0
    # five pinned programs, none with a kernel, each dispatched
    assert set(checks["kernel_in_program"]) == {
        "prefill_window", "admit", "decode_tick", "state_snapshot",
        "state_restore"}
    assert not any(checks["kernel_in_program"].values())
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
    assert last["metrics"]["prefix_hit_pct"]["value"] > 60


def test_rings_that_keep_nothing_read_not_correct(phi_root, monkeypatch):
    """Every prefill window starts from empty rings, whatever the slot
    holds: the probe's logits leave the reference's; the run still ends and
    prints its line."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.sambay import SambaY
    real = SambaY.decode_window_paged

    def forgetful(self, *args, state, **kwargs):
        state = dict(state, **{k: jnp.zeros_like(v) for k, v in
                               state.items() if k.startswith("window_")})
        return real(self, *args, state=state, **kwargs)

    monkeypatch.setattr(SambaY, "decode_window_paged", forgetful)
    last = _run(phi_root, 0, 1)[-1]
    assert last["correct"] is False
    compared = last["compared"]
    assert compared["logit_max_abs_err"]["value"] > \
        10 * compared["logit_max_abs_err"]["limit"]


def test_the_familys_counts_at_the_published_configuration():
    """The configuration file's arithmetic: 3,852,562,944 parameters, 7.71
    GB in bf16; a slot block of 25.5 MB; at 24 live slots and 4,600 tokens
    each a decode step must move 12.9 GB, a third of it the shared K/V."""
    cell = BENCH.cell(CELL)
    family, config = BENCH.family(cell), cell.config
    assert family.total_params(config) == 3_852_562_944
    step = family.decode_step_bytes(config, 24, 24 * 4600)
    assert set(step) == {"weights", "shared_kv", "window_kv",
                         "recurrent_state"}
    assert round(step["weights"] / 1e9, 2) == 7.71
    assert step["shared_kv"] == 8 * 5120 * (24 * 4600 + 24)
    assert step["window_kv"] == 8 * 5120 * 24 * 512
    assert step["recurrent_state"] == 2 * 24 * 9 * (16 * 5120 * 4
                                                    + 3 * 5120 * 2)
    assert 12.8e9 < sum(step.values()) < 13.0e9
    assert 0.34 < step["shared_kv"] / sum(step.values()) < 0.36
    # short contexts: the window term is the tokens themselves
    assert family.decode_step_bytes(config, 2, 300)["window_kv"] == \
        8 * 5120 * 302
    # 15,360 operations a position a layer: the window layers stop at 512
    flops = family.serve_flops_per_token
    assert flops(config, 600) - flops(config, 100) == \
        15360 * (8 * 412 + 8 * 500)
    assert flops(config, 5000) - flops(config, 4000) == 15360 * 8 * 1000
    assert flops(config, 0) - flops(config, 0, head=False) == \
        2 * 200064 * 2560
    from distributed_tensorflow_tpu.serve import pages
    model = family.build_model(config)
    assert pages.state_bytes_per_slot(model) == 25_507_840
    assert family.kernel_expected(config, "decode_tick") is False


def test_the_cell_steers_clear_of_the_harness_traps():
    """The probe's context is the first client's first prompt cut to
    ``check_context_tokens``: at most the system prompt and the shortest
    message, so its length is every seed's; it lies past the window and
    wraps the rings; and the grids deal into 32 equal hands."""
    cell = BENCH.cell(CELL)
    serve, traffic = cell.config["serve"], cell.traffic["params"]
    assert serve["check_context_tokens"] <= (
        traffic["system_prompt_tokens"]
        + min(grids.quantile_grid(traffic["user_message_tokens"])))
    model = BENCH.family(cell).build_model(cell.config)
    assert serve["check_context_tokens"] > model.config.ring == 544
    for grid in ("user_message_tokens", "output_tokens"):
        grids.Deal(grids.quantile_grid(traffic[grid]), traffic["clients"], 0)
    assert traffic["clients"] == serve["num_slots"] == 32


def test_shared_kv_bytes_pct_on_hand_made_spans(monkeypatch):
    """Two window ticks of 4 steps: the shared term over all the family's
    terms, summed over every dispatch; a family with no shared term and a
    program without the spans' counts read nothing."""
    read = BENCH.layer_reader("shared_kv_bytes_pct")
    rows, lengths = [], []
    for n, start in enumerate((0.0, 100.0, 200.0)):
        tick = len(rows)
        rows.append(SpanRecord("serve.tick", start * 1e3,
                               (start + 60) * 1e3, None, {"tick": n + 1}, 1))
        rows.append(SpanRecord(
            "serve.decode_dispatch", (start + 10) * 1e3, (start + 11) * 1e3,
            tick, {"steps": 4, "active": 2, "cached_tokens": 1000,
                   "window_tokens": 64}, 1))
        rows.append(SpanRecord("serve.decode_fetch", (start + 20) * 1e3,
                               (start + 50) * 1e3, tick, {"live_steps": 8},
                               1))
        lengths.append(0.060)
    found = {"family": (SimpleNamespace(
        decode_step_bytes=lambda config, live, cached: {
            "weights": 1000.0, "shared_kv": 2.0 * cached,
            "recurrent_state": 10.0 * live}), {})}
    monkeypatch.setattr(read.__globals__["_bytes"], "cell_family",
                        lambda record: found["family"])
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    monkeypatch.setattr(ps, "_LAST", [None, None])
    record = {"kind": "serve", "platform": "tpu",
              "device_kind": "TPU v5 lite",
              "tick_seconds": [s + 0.0003 for s in lengths[:2]]}
    traced = SimpleNamespace(window_s=0.0601)
    # two window ticks: 2 live slots x 4 steps, a slot's 1000 / 2 cached
    weights, shared, state = 2 * 4 * 1000.0, 2 * 4 * 2.0 * 1000, \
        2 * 4 * 10.0 * 2
    assert read(record, traced) == pytest.approx(
        100 * shared / (weights + shared + state), rel=1e-9)
    found["family"] = (SimpleNamespace(
        decode_step_bytes=lambda config, live, cached: {"weights": 1.0}), {})
    monkeypatch.setattr(ps, "_LAST", [None, None])
    assert read(record, traced) is None
