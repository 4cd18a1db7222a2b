"""The trace reducer on hand-made interval lists, and its ``ProfileData``
adapter on the small trace recorded on the chip (``record_small_trace.py``)."""
import os

import pytest

import bench_paths
from harness import trace as trace_lib

MS = 1e6     # ns


def _trace(devices, host_spans=(), window=(0.0, 100 * MS)):
    return trace_lib.Trace(devices=devices, host_spans=list(host_spans),
                           window=window)


def test_busy_is_the_union_and_idle_its_complement():
    ops = [("fusion.1", 0 * MS, 30 * MS),
           ("fusion.2", 20 * MS, 20 * MS),      # overlaps fusion.1 by 10 ms
           ("fusion.3", 60 * MS, 10 * MS)]
    r = trace_lib.reduce(_trace({0: ops}))
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.050)      # [0,40] + [60,70]
    assert r.idle_share == pytest.approx(0.5)
    assert r.devices == 1


def test_a_container_is_charged_only_what_its_children_leave():
    ops = [("while.7", 0 * MS, 50 * MS),
           ("fusion.a", 0 * MS, 20 * MS),
           ("all-gather-start.3", 20 * MS, 5 * MS),
           ("custom-call.9", 25 * MS, 15 * MS),
           ("all-reduce.2", 60 * MS, 10 * MS)]
    r = trace_lib.reduce(_trace({0: ops}))
    assert r.busy_s == pytest.approx(0.060)
    assert r.collective_s == pytest.approx(0.015)
    assert r.custom_call_s == pytest.approx(0.015)
    table = dict(r.device_ops)
    assert table["while.7"] == pytest.approx(0.010)     # 50 - 20 - 5 - 15
    assert table["fusion.a"] == pytest.approx(0.020)
    assert r.device_ops[0][0] == "fusion.a"


def test_means_over_devices_and_the_window_clips():
    a = [("all-reduce.1", -10 * MS, 30 * MS)]           # starts before 0
    b = [("fusion.1", 90 * MS, 30 * MS)]                # ends after 100
    r = trace_lib.reduce(_trace({0: a, 1: b}))
    assert r.devices == 2
    assert r.busy_s == pytest.approx((0.020 + 0.010) / 2)
    assert r.collective_s == pytest.approx(0.020 / 2)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [("f.1", 0 * MS, 10 * MS), ("f.2", 10.01 * MS, 10 * MS),
           ("f.3", 50 * MS, 10 * MS)]
    host = [("fetch", 15 * MS, 25 * MS), ("next_batch", 25 * MS, 48 * MS),
            (trace_lib.WINDOW_SPAN, 0.0, 100 * MS)]
    r = trace_lib.reduce(_trace({0: ops}, host))
    gaps = dict(r.idle_gaps)
    assert gaps[trace_lib.SHORT_GAP_BUCKET] == pytest.approx(0.01e-3)
    assert gaps["next_batch"] == pytest.approx(0.02999)  # 20.01..50 ms
    assert gaps["unattributed"] == pytest.approx(0.040)  # 60..100 ms
    assert sum(gaps.values()) + r.busy_s == pytest.approx(r.window_s)


def test_names_collectives_and_custom_calls():
    assert trace_lib.is_collective("all-gather-start.12")
    assert trace_lib.is_collective("reduce-scatter.3")
    assert trace_lib.is_collective("collective-permute-done.1")
    assert not trace_lib.is_collective("fusion.494")
    assert trace_lib.is_custom_call("custom-call.4")
    layout = "{1,0:T(8,128)(2,1)S(1)}"
    assert trace_lib.short_name(
        f"%fusion.4 = bf16[24,16,1024,1024]{layout} "
        "fusion(%p0, %p1), kind=kOutput") == "fusion.4 bf16[24,16,1024,1024]"
    kernel = trace_lib.short_name(
        f"%program.1 = bf16[512,512]{layout} custom-call(bf16[512,512]"
        f"{layout} %while.7), custom_call_target=\"tpu_custom_call\"")
    assert kernel == "program.1 custom-call bf16[512,512]"
    assert trace_lib.is_custom_call(kernel)
    assert trace_lib.short_name(
        f"%while = (s32[]{{:T(128)}}, bf16[512,512]{layout}) "
        f"while((s32[]{{:T(128)}}, bf16[512,512]{layout}) %tuple)"
    ) == "while s32[]"
    assert trace_lib.short_name(
        f"%all-gather-start.3 = (f32[8]{layout}, f32[32]{layout}) "
        "all-gather-start(%p)") == "all-gather-start.3 f32[8]"
    assert trace_lib.short_name("fusion.12") == "fusion.12"


def test_empty_trace_reduces_to_nothing():
    r = trace_lib.reduce(_trace({}, window=(0.0, 0.0)))
    assert r.busy_s == 0 and r.idle_share is None and r.devices == 0


# ------------------------------------------------- the recorded chip trace

SMALL = os.path.join(bench_paths.DATA_DIR, "small_trace.xplane.pb")


def test_adapter_on_the_trace_recorded_on_the_chip():
    """Three calls of ``record_small_trace.py``'s program on one v5e: a
    ``while`` of four matmul+tanh steps and one Pallas kernel each."""
    from jax.profiler import ProfileData
    assert os.path.getsize(SMALL) < 1_000_000
    trace = trace_lib.from_profile(ProfileData.from_file(SMALL))
    assert sorted(trace.devices) == [0]
    assert {"dispatch", "fetch", trace_lib.WINDOW_SPAN} <= {
        s[0] for s in trace.host_spans}
    window = [s for s in trace.host_spans if s[0] == trace_lib.WINDOW_SPAN][0]
    assert trace.window == (window[1], window[2])
    # device and host events are on one clock, to about a millisecond: the
    # first program shows 1.0 ms BEFORE the span it was dispatched in
    skew = 2e6
    for _, start, dur in trace.devices[0]:
        assert window[1] - skew <= start and start + dur <= window[2] + skew
    assert min(o[1] for o in trace.devices[0]) < window[1]
    r = trace_lib.reduce(trace)
    assert 0 < r.busy_s < r.window_s
    assert r.collective_s == 0
    assert 0 < r.custom_call_s < r.busy_s
    names = [n for n, _ in r.device_ops]
    assert any(trace_lib.is_custom_call(n) for n in names)
    assert sum(s for _, s in r.idle_gaps) + r.busy_s == pytest.approx(
        r.window_s)
    assert {"dispatch", "fetch"} & {n for n, _ in r.idle_gaps}
