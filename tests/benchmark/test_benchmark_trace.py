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


def test_every_named_kernel_is_summed_not_only_the_ten_longest():
    """Twelve fusions outlast the kernels, so no kernel is among the ten
    ``device_ops`` rows; ``kernel_s`` / ``kernel_calls`` carry them all the
    same, by kernel name without the instance suffix, mean over devices."""
    fusions = [(f"fusion.{i} bf16[8,8]", i * 5 * MS, 4 * MS)
               for i in range(12)]
    dev0 = fusions + [
        ("dttpu_paged_decode.4 custom-call bf16[8,1,25,64]", 61 * MS, 1 * MS),
        ("dttpu_paged_decode.4 custom-call bf16[8,1,25,64]", 63 * MS, 1 * MS),
        ("dttpu_paged_window.5 custom-call bf16[1,32,25,64]", 65 * MS,
         0.5 * MS),
        ("dttpu_paged_window.7 custom-call bf16[1,32,25,64]", 67 * MS,
         0.25 * MS),
        ("dttpu_flash_fwd custom-call f32[4]", 99.5 * MS, 1 * MS),  # clipped
        ("closed_call.9 custom-call bf16[8]", 70 * MS, 0.125 * MS)]
    dev1 = fusions + [
        ("dttpu_paged_decode.4 custom-call bf16[8,1,25,64]", 61 * MS, 3 * MS)]
    r = trace_lib.reduce(_trace({0: dev0, 1: dev1}))
    assert not any(n.startswith("dttpu_") for n, _ in r.device_ops)
    assert r.kernel_s == pytest.approx({
        "dttpu_flash_fwd": 0.0005 / 2, "dttpu_paged_decode": 0.005 / 2,
        "dttpu_paged_window": 0.00075 / 2})
    assert r.kernel_calls == {"dttpu_flash_fwd": 0.5,
                              "dttpu_paged_decode": 1.5,
                              "dttpu_paged_window": 1.0}
    # additive: the accepted sums read what they read
    assert r.custom_call_s == pytest.approx(
        (0.005 + 0.00075 + 0.0005 + 0.000125) / 2)
    assert trace_lib.kernel_name("fusion.4 bf16[8,8]") is None
    assert trace_lib.kernel_name("dttpu_fused_adam.12 custom-call") == \
        "dttpu_fused_adam"


def test_kernel_rows_among_the_ten_equal_the_kernels_sum():
    ops = [("dttpu_paged_decode.4 custom-call bf16[8,1,25,64]", 0.0, 7 * MS),
           ("dttpu_paged_decode.4 custom-call bf16[8,1,25,64]", 10 * MS,
            6 * MS),
           ("fusion.1 bf16[8]", 20 * MS, 2 * MS)]
    r = trace_lib.reduce(_trace({0: ops}))
    rows = dict(r.device_ops)
    for kernel, seconds in r.kernel_s.items():
        assert seconds == pytest.approx(sum(
            s for n, s in rows.items() if trace_lib.kernel_name(n) == kernel))
    assert r.kernel_s == pytest.approx({"dttpu_paged_decode": 0.013})


def test_empty_trace_reduces_to_nothing():
    r = trace_lib.reduce(_trace({}, window=(0.0, 0.0)))
    assert r.busy_s == 0 and r.idle_share is None and r.devices == 0
    assert r.kernel_s == {} and r.kernel_calls == {}


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


def test_the_recorded_trace_reads_what_it_read_and_its_kernels_add_up():
    """``kernel_s`` is additive: on the recorded trace every older field is
    what it was before the field existed (the numbers are PR 24's reducer on
    this file), and every ``kernel_s`` entry equals the ``device_ops`` rows
    of that kernel (this trace's one kernel, ``program.1``, carries no
    ``dttpu_`` name, so it has none)."""
    from jax.profiler import ProfileData
    r = trace_lib.reduce(trace_lib.from_profile(ProfileData.from_file(SMALL)))
    assert (r.window_s, r.busy_s, r.collective_s, r.custom_call_s) == (
        0.102327414, 1.8929e-05, 0.0, 2.124e-06)
    assert r.device_ops == [
        ("fusion.8 bf16[512,512]", 1.1861e-05),
        ("copy-done bf16[512,512]", 3.156e-06),
        ("program.1 custom-call bf16[512,512]", 2.124e-06),
        ("dynamic_slice.1 dynamic-slice bf16[1,1]", 9.36e-07),
        ("copy.11 bf16[512,512]", 7.35e-07),
        ("while s32[]", 6.8e-08),
        ("copy-start bf16[512,512]", 4.9e-08)]
    assert r.idle_gaps == [("fetch", 0.10230624),
                           ("between_ops_under_20us", 2.245e-06)]
    rows = dict(r.device_ops)
    for kernel, seconds in r.kernel_s.items():
        assert seconds == pytest.approx(sum(
            s for n, s in rows.items() if trace_lib.kernel_name(n) == kernel))
    assert r.kernel_s == {} and r.kernel_calls == {}


def test_kernel_seconds_on_the_trace_recorded_with_a_named_kernel():
    """``record_small_trace.py dttpu_small_double`` on one v5e (PR 28): the
    same program with its Pallas kernel named as the program names its own.
    The kernel's row is among ``device_ops`` here, so its ``kernel_s`` entry
    equals that row; its three calls are three events, two of them inside
    the window."""
    from jax.profiler import ProfileData
    named = os.path.join(bench_paths.DATA_DIR, "named_kernel_trace.xplane.pb")
    assert os.path.getsize(named) < 1_000_000
    trace = trace_lib.from_profile(ProfileData.from_file(named))
    kernel_events = [o for o in trace.devices[0]
                     if trace_lib.kernel_name(o[0]) == "dttpu_small_double"]
    assert len(kernel_events) == 3
    r = trace_lib.reduce(trace)
    assert r.kernel_calls == {"dttpu_small_double": 2.0}
    rows = dict(r.device_ops)
    assert r.kernel_s == {
        "dttpu_small_double":
        rows["dttpu_small_double.1 custom-call bf16[512,512]"]}
    assert r.kernel_s["dttpu_small_double"] == r.custom_call_s == 2.126e-06
    assert (r.window_s, r.busy_s) == (0.102015683, 1.9026e-05)
