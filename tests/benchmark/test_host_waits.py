"""The two readers of the program's account of its device's waits on the host
(``benchmark/harness/host_waits.py``, ``device_waits_host_pct``,
``longest_host_wait_ms``) on hand-made spans: the value, the split by what
covered each wait, the slow tick's four parts, the traced segment's
cross-check, None for a program without the account; and that a read's
``device.ready_wait`` child leaves ``tick_host_exposed_pct`` as it was."""
from types import SimpleNamespace

import pytest

import bench_paths
from harness import host_waits
from harness import program_spans as ps
from harness import spec
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.obs.trace import SpanRecord

trace_lib.deactivate(ps.ACTIVATED)

BENCH = spec.Benchmark(bench_paths.ROOT)
METRICS = ["device_waits_host_pct", "longest_host_wait_ms"]
# per tick, the fetch's wait for the device (ms); tick 6 is the slow one
DECODE_MS = [5, 7, 5, 9, 8, 31, 10, 8, 6]
WINDOW = slice(3, 7)          # the window's ticks; 7 and 8 are traced
GC_TICK = 4                   # a 0.1 ms collection inside its deliver


def _row(name, start_ms, end_ms, parent=None, **args):
    return SpanRecord(name, start_ms * 1e3, end_ms * 1e3, parent, args, 1)


def _build(account=True, ready_child=True):
    """Nine ticks: lead-in 0.1, housekeeping 0.05, decode dispatch 0.4, the
    fetch (its wait for the device, then a 0.2 ms copy), deliver 0.3, tail
    0.2; 0.5 ms between ticks.  The device drains as each fetch's wait ends
    and waits until the next tick's decode dispatch starts: a 1.35 ms
    ``device.wait_host`` from tick 1 on (the last poll that saw it busy
    0.05 ms earlier, the next program queued at the dispatch's end)."""
    rows, at, drained = [], 0.0, None
    waits = []
    for n, decode_ms in enumerate(DECODE_MS):
        tick = len(rows)
        rows.append(None)
        t = at + 0.1
        rows.append(_row("serve.housekeeping", t, t + 0.05, tick))
        t += 0.05
        dispatch = t
        rows.append(_row("serve.decode_dispatch", t, t + 0.4, tick))
        t += 0.4
        fetch = len(rows)
        rows.append(_row("serve.decode_fetch", t, t + decode_ms + 0.2, tick))
        if ready_child:
            rows.append(_row(trace_lib.READY_WAIT, t, t + decode_ms, fetch))
        waited = 0.0
        if drained is not None:
            waits.append(_row(trace_lib.WAIT_HOST, drained, dispatch, None,
                              earliest_us=(drained - 0.05) * 1e3,
                              after="tick", before="tick",
                              dispatched_us=(dispatch + 0.4) * 1e3))
            waited = (dispatch - drained) * 1e3
        drained = t + decode_ms
        t += decode_ms + 0.2
        rows.append(_row("serve.deliver", t, t + 0.3, tick))
        if n == GC_TICK:
            rows.append(_row(trace_lib.GC, t + 0.1, t + 0.2, None,
                             generation=0, collected=0))
        t += 0.5
        args = {"tick": n + 1, "windows": 0, "tokens": 8}
        if account:
            args["waited_host_us"] = waited
        rows[tick] = _row("serve.tick", at, t, None, **args)
        at = t + 0.5
    return rows + waits


def _case(monkeypatch, **kw):
    rows = _build(**kw)
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    ticks = [r for r in rows if r.name == "serve.tick"]
    lengths = [(t.end_us - t.start_us) / 1e6 for t in ticks]
    record = {"kind": "serve", "platform": "tpu",
              "tick_seconds": [s + 0.0003 for s in lengths[WINDOW]]}
    # the device trace of the traced segment: 10 % idle, 0.2 ms of it in
    # gaps under 20 us
    window_s = (ticks[8].end_us - ticks[7].start_us) / 1e6
    traced = SimpleNamespace(
        window_s=window_s + 0.0004, idle_share=0.1,
        idle_gaps=[("engine_step", 0.1 * window_s - 0.0002),
                   ("between_ops_under_20us", 0.0002)])
    return rows, record, traced


def test_readers_on_hand_made_spans(monkeypatch, capsys):
    rows, record, traced = _case(monkeypatch)
    window = ps.window(record, traced)
    assert [rows[t].args["tick"] for t in window.units] == [4, 5, 6, 7]
    # the window runs from tick 4's start to tick 7's end and holds the
    # waits ending in ticks 5, 6, 7 whole, and 0.15 ms of tick 4's; one
    # more starts in tick 7 and is cut at its end (copy 0.2 + deliver 0.3
    # + tail 0.2)
    lower_ms = 3 * 1.35 + 0.15 + 0.7
    window_ms = (window.end_us - window.start_us) / 1e3
    value = BENCH.layer_reader("device_waits_host_pct")(record, traced)
    assert value == pytest.approx(100 * lower_ms / window_ms, rel=1e-9)
    assert BENCH.layer_reader("longest_host_wait_ms")(record, traced) == \
        pytest.approx(1.35)
    err = capsys.readouterr().err
    assert '"metric": "device_waits_host_pct"' in err
    assert '"metric": "longest_host_wait_ms"' in err
    assert '"unknown": null' in err     # no live tracer here


def test_the_split_by_what_covered_each_wait(monkeypatch):
    rows, record, traced = _case(monkeypatch)
    window = ps.window(record, traced)
    inside = host_waits.waits(rows, window.start_us, window.end_us)
    by_span = host_waits.split_by_span(rows, inside)
    # a whole wait: the copy 0.2 (the fetch's own), deliver 0.3, the
    # tick's tail 0.2 and lead-in 0.1, between ticks 0.5, housekeeping 0.05
    assert by_span == pytest.approx({
        "serve.deliver": (3 * 0.3 + 0.3 - 0.1) / 1e3,
        "serve.tick": (3 * 0.3 + 0.1 + 0.2) / 1e3,
        "serve.decode_fetch": 4 * 0.2 / 1e3,
        host_waits.BETWEEN: 3 * 0.5 / 1e3,
        "serve.housekeeping": 4 * 0.05 / 1e3,
        trace_lib.GC: 0.1 / 1e3})
    assert sum(by_span.values()) == pytest.approx(
        sum(b - a for _, a, b in inside) / 1e6)
    assert host_waits.split_by_pair(rows, inside) == pytest.approx(
        {"tick -> tick": sum(by_span.values())})
    # the bracket: each wait from its last busy poll to its next program
    # queued, 0.05 + 0.4 ms wider than the wait, cut to the window
    upper_ms = host_waits.bracket_us(rows, window.start_us,
                                     window.end_us) / 1e3
    assert upper_ms == pytest.approx(3 * 1.8 + 0.55 + 0.75)
    # from the drain instead: the lower bound and the dispatch calls
    queued_ms = host_waits.bracket_us(rows, window.start_us, window.end_us,
                                      from_drain=True) / 1e3
    assert queued_ms == pytest.approx(lower_ms_of(inside) + 4 * 0.4)


def lower_ms_of(inside):
    return sum(b - a for _, a, b in inside) / 1e3


def test_a_slow_tick_split_four_ways(monkeypatch):
    rows, record, traced = _case(monkeypatch)
    window = ps.window(record, traced)
    (slow,) = host_waits.slow_ticks(window, 2.0)
    assert rows[slow].args["tick"] == 6
    split = host_waits.tick_split(window, slow)
    # the wait that ended in it (lead-in + housekeeping) and the one that
    # began in it (copy, deliver, tail): the copy's 0.2 is the transfer's
    assert split["ready_wait_ms"] == pytest.approx(31)
    assert split["transfer_ms"] == pytest.approx(0.2)
    assert split["wait_host_ms"] == pytest.approx(0.15 + 0.5)
    assert split["wait_host_in_transfer_ms"] == pytest.approx(0.2)
    assert split["rest_ms"] == pytest.approx(0.4)      # the dispatch
    assert sum(split[k] for k in ("wait_host_ms", "ready_wait_ms",
                                  "transfer_ms", "rest_ms")) == \
        pytest.approx(split["tick_ms"])
    assert split["self_ms_by_span"][trace_lib.READY_WAIT] == \
        pytest.approx(31)
    assert split["self_ms_by_span"]["serve.decode_dispatch"] == \
        pytest.approx(0.4)


def test_the_traced_segment_beside_the_device_trace(monkeypatch):
    rows, record, traced = _case(monkeypatch)
    check = host_waits.segment_check(ps.window(record, traced), traced)
    assert check["ticks"] == 2
    # the wait that ends in tick 8 from its start, and tick 9's whole; the
    # last drain is never followed by a dispatch, so no wait is recorded
    assert check["lower_s"] == pytest.approx((0.15 + 1.35) / 1e3)
    assert check["trace_idle_s"] == pytest.approx(0.1 * traced.window_s)
    assert check["trace_idle_outside_short_gaps_s"] == pytest.approx(
        0.1 * traced.window_s - 0.0002)
    assert check["lower_s"] <= check["upper_s"]


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_account_reads_nothing(monkeypatch, metric):
    """A parent commit's ticks carry no ``waited_host_us``: None, not 0."""
    _, record, traced = _case(monkeypatch, account=False)
    assert ps.window(record, traced) is not None
    assert BENCH.layer_reader(metric)(record, traced) is None
    monkeypatch.setattr(ps, "HAS_SPINE", False)
    assert BENCH.layer_reader(metric)(dict(record), traced) is None


def test_a_ready_wait_child_leaves_the_exposed_share_as_it_was(monkeypatch):
    """The fetch's ``device.ready_wait`` child moves self time from the
    fetch to the child and nothing else: ``tick_host_exposed_pct`` reads
    exactly the same on the same ticks with and without it."""
    read = BENCH.layer_reader("tick_host_exposed_pct")
    values, splits = [], []
    for ready_child in (False, True):
        _, record, traced = _case(monkeypatch, ready_child=ready_child)
        values.append(read(record, traced))
        splits.append(ps.tick_exposure(ps.window(record, traced)))
    assert values[0] is not None and values[0] == values[1]
    assert splits[0]["exposed_s_by_span"] == splits[1]["exposed_s_by_span"]
    assert splits[1]["self_s_by_span"]["serve.decode_fetch"] == \
        pytest.approx(4 * 0.2 / 1e3)
