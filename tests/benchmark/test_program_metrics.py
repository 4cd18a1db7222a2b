"""The six per-layer metrics that read the program's own spans and counts
(``benchmark/harness/program_spans.py`` and its readers): each reader on
hand-made spans, and the window selection on a rehearsed CPU cell — right
when the driver's list is the run's own, None when it is tampered with."""
import contextlib
import io
import json
from types import SimpleNamespace

import pytest

import bench_paths
import run as bench_run
import tiny_bench
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
SERVE_METRICS = ["tick_host_exposed_pct", "prefill_windows_per_tick",
                 "ttft_prefill_ticks_p95", "queue_wait_ms_p95"]
TRAIN_METRICS = ["prefetch_wait_pct", "step_dispatch_ms_p50"]


def _reader(name):
    return BENCH.layer_reader(name)


def _row(name, start_ms, end_ms, parent=None, **args):
    return SpanRecord(name, start_ms * 1e3, end_ms * 1e3, parent, args, 1)


def _tick(rows, start_ms, parts, **args):
    """Append one tick whose children are ``parts`` laid end to end after a
    0.1 ms lead-in: ``(name, ms)`` or ``(name, ms, [grandchildren])``."""
    at = start_ms + 0.1
    tick = len(rows)
    rows.append(None)
    for part in parts:
        name, ms = part[0], part[1]
        me = len(rows)
        rows.append(_row(name, at, at + ms, tick))
        inner = at
        for child, child_ms in (part[2] if len(part) > 2 else ()):
            rows.append(_row(child, inner, inner + child_ms, me))
            inner += child_ms
        at += ms
    rows[tick] = _row("serve.tick", start_ms, at + 0.2, None, **args)
    return at + 0.2


# ------------------------------------------------------- hand-made spans

@pytest.fixture
def serve_case(monkeypatch):
    """Three fill ticks, four window ticks, two traced ticks; turns whose
    first token falls before, in and after the window."""
    reqtrace.reset()
    rows, at, lengths = [], 0.0, []
    plan = [  # (decode ms, prefill windows) per tick
        (5, 0), (7, 1), (5, 0),            # fill
        (20, 2), (9, 0), (31, 1), (12, 0),  # the window
        (8, 0), (6, 0)]                     # traced
    for n, (decode_ms, windows) in enumerate(plan):
        parts = [("serve.housekeeping", 0.05)]
        for w in range(windows):
            last = w == windows - 1
            inner = [("serve.prefill_dispatch", 0.3)]
            if last:
                inner += [("serve.first_token_fetch", 2.0),
                          ("serve.register", 0.1)]
            parts.append(("serve.prefill", sum(ms for _, ms in inner) + 0.05,
                          inner))
        parts += [("serve.decode_dispatch", 0.4),
                  ("serve.decode_fetch", decode_ms), ("serve.deliver", 0.3)]
        start = at
        at = _tick(rows, start, parts, tick=n + 1, windows=windows,
                   admissions=0, active=2, tokens=4) + 0.5
        lengths.append((at - 0.5 - start) / 1e3)
    ticks = [r for r in rows if r.name == "serve.tick"]
    with trace_lib.activated(trace_lib.Tracer()):
        for i, (first_ms, prefill_ticks, wait_s) in enumerate([
                (ticks[1].start_us / 1e3 + 1, 9, 0.5),      # before
                (ticks[3].start_us / 1e3 + 1, 2, 0.001),
                (ticks[5].start_us / 1e3 + 1, 3, 0.004),
                (ticks[6].end_us / 1e3 - 1, 1, 0.002),
                (ticks[8].start_us / 1e3 + 1, 7, 0.9)]):    # after
            tid = reqtrace.mint()
            reqtrace.submitted(tid, ts_us=0.0)
            reqtrace.mark(tid, "first_token", ts_us=first_ms * 1e3)
            reqtrace.note(tid, prefill_ticks=prefill_ticks,
                          prefill_windows=prefill_ticks,
                          queue_wait_s=wait_s)
            if i != 2:
                reqtrace.retired(tid, "ok")      # one turn is still live
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    # the benchmark's tick encloses the program's: 0.3 ms longer
    record = {"kind": "serve", "platform": "tpu",
              "tick_seconds": [s + 0.0003 for s in lengths[3:7]]}
    traced = SimpleNamespace(window_s=(ticks[8].end_us - ticks[7].start_us
                                       + 400.0) / 1e6)
    yield rows, record, traced
    reqtrace.reset()


def test_serve_readers_on_hand_made_spans(serve_case):
    rows, record, traced = serve_case
    window = ps.window(record, traced)
    assert [rows[t].args["tick"] for t in window.units] == [4, 5, 6, 7]
    values = {m: _reader(m)(record, traced) for m in SERVE_METRICS}
    assert values["prefill_windows_per_tick"] == pytest.approx(3 / 4)
    assert values["ttft_prefill_ticks_p95"] == 3      # of 2, 3 (live), 1
    assert values["queue_wait_ms_p95"] == pytest.approx(4.0)
    # exposed, by hand: per tick the 0.1 ms lead-in + housekeeping 0.05,
    # then from the decode fetch's return: deliver 0.3 + tail 0.2; in a
    # tick that admits, also register 0.1 + the prefill span's own 0.05
    # after the first-token fetch returned.  A mid window is dispatch
    # only: everything after its start is covered until the next fetch.
    tick_ms = sum(rows[t].end_us - rows[t].start_us
                  for t in window.units) / 1e3
    exposed_ms = 4 * (0.1 + 0.05 + 0.3 + 0.2) + 2 * (0.1 + 0.05)
    assert values["tick_host_exposed_pct"] == pytest.approx(
        100 * exposed_ms / tick_ms, rel=1e-6)
    account = ps.tick_exposure(window)
    assert account["self_times_sum_to_ticks_s"] == pytest.approx(
        account["tick_s"], abs=1e-9)
    assert sum(account["self_s_by_span"].values()) == pytest.approx(
        account["tick_s"], abs=1e-9)
    assert account["exposed_s_by_span"]["serve.deliver"] == pytest.approx(
        4 * 0.3e-3)


@pytest.mark.parametrize("metric", SERVE_METRICS)
def test_serve_reader_is_none_on_a_tampered_list(serve_case, metric):
    rows, record, traced = serve_case
    tampered = dict(record, tick_seconds=list(record["tick_seconds"]))
    tampered["tick_seconds"][2] += 0.005          # one tick 5 ms off
    assert _reader(metric)(tampered, traced) is None
    shifted = dict(record, tick_seconds=record["tick_seconds"][1:] + [0.0125])
    assert _reader(metric)(shifted, traced) is None
    assert _reader(metric)(dict(record, platform="cpu"), traced) is None
    assert _reader(metric)(record, traced) is not None   # and back


def test_selection_falls_back_to_a_neighbour_that_verifies(serve_case):
    """A traced window longer than its ticks (a slow profiler stop) makes
    the guess take one tick too many; the verified neighbour is used."""
    rows, record, _ = serve_case
    units, why = ps.select_ticks(rows, record["tick_seconds"], 0.050)
    assert why == "" and [rows[t].args["tick"] for t in units] == [4, 5, 6, 7]
    assert ps.select_ticks(rows, [0.001] * 4, 0.050)[0] is None
    assert ps.select_ticks(rows, [0.001] * 40, 0.050)[0] is None


@pytest.fixture
def train_case(monkeypatch):
    """Readings of two steps: a compile step, two warm-up readings, three
    window readings, one traced.  Each step: a prefetch wait, then
    ``train.step`` holding ``train.dispatch``; the fetch fills the rest."""
    rows, at = [], 0.0
    reading_ms = [50.0, 50.0, 40.0, 41.0, 40.5, 40.0]
    waits_ms = iter([0.02] * 5 + [0.9, 0.02, 0.03, 0.03, 0.02, 0.03]
                    + [0.02] * 4)

    def step(n):
        nonlocal at
        wait = next(waits_ms)
        rows.append(_row("data.prefetch_wait", at, at + wait))
        at += wait + 0.01
        me = len(rows)
        rows.append(_row("train.step", at, at + 1.1, None, step=n))
        rows.append(_row("train.dispatch", at + 0.05, at + 1.05, me))
        at += 1.1

    step(1)
    at = 30.0
    n = 2
    for ms in reading_ms:
        start = at
        step(n)
        step(n + 1)
        n += 2
        at = start + ms
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    window_s = sum(reading_ms[2:5]) / 1e3
    record = {"kind": "train", "platform": "tpu", "steps_per_reading": 2,
              "reading_seconds": [ms / 1e3 for ms in reading_ms[2:5]],
              "window_s": window_s, "traced_steps": 2}
    return rows, record, SimpleNamespace(window_s=reading_ms[5] / 1e3)


def test_train_readers_on_hand_made_spans(train_case):
    rows, record, traced = train_case
    window = ps.window(record, traced)
    assert [rows[u].args["step"] for u in window.units] == [6, 7, 8, 9, 10, 11]
    assert _reader("step_dispatch_ms_p50")(record, traced) == \
        pytest.approx(1.0)
    waited_ms = 0.9 + 0.02 + 0.03 + 0.03 + 0.02 + 0.03
    assert _reader("prefetch_wait_pct")(record, traced) == pytest.approx(
        100 * waited_ms / (record["window_s"] * 1e3), rel=1e-6)
    # a stalled input pipeline does not cost the verification: the
    # reading's anchor is the wait's start, not the late step's
    assert window.start_us == pytest.approx(
        next(r for r in rows if r.name == "train.step"
             and r.args["step"] == 6).start_us - 910.0)


@pytest.mark.parametrize("metric", TRAIN_METRICS)
def test_train_reader_is_none_on_a_tampered_list(train_case, metric):
    rows, record, traced = train_case
    tampered = dict(record, reading_seconds=[0.040, 0.046, 0.0405])
    assert _reader(metric)(tampered, traced) is None
    assert _reader(metric)(dict(record, platform="cpu"), traced) is None
    assert _reader(metric)(record, traced) is not None


def test_a_program_without_the_spine_reads_nothing(serve_case, monkeypatch):
    """Laid over a parent commit, the readers return None and do not raise."""
    rows, record, traced = serve_case
    monkeypatch.setattr(ps, "HAS_SPINE", False)
    for metric in SERVE_METRICS + TRAIN_METRICS:
        assert _reader(metric)(dict(record), traced) is None


# ------------------------------------------- a rehearsed cell on the CPU

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The tiny benchmark of new files only (``tiny_bench.py``) with a
    reader that keeps what the driver handed it."""
    return tiny_bench.build(
        tmp_path_factory.mktemp("tiny_program_metrics"), "kept_record",
        "from harness import program_spans\n"
        "def read(record, trace):\n"
        "    program_spans.kept = (record, trace)\n"
        "    return 1.0\n",
        {"unit": "count", "better": "higher", "source": "program_counter",
         "layer": "entry", "moves": "setup_s"})


@contextlib.contextmanager
def _rehearse(root, workload, seconds, monkeypatch):
    """One traced run of a tiny cell under a tracer of our own, which stays
    active for the body -> (tracer, record, reduced trace, lines)."""
    # a CPU shared with the other test workers can lose many milliseconds
    # between two clock reads (seen: 5 ms under six workers), and the tiny
    # ticks are that short themselves: here the tolerance only has to tell
    # the run's own list from one tampered with by a second, and what is
    # tested is that the FIRST choice (by the traced segment's extent) is
    # the right ticks.  The chip run keeps the 2 ms.
    monkeypatch.setattr(ps, "TOLERANCE_S", 0.25)
    reqtrace.reset()
    reqtrace.configure(ring=ps.RING)
    out = io.StringIO()
    try:
        with trace_lib.activated(trace_lib.Tracer()) as tracer:
            code = bench_run.main(
                ["--workload", workload, "--seed", str(2 ** 31 + 29),
                 "--seconds", str(seconds), "--trace", "1"],
                out, root=root, rehearse_on_cpu=True)
            assert code == 0
            lines = [json.loads(line)
                     for line in out.getvalue().splitlines()]
            record, reduced = ps.kept
            yield tracer, record, reduced, lines
    finally:
        reqtrace.reset()


def test_window_selection_on_a_rehearsed_serve_cell(tiny_root, monkeypatch):
    with _rehearse(tiny_root, "tiny.chat", 2, monkeypatch) as (
            tracer, record, reduced, lines):
        # on the CPU the new metrics are left out, as peaks and memory are
        assert not set(SERVE_METRICS) & set(lines[-1]["metrics"])
        spans = tracer.spans()
        fill = next(line for line in lines if "fill_ticks" in line)[
            "fill_ticks"]
        need = len(record["tick_seconds"])
        units, why = ps.select_ticks(spans, record["tick_seconds"],
                                     reduced.window_s)
        assert why == "" and units is not None
        assert len(spans) - 1 - max(units) > 0    # traced ticks follow
        # the window's ticks are the engine's ticks fill+1 .. fill+need
        assert [spans[u].args["tick"] for u in units] == list(
            range(fill + 1, fill + need + 1))
        for u, outside in zip(units, record["tick_seconds"]):
            inside = (spans[u].end_us - spans[u].start_us) / 1e6
            assert 0 <= outside - inside < ps.TOLERANCE_S
        on_chip = dict(record, platform="tpu")
        values = {m: _reader(m)(on_chip, reduced) for m in SERVE_METRICS}
        assert all(v is not None for v in values.values()), values
        assert 0 < values["tick_host_exposed_pct"] < 100
        assert 0 < values["prefill_windows_per_tick"] <= 4
        assert 1 <= values["ttft_prefill_ticks_p95"] <= 12
        assert 0 <= values["queue_wait_ms_p95"] < 1e3
        account = ps.tick_exposure(ps.window(on_chip, reduced))
        assert account["self_times_sum_to_ticks_s"] == pytest.approx(
            account["tick_s"], abs=1e-6)
        # tampered: one tick a second longer than the program ever ran
        tampered = dict(on_chip, tick_seconds=list(record["tick_seconds"]))
        tampered["tick_seconds"][need // 2] += 1.0
        for metric in SERVE_METRICS:
            assert _reader(metric)(tampered, reduced) is None


def test_window_selection_on_a_rehearsed_train_cell(tiny_root, monkeypatch):
    with _rehearse(tiny_root, "tiny.train", 1, monkeypatch) as (
            tracer, record, reduced, lines):
        assert not set(TRAIN_METRICS) & set(lines[-1]["metrics"])
        spans = tracer.spans()
        per = record["steps_per_reading"]
        need = len(record["reading_seconds"]) * per
        # two traced readings follow the window: the driver's count of them
        # is the selection's first choice, whatever their extent
        assert record["traced_steps"] == 2 * per
        units, why = ps.select_steps(spans, record["reading_seconds"], per,
                                     record["traced_steps"])
        assert why == "" and units is not None
        # one compile step and two warm-up readings come before the window
        first = 1 + 2 * per + 1
        assert [spans[u].args["step"] for u in units] == list(
            range(first, first + need))
        on_chip = dict(record, platform="tpu")
        inside = _reader("prefetch_wait_pct")(on_chip, reduced)
        outside = 100 * record["span_seconds"].get("next_batch", 0.0) \
            / record["window_s"]
        assert 0 <= inside <= outside      # the program's wait is inside
        assert 0 < _reader("step_dispatch_ms_p50")(on_chip, reduced) < 1e3
        tampered = dict(on_chip, reading_seconds=[
            s + 1.0 for s in record["reading_seconds"]])
        for metric in TRAIN_METRICS:
            assert _reader(metric)(tampered, reduced) is None
