"""``decode_pages_walked_pct`` on hand-made spans: 100 for a walk of every
table entry, the share walked otherwise, nothing for a program whose decode
dispatches carry no count (a parent commit) and for a window that cannot be
verified."""
from types import SimpleNamespace

import pytest

import bench_paths
from harness import program_spans as ps
from harness import spec
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.obs.trace import SpanRecord

# importing program_spans is a traced run's on-switch; collecting this file
# must not leave tracing on for the tests that follow
trace_lib.deactivate(ps.ACTIVATED)
reqtrace.reset()

READ = spec.Benchmark(bench_paths.ROOT).layer_reader(
    "decode_pages_walked_pct")
TABLE = 4 * 8 * 64               # steps x slots x pages a slot


def _case(monkeypatch, walked_by_tick):
    """Two fill ticks, ``len(walked_by_tick)`` window ticks, one traced
    tick, each a decode dispatch and its fetch; ``walked_by_tick`` gives the
    window's dispatches their ``pages_walked`` (None: no count at all)."""
    rows, lengths, at = [], [], 0.0
    plan = [TABLE, TABLE] + list(walked_by_tick) + [7]
    for n, walked in enumerate(plan):
        args = ({} if walked is None
                else {"pages_walked": walked, "pages_table": TABLE})
        tick, start = len(rows), at
        rows.append(None)
        rows.append(SpanRecord("serve.decode_dispatch", (at + 0.1) * 1e3,
                               (at + 0.5) * 1e3, tick,
                               dict(args, steps=4, active=8), 1))
        rows.append(SpanRecord("serve.decode_fetch", (at + 0.5) * 1e3,
                               (at + 20.0 + n) * 1e3, tick, {}, 1))
        at += 20.2 + n
        rows[tick] = SpanRecord("serve.tick", start * 1e3, at * 1e3, None,
                                {"tick": n + 1}, 1)
        lengths.append((at - start) / 1e3)
        at += 0.5
    monkeypatch.setattr(ps, "_program_spans", lambda: rows)
    record = {"kind": "serve", "platform": "tpu",
              "tick_seconds": [s + 0.0003 for s in lengths[2:-1]]}
    traced = SimpleNamespace(window_s=lengths[-1] + 0.0004)
    return record, traced


@pytest.mark.parametrize("walked,want", [
    ([TABLE, TABLE, TABLE], 100.0),             # every entry, every step
    ([TABLE // 2, TABLE // 4, TABLE], 100.0 * 7 / 12),
    ([0, 0, 0], 0.0),                           # no slot live
    ([None, None, None], None),                 # a program without the count
], ids=["full_table", "held_pages", "nothing_live", "no_count"])
def test_reader_on_hand_made_spans(monkeypatch, walked, want):
    record, traced = _case(monkeypatch, walked)
    got = READ(record, traced)
    assert got == (None if want is None else pytest.approx(want))


def test_reader_reports_nothing_for_an_unverified_window(monkeypatch):
    record, traced = _case(monkeypatch, [TABLE, TABLE, TABLE])
    record["tick_seconds"][1] += 0.01           # not this run's ticks
    assert READ(record, traced) is None
