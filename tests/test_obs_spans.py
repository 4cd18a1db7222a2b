"""The span spine (obs/trace.py) and what the serve tick and the train step
record on it: parent links, self times, the profiler's view, the counters at
the same boundaries, and that one measurement feeds a span, a goodput frame
and a critpath phase.  All CPU, none slow."""
import glob
import os
import statistics
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from distributed_tensorflow_tpu import data, obs, ops, optim, serve, train
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.obs import critpath as critpath_lib
from distributed_tensorflow_tpu.obs import goodput as goodput_lib
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TICK_CHILDREN = {"serve.housekeeping", "serve.admit", "serve.prefill",
                 "serve.decode_dispatch", "serve.decode_fetch",
                 "serve.deliver"}
PREFILL_CHILDREN = {"serve.prefill_dispatch", "serve.first_token_read",
                    "serve.register"}


def _prompt(plen, seed=1, vocab=512):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (plen,), 0, vocab), np.int32)


@pytest.fixture(scope="module")
def model_params():
    model = gpt_tiny(dropout_rate=0.0)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_params, **kw):
    model, params = model_params
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("tick_steps", 2)
    return serve.Engine(model, params, registry=metrics_lib.Registry(), **kw)


@pytest.fixture
def tracer():
    reqtrace.reset()
    with trace_lib.activated(trace_lib.Tracer()) as t:
        yield t
    reqtrace.reset()


def _schedule(engine):
    """A fixed tiny schedule: a long and a short prompt together, then a
    third request while the first two decode."""
    handles = [engine.submit(_prompt(14, seed=3), 6),
               engine.submit(_prompt(5, seed=4), 4)]
    for _ in range(3):
        engine.step()
    handles.append(engine.submit(_prompt(9, seed=5), 5))
    engine.drain()
    assert all(h.status == "ok" for h in handles)
    return handles


# ------------------------------------------------------------- the spine

class TestSpine:
    def test_parent_links_args_and_self_times(self):
        t = trace_lib.Tracer()
        with t.span("outer", k=1) as outer:
            with t.span("a"):
                with t.span("leaf"):
                    pass
            with t.span("b") as b:
                b.set(n=3)
        t.add_span("retro", 10.0, 25.0, why="x")
        rows = t.spans()
        assert [r.name for r in rows] == ["outer", "a", "leaf", "b", "retro"]
        assert [r.parent for r in rows] == [None, 0, 1, 0, None]
        assert rows[0].args == {"k": 1} and rows[3].args == {"n": 3}
        assert all(r.end_us >= r.start_us for r in rows)
        own = trace_lib.self_times_us(rows)
        # a span and everything below it sum to the span
        assert sum(own[:4]) == pytest.approx(
            rows[0].end_us - rows[0].start_us, abs=1e-6)
        assert own[1] == pytest.approx(
            (rows[1].end_us - rows[1].start_us)
            - (rows[2].end_us - rows[2].start_us), abs=1e-6)
        assert own[4] == pytest.approx(15.0)
        # the context yields its own measurement, on perf_counter's clock
        assert outer.duration_s == pytest.approx(
            (rows[0].end_us - rows[0].start_us) / 1e6, abs=1e-9)
        assert trace_lib.to_perf_counter_s(rows[0].start_us) == \
            pytest.approx(outer.start_s, abs=1e-6)
        # the Chrome view is derived from the same rows
        xs = {e["name"]: e for e in t.events() if e["ph"] == "X"}
        assert set(xs) == {"outer", "a", "leaf", "b", "retro"}
        assert xs["b"]["args"] == {"n": 3}

    def test_open_spans_and_threads_keep_their_own_stacks(self):
        t = trace_lib.Tracer()
        seen = {}

        def worker():
            with t.span("worker"):
                seen["rows"] = t.spans()

        with t.span("main"):
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        rows = t.spans()
        assert {r.name: r.parent for r in rows} == {"main": None,
                                                    "worker": None}
        open_main = next(r for r in seen["rows"] if r.name == "main")
        assert open_main.end_us is None          # still open when read
        assert trace_lib.self_times_us(seen["rows"])[0] == 0.0

    def test_timed_measures_without_a_tracer_and_records_with_one(self):
        trace_lib.deactivate()
        with trace_lib.timed("x", a=1) as s:
            pass
        assert s.duration_s >= 0 and s.end_s >= s.start_s > 0
        t = trace_lib.Tracer()
        with trace_lib.activated(t):
            with trace_lib.timed("x", a=1) as s:
                pass
        (row,) = t.spans()
        assert row.name == "x" and row.args == {"a": 1}
        assert (row.end_us - row.start_us) / 1e6 == pytest.approx(
            s.duration_s, abs=1e-9)

    def test_no_tracer_is_the_cached_null_span_and_imports_no_jax(self):
        """Loaded by path in a fresh interpreter (the package's __init__
        imports JAX; obs/trace.py itself must not)."""
        code = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location(
    "t", "distributed_tensorflow_tpu/obs/trace.py")
t = importlib.util.module_from_spec(spec); spec.loader.exec_module(t)
a, b = t.span("x", k=1), t.span("y")
assert a is b is t._NULL_SPAN
with a as s:
    s.set(n=1)
with t.timed("z"):
    pass
assert "jax" not in sys.modules, "obs.trace imported jax"
with t.activated(t.Tracer()):
    with t.span("annotated"):
        pass
print("jax" in sys.modules)
"""
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=120,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stderr
        # ... and the first RECORDED span is what imports it, lazily
        assert proc.stdout.strip() == "True"

    def test_reqtrace_reset_restores_capacities(self):
        reqtrace.configure(ring=4, forensics=2)
        reqtrace.reset()
        with trace_lib.activated(trace_lib.Tracer()):
            for i in range(6):
                tid = reqtrace.mint()
                reqtrace.submitted(tid)
                reqtrace.note(tid, prefill_ticks=i)
                reqtrace.retired(tid, "ok")
        done = reqtrace.completed()
        assert len(done) == 6                    # not the 4 left behind
        assert [r["counts"]["prefill_ticks"] for r in done] == list(range(6))
        assert reqtrace._ring.maxlen == reqtrace.RING == 256
        assert reqtrace._forensics.maxlen == reqtrace.FORENSICS == 64
        reqtrace.reset()


# --------------------------------------------------------- the serve tick

def test_tick_tree_self_times_sum_and_heartbeat_is_the_span(model_params,
                                                            tracer):
    engine = _engine(model_params)
    _schedule(engine)
    rows = tracer.spans()
    own = trace_lib.self_times_us(rows)
    ticks = [i for i, r in enumerate(rows) if r.name == "serve.tick"]
    assert len(ticks) == engine.stats().ticks_completed >= 5
    kids = {}
    for i, r in enumerate(rows):
        if r.parent is not None:
            kids.setdefault(r.parent, []).append(i)
    for t in ticks:
        names = {rows[c].name for c in kids[t]}
        assert names <= TICK_CHILDREN and "serve.deliver" in names
        subtree, todo = 0.0, [t]
        while todo:
            i = todo.pop()
            subtree += own[i]
            todo.extend(kids.get(i, ()))
            assert rows[i].start_us >= rows[t].start_us
            assert rows[i].end_us <= rows[t].end_us
        assert subtree == pytest.approx(rows[t].end_us - rows[t].start_us,
                                        abs=1e-3)
        for c in kids[t]:
            if rows[c].name == "serve.prefill":
                # a group's span: the windows one program holds, or the
                # read of one program's first tokens; it names its requests
                assert {rows[g].name for g in kids[c]} <= PREFILL_CHILDREN
                assert rows[c].args["trace_ids"]
                for g in kids[c]:
                    if rows[g].name == "serve.prefill_dispatch":
                        a = rows[g].args
                        assert 1 <= a["real"] <= a["rows"] <= 2
                        assert a["real"] == len(rows[c].args["trace_ids"])
    # the tick's args are the tick's counts
    args = [rows[t].args for t in ticks]
    assert [a["tick"] for a in args] == list(range(1, len(ticks) + 1))
    stats = engine.stats()
    assert sum(a["windows"] for a in args) == stats.prefill_windows_total
    assert sum(a["admissions"] for a in args) == 3
    assert sum(a["tokens"] for a in args) == 6 + 4 + 5
    # the heartbeat's stamps are the last tick span's two clock reads
    last = rows[ticks[-1]]
    assert stats.last_tick_start_s == pytest.approx(
        trace_lib.to_perf_counter_s(last.start_us), abs=1e-6)
    assert stats.last_tick_duration_s == pytest.approx(
        (last.end_us - last.start_us) / 1e6, abs=1e-6)


def test_counters_and_request_counts_repeat_exactly(model_params, tracer):
    """The fixed schedule twice on fresh engines: same windows, same ticks
    to first token, same bounces — and the numbers are the arithmetic's."""
    seen = []
    for _ in range(2):
        reqtrace.reset()
        # 6 pages of 8 tokens, 5 usable: two 3-page requests cannot both
        # hold a lease, so the second bounces until the first retires
        engine = _engine(model_params, max_len=32, prefill_chunk=8,
                         page_size=8, num_pages=6)
        a = engine.submit(_prompt(16, seed=7), 8)
        b = engine.submit(_prompt(15, seed=8), 8)
        engine.drain()
        assert a.status == b.status == "ok"
        stats = engine.stats()
        done = {r["trace_id"]: r["counts"] for r in reqtrace.completed()}
        counts = [done[h._req.trace_id] for h in (a, b)]
        seen.append((stats.prefill_windows_total, stats.decode_steps_total,
                     stats.admit_backpressure_total, stats.ticks_completed,
                     [(c["prefill_ticks"], c["prefill_windows"])
                      for c in counts]))
        assert all(c["queue_wait_s"] >= 0 for c in counts)
        assert counts[1]["queue_wait_s"] > counts[0]["queue_wait_s"]
        # registry series render from the same stats
        reg = engine.metrics.registry
        assert reg.get("dttpu_serve_prefill_windows_total").value == \
            stats.prefill_windows_total
        assert reg.get("dttpu_serve_admit_backpressure_total").value == \
            stats.admit_backpressure_total
        assert reg.get("dttpu_serve_decode_steps_total").value == \
            stats.decode_steps_total
    assert seen[0] == seen[1]
    windows, decode_steps, bounces, ticks, per_request = seen[0]
    assert windows == 4                       # 16 and 15 tokens, windows of 8
    assert per_request == [(2, 2), (2, 2)]    # one window a tick
    assert bounces >= 1
    assert decode_steps % 2 == 0 and decode_steps >= 8


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "gather"])
def test_decode_pages_walked_counts_what_the_steps_read(model_params,
                                                        tracer, use_kernel):
    """``decode_pages_walked_total`` over ``decode_pages_table_total``:
    with the page-walk kernel a decode step reads the pages the slot's
    tokens lie on (a 7-token prompt with a budget of 9 on 8-token pages:
    eight steps, the first over 8 columns, the others into the second
    page), the gather read takes both slots' whole tables; the spans, the
    stats and the registry carry the same counts."""
    engine = _engine(model_params, tick_steps=3, page_size=8,
                     use_paged_kernel=use_kernel)
    handle = engine.submit(_prompt(7, seed=3), 9)
    engine.drain()
    assert handle.status == "ok" and len(handle.tokens) == 9
    stats = engine.stats()
    table = 3 * 3 * 2 * 8            # dispatches x steps x slots x pages
    assert stats.decode_steps_total == 9
    assert stats.decode_pages_table_total == table
    assert stats.decode_pages_walked_total == (1 + 7 * 2 if use_kernel
                                               else table)
    dispatches = [s.args for s in tracer.spans()
                  if s.name == "serve.decode_dispatch"]
    assert [a["pages_table"] for a in dispatches] == [table // 3] * 3
    assert [a["pages_walked"] for a in dispatches] == (
        [5, 6, 4] if use_kernel else [table // 3] * 3)
    reg = engine.metrics.registry
    assert reg.get("dttpu_serve_decode_pages_walked_total").value == \
        stats.decode_pages_walked_total
    assert reg.get("dttpu_serve_decode_pages_table_total").value == \
        stats.decode_pages_table_total


def test_a_decoding_tick_leaves_the_device_no_empty_queue(model_params,
                                                         tracer):
    """Beside a decoding request, a four-window prompt: each tick reads an
    admitting window's token only after its decode dispatch and before its
    decode fetch, and dispatches the mid window the NEXT tick would open
    with behind the decode program, so that tick dispatches none for the
    request: still one window a tick (``prefill_ticks`` is the windows)."""
    engine = _engine(model_params)
    first = engine.submit(_prompt(5, seed=21), 20)
    while not first.tokens:
        engine.step()
    mark = len(tracer.spans())
    second = engine.submit(_prompt(14, seed=22), 4)
    engine.drain()
    assert first.status == second.status == "ok"
    rows = tracer.spans()
    kids = {}
    for i, r in enumerate(rows):
        if r.parent is not None:
            kids.setdefault(r.parent, []).append(i)
    windows_by_tick = []
    for t in [i for i in range(mark, len(rows))
              if rows[i].name == "serve.tick"]:
        named = {rows[c].name: rows[c] for c in kids[t]}
        if "serve.decode_dispatch" not in named:
            continue
        lo = named["serve.decode_dispatch"].end_us
        hi = named["serve.decode_fetch"].start_us
        grand = [rows[g] for c in kids[t] for g in kids.get(c, ())
                 if second._req.trace_id in rows[c].args.get("trace_ids",
                                                             ())]
        for g in grand:
            if g.name == "serve.first_token_read":
                assert lo <= g.start_us and g.end_us <= hi
        dispatched = [g for g in grand if g.name == "serve.prefill_dispatch"]
        windows_by_tick.append(
            ["ahead" if lo <= g.start_us <= hi else "opening"
             for g in dispatched])
        assert all(g.end_us <= hi for g in dispatched)
    # window 0 opens its tick and window 1 runs ahead in it, window 2 runs
    # ahead in the next, whose own turn is spent; the admitting window is
    # never ahead
    assert [w for w in windows_by_tick if w][:3] == [
        ["opening", "ahead"], ["ahead"], ["opening"]]
    assert windows_by_tick[1] == ["ahead"] and windows_by_tick[2] == []
    counts = {r["trace_id"]: r["counts"] for r in reqtrace.completed()}[
        second._req.trace_id]
    assert (counts["prefill_ticks"], counts["prefill_windows"]) == (4, 4)


def test_profiler_capture_shows_the_same_spans(model_params, tracer,
                                               tmp_path):
    """Under a jax.profiler capture the program's spans are
    ``dttpu:<name>`` events on /host:CPU, nested as in memory, and agree
    with the in-memory spans to 50 us (length, and start on one clock
    mapping)."""
    from jax.profiler import ProfileData
    engine = _engine(model_params)
    engine.submit(_prompt(6, seed=11), 3)
    engine.drain()                                # compile outside the capture
    mark = len(tracer.spans())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.submit(_prompt(10, seed=12), 4)
        engine.submit(_prompt(7, seed=13), 3)
        engine.drain()
    finally:
        jax.profiler.stop_trace()
    rows = [r for r in tracer.spans()[mark:] if r.name.startswith("serve.")]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = sorted(
        ((e.start_ns, e.duration_ns, e.name[len("dttpu:"):])
         for line in host.lines for e in line.events
         if e.name.startswith("dttpu:serve.")))
    assert [name for _, _, name in events] == [r.name for r in rows]
    assert sum(r.name == "serve.tick" for r in rows) >= 3
    # one clock mapping for the whole capture (the median offset), then
    # every span's start and length agree to 50 us — but for the rare span
    # in which this shared CPU took a time slice between the annotation's
    # clock read and the span's own: nine in ten must agree, and none may
    # be off by a millisecond
    offsets = [start_ns / 1e3 - r.start_us
               for (start_ns, _, _), r in zip(events, rows)]
    mapping = statistics.median(offsets)
    off_by = [max(abs(offset - mapping),
                  abs(dur_ns / 1e3 - (r.end_us - r.start_us)))
              for offset, (_, dur_ns, _), r in zip(offsets, events, rows)]
    assert statistics.median(off_by) < 50
    assert sum(d < 50 for d in off_by) >= 0.9 * len(off_by), off_by
    assert max(off_by) < 1000, off_by
    # nested on the profiler's clock as in memory: a child inside its tick
    by_index = dict(zip((i for i, r in enumerate(tracer.spans()[mark:])
                         if r.name.startswith("serve.")), events))
    spans = tracer.spans()[mark:]
    for i, r in enumerate(spans):
        if r.name.startswith("serve.") and r.parent is not None \
                and r.parent >= mark:
            child, parent = by_index[i], by_index[r.parent - mark]
            assert parent[0] <= child[0]
            assert child[0] + child[1] <= parent[0] + parent[1]


# ------------------------------------- one measurement, every consumer

def test_critpath_phases_are_the_spans_durations(model_params, tracer):
    """prefill_compute is the request's ``serve.prefill`` spans, decode
    compute the dispatch + fetch spans of the ticks it decoded in: the
    same clock reads, so equal to rounding, not merely close."""
    engine = _engine(model_params, num_slots=1)
    with critpath_lib.activated(critpath_lib.CritpathLedger()):
        handle = engine.submit(_prompt(10, seed=21), 5)
        engine.drain()
    cp = handle.critpath
    rows = tracer.spans()

    def total(name):
        return sum(r.end_us - r.start_us for r in rows
                   if r.name == name) / 1e6

    assert cp["prefill_compute"] == pytest.approx(total("serve.prefill"),
                                                  abs=1e-9)
    assert cp["decode_compute"] == pytest.approx(
        total("serve.decode_dispatch") + total("serve.decode_fetch"),
        abs=1e-9)
    assert cp["prefill_interference"] == 0.0
    (admit,) = [r for r in rows if r.name == "serve.admit"]
    assert admit.args["outcome"] == "ok"
    assert cp["queue_wait"] == pytest.approx(
        trace_lib.to_perf_counter_s(admit.end_us) - handle._req.submit_time,
        abs=1e-6)
    (record,) = reqtrace.completed()
    assert record["counts"]["queue_wait_s"] == pytest.approx(
        cp["queue_wait"], abs=1e-9)
    assert record["counts"]["prefill_windows"] == 3      # 10 tokens / 4


def test_train_step_spans_feed_goodput_and_the_save_histogram(tmp_path):
    """One ``with`` per boundary: the goodput "step" bucket IS the
    ``train.dispatch`` spans, "data_stall" the ``data.prefetch_wait``
    spans, and save()'s histogram, bucket and ``checkpoint`` span are one
    measurement."""
    model = ops.serial(ops.Dense(8, "relu"), ops.Dense(32, "sigmoid"))
    opt = optim.adam()
    state = train.init_train_state(model, opt, jax.random.PRNGKey(0), (64,))
    step = train.make_train_step(model, "mse", opt)
    (xt, yt), _ = data.xor_data(200, val_size=10, seed=0)
    tele = obs.Telemetry(trace_dir=str(tmp_path))
    acct = goodput_lib.GoodputAccountant()
    batches = data.prefetch_to_device(
        iter([(xt[:50], yt[:50])] * 4), size=2)
    with goodput_lib.activated(acct):
        with train.TrainSession(state, step, telemetry=tele,
                                checkpoint_dir=str(tmp_path / "ck"),
                                hooks=[train.TraceHook(tele)]) as sess:
            for batch in batches:
                sess.run_step(batch)
            sess.save()
    rows = tele.tracer.spans()
    tele.close()

    def named(name):
        return [r for r in rows if r.name == name]

    def seconds(rs):
        return sum(r.end_us - r.start_us for r in rs) / 1e6

    steps, dispatches = named("train.step"), named("train.dispatch")
    assert [r.args["step"] for r in steps] == [1, 2, 3, 4]
    assert len(dispatches) == 4
    assert all(rows[d.parent].name == "train.step" for d in dispatches)
    assert not named("step") and not named("data_load")   # no second timing
    buckets = acct.snapshot()
    # the first dispatch holds the compile: exclusive frames would move
    # that to "compile" under a RetraceGuard; without one it is all "step"
    assert buckets["step"] == pytest.approx(seconds(dispatches), abs=1e-9)
    waits = named("data.prefetch_wait")
    assert len(waits) == 5                         # four batches + the end
    assert buckets["data_stall"] == pytest.approx(seconds(waits), abs=1e-9)
    (save,) = named("checkpoint")
    assert buckets["checkpoint_save"] == pytest.approx(seconds([save]),
                                                       abs=1e-9)
    hist = tele.registry.get("dttpu_checkpoint_save_seconds")
    assert hist.count == 1
    assert hist.sum == pytest.approx(seconds([save]), abs=1e-9)


def test_state_snapshot_spans_and_counters_of_a_recurrent_state_model(tracer):
    """A model with recurrent state beside K/V: every snapshot and restore
    is a span inside the tick with slot, depth and bytes; the admit span
    says whether the turn resumed; the decode spans carry what a byte count
    of the step is made from; the counters agree with the spans and render
    in the registry.  With no tracer the same calls are null spans."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.hybrid import hybrid_tiny
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib

    model = hybrid_tiny(conv_state_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    registry = metrics_lib.Registry()
    engine = serve.Engine(model, params, num_slots=2, max_len=128,
                          registry=registry)
    first = _prompt(45, seed=3, vocab=128)
    handle = engine.submit(first, 6)
    while not handle.done:
        engine.step()
    second = np.concatenate([first, np.asarray(handle.tokens, np.int32),
                             _prompt(9, seed=4, vocab=128)])
    handle = engine.submit(second, 4)
    while not handle.done:
        engine.step()

    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    snaps, restores = by_name["serve.state_snapshot"], \
        by_name["serve.state_restore"]
    state_bytes = serve.pages.state_bytes_per_slot(model)
    assert [s.args["at"] for s in snaps] == ["prompt_end", "turn_end"] * 2
    assert [s.args["depth"] for s in snaps] == [45, 50, 60, 63]
    assert len(restores) == 1 and restores[0].args["depth"] == 50
    for s in snaps + restores:
        assert s.args["bytes"] == state_bytes and s.args["slot"] in (0, 1)
        assert spans[s.parent].name in ("serve.register", "serve.deliver",
                                        "serve.admit")
    assert [s.args["resumed"] for s in by_name["serve.admit"]] == \
        [False, True]
    for dispatch, fetch in zip(by_name["serve.decode_dispatch"],
                               by_name["serve.decode_fetch"]):
        assert dispatch.args["cached_tokens"] >= 45
        assert 0 < fetch.args["live_steps"] <= dispatch.args["steps"]
    stats = engine.stats()
    assert (stats.state_snapshots_total, stats.state_restores_total,
            stats.state_snapshots_evicted_total) == (4, 1, 0)
    # a radix node keeps one snapshot, the newest: depths 50, 60 and 63
    # end in the same page, so two are held now (45 and 63)
    assert stats.state_snapshot_bytes == 2 * state_bytes
    assert registry.get("dttpu_serve_state_snapshots_total").value == 4
    assert registry.get("dttpu_serve_state_restores_total").value == 1
    assert registry.get("dttpu_serve_state_snapshot_bytes").value == \
        2 * state_bytes


def test_router_counts_ride_the_fetches_of_an_expert_layer_model(tracer):
    """A model with an expert layer: the device's router counts come out
    with the tokens of the admitting window (``serve.first_token_read``)
    and of the decode program (``serve.decode_fetch``) as span arguments,
    add up to ``Engine.stats()``'s counters and render in the registry; the
    model's scopes name its pieces.  GPT-2 has none of it."""
    from distributed_tensorflow_tpu.models.longcat_flash import (
        longcat_flash_tiny)
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib

    model = longcat_flash_tiny(experts_held=4, expert_offset=2)
    c = model.config
    params = model.init(jax.random.PRNGKey(0))
    registry = metrics_lib.Registry()
    engine = serve.Engine(model, params, num_slots=2, max_len=128,
                          prefill_chunk=8, tick_steps=2, registry=registry)
    handles = [engine.submit(_prompt(21, seed=3, vocab=128), 6),
               engine.submit(_prompt(9, seed=4, vocab=128), 5)]
    engine.drain()
    assert all(h.status == "ok" for h in handles)

    by_name = {}
    for s in tracer.spans():
        by_name.setdefault(s.name, []).append(s)
    reads = by_name["serve.first_token_read"] + by_name["serve.decode_fetch"]
    keys = {"router_picks", "router_picks_identity", "router_picks_held",
            "expert_tokens"}
    assert all(keys <= set(s.args) for s in reads)
    stats = engine.stats()
    consumed = 21 + 9 + sum(len(h.tokens) - 1 for h in handles)
    assert stats.router_picks_total == consumed * c.num_layers * c.moe_topk
    for field in ("router_picks", "router_picks_identity",
                  "router_picks_held"):
        assert sum(s.args[field] for s in reads) == \
            getattr(stats, field + "_total")
    summed = np.sum([s.args["expert_tokens"] for s in reads], axis=0)
    assert summed.shape == (c.num_layers, c.experts_held)
    assert summed.tolist() == [list(r) for r in stats.expert_tokens_total]
    assert stats.router_picks_held_total == int(summed.sum()) > 0
    # held experts x expert layers x steps, and those that got a token
    for fetch in by_name["serve.decode_fetch"]:
        assert fetch.args["experts_held_steps"] == 2 * c.num_layers * 4
        assert 0 <= fetch.args["experts_touched"] <= \
            min(fetch.args["experts_held_steps"],
                fetch.args["live_steps"] * c.num_layers * c.moe_topk)
    assert not any("experts_touched" in s.args
                   for s in by_name["serve.first_token_read"])
    for name in ("router_picks", "router_picks_identity",
                 "router_picks_held"):
        assert registry.get(f"dttpu_serve_{name}_total").value == \
            getattr(stats, name + "_total")
    assert registry.get("dttpu_serve_expert_tokens_total",
                        labels={"layer": "1", "expert": "3"}).value == \
        stats.expert_tokens_total[1][3]

    # the model's scopes, in the decode program's lowered text
    sched = engine.scheduler
    tick = next(t for t in sched.graph_targets() if t.name == "decode_tick")
    text = tick.fn.lower(*tick.args).as_text(debug_info=True)
    for scope in ("mla_q", "mla_kv", "mla_attend", "router", "experts",
                  "identity_experts", "shortcut_join"):
        assert scope in text, scope


def test_a_model_without_experts_reports_no_router_counts(model_params,
                                                          tracer):
    registry = metrics_lib.Registry()
    model, params = model_params
    engine = serve.Engine(model, params, num_slots=2, max_len=64,
                          prefill_chunk=4, tick_steps=2, registry=registry)
    _schedule(engine)
    stats = engine.stats()
    assert (stats.router_picks_total, stats.router_picks_identity_total,
            stats.router_picks_held_total, stats.expert_tokens_total) == \
        (0, 0, 0, ())
    for s in tracer.spans():
        assert not {"router_picks", "experts_touched", "expert_tokens"} \
            & set(s.args), s.name
    assert registry.get("dttpu_serve_router_picks_total") is None
    assert "counters" not in engine.scheduler._cache


def test_router_counts_leave_the_device_inside_the_token_arrays(
        model_params):
    """No fetch, transfer or output of their own: a counting model's admit
    and decode programs return what a K/V-only model's return, the int32
    array of tokens longer by the counters (``SlotScheduler._split_read``
    parts them on the host).  GPT-2's arrays are what they were."""
    from distributed_tensorflow_tpu.models.longcat_flash import (
        longcat_flash_tiny)

    def outputs(model, params):
        engine = serve.Engine(model, params, num_slots=2, max_len=128,
                              prefill_chunk=8, tick_steps=2)
        targets = {t.name: t for t in engine.scheduler.graph_targets()}
        admit = jax.eval_shape(targets["admit"].fn, *targets["admit"].args)
        tick = jax.eval_shape(targets["decode_tick"].fn,
                              *targets["decode_tick"].args)
        return engine.scheduler, admit, tick

    model = longcat_flash_tiny(experts_held=4, expert_offset=2)
    sched, admit, tick = outputs(model, model.init(jax.random.PRNGKey(0)))
    counters = (model.config.num_layers * (4 + 2)) + 2
    assert sum(int(np.prod(shape)) for _, shape in
               sched._counter_shapes) == counters
    _, gpt_admit, gpt_tick = outputs(*model_params)
    assert len(admit) == len(gpt_admit) and len(tick) == len(gpt_tick) == 3
    # the admit program at its largest rung: a token a row (2 slots: 2)
    assert (gpt_admit[0].shape, gpt_tick[1].shape) == ((2,), (2, 2))
    assert (admit[0].shape, tick[1].shape) == ((2 + counters,),
                                               (2 * 2 + counters,))
    assert admit[0].dtype == tick[1].dtype == np.int32
    read = np.arange(4 + counters, dtype=np.int32)
    tokens, parted = sched._split_read(read, (2, 2))
    assert tokens.tolist() == [[0, 1], [2, 3]]
    assert parted["router"].shape == (model.config.num_layers, 6)
    assert parted["router"][0, 0] == 4 and parted["touched"].tolist() == \
        [4 + counters - 2, 4 + counters - 1]
