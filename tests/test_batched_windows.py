"""One batched prefill-window program a tick (ISSUE 37).

Two halves, neither of which shows anything alone:

* the model methods (``GPT`` through the gather read and through the page
  kernel in interpret mode, ``HybridDecoder``, ``LongcatFlash``,
  ``SambaY``): ``n``
  windows of different requests at different ``pos`` / ``valid`` in ONE
  ``decode_window_paged`` call equal the same windows called one by one —
  logits of the rows, every written pool cell, every state row, the
  device's counters — with padding rows in the batch, and what the batch
  does not own unchanged; the batch-1 call form still works;
* the scheduler: with 1..``num_slots`` requests prefilling at once the
  greedy outputs are ``generate()``'s, the window counts are what they
  were, a request cancelled between collection and dispatch is dropped from
  its group, and nothing compiles after construction whatever group sizes
  arrive.

CPU, toy sizes, float32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.models.hybrid import hybrid_tiny
from distributed_tensorflow_tpu.models.longcat_flash import longcat_flash_tiny
from distributed_tensorflow_tpu.models.sambay import sambay_tiny
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.serve import pages as pages_lib

W, PG, PPS, SLOTS = 8, 8, 6, 5           # window, page, pages a row, slots
KINDS = ["gpt_gather", "gpt_kernel", "hybrid", "longcat", "sambay"]
# (first column, real tokens) of the batch's windows: a sequence's start, a
# start that is no page or window boundary, a short last window; rows 3 and
# 4 of the batch are padding
WINDOWS = [(0, W), (11, W), (16, 3)]


def _ids(seed, *shape, vocab=128):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                         vocab), np.int32)


@pytest.fixture(scope="module")
def models():
    built = {}
    for kind, make in (("gpt", lambda: gpt_tiny(vocab_size=128,
                                                 max_position=PG * PPS,
                                                 dropout_rate=0.0)),
                       ("hybrid", lambda: hybrid_tiny(
                           max_position=PG * PPS)),
                       ("longcat", lambda: longcat_flash_tiny(
                           max_position=PG * PPS, experts_held=4,
                           expert_offset=2)),
                       ("sambay", lambda: sambay_tiny(
                           max_position=PG * PPS))):
        model = make()
        built[kind] = (model, model.init(jax.random.PRNGKey(0)))
    return built


class _Case:
    """One model's paged cache with three requests part-way through their
    prompts, and the window call in both forms."""

    def __init__(self, kind, models):
        self.kind = kind
        self.model, self.params = models[kind.split("_")[0]]
        self.use_kernel = kind == "gpt_kernel"
        # request r owns table row r: pages 1 + r * PPS .. (r + 1) * PPS
        self.tables = np.arange(1, 1 + SLOTS * PPS,
                                dtype=np.int32).reshape(SLOTS, PPS)
        self.cache = pages_lib.init_paged_cache(
            self.model, SLOTS, 1 + SLOTS * PPS, PG)
        self.tokens = _ids(7, len(WINDOWS), PG * PPS)
        # the history before each request's window, prefilled one by one
        for r, (pos, _) in enumerate(WINDOWS):
            for start in range(0, pos, W):
                real = min(W, pos - start)
                self.cache = self.call(
                    self.cache, self.window_tokens(r, start, real)[None],
                    self.tables[r], np.int32(start), np.int32(real),
                    np.int32(r), "none")[1]

    def window_tokens(self, r, pos, real):
        toks = np.full((W,), 5, np.int32)            # pads are not zeros
        toks[:real] = self.tokens[r, pos:pos + real]
        return toks

    def call(self, cache, toks, rows, pos, valid, slot, head):
        """``decode_window_paged`` on ``cache`` -> (logits, new cache)."""
        extra = {}
        if "state" in cache:
            extra.update(state=cache["state"], slot=slot)
        if "counters" in cache:
            extra.update(counters=cache["counters"])
        if self.use_kernel:
            extra.update(use_kernel=True)
        logits, *new = self.model.decode_window_paged(
            self.params, cache["kv"], toks, rows, pos, head=head,
            valid=valid, **extra)
        held = [n for n in ("state", "counters") if n in cache]
        return logits, dict(cache, **dict(zip(["kv"] + held, new)))

    def batch(self, order, rows):
        """The arguments of one call that holds ``order``'s windows in that
        order, padded to ``rows`` rows."""
        toks = np.full((rows, W), 9, np.int32)
        tables = np.zeros((rows, PPS), np.int32)
        pos, valid = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
        slot = np.full(rows, 3, np.int32)     # a padding row names a slot:
        for i, r in enumerate(order):         # it must not write there
            pos[i], valid[i] = WINDOWS[r]
            toks[i] = self.window_tokens(r, *WINDOWS[r])
            tables[i], slot[i] = self.tables[r], r
        return toks, tables, pos, valid, slot


def _leaves(cache):
    """The cache by leaf, the trash page (0) of every pool leaf left out."""
    out = {f"kv/{k}": np.asarray(v)[:, 1:] for k, v in cache["kv"].items()}
    for group in ("state", "counters"):
        for k, v in cache.get(group, {}).items():
            out[f"{group}/{k}"] = np.asarray(v)
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_a_batch_of_windows_equals_the_windows_one_by_one(kind, models):
    """Three requests' windows and two padding rows in one call, rows in an
    order that is not the slots': the logits at each row's last real
    position, every pool cell, every state row and the counters are those
    of the three windows called one by one."""
    case = _Case(kind, models)
    order = [2, 0, 1]
    want_logits, one_by_one = {}, case.cache
    for r in order:
        pos, real = WINDOWS[r]
        logits, one_by_one = case.call(
            one_by_one, case.window_tokens(r, pos, real)[None],
            case.tables[r], np.int32(pos), np.int32(real), np.int32(r),
            "all")
        want_logits[r] = np.asarray(logits[0, real - 1])
    toks, tables, pos, valid, slot = case.batch(order, 5)
    logits, batched = case.call(case.cache, toks, tables, pos, valid, slot,
                                "last")
    assert logits.shape == (5, 128)
    for i, r in enumerate(order):
        np.testing.assert_allclose(np.asarray(logits[i]), want_logits[r],
                                   atol=2e-5, err_msg=f"row {i}")
    got, want = _leaves(batched), _leaves(one_by_one)
    before = _leaves(case.cache)
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                   err_msg=name)
        if name.startswith("counters/"):
            assert (got[name] == want[name]).all(), name
    # what the batch does not own: the other requests' pages and state rows
    unowned = case.tables[3:].reshape(-1) - 1        # trash page left out
    for name in before:
        if name.startswith("kv/"):
            assert (got[name][:, unowned] == before[name][:, unowned]).all()
        elif name.startswith("state/"):
            assert (got[name][:, 3:] == before[name][:, 3:]).all(), name
            assert not (got[name][:, :3] == before[name][:, :3]).all()


@pytest.mark.parametrize("kind", KINDS)
def test_the_batch_1_call_form_is_the_n_1_case(kind, models):
    """A rank-1 page row with scalar ``pos`` / ``valid`` / ``slot`` (how the
    benchmark's probes call it) gives what the ``[1, ...]`` form gives, and
    ``head="all"`` at the last real position what ``head="last"`` gives."""
    case = _Case(kind, models)
    pos, real = WINDOWS[1]
    toks = case.window_tokens(1, pos, real)[None]
    scalar_all, scalar = case.call(
        case.cache, toks, case.tables[1], np.int32(pos), np.int32(real),
        np.int32(1), "all")
    ranked_last, ranked = case.call(
        case.cache, toks, case.tables[1][None], np.asarray([pos], np.int32),
        np.asarray([real], np.int32), np.asarray([1], np.int32), "last")
    assert scalar_all.shape == (1, W, 128) and ranked_last.shape == (1, 128)
    np.testing.assert_allclose(np.asarray(scalar_all[0, real - 1]),
                               np.asarray(ranked_last[0]), atol=2e-5)
    got, want = _leaves(ranked), _leaves(scalar)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                   err_msg=name)
    assert case.call(case.cache, toks, case.tables[1], np.int32(pos),
                     np.int32(real), np.int32(1), "none")[0] is None


@pytest.mark.parametrize("kind", KINDS)
def test_a_program_of_padding_rows_writes_the_trash_page_alone(kind, models):
    """What the scheduler dispatches at construction to compile a rung:
    every row padding (``valid`` 0), each naming a live slot and a live
    request's pages.  No pool cell but the trash page's, no state row, no
    counter moves."""
    case = _Case(kind, models)
    rows = 4
    toks = _ids(3, rows, W)
    tables = np.tile(case.tables[1], (rows, 1))
    _, after = case.call(
        case.cache, toks, tables, np.full(rows, 11, np.int32),
        np.zeros(rows, np.int32), np.arange(rows, dtype=np.int32) % 3,
        "last")
    got, before = _leaves(after), _leaves(case.cache)
    for name in before:
        assert (got[name] == before[name]).all(), name


# ------------------------------------------------------------ the scheduler

def _generate(model, params, prompt, n):
    out = model.generate(params, jnp.asarray(prompt)[None], n,
                         temperature=0.0, max_len=PG * PPS)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


PROMPTS = [23, 9, 17, 30, 12]            # tokens: 3, 2, 3, 4, 2 windows of 8


@pytest.fixture
def tracer():
    reqtrace.reset()
    with trace_lib.activated(trace_lib.Tracer()) as t:
        yield t
    reqtrace.reset()


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5])
def test_k_requests_prefilling_at_once_emit_what_generate_emits(k, models,
                                                                tracer):
    """``k`` of 5 slots prefill at once: every request's greedy tokens are
    ``generate()``'s, it ran the windows and took the ticks its prompt
    takes alone (a request still advances one window a tick), the windows'
    count is the prompts', and ``k`` windows went out in one program."""
    model, params = models["gpt"]
    engine = serve.Engine(model, params, num_slots=SLOTS, max_len=PG * PPS,
                          prefill_chunk=W, tick_steps=2, page_size=PG,
                          prefix_cache=False)
    assert engine.scheduler._rungs == (1, 4, 5)
    prompts = [_ids(40 + i, n) for i, n in enumerate(PROMPTS[:k])]
    handles = [engine.submit(p, 6) for p in prompts]
    engine.drain()
    for handle, prompt in zip(handles, prompts):
        assert handle.tokens == _generate(model, params, prompt, 6)
    windows = [-(-n // W) for n in PROMPTS[:k]]
    stats = engine.stats()
    assert stats.prefill_windows_total == sum(windows)
    counts = {r["trace_id"]: r["counts"] for r in reqtrace.completed()}
    for handle, n in zip(handles, windows):
        c = counts[handle._req.trace_id]
        assert (c["prefill_windows"], c["prefill_ticks"]) == (n, n)
    dispatches = [s for s in tracer.spans()
                  if s.name == "serve.prefill_dispatch"]
    assert len(dispatches) == stats.prefill_dispatches_total
    assert sum(s.args["real"] for s in dispatches) == sum(windows)
    assert sum(s.args["rows"] - s.args["real"] for s in dispatches) == \
        stats.prefill_rows_padded_total
    # the first tick opens with every request's first window, in one program
    assert dispatches[0].args["real"] == k
    assert dispatches[0].args["rows"] == min(r for r in (1, 4, 5)
                                             if r >= k)
    assert all(s.args["rows"] in (1, 4, 5) for s in dispatches)
    if k > 1:
        assert stats.prefill_dispatches_total < stats.prefill_windows_total


@pytest.mark.parametrize("kind", ["hybrid", "longcat"])
def test_requests_prefilled_together_emit_what_they_emit_alone(kind, models):
    """The models with recurrent state and with an expert layer: three
    requests prefilled at once emit the tokens each emits on an engine of
    its own, and the device's counters count the same picks."""
    model, params = models[kind]

    def engine():
        return serve.Engine(model, params, num_slots=4, max_len=PG * PPS,
                            prefill_chunk=W, tick_steps=2, page_size=PG,
                            prefix_cache=False)

    prompts = [_ids(60 + i, n) for i, n in enumerate(PROMPTS[:3])]
    alone, picks = [], 0
    for prompt in prompts:
        single = engine()
        handle = single.submit(prompt, 5)
        single.drain()
        alone.append(handle.tokens)
        picks += single.stats().router_picks_total
    together = engine()
    handles = [together.submit(p, 5) for p in prompts]
    together.drain()
    assert [h.tokens for h in handles] == alone
    stats = together.stats()
    assert stats.router_picks_total == picks
    assert (picks > 0) == (kind == "longcat")
    assert stats.prefill_dispatches_total < stats.prefill_windows_total == 8


def test_a_request_cancelled_after_collection_is_dropped_from_its_group(
        models, tracer):
    """Three prefills collected for one program, one of them cancelled
    before the program is dispatched: the program holds the other two (its
    group is not padded out with the cancelled request's window), and they
    finish with ``generate()``'s tokens."""
    model, params = models["gpt"]
    engine = serve.Engine(model, params, num_slots=4, max_len=PG * PPS,
                          prefill_chunk=W, tick_steps=2, page_size=PG,
                          prefix_cache=False)
    sched = engine.scheduler
    prompts = [_ids(80 + i, n) for i, n in enumerate(PROMPTS[:3])]
    handles = [engine.submit(p, 4) for p in prompts]
    with sched._pump_lock:
        for _ in prompts:
            assert sched._admit(sched._queue.popleft())
        group = list(sched._prefills)
        assert len(group) == 3
        assert sched.cancel(handles[1]._req)
        firsts = []
        ran = sched._advance_group(group, firsts)
    assert ran == [handles[0]._req, handles[2]._req] and not firsts
    (dispatch,) = [s for s in tracer.spans()
                   if s.name == "serve.prefill_dispatch"]
    assert (dispatch.args["real"], dispatch.args["rows"]) == (2, 4)
    assert engine.stats().prefill_windows_total == 2
    engine.drain()
    assert handles[1].status == "cancelled" and not handles[1].tokens
    for i in (0, 2):
        assert handles[i].tokens == _generate(model, params, prompts[i], 4)


@pytest.mark.retrace_guard(budget=1, enforce_donation=True)
def test_no_compile_happens_after_construction_whatever_groups_arrive(
        models):
    """Every window program of the ladder is compiled as the scheduler is
    built; waves of 1, 2, 3, 4 and 5 requests at once then meet every rung
    and compile nothing: under the sanitizer (budget 1: a second trace of
    any jitted callable fails the test) and by the backend's own count of
    compiles, taken from the first tick on."""
    from jax import monitoring

    model, params = models["gpt"]
    engine = serve.Engine(model, params, num_slots=SLOTS, max_len=PG * PPS,
                          prefill_chunk=W, tick_steps=2, page_size=PG,
                          prefix_cache=False)
    sched = engine.scheduler
    for fns in (sched._win_mid, sched._last_admit):
        assert sorted(fns) == [1, 4, 5]
        assert all(fn._cache_size() == 1 for fn in fns.values())
    warm = engine.submit(_ids(1, 3), 3)       # the decode program, once
    engine.drain()
    assert warm.status == "ok"
    waves = [[_ids(90 + wave + i, n) for i, n in enumerate(PROMPTS[:wave])]
             for wave in (1, 2, 3, 4, 5)]
    compiles = []

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        seen = set()
        with trace_lib.activated(trace_lib.Tracer()) as tracer:
            for prompts in waves:
                handles = [engine.submit(p, 3) for p in prompts]
                engine.drain()
                assert all(h.status == "ok" for h in handles)
            seen = {s.args["rows"] for s in tracer.spans()
                    if s.name == "serve.prefill_dispatch"}
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    assert seen == {1, 4, 5}
    assert compiles == []
    for fns in (sched._win_mid, sched._last_admit):
        assert all(fn._cache_size() == 1 for fn in fns.values())
