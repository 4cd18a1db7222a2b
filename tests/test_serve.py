"""Continuous-batching engine tests: slot admission exactness, stale-KV
safety, retrace-free scheduling, metrics.

The contracts pinned here (docs/SERVING.md):
  * single request through the engine == greedy ``GPT.generate``
    token-for-token (chunked prefill included),
  * a reused slot never reads the previous occupant's K/V,
  * admission/retirement never recompile anything (RetraceGuard).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.obs import metrics as metrics_lib


def _model_params(seed=0, **kw):
    model = gpt_tiny(dropout_rate=0.0, **kw)
    return model, model.init(jax.random.PRNGKey(seed))


def _prompt(plen, seed=1, vocab=512):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (plen,), 0, vocab), np.int32)


def _generate_tokens(model, params, prompt, new, max_len, **kw):
    out = model.generate(params, jnp.asarray(prompt[None]),
                         max_new_tokens=new, max_len=max_len, **kw)
    return np.asarray(out)[0, prompt.size:].tolist()


# ---------------------------------------------------------------------------
# exactness: engine vs generate


def test_single_request_matches_generate():
    """One request in flight: streamed tokens == generate() greedy,
    token-for-token — with a single-window AND a chunked (multi-window)
    prefill."""
    model, params = _model_params()
    prompt = _prompt(7)
    want = _generate_tokens(model, params, prompt, 9, 32)
    for chunk in (8, 3):           # one window; 3 windows (ragged last)
        eng = serve.Engine(model, params, num_slots=3, max_len=32,
                           prefill_chunk=chunk, tick_steps=2)
        h = eng.submit(prompt, max_new_tokens=9)
        eng.drain()
        assert h.done and h.tokens == want, (chunk, h.tokens, want)
        assert h.ttft_s is not None and h.ttft_s > 0


def test_single_request_eos_matches_generate():
    """EOS retirement: the engine stops at the token where generate()
    starts padding, and delivers the EOS itself."""
    model, params = _model_params()
    prompt = _prompt(6, seed=3)
    plain = _generate_tokens(model, params, prompt, 10, 32)
    eos = plain[2]                  # force an early stop on a real token
    want = plain[:plain.index(eos) + 1]
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=8, tick_steps=3, eos_id=eos)
    h = eng.submit(prompt, max_new_tokens=10)
    eng.drain()
    assert h.tokens == want
    gen = _generate_tokens(model, params, prompt, 10, 32, eos_id=eos)
    assert gen[:len(want)] == want          # same prefix, then pad
    assert all(t == eos for t in gen[len(want):])


def test_concurrent_unequal_requests_match_solo():
    """Unequal-length requests decoding CONCURRENTLY in slots each equal
    their own solo generate — ragged batching without any padding."""
    model, params = _model_params()
    prompts = [_prompt(7, seed=1), _prompt(5, seed=2), _prompt(3, seed=4)]
    budgets = [9, 12, 6]
    wants = [_generate_tokens(model, params, p, n, 32)
             for p, n in zip(prompts, budgets)]
    eng = serve.Engine(model, params, num_slots=3, max_len=32,
                       prefill_chunk=4, tick_steps=3)
    handles = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.drain()
    for h, want in zip(handles, wants):
        assert h.tokens == want


def test_rope_gqa_engine_matches_generate():
    """The slot step's per-row positions drive RoPE too (Llama-shaped
    recipe: rotary positions + grouped-query cache)."""
    model, params = _model_params(position_embedding="rope", num_heads=4,
                                  hidden_size=128, num_kv_heads=2)
    prompt = _prompt(6, seed=5)
    want = _generate_tokens(model, params, prompt, 8, 32)
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2)
    h = eng.submit(prompt, 8)
    eng.drain()
    assert h.tokens == want


# ---------------------------------------------------------------------------
# isolation: admission / stale KV


def test_retire_then_reuse_never_reads_stale_kv():
    """Three requests through ONE slot: each newcomer's tokens equal its
    solo generate even though the slot's cache still holds the previous
    occupant's K/V beyond the new validity window — including a reuse
    where the new request is SHORTER than the leftovers."""
    model, params = _model_params()
    long_p, short_p = _prompt(12, seed=11), _prompt(3, seed=12)
    eng = serve.Engine(model, params, num_slots=1, max_len=40,
                       prefill_chunk=4, tick_steps=4)
    h1 = eng.submit(long_p, 20)     # fills columns 0..31
    h2 = eng.submit(short_p, 5)     # reuse: much shorter
    h3 = eng.submit(long_p, 20)     # reuse again with the long one
    eng.drain()
    assert h1.tokens == _generate_tokens(model, params, long_p, 20, 40)
    assert h2.tokens == _generate_tokens(model, params, short_p, 5, 40)
    assert h3.tokens == h1.tokens


# ---------------------------------------------------------------------------
# scheduling behavior


@pytest.mark.retrace_guard(budget=1, enforce_donation=True)
def test_admission_and_retirement_never_recompile():
    """Every engine executable traces ONCE across a mixed workload of
    admissions, chunked prefills, EOS/budget retirements, and slot
    reuse (budget=1: the second trace of anything fails the test).
    Donation enforcement doubles as a use-after-donate check on the
    scheduler's buffer management."""
    model, params = _model_params()
    rng = np.random.default_rng(0)
    eng = serve.Engine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, tick_steps=3, eos_id=7)
    handles = []
    for i in range(7):
        plen = int(rng.integers(2, 11))
        prompt = rng.integers(0, 512, plen).astype(np.int32)
        handles.append(eng.submit(prompt, int(rng.integers(1, 12))))
        eng.step()
    eng.drain()
    assert all(h.done for h in handles)
    assert all(len(h.tokens) >= 1 for h in handles)


def test_streaming_callbacks_deliver_everything_in_order():
    model, params = _model_params()
    prompt = _prompt(5, seed=2)
    got = []
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=8, tick_steps=2)
    h = eng.submit(prompt, 9, on_token=got.extend)
    eng.drain()
    assert got == h.tokens
    assert h.result() == h.tokens        # result() on a done handle


def test_sampled_mode_runs_and_stays_in_vocab():
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=8, tick_steps=2, temperature=0.9,
                       top_p=0.95, rng=jax.random.PRNGKey(5))
    h1 = eng.submit(_prompt(4, seed=1), 8)
    h2 = eng.submit(_prompt(6, seed=2), 8)
    eng.drain()
    for h in (h1, h2):
        assert len(h.tokens) == 8
        assert all(0 <= t < 512 for t in h.tokens)


def test_submit_validation():
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=16,
                       prefill_chunk=4)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompt(4), 0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(_prompt(4), 13)           # 4 + 13 > 16
    eng.submit(_prompt(15), 1)               # chunk-padded 16 fits
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(_prompt(17), 1)           # chunk-padded 20 > 16
    with pytest.raises(ValueError, match="num_slots"):
        serve.Engine(model, params, num_slots=0, max_len=16)


@pytest.mark.parametrize("paged", [True, False])
def test_storage_layout_is_not_an_option(paged):
    """The page pool is the one K/V storage: ``paged=`` is an unknown
    keyword like any other, with no shim for either value."""
    model, params = _model_params()
    with pytest.raises(TypeError, match="paged"):
        serve.Engine(model, params, num_slots=2, max_len=16, paged=paged)


def test_inflight_prefill_is_a_named_record_compared_by_identity():
    """``st in self._prefills`` / ``.remove(st)`` must mean THIS prefill:
    two requests with equal prompts are two records."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2,
                       registry=metrics_lib.Registry())
    h1, h2 = eng.submit(_prompt(9), 3), eng.submit(_prompt(9), 3)
    eng.step()                                   # two prefills begun
    a, b = eng.scheduler._prefills
    assert (a.req.rid, b.req.rid) == (h1.rid, h2.rid)
    assert a.next == b.next == 1 and a.lease is not b.lease
    assert len(a.windows) == 3 and a.plan[1][:2] == (4, 4)
    assert a != b and eng.scheduler._prefills.index(b) == 1
    eng.drain()
    assert h1.tokens == h2.tokens


def test_engine_metrics_land_in_registry():
    """The obs wiring: queue/active gauges move, TTFT and per-request
    histograms observe once per request, token/request counters add up —
    all scrapable through the standard exposition path."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=8, tick_steps=2, registry=reg)
    n_tok = [6, 4, 9]
    handles = [eng.submit(_prompt(4 + i, seed=i), n)
               for i, n in enumerate(n_tok)]
    eng.drain()
    assert all(h.done for h in handles)
    assert reg.get("dttpu_serve_requests_total").value == 3
    assert reg.get("dttpu_serve_tokens_total").value == sum(n_tok)
    assert reg.get("dttpu_serve_ttft_seconds").count == 3
    assert reg.get("dttpu_serve_request_decode_seconds").count == 3
    assert reg.get("dttpu_serve_queue_depth").value == 0
    assert reg.get("dttpu_serve_active_slots").value == 0
    doc = metrics_lib.parse_exposition(reg.expose())
    assert doc["dttpu_serve_ttft_seconds"]["type"] == "histogram"
    assert doc["dttpu_serve_tokens_total"]["type"] == "counter"


def test_generate_batch_convenience_and_queueing():
    """More requests than slots: the queue drains through slot reuse and
    every output matches its solo generate."""
    model, params = _model_params()
    prompts = [_prompt(3 + i, seed=20 + i) for i in range(6)]
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=3,
                       default_max_new_tokens=5)
    outs = eng.generate_batch(prompts)
    for p, got in zip(prompts, outs):
        assert got == _generate_tokens(model, params, p, 5, 32)


# ---------------------------------------------------------------------------
# the weights in the formats the decode program reads


def _serve_tokens(model, params, plain_tick=False):
    """Three concurrent requests through an engine; with ``plain_tick``
    its decode tick is the same function jitted plainly, each weight read
    in the format it came in."""
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=3, max_len=32,
                       prefill_chunk=4, tick_steps=3, registry=reg)
    if plain_tick:
        eng.scheduler._tick = jax.jit(
            eng.scheduler._tick._jitted.__wrapped__,
            donate_argnums=(1, 3, 4, 5, 6))
    handles = [eng.submit(_prompt(7, seed=1), 9),
               eng.submit(_prompt(5, seed=2), 12),
               eng.submit(_prompt(3, seed=4), 6)]
    eng.drain()
    return eng, reg, [h.tokens for h in handles]


def test_decode_program_as_compiled_keeps_the_callers_weights_on_the_cpu():
    """The decode tick is compiled once, the weights' formats left to the
    compiler, and dispatched as that executable.  On the CPU the compiler
    keeps every weight in the format it came in: nothing is moved
    (``weights_relaid_bytes`` and its gauge read 0), the scheduler holds
    the caller's own arrays, and the tokens are bit for bit those of the
    same program jitted plainly."""
    model, params = _model_params()
    eng, reg, got = _serve_tokens(model, params)
    _, _, want = _serve_tokens(model, params, plain_tick=True)
    assert got == want
    assert eng.stats().weights_relaid_bytes == 0
    assert reg.get("dttpu_serve_weights_relaid_bytes").value == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.scheduler.params),
                                      jax.tree.leaves(params)))
    assert eng.scheduler._tick._cache_size() == 1


def test_weight_in_another_format_is_placed_once_callers_tree_untouched(
        caplog):
    """A weight that comes in another format than the one the decode
    program reads (here the word embedding column-major; on a TPU, the
    formats the chip gives GPT-2-XL's FFN out-projection and embedding) is
    put in the program's format ONCE, at construction, in a tree of the
    scheduler's own: counted by ``weights_relaid_bytes`` and its gauge,
    named on the log, the caller's tree left as it was (the same arrays,
    in their format, not donated), every other leaf shared, the tokens
    those of ``generate``, and no program compiled twice."""
    import logging
    from jax.experimental.layout import Format, Layout
    model, params = _model_params()
    word = params["embeddings"]["word"]
    col = jax.device_put(word, Format(Layout(major_to_minor=(1, 0)),
                                      word.sharding))
    given = dict(params, embeddings=dict(params["embeddings"], word=col))
    with caplog.at_level(logging.INFO,
                         logger="distributed_tensorflow_tpu.serve.scheduler"):
        eng, reg, got = _serve_tokens(model, given)
    prompts = [(_prompt(7, seed=1), 9), (_prompt(5, seed=2), 12),
               (_prompt(3, seed=4), 6)]
    assert got == [_generate_tokens(model, params, p, n, 32)
                   for p, n in prompts]
    sched = eng.scheduler
    assert sched._relaid == [("['embeddings']['word']", tuple(col.shape),
                              (1, 0), (0, 1), col.nbytes)]
    assert eng.stats().weights_relaid_bytes == col.nbytes
    assert reg.get("dttpu_serve_weights_relaid_bytes").value == col.nbytes
    assert "['embeddings']['word'] [512, 128] (1, 0) -> (0, 1)" in caplog.text
    placed = sched.params["embeddings"]["word"]
    assert placed is not col and placed.format.layout.major_to_minor == (0, 1)
    assert given["embeddings"]["word"] is col and not col.is_deleted()
    assert col.format.layout.major_to_minor == (1, 0)
    np.testing.assert_array_equal(np.asarray(col), np.asarray(word))
    moved = {id(col)}
    for a, b in zip(jax.tree.leaves(sched.params), jax.tree.leaves(given)):
        assert (a is b) != (id(b) in moved)
    # as many programs as an engine over the weights as they came
    plain = _serve_tokens(model, params)[0].scheduler

    def programs(s):
        return [s._tick._cache_size()] + [
            fn._cache_size() for fns in (s._win_mid, s._last_admit)
            for fn in fns.values()]
    assert programs(sched) == programs(plain) and programs(sched)[0] == 1


# one process of two sharing a persistent compile cache: a weight copied
# into a format other than the device's own, and a program compiled for it
_RELAY_RUN = """
import jax, numpy as np
from jax.experimental.layout import Format, Layout
from distributed_tensorflow_tpu.serve.scheduler import _relay
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
x = np.arange(24, dtype=np.float32).reshape(4, 3, 2)
one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
(y,) = _relay([jax.device_put(x, one)], [Format(Layout((1, 0, 2)), one)])
doubled = jax.jit(lambda a: a * 2)(y)
print(y.format.layout.major_to_minor, np.array_equal(np.asarray(y), x),
      np.array_equal(np.asarray(doubled), 2 * x))
"""


def test_weights_placed_alike_when_the_compile_cache_serves_a_second_run(
        tmp_path):
    """The copy that puts a weight in the decode program's format stays out
    of the persistent compile cache: an executable read back from it gives
    its outputs the default format, so a second run's copy would be
    labelled in a format it is not in (on the chip, every window program
    then refused the weights).  Two runs over one cache: both hold the
    copy in the format asked for, with the caller's values, and a program
    compiled for it (served from the cache the second time) reads it
    right."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = [subprocess.run([sys.executable, "-c", _RELAY_RUN], env=env,
                           capture_output=True, text=True, check=True,
                           timeout=120).stdout.splitlines()[-1]
            for _ in range(2)]
    assert runs == ["(1, 0, 2) True True"] * 2
    cached = os.listdir(tmp_path / "cache")
    assert any(name.startswith("jit__lambda") for name in cached), cached
    assert not any(name.startswith("jit_relaid") for name in cached), cached


# ---------------------------------------------------------------------------
# graceful degradation: backpressure, deadlines, failure isolation
# (docs/RESILIENCE.md)


def test_queue_full_rejects_with_metric():
    """Admission control: the queue holds max_queue_depth requests, the
    next submit is rejected loudly (and counted), and a later submit is
    accepted again once the queue drains."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=1, max_len=32,
                       prefill_chunk=8, tick_steps=2, registry=reg,
                       max_queue_depth=2)
    handles = [eng.submit(_prompt(4, seed=i), 4) for i in range(2)]
    with pytest.raises(serve.QueueFullError):
        eng.submit(_prompt(4, seed=9), 4)
    assert reg.get("dttpu_serve_rejected_total").value == 1
    assert reg.get("dttpu_serve_requests_total").value == 2
    eng.drain()
    assert all(h.status == "ok" for h in handles)
    h = eng.submit(_prompt(4, seed=9), 4)      # accepted after drain
    eng.drain()
    assert h.status == "ok"


def test_deadline_expires_queued_and_active_requests():
    """A queued request past its deadline never prefills; an ACTIVE one
    is retired mid-decode with partial tokens — both carry status
    deadline_exceeded + the metric, and neither decodes forever."""
    import time as time_mod
    model, params = _model_params()
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=1, max_len=64,
                       prefill_chunk=4, tick_steps=1, registry=reg)
    # queued expiry: one slot is busy, the second request's deadline
    # passes while it waits
    h_busy = eng.submit(_prompt(4, seed=1), 8)
    h_q = eng.submit(_prompt(4, seed=2), 8, deadline_s=0.0)
    time_mod.sleep(0.005)
    eng.drain()
    assert h_busy.status == "ok" and len(h_busy.tokens) == 8
    assert h_q.status == "deadline_exceeded" and h_q.tokens == []
    # active expiry: admit, decode a few ticks, then let the deadline hit
    h_a = eng.submit(_prompt(4, seed=3), 60, deadline_s=0.05)
    while not h_a.tokens:
        eng.step()
    deadline = time_mod.perf_counter() + 2.0
    while not h_a.done and time_mod.perf_counter() < deadline:
        eng.step()
        time_mod.sleep(0.005)
    assert h_a.status == "deadline_exceeded"
    assert 0 < len(h_a.tokens) < 60
    assert reg.get("dttpu_serve_deadline_expired_total").value == 2
    assert not eng.busy


def test_deadline_expiry_dumps_victim_span_tree():
    """Tail-latency forensics at the scheduler: a traced request that
    blows its deadline lands in reqtrace.forensics_log() with reason
    ``deadline_expired`` and its span tree intact — queued-only for a
    never-admitted victim, so the dump itself shows WHERE the budget
    went."""
    import time as time_mod
    from distributed_tensorflow_tpu.obs import reqtrace
    from distributed_tensorflow_tpu.obs import trace as obs_trace
    model, params = _model_params()
    reqtrace.reset()
    tracer = obs_trace.activate(obs_trace.Tracer(enabled=True))
    try:
        eng = serve.Engine(model, params, num_slots=1, max_len=64,
                           prefill_chunk=4, tick_steps=1,
                           registry=metrics_lib.Registry())
        h_busy = eng.submit(_prompt(4, seed=1), 8)
        h_q = eng.submit(_prompt(4, seed=2), 8, deadline_s=0.0)
        time_mod.sleep(0.005)
        eng.drain()
        assert h_busy.status == "ok"
        assert h_q.status == "deadline_exceeded"
        victims = [d for d in reqtrace.forensics_log()
                   if d["reason"] == "deadline_expired"]
        assert len(victims) == 1
        (root,) = victims[0]["spans"]
        assert root["name"] == "request"
        # the victim never left the queue — the dump says so
        assert [c["name"] for c in root["children"]] == ["queued"]
        # and the lane itself retired with the honest status
        assert reqtrace.lookup(
            victims[0]["trace_id"])["status"] == "deadline_exceeded"
    finally:
        obs_trace.deactivate(tracer)
        reqtrace.reset()


def test_poisoned_request_fails_alone_survivors_bit_exact():
    """THE serve acceptance contract: one request whose callback raises
    mid-decode fails ONLY its own handle; the scheduler keeps ticking
    and every surviving request's greedy output stays token-identical
    to generate()."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=3, max_len=32,
                       prefill_chunk=4, tick_steps=2, registry=reg)
    prompts = [_prompt(5, seed=1), _prompt(4, seed=2), _prompt(6, seed=3)]
    wants = [_generate_tokens(model, params, p, 8, 32) for p in prompts]

    poison_after = [3]

    def bad_callback(toks):
        poison_after[0] -= len(toks)
        if poison_after[0] <= 0:
            raise RuntimeError("poisoned request payload")

    h0 = eng.submit(prompts[0], 8)
    h1 = eng.submit(prompts[1], 8, on_token=bad_callback)
    h2 = eng.submit(prompts[2], 8)
    eng.drain()
    assert h1.status == "failed"
    assert isinstance(h1.error, RuntimeError)
    assert h0.status == "ok" and h0.tokens == wants[0]
    assert h2.status == "ok" and h2.tokens == wants[2]
    assert reg.get("dttpu_serve_failed_total").value == 1
    # the freed slot is reusable and still exact
    h3 = eng.submit(prompts[1], 8)
    eng.drain()
    assert h3.tokens == wants[1]


def test_injected_decode_fault_fails_exact_request():
    """resilience.faults fail_decode: rid-targeted injection fails that
    handle with InjectedFault; everyone else matches generate()."""
    from distributed_tensorflow_tpu.resilience import InjectedFault, faults
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2,
                       registry=metrics_lib.Registry())
    prompts = [_prompt(5, seed=1), _prompt(4, seed=2)]
    wants = [_generate_tokens(model, params, p, 6, 32) for p in prompts]
    plan = faults.FaultPlan([{"kind": "fail_decode", "at": 1}],
                            registry=metrics_lib.Registry())
    with faults.activated(plan):
        h0 = eng.submit(prompts[0], 6)
        h1 = eng.submit(prompts[1], 6)
        eng.drain()
    assert h0.status == "ok" and h0.tokens == wants[0]
    assert h1.status == "failed" and isinstance(h1.error, InjectedFault)
    assert plan.log == [{"kind": "fail_decode", "at": 1, "rid": 1}]


def test_generate_batch_failed_submit_cancels_earlier_handles():
    """Satellite regression: a mid-list submit failure must not leave
    the already-submitted handles permanently pending — they are
    cancelled before the error propagates."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=16,
                       prefill_chunk=4, tick_steps=2,
                       registry=metrics_lib.Registry())
    prompts = [_prompt(4, seed=1), _prompt(4, seed=2),
               _prompt(17, seed=3)]          # third fails validation
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.generate_batch(prompts, max_new_tokens=4)
    # nothing left in flight, nothing pending forever
    assert not eng.busy
    assert eng.scheduler.queued == 0
    # the engine still works afterwards
    outs = eng.generate_batch(prompts[:2], max_new_tokens=4)
    assert outs == [_generate_tokens(model, params, p, 4, 16)
                    for p in prompts[:2]]


def test_drain_timeout_exports_stragglers_lossless():
    """The old ``drain(timeout_s=) -> False`` left requests stranded in
    limbo; now a timed-out drain EXPORTS the stragglers (DrainResult is
    falsy, carries their snapshots, the engine ends idle) and importing
    a snapshot resumes bit-identically to an unmigrated run."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=1, max_len=64,
                       prefill_chunk=4, tick_steps=1,
                       registry=metrics_lib.Registry())
    want = _generate_tokens(model, params, _prompt(4, seed=1), 40, 64)
    h = eng.submit(_prompt(4, seed=1), 40)
    res = eng.drain(timeout_s=0.0)              # budget hit immediately
    assert not res                              # falsy: not completed
    assert len(res.exported) == 1
    assert h.status == "migrated" and h.done
    assert not eng.busy                         # nothing left in limbo
    h2 = eng.import_request(res.exported[0])    # resume in place
    assert eng.drain()                          # truthy: fully drained
    assert h2.status == "ok" and h2.tokens == want


def test_cancel_frees_slot_and_marks_status():
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=1, max_len=64,
                       prefill_chunk=4, tick_steps=1,
                       registry=metrics_lib.Registry())
    want = _generate_tokens(model, params, _prompt(4, seed=2), 6, 64)
    h = eng.submit(_prompt(4, seed=1), 40)
    while not h.tokens:
        eng.step()
    assert eng.cancel(h) is True
    assert h.status == "cancelled" and h.done
    assert eng.cancel(h) is False               # already finished
    h2 = eng.submit(_prompt(4, seed=2), 6)      # slot reuse stays exact
    eng.drain()
    assert h2.tokens == want
