"""The decoder-hybrid-decoder (models/sambay.py) against its plain reference
(benchmark/families/phi4flash_reference.py: float32, explicit masks, Mamba-1
token by token) at a toy size on the CPU — Mamba, window, Mamba, window,
Mamba-memory, full, GMU, cross; hidden 64; a window of 8 keys in rings of 8
+ the 32-token chunk — and the serving path's handling of what it keeps:
rings that wrap, the shared plane, snapshots, frozen rows, the window-read
count, and an engine beside the state-space family's in one process."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.hybrid import hybrid_tiny
from distributed_tensorflow_tpu.models.sambay import sambay_tiny
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.ops import attention as attn_lib
from distributed_tensorflow_tpu.ops import ssm
from distributed_tensorflow_tpu.serve import pages as pages_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, MAX_LEN, PG = 128, 256, 16


def _load(name):
    path = os.path.join(ROOT, "benchmark", "families", name + ".py")
    spec = importlib.util.spec_from_file_location("_test_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("phi4flash_reference")


def reference_config(model):
    """The reference's view of a toy model: the configuration file's keys."""
    c = model.config
    return {"num_hidden_layers": c.num_layers, "hidden_size": c.hidden_size,
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "sliding_window": c.sliding_window,
            "layer_norm_eps": c.layer_norm_eps,
            "assumed": {"mamba_d_state": c.d_state,
                        "mamba_d_conv": c.d_conv, "mamba_dt_rank": c.rank}}


@pytest.fixture(scope="module")
def toy():
    # float32 compute and convolution inputs: both sides then differ by
    # summation order alone
    model = sambay_tiny(vocab_size=VOCAB, max_position=MAX_LEN,
                        conv_state_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, reference_config(model)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(0, VOCAB, shape,
                                                dtype=np.int32)


_JITTED = {}


def _programs(model, params):
    """The window program (head static) and the decode step, jitted once a
    model."""
    if id(model) not in _JITTED:
        row = np.arange(1, MAX_LEN // PG + 1, dtype=np.int32)
        window = jax.jit(
            lambda c, toks, pos, real, head: model.decode_window_paged(
                params, c["kv"], toks, row, pos, head=head,
                state=c["state"], slot=np.int32(1), valid=real),
            static_argnums=4)
        tab = np.stack([np.zeros_like(row), row])
        step = jax.jit(lambda c, toks, live: pages_lib.decode_paged_step(
            model, params, c, tab, toks, live))
        _JITTED[id(model)] = (window, step)
    return _JITTED[id(model)]


def _prefill(model, params, context, plen, w):
    """``context[:plen]`` in windows of ``w`` into slot 1 of a 2-slot paged
    cache -> (logits at the last prompt position, the cache armed for
    decode)."""
    window, _ = _programs(model, params)
    cache = pages_lib.init_paged_cache(model, 2, MAX_LEN // PG + 1, PG)
    for pos in range(0, plen, w):
        real = min(w, plen - pos)
        toks = np.full((1, w), 7, np.int32)          # pads are not zeros
        toks[0, :real] = context[pos:pos + real]
        logits, kv, state = window(cache, toks, np.int32(pos),
                                   np.int32(real),
                                   "all" if pos + real == plen else "none")
        cache = dict(cache, kv=kv, state=state)
    return np.asarray(logits[0, real - 1]), dict(
        cache, write_col=jnp.asarray([0, plen], jnp.int32),
        positions=jnp.asarray([0, plen], jnp.int32))


def _decode(model, params, cache, tokens, live=(False, True)):
    """Feed ``tokens`` to slot 1 one a step -> (logits a step, cache)."""
    _, step = _programs(model, params)
    got = []
    for token in tokens:
        lg, cache = step(cache, jnp.asarray([3, token], jnp.int32),
                         jnp.asarray(live))
        got.append(np.asarray(lg[1]))
    return got, cache


# ------------------------------------------------------- the model itself

def test_the_stack_and_the_slot_block_are_what_the_model_says():
    """Two self-decoder pairs and one cross pair stacked, the memory and
    full layers apart; one paged plane; rings of window + chunk rows."""
    model = sambay_tiny()
    c = model.config
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["self"]["mamba"]["mixer"]["a_log"].shape == (2, 16, 128)
    assert params["self"]["window"]["mixer"]["qkv"]["kernel"].shape == (
        2, 64, 64 + 2 * 32)
    assert params["cross"]["attention"]["mixer"]["query"]["kernel"].shape \
        == (1, 64, 64)
    assert "qkv" not in params["cross"]["attention"]["mixer"]
    assert params["cross"]["gmu"]["mixer"]["w_in"]["kernel"].shape == (
        1, 64, 128)
    assert set(params) == {"embeddings", "self", "memory", "full", "cross",
                           "ln_f"}
    spec = model.paged_cache_spec()
    assert spec["kv_layers"] == 1 and c.ring == 8 + 32
    assert spec["state"]["window_k"][:2] == (2, (40, 32))
    assert spec["state"]["ssm"][:2] == (3, (16, 128))
    assert model.attention_window == 8 and model.paged_kernel_ok is False


@pytest.mark.parametrize("seq", [5, 21, 40])
def test_full_forward_matches_the_reference(toy, seq):
    """Past the window (8) and short of it, against the token-by-token
    recurrence and explicit masks."""
    model, params, config = toy
    ids = _ids(seq, 2, seq)
    got = jax.jit(lambda p, i: model.logits(p, model.apply(p, i)))(
        params, ids)
    want = reference.logits(params, jnp.asarray(ids), config)
    np.testing.assert_allclose(got, want, atol=3e-5)
    # the reference's other entry points agree with its own logits
    values, best = reference.top2(params, ids, config)
    np.testing.assert_array_equal(best, np.argmax(want, -1))
    np.testing.assert_allclose(values[..., 0], np.max(want, -1), atol=1e-6)
    np.testing.assert_allclose(
        reference.tail_logits(params, ids, config, 3), want[:, -3:],
        atol=1e-6)


@pytest.mark.parametrize("plen,w", [(140, 32), (45, 32), (23, 7)])
def test_windows_then_decode_match_the_reference(toy, plen, w):
    """Prefill in windows (the scheduler's 32 — 4.4 windows, every ring
    wrapped three times — and a width and a length whose padding is a
    multiple of nothing) then decode through the paged cache and the rings,
    against the reference's full forward: logits, not tokens."""
    model, params, config = toy
    context = _ids(plen, plen + 6)
    last, cache = _prefill(model, params, context, plen, w)
    decoded, _ = _decode(model, params, cache, context[plen:])
    want = reference.tail_logits(params, context[None], config, 7)[0]
    np.testing.assert_allclose(np.stack([last] + decoded), want, atol=5e-5)


def test_a_snapshot_restored_into_another_slot_continues_as_the_reference(
        toy):
    """The family's serving probe, by the methods the scheduler calls:
    prefill half a context, snapshot the slot (Mamba state, convolution
    inputs and both rings) at a depth that is no page boundary, restore it
    into the other slot beside the shared pages, prefill on and decode."""
    model, params, config = toy
    family = _load("phi4flash")
    engine = serve.Engine(model, params, num_slots=2, max_len=MAX_LEN)
    context = _ids(77, 150)
    got = family.serve_probe(model, params, engine.scheduler, context, 6)
    want = reference.tail_logits(params, context[None], config, 7)[0]
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_padding_and_a_row_that_is_not_live_leave_state_and_rings(toy):
    """The same 13 tokens in a window of 13 and in one of 32 leave the same
    state, inputs and rings; a decode step with slot 1 not live leaves its
    whole state row exactly as it was, and moves slot 0's."""
    model, params, _ = toy
    context = _ids(5, 13)
    _, tight = _prefill(model, params, context, 13, 13)
    _, padded = _prefill(model, params, context, 13, 32)
    for name in ("ssm", "conv", "window_k", "window_v"):
        np.testing.assert_allclose(tight["state"][name][:, 1],
                                   padded["state"][name][:, 1], atol=1e-6)
        assert float(jnp.abs(tight["state"][name][:, 1]).max()) > 0
    before = jax.tree.map(np.asarray, padded["state"])
    _, after = _decode(model, params, padded, [4], live=(True, False))
    for name in before:
        np.testing.assert_array_equal(after["state"][name][:, 1],
                                      before[name][:, 1])
        assert not np.array_equal(after["state"][name][:, 0],
                                  before[name][:, 0])


# --------------------------------------------------------------- the ops

def test_mamba1_step_window_form_and_plain_recurrence_agree():
    """``selective_step`` a token at a time, ``selective_scan`` over the
    block and the reference's recurrence; a position whose dt is 0 leaves
    the state exactly."""
    rng = np.random.default_rng(0)
    b, s, n, c = 2, 9, 16, 24
    x = jnp.asarray(rng.normal(size=(b, s, c)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, (b, s, c)), jnp.float32)
    dt = dt.at[1, 4:].set(0.0)                     # row 1 frozen from 4 on
    a = -jnp.asarray(rng.uniform(1, 16, (n, c)), jnp.float32)
    bb, cc = (jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
              for _ in range(2))
    h0 = jnp.asarray(rng.normal(size=(b, n, c)), jnp.float32)
    y, h = ssm.selective_scan(x, dt, a, bb, cc, h0)
    hs, ys = h0, []
    for t in range(s):
        yt, hs = ssm.selective_step(x[:, t], dt[:, t], a, bb[:, t],
                                    cc[:, t], hs)
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=1e-5)
    np.testing.assert_allclose(h, hs, atol=1e-5)
    plain = np.asarray(h0[0], np.float64)
    for t in range(s):
        plain = (np.exp(np.asarray(dt[0, t])[None] * np.asarray(a)) * plain
                 + (np.asarray(dt[0, t]) * np.asarray(x[0, t]))[None]
                 * np.asarray(bb[0, t])[:, None])
        np.testing.assert_allclose(
            y[0, t], (plain * np.asarray(cc[0, t])[:, None]).sum(0),
            rtol=1e-4, atol=1e-4)
    _, frozen = ssm.selective_scan(x[:, :4], dt[:, :4], a, bb[:, :4],
                                   cc[:, :4], h0)
    np.testing.assert_array_equal(h[1], frozen[1])


@pytest.mark.parametrize("sq", [1, 5], ids=["one_query", "a_block"])
def test_differential_attention_with_lambda_zero_is_one_map(sq):
    """Both forms: with ``lam`` 0 a differential head is the softmax of its
    first map over the pair's 128-wide values; with ``lam`` the two maps'
    difference, whatever the form."""
    rng = np.random.default_rng(sq)
    b, t, heads, kv_heads, hd = 2, 11, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(b, sq, heads, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kv_heads * hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv_heads * hd)), jnp.float32)
    mask = jnp.where(rng.uniform(size=(b, sq, t)) < 0.7, 0.0,
                     attn_lib.NEG_INF).at[..., 0].set(0.0)
    kh, vh = k.reshape(b, t, kv_heads, hd), v.reshape(b, t, kv_heads, hd)

    def one_map(h, kv_head):
        s = jnp.einsum("bqd,btd->bqt", q[:, :, h], kh[:, :, kv_head]) / 4.0
        return jax.nn.softmax(s + mask, axis=-1)

    group = heads // kv_heads
    for lam in (0.0, 0.37):
        got = attn_lib.differential_attention(q, k, v, lam, mask, 0.25)
        assert got.shape == (b, sq, heads // 2, 2 * hd)
        for i in range(heads // 2):
            j = i // group
            values = jnp.concatenate([vh[:, :, 2 * j], vh[:, :, 2 * j + 1]],
                                     -1)
            want = (one_map(2 * i, 2 * j)
                    - lam * one_map(2 * i + 1, 2 * j + 1)) @ values
            np.testing.assert_allclose(got[:, :, i], want, atol=2e-5)


def test_the_normed_differential_layer_matches_the_reference(toy):
    """The full layer's attention on random K/V rows — both maps, lambda
    from its vectors, each head's norm and ``1 - lambda_init``, the
    out-projection — against the reference's one head at a time."""
    model, params, config = toy
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    q, k, v = model._qkv(params["full"], x)
    mask = attn_lib.causal_mask(12)[:, 0]
    block = model._attend(params["full"], x, q, k, v, mask, 5)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["full"])
    mixed = reference._differential(
        p["mixer"], q[0].reshape(12, -1), k[0], v[0],
        np.tril(np.ones((12, 12), bool)), 5, config)
    x1 = x[0] + mixed
    want = x1 + reference._mlp(p["ffn"], reference._layer_norm(
        p["ln_2"], x1, config["layer_norm_eps"]))
    np.testing.assert_allclose(block[0], want, atol=2e-5)


# --------------------------------------- what the second half reads, and not

def test_the_second_half_reads_the_memory_and_the_shared_plane_only(toy):
    """After 100 prompt tokens: changing the full layer's K/V rows of
    earlier positions in the pool moves the next step's logits, and so does
    changing a ring row inside the window; changing the ring rows that hold
    positions behind the window does not move them at all.  A GMU handed no
    memory moves the forward's logits."""
    model, params, _ = toy
    c = model.config
    context = _ids(11, 101)
    _, cache = _prefill(model, params, context, 100, 32)
    base, _ = _decode(model, params, cache, context[100:])

    def step_after(edit):
        return _decode(model, params, edit(cache), context[100:])[0][0]

    def pool_edit(cache):
        kv = dict(cache["kv"], k=cache["kv"]["k"].at[0, 1:4].add(1.0))
        return dict(cache, kv=kv)               # columns 0 .. 47 of slot 1

    held = np.asarray(attn_lib.ring_positions(jnp.asarray([100]), c.ring))[0]
    behind = np.flatnonzero(held <= 100 - c.sliding_window)
    inside = np.flatnonzero(held > 100 - c.sliding_window)
    # (the entry of position 100 is the step's own: written before read)
    assert len(behind) == c.ring - c.sliding_window
    assert len(inside) == c.sliding_window

    def ring_edit(rows):
        def edit(cache):
            state = dict(cache["state"])
            for name in ("window_k", "window_v"):
                state[name] = state[name].at[:, 1, rows].add(3.0)
            return dict(cache, state=state)
        return edit

    assert np.abs(step_after(pool_edit) - base[0]).max() > 1e-3
    assert np.abs(step_after(ring_edit(inside)) - base[0]).max() > 1e-3
    np.testing.assert_array_equal(step_after(ring_edit(behind)), base[0])

    real_gmu = type(model)._gmu
    ids = _ids(2, 1, 20)
    forward = lambda m: jax.jit(lambda p: m.logits(p, m.apply(p, ids)))(
        params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(type(model), "_gmu", lambda self, p, x, memory: real_gmu(
            self, p, x, jnp.zeros_like(memory)))
        forgetful = forward(model)
    assert np.abs(forgetful - forward(model)).max() > 1e-2


# ------------------------------------------------------------- the engine

_GREEDY = {}


def _greedy_reference(params, config, prompt, count):
    """The reference's greedy continuation of ``prompt``: its full forward
    over the sequence so far (right-padded to one compiled length; the
    stack is causal), once a token."""
    if "fn" not in _GREEDY:
        _GREEDY["fn"] = jax.jit(lambda p, ids: jnp.argmax(
            reference.logits(p, ids, config), -1))
    ids = np.zeros((1, 160), np.int32)
    n = len(prompt)
    ids[0, :n] = prompt
    for _ in range(count):
        ids[0, n] = int(_GREEDY["fn"](params, ids)[0, n - 1])
        n += 1
    return [int(t) for t in ids[0, len(prompt):n]]


def _run(engine, prompt, budget):
    handle = engine.submit(prompt, budget)
    while not handle.done:
        engine.step()
    assert handle.status == "ok"
    return list(handle.tokens)


def test_a_session_resumed_from_a_snapshot_emits_the_references_tokens(toy):
    """A 75-token first turn (the rings wrapped), then its second turn,
    which resumes from the turn-end snapshot: both emit the reference's
    greedy tokens.  Every decode dispatch says how many keys its window
    reads covered, ``min(position + 1, 8)`` a live step, and the engine
    counts the same."""
    model, params, config = toy
    rng = np.random.default_rng(4)
    first = rng.integers(0, VOCAB, 75, dtype=np.int32)
    reqtrace.reset()
    with trace_lib.activated(trace_lib.Tracer()) as tracer:
        engine = serve.Engine(model, params, num_slots=2, max_len=MAX_LEN,
                              registry=metrics_lib.Registry())
        reply = _run(engine, first, 6)
        second = np.concatenate([first, np.asarray(reply, np.int32),
                                 rng.integers(0, VOCAB, 9, dtype=np.int32)])
        before = engine.stats().state_restores_total
        resumed = _run(engine, second, 5)
    reqtrace.reset()
    stats = engine.stats()
    assert stats.state_restores_total == before + 1
    assert reply == _greedy_reference(params, config, first, 6)
    assert resumed == _greedy_reference(params, config, second, 5)
    # every step after a turn's first token: 5 + 4 live steps, all at or
    # past position 8
    assert stats.decode_window_tokens_total == 8 * (5 + 4)
    reads = [s.args["window_tokens"] for s in tracer.spans()
             if s.name == "serve.decode_dispatch"]
    assert sum(reads) == stats.decode_window_tokens_total
    snaps = [s.args for s in tracer.spans()
             if s.name in ("serve.state_snapshot", "serve.state_restore")]
    assert snaps and engine.scheduler.pages.state_row_bytes == \
        pages_lib.state_bytes_per_slot(model)


def test_a_sambay_and_a_hybrid_engine_in_one_process(toy):
    """Each engine's tokens, stepping the two in turn, are what it gives
    alone: nothing of one model's programs or caches leaks into the
    other's."""
    model, params, _ = toy
    other = hybrid_tiny(vocab_size=VOCAB, max_position=MAX_LEN)
    other_params = other.init(jax.random.PRNGKey(1))
    prompts = [_ids(20 + i, 30 + 7 * i) for i in range(2)]

    def alone(m, p):
        engine = serve.Engine(m, p, num_slots=2, max_len=MAX_LEN)
        return [_run(engine, prompt, 5) for prompt in prompts]

    want = {"sambay": alone(model, params),
            "hybrid": alone(other, other_params)}
    engines = {"sambay": serve.Engine(model, params, num_slots=2,
                                      max_len=MAX_LEN),
               "hybrid": serve.Engine(other, other_params, num_slots=2,
                                      max_len=MAX_LEN)}
    handles = {k: [e.submit(p, 5) for p in prompts]
               for k, e in engines.items()}
    while any(not h.done for hs in handles.values() for h in hs):
        for e in engines.values():
            e.step()
    for kind, hs in handles.items():
        assert [list(h.tokens) for h in hs] == want[kind], kind
    assert engines["hybrid"].stats().decode_window_tokens_total == 0
