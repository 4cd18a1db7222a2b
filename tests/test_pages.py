"""Paged KV cache + radix prefix reuse tests (serve/pages.py).

The contracts pinned here (docs/SERVING.md):
  * engine == greedy ``GPT.generate`` token-for-token (chunked
    prefill, RoPE + GQA, int8 scale planes), and below the engine the
    jitted paged step == ``decode_step``'s logits,
  * admitting a request mid-decode leaves other slots' logits
    BIT-identical; a retired row's writes land on the trash page,
  * a prefix-cache HIT request's tokens are bit-identical to the same
    request on a COLD cache, and the skipped prefill windows are
    measured, not assumed,
  * whole-chain prompts split their last page copy-on-write style
    (re-prefilled private copy) and stay exact,
  * eviction reclaims only refcount-0 chains — a pinned chain never
    loses a page while its holder is in flight; exhaustion requeues
    and always drains,
  * the fused page-walk kernel read path (``use_paged_kernel=True``,
    interpret mode on CPU) is token-identical to the gather path across
    config families and keeps the prefix-reuse contracts,
  * admission / page allocation / COW split / eviction never recompile
    (RetraceGuard budget=1) — on the kernel build too,
  * concurrent submitters sharing a prefix never tear the pool
    (race_harness: refcounts, free list, and radix stay consistent).
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.ops import attention as attn_lib
from distributed_tensorflow_tpu.serve import pages as pages_lib


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


def _radix_pages(pool):
    """Pages currently held by the radix tree (and max refcount seen)."""
    n, max_ref = 0, 0
    stack = list(pool._root.children.values())
    while stack:
        node = stack.pop()
        n += 1
        max_ref = max(max_ref, node.refcount)
        stack.extend(node.children.values())
    return n, max_ref


# ---------------------------------------------------------------------------
# layout units


def test_auto_page_size_divides_max_len():
    assert pages_lib.auto_page_size(256) == 16
    assert pages_lib.auto_page_size(40) == 10
    assert pages_lib.auto_page_size(16) == 16
    assert pages_lib.auto_page_size(7) == 7
    assert pages_lib.auto_page_size(31) == 1     # prime: token pages
    for n in (16, 24, 40, 256, 31):
        assert n % pages_lib.auto_page_size(n) == 0


def test_init_paged_cache_shapes_and_int8_planes():
    model, _ = _model_params(kv_cache_dtype="int8")
    c = model.config
    cache = pages_lib.init_paged_cache(model, num_slots=3, num_pages=9,
                                       page_size=8)
    # a token's heads are ONE flat row, a scale plane one scale a head
    assert cache["kv"]["k"].shape == (c.num_layers, 9, 8,
                                      c.kv_heads * c.head_dim)
    assert cache["kv"]["k"].dtype == jnp.int8
    assert cache["kv"]["k_scale"].shape == (c.num_layers, 9, 8,
                                            c.kv_heads)
    assert cache["kv"]["k_scale"].dtype == jnp.float32
    assert cache["write_col"].shape == (3,)


@pytest.mark.parametrize("layers,heads", [(48, 25), (24, 16)],
                         ids=["gpt2-xl", "gpt2-medium"])
def test_pool_tiles_almost_unpadded_at_published_widths(layers, heads):
    """The layout's point: under the v5e's (16, 128) bf16 tile the pool
    takes at most 1.05 x its logical bytes — 8 slots x 1024 tokens of
    GPT-2-XL are 2.52 GB and tile to 2.62, where ``[.., 25, 64]`` minor
    dimensions tiled to 6.46."""
    model = gpt_tiny(num_layers=layers, num_heads=heads,
                     hidden_size=64 * heads, dtype=jnp.bfloat16)
    cache = jax.eval_shape(
        lambda: pages_lib.init_paged_cache(model, 8, 513, 16))
    logical, tiled = pages_lib.kv_pool_bytes(cache["kv"])
    assert logical == 2 * layers * 513 * 16 * heads * 64 * 2
    assert logical <= tiled <= 1.05 * logical
    # ... and the function sees padding where there is some
    old = {"k": jax.ShapeDtypeStruct((layers, 513, 16, heads, 64),
                                     jnp.bfloat16)}
    logical, tiled = pages_lib.kv_pool_bytes(old)
    assert tiled == logical * (-(-heads // 16) * 16 * 128) // (heads * 64)


def test_pool_validation():
    with pytest.raises(ValueError, match="num_pages"):
        pages_lib.PagePool(num_pages=4, page_size=8, pages_per_slot=4)
    with pytest.raises(ValueError, match="page_size"):
        pages_lib.PagePool(num_pages=8, page_size=0, pages_per_slot=2)
    model, params = _model_params()
    with pytest.raises(ValueError, match="page_size"):
        serve.Engine(model, params, num_slots=2, max_len=32,
                     page_size=7)          # 7 does not divide 32


# ---------------------------------------------------------------------------
# host pool semantics (no device work)


def test_pool_match_register_release_refcounts():
    pool = pages_lib.PagePool(num_pages=17, page_size=4,
                              pages_per_slot=4)
    prompt = np.arange(10, dtype=np.int32)        # 2 full chunks + 2
    a = pool.begin(prompt, 12)
    assert a.skip == 0 and a.n_pages == 3 and len(a.private) == 3
    pool.register(a, prompt)                      # publish chunks 0, 1
    assert len(a.private) == 1 and len(a.shared) == 2
    cached, max_ref = _radix_pages(pool)
    assert cached == 2 and max_ref == 1           # pinned by a itself

    b = pool.begin(prompt, 12)                    # same prompt: a hit
    assert b.skip == 8 and len(b.shared) == 2 and len(b.private) == 1
    _, max_ref = _radix_pages(pool)
    assert max_ref == 2                           # both leases pin
    assert pool.stats()["prefix_hits_total"] == 1
    assert pool.stats()["prefix_tokens_reused_total"] == 8

    pool.release(a)
    pool.release(a)                               # idempotent
    _, max_ref = _radix_pages(pool)
    assert max_ref == 1                           # b still pins
    pool.release(b)
    cached, max_ref = _radix_pages(pool)
    assert cached == 2 and max_ref == 0           # cached, evictable
    st = pool.stats()
    assert st["pages_free"] + cached == st["pages_total"]


def test_pool_eviction_lru_and_pinning():
    pool = pages_lib.PagePool(num_pages=7, page_size=4,
                              pages_per_slot=4)   # 6 usable
    # two cached chains of one page each
    p1 = np.arange(4, dtype=np.int32)
    p2 = np.arange(4, 8, dtype=np.int32)
    for p in (p1, p2):
        lease = pool.begin(p, 5)                  # 2 pages
        pool.register(lease, p)
        pool.release(lease)
    assert pool.stats()["pages_free"] == 4
    # PIN p2's chain: a request extending p2 maps its page read-only
    held = pool.begin(np.concatenate([p2, np.arange(90, 94,
                                                    dtype=np.int32)]), 9)
    assert held.skip == 4 and len(held.shared) == 1
    # demand 3 pages with 2 free: must evict p1's chain (refcount 0)
    # but NEVER p2's pinned page
    big = pool.begin(np.arange(100, 112, dtype=np.int32), 12)
    assert pool.stats()["prefix_evictions_total"] == 1
    pool.release(big)
    probe = pool.begin(np.concatenate([p2, p2]), 9)
    assert probe.skip == 4                        # p2's page survived
    pool.release(probe)
    # p1's chain is gone: re-seeing it is a miss now
    miss = pool.begin(np.concatenate([p1, p1]), 9)
    assert miss.skip == 0
    pool.release(miss)
    pool.release(held)
    cached, max_ref = _radix_pages(pool)
    assert max_ref == 0
    assert pool.stats()["pages_free"] + cached == 6


def test_pool_exhausted_rolls_back_pins():
    pool = pages_lib.PagePool(num_pages=7, page_size=4,
                              pages_per_slot=4)   # 6 usable
    p = np.arange(8, dtype=np.int32)
    a = pool.begin(p, 9)                          # 3 pages
    pool.register(a, p)                           # 2 cached+pinned
    c = pool.begin(np.arange(50, 58, dtype=np.int32), 12)  # 3 private
    assert pool.stats()["pages_free"] == 0
    # shares a's prefix (pins +1 each during match) but cannot get its
    # 2 private pages: the pins must roll back on exhaustion
    with pytest.raises(pages_lib.PagePoolExhausted):
        pool.begin(np.concatenate([p, np.arange(60, 64, dtype=np.int32)]), 16)
    _, max_ref = _radix_pages(pool)
    assert max_ref == 1                           # only a's own pins
    assert pool.stats()["pages_free"] == 0        # nothing leaked
    pool.release(a)
    pool.release(c)
    cached, _ = _radix_pages(pool)
    assert pool.stats()["pages_free"] + cached == 6


# ---------------------------------------------------------------------------
# engine exactness: paged == contiguous == generate


_FAMILIES = pytest.mark.parametrize("kw", [
    {},
    {"position_embedding": "rope", "num_heads": 4, "hidden_size": 128,
     "num_kv_heads": 2},
    {"kv_cache_dtype": "int8"},
], ids=["base", "rope_gqa", "int8"])


@_FAMILIES
def test_paged_engine_matches_generate(kw):
    """The tentpole exactness contract, per config family: a mixed
    workload through the engine equals solo generate
    request-for-request."""
    model, params = _model_params(**kw)
    prompts = [_prompt(7, seed=1), _prompt(5, seed=2), _prompt(9, seed=3),
               _prompt(3, seed=4)]
    budgets = [9, 6, 4, 8]
    wants = [_generate_tokens(model, params, p, n, 64)
             for p, n in zip(prompts, budgets)]
    eng = serve.Engine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, tick_steps=3,
                       registry=metrics_lib.Registry())
    hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    eng.drain()
    assert [h.tokens for h in hs] == wants


# ---------------------------------------------------------------------------
# below the engine: the jitted paged step against decode_step


def _prefill_into_pages(model, params, cache, slot, page_row, prompt,
                        window=8, use_kernel=False):
    """What admission does, by hand: ``prompt`` through
    ``decode_window_paged`` into the pages of ``page_row`` (the last
    window right-padded), then the slot's column state armed."""
    kv = cache["kv"]
    for pos in range(0, prompt.size, window):
        win = np.zeros((1, window), np.int32)
        real = prompt[pos:pos + window]
        win[0, :real.size] = real
        _, kv = model.decode_window_paged(
            params, kv, jnp.asarray(win), jnp.asarray(page_row),
            jnp.int32(pos), head="none", use_kernel=use_kernel)
    n = jnp.int32(prompt.size)
    return dict(cache, kv=kv,
                start_col=cache["start_col"].at[slot].set(0),
                write_col=cache["write_col"].at[slot].set(n),
                positions=cache["positions"].at[slot].set(n))


def _paged_step(model, use_kernel=False):
    return jax.jit(lambda params, cache, tab, toks, live:
                   pages_lib.decode_paged_step(model, params, cache, tab,
                                               toks, live,
                                               use_kernel=use_kernel))


@_FAMILIES
def test_decode_paged_step_matches_decode_step_logits(kw):
    """Numeric oracle below the engine: a slot whose prompt went through
    ``decode_window_paged`` into pool pages produces ``decode_step``'s
    logits over a ``decode_block`` prefill, step after step (per-row
    column state and a page table vs one scalar ``pos``)."""
    model, params = _model_params(**kw)
    prompt, max_len, page_size = _prompt(6, seed=7), 32, 8
    ref = model.init_cache(1, max_len)
    _, ref = model.decode_block(params, ref, jnp.asarray(prompt[None]))
    tab = np.zeros((3, max_len // page_size), np.int32)
    tab[0] = [3, 1, 4, 2]               # any pages but the trash page
    cache = pages_lib.init_paged_cache(model, 3, 9, page_size)
    cache = _prefill_into_pages(model, params, cache, 0, tab[0], prompt)
    if "k_scale" in ref:
        # the int8 planes AND their f32 scales, token for token: the
        # pool's flat rows are a contiguous cache row's heads side by
        # side (that row filled by the same window — a whole-prompt
        # ``decode_block`` rounds a few values the other way)
        assert cache["kv"]["k"].dtype == jnp.int8
        assert cache["kv"]["k_scale"].dtype == jnp.float32
        win = np.zeros((1, 8), np.int32)
        win[0, :prompt.size] = prompt
        _, row = model.decode_window(params, model.init_cache(1, max_len),
                                     jnp.asarray(win), head="none")
        for name in ("k", "v", "k_scale", "v_scale"):
            want = np.asarray(row[name][:, 0, :prompt.size])
            got = np.asarray(cache["kv"][name][:, tab[0, 0], :prompt.size])
            np.testing.assert_array_equal(
                got, want.reshape(want.shape[:2] + (-1,)))
        ref = dict(row, pos=jnp.int32(prompt.size))    # same rounding
    step = _paged_step(model)
    live = jnp.asarray([True, False, False])
    tok = int(prompt[-1])               # any token, fed to both sides
    for n in range(5):
        ref_logits, ref = model.decode_step(params, ref,
                                            jnp.asarray([tok], jnp.int32))
        logits, cache = step(params, cache, tab,
                             jnp.asarray([tok, 0, 0], jnp.int32), live)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(ref_logits[0]), atol=2e-4)
        tok = int(jnp.argmax(ref_logits[0]))
    assert int(cache["write_col"][0]) == prompt.size + 5  # live: advanced
    assert int(cache["write_col"][1]) == 0                # dead: frozen


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_admission_mid_decode_keeps_other_slots_bit_identical(use_kernel):
    """Arming slot 1 mid-decode (its prompt prefilled into its own pages,
    its table row and column state set) must not change slot 0's logits
    by even one bit: same executable, row-independent math — through the
    gather read and through the page-walk kernel (interpret mode)."""
    model, params = _model_params()
    p0, p1 = _prompt(6, seed=1), _prompt(4, seed=2)
    feed = np.asarray(_prompt(6, seed=9))       # fixed row-0 token feed
    step = _paged_step(model, use_kernel)

    def run(arm_at):
        tab = np.zeros((2, 4), np.int32)
        tab[0] = [1, 2, 3, 4]
        cache = pages_lib.init_paged_cache(model, 2, 9, 8)
        cache = _prefill_into_pages(model, params, cache, 0, tab[0], p0,
                                    use_kernel=use_kernel)
        live, out = jnp.asarray([True, False]), []
        for t in range(6):
            if t == arm_at:
                tab[1] = [5, 6, 7, 8]
                cache = _prefill_into_pages(model, params, cache, 1,
                                            tab[1], p1,
                                            use_kernel=use_kernel)
                live = jnp.asarray([True, True])
            logits, cache = step(params, cache, tab.copy(),
                                 jnp.asarray([feed[t], 0], jnp.int32),
                                 live)
            out.append(np.asarray(logits[0]))
        return out

    for alone, beside in zip(run(arm_at=None), run(arm_at=3)):
        np.testing.assert_array_equal(alone, beside)


def test_retired_row_writes_land_on_the_trash_page():
    """A retired slot still computes (static shapes), but the scheduler
    hands the tick an all-zero table row for it, so its frozen write
    lands on page 0: the pages it held — reallocatable now — and every
    other slot's pages do not change by a bit."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       registry=metrics_lib.Registry())
    eng.submit(_prompt(5, seed=3), 3)
    eng.drain()
    assert not eng.scheduler._page_tab.any()    # retired rows map trash

    tab = np.zeros((2, 4), np.int32)
    tab[0] = [1, 2, 3, 4]
    cache = pages_lib.init_paged_cache(model, 2, 9, 8)
    cache = _prefill_into_pages(model, params, cache, 0, tab[0],
                                _prompt(6, seed=1))
    cache = _prefill_into_pages(model, params, cache, 1,
                                np.asarray([5, 6, 7, 8], np.int32),
                                _prompt(11, seed=2))
    before = jax.tree.map(np.asarray, cache["kv"])
    step = _paged_step(model)
    live = jnp.asarray([True, False])           # slot 1 retired: row 0s
    for _ in range(3):
        _, cache = step(params, cache, tab,
                        jnp.asarray([7, 9], jnp.int32), live)
    assert int(cache["write_col"][1]) == 11     # frozen write head
    for name, was in before.items():
        now = np.asarray(cache["kv"][name])
        changed = np.argwhere((now != was).any(axis=(0, 3)))
        # slot 0 wrote its columns 6..8: cells 6, 7 of its first page
        # and cell 0 of its second; the frozen row wrote trash cell
        # 11 % 8 three times over — and no cell of the pages it held
        assert changed.tolist() == [[0, 3], [1, 6], [1, 7], [2, 0]], name


def test_prefix_hit_bit_identical_to_cold_cache_and_skips_windows():
    """A request whose system prompt is radix-cached decodes tokens
    BIT-identical to the same request on a cold cache — and measurably
    skips its shared prefill windows."""
    model, params = _model_params()
    sys_prompt = _prompt(16, seed=7)              # 2 pages at page_size 8
    tails = [_prompt(5, seed=8), _prompt(3, seed=9)]
    reqs = [np.concatenate([sys_prompt, t]) for t in tails]

    def run(eng, req, new=7):
        h = eng.submit(req, new)
        eng.drain()
        assert h.status == "ok"
        return h.tokens

    warm = serve.Engine(model, params, num_slots=2, max_len=64,
                        prefill_chunk=4, tick_steps=2, page_size=8,
                        registry=metrics_lib.Registry())
    got_a = run(warm, reqs[0])                    # seeds the radix cache
    got_b = run(warm, reqs[1])                    # hits it
    st = warm.stats()
    assert st.prefix_hits_total == 1
    assert st.prefix_tokens_reused_total == 16
    assert st.prefill_windows_skipped_total == 4  # 16 skipped / W=4
    assert st.prefix_hit_rate == 0.5              # 1 of 2 lookups

    for req, got in zip(reqs, (got_a, got_b)):
        cold = serve.Engine(model, params, num_slots=2, max_len=64,
                            prefill_chunk=4, tick_steps=2, page_size=8,
                            registry=metrics_lib.Registry())
        assert run(cold, req) == got              # bit-identical tokens
        assert cold.stats().prefix_hits_total == 0


def test_concurrent_shared_prefix_requests_match_solo():
    """Requests sharing a prefix IN FLIGHT TOGETHER (the second maps
    pages the first published at admission) each equal their solo
    generate — read-only sharing never couples the streams."""
    model, params = _model_params()
    sys_prompt = _prompt(8, seed=11)
    tails = [_prompt(4, seed=20 + i) for i in range(4)]
    reqs = [np.concatenate([sys_prompt, t]) for t in tails]
    wants = [_generate_tokens(model, params, r, 8, 64) for r in reqs]
    eng = serve.Engine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       registry=metrics_lib.Registry())
    hs = [eng.submit(r, 8) for r in reqs]
    eng.drain()
    assert [h.tokens for h in hs] == wants
    st = eng.stats()
    assert st.prefix_hits_total >= 1              # later arrivals hit
    # all leases released: free + radix-cached == total, zero pins
    pool = eng.scheduler.pages
    cached, max_ref = _radix_pages(pool)
    assert pool.stats()["pages_free"] + cached == st.pages_total
    assert max_ref == 0


def test_cow_split_whole_chain_prompt_stays_exact():
    """A prompt EXACTLY equal to a cached chain must re-prefill its
    last page (the COW split — decode writes need a private copy) and
    still match solo generate token-for-token."""
    model, params = _model_params()
    prompt = _prompt(16, seed=13)                 # exactly 2 pages
    want = _generate_tokens(model, params, prompt, 6, 64)
    eng = serve.Engine(model, params, num_slots=2, max_len=64,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       registry=metrics_lib.Registry())
    h1 = eng.submit(prompt, 6)
    eng.drain()
    h2 = eng.submit(prompt, 6)                    # whole-chain re-submit
    eng.drain()
    assert h1.tokens == h2.tokens == want
    st = eng.stats()
    assert st.cow_splits_total == 1
    assert st.prefix_hits_total == 1              # page 0 still mapped
    assert st.prefix_tokens_reused_total == 8     # one page, not two


def test_exhaustion_requeues_pinned_chains_survive_and_drains():
    """More demand than pages: admission requeues on exhaustion (no
    deadlock — retirements free pages), an in-flight holder's chain is
    never evicted from under it, and every request finishes exact."""
    model, params = _model_params()
    # pool: 2 slots x 4 pages (page_size 8, max_len 32) + 1 spare + trash
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       num_pages=10, registry=metrics_lib.Registry())
    prompts = [_prompt(9 + (i % 3), seed=30 + i) for i in range(6)]
    wants = [_generate_tokens(model, params, p, 10, 32) for p in prompts]
    hs = [eng.submit(p, 10) for p in prompts]     # each needs 3 pages
    eng.drain()
    for h, want in zip(hs, wants):
        assert h.status == "ok" and h.tokens == want
    pool = eng.scheduler.pages
    cached, max_ref = _radix_pages(pool)
    assert max_ref == 0
    assert pool.stats()["pages_free"] + cached \
        == pool.stats()["pages_total"]


def test_eviction_under_pressure_then_reseeded_prefix_still_hits():
    """Distinct prompts fill the radix cache past the pool's capacity:
    LRU chains evict to keep admissions flowing, and a prefix evicted
    then re-seen simply re-prefills (a miss), while a recent one still
    hits."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=8, tick_steps=2, page_size=8,
                       num_pages=9, registry=metrics_lib.Registry())
    prompts = [_prompt(8, seed=50 + i) for i in range(8)]
    for p in prompts:                             # serially: each caches
        h = eng.submit(p, 3)                      # 2 pages in flight,
        eng.drain()                               # 1 cached after
        assert h.status == "ok"
    st = eng.stats()
    assert st.prefix_evictions_total >= 1         # pressure reclaimed LRU
    # the most recent prefix survived: resubmitting hits
    h = eng.submit(np.concatenate([prompts[-1], _prompt(2, seed=99)]), 3)
    eng.drain()
    assert h.status == "ok"
    assert eng.stats().prefix_hits_total >= 1


# ---------------------------------------------------------------------------
# retrace-free + concurrency


@pytest.mark.retrace_guard(budget=1, enforce_donation=True)
def test_paged_admission_alloc_cow_evict_never_recompile():
    """Every paged executable traces ONCE across a workload that
    exercises admission, page allocation, prefix hits, a COW split,
    eviction under pressure, and slot reuse (budget=1: the second
    trace of anything fails; donation enforcement doubles as a
    use-after-donate check on the pool buffer chain)."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       num_pages=9, eos_id=7,
                       registry=metrics_lib.Registry())
    sys_prompt = _prompt(8, seed=61)
    handles = []
    for i in range(2):                            # seed, then hit
        handles.append(eng.submit(
            np.concatenate([sys_prompt, _prompt(3, seed=70 + i)]), 5))
        eng.drain()
    handles.append(eng.submit(sys_prompt, 4))     # COW split
    eng.drain()
    for i in range(7):                            # distinct: evictions
        handles.append(eng.submit(_prompt(8, seed=80 + i), 4))
        eng.drain()
    assert all(h.done for h in handles)
    assert all(len(h.tokens) >= 1 for h in handles)
    st = eng.stats()
    assert st.prefix_hits_total >= 1
    assert st.cow_splits_total >= 1
    assert st.prefix_evictions_total >= 1


@pytest.mark.race_harness(
    seed=17, scope=("distributed_tensorflow_tpu/serve/",))
def test_concurrent_prefix_submits_never_tear_the_pool(request):
    """THE pool race test: 3 submitter threads sharing one system
    prompt against a pumping engine under seeded preemption.  Every
    request finishes exact (refcounts never dropped a live page), and
    the pool balances to free + radix-cached == total with zero
    refcounts — eviction/release under preemption never double-freed
    or leaked a page."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=3, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       registry=metrics_lib.Registry())
    sys_prompt = _prompt(8, seed=91)
    reqs = {i: np.concatenate([sys_prompt, _prompt(2 + (i % 3),
                                                   seed=100 + i)])
            for i in range(6)}
    wants = {i: _generate_tokens(model, params, reqs[i], 5, 32)
             for i in reqs}
    handles = {}
    hlock = threading.Lock()
    barrier = threading.Barrier(3)

    def submitter(ids):
        barrier.wait(timeout=60)
        for i in ids:
            h = eng.submit(reqs[i], 5)
            with hlock:
                handles[i] = h

    ts = [threading.Thread(target=submitter, args=([k, k + 3],),
                           name=f"dttpu-pages-{k}", daemon=True)
          for k in range(3)]
    for t in ts:
        t.start()
    deadline = time.time() + 300
    while True:
        with hlock:
            got = dict(handles)
        if len(got) == 6 and all(h.done for h in got.values()):
            break
        eng.step()
        assert time.time() < deadline, "engine did not drain"
    for t in ts:
        t.join(timeout=60)

    harness = request.node.race_harness
    assert harness.preemptions > 0, "harness never fired"
    for i, h in handles.items():
        assert h.status == "ok" and h.tokens == wants[i], i
    pool = eng.scheduler.pages
    cached, max_ref = _radix_pages(pool)
    st = pool.stats()
    assert max_ref == 0                           # no leaked pins
    assert st["pages_free"] + cached == st["pages_total"]
    assert eng.stats().prefix_hits_total >= 1


# ---------------------------------------------------------------------------
# metrics plumbing


def test_paged_metrics_land_in_registry():
    """The obs wiring for the new series: pages gauges move with the
    stats snapshot, prefix counters advance by delta, all scrapable
    through the standard exposition path."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       registry=reg)
    sys_prompt = _prompt(8, seed=5)
    for i in range(2):
        # serial: the hit needs the seeder's pages registered first
        eng.submit(np.concatenate([sys_prompt, _prompt(3, seed=i)]), 4)
        eng.drain()
    st = eng.stats()
    assert reg.get("dttpu_serve_pages_free").value == st.pages_free
    cached, _ = _radix_pages(eng.scheduler.pages)
    assert st.pages_free + cached == st.pages_total   # leases released
    assert reg.get("dttpu_serve_prefix_hits_total").value \
        == st.prefix_hits_total == 1
    assert reg.get("dttpu_serve_prefix_evictions_total").value == 0
    doc = metrics_lib.parse_exposition(reg.expose())
    assert doc["dttpu_serve_pages_free"]["type"] == "gauge"
    assert doc["dttpu_serve_pages_per_request"]["type"] == "gauge"
    assert doc["dttpu_serve_prefix_hits_total"]["type"] == "counter"
    # the pool's size as built: logical and tiled (f32 pool here: 8-row
    # pages by 128 lanes tile exactly)
    kv = eng.scheduler._cache["kv"]
    assert st.kv_pool_bytes == sum(v.nbytes for v in kv.values()) > 0
    assert (st.kv_pool_bytes, st.kv_pool_tiled_bytes) \
        == pages_lib.kv_pool_bytes(kv)
    assert reg.get("dttpu_serve_kv_pool_bytes").value == st.kv_pool_bytes
    assert reg.get("dttpu_serve_kv_pool_tiled_bytes").value \
        == st.kv_pool_tiled_bytes
    assert doc["dttpu_serve_kv_pool_tiled_bytes"]["type"] == "gauge"


# ---------------------------------------------------------------------------
# fused page-walk kernel read path (ops/pallas/paged_attention.py)


def test_auto_page_size_multiple_of():
    """The kernel-tileability constraint: prefer a multiple-of-8
    divisor, fall back to the plain largest-divisor pick when max_len
    has none (the scheduler then logs and takes the gather path)."""
    assert pages_lib.auto_page_size(256, multiple_of=8) == 16
    assert pages_lib.auto_page_size(64, multiple_of=8) == 16
    assert pages_lib.auto_page_size(128, multiple_of=8) == 16
    assert pages_lib.auto_page_size(40, multiple_of=8) == 8
    # no lane-tileable divisor exists: unconstrained fallback
    assert pages_lib.auto_page_size(30, multiple_of=8) == 15
    assert pages_lib.auto_page_size(7, multiple_of=8) == 7


_ROPE_GQA = {"position_embedding": "rope", "num_heads": 4,
             "hidden_size": 128, "num_kv_heads": 2}
# a table of 20 eight-token pages, which the kernel's 16 pages a grid step
# do not divide: contexts of a token, a page, a page + 1, a step's pages
# - 1 / +- 0 / + 1, and one that ends on the table's last column; the two
# longest share their first 24 tokens (pages the radix tree maps into both
# rows); three slots for seven requests, so rows retire onto the trash page
# beside rows that decode on
_LONG = dict(max_len=160, max_position=160, num_slots=3,
             prompts=[1, 8, 9, 127, 128, 129, 150],
             budgets=[6, 5, 5, 4, 4, 4, 10], shared=(5, 6, 24))
_SHORT = dict(max_len=64, num_slots=2, prompts=[7, 5, 9, 3],
              budgets=[9, 6, 4, 8])


@pytest.mark.parametrize("kw,traffic", [
    ({}, _SHORT),
    (_ROPE_GQA, _SHORT),
    ({"kv_cache_dtype": "int8"}, _SHORT),
    # K/V rows that are no multiple of 128 lanes, which the flat pool
    # layout exists for: 5 heads x 64 = 320; the same with grouped
    # queries and with int8 planes; GPT-2-XL's own 25 x 64 = 1600
    ({"num_heads": 5, "hidden_size": 320}, _SHORT),
    ({"position_embedding": "rope", "num_heads": 10, "hidden_size": 640,
      "num_kv_heads": 5}, _SHORT),
    ({"num_heads": 5, "hidden_size": 320, "kv_cache_dtype": "int8"},
     _SHORT),
    ({"num_heads": 25, "hidden_size": 1600, "intermediate_size": 256},
     _SHORT),
    ({}, _LONG),
    (_ROPE_GQA, _LONG),
    ({"kv_cache_dtype": "int8"}, _LONG),
], ids=["base", "rope_gqa", "int8", "w320", "w320_rope_gqa", "w320_int8",
        "w1600_xl_heads", "base_ragged_steps", "rope_gqa_ragged_steps",
        "int8_ragged_steps"])
def test_kernel_engine_matches_gather_and_generate(kw, traffic):
    """The kernel exactness contract, per config family: the fused
    page-walk read path produces token streams bit-identical to the
    XLA gather path and solo greedy generate (the kernel runs in
    interpret mode on the CPU mesh, so this executes the real kernel
    body)."""
    traffic = dict(traffic)
    max_len, slots = traffic.pop("max_len"), traffic.pop("num_slots")
    budgets = traffic.pop("budgets")
    prompts = [_prompt(n, seed=1 + i)
               for i, n in enumerate(traffic.pop("prompts"))]
    if "shared" in traffic:
        a, b, n = traffic.pop("shared")
        prompts[b] = np.concatenate([prompts[a][:n], prompts[b][n:]])
    model, params = _model_params(**kw, **traffic)
    wants = [_generate_tokens(model, params, p, n, max_len)
             for p, n in zip(prompts, budgets)]
    outs = {}
    for label, ekw in (("kernel", dict(use_paged_kernel=True,
                                       page_size=8)),
                       ("gather", dict(use_paged_kernel=False,
                                       page_size=8))):
        eng = serve.Engine(model, params, num_slots=slots, max_len=max_len,
                           prefill_chunk=4, tick_steps=3,
                           registry=metrics_lib.Registry(), **ekw)
        hs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        eng.drain()
        outs[label] = [h.tokens for h in hs]
    assert outs["kernel"] == outs["gather"] == wants


@pytest.mark.parametrize("kw", [{}, {"kv_cache_dtype": "int8"}],
                         ids=["base", "int8"])
def test_kernel_step_matches_gather_step_on_ragged_runs(kw):
    """Below the engine: one decode step over slots whose runs start past
    column 0 (mid-page, on a page boundary, a grid step's pages in), end
    on the table's last column, hold one token, or are not live — the
    kernel's logits are the gather path's for every live row, to float
    round-off and to the argmax, and both advance the same state."""
    model, params = _model_params(max_position=160, **kw)
    pg, pps, slots = 8, 20, 6
    rng = np.random.default_rng(5)
    cache = pages_lib.init_paged_cache(model, slots, slots * pps + 1, pg)
    cache["kv"] = {
        name: (jnp.asarray(rng.integers(-127, 128, leaf.shape), leaf.dtype)
               if leaf.dtype == jnp.int8 else
               jnp.asarray(rng.uniform(0.01, 0.05, leaf.shape)
                           if name.endswith("_scale")
                           else rng.normal(size=leaf.shape), leaf.dtype))
        for name, leaf in cache["kv"].items()}
    tab = rng.permutation(slots * pps).reshape(slots, pps) + 1
    tab[4] = 0                                  # retired: the trash page
    tab[5, :3] = tab[1, :3]                     # a prefix shared with row 1
    start = jnp.asarray([0, 5, 8, 131, 0, 0], jnp.int32)
    write = jnp.asarray([159, 6, 140, 150, 17, 0], jnp.int32)
    live = jnp.asarray([True, True, True, True, False, True])
    cache = dict(cache, start_col=start, write_col=write,
                 positions=write - start)
    toks = jnp.asarray(rng.integers(0, 512, slots), jnp.int32)
    outs = {use_kernel: _paged_step(model, use_kernel)(
        params, cache, jnp.asarray(tab, jnp.int32), toks, live)
        for use_kernel in (False, True)}
    (want, want_cache), (got, got_cache) = outs[False], outs[True]
    rows = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               atol=2e-4, rtol=2e-4)
    assert (np.asarray(got.argmax(-1))[rows]
            == np.asarray(want.argmax(-1))[rows]).all()
    assert np.isfinite(np.asarray(got)).all()   # dead rows too
    for name in ("start_col", "write_col", "positions"):
        np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                      np.asarray(want_cache[name]))


def test_prefix_hit_and_cow_exact_through_kernel():
    """Radix reuse composes with the kernel read path: a prefix HIT
    and a whole-chain COW split through the kernel engine both stay
    token-identical to the gather engine on a cold cache."""
    model, params = _model_params()
    sys_prompt = _prompt(16, seed=7)
    tails = [_prompt(5, seed=8), _prompt(3, seed=9)]
    reqs = [np.concatenate([sys_prompt, t]) for t in tails]

    def run(eng, req, new=7):
        h = eng.submit(req, new)
        eng.drain()
        assert h.status == "ok"
        return h.tokens

    warm = serve.Engine(model, params, num_slots=2, max_len=64,
                        prefill_chunk=4, tick_steps=2, page_size=8,
                        use_paged_kernel=True,
                        registry=metrics_lib.Registry())
    assert warm.scheduler.use_paged_kernel is True
    got_a = run(warm, reqs[0])                    # seeds the radix cache
    got_b = run(warm, reqs[1])                    # hits it
    assert warm.stats().prefix_hits_total == 1
    got_cow = run(warm, sys_prompt)               # whole-chain COW split
    assert warm.stats().cow_splits_total == 1

    cold = serve.Engine(model, params, num_slots=2, max_len=64,
                        prefill_chunk=4, tick_steps=2, page_size=8,
                        use_paged_kernel=False,
                        registry=metrics_lib.Registry())
    assert run(cold, reqs[0]) == got_a
    assert run(serve.Engine(model, params, num_slots=2, max_len=64,
                            prefill_chunk=4, tick_steps=2, page_size=8,
                            use_paged_kernel=False,
                            registry=metrics_lib.Registry()),
               reqs[1]) == got_b
    assert got_cow == _generate_tokens(model, params, sys_prompt, 7, 64)


def _pool_equations(jaxpr, pool_shape, found):
    """Every equation, however deeply nested, one of whose operands has
    the pool's shape -> ``found`` {primitive name: count}; a
    ``pallas_call`` is ONE equation (its body reads blocks, not the
    pool)."""
    for eqn in jaxpr.eqns:
        if any(getattr(v.aval, "shape", None) == pool_shape
               for v in eqn.invars):
            name = eqn.primitive.name
            found[name] = found.get(name, 0) + 1
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pool_equations(sub, pool_shape, found)
    return found


@pytest.mark.parametrize("program", ["decode_step", "prefill_window"])
def test_kernel_programs_touch_the_pool_by_scatter_and_kernel_only(
        program):
    """With the kernel on, the pool is written by ONE scatter a leaf on
    the scan-carried array and read by the kernel alone: no
    ``dynamic_slice`` / ``dynamic_update_slice`` takes a pool leaf (a
    layer sliced out and written back moved 2 x 67 MB a layer on the
    chip to place 8 rows), and no gather materializes a view."""
    model, params = _model_params(num_heads=5, hidden_size=320)
    pg, pps, slots = 8, 4, 2
    cache = pages_lib.init_paged_cache(model, slots, slots * pps + 1, pg)
    kv = cache["kv"]
    if program == "decode_step":
        jaxpr = jax.make_jaxpr(
            lambda kv: model.decode_step_slots_paged(
                params, kv, jnp.zeros((slots,), jnp.int32),
                jnp.ones((slots, pps), jnp.int32),
                jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32), use_kernel=True))(kv)
    else:
        jaxpr = jax.make_jaxpr(
            lambda kv, pos: model.decode_window_paged(
                params, kv, jnp.zeros((1, 4), jnp.int32),
                jnp.ones((pps,), jnp.int32), pos, head="none",
                use_kernel=True))(kv, jnp.int32(3))
    found = _pool_equations(jaxpr.jaxpr, kv["k"].shape, {})
    assert "dynamic_slice" not in found, found
    assert "dynamic_update_slice" not in found, found
    assert "gather" not in found, found
    assert found["scatter"] == 2 and found["pallas_call"] == 1, found


@pytest.mark.retrace_guard(budget=1, enforce_donation=True)
def test_kernel_engine_admission_retirement_never_recompile():
    """The kernel build must keep the retrace discipline: the fused
    read path REPLACES the gather read path inside the same three
    executables, so admission, prefix hits, a COW split, eviction
    pressure, and slot reuse still trace each program ONCE."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       num_pages=9, eos_id=7, use_paged_kernel=True,
                       registry=metrics_lib.Registry())
    sys_prompt = _prompt(8, seed=61)
    handles = []
    for i in range(2):                            # seed, then hit
        handles.append(eng.submit(
            np.concatenate([sys_prompt, _prompt(3, seed=70 + i)]), 5))
        eng.drain()
    handles.append(eng.submit(sys_prompt, 4))     # COW split
    eng.drain()
    for i in range(7):                            # distinct: evictions
        handles.append(eng.submit(_prompt(8, seed=80 + i), 4))
        eng.drain()
    assert all(h.done for h in handles)
    assert all(len(h.tokens) >= 1 for h in handles)
    st = eng.stats()
    assert st.prefix_hits_total >= 1
    assert st.cow_splits_total >= 1
    assert st.prefix_evictions_total >= 1


def test_use_paged_kernel_page_size_validation(monkeypatch):
    """Both failure directions of the lane-tileability rule: explicit
    True + incompatible page_size is a construction-time ValueError;
    an "auto" that WOULD dispatch falls back to the gather path with a
    RuntimeWarning instead of a Mosaic error inside the kernel."""
    model, params = _model_params()
    with pytest.raises(ValueError, match="use_paged_kernel"):
        serve.Engine(model, params, num_slots=2, max_len=30,
                     page_size=10, use_paged_kernel=True,
                     registry=metrics_lib.Registry())
    # make the auto gate say yes (TPU backend, threshold met) while the
    # layout stays incompatible: warn + fall back, never raise
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_lib, "_PAGED_KERNEL_MIN_VIEW", 16)
    with pytest.warns(RuntimeWarning, match="gather"):
        eng = serve.Engine(model, params, num_slots=2, max_len=30,
                           page_size=10, registry=metrics_lib.Registry())
    assert eng.scheduler.use_paged_kernel is False
