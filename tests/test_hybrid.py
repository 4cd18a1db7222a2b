"""The decoder of state-space and attention layers (models/hybrid.py) against
its plain reference (benchmark/families/granitemoehybrid_reference.py:
float32, the recurrence token by token) at a toy size on the CPU, and the
serving path's handling of its recurrent state: windows, padding, snapshots,
frozen rows, eviction, export / import and the page wire."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models.hybrid import hybrid_tiny
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.serve import pages as pages_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 128


def _load(name):
    path = os.path.join(ROOT, "benchmark", "families", name + ".py")
    spec = importlib.util.spec_from_file_location("_test_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("granitemoehybrid_reference")


def reference_config(model):
    """The reference's view of a toy model: the configuration file's keys."""
    c = model.config
    return {"layer_types": list(c.layer_types),
            "num_attention_heads": c.num_heads,
            "num_key_value_heads": c.num_kv_heads,
            "mamba_n_heads": c.ssm_heads, "mamba_d_head": c.ssm_head_dim,
            "mamba_d_state": c.ssm_state, "mamba_d_conv": c.conv_width,
            "rms_norm_eps": c.layer_norm_eps,
            "embedding_multiplier": c.embedding_multiplier,
            "residual_multiplier": c.residual_multiplier,
            "attention_multiplier": c.attention_multiplier,
            "logits_scaling": c.logits_scaling}


@pytest.fixture(scope="module")
def toy():
    # float32 compute and convolution inputs: both sides then differ by
    # summation order alone
    model = hybrid_tiny(vocab_size=VOCAB, conv_state_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, reference_config(model)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(0, VOCAB, shape,
                                                dtype=np.int32)


_REFERENCE_BEST = {}


def _greedy_reference(params, config, prompt, count):
    """The reference's greedy continuation of ``prompt``: its full forward
    over the sequence so far (right-padded to one compiled length; the
    stack is causal), once a token."""
    if "fn" not in _REFERENCE_BEST:
        _REFERENCE_BEST["fn"] = jax.jit(lambda p, ids: jnp.argmax(
            reference.logits(p, ids, config), -1))
    ids = np.zeros((1, 128), np.int32)
    n = len(prompt)
    ids[0, :n] = prompt
    for _ in range(count):
        ids[0, n] = int(_REFERENCE_BEST["fn"](params, ids)[0, n - 1])
        n += 1
    return [int(t) for t in ids[0, len(prompt):n]]


def _run(engine, prompt, budget):
    handle = engine.submit(prompt, budget)
    while not handle.done:
        engine.step()
    assert handle.status == "ok"
    return list(handle.tokens)


# ------------------------------------------------------- the model itself

def test_layers_keep_their_published_order_in_runs():
    model = hybrid_tiny(layer_types=("mamba",) * 2 + ("attention",)
                        + ("mamba",) * 3 + ("attention", "mamba"))
    assert model.config.plan == (("mamba", 0, 2), ("attention", 0, 1),
                                 ("mamba", 2, 3), ("attention", 1, 1),
                                 ("mamba", 5, 1))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert [seg["ln_1"]["gamma"].shape[0]
            for seg in params["segments"]] == [2, 3, 1]
    assert len(params["attention"]) == 2


@pytest.mark.parametrize("seq", [5, 21, 40])
def test_full_forward_matches_the_reference(toy, seq):
    """Chunked (matrix) form, chunks of 8 with a ragged tail, against the
    token-by-token recurrence."""
    model, params, config = toy
    ids = _ids(seq, 2, seq)
    got = model.logits(params, model.apply(params, ids))
    want = reference.logits(params, jnp.asarray(ids), config)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the reference's other entry points agree with its own logits
    values, best = reference.top2(params, ids, config)
    np.testing.assert_array_equal(best, np.argmax(want, -1))
    np.testing.assert_allclose(values[..., 0], np.max(want, -1), atol=1e-6)
    np.testing.assert_allclose(
        reference.tail_logits(params, ids, config, 3), want[:, -3:],
        atol=1e-6)


def test_loss_gradient_matches_the_reference(toy):
    """The model is trainable though no train cell comes with it."""
    model, params, config = toy
    ids = jnp.asarray(_ids(3, 2, 17))

    def ours(p):
        return model.lm_loss_fn()(p, None, {"input_ids": ids}, None,
                                  True)[0]

    def theirs(p):
        return jnp.mean(reference.token_losses(
            reference.logits(p, ids[:, :-1], config), ids[:, 1:]))

    (loss, grads), (ref_loss, ref_grads) = (
        jax.value_and_grad(ours)(params), jax.value_and_grad(theirs)(params))
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    flat, ref_flat = jax.tree.leaves(grads), jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat) and len(flat) > 20
    for g, r in zip(flat, ref_flat):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=1e-3)


def test_every_parameter_has_a_partition_rule(toy):
    model, params, _ = toy
    specs = model.partition_rules(fsdp=True).tree_specs(params)
    named = [s for s in jax.tree.leaves(
        specs, is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec))
        if any(axis is not None for axis in s)]
    # every matrix is split somewhere; gains, biases and per-head scalars
    # stay whole
    matrices = [x for x in jax.tree.leaves(params) if x.ndim >= 2
                and x.shape[-1] > 16 and x.shape[-2] > 4]
    assert len(named) >= len(matrices) - 2


# ------------------------------------------------ windows, pads, decoding

def _window_then_decode(model, params, context, plen, w, pg=16):
    """Prefill ``context[:plen]`` in windows of ``w`` into slot 1 of a
    2-slot paged cache and decode the rest one token at a time ->
    logits at the last prompt position and at every decoded one."""
    pps = model.config.max_position // pg
    cache = pages_lib.init_paged_cache(model, 2, pps + 1, pg)
    row = np.arange(1, pps + 1, dtype=np.int32)
    window = jax.jit(
        lambda c, toks, pos, real, head: model.decode_window_paged(
            params, c["kv"], toks, row, pos, head=head, state=c["state"],
            slot=np.int32(1), valid=real), static_argnums=4)
    for pos in range(0, plen, w):
        real = min(w, plen - pos)
        toks = np.full((1, w), 7, np.int32)          # pads are not zeros
        toks[0, :real] = context[pos:pos + real]
        logits, kv, state = window(cache, toks, np.int32(pos),
                                   np.int32(real),
                                   "all" if pos + real == plen else "none")
        cache = dict(cache, kv=kv, state=state)
    got = [np.asarray(logits[0, real - 1])]
    cache = dict(cache, write_col=jnp.asarray([0, plen], jnp.int32),
                 positions=jnp.asarray([0, plen], jnp.int32))
    tab = np.stack([np.zeros_like(row), row])
    live = jnp.asarray([False, True])
    step = jax.jit(lambda c, toks: pages_lib.decode_paged_step(
        model, params, c, tab, toks, live))
    for token in context[plen:]:
        lg, cache = step(cache, jnp.asarray([0, token], jnp.int32))
        got.append(np.asarray(lg[1]))
    return np.stack(got), cache


@pytest.mark.parametrize("plen,w", [(64, 32), (45, 32), (23, 7)])
def test_windows_then_decode_match_the_reference(toy, plen, w):
    """Prefill in windows (the scheduler's 32, and a width and a length
    whose padding is a multiple of nothing) then decode through the paged
    cache, against the reference's full forward: logits, not tokens."""
    model, params, config = toy
    context = _ids(plen, plen + 6)
    got, _ = _window_then_decode(model, params, context, plen, w)
    want = reference.tail_logits(params, context[None], config, 7)[0]
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_padding_does_not_advance_the_state(toy):
    """The same 13 tokens in a window of 13 and in a window of 32: the
    state and the convolution's inputs after it are the same."""
    model, params, _ = toy
    context = _ids(5, 13)
    _, tight = _window_then_decode(model, params, context, 13, 13)
    _, padded = _window_then_decode(model, params, context, 13, 32)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(tight["state"][name][:, 1],
                                   padded["state"][name][:, 1], atol=1e-6)
        assert float(jnp.abs(tight["state"][name][:, 1]).max()) > 0


def test_a_row_that_is_not_live_keeps_its_state(toy):
    model, params, _ = toy
    context = _ids(9, 30)
    _, cache = _window_then_decode(model, params, context, 24, 32)
    before = jax.tree.map(np.asarray, cache["state"])
    row = np.arange(1, cache["kv"]["k"].shape[1], dtype=np.int32)
    tab = np.stack([np.zeros_like(row), row])
    _, after = pages_lib.decode_paged_step(
        model, params, cache, tab, jnp.asarray([3, 4], jnp.int32),
        jnp.asarray([True, False]))
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(after["state"][name][:, 1],
                                      before[name][:, 1])      # frozen
        assert not np.array_equal(after["state"][name][:, 0],
                                  before[name][:, 0])          # live


# ----------------------------------------------- the engine and snapshots

def _engine(toy, **kw):
    model, params, _ = toy
    kw.setdefault("num_slots", 3)
    return serve.Engine(model, params, max_len=128, **kw)


def _session(seed):
    rng = np.random.default_rng(seed)
    system = rng.integers(0, VOCAB, 40, dtype=np.int32)
    first = np.concatenate([system, rng.integers(0, VOCAB, 13,
                                                 dtype=np.int32)])
    return system, first, rng


def test_a_turn_resumed_from_a_snapshot_equals_recompute_and_reference(toy):
    """A session's second turn starts from the snapshot its first turn's
    end left (a depth that is no page boundary: the partial page is
    copied); it emits what an engine without any reuse emits, and what the
    reference's full forward picks."""
    model, params, config = toy
    _, first, rng = _session(1)
    warm, cold = _engine(toy), _engine(toy, prefix_cache=False)
    reply = _run(warm, first, 9)
    second = np.concatenate([first, np.asarray(reply, np.int32),
                             rng.integers(0, VOCAB, 11, dtype=np.int32)])
    before = warm.stats()
    resumed = _run(warm, second, 7)
    after = warm.stats()
    # resumed after its own history, reply included (all but the newest
    # token, which was never fed): 53 + 9 - 1 tokens
    assert after.state_restores_total - before.state_restores_total == 1
    assert (after.prefix_tokens_reused_total
            - before.prefix_tokens_reused_total) == 61
    assert cold.stats().state_restores_total == 0
    assert _run(cold, first, 9) == reply
    assert _run(cold, second, 7) == resumed
    assert _greedy_reference(params, config, second, 7) == resumed


def test_a_shared_prefix_gets_its_snapshot_where_a_second_prompt_meets_it(
        toy):
    """The first prompt's snapshots lie at ITS end; a second session behind
    the same system prompt meets the chain at the last shared page (32 of
    40 tokens), is a miss, and leaves a snapshot there; the third hits
    it."""
    model, params, config = toy
    system, first, rng = _session(2)
    engine = _engine(toy)
    _run(engine, first, 5)
    reused = []
    for _ in range(2):
        prompt = np.concatenate(
            [system, rng.integers(0, VOCAB, 17, dtype=np.int32)])
        before = engine.stats().prefix_tokens_reused_total
        tokens = _run(engine, prompt, 5)
        reused.append(engine.stats().prefix_tokens_reused_total - before)
        assert tokens == _greedy_reference(params, config, prompt, 5)
    assert reused == [0, 32]


def test_an_evicted_snapshot_turns_a_hit_into_a_miss_and_stays_right(toy):
    """One snapshot row: every new snapshot evicts the one before, so the
    turn-end snapshot of the first session is gone when its second turn
    arrives — pages without their snapshot are a miss — and the answer is
    the recomputed one."""
    model, params, config = toy
    _, first, rng = _session(3)
    engine = _engine(toy)
    _snapshot_rows(engine, 1)
    reply = _run(engine, first, 9)
    other = rng.integers(0, VOCAB, 50, dtype=np.int32)
    _run(engine, other, 4)                      # takes the only row
    second = np.concatenate([first, np.asarray(reply, np.int32),
                             rng.integers(0, VOCAB, 11, dtype=np.int32)])
    before = engine.stats()
    tokens = _run(engine, second, 7)
    after = engine.stats()
    assert after.state_restores_total == before.state_restores_total
    assert after.prefix_tokens_reused_total \
        == before.prefix_tokens_reused_total
    assert after.state_snapshots_evicted_total >= 2
    assert after.state_snapshot_bytes \
        == pages_lib.state_bytes_per_slot(model)
    assert tokens == _greedy_reference(params, config, second, 7)


def test_a_retired_rows_state_is_unchanged_by_later_ticks(toy):
    engine = _engine(toy, num_slots=2)
    sched = engine.scheduler
    short = engine.submit(_ids(4, 20), 3)       # slot 0: retires early
    busy = engine.submit(_ids(6, 20), 30)       # slot 1: decodes on
    while not short.done:
        engine.step()
    frozen = jax.tree.map(lambda x: np.asarray(x[:, 0]),
                          sched._cache["state"])
    moving = np.asarray(sched._cache["state"]["ssm"][:, 1])
    ticks = 0
    while not busy.done:
        engine.step()
        ticks += 1
        for name, want in frozen.items():
            np.testing.assert_array_equal(
                np.asarray(sched._cache["state"][name][:, 0]), want)
    assert ticks >= 3
    assert not np.array_equal(
        np.asarray(sched._cache["state"]["ssm"][:, 1]), moving)


def test_export_and_import_resume_by_prefilling_again(toy):
    """``RequestSnapshot`` holds no device buffer: an exported request
    re-enters through the same windows, helped by the snapshot its export
    left, and ends where an undisturbed one does."""
    model, params, config = toy
    prompt = _ids(8, 37)
    want = _greedy_reference(params, config, prompt, 14)
    engine = _engine(toy)
    handle = engine.submit(prompt, 14)
    while len(handle.tokens) < 5:
        engine.step()
    snap = engine.export_request(handle)
    assert all(not isinstance(v, jax.Array) for v in vars(snap).values())
    assert not snap.shipped_pages               # nothing for the page wire
    before = engine.stats().state_restores_total
    resumed = engine.import_request(snap)
    while not resumed.done:
        engine.step()
    assert list(resumed.tokens) == want
    assert engine.stats().state_restores_total == before + 1


def test_the_page_wire_declines_a_model_with_recurrent_state(toy):
    """Pages without the state after them would be a wrong hit: the wire
    neither exports nor adopts them, with an error that says so, and
    ``PageWire.ship`` degrades that to re-prefill."""
    engine = _engine(toy)
    context = _ids(10, 48)
    _run(engine, context[:40], 4)
    with pytest.raises(ValueError, match="recurrent state"):
        engine.scheduler.export_chain_pages(context)
    with pytest.raises(ValueError, match="re-prefills"):
        engine.scheduler.import_wire_pages(context, [object()])


# ------------------------------------------------- the pool's accounting

def _snapshot_rows(engine, rows):
    """Cut a fresh engine's snapshot budget (``num_slots + 8`` rows) to
    ``rows``, so that a few toy turns meet its limit."""
    pool = engine.scheduler.pages
    assert not pool._snaps and len(pool._snap_free) == pool.state_rows
    pool.state_rows = rows
    del pool._snap_free[rows:]


def _accounting(engine):
    """Where every page and snapshot row is, from the pool's ``stats()``, a
    walk of its radix tree and the scheduler's live leases."""
    sched = engine.scheduler
    pool = sched.pages
    with sched._lock:
        leases = [r._lease for r in sched._slots if r is not None] \
            + [st.lease for st in sched._prefills]
    with pool._lock:
        reachable, stack = set(), [pool._root]
        while stack:
            node = stack.pop()
            reachable.add(id(node))
            stack.extend(node.children.values())
        snaps = list(pool._snaps.values())
    return {
        "free": pool.stats()["pages_free"],
        "private": sum(len(lease.private) for lease in leases
                       if lease is not None and not lease.released),
        "chain": len(reachable) - 1,
        "snapshot_pages": sum(1 for sn in snaps if sn.page),
        "snapshots": len(snaps),
        "snapshot_bytes": pool.stats()["state_snapshot_bytes"],
        "budget_bytes": pool.state_rows * pool.state_row_bytes,
        "orphan_snapshots": sum(1 for sn in snaps
                                if id(sn.node) not in reachable
                                or sn.node.snap is not sn),
    }


@pytest.mark.parametrize("kind", ["hybrid", "gpt"])
def test_pool_accounting_after_a_rehearsed_window(toy, kind):
    """Sessions through a small pool until chains and snapshots are evicted:
    every page is free, leased, in a chain or a snapshot's; snapshot bytes
    stay inside the budget; no snapshot outlives its chain.  The K/V-only
    toy runs the same pool with the snapshot side empty."""
    if kind == "hybrid":
        model, params, _ = toy
        engine = serve.Engine(model, params, num_slots=2, max_len=128,
                              num_pages=14)
        _snapshot_rows(engine, 3)
    else:
        model = gpt_tiny(vocab_size=VOCAB, max_position=128,
                         dropout_rate=0.0)
        engine = serve.Engine(model, model.init(jax.random.PRNGKey(0)),
                              num_slots=2, max_len=128, num_pages=14)
    pool = engine.scheduler.pages
    rng = np.random.default_rng(11)
    system = rng.integers(0, VOCAB, 35, dtype=np.int32)
    handles = []
    for turn in range(16):
        prompt = np.concatenate(
            [system, rng.integers(0, VOCAB, 20 + 2 * turn, dtype=np.int32)])
        handles.append(engine.submit(prompt, 6))
        for _ in range(3):
            engine.step()
            books = _accounting(engine)
            assert (books["free"] + books["private"] + books["chain"]
                    + books["snapshot_pages"]) == pool.usable_pages()
            assert books["snapshot_bytes"] <= books["budget_bytes"]
            assert books["orphan_snapshots"] == 0
    while any(not h.done for h in handles):
        engine.step()
    books, stats = _accounting(engine), engine.stats()
    assert books["private"] == 0
    assert books["free"] + books["chain"] + books["snapshot_pages"] \
        == pool.usable_pages()
    assert stats.prefix_evictions_total > 0
    if kind == "hybrid":
        assert 0 < books["snapshots"] <= 3
        assert stats.state_snapshots_evicted_total > 0
        assert stats.state_snapshot_bytes == books["snapshot_bytes"]
    else:
        assert books["snapshots"] == books["budget_bytes"] == 0
        assert stats.state_snapshots_total == 0

