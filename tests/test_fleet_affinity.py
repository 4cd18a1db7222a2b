"""Prefix-affinity fleet routing tests (docs/SERVING.md §Fleet
affinity policy).

The contracts pinned here:
  * ``EngineStats.prefix_hit_rate`` never divides by zero — a cold
    engine (and a bare stats dataclass) reports 0.0,
  * ``Trace.fingerprint`` folds the per-prefix popularity histogram in
    and stays a determinism pin (same seed -> equal, different seed ->
    different),
  * the pool publishes EVERY depth of a registered chain in its
    bounded fingerprint, and the router's ``expected_pages_reused``
    scores a real ``EngineStats`` and a sim ``_SimStats`` identically
    for identical coverage (sim/real scorer parity),
  * placement prefers the fingerprint holder over the id-tie winner
    (real engines AND SimEngines behind the same Router), and an
    identical replayed trace reproduces ``router.placements`` exactly,
  * with no fingerprints anywhere (contiguous engines) placement
    degrades EXACTLY to the original least-loaded (inflight, id)
    order — the blind fleet replays unchanged,
  * migrate-based scale-in spares the sole holder of a hot chain
    (the old newest-first tie-break victim survives when its chains
    are replicated nowhere else),
  * migration/failover re-placement runs through the SAME scorer: a
    removed replica's in-flight request lands on the survivor holding
    its prefix, not the lowest id,
  * race_harness: concurrent prefix-sharing submits never tear the
    fingerprint — it stays bounded, page-aligned, and scoreable.
"""
import threading
import time

import numpy as np
import pytest

import jax

from distributed_tensorflow_tpu import fleet, serve
from distributed_tensorflow_tpu.fleet import router as router_lib
from distributed_tensorflow_tpu.fleet import sim as sim_lib
from distributed_tensorflow_tpu.fleet import workload
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.serve import pages as pages_lib
from distributed_tensorflow_tpu.serve.scheduler import EngineStats


def _model_params(seed=0, **kw):
    model = gpt_tiny(dropout_rate=0.0, **kw)
    return model, model.init(jax.random.PRNGKey(seed))


def _prompt(plen, seed=1, vocab=512):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (plen,), 0, vocab), np.int32)


def _generate_tokens(model, params, prompt, new, max_len, **kw):
    import jax.numpy as jnp
    out = model.generate(params, jnp.asarray(prompt[None]),
                         max_new_tokens=new, max_len=max_len, **kw)
    return np.asarray(out)[0, prompt.size:].tolist()


def _engine(model, params, reg=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("page_size", 8)
    return serve.Engine(model, params, tick_steps=2,
                        registry=reg or metrics_lib.Registry(), **kw)


def _cost_model(**kw):
    kw.setdefault("n_params", 1.0e8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("num_slots", 4)
    kw.setdefault("tick_steps", 4)
    return sim_lib.CostModel.analytic(hw=sim_lib.HardwarePoint(), **kw)


# ---------------------------------------------------------------------------
# EngineStats.prefix_hit_rate: the zero-division guard


def test_prefix_hit_rate_zero_lookups_is_zero():
    """A stats snapshot with zero prefix lookups reports hit rate 0.0
    instead of dividing by zero — both the bare dataclass and a cold
    paged engine that has never admitted a request."""
    cold = EngineStats(queued=0, prefilling=0, active=0, num_slots=2,
                       inflight_per_tenant={},
                       tokens_inflight_per_tenant={})
    assert cold.prefix_lookups_total == 0
    assert cold.prefix_hit_rate == 0.0
    model, params = _model_params()
    eng = _engine(model, params)
    st = eng.stats()
    assert st.prefix_lookups_total == 0
    assert st.prefix_hit_rate == 0.0


# ---------------------------------------------------------------------------
# Trace fingerprint: popularity histogram + determinism pin


def test_trace_fingerprint_and_prefix_popularity():
    a = workload.synthesize(200, seed=5, prefix_populations=8,
                            prefix_fraction=0.6)
    b = workload.synthesize(200, seed=5, prefix_populations=8,
                            prefix_fraction=0.6)
    c = workload.synthesize(200, seed=6, prefix_populations=8,
                            prefix_fraction=0.6)
    # same seed -> identical fingerprint AND histogram; other seed
    # differs (the determinism pin the ablation arms rely on)
    assert a.fingerprint() == b.fingerprint()
    assert a.prefix_popularity() == b.prefix_popularity()
    assert a.fingerprint() != c.fingerprint()
    # the histogram covers exactly the prefix-carrying requests,
    # sorted by id, every id positive
    pop = a.prefix_popularity()
    assert sum(n for _, n in pop) == int((a.prefix_id > 0).sum())
    ids = [i for i, _ in pop]
    assert ids == sorted(ids) and all(i > 0 for i in ids)
    assert all(n > 0 for _, n in pop)


# ---------------------------------------------------------------------------
# fingerprint publication + sim/real scorer parity


def test_pool_publishes_every_chain_depth():
    """One 16-token prompt through a page_size=8 pool lands BOTH chain
    depths (8 and 16 cached tokens) in the published fingerprint, keyed
    exactly by ``prompt_chain_keys`` — a follower sharing only the
    first page still scores."""
    model, params = _model_params()
    eng = _engine(model, params)
    p = _prompt(16, seed=3)
    eng.submit(p, 4)
    eng.drain()
    st = eng.stats()
    assert st.page_size == 8
    keys = pages_lib.prompt_chain_keys(p, 8)
    assert [tok for _, tok in keys] == [8, 16]
    for key, tokens in keys:
        assert st.prefix_fingerprint.get(key) == tokens


def test_expected_pages_reused_sim_real_parity():
    """The scorer returns the SAME page count for the same coverage on
    both sides of the sim/real boundary: a real engine holding a
    16-token chain (page_size 8) and a SimEngine holding a 32-token
    prefix (chunk 16) both score 2 pages for a follower."""
    model, params = _model_params()
    eng = _engine(model, params)
    sys_prompt = _prompt(16, seed=3)
    eng.submit(sys_prompt, 4)
    eng.drain()
    follower = np.concatenate([sys_prompt, _prompt(3, seed=4)])
    real_score = router_lib.expected_pages_reused(follower, eng.stats())

    sim = sim_lib.SimEngine(_cost_model(), num_slots=4,
                            prefill_chunk=16)
    sim.submit((32, 7, 32, 0.0), 4)
    while sim.step():
        pass
    st = sim.stats()
    assert st.page_size == 16
    assert st.prefix_fingerprint == {7: 32}
    sim_score = router_lib.expected_pages_reused((40, 7, 32, 0.0), st)
    assert real_score == sim_score == 2
    # no-prefix requests score zero on both sides: prefix-free sim
    # tuple, and a real prompt sharing no leading chain
    assert router_lib.expected_pages_reused((40, 0, 0, 0.0), st) == 0
    assert router_lib.expected_pages_reused(
        np.concatenate([_prompt(8, seed=9), sys_prompt[:8]]),
        eng.stats()) == 0


# ---------------------------------------------------------------------------
# router placement: affinity beats the id tie, replays exactly


def test_affinity_placement_prefers_holder_and_replays():
    """The seeded replica (id 1 — NOT the id-tie winner) attracts every
    follower sharing its prefix while loads are equal, and an identical
    replayed trace reproduces ``placements`` exactly."""
    model, params = _model_params()

    def run():
        reg = metrics_lib.Registry()
        router = fleet.Router(
            [_engine(model, params, reg=reg) for _ in range(2)],
            registry=reg)
        sys_prompt = _prompt(16, seed=3)
        # park junk on replica 0 so the seed lands on replica 1
        junk = router.submit(_prompt(8, seed=99), 4)
        seed = router.submit(sys_prompt, 4)
        assert router.placements == [(junk.rid, 0), (seed.rid, 1)]
        router.drain()
        hs = []
        for i in range(4):
            h = router.submit(
                np.concatenate([sys_prompt, _prompt(3, seed=10 + i)]), 4)
            hs.append(h)
            router.drain()
        # all idle at each submit: the blind tie-break picks id 0, the
        # fingerprint holder (id 1) wins only through affinity
        assert [rid for _, rid in router.placements[2:]] == [1] * 4
        assert all(h.status == "ok" for h in hs)
        assert reg.get("dttpu_router_affinity_hits_total").value == 4
        assert reg.get("dttpu_router_affinity_score").value == 2
        return router.placements

    assert run() == run()               # deterministic replay


def test_hot_prefix_convergence_sim_fleet():
    """SimEngines behind the SAME Router converge hot-prefix traffic
    onto the holding replica under equal load; a blind router
    (affinity_weight=0) sends the identical trace to the id-tie
    winner instead."""
    def run(weight):
        reg = metrics_lib.Registry()
        router = fleet.Router(
            [sim_lib.SimEngine(_cost_model(), num_slots=4)
             for _ in range(2)],
            registry=reg, affinity_weight=weight)
        junk = router.submit((64, 0, 0, 0.0), 4)
        seed = router.submit((32, 7, 32, 0.0), 4)
        assert router.placements == [(junk.rid, 0), (seed.rid, 1)]
        router.drain()
        for _ in range(6):
            router.submit((40, 7, 32, 0.0), 4)
            router.drain()
        return [rid for _, rid in router.placements[2:]]

    assert run(1.0) == [1] * 6          # converges on the holder
    assert run(0.0) == [0] * 6          # blind: id tie every time


def test_blind_fallback_engines_without_fingerprint_keep_original_order():
    """Engines whose pool has its prefix cache off publish NO
    fingerprint, so the affinity router's placement order degrades
    exactly to the original least-loaded (inflight, id) order —
    bit-identical to an affinity_weight=0 fleet on the same trace."""
    model, params = _model_params()

    def run(weight):
        reg = metrics_lib.Registry()
        router = fleet.Router(
            [_engine(model, params, reg=reg, prefix_cache=False)
             for _ in range(2)],
            registry=reg, affinity_weight=weight)
        for i in range(6):
            router.submit(_prompt(4 + i % 3, seed=i), 5)
            if i % 2:
                router.step()
        router.drain()
        assert reg.get("dttpu_router_affinity_hits_total").value == 0
        return router.placements

    affinity, blind = run(1.0), run(0.0)
    assert affinity[:2] == [(0, 0), (1, 1)]     # idle tie -> id order
    assert affinity == blind


# ---------------------------------------------------------------------------
# scale-in: spare the sole holder


def test_scale_in_spares_sole_holder_of_hot_chain():
    """Replicas 0 and 1 share a hot chain; replica 2 is the ONLY
    holder of another.  The old rule (least inflight, ties newest
    first) would retire replica 2; the affinity-aware rule retires a
    replicated holder (replica 1) and keeps the sole copy alive."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    engines = [_engine(model, params, reg=reg) for _ in range(3)]
    shared, unique = _prompt(16, seed=3), _prompt(16, seed=4)
    for eng, p in zip(engines, [shared, shared, unique]):
        eng.submit(p, 4)
        eng.drain()
    router = fleet.Router(engines, registry=reg)
    scaler = fleet.Autoscaler(
        router, lambda: _engine(model, params, reg=reg),
        fleet.SLO(ttft_s=2.0, itl_s=0.1), registry=reg)
    victim = scaler._scale_in_victim(router.stats())
    assert victim == 1                  # replicated holder, newest-first
    assert 2 in router.stats()          # sole holder survives
    assert scaler.scale_ins == 1


# ---------------------------------------------------------------------------
# migration/failover re-placement goes through the scorer


def test_migration_replacement_lands_on_fingerprint_holder():
    """An in-flight request whose replica is removed re-places through
    the affinity scorer: it lands on the survivor holding its prefix
    chains (replica 2), not the id-tie survivor (replica 1), and
    finishes token-exact."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    router = fleet.Router(
        [_engine(model, params, reg=reg) for _ in range(3)],
        registry=reg)
    sys_prompt = _prompt(16, seed=3)
    # seed the prefix on replica 2 (park junk on 0 and 1 first)
    router.submit(_prompt(8, seed=98), 4)
    router.submit(_prompt(8, seed=99), 4)
    seed = router.submit(sys_prompt, 4)
    assert router.placements[-1] == (seed.rid, 2)
    router.drain()
    # keep the follower OFF the holder: mark 2 draining for one submit
    assert router.drain_replica(2, timeout_s=5.0)
    follower = np.concatenate([sys_prompt, _prompt(3, seed=7)])
    fh = router.submit(follower, 6)
    assert fh.replica_id == 0
    router.resume_replica(2)
    # removing replica 0 exports the request; re-placement scores the
    # survivors and picks the fingerprint holder over the lower id
    router.remove_replica(0)
    assert router.placements[-1] == (fh.rid, 2)
    assert fh.migrations == 1
    router.drain()
    assert fh.status == "ok"
    assert fh.tokens == _generate_tokens(model, params, follower, 6, 32)


# ---------------------------------------------------------------------------
# race harness: fingerprint coherence under concurrent submits


@pytest.mark.race_harness(
    seed=23, scope=("distributed_tensorflow_tpu/serve/",))
def test_fingerprint_coherent_under_concurrent_submits(request):
    """3 submitter threads sharing one system prompt against a pumping
    engine under seeded preemption: every request finishes exact, and
    the published fingerprint stays coherent — bounded by
    ``fingerprint_k``, every entry a positive multiple of the page
    size, and the hot chain still scores through the router's
    ``expected_pages_reused``."""
    model, params = _model_params()
    eng = _engine(model, params, num_slots=3)
    sys_prompt = _prompt(8, seed=91)
    reqs = {i: np.concatenate([sys_prompt,
                               _prompt(2 + (i % 3), seed=100 + i)])
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
                           name=f"dttpu-affinity-{k}", daemon=True)
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
        # mid-flight snapshots must already be coherent
        st = eng.stats()
        assert len(st.prefix_fingerprint) <= pages_lib.FINGERPRINT_K
        assert all(tok > 0 and tok % 8 == 0
                   for tok in st.prefix_fingerprint.values())
        assert time.time() < deadline, "engine did not drain"
    for t in ts:
        t.join(timeout=60)

    harness = request.node.race_harness
    assert harness.preemptions > 0, "harness never fired"
    for i, h in handles.items():
        assert h.status == "ok" and h.tokens == wants[i], i
    pool = eng.scheduler.pages
    st = eng.stats()
    assert len(st.prefix_fingerprint) <= pool.fingerprint_k
    assert all(tok > 0 and tok % pool.page_size == 0
               for tok in st.prefix_fingerprint.values())
    # the shared chain survived the churn and still scores
    assert router_lib.expected_pages_reused(
        np.concatenate([sys_prompt, _prompt(2, seed=200)]), st) >= 1
