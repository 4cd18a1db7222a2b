"""Fleet router: spread requests over N Engine replicas, survive losses.

One ``serve.Engine`` is one mesh; a fleet is N of them behind a
``Router`` façade with the same ``submit() -> handle`` surface
(docs/SERVING.md §Fleet):

* **Prefix-affinity placement** — each submit reads every live
  replica's ``Engine.stats()`` snapshot (a cheap host-side read, never
  a ``/metrics`` text scrape) and scores candidates JOINTLY by load
  and expected prefix-cache reuse: effective load = ``inflight -
  affinity_weight * expected_pages_reused(prompt, fingerprint)``,
  where the fingerprint is the replica's bounded hot-radix-chain
  digest (``serve/pages.py``; mirrored by ``fleet.sim.SimEngine`` so
  sim and real fleets score identically) and the request side is
  :func:`expected_pages_reused` below.  Ties break by raw inflight
  then replica id, and empty fingerprints score 0 everywhere — the
  policy degrades EXACTLY to the original least-loaded order, so a
  replayed trace reproduces its placement decisions bit-for-bit
  (``router.placements``, pinned by tests/test_fleet.py and
  tests/test_fleet_affinity.py).  ``affinity_weight=0`` turns the
  policy off (the bench ablation's blind arm).
* **Retry within the deadline** — a submit REJECTED by one replica
  (queue full, tenant quota) tries the others in load order before the
  rejection reaches the caller; a request whose replica dies, drains,
  or is quarantined MIGRATES to a survivor as long as its deadline
  allows: the router exports a ``RequestSnapshot`` (progress intact)
  and imports it elsewhere, so decode work is preserved and the
  terminal tokens are bit-identical to an unmigrated run.  Every
  ``on_token`` the router attaches is an offset-deduplicating stream
  shim, so delivery is EXACTLY-ONCE across any number of hops — even
  on the raw-resubmit fallback when an export is impossible.
* **Rolling restarts** — ``drain_replica`` stops routing new traffic
  to a replica and (by default) migrates its in-flight requests to the
  survivors instead of waiting them out; ``remove_replica`` /
  ``add_replica`` / ``resume_replica`` swap replicas in and out with
  in-flight work migrated, turning the PR 5 backpressure/deadline/
  drain primitives into zero-downtime deploys.
* **Quarantine** — ``quarantine_replica`` takes a stuck-but-alive
  replica out of rotation (the fleet ``Watchdog``'s tick-deadline
  policy drives it; the PR 5 checkpoint-quarantine vocabulary, applied
  to replicas), force-exports what it can past the wedged pump, and
  migrates; the detached engine is kept in ``router.quarantined`` for
  the operator.
* **Chaos** — ``kill_replica`` raises at the router's pump site,
  ``stall_tick``/``wedge_replica`` (resilience.faults) bend the
  engine's own pump; the acceptance tests pin that every non-expired
  request completes on a survivor bit-identical to solo ``generate``
  with zero duplicated stream tokens (tests/test_migration.py).

The router is synchronous like the engine: the caller pumps ``step()``
(one tick of every live replica + the retry sweep) or ``drain()``.

Thread-safety: ``submit``/``cancel``/``stats``/replica management may
run on any thread concurrently with the pump.  One state lock guards
the replica table, the in-flight list, and the placement log; engines
are pumped OUTSIDE it (each engine serializes its own ticks), so a
slow tick never blocks a concurrent submit.  Lock order is strictly
router -> engine (scheduler/adapter locks) — no path takes them the
other way around.

Metrics (``registry=``): ``dttpu_router_replicas`` gauge,
``dttpu_router_requests_total`` / ``dttpu_router_retries_total`` /
``dttpu_router_replica_down_total`` / ``dttpu_router_rejected_total``
/ ``dttpu_migrations_total`` /
``dttpu_router_affinity_hits_total`` /
``dttpu_router_wire_migrations_total`` /
``dttpu_router_wire_degraded_total`` counters, the
``dttpu_router_affinity_score`` gauge, and per-replica
``dttpu_router_placed_total{replica=...}``.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import (Callable, Dict, List, Optional, Protocol, Tuple,
                    runtime_checkable)

from ..obs import metrics as metrics_lib
from ..obs import reqtrace
from ..resilience import faults as faults_lib
from ..serve import pages as pages_lib
from ..serve.engine import (Engine, QueueFullError, RequestHandle,
                            RequestSnapshot)
from .pagewire import WireError
from .tenancy import QuotaExceededError

log = logging.getLogger(__name__)

__all__ = ["EngineProtocol", "FleetHandle", "NoReplicaError", "Router",
           "expected_pages_reused", "request_chain_keys"]


def request_chain_keys(prompt, page_size: int):
    """``(fingerprint key, tokens covered)`` pairs for a request's
    prompt — the request-side half of the affinity scorer, dispatching
    on what a "prompt" is in each fleet:

    * a real token sequence -> the blake2b chain hashes of its full
      ``page_size`` chunks (``serve.pages.prompt_chain_keys``);
    * a ``fleet.sim`` prompt tuple ``(plen, prefix_id, prefix_len,
      arrival)`` -> the prefix id itself, covering the full chunks of
      ``prefix_len`` (``SimEngine`` fingerprints by prefix id — same
      key space on both sides of the score);
    * anything else (e.g. a bare int) -> no keys, affinity 0.
    """
    if type(prompt) is tuple:
        plen, prefix_id, prefix_len = prompt[0], prompt[1], prompt[2]
        covered = int(prefix_len) - int(prefix_len) % int(page_size)
        if prefix_id and covered > 0:
            return ((int(prefix_id), covered),)
        return ()
    if prompt is None or isinstance(prompt, (int, float)):
        return ()
    return pages_lib.prompt_chain_keys(prompt, page_size)


def expected_pages_reused(prompt, stats, manifest=None) -> int:
    """How many whole KV pages of ``prompt``'s prefix the replica
    behind ``stats`` (an ``EngineStats``-shaped snapshot carrying
    ``prefix_fingerprint`` + ``page_size``) would serve from its radix
    cache — the affinity term of the placement score.  The deepest
    fingerprint match wins; the cached length caps what a shallower
    cached chain can give.  0 when the replica publishes no
    fingerprint (cold pool, prefix cache off, no pool at all) —
    which is what makes the blind fallback exact.

    ``manifest`` (a ``RequestSnapshot.shipped_pages`` tuple) overrides
    the prompt-derived keys: a migrating request scores by the chains
    its export actually handed off — prompt PLUS generated tokens —
    so a survivor already holding them (an earlier wire transfer, a
    shared prefix) outranks an equally-loaded cold one."""
    fp = getattr(stats, "prefix_fingerprint", None)
    pg = int(getattr(stats, "page_size", 0) or 0)
    if not fp or pg < 1:
        return 0
    keys = manifest if manifest else request_chain_keys(prompt, pg)
    best = 0
    for key, tokens in keys:
        cached = fp.get(key, 0)
        got = tokens if tokens < cached else cached
        if got > best:
            best = got
    return best // pg

# submit errors that mean "THIS replica won't take it right now" — safe
# to retry on another replica.  Anything else (validation, unknown
# adapter) is wrong everywhere and propagates to the caller.
_REJECTIONS = (QueueFullError, QuotaExceededError)


class NoReplicaError(RuntimeError):
    """No live replica can take this request (all dead or draining)."""


@runtime_checkable
class EngineProtocol(Protocol):
    """What the router actually requires of a replica.

    ``serve.Engine`` (a real mesh) and ``fleet.sim.SimEngine`` (the
    virtual-time cost-model replica) both conform — pinned by
    tests/test_fleet_sim.py — which is what lets one ``Router`` +
    ``Watchdog`` + ``Autoscaler`` stack run unchanged against either
    fleet.  ``add_replica`` enforces conformance with ``isinstance``
    (structural: a runtime-checkable Protocol checks member presence,
    not signatures), so a bogus replica fails loudly at registration
    instead of at first pump."""

    def submit(self, prompt, max_new_tokens=None, on_token=None,
               **kwargs): ...

    def stats(self): ...

    def step(self) -> bool: ...

    def drain(self, timeout_s=None) -> bool: ...

    def cancel(self, handle) -> bool: ...

    def export_request(self, handle, timeout_s=None): ...

    def import_request(self, snapshot, on_token=None): ...

    def load_adapter(self, adapter_id, adapter) -> None: ...

    @property
    def busy(self) -> bool: ...


class FleetHandle:
    """Caller-facing view of one fleet request across retries.

    Mirrors ``RequestHandle`` (tokens / done / status / error / ttft_s)
    but survives replica failures: after a migration or failover the
    handle simply tracks the replacement attempt.  ``replica_id`` is
    the current (or final) placement; ``attempts`` counts placements;
    ``migrations`` counts snapshot-based moves and
    ``tokens_preserved`` the decode work those moves salvaged (tokens
    carried over instead of regenerated)."""

    def __init__(self, rid: int, spec: dict,
                 deadline: Optional[float], retries_left: int,
                 router: "Router"):
        self.rid = rid
        self.spec = spec
        self.deadline = deadline            # absolute perf_counter or None
        self.retries_left = retries_left
        self.attempts = 0
        self.migrations = 0
        self.tokens_preserved = 0
        self.replica_id: Optional[int] = None
        self._router = router
        self._handle: Optional[RequestHandle] = None
        self._snapshot: Optional[RequestSnapshot] = None
        # captured page-wire records riding with an orphaned snapshot
        # (fleet/pagewire.py): host copies of the radix pages the
        # export handed off, shipped to whichever survivor imports
        self._wire_records: Optional[list] = None
        self._streamed = 0                  # tokens forwarded to the user
        self._ttft: Optional[float] = None  # pinned at first placement
        self._status = "pending"
        self.error: Optional[BaseException] = None

    @property
    def tokens(self) -> List[int]:
        if self._handle is not None:
            return self._handle.tokens
        if self._snapshot is not None:      # orphaned mid-migration
            return list(self._snapshot.generated)
        return []

    @property
    def status(self) -> str:
        return self._status

    @property
    def done(self) -> bool:
        return self._status != "pending"

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit-to-first-token of the FIRST placement that produced a
        token — migration does not reset it (the caller saw the stream
        start exactly once)."""
        if self._ttft is not None:
            return self._ttft
        return self._handle.ttft_s if self._handle is not None else None

    @property
    def tenant(self) -> str:
        return self.spec["tenant"]

    @property
    def critpath(self) -> Optional[dict]:
        """The finished critical-path breakdown (``obs.critpath``) of
        the placement that retired the request.  Migration carries the
        accrual on the snapshot, so the breakdown spans every hop; None
        while in flight or with no ledger active at submit."""
        return self._handle.critpath if self._handle is not None else None

    def _attempt_stream(self, base: int):
        """An ``on_token`` shim for one placement: forwards only tokens
        the user has not seen yet, making delivery exactly-once across
        migrations AND raw-resubmit retries.  ``base`` is the stream
        position where this attempt starts emitting (a snapshot
        import's ``stream_offset``; 0 for a fresh submit).  A raising
        user callback propagates BEFORE ``_streamed`` advances, so a
        retried attempt re-delivers exactly the tokens the user never
        accepted."""
        user = self.spec["on_token"]
        pos = [base]

        def shim(toks: List[int]) -> None:
            start = pos[0]
            pos[0] = start + len(toks)
            fresh = toks[max(0, self._streamed - start):]
            if not fresh:
                return
            if user is not None:
                user(fresh)
            self._streamed = max(self._streamed, pos[0])

        return shim

    def result(self) -> List[int]:
        """Pump the fleet until this request finishes; return its
        tokens (synchronous router: waiting IS driving)."""
        while not self.done:
            if not self._router.step():
                break
        return self.tokens

    def _finalize(self, status: str,
                  error: Optional[BaseException] = None) -> None:
        self._status = status
        self.error = error


class Router:
    """Spread ``submit()`` traffic over N ``serve.Engine`` replicas.

    Args:
      replicas: engines to start with (``add_replica`` adds more; each
        gets the next integer replica id).
      registry: obs registry for the router metrics (default: the
        process registry).
      max_retries: placements a request may consume AFTER its first
        (failover budget; rejected-at-submit probing of other replicas
        does not count).
      export_timeout_s: how long failure-path exports wait for a dead/
        quarantined replica's pump mutex before falling back to a
        forced (``clean=False``) export — the wedged-pump escape hatch.
      affinity_weight: inflight-units of load one expected reused KV
        page is worth when scoring placement candidates (see module
        doc).  0 disables prefix affinity (pure least-loaded — the
        ablation's blind arm); the default 1.0 means "prefer a replica
        holding my prefix until it is that many requests busier".
      page_wire: a ``fleet.pagewire.PageWire`` — migrations then SHIP
        the victim's radix-cached KV pages to the destination instead
        of re-prefilling them (export captures host copies, the wire
        chunks/CRCs/retries, the import radix-matches the shipped
        chain).  None (default) keeps plain re-prefill migration; any
        unrecoverable wire failure degrades to it per-migration
        (``dttpu_router_wire_degraded_total``), so correctness never
        rides the wire.
    """

    def __init__(self, replicas=(), *,
                 registry: Optional[metrics_lib.Registry] = None,
                 max_retries: int = 2,
                 export_timeout_s: float = 1.0,
                 affinity_weight: float = 1.0,
                 page_wire=None):
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0; got {max_retries}")
        if affinity_weight < 0:
            raise ValueError(
                f"affinity_weight must be >= 0; got {affinity_weight}")
        reg = registry if registry is not None else metrics_lib.REGISTRY
        self.registry = reg
        self.max_retries = int(max_retries)
        self.export_timeout_s = float(export_timeout_s)
        self.affinity_weight = float(affinity_weight)
        self.page_wire = page_wire
        # guards the replica table, draining set, in-flight list, and
        # placement log; never held while pumping an engine tick
        self._lock = threading.Lock()
        self._replicas: Dict[int, Engine] = {}
        self._draining: set = set()
        # replicas the watchdog (or operator) pulled for being unhealthy:
        # {replica_id: (engine, reason)} — detached, kept for inspection
        self.quarantined: Dict[int, Tuple[Engine, str]] = {}
        self._next_replica = 0
        self._next_rid = 0
        self._inflight: List[FleetHandle] = []
        self.placements: List[tuple] = []      # (fleet rid, replica id)
        self._m_replicas = reg.gauge(
            "dttpu_router_replicas", "Live engine replicas behind the "
            "router (draining replicas still count until empty).")
        self._m_requests = reg.counter(
            "dttpu_router_requests_total",
            "Requests accepted by the router.")
        self._m_retries = reg.counter(
            "dttpu_router_retries_total",
            "Failover resubmissions (replica death or failed handle).")
        self._m_down = reg.counter(
            "dttpu_router_replica_down_total",
            "Replicas removed after a pump failure.")
        self._m_rejected = reg.counter(
            "dttpu_router_rejected_total",
            "Submits rejected by EVERY live replica (fleet-wide "
            "backpressure surfaced to the caller).")
        self._m_migrations = reg.counter(
            "dttpu_migrations_total",
            "In-flight requests moved live (RequestSnapshot export -> "
            "import on a survivor) across failover, drain, removal, or "
            "quarantine.")
        self._m_affinity_hits = reg.counter(
            "dttpu_router_affinity_hits_total",
            "Placements that landed on a replica already holding part "
            "of the request's prefix (expected_pages_reused > 0).")
        self._m_affinity_score = reg.gauge(
            "dttpu_router_affinity_score",
            "Expected KV pages reused by the most recent placement "
            "(0 = blind landing).")
        self._m_wire_migrations = reg.counter(
            "dttpu_router_wire_migrations_total",
            "Migrations whose KV pages were shipped over the page "
            "wire and adopted by the destination pool (the skipped "
            "re-prefill windows show up in the destination's "
            "EngineStats.prefill_windows_skipped_total).")
        self._m_wire_degraded = reg.counter(
            "dttpu_router_wire_degraded_total",
            "Migrations that fell back to re-prefill after an "
            "unrecoverable page-wire failure (link down, chunk "
            "retries exhausted).")
        self._m_placed: Dict[int, metrics_lib.Counter] = {}
        for engine in replicas:
            self.add_replica(engine)

    # -------------------------------------------------------- replicas

    def add_replica(self, engine: Engine) -> int:
        if not isinstance(engine, EngineProtocol):
            missing = [m for m in ("submit", "stats", "step", "drain",
                                   "cancel", "export_request",
                                   "import_request", "load_adapter",
                                   "busy")
                       if not hasattr(engine, m)]
            raise TypeError(
                f"replica {type(engine).__name__} does not implement "
                f"the router's EngineProtocol (missing: {missing})")
        with self._lock:
            rid = self._next_replica
            self._next_replica += 1
            self._replicas[rid] = engine
            # chaos identity: engine-targeted fault kinds (stall_tick,
            # wedge_replica) address this replica by its fleet id
            engine.chaos_tag = rid
            self._m_placed[rid] = self.registry.counter(
                "dttpu_router_placed_total",
                "Requests placed, by replica.",
                labels={"replica": str(rid)})
            self._m_replicas.set(len(self._replicas))
        return rid

    @property
    def replica_ids(self):
        with self._lock:
            return tuple(self._replicas)

    def replica(self, replica_id: int) -> Engine:
        with self._lock:
            return self._replicas[replica_id]

    def stats(self) -> Dict[int, object]:
        """{replica_id: EngineStats} for every live replica.  Paged-KV
        engines carry their page-pool occupancy and radix prefix-cache
        counters in the same snapshot (``pages_free``,
        ``prefix_hits_total``, ... — serve/pages.py), so fleet-level
        capacity dashboards read one surface, not N /metrics scrapes."""
        with self._lock:
            live = list(self._replicas.items())
        return {rid: eng.stats() for rid, eng in live}

    def pages_free(self) -> int:
        """Fleet-wide free KV pages (sum over live paged replicas) —
        the admission-headroom signal a capacity autoscaler would act
        on; 0 when no replica reports a pool."""
        return sum(s.pages_free for s in self.stats().values())

    def load_adapter(self, adapter_id: str, adapter) -> None:
        """Register a LoRA adapter on EVERY live replica (each holds its
        own device table) so placement stays adapter-agnostic."""
        with self._lock:
            live = list(self._replicas.values())
        for eng in live:
            eng.load_adapter(adapter_id, adapter)

    # ---------------------------------------------------------- intake

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               on_token: Optional[Callable[[List[int]], None]] = None,
               deadline_s: Optional[float] = None,
               tenant: str = "default",
               adapter_id: Optional[str] = None) -> FleetHandle:
        """Place one request on the best-scoring live replica (load
        net of prefix affinity — see module doc) -> handle.  Replicas
        that reject (queue full, tenant quota) are skipped for the
        next-scored one; if EVERY live replica rejects, the last
        rejection propagates (fleet-wide backpressure).  ``deadline_s``
        is a FLEET deadline: retries submit with the remaining budget."""
        deadline = (None if deadline_s is None
                    else time.perf_counter() + deadline_s)
        # mint the request trace id at the FLEET front door, so every
        # placement attempt and migration hop shares one lane; None
        # (tracing off) costs a module check per request
        trace_id = reqtrace.mint()
        with self._lock:
            fh = FleetHandle(
                rid=self._next_rid,
                spec=dict(prompt=prompt, max_new_tokens=max_new_tokens,
                          on_token=on_token, tenant=tenant,
                          adapter_id=adapter_id, trace_id=trace_id),
                deadline=deadline, retries_left=self.max_retries,
                router=self)
            self._next_rid += 1
            self._place(fh, raise_rejection=True)
            self._m_requests.inc()
            self._inflight.append(fh)
        return fh

    def _candidates(self, fh: Optional[FleetHandle] = None
                    ) -> Tuple[List[int], Dict[int, int]]:
        """Live, non-draining replica ids in placement order, plus each
        candidate's affinity score (expected pages reused; all 0 when
        scoring is off or no prompt is given).  Order: effective load
        ``inflight - affinity_weight * affinity`` first, ties by raw
        inflight then replica id — with no fingerprints anywhere this
        is EXACTLY the original least-loaded (inflight, id) order, so
        blind-fleet placement replays unchanged.  Called with the
        router lock held."""
        ids = [rid for rid in self._replicas
               if rid not in self._draining]
        stats = {rid: self._replicas[rid].stats() for rid in ids}
        if fh is None or not self.affinity_weight:
            ids.sort(key=lambda rid: (stats[rid].inflight, rid))
            return ids, {rid: 0 for rid in ids}
        prompt = fh.spec["prompt"]
        manifest = getattr(fh._snapshot, "shipped_pages", None)
        aff = {rid: expected_pages_reused(prompt, stats[rid],
                                          manifest=manifest)
               for rid in ids}
        ids.sort(key=lambda rid: (
            stats[rid].inflight - self.affinity_weight * aff[rid],
            stats[rid].inflight, rid))
        return ids, aff

    def _place(self, fh: FleetHandle, raise_rejection: bool) -> bool:
        """Try to place ``fh`` on each candidate replica in score order
        — a snapshot-carrying handle is IMPORTED (progress intact), a
        fresh one submitted.  True on placement; False when every
        candidate rejected (or none exists) and ``raise_rejection`` is
        off.  Fresh submits, rejection probing, AND migration/failover
        re-placement all pass through here, so the affinity scorer
        covers every path a request can take onto a replica — a
        migrated request whose old replica published its pages via
        ``handoff`` scores the survivor holding them.  Called with the
        router lock held (engine submits take the engine's own state
        lock — lock order router -> engine, never reversed)."""
        remaining = None
        if fh.deadline is not None:
            remaining = fh.deadline - time.perf_counter()
            if remaining <= 0:
                fh._finalize("deadline_exceeded")
                return False
        candidates, affinity = self._candidates(fh)
        if not candidates:
            err = NoReplicaError("no live replica available")
            if raise_rejection:
                raise err
            fh._finalize("failed", error=fh.error or err)
            return False
        snap = fh._snapshot
        if snap is not None and fh.deadline is not None:
            # the fleet deadline stays authoritative across the
            # export->import gap (the snapshot froze its remaining
            # budget at export time); an engine-level default deadline
            # in the snapshot is left alone
            snap.deadline_remaining_s = remaining
        last: Optional[BaseException] = None
        for rid in candidates:
            eng = self._replicas[rid]
            try:
                if snap is not None:
                    # pre-warm: ship the exported radix pages into THIS
                    # candidate's pool first, so the import below
                    # radix-matches and skips the shipped prefill
                    # windows.  Purely best-effort — every wire failure
                    # shape ends with a plain re-prefill import.
                    self._ship_wire_pages(fh, eng, snap)
                    h = eng.import_request(
                        snap,
                        on_token=fh._attempt_stream(snap.stream_offset))
                else:
                    h = eng.submit(
                        fh.spec["prompt"], fh.spec["max_new_tokens"],
                        on_token=fh._attempt_stream(0),
                        deadline_s=remaining,
                        tenant=fh.spec["tenant"],
                        adapter_id=fh.spec["adapter_id"],
                        trace_id=fh.spec.get("trace_id"))
            except _REJECTIONS as e:
                last = e
                continue
            except Exception as e:
                # not backpressure: this request cannot be placed
                # anywhere (validation/compat error).  Surface it
                # instead of spinning forever in the sweep.
                if raise_rejection:
                    raise
                fh._finalize("failed", error=e)
                return False
            if snap is not None:
                # consumed: further failovers re-export from the new
                # replica, which now owns the freshest progress
                fh._snapshot = None
                fh._wire_records = None
                fh.migrations += 1
                fh.tokens_preserved += len(snap.generated)
                self._m_migrations.inc()
            fh._handle = h
            fh.replica_id = rid
            fh.attempts += 1
            self.placements.append((fh.rid, rid))
            self._m_placed[rid].inc()
            score = affinity.get(rid, 0)
            if score > 0:
                self._m_affinity_hits.inc()
            self._m_affinity_score.set(score)
            return True
        if raise_rejection:
            self._m_rejected.inc()
            raise last
        return False                    # stays pending; retried next step

    def _ship_wire_pages(self, fh: FleetHandle, eng: Engine,
                         snap: RequestSnapshot) -> None:
        """Ship an orphan's captured radix pages into candidate ``eng``
        before its import (``_place``).  Outcomes: pages adopted (the
        import skips their prefill windows), destination refused (0
        adopted — records kept for the next candidate), or the wire
        failed unrecoverably (``WireError`` — records dropped, this
        migration re-prefills: ``dttpu_router_wire_degraded_total``)."""
        if self.page_wire is None or not fh._wire_records:
            return
        try:
            adopted = self.page_wire.ship(fh._wire_records, eng, snap)
        except WireError as e:
            log.warning("page wire failed for fleet rid %d — "
                        "degrading to re-prefill migration: %s",
                        fh.rid, e)
            fh._wire_records = None
            self._m_wire_degraded.inc()
            return
        if adopted:
            self._m_wire_migrations.inc()

    # ----------------------------------------------------------- drive

    @property
    def busy(self) -> bool:
        with self._lock:
            live = list(self._replicas.values())
            pending = any(not fh.done for fh in self._inflight)
        return pending or any(eng.busy for eng in live)

    def step(self) -> bool:
        """One fleet tick: pump every live replica (a replica whose pump
        RAISES is declared dead and its in-flight requests rerouted),
        then sweep handles — finalize finished ones, resubmit failed or
        orphaned ones that still have deadline and retry budget.

        Engines are pumped WITHOUT the router lock (each engine's pump
        mutex serializes its ticks), so submit/cancel/stats on other
        threads never stall behind a device dispatch."""
        did = False
        plan = faults_lib.active()
        with self._lock:
            live = list(self._replicas.items())
        for rid, eng in live:
            try:
                if plan is not None:
                    plan.on_replica_step(rid)
                did = eng.step() or did
            except Exception as e:
                self._replica_down(rid, e)
                did = True
        with self._lock:
            did = self._sweep() or did
        return did

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Pump until every request reached a terminal status; with
        ``timeout_s``, stop at the budget and return False."""
        deadline = (None if timeout_s is None
                    else time.perf_counter() + timeout_s)
        while self.busy:
            if deadline is not None and time.perf_counter() >= deadline:
                return False
            self.step()
        return True

    def cancel(self, fh: FleetHandle) -> bool:
        """Abort one fleet request; False if already terminal."""
        with self._lock:
            if fh.done:
                return False
            handle, eng = fh._handle, self._replicas.get(fh.replica_id)
            fh._finalize("cancelled")
        if handle is not None and eng is not None:
            eng.cancel(handle)
        return True

    # ----------------------------------------------- rolling restarts

    def drain_replica(self, replica_id: int,
                      timeout_s: Optional[float] = None,
                      migrate: bool = True) -> bool:
        """Stop routing NEW traffic to ``replica_id`` and empty it.
        With ``migrate=True`` (the default) its in-flight requests are
        exported and re-placed on the survivors with their progress
        intact — the drain completes in one export/import round instead
        of waiting out every decode.  ``migrate=False`` keeps the
        legacy wait-drain (pump the fleet until the replica empties).
        Returns False on timeout (the replica stays draining — call
        again, ``remove_replica`` to force, or ``resume_replica`` to
        put it back in rotation)."""
        with self._lock:
            if replica_id not in self._replicas:
                raise KeyError(f"unknown replica {replica_id}")
            self._draining.add(replica_id)
            eng = self._replicas[replica_id]
            if migrate and not any(
                    r != replica_id and r not in self._draining
                    for r in self._replicas):
                # no survivor to migrate to: fall back to wait-drain
                # rather than failing the in-flight requests
                migrate = False
            victims = (self._victims_locked(replica_id) if migrate
                       else [])
        if migrate:
            # blocking clean exports: a draining replica's pump is
            # healthy, so each export just waits out the running tick
            self._export_and_orphan(victims, eng, timeout_s=None)
            with self._lock:
                self._sweep()       # re-place on survivors immediately
        deadline = (None if timeout_s is None
                    else time.perf_counter() + timeout_s)
        while True:
            with self._lock:
                waiting = any(fh.replica_id == replica_id
                              for fh in self._inflight if not fh.done)
            if not (eng.busy or waiting):
                break
            if deadline is not None and time.perf_counter() >= deadline:
                return False
            if not self.step():
                break
        return not eng.busy

    def resume_replica(self, replica_id: int) -> None:
        """Put a draining replica back into rotation (the rolling-
        restart counterpart of ``drain_replica`` when the restart is
        done in place)."""
        with self._lock:
            if replica_id not in self._replicas:
                raise KeyError(f"unknown replica {replica_id}")
            self._draining.discard(replica_id)

    def remove_replica(self, replica_id: int) -> Engine:
        """Take ``replica_id`` out of the fleet.  In-flight requests on
        it are exported and MIGRATED to the survivors with their
        progress intact (deadline/retry budget permitting).  Returns
        the detached engine (restart it, then ``add_replica`` it
        back)."""
        with self._lock:
            eng = self._replicas.pop(replica_id)
            self._draining.discard(replica_id)
            self._m_replicas.set(len(self._replicas))
            victims = self._victims_locked(replica_id)
        self._export_and_orphan(victims, eng,
                                timeout_s=self.export_timeout_s)
        with self._lock:
            self._sweep()
        return eng

    def quarantine_replica(self, replica_id: int,
                           reason: str = "unhealthy",
                           export_timeout_s: Optional[float] = None
                           ) -> Engine:
        """Pull a stuck-but-alive replica out of rotation (the fleet
        ``Watchdog``'s action; same vocabulary as the PR 5 checkpoint
        quarantine): the engine moves to ``router.quarantined`` with
        its ``reason``, its requests are exported — past a wedged pump
        if need be (``export_timeout_s``, default the router's) — and
        migrated to the survivors.  Returns the detached engine for
        inspection; ``add_replica`` re-admits it after repair."""
        with self._lock:
            if replica_id not in self._replicas:
                raise KeyError(f"unknown replica {replica_id}")
            eng = self._replicas.pop(replica_id)
            self._draining.discard(replica_id)
            self.quarantined[replica_id] = (eng, str(reason))
            self._m_replicas.set(len(self._replicas))
            victims = self._victims_locked(replica_id)
        timeout = (self.export_timeout_s if export_timeout_s is None
                   else export_timeout_s)
        self._export_and_orphan(victims, eng, timeout_s=timeout)
        with self._lock:
            self._sweep()
        return eng

    # ------------------------------------------------------- internals

    def _victims_locked(self, replica_id: int
                        ) -> List[Tuple[FleetHandle,
                                        Optional[RequestHandle]]]:
        """(handle, engine handle) pairs still pending on a replica —
        router lock held."""
        return [(fh, fh._handle) for fh in self._inflight
                if fh.replica_id == replica_id and not fh.done]

    def _export_and_orphan(self, victims, eng: Engine,
                           timeout_s: Optional[float],
                           error: Optional[BaseException] = None) -> None:
        """Export each victim's live state from ``eng`` and mark the
        fleet handle orphaned-with-snapshot (the sweep imports it on a
        survivor).  An export that fails — the request finished
        concurrently, or the engine is too far gone — falls back to
        cancel + raw resubmit, which the stream shim still keeps
        exactly-once.  Called WITHOUT the router lock (exports take the
        engine's pump/state locks; order router -> engine holds)."""
        for fh, h in victims:
            snap: Optional[RequestSnapshot] = None
            recs: Optional[list] = None
            if h is not None:
                if h.done:
                    continue            # sweep finalizes from the handle
                try:
                    snap = eng.export_request(h, timeout_s=timeout_s)
                except Exception:
                    snap = None
                if snap is None:
                    if h.done:
                        continue        # finished during the export race
                    eng.cancel(h)       # stop the doomed attempt
                elif self.page_wire is not None \
                        and getattr(snap, "shipped_pages", None):
                    # page-wire capture: host copies of the pages the
                    # export just handed off, while the source is still
                    # reachable.  Best-effort — a source too far gone
                    # to read simply ships nothing (re-prefill).
                    try:
                        recs = eng.export_wire_pages(
                            snap, timeout_s=timeout_s) or None
                    except Exception:
                        recs = None
            with self._lock:
                if fh.done:
                    continue
                if fh._ttft is None and h is not None:
                    fh._ttft = h.ttft_s
                fh._snapshot = snap
                fh._wire_records = recs
                if error is not None:
                    fh.error = error
                fh._handle = None       # orphaned: the sweep re-places
                fh.replica_id = None
                self._m_retries.inc()

    def _replica_down(self, replica_id: int, error: BaseException) -> None:
        with self._lock:
            eng = self._replicas.pop(replica_id, None)
            self._draining.discard(replica_id)
            self._m_down.inc()
            self._m_replicas.set(len(self._replicas))
            victims = self._victims_locked(replica_id)
        if eng is not None:
            # the pump raised but the engine's HOST state is intact (the
            # scheduler's locks were released with the failing tick), so
            # in-flight progress is still exportable — the kill loses a
            # replica, not the decode work on it
            self._export_and_orphan(victims, eng,
                                    timeout_s=self.export_timeout_s,
                                    error=error)

    def _sweep(self) -> bool:
        """Called with the router lock held."""
        did = False
        still: List[FleetHandle] = []
        for fh in self._inflight:
            if fh.done:
                continue
            h = fh._handle
            if h is None:               # orphaned (death/removal/retry)
                did = True
                self._place(fh, raise_rejection=False)
            elif h.done:
                did = True
                if h.status == "failed" and fh.retries_left > 0 \
                        and self._deadline_ok(fh):
                    fh.retries_left -= 1
                    fh._handle = None
                    fh.replica_id = None
                    self._m_retries.inc()
                    self._place(fh, raise_rejection=False)
                elif h.status == "failed":
                    fh._finalize("failed", error=h.error)
                else:                   # ok | deadline_exceeded | cancelled
                    fh._finalize(h.status, error=h.error)
            if not fh.done:
                still.append(fh)
        self._inflight = still
        return did

    @staticmethod
    def _deadline_ok(fh: FleetHandle) -> bool:
        return fh.deadline is None or time.perf_counter() < fh.deadline
