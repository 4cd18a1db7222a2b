"""Engine façade: submit() -> handle, streaming callbacks, obs metrics.

The thin public layer over ``serve.scheduler.SlotScheduler``::

    from distributed_tensorflow_tpu import serve

    eng = serve.Engine(model, params, num_slots=8, max_len=256,
                       prefill_chunk=32)
    h = eng.submit(prompt_ids, max_new_tokens=64,
                   on_token=lambda toks: print(toks))
    eng.drain()                     # or pump eng.step() yourself
    h.tokens                        # the generated ids (incl. EOS)

The engine is synchronous — the caller pumps ``step()``/``drain()``
(examples/serve_gpt.py ``--engine`` and ``bench.py --config=gpt_serve``
are the reference drivers); a thread wrapping ``drain()`` gives a
background server loop when needed.

Graceful degradation (docs/RESILIENCE.md): ``max_queue_depth`` bounds
admission — a full queue rejects with ``QueueFullError`` instead of
buffering unbounded work; per-request ``deadline_s`` retires requests
that would otherwise decode forever (status ``deadline_exceeded``);
``drain(timeout_s=...)`` bounds shutdown; and a poisoned request (a
raising ``on_token`` callback, an injected decode fault) fails ONLY its
own handle — the scheduler tick loop and every other slot's bit-exact
stream survive.

Metrics (``registry=`` — defaults to the process registry served at the
existing ``/metrics`` endpoint, docs/OBSERVABILITY.md):

* ``dttpu_serve_queue_depth`` / ``dttpu_serve_active_slots`` gauges,
* ``dttpu_serve_ttft_seconds`` histogram (submit -> first token on host),
* ``dttpu_serve_request_decode_seconds`` histogram (first -> last token),
* ``dttpu_serve_tokens_total`` / ``dttpu_serve_requests_total`` counters
  (rates are the scraper's job, e.g. ``rate(...[1m])``),
* ``dttpu_serve_rejected_total`` / ``dttpu_serve_deadline_expired_total``
  / ``dttpu_serve_failed_total`` counters — the degradation triad.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Union

import numpy as np

from ..obs import metrics as metrics_lib
from ..obs import reqtrace
from .adapters import AdapterTable
from .scheduler import (EngineStats, QueueFullError, Request,
                        RequestSnapshot, SlotScheduler)

__all__ = ["DrainResult", "Engine", "EngineStats", "QueueFullError",
           "RequestHandle", "RequestSnapshot", "ServeMetrics"]


class ServeMetrics:
    """obs wiring for the scheduler's duck-typed metrics sink."""

    # TTFT is queue-position dependent; sub-ms to minutes, so a wide grid
    _TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

    def __init__(self, registry: Optional[metrics_lib.Registry] = None):
        reg = registry if registry is not None else metrics_lib.REGISTRY
        self.registry = reg
        self.queue_depth = reg.gauge(
            "dttpu_serve_queue_depth",
            "Requests queued, not yet prefilling.")
        self.active_slots = reg.gauge(
            "dttpu_serve_active_slots",
            "Slots holding an in-flight request.")
        self.ttft = reg.histogram(
            "dttpu_serve_ttft_seconds",
            "Submit to first generated token on the host.",
            buckets=self._TTFT_BUCKETS)
        self.request_decode = reg.histogram(
            "dttpu_serve_request_decode_seconds",
            "First to last generated token, per request.")
        self.tokens = reg.counter(
            "dttpu_serve_tokens_total",
            "Generated tokens delivered to callers.")
        self.requests = reg.counter(
            "dttpu_serve_requests_total",
            "Requests submitted to the engine.")
        self.rejected = reg.counter(
            "dttpu_serve_rejected_total",
            "Requests rejected at submit (queue at max_queue_depth).")
        self.deadline_expired = reg.counter(
            "dttpu_serve_deadline_expired_total",
            "Requests retired past their deadline_s budget.")
        self.failed = reg.counter(
            "dttpu_serve_failed_total",
            "Requests failed individually (callback/decode error) "
            "without killing the scheduler.")
        # live migration (docs/RESILIENCE.md): where imported requests'
        # streams resume — the offset IS the decode work the snapshot
        # salvaged, so the distribution doubles as a preserved-work view
        self.stream_resume = reg.histogram(
            "dttpu_serve_stream_resume_offset",
            "Stream offset (tokens already delivered on the source "
            "engine) at which an imported request resumed.",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                     256.0, 512.0))
        # page-pool series (serve/pages.py) — rendered from the same
        # Engine.stats() snapshot as the gauges above, so there is
        # exactly ONE bookkeeping source
        self.pages_free = reg.gauge(
            "dttpu_serve_pages_free",
            "KV-cache pool pages on the free list.")
        self.pages_per_request = reg.gauge(
            "dttpu_serve_pages_per_request",
            "Average pages held per in-flight request "
            "(shared prefix pages count once per holder).")
        self.prefix_hits = reg.counter(
            "dttpu_serve_prefix_hits_total",
            "Requests that mapped radix-cached prefix pages and "
            "skipped their prefill windows.")
        self.prefix_evictions = reg.counter(
            "dttpu_serve_prefix_evictions_total",
            "Radix-cached prefix pages reclaimed by LRU eviction "
            "under allocation pressure.")
        # recurrent-state snapshots (serve/pages.py; flat zero for a
        # model that caches keys and values only)
        self.state_snapshots = reg.counter(
            "dttpu_serve_state_snapshots_total",
            "Recurrent-state snapshots taken (prompt end, turn end, "
            "where a prompt met a cached chain).")
        self.state_restores = reg.counter(
            "dttpu_serve_state_restores_total",
            "Admissions whose prefill resumed from a state snapshot.")
        self.state_snapshots_evicted = reg.counter(
            "dttpu_serve_state_snapshots_evicted_total",
            "State snapshots evicted: every row taken, or their chain "
            "reclaimed.")
        self.state_snapshot_bytes = reg.gauge(
            "dttpu_serve_state_snapshot_bytes",
            "Bytes of recurrent state held in snapshots.")
        self.kv_pool_bytes = reg.gauge(
            "dttpu_serve_kv_pool_bytes",
            "Logical bytes of the page pool's K/V leaves.")
        self.kv_pool_tiled_bytes = reg.gauge(
            "dttpu_serve_kv_pool_tiled_bytes",
            "Bytes the page pool's K/V leaves take once tiled on the "
            "TPU.")
        self.weights_relaid_bytes = reg.gauge(
            "dttpu_serve_weights_relaid_bytes",
            "Bytes of weight leaves the scheduler placed in another format "
            "than the one they came in: the one its decode program reads.")
        # what the pump dispatched (scheduler.py counts each where it
        # happens): over ticks, windows and decode steps a tick
        self.ticks = reg.counter(
            "dttpu_serve_ticks_total", "Scheduler ticks completed.")
        self.prefill_windows = reg.counter(
            "dttpu_serve_prefill_windows_total",
            "Prefill windows run (mid windows and the admitting last "
            "window).")
        self.prefill_dispatches = reg.counter(
            "dttpu_serve_prefill_dispatches_total",
            "Prefill window programs dispatched: one holds the windows "
            "a tick dispatches together, up to the ladder's largest row "
            "count.")
        self.prefill_rows_padded = reg.counter(
            "dttpu_serve_prefill_rows_padded_total",
            "Padding rows those programs carried (a group is padded up "
            "to the next row count of the ladder).")
        self.decode_steps = reg.counter(
            "dttpu_serve_decode_steps_total",
            "Decode steps dispatched (tick_steps per decode dispatch).")
        self.decode_pages_walked = reg.counter(
            "dttpu_serve_decode_pages_walked_total",
            "Page-table entries the decode steps read: with the "
            "page-walk kernel, the pages live slots' tokens lie on.")
        self.decode_pages_table = reg.counter(
            "dttpu_serve_decode_pages_table_total",
            "Page-table entries those steps' tables hold (steps x slots "
            "x pages a slot): what a full-table read takes.")
        self.decode_window_tokens = reg.counter(
            "dttpu_serve_decode_window_tokens_total",
            "Keys the window layers' decode reads covered (models with "
            "sliding-window layers): min(position + 1, window) a live "
            "slot's step.")
        self.admit_backpressure = reg.counter(
            "dttpu_serve_admit_backpressure_total",
            "Admissions bounced back to the queue: every adapter row "
            "or pool page was pinned by an in-flight request.")
        # prefix-affinity federation (obs/federate.py): the pool's
        # hot-chain fingerprint rendered as labeled gauges so a
        # cross-host router can score prefix affinity from SCRAPED
        # stats — ``chain`` is the radix chain hash (hex; bounded by
        # ``pages.FINGERPRINT_K``, so cardinality is a config knob, not
        # traffic-dependent), the value is cached tokens.  Page size
        # rides along: remote scorers must chunk prompts identically.
        self.page_size_gauge = reg.gauge(
            "dttpu_serve_page_size",
            "KV page-pool page size in tokens.")
        self._chain_gauges: dict = {}
        # counters render by delta against the stats() snapshot (the
        # exposition forbids decreasing counters; stats are monotonic)
        self._by_delta = [
            [self.prefix_hits, "prefix_hits_total", 0],
            [self.prefix_evictions, "prefix_evictions_total", 0],
            [self.state_snapshots, "state_snapshots_total", 0],
            [self.state_restores, "state_restores_total", 0],
            [self.state_snapshots_evicted,
             "state_snapshots_evicted_total", 0],
            [self.ticks, "ticks_completed", 0],
            [self.prefill_windows, "prefill_windows_total", 0],
            [self.prefill_dispatches, "prefill_dispatches_total", 0],
            [self.prefill_rows_padded, "prefill_rows_padded_total", 0],
            [self.decode_steps, "decode_steps_total", 0],
            [self.decode_pages_walked, "decode_pages_walked_total", 0],
            [self.decode_pages_table, "decode_pages_table_total", 0],
            [self.decode_window_tokens, "decode_window_tokens_total", 0],
            [self.admit_backpressure, "admit_backpressure_total", 0]]
        # an expert layer's router statistics (scheduler.py
        # ``_absorb_counters``): created at first sight of a pick, so a
        # model without experts exposes none of these series
        self._router: dict = {}
        # per-tenant series, created lazily at first sight of a tenant
        # (cardinality = the tenant set, which admission policy bounds)
        self._tenant_tokens: dict = {}
        self._tenant_inflight: dict = {}
        self._tenant_rejected: dict = {}

    def tenant_rejected(self, tenant: str):
        c = self._tenant_rejected.get(tenant)
        if c is None:
            c = self._tenant_rejected[tenant] = self.registry.counter(
                "dttpu_tenant_rejected_total",
                "Requests rejected by per-tenant quota at admission.",
                labels={"tenant": tenant})
        return c

    def _tenant_gauge(self, tenant: str):
        g = self._tenant_inflight.get(tenant)
        if g is None:
            g = self._tenant_inflight[tenant] = self.registry.gauge(
                "dttpu_tenant_inflight",
                "In-flight requests (queued+prefilling+active), "
                "by tenant.", labels={"tenant": tenant})
        return g

    _ROUTER_SERIES = (
        ("router_picks_total",
         "Router picks made for real tokens (top-k a token and expert "
         "layer), prefill and decode."),
        ("router_picks_identity_total",
         "Router picks that fell on identity (zero-compute) experts."),
        ("router_picks_held_total",
         "Router picks that fell on FFN experts this engine holds; the "
         "rest fell on experts held elsewhere and add nothing here."))

    def _router_counters(self, stats: EngineStats) -> None:
        """The router's counters by delta, and the tokens each held expert
        received as ``dttpu_serve_expert_tokens_total{layer, expert}``
        (cardinality: expert layers x experts held, fixed at build)."""
        series = [(field, field, help_text, None, getattr(stats, field))
                  for field, help_text in self._ROUTER_SERIES]
        series += [((layer, expert), "expert_tokens_total",
                    "Tokens a held FFN expert received, by expert layer "
                    "and held expert.",
                    {"layer": str(layer), "expert": str(expert)}, n)
                   for layer, row in enumerate(stats.expert_tokens_total)
                   for expert, n in enumerate(row)]
        for key, name, help_text, labels, now in series:
            entry = self._router.get(key)
            if entry is None:
                entry = self._router[key] = [self.registry.counter(
                    f"dttpu_serve_{name}", help_text, labels=labels), 0]
            if now > entry[1]:
                entry[0].inc(now - entry[1])
                entry[1] = now

    # -- scheduler hooks --------------------------------------------------

    def submitted(self, req: Request) -> None:
        self.requests.inc()

    def admitted(self, req: Request) -> None:
        if req.ttft_s is not None:
            self.ttft.observe(req.ttft_s)

    def emitted(self, req: Request, n: int) -> None:
        self.tokens.inc(n)
        c = self._tenant_tokens.get(req.tenant)
        if c is None:
            c = self._tenant_tokens[req.tenant] = self.registry.counter(
                "dttpu_tenant_tokens_total",
                "Generated tokens delivered, by tenant.",
                labels={"tenant": req.tenant})
        c.inc(n)

    def finished(self, req: Request) -> None:
        if req.ttft_s is None:
            return
        if req.first_token_time is not None and req.finish_time is not None:
            self.request_decode.observe(
                req.finish_time - req.first_token_time)

    def aborted(self, req: Request, status: str) -> None:
        if status == "deadline_exceeded":
            self.deadline_expired.inc()
        elif status == "failed":
            self.failed.inc()

    def depth(self, stats: EngineStats) -> None:
        """Render the gauges from the scheduler's ``stats()`` snapshot —
        the one bookkeeping source (no separate counters here; the
        paged-KV counters advance by snapshot delta)."""
        self.queue_depth.set(stats.queued)
        self.active_slots.set(stats.active)
        self.pages_free.set(stats.pages_free)
        self.state_snapshot_bytes.set(stats.state_snapshot_bytes)
        self.kv_pool_bytes.set(stats.kv_pool_bytes)
        self.kv_pool_tiled_bytes.set(stats.kv_pool_tiled_bytes)
        self.weights_relaid_bytes.set(stats.weights_relaid_bytes)
        self.pages_per_request.set(stats.pages_per_request)
        for entry in self._by_delta:
            counter, field, last = entry
            now = getattr(stats, field)
            if now > last:
                counter.inc(now - last)
                entry[2] = now
        if stats.router_picks_total:
            self._router_counters(stats)
        for tenant, n in stats.inflight_per_tenant.items():
            self._tenant_gauge(tenant).set(n)
        for tenant, g in self._tenant_inflight.items():
            if tenant not in stats.inflight_per_tenant:
                g.set(0)
        self.page_size_gauge.set(stats.page_size)
        for chain, tokens in stats.prefix_fingerprint.items():
            key = chain.hex()
            g = self._chain_gauges.get(key)
            if g is None:
                g = self._chain_gauges[key] = self.registry.gauge(
                    "dttpu_serve_prefix_chain_tokens",
                    "Radix-cached tokens under this chain hash — the "
                    "pool's hot-chain fingerprint, federated for "
                    "cross-host prefix-affinity routing.",
                    labels={"chain": key})
            g.set(tokens)
        live = {c.hex() for c in stats.prefix_fingerprint}
        for key, g in self._chain_gauges.items():
            if key not in live:
                g.set(0)             # evicted chain: renders 0, and the
                #                      federation layer drops 0-chains


class RequestHandle:
    """Caller-facing view of one request."""

    def __init__(self, req: Request, engine: "Engine"):
        self._req = req
        self._engine = engine

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def tokens(self) -> List[int]:
        """Generated ids so far (includes the EOS token when one fired)."""
        return list(self._req.tokens)

    @property
    def done(self) -> bool:
        return self._req.done.is_set()

    @property
    def tenant(self) -> str:
        return self._req.tenant

    @property
    def adapter_id(self) -> Optional[str]:
        return self._req.adapter_id

    @property
    def status(self) -> str:
        """``pending`` while in flight; terminal: ``ok`` |
        ``deadline_exceeded`` | ``failed`` | ``cancelled`` |
        ``migrated`` (exported as a ``RequestSnapshot`` — the request
        continues wherever the snapshot is imported).  Non-ok handles
        keep whatever tokens were delivered before the abort."""
        return self._req.status

    @property
    def error(self) -> Optional[BaseException]:
        """The isolating failure for status ``failed``; None otherwise."""
        return self._req.error

    @property
    def ttft_s(self) -> Optional[float]:
        return self._req.ttft_s

    @property
    def decode_s(self) -> Optional[float]:
        if self._req.first_token_time is None \
                or self._req.finish_time is None:
            return None
        return self._req.finish_time - self._req.first_token_time

    @property
    def critpath(self) -> Optional[Dict[str, float]]:
        """The finished critical-path breakdown (``obs.critpath``):
        exclusive phase seconds summing to ``e2e_s``, plus
        ``interference_share``.  None while in flight, or when no
        critpath ledger was active at submit."""
        cp = self._req.critpath
        return dict(cp) if cp is not None else None

    def result(self) -> List[int]:
        """Pump the engine until this request finishes; return its
        tokens.  (Synchronous engine: waiting IS driving.)"""
        while not self.done:
            if not self._engine.step():
                break
        return self.tokens


class DrainResult:
    """Outcome of ``Engine.drain``: truthy iff every request finished
    in place.  A timed-out drain no longer strands in-flight requests
    in limbo — the stragglers are EXPORTED (``exported``: their
    ``RequestSnapshot``s, the engine left idle) so the caller can
    migrate them to another engine, ``import_request`` them back after
    the restart, or drop them deliberately.  ``bool(result)`` keeps the
    old ``drain() -> bool`` call sites working."""

    __slots__ = ("completed", "exported")

    def __init__(self, completed: bool, exported=()):
        self.completed = bool(completed)
        self.exported: List[RequestSnapshot] = list(exported)

    def __bool__(self) -> bool:
        return self.completed

    def __repr__(self) -> str:
        return (f"DrainResult(completed={self.completed}, "
                f"exported={len(self.exported)})")


class Engine:
    """Continuous-batching serving engine over one jitted decode step.

    K/V storage is the page pool (serve/pages.py): slots hold
    fixed-size pool pages through per-slot page tables instead of full
    ``[max_len]`` stripes — memory scales with actual request lengths,
    requests sharing a prompt prefix map the same read-only
    radix-cached pages and skip those prefill windows entirely, and
    allocation/sharing/eviction never recompile the hot executables.
    ``page_size``/``num_pages`` tune the pool (defaults: the largest
    divisor of ``max_len`` <= 16, and ``max_len`` tokens for every
    slot).  Output tokens are bit-identical to ``GPT.generate``'s
    (tests/test_pages.py).

    Args mirror ``SlotScheduler`` (num_slots, max_len, prefill_chunk,
    tick_steps, temperature/top_k/top_p, eos_id/pad_id, rng,
    page_size/num_pages) plus:

      registry: obs metrics registry to record into (default: the
        process registry ``obs.metrics.REGISTRY`` — served by any
        ``MetricsServer``/``Telemetry`` endpoint already running).
      default_max_new_tokens: ``submit()`` budget when none is given.
      max_queue_depth: admission bound — ``submit`` raises
        ``QueueFullError`` (and bumps ``dttpu_serve_rejected_total``)
        when this many requests are already queued ahead of prefill.
        ``None`` (default) keeps the old accept-everything behavior.
      default_deadline_s: ``submit()`` deadline when none is given
        (``None`` = no deadline).
      tenancy: a per-tenant admission policy (``fleet.tenancy.
        TenantPolicy``): quota checks run at ``submit`` (raising the
        policy's quota error + ``dttpu_tenant_rejected_total``) and the
        admission queue becomes the policy's deficit-weighted fair
        queue, so one tenant's burst cannot starve others.
      adapter_capacity / adapter_rank: > 0 builds a fixed-capacity LoRA
        ``AdapterTable`` (serve/adapters) — ``load_adapter()`` +
        ``submit(adapter_id=...)`` then hot-swap per-request adapters
        with zero recompiles; ``adapter_id=None`` requests ride the
        reserved zero row and stay token-identical to an adapter-free
        engine.
    """

    def __init__(self, model, params, *,
                 registry: Optional[metrics_lib.Registry] = None,
                 default_max_new_tokens: int = 64,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 tenancy=None,
                 adapter_capacity: int = 0,
                 adapter_rank: int = 8,
                 **scheduler_kwargs):
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1; got {max_queue_depth}")
        self.metrics = ServeMetrics(registry)
        self.default_max_new_tokens = default_max_new_tokens
        self.max_queue_depth = max_queue_depth
        self.default_deadline_s = default_deadline_s
        self.tenancy = tenancy
        self.adapters = (AdapterTable(model, adapter_capacity,
                                      adapter_rank,
                                      registry=self.metrics.registry)
                         if adapter_capacity else None)
        queue = tenancy.make_queue() if tenancy is not None else None
        # admission (queue depth + tenant quota) lives INSIDE the
        # scheduler, under its state lock, so concurrent submitters get
        # one atomic decision instead of check-then-enqueue races
        self.scheduler = SlotScheduler(model, params,
                                       metrics=self.metrics,
                                       queue=queue,
                                       adapters=self.adapters,
                                       max_queue_depth=max_queue_depth,
                                       tenancy=tenancy,
                                       **scheduler_kwargs)

    # ----------------------------------------------------------- intake

    def stats(self) -> EngineStats:
        """Lock-cheap load snapshot (queue depth, prefilling, active
        slots, per-tenant in-flight, pump heartbeat) — the router's
        placement signal, the watchdog's health signal, and the source
        the serve gauges render from."""
        return self.scheduler.stats()

    @property
    def chaos_tag(self) -> int:
        """Identity for engine-targeted fault kinds (stall_tick /
        wedge_replica); the fleet Router stamps the replica id here."""
        return self.scheduler.chaos_tag

    @chaos_tag.setter
    def chaos_tag(self, tag: int) -> None:
        self.scheduler.chaos_tag = int(tag)

    def load_adapter(self, adapter_id: str, adapter) -> None:
        """Register a LoRA adapter (``GPT.init_lora`` layout) for
        ``submit(adapter_id=...)``.  Host-side copy now; the device
        splice happens lazily at first use (and re-splices in place if
        the id is already resident — the hot-update path)."""
        if self.adapters is None:
            raise ValueError("engine built without adapters "
                             "(adapter_capacity=0)")
        self.adapters.register(adapter_id, adapter)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               on_token: Optional[Callable[[List[int]], None]] = None,
               deadline_s: Optional[float] = None,
               tenant: str = "default",
               adapter_id: Optional[str] = None,
               trace_id: Optional[str] = None) -> RequestHandle:
        """Queue one prompt ([plen] ids, any length per request) ->
        handle.  ``on_token`` streams each delivered token batch.
        Raises ``QueueFullError`` at ``max_queue_depth`` — shed load at
        the door instead of queueing work that will miss every SLO.
        With a ``tenancy`` policy, ``tenant`` is checked against its
        quotas here too (the policy's quota error propagates);
        ``adapter_id`` selects a loaded LoRA adapter.  ``trace_id``
        carries a caller-minted request trace id (the fleet router's);
        when None and a tracer is active, one is minted HERE — the
        engine is the front door for direct submits."""
        new_tokens = max_new_tokens or self.default_max_new_tokens
        if trace_id is None:
            trace_id = reqtrace.mint()
        try:
            req = self.scheduler.submit(
                prompt, new_tokens,
                on_token=on_token,
                deadline_s=(deadline_s if deadline_s is not None
                            else self.default_deadline_s),
                tenant=tenant, adapter_id=adapter_id,
                trace_id=trace_id)
        except QueueFullError:
            self.metrics.rejected.inc()
            raise
        except (ValueError, KeyError):
            raise                    # validation, not admission policy
        except Exception:
            if self.tenancy is not None:
                self.metrics.tenant_rejected(tenant).inc()
            raise
        return RequestHandle(req, self)

    # ------------------------------------------------------------ drive

    @property
    def busy(self) -> bool:
        return self.scheduler.busy

    def inflight_trace_ids(self) -> List[str]:
        """Trace ids of every in-flight request — the fleet watchdog's
        pre-quarantine forensics capture (``obs.reqtrace``)."""
        return self.scheduler.inflight_trace_ids()

    def inflight_critpath(self) -> Dict[str, dict]:
        """Live critical-path breakdowns keyed by trace_id
        (``obs.critpath``) — the watchdog dumps a quarantine victim's
        phase budget from these next to its goodput split."""
        return self.scheduler.inflight_critpath()

    def step(self) -> bool:
        """One scheduler tick; False when fully idle."""
        return self.scheduler.step()

    def drain(self, timeout_s: Optional[float] = None) -> DrainResult:
        """Run until every submitted request has finished.  Returns a
        truthy ``DrainResult`` on a complete drain.  With ``timeout_s``
        the drain is LOSSLESS even when the budget runs out: instead of
        returning False with requests stranded in limbo (the old
        contract), the stragglers are exported as ``RequestSnapshot``s
        — their handles end ``migrated``, the engine is left idle, and
        ``result.exported`` carries the snapshots for
        ``import_request`` here or on another engine."""
        if timeout_s is None:
            self.scheduler.drain()
            return DrainResult(True)
        deadline = time.perf_counter() + timeout_s
        while self.scheduler.busy:
            if time.perf_counter() >= deadline:
                snaps = self.scheduler.export_all()
                return DrainResult(not snaps, snaps)
            self.scheduler.step()
        return DrainResult(True)

    def cancel(self, handle: RequestHandle) -> bool:
        """Abort one request (status ``cancelled``); False if it already
        finished."""
        return self.scheduler.cancel(handle._req)

    # ------------------------------------------------- live migration

    def export_request(self, handle: Union[RequestHandle, int],
                       timeout_s: Optional[float] = None
                       ) -> RequestSnapshot:
        """Export one in-flight request (a handle or its rid) as a
        portable ``RequestSnapshot`` and retire it here with status
        ``migrated`` — no device buffers cross: the destination's
        ``import_request`` rebuilds the KV deterministically and the
        stream resumes at the snapshot's offset (docs/RESILIENCE.md).
        ``timeout_s`` bounds the wait for the pump mutex — pass one
        when the pump may be wedged (watchdog quarantine); the forced
        export is marked ``clean=False``.  Raises ``KeyError`` for an
        unknown rid, ``RuntimeError`` for a request already terminal."""
        if isinstance(handle, RequestHandle):
            req = handle._req
        else:
            req = self.scheduler.find(int(handle))
            if req is None:
                raise KeyError(f"no in-flight request with rid {handle}")
        return self.scheduler.export(req, timeout_s=timeout_s)

    def export_inflight(self, timeout_s: Optional[float] = None
                        ) -> List[RequestSnapshot]:
        """Export EVERY in-flight request (rid order), leaving the
        engine idle — the quarantine/shutdown bulk path."""
        return self.scheduler.export_all(timeout_s=timeout_s)

    def export_wire_pages(self, snap: RequestSnapshot,
                          timeout_s: Optional[float] = None) -> list:
        """Page-wire sender capture (fleet/pagewire.py): read the
        radix-cached KV pages behind ``snap``'s shipped-pages manifest
        off this engine's device — ``[(chunk_index, chain_hash,
        payload)]`` ready for ``PageWire.ship``.  Call AFTER
        ``export_request``: the export's lease handoff published the
        pages into the radix tree, where they stay readable (and
        evictable — whatever was evicted since simply doesn't ship).
        Returns ``[]`` for a snapshot without a manifest, a pool with
        its prefix cache off, or a pump busy past ``timeout_s`` — the migration then
        proceeds as plain re-prefill."""
        manifest = getattr(snap, "shipped_pages", None)
        if not manifest:
            return []
        prompt = snap.prompt
        generated = [int(t) for t in snap.generated]
        ctx = (np.concatenate([np.asarray(prompt, np.int32).reshape(-1),
                               np.asarray(generated, np.int32)])
               if generated
               else np.asarray(prompt, np.int32).reshape(-1))
        # the manifest's coverage is authoritative: ship at most the
        # tokens the export actually handed off
        return self.scheduler.export_chain_pages(
            ctx[:int(manifest[-1][1])], timeout_s=timeout_s)

    def import_wire_pages(self, snap: RequestSnapshot, records,
                          timeout_s: Optional[float] = 5.0) -> int:
        """Page-wire receiver splice: adopt shipped pages for ``snap``
        into this engine's pool BEFORE ``import_request`` admits it, so
        the resumed request's prefill radix-matches the shipped chain
        and skips those windows.  Returns chunks adopted (0 = nothing
        usable — incompatible page size/layout, pool pressure, or pump
        busy past ``timeout_s``; the import just re-prefills).  The
        default timeout is finite because the fleet router calls this
        toward a POSSIBLY-unhealthy destination — a wedged pump must
        degrade the transfer, not deadlock the router."""
        if not getattr(snap, "page_size", 0) \
                or snap.page_size != getattr(self.scheduler,
                                             "page_size", 0):
            return 0                 # chunking differs: chains alien
        prompt = np.asarray(snap.prompt, np.int32).reshape(-1)
        generated = [int(t) for t in snap.generated]
        ctx = (np.concatenate([prompt,
                               np.asarray(generated, np.int32)])
               if generated else prompt)
        return self.scheduler.import_wire_pages(ctx, records,
                                                timeout_s=timeout_s)

    def import_request(self, snap: RequestSnapshot,
                       on_token: Optional[Callable[[List[int]], None]]
                       = None) -> RequestHandle:
        """Resume an exported request here -> handle.  Admission is the
        same door ``submit`` uses (queue depth, tenant quota — charged
        at the snapshot's REMAINING budget) and the prefill/decode run
        through the same three hot executables, so importing never
        recompiles.  ``on_token`` streams only tokens BEYOND the
        snapshot's ``stream_offset`` (callbacks are not serializable,
        so the caller re-attaches one); the handle's ``tokens`` are the
        full sequence, pre-seeded with the snapshot's."""
        try:
            req = self.scheduler.import_snapshot(snap, on_token=on_token)
        except QueueFullError:
            self.metrics.rejected.inc()
            raise
        except (ValueError, KeyError):
            raise                    # validation, not admission policy
        except Exception:
            if self.tenancy is not None:
                self.metrics.tenant_rejected(str(snap.tenant)).inc()
            raise
        self.metrics.stream_resume.observe(float(snap.stream_offset))
        return RequestHandle(req, self)

    def generate_batch(self, prompts,
                       max_new_tokens: Optional[int] = None
                       ) -> List[List[int]]:
        """Convenience: submit a list of prompts, drain, return each
        request's generated tokens (in submission order).

        If a mid-list ``submit`` raises (validation, queue full), the
        already-submitted handles are cancelled before the error
        propagates — the seed version drained anyway and left them
        permanently pending."""
        handles = []
        try:
            for p in prompts:
                handles.append(self.submit(p, max_new_tokens))
        except BaseException:
            for h in handles:
                self.scheduler.cancel(h._req)
            raise
        self.drain()
        return [h.tokens for h in handles]
