"""Slot scheduler: admission, chunked prefill, decode ticks, retirement.

The control plane of the continuous-batching engine (docs/SERVING.md).
All device work goes through THREE jitted functions built once at
construction — a batch of mid-prefill windows, a batch of windows of
which some are last ones (+ first-token sample + slot arm, a row), and
the K-step decode tick — each with fully static shapes: the two window
programs at the few row counts of a ladder (``_rungs``), every one of
them compiled AT construction, so admitting and retiring requests never
recompiles anything whatever group sizes arrive (pinned by
tests/test_serve.py under the runtime sanitizer, and warn-checked by
``bench.py --config=gpt_serve``).

One storage layout: the page pool (serve/pages.py) maps slot columns
to fixed-size pool pages through per-slot page tables — prefill writes
straight into the request's leased pages, shared prompt prefixes map
the same read-only radix-cached pages and skip their prefill windows,
and page allocation/eviction is host bookkeeping handed to the same
three executables as traced arguments.

Request lifecycle::

    QUEUED --admission--> PREFILLING --last window--> ACTIVE --> FINISHED
                (free slot)   (chunked)    (first token)  (EOS/budget)

Any in-flight state is also EXPORTABLE as a portable ``RequestSnapshot``
(``export``/``import_snapshot`` — live migration, docs/RESILIENCE.md):
the destination re-enters the same lifecycle with its prefill context
set to ``prompt + generated`` and its token list pre-seeded, so decode
resumes where the source stopped through the SAME three executables.

* **Chunked prefill**: the prompt is RIGHT-padded to a multiple of
  ``prefill_chunk`` and streamed through ``GPT.decode_window_paged`` one
  fixed-width window per tick, straight into the pages the request
  leased at admission — so a long prompt never stalls in-flight decodes
  for more than one window per tick, and every prompt length reuses the
  same two executables.  The windows a tick dispatches together — every
  prefilling request's one — go to the device as ONE ``[rows, W]``
  program (groups of at most ``_rungs[-1]`` rows, padded up to the next
  rung): they read the weights once, not once a request.
  Free slots are filled eagerly: up to one prefill per free slot runs
  concurrently (each advancing one window per tick), so a burst of
  arrivals admits at slot rate, not one request per tick.  The pad
  columns are written but never flagged valid, so they are dead weight,
  not state.  The last window gathers logits at the prompt's real final
  position, samples the first token, and arms the slot's column state
  in the SAME dispatch (time-to-first-token stops when that token
  reaches the host).
* **Decode tick**: ``tick_steps`` decode steps scanned inside ONE
  dispatch (the same dispatch-amortization lever as
  ``train.make_multi_train_step``), sampling in-graph and freezing rows
  as they finish via ``ops.decoding.finish_step`` — finished rows emit
  ``pad`` and stop advancing, exactly the generate() semantics.  Tokens
  stream to the host once per tick, so retirement/admission decisions
  lag at most one tick.
* **Retirement**: EOS (when configured) or the request's token budget.
  A retired slot is immediately admissible; the slot's validity window
  (the cache's ``start_col``/``write_col``) and its own page-table row
  guarantee the newcomer never attends the departed request's K/V.

Exactness contract: with one request in flight the emitted tokens equal
``GPT.generate``'s greedy output token-for-token, and admission
mid-decode leaves other slots' logits bit-identical — see
``GPT.decode_step_slots_paged`` and tests/test_pages.py.

Thread-safety contract (dtlint DT3xx + tests/test_thread_safety.py):
``submit``/``cancel``/``stats`` may run on any thread concurrently with
the pump.  Two locks, strictly ordered pump -> state:

* ``_pump_lock`` serializes ticks — device state (``_cache``/
  ``_tokens``/``_finished``/``_remaining``/``_key``) is touched ONLY
  with the pump mutex held, so donation in the hot executables is
  race-free and concurrent ``step()`` callers simply queue behind the
  running tick;
* ``_lock`` guards host bookkeeping (queue, slots table, prefill list,
  page tables, tenant counters) in short critical sections that never
  span a device dispatch or a user callback.

Cross-thread ``cancel`` never touches device arrays: it marks the row
in ``_stale_rows`` (the pump freezes it at the next tick) and moves an
in-flight prefill to the orphan list (the pump releases its lease).  Token
delivery and terminal transitions are queued in tick order and flushed
at the END of the tick — holding the pump mutex but NOT the state lock,
so a slow ``on_token`` callback never blocks a concurrent ``submit``.
Callbacks run on the pumping thread and must not re-enter ``step()``.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..analysis import graph as graph_lib
from ..obs import critpath as critpath_lib
from ..obs import reqtrace
from ..obs import trace as trace_lib
from ..resilience import faults as faults_lib
from ..ops import decoding as dec
from . import pages as pages_lib
from .adapters import AdapterTableFull

__all__ = ["EngineStats", "QueueFullError", "Request", "RequestSnapshot",
           "SlotScheduler"]

log = logging.getLogger(__name__)


class QueueFullError(RuntimeError):
    """``submit`` rejected: the queue is at ``max_queue_depth``.
    Backpressure, not failure — retry after in-flight work retires."""


@dataclasses.dataclass
class Request:
    """One in-flight generation request (host-side bookkeeping).

    ``status`` is the terminal disposition: ``"pending"`` while in
    flight, then ``"ok"`` | ``"deadline_exceeded"`` | ``"failed"`` |
    ``"cancelled"`` | ``"migrated"`` (the request's live state was
    exported as a ``RequestSnapshot`` and continues elsewhere —
    docs/RESILIENCE.md).  ``deadline`` is an absolute
    ``perf_counter`` instant; expiry is checked once per tick, so a
    retirement can lag the deadline by at most one tick.

    ``tenant`` attributes the request for quotas/fair-share (fleet/
    tenancy — the scheduler only accounts, the policy decides);
    ``adapter_id`` names the LoRA adapter it decodes under
    (serve/adapters), resolved to table row ``adapter_row`` while the
    request holds a pin (prefill begin -> retirement).
    """
    rid: int
    prompt: np.ndarray                       # [plen] int32
    max_new_tokens: int
    on_token: Optional[Callable[[List[int]], None]] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    submit_time: float = 0.0
    deadline: Optional[float] = None
    tenant: str = "default"
    adapter_id: Optional[str] = None
    adapter_row: Optional[int] = None
    status: str = "pending"
    error: Optional[BaseException] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # terminal transitions are claim-once (cancel vs pump races resolve
    # in _retire_accounting under the scheduler lock)
    _retired: bool = dataclasses.field(default=False, repr=False)
    # the request's page holdings (serve/pages.py), granted at prefill
    # begin, released once at retirement
    _lease: Optional[object] = dataclasses.field(default=None,
                                                 repr=False)
    # migration (import_snapshot): ``context`` is what prefill actually
    # runs over — the original prompt plus every token already generated
    # on the source engine (== prompt for a fresh submit); ``resumed``
    # counts the pre-seeded tokens; ``token_cost`` is what tenancy
    # accounting charged at admission (the REMAINING budget — resumed
    # work was already paid for on the source)
    context: Optional[np.ndarray] = dataclasses.field(default=None,
                                                      repr=False)
    resumed: int = 0
    token_cost: int = 0
    # request-scoped tracing (obs/reqtrace.py): minted at the front
    # door (Router.submit / Engine.submit) when a tracer is active,
    # carried across migration on the snapshot; None = tracing off
    trace_id: Optional[str] = None
    # critical-path accounting (obs/critpath.py): ``phases`` is the
    # live accrual dict (None = no ledger active at intake — every
    # accrual site then reduces to one attribute check); ``critpath``
    # is the finalized breakdown attached at retirement; ``e2e_base``
    # carries wall time already spent on previous engines across
    # migration; ``_cp_wait``/``_cp_t0`` are the open wait-phase
    # stopwatch (queue_wait until the admission that starts prefill,
    # backpressure_requeue after an admission bounce)
    phases: Optional[Dict[str, float]] = dataclasses.field(
        default=None, repr=False)
    critpath: Optional[Dict[str, float]] = dataclasses.field(
        default=None, repr=False)
    e2e_base: float = 0.0
    _cp_wait: Optional[str] = dataclasses.field(default="queue_wait",
                                                repr=False)
    _cp_t0: float = dataclasses.field(default=0.0, repr=False)
    # what the pump counts per request at its own boundaries: the tick
    # that admitted it and the prefill windows dispatched for it
    # (reported on the reqtrace record at first token)
    _admit_tick: int = dataclasses.field(default=0, repr=False)
    _windows: int = dataclasses.field(default=0, repr=False)

    @property
    def remaining_budget(self) -> int:
        """Tokens this engine still owes the caller (== max_new_tokens
        for a fresh submit; the unserved tail for an import)."""
        return self.max_new_tokens - self.resumed

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time


@dataclasses.dataclass
class RequestSnapshot:
    """A portable, host-side snapshot of one in-flight request — the
    unit of live migration (docs/RESILIENCE.md §migration).

    Deliberately contains NO device state: the destination engine
    rebuilds the KV cache bit-identically by running its deterministic
    chunked prefill over ``prompt + generated`` (the radix prefix cache
    makes that cheap when the destination has seen the prefix), then
    decode continues where the source stopped.  ``generated`` is every
    token the source delivered — the import pre-seeds the new request's
    token list with it, so the terminal ``tokens`` are the full
    sequence and the destination's callbacks fire only for NEW tokens
    (``stream_offset`` == ``len(generated)`` is where the stream
    resumes: exactly-once delivery).  Under greedy decoding the resumed
    tail is bit-identical to an unmigrated run (stochastic sampling
    draws from the destination's key stream — ``sampling`` carries the
    source's static sampling config so the destination can refuse an
    incompatible import instead of silently changing the
    distribution).

    ``max_new_tokens`` stays the ORIGINAL total budget across any
    number of hops; ``deadline_remaining_s`` is the wall-clock budget
    left at export (relative, so the snapshot survives a host change).
    ``clean`` records whether the export quiesced the source pump
    (pump mutex held) — a forced export of a wedged engine is still
    consistent, but exactly-once streaming then relies on a
    deduplicating consumer such as the fleet router's stream shim."""
    rid: int
    prompt: np.ndarray                       # [plen] int32, the original
    generated: List[int]                     # tokens delivered so far
    max_new_tokens: int                      # original total budget
    stream_offset: int                       # == len(generated)
    tenant: str = "default"
    adapter_id: Optional[str] = None
    deadline_remaining_s: Optional[float] = None
    sampling: Optional[dict] = None          # source sampling config
    clean: bool = True                       # pump-quiesced export
    trace_id: Optional[str] = None           # the lane continues (obs/reqtrace)
    # critical-path carry (obs/critpath.py): the source's phase accrual
    # plus elapsed wall so far and the export instant — the importer
    # charges the export->import gap to ``migration`` and resumes, so a
    # migrated request neither double-counts nor loses time
    critpath: Optional[dict] = None
    # page-wire manifest (fleet/pagewire.py): ``(chain hash, tokens
    # covered)`` for every full ``page_size``-token chunk the export
    # handed off into the source radix tree — what the wire can ship
    # so the destination's re-prefill skips those windows.  PURELY an
    # optimization hint: correctness never depends on it (a missing or
    # stale manifest just means full re-prefill), so the snapshot stays
    # device-free and portable
    shipped_pages: Optional[Tuple[Tuple[bytes, int], ...]] = None
    page_size: int = 0                       # source pool's page size


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Lock-cheap snapshot of one engine's load — what the fleet router
    spreads traffic by (``Router`` least-loaded placement) and what the
    serve gauges render.  Plain ints + small dict copies: reading it
    never touches the device or takes a lock."""
    queued: int                              # accepted, not yet prefilling
    prefilling: int                          # in a chunked-prefill window
    active: int                              # slots holding a request
    num_slots: int
    inflight_per_tenant: Dict[str, int]      # queued+prefilling+active
    tokens_inflight_per_tenant: Dict[str, int]   # sum of max_new_tokens
    # page-pool occupancy and radix prefix-cache counters (serve/pages.py;
    # all-zero from an engine that reports no pool, e.g. the simulator's) —
    # the single source the dttpu_serve_pages_*/dttpu_serve_prefix_*
    # series render from
    pages_total: int = 0                     # pool capacity (sans trash)
    pages_free: int = 0
    pages_per_request: float = 0.0           # avg pages held per lease
    prefix_lookups_total: int = 0
    prefix_hits_total: int = 0               # requests that mapped pages
    prefix_tokens_reused_total: int = 0
    prefix_evictions_total: int = 0          # radix pages reclaimed
    cow_splits_total: int = 0                # whole-chain prompts resplit
    # recurrent-state models only (serve/pages.py "State snapshots")
    state_snapshots_total: int = 0           # snapshots booked + copied
    state_restores_total: int = 0            # admissions resumed from one
    state_snapshots_evicted_total: int = 0   # lost to row or page pressure
    state_snapshot_bytes: int = 0            # held now (gauge)
    # the K/V leaves of the page pool: their logical size and what the
    # TPU's tiling makes of it (pages.kv_pool_bytes; fixed at build)
    kv_pool_bytes: int = 0
    kv_pool_tiled_bytes: int = 0
    # the weight leaves the scheduler put in another format than the one
    # they came in: the decode tick's choice (``_place_weights``; fixed at
    # build, 0 where every leaf came as the programs read it)
    weights_relaid_bytes: int = 0
    prefill_windows_skipped_total: int = 0   # window dispatches avoided
    # prefix-affinity placement inputs (fleet/router.py): the pool's
    # bounded hot-chain digest (chain hash -> cached tokens, already a
    # copy — see PagePool.fingerprint) and the page size the router
    # needs to chunk candidate prompts identically.  Empty/0 from an
    # engine that reports no pool, which degrades the router to
    # least-loaded
    page_size: int = 0
    prefix_fingerprint: Dict[bytes, int] = dataclasses.field(
        default_factory=dict)
    # pump heartbeat (fleet/watchdog.py): tick counters + perf_counter
    # stamps bracketing the most recent tick.  started > completed with
    # a stale start stamp = a wedged pump; a completed tick whose
    # duration blew the watchdog's tick deadline = a stall — both are
    # visible here without touching the (possibly stuck) pump thread
    ticks_started: int = 0
    ticks_completed: int = 0
    last_tick_start_s: float = 0.0           # perf_counter at tick entry
    last_tick_end_s: float = 0.0             # perf_counter at tick exit
    last_tick_duration_s: float = 0.0
    # what the pump dispatched, cumulative, counted where it happens
    # (over ticks_completed: windows and decode steps a tick)
    prefill_windows_total: int = 0           # windows run, mid + last
    # the programs that ran them (one holds a tick's windows, up to the
    # ladder's largest row count) and the padding rows those programs
    # carried: windows / dispatches is how often batching engages
    prefill_dispatches_total: int = 0
    prefill_rows_padded_total: int = 0
    decode_steps_total: int = 0              # tick_steps per decode dispatch
    # page-table entries those steps read, and the entries their tables
    # hold (steps x slots x pages a slot): the page-walk kernel reads the
    # pages a live slot's tokens lie on, the gather read all of them
    decode_pages_walked_total: int = 0
    decode_pages_table_total: int = 0
    # models with sliding-window layers only: the keys those steps' window
    # reads cover, each live slot's step at position p reading min(p + 1,
    # window) (a window layer's read; every such layer reads as many)
    decode_window_tokens_total: int = 0
    admit_backpressure_total: int = 0        # admissions bounced + requeued
    # expert-layer models only (the device's own counts, read with the
    # tick's fetches: models/longcat_flash.py ``counters``): the router's
    # picks for real tokens, those that fell on identity (zero-compute)
    # experts and on experts held here (the rest fell on absent ones), and
    # the tokens each held expert received, by expert layer
    router_picks_total: int = 0
    router_picks_identity_total: int = 0
    router_picks_held_total: int = 0
    expert_tokens_total: Tuple[Tuple[int, ...], ...] = ()

    @property
    def inflight(self) -> int:
        return self.queued + self.prefilling + self.active

    @property
    def free_slots(self) -> int:
        return self.num_slots - self.active

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefix lookups that mapped at least one page."""
        if not self.prefix_lookups_total:
            return 0.0
        return self.prefix_hits_total / self.prefix_lookups_total


class _NullMetrics:
    """Duck-typed metrics sink; the engine supplies a real one."""

    def submitted(self, req):
        pass

    def admitted(self, req):
        pass

    def emitted(self, req, n):
        pass

    def finished(self, req):
        pass

    def aborted(self, req, status):
        pass

    def depth(self, stats):
        pass


_WIRE_DECLINED = (
    "the page wire ships K/V pages only: for a model with recurrent state "
    "a chain without the state snapshot after it would be a wrong prefix "
    "hit, so this engine neither exports nor adopts wire pages and a "
    "migrated request re-prefills")


def _consumed(req: "Request") -> int:
    """Tokens an ACTIVE request's slot has consumed: its context and all
    but the newest token generated here (fed at the next step)."""
    ctx = req.context if req.context is not None else req.prompt
    return ctx.size + max(0, len(req.tokens) - req.resumed - 1)


def _written_context(req: "Request") -> np.ndarray:
    """Those tokens themselves."""
    ctx = req.context if req.context is not None else req.prompt
    fresh = req.tokens[req.resumed:]
    return (np.concatenate([ctx, np.asarray(fresh[:-1], np.int32)])
            if len(fresh) > 1 else ctx)


# what a row of a window program is told beyond its tokens and its page
# row: the columns of one int32 ``[rows, 6]`` array
_POS, _VALID, _SLOT, _ADMIT, _LENGTH, _BUDGET = range(6)
# a window program's largest row count: 8 windows of ``prefill_chunk`` 32
# are 256 tokens, about the v5e's ridge (197e12 / 819e9 = 240 operations a
# byte; with bf16 weights ~240 tokens through a matrix before the matmul
# stops being bound by reading it), so up to here a batch of windows
# costs about what one costs and beyond it batching buys nothing more
_MAX_WINDOW_ROWS = 8


def _window_rungs(num_slots: int) -> Tuple[int, ...]:
    """The row counts the two window programs are compiled at: 1 and 4
    where they lie under ``R = min(num_slots, _MAX_WINDOW_ROWS)``, then
    ``R``.  A dispatch takes the smallest that holds its group.  Few,
    because every rung is two more programs to trace, lower and load in
    every process, whatever the compile cache holds (~0.5 s a program at
    GPT-2-XL: with the rung at 2 as well the set-up of an 8-slot engine
    rose by 1.7-2.1 s, with these by 1.0; a group of two pays for it with
    two padding rows, PERF.md section 6, PR 37)."""
    top = min(num_slots, _MAX_WINDOW_ROWS)
    return tuple(r for r in (1, 4) if r < top) + (top,)


def _shapes(tree, carry: str = ""):
    """``tree``'s leaves as ``jax.ShapeDtypeStruct``; ``carry`` ``"sharding"``
    or ``"format"`` keeps each leaf's sharding, or its sharding and layout,
    where it has one (a program lowered with them is the one a dispatch on
    the arrays compiles)."""
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            tuple(getattr(x, "shape", ())), x.dtype,
            sharding=getattr(x, carry, None) if carry else None), tree)


def _layout_of(x):
    """A weight leaf's own layout: an array's, or for a shape placed on a
    device with none given, the one that device gives such an array."""
    from jax.experimental.layout import Layout
    fmt = x.format
    if fmt.layout is not None:
        return fmt.layout
    dev = next(iter(fmt.sharding.device_set))
    return Layout.from_pjrt_layout(dev.client.get_default_layout(
        x.dtype, fmt.sharding.shard_shape(x.shape), dev))


def _relay(leaves, formats) -> tuple:
    """``leaves`` (device arrays) copied into ``formats``, by one program
    compiled here and kept out of the persistent compile cache.  An
    executable read back from that cache reports its outputs in the
    default format (jax 0.9): the copies it returned would be labelled in
    a format they are not in, and every program compiled for them would
    expect other bytes than they hold (on a TPU, a size check refuses the
    dispatch).  ``jax.device_put`` to a format is such a program, cached
    like any other.  The copy compiles in well under a second."""
    import jax
    from jax._src import config

    def relaid(*xs):
        return xs

    with config.persistent_cache_min_compile_time_secs(float("inf")):
        return jax.jit(relaid, out_shardings=tuple(formats))(*leaves)


class _AutoFormatProgram:
    """The decode tick: a jitted callable that leaves the weights' formats
    to the compiler (``Layout.AUTO``), compiled ONCE, by ``compile``, and
    dispatched as that executable.  A jitted callable refuses a committed
    array where it was told ``AUTO``, and one told the formats the compiler
    chose compiles the program a second time — and not to the same
    program: for the TPU, GPT-2-XL's decode tick compiled for the chosen
    formats copies its FFN out-projection again, the AUTO one does not.
    ``lower``, and a call under a trace (the graph tier's, ``eval_shape``),
    go to the jitted callable, so what is analysed is what is dispatched;
    ``_cache_size`` says, as a jitted callable's does, whether a program
    has been dispatched."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._compiled = None
        self._dispatched = False

    def compile(self, *args):
        self._compiled = self._jitted.lower(*args).compile()
        return self._compiled

    def lower(self, *args):
        return self._jitted.lower(*args)

    def __call__(self, *args):
        import jax
        if self._compiled is None or any(
                isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(args)):
            return self._jitted(*args)
        self._dispatched = True
        return self._compiled(*args)

    def _cache_size(self) -> int:
        return int(self._dispatched)


@dataclasses.dataclass(slots=True, eq=False)
class _Prefill:
    """One in-flight prefill.  Compared by identity: ``st in
    self._prefills`` and ``.remove(st)`` mean THIS prefill."""
    req: Request
    windows: np.ndarray                      # [n, W] int32
    next: int                                # index of the next window
    lease: pages_lib.PageLease
    plan: List[Tuple[int, int, int]]         # (pos, real, snapshot depth)
    slot: Optional[int]                      # a recurrent-state model's row
    ran_ahead: bool = False                  # this tick's window is out


class SlotScheduler:
    """Drive a slot cache for a GPT-family ``model``/``params`` pair.

    Synchronous by design: callers pump ``step()`` (one tick: at most
    one prefill window a request, the tick's windows batched a few
    programs, + one K-step decode dispatch) or ``drain()``.
    Sampling config (temperature/top_k/top_p/eos) is static — it is
    baked into the compiled tick, like generate()'s.
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 max_len: Optional[int] = None, prefill_chunk: int = 32,
                 tick_steps: int = 4, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 eos_id: Optional[int] = None, pad_id: Optional[int] = None,
                 rng=None, metrics=None, queue=None, adapters=None,
                 max_queue_depth: Optional[int] = None, tenancy=None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 use_paged_kernel="auto"):
        import jax
        import jax.numpy as jnp

        c = model.config
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1; got {num_slots}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1; got {prefill_chunk}")
        if tick_steps < 1:
            raise ValueError(f"tick_steps must be >= 1; got {tick_steps}")
        max_len = max_len or c.max_position
        if max_len > c.max_position and getattr(
                c, "position_embedding", None) == "learned":
            raise ValueError(f"max_len {max_len} exceeds max_position "
                             f"{c.max_position}")
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.tick_steps = tick_steps
        self.eos_id = eos_id
        self.pad_id = dec.resolve_pad(eos_id, pad_id)
        # static sampling config, stamped onto exported RequestSnapshots
        # so an import into a differently-configured engine fails loudly
        # instead of silently resuming under another distribution
        self._sampling = dict(temperature=float(temperature),
                              top_k=top_k, top_p=top_p, eos_id=eos_id)
        # chaos identity for the stall_tick/wedge_replica fault kinds
        # (resilience/faults.py): the fleet Router stamps the replica id
        # here so a plan can target one engine deterministically
        self.chaos_tag = 0
        # pump heartbeat (read by stats()/fleet.Watchdog under _lock)
        self._ticks_started = 0
        self._ticks_completed = 0
        self._tick_start_t = 0.0
        self._tick_end_t = 0.0
        self._last_tick_s = 0.0
        # dispatch counters (stats(); written by the pump under _lock)
        self._prefill_windows = 0
        self._prefill_dispatches = 0
        self._prefill_rows_padded = 0
        self._decode_steps = 0
        self._decode_pages_walked = 0
        self._decode_pages_table = 0
        self._decode_window_tokens = 0
        self._admit_backpressure = 0
        self.metrics = metrics if metrics is not None else _NullMetrics()
        self.adapters = adapters
        self.max_queue_depth = max_queue_depth
        self._windows_skipped = 0
        # a model whose slot cache holds recurrent state beside K/V
        # (``paged_cache_spec()["state"]``: models/hybrid.py).  Its slots
        # are reserved when a prefill STARTS (the windows build the state
        # in the slot's row) and its prefix hits resume from state
        # snapshots (serve/pages.py).
        self._stateful = bool(model.paged_cache_spec()["state"])
        # a model that counts on the device (``paged_cache_spec()
        # ["counters"]``: an expert layer's router statistics).  Its
        # programs add to counters in the cache; the admitting window and
        # the decode program clear them and append what they held, flat,
        # to the int32 array of tokens the host reads anyway (no fetch or
        # transfer of its own).  ``_counter_shapes``: the leaves in the
        # order they are appended; host totals: [expert layers, held + 2]
        self._counter_shapes = sorted(
            (name, tuple(shape)) for name, (shape, _) in
            (model.paged_cache_spec().get("counters") or {}).items())
        self._counted = bool(self._counter_shapes)
        self._router_counts = None
        # a model with sliding-window layers says how many keys a query of
        # theirs sees (models/sambay.py): the decode dispatch counts them
        self._attention_window = getattr(model, "attention_window", None)
        if not getattr(model, "paged_kernel_ok", True):
            if use_paged_kernel is True:
                raise ValueError(
                    f"{type(model).__name__} cannot read its pages through "
                    "the paged-attention kernel (paged_kernel_ok is False)")
            use_paged_kernel = False
        # the K/V storage (serve/pages.py): slot columns map to
        # fixed-size pool pages through per-slot page tables, prefill
        # writes straight into the request's pages, and shared prompt
        # prefixes map the same read-only pages
        from ..ops import attention as attn_lib
        from ..ops.pallas import paged_attention as paged_kernel_lib
        if page_size:
            page_size = int(page_size)
        else:
            # prefer a kernel-tileable size whenever the kernel may
            # dispatch; plain largest-divisor pick otherwise
            page_size = pages_lib.auto_page_size(
                max_len,
                multiple_of=(1 if use_paged_kernel is False
                             else paged_kernel_lib.MIN_PAGE_SIZE))
        if page_size < 1 or max_len % page_size:
            raise ValueError(
                f"page_size must divide max_len {max_len} (the "
                f"gathered page view must tile the stripe shape "
                f"exactly); got {page_size}")
        # fused-kernel gate: resolved ONCE here (the executables
        # below close over the static answer — no retrace surface).
        # An explicit use_paged_kernel=True with a non-tileable
        # page_size is a configuration error, surfaced NOW as a
        # ValueError instead of a Mosaic failure inside the kernel;
        # "auto" falls back to the gather read path with a logged
        # reason.
        kernel_ok = paged_kernel_lib.page_size_kernel_ok(page_size)
        if use_paged_kernel is True and not kernel_ok:
            raise ValueError(
                f"use_paged_kernel=True requires a lane-tileable "
                f"page_size (a multiple of "
                f"{paged_kernel_lib.MIN_PAGE_SIZE}, Mosaic's "
                f"sublane tile); got page_size={page_size}. Pick a "
                f"compatible page_size or leave use_paged_kernel="
                f"'auto' to fall back to the gather read path.")
        resolved = attn_lib.resolve_use_paged_kernel(
            use_paged_kernel, max_len)
        if resolved and not kernel_ok:
            import warnings
            warnings.warn(
                f"paged-attention kernel disabled: page_size "
                f"{page_size} is not a multiple of "
                f"{paged_kernel_lib.MIN_PAGE_SIZE} (Mosaic lane "
                f"tiling) — falling back to the XLA gather read "
                f"path", RuntimeWarning, stacklevel=2)
            resolved = False
        self.use_paged_kernel = resolved
        pps = max_len // page_size
        if num_pages is None:
            # default: max_len tokens for every slot plus the reserved
            # trash page — shareable and pay-as-you-go (floor: one full
            # slot plus a spare, the pool's own minimum)
            num_pages = max(num_slots * pps + 1, pps + 2)
        self.page_size = page_size
        self.num_pages = int(num_pages)
        # snapshot budget: a row for every slot's turn end and eight
        # for shared prefixes (a system prompt's, a chain met without
        # one), each the size of one slot's state: the deployment's
        # configuration counts them with its bytes.  None where the
        # model caches keys and values only
        snapshot_rows = num_slots + 8 if self._stateful else None
        self.pages = pages_lib.PagePool(
            self.num_pages, page_size, pps, prefix_cache=prefix_cache,
            state_rows=snapshot_rows,
            state_row_bytes=pages_lib.state_bytes_per_slot(model))
        self._page_tab = np.zeros((num_slots, pps), np.int32)
        # duck-typed admission policy (fleet.tenancy.TenantPolicy):
        # checked under the state lock so quota decisions are atomic
        # against concurrent submitters
        self.tenancy = tenancy
        self._next_rid = 0
        # host-bookkeeping lock: queue/slots/prefills/pool/tenant
        # counters — short sections only, never spanning a dispatch or a
        # callback.  The pump mutex serializes ticks: device state is
        # touched only with it held (lock order: pump -> state).
        self._lock = threading.Lock()
        self._pump_lock = threading.Lock()
        # cross-thread cancel leaves device work to the pump: rows to
        # freeze at the next tick, cancelled prefills whose leases the
        # pump releases
        self._stale_rows: set = set()
        self._orphans: List[_Prefill] = []
        # admission queue: a deque by default; any object with append/
        # popleft/remove/__len__/__iter__ (e.g. fleet.tenancy's deficit-
        # weighted fair queue) plugs in — the scheduler only asks "next
        # admissible request", the policy decides whose turn it is
        self._queue = queue if queue is not None else collections.deque()
        self._slots: List[Optional[Request]] = [None] * num_slots
        self._prefills: List[_Prefill] = []
        # per-tenant in-flight accounting (the ONE bookkeeping source:
        # quotas, fair-share, gauges, and Engine.stats() all read it)
        self._tenant_inflight: Dict[str, int] = {}
        self._tenant_tokens: Dict[str, int] = {}

        # -- device state -------------------------------------------------
        self._cache = pages_lib.init_paged_cache(
            model, num_slots, self.num_pages, self.page_size)
        self._kv_pool_bytes = pages_lib.kv_pool_bytes(self._cache["kv"])
        # state snapshots: the slot state's layout, one row a snapshot
        # (empty dict for a K/V-only model)
        self._snaps = (pages_lib.init_state_snapshots(
                           model, max(self.pages.state_rows, 1))
                       if self._stateful else {})
        self._tokens = jnp.zeros((num_slots,), jnp.int32)
        self._finished = jnp.ones((num_slots,), bool)   # empty = finished
        self._remaining = jnp.zeros((num_slots,), jnp.int32)
        self._key = rng if rng is not None else jax.random.PRNGKey(0)
        # per-slot adapter table row (host np: only admission writes it).
        # With no adapter table the executables are passed None for both
        # adapter args (empty pytrees) — the compiled graphs are the
        # SAME programs as an adapter-free build.
        self._adapter_rows = (np.zeros((num_slots,), np.int32)
                              if adapters is not None else None)

        # -- the three hot executables (built ONCE; static shapes) --------
        pad = self.pad_id if self.pad_id is not None else 0

        def sample_step(carry_step, step_fn):
            """The tick's step body: one decode dispatch via
            ``step_fn``, in-graph sampling, EOS/budget freeze — the
            retirement semantics of generate()."""
            cache, tokens, finished, remaining, key = carry_step
            live = ~finished
            logits, cache = step_fn(cache, tokens, live)
            key, sub = jax.random.split(key)
            nxt = dec.sample_logits(sub, logits, temperature,
                                    top_k=top_k, top_p=top_p)
            if eos_id is not None:
                nxt, finished = dec.finish_step(nxt, finished,
                                                eos_id, pad)
            remaining = remaining - live.astype(jnp.int32)
            emitted = jnp.where(live, nxt, jnp.int32(pad))
            finished = finished | (remaining <= 0)
            tokens = jnp.where(live, nxt, tokens)
            return (cache, tokens, finished, remaining, key), \
                (emitted, live)

        def first_tokens(logits, told, key, tokens, finished, remaining):
            """The admitting rows' tail: sample each one's first token
            from its prompt's final-position ``logits`` [rows, vocab] and
            arm its slot's tokens/finished/remaining rows.  The key is
            split once an ADMITTING row, in row order — the stream of the
            same windows dispatched one by one — and the scatters drop
            every other row."""
            admit = told[:, _ADMIT] > 0

            def split(key, admitting):
                new, sub = jax.random.split(key)
                return jnp.where(admitting, new, key), sub

            key, subs = jax.lax.scan(split, key, admit)
            tok = jax.vmap(lambda sub, row: dec.sample_logits(
                sub, row[None], temperature, top_k=top_k,
                top_p=top_p)[0])(subs, logits)
            budget = told[:, _BUDGET]
            slot = jnp.where(admit, told[:, _SLOT], num_slots)
            done0 = budget <= 1
            if eos_id is not None:
                done0 = done0 | (tok == eos_id)
            tokens = tokens.at[slot].set(tok, mode="drop")
            finished = finished.at[slot].set(done0, mode="drop")
            # the first token was already emitted from the prefill logits
            remaining = remaining.at[slot].set(budget - 1, mode="drop")
            return tok, slot, key, tokens, finished, remaining

        # static per-build: the fused-kernel gate resolved above — the
        # three paged executables close over the answer, so the kernel
        # build REPLACES the gather build (same 3 programs, DT405-pinned)
        use_kernel = self.use_paged_kernel

        def hand_out_counters(cache, read):
            """``(cache with its counters cleared, read with the counters
            appended)``: a counting model's statistics leave the device
            flat behind the int32 tokens ``read`` that the host fetches
            anyway (``_split_read`` parts them); a model that has none
            returns what it always did."""
            if "counters" not in cache:
                return cache, read
            counters = cache["counters"]
            read = jnp.concatenate([read.reshape(-1)] + [
                counters[name].reshape(-1) for name in sorted(counters)])
            return dict(cache, counters=jax.tree.map(
                jnp.zeros_like, counters)), read

        def paged_windows(params, cache, windows, page_rows, told, head,
                          ad, ad_rows):
            """A batch of prefill windows through the model -> (logits,
            cache): row r is one request's window ``windows[r]`` [W]
            through its table row ``page_rows[r]``, and ``told[r]`` (the
            ``_POS`` .. ``_BUDGET`` columns; one array, because every
            argument is a transfer of its own) says where it starts, how
            many of its tokens are real (0: the row is padding up to the
            rung) and which slot's recurrent state it advances.  What the
            model is handed beyond the pool goes by what the cache holds,
            as in ``pages.decode_paged_step``: a model with recurrent
            state reads each row's slot row, advances it over the row's
            real tokens and scatters it back; one that counts adds the
            real tokens' counts."""
            held = [n for n in ("state", "counters") if n in cache]
            extra = {n: cache[n] for n in held}
            if "state" in cache:
                extra["slot"] = told[:, _SLOT]
            logits, *new = model.decode_window_paged(
                params, cache["kv"], windows, page_rows, told[:, _POS],
                head=head, adapters=ad, adapter_rows=ad_rows,
                use_kernel=use_kernel, valid=told[:, _VALID], **extra)
            return logits, dict(cache, **dict(zip(["kv"] + held, new)))

        def paged_win_mid(params, cache, windows, page_rows, told, ad,
                          ad_rows):
            """Mid prefill windows straight into their requests' pages —
            the whole cache (pool + slot state) is donated and flows
            through so win/admit/tick chain on one buffer set."""
            return paged_windows(params, cache, windows, page_rows, told,
                                 "none", ad, ad_rows)[1]

        def paged_last_admit(params, cache, windows, page_rows, told, key,
                             tokens, finished, remaining, ad, ad_rows):
            """A batch of windows of which some are LAST ones (``_ADMIT``)
            + their first-token samples + slot arms in ONE dispatch.  The
            prompts' K/V already live in the requests' pages — admission
            just points each slot's column state at them (the page-table
            rows are host state, handed to the next tick).  Returns the
            rows' tokens (an admitting row's is its first token)."""
            logits, cache = paged_windows(
                params, cache, windows, page_rows, told, "last", ad,
                ad_rows)
            tok, slot, key, tokens, finished, remaining = first_tokens(
                logits, told, key, tokens, finished, remaining)
            length = told[:, _LENGTH]
            cache = dict(
                cache,
                start_col=cache["start_col"].at[slot].set(
                    0, mode="drop"),
                write_col=cache["write_col"].at[slot].set(
                    length, mode="drop"),
                positions=cache["positions"].at[slot].set(
                    length, mode="drop"))
            cache, tok = hand_out_counters(cache, tok)
            return tok, cache, tokens, finished, remaining, key

        def copy_page(kv, src, dst):
            return {k: v.at[:, dst].set(v[:, src]) for k, v in kv.items()}

        def state_snapshot(cache, snaps, where):
            """Slot ``slot``'s recurrent state -> snapshot row ``row``,
            and the page holding the tokens past its last full page ->
            the snapshot's own page (trash onto trash when there are
            none); ``where`` = [slot, row, source page, target page], one
            small array because every scalar argument is a transfer of
            its own.  A device copy in the tick's stream; both donated."""
            slot, row, src_page, dst_page = where
            snaps = {k: v.at[:, row].set(cache["state"][k][:, slot])
                     for k, v in snaps.items()}
            return dict(cache, kv=copy_page(cache["kv"], src_page,
                                            dst_page)), snaps

        def state_restore(cache, snaps, where):
            """The reverse: snapshot row ``row`` -> slot ``slot``'s state,
            the snapshot's partial page COPIED into the request's own."""
            slot, row, src_page, dst_page = where
            state = {k: v.at[:, slot].set(snaps[k][:, row])
                     for k, v in cache["state"].items()}
            return dict(cache, state=state,
                        kv=copy_page(cache["kv"], src_page, dst_page))

        def paged_tick(params, cache, page_tab, tokens, finished,
                       remaining, key, ad, ad_rows):
            def one(carry, _):
                return sample_step(
                    carry,
                    lambda cache, toks, live: pages_lib.decode_paged_step(
                        model, params, cache, page_tab, toks, live,
                        adapters=ad, adapter_rows=ad_rows,
                        use_kernel=use_kernel))

            carry, (em, mask) = jax.lax.scan(
                one, (cache, tokens, finished, remaining, key), None,
                length=tick_steps)
            cache, em = hand_out_counters(carry[0], em)
            return (cache,) + carry[1:], em, mask

        def wire_gather(kv, idx):
            # page-wire device read (fleet/pagewire.py): gather the
            # pages at ``idx`` (padded to pages_per_slot — ONE shape,
            # one trace; unused entries gather the trash page and are
            # ignored on host) out of every pool leaf.  Not part of the
            # serve-hot census: cold path, runs once per migration.
            import jax.numpy as jnp
            return {k: jnp.take(v, idx, axis=1) for k, v in kv.items()}

        def wire_splice(kv, page, payload):
            # page-wire device write: splice one shipped page's host
            # payload into pool page ``page`` (traced scalar — one
            # trace for any index) across every leaf.  Donated: the
            # pool buffer is rebound to the result by the caller.
            return {k: v.at[:, page].set(payload[k])
                    for k, v in kv.items()}

        # the window programs' row counts, picked per dispatch from the
        # size of the group at hand.  A jitted callable a rung, so that
        # each still traces ONCE (the runtime sanitizer's budget of one
        # is the proof that nothing compiles after construction)
        self._rungs = _window_rungs(num_slots)
        self._win_mid = {rows: jax.jit(paged_win_mid, donate_argnums=(1,))
                         for rows in self._rungs}
        self._last_admit = {
            rows: jax.jit(paged_last_admit, donate_argnums=(1, 5, 6, 7, 8))
            for rows in self._rungs}
        # the weights' formats are left to the compiler: compiled below, by
        # ``_place_weights``, and dispatched as compiled
        from jax.experimental.layout import Format, Layout
        self._tick = _AutoFormatProgram(jax.jit(
            paged_tick, donate_argnums=(1, 3, 4, 5, 6),
            in_shardings=(jax.tree.map(lambda _: Format(Layout.AUTO), params),)
            + (None,) * 8))
        self._wire_gather = jax.jit(wire_gather)
        self._wire_splice = jax.jit(wire_splice, donate_argnums=(0,))
        # recurrent-state models: two more pinned programs, both row
        # copies dispatched in the tick's stream (census: 3 + 2)
        self._state_snapshot = jax.jit(state_snapshot,
                                       donate_argnums=(0, 1))
        self._state_restore = jax.jit(state_restore, donate_argnums=(0,))
        # the weights as the decode tick reads them: a tree of its own (the
        # caller's is left as it came), sharing every leaf that needs no move
        self.params, self._relaid = self._place_weights(params)
        # every rung is compiled NOW (through the persistent compile cache
        # where there is one), so a rung first met under traffic is never
        # a compile under traffic
        self._compile_window_rungs()

    def _padding_rows(self, rows: int) -> tuple:
        """``(windows, page_rows, told, adapter rows)`` of a window
        program at ``rows`` rows, every row padding: no real token, every
        page the trash page, no slot named."""
        pps = self.max_len // self.page_size
        told = np.zeros((rows, 6), np.int32)
        told[:, _SLOT] = self.num_slots
        return (np.zeros((rows, self.prefill_chunk), np.int32),
                np.zeros((rows, pps), np.int32), told,
                None if self.adapters is None
                else np.zeros((rows,), np.int32))

    def _decode_args(self, params, cache, carry, adapter) -> tuple:
        """The decode tick's arguments as ``_decode_dispatch`` passes them
        (``carry``: tokens, finished, remaining, key; ``adapter``: the
        table and the slots' rows), as shapes: the weights' with their
        shardings, their formats being the compiler's to choose."""
        import jax
        pps = self.max_len // self.page_size
        return (_shapes(params, "sharding"), _shapes(cache),
                jax.ShapeDtypeStruct((self.num_slots, pps), np.int32),
                *_shapes(carry), *_shapes(adapter))

    def _place_weights(self, params) -> Tuple[object, list]:
        """Compile the decode tick, the weights' formats left to the
        compiler, and put each weight leaf it wants in another format than
        its own there, once: ``(the weights as the hot programs take them,
        [(leaf, shape, from, to, bytes)] for each leaf moved)``.  The window
        programs are then compiled for the leaves as placed (a jitted
        callable takes a committed array's format as its own), so no hot
        program re-lays a weight out in every dispatch.  With the formats
        the arrays came in, the TPU's compiler copied GPT-2-XL's whole stack
        of FFN out-projections (0.98 GB) and its word embedding (161 MB) in
        each decode dispatch and the embedding again in each window program
        (PERF.md).  A leaf already in the wanted format is the caller's own
        array; a scheduler built over shapes that name no device has nothing
        to compile for and keeps what it was given."""
        import jax
        leaves = jax.tree.leaves(params)
        if any(getattr(x, "sharding", None) is None for x in leaves):
            return params, []
        devices = {d for x in leaves for d in x.sharding.device_set}
        if isinstance(leaves[0], jax.Array) and len(devices) == 1:
            # the state the programs take, committed to the weights'
            # device: a committed weight (a placed one is) commits every
            # output, and a program first dispatched on uncommitted state
            # would compile again at its second dispatch
            (self._cache, self._snaps, self._tokens, self._finished,
             self._remaining, self._key) = jax.device_put(
                (self._cache, self._snaps, self._tokens, self._finished,
                 self._remaining, self._key),
                jax.sharding.SingleDeviceSharding(devices.pop()))
        wanted = self._tick.compile(*self._decode_args(
            params, self._cache, (self._tokens, self._finished,
                                  self._remaining, self._key),
            (None if self.adapters is None else self.adapters.arrays,
             self._adapter_rows))).input_formats
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        placed = [x for _, x in flat]
        relaid, moved = [], []
        for i, ((path, x), fmt) in enumerate(
                zip(flat, tree.flatten_up_to(wanted[0][0]))):
            own = _layout_of(x)
            if fmt.layout is None or own == fmt.layout:
                continue
            relaid.append((jax.tree_util.keystr(path), tuple(x.shape),
                           own.major_to_minor, fmt.layout.major_to_minor,
                           x.size * x.dtype.itemsize))
            if isinstance(x, jax.Array):
                moved.append((i, fmt))
            else:
                placed[i] = jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=fmt)
        if moved:
            copies = _relay([placed[i] for i, _ in moved],
                            [fmt for _, fmt in moved])
            for (i, _), copy in zip(moved, copies):
                placed[i] = copy
        placed = tree.unflatten(placed)
        if relaid:
            log.info("weights placed in the formats the decode tick reads, "
                     "%d bytes: %s", sum(r[-1] for r in relaid), "; ".join(
                         f"{name} {list(shape)} {a} -> {b}"
                         for name, shape, a, b, _ in relaid))
        return placed, relaid

    def _compile_window_rungs(self) -> None:
        """One dispatch of each window program at each rung, on padding
        rows: they write the trash page and nothing else (no slot's
        state, no counter, no key split), so the scheduler is as it was,
        with every shape it will ever dispatch compiled and in the jitted
        callables' caches.  A scheduler built over shapes (the graph
        tier's, a rehearsal's) has nothing to run them on."""
        import jax
        if not all(isinstance(x, (jax.Array, np.ndarray)) for x in
                   jax.tree.leaves((self.params, self._cache))):
            return
        ad = None if self.adapters is None else self.adapters.arrays
        for rows in self._rungs:
            windows, page_rows, told, ad_rows = self._padding_rows(rows)
            self._cache = self._win_mid[rows](
                self.params, self._cache, windows, page_rows, told, ad,
                ad_rows)
            _, self._cache, self._tokens, self._finished, \
                self._remaining, self._key = self._last_admit[rows](
                    self.params, self._cache, windows, page_rows, told,
                    self._key, self._tokens, self._finished,
                    self._remaining, ad, ad_rows)

    # ------------------------------------------------ graph-tier targets

    def graph_targets(self, hbm_budget: Optional[int] = None) -> list:
        """The three hot executables as dtlint graph-tier trace targets
        (``analysis/graph.py``): abstract shape/dtype specs matching
        exactly what ``_advance_group``/``_decode_dispatch`` pass, so
        the DT4xx rules and the DT405 census lint the REAL programs — the
        two window programs at the LARGEST rung of the ladder (the most
        rows, the most temporaries; the smaller rungs are the same
        programs at fewer rows, compiled at construction with it).  Kept
        in this file so the specs cannot drift from the call sites
        without the diff showing both.  Serializes against the pump
        (shape/dtype reads of buffers a running tick donates)."""
        import jax

        top = self._rungs[-1]
        win, prow, told, row_ad = _shapes(self._padding_rows(top))
        with self._pump_lock:
            # the window programs take the weights as placed
            params, cache = _shapes(self.params, "format"), _shapes(
                self._cache)
            toks, fin = _shapes(self._tokens), _shapes(self._finished)
            rem, key = _shapes(self._remaining), _shapes(self._key)
            snaps = _shapes(self._snaps)
            adapter = self._adapter_args()
            decode = self._decode_args(
                self.params, self._cache, (self._tokens, self._finished,
                                           self._remaining, self._key),
                adapter)
        ad = _shapes(adapter[0])
        targets = [
            graph_lib.Target(
                "prefill_window", self._win_mid[top],
                (params, cache, win, prow, told, ad, row_ad),
                hbm_budget=hbm_budget),
            graph_lib.Target(
                "admit", self._last_admit[top],
                (params, cache, win, prow, told, key, toks, fin, rem, ad,
                 row_ad),
                hbm_budget=hbm_budget),
            graph_lib.Target("decode_tick", self._tick, decode,
                             hbm_budget=hbm_budget),
        ]
        if self._stateful:
            # the snapshot copies are programs of their own (a turn's
            # end is known only after the tick's fetch, so the copy
            # cannot ride inside the tick); listed here so that they
            # are warmed, analysed and censused with the three
            copy = (cache, snaps,
                    jax.ShapeDtypeStruct((4,), np.int32))
            targets += [
                graph_lib.Target("state_snapshot",
                                 self._state_snapshot, copy,
                                 hbm_budget=hbm_budget),
                graph_lib.Target("state_restore", self._state_restore,
                                 copy, hbm_budget=hbm_budget)]
        return targets

    # ------------------------------------------------------------- intake

    def submit(self, prompt, max_new_tokens: int,
               on_token: Optional[Callable[[List[int]], None]] = None,
               deadline_s: Optional[float] = None,
               tenant: str = "default",
               adapter_id: Optional[str] = None,
               trace_id: Optional[str] = None) -> Request:
        """Queue one request.  ``prompt``: [plen] int token ids (no
        padding — slots are per-request, unequal lengths batch freely).
        Enforces generate()'s length rule: prompt + max_new_tokens must
        fit ``max_len``, and the chunk-padded prompt must too.

        ``deadline_s``: total wall-clock budget from submit; a request
        still queued/decoding past it is retired with status
        ``deadline_exceeded`` at the next tick instead of decoding
        forever.

        ``tenant`` attributes the request for accounting/fair-share;
        ``adapter_id`` selects a registered LoRA adapter (requires the
        scheduler's ``adapters`` table; the id must be registered —
        unknown ids fail HERE, not mid-flight)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = prompt.size
        if plen < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0; got {deadline_s}")
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "adapter_id requires an engine built with an adapter "
                    "table (adapter_capacity > 0)")
            if not self.adapters.known(adapter_id):
                raise KeyError(f"unknown adapter_id {adapter_id!r}; "
                               "load_adapter() it first")
        padded = -(-plen // self.prefill_chunk) * self.prefill_chunk
        if plen + max_new_tokens > self.max_len or padded > self.max_len:
            raise ValueError(
                f"prompt ({plen}, chunk-padded {padded}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.max_len}")
        now = time.perf_counter()
        tenant = str(tenant)
        # built OUTSIDE the state lock (lock sections stay call-free)
        cp_phases = critpath_lib.new_phases()
        with self._lock:
            # depth + quota + enqueue + counter bump are ONE atomic
            # admission decision, however many threads submit at once
            if self.max_queue_depth is not None \
                    and len(self._queue) >= self.max_queue_depth:
                raise QueueFullError(
                    f"queue at max_queue_depth={self.max_queue_depth}; "
                    "retry after in-flight requests retire")
            if self.tenancy is not None:
                self.tenancy.check_admission(
                    tenant, int(max_new_tokens),
                    inflight=self._tenant_inflight.get(tenant, 0),
                    tokens_inflight=self._tenant_tokens.get(tenant, 0))
            req = Request(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=int(max_new_tokens),
                          on_token=on_token, submit_time=now,
                          deadline=None if deadline_s is None
                          else now + deadline_s,
                          tenant=tenant, adapter_id=adapter_id,
                          context=prompt,
                          token_cost=int(max_new_tokens),
                          trace_id=trace_id)
            req.phases = cp_phases
            req._cp_t0 = now
            self._next_rid += 1
            self._enqueue_locked(req)
        if req.trace_id:
            # the request lane opens here: async "b" request + queued
            reqtrace.submitted(req.trace_id, rid=req.rid,
                               tenant=req.tenant, plen=int(plen),
                               max_new_tokens=int(max_new_tokens))
        self.metrics.submitted(req)
        self._report_depth()
        return req

    def _enqueue_locked(self, req: Request) -> None:
        """Enqueue + per-tenant accounting (state lock held) — shared
        by ``submit`` and ``import_snapshot`` so admission bookkeeping
        can never diverge between the two intake paths."""
        self._queue.append(req)
        self._tenant_inflight[req.tenant] = \
            self._tenant_inflight.get(req.tenant, 0) + 1
        self._tenant_tokens[req.tenant] = \
            self._tenant_tokens.get(req.tenant, 0) + req.token_cost

    # ---------------------------------------------------------- the tick

    @property
    def busy(self) -> bool:
        with self._lock:
            return bool(self._queue) or bool(self._prefills) \
                or any(r is not None for r in self._slots)

    @property
    def queued(self) -> int:
        """Requests accepted but not yet prefilling (the engine's
        ``max_queue_depth`` admission-control signal)."""
        with self._lock:
            return len(self._queue)

    def stats(self) -> EngineStats:
        """The load snapshot (``EngineStats``): queue depth, prefill and
        slot occupancy, per-tenant in-flight counts.  Cheap host-side
        reads — the router polls this per placement and the serve gauges
        render from it, so there is exactly ONE bookkeeping source."""
        with self._lock:
            base = dict(
                queued=len(self._queue),
                prefilling=len(self._prefills),
                active=sum(r is not None for r in self._slots),
                num_slots=self.num_slots,
                inflight_per_tenant=dict(self._tenant_inflight),
                tokens_inflight_per_tenant=dict(self._tenant_tokens),
                ticks_started=self._ticks_started,
                ticks_completed=self._ticks_completed,
                last_tick_start_s=self._tick_start_t,
                last_tick_end_s=self._tick_end_t,
                last_tick_duration_s=self._last_tick_s,
                prefill_windows_total=self._prefill_windows,
                prefill_dispatches_total=self._prefill_dispatches,
                prefill_rows_padded_total=self._prefill_rows_padded,
                decode_steps_total=self._decode_steps,
                decode_pages_walked_total=self._decode_pages_walked,
                decode_pages_table_total=self._decode_pages_table,
                decode_window_tokens_total=self._decode_window_tokens,
                admit_backpressure_total=self._admit_backpressure)
            if self._router_counts is not None:
                held = self._router_counts[:, :-2]
                base.update(
                    router_picks_total=int(self._router_counts.sum()),
                    router_picks_identity_total=int(
                        self._router_counts[:, -2].sum()),
                    router_picks_held_total=int(held.sum()),
                    expert_tokens_total=tuple(
                        tuple(int(n) for n in row) for row in held))
            skipped = self._windows_skipped
        p = self.pages.stats()
        base.update(
            pages_total=p["pages_total"],
            pages_free=p["pages_free"],
            pages_per_request=p["pages_per_request"],
            prefix_lookups_total=p["prefix_lookups_total"],
            prefix_hits_total=p["prefix_hits_total"],
            prefix_tokens_reused_total=p["prefix_tokens_reused_total"],
            prefix_evictions_total=p["prefix_evictions_total"],
            cow_splits_total=p["cow_splits_total"],
            state_snapshots_total=p["state_snapshots_total"],
            state_restores_total=p["state_restores_total"],
            state_snapshots_evicted_total=p[
                "state_snapshots_evicted_total"],
            state_snapshot_bytes=p["state_snapshot_bytes"],
            kv_pool_bytes=self._kv_pool_bytes[0],
            kv_pool_tiled_bytes=self._kv_pool_bytes[1],
            weights_relaid_bytes=sum(r[-1] for r in self._relaid),
            prefill_windows_skipped_total=skipped,
            page_size=p["page_size"],
            prefix_fingerprint=p["prefix_fingerprint"])
        return EngineStats(**base)

    def tenant_inflight(self, tenant: str) -> int:
        with self._lock:
            return self._tenant_inflight.get(tenant, 0)

    def tenant_tokens_inflight(self, tenant: str) -> int:
        with self._lock:
            return self._tenant_tokens.get(tenant, 0)

    def step(self) -> bool:
        """One tick: retire expired deadlines, advance every in-flight
        prefill by one window (starting new prefills for free slots
        first; the tick's windows go to the device together, a program a
        group of at most ``_rungs[-1]`` rows), then one decode dispatch
        over the slots.  Returns False when fully idle.

        Thread-safe: ticks are serialized by the pump mutex (concurrent
        callers queue behind the running tick); ``submit``/``cancel``/
        ``stats`` interleave freely.  Callbacks fire on the pumping
        thread at the end of the tick and must not re-enter ``step``.

        Each tick is bracketed by a heartbeat (started/completed
        counters + perf_counter stamps in ``stats()``) — the signal the
        fleet ``Watchdog`` reads to tell a wedged or stalled pump from
        a merely idle one.  The heartbeat's stamps ARE the
        ``serve.tick`` span's two clock reads (obs/trace.py)."""
        with self._pump_lock:
            tick = trace_lib.timed("serve.tick")
            try:
                with tick:
                    with self._lock:
                        self._ticks_started += 1
                        self._tick_start_t = tick.start_s
                        tick.set(tick=self._ticks_started)
                    plan = faults_lib.active()
                    if plan is not None:
                        # chaos: stall_tick sleeps here, wedge_replica
                        # blocks here — DELIBERATELY inside the pump
                        # mutex, because a real pathological tick holds
                        # it too; that held mutex is exactly what the
                        # watchdog's in-progress heartbeat check and the
                        # forced-export path exist to handle
                        plan.on_engine_tick(self.chaos_tag)  # dtlint: disable=DT303 -- see comment
                    return self._step_locked(tick)
            finally:
                with self._lock:
                    self._ticks_completed += 1
                    self._tick_end_t = tick.end_s
                    self._last_tick_s = tick.duration_s

    def _step_locked(self, tick) -> bool:
        """The tick's layer boundaries, one span each (children of
        ``serve.tick``; docs/OBSERVABILITY.md has the table).  The spans
        whose length the critpath ledger accrues are ``timed``: that one
        measurement is the span's and the phase's.  A ``serve.prefill``
        span is a GROUP's — the windows one program holds, or the read of
        one program's first tokens — and names its requests
        (``trace_ids``), over which its time is split."""
        did = False
        outbox: List[tuple] = []     # tick-ordered deliveries/finishes
        waited_us = trace_lib.waited_host_us()     # None: no tracer
        with trace_lib.span("serve.housekeeping"):
            self._harvest_orphans()
            self._freeze_stale_rows()
            self._expire_deadlines()
        while True:
            with self._lock:
                req = None
                free = sum(r is None for r in self._slots)
                if self._queue and len(self._prefills) < free:
                    req = self._queue.popleft()
            if req is None or not self._admit(req):
                break
        with self._lock:
            pending = list(self._prefills)
        # critpath (obs/critpath.py): prefill_s totals this tick's
        # window cost, win_by_req keys each request's OWN share (and
        # doubles as "prefilled this tick", which exempts a request
        # admitted mid-tick from interference: it was not yet decoding
        # when the windows ran).  A group's span is its DISPATCH: the
        # device's time for it lands in the tick's reads and its fetch.
        prefill_s = 0.0
        windows = 0
        win_by_req: Dict[int, float] = {}

        def charge(reqs: List[Request], dt: float) -> None:
            """A group's ``serve.prefill`` time, split over the requests
            in it."""
            nonlocal prefill_s
            prefill_s += dt
            for req in reqs:
                share = dt / len(reqs)
                win_by_req[id(req)] = win_by_req.get(id(req), 0.0) + share
                if req.phases is not None:
                    req.phases["prefill_compute"] += share

        def windows_of(sts: List[_Prefill]) -> int:
            """One window for each of ``sts``, a program a group of at
            most the ladder's largest rung; returns the windows run."""
            ran = 0
            top = self._rungs[-1]
            for k in range(0, len(sts), top):
                with trace_lib.timed("serve.prefill") as group:
                    reqs = self._advance_group(sts[k:k + top], firsts)
                    group.set(trace_ids=[r.trace_id for r in reqs])
                charge(reqs, group.duration_s)
                ran += len(reqs)
            return ran

        # The tick keeps the device's queue from running empty: admitting
        # windows go last of the windows, their tokens are read only after
        # everything of the tick is dispatched, and the mid windows the NEXT
        # tick would open with are dispatched behind the decode program
        # (``ran_ahead``), so deliveries, admissions and the caller's own work
        # between ticks run beside a busy device.  A request still gets one
        # window a tick, in the same place of the device's stream; the
        # windows of each of the two places go out together, so the program
        # that holds the tick's admitting windows is the last one before
        # the decode program.
        firsts: List[tuple] = []     # admitting groups, tokens unread
        opening = []
        for st in pending:
            did = True
            if st.ran_ahead:
                st.ran_ahead = False     # behind the last decode
            else:
                opening.append(st)
        opening.sort(key=lambda st: st.next == len(st.windows) - 1)
        windows += windows_of(opening)
        with self._lock:
            active = sum(r is not None for r in self._slots)
        decoded = None
        if active:
            did = True
            decoded = self._decode_dispatch(active)
            with self._lock:
                ahead = [st for st in self._prefills
                         if st.next < len(st.windows) - 1]
            windows += windows_of(ahead)
            for st in ahead:
                st.ran_ahead = True
        for toks, rows, admitted in firsts:
            with trace_lib.timed(
                    "serve.prefill",
                    trace_ids=[st.req.trace_id for st, _, _ in admitted]
                    ) as read:
                self._first_tokens(toks, rows, admitted, outbox)
            charge([st.req for st, _, _ in admitted], read.duration_s)
        if decoded is not None:
            decoded = self._decode_fetch(*decoded)
        with trace_lib.span("serve.deliver"):
            if decoded is not None:
                self._collect(outbox, decoded, prefill_s, win_by_req)
            tokens = self._flush(outbox)
        tick.set(windows=windows, tokens=tokens)
        if waited_us is not None:
            # the device's waits on the host that ended in this tick
            tick.set(waited_host_us=trace_lib.waited_host_us() - waited_us)
        if did:
            self._report_depth()
        return did

    def _admit(self, req: Request) -> bool:
        """A popped request -> an in-flight prefill (adapter pin, page
        lease with its radix lookup).  False when every adapter row /
        pool page is pinned by an in-flight request: the request goes
        back to the FRONT of the queue (a retirement frees pins and
        pages, so this always drains), admission stops for this tick,
        and the continued wait is attributed to backpressure, not queue
        order."""
        with trace_lib.timed("serve.admit",
                             trace_id=req.trace_id) as admit:
            try:
                st = self._begin_prefill(req)
                admit.set(outcome="ok")
                if self._stateful:
                    # it holds a ``serve.state_restore`` span iff True
                    admit.set(resumed=st.lease.restore is not None)
            except (AdapterTableFull, pages_lib.PagePoolExhausted):
                st = None
                admit.set(outcome="backpressure")
        now = admit.end_s
        if st is None:
            if req.phases is not None:
                self._cp_close_wait(req, now,
                                    reopen="backpressure_requeue")
            with self._lock:
                self._admit_backpressure += 1
                self._requeue(req)
            return False
        if req.phases is not None:
            self._cp_close_wait(req, now)
        req._admit_tick = self._ticks_started
        if req.trace_id:
            reqtrace.stage(req.trace_id, "prefill")
            reqtrace.note(req.trace_id,
                          queue_wait_s=now - req.submit_time)
        with self._lock:
            self._prefills.append(st)
        return True

    def _harvest_orphans(self) -> None:
        """Release the leases of prefills cancelled cross-thread (only
        the pump owns recycling — a cancel mid-window must not hand
        pages back while a dispatch is still writing them).  Idempotent:
        the cancelling thread's abort usually got there first."""
        with self._lock:
            orphans, self._orphans = self._orphans, []
        for st in orphans:
            self.pages.release(st.lease)

    def _freeze_stale_rows(self) -> None:
        """Freeze device rows cancelled cross-thread since the last
        tick.  Runs BEFORE admissions so a newcomer armed in the
        freed slot this tick is never frozen by the departed request's
        leftover mark (reservation also discards its slot from the
        set — admission rewrites the whole row anyway).  The row's page
        table is remapped to the trash page, so its frozen writes can
        never land in a reallocated page."""
        with self._lock:
            stale = sorted(self._stale_rows)
            self._stale_rows.clear()
            for r in stale:
                self._page_tab[r] = 0
        if stale:
            self._freeze(np.asarray(stale))

    def _freeze(self, rows) -> None:
        """Mark ``rows`` finished on the device: an eager update, a program
        of its own in the device's stream."""
        with trace_lib.span("serve.freeze_rows"):
            self._finished = self._finished.at[rows].set(True)
            trace_lib.dispatched("freeze_rows", self._finished)

    def _cp_close_wait(self, req: Request, now: float,
                       reopen: Optional[str] = None) -> None:
        """Close the request's open wait phase (queue_wait or
        backpressure_requeue) at ``now``; ``reopen`` restarts the
        stopwatch under a new phase (an admission bounce).  Pump-only —
        the wait stopwatch has a single writer."""
        if req._cp_wait is not None:
            req.phases[req._cp_wait] += max(0.0, now - req._cp_t0)
        req._cp_wait = reopen
        req._cp_t0 = now

    def _requeue(self, req: Request) -> None:
        """Put a popped-but-unstartable request back at the FRONT of its
        queue position (fair-share queues refund the deficit charge)."""
        if hasattr(self._queue, "requeue"):
            self._queue.requeue(req)
        else:
            self._queue.appendleft(req)

    def drain(self) -> None:
        """Pump until every queued/in-flight request has finished."""
        while self.busy:
            self.step()

    # ---------------------------------------------------------- prefill

    def _begin_prefill(self, req: Request) -> _Prefill:
        w = self.prefill_chunk
        # prefill runs over the request's CONTEXT — prompt + any tokens
        # already generated on a source engine (import_snapshot); a
        # fresh submit's context IS its prompt
        ctx = req.context if req.context is not None else req.prompt
        plen = ctx.size
        if self.adapters is not None:
            # pin the adapter BEFORE touching cache storage: acquire
            # may raise AdapterTableFull and the request must requeue
            # with nothing to unwind
            req.adapter_row = self.adapters.acquire(req.adapter_id)
        try:
            slot = self._reserve_state_row() if self._stateful else None
            # page lease: map any cached prefix chain read-only and
            # allocate private pages for the rest of the request's
            # whole footprint (context + remaining decode budget —
            # upfront, so a mid-decode tick can never starve)
            lease = self.pages.begin(
                ctx, plen + req.remaining_budget - 1)
            req._lease = lease
            if lease.restore is not None:
                # the hit's state, and its partial page, into the
                # slot's row and the request's own page: a device copy
                # ahead of the first window in the same stream
                row, src, dst = lease.restore
                with trace_lib.span(
                        "serve.state_restore", trace_id=req.trace_id,
                        depth=int(lease.skip)):
                    self._cache = self._state_restore(
                        self._cache, self._snaps,
                        np.asarray([slot, row, src, dst], np.int32))
                    trace_lib.dispatched("state_restore", self._cache)
            # the windows: W tokens each from ``skip`` on, cut where
            # the pool asked for a snapshot (``snap_at``: the prompt
            # met a chain there), each stretch's last one padded
            cuts = [c for c in (lease.snap_at,)
                    if lease.skip < c < plen] + [plen]
            plan, rows, start = [], [], lease.skip
            for cut in cuts:
                for pos in range(start, cut, w):
                    real = min(w, cut - pos)
                    row_ = np.zeros((w,), np.int32)
                    row_[:real] = ctx[pos:pos + real]
                    rows.append(row_)
                    plan.append((pos, real,
                                 cut if pos + real == cut < plen
                                 else 0))
                start = cut
            n_win = len(plan)
            with self._lock:
                # window dispatches avoided by the prefix hit — the
                # measured TTFT/FLOPs saving, reported via stats()
                self._windows_skipped += max(0, -(-plen // w) - n_win)
            return _Prefill(req, np.stack(rows), 0, lease, plan, slot)
        except BaseException:
            # admission failed after the pin: pool exhaustion is the
            # common case, but begin() also raises ValueError for a
            # footprint over pages_per_slot — every path must unwind the
            # lease and the pin so a requeued (or propagating) request
            # holds nothing
            if req._lease is not None:
                self.pages.release(req._lease)
                req._lease = None
            if req.adapter_row is not None and self.adapters is not None:
                self.adapters.release(req.adapter_id)
                req.adapter_row = None
            raise

    def _reserve_state_row(self) -> int:
        """The slot a recurrent-state model's prefill builds its state in:
        free, reserved by no other prefill, and with no freeze pending (a
        row cancelled cross-thread decodes on until the next tick's
        housekeeping freezes it).  None to be had is backpressure."""
        with self._lock:
            taken = {st.slot for st in self._prefills} | self._stale_rows
            for r, holder in enumerate(self._slots):
                if holder is None and r not in taken:
                    return r
        raise pages_lib.PagePoolExhausted("no slot's state row is free")

    def _snapshot_state(self, req: Request, lease, context, slot: int,
                        at: str) -> None:
        """Book a snapshot of ``slot``'s recurrent state after exactly
        ``context`` (serve/pages.py ``snapshot``) and dispatch its device
        copy; nothing when the pool books none."""
        booked = self.pages.snapshot(lease, context)
        if booked is None:
            return
        row, src, dst = booked
        with trace_lib.span("serve.state_snapshot", trace_id=req.trace_id,
                            depth=int(len(context)), at=at):
            self._cache, self._snaps = self._state_snapshot(
                self._cache, self._snaps,
                np.asarray([slot, row, src, dst], np.int32))
            trace_lib.dispatched("state_snapshot", self._snaps)

    def _adapter_args(self):
        """(table arrays, the slots' rows) for the executables — (None,
        None) when adapters are off, so the compiled programs are
        identical to an adapter-free build.  A window program's rows are
        its requests' own (``_advance_group``)."""
        if self.adapters is None:
            return None, None
        return self.adapters.arrays, self._adapter_rows

    def _advance_group(self, group: List[_Prefill],
                       firsts: List[tuple]) -> List[Request]:
        """One window for each in-flight prefill of ``group`` (at most the
        ladder's largest rung), all in ONE program: the smallest rung that
        holds them, padded with rows that write the trash page alone.  A
        request on its last window is admitted into its slot by the same
        program, and its token stays on the device: ``firsts`` gains
        ``(the program's tokens, rows, [(st, row, slot), ...])`` for
        ``_first_tokens`` to read once the tick's other work is
        dispatched.  Pump-only.  Returns the requests whose window ran (a
        request cancelled cross-thread since the group was collected is
        dropped from it).

        The windows go straight into the requests' leased pages
        (``decode_window_paged`` at ``pos = skip + i*W`` — a prefix hit
        starts past the shared pages, whose windows are simply never
        dispatched), so admission is column-state arming plus a host
        page-table write; a request's full prompt pages are published to
        the radix cache right after."""
        live = []                        # (st, last window?, slot)
        with self._lock:
            for st in group:
                last = st.next == len(st.windows) - 1
                if st not in self._prefills or (
                        last and st.req.done.is_set()):
                    continue     # cancelled cross-thread: harvest recycles
                slot = st.slot
                if last:
                    self._prefills.remove(st)
                    if slot is None:
                        slot = self._slots.index(None)
                    # reserve before the dispatch so the free-slot count
                    # stays consistent for concurrent admissions and
                    # stats(); admission rewrites the row, so a leftover
                    # freeze mark from the slot's previous (cancelled)
                    # occupant must not fire
                    self._slots[slot] = st.req
                    self._stale_rows.discard(slot)
                live.append((st, last, slot))
        if not live:
            return []
        rows = next(r for r in self._rungs if r >= len(live))
        windows, page_rows, told, ad_rows = self._padding_rows(rows)
        for r, (st, last, slot) in enumerate(live):
            req = st.req
            pos, real, _ = st.plan[st.next]
            ctx = req.context if req.context is not None else req.prompt
            windows[r] = st.windows[st.next]
            page_rows[r] = st.lease.row
            told[r] = (pos, real, self.num_slots if slot is None else slot,
                       last, ctx.size, req.remaining_budget)
            if ad_rows is not None:
                ad_rows[r] = req.adapter_row
                if last:
                    self._adapter_rows[slot] = req.adapter_row
        ad, _ = self._adapter_args()
        admits = any(last for _, last, _ in live)
        with trace_lib.span("serve.prefill_dispatch", rows=rows,
                            real=len(live), last=admits):
            if admits:
                toks, self._cache, self._tokens, self._finished, \
                    self._remaining, self._key = self._last_admit[rows](
                        self.params, self._cache, windows, page_rows,
                        told, self._key, self._tokens, self._finished,
                        self._remaining, ad, ad_rows)
                trace_lib.dispatched("last_admit", toks)
            else:
                self._cache = self._win_mid[rows](
                    self.params, self._cache, windows, page_rows, told,
                    ad, ad_rows)
                trace_lib.dispatched("win_mid", self._cache)
        with self._lock:
            self._prefill_dispatches += 1
            self._prefill_rows_padded += rows - len(live)
        admitted = []
        for r, (st, last, slot) in enumerate(live):
            req, lease = st.req, st.lease
            ctx = req.context if req.context is not None else req.prompt
            if last:
                if self._stateful:
                    # publish the context's pages and snapshot the state
                    # after its last token now, behind the admitting
                    # window in the device's stream
                    with trace_lib.span("serve.register"):
                        self.pages.register(lease, ctx)
                        self._snapshot_state(req, lease, ctx, slot,
                                             "prompt_end")
                with self._lock:
                    # before the tick's decode dispatch copies the table
                    self._page_tab[slot] = lease.row
                admitted.append((st, r, slot))
                continue
            i = st.next
            req._windows += 1
            with self._lock:
                st.next = i + 1
                self._prefill_windows += 1
            if req.trace_id:
                reqtrace.mark(req.trace_id, "prefill_window",
                              window=int(i))
            snap_depth = st.plan[i][2]
            if snap_depth:
                self._snapshot_state(req, lease, ctx[:snap_depth],
                                     st.slot, "chain_met")
        if admitted:
            firsts.append((toks, rows, admitted))
        return [st.req for st, _, _ in live]

    def _first_tokens(self, toks, rows: int, admitted: List[tuple],
                      outbox: List[tuple]) -> None:
        """Read an admitting program's ``rows`` tokens off the device and
        finish its admissions on the host; deliveries are queued on ``outbox``
        (flushed at end of tick).  The tick's decode program and the next
        tick's first windows are queued behind the program by now, so the
        read waits beside a busy device: it is no barrier, and its span is
        not named as the fetches that are (``serve.decode_fetch``)."""
        with trace_lib.span("serve.first_token_read") as read:
            # returns as the admitting program ends
            toks, counters = self._split_read(toks, (rows,))
            self._absorb_counters(counters, read)
        for st, r, slot in admitted:
            self._first_token(st, int(toks[r]), slot, outbox)

    def _first_token(self, st: _Prefill, first: int, slot: int,
                     outbox: List[tuple]) -> None:
        """The host's part of one admission, its first token read."""
        req = st.req
        ctx = req.context if req.context is not None else req.prompt
        req.first_token_time = time.perf_counter()
        req._windows += 1
        with trace_lib.span("serve.register"):
            if not self._stateful:
                # the context's full pages are final now — publish them
                # so the NEXT request with this prefix skips their
                # windows
                self.pages.register(st.lease, ctx)
            with self._lock:
                self._prefill_windows += 1
                cancelled = req.done.is_set()
                if cancelled and self._slots[slot] is req:
                    self._slots[slot] = None
                    self._page_tab[slot] = 0
        if cancelled:
            # cancel() raced the admission: retire the freshly armed row
            # (frozen rows never perturb the others) and deliver nothing
            self._freeze(slot)
            return
        self.metrics.admitted(req)
        if req.trace_id:
            reqtrace.mark(req.trace_id, "prefill_window",
                          window=len(st.windows) - 1)
            reqtrace.mark(req.trace_id, "admitted", slot=int(slot))
            reqtrace.mark(req.trace_id, "first_token",
                          ttft_s=req.first_token_time - req.submit_time)
            reqtrace.note(
                req.trace_id, prefill_windows=req._windows,
                prefill_ticks=self._ticks_started - req._admit_tick + 1)
            reqtrace.stage(req.trace_id, "decode")
        if req.remaining_budget <= 1 or (self.eos_id is not None
                                         and first == self.eos_id):
            self._drop_slot(slot, req)
            # armed but already finished in-graph: the slot stays free
            # host-side
            outbox.append(("deliver", req, [first], None))
            outbox.append(("finish", req))
        else:
            outbox.append(("deliver", req, [first], slot))

    # ----------------------------------------------------------- decode

    def _drop_slot(self, r: int, req: Request) -> None:
        """Free slot ``r`` if ``req`` still holds it, and remap the
        row's page table to the trash page so the frozen row's future
        writes can never touch a reallocated page."""
        with self._lock:
            if self._slots[r] is req:
                self._slots[r] = None
                self._page_tab[r] = 0

    def _decode_walk(self, slots: List[Optional[Request]]) -> Tuple[int,
                                                                    int]:
        """``(walked, table)``: the page-table entries the decode steps of
        one dispatch over ``slots`` read, and the entries their tables hold.
        Host arithmetic on what the scheduler knows as it dispatches: a
        slot's step reads the pages its consumed tokens and the one it is
        fed lie on, for as many steps as its budget keeps it live (an EOS
        mid-dispatch is not foreseen: those steps are counted); the gather
        read takes every table whole."""
        pps = self.max_len // self.page_size
        table = self.tick_steps * self.num_slots * pps
        if not self.use_paged_kernel:
            return table, table
        walked = 0
        for req in slots:
            if req is None:
                continue
            # tokens the device has emitted: the first comes with admission
            fed = max(1, len(req.tokens) - req.resumed)
            cols = _consumed(req) + 1
            for j in range(min(self.tick_steps, req.remaining_budget - fed)):
                walked += min(pps, -(-(cols + j) // self.page_size))
        return walked, table

    def _decode_window_reads(self, slots: List[Optional[Request]]) -> int:
        """Keys a window layer's queries read in one dispatch over
        ``slots``: ``min(p + 1, window)`` for each step a live slot takes at
        position ``p``, for as many steps as its budget keeps it live, as
        ``_decode_walk`` counts them."""
        reads = 0
        for req in slots:
            if req is None:
                continue
            fed = max(1, len(req.tokens) - req.resumed)
            pos = _consumed(req)
            for j in range(min(self.tick_steps, req.remaining_budget - fed)):
                reads += min(pos + j + 1, self._attention_window)
        return reads

    def _decode_dispatch(self, active: int) -> tuple:
        """One K-step decode dispatch over the slots; what ``_decode_fetch``
        takes: ``(slots, emitted, mask, dispatch_s)``, the tokens still on
        the device."""
        with self._lock:
            slots = list(self._slots)
            # page-table snapshot for this dispatch: host mutations
            # (admissions, retirements) between ticks never tear a
            # dispatch mid-read
            tab = self._page_tab.copy()
            walked, table = self._decode_walk(slots)
            self._decode_pages_walked += walked
            self._decode_pages_table += table
            window = (self._decode_window_reads(slots)
                      if self._attention_window else None)
            if window is not None:
                self._decode_window_tokens += window
        ad, ad_rows = self._adapter_args()
        with trace_lib.timed("serve.decode_dispatch",
                             steps=self.tick_steps, active=active,
                             pages_walked=walked,
                             pages_table=table) as dispatch:
            if window is not None:
                dispatch.set(window_tokens=window)
            if trace_lib.active_tracer() is not None:
                # tokens the live slots have in their caches as the
                # dispatch starts: what a byte count of the step is made of
                dispatch.set(cached_tokens=sum(
                    _consumed(r) for r in slots if r is not None))
            (self._cache, self._tokens, self._finished,
             self._remaining, self._key), em, mask = self._tick(
                self.params, self._cache, tab, self._tokens,
                self._finished, self._remaining, self._key, ad, ad_rows)
            trace_lib.dispatched("tick", em)
        return slots, em, mask, self._finished, dispatch.duration_s

    def _split_read(self, read, shape) -> tuple:
        """A program's tokens as a host array of ``shape`` (the host sync)
        and, for a counting model, the device's counters that rode behind
        them in the same array (``hand_out_counters``), by leaf.  With a
        tracer, the wait for the device (``device.ready_wait``) is timed
        apart from the copy, which stays in the caller's span."""
        if trace_lib.active_tracer() is not None:
            with trace_lib.span(trace_lib.READY_WAIT):
                # the tick's host sync is inside the pump mutex by design
                # (step()); this only times apart the wait that the
                # np.asarray below would make
                read.block_until_ready()  # dtlint: disable=DT304 -- see comment
        read = np.asarray(read)
        if not self._counted:
            return read, None
        tokens, rest = np.split(read, [int(np.prod(shape, dtype=int))])
        counters = {}
        for name, leaf in self._counter_shapes:
            head, rest = np.split(rest, [int(np.prod(leaf, dtype=int))])
            counters[name] = head.reshape(leaf)
        return tokens.reshape(shape), counters

    def _absorb_counters(self, counters, span) -> None:
        """Add what a program handed out of the device's counters (by
        leaf, host arrays; None for a model that has none) to the host's
        totals, and say on ``span`` — the read of that program's tokens,
        which brought them — what was added: the picks by kind, the tokens
        each held expert received ``[expert layer][held expert]``, and,
        where decode steps ran, ``experts_touched`` (held experts that
        received a token, over expert layers and steps) beside
        ``experts_held_steps`` (how many they could have been)."""
        if counters is None:
            return
        router = counters["router"].astype(np.int64)
        touched, could = (int(n) for n in counters["touched"])
        with self._lock:
            if self._router_counts is None:
                self._router_counts = np.zeros_like(router)
            self._router_counts += router
        if trace_lib.active_tracer() is None:
            return
        span.set(router_picks=int(router.sum()),
                 router_picks_identity=int(router[:, -2].sum()),
                 router_picks_held=int(router[:, :-2].sum()),
                 expert_tokens=router[:, :-2].tolist())
        if could:
            span.set(experts_touched=touched, experts_held_steps=could)

    def _decode_fetch(self, slots, em, mask, finished,
                      dispatch_s: float) -> tuple:
        """The host sync on a decode dispatch: ``(slots, em, mask, fin,
        decode_s)`` for ``_collect``.  ``decode_s`` is the dispatch-to-
        host-sync wall — the two spans' own durations — identical for
        every live slot in the batch."""
        with trace_lib.timed("serve.decode_fetch") as fetch:
            # [K, S]: the host sync
            em, counters = self._split_read(
                em, (self.tick_steps, self.num_slots))
            mask = np.asarray(mask)
            fin = np.asarray(finished)
            # (slot, step) pairs that were live: what the steps computed
            fetch.set(live_steps=int(mask.sum()))
            self._absorb_counters(counters, fetch)
        with self._lock:
            self._decode_steps += self.tick_steps
        return slots, em, mask, fin, dispatch_s + fetch.duration_s

    def _collect(self, outbox: List[tuple], decoded: tuple,
                 prefill_s: float, win_by_req: Dict[int, float]) -> None:
        """Sort a decode dispatch's tokens into ``outbox`` and accrue
        its critpath phases.  ``prefill_s`` is this tick's total
        prefill-window wall time: every slot that was already decoding
        when those windows ran is charged the FULL amount as
        ``prefill_interference`` — all decode slots experience the
        stretch in parallel, which is exactly how the fleet simulator
        prices the HOL penalty — while requests in ``win_by_req``
        (prefilled/admitted this same tick) are exempt."""
        slots, em, mask, fin, decode_s = decoded
        for r, req in enumerate(slots):
            if req is None:
                continue
            with self._lock:
                if self._slots[r] is not req:
                    continue         # cancelled mid-dispatch: drop tokens
            if req.phases is not None:
                ph = req.phases
                ph["decode_compute"] += decode_s
                if id(req) not in win_by_req:
                    ph["prefill_interference"] += prefill_s
            toks = em[:, r][mask[:, r]]
            if toks.size:
                outbox.append(("deliver", req, [int(t) for t in toks], r))
            if fin[r]:
                self._drop_slot(r, req)
                outbox.append(("finish", req, r))

    def _flush(self, outbox: List[tuple]) -> int:
        """Deliver tokens and terminal transitions in tick order;
        returns the tokens delivered.  Runs
        at the end of the tick: pump mutex held (so streams stay ordered
        per request across concurrently pumping threads) but the state
        lock is NOT — a slow callback never blocks submit/cancel/stats.
        A raising callback fails only its own request (failure
        isolation): its row freezes, every other stream is untouched."""
        poisoned: set = set()
        delivered = 0
        for ev in outbox:
            kind, req = ev[0], ev[1]
            if id(req) in poisoned or req.done.is_set():
                continue             # failed earlier this tick/cancelled
            if kind == "deliver":
                toks, row = ev[2], ev[3]
                try:
                    self._deliver(req, toks)
                    delivered += len(toks)
                except Exception as e:
                    poisoned.add(id(req))
                    if row is not None:
                        self._drop_slot(row, req)
                        self._freeze(row)
                    self._abort(req, "failed", error=e)
            else:                    # "finish"
                if self._stateful and len(ev) > 2:
                    self._snapshot_turn_end(req, ev[2])
                self._finish(req)
        return delivered

    def _snapshot_turn_end(self, req: Request, row: int) -> None:
        """A finished turn of a recurrent-state model: publish the pages
        of everything the slot consumed (the context and all but the
        newest generated token, which was never fed) and snapshot the
        frozen row's state at exactly that depth, so the session's next
        turn resumes after its own history, reply included.  Runs in the
        tick's flush, before any admission can reserve the row."""
        lease = req._lease
        if lease is None or lease.released:
            return
        written = _written_context(req)
        with trace_lib.span("serve.register"):
            self.pages.register(lease, written)
        self._snapshot_state(req, lease, written, row, "turn_end")

    # --------------------------------------------- degradation paths

    def _expire_deadlines(self) -> None:
        """Retire every request past its deadline, wherever it is —
        queued (never admitted), mid-prefill (the lease comes back via
        the abort's retirement accounting), or active (row frozen).  Runs
        once per tick, on the pump."""
        now = time.perf_counter()

        def expired(req):
            return req is not None and req.deadline is not None \
                and now > req.deadline and not req.done.is_set()

        aborts: List[Request] = []
        rows: List[int] = []
        with self._lock:
            for req in [r for r in self._queue if expired(r)]:
                self._queue.remove(req)
                aborts.append(req)
            still = []
            for st in self._prefills:
                if expired(st.req):
                    aborts.append(st.req)
                else:
                    still.append(st)
            self._prefills = still
            for r, req in enumerate(self._slots):
                if expired(req):
                    self._slots[r] = None
                    self._page_tab[r] = 0
                    rows.append(r)
                    aborts.append(req)
        if rows:
            self._freeze(np.asarray(rows))
        for req in aborts:
            self._abort(req, "deadline_exceeded")
            if req.trace_id:
                # tail-latency forensics: snapshot the victim's span
                # tree while the evidence is warm (bounded log), with
                # the phase budget the deadline was spent on alongside
                extra = ({"critpath": req.critpath}
                         if req.critpath is not None else {})
                reqtrace.forensic_dump(req.trace_id, "deadline_expired",
                                       rid=req.rid, tenant=req.tenant,
                                       **extra)
        if aborts:
            self._report_depth()

    def cancel(self, req: Request, status: str = "cancelled") -> bool:
        """Abort one request wherever it is; False if already finished.
        (The engine's ``generate_batch`` error path uses this so a
        failed submit never strands earlier handles pending forever.)

        Thread-safe against a concurrently running tick: device work is
        left to the pump — an active row lands in ``_stale_rows`` (the
        pump freezes it next tick), a mid-window prefill moves to the
        orphan list (the pump releases its lease when no dispatch can
        still be writing its pages)."""
        if req.done.is_set():
            return False
        with self._lock:
            if req in self._queue:
                self._queue.remove(req)
            for st in list(self._prefills):
                if st.req is req:
                    self._prefills.remove(st)
                    self._orphans.append(st)
            for r, other in enumerate(self._slots):
                if other is req:
                    self._slots[r] = None
                    # the page-table row is cleared by the pump's
                    # freeze (_freeze_stale_rows) — the in-flight tick
                    # may still be reading the snapshot that maps it
                    self._stale_rows.add(r)
        self._abort(req, status)
        self._report_depth()
        return True

    # -------------------------------------------- migration (snapshots)

    def find(self, rid: int) -> Optional[Request]:
        """The in-flight ``Request`` with id ``rid``, wherever it is
        (queued, prefilling, active); None when no such request is in
        flight."""
        with self._lock:
            for req in self._queue:
                if req.rid == rid:
                    return req
            for st in self._prefills:
                if st.req.rid == rid:
                    return st.req
            for req in self._slots:
                if req is not None and req.rid == rid:
                    return req
        return None

    def inflight_trace_ids(self) -> List[str]:
        """Trace ids of every in-flight request (queued, prefilling,
        active) — the fleet watchdog captures these BEFORE quarantining
        a wedged replica so it can forensic-dump each victim."""
        with self._lock:
            reqs = ([r for r in self._queue]
                    + [st.req for st in self._prefills]
                    + [r for r in self._slots if r is not None])
        return [r.trace_id for r in reqs if r.trace_id]

    def inflight_critpath(self) -> Dict[str, dict]:
        """Live critical-path breakdowns keyed by trace_id — each
        in-flight (un-retired) request's phase accrual so far,
        finalized against wall-now with its open wait phase included.
        The fleet watchdog captures these BEFORE quarantining a wedged
        replica, so a victim's phase budget lands in the forensic
        record next to its goodput split.  Snapshot under the state
        lock; the finalize arithmetic runs outside it."""
        with self._lock:
            reqs = ([r for r in self._queue]
                    + [st.req for st in self._prefills]
                    + [r for r in self._slots if r is not None])
        now = time.perf_counter()
        out: Dict[str, dict] = {}
        for req in reqs:
            if req.phases is None or not req.trace_id:
                continue
            ph = dict(req.phases)
            if req._cp_wait is not None:
                ph[req._cp_wait] = ph.get(req._cp_wait, 0.0) \
                    + max(0.0, now - req._cp_t0)
            e2e = req.e2e_base + max(0.0, now - req.submit_time)
            out[req.trace_id] = critpath_lib.finalize(ph, e2e)
        return out

    def export(self, req: Request,
               timeout_s: Optional[float] = None) -> RequestSnapshot:
        """Export one in-flight request as a portable
        ``RequestSnapshot`` and retire it here with status
        ``migrated`` (live migration, docs/RESILIENCE.md).

        The export serializes against the pump: with ``timeout_s=None``
        it waits for the running tick and is fully atomic (tokens are
        delivered entirely before the snapshot or entirely after — the
        snapshot and the callback stream can never disagree).  With a
        ``timeout_s`` the pump mutex is only awaited that long — a
        WEDGED pump (fleet watchdog quarantine) is then bypassed: the
        snapshot is still consistent (host bookkeeping is lock-
        protected and the wedged tick's late deliveries are dropped at
        the terminal-status check), but it is stamped ``clean=False``
        because a delivery racing the forced capture may be
        regenerated by the destination — exactly-once streaming then
        needs an offset-deduplicating consumer (the fleet router's
        stream shim).

        Raises ``RuntimeError`` when the request reached a terminal
        status first (finished/cancelled mid-export): there is nothing
        left to migrate."""
        if timeout_s is None:
            clean = self._pump_lock.acquire()
        else:
            clean = self._pump_lock.acquire(timeout=timeout_s)
        try:
            return self._export(req, clean)
        finally:
            if clean:
                self._pump_lock.release()

    def export_all(self, timeout_s: Optional[float] = None
                   ) -> List[RequestSnapshot]:
        """Export EVERY in-flight request (rid order, so a replayed
        migration re-admits deterministically), leaving the scheduler
        empty of user work.  The drain-timeout and replica-quarantine
        path."""
        if timeout_s is None:
            clean = self._pump_lock.acquire()
        else:
            clean = self._pump_lock.acquire(timeout=timeout_s)
        try:
            with self._lock:
                reqs = ([r for r in self._queue]
                        + [st.req for st in self._prefills]
                        + [r for r in self._slots if r is not None])
            snaps = []
            for req in sorted(reqs, key=lambda r: r.rid):
                try:
                    snaps.append(self._export(req, clean))
                except RuntimeError:
                    continue          # finished while we were exporting
            return snaps
        finally:
            if clean:
                self._pump_lock.release()

    def _export(self, req: Request, clean: bool) -> RequestSnapshot:
        """Capture + retire (caller handled the pump mutex)."""
        if req.done.is_set():
            raise RuntimeError(
                f"request {req.rid} already terminal ({req.status!r}); "
                "nothing to export")
        ctx = req.context if req.context is not None else req.prompt
        with self._lock:
            prefill = next((st for st in self._prefills
                            if st.req is req), None)
            row = next((r for r, other in enumerate(self._slots)
                        if other is req), None)
        active = row is not None
        generated = list(req.tokens)
        now = time.perf_counter()
        snap = RequestSnapshot(
            rid=req.rid, prompt=req.prompt.copy(),
            generated=generated,
            max_new_tokens=req.max_new_tokens,
            stream_offset=len(generated),
            tenant=req.tenant, adapter_id=req.adapter_id,
            deadline_remaining_s=(None if req.deadline is None
                                  else max(0.0, req.deadline - now)),
            sampling=dict(self._sampling), clean=clean)
        if req.phases is not None:
            # critpath carry: a COPY with the open wait phase closed at
            # the export instant; the importer charges the
            # export->import gap to ``migration`` and resumes the
            # stopwatch on its own clock (perf_counter instants are
            # comparable in-process, where fleet migration lives)
            ph = dict(req.phases)
            if req._cp_wait is not None:
                ph[req._cp_wait] = ph.get(req._cp_wait, 0.0) \
                    + max(0.0, now - req._cp_t0)
            snap.critpath = {
                "phases": ph,
                "elapsed_s": req.e2e_base
                + max(0.0, now - req.submit_time),
                "exported_at": now,
            }
        # lease handoff (serve/pages.py): publish the request's FINAL
        # full pages into the radix tree before the retirement below
        # releases them — a re-import into this engine then skips those
        # prefill windows.  "Final" = columns the device has finished:
        # the whole context plus all but the newest generated token for
        # an active row (its K/V is written when it is next FED), or
        # the completed windows of an in-flight prefill (the current
        # window may still be mid-dispatch under a forced export).
        lease = req._lease
        if lease is not None and not lease.released:
            fresh = generated[req.resumed:]
            if active:
                written = ctx.size + max(0, len(fresh) - 1)
                full = (np.concatenate(
                            [ctx, np.asarray(fresh, np.int32)])
                        if fresh else ctx)
            else:
                # the windows a prefill has completed end where its next
                # one starts (the current one may be mid-dispatch)
                written = (prefill.plan[prefill.next][0]
                           if prefill is not None else lease.skip)
                full = ctx
            published_ctx = full[:written]
            if self._stateful and active and clean:
                # pages without the state after them would be a miss on
                # re-import: snapshot the row too (clean = pump mutex
                # held, so a device copy may be dispatched)
                self.pages.register(lease, published_ctx)
                self._snapshot_state(req, lease, published_ctx, row,
                                     "export")
            self.pages.handoff(lease, published_ctx)
            # page-wire manifest: the chain keys just handed off — the
            # fleet's wire (fleet/pagewire.py) may ship those pages so
            # the destination skips their prefill windows.  Chains are
            # re-verified against the live radix tree at capture time
            # (``chain_pages``), so eviction between now and then only
            # shrinks what ships, never corrupts it.
            # (none for a recurrent-state model: the wire ships no state
            # snapshots, and pages alone would be a wrong hit)
            keys = (() if self._stateful else pages_lib.prompt_chain_keys(
                published_ctx, self.page_size))
            if keys:
                snap.shipped_pages = keys
                snap.page_size = self.page_size
        if not self.cancel(req, status="migrated"):
            raise RuntimeError(
                f"request {req.rid} finished during export")
        if req.trace_id:
            # the lane continues on the importer: close this replica's
            # stage and start the migration flow arrow
            snap.trace_id = req.trace_id
            reqtrace.exported(req.trace_id, rid=req.rid,
                              generated=len(generated),
                              clean=bool(clean))
        return snap

    def export_chain_pages(self, context: np.ndarray,
                           timeout_s: Optional[float] = None) -> list:
        """Page-wire sender capture (fleet/pagewire.py): read the radix
        pages covering ``context``'s full chunks off the device —
        ``[(chunk_index, chain_hash, {leaf: np.ndarray})]``, each
        payload one ``[L, page_size, ...]`` page per pool leaf (int8
        scale planes ride as ordinary leaves).  Runs under the pump
        mutex: eviction lives inside ``begin``'s allocation, which the
        same mutex serializes, so the looked-up pages cannot be
        recycled mid-read.  Every failure shape degrades to ``[]`` —
        pump busy past ``timeout_s``, prefix cache off, nothing cached
        — because shipping fewer pages only costs prefill windows,
        never correctness."""
        import jax

        if self._stateful:
            raise ValueError(_WIRE_DECLINED)
        if not self.pages.prefix_cache:
            return []
        if timeout_s is None:
            ok = self._pump_lock.acquire()
        else:
            ok = self._pump_lock.acquire(timeout=timeout_s)
        if not ok:
            return []                    # pump wedged: ship nothing
        try:
            entries = self.pages.chain_pages(
                np.asarray(context, np.int32).reshape(-1))
            if not entries:
                return []
            # ONE gather shape (pages_per_slot, the page-table row
            # width): pad with the trash page so any chain length is
            # the same traced program (RetraceGuard budget=1)
            idx = np.zeros((self._page_tab.shape[1],), np.int32)
            for j, (_, page, _) in enumerate(entries):
                idx[j] = page
            # dispatch under the mutex — stream order puts the copy
            # ahead of any later donating tick — but WAIT for the
            # fresh output buffers after releasing it
            with trace_lib.span("serve.wire_gather"):
                view_dev = self._wire_gather(self._cache["kv"], idx)
                trace_lib.dispatched("wire_gather", view_dev)
        finally:
            self._pump_lock.release()
        view = jax.device_get(view_dev)
        return [(chunk, chain,
                 {k: np.asarray(v[:, j]) for k, v in view.items()})
                for j, (chunk, _, chain) in enumerate(entries)]

    def import_wire_pages(self, context: np.ndarray, records,
                          timeout_s: Optional[float] = None) -> int:
        """Page-wire receiver splice: adopt shipped pages for
        ``context``'s leading full chunks into this engine's pool
        through the SAME lease seam every request uses — ``begin`` the
        shipped prefix (radix hits dedup chunks we already hold, which
        makes re-delivery idempotent), write each still-missing chunk's
        payload into its leased page, ``handoff`` to publish the chain.
        The next ``import_snapshot`` then radix-matches and skips those
        prefill windows.  Returns chunks now cached for the context
        (0 = adopt nothing: wrong page size, alien leaf layout, chain
        mismatch, pool exhausted, or pump busy past ``timeout_s`` —
        all degrade to plain re-prefill)."""
        if self._stateful:
            raise ValueError(_WIRE_DECLINED)
        if not self.pages.prefix_cache or not records:
            return 0
        pg = self.page_size
        ctx = np.asarray(context, np.int32).reshape(-1)
        expected = pages_lib.prompt_chain_keys(ctx, pg)
        if timeout_s is None:
            ok = self._pump_lock.acquire()
        else:
            ok = self._pump_lock.acquire(timeout=timeout_s)
        if not ok:
            return 0                     # pump wedged: re-prefill
        try:
            # shape vetting reads _cache under the same mutex that
            # serializes every rebind of it (ticks donate)
            kv_host_shapes = {
                k: (tuple(v.shape[:1]) + tuple(v.shape[2:]), v.dtype)
                for k, v in self._cache["kv"].items()}
            take = []
            for j, rec in enumerate(sorted(records,
                                           key=lambda r: r.index)):
                if rec.index != j or j >= len(expected) \
                        or rec.chain != expected[j][0]:
                    break                # gap or foreign chain: stop
                if set(rec.payload) != set(kv_host_shapes):
                    return 0             # alien pool layout
                bad = any(
                    tuple(rec.payload[k].shape) != kv_host_shapes[k][0]
                    or rec.payload[k].dtype != kv_host_shapes[k][1]
                    for k in kv_host_shapes)
                if bad:
                    return 0             # page-size/dtype mismatch
                take.append(rec)
            if not take:
                return 0
            ship = ctx[:len(take) * pg]
            try:
                lease = self.pages.begin(ship, ship.size)
            except pages_lib.PagePoolExhausted:
                return 0                 # no room: re-prefill instead
            kv = self._cache["kv"]
            try:
                # chunks below lease.skip are radix hits the pool
                # already holds (free dedup; a COW'd final chunk costs
                # one redundant page write); the rest get the shipped
                # payload spliced into their freshly leased pages
                for j in range(lease.skip // pg, len(take)):
                    with trace_lib.span("serve.wire_splice"):
                        kv = self._wire_splice(kv,
                                               np.int32(int(lease.row[j])),
                                               take[j].payload)
                        trace_lib.dispatched("wire_splice", kv)
            except BaseException:
                # _wire_splice donates: rebind the latest buffers so
                # the pool is never left holding freed device memory
                self._cache["kv"] = kv
                self.pages.release(lease)
                raise
            self._cache["kv"] = kv
            self.pages.handoff(lease, ship)
        finally:
            self._pump_lock.release()
        return len(take)

    def import_snapshot(self, snap: RequestSnapshot,
                        on_token: Optional[Callable[[List[int]], None]]
                        = None) -> Request:
        """Admit an exported request and resume it where it stopped.

        The new request's prefill context is ``prompt + generated`` —
        the destination rebuilds the KV cache through the SAME chunked-
        prefill executables every fresh prompt uses (no new programs,
        RetraceGuard budget=1 holds; a radix prefix hit makes the warm
        handoff cheap), then the last window's logits yield the NEXT
        token and decode continues.  ``generated`` pre-seeds the token
        list, so callbacks fire only for new tokens (exactly-once
        streaming at ``stream_offset``) and the terminal ``tokens`` are
        the full sequence.  Admission control is the same as
        ``submit``: queue depth (``QueueFullError``) and tenancy quotas
        apply, charged at the REMAINING budget.

        Raises ``ValueError`` for a snapshot this engine cannot resume
        faithfully: exhausted budget, context too long for ``max_len``,
        or a sampling config differing from the source's."""
        prompt = np.asarray(snap.prompt, np.int32).reshape(-1)
        generated = [int(t) for t in snap.generated]
        if snap.sampling is not None and snap.sampling != self._sampling:
            raise ValueError(
                f"sampling config mismatch: snapshot {snap.sampling} "
                f"vs engine {self._sampling} — resuming here would "
                "silently change the request's distribution")
        remaining = int(snap.max_new_tokens) - len(generated)
        if remaining < 1:
            raise ValueError(
                f"snapshot {snap.rid} has no remaining budget "
                f"({len(generated)}/{snap.max_new_tokens} generated)")
        ctx = (np.concatenate([prompt, np.asarray(generated, np.int32)])
               if generated else prompt)
        clen = int(ctx.size)
        if clen < 1:
            raise ValueError("empty snapshot context")
        if snap.adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "snapshot carries adapter_id but this engine has no "
                    "adapter table (adapter_capacity > 0)")
            if not self.adapters.known(snap.adapter_id):
                raise KeyError(f"unknown adapter_id {snap.adapter_id!r}; "
                               "load_adapter() it first")
        padded = -(-clen // self.prefill_chunk) * self.prefill_chunk
        if clen + remaining > self.max_len or padded > self.max_len:
            raise ValueError(
                f"snapshot context ({clen}, chunk-padded {padded}) + "
                f"remaining budget ({remaining}) exceeds max_len "
                f"{self.max_len}")
        now = time.perf_counter()
        tenant = str(snap.tenant)
        # critpath resume (outside the state lock): a snapshot carrying
        # accrual continues it here regardless of the LOCAL ledger
        # state — losing a migrated request's history would break the
        # sums-to-e2e invariant the chaos property test asserts.  The
        # export->import gap is the ``migration`` phase (clamped at 0:
        # a cross-host import's foreign perf_counter origin contributes
        # no gap rather than garbage).
        carry = snap.critpath
        cp_base = 0.0
        if carry is not None:
            src = carry.get("phases") or {}
            cp_phases = {p: float(src.get(p, 0.0))
                         for p in critpath_lib.PHASES[:-1]}
            gap = max(0.0, now - float(carry.get("exported_at", now)))
            cp_phases["migration"] += gap
            cp_base = float(carry.get("elapsed_s", 0.0)) + gap
        else:
            cp_phases = critpath_lib.new_phases()
        with self._lock:
            if self.max_queue_depth is not None \
                    and len(self._queue) >= self.max_queue_depth:
                raise QueueFullError(
                    f"queue at max_queue_depth={self.max_queue_depth}; "
                    "retry after in-flight requests retire")
            if self.tenancy is not None:
                self.tenancy.check_admission(
                    tenant, remaining,
                    inflight=self._tenant_inflight.get(tenant, 0),
                    tokens_inflight=self._tenant_tokens.get(tenant, 0))
            req = Request(rid=self._next_rid, prompt=prompt,
                          max_new_tokens=int(snap.max_new_tokens),
                          on_token=on_token, submit_time=now,
                          deadline=(None
                                    if snap.deadline_remaining_s is None
                                    else now + snap.deadline_remaining_s),
                          tenant=tenant, adapter_id=snap.adapter_id,
                          context=ctx, resumed=len(generated),
                          token_cost=remaining,
                          trace_id=snap.trace_id)
            req.tokens = list(generated)
            req.phases = cp_phases
            req.e2e_base = cp_base
            req._cp_t0 = now
            self._next_rid += 1
            self._enqueue_locked(req)
        if req.trace_id:
            # NOT submitted(): the lane is already open — finish the
            # flow arrow and re-enter queued on the same async id
            reqtrace.imported(req.trace_id, rid=req.rid,
                              resumed=req.resumed)
        self.metrics.submitted(req)
        self._report_depth()
        return req

    # ------------------------------------------------------ bookkeeping

    def _deliver(self, req: Request, toks: List[int]) -> None:
        plan = faults_lib.active()
        if plan is not None:
            # chaos: may fail THIS request only.  The injection hook is
            # the test double for this delivery path — it runs exactly
            # where the real callback does, pump mutex and all
            plan.on_decode(req.rid)  # dtlint: disable=DT303 -- see above
        req.tokens.extend(toks)
        self.metrics.emitted(req, len(toks))
        if req.on_token is not None:
            # state lock NOT held here (submit/cancel/stats stay live);
            # the pump mutex is — delivery is the tick's last phase, and
            # callbacks are documented to never re-enter step()
            req.on_token(toks)  # dtlint: disable=DT303 -- see comment

    def _retire_accounting(self, req: Request) -> bool:
        """Shared terminal bookkeeping: per-tenant in-flight counters
        come down, the adapter pin (if any) is released, and a fair-
        share queue is told the request left the system.  Claim-once:
        returns False when another thread already retired the request
        (cancel racing the pump), so status/metrics fire exactly once."""
        with self._lock:
            if req._retired:
                return False
            req._retired = True
            t = req.tenant
            n = self._tenant_inflight.get(t, 0) - 1
            if n > 0:
                self._tenant_inflight[t] = n
            else:
                self._tenant_inflight.pop(t, None)
            k = self._tenant_tokens.get(t, 0) - req.token_cost
            if k > 0:
                self._tenant_tokens[t] = k
            else:
                self._tenant_tokens.pop(t, None)
            release = getattr(self._queue, "release", None)
            if release is not None:
                release(req)
        if req.adapter_row is not None and self.adapters is not None:
            # outside the state lock: release takes the adapter table's
            # own lock (lock order stays scheduler-independent)
            self.adapters.release(req.adapter_id)
            req.adapter_row = None
        if req._lease is not None:
            # same discipline for the page lease: the pool has its own
            # lock, release is idempotent, and shared prefix pages stay
            # CACHED (refcount drops; eviction reclaims them only under
            # allocation pressure)
            self.pages.release(req._lease)
        return True

    def _finalize_critpath(self, req: Request) -> None:
        """Close the request's phase accrual into the finished
        breakdown (obs/critpath.py), attach it to the request, and fold
        it into the active ledger.  Runs inside the claim-once
        retirement (so exactly once per request) with ``finish_time``
        already stamped; ``migrated`` requests carry their accrual on
        the snapshot instead — finalizing the hop here too would
        double-count it on the importer."""
        if req.phases is None or req.status == "migrated":
            return
        now = req.finish_time
        if req._cp_wait is not None:
            req.phases[req._cp_wait] += max(0.0, now - req._cp_t0)
            req._cp_wait = None
        e2e = req.e2e_base + max(0.0, now - req.submit_time)
        req.critpath = critpath_lib.finalize(req.phases, e2e)
        critpath_lib.observe(req.tenant, req.critpath,
                             trace_id=req.trace_id)

    def _finish(self, req: Request) -> None:
        if not self._retire_accounting(req):
            return
        req.status = "ok"
        req.finish_time = time.perf_counter()
        self._finalize_critpath(req)
        if req.trace_id:
            # claim-once above guarantees exactly one terminal span;
            # the finished breakdown rides the terminal event's args
            extra = ({"critpath": req.critpath}
                     if req.critpath is not None else {})
            reqtrace.retired(req.trace_id, "ok", tokens=len(req.tokens),
                             **extra)
        self.metrics.finished(req)
        req.done.set()

    def _abort(self, req: Request, status: str,
               error: Optional[BaseException] = None) -> None:
        if not self._retire_accounting(req):
            return
        req.status = status
        req.error = error
        req.finish_time = time.perf_counter()
        self._finalize_critpath(req)
        if req.trace_id:
            # "migrated" is a no-op here: exported() owns the hop
            extra = ({"critpath": req.critpath}
                     if req.critpath is not None else {})
            reqtrace.retired(req.trace_id, status,
                             tokens=len(req.tokens), **extra)
        self.metrics.aborted(req, status)
        req.done.set()

    def _report_depth(self) -> None:
        self.metrics.depth(self.stats())


# --------------------------------------------------- dtlint graph tier

# The serving contract this whole file is built around: exactly THREE
# hot programs — the two window programs at the ladder's row counts
# (``_window_rungs``: the same program at a few batch sizes, listed here
# at the largest), and the decode tick — all compiled at construction, so
# admission/retirement never recompiles.  DT405 makes that a lint
# invariant — a fourth jitted program (or two of the three collapsing
# into one) fails `scripts/lint.sh` statically instead of surfacing as a
# RetraceGuard warning at serve time.
graph_lib.expect_census("serve-hot", 3)


@graph_lib.trace_entry("serve", group="serve-hot",
                       hbm_budget=2 << 20)
def _graph_entries():
    """Registry-scale serve build for the DT4xx pack: a tiny CPU config
    with ABSTRACT params (``jax.eval_shape`` — no weights materialize),
    running the same ``__init__`` jit-builder code as production.  The
    HBM budget pins the tiny build's working set: a structural change
    that blows up peak memory (a gather materializing the whole pool, a
    lost donation) trips DT404 here at the small scale where the ratio
    is the same."""
    import jax
    from ..models.gpt import gpt_tiny

    model = gpt_tiny(vocab_size=64, hidden_size=32, num_heads=2,
                     intermediate_size=64, max_position=32,
                     dropout_rate=0.0)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sched = SlotScheduler(model, params, num_slots=2, max_len=32,
                          prefill_chunk=8, tick_steps=2,
                          temperature=0.0)
    return sched.graph_targets(hbm_budget=2 << 20)


# A model with recurrent state beside K/V (models/hybrid.py) pins FIVE:
# the same three and the two state-snapshot copies.  They are programs of
# their own because a turn's end is known only after the tick's fetch, so
# its snapshot cannot ride inside the tick, and a restore precedes the
# first window of a prefill whose windows are otherwise all alike; both are
# row copies listed by ``graph_targets()`` with the three, so they are
# warmed before a serving window and can never compile inside one.
graph_lib.expect_census("serve-hot-state", 5)


@graph_lib.trace_entry("serve-state", group="serve-hot-state",
                       hbm_budget=4 << 20)
def _graph_entries_state():
    """The same registry-scale build over a toy state-space / attention
    decoder: the three hot programs carrying the slot state, and the
    snapshot and restore copies."""
    import jax
    from ..models.hybrid import hybrid_tiny

    model = hybrid_tiny(vocab_size=64, max_position=32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sched = SlotScheduler(model, params, num_slots=2, max_len=32,
                          prefill_chunk=8, tick_steps=2,
                          temperature=0.0)
    return sched.graph_targets(hbm_budget=4 << 20)
