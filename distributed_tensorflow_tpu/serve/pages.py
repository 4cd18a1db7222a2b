"""Paged KV cache + shared-prefix (radix) reuse: the memory layer of
the serving tier.

The batch dimension of the K/V cache is a bank of SLOTS, each one
independent in-flight request at its own length.  A full ``[max_len]``
K/V stripe per slot would scale HBM with the WORST-CASE length, and two
requests sharing a system prompt would each hold their own copy of its
K/V.  So the storage under the slots is fixed-size PAGES:

* **Device**: one pool of ``num_pages`` pages per K/V leaf —
  ``[L, num_pages, page_size, kv_heads * head_dim]``: a token's heads
  are one FLAT row, for every model family, so a page is ``page_size``
  sublanes by whole lane tiles and the pool tiles on the TPU almost
  unpadded (``kv_pool_bytes`` says by how much; ``[.., 25, 64]`` minor
  dimensions padded 2.56 x).  int8 scale planes ride along as
  ``[..., kv_heads]`` (the PR 4 splice-exact per-(token, head) scales).
  Page 0 is the reserved TRASH page: retired rows' frozen writes and
  prefill pad columns land there, and no validity mask ever admits its
  cells.
* **Host** (``PagePool``): free-list + per-page refcounts + the radix
  (prefix) tree, all under one lock.  Logical slot columns map to pool
  pages through a per-slot PAGE TABLE — a small host int32 row handed
  to the hot executables as a TRACED argument, so allocation, sharing,
  and retirement never recompile anything (``GPT.decode_window_paged``
  / ``GPT.decode_step_slots_paged`` read through the table and write
  page-indexed).

**Radix prefix cache.**  Prompts are keyed by ``page_size``-token
chunks: a tree node per FULL chunk, holding the pool page with that
chunk's K/V.  A request whose prompt starts with cached chunks maps
those pages read-only (refcount++) and starts its chunked prefill at
``pos = skip`` — the skipped windows are never dispatched, which is the
whole TTFT/FLOPs win.  At admission the request's own full prompt pages
are registered back into the tree, so the FIRST request with a system
prompt seeds the cache for every follower.

Immutability makes copy-on-write cheap: only FULL chunks are ever
shared, so a shared page is never written again (decode writes start at
``write_col >= prompt_len``, always on a private page).  The one COW
case — a prompt exactly equal to a cached chain, whose last page must
take decode writes — is split by RE-PREFILLING that page into a fresh
private copy (bit-identical by construction: same tokens, same
executable) instead of a device copy; ``cow_splits_total`` counts it.

Eviction is LRU over refcount-0 LEAF nodes (a pinned chain can never
lose an interior page): when ``allocate`` finds the free list short it
evicts stale chains page by page, and only gives up —
``PagePoolExhausted``, the scheduler requeues the request — when every
remaining page is pinned by an in-flight request.

**State snapshots** (a model whose slot cache holds recurrent state
beside K/V, ``paged_cache_spec()["state"]``).  K/V pages of a shared
prefix are useless to such a model without the recurrent state at exactly
the prefix's last token, so the pool keeps, beside the chains, SNAPSHOTS:
each names a row of the scheduler's device snapshot arrays, hangs off the
radix node of its last full page, and carries the tokens past that page
boundary (``tail``) with a pool page of its own holding their K/V (a hit
COPIES that partial page, it never shares it).  ``begin`` returns as a
hit the deepest snapshot whose chain and tail match the prompt — pages
matched beyond it are a miss, and are asked for as ``lease.snap_at``: the
depth where this prompt met an existing chain, which is where the NEXT
request with that prefix wants a snapshot.  Snapshots are taken at three
depths (serve/scheduler.py): the end of a prompt's prefill, a turn's end
(``handoff``), and such a meeting point.  They count against a fixed
number of rows (the budget), are evicted least-recently-used when the rows
run out, and go with their node when the page LRU evicts it: none outlives
its chain, and a chain whose snapshot is gone is a miss at that depth.

**Prefix fingerprint.**  The pool also maintains a BOUNDED digest of
its hot radix chains — at most ``fingerprint_k`` entries mapping a
chain hash (the incremental blake2b of the chunk bytes from the root,
carried on every node) to the cached prefix length in tokens, scored
by cached length × LRU recency.  It is updated incrementally where the
tree itself changes (``register``/``handoff`` extend it, eviction
removes the reclaimed chain, ``begin`` refreshes the recency of a hit
chain) — NEVER by walking the tree — so ``stats()`` can publish it as
a lock-cheap copy.  The fleet router scores placement candidates
against it (``fleet.router.expected_pages_reused``): the request-side
half of the same hash chain is :func:`prompt_chain_keys`.

Thread-safety: every ``PagePool`` method takes the pool's own lock and
never calls back out, so the scheduler may call it from ``submit``/
``cancel`` threads as well as the pump (lock order: scheduler state
lock -> pool lock, never the reverse).
"""
from __future__ import annotations

import hashlib
import heapq
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["FINGERPRINT_K", "PageLease", "PagePool", "PagePoolExhausted",
           "StateSnapshot", "auto_page_size", "decode_paged_step",
           "init_paged_cache", "init_state_snapshots", "kv_pool_bytes",
           "prompt_chain_keys", "state_bytes_per_slot"]

# default bound on the hot-chain fingerprint (entries, not pages): big
# enough for a handful of system prompts at every chunk depth, small
# enough that copying it in stats() stays lock-cheap
FINGERPRINT_K = 32


def _chain_hash(parent_chain: bytes, chunk: bytes) -> bytes:
    """One incremental step of the chain hash: H(parent || chunk).
    blake2b-64: process-stable (placement must replay across runs,
    unlike ``hash()``), 8 bytes because fingerprint keys are a
    popularity digest, not a cryptographic commitment."""
    return hashlib.blake2b(parent_chain + chunk, digest_size=8).digest()


def prompt_chain_keys(prompt, page_size: int
                      ) -> Tuple[Tuple[bytes, int], ...]:
    """The request-side half of the prefix fingerprint: ``(chain hash,
    tokens covered)`` for every full ``page_size``-token chunk prefix
    of ``prompt`` — exactly the keys ``PagePool.register`` publishes,
    so ``fingerprint.get(key)`` answers "how many of this prompt's
    leading tokens does that replica already hold"."""
    pg = int(page_size)
    if pg < 1:
        return ()
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    out = []
    chain = b""
    for j in range(prompt.size // pg):
        chain = _chain_hash(chain, prompt[j * pg:(j + 1) * pg].tobytes())
        out.append((chain, (j + 1) * pg))
    return tuple(out)


class PagePoolExhausted(RuntimeError):
    """``allocate`` could not find enough free/evictable pages: every
    remaining page is pinned by an in-flight request.  Backpressure,
    not failure — the scheduler requeues and retries after a
    retirement frees pages."""


def auto_page_size(max_len: int, target: int = 16,
                   multiple_of: int = 1) -> int:
    """Largest divisor of ``max_len`` that is <= ``target``.  Pages
    must tile ``max_len`` exactly so the gathered page view has the
    SAME shape as a ``[max_len]`` cache row of ``GPT.init_cache`` — that
    shape equality is what makes paged attention bit-identical to the
    ``generate()`` path.

    ``multiple_of`` additionally constrains the result to multiples of
    that value — the fused paged-attention kernel's lane-tileability
    rule (``ops.pallas.MIN_PAGE_SIZE``): Mosaic tiles a page block in
    sublane units of 8, so the scheduler asks for ``multiple_of=8``
    when the kernel is in play.  Falls back to the unconstrained pick
    (kernel-incompatible — the scheduler then logs and takes the
    gather path) when no such divisor exists."""
    for d in range(min(target, max_len), 0, -1):
        if max_len % d == 0 and d % multiple_of == 0:
            return d
    if multiple_of > 1:
        return auto_page_size(max_len, target)
    return 1


def init_paged_cache(model, num_slots: int, num_pages: int,
                     page_size: int):
    """Device state for a paged slot cache, built from what the model
    says a slot's cache is made of (``model.paged_cache_spec()``:
    ``kv_layers``, ``kv`` {leaf: (per-token shape, dtype)}, ``state``
    {leaf: (layers, per-slot shape, dtype)}, empty for a model that
    caches keys and values only): a page-pool K/V subtree (``[kv_layers,
    num_pages, page_size, kv_heads * head_dim]`` leaves, a token's heads
    one flat row; int8 scale planes ``[..., kv_heads]`` included)
    plus the per-slot column state [S] — a slot's tokens occupy the
    LOGICAL column run ``[start_col, write_col)`` (validity is two ints;
    the gather read derives its boolean view per step, the kernel
    walks the run's pages), ``positions`` is its next position index;
    all slots start retired (an empty window at column 0) — and, for
    a model with recurrent state, one ``[layers, num_slots, ...]`` block
    per state leaf under ``"state"`` (absent otherwise, so a K/V-only
    model's programs are what they were).  The per-token leaves need not
    be keys and values: the pool has whatever leaves the spec names (a
    latent and a shared rotary key, models/longcat_flash.py), and only a
    model whose leaves are ``k`` / ``v`` rows can say ``paged_kernel_ok``.
    A model that counts on the device (``spec["counters"]`` {leaf: (shape,
    dtype)}: an expert layer's router statistics) gets those leaves, zero,
    under ``"counters"``: its programs add to them and the scheduler reads
    and clears them with the fetches it makes anyway."""
    import jax.numpy as jnp
    spec = model.paged_cache_spec()
    cache = {"kv": {name: jnp.zeros(
                 (spec["kv_layers"], num_pages, page_size) + tuple(tail),
                 dtype) for name, (tail, dtype) in spec["kv"].items()},
             "start_col": jnp.zeros((num_slots,), jnp.int32),
             "write_col": jnp.zeros((num_slots,), jnp.int32),
             "positions": jnp.zeros((num_slots,), jnp.int32)}
    if spec["state"]:
        cache["state"] = init_state_snapshots(model, num_slots)
    if spec.get("counters"):
        cache["counters"] = {name: jnp.zeros(shape, dtype) for name,
                             (shape, dtype) in spec["counters"].items()}
    return cache


def kv_pool_bytes(kv) -> Tuple[int, int]:
    """``(logical, tiled)`` bytes of a pool's K/V leaves (arrays or
    shapes).  Tiled is what the TPU holds: the two minor dimensions
    rounded up to the dtype's tile, 128 lanes by 8 sublanes of 32 bits
    (so 16 rows of bf16, 32 of int8) — a ``[page_size, kv_heads *
    head_dim]`` page of GPT-2-XL pads 4 % where ``[.., 25, 64]`` padded
    156 %."""
    logical = tiled = 0
    for leaf in kv.values():
        *lead, rows, lanes = leaf.shape
        itemsize = np.dtype(leaf.dtype).itemsize
        sublanes = 8 * max(1, 4 // itemsize)
        lead = int(np.prod(lead, dtype=np.int64)) * itemsize
        logical += lead * rows * lanes
        tiled += lead * (-(-rows // sublanes) * sublanes
                         * -(-lanes // 128) * 128)
    return logical, tiled


def init_state_snapshots(model, rows: int):
    """``rows`` blocks of the model's per-slot recurrent state:
    ``{leaf: [layers, rows, ...]}`` zeros.  The slot cache's ``"state"``
    and the scheduler's snapshot arrays have this one layout, so a
    snapshot or a restore is a row copy."""
    import jax.numpy as jnp
    return {name: jnp.zeros((layers, rows) + tuple(shape), dtype)
            for name, (layers, shape, dtype)
            in model.paged_cache_spec()["state"].items()}


def state_bytes_per_slot(model) -> int:
    """Bytes of recurrent state one slot (or one snapshot) holds."""
    return sum(int(layers) * int(np.prod(shape)) * np.dtype(dtype).itemsize
               for layers, shape, dtype
               in model.paged_cache_spec()["state"].values())


def decode_paged_step(model, params, cache, page_tab, tokens, live,
                      adapters=None, adapter_rows=None,
                      use_kernel: bool = False):
    """One decode step for every slot against the page pool -> (logits
    [S, vocab], new cache).  ``tokens`` [S]: each live slot's input
    token (the one it emitted last).  Dead rows compute too (static
    shapes — the price of never recompiling) but their state is FROZEN:
    only ``live`` rows advance write_col/positions, and row independence
    makes live rows' logits bit-identical whatever the dead rows hold.
    ``page_tab`` [S, pages_per_slot] is the traced page-table snapshot
    for this tick (retired rows map the trash page, so their frozen
    writes can never touch a live page).
    ``use_kernel`` (STATIC, resolved once at scheduler construction):
    read through the fused Pallas page-walk kernel instead of the XLA
    gather (models/gpt.py ``decode_step_slots_paged``)."""
    import jax.numpy as jnp
    # a model with recurrent state advances it here, row by row, and
    # leaves the rows that are not live exactly as they were
    stateful = ({"state": cache["state"], "live": live}
                if "state" in cache else {})
    # a model that counts on the device adds this step's live rows to its
    # counters (third of what it returns, after kv and any state)
    if "counters" in cache:
        stateful = dict(stateful, counters=cache["counters"], live=live)
    start_col = cache["start_col"]
    if use_kernel:
        # the kernel walks the pages a row's columns lie on: a row that
        # is not live is handed an empty run, and walks none
        start_col = jnp.where(live, start_col, cache["write_col"] + 1)
    logits, *new = model.decode_step_slots_paged(
        params, cache["kv"], tokens, page_tab, start_col,
        cache["write_col"], cache["positions"],
        adapters=adapters, adapter_rows=adapter_rows,
        use_kernel=use_kernel, **stateful)
    live = live.astype(jnp.int32)
    return logits, dict(
        zip([n for n in ("kv", "state", "counters") if n in cache], new),
        start_col=cache["start_col"],
        write_col=cache["write_col"] + live,
        positions=cache["positions"] + live)


class _RadixNode:
    """One FULL prompt chunk: the pool page holding its K/V, its place
    in the tree, a refcount (in-flight requests mapping it), and an
    LRU stamp (monotonic counter, not wall clock — eviction order must
    replay deterministically)."""

    __slots__ = ("page", "parent", "children", "refcount", "stamp",
                 "key", "chain", "snap")

    def __init__(self, page: int, parent: Optional["_RadixNode"],
                 key: bytes, stamp: int):
        self.snap: Optional["StateSnapshot"] = None
        self.page = page
        self.parent = parent
        self.children: Dict[bytes, "_RadixNode"] = {}
        self.refcount = 0
        self.stamp = stamp
        self.key = key
        # incremental chain hash from the root — the fingerprint key
        # for "the prefix ending at this node", paid once at node
        # creation instead of on every fingerprint update
        self.chain = (_chain_hash(parent.chain, key)
                      if parent is not None else b"")


class StateSnapshot:
    """One recurrent-state snapshot (module doc): ``row`` of the
    scheduler's device snapshot arrays holds the state after exactly
    ``depth`` tokens — the chain down to ``node`` plus ``tail``, the
    tokens past that page boundary, whose K/V sit in ``page`` (0 when the
    depth is a page boundary).  ``stamp`` orders the row LRU."""

    __slots__ = ("row", "node", "tail", "page", "stamp", "depth")

    def __init__(self, row: int, node: "_RadixNode", tail: np.ndarray,
                 page: int, stamp: int, depth: int):
        self.row, self.node, self.tail = row, node, tail
        self.page, self.stamp, self.depth = page, stamp, depth


class PageLease:
    """One request's page holdings: the page-table row it decodes
    through, which of those pages are shared radix nodes vs private,
    and how many logical columns the row maps.  Created by
    ``PagePool.begin`` at prefill start, registered into the radix tree
    at admission, released (idempotently) at retirement/cancel."""

    __slots__ = ("row", "n_pages", "skip", "shared", "private",
                 "released", "restore", "snap_at")

    def __init__(self, row: np.ndarray, n_pages: int, skip: int,
                 shared: List[_RadixNode], private: List[int]):
        self.row = row                   # [pages_per_slot] int32
        self.n_pages = n_pages           # mapped entries (shared+private)
        self.skip = skip                 # prefix tokens the prefill skips
        self.shared = shared             # radix nodes we hold a ref on
        self.private = private           # pool pages we own outright
        self.released = False
        # recurrent-state pools only: the device copy a hit starts from,
        # (snapshot row, its partial page, this lease's page for it), and
        # the depth at which this prompt met a chain without a snapshot
        self.restore: Optional[Tuple[int, int, int]] = None
        self.snap_at = 0


class PagePool:
    """Host bookkeeping for the device page pool: free list, refcounts,
    and the radix prefix tree.  All methods are thread-safe behind the
    pool's own lock and never invoke callbacks or block under it."""

    def __init__(self, num_pages: int, page_size: int,
                 pages_per_slot: int, prefix_cache: bool = True,
                 fingerprint_k: int = FINGERPRINT_K,
                 state_rows: Optional[int] = None,
                 state_row_bytes: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1; got {page_size}")
        if fingerprint_k < 0:
            raise ValueError(
                f"fingerprint_k must be >= 0; got {fingerprint_k}")
        if num_pages < pages_per_slot + 2:
            # one trash page + at least one full slot's worth: anything
            # smaller cannot serve even a single max-length request
            raise ValueError(
                f"num_pages must be >= pages_per_slot + 2 = "
                f"{pages_per_slot + 2}; got {num_pages}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        # prefix_cache=False: paged allocation only, no radix matching
        # or registration — the ablation arm bench.py measures the
        # reuse win against
        self.prefix_cache = bool(prefix_cache)
        # hot-chain digest: chain hash -> (cached tokens, recency
        # stamp), bounded to fingerprint_k entries (see module doc).  A
        # recurrent-state pool keeps none: cached PAGES do not say what
        # such a model can reuse (a hit needs a snapshot), and touching
        # the digest at every depth of 250-page chains was the largest
        # part of a tick's host time (PERF.md, PR 30)
        self.fingerprint_k = 0 if state_rows is not None \
            else int(fingerprint_k)
        self._fingerprint: Dict[bytes, Tuple[int, int]] = {}
        # recurrent-state snapshots (module doc): ``state_rows`` is the
        # budget in rows of the scheduler's snapshot arrays, None for a
        # model that caches keys and values only
        self.stateful = state_rows is not None
        self.state_rows = int(state_rows or 0)
        self.state_row_bytes = int(state_row_bytes)
        self._snap_free: List[int] = list(range(self.state_rows))
        self._snaps: Dict[int, StateSnapshot] = {}
        self.state_snapshots = 0
        self.state_restores = 0
        self.state_snapshots_evicted = 0
        self._lock = threading.Lock()
        # page 0 is the reserved trash page — never allocated
        self._free: List[int] = list(range(1, num_pages))
        self._root = _RadixNode(0, None, b"", 0)
        self._stamp = 0
        # live-lease accounting for the pages_per_request gauge
        self._lease_count = 0
        self._lease_pages = 0
        # counters (rendered via EngineStats -> /metrics)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.evictions = 0
        self.cow_splits = 0

    # ------------------------------------------------------------ intake

    def required_pages(self, total_cols: int) -> int:
        """Pages a request writing ``total_cols`` logical columns needs
        in the worst (no shared prefix) case."""
        return -(-int(total_cols) // self.page_size)

    def usable_pages(self) -> int:
        """Pool capacity minus the reserved trash page — the submit
        validation bound: one request may never need more."""
        return self.num_pages - 1

    def begin(self, prompt: np.ndarray, total_cols: int) -> PageLease:
        """Start one request: match its prompt against the radix tree
        (full ``page_size`` chunks only, always leaving at least one
        token to prefill so the last window can produce logits), pin
        the matched chain, allocate private pages for the rest, and
        return the lease with its page-table row.

        ``total_cols``: columns the request will ever write (prompt +
        decode budget).  Raises ``PagePoolExhausted`` — with every
        acquired ref rolled back — when not enough pages are free or
        evictable."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = prompt.size
        pg = self.page_size
        total = self.required_pages(max(total_cols, plen))
        if total > self.pages_per_slot:
            raise ValueError(
                f"request spans {total} pages > pages_per_slot "
                f"{self.pages_per_slot}")
        with self._lock:
            self.prefix_lookups += 1
            shared: List[_RadixNode] = []
            node = self._root
            chunks = plen // pg if self.prefix_cache else 0
            for j in range(chunks):
                child = node.children.get(
                    prompt[j * pg:(j + 1) * pg].tobytes())
                if child is None:
                    break
                shared.append(child)
                node = child
            hit = None
            snap_at = 0
            if self.stateful:
                # pages are a hit only as deep as a snapshot of the state
                # after exactly that many tokens (module doc)
                skip, keep = 0, 0
                for j, n in enumerate([self._root] + shared):
                    sn = n.snap
                    if sn is not None and sn.depth <= plen - 1 \
                            and np.array_equal(prompt[j * pg:sn.depth],
                                               sn.tail):
                        hit, skip, keep = sn, sn.depth, j
                met = min(len(shared), (plen - 1) // pg) * pg
                snap_at = met if met > skip else 0
                del shared[keep:]
            else:
                if len(shared) * pg >= plen:
                    # the whole prompt is a cached chain, but its last
                    # page must take this request's decode writes: split
                    # it off as a fresh private copy, re-prefilled rather
                    # than device-copied (bit-identical — same tokens,
                    # same executable).  This is the COW case.
                    shared.pop()
                    self.cow_splits += 1
                skip = len(shared) * pg
            stamp = self._next_stamp()
            for n in shared:
                n.refcount += 1
                n.stamp = stamp
            try:
                private = self._allocate_locked(total - len(shared))
            except PagePoolExhausted:
                for n in shared:          # roll back the pins
                    n.refcount -= 1
                raise
            if skip:
                self.prefix_hits += 1
                self.prefix_tokens_reused += skip
            for j, n in enumerate(shared):
                # refresh the hit chain's fingerprint recency at every
                # depth — the list we just walked, never a tree walk
                self._fp_touch_locked(n.chain, (j + 1) * pg, stamp)
            row = np.zeros((self.pages_per_slot,), np.int32)
            for j, n in enumerate(shared):
                row[j] = n.page
            row[len(shared):total] = private
            lease = PageLease(row, total, skip, shared, private)
            lease.snap_at = snap_at
            if hit is not None:
                hit.stamp = stamp
                self.state_restores += 1
                lease.restore = (hit.row, hit.page,
                                 int(row[len(shared)]) if hit.page else 0)
            self._lease_count += 1
            self._lease_pages += total
            return lease

    def register(self, lease: PageLease, prompt: np.ndarray) -> None:
        """Publish the lease's FULL prompt pages into the radix tree
        (called at admission, when their contents are final).  Pages
        donated to the tree move from the lease's private list to its
        shared refs; on a chunk another request registered first, stop
        — ours stay private (rare race, costs one duplicate page until
        retirement)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pg = self.page_size
        if not self.prefix_cache:
            return
        with self._lock:
            if lease.released:
                return               # cancelled before admission landed
            stamp = self._next_stamp()
            chunks = prompt.size // pg
            node, done = self._pinned_prefix_locked(lease, chunks)
            for j, pinned in enumerate(done):
                pinned.stamp = stamp
                self._fp_touch_locked(pinned.chain, (j + 1) * pg, stamp)
            for j in range(len(done), chunks):
                key = prompt[j * pg:(j + 1) * pg].tobytes()
                child = node.children.get(key)
                if child is not None:
                    child.stamp = stamp
                    node = child
                    self._fp_touch_locked(child.chain, (j + 1) * pg,
                                          stamp)
                    continue
                page = int(lease.row[j])
                if page not in lease.private:
                    break            # a shared entry we did not match??
                child = _RadixNode(page, node, key, stamp)
                child.refcount = 1   # the lease's own pin
                node.children[key] = child
                lease.private.remove(page)
                lease.shared.append(child)
                node = child
                # publish EVERY depth, not just the deepest: the
                # deepest node carries this prompt's unique suffix,
                # while followers match at the shared shallow depths
                self._fp_touch_locked(child.chain, (j + 1) * pg, stamp)

    def _pinned_prefix_locked(self, lease: PageLease, chunks: int
                              ) -> Tuple[_RadixNode, List[_RadixNode]]:
        """The lease's pinned nodes that ARE its chain's first chunks, in
        order — ``begin`` matched them against this prompt and
        ``register`` appends as it publishes — and the node the walk goes
        on from: a 250-page history is walked without hashing it again.
        The run ends at the first pinned node whose parent is not the one
        before it (a chunk another request registered first lies
        between)."""
        node, done = self._root, []
        for pinned in lease.shared[:chunks]:
            if pinned.parent is not node:
                break
            done.append(pinned)
            node = pinned
        return node, done

    def handoff(self, lease: PageLease, context: np.ndarray) -> int:
        """Export-path lease handoff (docs/RESILIENCE.md §migration):
        publish the lease's FINAL full-chunk pages for ``context`` (the
        request's prompt + fully-written generated tokens — the caller
        truncates to columns the device has actually finished) into the
        radix tree, then release the lease.  A re-import into THIS
        engine — a drain timeout's stragglers, a watchdog quarantine
        that resolves locally — then radix-matches the handed-off chain
        and skips those prefill windows, so migration re-prefill costs
        only the unpublished tail.  Returns pages published (0 with the
        prefix cache off, where this degrades to a plain release)."""
        published = 0
        if self.prefix_cache and not lease.released:
            before = len(lease.shared)
            self.register(lease, context)
            published = len(lease.shared) - before
        self.release(lease)
        return published

    def snapshot(self, lease: PageLease, context: np.ndarray
                 ) -> Optional[Tuple[int, int, int]]:
        """Book a snapshot of the recurrent state after exactly
        ``context`` (the tokens the lease's slot has consumed): it hangs
        off the radix node of ``context``'s last full page — registered
        by this or another request; if the chain does not reach that far
        there is nothing to hang it on — and owns a fresh pool page for
        the tokens past that boundary.  Returns ``(snapshot row, the
        lease's page holding the partial K/V, the snapshot's page)`` for
        the scheduler's device copy (pages 0, 0 at a page boundary), or
        None when no snapshot was booked: not a recurrent-state pool, the
        prefix cache off, no rows, no chain, or no page to be had.  A
        node keeps one snapshot, the newest; with every row taken the
        least recently used snapshot makes room."""
        if not (self.stateful and self.prefix_cache and self.state_rows):
            return None
        context = np.asarray(context, np.int32).reshape(-1)
        pg = self.page_size
        chunks, tail = divmod(context.size, pg)
        if context.size < 1:
            return None
        with self._lock:
            if lease.released:
                return None
            page = 0
            if tail:
                # before the walk: making room may evict the very node
                try:
                    page = self._allocate_locked(1)[0]
                except PagePoolExhausted:
                    return None
            node, done = self._pinned_prefix_locked(lease, chunks)
            for j in range(len(done), chunks):
                node = node.children.get(
                    context[j * pg:(j + 1) * pg].tobytes())
                if node is None:
                    if page:
                        self._free.append(page)
                    return None
            if node.snap is not None:
                self._drop_snapshot_locked(node.snap)
            if not self._snap_free:
                self._drop_snapshot_locked(min(
                    self._snaps.values(), key=lambda sn: sn.stamp))
                self.state_snapshots_evicted += 1
            sn = StateSnapshot(self._snap_free.pop(), node,
                               context[chunks * pg:].copy(), page,
                               self._next_stamp(), context.size)
            node.snap = self._snaps[sn.row] = sn
            self.state_snapshots += 1
            return sn.row, int(lease.row[chunks]) if tail else 0, page

    def _drop_snapshot_locked(self, sn: StateSnapshot) -> None:
        sn.node.snap = None
        del self._snaps[sn.row]
        self._snap_free.append(sn.row)
        if sn.page:
            self._free.append(sn.page)

    def chain_pages(self, context: np.ndarray) -> list:
        """Snapshot the radix chain covering ``context``'s full chunks:
        ``[(chunk_index, page, chain_hash)]`` down the tree, stopping at
        the first unmatched chunk (everything past a miss would need
        re-prefill anyway).  This is the page wire's sender-side lookup
        (fleet/pagewire.py): the caller reads the returned device pages
        while still holding the scheduler's pump mutex — eviction only
        runs inside ``begin``'s allocation, which the same mutex
        serializes, so the snapshot cannot be recycled underneath the
        read.  Empty with the prefix cache off."""
        if not self.prefix_cache:
            return []
        context = np.asarray(context, np.int32).reshape(-1)
        pg = self.page_size
        out = []
        with self._lock:
            node = self._root
            for j in range(context.size // pg):
                child = node.children.get(
                    context[j * pg:(j + 1) * pg].tobytes())
                if child is None:
                    break
                out.append((j, int(child.page), child.chain))
                node = child
        return out

    def release(self, lease: PageLease) -> None:
        """Return a lease's holdings: shared pins drop (the chain stays
        cached, evictable once refcount-0), private pages go straight
        back to the free list.  Idempotent — cancel racing retirement
        must not double-free."""
        with self._lock:
            if lease.released:
                return
            lease.released = True
            stamp = self._next_stamp()
            for n in lease.shared:
                n.refcount -= 1
                n.stamp = stamp
            self._free.extend(lease.private)
            self._lease_count -= 1
            self._lease_pages -= lease.n_pages

    # ----------------------------------------------------- alloc / evict

    def _next_stamp(self) -> int:
        self._stamp += 1
        return self._stamp

    def _allocate_locked(self, n: int) -> List[int]:
        if len(self._free) < n:
            self._evict_locked(n - len(self._free))
        if len(self._free) < n:
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free and no "
                "unpinned prefix chains left to evict")
        pages, self._free = self._free[:n], self._free[n:]
        return pages

    def _evict_locked(self, pages: int) -> None:
        """Evict least-recently-used refcount-0 LEAF nodes until ``pages``
        more pages are free or none is left (chains evict tail-first, so
        an interior page is never freed while a descendant still chains
        through it; pinned nodes are untouchable).  ONE walk of the tree
        finds the leaves; a heap hands them out oldest first, and a node
        whose last child went joins it — the same order as choosing the
        oldest leaf afresh for every page, without a walk of 8,000 nodes
        for each of the 80 pages a new session needs (130 ms of a tick on
        the chip's host; PERF.md, PR 30)."""
        heap: list = []
        seen = 0                          # ties: the walk's order
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif node.refcount == 0:
                heap.append((node.stamp, seen, node))
                seen += 1
        heapq.heapify(heap)
        target = len(self._free) + pages
        while heap and len(self._free) < target:
            _, _, node = heapq.heappop(heap)
            parent = node.parent
            del parent.children[node.key]
            if node.snap is not None:     # a snapshot goes with its chain
                self._drop_snapshot_locked(node.snap)
                self.state_snapshots_evicted += 1
            self._free.append(node.page)
            self._fingerprint.pop(node.chain, None)
            self.evictions += 1
            if parent is not self._root and not parent.children \
                    and parent.refcount == 0:
                heapq.heappush(heap, (parent.stamp, seen, parent))
                seen += 1

    # ------------------------------------------------------- fingerprint

    def _fp_touch_locked(self, key: bytes, tokens: int,
                         stamp: int) -> None:
        """Upsert one chain into the bounded fingerprint; on overflow
        drop the entry with the lowest cached-length × recency score
        (ties: older stamp, then key bytes — fully deterministic)."""
        if not self.fingerprint_k:
            return
        fp = self._fingerprint
        fp[key] = (tokens, stamp)
        if len(fp) > self.fingerprint_k:
            drop = min(fp.items(),
                       key=lambda kv: (kv[1][0] * kv[1][1], kv[1][1],
                                       kv[0]))[0]
            del fp[drop]

    def fingerprint(self) -> Dict[bytes, int]:
        """Copy of the hot-chain digest: chain hash -> cached tokens.
        Lock-cheap (<= fingerprint_k small entries); this is the map
        ``fleet.router.expected_pages_reused`` scores against."""
        with self._lock:
            return {k: v[0] for k, v in self._fingerprint.items()}

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, float]:
        """Counter/gauge snapshot for ``EngineStats`` (the ONE
        bookkeeping source the serve gauges render from)."""
        with self._lock:
            per_req = (self._lease_pages / self._lease_count
                       if self._lease_count else 0.0)
            return {
                "pages_total": self.num_pages - 1,
                "pages_free": len(self._free),
                "pages_per_request": per_req,
                "prefix_lookups_total": self.prefix_lookups,
                "prefix_hits_total": self.prefix_hits,
                "prefix_tokens_reused_total": self.prefix_tokens_reused,
                "prefix_evictions_total": self.evictions,
                "cow_splits_total": self.cow_splits,
                "page_size": self.page_size,
                "prefix_fingerprint": {
                    k: v[0] for k, v in self._fingerprint.items()},
                "state_snapshots_total": self.state_snapshots,
                "state_restores_total": self.state_restores,
                "state_snapshots_evicted_total":
                    self.state_snapshots_evicted,
                "state_snapshot_bytes":
                    len(self._snaps) * self.state_row_bytes,
            }
