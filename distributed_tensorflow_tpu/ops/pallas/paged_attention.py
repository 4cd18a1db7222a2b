"""Fused Pallas paged attention: walk the page table inside the kernel.

The paged serve tier (serve/pages.py) stores K/V as fixed-size pages in
one pool per leaf — ``[L, num_pages, page_size, kv_heads * head_dim]``,
a token's heads one flat row, so a page is ``page_size`` sublanes by
whole lane tiles and the pool tiles almost unpadded whatever the head
count (``[.., 25, 64]`` minor dimensions padded 2.56 x and cost every hot
program a relayout of the whole pool) — with a per-slot page-table row
mapping logical columns to pool pages.
The XLA read path (``models/gpt.py _paged_layer_kv``) gathers each row's
pages into a contiguous operand before attention runs; the measured
``vs_lockstep_paged`` ≈ 0.75 smoke cost is exactly that gather (the
ROADMAP item PR 13 closes).  This kernel consumes the page table directly:
what it reads of the table rides the grid as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), and the k/v BlockSpec index maps
read them to pick the pool pages of every grid step — no contiguous view
is ever materialized, on-device or in the jaxpr (statically checkable:
this module's DT4xx graph entry carries an HBM budget sized to the pool
+ operands, with no room for a gathered copy).

**The walk.**  A row's valid columns are a run ``[col_lo, col_hi)``: two
integers (a slot's ``start_col`` up to and with its just-written column;
a prefill window's ``[0, pos + s)``), never a mask.  ``page_walk`` turns
the table and the runs into what the kernel reads — built once per
program, outside the layer scan, the same for every layer: the list of
grid steps that have pages to read, row after row.  A grid step fetches
``n`` table entries' pages (``_pages_per_step``: 128 logical columns'
worth, the MXU's width, fewer for a row too wide for VMEM) as ``n``
blocks a pool leaf and contracts them as ONE ``[rows, lanes] x [lanes,
n * page_size]`` logits matmul and ONE context matmul per lane block,
flash-style online softmax across the row's steps.  The grid is
one-dimensional and DYNAMIC — the walk's number of steps, a traced
scalar — so a table entry outside a run costs no step, no copy and no
compute, a row with an empty run (retired, not live) costs nothing and
reads as zeros, and the one compiled kernel serves every length.  Only
pages the runs hold are ever fetched: where a step's last buffers have no
page of its row left, the walk names the page each is fetched for next,
so the pipeline — which copies a block only when its index changes, one
step ahead — brings the next row's first pages in while this row
computes.  The scalar-prefetch operands are the layer and the walk (each
step's row, its place in the row, its pages, and the two run bounds a
row); the in-page mask is an iota against the bounds.

Two variants share ONE kernel body (``_make_paged_kernel``):

* **decode** (``paged_decode_attention``): s=1 per slot row; the run is
  the slot's start_col/write_col window plus the row's own just-written
  column.
* **prefill window** (``paged_window_attention``): query block x
  page-walk for one row's chunked-prefill window, causal against the
  TRACED window origin (the run ends with the window's last column, so
  the origin is the run's end less the window's rows and the mask is
  computed in-kernel, never materialized at ``view_len``).

Both mirror ``_paged_layer_kv`` + ``ops.attention.dot_product_attention``
semantics: f32 logits, softmax weights cast to the compute dtype for the
second matmul, additive finite ``NEG_INF`` masks (matching
``ops.attention.NEG_INF``), GQA by query rows that share their kv head's
lanes (the kv heads are never broadcast in memory), int8 KV dequantized
at the operand from the pool's ``[..., kv_heads]`` scale planes.  The
kernel reads a page as it lies — ``[page_size, kv_heads * head_dim]`` —
and never splits heads out of the lane dimension: the wrapper hands it
BLOCK-DIAGONAL queries — over the whole row for a decode step's few
query rows, per 128-lane block of it for a window's many
(``_lane_block``) — so logits and context are plain matmuls against
aligned lane slices of a step that is bound by the bytes it reads and by
its fixed costs, not by FLOPs.  ``kv_heads`` is what the leaf's width
and the query's head size say it is.  Masked columns underflow to
exactly 0.0 in the exp — a block holds nothing but pool pages, so what
they multiply is finite — and the online softmax agrees with the
reference full softmax to float round-off; greedy token streams are
bit-identical (tests/test_pages.py pins kernel == gather ==
generate).

Off-TPU the kernel runs in Pallas interpret mode (ops/pallas/common.py),
so the tier-1 suite executes THIS kernel code on CPU; Mosaic compilation
(interpret=False) is certified on hardware by
scripts/validate_paged_tpu.py.  Mosaic's sublane tiling constrains
``page_size`` to multiples of :data:`MIN_PAGE_SIZE` — enforced at
``SlotScheduler`` construction (serve/scheduler.py) so an incompatible
layout is a clear ValueError or a logged gather fallback, never a Mosaic
error from inside the kernel.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import use_interpret

__all__ = ["MIN_PAGE_SIZE", "PageWalk", "page_size_kernel_ok", "page_walk",
           "paged_decode_attention", "paged_window_attention"]

# Mirrors ops.attention.NEG_INF (kept literal: ops.attention imports this
# package for the dispatch gate, so the constant cannot flow the other
# way without a cycle).  Finite on purpose — the reference softmax adds
# -1e9, never -inf, and exp(-1e9 - m) underflows to exactly 0.0 in f32,
# which is what makes kernel-vs-gather agreement testable.
NEG_INF = -1e9

# Mosaic sublane tile: a page is the sublane dimension of its k/v block
# ([page_size, kv_heads * head_dim]), which tiles in units of 8, so the
# kernel requires page_size % 8 == 0 (and >= 8).
# serve/scheduler.py validates this at construction; serve/pages.py
# ``auto_page_size(multiple_of=...)`` prefers compatible sizes.
MIN_PAGE_SIZE = 8


def page_size_kernel_ok(page_size: int) -> bool:
    """True iff the paged-attention kernel can consume pages of this
    size (lane-tileable: a multiple of :data:`MIN_PAGE_SIZE`)."""
    return page_size >= MIN_PAGE_SIZE and page_size % MIN_PAGE_SIZE == 0


# Query rows (kv_heads * group * window rows) up to which the kernel
# contracts a pool row whole.  On the v5e at GPT-2-XL's 1600-lane row, one
# row's walk of ~40 pages, eight a grid step (my chip run, PR 34, whole
# against 128-lane blocks): 25 rows 0.0164 / 0.0225 ms, 50 rows 0.0172 /
# 0.0224, 100 rows 0.0206 / 0.0231, 150 rows 0.0229 / 0.0244; 200 rows
# 0.0269 / 0.0246, 400 rows 0.0390 / 0.0282, 800 rows (a 32-token window)
# 0.0657 / 0.0296; the decode step, 25 rows a slot over 8 slots' walks,
# 0.0556 / 0.0872.  Whole, the body is also one block's, not thirteen's,
# to trace and lower.
WHOLE_ROW_MAX_QUERY_ROWS = 128


def _lane_block(width: int, head_dim: int, query_rows: int) -> int:
    """Lanes of a pool row the kernel contracts at once, always whole
    heads.  Few query rows (a decode step): the row's full width — one
    matmul a grid step, ``kv_heads`` times the useful FLOPs of a step
    whose cost is its per-block bookkeeping.  Many (a prefill window): a
    whole number of 128-lane tiles wherever the head size allows (two
    64-lane heads, one 128-lane head), so every K/V slice starts on a
    tile boundary and the excess FLOPs stay at ``128 / head_dim`` times."""
    if query_rows <= WHOLE_ROW_MAX_QUERY_ROWS:
        return width
    if head_dim % 128 == 0:
        return head_dim
    if 128 % head_dim == 0:
        return min(128, width)
    return width


# Logical columns of one grid step: the pages fetched together are
# contracted as one logits matmul with this many columns, the MXU's 128.
# On the v5e at GPT-2-XL's 16-token pages (my chip run, PR 34; 64 / 128 /
# 256 columns): the decode step over 6 live slots of ~630 tokens 0.0569 /
# 0.0556 / 0.0541 ms, over 8 full slots 0.1037 / 0.0987 / 0.0884, a
# 32-token window at column 992 0.0536 / 0.0352 / 0.0290.  256 would read
# 3-18 % faster still, for twice the blocks to trace, lower and buffer.
STEP_COLUMNS = 128

# What a step's K and V pages, double-buffered, may take of VMEM; a row
# so wide that ``STEP_COLUMNS`` of it pass this gets fewer pages a step.
PAGE_BUFFER_BYTES = 8 << 20


def _pages_per_step(page_size: int, width: int, itemsize: int) -> int:
    """Table entries whose pages one grid step fetches and contracts
    together: ``STEP_COLUMNS`` logical columns' worth, fewer where four
    buffers of that many ``width``-lane rows would pass
    ``PAGE_BUFFER_BYTES``, never less than one page."""
    fit = PAGE_BUFFER_BYTES // (4 * page_size * width * itemsize)
    return max(1, min(STEP_COLUMNS // page_size, fit))


def _held_pages(col_lo, col_hi, page_size: int):
    """(first table entry, entries) of the pages that hold the logical
    columns ``[col_lo, col_hi)``: none for an empty run.  The one rule of
    the wrapper's page walk and the kernel's step count."""
    first = col_lo // page_size
    end = (col_hi + page_size - 1) // page_size
    return first, jnp.where(col_hi > col_lo, end - first, 0)


class PageWalk(NamedTuple):
    """What the kernel reads of a page table (``page_walk``): its grid
    steps in order, ``n`` pages a step."""
    steps: jax.Array      # [1] int32: grid steps, the kernel's grid
    rows: jax.Array       # [T] int32: the row a step works for
    groups: jax.Array     # [T] int32: which of its row's steps it is
    pages: jax.Array      # [T * n] int32: the pool pages a step fetches
    col_lo: jax.Array     # [B] int32: row b attends the logical columns
    col_hi: jax.Array     # [B] int32: [col_lo[b], col_hi[b])


def page_walk(kv, page_tab, col_lo, col_hi) -> PageWalk:
    """The page walk of ``page_tab`` [B, P] whose row b holds the logical
    columns ``[col_lo[b], col_hi[b])`` ([B] int32; none where ``col_hi <=
    col_lo``), for the kernels below on the pool ``kv``.  The same for
    every layer: build it once, outside the layer scan.

    The walk is the list of grid steps that have pages to read, row after
    row: row b takes ``ceil(held / n)`` of them, ``held`` the table
    entries its run lies on and ``n`` the pages of a step, and a row
    with an empty run none.  Their number is the kernel's (dynamic) grid;
    the arrays are sized for full tables, ``T = B * ceil(P / n)``.
    ``pages[t * n + j]`` is the pool page step t fetches into its buffer
    j: the table entry ``first + groups[t] * n + j`` of ``rows[t]``
    where the run holds it.  A buffer a step does not use names the page
    it is NEXT used for — the pipeline copies a block only when its index
    changes, so that page comes in early and nothing else moves — or,
    after its last use, the page it was last used for.  No page outside
    a run is ever fetched, and a buffer never holds anything but pool
    pages."""
    _, _, page_size, width = kv["k"].shape
    return _page_walk(
        page_tab, col_lo, col_hi, page_size=page_size,
        n=_pages_per_step(page_size, width, kv["k"].dtype.itemsize))


# jitted on its own, as ``_paged_attention`` is: a process traces a walk and
# a kernel call once for all its programs that hold them at the same shapes
# (a scheduler's two window programs; a probe's beside them), which is set-up
# time that no compile cache serves
@functools.partial(jax.jit, static_argnames=("page_size", "n"))
def _page_walk(page_tab, col_lo, col_hi, *, page_size, n) -> PageWalk:
    B, P = page_tab.shape
    T = B * -(-P // n)
    col_lo = col_lo.astype(jnp.int32)
    # a full slot's frozen write head stands one past its table row
    col_hi = jnp.minimum(col_hi.astype(jnp.int32), P * page_size)
    first, held = _held_pages(col_lo, col_hi, page_size)
    steps = (held + n - 1) // n                     # of each row
    ends = jnp.cumsum(steps)                        # a row's last step + 1
    t = jnp.arange(T, dtype=jnp.int32)
    rows = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1), B - 1)
    groups = t - (ends - steps)[rows]
    entry = groups[:, None] * n + jnp.arange(n, dtype=jnp.int32)[None, :]
    used = (entry < held[rows][:, None]) & (t < ends[-1])[:, None]
    pages = page_tab.astype(jnp.int32)[
        rows[:, None], jnp.clip(first[rows][:, None] + entry, 0, P - 1)]
    following = jax.lax.cummin(jnp.where(used, t[:, None], T), axis=0,
                               reverse=True)
    before = jax.lax.cummax(jnp.where(used, t[:, None], 0), axis=0)
    source = jnp.where(following < T, following, before)
    pages = jnp.take_along_axis(pages, source, axis=0)
    return PageWalk(ends[-1:], rows, groups, pages.reshape(T * n), col_lo,
                    col_hi)


def _make_paged_kernel(*, scale, head_dim, blocks, window_rows, quantized,
                       pages_per_step):
    """One body for both variants.  Ref order (after the 6 scalar-
    prefetch refs: layer, then the walk's rows, groups, pages and the two
    run bounds) matches the in_specs built in ``_paged_attention``: q,
    then one ref per page of the step for k, for v[, for k_scale, for
    v_scale], [the window row of every query row,] out, then acc/m/l
    scratch.

    ``blocks``: static ``(first lane, lanes, first kv head, kv heads)``
    per lane block of a pool row.  Block ``j``'s queries arrive
    BLOCK-DIAGONAL — ``q_ref[0, j]`` is ``[rows, lanes]`` with a row's
    head vector in the lanes of its K/V head and zeros elsewhere — so
    its logits are one ``[rows, lanes] x [lanes, columns]`` matmul
    against the step's pages as they lie in the pool, and its context
    one ``[rows, columns] x [columns, lanes]``, ``columns`` =
    ``pages_per_step * page_size``: heads are never split out of the
    lane dimension, and a block costs the same two matmuls and one
    softmax update whatever ``pages_per_step`` is.  A row's context is
    the lanes of its own head; the wrapper picks them.

    Every grid step has pages to read (``page_walk``): a row's first
    step resets the online softmax, its last writes the row out.
    ``window_rows`` (static, 0 for the decode step): the queries are that
    many window rows, row ``j`` attending columns ``<= hi - window_rows +
    j``."""
    n = pages_per_step

    def kernel(layer_ref, step_row_ref, group_ref, pages_ref, lo_ref,
               hi_ref, q_ref, *rest):
        del layer_ref, pages_ref  # consumed by the BlockSpec index maps
        rest = list(rest)
        k_refs = [rest.pop(0) for _ in range(n)]
        v_refs = [rest.pop(0) for _ in range(n)]
        ks_refs = [rest.pop(0) for _ in range(n)] if quantized else None
        vs_refs = [rest.pop(0) for _ in range(n)] if quantized else None
        rows_ref = rest.pop(0) if window_rows else None
        o_ref, acc_ref, m_ref, l_ref = rest
        # program_id must be read at kernel top level (the HLO
        # interpreter cannot lower it inside pl.when).
        t = pl.program_id(0)
        b, g = step_row_ref[t], group_ref[t]
        rows = q_ref.shape[2]
        page_size = k_refs[0].shape[2]
        columns = n * page_size
        dtype = q_ref.dtype
        lo, hi = lo_ref[b], hi_ref[b]
        first, held = _held_pages(lo, hi, page_size)

        @pl.when(g == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        def step_pages(refs):
            """The step's pages of one leaf, each read once, as one
            ``[columns, width]`` operand.  Pages whose rows fill whole
            sublane tiles of their dtype are stacked as they lie; others
            go through float32, whose tile every admissible page size
            fills."""
            pages = [r[0, 0] for r in refs]
            if quantized or page_size % (32 // pages[0].dtype.itemsize):
                pages = [x.astype(jnp.float32) for x in pages]
            return pages[0] if n == 1 else jnp.concatenate(pages, axis=0)

        def dequant(x, scales, lanes, h0, nh):
            """int8 page lanes -> float32 through the per-(token, head)
            scale plane, as quant.dequantize_tensor does in
            _paged_layer_kv: each head's scale column spread over its
            head_dim lanes."""
            head = jax.lax.broadcasted_iota(
                jnp.int32, (columns, lanes), 1) // head_dim
            spread = jnp.zeros((columns, lanes), jnp.float32)
            for hh in range(nh):
                spread = jnp.where(head == hh,
                                   scales[:, h0 + hh:h0 + hh + 1], spread)
            return x * spread

        col = (first + g * n) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, columns), 1)
        seen = (col >= lo) & (col < hi)
        if window_rows:
            # prefix + causal-in-window, decode_window's positional
            # mask: row j attends columns <= pos + j
            seen &= col <= hi - window_rows + rows_ref[...]
        # the step's buffers past the row's last page hold another pool
        # page, finite: masked here, and exp(NEG_INF - m) is exactly 0.0
        mask = jnp.where(seen, 0.0, NEG_INF)
        k_pages, v_pages = step_pages(k_refs), step_pages(v_refs)
        if quantized:
            k_scales, v_scales = step_pages(ks_refs), step_pages(vs_refs)

        for j, (lane0, lanes, h0, nh) in enumerate(blocks):
            q = q_ref[0, j, :, :lanes]                # [rows, lanes]
            k = k_pages[:, lane0:lane0 + lanes]       # [columns, lanes]
            v = v_pages[:, lane0:lane0 + lanes]
            if quantized:
                k = dequant(k, k_scales, lanes, h0, nh)
                v = dequant(v, v_scales, lanes, h0, nh)
            logits = jax.lax.dot_general(
                q, k.astype(dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + mask

            # Online softmax (flash scaffold): masks are FINITE, so
            # only the -inf init needs the isfinite guard.
            m_prev = m_ref[j]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=-1, keepdims=True))
            shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - shift)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - shift), 0.0)
            l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            # weights in the compute dtype for the MXU, as
            # ops.attention.dot_product_attention casts them
            pv = jax.lax.dot_general(
                p.astype(dtype), v.astype(dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[j, :, :lanes] = acc_ref[j, :, :lanes] * alpha + pv
            m_ref[j] = m_new

        @pl.when((g + 1) * n >= held)
        def _finalize():
            l = l_ref[...]
            o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("window_causal", "scale", "interpret"))
def _paged_attention(q, kv, layer, walk: PageWalk, *, window_causal, scale,
                     interpret):
    """Shared pallas_call builder.

    q [B, sq, h, hd]; kv pool dict (k/v [L, num_pages, page_size, kvh *
    hd], optional k_scale/v_scale [..., kvh]); layer traced int32
    scalar; walk: ``page_walk`` of the B rows on this pool (row b attends
    its run's columns; its window rows causally, the last of them the
    run's last column, if ``window_causal``).  ``kvh`` is what the
    leaf's width and q's head size say it is.
    Returns [B, sq, h, hd] in q.dtype.
    """
    B, sq, h, hd = q.shape
    _, _, page_size, width = kv["k"].shape
    kvh = width // hd
    group = h // kvh
    quantized = "k_scale" in kv

    # lane blocks of a pool row, and the block-diagonal queries: block j
    # holds ``hpb`` kv heads (the last maybe fewer: zero rows and lanes),
    # row ((hh * group + g) * sq + s) the query of head (h0 + hh) * group
    # + g at window row s, placed in lanes [hh * hd, (hh + 1) * hd)
    lb = _lane_block(width, hd, kvh * group * sq)
    hpb = lb // hd
    nb = -(-kvh // hpb)
    blocks = tuple((j * lb, min(lb, width - j * lb), j * hpb,
                    min(hpb, kvh - j * hpb)) for j in range(nb))
    rows = hpb * group * sq
    qh = q.reshape(B, sq, kvh, group, hd)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, nb * hpb - kvh), (0, 0), (0, 0)))
    qh = qh.reshape(B, sq, nb, hpb, group, hd).transpose(0, 2, 3, 4, 1, 5)
    own = jnp.eye(hpb, dtype=q.dtype)[:, None, None, :, None]
    q_diag = (qh[:, :, :, :, :, None, :] * own).reshape(B, nb, rows, lb)

    n = _pages_per_step(page_size, width, kv["k"].dtype.itemsize)

    # Index maps receive the grid index then the scalar-prefetch refs
    # (layer, the walk); the k/v maps are the page walk itself.
    def q_map(t, lr, row, group, pages, lo, hi):
        return (row[t], 0, 0, 0)

    def page_map(j):
        return lambda t, lr, row, group, pages, lo, hi: (
            lr[0], pages[t * n + j], 0, 0)

    def pages_of(leaf):
        return [pl.BlockSpec((1, 1, page_size, leaf.shape[-1]), page_map(j))
                for j in range(n)]

    leaves = [kv["k"], kv["v"]]
    if quantized:
        leaves += [kv["k_scale"], kv["v_scale"]]
    in_specs = [pl.BlockSpec((1, nb, rows, lb), q_map)]
    inputs = [q_diag]
    for leaf in leaves:
        in_specs += pages_of(leaf)
        inputs += [leaf] * n
    if window_causal:
        # the window row of every query row (row order above)
        in_specs.append(pl.BlockSpec((rows, 1), lambda *_: (0, 0)))
        inputs.append(jnp.tile(jnp.arange(sq, dtype=jnp.int32),
                               hpb * group)[:, None])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(walk.steps[0],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nb, rows, lb), q_map),
        scratch_shapes=[
            pltpu.VMEM((nb, rows, lb), jnp.float32),
            pltpu.VMEM((nb, rows, 1), jnp.float32),
            pltpu.VMEM((nb, rows, 1), jnp.float32),
        ],
    )
    kernel = _make_paged_kernel(scale=scale, head_dim=hd, blocks=blocks,
                                window_rows=sq if window_causal else 0,
                                quantized=quantized, pages_per_step=n)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, nb, rows, lb), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # the kernel's name in HLO and in a device trace
        name=("dttpu_paged_window" if window_causal
              else "dttpu_paged_decode"),
    )
    out = call(jnp.asarray(layer, jnp.int32).reshape(1), *walk[1:], *inputs)
    # a row with no column has no grid step, and nothing was written for it
    out = jnp.where((walk.col_hi > walk.col_lo)[:, None, None, None], out, 0)
    # a row's context is the lanes of its own head
    out = out.reshape(B, nb, hpb, group, sq, hpb, hd)
    out = jnp.stack([out[:, :, hh, :, :, hh] for hh in range(hpb)], axis=2)
    out = out.reshape(B, nb * hpb, group, sq, hd)[:, :kvh]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, sq, h, hd)


def _defaults(q, scale, interpret):
    """The softmax scale and the interpret switch as the caller left them
    or as the head size and the backend give them: resolved out here, so
    that they key ``_paged_attention``'s trace."""
    return (1.0 / math.sqrt(q.shape[-1]) if scale is None else scale,
            use_interpret() if interpret is None else interpret)


def paged_decode_attention(q, kv, layer, walk: PageWalk, *, scale=None,
                           interpret=None):
    """s=1 decode attention straight off the page pool.

    q [S, 1, h, hd]; kv pool subtree (serve/pages.py leaves); layer
    traced layer index; walk: ``page_walk(kv, page_tab, col_lo,
    col_hi)`` of the S slots — row r attends its logical columns
    ``[col_lo[r], col_hi[r])`` (the slot's start_col up to and with its
    just-written column, ``write_col + 1``) and fetches no page outside
    them; a row with ``col_hi <= col_lo`` (retired, not live) reads
    nothing and comes back zeros.
    Returns the attention context [S, 1, h, hd].
    """
    scale, interpret = _defaults(q, scale, interpret)
    return _paged_attention(q, kv, layer, walk, window_causal=False,
                            scale=scale, interpret=interpret)


def paged_window_attention(q, kv, layer, walk: PageWalk, *, scale=None,
                           interpret=None):
    """Prefill-window attention for ONE row through its page walk.

    q [1, s, h, hd] (the window's queries); walk: ``page_walk(kv,
    page_row[None], [0], [pos + s])``, ``pos`` the traced logical column
    of the window's first token: the walk ends at the page of the
    window's last column.  Row j attends columns <= pos + j (prefix +
    causal within the window) — the positional mask ``decode_window``
    applies, computed in-kernel from the run's end so no [s, view_len]
    mask is ever built.  Returns [1, s, h, hd].
    """
    scale, interpret = _defaults(q, scale, interpret)
    return _paged_attention(q, kv, layer, walk, window_causal=True,
                            scale=scale, interpret=interpret)


# --- dtlint graph tier registration (docs/ANALYSIS.md) ----------------
# Budget: the tiny-entry pool (2 layers x 9 pages x 8 x (2 x 16) f32 x 2
# leaves ~= 36 KiB) + operands, with NO headroom for a gathered
# [S, view_len, kvh, hd] copy at real scale — DT404 is the static proof
# that the gather never came back.
from ...analysis import graph as _graph_lib  # noqa: E402


@_graph_lib.trace_entry("paged_attention", hbm_budget=1 << 20)
def _graph_entries():
    """Both kernel variants at tiny pool shapes, traced abstractly on
    CPU (interpret-mode pallas_call has an abstract eval, so the graph
    tier sees the real call signature without touching a device)."""
    S, P, PG, KVH, GROUP, HD, L, NP = 2, 4, 8, 2, 2, 16, 2, 9
    h = KVH * GROUP
    sds = jax.ShapeDtypeStruct
    kv = {"k": sds((L, NP, PG, KVH * HD), jnp.float32),
          "v": sds((L, NP, PG, KVH * HD), jnp.float32)}
    i32 = lambda *shape: sds(shape, jnp.int32)
    return [
        _graph_lib.Target(
            "decode",
            lambda q, kv, layer, tab, lo, hi: paged_decode_attention(
                q, kv, layer, page_walk(kv, tab, lo, hi)),
            args=(sds((S, 1, h, HD), jnp.float32), kv, i32(), i32(S, P),
                  i32(S), i32(S))),
        _graph_lib.Target(
            "prefill_window",
            lambda q, kv, layer, row, end: paged_window_attention(
                q, kv, layer,
                page_walk(kv, row[None], jnp.zeros_like(end), end)),
            args=(sds((1, PG, h, HD), jnp.float32), kv, i32(), i32(P),
                  i32(1))),
    ]
