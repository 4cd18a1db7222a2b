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
ROADMAP item PR 13 closes).  This kernel consumes the page table directly: the table
rides the grid as a SCALAR-PREFETCH operand
(``pltpu.PrefetchScalarGridSpec``), and the k/v BlockSpec index maps
read it to pick the pool page for every grid step — no contiguous view
is ever materialized, on-device or in the jaxpr (statically checkable:
this module's DT4xx graph entry carries an HBM budget sized to the pool
+ operands, with no room for a gathered copy).

Two variants share ONE kernel body (``_make_paged_kernel``):

* **decode** (``paged_decode_attention``): s=1 per slot row, grid
  ``(slots, pages_per_slot)`` with the page walk minormost, flash-style
  online softmax across the row's pages; validity (the
  start_col/write_col window plus the row's own just-written column)
  arrives as a per-page mask plane, so only valid pages contribute and
  retired rows' trash-page mapping is harmless — every trash column is
  masked and its exp underflows to exactly 0.0.
* **prefill window** (``paged_window_attention``): query block ×
  page-walk for one row's chunked-prefill window, causal against the
  TRACED window origin (``pos`` rides the scalar-prefetch tuple so the
  mask is computed in-kernel, never materialized at ``view_len``).

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
exactly 0.0 in the exp, so the online softmax agrees with the reference
full softmax to float round-off and greedy token streams are
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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import use_interpret

__all__ = ["MIN_PAGE_SIZE", "page_size_kernel_ok", "paged_decode_attention",
           "paged_window_attention"]

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
# contracts a pool row whole.  On the v5e at GPT-2-XL's 1600-lane row, 64
# page steps a call (my chip run, PR 31): 25 rows (the decode step, 8
# slots) 0.307 ms whole against 0.446 by 128-lane blocks, 50 rows 0.056
# against 0.073; 100 rows 0.075 against 0.063, 200 rows 0.110 against
# 0.071, 800 rows (a 32-token window) 0.338 against 0.170.
WHOLE_ROW_MAX_QUERY_ROWS = 64


def _lane_block(width: int, head_dim: int, query_rows: int) -> int:
    """Lanes of a pool row the kernel contracts at once, always whole
    heads.  Few query rows (a decode step): the row's full width — one
    matmul a page, ``kv_heads`` times the useful FLOPs of a step whose
    cost is its per-block bookkeeping.  Many (a prefill window): a whole
    number of 128-lane tiles wherever the head size allows (two 64-lane
    heads, one 128-lane head), so every K/V slice starts on a tile
    boundary and the excess FLOPs stay at ``128 / head_dim`` times."""
    if query_rows <= WHOLE_ROW_MAX_QUERY_ROWS:
        return width
    if head_dim % 128 == 0:
        return head_dim
    if 128 % head_dim == 0:
        return min(128, width)
    return width


def _make_paged_kernel(*, scale, head_dim, blocks, window_causal,
                       quantized):
    """One body for both variants.  Ref order (after the 3 scalar-
    prefetch refs) matches the in_specs built in ``_paged_attention``:
    q, k, v, [k_scale, v_scale,] valid, [row_pos,] out, then acc/m/l
    scratch.

    ``blocks``: static ``(first lane, lanes, first kv head, kv heads)``
    per lane block of a pool row.  Block ``j``'s queries arrive
    BLOCK-DIAGONAL — ``q_ref[0, j]`` is ``[rows, lanes]`` with a row's
    head vector in the lanes of its K/V head and zeros elsewhere — so
    its logits are one ``[rows, lanes] x [lanes, page_size]`` matmul
    against the page's lanes as they lie in the pool, and its context
    one ``[rows, page_size] x [page_size, lanes]``: heads are never
    split out of the lane dimension.  A row's context is the lanes of
    its own head; the wrapper picks them."""

    def kernel(layer_ref, tab_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        del layer_ref, tab_ref  # consumed by the BlockSpec index maps
        rest = list(rest)
        ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quantized \
            else (None, None)
        valid_ref = rest.pop(0)
        rows_ref = rest.pop(0) if window_causal else None
        o_ref, acc_ref, m_ref, l_ref = rest
        # program_id must be read at kernel top level (the HLO
        # interpreter cannot lower it inside pl.when).
        pi = pl.program_id(1)
        npages = pl.num_programs(1)

        @pl.when(pi == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        rows = q_ref.shape[2]
        page_size = k_ref.shape[2]
        dtype = q_ref.dtype
        pvalid = valid_ref[0, 0]              # [1, page_size] f32 plane
        mask = jnp.where(pvalid > 0.5, 0.0, NEG_INF)
        if window_causal:
            # logical column of lane t in this page vs the window row a
            # query row belongs to: attend iff col <= pos + j (prefix +
            # causal-in-window), matching decode_window's positional
            # mask.
            col = pi * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            mask = mask + jnp.where(col <= pos_ref[0] + rows_ref[...],
                                    0.0, NEG_INF)

        def dequant(x, s_ref, lanes, h0, nh):
            """int8 page lanes -> compute dtype through the per-(token,
            head) scale plane, as quant.dequantize_tensor does in
            _paged_layer_kv: each head's scale column spread over its
            head_dim lanes."""
            head = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, lanes), 1) // head_dim
            spread = jnp.zeros((page_size, lanes), jnp.float32)
            for hh in range(nh):
                spread = jnp.where(head == hh,
                                   s_ref[0, 0, :, h0 + hh:h0 + hh + 1],
                                   spread)
            return (x.astype(jnp.float32) * spread).astype(dtype)

        for j, (lo, lanes, h0, nh) in enumerate(blocks):
            q = q_ref[0, j, :, :lanes]                # [rows, lanes]
            k = k_ref[0, 0, :, lo:lo + lanes]         # [page_size, lanes]
            v = v_ref[0, 0, :, lo:lo + lanes]
            if quantized:
                k = dequant(k, ks_ref, lanes, h0, nh)
                v = dequant(v, vs_ref, lanes, h0, nh)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale + mask

            # Online softmax (flash scaffold): masks are FINITE, so only
            # the -inf init needs the isfinite guard.
            m_prev = m_ref[j]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(logits - shift)
            alpha = jnp.where(jnp.isfinite(m_prev),
                              jnp.exp(m_prev - shift), 0.0)
            l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            # weights in the compute dtype for the MXU, as
            # ops.attention.dot_product_attention casts them
            pv = jax.lax.dot_general(
                p.astype(dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[j, :, :lanes] = acc_ref[j, :, :lanes] * alpha + pv
            m_ref[j] = m_new

        @pl.when(pi == npages - 1)
        def _finalize():
            l = l_ref[...]
            o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(o_ref.dtype)

    return kernel


def _paged_attention(q, kv, layer, page_tab, valid_plane, pos, *,
                     window_causal, scale=None, interpret=None):
    """Shared pallas_call builder.

    q [B, sq, h, hd]; kv pool dict (k/v [L, num_pages, page_size, kvh *
    hd], optional k_scale/v_scale [..., kvh]); layer traced int32
    scalar; page_tab [B, P] int32; valid_plane [B, P, 1, page_size] f32;
    pos traced window origin (ignored unless window_causal).  ``kvh`` is
    what the leaf's width and q's head size say it is.
    Returns [B, sq, h, hd] in q.dtype.
    """
    B, sq, h, hd = q.shape
    _, _, page_size, width = kv["k"].shape
    kvh = width // hd
    P = page_tab.shape[1]
    group = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = use_interpret()
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

    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos_arr = jnp.asarray(0 if pos is None else pos, jnp.int32).reshape(1)
    tab = page_tab.astype(jnp.int32)

    # Index maps receive the grid indices then the scalar-prefetch refs
    # (layer, table, pos); the k/v maps are the page walk itself.
    def q_map(b, p, lr, tb, ps):
        return (b, 0, 0, 0)

    def kv_map(b, p, lr, tb, ps):
        return (lr[0], tb[b, p], 0, 0)

    def valid_map(b, p, lr, tb, ps):
        return (b, p, 0, 0)

    in_specs = [
        pl.BlockSpec((1, nb, rows, lb), q_map),
        pl.BlockSpec((1, 1, page_size, width), kv_map),
        pl.BlockSpec((1, 1, page_size, width), kv_map),
    ]
    inputs = [q_diag, kv["k"], kv["v"]]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page_size, kvh), kv_map)] * 2
        inputs += [kv["k_scale"], kv["v_scale"]]
    in_specs.append(pl.BlockSpec((1, 1, 1, page_size), valid_map))
    inputs.append(valid_plane)
    if window_causal:
        # the window row of every query row (row order above)
        in_specs.append(pl.BlockSpec((rows, 1),
                                     lambda b, p, lr, tb, ps: (0, 0)))
        inputs.append(jnp.tile(jnp.arange(sq, dtype=jnp.int32),
                               hpb * group)[:, None])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nb, rows, lb), q_map),
        scratch_shapes=[
            pltpu.VMEM((nb, rows, lb), jnp.float32),
            pltpu.VMEM((nb, rows, 1), jnp.float32),
            pltpu.VMEM((nb, rows, 1), jnp.float32),
        ],
    )
    kernel = _make_paged_kernel(scale=scale, head_dim=hd, blocks=blocks,
                                window_causal=window_causal,
                                quantized=quantized)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, nb, rows, lb), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # the kernel's name in HLO and in a device trace
        name=("dttpu_paged_window" if window_causal
              else "dttpu_paged_decode"),
    )
    out = call(layer_arr, tab, pos_arr, *inputs)
    # a row's context is the lanes of its own head
    out = out.reshape(B, nb, hpb, group, sq, hpb, hd)
    out = jnp.stack([out[:, :, hh, :, :, hh] for hh in range(hpb)], axis=2)
    out = out.reshape(B, nb * hpb, group, sq, hd)[:, :kvh]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, sq, h, hd)


def paged_decode_attention(q, kv, layer, page_tab, valid, *, scale=None,
                           interpret=None):
    """s=1 decode attention straight off the page pool.

    q [S, 1, h, hd]; kv pool subtree (serve/pages.py leaves); layer
    traced layer index; page_tab [S, pages_per_slot]; valid
    [S, view_len] bool (the kv-valid window OR the row's own column —
    exactly the mask ``decode_step_slots_paged`` hands the gather path).
    Returns the attention context [S, 1, h, hd].
    """
    S, sq, _, _ = q.shape
    page_size = kv["k"].shape[2]
    P = page_tab.shape[1]
    valid_plane = valid.reshape(S, P, 1, page_size).astype(jnp.float32)
    return _paged_attention(q, kv, layer, page_tab, valid_plane, None,
                            window_causal=False, scale=scale,
                            interpret=interpret)


def paged_window_attention(q, kv, layer, page_row, pos, *, scale=None,
                           interpret=None):
    """Prefill-window attention for ONE row through its page walk.

    q [1, s, h, hd] (the window's queries); page_row [pages_per_row];
    pos: traced logical column of the window's first token.  Row j
    attends columns <= pos + j (prefix + causal within the window) —
    the positional mask ``decode_window`` applies, computed in-kernel
    from ``pos`` so no [s, view_len] mask is ever built.
    Returns [1, s, h, hd].
    """
    page_size = kv["k"].shape[2]
    P = page_row.shape[0]
    ones = jnp.ones((1, P, 1, page_size), jnp.float32)
    return _paged_attention(q, kv, layer, page_row[None, :], ones, pos,
                            window_causal=True, scale=scale,
                            interpret=interpret)


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
    return [
        _graph_lib.Target(
            "decode",
            lambda q, kv, layer, tab, valid: paged_decode_attention(
                q, kv, layer, tab, valid),
            args=(sds((S, 1, h, HD), jnp.float32), kv,
                  sds((), jnp.int32), sds((S, P), jnp.int32),
                  sds((S, P * PG), jnp.bool_))),
        _graph_lib.Target(
            "prefill_window",
            lambda q, kv, layer, row, pos: paged_window_attention(
                q, kv, layer, row, pos),
            args=(sds((1, PG, h, HD), jnp.float32), kv,
                  sds((), jnp.int32), sds((P,), jnp.int32),
                  sds((), jnp.int32))),
    ]
