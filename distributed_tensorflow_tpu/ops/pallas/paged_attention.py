"""Fused Pallas paged attention: walk the page table inside the kernel.

The paged serve tier (serve/pages.py) stores K/V as fixed-size pages in
one pool per leaf — ``[L, num_pages, page_size, kv_heads, head_dim]`` —
with a per-slot page-table row mapping logical columns to pool pages.
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
semantics: f32 logits, additive finite ``NEG_INF`` masks (matching
``ops.attention.NEG_INF``), GQA by head-group reshape (the kv heads are
never broadcast in memory), int8 KV dequantized at the operand from the
pool's scale planes.  Masked columns underflow to exactly 0.0 in the
exp, so the online softmax agrees with the reference full softmax to
float round-off and greedy token streams are bit-identical
(tests/test_pages.py pins kernel == gather == contiguous == generate).

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

# Mosaic sublane tile: a k/v page block's second-minor dims tile in
# units of 8, so the kernel requires page_size % 8 == 0 (and >= 8).
# serve/scheduler.py validates this at construction; serve/pages.py
# ``auto_page_size(multiple_of=...)`` prefers compatible sizes.
MIN_PAGE_SIZE = 8


def page_size_kernel_ok(page_size: int) -> bool:
    """True iff the paged-attention kernel can consume pages of this
    size (lane-tileable: a multiple of :data:`MIN_PAGE_SIZE`)."""
    return page_size >= MIN_PAGE_SIZE and page_size % MIN_PAGE_SIZE == 0


def _make_paged_kernel(*, scale, group, page_size, window_causal,
                       quantized):
    """One body for both variants.  Ref order (after the 3 scalar-
    prefetch refs) matches the in_specs built in ``_paged_attention``:
    q, k, v, [k_scale, v_scale,] valid, out, then acc/m/l scratch."""

    def kernel(layer_ref, tab_ref, pos_ref, q_ref, k_ref, v_ref, *rest):
        del layer_ref, tab_ref  # consumed by the BlockSpec index maps
        if quantized:
            ks_ref, vs_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref = rest
        else:
            valid_ref, o_ref, acc_ref, m_ref, l_ref = rest
        # program_id must be read at kernel top level (the HLO
        # interpreter cannot lower it inside pl.when).
        pi = pl.program_id(1)
        npages = pl.num_programs(1)

        @pl.when(pi == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        sq, h, hd = q_ref.shape[1:]
        kvh = k_ref.shape[3]
        # GQA: q head ih reads kv head ih // group — a reshape, never a
        # materialized broadcast of the kv heads.
        q = q_ref[0].astype(jnp.float32).reshape(sq, kvh, group, hd)
        k = k_ref[0, 0].astype(jnp.float32)   # [page_size, kvh, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            # dequant-at-the-operand from the pool's scale planes,
            # mirroring quant.dequantize_tensor in _paged_layer_kv.
            k = k * ks_ref[0, 0]              # [page_size, kvh, 1] f32
            v = v * vs_ref[0, 0]

        # [kvh, sq, group, page_size] — batch over kv heads.
        logits = jax.lax.dot_general(
            q, k, (((3,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale
        pvalid = valid_ref[0, 0, 0]           # [page_size] f32 plane
        logits = logits + jnp.where(pvalid > 0.5, 0.0, NEG_INF)
        if window_causal:
            # logical column of lane t in this page vs window row j:
            # attend iff col <= pos + j (prefix + causal-in-window),
            # matching decode_window's positional mask.
            col = pi * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, sq, 1, page_size), 3)
            row = jax.lax.broadcasted_iota(
                jnp.int32, (1, sq, 1, page_size), 1)
            logits = logits + jnp.where(col <= pos_ref[0] + row,
                                        0.0, NEG_INF)

        # Online softmax (flash scaffold): masks are FINITE, so only the
        # -inf init needs the isfinite guard.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - shift)
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - shift), 0.0)
        l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

        @pl.when(pi == npages - 1)
        def _finalize():
            l = l_ref[...]
            out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
            out = out.transpose(1, 0, 2, 3).reshape(sq, kvh * group, hd)
            o_ref[0] = out.astype(o_ref.dtype)

    return kernel


def _paged_attention(q, kv, layer, page_tab, valid_plane, pos, *,
                     window_causal, scale=None, interpret=None):
    """Shared pallas_call builder.

    q [B, sq, h, hd]; kv pool dict (k/v [L, num_pages, page_size, kvh,
    hd], optional k_scale/v_scale [..., 1]); layer traced int32 scalar;
    page_tab [B, P] int32; valid_plane [B, P, 1, page_size] f32; pos
    traced window origin (ignored unless window_causal).
    Returns [B, sq, h, hd] in q.dtype.
    """
    B, sq, h, hd = q.shape
    _, _, page_size, kvh, _ = kv["k"].shape
    P = page_tab.shape[1]
    group = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = use_interpret()
    quantized = "k_scale" in kv

    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos_arr = jnp.asarray(0 if pos is None else pos, jnp.int32).reshape(1)
    tab = page_tab.astype(jnp.int32)

    # Index maps receive the grid indices then the scalar-prefetch refs
    # (layer, table, pos); the k/v maps are the page walk itself.
    def q_map(b, p, lr, tb, ps):
        return (b, 0, 0, 0)

    def kv_map(b, p, lr, tb, ps):
        return (lr[0], tb[b, p], 0, 0, 0)

    def valid_map(b, p, lr, tb, ps):
        return (b, p, 0, 0)

    in_specs = [
        pl.BlockSpec((1, sq, h, hd), q_map),
        pl.BlockSpec((1, 1, page_size, kvh, hd), kv_map),
        pl.BlockSpec((1, 1, page_size, kvh, hd), kv_map),
    ]
    inputs = [q, kv["k"], kv["v"]]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page_size, kvh, 1), kv_map)] * 2
        inputs += [kv["k_scale"], kv["v_scale"]]
    in_specs.append(pl.BlockSpec((1, 1, 1, page_size), valid_map))
    inputs.append(valid_plane)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, sq, h, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, sq, group, hd), jnp.float32),
            pltpu.VMEM((kvh, sq, group, 1), jnp.float32),
            pltpu.VMEM((kvh, sq, group, 1), jnp.float32),
        ],
    )
    kernel = _make_paged_kernel(scale=scale, group=group,
                                page_size=page_size,
                                window_causal=window_causal,
                                quantized=quantized)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, sq, h, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # the kernel's name in HLO and in a device trace
        name=("dttpu_paged_window" if window_causal
              else "dttpu_paged_decode"),
    )
    return call(layer_arr, tab, pos_arr, *inputs)


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
# Budget: the tiny-entry pool (2 layers x 9 pages x 8 x 2 x 16 f32 x 2
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
    kv = {"k": sds((L, NP, PG, KVH, HD), jnp.float32),
          "v": sds((L, NP, PG, KVH, HD), jnp.float32)}
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
