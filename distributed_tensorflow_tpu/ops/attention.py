"""Attention ops: scaled-dot-product and multi-head attention.

The reference has no attention at all (its model is an MLP, reference
example.py:149-155); this module exists for the driver's BERT-base baseline
config and the long-context design requirement (SURVEY.md §5 long-context
row).  TPU-first choices:

  * head layout ``[batch, seq, heads, head_dim]`` with projections stored
    ``[d_model, heads, head_dim]`` — the heads axis is the natural tensor-
    parallel shard (``P(None, 'tensor', None)``), so TP needs no reshapes;
  * logits/softmax computed in float32 regardless of activation dtype
    (bf16-safe), matmuls in the input dtype so they hit the MXU in bf16;
  * additive masks (0 / -inf convention) so causal+padding masks compose by
    addition and fuse into one XLA op.

``ring_attention`` (sequence parallelism over the ``seq`` mesh axis) builds
on this module from ``parallel.ring``; a fused Pallas flash-attention kernel
slots in behind the same ``dot_product_attention`` signature.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import initializers as init_lib
from .layers import Layer

__all__ = ["dot_product_attention", "causal_mask", "padding_mask",
           "attention_core", "ffn_core", "ffn_swiglu_core",
           "rotary_embedding", "rope_tables", "apply_rope",
           "MultiHeadAttention", "flash_wins", "resolve_use_flash",
           "paged_kernel_wins", "resolve_use_paged_kernel",
           "PagedWindows", "paged_windows", "paged_window_mask",
           "last_real_position"]

NEG_INF = -1e9  # finite -inf stand-in: keeps softmax well-defined in f32

# Sequence length at/above which the fused Pallas flash kernel dispatches
# under use_flash="auto".  Measured on v5e with (512, 1024) blocks and
# RTT-amortised scan timing (docs/PERF.md, 2026-07-31): flash ties XLA at
# seq <= 1024 (0.95x), wins 1.3-1.7x at 2048 and ~3x at 4096 — XLA's
# materialised s^2 logits hit memory pressure exactly where the kernel's
# O(seq) streaming pays off.  Override with DTTPU_FLASH_MIN_SEQ;
# re-calibrate with scripts/validate_flash_tpu.py on new hardware.
_FLASH_MIN_SEQ_DEFAULT = 2048


def flash_wins(seq_len: int) -> bool:
    """Auto-dispatch policy: fused flash attention only on a real TPU
    backend and only at sequence lengths past the measured crossover."""
    import os

    import jax as _jax
    min_seq = int(os.environ.get("DTTPU_FLASH_MIN_SEQ",
                                 _FLASH_MIN_SEQ_DEFAULT))
    return seq_len >= min_seq and _jax.default_backend() == "tpu"


def resolve_activation(name: str):
    """Config ``hidden_act`` string -> activation fn — ONE mapping for
    every model config (BertConfig/ViTConfig) so numerics fixes and new
    activations land in exactly one place (same principle as ffn_core)."""
    import functools
    table = {
        "gelu_approx": jax.nn.gelu,                        # tanh, zoo default
        "gelu_new": jax.nn.gelu,                           # HF alias (tanh)
        "gelu_pytorch_tanh": jax.nn.gelu,                  # HF alias (tanh)
        "gelu": functools.partial(jax.nn.gelu, approximate=False),  # erf
        "relu": jax.nn.relu,
    }
    if name not in table:
        raise ValueError(f"unsupported hidden_act {name!r}; "
                         f"one of {sorted(table)}")
    return table[name]


def resolve_use_flash(use_flash, seq_len: int) -> bool:
    """Resolve a config's ``use_flash`` (True / False / "auto") for one
    forward at ``seq_len`` — the single dispatch point for BERT/GPT."""
    if use_flash == "auto":
        return flash_wins(seq_len)
    return bool(use_flash)


# Per-slot view length (pages_per_slot x page_size) at/above which the
# fused paged-attention kernel (ops/pallas/paged_attention.py)
# dispatches under use_paged_kernel="auto".  Seeded from the same v5e
# methodology as _FLASH_MIN_SEQ_DEFAULT: the XLA page-gather the kernel
# removes costs O(view_len) HBM traffic per layer per step, so the
# kernel wins as soon as the gathered operand stops fitting the fusion
# window — measured crossover printed by scripts/validate_paged_tpu.py;
# re-calibrate on new hardware.
_PAGED_KERNEL_MIN_VIEW = 512


def paged_kernel_wins(view_len: int) -> bool:
    """Auto-dispatch policy: the fused paged-attention kernel only on a
    real TPU backend and only at per-slot view lengths past the measured
    crossover (off-TPU the interpret-mode kernel is a correctness tool,
    never a win)."""
    import jax as _jax
    return (view_len >= _PAGED_KERNEL_MIN_VIEW
            and _jax.default_backend() == "tpu")


def resolve_use_paged_kernel(use_paged_kernel, view_len: int) -> bool:
    """Resolve a scheduler's ``use_paged_kernel`` (True / False /
    "auto") for a paged build whose slots see ``view_len`` logical
    columns — the single dispatch point for the serve tier's paged read
    path (serve/scheduler.py resolves once at construction; the page-
    size tileability check lives there too, so this stays a pure policy
    function)."""
    if use_paged_kernel == "auto":
        return paged_kernel_wins(view_len)
    return bool(use_paged_kernel)


class PagedWindows(NamedTuple):
    """A batch of ``n`` prefill windows of ``s`` tokens against a page
    pool (``paged_windows``)."""
    page_rows: jnp.ndarray   # [n, pages_per_row]: each window's table row
    pos: jnp.ndarray         # [n]: the logical column of its first token
    valid: jnp.ndarray       # [n]: how many of its tokens are real
    cols: jnp.ndarray        # [n, s]: its tokens' logical columns
    pages: jnp.ndarray       # [n * s]: the pool page each token writes
    offs: jnp.ndarray        # [n * s]: its cell on that page


def paged_windows(page_row, pos, valid, n: int, s: int,
                  page_size: int) -> PagedWindows:
    """Where a batch of prefill windows reads and writes a page pool: the
    one place the serving models' ``decode_window_paged`` take their call
    forms apart.  ``page_row`` [n, pages_per_row] (or rank 1 for n = 1),
    ``pos`` / ``valid`` [n] or scalars (``valid`` None: every token real).
    A window's pad columns — past its ``valid`` real tokens — are written
    to the reserved trash page 0, so a row with ``valid == 0`` (padding of
    the batch) writes nowhere else."""
    page_rows = jnp.reshape(page_row, (-1, page_row.shape[-1]))
    if page_rows.shape[0] != n:
        raise ValueError(f"{n} windows need {n} page rows; got "
                         f"{page_rows.shape[0]}")

    def per_row(v):
        return jnp.broadcast_to(jnp.asarray(v, jnp.int32).reshape(-1), (n,))

    pos = per_row(pos)
    valid = jnp.full((n,), s, jnp.int32) if valid is None else per_row(valid)
    j = jnp.arange(s)
    cols = pos[:, None] + j
    pages = jnp.take_along_axis(
        page_rows, jnp.minimum(cols // page_size, page_rows.shape[1] - 1),
        axis=1)
    pages = jnp.where(j < valid[:, None], pages, 0)
    return PagedWindows(page_rows, pos, valid, cols, pages.reshape(-1),
                        (cols % page_size).reshape(-1))


def paged_window_mask(windows: PagedWindows, page_size: int) -> jnp.ndarray:
    """[n, 1, s, view_len] additive mask over each window's gathered pages:
    token j attends every column ``<= pos + j`` (all of them its request's
    own, shared prefix pages included)."""
    view = jnp.arange(windows.page_rows.shape[1] * page_size)
    return jnp.where(view[None, None, :] <= windows.cols[:, :, None],
                     0.0, NEG_INF)[:, None]


def last_real_position(x: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """``x`` [n, s, d] -> [n, 1, d]: each window's last real position (its
    first where it has none), taken before the head matmul."""
    return jnp.take_along_axis(
        x, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)


def causal_mask(seq_len: int) -> jnp.ndarray:
    """[1, 1, seq, seq] additive mask; position i attends to j<=i."""
    mask = jnp.tril(jnp.ones((seq_len, seq_len), jnp.bool_))
    return jnp.where(mask, 0.0, NEG_INF)[None, None, :, :]


def padding_mask(valid: jnp.ndarray) -> jnp.ndarray:
    """valid: [batch, seq] bool/int (1 = real token) -> [b, 1, 1, seq]."""
    return jnp.where(valid.astype(jnp.bool_), 0.0, NEG_INF)[:, None, None, :]


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          scale: Optional[float] = None) -> jnp.ndarray:
    """q: [batch, seq, heads, head_dim]; k,v: same, or with FEWER heads
    (grouped-query attention) -> [batch, seq, heads, head_dim].

    Logit/softmax math in f32; matmuls stay in the input dtype for the MXU.
    The GQA path contracts each kv head against its query group directly —
    the kv tensors are never materialized at full head count.
    """
    head_dim = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    hq, hk = q.shape[2], k.shape[2]
    if hq == hk:
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
                  * scale)
        if mask is not None:
            logits = logits + mask
        weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    if hq % hk:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hk}")
    group = hq // hk
    b, s = q.shape[0], q.shape[1]
    qg = q.reshape(b, s, hk, group, head_dim)
    logits = (jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
              * scale)
    if mask is not None:
        # masks are [b|1, 1, q, s]; insert the group axis
        logits = logits + mask[:, :, None, :, :]
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
    return ctx.reshape(b, s, hq, head_dim)


def rope_tables(positions: jnp.ndarray, head_dim: int,
                base: float = 10000.0):
    """(cos, sin) angle tables for RoPE, shaped to broadcast against
    [b, s, h, hd/2].  Compute ONCE per forward and reuse across layers —
    the tables are position-only, identical for every layer in a scan."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim} — "
                         "pick hidden_size/num_heads with an even quotient")
    half = head_dim // 2
    freqs = jnp.power(base, -jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    if angles.ndim == 2:                 # [s, half] -> [1, s, 1, half]
        angles = angles[None, :, None, :]
    else:                                # [b, s, half] -> [b, s, 1, half]
        angles = angles[:, :, None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate [b, s, h, hd] feature pairs by precomputed tables (f32 math,
    result cast back to x.dtype)."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def rotary_embedding(x: jnp.ndarray, positions: jnp.ndarray,
                     base: float = 10000.0) -> jnp.ndarray:
    """RoPE (Su et al., 2021): rotate feature pairs by position-dependent
    angles so q·k depends only on RELATIVE distance.

    ``x``: [b, s, h, hd] (hd even); ``positions``: [s] (shared across the
    batch) or [b, s].  One-shot convenience over
    ``rope_tables``/``apply_rope`` (use those to share tables across a
    layer scan).
    """
    cos, sin = rope_tables(positions, x.shape[-1], base)
    return apply_rope(x, cos, sin)


def attention_core(params, x, *, mask=None, dropout_rate: float = 0.0,
                   rng=None, train: bool = False,
                   attention_fn=dot_product_attention,
                   kv=None, qk_transform=None) -> jnp.ndarray:
    """The shared multi-head attention body.

    ``params``: {query,key,value: {kernel [d,h,hd], bias [h,hd]},
    out: {kernel [h,hd,d], bias [d]}} — used by both the
    ``MultiHeadAttention`` layer and the scanned BERT stack, so projection/
    dtype/dropout fixes land in exactly one place.  ``attention_fn``
    swaps the inner kernel (full softmax, ring attention, a Pallas flash
    kernel) behind the same signature.  ``kv``: optional memory sequence
    for cross-attention (keys/values project from it; queries from ``x``).
    """
    dtype = x.dtype

    def project(p, src):
        y = jnp.einsum("bsd,dhk->bshk", src, p["kernel"].astype(dtype))
        if "bias" in p:           # no-bias configs (Llama) omit the key
            y = y + p["bias"].astype(dtype)
        return y

    memory = x if kv is None else kv.astype(dtype)
    q = project(params["query"], x)
    k = project(params["key"], memory)
    v = project(params["value"], memory)
    if qk_transform is not None:
        # positional rotation (RoPE) — applied post-projection, pre-kernel
        q, k = qk_transform(q, k)
    if (k.shape[2] != q.shape[2]
            and attention_fn is not dot_product_attention
            and not getattr(attention_fn, "supports_gqa", False)):
        # grouped-query attention with a swapped kernel that expects equal
        # head counts: broadcast kv head groups here.  The default dense
        # kernel handles grouping natively (grouped einsum), and kernels
        # marked ``supports_gqa`` (the flash kernels, which map kv blocks
        # by q_head // group) take the raw shapes — no repeat either way.
        if q.shape[2] % k.shape[2]:
            raise ValueError(f"query heads {q.shape[2]} not a multiple of "
                             f"kv heads {k.shape[2]}")
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    ctx = attention_fn(q, k, v, mask=mask)
    if train and dropout_rate > 0.0:
        if rng is None:
            raise ValueError("attention dropout requires rng in train mode")
        keep = 1.0 - dropout_rate
        drop = jax.random.bernoulli(rng, keep, ctx.shape)
        ctx = jnp.where(drop, ctx / keep, jnp.zeros_like(ctx))
    out = jnp.einsum("bshk,hkd->bsd", ctx,
                     params["out"]["kernel"].astype(dtype))
    if "bias" in params["out"]:
        out = out + params["out"]["bias"].astype(dtype)
    return out


def ffn_core(params, x, activation=jax.nn.gelu) -> jnp.ndarray:
    """The shared transformer FFN body: w_in -> activation -> w_out, matmuls
    in the input dtype (MXU path) with params cast to match.

    ``params``: {w_in: {kernel [d, i], bias [i]}, w_out: {kernel [i, d],
    bias [d]}} — like ``attention_core``, one implementation serves
    BERT/GPT/seq2seq so dtype/numerics fixes land in exactly one place.
    """
    dtype = x.dtype
    h = activation(_affine(params["w_in"], x, dtype))
    return _affine(params["w_out"], h, dtype)


def _affine(p, x, dtype):
    """x @ kernel (+ bias when present — no-bias configs like Llama simply
    omit the key)."""
    y = jnp.einsum("...d,di->...i", x, p["kernel"].astype(dtype))
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    return y


def ffn_swiglu_core(params, x, activation=jax.nn.silu) -> jnp.ndarray:
    """Gated-linear FFN body (Llama / PaLM):
    ``w_out(silu(w_gate(x)) * w_in(x))`` — ``w_in`` is HF's up_proj,
    ``w_gate`` gate_proj, ``w_out`` down_proj.  Same param-dict shape
    conventions and dtype rules as ``ffn_core``."""
    dtype = x.dtype
    h = activation(_affine(params["w_gate"], x, dtype)) \
        * _affine(params["w_in"], x, dtype)
    return _affine(params["w_out"], h, dtype)


class MultiHeadAttention(Layer):
    """Self-attention with TP-ready [d, heads, head_dim] projections."""

    def __init__(self, num_heads: int, d_model: int,
                 head_dim: Optional[int] = None,
                 dropout_rate: float = 0.0,
                 kernel_init="glorot_uniform",
                 name: Optional[str] = None):
        super().__init__(name or "attention")
        self.num_heads = num_heads
        self.d_model = d_model
        self.head_dim = head_dim or d_model // num_heads
        self.dropout_rate = dropout_rate
        self.kernel_init = init_lib.get(kernel_init)

    def init(self, key, in_shape):
        d = in_shape[-1]
        keys = jax.random.split(key, 4)
        h, hd = self.num_heads, self.head_dim
        shape_in = (d, h, hd)

        def proj(k, shape):
            # variance-scaled on the flattened fan
            flat = self.kernel_init(k, (shape[0],
                                        int(jnp.prod(jnp.asarray(shape[1:])))))
            return flat.reshape(shape)

        params = {
            "query": {"kernel": proj(keys[0], shape_in),
                      "bias": jnp.zeros((h, hd), jnp.float32)},
            "key": {"kernel": proj(keys[1], shape_in),
                    "bias": jnp.zeros((h, hd), jnp.float32)},
            "value": {"kernel": proj(keys[2], shape_in),
                      "bias": jnp.zeros((h, hd), jnp.float32)},
            "out": {"kernel": proj(keys[3], (h * hd, self.d_model)
                                   ).reshape(h, hd, self.d_model),
                    "bias": jnp.zeros((self.d_model,), jnp.float32)},
        }
        return params, {}

    def out_shape(self, in_shape):
        return tuple(in_shape[:-1]) + (self.d_model,)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return attention_core(params, x, mask=mask,
                              dropout_rate=self.dropout_rate, rng=rng,
                              train=train), state
