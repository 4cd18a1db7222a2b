"""Mixture-of-Experts FFN with expert parallelism over an ``expert`` axis.

The reference has no routing/expert code (SURVEY.md §2c EP row: NO); this
supplies expert parallelism TPU-natively so the full dp/fsdp/tp/sp/pp/ep
axis set of ``parallel.mesh.AXIS_ORDER`` is covered.

TPU-first design (GShard/Switch style, not a port):
  * routing, dispatch and combine are dense einsums over one-hot
    capacity-slot masks — static shapes, MXU-friendly, no gather/scatter or
    data-dependent control flow, so the whole layer jits into one XLA
    program;
  * expert weights carry a leading ``num_experts`` dim sharded
    ``P('expert')``; with tokens sharded over ``data``, XLA lowers the
    dispatch/combine einsums to ``all_to_all`` over ICI automatically — the
    collective is implied by shardings, never hand-written;
  * over-capacity tokens are dropped (output zeros) — callers add the
    residual connection so dropped tokens degrade to identity, the standard
    MoE-transformer contract.

``aux_loss`` (Switch load-balancing: E * Σ_e f_e·P_e, =1.0 at perfect
balance) and ``router_z_loss`` must be added to the training loss by the
caller to keep routing healthy.

Beside that capacity-routed layer stands a DROPLESS one for serving
(``route_top_k`` + ``apply_routed_experts``): a float32 softmax router with
a choice bias and scaled, unrenormalised weights over FFN experts AND
identity (zero-compute) experts, for a layer that is told which of the FFN
experts it holds — one chip's share under expert parallelism.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import activations as act_lib
from . import initializers as init_lib

__all__ = ["init_moe", "apply_moe", "moe_partition_rules", "route_top_k",
           "apply_routed_experts"]


def init_moe(key, d_model: int, d_ff: int, num_experts: int,
             param_dtype=jnp.float32) -> Dict[str, Any]:
    """Router + a bank of ``num_experts`` two-matmul FFNs (leading E dim)."""
    k_r, k_in, k_out = jax.random.split(key, 3)
    glorot = init_lib.get("glorot_uniform")
    w_in = jnp.stack([
        glorot(k, (d_model, d_ff), param_dtype)
        for k in jax.random.split(k_in, num_experts)])
    w_out = jnp.stack([
        glorot(k, (d_ff, d_model), param_dtype)
        for k in jax.random.split(k_out, num_experts)])
    return {
        "router": {"kernel": glorot(k_r, (d_model, num_experts), param_dtype)},
        "experts": {
            "w_in": w_in,                                   # [E, D, F]
            "b_in": jnp.zeros((num_experts, d_ff), param_dtype),
            "w_out": w_out,                                 # [E, F, D]
            "b_out": jnp.zeros((num_experts, d_model), param_dtype),
        },
    }


def moe_partition_rules():
    """(regex, PartitionSpec) rows for ``parallel.PartitionRules``: experts
    sharded over ``expert``, the FFN hidden dim optionally over ``tensor``,
    router replicated."""
    return [
        (r"experts/w_in$", P("expert", None, "tensor")),
        (r"experts/b_in$", P("expert", "tensor")),
        (r"experts/w_out$", P("expert", "tensor", None)),
        (r"experts/b_out$", P("expert", None)),
        (r"router/", P()),
    ]


def _top_k_dispatch(probs: jnp.ndarray, k: int, capacity: int):
    """One-hot capacity-slot dispatch/combine tensors from router probs.

    probs: [T, E].  Returns (dispatch [T, E, C] bool-ish float,
    combine [T, E, C] float, top1_mask [T, E]).
    Iterative arg-max (k is 1 or 2 in practice): choice i masks out the
    experts already taken, then tokens claim capacity slots in token order
    via a cumsum — all static-shape, no sort network needed.
    """
    t, e = probs.shape
    remaining = probs
    fill = jnp.zeros((e,), jnp.int32)          # slots already used per expert
    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    top1_mask = None
    gate_sum = jnp.zeros((t,), probs.dtype)

    for i in range(k):
        idx = jnp.argmax(remaining, axis=-1)               # [T]
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)   # [T, E]
        if i == 0:
            top1_mask = mask
        gate = jnp.sum(probs * mask, axis=-1)              # [T]
        # Position of each token within its chosen expert's capacity.
        pos = (jnp.cumsum(mask, axis=0) - 1) * mask + fill[None, :] * mask
        pos_tok = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [T]
        keep = (pos_tok < capacity) & (jnp.max(mask, axis=-1) > 0)
        slot = jax.nn.one_hot(pos_tok, capacity,
                              dtype=probs.dtype)           # [T, C]
        assign = (mask[:, :, None] * slot[:, None, :]
                  * keep[:, None, None].astype(probs.dtype))
        dispatch = dispatch + assign
        combine = combine + assign * gate[:, None, None]
        gate_sum = gate_sum + gate * keep.astype(probs.dtype)
        fill = fill + jnp.sum(assign, axis=(0, 2)).astype(jnp.int32)
        remaining = remaining * (1.0 - mask)

    # Normalize combine weights over the (kept) top-k gates per token.
    combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]
    return dispatch, combine, top1_mask


def apply_moe(params: Dict[str, Any], x: jnp.ndarray, *, k: int = 2,
              capacity_factor: float = 1.25,
              capacity: Optional[int] = None,
              group_size: Optional[int] = None,
              activation="gelu", train: bool = False, rng=None,
              jitter: float = 1e-2) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """x: [..., d_model] -> (y [..., d_model], metrics).

    Routing is GROUPED (GShard style): tokens are split into fixed-size
    groups and each group routes into its own per-expert capacity slots, so
    the dispatch/combine tensors are [G, S, E, C] with C ∝ S — linear in
    total tokens, never O(T²).  Default grouping: the leading (batch) dim
    when ``x`` has ≥3 dims, one group otherwise; ``group_size`` overrides
    (must divide the token count).  ``capacity`` is per group per expert.

    ``metrics['aux_loss']`` / ``metrics['router_z_loss']`` are scalars the
    caller adds to the loss (weighted ~1e-2 / ~1e-3).  Dropped (over-
    capacity) tokens return zeros — add the residual outside.
    ``jitter``: multiplicative router-input noise when ``train`` and ``rng``.
    """
    act = act_lib.get(activation)
    *lead, d = x.shape
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    e = params["experts"]["w_in"].shape[0]

    if group_size is None:
        group_size = t // x.shape[0] if x.ndim >= 3 else t
    if t % group_size:
        raise ValueError(f"group_size {group_size} does not divide token "
                         f"count {t}")
    tok = tokens.reshape(-1, group_size, d)                # [G, S, D]
    if capacity is None:
        capacity = max(1, int(capacity_factor * k * group_size / e))

    router_in = tok
    if train and rng is not None and jitter > 0:
        router_in = tok * jax.random.uniform(
            rng, tok.shape, tok.dtype, 1.0 - jitter, 1.0 + jitter)
    logits = jnp.einsum("gsd,de->gse", router_in,
                        params["router"]["kernel"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    dispatch, combine, top1 = jax.vmap(
        lambda p: _top_k_dispatch(p, k, capacity))(probs)  # [G,S,E,C] x2
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    ex = params["experts"]
    # [G,S,E,C] x [G,S,D] -> [G,E,C,D]: the all_to_all boundary under
    # sharding (groups ride ``data``, experts ride ``expert``).
    staged = jnp.einsum("gsec,gsd->gecd", dispatch, tok)
    h = act(jnp.einsum("gecd,edf->gecf", staged, ex["w_in"].astype(x.dtype))
            + ex["b_in"].astype(x.dtype)[None, :, None, :])
    out_e = (jnp.einsum("gecf,efd->gecd", h, ex["w_out"].astype(x.dtype))
             + ex["b_out"].astype(x.dtype)[None, :, None, :])
    y = jnp.einsum("gsec,gecd->gsd", combine, out_e)

    frac_tokens = jnp.mean(top1, axis=(0, 1))              # f_e
    mean_probs = jnp.mean(probs, axis=(0, 1))              # P_e
    aux_loss = e * jnp.sum(frac_tokens * mean_probs)
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    metrics = {
        "aux_loss": aux_loss.astype(jnp.float32),
        "router_z_loss": jnp.mean(z ** 2),
        "dropped_fraction": 1.0 - jnp.sum(dispatch) / (k * t),
    }
    return y.reshape(*lead, d), metrics


# ------------------------------------------------- dropless routed layer

def route_top_k(router_kernel, choice_bias, x, *, top_k: int,
                scale: float):
    """The router's rules, in float32 whatever ``x``'s type: ``p =
    softmax(x W_r)`` over every expert (FFN and identity alike); the
    ``top_k`` largest of ``p + choice_bias`` are CHOSEN; the weights are the
    UNBIASED ``p`` of the chosen, times ``scale``, not renormalised.
    ``x`` [T, d] -> (choice [T, top_k] int32, weight [T, top_k] float32)."""
    f32 = jnp.float32
    logits = x.astype(f32) @ router_kernel.astype(f32)
    p = jax.nn.softmax(logits, axis=-1)
    _, choice = jax.lax.top_k(p + choice_bias.astype(f32), top_k)
    weight = jnp.take_along_axis(p, choice, axis=-1) * scale
    return choice.astype(jnp.int32), weight


def apply_routed_experts(params: Dict[str, Any], x: jnp.ndarray, *,
                         top_k: int, scale: float, num_ffn_experts: int,
                         expert_offset: int = 0,
                         valid: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A dropless top-k layer of SwiGLU FFN experts and identity experts,
    for the share of the FFN experts that lives here.

    ``params``: ``router/kernel`` [d, E] and ``router/choice_bias`` [E] over
    ALL ``E = num_ffn_experts + identity experts`` (the published router,
    whatever is held); ``experts/w_in/kernel`` [H, d, 2 f] (gate and up side
    by side) and ``experts/w_out/kernel`` [H, f, d] for the ``H`` FFN experts
    held: the published indices ``expert_offset .. expert_offset + H - 1``.
    Indices from ``num_ffn_experts`` up are identity experts: a pick adds
    ``w * x``, needs no weights and no exchange, and is computed here for
    every token that lives here.  A pick on an FFN expert that is NOT held
    adds nothing: what the absent experts would give is another chip's part
    of the sum (with all of them held, the layer is the whole model's).

    Dropless and exact for any assignment: there is no capacity and no
    ``[T, E, C]`` tensor.  The held experts go one at a time: one that
    received a token runs its FFN on the block's ``T`` rows with its
    per-token weight (zero where it was not picked), its matrices sliced out
    of the bank inside the branch (no copy of a bank); ``lax.cond`` skips
    one nobody picked, so a decode step or a prefill window of a few dozen
    tokens READS only the experts it touches and its time goes with the
    routing.  Cost: at most ``T x H`` FFN rows, linear in tokens; a block of
    thousands of tokens, where each token needs ``top_k * H / E`` experts,
    is better served by a grouped (sorted) matmul — the perf work this
    layer's counts size.

    ``x`` [T, d]; ``valid`` [T] bool marks the real rows (pad rows of a
    prefill window, slots that are not live): the others add nothing and
    are not counted.  Returns ``(y [T, d] in x's type, counts [H + 2]
    int32)``: tokens received by each held expert, picks on identity
    experts, picks on absent FFN experts.
    """
    f32 = jnp.float32
    bank_in = params["experts"]["w_in"]["kernel"]
    bank_out = params["experts"]["w_out"]["kernel"]
    held = bank_in.shape[0]
    with jax.named_scope("router"):
        choice, weight = route_top_k(
            params["router"]["kernel"], params["router"]["choice_bias"], x,
            top_k=top_k, scale=scale)
        real = (jnp.ones(x.shape[:1], bool) if valid is None
                else valid)[:, None]
        weight = jnp.where(real, weight, 0.0)
        local = choice - expert_offset                        # [T, k]
        on_held = (local >= 0) & (local < held) & real
        on_identity = (choice >= num_ffn_experts) & real
        # [T, k, H] one-hot of the held picks: H is the share, not E
        hit = (local[..., None] == jnp.arange(held)) & on_held[..., None]
        held_weight = jnp.sum(jnp.where(hit, weight[..., None], 0.0), axis=1)
        received = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)        # [H]
        n_identity = jnp.sum(on_identity, dtype=jnp.int32)
        n_absent = (top_k * jnp.sum(real, dtype=jnp.int32)
                    - jnp.sum(received) - n_identity)
        counts = jnp.concatenate([received,
                                  jnp.stack([n_identity, n_absent])])
    with jax.named_scope("identity_experts"):
        y = (jnp.sum(jnp.where(on_identity, weight, 0.0), axis=-1,
                     keepdims=True) * x.astype(f32))

    def one_expert(e, y):
        def run(y):
            w_in = jax.lax.dynamic_index_in_dim(bank_in, e, keepdims=False)
            w_out = jax.lax.dynamic_index_in_dim(bank_out, e, keepdims=False)
            gate, up = jnp.split(x @ w_in.astype(x.dtype), 2, axis=-1)
            out = (jax.nn.silu(gate) * up) @ w_out.astype(x.dtype)
            w = jax.lax.dynamic_index_in_dim(held_weight, e, axis=1)
            return y + w * out.astype(f32)

        return jax.lax.cond(received[e] > 0, run, lambda y: y, y)

    with jax.named_scope("experts"):
        y = jax.lax.fori_loop(0, held, one_expert, y)
    return y.astype(x.dtype), counts
