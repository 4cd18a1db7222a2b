"""Pipeline parallelism: GPipe-style microbatched stages over a ``pipe`` axis.

The reference has no pipeline parallelism (SURVEY.md §2c: "no stage
partitioning anywhere"); this supplies the strategy TPU-natively so the one
framework covers dp/fsdp/tp/sp/pp/ep on a single named Mesh.

Design (TPU-first, not a port of any PS/NCCL scheme):
  * every stage runs the SAME compiled program under ``shard_map`` manual
    over the ``pipe`` axis — SPMD, no per-stage executables, no host-side
    scheduler process;
  * stage parameters are stacked on a leading axis and sharded
    ``P('pipe')``, so each device holds exactly its stage's weights;
  * activations move stage-to-stage with ``lax.ppermute`` — a neighbor
    exchange that rides ICI, never the host;
  * the schedule is a ``lax.scan`` over ``num_microbatches + num_stages - 1``
    ticks (the classic GPipe fill/steady/drain trapezoid).  Backward is not
    hand-scheduled: JAX autodiff transposes the scan+ppermute program into
    the reverse pipeline automatically, which XLA overlaps the same way.

Constraint of this formulation: every stage maps activations of one shape to
activations of the SAME shape (transformer-block style).  Embed before the
pipeline, project after — see tests/test_pipeline.py for the usage pattern.

Known backend limitation (NOT a bug here): XLA:CPU miscompiles some of
these scan+ppermute programs with **bfloat16** activations — a fatal
"Invalid binary instruction opcode copy" check failure in the compiler
(seen in the GPipe autodiff transpose and in a jitted pipelined forward on
a pipe×data mesh; hand-scheduled 1F1B training compiles).  Use f32
activations for pp work on the CPU test rig (examples/train_gpt.py does
this automatically); TPU is the real target.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

__all__ = ["pipeline_apply", "stack_pipeline_params", "pipeline_rules_spec",
           "pipeline_value_and_grad"]


def stack_pipeline_params(stage_params: Sequence[Any]):
    """Stack per-stage param pytrees on a new leading ``pipe`` axis.

    All stages must share one tree structure/shapes (same-shape stages are
    already required by the schedule).  Shard the result with ``P('pipe')``
    on every leaf (``pipeline_rules_spec``).
    """
    return jax.tree.map(lambda *ps: jnp.stack(ps), *stage_params)


def pipeline_rules_spec(stacked_params, axis: str = "pipe"):
    """Same-structure pytree of ``P(axis)`` specs for the stacked params."""
    return jax.tree.map(lambda _: P(axis), stacked_params)


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stacked_params, x: jnp.ndarray, mesh: Mesh,
                   num_microbatches: int, axis: str = "pipe") -> jnp.ndarray:
    """Run ``x`` through ``num_stages`` copies of ``stage_fn`` as a pipeline.

    ``stage_fn(params_for_one_stage, acts) -> acts`` (same shape in/out).
    ``stacked_params``: leaves with leading dim == mesh.shape[axis]
    (see ``stack_pipeline_params``); pass them in already sharded
    ``P('pipe')`` or let shard_map slice them.
    ``x``: [global_batch, ...] — must divide by ``num_microbatches``.

    Returns [global_batch, ...] outputs, replicated over the pipe axis
    (a masked ``psum`` broadcast from the last stage).  Differentiable:
    ``jax.grad`` through this IS the backward pipeline.
    """
    n_stages = mesh.shape[axis]
    leading = {p.shape[0] for p in jax.tree.leaves(stacked_params)}
    if leading != {n_stages}:
        raise ValueError(
            f"stacked params have leading dim(s) {sorted(leading)} but the "
            f"'{axis}' mesh axis has {n_stages} stages — shard_map would "
            "silently drop stages")
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {num_microbatches} "
            "microbatches")
    mb = x.shape[0] // num_microbatches
    n_ticks = num_microbatches + n_stages - 1

    # Activation dtype for the scan carry: a stage may promote (bf16 batch
    # through f32 params -> f32 activations), and lax.scan requires a fixed
    # carry dtype — resolve the promotion once, outside the trace.
    one_stage = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype), stacked_params)
    mb_in = jax.ShapeDtypeStruct((mb, *x.shape[1:]), x.dtype)
    act_dtype = jnp.result_type(
        x.dtype, jax.eval_shape(stage_fn, one_stage, mb_in).dtype)

    def inner(params, x):
        # shard_map hands each device a leading pipe-dim of 1 — drop it.
        params = jax.tree.map(lambda p: p[0], params)
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        mbs = x.reshape(num_microbatches, mb, *x.shape[1:])

        shift_perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def tick(carry, t):
            state, buf = carry
            # Stage 0 injects microbatch t (clamped repeat once drained —
            # its outputs past t==M-1 never land in ``buf``); later stages
            # consume what arrived over the ring last tick.
            feed = mbs[jnp.clip(t, 0, num_microbatches - 1)]
            inp = jnp.where(is_first, feed.astype(act_dtype), state)
            out = stage_fn(params, inp).astype(act_dtype)
            # The last stage banks microbatch ``t - (n_stages-1)`` once the
            # pipeline has filled; O(1) slot-sized select, not a full-buffer
            # copy.
            slot = t - (n_stages - 1)
            write = is_last & (slot >= 0)
            slot_c = jnp.clip(slot, 0, num_microbatches - 1)
            buf = buf.at[slot_c].set(jnp.where(write, out, buf[slot_c]))
            state = lax.ppermute(out, axis, shift_perm)
            return (state, buf), None

        state0 = jnp.zeros((mb, *x.shape[1:]), act_dtype)
        buf0 = jnp.zeros((num_microbatches, mb, *x.shape[1:]), act_dtype)
        (_, buf), _ = lax.scan(tick, (state0, buf0), jnp.arange(n_ticks))
        # Broadcast the last stage's result to every stage (masked psum) so
        # the caller sees a pipe-replicated output.
        out = lax.psum(jnp.where(is_last, buf, 0.0), axis)
        return out.reshape(x.shape[0], *x.shape[1:])

    return shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stacked_params), P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
        check_vma=False)(stacked_params, x)


def pipeline_value_and_grad(stage_fn: Callable[[Any, jnp.ndarray],
                                               jnp.ndarray],
                            loss_fn: Callable[[jnp.ndarray, jnp.ndarray],
                                              jnp.ndarray],
                            stacked_params, x: jnp.ndarray, y: jnp.ndarray,
                            mesh: Mesh, num_microbatches: int,
                            axis: str = "pipe",
                            aux_params: Any = None,
                            with_dx: bool = False,
                            microbatch_weights: Any = None):
    """Hand-scheduled **1F1B** pipeline training pass -> ``(loss, grads)``.

    GPipe via ``jax.grad(pipeline_apply)`` runs all M forwards, then all M
    backwards — autodiff keeps every microbatch's residuals live, so
    activation memory grows O(M).  The 1F1B schedule (PipeDream-flush /
    Megatron) starts each microbatch's backward as soon as its forward
    clears the last stage, holding at most ``2*num_stages - 1`` microbatch
    inputs in flight — O(S), independent of M.  Residuals are not stored at
    all: the backward tick RECOMPUTES its stage forward from the stashed
    stage INPUT under ``jax.vjp`` (same FLOPs as GPipe-with-remat, which is
    how pipelines run in practice anyway).

    Schedule (lockstep SPMD, one fwd + one bwd sub-tick per tick): stage
    ``s`` forwards microbatch ``m`` at tick ``m + s`` (activations ppermute
    down the ring) and backwards it at tick ``m + 2(S-1) - s`` (cotangents
    ppermute back up), so the last stage's backward fires the very tick its
    forward completes — the "1F1B" interleave.  Total ``M + 2S - 2`` ticks.

    ``loss_fn(out_mb, y_mb) -> scalar`` (a per-microbatch mean); the
    returned loss is the mean over microbatches and the grads are exactly
    ``d(loss)/d(stacked_params)``, sharded ``P(axis)`` like the params.
    The last stage seeds both its own cotangent and the loss value through
    ONE combined ``jax.vjp`` over ``(out, loss)``, so every stage runs an
    identical program — no per-device branching.

    Full-model integration hooks (what lets a MODEL — embeddings before the
    pipeline, a head inside the loss — train under 1F1B, not just the
    stages):

      * ``aux_params``: extra pytree differentiated THROUGH the loss —
        ``loss_fn(aux_params, out_mb, y_mb)`` when given.  Returns their
        grads (pipe-replicated psum; only the last stage's loss touches
        them) appended to the result: the tied LM head / final-LN case.
      * ``with_dx=True``: also return ``d(loss)/d(x)`` — stage 0's input
        cotangents banked per microbatch — so the caller can chain
        ``jax.vjp`` through whatever produced ``x`` (embeddings).

    ``y`` may be any pytree whose leaves share the batch leading dim (e.g.
    ``{"targets": ..., "mask": ...}``); ``loss_fn`` receives the matching
    microbatch slice.  ``microbatch_weights``: optional [M] f32 summing to
    1 — the per-microbatch contribution to the total loss/gradient.  A
    MASKED-mean loss needs this: per-microbatch masked means averaged
    uniformly are NOT the global masked mean when mask counts differ, so
    pass each microbatch's normalizer share (mask-sum / total).  Default
    uniform 1/M is exact for plain-mean losses.

    Returns ``(loss, grads[, aux_grads][, dx])``.
    """
    n_stages = mesh.shape[axis]
    leading = {p.shape[0] for p in jax.tree.leaves(stacked_params)}
    if leading != {n_stages}:
        raise ValueError(
            f"stacked params have leading dim(s) {sorted(leading)} but the "
            f"'{axis}' mesh axis has {n_stages} stages")
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {num_microbatches} "
            "microbatches")
    mb = x.shape[0] // num_microbatches
    n_ticks = num_microbatches + 2 * (n_stages - 1)
    n_slots = min(num_microbatches, 2 * n_stages - 1)

    one_stage = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype), stacked_params)
    mb_in = jax.ShapeDtypeStruct((mb, *x.shape[1:]), x.dtype)
    act_dtype = jnp.result_type(
        x.dtype, jax.eval_shape(stage_fn, one_stage, mb_in).dtype)

    has_aux = aux_params is not None

    def inner(params, x, y, aux, weights):
        params = jax.tree.map(lambda p: p[0], params)
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        mbs = x.reshape(num_microbatches, mb, *x.shape[1:])
        mbs_y = jax.tree.map(
            lambda a: a.reshape(num_microbatches, a.shape[0]
                                // num_microbatches, *a.shape[1:]), y)

        fwd_perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
        bwd_perm = [(j, (j - 1) % n_stages) for j in range(n_stages)]

        # Differentiate only floating leaves: integer leaves (e.g. stacked
        # PRNG keys riding in the stage params) as vjp PRIMALS trip an
        # unimplemented ShardMapTracer path — close over them instead
        # (same-body closure, which shard_map allows).
        p_leaves, p_tdef = jax.tree_util.tree_flatten(params)
        p_isdiff = [jnp.issubdtype(l.dtype, jnp.floating) for l in p_leaves]
        p_diff = [l for l, d in zip(p_leaves, p_isdiff) if d]

        def rebuild(diff_leaves):
            it = iter(diff_leaves)
            return jax.tree_util.tree_unflatten(
                p_tdef, [next(it) if d else l
                         for l, d in zip(p_leaves, p_isdiff)])

        def fwd_and_loss(dl, xin, a, y_mb):
            # cast as the forward sub-tick does: the vjp's `out` cotangent
            # must be act_dtype or mixed-precision stages (bf16 compute on
            # f32 carries) reject the incoming bwd_state
            out = stage_fn(rebuild(dl), xin).astype(act_dtype)
            loss = (loss_fn(a, out, y_mb) if has_aux
                    else loss_fn(out, y_mb))
            return out, loss.astype(jnp.float32)

        def tick(carry, t):
            fwd_state, bwd_state, stash, gacc, ga_acc, dx_buf, loss_sum = \
                carry

            # ---- F sub-tick: stage s forwards microbatch t - s ----------
            mf = t - idx
            active_f = (mf >= 0) & (mf < num_microbatches)
            feed = mbs[jnp.clip(mf, 0, num_microbatches - 1)]
            xin = jnp.where(is_first, feed.astype(act_dtype), fwd_state)
            out = stage_fn(params, xin).astype(act_dtype)
            slot_f = jnp.clip(mf, 0, num_microbatches - 1) % n_slots
            stash = stash.at[slot_f].set(
                jnp.where(active_f, xin, stash[slot_f]))
            fwd_state = lax.ppermute(out, axis, fwd_perm)

            # ---- B sub-tick: stage s backwards t - 2(S-1) + s -----------
            mb_i = t - 2 * (n_stages - 1) + idx
            active_b = (mb_i >= 0) & (mb_i < num_microbatches)
            mb_c = jnp.clip(mb_i, 0, num_microbatches - 1)
            xin_b = stash[mb_c % n_slots]
            y_mb = jax.tree.map(lambda a: a[mb_c], mbs_y)
            (out_b, loss_b), vjp = jax.vjp(
                lambda dl, x_, a: fwd_and_loss(dl, x_, a, y_mb),
                p_diff, xin_b, aux)
            del out_b
            # last stage: seed this microbatch's share of d(loss); others:
            # incoming cotangent on out
            seed = weights[mb_c]
            g_out = jnp.where(is_last, jnp.zeros_like(bwd_state), bwd_state)
            g_loss = jnp.where(is_last, seed, jnp.float32(0.0))
            gp, gx, ga = vjp((g_out, g_loss))

            def acc(mask):
                def f(a_, g):
                    if g.dtype == jax.dtypes.float0:   # non-diff aux leaf
                        return a_
                    return a_ + jnp.where(mask, g, 0.0).astype(a_.dtype)
                return f

            gacc = jax.tree.map(acc(active_b), gacc, gp)
            # aux (loss-side) grads are nonzero only where g_loss seeds —
            # the last stage; accumulate there, psum-broadcast at the end
            ga_acc = jax.tree.map(acc(is_last & active_b), ga_acc, ga)
            if with_dx:
                # stage 0's input cotangent IS d(loss)/d(x[microbatch]) —
                # bank it (same slot trick as the forward output buffer;
                # act_dtype: each slot is written once, nothing accumulates)
                dx_buf = dx_buf.at[mb_c].set(
                    jnp.where(is_first & active_b, gx.astype(act_dtype),
                              dx_buf[mb_c]))
            bwd_state = lax.ppermute(gx.astype(act_dtype), axis, bwd_perm)
            loss_sum = loss_sum + jnp.where(
                is_last & active_b, loss_b, 0.0) * seed
            return (fwd_state, bwd_state, stash, gacc, ga_acc, dx_buf,
                    loss_sum), None

        fwd0 = jnp.zeros((mb, *x.shape[1:]), act_dtype)
        stash0 = jnp.zeros((n_slots, mb, *x.shape[1:]), act_dtype)
        gacc0 = [jnp.zeros(p.shape, jnp.float32) for p in p_diff]
        ga0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), aux)
        dx0 = jnp.zeros((num_microbatches, mb, *x.shape[1:]), act_dtype
                        ) if with_dx else jnp.zeros((), jnp.float32)
        carry0 = (fwd0, fwd0, stash0, gacc0, ga0, dx0, jnp.float32(0.0))
        (_, _, _, gacc, ga_acc, dx_buf, loss_sum), _ = lax.scan(
            tick, carry0, jnp.arange(n_ticks))
        loss = lax.psum(jnp.where(is_last, loss_sum, 0.0), axis)
        # grads in the full params structure; non-diff leaves get zeros
        g_it = iter(gacc)
        grads = jax.tree_util.tree_unflatten(
            p_tdef,
            [(next(g_it).astype(l.dtype) if d else jnp.zeros_like(l))[None]
             for l, d in zip(p_leaves, p_isdiff)])
        aux_grads = jax.tree.map(
            lambda g, p: lax.psum(jnp.where(is_last, g, 0.0), axis
                                  ).astype(p.dtype), ga_acc, aux)
        dx = (lax.psum(jnp.where(is_first, dx_buf, 0.0), axis
                       ).reshape(x.shape).astype(x.dtype)
              if with_dx else dx_buf)
        return loss, grads, aux_grads, dx

    aux_in = aux_params if has_aux else ()
    w_in = (jnp.full((num_microbatches,), 1.0 / num_microbatches,
                     jnp.float32)
            if microbatch_weights is None
            else jnp.asarray(microbatch_weights, jnp.float32))
    if w_in.shape != (num_microbatches,):
        raise ValueError(
            f"microbatch_weights shape {w_in.shape} != "
            f"({num_microbatches},) — clamp-indexing would silently "
            "mis-scale the loss")
    loss, grads, aux_grads, dx = shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stacked_params), P(),
                  jax.tree.map(lambda _: P(), y),
                  jax.tree.map(lambda _: P(), aux_in), P()),
        out_specs=(P(), jax.tree.map(lambda _: P(axis), stacked_params),
                   jax.tree.map(lambda _: P(), aux_in), P()),
        axis_names=frozenset({axis}),
        check_vma=False)(stacked_params, x, y, aux_in, w_in)
    result = (loss, grads)
    if has_aux:
        result += (aux_grads,)
    if with_dx:
        result += (dx,)
    return result


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("parallel.pipeline", hbm_budget=8 << 20)
def _graph_entries():
    """The GPipe forward at registry scale: stacked stage params sharded
    ``P('pipe')``, batch replicated.  The DT5xx ledger prices the
    per-tick ``ppermute`` neighbor exchange inside the scan (by design:
    activations MUST move every tick, so DT502 stays quiet) plus the
    masked psum broadcast after it."""
    import jax

    from .mesh import make_mesh

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"pipe": n})
    d = 16

    def stage(params, acts):
        w, b = params
        return jnp.tanh(acts @ w + b)

    def fwd(stacked, x):
        return pipeline_apply(stage, stacked, x, mesh,
                              num_microbatches=4)

    stacked = (jax.ShapeDtypeStruct((n, d, d), jnp.float32),
               jax.ShapeDtypeStruct((n, d), jnp.float32))
    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    return _graph_lib.Target(
        "pipeline_apply", fwd, (stacked, x),
        in_specs=((P("pipe"), P("pipe")), P()), mesh=mesh)
