"""Compiled train/eval step builders.

This is the TPU replacement for the reference's per-step
``sess.run([accuracy, loss, summ, train_step], feed_dict=...)`` hot loop
(reference example.py:207-213): the whole update — forward, backward, Adam
apply, metric computation, and (when sharded over a mesh's data axis) the
gradient all-reduce over ICI — is ONE jit-compiled XLA program.  There is no
per-step variable pull/push (SURVEY.md §3.1): parameters live on device
across steps and the state pytree is donated so updates happen in place.

Sharding: pass a ``Mesh`` (and optionally a params PartitionSpec pytree) and
the step is compiled with the batch sharded over the ``data`` axis.  Because
the loss is a *global-batch mean*, the gradient XLA computes under that
sharding already includes the cross-replica mean — the ``psum`` the north
star asks for is inserted by the partitioner.  (The explicit
``shard_map``+``psum`` spelling lives in ``parallel.data_parallel``.)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import losses as loss_lib
from ..ops import metrics as metric_lib
from ..optim import optimizers as opt_lib
from ..optim.ema import EMAState
from . import precision as prec_lib
from .session import TrainState

__all__ = ["make_train_step", "make_multi_train_step", "make_eval_step",
           "make_masked_eval_step", "make_1f1b_train_step",
           "init_train_state", "shard_train_state"]


def shard_train_state(state: "TrainState", mesh: Mesh, rules) -> "TrainState":
    """Place a TrainState for fsdp/tensor-parallel training.

    Params get their rule-table shardings; every opt_state subtree with the
    SAME tree structure as params (Adam's m/v, momentum's mu) gets the SAME
    shardings — the ZeRO requirement that optimizer moments shard with
    their parameters, not replicate.  Everything else (step counters,
    model_state) replicates.  Use with a plain-jit step (no pinned
    in_shardings): XLA propagates these placements through the program.
    """
    params_sh = rules.tree_shardings(mesh, state.params)
    params_def = jax.tree_util.tree_structure(state.params)
    replicated = NamedSharding(mesh, P())

    def place(subtree):
        """Recursive ZeRO placement: any params-shaped subtree (Adam m/v,
        momentum mu, EMA shadow) shards like the params; containers and
        wrapper states (with_ema's {'opt': OptState, 'ema': EMAState})
        recurse; scalars/leftovers replicate."""
        # Params-shaped FIRST: momentum's mu IS a params-shaped pytree
        # (dict or bare array) and must shard with the params, not fall
        # into the container branches and replicate.  Leaf-by-leaf shape
        # check: adafactor's factored moment trees share the params
        # TREEDEF but hold rank-reduced vectors — those replicate (they
        # are O(r + c); replication costs ~nothing).
        if jax.tree_util.tree_structure(subtree) == params_def:
            def put(leaf, sh, p_leaf):
                ok = tuple(jnp.shape(leaf)) == tuple(jnp.shape(p_leaf))
                return jax.device_put(leaf, sh if ok else replicated)
            return jax.tree.map(put, subtree, params_sh, state.params)
        if isinstance(subtree, dict):
            return {k: place(v) for k, v in subtree.items()}
        if isinstance(subtree, opt_lib.OptState):
            return opt_lib.OptState(
                jax.device_put(subtree.count, replicated),
                place(subtree.inner))
        if isinstance(subtree, EMAState):
            # shard the shadow like the params, replicate the scalars
            return EMAState(
                jax.device_put(subtree.count, replicated),
                jax.device_put(subtree.decay, replicated),
                jax.device_put(subtree.debias, replicated),
                place(subtree.shadow))
        if not jax.tree_util.tree_leaves(subtree):
            return subtree         # stateless (sgd)
        return jax.device_put(subtree, replicated)

    opt_state = state.opt_state
    new_opt = type(opt_state)(jax.device_put(opt_state.count, replicated),
                              place(opt_state.inner))
    return state._replace(
        step=jax.device_put(state.step, replicated),
        params=jax.device_put(state.params, params_sh),
        opt_state=new_opt,
        model_state=jax.device_put(state.model_state, replicated)
        if jax.tree_util.tree_leaves(state.model_state)
        else state.model_state)


def init_train_state(model, optimizer, key, in_shape) -> TrainState:
    """Initialize params/state/opt_state for a layer Stack + Optimizer."""
    params, model_state = model.init(key, in_shape)
    opt_state = optimizer.init(params)
    return TrainState.create(params, opt_state, model_state)


def _state_batch_shardings(mesh: Mesh, params_spec, batch_spec: P):
    """(TrainState shardings, (x, y) shardings) for the pjit'd step — shared
    by the single-step and scanned multi-step builders."""
    replicated = NamedSharding(mesh, P())
    params_shardings = replicated
    if params_spec is not None:
        params_shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), params_spec,
            is_leaf=lambda v: isinstance(v, P))
    state_shardings = TrainState(step=replicated, params=params_shardings,
                                 opt_state=replicated,
                                 model_state=replicated)
    batch_sharding = NamedSharding(mesh, batch_spec)
    return state_shardings, (batch_sharding, batch_sharding)


def _metric_dict(metric_fns, preds, y) -> Dict[str, jnp.ndarray]:
    out = {}
    for name, fn in (metric_fns or {}).items():
        out[name] = metric_lib.get(fn)(preds, y)
    return out


def make_train_step(model, loss, optimizer: opt_lib.Optimizer,
                    metric_fns: Optional[Dict[str, Any]] = None,
                    seed: int = 0,
                    mesh: Optional[Mesh] = None,
                    params_spec: Any = None,
                    batch_spec: P = P("data"),
                    jit: bool = True,
                    grad_clip_norm: Optional[float] = None,
                    accum_steps: int = 1,
                    policy: Any = None,
                    loss_scale: bool = False,
                    device_health: bool = False,
                    skip_nonfinite: bool = False) -> Callable:
    """Build ``step(state, (x, y)) -> (new_state, metrics)``.

    Thin adapter over ``make_custom_train_step``: wraps the (model, loss,
    metrics) trio into the generic loss-fn contract, and translates the
    (mesh, params_spec, batch_spec) convenience arguments into state/batch
    sharding pytrees.  XLA partitions the whole step and inserts the
    gradient all-reduce implied by the global-mean loss.

    Dropout randomness: one base key from ``seed``, folded with the global
    step inside the trace — deterministic, resume-stable, and unique per
    step (the explicit-PRNG answer to the reference's learning-phase feed,
    example.py:213; SURVEY.md §7 "Dropout determinism").
    """
    loss_value_fn = loss_lib.get(loss)

    def loss_fn(params, model_state, batch, rng, train):
        x, y = batch
        preds, new_model_state = model.apply(params, model_state, x,
                                             train=train, rng=rng)
        metrics = _metric_dict(metric_fns, preds, y)
        return loss_value_fn(preds, y), (metrics, new_model_state)

    state_shardings = batch_shardings = None
    if mesh is not None:
        state_shardings, batch_shardings = _state_batch_shardings(
            mesh, params_spec, batch_spec)

    return make_custom_train_step(loss_fn, optimizer, seed=seed, mesh=mesh,
                                  state_shardings=state_shardings,
                                  batch_shardings=batch_shardings, jit=jit,
                                  grad_clip_norm=grad_clip_norm,
                                  accum_steps=accum_steps, policy=policy,
                                  loss_scale=loss_scale,
                                  device_health=device_health,
                                  skip_nonfinite=skip_nonfinite)


def make_custom_train_step(loss_fn, optimizer: opt_lib.Optimizer,
                           seed: int = 0,
                           mesh: Optional[Mesh] = None,
                           state_shardings: Any = None,
                           batch_shardings: Any = None,
                           jit: bool = True,
                           grad_clip_norm: Optional[float] = None,
                           accum_steps: int = 1,
                           policy: Any = None,
                           loss_scale: bool = False,
                           device_health: bool = False,
                           skip_nonfinite: bool = False) -> Callable:
    """Generalized step builder for model families with structured batches.

    ``loss_fn(params, model_state, batch, rng, train) ->
    (loss, (metrics_dict, new_model_state))`` — the contract used by the
    model zoo (BERT MLM, ResNet, ...).  Sharding: pass a TrainState-shaped
    ``state_shardings`` and a batch-shaped ``batch_shardings`` (NamedSharding
    pytrees) for the pjit path.

    ``accum_steps > 1``: gradient accumulation — the batch's leading dim is
    split into that many microbatches, gradients/metrics are averaged over a
    ``lax.scan`` (peak activation memory drops ~accum_steps-fold) and ONE
    optimizer update is applied.  Each microbatch gets its own dropout key
    and model_state (BatchNorm stats) threads through sequentially.

    Masked-mean losses: a per-microbatch masked mean averaged with equal
    weights is NOT the full-batch masked mean when mask counts differ per
    microbatch.  A ``loss_fn`` whose loss normalizes by a mask (GPT/BERT
    LM heads) should report ``metrics['loss_weight']`` = its normalizer
    (e.g. the mask sum); accumulation then weights every microbatch's
    gradients/loss/metrics by it, recovering the exact full-batch gradient.
    Without that key all microbatches weigh 1 (exact for plain-mean losses).

    ``policy``: a precision.Policy (or its string spec, e.g.
    ``"mixed_bfloat16"``) — params are cast to the compute dtype inside the
    differentiated function, so gradients come back in the param dtype and
    the master copy stays full-precision.  ``loss_scale=True``: the state's
    ``model_state`` must be wrapped via ``precision.attach_loss_scale``;
    the step scales the loss, unscales the gradients, SKIPS the update on
    non-finite gradients, and threads the adjusted scale forward (reported
    as ``metrics['loss_scale']`` / ``metrics['grads_finite']``).

    ``device_health=True``: replica-health accumulators (``obs.device``:
    global grad L2 norm + non-finite gradient element count) are computed
    IN-GRAPH and ride the returned metrics dict — the telemetry contract:
    the health scalars are two reductions fused into the step, hooks pull
    them only when they fire, and the hot loop gains no device->host
    syncs.  (``grad_clip_norm`` already reports ``grad_norm``; the health
    key defers to it.)

    ``skip_nonfinite=True``: when any gradient element is non-finite the
    whole update is dropped IN-GRAPH — params, optimizer state (bias
    correction must not see skipped steps), and model_state keep their
    pre-step values; only the step cursor advances.  The rollback must
    live inside the compiled step because the state is donated: by the
    time a hook could react on the host, the pre-step buffers are gone.
    The returned state therefore already IS the rolled-back one, and
    ``metrics['grads_finite']`` reports what happened — pair with
    ``resilience.NonfiniteGuardHook`` to abort (for a supervisor
    restart) after K consecutive skips.  ``loss_scale=True`` includes
    this skip already (plus scale adjustment); combining both is
    rejected.
    """
    if skip_nonfinite and loss_scale:
        raise ValueError("loss_scale=True already skips non-finite "
                         "updates; drop skip_nonfinite")
    base_key = jax.random.PRNGKey(seed)
    pol = prec_lib.policy(policy) if policy is not None else None

    def grad_of(params, model_state, mb, rng, ls=None):
        def compute(p):
            mb_ = mb
            if pol is not None:
                p = pol.cast_to_compute(p)
                mb_ = pol.cast_to_compute(mb)
            value, aux = loss_fn(p, model_state, mb_, rng, True)
            if ls is not None:
                value = ls.scale(value)
            return value, aux
        return jax.value_and_grad(compute, has_aux=True)(params)

    def step(state: TrainState, batch):
        rng = jax.random.fold_in(base_key, state.step)
        if loss_scale:
            if not isinstance(state.model_state, prec_lib.LossScaled):
                raise TypeError(
                    "loss_scale=True needs state.model_state wrapped by "
                    "precision.attach_loss_scale(state, loss_scale)")
            model_state_in = state.model_state.model_state
            ls = state.model_state.loss_scale
        else:
            model_state_in, ls = state.model_state, None

        if accum_steps == 1:
            (loss_value, (metrics, new_model_state)), grads = grad_of(
                state.params, model_state_in, batch, rng, ls)
        else:
            lead = {a.shape[0] for a in jax.tree.leaves(batch)}
            bad = [n for n in lead if n % accum_steps]
            if bad:
                raise ValueError(
                    f"batch leading dim(s) {sorted(bad)} not divisible by "
                    f"accum_steps={accum_steps}")
            mbs = jax.tree.map(
                lambda a: a.reshape(accum_steps, a.shape[0] // accum_steps,
                                    *a.shape[1:]), batch)
            mb_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), mbs)
            (loss_s, (metrics_s, _)), grads_s = jax.eval_shape(
                grad_of, state.params, model_state_in, mb_shapes, rng)
            has_weight = "loss_weight" in metrics_s
            metrics_s = dict(metrics_s)
            metrics_s.pop("loss_weight", None)

            def zeros(tree):
                return jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), tree)

            def body(carry, inp):
                grads, loss_sum, metrics_sum, model_state, w_sum = carry
                mb, i = inp
                (l, (m, model_state)), g = grad_of(
                    state.params, model_state, mb, jax.random.fold_in(rng, i),
                    ls)
                m = dict(m)
                w = m.pop("loss_weight", jnp.ones((), jnp.float32))
                w = w.astype(jnp.float32)
                grads = jax.tree.map(lambda a, b: a + b * w, grads, g)
                metrics_sum = jax.tree.map(lambda a, b: a + b * w,
                                           metrics_sum, m)
                return (grads, loss_sum + l * w, metrics_sum, model_state,
                        w_sum + w), None

            carry0 = (zeros(grads_s), jnp.zeros(loss_s.shape, loss_s.dtype),
                      zeros(metrics_s), model_state_in,
                      jnp.zeros((), jnp.float32))
            (grads, loss_value, metrics, new_model_state, w_sum), _ = \
                jax.lax.scan(body, carry0, (mbs, jnp.arange(accum_steps)))
            inv = 1.0 / jnp.maximum(w_sum, 1e-9)
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss_value = loss_value * inv
            metrics = jax.tree.map(lambda m: m * inv, metrics)
            if has_weight:
                metrics["loss_weight"] = w_sum
        if ls is not None:
            grads = ls.unscale(grads)
            loss_value = ls.unscale(loss_value)
            finite = prec_lib.all_finite(grads)
            new_ls = ls.adjust(finite)
            # Zero the grads on overflow: the update is dropped below, and
            # this keeps inf/nan out of everything derived from them
            # (grad_norm metric, optimizer moment math).
            grads = jax.tree.map(
                lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads)
        if pol is not None:
            # output_dtype governs what leaves the step: reported loss and
            # metrics come back widened (bf16 compute, f32 logs).
            loss_value = pol.cast_to_output(loss_value)
            metrics = pol.cast_to_output(metrics)
        metrics = {"loss": loss_value, **metrics}
        if device_health:
            from ..obs import device as obs_device
            for k, v in obs_device.grad_health(grads).items():
                metrics.setdefault(k, v)
        sn_finite = prec_lib.all_finite(grads) if skip_nonfinite else None
        if grad_clip_norm is not None:
            grads, gnorm = opt_lib.clip_by_global_norm(grads, grad_clip_norm)
            metrics["grad_norm"] = gnorm
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = opt_lib.apply_updates(state.params, updates)
        if sn_finite is not None:
            # In-graph rollback: the NaN-contaminated candidates are
            # computed then discarded by the select — where() never
            # propagates the unselected branch's NaNs.  Same keep shape
            # as the loss-scale skip below.
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(sn_finite, n, o), new, old)
            new_params = keep(new_params, state.params)
            new_opt_state = keep(new_opt_state, state.opt_state)
            new_model_state = keep(new_model_state, model_state_in)
            metrics["grads_finite"] = sn_finite
        if ls is not None:
            # Non-finite grads: drop the whole update (params, optimizer
            # state including its step count — bias correction must not see
            # skipped steps — and model_state: overflow activations must not
            # contaminate running stats), shrink the scale, advance only the
            # cursor.  The reported loss is sanitized on skipped steps so a
            # NaNHook doesn't abort the run this machinery just rescued.
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_params = keep(new_params, state.params)
            new_opt_state = keep(new_opt_state, state.opt_state)
            new_model_state = keep(new_model_state, model_state_in)
            metrics["loss"] = jnp.where(finite, metrics["loss"],
                                        jnp.zeros_like(metrics["loss"]))
            metrics["grads_finite"] = finite
            metrics["loss_scale"] = new_ls.scale_value
            new_model_state = prec_lib.LossScaled(new_model_state, new_ls)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt_state,
                          model_state=new_model_state), metrics

    if not jit:
        return step
    if mesh is None or state_shardings is None:
        return jax.jit(step, donate_argnums=0)
    return jax.jit(step, donate_argnums=0,
                   in_shardings=(state_shardings, batch_shardings))


def make_1f1b_train_step(model, optimizer: opt_lib.Optimizer,
                         seed: int = 0,
                         grad_clip_norm: Optional[float] = None,
                         jit: bool = True) -> Callable:
    """``step(state, batch) -> (new_state, metrics)`` whose gradients come
    from the model's hand-scheduled **1F1B** pipeline pass — O(stages)
    activation memory instead of the GPipe path's O(microbatches).

    ``model`` must expose ``lm_1f1b_value_and_grad(params, batch, rng,
    train)`` (``models.gpt.GPT`` with ``pipeline_stages > 1``); everything
    else (fold-in dropout keys, clip, donated state) matches the plain
    step builders.
    """
    base_key = jax.random.PRNGKey(seed)

    def step(state: TrainState, batch):
        rng = jax.random.fold_in(base_key, state.step)
        loss_value, grads = model.lm_1f1b_value_and_grad(
            state.params, batch, rng, True)
        metrics = {"loss": loss_value}
        if grad_clip_norm is not None:
            grads, gnorm = opt_lib.clip_by_global_norm(grads, grad_clip_norm)
            metrics["grad_norm"] = gnorm
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = opt_lib.apply_updates(state.params, updates)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt_state,
                          model_state=state.model_state), metrics

    return jax.jit(step, donate_argnums=0) if jit else step


def make_multi_train_step(model, loss, optimizer: opt_lib.Optimizer,
                          steps_per_call: int,
                          metric_fns: Optional[Dict[str, Any]] = None,
                          seed: int = 0,
                          mesh: Optional[Mesh] = None,
                          params_spec: Any = None,
                          batch_spec: P = P("data"),
                          grad_clip_norm: Optional[float] = None,
                          accum_steps: int = 1,
                          policy: Any = None,
                          loss_scale: bool = False) -> Callable:
    """``step(state, (xs, ys)) -> (state, metrics)`` running
    ``steps_per_call`` updates in ONE dispatch via ``lax.scan``.

    Batch leaves carry a leading ``steps_per_call`` dim ([K, batch, ...]).
    Metrics come back stacked ([K]); reduce (e.g. ``metrics['loss'][-1]``)
    on the host.  Why: a per-step dispatch pays host→runtime latency every
    update — the feed_dict tax the reference pays at example.py:213 in
    different clothing.  For small models that latency dominates; scanning K
    updates inside the compiled program amortizes it (measured 2-3x on the
    MNIST MLP) while keeping identical update semantics (the scan body IS
    the single-step function).
    """
    inner = make_train_step(model, loss, optimizer, metric_fns=metric_fns,
                            seed=seed, jit=False,
                            grad_clip_norm=grad_clip_norm,
                            accum_steps=accum_steps, policy=policy,
                            loss_scale=loss_scale)

    def multi(state: TrainState, batch):
        return jax.lax.scan(inner, state, batch, length=steps_per_call)

    if mesh is None:
        return jax.jit(multi, donate_argnums=0)
    state_shardings, batch_shardings = _state_batch_shardings(
        mesh, params_spec, P(None, *batch_spec))  # leading K dim unsharded
    return jax.jit(multi, donate_argnums=0,
                   in_shardings=(state_shardings, batch_shardings))


def _eval_forward(model, pol, state: TrainState, x):
    """The ONE eval-phase forward shared by the plain and masked eval
    steps (so precision-policy/state-unwrap changes can never make the
    multi-process ragged-tail path drift from the plain path)."""
    # A loss-scaled TrainState wraps model_state; models see through it.
    model_state = state.model_state
    if isinstance(model_state, prec_lib.LossScaled):
        model_state = model_state.model_state
    params = state.params
    if pol is not None:
        params = pol.cast_to_compute(params)
        x = pol.cast_to_compute(x)
    preds, _ = model.apply(params, model_state, x,
                           train=False, rng=None)
    if pol is not None:
        preds = pol.cast_to_output(preds)
    return preds


def make_eval_step(model, loss,
                   metric_fns: Optional[Dict[str, Any]] = None,
                   mesh: Optional[Mesh] = None,
                   batch_spec: P = P("data"),
                   jit: bool = True,
                   policy: Any = None) -> Callable:
    """Build ``eval_step(state, (x, y)) -> metrics`` (train=False phase,
    the ``learning_phase: 0`` analogue of reference example.py:225).

    ``policy``: same spec as the train builders — params/inputs are cast to
    the compute dtype for the forward pass, predictions to the output dtype
    before loss/metrics.
    """
    loss_fn = loss_lib.get(loss)
    pol = prec_lib.policy(policy) if policy is not None else None

    def eval_step(state: TrainState, batch):
        x, y = batch
        preds = _eval_forward(model, pol, state, x)
        metrics = {"loss": loss_fn(preds, y)}
        metrics.update(_metric_dict(metric_fns, preds, y))
        return metrics

    if not jit:
        return eval_step
    # No pinned in_shardings: input shardings propagate, so the same
    # compiled fn serves mesh-sharded full batches and an unsharded
    # remainder batch (each sharding combination caches its own executable).
    del mesh, batch_spec
    return jax.jit(eval_step)


def make_masked_eval_step(model, loss,
                          metric_fns: Optional[Dict[str, Any]] = None,
                          policy: Any = None) -> Callable:
    """``eval_step(state, (x, y, w)) -> metrics`` with a per-example
    validity weight ``w`` ([batch] float, 1 real / 0 padding).

    This is what lets a MULTI-process ``evaluate`` keep its ragged tail
    batch: the tail is padded up to a shardable size, uploaded as a global
    array, and the padding is excluded from the means here — so N-process
    eval equals the 1-process means instead of dropping the tail
    (drop_remainder divergence).

    Loss and metrics are computed per example — the scalar fn applied to
    each example's own ``[1, ...]`` slice (same idiom as Sequential's
    sample-weight step) — then mask-weight-averaged.  Exact for every
    mean-of-per-example-terms loss/metric (all built-in losses, accuracy
    family); for batch-ratio metrics (precision/recall/f1) the tail
    batch's value becomes a mean of per-example ratios, which is the
    standard Keras per-batch-averaging caveat, not a new one.
    """
    loss_fn = loss_lib.get(loss)
    pol = prec_lib.policy(policy) if policy is not None else None

    def masked_eval_step(state: TrainState, batch):
        x, y, w = batch
        preds = _eval_forward(model, pol, state, x)

        def masked_mean(fn):
            per = jax.vmap(lambda pi, yi: fn(pi[None], yi[None]))(preds, y)
            wf = w.astype(per.dtype)
            return jnp.sum(per * wf) / jnp.maximum(jnp.sum(wf), 1.0)

        metrics = {"loss": masked_mean(loss_fn)}
        for name, fn in (metric_fns or {}).items():
            metrics[name] = masked_mean(metric_lib.get(fn))
        return metrics

    return jax.jit(masked_eval_step)


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("train", hbm_budget=16 << 20)
def _graph_entries():
    """Registry-scale train-step builds for the DT4xx pack: the single-
    dispatch and scanned multi-step builders traced abstractly (params
    via ``jax.eval_shape`` — nothing materializes) on the MNIST MLP.
    DT403 reads the donation straight off the traced ``pjit`` equation,
    so a refactor that breaks the donated-state chain (state no longer
    aliasable to an output) fails lint before it ships a 2x HBM step."""
    import jax
    from ..models import mnist_mlp
    from ..optim import adam

    model = mnist_mlp()
    optimizer = adam()
    step = make_train_step(model, "sparse_categorical_crossentropy",
                           optimizer)
    multi = make_multi_train_step(model,
                                  "sparse_categorical_crossentropy",
                                  optimizer, steps_per_call=4)
    state = jax.eval_shape(
        lambda k: init_train_state(model, optimizer, k, (784,)),
        jax.random.PRNGKey(0))
    f32, i32 = jnp.float32, jnp.int32
    batch = (jax.ShapeDtypeStruct((8, 784), f32),
             jax.ShapeDtypeStruct((8,), i32))
    mbatch = (jax.ShapeDtypeStruct((4, 8, 784), f32),
              jax.ShapeDtypeStruct((4, 8), i32))
    return [_graph_lib.Target("make_train_step", step, (state, batch)),
            _graph_lib.Target("make_multi_train_step", multi,
                              (state, mbatch))]
