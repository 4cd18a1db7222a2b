"""Sequential — the high-level ``compile``/``fit`` tier.

Capability parity with the reference's Keras path (reference
example2.py:148-200): ``Sequential`` container, ``add``, ``compile(loss,
optimizer, metrics)``, ``fit(x, y, epochs, batch_size, validation_data,
callbacks)``, ``evaluate``, ``predict`` — re-built on the framework's own
compiled steps (no session binding: where the reference must smuggle the
monitored session into Keras via ``K.set_session`` at example2.py:194-195,
here ``fit`` simply drives the same jitted step the low-level API uses).

Distribution: pass ``mesh=`` at compile time and the whole fit loop runs
data-parallel over the mesh's ``data`` axis with batches prefetched to
device already sharded — the high-level user never sees a collective.
"""
from __future__ import annotations

import collections
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.pipeline import Dataset, prefetch_to_device
from ..ops import layers as layer_lib
from ..ops import losses as loss_lib
from ..ops import metrics as metric_lib
from ..optim import optimizers as opt_lib
from ..train import step as step_lib
from ..train.session import TrainState
from .callbacks import Callback, History

log = logging.getLogger(__name__)

__all__ = ["Sequential"]


def _group_batches(it, spe: int, active: bool):
    """K-stack consecutive same-shaped batches for the multi-step path;
    a count-tail shorter than ``spe`` falls through as single batches.
    Runs on the prefetch producer thread."""
    if not active or spe <= 1:
        yield from it
        return
    buf = []
    for b in it:
        # A ragged batch (e.g. a drop_remainder=False tail) can't be
        # stacked with its neighbours; flush the buffer as single batches
        # instead of letting np.stack raise an opaque ValueError from
        # inside the producer thread.
        if buf and any(x.shape != y.shape for x, y in zip(b, buf[0])):
            yield from buf
            buf = []
        buf.append(b)
        if len(buf) == spe:
            yield tuple(np.stack(z) for z in zip(*buf))
            buf = []
    yield from buf


def _stream_shardings(mesh, base_ndim, want_multi: bool):
    """(per-batch sharding, sharding_fn) for prefetch_to_device — the fn
    routes [K, batch, ...] groups to P(None, 'data') and plain batches to
    P('data')."""
    if mesh is None:
        return None, None
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    if not want_multi:
        return sharding, None
    multi = NamedSharding(mesh, PartitionSpec(None, "data"))

    def fn(item):
        return multi if item[0].ndim > base_ndim else sharding

    return sharding, fn


def _sync_every(mesh) -> int:
    """Metric-pull cadence: XLA:CPU's collective rendezvous dies under a
    deep async queue, so the CPU mesh syncs every dispatch; TPU pulls
    rarely and keeps the queue async."""
    return (1 if jax.devices()[0].platform == "cpu" and mesh is not None
            else 50)


class _MeanAccumulator:
    """Exact epoch mean of step metrics with no per-batch host pulls:
    every dispatch's scalars (or the [K] vector of a multi-step group)
    are summed into a DEVICE-resident running total — a couple of tiny
    async dispatches per step — and the host pulls once, at epoch end.
    Replaces the round-3 sampled mean (every ~50th dispatch on TPU),
    which made History a sample rather than the Keras mean over all
    batches.  ``block()`` is the queue-depth bound: called at the sync
    cadence it waits for the running total (and therefore every chained
    step before it) without transferring anything."""

    def __init__(self):
        self.sums: Dict[str, Any] = {}
        self.counts: Dict[str, int] = {}

    def add(self, metrics: Dict[str, Any]) -> None:
        for k, v in metrics.items():
            s = jnp.sum(jnp.asarray(v), dtype=jnp.float32)
            prev = self.sums.get(k)
            self.sums[k] = s if prev is None else prev + s
            self.counts[k] = (self.counts.get(k, 0)
                              + int(np.prod(np.shape(v)) or 1))

    def block(self) -> None:
        for v in self.sums.values():
            jax.block_until_ready(v)
            break

    def means(self) -> Dict[str, float]:
        return {k: float(self.sums[k]) / self.counts[k] for k in self.sums}


class Sequential:
    def __init__(self, layers: Sequence[layer_lib.Layer] = (),
                 name: str = "sequential"):
        self.name = name
        self._layers: List[layer_lib.Layer] = list(layers)
        self._stack: Optional[layer_lib.Stack] = None
        self.state: Optional[TrainState] = None
        self.stop_training = False
        self._compiled = None
        self._compile_config = None   # JSON-able compile args (for save)
        self._in_shape = None         # recorded at build (for load)

    # -- construction ----------------------------------------------------
    def add(self, layer: layer_lib.Layer) -> None:
        """reference example2.py:151-156 ``model.add`` parity."""
        self._layers.append(layer)
        self._stack = None
        self._compiled = None

    @property
    def stack(self) -> layer_lib.Stack:
        if self._stack is None:
            self._stack = layer_lib.Stack(self._layers, name=self.name)
        return self._stack

    @property
    def layers(self) -> List[layer_lib.Layer]:
        """Ordered layer list (Keras ``model.layers`` parity); consumed by
        ``summary.model_graph_nodes`` for the TB graph event."""
        return self._layers

    # -- compile ---------------------------------------------------------
    def compile(self, loss, optimizer="adam",
                metrics: Sequence = (),
                mesh=None, params_spec=None, seed: int = 0,
                grad_clip_norm: Optional[float] = None,
                policy=None, steps_per_execution: int = 1,
                grad_accum_steps: int = 1) -> None:
        """reference example2.py:165 parity: strings or callables/objects.

        ``policy``: mixed-precision spec (e.g. ``"mixed_bfloat16"``) applied
        to both the train and eval steps — see train/precision.py.

        ``steps_per_execution``: run K optimizer updates per compiled
        dispatch (``lax.scan`` inside the step — train/step.py's
        make_multi_train_step).  Each dispatch pays one host→device
        launch; for small models that latency dominates (builder-measured
        2026-08-01: 5.6x on the MNIST MLP at K=64).
        Update semantics are IDENTICAL to K single steps — the scan body
        is the single-step function — and epoch-boundary callbacks are
        unaffected (this fit has no per-batch callbacks).  Epoch tails
        shorter than K fall back to the single-step path.  fit() with
        ``sample_weight``/``class_weight`` ignores it (those compile
        dedicated single-step programs) — a one-line log says so.

        ``grad_accum_steps``: split each batch into that many microbatches
        inside the step (train/step.py gradient accumulation): ONE
        optimizer update from the averaged gradients, peak activation
        memory down ~accum-fold — the HBM lever when the target batch
        doesn't fit.  Requires ``fit(batch_size=...)`` divisible by it;
        composes with ``steps_per_execution``.
        """
        loss_fn = loss_lib.get(loss)
        # with_lr_scale: LearningRateScheduler / ReduceLROnPlateau mutate a
        # device scalar in opt_state between steps — no recompilation.
        opt = opt_lib.with_lr_scale(opt_lib.get(optimizer))
        metric_fns = {}
        for m in metrics:
            fn = metric_lib.get(m)
            metric_fns[getattr(fn, "__name__", str(m))] = fn
        # ONE kwargs dict builds the default step AND any class-weighted
        # sibling fit() compiles later — they can never drift apart.
        if grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1; got {grad_accum_steps}")
        step_kwargs = dict(metric_fns=metric_fns, seed=seed, mesh=mesh,
                           params_spec=params_spec,
                           grad_clip_norm=grad_clip_norm, policy=policy,
                           accum_steps=int(grad_accum_steps))
        if steps_per_execution < 1:
            raise ValueError(
                f"steps_per_execution must be >= 1; got {steps_per_execution}")
        self._compiled = dict(
            loss=loss_fn, optimizer=opt, metric_fns=metric_fns, mesh=mesh,
            loss_name=loss if isinstance(loss, str) else None,
            step_kwargs=step_kwargs,
            weighted_steps={},
            steps_per_execution=int(steps_per_execution),
            multi_train_step=(step_lib.make_multi_train_step(
                self.stack, loss_fn, opt,
                steps_per_call=int(steps_per_execution), **step_kwargs)
                if steps_per_execution > 1 else None),
            train_step=step_lib.make_train_step(
                self.stack, loss_fn, opt, **step_kwargs),
            eval_step=step_lib.make_eval_step(
                self.stack, loss_fn, metric_fns=metric_fns, mesh=mesh,
                policy=policy),
        )
        # Record the compile call for model.save when every piece is a
        # JSON-able registry name (a mesh or callable can't round-trip).
        serializable = (isinstance(loss, str) and isinstance(optimizer, str)
                        and all(isinstance(m, str) for m in metrics)
                        and (policy is None or isinstance(policy, str))
                        and mesh is None and params_spec is None)
        self._compile_config = dict(
            loss=loss, optimizer=optimizer, metrics=list(metrics),
            seed=seed, grad_clip_norm=grad_clip_norm, policy=policy,
            steps_per_execution=int(steps_per_execution),
            grad_accum_steps=int(grad_accum_steps)
        ) if serializable else None
        # Recompile keeps the weights but resets the optimizer state for
        # the new optimizer (Keras recompile semantics) — also what lets
        # load_model restore weights before the user's own compile().
        if self.state is not None:
            self.state = self.state._replace(
                opt_state=opt.init(self.state.params))

    def _require_compiled(self) -> dict:
        if self._compiled is None:
            raise RuntimeError("call model.compile(...) before fit/evaluate")
        return self._compiled

    def build(self, in_shape: Tuple[int, ...], seed: int = 0) -> TrainState:
        """Initialize parameters for per-example feature shape ``in_shape``."""
        c = self._require_compiled()
        key = jax.random.PRNGKey(seed)
        self._in_shape = tuple(int(d) for d in in_shape)
        self.state = step_lib.init_train_state(self.stack, c["optimizer"],
                                               key, in_shape)
        if c["mesh"] is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            replicated = NamedSharding(c["mesh"], PartitionSpec())
            self.state = jax.device_put(self.state, replicated)
        return self.state

    # -- training --------------------------------------------------------
    def fit(self, x, y, epochs: int = 1, batch_size: int = 32,
            validation_data: Optional[Tuple] = None,
            validation_split: float = 0.0,
            callbacks: Sequence[Callback] = (),
            shuffle: bool = True, seed: int = 0,
            verbose: int = 1, augment=None,
            class_weight=None, sample_weight=None) -> History:
        """reference example2.py:197-200 parity (sync-DP underneath).

        ``augment``: per-batch transform from ``data.augment`` (host-side,
        overlapped with device compute via the prefetch queue); applied to
        training batches only, never to validation.

        ``validation_split``: fraction (0, 1) held out from the END of
        ``(x, y)`` before shuffling (Keras semantics) when no explicit
        ``validation_data`` is given.

        Epoch ``logs``/History values are the SAMPLED running mean of
        compiled-step metrics — every dispatch pulled at a sync point
        contributes (all K of a multi-step group).  Pulling every batch
        would stall the async dispatch queue, so on TPU the mean samples
        every ~50th dispatch; on the CPU mesh (sync_every=1) it is exactly
        Keras's epoch mean of batch metrics.  Exact full-data means are
        available via ``evaluate()``.

        ``class_weight``: {class_id: weight} applied to the TRAINING loss
        (Keras semantics; validation stays unweighted).  Requires a
        string classification loss (see ``ops.losses.class_weighted``);
        each distinct weighting compiles its own step once and is cached.

        ``sample_weight``: per-sample float array [n] weighting the
        TRAINING loss with Keras 2.0.8's exact normalization
        (``sum(loss_i * w_i) / count_nonzero(w)`` — the
        ``weighted_masked_objective`` rule the reference's ``model.fit``
        applies, reference example2.py:200).  The weights ride the batch
        tuple through ONE compiled weighted step (no recompile per call);
        shuffling/sharding stay aligned with (x, y).  Assumes a loss whose
        batch value is the mean of independent per-sample terms (true of
        every registry loss).  Divergences from Keras 2.0.8, by design:
        metrics stay unweighted, and combining with ``class_weight``
        raises instead of silently preferring ``sample_weight``.
        """
        c = self._require_compiled()
        train_step = c["train_step"]
        accum = c["step_kwargs"].get("accum_steps", 1)
        if accum > 1 and (sample_weight is not None
                          or class_weight is not None):
            # per-microbatch weighted means averaged equally are NOT the
            # full-batch weighted mean when the weight mass differs per
            # microbatch — refuse rather than silently bias gradients
            raise ValueError(
                "grad_accum_steps > 1 composes only with the unweighted "
                "loss path; drop sample_weight/class_weight or recompile "
                "with grad_accum_steps=1")
        if sample_weight is not None:
            if class_weight is not None:
                raise ValueError(
                    "pass either sample_weight or class_weight, not both "
                    "(Keras 2.0.8 silently ignored class_weight here; "
                    "refusing is safer)")
            sample_weight = np.asarray(sample_weight, np.float32)
            if sample_weight.shape != (int(np.shape(x)[0]),):
                raise ValueError(
                    f"sample_weight shape {sample_weight.shape} != "
                    f"({int(np.shape(x)[0])},) — one float per sample")
            train_step = self._sample_weighted_step(c)
        if class_weight is not None:
            if c["loss_name"] is None:
                raise ValueError("class_weight needs the model compiled "
                                 "with a loss NAME (string), not a callable")
            key_cw = tuple(sorted((int(k), float(v))
                                  for k, v in class_weight.items()))
            if key_cw not in c["weighted_steps"]:
                wfn = loss_lib.class_weighted(c["loss_name"], class_weight)
                c["weighted_steps"][key_cw] = step_lib.make_train_step(
                    self.stack, wfn, c["optimizer"], **c["step_kwargs"])
            train_step = c["weighted_steps"][key_cw]
        if validation_split and validation_data is None:
            if not 0.0 < validation_split < 1.0:
                raise ValueError(
                    f"validation_split must be in (0, 1); got "
                    f"{validation_split}")
            n = int(np.shape(x)[0])
            split = n - max(1, int(n * validation_split))
            x, y = np.asarray(x), np.asarray(y)
            validation_data = (x[split:], y[split:])
            x, y = x[:split], y[:split]
            if sample_weight is not None:   # held-out rows eval unweighted
                sample_weight = sample_weight[:split]
        if self.state is None:
            self.build(tuple(np.shape(x)[1:]), seed=seed)

        history = History()
        callbacks = list(callbacks) + [history]
        self.stop_training = False

        if c["mesh"] is not None:
            from ..parallel.mesh import round_batch_to_mesh
            rounded = round_batch_to_mesh(batch_size, c["mesh"])
            if rounded != batch_size:
                log.info("batch_size %d -> %d (divisible by mesh data shards)",
                         batch_size, rounded)
                batch_size = rounded
        if accum > 1 and batch_size % accum:
            # validated AFTER mesh rounding — the rounded size is what the
            # step actually splits into microbatches
            raise ValueError(
                f"batch_size {batch_size} is not divisible by "
                f"grad_accum_steps {accum}")
        arrays = [np.asarray(x), np.asarray(y)]
        if sample_weight is not None:
            arrays.append(sample_weight)   # shuffles/shards with (x, y)
        dataset = Dataset(arrays, batch_size,
                          shuffle=shuffle, seed=seed, transform=augment)
        sharding = None
        if c["mesh"] is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(c["mesh"], PartitionSpec("data"))

        # steps_per_execution: scan K updates into one dispatch.  Only the
        # default step has a multi sibling — the weighted paths compile
        # dedicated single-step programs.
        spe = c["steps_per_execution"]
        multi_step = (c["multi_train_step"]
                      if train_step is c["train_step"] else None)
        if spe > 1 and multi_step is None:
            log.info("steps_per_execution=%d ignored for this fit "
                     "(sample_weight/class_weight use their own compiled "
                     "step)", spe)
        base_ndim = arrays[0].ndim   # group leaves carry one extra dim
        _, batch_sharding = _stream_shardings(
            c["mesh"], base_ndim, multi_step is not None)

        for cb in callbacks:
            cb.on_train_begin(self)
        for epoch in range(epochs):
            if self.stop_training:
                break
            for cb in callbacks:
                cb.on_epoch_begin(self, epoch)
            # Exact epoch mean, accumulated on device: every dispatch
            # contributes (a float() per batch would stall the async
            # dispatch queue, so the host pulls once at epoch end); the
            # sync cadence only BLOCKS — bounding queue depth, and on the
            # CPU mesh guarding the collective rendezvous.
            sync_every = _sync_every(c["mesh"])
            acc = _MeanAccumulator()
            last_metrics: Dict[str, Any] = {}
            dispatches = 0
            groups = _group_batches(iter(dataset), spe,
                                    multi_step is not None)
            for batch in prefetch_to_device(groups, sharding=sharding,
                                            sharding_fn=batch_sharding):
                if batch[0].ndim > base_ndim:       # [K, batch, ...] group
                    self.state, last_metrics = multi_step(self.state, batch)
                else:
                    self.state, last_metrics = train_step(self.state, batch)
                dispatches += 1
                acc.add(last_metrics)
                if dispatches % sync_every == 0:
                    acc.block()
            logs = acc.means()
            if validation_data is not None:
                val = self.evaluate(validation_data[0], validation_data[1],
                                    batch_size=batch_size, verbose=0)
                logs.update({f"val_{k}": v for k, v in val.items()})
            if verbose:
                parts = ", ".join(f"{k}={v:.4f}" for k, v in logs.items())
                print(f"Epoch {epoch + 1}/{epochs}: {parts}", flush=True)
            for cb in callbacks:
                cb.on_epoch_end(self, epoch, logs)
        for cb in callbacks:
            cb.on_train_end(self)
        return history

    def fit_stream(self, batches, steps_per_epoch: int, epochs: int = 1,
                   callbacks: Sequence[Callback] = (),
                   validation_data: Optional[Tuple] = None,
                   verbose: int = 1) -> History:
        """Train from streamed batches — the ``fit_generator``-shaped
        entry for sources that don't fit in memory.

        ``batches``: an iterator of ``(x, y)`` numpy batch tuples, or a
        callable ``epoch -> iterator`` (pass ``data.tfrecord_batches``
        with its ``epoch=`` argument for the per-epoch reshuffle
        contract).  All batches must share one shape, divisible by the
        mesh's data shards and by ``grad_accum_steps`` (validated on the
        first batch — the stream fixes the size, so nothing is rounded).
        Each epoch draws ``steps_per_epoch`` batches; a source that ends
        sooner ends the epoch — and training — early, with no ghost
        epoch.  ``compile(steps_per_execution=K)`` groups dispatches
        exactly as in ``fit``; sample/class weights are not supported on
        this path.
        """
        c = self._require_compiled()
        train_step = c["train_step"]
        spe = c["steps_per_execution"]
        multi_step = c["multi_train_step"]

        def epoch_iter(epoch):
            it = batches(epoch) if callable(batches) else batches
            for _ in range(steps_per_epoch):
                try:
                    yield next(it)
                except StopIteration:
                    return

        # Build + validate from the first batch: the stream fixes the
        # batch size, so incompatibilities must fail HERE with the
        # parameter's name, not at trace time inside the step.
        first_it = epoch_iter(0)
        try:
            first = next(first_it)
        except StopIteration:
            raise ValueError("batch stream is empty")
        bs = int(np.shape(first[0])[0])
        accum = c["step_kwargs"].get("accum_steps", 1)
        if accum > 1 and bs % accum:
            raise ValueError(f"streamed batch size {bs} is not divisible "
                             f"by grad_accum_steps {accum}")
        if c["mesh"] is not None:
            shards = c["mesh"].shape.get("data", 1)
            if bs % shards:
                raise ValueError(f"streamed batch size {bs} is not "
                                 f"divisible by the mesh's {shards} data "
                                 f"shards")
        if self.state is None:
            self.build(tuple(np.shape(first[0])[1:]))
        base_ndim = np.asarray(first[0]).ndim
        sharding, batch_sharding = _stream_shardings(
            c["mesh"], base_ndim, multi_step is not None)

        import itertools
        history = History()
        callbacks = list(callbacks) + [history]
        self.stop_training = False
        exhausted = False
        for cb in callbacks:
            cb.on_train_begin(self)
        for epoch in range(epochs):
            if self.stop_training or exhausted:
                break
            it = (itertools.chain([first], first_it) if epoch == 0
                  else epoch_iter(epoch))
            sync_every = _sync_every(c["mesh"])
            acc = _MeanAccumulator()
            last_metrics: Dict[str, Any] = {}
            drawn = 0
            dispatches = 0
            epoch_began = False
            groups = _group_batches(it, spe, multi_step is not None)
            for batch in prefetch_to_device(groups, sharding=sharding,
                                            sharding_fn=batch_sharding):
                if not epoch_began:
                    # after the first batch exists: an exactly-exhausted
                    # stream must not produce a ghost zero-step epoch
                    epoch_began = True
                    for cb in callbacks:
                        cb.on_epoch_begin(self, epoch)
                if batch[0].ndim > base_ndim:
                    self.state, last_metrics = multi_step(self.state, batch)
                    drawn += batch[0].shape[0]
                else:
                    self.state, last_metrics = train_step(self.state, batch)
                    drawn += 1
                dispatches += 1
                acc.add(last_metrics)
                if dispatches % sync_every == 0:
                    acc.block()
            if not epoch_began:
                break                              # stream already dry
            exhausted = drawn < steps_per_epoch
            logs = acc.means()
            if validation_data is not None:
                val = self.evaluate(validation_data[0], validation_data[1],
                                    verbose=0)
                logs.update({f"val_{k}": v for k, v in val.items()})
            if verbose:
                parts = ", ".join(f"{k}={v:.4f}" for k, v in logs.items())
                print(f"Epoch {epoch + 1}/{epochs}: {parts}", flush=True)
            for cb in callbacks:
                cb.on_epoch_end(self, epoch, logs)
        for cb in callbacks:
            cb.on_train_end(self)
        return history

    def _sample_weighted_step(self, c) -> Any:
        """Compiled ``step(state, (x, y, w))`` applying Keras 2.0.8's
        sample-weight rule; built once per compile and cached (the weights
        are batch data, so every fit(sample_weight=...) reuses it)."""
        if "sample_step" in c:
            return c["sample_step"]
        loss_value_fn = c["loss"]
        metric_fns = c["metric_fns"]
        stack = self.stack

        def loss_fn(params, model_state, batch, rng, train):
            xb, yb, wb = batch
            preds, new_ms = stack.apply(params, model_state, xb,
                                        train=train, rng=rng)
            # per-sample losses: the scalar loss of each sample's own
            # [1, ...] slice (exact for any mean-of-per-sample-terms loss)
            per = jax.vmap(
                lambda pi, yi: loss_value_fn(pi[None], yi[None]))(preds, yb)
            w = wb.astype(per.dtype)
            nonzero = jnp.sum((w != 0).astype(per.dtype))
            loss = jnp.sum(per * w) / jnp.maximum(nonzero, 1.0)
            metrics = {name: metric_lib.get(fn)(preds, yb)
                       for name, fn in metric_fns.items()}
            return loss, (metrics, new_ms)

        kw = c["step_kwargs"]
        mesh, state_sh, batch_sh = kw["mesh"], None, None
        if mesh is not None:
            from jax.sharding import PartitionSpec
            state_sh, (bx, by) = step_lib._state_batch_shardings(
                mesh, kw["params_spec"], PartitionSpec("data"))
            batch_sh = (bx, by, by)
        c["sample_step"] = step_lib.make_custom_train_step(
            loss_fn, c["optimizer"], seed=kw["seed"], mesh=mesh,
            state_shardings=state_sh, batch_shardings=batch_sh,
            grad_clip_norm=kw["grad_clip_norm"], policy=kw["policy"])
        return c["sample_step"]

    def _masked_eval_step(self, c) -> Any:
        """Compiled ``eval_step(state, (x, y, w))`` excluding mask-0
        examples from the means (multi-process ragged-tail path); built
        lazily and cached per compile like the sample-weight step."""
        if "masked_eval_step" not in c:
            c["masked_eval_step"] = step_lib.make_masked_eval_step(
                self.stack, c["loss"], metric_fns=c["metric_fns"],
                policy=c["step_kwargs"]["policy"])
        return c["masked_eval_step"]

    # -- single-batch steps (Keras train/test/predict_on_batch parity) ---
    def _mesh_batch(self, x, y, train: bool):
        """Shard an on-batch pair for a mesh-compiled model.  The train
        step pins ``P('data')`` in_shardings, so its batch MUST divide the
        data shards; the eval step propagates shardings and accepts either."""
        c = self._require_compiled()
        batch = (np.asarray(x), np.asarray(y))
        mesh = c["mesh"]
        if mesh is None:
            return batch
        shards = mesh.shape["data"]
        if batch[0].shape[0] % shards == 0:
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.device_put(
                batch, NamedSharding(mesh, PartitionSpec("data")))
        if train:
            raise ValueError(
                f"train_on_batch with a mesh-compiled model needs the batch "
                f"({batch[0].shape[0]}) divisible by the mesh's data shards "
                f"({shards})")
        return batch

    def train_on_batch(self, x, y) -> Dict[str, float]:
        """One optimizer step on one batch -> metric dict."""
        c = self._require_compiled()
        if self.state is None:
            self.build(tuple(np.shape(x)[1:]))
        self.state, metrics = c["train_step"](
            self.state, self._mesh_batch(x, y, train=True))
        return {k: float(v) for k, v in metrics.items()}

    def test_on_batch(self, x, y) -> Dict[str, float]:
        """Loss/metrics on one batch, no state change."""
        c = self._require_compiled()
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        metrics = c["eval_step"](self.state,
                                 self._mesh_batch(x, y, train=False))
        return {k: float(v) for k, v in metrics.items()}

    def predict_on_batch(self, x) -> np.ndarray:
        return self.predict(np.asarray(x), batch_size=int(np.shape(x)[0]))

    def evaluate(self, x, y, batch_size: int = 32,
                 verbose: int = 1) -> Dict[str, float]:
        self._require_compiled()
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        dataset = Dataset([np.asarray(x), np.asarray(y)], batch_size,
                          shuffle=False, drop_remainder=False)
        return self._evaluate_batches(iter(dataset), verbose)

    def evaluate_stream(self, batches, steps: Optional[int] = None,
                        verbose: int = 1) -> Dict[str, float]:
        """``evaluate`` over streamed ``(x, y)`` batches (an iterator, e.g.
        ``data.tfrecord_batches``): batch-size-weighted metric means over
        up to ``steps`` batches (all of them when ``steps`` is None; the
        limit is an ``islice``, so no extra batch is drawn from a shared
        iterator).  Same pull discipline and multi-host upload path as
        ``evaluate``/``fit_stream``."""
        import itertools
        it = batches if steps is None else itertools.islice(batches, steps)
        return self._evaluate_batches(it, verbose)

    def _evaluate_batches(self, it, verbose: int) -> Dict[str, float]:
        """ONE eval core: batch-size-weighted metric means over an
        iterator of (x, y) batches.  Pulls are deferred (a float() per
        batch would sync the async dispatch queue once per dispatch, which
        for small models costs more than the eval compute) but
        BOUNDED by the same ``_sync_every`` cadence the fit paths use, so
        neither the dispatch queue nor the pending list grows with the
        stream; on the CPU mesh the cadence is 1, which is also the
        collective-rendezvous guard.  Uploads route through
        ``prefetch_to_device`` — overlap plus the multi-host per-process
        assembly.  A batch not divisible by the mesh's data shards (the
        ragged eval tail) is uploaded unsharded on one host in a
        single-process run (exact); in a MULTI-process run it is PADDED
        up to the next shardable size with a per-example validity mask
        and fed through a masked eval step that excludes the padding from
        the means — so N-process ``evaluate`` equals the 1-process means
        instead of silently applying drop_remainder semantics."""
        c = self._require_compiled()
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        sharding, _ = _stream_shardings(c["mesh"], 0, want_multi=False)
        shards = (sharding.mesh.shape["data"] if sharding is not None
                  else 1)
        multi_process = jax.process_count() > 1
        # Each process uploads its LOCAL batch; the assembled global array
        # needs the local leading dim divisible by the process's share of
        # the data axis (equal local tails across processes, same contract
        # as the divisible-batch path).
        local_shards = max(1, shards // jax.process_count())
        # Host-side real-count carry for padded tails: prefetch preserves
        # FIFO order, so the consumer pops the global real count matching
        # each 3-tuple batch (device-summing the mask would sync the
        # async dispatch queue).  Equal local tails across processes is
        # the same contract the divisible-batch path already assumes.
        tail_real = collections.deque()

        def keep(it):
            for b in it:
                if (sharding is not None and multi_process
                        and b[0].shape[0] % shards):
                    bs = b[0].shape[0]
                    padded = -(-bs // local_shards) * local_shards
                    pad = padded - bs
                    w = np.concatenate([np.ones(bs, np.float32),
                                        np.zeros(pad, np.float32)])
                    tail_real.append(bs * jax.process_count())
                    # pad value is arbitrary (masked out); repeating the
                    # last example keeps dtypes/shapes without branches
                    yield tuple(np.concatenate(
                        [a, np.repeat(a[-1:], pad, axis=0)]) for a in b
                    ) + (w,)
                    continue
                yield b

        it = keep(it)

        def batch_sharding(item):
            if sharding is None:
                return None
            if len(item) == 3 or item[0].shape[0] % shards == 0:
                return sharding
            return None

        sync_every = _sync_every(c["mesh"])
        pending = []
        totals: Dict[str, float] = {}
        n = 0

        def pull_all():
            nonlocal n
            for bs, metrics in pending:
                for k, v in metrics.items():
                    totals[k] = totals.get(k, 0.0) + float(v) * bs
                n += bs
            pending.clear()

        masked_step = None
        for batch in prefetch_to_device(it, sharding=None,
                                        sharding_fn=batch_sharding):
            if len(batch) == 3:
                if masked_step is None:
                    masked_step = self._masked_eval_step(c)
                pending.append((tail_real.popleft(),
                                masked_step(self.state, batch)))
            else:
                pending.append((batch[0].shape[0],
                                c["eval_step"](self.state, batch)))
            if len(pending) >= sync_every:
                pull_all()
        pull_all()
        out = {k: v / max(n, 1) for k, v in totals.items()}
        if verbose:
            parts = ", ".join(f"{k}={v:.4f}" for k, v in out.items())
            print(f"evaluate: {parts}", flush=True)
        return out

    # -- weights IO (Keras save_weights/load_weights parity) -------------
    def save_weights(self, ckpt_dir: str) -> str:
        """Write {params, model_state} (not optimizer state) as a
        step-stamped checkpoint under ``ckpt_dir``."""
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        from ..train import checkpoint as ck
        return ck.save(ckpt_dir, int(self.state.step),
                       {"params": self.state.params,
                        "model_state": self.state.model_state})

    def load_weights(self, ckpt_dir: str) -> None:
        """Restore the latest weights checkpoint from ``ckpt_dir`` into the
        (built) model — optimizer state is untouched."""
        if self.state is None:
            raise RuntimeError("build the model (compile + build/fit) "
                               "before load_weights")
        from ..train import checkpoint as ck
        latest = ck.latest_checkpoint(ckpt_dir)
        if latest is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
        restored = ck.restore({"params": self.state.params,
                               "model_state": self.state.model_state},
                              latest)
        self.state = self.state._replace(params=restored["params"],
                                         model_state=restored["model_state"])

    def predict(self, x, batch_size: int = 256) -> np.ndarray:
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        apply_fn = jax.jit(
            lambda params, model_state, xb: self.stack.apply(
                params, model_state, xb, train=False, rng=None)[0])
        outs = []
        x = np.asarray(x)
        for lo in range(0, x.shape[0], batch_size):
            # device arrays, un-pulled: dispatch the whole stream async,
            # convert once at the end (one sync, not one per batch)
            outs.append(apply_fn(self.state.params, self.state.model_state,
                                 x[lo:lo + batch_size]))
        return np.concatenate([np.asarray(o) for o in outs], axis=0)

    # -- flat weights access (Keras get_weights/set_weights analogue) ----
    def _layer_leaves(self):
        """(layer_key, leaves, treedef) per param-owning layer, in LAYER
        order (dict-key sorting would put 'dense_10' before 'dense_2')."""
        out = []
        for key in self.stack.keys:
            sub = self.state.params.get(key)
            if sub is not None:
                leaves, treedef = jax.tree_util.tree_flatten(sub)
                out.append((key, leaves, treedef))
        return out

    def get_weights(self) -> List[np.ndarray]:
        """Parameters as a flat list of host arrays: layers in model
        order, leaves in this framework's (sorted-key) order within each
        layer — ``set_weights`` is the exact inverse."""
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        return [np.asarray(w) for _, leaves, _ in self._layer_leaves()
                for w in leaves]

    def set_weights(self, weights: Sequence[np.ndarray]) -> None:
        """Inverse of ``get_weights``: same order, shapes must match."""
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        per_layer = self._layer_leaves()
        total = sum(len(leaves) for _, leaves, _ in per_layer)
        if len(weights) != total:
            raise ValueError(f"expected {total} arrays, got {len(weights)}")
        params = dict(self.state.params)
        i = 0
        for key, leaves, treedef in per_layer:
            new = []
            for cur in leaves:
                w = np.asarray(weights[i])
                i += 1
                if w.shape != cur.shape:
                    raise ValueError(f"shape mismatch at {key!r}: expected "
                                     f"{cur.shape}, got {w.shape}")
                new.append(jnp.asarray(w, cur.dtype))
            params[key] = jax.tree_util.tree_unflatten(treedef, new)
        self.state = self.state._replace(params=params)

    # -- full-model IO (Keras model.save / load_model / to_json parity) --
    def save(self, path: str) -> str:
        """Architecture + weights under ``path`` (see models.saving)."""
        from . import saving
        return saving.save_model(self, path)

    def to_json(self, **dump_kwargs) -> str:
        from . import saving
        import json
        return json.dumps(saving.model_to_config(self), **dump_kwargs)

    @classmethod
    def from_json(cls, text: str) -> "Sequential":
        from . import saving
        import json
        return saving.model_from_config(json.loads(text))

    # -- learning-rate control (Keras optimizer.lr mutation analogue) ----
    @property
    def lr_scale(self) -> float:
        """Multiplier on the compiled optimizer's learning rate."""
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        return opt_lib.get_lr_scale(self.state.opt_state)

    @lr_scale.setter
    def lr_scale(self, value: float) -> None:
        if self.state is None:
            raise RuntimeError("model has no state; call fit or build first")
        self.state = self.state._replace(
            opt_state=opt_lib.set_lr_scale(self.state.opt_state, value))

    # -- introspection ---------------------------------------------------
    def summary(self) -> str:
        lines = [f"Model: {self.name}"]
        total = 0
        if self.state is not None:
            for name, p in self.state.params.items():
                n = sum(int(np.prod(leaf.shape))
                        for leaf in jax.tree_util.tree_leaves(p))
                total += n
                lines.append(f"  {name}: {n:,} params")
            lines.append(f"Total params: {total:,}")
        else:
            lines += [f"  {layer!r}" for layer in self._layers]
        text = "\n".join(lines)
        print(text, flush=True)
        return text
