"""Explicit ``shard_map`` + ``psum`` sync data parallelism.

The north-star translation of the reference's PS architecture
(BASELINE.json; SURVEY.md §2c): each replica computes gradients on its batch
shard and the mean is taken with ONE ``lax.pmean`` all-reduce over the ICI
``data`` axis — replacing the per-step variable pull / async gradient push
gRPC round-trips of `replica_device_setter` training (reference
example.py:133-141, §3.1 hot loop).

Two spellings of the same computation exist in this framework:
  * ``train.make_train_step(mesh=...)`` — the pjit/global-view spelling:
    the loss is a global-batch mean and XLA's partitioner inserts the
    all-reduce implied by the shardings (preferred; composes with tp/sp/pp);
  * this module — the explicit per-replica spelling with a visible
    ``pmean``, mirroring how pmap-era training loops were written and
    serving as the numerical cross-check of the pjit path
    (tests/test_parallel.py::test_psum_spelling_matches_pjit_step).

Per-replica RNG: the dropout key is folded with BOTH the global step and the
replica index (SURVEY.md §7 "Dropout determinism"), so replicas draw
independent masks while remaining resume-deterministic.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops import losses as loss_lib
from ..ops import metrics as metric_lib
from ..optim import optimizers as opt_lib

__all__ = ["make_psum_train_step"]


def make_psum_train_step(model, loss, optimizer: opt_lib.Optimizer,
                         mesh: Mesh, axis: str = "data",
                         metric_fns: Optional[Dict[str, Any]] = None,
                         seed: int = 0,
                         per_replica_rng: bool = True) -> Callable:
    """Build ``step(state, (x, y)) -> (new_state, metrics)``.

    ``state`` is replicated; the batch is sharded over ``axis``.  Inside the
    ``shard_map`` every replica runs forward/backward on its shard, then
    ``lax.pmean`` reduces gradients and metrics — parameters stay bit-
    identical across replicas without a parameter server.

    ``per_replica_rng=False`` gives every replica the same dropout key —
    only useful for numerical parity tests against a single-device run.
    """
    from ..train.session import TrainState

    loss_value_fn = loss_lib.get(loss)
    base_key = jax.random.PRNGKey(seed)

    def replica_step(state: TrainState, batch):
        x, y = batch
        rng = jax.random.fold_in(base_key, state.step)
        if per_replica_rng:
            rng = jax.random.fold_in(rng, lax.axis_index(axis))

        def compute(params):
            preds, new_model_state = model.apply(params, state.model_state,
                                                 x, train=True, rng=rng)
            metrics = {name: metric_lib.get(fn)(preds, y)
                       for name, fn in (metric_fns or {}).items()}
            return loss_value_fn(preds, y), (metrics, new_model_state)

        (loss_value, (metrics, new_model_state)), grads = jax.value_and_grad(
            compute, has_aux=True)(state.params)

        # THE all-reduce: grad/metric mean over the data axis (equal shard
        # sizes => identical to the global-batch mean of the pjit spelling).
        grads = lax.pmean(grads, axis)
        metrics = lax.pmean({"loss": loss_value, **metrics}, axis)

        updates, new_opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
        new_params = opt_lib.apply_updates(state.params, updates)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt_state,
                          model_state=new_model_state), metrics

    sharded = shard_map(
        replica_step, mesh=mesh,
        in_specs=(P(), (P(axis), P(axis))),
        out_specs=(P(), P()),
        axis_names=frozenset({axis}),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=0)


# --------------------------------------------------- dtlint graph tier

from ..analysis import graph as _graph_lib  # noqa: E402  (registration)


@_graph_lib.trace_entry("parallel.data_parallel", hbm_budget=8 << 20)
def _graph_entries():
    """The psum-spelled data-parallel step on a tiny MLP, seeded with
    the specs callers actually use (state replicated, batch sharded
    over ``data``), so the DT5xx ledger prices THE all-reduce: one
    grad/metric pmean over the data axis per step."""
    import jax
    import jax.numpy as jnp

    from .. import ops
    from ..optim import adam
    from ..train import init_train_state
    from .mesh import make_mesh

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"data": n})
    model = ops.serial(ops.Dense(32, "relu"), ops.Dense(8, "sigmoid"))
    optimizer = adam()
    step = make_psum_train_step(model, "mse", optimizer, mesh)
    state = jax.eval_shape(
        lambda k: init_train_state(model, optimizer, k, (64,)),
        jax.random.PRNGKey(0))
    batch = (jax.ShapeDtypeStruct((n * 4, 64), jnp.float32),
             jax.ShapeDtypeStruct((n * 4, 8), jnp.float32))
    return _graph_lib.Target(
        "make_psum_train_step", step, (state, batch),
        in_specs=(P(), (P("data"), P("data"))), mesh=mesh)
