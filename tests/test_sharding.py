"""Partition-rule machinery tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hlo_collectives import activation_allreduces, collectives

from distributed_tensorflow_tpu.parallel import make_mesh
from distributed_tensorflow_tpu.parallel.sharding import (PartitionRules,
                                                          prune_spec,
                                                          shard_pytree,
                                                          tree_paths)


def test_tree_paths():
    tree = {"a": {"b": jnp.zeros(2), "c": jnp.zeros(3)}, "d": jnp.zeros(4)}
    assert tree_paths(tree) == ["a/b", "a/c", "d"]


def test_first_match_wins():
    rules = PartitionRules([
        (r"special/kernel", P("tensor")),
        (r"kernel", P("data")),
    ])
    assert rules.spec_for("layer/special/kernel") == P("tensor")
    assert rules.spec_for("layer/other/kernel") == P("data")
    assert rules.spec_for("layer/bias") == P()


def test_prune_spec_degrades_gracefully():
    mesh = make_mesh({"data": 8})
    assert prune_spec(P("tensor", None), mesh) == P(None, None)
    assert prune_spec(P("data", "tensor"), mesh) == P("data", None)
    assert prune_spec(P(("data", "fsdp"), None), mesh) == P(("data",), None)


def test_shard_pytree_places_leaves():
    mesh = make_mesh({"data": 4, "tensor": 2})
    params = {"dense": {"kernel": jnp.ones((8, 16)), "bias": jnp.ones((16,))}}
    rules = PartitionRules([(r"kernel", P(None, "tensor"))])
    out = shard_pytree(params, mesh, rules)
    assert "tensor" in str(out["dense"]["kernel"].sharding.spec)
    # bias replicated across all 8 devices
    assert len(out["dense"]["bias"].sharding.device_set) == 8
    shapes = {s.data.shape for s in out["dense"]["kernel"].addressable_shards}
    assert shapes == {(8, 8)}



def test_fsdp_shards_params_and_optimizer_moments():
    """ZeRO requirement: Adam m/v shard WITH their params over fsdp; the
    sharded run matches the replicated run numerically."""
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train
    from distributed_tensorflow_tpu.models.gpt import gpt_tiny
    from distributed_tensorflow_tpu.parallel import make_mesh

    mesh = make_mesh({"fsdp": 8})
    model = gpt_tiny(dropout_rate=0.0)
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.adam(1e-3)
    rules = model.partition_rules(fsdp=True)

    state = train.TrainState.create(
        jax.tree.map(jnp.copy, params), opt.init(params))
    state = train.shard_train_state(state, mesh, rules)
    w_in = state.params["decoder"]["ffn"]["w_in"]["kernel"]
    assert "fsdp" in str(w_in.sharding.spec)
    m_in = state.opt_state.inner["m"]["decoder"]["ffn"]["w_in"]["kernel"]
    assert m_in.sharding == w_in.sharding  # moments shard with params

    step = train.make_custom_train_step(model.lm_loss_fn(), opt)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 512)
    state, m = step(state, {"input_ids": ids})
    assert np.isfinite(float(m["loss"]))
    # placements survive the step
    assert "fsdp" in str(
        state.params["decoder"]["ffn"]["w_in"]["kernel"].sharding.spec)
    assert state.opt_state.inner["m"]["decoder"]["ffn"]["w_in"][
        "kernel"].sharding == state.params["decoder"]["ffn"]["w_in"][
        "kernel"].sharding

    ref_state = train.TrainState.create(
        jax.tree.map(jnp.copy, params), opt.init(params))
    ref_state, ref_m = step(ref_state, {"input_ids": ids})
    np.testing.assert_allclose(float(m["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    # atol 5e-5: sharded reductions reorder float sums vs the replicated run
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=5e-5),
        jax.device_get(state.params), jax.device_get(ref_state.params))


def test_shard_train_state_momentum_and_sgd():
    """momentum's mu (params-shaped inner) shards WITH params; sgd's empty
    inner passes through; bare-array params don't crash."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train
    from distributed_tensorflow_tpu.parallel import (PartitionRules,
                                                     make_mesh)

    mesh = make_mesh({"fsdp": 8})
    params = {"dense": {"kernel": jnp.ones((16, 8))}}
    rules = PartitionRules([(r"kernel", P("fsdp", None))])

    opt = optim.momentum(0.1)
    state = train.shard_train_state(
        train.TrainState.create(params, opt.init(params)), mesh, rules)
    k_sh = state.params["dense"]["kernel"].sharding
    assert "fsdp" in str(k_sh.spec)
    assert state.opt_state.inner["dense"]["kernel"].sharding == k_sh

    opt2 = optim.sgd(0.1)
    s2 = train.shard_train_state(
        train.TrainState.create(params, opt2.init(params)), mesh, rules)
    assert s2.opt_state.inner == ()


# ---------------------------------------------------------------- fsdp is a
# batch axis for activations (``constrain_batch``), a storage axis for params

_TINY = dict(vocab_size=256, hidden_size=128, num_layers=2, num_heads=4,
             intermediate_size=512, max_position=16, dropout_rate=0.0,
             remat=True)
_BATCH, _SEQ = 8, 16


def _tiny_fsdp_step(axes, with_mesh=True):
    """The benchmark's train cell at toy widths: state placed by
    ``shard_train_state``, batch placed ``P(("data", "fsdp"))``, the plain
    ``make_custom_train_step`` step."""
    import math
    from jax.sharding import NamedSharding
    from distributed_tensorflow_tpu import optim, train
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    mesh = make_mesh(axes, devices=jax.devices()[:math.prod(axes.values())])
    model = GPT(GPTConfig(**_TINY), mesh=mesh if with_mesh else None)
    opt = optim.adamw(1e-3)
    params = model.init(jax.random.PRNGKey(0))
    state = train.shard_train_state(
        train.TrainState.create(params, opt.init(params)), mesh,
        model.partition_rules(fsdp=True))
    step = train.make_custom_train_step(model.lm_loss_fn(), opt,
                                        grad_clip_norm=1.0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (_BATCH, _SEQ + 1), 0,
                             _TINY["vocab_size"])
    batch = {"input_ids": jax.device_put(ids, NamedSharding(
        mesh, prune_spec(P(("data", "fsdp")), mesh)))}
    return model, mesh, step, state, batch


@pytest.mark.parametrize("axes", [
    {"fsdp": 4}, {"data": 2, "fsdp": 2}, {"data": 2, "fsdp": 2, "tensor": 2},
], ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
def test_fsdp_step_gathers_weights_and_keeps_the_batch_apart(axes):
    """The compiled fsdp train step is ZeRO-3: weights all-gathered at use,
    no all-reduce of a whole-batch activation, attention scores at the
    LOCAL batch.  (Under ``tensor`` Megatron's row-parallel all-reduce
    stays, at the local batch.)"""
    import re
    _, mesh, step, state, batch = _tiny_fsdp_step(axes)
    text = step.lower(state, batch).compile().as_text()
    assert not activation_allreduces(text, _BATCH, _SEQ)
    assert any(op == "all-gather" and any(len(s) >= 2 for s in shapes)
               for op, shapes in collectives(text)), "no weight all-gather"
    local = _BATCH // (mesh.shape.get("data", 1) * mesh.shape["fsdp"])
    heads = _TINY["num_heads"] // mesh.shape.get("tensor", 1)
    scores = lambda b: re.search(rf"\[{b},{heads},{_SEQ},{_SEQ}\]", text)
    assert scores(local), "no attention-score shape at the local batch"
    assert not scores(_BATCH), "attention scores over the whole batch"


def test_unpinned_fsdp_step_is_what_the_pin_repairs():
    """The same step from a model that knows no mesh (so nothing is pinned)
    replicates the batch and all-reduces whole-batch activations: the
    assertions above do discriminate."""
    _, _, step, state, batch = _tiny_fsdp_step({"fsdp": 4}, with_mesh=False)
    text = step.lower(state, batch).compile().as_text()
    assert activation_allreduces(text, _BATCH, _SEQ)


def test_fsdp4_step_matches_single_device():
    """Only where operands live changes: loss and updated parameters of one
    step on the fsdp 4 mesh equal the single-device step's."""
    from distributed_tensorflow_tpu import optim, train
    model, _, step, state, batch = _tiny_fsdp_step({"fsdp": 4})
    ids = jax.device_get(batch["input_ids"])
    params = jax.device_get(state.params)
    opt = optim.adamw(1e-3)
    ref_state = train.TrainState.create(params, opt.init(params))
    new, m = step(state, batch)
    ref_new, ref_m = step(ref_state, {"input_ids": ids})
    np.testing.assert_allclose(float(m["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    # atol 5e-5: sharded reductions reorder float sums
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=5e-5),
        jax.device_get(new.params), jax.device_get(ref_new.params))
    assert "fsdp" in str(
        new.params["decoder"]["ffn"]["w_in"]["kernel"].sharding.spec)


def test_batch_the_mesh_does_not_divide_runs_and_matches():
    """The benchmark's correctness check calls ``lm_loss_fn`` with 2
    sequences on the four-chip mesh: the pin leaves such a batch to
    propagation, the program runs and matches the single-device loss."""
    model, _, _, state, batch = _tiny_fsdp_step({"fsdp": 4})
    ids = jax.device_get(batch["input_ids"])[:2]
    loss_fn = model.lm_loss_fn()
    loss = lambda p: loss_fn(p, (), {"input_ids": ids}, None, False)[0]
    sharded = float(jax.jit(loss)(state.params))
    single = float(jax.jit(loss)(jax.device_get(state.params)))
    np.testing.assert_allclose(sharded, single, rtol=1e-5)


def test_constrain_batch_follows_mesh_and_shape():
    from jax.sharding import NamedSharding
    from distributed_tensorflow_tpu.parallel.sharding import constrain_batch
    x = jnp.zeros((8, 16, 4))

    def placed(mesh, spec, **kw):
        got = jax.jit(lambda a: constrain_batch(a, mesh, **kw))(x).sharding
        return got.is_equivalent_to(NamedSharding(mesh, spec), x.ndim)

    dev = jax.devices()
    assert constrain_batch(x, None) is x
    # no batch axis larger than 1: identity, nothing traced
    for axes, n in (({"data": 1, "fsdp": 1}, 1), ({"tensor": 4}, 4)):
        assert constrain_batch(x, make_mesh(axes, devices=dev[:n])) is x
    mesh = make_mesh({"data": 2, "fsdp": 2, "seq": 2})
    assert placed(mesh, P(("data", "fsdp"), None, None))
    assert placed(mesh, P(("data", "fsdp"), "seq", None), seq_axis="seq")
    # size-1 axes drop out of the spec
    assert placed(make_mesh({"data": 1, "fsdp": 4}, devices=dev[:4]),
                  P("fsdp", None, None))
    # a batch the axes do not divide is left alone
    odd = jnp.zeros((2, 16, 4))
    assert constrain_batch(odd, mesh) is odd


def test_one_device_mesh_traces_the_same_program():
    """On a mesh with no batch axis > 1 the pin prunes to nothing: the
    lowered train step is text-for-text the one of a model with no mesh."""
    lowered = []
    for with_mesh in (True, False):
        _, _, step, state, batch = _tiny_fsdp_step(
            {"data": 1, "fsdp": 1}, with_mesh=with_mesh)
        lowered.append(step.lower(state, batch).as_text())
    assert lowered[0] == lowered[1]
