"""The decoder of shortcut-connected expert layers over latent attention
(models/longcat_flash.py, ops/moe.py ``apply_routed_experts``) against its
plain reference (benchmark/families/longcat_flash_reference.py: float32, the
published attention form, a loop over held experts) at a toy size on the
CPU, the reference against ``transformers``' ``LongcatFlashForCausalLM``, and
the rules the layer equations state, one by one."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import serve
from distributed_tensorflow_tpu.models import longcat_flash as lf
from distributed_tensorflow_tpu.ops import attention as attn_lib
from distributed_tensorflow_tpu.ops import moe as moe_lib
from distributed_tensorflow_tpu.serve import pages as pages_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 128
TOL = 5e-5          # float32 on both sides: summation order alone differs


def _load(name):
    path = os.path.join(ROOT, "benchmark", "families", name + ".py")
    spec = importlib.util.spec_from_file_location("_test_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("longcat_flash_reference")
family = _load("longcat_flash")


def reference_config(model):
    """The reference's view of a toy model: the configuration file's keys,
    a cut block where the model holds a share."""
    c = model.config
    config = {
        "hidden_size": c.hidden_size, "num_layers": c.num_layers,
        "num_attention_heads": c.num_attention_heads,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "mla_scale_q_lora": c.mla_scale_q_lora,
        "mla_scale_kv_lora": c.mla_scale_kv_lora,
        "n_routed_experts": c.experts_held,
        "zero_expert_num": c.zero_expert_num, "moe_topk": c.moe_topk,
        "routed_scaling_factor": c.routed_scaling_factor,
        "rms_norm_eps": c.rms_norm_eps, "rope_theta": c.rope_theta}
    if c.experts_held != c.n_routed_experts_published:
        config["cut"] = {
            "published": {"n_routed_experts": c.n_routed_experts_published},
            "expert_offset": c.expert_offset}
    return config


def share_of(params, offset, held):
    """``params`` with every layer's bank cut to ``held`` experts from
    ``offset`` on: what the chip holding that share holds."""
    def cut(layer):
        bank = jax.tree.map(lambda w: w[offset:offset + held],
                            layer["moe"]["experts"])
        return dict(layer, moe=dict(layer["moe"], experts=bank))
    return dict(params, layers=[cut(layer) for layer in params["layers"]])


@pytest.fixture(scope="module")
def toy():
    model = lf.longcat_flash_tiny(vocab_size=VOCAB)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, reference_config(model)


@pytest.fixture(scope="module")
def toy_share(toy):
    """Experts 2..5 of the toy's 8: one chip's share."""
    _, params, _ = toy
    model = lf.longcat_flash_tiny(vocab_size=VOCAB, experts_held=4,
                                  expert_offset=2)
    return model, share_of(params, 2, 4), reference_config(model)


def _ids(seed, *shape):
    return np.random.default_rng(seed).integers(0, VOCAB, shape,
                                                dtype=np.int32)


def _err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


# ------------------------------------------------ (a) the full forward

@pytest.mark.parametrize("which", ["toy", "toy_share"])
def test_full_forward_logits_are_the_references(which, request):
    model, params, config = request.getfixturevalue(which)
    ids = _ids(1, 2, 40)
    got = model.logits(params, model.apply(params, ids))
    want = reference.logits(params, ids, config)
    assert float(np.max(np.abs(want))) > 1.0        # logits of order one
    assert _err(got, want) < TOL


def test_a_window_counts_every_real_tokens_picks_as_the_reference_makes_them(
        toy_share):
    """A 24-token window of which 19 are real: every layer's counts add up
    to 19 x top-k, and the first layer's are the reference router's picks
    for the same rows."""
    model, params, config = toy_share
    c = model.config
    ids = _ids(2, 1, 24)
    cache = pages_lib.init_paged_cache(model, 1, 9, 4)
    _, _, counters = model.decode_window_paged(
        params, cache["kv"], ids, np.arange(1, 9, dtype=np.int32),
        np.int32(0), valid=np.int32(19), counters=cache["counters"])
    counts = np.asarray(counters["router"])
    assert counts.shape == (c.num_layers, c.experts_held + 2)
    assert (counts.sum(axis=1) == 19 * c.moe_topk).all()
    assert np.asarray(counters["touched"]).tolist() == [0, 0]  # decode's
    x = params["embeddings"]["word"][ids[0, :19]].astype(jnp.float32)
    cos, sin = reference.rotary_tables(19, config)
    layer = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        h = reference._rms_norm(layer["attention"][0]["ln"]["gamma"], x,
                                c.rms_norm_eps)
        x = x + reference._mla(layer["attention"][0], h, config, cos, sin)
        m = reference._rms_norm(layer["ffn"][0]["ln"]["gamma"], x,
                                c.rms_norm_eps)
        choice, _ = reference.route(layer["moe"], m, config)
    choice = np.asarray(choice)
    for e in range(c.experts_held):
        assert counts[0, e] == np.sum(choice == c.expert_offset + e)
    assert counts[0, -2] == np.sum(choice >= c.n_routed_experts_published)


# ------------------------- (b) windows, shared pages, decode, the engine

def test_probe_through_the_page_pool_is_the_references_full_forward(
        toy_share):
    """The family's probe, by the scheduler's own methods: prefill to a
    depth that is no page or window boundary in slot 0, its full pages
    shared with slot 1, the rest prefilled there, eight positions decoded
    with slot 0 not live."""
    model, params, config = toy_share
    engine = serve.Engine(model, params, num_slots=2, max_len=128,
                          prefill_chunk=8, page_size=4)
    context = _ids(3, 70 + 8)
    got = family.serve_probe(model, params, engine.scheduler, context, 8)
    want = reference.tail_logits(params, context[None], config, 9)[0]
    assert got.shape == want.shape == (9, VOCAB)
    assert _err(got, want) < TOL


def test_engine_greedy_tokens_are_the_references_argmax_with_a_prefix_hit(
        toy_share):
    model, params, config = toy_share
    engine = serve.Engine(model, params, num_slots=3, max_len=128,
                          prefill_chunk=8, page_size=4)
    system = _ids(4, 21)
    prompts = [np.concatenate([system, _ids(5 + i, n)])
               for i, n in enumerate((7, 13))]
    handles = []
    for prompt in prompts:          # the second session after the first
        handles.append(engine.submit(prompt, 9))
        while not handles[-1].done:
            engine.step()
    for prompt, handle in zip(prompts, handles):
        tokens = np.asarray(handle.tokens, np.int32)
        full = np.concatenate([prompt, tokens[:-1]])
        want = np.argmax(np.asarray(reference.logits(
            params, full[None], config))[0, len(prompt) - 1:], axis=-1)
        assert tokens.tolist() == want.tolist()
    stats = engine.stats()
    assert stats.prefix_hits_total == 1
    assert stats.prefix_tokens_reused_total == 20       # five pages of 4
    # every token the device consumed made top-k picks in every layer
    consumed = (sum(len(p) for p in prompts) - 20
                + sum(len(h.tokens) - 1 for h in handles))
    c = model.config
    assert stats.router_picks_total == consumed * c.num_layers * c.moe_topk
    assert (stats.router_picks_held_total
            == sum(map(sum, stats.expert_tokens_total)))
    assert 0 < stats.router_picks_identity_total < stats.router_picks_total


# ------------------------------------------------ (c) the shares add up

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """32 FFN + 8 identity experts, one layer: what the four 8-expert
    shares give, with the identity part and everything outside the expert
    layer counted once, is the uncut reference's layer."""
    kw = dict(vocab_size=VOCAB, num_layers=1, n_routed_experts_published=32,
              zero_expert_num=8, moe_topk=6)
    whole = lf.longcat_flash_tiny(**kw)
    params = whole.init(jax.random.PRNGKey(7))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(8), (12, 64), jnp.float32)
    cos, sin = reference.rotary_tables(12, reference_config(whole))
    layer = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        uncut = reference._layer(layer, x, reference_config(whole), cos, sin)
        # no expert held: the identity part and everything else
        nothing = dict(reference_config(whole), n_routed_experts=0,
                       cut={"published": {"n_routed_experts": 32}})
        rest = reference._layer(share_of(params, 0, 0)["layers"][0], x,
                                nothing, cos, sin)

    p_cos, p_sin = attn_lib.rope_tables(jnp.arange(12), 8, whole.config.rope_theta)
    mask = attn_lib.causal_mask(12)
    parts = []
    for j in range(4):
        model = lf.longcat_flash_tiny(experts_held=8, expert_offset=8 * j,
                                      **kw)
        mine = share_of(params, 8 * j, 8)["layers"][0]
        out, counts = model._layer(
            mine, x[None], lambda pa, _, h: model._attend_expanded(
                pa, h, p_cos, p_sin, mask))
        parts.append(np.asarray(out[0]))
        assert int(counts.sum()) == 12 * 6
    assert _err(uncut, rest) > 0.05          # the FFN experts weigh in
    assert _err(sum(parts) - 3 * np.asarray(rest), uncut) < TOL
    # and one share alone is not the layer
    assert _err(parts[0], uncut) > 100 * TOL


# ------------------------------------------- (d) the router's rules

def _router(bias):
    kernel = jnp.asarray([[2.0, 1.9, 0.0, -1.0, 0.5, 0.4]], jnp.float32)
    x = jnp.ones((1, 1), jnp.float32)
    return kernel, jnp.asarray(bias, jnp.float32), x


def test_the_bias_moves_the_choice_and_not_the_weight():
    kernel, bias, x = _router([0.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    p = np.asarray(jax.nn.softmax(kernel[0]))
    choice, weight = moe_lib.route_top_k(kernel, bias, x, top_k=2,
                                         scale=6.0)
    assert sorted(np.asarray(choice)[0].tolist()) == [0, 1]
    # a bias on expert 3, the least likely, brings it in ...
    kernel, bias, x = _router([0.0, 0.0, 0.0, 1.0, 0.0, 0.0])
    choice, weight = moe_lib.route_top_k(kernel, bias, x, top_k=2,
                                         scale=6.0)
    choice, weight = np.asarray(choice)[0], np.asarray(weight)[0]
    assert sorted(choice.tolist()) == [0, 3]
    # ... at its UNBIASED probability, times the factor
    for e, w in zip(choice, weight):
        assert w == pytest.approx(6.0 * p[e], rel=1e-6)
    assert weight[list(choice).index(3)] < 6.0 * 0.05


def test_the_weights_are_scaled_and_not_renormalised():
    kernel, bias, x = _router([0.0] * 6)
    p = np.asarray(jax.nn.softmax(kernel[0]))
    _, weight = moe_lib.route_top_k(kernel, bias, x, top_k=2, scale=6.0)
    total = float(np.sum(np.asarray(weight)))
    assert total == pytest.approx(6.0 * (p[0] + p[1]), rel=1e-6)
    assert abs(total - 6.0) > 1.0            # renormalised, it would be 6


def _tiny_layer(bias, held=2, d=8, inner=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    outputs = len(bias)
    return {"router": {"kernel": 0.3 * jax.random.normal(ks[0], (d, outputs)),
                       "choice_bias": jnp.asarray(bias, jnp.float32)},
            "experts": {
                "w_in": {"kernel": jax.random.normal(ks[1],
                                                     (held, d, 2 * inner))},
                "w_out": {"kernel": jax.random.normal(ks[2],
                                                      (held, inner, d))}}}


def test_an_identity_expert_adds_its_weight_times_the_input():
    """Two FFN experts held of two, two identity experts, and a bias that
    makes every token pick the two identity experts: ``y = (w_a + w_b) m``
    and no FFN expert runs."""
    layer = _tiny_layer([0.0, 0.0, 5.0, 5.0])
    m = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    y, counts = moe_lib.apply_routed_experts(
        layer, m, top_k=2, scale=6.0, num_ffn_experts=2)
    p = jax.nn.softmax(m @ layer["router"]["kernel"], axis=-1)
    want = 6.0 * (p[:, 2] + p[:, 3])[:, None] * m
    assert _err(y, want) < 1e-5
    assert np.asarray(counts).tolist() == [0, 0, 10, 0]


def test_a_pick_on_an_absent_expert_adds_nothing_and_is_counted():
    """The same four outputs, but only FFN expert 1 is held: picks on
    expert 0 are another chip's."""
    layer = _tiny_layer([5.0, 4.0, 0.0, 0.0])        # everyone picks 0, 1
    m = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    whole, _ = moe_lib.apply_routed_experts(
        layer, m, top_k=2, scale=6.0, num_ffn_experts=2)
    mine = jax.tree.map(lambda w: w[1:], layer["experts"])
    part, counts = moe_lib.apply_routed_experts(
        dict(layer, experts=mine), m, top_k=2, scale=6.0, num_ffn_experts=2,
        expert_offset=1)
    other, _ = moe_lib.apply_routed_experts(
        dict(layer, experts=jax.tree.map(lambda w: w[:1],
                                         layer["experts"])),
        m, top_k=2, scale=6.0, num_ffn_experts=2, expert_offset=0)
    assert np.asarray(counts).tolist() == [5, 0, 5]
    assert _err(part + other, whole) < 1e-5
    assert _err(part, whole) > 0.1


def test_rows_that_are_not_valid_add_nothing_and_are_not_counted():
    layer = _tiny_layer([0.0, 0.0, 0.0, 0.0])
    m = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    valid = jnp.asarray([True, True, False, True, False, False])
    y, counts = moe_lib.apply_routed_experts(
        layer, m, top_k=2, scale=6.0, num_ffn_experts=2, valid=valid)
    full, _ = moe_lib.apply_routed_experts(
        layer, m, top_k=2, scale=6.0, num_ffn_experts=2)
    assert int(counts.sum()) == 3 * 2
    assert _err(y[np.asarray(valid)], full[np.asarray(valid)]) < 1e-6
    assert float(jnp.max(jnp.abs(y[~np.asarray(valid)]))) == 0.0


def test_skipping_untouched_experts_changes_nothing_but_the_work():
    """The layer's one path (an expert at a time, one nobody picked skipped
    under a branch) gives the sum a plain loop over every held expert gives
    — with an expert nobody picked among the held."""
    layer = _tiny_layer([3.0, -9.0, 0.0, 2.0, 1.0], held=3)   # 1 is shunned
    m = jax.random.normal(jax.random.PRNGKey(2), (7, 8))
    loop, counts = moe_lib.apply_routed_experts(
        layer, m, top_k=2, scale=6.0, num_ffn_experts=3)
    choice, weight = moe_lib.route_top_k(
        layer["router"]["kernel"], layer["router"]["choice_bias"], m,
        top_k=2, scale=6.0)
    dense = jnp.sum(jnp.where(choice >= 3, weight, 0.0), -1,
                    keepdims=True) * m
    for e in range(3):
        gate, up = jnp.split(m @ layer["experts"]["w_in"]["kernel"][e], 2,
                             axis=-1)
        out = (jax.nn.silu(gate) * up) @ layer["experts"]["w_out"]["kernel"][e]
        dense = dense + jnp.sum(jnp.where(choice == e, weight, 0.0), -1,
                                keepdims=True) * out
    assert int(counts[1]) == 0 and int(counts[0]) == 7
    assert float(jnp.max(jnp.abs(loop))) > 10.0       # values of order 40
    assert _err(loop, dense) < 1e-4


def _broken(rule):
    """``route_top_k`` or the layer with one rule of the router broken."""
    true_route, true_layer = moe_lib.route_top_k, moe_lib.apply_routed_experts

    def route(kernel, bias, x, *, top_k, scale):
        p = jax.nn.softmax(x.astype(jnp.float32)
                           @ kernel.astype(jnp.float32), axis=-1)
        biased = p + bias.astype(jnp.float32)
        _, choice = jax.lax.top_k(biased, top_k)
        if rule == "bias_ignored_in_the_choice":
            _, choice = jax.lax.top_k(p, top_k)
        source = biased if rule == "bias_in_the_weight" else p
        weight = jnp.take_along_axis(source, choice, axis=-1)
        if rule == "renormalised":
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        if rule != "unscaled":
            weight = weight * scale
        return choice.astype(jnp.int32), weight

    def layer(params, x, **kw):
        if rule == "identity_adds_nothing":      # identity picks as absent
            kw["num_ffn_experts"] = params["router"]["kernel"].shape[1]
        return true_layer(params, x, **kw)

    if rule == "identity_adds_nothing":
        return true_route, layer
    return route, true_layer


@pytest.mark.parametrize("rule", [
    None, "bias_ignored_in_the_choice", "bias_in_the_weight", "renormalised",
    "unscaled", "identity_adds_nothing"])
def test_a_broken_router_rule_leaves_the_reference(toy, monkeypatch, rule):
    """The comparison with the reference sees each of the router's rules:
    with any one of them broken in the program the logits leave the
    reference's; with none broken they are the reference's."""
    model, params, config = toy
    # a bias of the scores' own scale, so that choice and weight differ
    params = dict(params, layers=[dict(layer, moe=dict(
        layer["moe"], router=dict(
            layer["moe"]["router"],
            choice_bias=0.2 * jnp.cos(jnp.arange(12.0) + i))))
        for i, layer in enumerate(params["layers"])])
    if rule is not None:
        route, layer = _broken(rule)
        monkeypatch.setattr(moe_lib, "route_top_k", route)
        monkeypatch.setattr(moe_lib, "apply_routed_experts", layer)
    ids = _ids(9, 2, 24)
    err = _err(model.logits(params, model.apply(params, ids)),
               reference.logits(params, ids, config))
    if rule is None:
        assert err < TOL
    else:
        assert err > 200 * TOL, rule


# ----------------------------------------------------------- (e) dropless

def test_a_window_whose_tokens_all_pick_one_expert_is_the_full_forward(toy):
    """Every token of a 32-token window routed to the same FFN experts: a
    layer with a capacity would drop most of them."""
    model, params, config = toy
    bias = jnp.zeros((12,)).at[3].set(10.0).at[5].set(9.0).at[0].set(8.0)
    params = dict(params, layers=[dict(layer, moe=dict(
        layer["moe"], router=dict(layer["moe"]["router"],
                                  choice_bias=bias)))
        for layer in params["layers"]])
    ids = _ids(10, 1, 32)
    cache = pages_lib.init_paged_cache(model, 1, 9, 4)
    row = np.arange(1, 9, dtype=np.int32)
    logits, _, counters = model.decode_window_paged(
        params, cache["kv"], ids, row, np.int32(0), valid=np.int32(32),
        counters=cache["counters"])
    router = np.asarray(counters["router"])
    assert (router[:, [0, 3, 5]] == 32).all() and router.sum() == 2 * 96
    assert _err(logits, reference.logits(params, ids, config)) < TOL


def test_the_layer_has_no_capacity_shaped_tensor():
    """Cost linear in tokens: nothing in the traced layer has the shape of a
    ``[tokens, experts, capacity]`` dispatch, and no dimension is the full
    count of published experts but the router's own."""
    model = lf.longcat_flash_tiny(experts_held=4, expert_offset=2)
    layer = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"][0]
    m = jax.ShapeDtypeStruct((40, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: moe_lib.apply_routed_experts(
        p, x, top_k=3, scale=2.0, num_ffn_experts=8, expert_offset=2))(
            layer["moe"], m)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
              for v in eqn.outvars}
    # whatever has the tokens' dimension is no larger than the tokens'
    # activations [40, 64]: the router's [40, 12], the held picks' one-hot
    # [40, 3, 4]; a [tokens, experts, capacity] tensor would be
    assert (40, 3, 4) in shapes and (40, 12) in shapes
    assert all(int(np.prod(s)) <= 40 * 64 for s in shapes if 40 in s), shapes


# ------------------------------------ (f) absorbed and expanded attention

def test_absorbed_and_expanded_attention_agree(toy):
    model, params, _ = toy
    c = model.config
    pa = params["layers"][0]["attention"][1]
    h = jax.random.normal(jax.random.PRNGKey(11), (2, 12, 64), jnp.float32)
    cos, sin = attn_lib.rope_tables(jnp.arange(12), c.qk_rope_head_dim,
                                    c.rope_theta)
    mask = attn_lib.causal_mask(12)
    expanded = model._attend_expanded(pa, h, cos, sin, mask)
    q_nope, q_rot = model._queries(pa, h, cos, sin)
    rows = jnp.concatenate(model._latents(pa, h, cos, sin), axis=-1)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, c.cache_row_width - 24)))
    absorbed = model._attend_absorbed(pa, q_nope, q_rot, rows, mask)
    assert float(jnp.max(jnp.abs(expanded))) > 0.01
    assert _err(absorbed, expanded) < 1e-5


def test_rotary_is_over_interleaved_pairs(toy):
    """Position p turns the pair (x[2j], x[2j+1]) by p * theta^(-2j/r): a
    rotary over halves (x[j], x[j + r/2]) gives other scores."""
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 5, 1, 8), jnp.float32)
    cos, sin = attn_lib.rope_tables(jnp.arange(5), 8, 1e4)
    got = np.asarray(lf._rope_interleaved(x, cos, sin))[0, :, 0]
    xs = np.asarray(x)[0, :, 0]
    for p in range(5):
        for j in range(4):
            angle = p * 1e4 ** (-j / 4)
            a, b = xs[p, 2 * j], xs[p, 2 * j + 1]
            assert got[p, j] == pytest.approx(
                a * np.cos(angle) - b * np.sin(angle), abs=1e-5)
            assert got[p, 4 + j] == pytest.approx(
                b * np.cos(angle) + a * np.sin(angle), abs=1e-5)
    ref_cos, ref_sin = reference.rotary_tables(5, {"qk_rope_head_dim": 8,
                                                   "rope_theta": 1e4})
    assert _err(reference._rotary(jnp.asarray(xs), ref_cos, ref_sin),
                got) < 1e-5


# ------------------------------------------------------ (g) the shortcut

def test_the_shortcut_joins_after_the_second_ffn(toy):
    """With ``MLP_1`` zeroed the layer's output is the stream after the
    second attention — which saw nothing of the expert layer — plus the
    expert layer's result on the FIRST sublayer's FFN input."""
    model, params, _ = toy
    c = model.config
    p = params["layers"][0]
    zeroed = dict(p, ffn=[p["ffn"][0], dict(
        p["ffn"][1], w_out={"kernel": jnp.zeros_like(
            p["ffn"][1]["w_out"]["kernel"])})])
    x = jax.random.normal(jax.random.PRNGKey(13), (1, 10, 64), jnp.float32)
    cos, sin = attn_lib.rope_tables(jnp.arange(10), c.qk_rope_head_dim,
                                    c.rope_theta)
    mask = attn_lib.causal_mask(10)

    def attend(pa, _, h):
        return model._attend_expanded(pa, h, cos, sin, mask)

    out, _ = model._layer(zeroed, x, attend)
    att, ffn = p["attention"], p["ffn"]
    x1 = x + attend(att[0], 0, lf._rms_norm(att[0]["ln"], x, c.rms_norm_eps))
    m = lf._rms_norm(ffn[0]["ln"], x1, c.rms_norm_eps)
    shortcut, _ = model._moe(p["moe"], m)
    x1 = x1 + model._mlp(ffn[0], m)
    x2 = x1 + attend(att[1], 1, lf._rms_norm(att[1]["ln"], x1,
                                             c.rms_norm_eps))
    assert float(jnp.max(jnp.abs(shortcut))) > 0.05
    assert _err(out, x2 + shortcut) < 1e-5
    assert _err(out, x2) > 0.05


# ------------------------------------- (h) the reference vs transformers

def _to_transformers(params, model):
    """The program's tree as the public model's state dict (torch keeps
    ``[out, in]`` matrices; a head's lanes are contiguous)."""
    import torch
    c = model.config

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    state = {"model.embed_tokens.weight": t(params["embeddings"]["word"]),
             "model.norm.weight": t(params["ln_f"]["gamma"]),
             "lm_head.weight": t(params["lm_head"]["kernel"].T)}
    for i, layer in enumerate(params["layers"]):
        base = f"model.layers.{i}."
        for j, a in enumerate(layer["attention"]):
            at = f"{base}self_attn.{j}."
            state[f"{base}input_layernorm.{j}.weight"] = t(a["ln"]["gamma"])
            state[at + "q_a_proj.weight"] = t(a["q_a"]["kernel"].T)
            state[at + "q_a_layernorm.weight"] = t(a["q_norm"]["gamma"])
            state[at + "q_b_proj.weight"] = t(
                a["q_b"]["kernel"].reshape(c.q_lora_rank, -1).T)
            state[at + "kv_a_proj_with_mqa.weight"] = t(jnp.concatenate(
                [a["kv_a"]["kernel"], a["k_rope"]["kernel"]], axis=1).T)
            state[at + "kv_a_layernorm.weight"] = t(a["kv_norm"]["gamma"])
            state[at + "kv_b_proj.weight"] = t(
                a["kv_b"]["kernel"].reshape(c.kv_lora_rank, -1).T)
            state[at + "o_proj.weight"] = t(
                a["out"]["kernel"].reshape(-1, c.hidden_size).T)
        for j, f in enumerate(layer["ffn"]):
            inner = c.ffn_hidden_size
            w_in = f["w_in"]["kernel"]
            state[f"{base}post_attention_layernorm.{j}.weight"] = t(
                f["ln"]["gamma"])
            state[f"{base}mlps.{j}.gate_proj.weight"] = t(w_in[:, :inner].T)
            state[f"{base}mlps.{j}.up_proj.weight"] = t(w_in[:, inner:].T)
            state[f"{base}mlps.{j}.down_proj.weight"] = t(
                f["w_out"]["kernel"].T)
        moe = layer["moe"]
        state[base + "mlp.router.classifier.weight"] = t(
            moe["router"]["kernel"].T)
        state[base + "mlp.router.e_score_correction_bias"] = t(
            moe["router"]["choice_bias"])
        inner = c.expert_ffn_hidden_size
        for e in range(c.experts_held):
            ex = f"{base}mlp.experts.{e}."
            w_in = moe["experts"]["w_in"]["kernel"][e]
            state[ex + "gate_proj.weight"] = t(w_in[:, :inner].T)
            state[ex + "up_proj.weight"] = t(w_in[:, inner:].T)
            state[ex + "down_proj.weight"] = t(
                moe["experts"]["w_out"]["kernel"][e].T)
    return state


def test_the_reference_is_transformers_longcat_flash(toy):
    """The repo's reference against ``LongcatFlashForCausalLM`` on the same
    seeded weights (uncut: every expert held)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "LongcatFlashForCausalLM"):
        pytest.skip("this transformers has no LongcatFlashForCausalLM")
    model, params, config = toy
    c = model.config
    hf_config = transformers.LongcatFlashConfig(
        vocab_size=VOCAB, hidden_size=c.hidden_size,
        num_layers=c.num_layers, num_hidden_layers=2 * c.num_layers,
        num_attention_heads=c.num_attention_heads,
        ffn_hidden_size=c.ffn_hidden_size, q_lora_rank=c.q_lora_rank,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, moe_topk=c.moe_topk,
        n_routed_experts=c.n_routed_experts_published,
        zero_expert_num=c.zero_expert_num,
        expert_ffn_hidden_size=c.expert_ffn_hidden_size,
        routed_scaling_factor=c.routed_scaling_factor,
        rope_theta=c.rope_theta, rms_norm_eps=c.rms_norm_eps,
        max_position_embeddings=256, attn_implementation="eager")
    public = transformers.LongcatFlashForCausalLM(hf_config).eval()
    state = _to_transformers(params, model)
    missing, unexpected = public.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k or "inv_freq" in k
                                  for k in missing), (missing, unexpected)
    ids = _ids(14, 2, 24)
    with torch.no_grad():
        want = public(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    got = reference.logits(params, ids, config)
    assert float(np.max(np.abs(want))) > 1.0
    assert _err(got, want) < 2e-4


# ------------------------------------------------------ shapes and bytes

def test_the_cache_spec_and_the_sharding_rules(toy):
    model, params, _ = toy
    c = model.config
    spec = model.paged_cache_spec()
    assert spec["kv_layers"] == 2 * c.num_layers and spec["state"] == {}
    assert {k: v[0] for k, v in spec["kv"].items()} == {
        "latent_key": (128,)}          # 16 + 8, and zeros to a lane tile
    assert model.paged_kernel_ok is False
    with pytest.raises(ValueError, match="paged_kernel_ok"):
        serve.Engine(model, params, num_slots=2, max_len=128,
                     use_paged_kernel=True)
    specs = model.partition_rules(fsdp=True).tree_specs(params)
    bank = specs["layers"][0]["moe"]["experts"]["w_in"]["kernel"]
    assert bank[0] == "expert"


def test_the_cached_row_is_what_a_latent_and_a_key_tile_to():
    """At the published widths a 576-lane leaf, a 512 + 64 pair and the
    640-lane row the model declares all take 640 lanes a token on the TPU:
    the declared padding costs nothing that was not there."""
    pool = (8, 16385, 16)

    def leaves(*widths):
        return {str(w): jax.ShapeDtypeStruct(pool + (w,), jnp.bfloat16)
                for w in widths}

    tiled = {pages_lib.kv_pool_bytes(leaves(*w))[1]
             for w in ((512, 64), (576,), (640,))}
    assert tiled == {8 * 16385 * 16 * 640 * 2}
    config = lf.LongcatFlashConfig(
        vocab_size=16384, hidden_size=6144, ffn_hidden_size=12288,
        expert_ffn_hidden_size=2048, num_layers=4, num_attention_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts_published=512,
        zero_expert_num=256, moe_topk=12, routed_scaling_factor=6.0,
        experts_held=16)
    assert config.cache_row_width == 640 and config.router_outputs == 768


def test_weights_are_made_in_the_param_dtype():
    model = lf.longcat_flash_tiny(param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    kinds = {str(leaf.dtype) for leaf in jax.tree.leaves(shapes)}
    assert kinds == {"bfloat16", "float32"}
    bias = shapes["layers"][0]["moe"]["router"]["choice_bias"]
    assert bias.dtype == jnp.float32            # the choice bias alone
    assert sum(leaf.dtype == jnp.float32
               for leaf in jax.tree.leaves(shapes)) == 2
