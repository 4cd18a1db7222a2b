"""Family ``longcat_flash``: everything about a cell that depends on the
model, for a decoder of shortcut-connected expert layers (FFN and identity
experts) over latent attention.

It reads the public ``LongcatFlashConfig`` keys of a configuration file
(``hidden_size``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``num_layers``, ``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``n_routed_experts``, ``zero_expert_num``, ``moe_topk``,
``routed_scaling_factor``, ``rope_theta``, ``rms_norm_eps``, the two
``mla_scale_*`` switches) and builds the program's
``distributed_tensorflow_tpu.models.longcat_flash.LongcatFlash`` on its
normal constructor.  A cut configuration's ``n_routed_experts`` and
``vocab_size`` are what this chip HOLDS; ``cut.published`` has what the
router scores, ``cut.expert_offset`` the first held expert's index (0 when
absent).  ``families/gpt2.py`` says what each name here is for.  Beyond
those, this file holds the byte count of a decode step
(``decode_step_bytes``) for the reader of ``decode_hbm_roofline_pct``.
"""
from __future__ import annotations

from typing import Any, Dict

REFERENCE = "longcat_flash_reference"

# What the drivers compare against, each with the measurement it was set
# from (my chip runs, PR 36, at the published widths: the program over 46
# runs of the cell itself, each a seed of its own — the final recipe's
# thirteen, call H; thirteen with the fitted bias and no centring, call G;
# thirteen of the first hand-in, call F; seven of an earlier tree, call A;
# the control, ``tests/benchmark/control_large.py`` —
# ``control_readings.py``'s rounding and comparisons with one copy of the 10.35 GB of weights held at
# a time — over 3 seeds ON CALL A's RECIPE (a seeded bias; not read again
# with the fitted one): the reference with every matrix rounded to 8 bits
# and back, int8 with a scale per channel and fp8 e4m3; PERF.md section 6
# has every reading).
TOLERANCES = {
    # bf16 weights and activations through 8 latent attentions, 8 dense FFNs
    # and 4 expert layers against the float32 reference: max-abs over 9
    # positions x 16,384 logits (of spread ~1.6) after prefill -> shared
    # pages -> prefill from an unaligned depth -> decode.  The program reads
    # 0.267-0.345 on the final recipe's thirteen seeds, 0.268-0.383 on call
    # G's, 0.265-0.345 on call F's and 0.273-0.326 on six of call A's seven,
    # 0.452 on the seventh (seed 2147490005, read twice);
    # the int8 control 1.04-1.16, the fp8 control 3.01-3.17.  A router pick
    # that flips on rounding is part of what the program's reading
    # carries: at a third of the (position, expert layer)
    # pairs of a 1,008-token context (33.5-34.0 % on three seeds) the
    # program's 12 picks are not the reference's 12, 3.0 % of single picks
    # differ, and each flipped pick moves the stream by ~0.07 of a unit
    # vector — which is why this family reads ten times what the
    # state-space family does.  The limit stands between the program and
    # BOTH controls: 1.9 x the program's largest, 0.82 x the int8 control's
    # smallest, 0.28 x the fp8 control's smallest.  What it cannot see at
    # this width: a router that WEIGHTED by the biased score (+-1e-3 on
    # p ~ 1e-2); the CPU tests hold that rule.
    "logit": 0.85,
    # Share of ALL emitted tokens that must equal the reference's argmax
    # (two finished turns, 130-170 positions: a position is +-0.7 points).
    # The program read 0.894-0.968 on the final recipe's thirteen seeds,
    # 0.885-0.986 on call G's, 0.860-0.945 on call F's and 0.859-0.950 on
    # call A's seven: with 16,384 logits of spread 1.6
    # the top two lie ~0.35 apart, the size of the program's error; the
    # int8 control 0.664-0.691, the fp8 control 0.285-0.290 (2,048 positions
    # each).  Under the program's smallest by three of its own standard
    # errors, over int8's largest.
    "min_agreement": 0.78,
    # UNSET: no train cell comes with this family (the configuration's
    # ``why_no_train``), so no reading stands behind these two and no run
    # compares against them.  The harness asks a family for all four names;
    # zero fails closed: a train cell has to set its own from its runs.
    "loss": 0.0,
    "token_loss": 0.0,
}


# ------------------------------------------------------------- the model

def share(config: Dict[str, Any]):
    """``(published FFN experts, held here, first held index)``."""
    cut = config.get("cut") or {}
    held = config["n_routed_experts"]
    return (cut.get("published", {}).get("n_routed_experts", held), held,
            cut.get("expert_offset", 0))


def model_config(config: Dict[str, Any]):
    """The program's ``LongcatFlashConfig`` from the configuration file's
    keys.  Only what defines the model is passed; ``max_position`` is the
    deployment's longest sequence (positions are rotary)."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.longcat_flash import (
        LongcatFlashConfig)
    if (config["attention_bias"] or config["zero_expert_type"] != "identity"
            or config["attention_method"] != "MLA"
            or config.get("rope_scaling") is not None):
        raise ValueError("the reference implements bias-free latent "
                         "attention with plain rotary positions and "
                         "identity zero experts only")
    assumed = config["assumed"]
    published, held, offset = share(config)
    return LongcatFlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_layers=config["num_layers"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts_published=published, experts_held=held,
        expert_offset=offset, zero_expert_num=config["zero_expert_num"],
        moe_topk=config["moe_topk"],
        routed_scaling_factor=config["routed_scaling_factor"],
        mla_scale_q_lora=config["mla_scale_q_lora"],
        mla_scale_kv_lora=config["mla_scale_kv_lora"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        max_position=config["serve"]["max_len"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        initializer_range=assumed["initializer_range"],
        choice_bias_range=assumed["choice_bias_range"],
        dropout_rate=assumed["dropout"])


def build_model(config: Dict[str, Any], mesh=None):
    """The program's model on its normal constructor.  Where the
    configuration's weight recipe names a ``choice_bias_balance``, ``init``
    is the program's followed by ``centre_router_groups`` and
    ``balance_choice_bias``: the weights are the benchmark's to make, the
    model's equations are not."""
    from distributed_tensorflow_tpu.models.longcat_flash import LongcatFlash
    if not config["assumed"].get("choice_bias_balance"):
        return LongcatFlash(model_config(config), mesh=mesh)

    class Balanced(LongcatFlash):
        def init(self, key):
            return balance_choice_bias(
                centre_router_groups(super().init(key), config), key, config)

    return Balanced(model_config(config), mesh=mesh)


def _reference():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        REFERENCE, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                REFERENCE + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def centre_router_groups(params, config: Dict[str, Any]):
    """``params`` with the router's columns centred over each chip's group
    of FFN experts (the ``held`` consecutive ones a chip of the cut holds:
    every group's columns then sum to zero; identity experts' columns as
    drawn).  What it is for, plainly: the benchmark's steadiness.  The
    tokens of a session share a context, and what they share (the mean of
    the values they attend to, ~5 % of a router input's norm at the
    published widths) moves every expert's logit by a step of its own for
    the whole session: behind a shared prompt an expert's load spreads by
    25-30 % whatever the choice bias (my chip run, PR 36), and the share of
    the picks that falls on the 16 experts held here — the bytes a step
    reads — goes with the seed's prompt.  With a group's columns summing to
    zero those steps cancel over the group to first order, whatever the
    context: each expert's load still goes with the context, the group's
    does not.  A column changes by a sixteenth of the group's sum (two
    columns of a group correlate at -1/15 where they were independent);
    the softmax, the choice and the weights are the model's."""
    import jax.numpy as jnp
    published, held, _ = share(config)
    if published % held:
        raise ValueError(f"{published} FFN experts are not whole groups "
                         f"of {held}")
    layers = []
    for layer in params["layers"]:
        router = layer["moe"]["router"]
        kernel = router["kernel"]
        groups = kernel[:, :published].astype(jnp.float32).reshape(
            kernel.shape[0], published // held, held)
        groups = groups - jnp.mean(groups, axis=-1, keepdims=True)
        kernel = jnp.concatenate(
            [groups.reshape(kernel.shape[0], published).astype(kernel.dtype),
             kernel[:, published:]], axis=-1)
        layers.append(dict(layer, moe=dict(layer["moe"], router=dict(
            router, kernel=kernel))))
    return dict(params, layers=layers)


def balance_choice_bias(params, key, config: Dict[str, Any]):
    """``params`` with every router's ``choice_bias`` set to what a trained
    ``e_score_correction_bias`` is: the load-balancing state that gives
    every expert, FFN and identity alike, the same share of the picks
    (``moe_topk / outputs`` of the tokens) — the fixed point of the
    bias update the public model was trained with (auxiliary-loss-free
    balancing: an expert chosen too often has its bias lowered).  A seeded
    router without it favours some experts for every token: at the
    published widths a held expert's load on random tokens spreads by
    12-14 % of the mean (my chip run, PR 36, four seeds; by count about half
    of it is the seeded bias itself, ``choice_bias_range`` 1e-3 against a
    12th-largest score of ~0.011) and by 2.5-3 % fitted.  The share of the
    picks that falls on the experts held here, and with it the bytes a
    serving step reads, otherwise goes with the seed.

    The fit, traceable (the harness jits ``init``): ``rows x tokens`` ids
    drawn from ``key`` go through the plain reference's forward at the
    default matmul precision, with the seeded bias in the routing; for each
    expert layer, an expert's logit over those tokens is taken as normal
    with the sample's mean and deviation (a smooth estimate: by count, the
    tally of its picks among the same tokens is three times as noisy), a
    token chooses it where ``p + bias`` passes the token's own
    ``moe_topk``-th largest biased score, and the bias is found by
    bisection, every expert at once, twice: the thresholds are taken anew
    under the first fit.  What it cannot balance is what a trained bias
    cannot either, the skew a CONTEXT gives (``centre_router_groups``):
    alone it left the cell's six seeds spreading by 0.51-0.76 %."""
    import jax
    import jax.numpy as jnp
    recipe = config["assumed"]["choice_bias_balance"]
    top_k = config["moe_topk"]
    ids = jax.random.randint(jax.random.fold_in(key, 0x6a1a),
                             (recipe["rows"], recipe["tokens"]), 0,
                             config["vocab_size"])
    logits = _reference().router_logits(params, ids, config)

    def balanced(z, bias):
        """``z`` [T, E] -> the bias [E] under which every expert's modelled
        frequency is ``top_k / E``."""
        outputs = z.shape[-1]
        lse = jax.nn.logsumexp(z, axis=-1, keepdims=True)
        p = jnp.exp(z - lse)
        mean, deviation = jnp.mean(z, axis=0), jnp.std(z, axis=0)

        def frequency(bias, passing):
            # the logit at which p + bias reaches a token's passing score
            need = jnp.log(jnp.maximum(passing - bias, 1e-30)) + lse
            return jnp.mean(0.5 * jax.scipy.special.erfc(
                (need - mean) / (deviation * 2.0 ** 0.5)), axis=0)

        for _ in range(2):
            passing = jax.lax.top_k(p + bias, top_k)[0][:, -1:]

            def halve(_, bounds):
                low, high = bounds
                mid = 0.5 * (low + high)
                often = frequency(mid, passing) > top_k / outputs
                return jnp.where(often, low, mid), jnp.where(often, mid, high)

            low, high = jax.lax.fori_loop(
                0, 24, halve, (jnp.full((outputs,), -1.0, jnp.float32),
                               jnp.full((outputs,), 1.0, jnp.float32)))
            bias = 0.5 * (low + high)
        return bias

    layers = []
    for i, layer in enumerate(params["layers"]):
        router = layer["moe"]["router"]
        bias = balanced(logits[:, i].reshape(-1, logits.shape[-1]),
                        router["choice_bias"].astype(jnp.float32))
        layers.append(dict(layer, moe=dict(layer["moe"], router=dict(
            router, choice_bias=bias.astype(router["choice_bias"].dtype)))))
    return dict(params, layers=layers)


def vocab_size(config: Dict[str, Any]) -> int:
    """The slice of the vocabulary held here: the traffic draws its ids
    from it, and the logits and the sampling are over it."""
    return config["vocab_size"]


def forward_logits(model, params, input_ids):
    return model.logits(params, model.apply(params, input_ids))


def shard_witness(params):
    return params["layers"][0]["ffn"][0]["w_in"]["kernel"]


def kernel_expected(config: Dict[str, Any], program: str) -> bool:
    """No hot program of this family holds a Mosaic kernel: its attention
    reads gathered pages of latents (the configuration's
    ``paged_attention_kernel`` says why) and the expert layer is XLA's."""
    return bool(config["serve"]["paged_attention_kernel"])


# ------------------------------------------------------------- operations

def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    attention = (d * rq + rq + rq * h * (nope + rope)     # W_qa, norm, W_qb
                 + d * (rkv + rope) + rkv                  # W_kva, norm
                 + rkv * h * (nope + v) + h * v * d)       # W_kvb, W_o
    dense = 3 * d * config["ffn_hidden_size"]
    outputs = share(config)[0] + config["zero_expert_num"]
    router = d * outputs + outputs                 # + the choice bias
    return {"attention": attention, "dense_ffn": dense, "router": router,
            # two attentions, two dense FFNs, the router, four norms
            "layer_outside_experts": (2 * attention + 2 * dense + router
                                      + 4 * d),
            "expert": 3 * d * config["expert_ffn_hidden_size"],
            "head": d * config["vocab_size"], "router_outputs": outputs,
            "cache_token": 2 * config["num_layers"] * (rkv + rope)}


def _body_params(config: Dict[str, Any]) -> int:
    """Every parameter a token passes through whatever the router picks:
    all layers outside the experts, and the final norm."""
    return (config["num_layers"] * _counts(config)["layer_outside_experts"]
            + config["hidden_size"])


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter held here: the body, the held experts, the
    embedding's and the untied head's slices (5.17 B at the cut)."""
    c = _counts(config)
    return (_body_params(config)
            + config["num_layers"] * share(config)[1] * c["expert"]
            + 2 * c["head"])


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Three times the forward count at the mean context of a sequence."""
    return 3.0 * serve_flops_per_token(config, (seq + 1) / 2)


def serve_flops_per_token(config: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations for one token of THE MODEL, not of the
    implementation: 2 x every parameter outside the experts (and the
    head's slice), 2 x an expert's parameters x the picks a token is
    expected to make on experts held here (``moe_topk x held /
    router outputs`` a layer: identity picks cost nothing, absent experts
    are another chip's), and for attention ``2 heads (qk_head_dim +
    v_head_dim)`` a cached position a sublayer — the published (expanded)
    form's scores and weighted sum.  What the absorbed form adds (512-wide
    scores and sums in place of 192 / 128) is NOT credited, nor is an
    expert's run over rows that did not pick it."""
    c = _counts(config)
    held_picks = config["moe_topk"] * share(config)[1] / c["router_outputs"]
    attention = (2.0 * config["num_attention_heads"]
                 * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                    + config["v_head_dim"]))
    return (2.0 * (_body_params(config) + (c["head"] if head else 0))
            + 2.0 * c["expert"] * held_picks * config["num_layers"]
            + attention * 2 * config["num_layers"] * context)


def decode_step_bytes(config: Dict[str, Any], live_slots: float,
                      cached_tokens: float) -> Dict[str, float]:
    """Bytes one decode step MUST move, a lower bound on its traffic:
    ``weights``: everything outside the experts and the head's slice, once,
    in the served type; ``expert_weights``: an expert's bytes x the expert
    layers x the held experts a step of ``live_slots`` tokens is EXPECTED
    to touch when every pick is uniform over the router's outputs, ``held
    (1 - (1 - moe_topk / outputs) ** live)`` — this term is a model of the
    routing, not a count: ``experts_touched_pct`` reads what the steps
    really touched and is what checks it; ``latent_cache``: every live
    slot's latents and shared keys up to its position (``cached_tokens``:
    over the live slots together) read once, the step's own row written."""
    import numpy as np
    c = _counts(config)
    weight = np.dtype(config["serve"]["weight_dtype"]).itemsize
    cache = np.dtype(config["assumed"]["compute_dtype"]).itemsize
    held = share(config)[1]
    touched = held * (1.0 - (1.0 - config["moe_topk"] / c["router_outputs"])
                      ** live_slots)
    return {"weights": float((_body_params(config) + c["head"]) * weight),
            "expert_weights": (float(c["expert"] * weight)
                               * config["num_layers"] * touched),
            "latent_cache": (float(c["cache_token"] * cache)
                             * (cached_tokens + live_slots))}


# ------------------------------------------------------- the serving probe

def serve_probe(model, params, sched, context, decode_positions: int):
    """What a session's second turn does, by the methods the scheduler
    calls and with its page size, window and programs: prefill the first
    half of ``context[:-decode_positions]`` in the scheduler's windows into
    slot 0, to a depth that is no page or window boundary; give slot 1 a
    page row that SHARES slot 0's full pages (what a radix prefix hit maps)
    and its own pages from there on; prefill the rest in slot 1 from the
    end of the shared pages — a page boundary, no window boundary — and
    decode ``decode_positions`` tokens one at a time
    (``pages.decode_paged_step``) with slot 0 not live.  Returns the logits
    at the last prompt position and at every decoded one, float32 ``[1 +
    decode_positions, vocab]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.serve import pages as pages_lib

    pg, w = sched.page_size, sched.prefill_chunk
    pps = sched.max_len // pg
    plen = len(context) - decode_positions
    cut = plen // 2 + 3                  # where the "first turn" ended
    shared = cut // pg                   # its full pages
    cache = pages_lib.init_paged_cache(model, 2, 2 * pps + 1, pg)
    first = np.arange(1, pps + 1, dtype=np.int32)
    second = first.copy()                # shares the first row's full pages
    second[shared:] = np.arange(pps + 1, 2 * pps + 1 - shared,
                                dtype=np.int32)

    # donated, as the scheduler's programs are: the host dispatches every
    # window before the first has run
    window = jax.jit(
        lambda p, kv, counters, toks, row, pos, real, head:
        model.decode_window_paged(p, kv, toks, row, pos, head=head,
                                  valid=real, counters=counters),
        static_argnums=7, donate_argnums=(1, 2))

    def prefill(cache, row, start, stop):
        logits = None
        for pos in range(start, stop, w):
            real = min(w, stop - pos)
            toks = np.zeros((1, w), np.int32)
            toks[0, :real] = context[pos:pos + real]
            last = pos + real == stop == plen
            logits, kv, counters = window(
                params, cache["kv"], cache["counters"], toks, row,
                np.int32(pos), np.int32(real), "all" if last else "none")
            cache = dict(cache, kv=kv, counters=counters)
        return cache, logits, real

    cache, _, _ = prefill(cache, first, 0, cut)
    cache, logits, real = prefill(cache, second, shared * pg, plen)
    got = [np.asarray(logits[0, real - 1], np.float32)]

    tab = np.stack([np.zeros_like(second), second])
    live = jnp.asarray([False, True])
    cache = dict(cache,
                 start_col=jnp.zeros((2,), jnp.int32),
                 write_col=jnp.asarray([0, plen], jnp.int32),
                 positions=jnp.asarray([0, plen], jnp.int32))
    step = jax.jit(lambda p, c, tok: pages_lib.decode_paged_step(
        model, p, c, tab, tok, live), donate_argnums=1)
    for j in range(decode_positions):
        lg, cache = step(params, cache, jnp.asarray(
            [0, context[plen + j]], jnp.int32))
        got.append(np.asarray(lg[1], np.float32))
    return np.stack(got)
