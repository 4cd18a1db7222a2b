"""Family ``gpt2``: everything about a cell that depends on the model.

A configuration file names its family (``"family": "gpt2"``) and
``harness/spec.py`` finds this file by that name, as it finds a generator or
a per-layer reader.  The two drivers take from it — and from nowhere else —
the program's model, the ids the traffic may draw, the plain reference
(``REFERENCE`` names its file, beside this one), the operation counts, the
serving probe, the tolerances and their reasons, the leaf that witnesses
sharding, and whether a kernel is expected in a hot program.  What the
drivers own stays theirs: the window, the rates, the comparison itself and
its verdict.  A family supplies inputs to the comparison, never the verdict.

This family reads the HF GPT-2 keys of a configuration file (``n_embd``,
``n_layer``, ``n_head``, ``n_inner``, ``n_positions``, ``vocab_size``,
``layer_norm_epsilon``, ``activation_function``) and builds
``distributed_tensorflow_tpu.models.gpt.GPT``.  A later family is a new
file with these names in it; no file under ``harness/`` knows a family.
"""
from __future__ import annotations

from typing import Any, Dict

REFERENCE = "gpt2_reference"     # families/gpt2_reference.py

# What the drivers compare against, each with the measurement it was set
# from.  The drivers keep no tolerance of their own.
TOLERANCES = {
    # bf16 weights and activations through 48 layers against float32: logits
    # are O(1) (sigma ~0.8 with 0.02-normal weights at width 1600).  Measured
    # at GPT-2-XL on the v5e: 0.050-0.065 max-abs over 9 positions x 50,257
    # logits in 17 runs (my chip runs, PR 24); PR 22 measured 0.0013 for the
    # paged kernel alone at GPT-2-small.  2.3x the largest measured; a wrong
    # position, mask or page mapping moves logits by O(1).
    "logit": 0.15,
    # Share of ALL emitted tokens that must equal the reference's argmax:
    # with random weights the top two logits are often closer than bf16
    # resolves (PR 22 measured 0.91 agreement at GPT-2-small); a wrong engine
    # agrees 1 in 50,257.
    "min_agreement": 0.6,
    # The program's bf16 compute against the float32 reference on 2 sequences
    # of the batch, two ways.  (1) ``loss``: the mean loss of ``lm_loss_fn``,
    # the function the step differentiates: rounding errors average out over
    # 2048 tokens, and the largest |diff| of 28 runs over 6 seeds on the v5e
    # was 2.1e-4 at GPT-2-medium and 4.9e-4 at GPT-2-XL (my chip runs, PR
    # 24); the tolerance is four times that.  (2) ``token_loss``: every
    # token's own loss from the program's forward pass and head, max-abs over
    # the 2 x seq positions: a mean near ln(vocab) hides a wrong mask or
    # position table, a single position cannot.  On the float32 reference at
    # GPT-2-medium, 2 x 256 tokens, dropping the causal mask moves the mean
    # by 0.0099 and single positions by up to 1.49; a position table shifted
    # by one row 0.0005 and 0.93; bf16 rounding 0.00004 and 0.030 (CPU
    # arithmetic, PR 24).
    "loss": 2e-3,
    "token_loss": 0.1,
}


# ------------------------------------------------------------- the model

def model_config(config: Dict[str, Any]):
    """The program's ``GPTConfig`` from a configuration file's HF GPT-2
    keys.  Only what defines the model is passed: every tuning knob the
    program has a default for (flash thresholds, loss chunking, fused
    norms, remat policy) keeps that default, so a later PR that finds a
    better one is measured."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.gpt import GPTConfig
    if config["activation_function"] != "gelu_new":
        raise ValueError("the reference implements GPT-2's gelu_new only")
    assumed = config["assumed"]
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config.get("n_inner") or 4 * config["n_embd"],
        max_position=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        dropout_rate=assumed["dropout"], remat=assumed["remat"])


def build_model(config: Dict[str, Any], mesh=None):
    """The program's model on its normal constructor; training passes its
    mesh, serving none."""
    from distributed_tensorflow_tpu.models.gpt import GPT
    return GPT(model_config(config), mesh=mesh)


def vocab_size(config: Dict[str, Any]) -> int:
    """The ids the traffic may draw: ``0 .. vocab_size - 1``."""
    return config["vocab_size"]


def forward_logits(model, params, input_ids):
    """The program's own forward pass and head: ``[b, s]`` ids ->
    ``[b, s, vocab]`` logits."""
    return model.logits(params, model.apply(params, input_ids))


def shard_witness(params):
    """The leaf whose placement witnesses that every device holds a
    parameter shard: a per-layer matrix the ZeRO rules shard."""
    return params["decoder"]["attention"]["query"]["kernel"]


def kernel_expected(config: Dict[str, Any], program: str) -> bool:
    """Whether the hot program ``program`` (a name of the scheduler's
    ``graph_targets()``) should hold a Mosaic kernel on a TPU: the
    deployment says so, never the scheduler, and all three of this family's
    hot programs run paged attention."""
    return bool(config["serve"]["paged_attention_kernel"])


# ------------------------------------------------------------- operations

def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul, biases and norms included
    (they are < 0.1 % and every published 6N count includes them).  The
    position table is not a matmul and is left out; the tied word matrix
    is counted once, as the head."""
    return _body_params(config) + _head_params(config)


def _body_params(config: Dict[str, Any]) -> int:
    d, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * d
    per_layer = (4 * d * d + 4 * d          # q, k, v, out + biases
                 + 2 * d * inner + inner + d   # FFN + biases
                 + 4 * d)                    # two layer norms
    return layers * per_layer + 2 * d       # + the final norm


def _head_params(config: Dict[str, Any]) -> int:
    return config["vocab_size"] * config["n_embd"]


def total_params(config: Dict[str, Any]) -> int:
    return matmul_params(config) + config["n_positions"] * config["n_embd"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """``6 N + 12 L h s``: 2N forward and 4N backward for the matmul path
    over N parameters, plus attention's QK^T and PV at 4 L h s forward,
    times three for training.  Recomputation (remat) is not counted: MFU
    counts what the algorithm needs.  Copied from ``bench.py``
    ``_transformer_flops_per_token``, with N from the configuration instead
    of from a parameter tree."""
    return (6.0 * matmul_params(config)
            + 12.0 * config["n_layer"] * config["n_embd"] * seq)


def serve_flops_per_token(config: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations for one token that attends over ``context`` cached
    positions (itself included): 2 x the parameters it passes through plus
    attention's QK^T and PV, ``4 L h context``.  ``head=False`` leaves the
    LM head out: a prefilled token that yields no logits does not need
    it."""
    through = _body_params(config) + (_head_params(config) if head else 0)
    return (2.0 * through
            + 4.0 * config["n_layer"] * config["n_embd"] * context)


# ------------------------------------------------------- the serving probe

def serve_probe(model, params, sched, context, decode_positions: int):
    """Prefill ``context[:-decode_positions]`` through a paged cache in the
    scheduler's own windows and decode ``decode_positions`` more tokens one
    at a time, by the methods the scheduler calls
    (``GPT.decode_window_paged``, ``pages.decode_paged_step``) with the
    scheduler's page size, window and kernel choice.  Returns the logits at
    the last prompt position and at every decoded one, float32
    ``[1 + decode_positions, vocab]``: the driver compares them with the
    reference's at the same positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.serve import pages as pages_lib

    use_kernel = sched.use_paged_kernel
    max_len = model.config.max_position
    pps = max_len // sched.page_size
    cache = pages_lib.init_paged_cache(model, 1, pps + 1, sched.page_size)
    row = jnp.arange(1, pps + 1, dtype=jnp.int32)
    w = sched.prefill_chunk
    plen = len(context) - decode_positions
    n_win = -(-plen // w)
    padded = np.zeros((n_win * w,), np.int32)
    padded[:plen] = context[:plen]

    window = jax.jit(
        lambda p, kv, toks, pos, head: model.decode_window_paged(
            p, kv, toks, row, pos, head=head, use_kernel=use_kernel),
        static_argnums=4)
    step = jax.jit(lambda p, c, tok: pages_lib.decode_paged_step(
        model, p, c, row[None], tok, jnp.ones((1,), bool),
        use_kernel=use_kernel))

    kv = cache["kv"]
    for i in range(n_win - 1):
        _, kv = window(params, kv, padded[None, i * w:(i + 1) * w],
                       np.int32(i * w), "none")
    logits, kv = window(params, kv, padded[None, (n_win - 1) * w:],
                        np.int32((n_win - 1) * w), "all")
    got = [np.asarray(logits[0, plen - 1 - (n_win - 1) * w], np.float32)]
    cache = {"kv": kv, "start_col": jnp.zeros((1,), jnp.int32),
             "write_col": jnp.full((1,), plen, jnp.int32),
             "positions": jnp.full((1,), plen, jnp.int32)}
    for j in range(decode_positions):
        lg, cache = step(params, cache,
                         jnp.asarray(context[plen + j:plen + j + 1]))
        got.append(np.asarray(lg[0], np.float32))
    return np.stack(got)
