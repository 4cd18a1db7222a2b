"""Family ``phi4flash``: everything about a cell that depends on the model,
for the SambaY decoder-hybrid-decoder (Mamba-1 and sliding-window attention
in the first half; a memory layer, one full-attention layer whose K/V every
later attention layer reads, and gated memory units beside cross-attention
in the second; differential attention throughout).

It reads the published ``phi4flash`` keys of a configuration file
(``hidden_size``, ``intermediate_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``layer_norm_eps``, ``vocab_size``) and the Mamba-1 sizes the file lists
under ``assumed``, and builds the program's
``distributed_tensorflow_tpu.models.sambay.SambaY`` on its normal
constructor.  ``families/gpt2.py`` says what each name here is for; the
serving probe is the state-space family's (``granitemoehybrid.py``): prefill
in the scheduler's windows, snapshot, restore, prefill on, decode.  Beyond
those, this file holds the byte count of a decode step
(``decode_step_bytes``) for the readers of ``decode_hbm_roofline_pct``,
``recurrent_state_bytes_pct`` and ``shared_kv_bytes_pct``.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

REFERENCE = "phi4flash_reference"

# What the drivers compare against, each with the measurement it was set
# from (on one TPU v5e: the program over 7 seeds; the control,
# ``tests/benchmark/control_large.py`` at this model's widths: the reference
# with every matrix rounded to 8 bits and back, int8 with a scale per
# channel and fp8 e4m3; PERF.md section 6 has every reading).
TOLERANCES = {
    # bf16 weights and activations through 32 layers, the Mamba state in
    # float32, against the float32 reference: max-abs over 9 positions x
    # 200,064 logits after prefill -> snapshot -> restore -> prefill ->
    # decode of a 1,056-token context (every ring wrapped).  The program
    # reads 0.171-0.213, eight times the state-space family's (whose
    # residual branches are scaled by 0.22 and logits by 1/8).  The int8
    # control reads 0.473, the fp8 one 2.02.  The limit stands between the
    # program and the int8 control, about 1.5 x from each.
    "logit": 0.32,
    # Share of ALL emitted tokens that must equal the reference's argmax:
    # the program reads 0.881-0.932 (200,064 ids: the top two of random
    # weights are often closer than bf16 moves them), the int8 control
    # 0.782, the fp8 one 0.234.  Between the program and the int8 control.
    "min_agreement": 0.83,
    # UNSET: no train cell comes with this family (the configuration's
    # ``why_no_train``), so no run compares against these two; the harness
    # asks a family for all four names, and these are the state-space
    # family's.
    "loss": 5e-5,
    "token_loss": 0.02,
}


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"_phi4flash_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ------------------------------------------------------------- the model

def model_config(config: Dict[str, Any]):
    """The program's ``SambaYConfig`` from the configuration file's keys.
    Only what defines the model is passed; ``max_position`` is the
    deployment's longest sequence (the stack has no positional table)."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.sambay import SambaYConfig
    assumed = config["assumed"]
    if (config["model_type"] != "phi4flash" or config["hidden_act"] != "silu"
            or not config["tie_word_embeddings"] or config["mlp_bias"]
            or config["lm_head_bias"] or config["mb_per_layer"] != 2
            or not assumed["mamba_conv_bias"] or assumed["mamba_proj_bias"]
            or not assumed["attention_bias"] or assumed["dropout"]):
        raise ValueError("the reference implements the published recipe "
                         "only: SiLU MLP without bias, tied head, Mamba-1 "
                         "in every second layer of the self-decoder, no "
                         "dropout")
    return SambaYConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        sliding_window=config["sliding_window"],
        d_state=assumed["mamba_d_state"], d_conv=assumed["mamba_d_conv"],
        expand=assumed["mamba_expand"], dt_rank=assumed["mamba_dt_rank"],
        layer_norm_eps=config["layer_norm_eps"],
        max_position=config["serve"]["max_len"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]),
        state_dtype=jnp.dtype(assumed["recurrent_state_dtype"]),
        conv_state_dtype=jnp.dtype(assumed["conv_state_dtype"]),
        initializer_range=assumed["initializer_range"],
        lambda_std=assumed["lambda_std"])


def build_model(config: Dict[str, Any], mesh=None):
    from distributed_tensorflow_tpu.models.sambay import SambaY
    return SambaY(model_config(config), mesh=mesh)


def vocab_size(config: Dict[str, Any]) -> int:
    return config["vocab_size"]


def forward_logits(model, params, input_ids):
    return model.logits(params, model.apply(params, input_ids))


def shard_witness(params):
    return params["self"]["mamba"]["ffn"]["w_in"]["kernel"]


def kernel_expected(config: Dict[str, Any], program: str) -> bool:
    """No hot program of this family holds a Mosaic kernel: the shared
    plane is read through the gather path and the rings from the slot's
    state block (the configuration's ``paged_attention_kernel_why``)."""
    return bool(config["serve"]["paged_attention_kernel"])


# ------------------------------------------------------------- operations

def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    a = config["assumed"]
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    inner = a["mamba_expand"] * d
    n, rank = a["mamba_d_state"], a["mamba_dt_rank"]
    heads = config["num_attention_heads"]
    hd = d // heads
    kv = config["num_key_value_heads"] * hd
    block = (2 * d * config["intermediate_size"]            # W1 (gate, up)
             + config["intermediate_size"] * d + 4 * d)     # W2, two norms
    mamba = (d * 2 * inner + inner * (a["mamba_d_conv"] + 1)   # in, conv
             + inner * (rank + 2 * n) + rank * inner + inner   # x, dt
             + n * inner + inner + inner * d)                  # A, D, out
    diff = 4 * hd + 2 * hd                    # the lambdas, the heads' norm
    attention = (d + 1) * (heads * hd + 2 * kv) + (heads * hd + 1) * d + diff
    cross = 2 * (d + 1) * d + diff
    gmu = 2 * d * inner
    pairs = layers // 4
    return {"mamba_layers": pairs + 1, "window_layers": pairs,
            "shared_readers": pairs, "cross_layers": pairs - 1,
            "mamba": mamba + block, "attention": attention + block,
            "cross": cross + block, "gmu": gmu + block,
            "head": config["vocab_size"] * d, "kv_width": kv,
            "state": n * inner, "conv_state": (a["mamba_d_conv"] - 1) * inner}


def _body_params(config: Dict[str, Any]) -> int:
    c = _counts(config)
    return (c["mamba_layers"] * c["mamba"]
            + (c["window_layers"] + 1) * c["attention"]
            + c["cross_layers"] * (c["gmu"] + c["cross"])
            + 2 * config["hidden_size"])                   # the final norm


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter; the tied word matrix once (3.85 B here)."""
    return _body_params(config) + _counts(config)["head"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Three times the forward count at the mean context of a sequence."""
    return 3.0 * serve_flops_per_token(config, (seq + 1) / 2)


def serve_flops_per_token(config: Dict[str, Any], context: float,
                          head: bool = True) -> float:
    """Forward operations for one token, the model's and not the
    implementation's: 2 x the parameters it passes through; for each
    attended position of each attention layer two score maps of a
    differential head's query pair and two sums of its 128-wide value pair,
    ``6 x hidden_size`` (15,360) — the window layers at ``min(context,
    sliding_window)`` positions, the full layer and the cross layers at the
    whole ``context``; and about 6 operations per element of every Mamba
    layer's state (the decay's product and exponential, the input, the sum;
    the read-out's multiply and add)."""
    c = _counts(config)
    through = _body_params(config) + (c["head"] if head else 0)
    per_position = 6.0 * config["hidden_size"]
    return (2.0 * through + 6.0 * c["mamba_layers"] * c["state"]
            + per_position * (
                c["window_layers"] * min(context, config["sliding_window"])
                + c["shared_readers"] * context))


def decode_step_bytes(config: Dict[str, Any], live_slots: float,
                      cached_tokens: float) -> Dict[str, float]:
    """Bytes one decode step MUST move, a lower bound on its traffic:
    ``weights`` once in the served type; ``shared_kv``, the full layer's
    K/V of every live slot up to its position with the step's own row, read
    by each of its readers (the full layer and every cross layer: no reader
    can use another's read); ``window_kv``, each window layer's K/V rows of
    the last ``sliding_window`` positions of every live slot — taken as
    ``min(cached + live, sliding_window x live)``, which is the sum over
    slots whenever every live slot lies at or past the window, as in every
    cell of this family; and ``recurrent_state``, every live slot's Mamba
    and convolution state read and written once."""
    import numpy as np
    assumed, c = config["assumed"], _counts(config)
    weight = np.dtype(config["serve"]["weight_dtype"]).itemsize
    compute = np.dtype(assumed["compute_dtype"]).itemsize
    row = 2 * c["kv_width"] * compute                  # a token's K and V
    state = c["mamba_layers"] * (
        c["state"] * np.dtype(assumed["recurrent_state_dtype"]).itemsize
        + c["conv_state"] * np.dtype(assumed["conv_state_dtype"]).itemsize)
    attended = cached_tokens + live_slots
    return {"weights": float(total_params(config) * weight),
            "shared_kv": float(c["shared_readers"] * row) * attended,
            "window_kv": float(c["window_layers"] * row) * min(
                attended, config["sliding_window"] * live_slots),
            "recurrent_state": 2.0 * live_slots * state}


# ------------------------------------------------------- the serving probe

serve_probe = _sibling("granitemoehybrid").serve_probe
