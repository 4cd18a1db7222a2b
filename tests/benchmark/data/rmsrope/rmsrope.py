"""Family ``rmsrope``, a test fixture: a decoder of another block recipe
that the program already runs through ``GPT`` — RMSNorm, SwiGLU, rotary
positions, grouped-query heads, an untied head, no biases — with a
configuration file that has none of GPT-2's keys.  It shows that a family
is added as files: this one, its plain reference beside it, a configuration
and ``BENCHMARK.json`` entries; nothing under ``benchmark/harness/`` knows
its name.  The recipe is of a family the benchmark's configurations exclude;
it exists at a toy size for the tests and is in no ``BENCHMARK.json``.
"""
from __future__ import annotations

REFERENCE = "rmsrope_reference"

# float32 compute against the float32 reference at a toy size (this
# sandbox's CPU, three seeds: logits within 1.8e-7, token losses 9.5e-7, mean
# loss 9.5e-7): the tolerances are a toy's own, as a real family's are its
# own chip runs'.
TOLERANCES = {"logit": 1e-3, "min_agreement": 0.9, "loss": 1e-4,
              "token_loss": 1e-3}


def build_model(config, mesh=None):
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    assumed = config["assumed"]
    return GPT(GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        layer_norm_eps=config["rms_norm_eps"],
        rope_base=config["rope_theta"], position_embedding="rope",
        norm="rmsnorm", ffn_activation="swiglu", use_bias=False,
        tied_head=config["tie_word_embeddings"],
        dtype=jnp.dtype(assumed["compute_dtype"]),
        dropout_rate=assumed["dropout"], remat=assumed["remat"]), mesh=mesh)


def vocab_size(config) -> int:
    """A sliced vocabulary: the traffic draws from the ids that are served."""
    return config["served_vocab_size"]


def forward_logits(model, params, input_ids):
    return model.logits(params, model.apply(params, input_ids))


def shard_witness(params):
    return params["decoder"]["ffn"]["w_gate"]["kernel"]


def kernel_expected(config, program: str) -> bool:
    return bool(config["serve"]["paged_attention_kernel"])


def _body_params(config) -> int:
    d, inner = config["hidden_size"], config["intermediate_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    head_dim = d // heads
    per_layer = (2 * d * heads * head_dim + 2 * d * kv * head_dim
                 + 3 * d * inner + 2 * d)
    return config["num_hidden_layers"] * per_layer + d


def _head_params(config) -> int:
    return config["vocab_size"] * config["hidden_size"]


def train_flops_per_token(config, seq: int) -> float:
    return (6.0 * (_body_params(config) + _head_params(config))
            + 12.0 * config["num_hidden_layers"] * config["hidden_size"]
            * seq)


def serve_flops_per_token(config, context: float, head: bool = True) -> float:
    through = _body_params(config) + (_head_params(config) if head else 0)
    return (2.0 * through + 4.0 * config["num_hidden_layers"]
            * config["hidden_size"] * context)


def serve_probe(model, params, sched, context, decode_positions: int):
    """The scheduler calls the same ``GPT`` methods for this recipe as for
    GPT-2, so the probe is that family's, found beside the harness."""
    import os

    from harness import spec
    bench_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(spec.__file__))))
    gpt2 = spec.Benchmark(bench_root).load_module("families", "gpt2")
    return gpt2.serve_probe(model, params, sched, context, decode_positions)
