"""The control of ``correct`` at a toy size: the reference at the next lower
precision in the program's place, through the drivers' own comparisons
(``control_readings.py``; the chip's readings at the cells' sizes are in
PERF.md)."""
import json

import numpy as np
import pytest

import bench_paths
import control_readings
from harness import common, spec

BENCH = spec.Benchmark(bench_paths.ROOT)


@pytest.fixture(scope="module")
def toy():
    import jax
    cell = BENCH.cell("gpt2-xl.chat_sessions")
    family = BENCH.family(cell)
    config = dict(cell.config, n_embd=64, n_head=2, n_layer=2,
                  n_positions=128, n_ctx=128, vocab_size=512)
    config["assumed"] = dict(config["assumed"], compute_dtype="float32")
    params = jax.jit(family.build_model(config).init)(common.prng_key(7))
    rng = np.random.default_rng(7)
    ids = {"probe": rng.integers(0, 512, (1, 48), dtype=np.int32),
           "turns": rng.integers(0, 512, (2, 128), dtype=np.int32),
           "train": rng.integers(0, 512, (2, 65), dtype=np.int32)}
    return family, config, params, ids


def _read(toy, control, logit_tol=0.01):
    family, config, params, ids = toy
    return control_readings.readings(
        family.reference, config, params, control, ids["probe"],
        ids["turns"], ids["train"], logit_tol, tail=9)


def test_the_reference_in_its_own_place_reads_nought(toy):
    out = _read(toy, toy[2])
    assert out["logit_max_abs_err"] == out["token_loss_max_abs_err"] == 0
    assert out["token_agreement_share"] == 1.0
    assert out["token_positions_clear_wrong"] == 0


@pytest.mark.parametrize("how", ["int8", "fp8"])
def test_rounding_to_eight_bits_touches_matrices_only(toy, how):
    import jax
    params = toy[2]
    control = control_readings.rounded(params, how)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, before), after in zip(flat, jax.tree.leaves(control)):
        name = path[-1].key
        assert after.dtype == before.dtype and after.shape == before.shape
        same = bool(np.array_equal(np.asarray(before), np.asarray(after)))
        assert same == (name not in control_readings.MATRICES), name
        if how == "int8" and not same:
            # at most 255 levels per output channel of a layer
            a = np.asarray(after)
            a = a[0] if a.ndim >= 3 else a    # one layer of a stacked leaf
            assert len(np.unique(a.reshape(-1, a.shape[-1])[:, 0])) <= 255


@pytest.mark.parametrize("how", ["int8", "fp8"])
def test_the_control_is_read_by_the_drivers_comparisons(toy, how):
    """Both controls move every compared number, fp8 by more than int8; the
    readings at the cells' own sizes, and which limits they fail, are the
    chip's (PERF.md)."""
    out = _read(toy, control_readings.rounded(toy[2], how))
    assert out["logit_max_abs_err"] > 1e-4
    assert out["token_loss_max_abs_err"] > 1e-4
    assert 0 < out["token_agreement_share"] < 1.0
    json.dumps(out)
    if how == "fp8":
        int8 = _read(toy, control_readings.rounded(toy[2], "int8"))
        assert out["logit_max_abs_err"] > int8["logit_max_abs_err"]
        assert out["token_loss_max_abs_err"] > int8["token_loss_max_abs_err"]
