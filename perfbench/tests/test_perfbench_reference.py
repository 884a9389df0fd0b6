"""The plain reference computes the function the program's model does,
and both sides of the comparison take the Δ-SGD hyperparameters from
the cell's traffic file, at a size the CPU holds."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness.common import load_cell  # noqa: E402

SMALL = {"d_model": 64, "decoder_layers": 2, "encoder_layers": 2,
         "decoder_attention_heads": 2, "encoder_attention_heads": 2,
         "decoder_ffn_dim": 128, "encoder_ffn_dim": 128,
         "vocab_size": 500, "max_source_positions": 24}


def small_cell(**mix):
    cell = load_cell("lm.whisper_tiny.fedtune4")
    cell.config = dict(cell.config, **SMALL)
    cell.mix = dict(cell.mix, seq=12, pool_rounds=1, **mix)
    return cell


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 3])
def test_reference_logits_match_the_program(seed):
    import jax
    import jax.numpy as jnp
    from drivers.fedtune import Build
    cell = small_cell()
    b = Build(cell)
    # the head is tied, as published: no separate output projection
    assert "lm_head" not in b.shapes
    params = b.weights(seed)
    batch = jax.tree.map(lambda a: jnp.asarray(a[0, 0]), b.pool(seed)[0])
    vocab = cell.config["vocab_size"]
    with jax.default_matmul_precision("highest"):
        want = cell.reference.logits(params, batch["tokens"],
                                     batch["frames"], vocab)
        got, _ = b.model.apply(params, batch)
    np.testing.assert_allclose(np.asarray(got)[..., :vocab],
                               np.asarray(want), rtol=0, atol=2e-4)


@pytest.mark.parametrize("hyper", [
    {"gamma": 2.0, "delta": 0.1, "eta0": 0.2, "theta0": 1.0},
    {"gamma": 1.5, "delta": 0.3, "eta0": 0.05, "theta0": 2.0}])
def test_both_sides_take_the_hyperparameters_of_the_mix(hyper):
    from drivers.fedtune import Build
    b = Build(small_cell(delta_sgd=hyper))
    assert b.hyper == hyper
    assert {k: b.copt.hyper[k] for k in hyper} == hyper
