"""The controls: the plain reference put in the program's place and
computed in bfloat16, the nearest precision below the float32 the
configuration states, comes out not correct against the cells' own
limits, at a size the CPU holds. (On the chip the same controls run at
the cells' sizes through ``perfbench/tools/calibrate.py``.)"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import fedref  # noqa: E402
from harness.common import load_cell  # noqa: E402

SMALL = {"d_model": 64, "decoder_layers": 2, "encoder_layers": 2,
         "decoder_attention_heads": 2, "encoder_attention_heads": 2,
         "decoder_ffn_dim": 128, "encoder_ffn_dim": 128,
         "vocab_size": 500, "max_source_positions": 24}


def failed(nums, limits):
    return [k for k, lim in limits.items() if nums[k] > lim]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
def test_training_control_fails(seed):
    from drivers.fedtune import Build
    cell = load_cell("lm.whisper_tiny.fedtune4")
    cell.config = dict(cell.config, **SMALL)
    cell.mix = dict(cell.mix, seq=12, pool_rounds=2)
    b = Build(cell)
    pool = b.pool(seed)
    ref = b.reference(seed, pool)
    c_rows, c_change, _ = b.reference(seed, pool, dtype=jnp.bfloat16)
    control = fedref.compare(c_rows, c_change, *ref)
    assert failed(control, cell.limits), control
