"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture (2 layers — or one pattern cycle — d_model ≤ 512,
≤ 4 experts) runs one forward and one federated train step on CPU with
correct output shapes and no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, FLConfig, get_config
from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_round, make_loss)
from repro.models import build_model

# heavyweight tier: CI runs -m 'not slow' first (scripts/ci.sh)
pytestmark = pytest.mark.slow

B, S = 2, 32


def _batch(cfg, rng, lead=()):
    out = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           lead + (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           lead + (B, S)), jnp.int32),
    }
    if cfg.encoder_layers:
        out["frames"] = jnp.asarray(
            rng.normal(size=lead + (B, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)
    if cfg.num_image_tokens:
        out["image_embeds"] = jnp.asarray(
            rng.normal(size=lead + (B, cfg.num_image_tokens, cfg.d_model)),
            jnp.float32)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch, rng):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 512 and (not cfg.num_experts
                                   or cfg.num_experts <= 4)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    logits, aux = jax.jit(model.apply)(params, _batch(cfg, rng))
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    loss, metrics = model.loss(params, _batch(cfg, rng))
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_federated_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    fl = FLConfig(local_steps=2)
    copt = get_client_opt("delta_sgd", fl)
    sopt = get_server_opt("fedavg")
    loss_fn = make_loss(lambda p, b: model.loss(p, b))
    rnd = jax.jit(make_fl_round(loss_fn, copt, sopt, num_rounds=10))
    params = model.init(jax.random.key(0))
    state = init_fl_state(params, sopt)
    C = 2
    batches = _batch(cfg, rng, lead=(C, fl.local_steps))
    state, metrics, _ = rnd(state, batches)
    assert np.isfinite(float(metrics["loss"]))
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert not bool(jnp.isnan(leaf).any())
    # params actually moved
    moved = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(params)))
    assert moved > 0


def test_param_counts_match_configs():
    """Analytic counts ballpark the advertised sizes (vocab padding and
    simplifications shift them slightly)."""
    expect = {"tinyllama-1.1b": (0.9e9, 1.4e9),
              "qwen2.5-14b": (12e9, 17e9),
              "granite-20b": (18e9, 24e9),
              "deepseek-v3-671b": (600e9, 760e9),
              "olmoe-1b-7b": (5e9, 8.5e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params_smaller():
    cfg = get_config("deepseek-v3-671b")
    assert cfg.active_param_count() < 0.12 * cfg.param_count()


def test_remat_has_one_switch():
    """Per-block remat and MLA's query-chunk recompute both follow
    ``common.remat_blocks``: a config with ``remat`` set traces the
    same training program as the config without it called under
    ``remat_blocks``, and both recompute more than the plain one."""
    from repro.models.common import remat_blocks
    cfg = get_config("kanana-2-30b-a3b").reduced()
    assert cfg.remat
    off = dataclasses.replace(cfg, remat=False)
    seq = jnp.zeros((1, 2048), jnp.int32)       # long enough to chunk
    batch = {"tokens": seq, "labels": seq}

    def program(c):
        model = build_model(c, jnp.float32)
        params = jax.eval_shape(model.init, jax.random.key(0))
        return str(jax.make_jaxpr(
            jax.grad(lambda p: model.loss(p, batch)[0]))(params))

    on = program(cfg)
    with remat_blocks(True):
        asked = program(off)
    plain = program(off)
    assert on == asked
    # a checkpoint a block, and more around MLA's query chunks
    assert plain.count("remat2[") == 0
    assert on.count("remat2[") > len(cfg.layer_types)
