"""MoE layer: routing math, dropless dispatch, dense equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.moe import apply_moe, init_moe


@pytest.fixture
def cfg():
    return get_config("olmoe-1b-7b").reduced()  # 4 experts, top-2


def _dense_reference(params, x, cfg):
    """Per-token loop: route, run chosen experts densely, combine."""
    B, S, D = x.shape
    xt = np.asarray(x.reshape(-1, D), np.float64)
    logits = xt @ np.asarray(params["router"], np.float64)
    if cfg.router_scoring == "sigmoid":
        p = 1.0 / (1.0 + np.exp(-logits))
        sel = p + np.asarray(params["router_bias"], np.float64)
    else:
        p = np.exp(logits - logits.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        sel = p
    K = cfg.num_experts_per_tok
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        idx = np.argsort(-sel[t])[:K]
        w = p[t, idx] * cfg.routed_scaling_factor
        if cfg.norm_topk_prob:
            w = w / p[t, idx].sum()
        for j, e in enumerate(idx):
            g = xt[t] @ np.asarray(params["w_gate"][e], np.float64)
            u = xt[t] @ np.asarray(params["w_in"][e], np.float64)
            h = (g / (1 + np.exp(-g))) * u
            out[t] += w[j] * (h @ np.asarray(params["w_out"][e], np.float64))
    if "shared" in params:
        sp = params["shared"]
        g = xt @ np.asarray(sp["w_gate"], np.float64)
        u = xt @ np.asarray(sp["w_in"], np.float64)
        out += ((g / (1 + np.exp(-g))) * u) @ np.asarray(sp["w_out"],
                                                         np.float64)
    return out.reshape(B, S, D)


def test_moe_matches_dense_reference(cfg, rng):
    params = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)) * 0.5, jnp.float32)
    out, aux, _ = apply_moe(params, x, cfg)
    ref = _dense_reference(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_gate_weights_normalized_and_aux_positive(cfg, rng):
    params = init_moe(jax.random.key(1), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)), jnp.float32)
    out, aux, _ = apply_moe(params, x, cfg)
    assert np.isfinite(float(aux)) and float(aux) > 0
    assert out.shape == x.shape


def test_aux_loss_uniform_router_is_coef(cfg):
    """With a perfectly uniform router, the Switch aux loss equals the
    coefficient exactly (E * (1/E) * (1) ... normalised by K)."""
    params = init_moe(jax.random.key(2), cfg, jnp.float32)
    params = dict(params)
    params["router"] = jnp.zeros_like(params["router"])  # uniform probs
    x = jnp.ones((1, 64, cfg.d_model), jnp.float32)
    _, aux, _ = apply_moe(params, x, cfg)
    # me = 1/E; ce = K/E per expert... sum(me*ce)*E/K = 1 -> aux = coef
    assert float(aux) == pytest.approx(cfg.router_aux_coef, rel=1e-3)


def test_uneven_routing_drops_no_token(cfg, rng):
    """Every token routed to the same top-k experts (a router that only
    sees a constant feature): all T * K pairs land on K experts, far past
    any even share, and the layer still matches the per-token reference
    with no pair dropped."""
    params = dict(init_moe(jax.random.key(3), cfg, jnp.float32))
    router = np.zeros((cfg.d_model, cfg.num_experts), np.float32)
    router[0] = np.arange(cfg.num_experts, dtype=np.float32)
    params["router"] = jnp.asarray(router * 4.0)
    x = np.asarray(rng.normal(size=(2, 64, cfg.d_model)), np.float32) * 0.3
    x[..., 0] = 1.0
    x = jnp.asarray(x)
    out, _, cnt = apply_moe(params, x, cfg)
    K = cfg.num_experts_per_tok
    assert int(cnt["moe_pairs"]) == 2 * 64 * K
    assert int(cnt["moe_load_max"]) == 2 * 64
    ref = _dense_reference(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_shared_expert_path(rng):
    cfg = get_config("deepseek-v3-671b").reduced()
    assert cfg.router_scoring == "sigmoid"
    params = dict(init_moe(jax.random.key(4), cfg, jnp.float32))
    params["router_bias"] = jnp.asarray([0.3, -0.2, 0.1, 0.0])
    x = jnp.asarray(rng.normal(size=(1, 8, cfg.d_model)) * 0.3, jnp.float32)
    out, aux, _ = apply_moe(params, x, cfg)
    assert float(aux) == 0.0          # noaux_tc: no load-balance loss
    ref = _dense_reference(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_grads_flow_through_dispatch(cfg, rng):
    params = init_moe(jax.random.key(5), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 16, cfg.d_model)), jnp.float32)

    def loss(p):
        out, aux, _ = apply_moe(p, x, cfg)
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(params)
    gn = sum(float(jnp.abs(l).sum()) for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0
    assert float(jnp.abs(g["router"]).sum()) > 0  # router receives gradient


def test_experts_sharded_over_an_8_device_mesh_match_one_device(rng):
    """Cross-silo sharding on a (data 2, model 4) mesh: the routed
    experts stored one per ``model`` device and D over ``data``, tokens
    over ``data``. The layer gathers the experts where the grouped
    product runs (no expert parallelism), and its output and gradients
    equal one device's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.models.common import logical_rules
    from repro.sharding.spec import (LogicalRules, cross_silo,
                                     make_param_shardings)
    cfg = get_config("deepseek-v3-671b").reduced()
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    spec = cross_silo(mesh)
    params = {"moe": dict(init_moe(jax.random.key(6), cfg, jnp.float32))}
    params["moe"]["router_bias"] = jnp.asarray([0.3, -0.2, 0.1, 0.0])
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)) * 0.3,
                    jnp.float32)

    def loss(p, x):
        out, _, cnt = apply_moe(p["moe"], x, cfg)
        return jnp.sum(out ** 2), (out, cnt)

    step = jax.jit(jax.value_and_grad(loss, has_aux=True))
    with jax.default_matmul_precision("highest"):
        (_, (want, cnt1)), g1 = step(params, x)
        shard = make_param_shardings(spec, mesh, params)
        assert shard["moe"]["w_gate"].spec[0] == "model"
        ps = jax.device_put(params, shard)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        with logical_rules(LogicalRules(spec, mesh)):
            (_, (got, cnt8)), g8 = step(ps, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert {k: int(v) for k, v in cnt8.items()} == \
        {k: int(v) for k, v in cnt1.items()}
    for a, b in zip(jax.tree.leaves(g8), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
