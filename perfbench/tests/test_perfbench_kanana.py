"""The Kanana-2 share: the program's model against the plain reference
(logits, loss, gradients), the expert shares against the uncut layer,
dropless routing, the correction bias, one C = 1 round of the fused loop
against the reference rounds, and the cell's files, at a size the CPU
holds (d 64, 4 heads, 16 experts of which 4 are held, vocabulary 500)."""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import fedref  # noqa: E402
from harness.common import load_cell, load_json  # noqa: E402

CELL = "lm.kanana2_30b_a3b.silo8k"
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "qk_head_dim": 24, "moe_intermediate_size": 32,
         "router_width": 16, "n_routed_experts": 4, "num_hidden_layers": 3,
         "vocab_size": 500}


def small_cell(**config):
    cell = load_cell(CELL)
    cell.config = dict(cell.config, **SMALL, **config)
    cell.mix = dict(cell.mix, batch=2, seq=16, pool_rounds=2)
    return cell


@pytest.fixture(scope="module")
def build():
    from drivers.fedtune_lm import Build
    return Build(small_cell())


def _batch(b, seed):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: jnp.asarray(a[0, 0]), b.pool(seed)[0])


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 3])
def test_reference_logits_loss_and_grads_match_the_program(build, seed):
    import jax
    cfg, ref = build.cfg, build.ref
    params = build.weights(seed)
    batch = _batch(build, seed)
    vocab = cfg["vocab_size"]
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, batch["tokens"], cfg)
        got, _ = build.model.apply(params, batch)
        np.testing.assert_allclose(np.asarray(got)[..., :vocab],
                                   np.asarray(want), rtol=0, atol=2e-5)
        lw, gw = jax.value_and_grad(ref.loss)(params, batch, cfg)
        lp, gp = jax.value_and_grad(
            lambda p: build.model.loss(p, batch)[0])(params)
    assert float(lp) == pytest.approx(float(lw), rel=1e-6)
    for path, a in jax.tree_util.tree_leaves_with_path(gw):
        b = gp
        for k in path:
            b = b[k.key]
        scale = float(np.max(np.abs(np.asarray(a)))) or 1.0
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, rtol=0, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def _moe_inputs(build, seed):
    import jax
    import jax.numpy as jnp
    params = build.weights(seed)
    layer = jax.tree.map(lambda a: a[0], params["stack"]["run1"]["moe"])
    x = jax.random.normal(jax.random.key(seed), (2, 16, 64), jnp.float32)
    return layer, x


def _uncut(build, layer, seed):
    """The whole layer's 16 experts (the share's 4 and 12 more drawn
    from the seed) and the reference's cfg for it."""
    import jax
    import jax.numpy as jnp
    extra = {k: jax.random.normal(jax.random.fold_in(jax.random.key(seed), i),
                                  (12,) + layer[k].shape[1:], jnp.float32)
             / np.sqrt(layer[k].shape[1])
             for i, k in enumerate(("w_gate", "w_in", "w_out"))}
    whole = dict(layer, **{k: jnp.concatenate([layer[k], extra[k]])
                           for k in extra})
    return whole, dict(build.cfg, n_routed_experts=16)


def test_the_four_expert_shares_add_up_to_the_uncut_layer(build):
    """Experts [0, 4), [4, 8), [8, 12), [12, 16): each share's output,
    with the shared experts counted once, sums to what the reference
    gives for the whole layer."""
    import dataclasses

    import jax
    from repro.models.moe import apply_moe
    layer, x = _moe_inputs(build, 11)
    whole, whole_cfg = _uncut(build, layer, 11)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for e0 in (0, 4, 8, 12):
            cfg = dataclasses.replace(build.mcfg, first_expert=e0)
            share = dict(whole, **{k: whole[k][e0:e0 + 4]
                                   for k in ("w_gate", "w_in", "w_out")})
            out, aux, cnt = apply_moe(share, x, cfg)
            assert float(aux) == 0.0
            total = total + out
        shared = build.ref.swiglu(layer["shared"], x.reshape(-1, 64))
        want = jax.vmap(lambda s: build.ref.moe(whole, s, whole_cfg))(x)
    got = np.asarray(total) - 3 * np.asarray(shared).reshape(x.shape)
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=2e-5)


def test_every_token_on_one_held_expert_drops_none(build):
    """A correction bias that puts held expert 2 in every token's top 6:
    its load is every token of the batch, far past an even share, and
    the layer still matches the reference with no pair dropped."""
    import jax
    from repro.models.moe import apply_moe
    layer, x = _moe_inputs(build, 12)
    layer = dict(layer, router_bias=layer["router_bias"].at[2].set(10.0))
    with jax.default_matmul_precision("highest"):
        out, _, cnt = apply_moe(layer, x, build.mcfg)
        want = jax.vmap(lambda s: build.ref.moe(layer, s, build.cfg))(x)
    assert int(cnt["moe_load_max"]) == 32
    assert 32 <= int(cnt["moe_pairs"]) <= 32 * 4
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                               atol=2e-5)


def test_the_correction_bias_selects_but_does_not_weigh(build):
    """A bias moves which experts a token uses; the weights stay the
    normalised, scaled sigmoid scores of the experts selected, and a
    bias equal for every expert changes nothing."""
    import jax
    import jax.numpy as jnp
    from repro.models.moe import route
    layer, x = _moe_inputs(build, 13)
    xt = x.reshape(-1, 64)
    s = jax.nn.sigmoid(jnp.dot(xt, layer["router"],
                               precision=jax.lax.Precision.HIGHEST))
    zero = dict(layer, router_bias=jnp.zeros(16))
    idx0, w0, _ = route(zero, xt, build.mcfg)
    idx1, w1, _ = route(layer, xt, build.mcfg)
    idx2, w2, _ = route(dict(layer, router_bias=layer["router_bias"] + 0.3),
                        xt, build.mcfg)
    assert bool(jnp.any(idx0 != idx1))
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    for idx, w in ((idx0, w0), (idx1, w1)):
        picked = jnp.take_along_axis(s, idx, axis=-1)
        want = 2.448 * picked / jnp.sum(picked, axis=-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w), np.asarray(want),
                                   rtol=1e-6)
    g = jax.grad(lambda p: jnp.sum(route(p, xt, build.mcfg)[1] ** 2))(layer)
    assert float(jnp.abs(g["router_bias"]).sum()) == 0.0
    assert float(jnp.abs(g["router"]).sum()) > 0.0


def test_one_round_of_the_fused_loop_matches_the_reference(build):
    """C = 1: the first block (2 rounds of 2 local steps) through the
    program's block driver against ``fedref.run_rounds``, with the
    cell's own limits, and the counters in every round's row."""
    from drivers.fedtune_lm import counters
    from harness.common import Spans
    seed = 2 ** 31 + 7
    pool = build.pool(seed)
    rows, change = build.first_block(seed, pool, Spans())
    build.state = None
    nums = fedref.compare(rows, change, *build.reference(seed, pool))
    limits = build.cell.limits
    assert all(nums[k] <= lim / 10 for k, lim in limits.items()), nums
    cnt = counters(rows)
    assert 0 < cnt["pairs"] <= 2 * 2 * 2 * 16 * 6 * 2
    assert all("moe_pairs" in r for r in rows)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7, 4000000007])
def test_the_fine_tune_keeps_routing_tokens_by_their_identity(build, seed):
    """Three blocks of the fused loop from the cell's weights: no held
    expert ever takes every token of a layer (the embedding's scale
    keeps each token's identity in the router's input, where at 0.02
    the first rounds fold every token onto the same experts), and a
    round routes as many pairs here as the round before it on the same
    batches, to within 5 %."""
    from harness.common import Spans
    pool = build.pool(seed)
    rows, _ = build.first_block(seed, pool, Spans())
    build.advance(4, pool, Spans(), rows)
    build.state = None
    tokens = build.mix["batch"] * build.mix["seq"]
    assert max(r["moe_load_max"] for r in rows) < tokens, rows
    pairs = [r["moe_pairs"] for r in rows]
    n = len(pool)
    for a, b in zip(pairs, pairs[n:]):
        assert abs(b - a) <= 0.05 * a, pairs


def test_cell_files_hold_the_catalog_config():
    """Every number of the catalog's config for
    kanana-2-30b-a3b-instruct-2601, under its own key, except the three
    cut keys, which ``reduced`` lists with their published values."""
    cell = load_cell(CELL)
    cfg = cell.config
    published = {
        "attention_bias": False, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    cut = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 16032}
    assert set(cfg["reduced"]) == set(cut)
    for k, v in published.items():
        assert cfg[k] == cut.get(k, v), k
        if k in cut:
            assert cfg["published"][k] == v, k
    assert cfg["router_width"] == 128 and cfg["first_expert"] == 0
    assert cell.mix["kind"] == "fedtune_lm"
    assert os.path.exists(os.path.join(BENCH, "drivers", "fedtune_lm.py"))
    assert set(cell.limits) == {"loss", "eta", "change"}
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana2_30b_a3b")
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == set(cut)
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_ms_per_round", "moe_route_ms_per_round",
            "moe_experts_ms_per_round", "moe_experts_roofline",
            "mfu.train", "delta_sgd_roofline",
            "delta_sgd_ms_per_round"} <= names


def test_the_program_config_is_the_files():
    """The program's ``kanana-2-30b-a3b`` holds the file's sizes, and
    the file maps onto it unchanged."""
    from drivers.fedtune import program_config
    from repro.configs import get_config
    cell = load_cell(CELL)
    base = get_config("kanana-2-30b-a3b")
    assert program_config(cell.config) == base
    assert base.layer_types == ("attn",) + ("moe",) * 4
    assert (base.q_lora_rank, base.rope_interleave, base.router_scoring,
            base.remat) == (0, True, "sigmoid", True)
    assert base.padded_vocab == 16128
    assert json.loads(json.dumps(cell.mix))["batch"] * cell.mix["seq"] \
        == 8192
