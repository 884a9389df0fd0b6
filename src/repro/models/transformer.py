"""Layer-stack machinery: block dispatch, segment runs, shared blocks.

Layers are grouped into *runs* of consecutive identical block types
(cfg.layer_types). Each run's params are stacked on a leading axis and
applied with ``lax.scan`` — one trace per run, so an 81-layer hybrid
compiles like a handful of blocks. ``shared_attn`` blocks (Zamba2) hold a
single global param set referenced by every occurrence.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm
from repro.models.common import (apply_norm, dense_init, init_norm,
                                 scan_unroll, shard_logical, split_keys,
                                 swiglu)


def segment_runs(layer_types: Tuple[str, ...]) -> List[Tuple[str, int]]:
    runs: List[Tuple[str, int]] = []
    for t in layer_types:
        if runs and runs[-1][0] == t:
            runs[-1] = (t, runs[-1][1] + 1)
        else:
            runs.append((t, 1))
    return runs


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(key, cfg, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    if cfg.mlp_variant == "swiglu":
        return {"w_gate": dense_init(ks[0], (D, F), dtype, fan_in=D),
                "w_in": dense_init(ks[1], (D, F), dtype, fan_in=D),
                "w_out": dense_init(ks[2], (F, D), dtype, fan_in=F)}
    return {"w_in": dense_init(ks[0], (D, F), dtype, fan_in=D),
            "b_in": jnp.zeros((F,), dtype),
            "w_out": dense_init(ks[1], (F, D), dtype, fan_in=F),
            "b_out": jnp.zeros((D,), dtype)}


def apply_mlp(params, x, cfg):
    if "w_gate" in params:
        h = swiglu(x @ params["w_gate"], x @ params["w_in"])
        h = shard_logical(h, ("batch", "seq", "ffn"))
        return h @ params["w_out"]
    h = jax.nn.gelu((x @ params["w_in"] + params["b_in"])
                    .astype(jnp.float32)).astype(x.dtype)
    h = shard_logical(h, ("batch", "seq", "ffn"))
    return h @ params["w_out"] + params["b_out"]


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------
def init_block(key, cfg, btype: str, dtype, *, decoder: bool = False):
    ks = split_keys(key, 6)
    if btype in ("attn", "shared_attn", "moe"):
        attn_init = attn.init_mla if cfg.use_mla else attn.init_attention
        p = {"ln1": init_norm(ks[0], cfg, dtype),
             "attn": attn_init(ks[1], cfg, dtype),
             "ln2": init_norm(ks[2], cfg, dtype)}
        if btype == "moe":
            from repro.models.moe import init_moe
            p["moe"] = init_moe(ks[3], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[3], cfg, dtype)
        if decoder and cfg.cross_attention:
            p["ln_x"] = init_norm(ks[4], cfg, dtype)
            p["xattn"] = attn.init_cross_attention(ks[5], cfg, dtype)
        return p
    if btype == "mamba2":
        return {"ln": init_norm(ks[0], cfg, dtype),
                "mixer": ssm.init_mamba2(ks[1], cfg, dtype)}
    if btype == "mlstm":
        return {"ln": init_norm(ks[0], cfg, dtype),
                "mixer": ssm.init_mlstm(ks[1], cfg, dtype)}
    if btype == "slstm":
        return {"ln": init_norm(ks[0], cfg, dtype),
                "mixer": ssm.init_slstm(ks[1], cfg, dtype)}
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# Block apply — full-sequence mode
# ---------------------------------------------------------------------------
def block_full(params, x, cfg, btype, *, positions, window=None,
               build_cache=False, enc_kv=None, causal=True,
               use_pallas=False):
    """Returns (x, cache, aux_loss, counters); the counters are the MoE
    layer's (``moe.COUNTERS``), empty for every other block."""
    aux = jnp.zeros((), jnp.float32)
    counters = {}
    if btype in ("attn", "shared_attn", "moe"):
        h = apply_norm(params["ln1"], x, cfg)
        if cfg.use_mla:
            a, cache = attn.mla_full(params["attn"], h, cfg,
                                     positions=positions, window=window,
                                     build_cache=build_cache,
                                     use_pallas=use_pallas)
        else:
            if not causal:
                a, cache = _bidir_attn(params["attn"], h, cfg, positions)
            else:
                a, cache = attn.gqa_full(params["attn"], h, cfg,
                                         positions=positions, window=window,
                                         build_cache=build_cache,
                                         use_pallas=use_pallas)
        x = x + a
        if enc_kv is not None:
            h = apply_norm(params["ln_x"], x, cfg)
            x = x + attn.cross_attend(params["xattn"], h, cfg, enc_kv)
        h = apply_norm(params["ln2"], x, cfg)
        if btype == "moe":
            from repro.models.moe import apply_moe
            m, aux, counters = apply_moe(params["moe"], h, cfg)
        else:
            m = apply_mlp(params["mlp"], h, cfg)
        x = x + m
        return x, cache, aux, counters
    h = apply_norm(params["ln"], x, cfg)
    fn = {"mamba2": ssm.mamba2_full, "mlstm": ssm.mlstm_full,
          "slstm": ssm.slstm_full}[btype]
    if btype == "mamba2":
        m, cache = fn(params["mixer"], h, cfg, build_cache=build_cache,
                      use_pallas=use_pallas)
    else:
        m, cache = fn(params["mixer"], h, cfg, build_cache=build_cache)
    return x + m, cache, aux, counters


def _bidir_attn(params, x, cfg, positions):
    """Non-causal attention (Whisper encoder)."""
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.rope_theta:
        q = attn.apply_rope(q, positions, cfg.rope_theta)
        k = attn.apply_rope(k, positions, cfg.rope_theta)
    qg = q.reshape(B, S, KV, H // KV, hd)
    out = attn._sdpa(qg, k, v, causal=False).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), None


# ---------------------------------------------------------------------------
# Block apply — single decode step
# ---------------------------------------------------------------------------
def block_step(params, x, cfg, btype, cache, *, t, slot, positions_buf,
               window=None, enc_kv=None):
    if btype in ("attn", "shared_attn", "moe"):
        h = apply_norm(params["ln1"], x, cfg)
        if cfg.use_mla:
            a, cache = attn.mla_step(params["attn"], h, cfg, cache, t=t,
                                     slot=slot, positions_buf=positions_buf,
                                     window=window)
        else:
            a, cache = attn.gqa_step(params["attn"], h, cfg, cache, t=t,
                                     slot=slot, positions_buf=positions_buf,
                                     window=window)
        x = x + a
        if enc_kv is not None:
            h = apply_norm(params["ln_x"], x, cfg)
            x = x + attn.cross_attend(params["xattn"], h, cfg, enc_kv)
        h = apply_norm(params["ln2"], x, cfg)
        if btype == "moe":
            from repro.models.moe import apply_moe
            m, _, _ = apply_moe(params["moe"], h, cfg)
        else:
            m = apply_mlp(params["mlp"], h, cfg)
        return x + m, cache
    h = apply_norm(params["ln"], x, cfg)
    fn = {"mamba2": ssm.mamba2_step, "mlstm": ssm.mlstm_step,
          "slstm": ssm.slstm_step}[btype]
    m, cache = fn(params["mixer"], h, cfg, cache)
    return x + m, cache


# ---------------------------------------------------------------------------
# Stack: init / full / step over segment runs
# ---------------------------------------------------------------------------
def init_stack(key, cfg, dtype, *, layer_types=None, decoder=False):
    layer_types = layer_types or cfg.layer_types
    runs = segment_runs(layer_types)
    keys = split_keys(key, len(runs) + 1)
    params = {}
    shared = None
    for i, (btype, n) in enumerate(runs):
        if btype == "shared_attn":
            if shared is None:
                shared = init_block(keys[-1], cfg, btype, dtype,
                                    decoder=decoder)
                params["shared_attn"] = shared
            continue
        if n == 1:
            params[f"run{i}"] = init_block(keys[i], cfg, btype, dtype,
                                           decoder=decoder)
        else:
            ks = jnp.stack(split_keys(keys[i], n))
            params[f"run{i}"] = jax.vmap(
                lambda k: init_block(k, cfg, btype, dtype, decoder=decoder)
            )(ks)
    return params


def add_counters(total, counters):
    """``total`` with one more layer's ``counters`` taken in
    (``common.counter_total``)."""
    from repro.models.common import counter_total
    out = dict(total)
    for k, v in counters.items():
        out[k] = (counter_total(k, jnp.stack([out[k], v])) if k in out
                  else v)
    return out


def stack_full(params, x, cfg, *, layer_types=None, positions, window=None,
               build_cache=False, enc_kv=None, causal=True,
               use_pallas=False):
    """Returns (x, cache_dict, total_aux, counters): the blocks' counters
    over the stack (``add_counters``), empty without MoE layers."""
    from repro.models.common import remat_on
    layer_types = layer_types or cfg.layer_types
    runs = segment_runs(layer_types)
    caches = {}
    aux_total = jnp.zeros((), jnp.float32)
    counters = {}

    def make_apply(btype):
        """Per-block apply; checkpointed under ``remat_blocks``:
        production policy, keep only the residual stream between
        layers."""
        def apply_one(pl, xx, ekv):
            return block_full(pl, xx, cfg, btype, positions=positions,
                              window=window, build_cache=build_cache,
                              enc_kv=ekv, causal=causal,
                              use_pallas=use_pallas)
        return jax.checkpoint(apply_one) if remat_on() else apply_one

    for i, (btype, n) in enumerate(runs):
        x = shard_logical(x, ("batch", "seq", "embed"))  # residual stream
        apply_one = make_apply(btype)
        if btype == "shared_attn":
            # params shared across occurrences; caches are NOT (each site
            # attends over its own history).
            p = params["shared_attn"]
            cs = []
            for _ in range(n):
                x, c, aux, cnt = apply_one(p, x, None)
                aux_total += aux
                counters = add_counters(counters, cnt)
                cs.append(c)
            if build_cache:
                caches[f"run{i}"] = jax.tree.map(
                    lambda *ys: jnp.stack(ys), *cs)
            continue
        p = params[f"run{i}"]
        if n == 1:
            x, c, aux, cnt = apply_one(p, x, _slice_enc(enc_kv, 0))
            aux_total += aux
            counters = add_counters(counters, cnt)
            if build_cache:
                caches[f"run{i}"] = jax.tree.map(lambda y: y[None], c)
        else:
            def body(carry, xs):
                xx, auxx, cntx = carry
                pl, ekv = xs
                xx, c, aux, cnt = apply_one(pl, xx, ekv)
                return (xx, auxx + aux, add_counters(cntx, cnt)), c

            if btype == "moe":
                from repro.models.moe import COUNTERS
                run_cnt = {k: jnp.zeros((), jnp.int32) for k in COUNTERS}
            else:
                run_cnt = {}
            (x, aux_total, run_cnt), cs = jax.lax.scan(
                body, (x, aux_total, run_cnt), (p, enc_kv),
                unroll=scan_unroll())
            counters = add_counters(counters, run_cnt)
            if build_cache:
                caches[f"run{i}"] = cs
    return x, (caches if build_cache else None), aux_total, counters


def stack_step(params, x, cfg, caches, *, layer_types=None, t, slot,
               positions_buf, window=None, enc_kv=None):
    layer_types = layer_types or cfg.layer_types
    runs = segment_runs(layer_types)
    new_caches = {}
    for i, (btype, n) in enumerate(runs):
        key = f"run{i}"
        if btype == "shared_attn":
            p = params["shared_attn"]

            def body(xx, cl):
                xx, cl = block_step(p, xx, cfg, btype, cl, t=t, slot=slot,
                                    positions_buf=positions_buf,
                                    window=window)
                return xx, cl

            x, cs = jax.lax.scan(body, x, caches[key],
                                 unroll=scan_unroll())
            new_caches[key] = cs
            continue
        p = params[key]
        if n == 1:
            c = jax.tree.map(lambda y: y[0], caches[key])
            x, c = block_step(p, x, cfg, btype, c, t=t, slot=slot,
                              positions_buf=positions_buf, window=window,
                              enc_kv=_slice_enc(enc_kv, 0))
            new_caches[key] = jax.tree.map(lambda y: y[None], c)
        else:
            def body(xx, xs):
                pl, cl, ekv = xs
                xx, cl = block_step(pl, xx, cfg, btype, cl, t=t, slot=slot,
                                    positions_buf=positions_buf,
                                    window=window, enc_kv=ekv)
                return xx, cl

            x, cs = jax.lax.scan(body, x, (p, caches[key], enc_kv),
                                 unroll=scan_unroll())
            new_caches[key] = cs
    return x, new_caches


def _shared_run_key(runs):
    return "shared"


def _slice_enc(enc_kv, layer_idx):
    """enc_kv is stacked per layer (num_layers, ...) for cross-attention."""
    if enc_kv is None:
        return None
    return jax.tree.map(lambda e: e[layer_idx], enc_kv)
