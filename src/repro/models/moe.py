"""Mixture-of-Experts layer: token-choice top-k routing over all experts,
dropless dispatch to the experts this layer holds, shared experts.

Routers (``cfg.router_scoring``):
  * ``softmax``: top-k of the softmax scores, a Switch-style
    load-balance loss (``router_aux_coef``);
  * ``sigmoid`` (DeepSeek-V3 ``noaux_tc``): s = sigmoid(x W_r), the
    top-k of s + b within the best ``router_topk_groups`` of
    ``router_groups`` expert groups, where the correction bias b only
    selects (no gradient) and no aux loss is added.
The selected scores are renormalised (``norm_topk_prob``) and scaled by
``routed_scaling_factor``. Router logits are float32 at the highest
matmul precision, as the published gates compute them.

Expert share (expert parallelism): the router spans all
``num_experts``; the layer holds experts [first_expert, first_expert +
held) and computes their part of the result for every token routed to
them. Token–expert pairs are sorted by held expert, the pairs of other
experts last, and one grouped matrix product per projection
(``kernels/grouped_matmul``) visits only the held groups' rows, so the
work follows the pairs routed here. No pair is dropped. On one chip the
share runs without its exchange: what the absent experts would add is
not computed.

``apply_moe`` returns (out, aux_loss, counters), the counters per call:
``moe_pairs`` (pairs routed to held experts) and ``moe_load_max`` (the
largest held expert's pairs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, split_keys

COUNTERS = ("moe_pairs", "moe_load_max")


def init_moe(key, cfg, dtype):
    D, E, F = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    Eh = cfg.held_experts
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), dtype, fan_in=D),
        "w_gate": dense_init(ks[1], (Eh, D, F), dtype, fan_in=D),
        "w_in": dense_init(ks[2], (Eh, D, F), dtype, fan_in=D),
        "w_out": dense_init(ks[3], (Eh, F, D), dtype, fan_in=F),
    }
    if cfg.router_scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), dtype)
    if cfg.num_shared_experts:
        Fs = cfg.expert_d_ff * cfg.num_shared_experts
        sk = split_keys(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(sk[0], (D, Fs), dtype, fan_in=D),
            "w_in": dense_init(sk[1], (D, Fs), dtype, fan_in=D),
            "w_out": dense_init(sk[2], (Fs, D), dtype, fan_in=Fs),
        }
    return p


def route(params, xt, cfg):
    """xt (T, D) -> (expert ids (T, K), weights (T, K) f32, aux loss)."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    aux = jnp.zeros((), jnp.float32)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + jax.lax.stop_gradient(
            params["router_bias"].astype(jnp.float32))
        if cfg.router_groups > 1:
            T, G = sel.shape[0], cfg.router_groups
            grouped = sel.reshape(T, G, E // G)
            gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, keep = jax.lax.top_k(gscore, cfg.router_topk_groups)
            gmask = jnp.zeros((T, G), bool).at[
                jnp.arange(T)[:, None], keep].set(True)
            sel = jnp.where(jnp.repeat(gmask, E // G, axis=1), sel,
                            -jnp.inf)
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(sel), K)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, K)
        # Switch load-balance loss, generalised to top-k
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                              axis=1), axis=0)
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce) / K
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg.routed_scaling_factor, aux


def dispatch(idx, cfg):
    """Sort the (T*K,) token–expert pairs by held expert, the pairs of
    experts not held last. Returns (order, group sizes (held + 1,),
    counters)."""
    Eh = cfg.held_experts
    local = idx.reshape(-1) - cfg.first_expert
    held = (local >= 0) & (local < Eh)
    gid = jnp.where(held, local, Eh).astype(jnp.int32)
    order = jnp.argsort(gid, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[gid].add(1)
    counters = {"moe_pairs": jnp.sum(sizes[:Eh]),
                "moe_load_max": jnp.max(sizes[:Eh])}
    return order, sizes, counters


def apply_moe(params, x, cfg):
    """x: (B,S,D) -> (out, aux_loss, counters); see the module doc."""
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    B, S, D = x.shape
    K = cfg.num_experts_per_tok
    xt = x.reshape(B * S, D)
    with jax.named_scope("moe_route"):
        idx, w, aux = route(params, xt, cfg)
        order, sizes, counters = dispatch(idx, cfg)
        tok = order // K
        xs = xt[tok]                                  # (T*K, D) sorted
        ws = w.reshape(-1)[order].astype(x.dtype)
    with jax.named_scope("moe_experts"):
        h = (jax.nn.silu(grouped_matmul(xs, params["w_gate"], sizes))
             * grouped_matmul(xs, params["w_in"], sizes))
        ys = grouped_matmul(h, params["w_out"], sizes)
    with jax.named_scope("moe_combine"):
        out = jnp.zeros((B * S, D), x.dtype).at[tok].add(ys * ws[:, None])
    if "shared" in params:
        with jax.named_scope("shared_experts"):
            sp = params["shared"]
            h = jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_in"])
            out = out + h @ sp["w_out"]
    return out.reshape(B, S, D), aux, counters
