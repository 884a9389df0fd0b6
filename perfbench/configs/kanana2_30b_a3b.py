"""Plain reference of one chip's share of Kanana-2-30B-A3B as this
repository runs it, the benchmark's weights for it, and its operation
counts.

Written from the published description in plain ``jax.numpy``, in
float32 (the driver runs it under ``jax.default_matmul_precision(
"highest")``); it imports nothing of the program. ``model_type``
deepseek_v3 (arXiv:2412.19437 §2.1), latent attention without a query
LoRA (arXiv:2405.04434 §2.1), the sizes of ``kanana2_30b_a3b.json``:

* token embedding; 5 pre-norm blocks (RMSNorm, ε 1e-6), the first with
  a SwiGLU MLP and the others with the MoE layer; final RMSNorm and an
  untied head over the vocabulary slice;
* attention: q = x W_q split into 128 dims without position and 64
  rotary dims; [c, k_pe] = x W_kva with the 512-wide latent c
  RMS-normed; k_nope = c W_kb, v = c W_vb per head; the rotary dims of
  q and of the one shared k_pe rotated in DeepSeek-V3's interleaved
  form (θ 1e6); causal softmax over q·k / sqrt(192);
* MoE: s = sigmoid(x W_r) over all 128 experts, the experts of the top 6
  of s + b, weights 2.448 · s / (sum of the 6 selected s); this share
  holds experts [0, 8): a dense loop over them with explicit masks, no
  capacity, and the 2 shared experts (width 1536) on every token.

``loss`` computes the mean next-token cross-entropy one sequence at a
time, each block rematerialised, so that a client step's gradient fits
the chip beside nothing else. The switches of ``flags`` plant the
faults the cell's limits are calibrated against (``perfbench/tools/
calibrate_lm.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
BIAS_STD = 0.05      # correction bias: enough to move selections
EMBED_STD = 10.0     # token rows: identity outweighs the context means


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _leaf_init(names, shape, key):
    leaf = names[-1]
    stacked = "run1" in names           # leading axis: the MoE layers
    core = shape[1:] if stacked else shape
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("scale", "kv_norm"):
        return 1.0 + 0.02 * normal
    if leaf == "router_bias":
        return BIAS_STD * normal
    if leaf == "embed":
        return EMBED_STD * normal
    if leaf == "wo":                    # (H, dv, D)
        fan_in = core[0] * core[1]
    elif "moe" in names and "shared" not in names and leaf != "router":
        fan_in = core[1]                # (experts, in, out)
    else:                               # (in, ...)
        fan_in = core[0]
    return normal / math.sqrt(fan_in)


def init_params(shapes, key):
    """Random weights for the parameter tree ``shapes`` (leaves with
    ``.shape``), float32, one key per leaf folded from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [_leaf_init([getattr(k, "key", str(k)) for k in path],
                         tuple(s.shape), jax.random.fold_in(key, i))
              for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * scale


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope_interleaved(x, theta):
    """x (S, H, d): DeepSeek-V3's ``apply_rotary_pos_emb_interleave``:
    de-interleave the last axis (x.view(d/2, 2).transpose), then the
    rotate-half rotation with inv_freq_i = θ^(-2i/d)."""
    S, H, d = x.shape
    x = x.reshape(S, H, d // 2, 2).swapaxes(-1, -2).reshape(S, H, d)
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = np.outer(np.arange(S, dtype=np.float64), inv)
    emb = np.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos = jnp.asarray(np.cos(emb), jnp.float32)
    sin = jnp.asarray(np.sin(emb), jnp.float32)
    return x * cos + rotate_half(x) * sin


def attention(p, x, cfg):
    """One sequence x (S, D) -> (S, D)."""
    S = x.shape[0]
    H, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    r = cfg["kv_lora_rank"]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[:, :r], p["kv_norm"])
    k_pe = kv[:, None, r:]                              # (S, 1, dr)
    k_nope = jnp.einsum("sr,rhk->shk", c, p["wk_b"])
    v = jnp.einsum("sr,rhk->shk", c, p["wv_b"])
    q_pe = rope_interleaved(q_pe, cfg["rope_theta"])
    k_pe = rope_interleaved(k_pe, cfg["rope_theta"])
    qs = jnp.concatenate([q_nope, q_pe], axis=-1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, dr))],
                         axis=-1)
    s = jnp.einsum("shk,thk->hst", qs, ks) / math.sqrt(dn + dr)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("shk,hkd->sd", o, p["wo"])


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_in"])) @ p["w_out"]


SOUND = {"bias": 1.0, "norm": 1.0, "scale": 1.0, "shift": 0.0,
         "capacity": 0.0, "half": 0.0}


def flags(fault=None, half_batch=False):
    """The switches ``loss`` reads from ``batch["flags"]`` (calibration
    only; a batch without them is sound). They are run-time values, so
    the sound reference and every planted fault share one compiled
    program. ``fault``: ``bias_ignored`` (selection by the sigmoid
    scores alone), ``no_norm`` / ``no_scale`` (the selected weights not
    renormalised / not scaled by the routing factor), ``wrong_share``
    (the held weights taken for the next share's experts), ``capacity``
    (each held expert keeps its first 1.25 T K / E tokens of a sequence,
    the rest dropped); ``half_batch``: the loss over the first half of
    each sequence."""
    out = dict(SOUND, half=float(half_batch))
    if fault is not None:
        key, value = {"bias_ignored": ("bias", 0.0),
                      "no_norm": ("norm", 0.0),
                      "no_scale": ("scale", 0.0),
                      "wrong_share": ("shift", 1.0),
                      "capacity": ("capacity", 1.0)}[fault]
        out[key] = value
    return out


def moe(p, x, cfg, f=SOUND):
    """One sequence x (S, D) -> this share's part of the MoE output,
    with the switches ``f`` of ``flags``."""
    E, K = cfg["router_width"], cfg["num_experts_per_tok"]
    held, S = cfg["n_routed_experts"], x.shape[0]
    e0 = cfg["first_expert"] + held * jnp.asarray(f["shift"], jnp.int32)
    s = jax.nn.sigmoid(x @ p["router"])                 # (S, E)
    sel = s + jnp.asarray(f["bias"], s.dtype) * p["router_bias"]
    _, top = jax.lax.top_k(jax.lax.stop_gradient(sel), K)
    w = jnp.take_along_axis(s, top, axis=-1)
    w = jnp.where(f["norm"] > 0, w / jnp.sum(w, axis=-1, keepdims=True), w)
    w = w * jnp.where(f["scale"] > 0, cfg["routed_scaling_factor"],
                      1.0).astype(w.dtype)
    cap = jnp.where(f["capacity"] > 0, int(1.25 * S * K / E), S)
    out = jnp.zeros_like(x)
    for j in range(held):
        hit = top == e0 + j                             # (S, K)
        gate = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)  # (S,)
        kept = jnp.cumsum(jnp.any(hit, axis=-1)) <= cap
        ex = {k: p[k][j] for k in ("w_gate", "w_in", "w_out")}
        out = out + jnp.where(kept, gate, 0.0)[:, None] * swiglu(ex, x)
    return out + swiglu(p["shared"], x)


def seq_logits(params, tokens, cfg, f=SOUND):
    """Logits (S, vocab) of one sequence: the dense block, then the MoE
    blocks in a scan over their stacked weights; each block
    rematerialised."""
    def block(x, p, dense):
        x = x + attention(p["attn"], rms_norm(x, p["ln1"]["scale"]), cfg)
        h = rms_norm(x, p["ln2"]["scale"])
        return x + (swiglu(p["mlp"], h) if dense else moe(p["moe"], h, cfg,
                                                          f))

    x = params["embed"][tokens]
    x = jax.checkpoint(block, static_argnums=2)(x, params["stack"]["run0"],
                                                True)
    moe_block = jax.checkpoint(lambda x, p: block(x, p, False))
    x, _ = jax.lax.scan(lambda x, p: (moe_block(x, p), None), x,
                        params["stack"]["run1"])
    x = rms_norm(x, params["final_norm"]["scale"])
    return x @ params["lm_head"][:, :cfg["vocab_size"]]


def logits(params, tokens, cfg):
    """Logits (B, S, vocab) for ``tokens`` (B, S)."""
    return jnp.stack([seq_logits(params, t, cfg) for t in tokens])


def loss(params, batch, cfg):
    """Mean next-token cross-entropy over the batch, one sequence at a
    time (``lax.map``; the blocks' inputs are what a sequence keeps for
    the backward), with the switches of ``batch["flags"]`` where the
    batch has them."""
    f = batch.get("flags", SOUND)

    def one(xs):
        tokens, labels = xs
        lg = seq_logits(params, tokens, cfg, f)
        ce = (jax.nn.logsumexp(lg, axis=-1)
              - jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0])
        S = ce.shape[0]
        keep = jnp.where(f["half"] > 0, jnp.arange(S) < S // 2, True)
        return jnp.sum(jnp.where(keep, ce, 0.0)) / jnp.sum(keep)

    per_seq = jax.lax.map(one, (batch["tokens"], batch["labels"]))
    return jnp.mean(per_seq)


def cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


# ---------------------------------------------------------------------------
# operation counts (multiply-add = 2 operations)
# ---------------------------------------------------------------------------
def expert_flops(cfg, pairs):
    """Forward operations of the held experts' grouped product for
    ``pairs`` token–expert pairs: gate, up and down projections."""
    return float(pairs) * 3 * 2 * cfg["hidden_size"] * \
        cfg["moe_intermediate_size"]


def expected_pairs(cfg, tokens):
    """Held pairs of ``tokens`` tokens over the MoE layers if routing
    were even: each of K choices lands here with held / E odds."""
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (tokens * cfg["num_experts_per_tok"] * moe_layers
            * cfg["n_routed_experts"] / cfg["router_width"])


def forward_flops(cfg, seq, batch=1, pairs=None):
    """Operations of one forward pass at ``batch`` x ``seq`` tokens:
    every projection, the causal attention scores and weighted sums at
    half their square, the dense MLP, the router over all experts, the
    shared experts, the held experts on ``pairs`` routed pairs (summed
    over the MoE layers; even routing where None) and the head over the
    vocabulary slice. Norms, softmax, activations, sorting and gathers
    are not counted."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    T = batch * seq
    E = cfg["router_width"]
    Fs = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    attn = (2 * T * D * H * (dn + dr)           # q
            + 2 * T * D * (r + dr)              # latent and k_pe
            + 2 * 2 * T * r * H * dn            # k_nope, v (dv = dn)
            + 2 * T * H * dv * D                # output
            + 2 * batch * H * (dn + dr + dv) * seq * seq / 2)
    dense = 3 * 2 * T * D * cfg["intermediate_size"]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    per_moe = 2 * T * D * E + 3 * 2 * T * D * Fs
    if pairs is None:
        pairs = expected_pairs(cfg, T)
    head = 2 * T * D * cfg["vocab_size"]
    return float(cfg["num_hidden_layers"] * attn + dense
                 + moe_layers * per_moe + expert_flops(cfg, pairs) + head)


def train_flops(cfg, seq, batch=1, pairs=None):
    """Forward and backward of one step: three forwards' worth."""
    return 3.0 * forward_flops(cfg, seq, batch, pairs)
