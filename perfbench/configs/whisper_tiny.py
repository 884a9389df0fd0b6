"""Plain reference of whisper-tiny as this repository runs it, the
benchmark's weights for it, and its operation counts.

The reference is written from the model's description in plain
``jax.numpy``, in float32 at the highest matmul precision; it imports
nothing of the program. Where the repo's model departs from the paper
(``whisper_tiny.json`` lists it under ``assumed``) the reference follows
the repo's model, so the two compute the same function:

* encoder: frame embeddings + interleaved sinusoids, pre-norm blocks of
  bidirectional attention and a GELU (tanh form) MLP, final layer norm;
* decoder: token embeddings + the same sinusoids, pre-norm blocks of
  causal self-attention, cross-attention to the encoder output and the
  MLP; final layer norm and the output head tied to the token
  embedding, over the vocabulary.

Weights are made by ``init_params`` from the seed, for the program's
parameter tree (names and shapes), in one jitted call on the device.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _leaf_init(path, shape, key):
    names = [getattr(k, "key", str(k)) for k in path]
    leaf = names[-1]
    stacked = "stack" in names          # leading axis: the layer
    core = shape[1:] if stacked else shape
    normal = jax.random.normal(key, shape, jnp.float32)
    if leaf == "scale":
        return 1.0 + 0.02 * normal
    if leaf in ("bias", "b_in", "b_out"):
        return 0.02 * normal
    if leaf == "embed":
        return 0.02 * normal
    if leaf == "wo":                    # (H, hd, D)
        fan_in = core[0] * core[1]
    else:                               # (D, ...) or (F, D)
        fan_in = core[0]
    return normal / math.sqrt(fan_in)


def init_params(shapes, key):
    """Random weights for the parameter tree ``shapes`` (leaves with
    ``.shape``), float32, one key per leaf folded from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [_leaf_init(path, tuple(s.shape), jax.random.fold_in(key, i))
              for i, (path, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def sinusoids(n, d):
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d)
    out = np.zeros((n, d), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


def layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def attention(p, xq, xkv, causal):
    """xq (B, S, D), xkv (B, T, D); p holds wq, wk, wv (D, H, hd) and
    wo (H, hd, D)."""
    q = jnp.einsum("bsd,dhk->bhsk", xq, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", xkv, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", xkv, p["wv"])
    s = jnp.einsum("bhsk,bhtk->bhst", q, k) / math.sqrt(q.shape[-1])
    if causal:
        S, T = s.shape[-2], s.shape[-1]
        keep = np.tril(np.ones((S, T), bool))
        s = jnp.where(keep, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bhtk->bhsk", w, v)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"])


def mlp(p, x):
    h = jax.nn.gelu(x @ p["w_in"] + p["b_in"], approximate=True)
    return h @ p["w_out"] + p["b_out"]


def _layer(stack, i):
    return jax.tree.map(lambda a: a[i], stack)


def encode(params, frames):
    x = frames + sinusoids(frames.shape[1], frames.shape[2]).astype(
        frames.dtype)
    stack = params["encoder"]["stack"]["run0"]
    for i in range(stack["ln1"]["scale"].shape[0]):
        p = _layer(stack, i)
        h = layer_norm(x, p["ln1"])
        x = x + attention(p["attn"], h, h, causal=False)
        x = x + mlp(p["mlp"], layer_norm(x, p["ln2"]))
    return layer_norm(x, params["encoder"]["norm"])


def logits(params, tokens, frames, vocab):
    """Decoder logits (B, S, vocab) for ``tokens`` (B, S) given frame
    embeddings (B, T, D)."""
    enc = encode(params, frames)
    x = params["embed"][tokens]
    x = x + sinusoids(x.shape[1], x.shape[2]).astype(x.dtype)
    stack = params["stack"]["run0"]
    for i in range(stack["ln1"]["scale"].shape[0]):
        p = _layer(stack, i)
        h = layer_norm(x, p["ln1"])
        x = x + attention(p["attn"], h, h, causal=True)
        h = layer_norm(x, p["ln_x"])
        x = x + attention(p["xattn"], h, enc, causal=False)
        x = x + mlp(p["mlp"], layer_norm(x, p["ln2"]))
    x = layer_norm(x, params["final_norm"])
    return x @ params["embed"][:vocab].T


def loss(params, batch, vocab):
    """Mean next-token cross-entropy over all positions of the batch."""
    lg = logits(params, batch["tokens"], batch["frames"], vocab)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, batch["labels"][..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


# ---------------------------------------------------------------------------
# operation counts (multiply-add = 2 operations)
# ---------------------------------------------------------------------------
def forward_flops(cfg, seq, frames, batch=1):
    """Operations of one forward pass at ``batch`` x ``seq`` tokens over
    ``frames`` encoder positions: every projection and MLP matmul, the
    attention scores and weighted sums (the causal self-attention at
    half its square), and the output head over the real vocabulary.
    Norms, softmax and activations are not counted."""
    D = cfg["d_model"]
    F_enc, F_dec = cfg["encoder_ffn_dim"], cfg["decoder_ffn_dim"]
    L_enc, L_dec = cfg["encoder_layers"], cfg["decoder_layers"]
    V, S, T = cfg["vocab_size"], seq, frames
    enc_layer = (4 * 2 * T * D * D          # q, k, v, o
                 + 2 * 2 * T * T * D        # scores, weighted sum
                 + 2 * 2 * T * D * F_enc)   # MLP in, out
    dec_layer = (4 * 2 * S * D * D          # self q, k, v, o
                 + 2 * 2 * S * S * D / 2    # causal scores, sum
                 + 2 * 2 * S * D * D        # cross q, o
                 + 2 * 2 * T * D * D        # cross k, v over frames
                 + 2 * 2 * S * T * D        # cross scores, sum
                 + 2 * 2 * S * D * F_dec)   # MLP
    head = 2 * S * D * V
    return float(batch * (L_enc * enc_layer + L_dec * dec_layer + head))


def train_flops(cfg, seq, frames, batch=1):
    """Forward and backward of one step: three forwards' worth."""
    return 3.0 * forward_flops(cfg, seq, frames, batch)
