"""Shared model building blocks: inits, norms, activations, rope, logical
sharding annotations.

The module system is plain pytrees-of-dicts + pure functions: every block
exposes ``init_*(key, cfg, dtype) -> params`` and ``apply(params, x, ...)``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Logical sharding annotations.
#
# Models annotate activations with *logical* axis names; the launcher installs
# a rule-set mapping logical names -> mesh axes (repro/sharding/spec.py). With
# no rules installed (CPU smoke tests) the annotation is a no-op.
# ---------------------------------------------------------------------------
_tls = threading.local()


def set_logical_rules(rules):
    _tls.rules = rules


def get_logical_rules():
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def logical_rules(rules):
    prev = get_logical_rules()
    set_logical_rules(rules)
    try:
        yield
    finally:
        set_logical_rules(prev)


# ---------------------------------------------------------------------------
# Scan-unroll flag. XLA's cost_analysis counts a while-loop body ONCE, not
# × trip count (verified empirically). The dry-run enables full unrolling of
# the structural scans (layer stack, K local steps) so the roofline FLOP /
# byte numbers are trip-count-correct. Time-sequential scans (sLSTM over
# time, SSD inter-chunk state propagation) are restructured so essentially
# all FLOPs sit outside the loop body.
# ---------------------------------------------------------------------------
def set_unroll(flag: bool):
    _tls.unroll = flag


def scan_unroll() -> bool:
    return getattr(_tls, "unroll", False)


@contextlib.contextmanager
def unroll_scans(flag: bool = True):
    prev = scan_unroll()
    set_unroll(flag)
    try:
        yield
    finally:
        set_unroll(prev)


def set_remat(flag: bool):
    _tls.remat = flag


def remat_on() -> bool:
    return getattr(_tls, "remat", False)


@contextlib.contextmanager
def remat_blocks(flag: bool = True):
    """Per-transformer-block activation checkpointing (standard production
    policy: recompute block internals in backward, keep only the residual
    stream between layers)."""
    prev = remat_on()
    set_remat(flag)
    try:
        yield
    finally:
        set_remat(prev)


def counter_total(name: str, values: jax.Array) -> jax.Array:
    """The total of a model counter over ``values`` (its readings over
    layers, steps or clients): their sum, or for a ``*_max`` counter the
    largest."""
    return jnp.max(values) if name.endswith("_max") else jnp.sum(values)


def shard_logical(x: jax.Array, names: Tuple[Optional[str], ...]) -> jax.Array:
    """Apply with_sharding_constraint according to the installed rules."""
    rules = get_logical_rules()
    if rules is None:
        return x
    return rules.constrain(x, names)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape: Sequence[int], dtype, fan_in: Optional[int] = None):
    """Truncated-normal with 1/sqrt(fan_in) scale (fan_in = shape[0] default)."""
    fan = fan_in if fan_in is not None else shape[0]
    std = 1.0 / np.sqrt(max(1, fan))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * scale + bias


def init_norm(key, cfg, dtype, d=None):
    d = d or cfg.d_model
    if cfg.norm_variant == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def apply_norm(params, x, cfg):
    if "bias" in params:
        return layernorm(x, params["scale"], params["bias"])
    return rmsnorm(x, params["scale"])


def swiglu(gate, up):
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               interleave: bool = False) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    Frequency i rotates the pair (x[i], x[i + hd/2]); with
    ``interleave`` the pair (x[2i], x[2i+1]) instead, written back in
    place (DeepSeek-V3's ``rope_interleave``)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                        # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    if interleave:
        pairs = xf.reshape(xf.shape[:-1] + (hd // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(xf.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_position_at(t, d: int):
    """Traced single-position sinusoidal embedding: t scalar -> (d,)."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    angle = t.astype(jnp.float32) / jnp.power(10000.0, 2 * i / d)
    out = jnp.zeros((d,), jnp.float32)
    out = out.at[0::2].set(jnp.sin(angle))
    out = out.at[1::2].set(jnp.cos(angle))
    return out


def sinusoidal_positions(num_pos: int, d: int) -> np.ndarray:
    pos = np.arange(num_pos)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d)
    out = np.zeros((num_pos, d), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------
def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def tree_size(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))
