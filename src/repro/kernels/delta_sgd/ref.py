"""Pure-jnp oracle for the fused Δ-SGD kernels.

Two ops, matching the kernel pair:
  norms_ref(g, g_prev)      -> (sum((g-g_prev)^2), sum(g^2))  [one pass]
  apply_ref(p, g, eta)      -> p - eta * g                    [one pass]

and their batched twins on (C, M, 128) client slabs.
"""
from __future__ import annotations

import jax.numpy as jnp


def norms_ref(g: jnp.ndarray, g_prev: jnp.ndarray):
    g32 = g.astype(jnp.float32)
    gp32 = g_prev.astype(jnp.float32)
    return (jnp.sum(jnp.square(g32 - gp32)), jnp.sum(jnp.square(g32)))


def apply_ref(p: jnp.ndarray, g: jnp.ndarray, eta) -> jnp.ndarray:
    return (p.astype(jnp.float32)
            - eta * g.astype(jnp.float32)).astype(p.dtype)


def batched_norms_ref(g: jnp.ndarray, g_prev: jnp.ndarray):
    """Per-client sums over (C, M, 128) client slabs -> pair of (C,)."""
    g32 = g.astype(jnp.float32)
    gp32 = g_prev.astype(jnp.float32)
    return (jnp.sum(jnp.square(g32 - gp32), axis=(1, 2)),
            jnp.sum(jnp.square(g32), axis=(1, 2)))


def batched_apply_ref(p: jnp.ndarray, g: jnp.ndarray, eta: jnp.ndarray,
                      mask=None, *, valid=None) -> jnp.ndarray:
    """P − η_c·where(valid_c, G, 0) on (C, M, 128) client slabs with
    per-client η (C,) and validity (C,) bool (None: all valid); optional
    bf16 rounding on mask=1 elements of the (M, 128) round mask."""
    g32 = g.astype(jnp.float32)
    if valid is not None:
        g32 = jnp.where(valid[:, None, None], g32, jnp.float32(0.0))
    r = p.astype(jnp.float32) - eta[:, None, None] * g32
    if mask is None:
        return r.astype(p.dtype)
    rounded = r.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(mask[None] > 0.0, rounded, r).astype(p.dtype)
