"""Pure-jnp oracle for the delta-compression kernels.

All three ops are CHUNK-LOCAL on the packed (C, N) flat buffer
(repro.core.flat): a chunk is one lane row of ``LANES`` consecutive
elements, so the (C, N) buffer is viewed as (C, M, LANES) with
``M = N // LANES``. Chunk locality is what makes the ops trivially
shardable — a per-shard slab of the flat dim is a whole number of
chunks by FlatLayout construction, so compression never communicates.

  quantize_int8_ref    (C, N) f32 -> ((C, N) int8, (C, M) f32 scales)
  dequantize_int8_ref  ((C, N) int8, (C, M) f32) -> (C, N) f32
  topk_mask_ref        (C, N) f32 -> (C, N) f32 with exactly k nonzero
                       slots kept per chunk (magnitude top-k, threshold
                       pass + first-index tie-break — deterministic)
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.flat import LANES


def _chunked(x: jnp.ndarray):
    C, n = x.shape
    assert n % LANES == 0, f"flat length {n} not lane-aligned"
    return x.reshape(C, n // LANES, LANES)


def quantize_int8_ref(x: jnp.ndarray):
    """Per-chunk symmetric int8: scale = absmax/127, q = round(x/scale).

    Zero chunks quantize to scale 0 (dequantized exactly to 0). Rounding
    is jnp.round (half-to-even), matching the Pallas kernel bit for bit.
    """
    x3 = _chunked(x.astype(jnp.float32))
    absmax = jnp.max(jnp.abs(x3), axis=-1)                    # (C, M)
    scale = absmax / 127.0
    inv = jnp.where(absmax > 0.0, 127.0 / absmax, 0.0)
    q = jnp.clip(jnp.round(x3 * inv[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_int8_ref(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """q * scale. The per-element select keeps XLA:CPU from contracting
    the product into an FMA with whatever consumes it (the EF21 add):
    whether it does depends on what else shares the fusion, so the same
    round compiled in two programs would otherwise round apart."""
    q3 = _chunked(q).astype(jnp.float32)
    return jnp.where(q3 != 0.0, q3 * scales[..., None], 0.0).reshape(q.shape)


def topk_mask_ref(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Keep exactly ``k`` slots per LANES-chunk by magnitude, zero the
    rest. Threshold pass: the k-th largest |x| per chunk is the keep
    threshold; ties at the threshold are broken by first index so the
    kept count is exactly k even for constant chunks."""
    if not 1 <= k <= LANES:
        raise ValueError(f"topk k must be in [1, {LANES}], got {k}")
    x3 = _chunked(x.astype(jnp.float32))
    a = jnp.abs(x3)
    thr = jnp.sort(a, axis=-1)[..., LANES - k]                # (C, M)
    greater = a > thr[..., None]
    n_greater = jnp.sum(greater, axis=-1, keepdims=True)
    eq = a == thr[..., None]
    eq_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1)
    keep = greater | (eq & (eq_rank <= (k - n_greater)))
    return jnp.where(keep, x3, 0.0).reshape(x.shape)
