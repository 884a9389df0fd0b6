"""Pallas TPU kernels for delta compression on the packed (C, N) buffer.

At the ROADMAP's millions-of-users scale the client->server link, not
the local step, is the bottleneck: every round each of the C cohort
clients ships an N-element f32 delta. These kernels compress that delta
IN PLACE on the flat engine's packed (C, N) buffer (repro.core.flat) so
that only compressed representations need to cross shard/wire
boundaries:

  quantize_int8   — per-chunk symmetric int8: one HBM pass over the
                    delta producing int8 values + one f32 scale per
                    LANES-chunk (absmax/127). Wire cost per element:
                    1 byte + 4/LANES bytes of scale (~3.88x vs f32).
  dequantize_int8 — the server-side inverse, one pass.
  topk_mask       — magnitude top-k sparsification with a THRESHOLD
                    pass (no host gather): per chunk the k-th largest
                    |x| is found by bisection on counts over its f32 bit
                    pattern (Mosaic has no sort), then a vectorized
                    keep-mask with first-index tie-break retains exactly
                    k slots. Wire cost per chunk: k x (4 + 1) bytes
                    (value + lane index).

All three ops are chunk-local (chunk = one row of LANES consecutive
elements), so a per-shard slab of the flat dim — a whole number of
row blocks by FlatLayout construction — compresses independently:
under ``shard_map`` no cross-device traffic is ever generated.

Launch-count math, per round: int8 costs exactly 2 launches
(quantize + dequantize), top-k exactly 1, independent of leaf count,
client count, and K — the Δ-SGD step pair (2/step) is untouched.
Like the delta_sgd kernels, everything runs in interpret mode off-TPU
(``repro.kernels.interpret_mode``), and ``repro.kernels.compress.ref``
is the pure-jnp oracle (used directly by the ``backend="xla"`` path of
meshed callers).
"""
from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import BLOCK_ROWS, LANES

# trace-time launch accounting, same contract as kernels.delta_sgd:
# incremented once per pallas_call *built* (launches per traced program)
LAUNCHES: Counter = Counter()


def reset_launch_count() -> None:
    LAUNCHES.clear()


def launch_count() -> int:
    return sum(LAUNCHES.values())


def _grid_shapes(n: int):
    """(M, rows, blocks) for a lane-aligned flat length n (no re-padding:
    FlatLayout guarantees M % rows == 0)."""
    assert n % LANES == 0, f"flat length {n} not lane-aligned"
    m = n // LANES
    rows = min(BLOCK_ROWS, m)
    assert m % rows == 0, f"flat length {n} not row-block aligned"
    return m, rows, m // rows


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)              # (1, rows, LANES)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)   # (1, rows, 1)
    s_ref[...] = absmax / 127.0
    inv = jnp.where(absmax > 0.0, 127.0 / absmax, 0.0)
    q = jnp.clip(jnp.round(x * inv), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)


def _dequantize_kernel(q_ref, s_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)
    # the same select as the ref: q * scale, never fused into an FMA
    out_ref[...] = jnp.where(q != 0.0, q * s_ref[...], 0.0)


def kth_smallest_key(keys, rank: int, *, bits: int = 32, axis=-1):
    """``rank``-th smallest (0-based) of int32 order keys over ``axis``
    (kept as size-1 dims), by bisection on counts: the smallest v with
    ``count(keys <= v) > rank``. Sort-free, so it lowers on Mosaic; the
    result is exact, hence equal to indexing a sorted copy. ``bits=31``
    when every key is nonnegative."""
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    axes = tuple(a % keys.ndim for a in axes)
    shape = tuple(1 if d in axes else n for d, n in enumerate(keys.shape))
    lo = jnp.full(shape, 0 if bits == 31 else -2 ** 31, jnp.int32)
    hi = jnp.full(shape, 2 ** 31 - 1, jnp.int32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)   # floor avg, no overflow
        n = jnp.sum((keys <= mid).astype(jnp.int32), axis=axes,
                    keepdims=True)
        up = n > rank
        return jnp.where(up, lo, mid + 1), jnp.where(up, mid, hi)

    lo, _ = jax.lax.fori_loop(0, bits, body, (lo, hi))
    return lo


def _topk_kernel(x_ref, out_ref, *, k: int):
    x = x_ref[0].astype(jnp.float32)                # (rows, LANES)
    a = jnp.abs(x)
    # |x| >= 0, so its f32 bit pattern orders like the value (NaN last,
    # as in a sort)
    key = jax.lax.bitcast_convert_type(a, jnp.int32)
    thr = jax.lax.bitcast_convert_type(
        kth_smallest_key(key, LANES - k, bits=31), jnp.float32)
    greater = a > thr
    n_greater = jnp.sum(greater.astype(jnp.int32), axis=-1, keepdims=True)
    eq = a == thr
    # inclusive prefix count of ties along the lanes, as a matmul with an
    # upper-triangular ones matrix (0/1 inputs: exact at any precision)
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    eq_rank = jax.lax.dot_general(
        eq.astype(jnp.float32), (r <= c).astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    keep = greater | (eq & (eq_rank <= (k - n_greater).astype(jnp.float32)))
    out_ref[0] = jnp.where(keep, x, 0.0)


def quantize_int8(x: jax.Array, *, interpret: bool = False):
    """Packed (C, N) f32 -> ((C, N) int8, (C, M) f32 per-chunk scales).

    ONE pallas launch for all clients and all chunks (2-D grid over
    (client, row-block)).
    """
    C, n = x.shape
    m, rows, blocks = _grid_shapes(n)
    x3 = x.reshape(C, m, LANES)
    LAUNCHES["quantize_int8"] += 1
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(C, blocks),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0))],
        out_specs=[pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
                   pl.BlockSpec((1, rows, 1), lambda c, j: (c, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((C, m, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((C, m, 1), jnp.float32)],
        interpret=interpret,
    )(x3)
    return q.reshape(C, n), s.reshape(C, m)


def dequantize_int8(q: jax.Array, scales: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """((C, N) int8, (C, M) f32) -> (C, N) f32. ONE pallas launch."""
    C, n = q.shape
    m, rows, blocks = _grid_shapes(n)
    q3 = q.reshape(C, m, LANES)
    LAUNCHES["dequantize_int8"] += 1
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(C, blocks),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
                  pl.BlockSpec((1, rows, 1), lambda c, j: (c, j, 0))],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
        out_shape=jax.ShapeDtypeStruct((C, m, LANES), jnp.float32),
        interpret=interpret,
    )(q3, scales.reshape(C, m, 1))
    return out.reshape(C, n)


def topk_mask(x: jax.Array, k: int, *, interpret: bool = False) -> jax.Array:
    """Keep exactly ``k`` slots per LANES-chunk of (C, N) by magnitude,
    zero the rest (threshold pass + first-index tie-break, fully on
    device). ONE pallas launch."""
    if not 1 <= k <= LANES:
        raise ValueError(f"topk k must be in [1, {LANES}], got {k}")
    C, n = x.shape
    m, rows, blocks = _grid_shapes(n)
    x3 = x.reshape(C, m, LANES)
    LAUNCHES["topk_mask"] += 1
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(C, blocks),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0))],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
        out_shape=jax.ShapeDtypeStruct((C, m, LANES), jnp.float32),
        interpret=interpret,
    )(x3)
    return out.reshape(C, n)
