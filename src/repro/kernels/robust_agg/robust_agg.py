"""Pallas TPU kernel for fused robust server aggregation.

Coordinate-wise trimmed mean / median over the packed ``(C, N)`` client
delta buffer (repro.federation.faults): per flat coordinate, sort the C
client values, cut ``t`` at each end, average the surviving window. The
kernel fuses sort + trim + mean into ONE HBM pass over the buffer — the
same launch discipline as the Δ-SGD pair (repro.kernels.delta_sgd),
with the same lane-aligned (C, N) → (C, M·128) tiling and a 1-D grid
over row blocks.

The sort is a BITONIC NETWORK along the client axis: C is padded to the
next power of two with +inf rows (which sort past every real value, so
the window [t, C−t) never sees them) and each compare-exchange is a
``jnp.minimum``/``jnp.maximum`` pair between two whole (rows, LANES)
client tiles, wired statically in Python — no ``lax.sort``, no gathers,
no reshapes, nothing Mosaic can't lower. For fleet-scale C the network
costs O(log² C) vector passes over a block that is already resident in
VMEM, so the kernel stays HBM-bound like the rest of the flat engine.
The row block shrinks as C grows so the block stays within VMEM.

``ref.py`` carries the ``jnp.sort`` oracle the kernel is parity-tested
against.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import BLOCK_ROWS, LANES

# trace-time launch accounting, one Counter per kernel module — the
# Δ-SGD 2-launches-per-step invariant counts ITS module's launches, so
# the aggregation kernel keeps its own book.
LAUNCHES: Counter = Counter()


def reset_launch_count() -> None:
    LAUNCHES.clear()


def launch_count() -> int:
    return sum(LAUNCHES.values())


def _bitonic_sort(tiles: list) -> list:
    """Ascending bitonic sort of a power-of-two list of equal-shape
    tiles, elementwise: after it, ``out[i]`` holds the i-th smallest of
    the inputs at every position."""
    x = list(tiles)
    n = len(x)
    k = 2
    while k <= n:
        s = k // 2
        while s >= 1:
            for i in range(n):
                j = i ^ s
                if j > i:
                    lo, hi = x[i], x[j]
                    mn, mx = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
                    x[i], x[j] = (mn, mx) if (i & k) == 0 else (mx, mn)
            s //= 2
        k *= 2
    return x


def _next_pow2(c: int) -> int:
    p = 1
    while p < c:
        p *= 2
    return p


def _make_trimmed_kernel(c: int, t: int):
    def kernel(x_ref, out_ref):
        xs = _bitonic_sort([x_ref[i] for i in range(x_ref.shape[0])])
        # pad rows are +inf and sort past index c−1; the surviving
        # window [t, c−t) is all real values
        acc = xs[t]
        for w in xs[t + 1:c - t]:
            acc = acc + w
        out_ref[...] = acc / jnp.float32(c - 2 * t)
    return kernel


# bytes of one (P2, rows, LANES) f32 input block; Pallas double-buffers
# it, and the scoped VMEM default on v5e is 16 MiB
_BLOCK_BYTES = 4 * 2 ** 20


def _grid_shapes(n: int, clients: int):
    """(M, rows, blocks) for a lane-aligned flat length n — same
    geometry contract as the Δ-SGD kernels (FlatLayout pre-pads), with
    the row block halved while ``clients`` tiles of it exceed
    _BLOCK_BYTES."""
    assert n % LANES == 0, f"flat length {n} not lane-aligned"
    m = n // LANES
    rows = min(BLOCK_ROWS, m)
    assert m % rows == 0, f"flat length {n} not row-block aligned"
    while clients * rows * LANES * 4 > _BLOCK_BYTES and rows % 16 == 0:
        rows //= 2
    return m, rows, m // rows


def batched_trimmed_mean(x: jax.Array, t: int, *,
                         interpret: bool = False) -> jax.Array:
    """Coordinate-wise trimmed mean over the packed (C, N) buffer:
    sort the C client values per coordinate, drop ``t`` at each end,
    average the rest. ONE pallas launch for all coordinates. Invalid
    clients must already be zeroed by the caller (the zero delta is the
    'no contribution' element — repro.federation.faults documents the
    semantics). ``t = (C−1)//2`` gives the coordinate-wise median."""
    C, n = x.shape
    if not 0 <= 2 * t < C:
        raise ValueError(f"trim count {t} leaves no window for C={C}")
    P2 = _next_pow2(C)
    m, rows, blocks = _grid_shapes(n, P2)
    x3 = x.astype(jnp.float32).reshape(C, m, LANES)
    if P2 > C:
        x3 = jnp.concatenate(
            [x3, jnp.full((P2 - C, m, LANES), jnp.inf, jnp.float32)])
    LAUNCHES["batched_trimmed_mean"] += 1
    out = pl.pallas_call(
        _make_trimmed_kernel(C, t),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((P2, rows, LANES), lambda j: (0, j, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.float32),
        interpret=interpret,
    )(x3)
    return out.reshape(n)
