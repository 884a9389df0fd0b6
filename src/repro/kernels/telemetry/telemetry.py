"""Pallas TPU kernels for the device-native telemetry plane.

The observability question the paper's claim raises — does each
client's η actually ADAPT, or did the fleet collapse onto one global
step size? — needs per-round distributions, not just eta_mean/min/max.
These kernels reduce a (C,) per-client vector into a fixed-shape
summary cheap enough to ride inside the round-fused ``lax.scan``:

  lane_histogram  (C,) values + static bin edges -> (B,) f32 counts.
                  One launch: the vector is padded with NaN (counts
                  nowhere) to a (rows, LANES) tile and every bin's
                  [lo, hi) band is summed in one VMEM pass.
  lane_quantiles  (C,) values -> (Q,) order statistics (min, deciles,
                  max at Q=11). One launch: pad with +inf, then each
                  static nearest-rank order statistic is found by
                  bisection on counts over an order-preserving int32 key
                  of the f32 values (Mosaic has no sort).

Launch accounting mirrors ``kernels/delta_sgd``: a module-level
``LAUNCHES`` counter incremented per ``pallas_call`` built, with its
OWN namespace — the Δ-SGD 2-launch/step invariant is counted on the
delta_sgd counter and stays untouched by telemetry
(tests/test_telemetry.py::test_launch_counters_separate_namespaces).
``ref.py`` is the pure-jnp oracle; both produce exact integer counts /
exact order statistics, so parity is equality, not a tolerance.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import LANES
from repro.kernels import interpret_mode
from repro.kernels.compress.compress import kth_smallest_key

from .ref import quantile_indices

# trace-time launch accounting (same contract as kernels/delta_sgd):
# one increment per pallas_call BUILT, i.e. launches per traced step.
LAUNCHES: Counter = Counter()

# f32 min tile on TPU is (8, 128): pad the (C,) vector up to at least
# 8 full lane rows so the single-block kernels stay tile-aligned.
_MIN_ROWS = 8


def reset_launch_count() -> None:
    LAUNCHES.clear()


def launch_count() -> int:
    return sum(LAUNCHES.values())


def _pad_rows(x: jax.Array, fill: float):
    """(C,) -> (rows, LANES) with ``fill`` padding, rows >= _MIN_ROWS."""
    C = x.shape[0]
    rows = max(_MIN_ROWS, -(-C // LANES))
    pad = rows * LANES - C
    flat = x.astype(jnp.float32)
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), fill, jnp.float32)])
    return flat.reshape(rows, LANES)


def _hist_kernel(x_ref, e_ref, out_ref):
    xf = x_ref[...].reshape(1, -1)                  # (1, rows*LANES)
    e = e_ref[...]                                  # (1, B+1)
    lo = e[0, :-1][:, None]                         # (B, 1)
    hi = e[0, 1:][:, None]
    out_ref[...] = jnp.sum((xf >= lo) & (xf < hi), axis=1,
                           dtype=jnp.float32).reshape(1, -1)


def lane_histogram(x: jax.Array, edges, *,
                   interpret: bool | None = None) -> jax.Array:
    """(C,) f32 values, (B+1,) ascending edges -> (B,) f32 counts.

    ONE pallas launch. NaN values (and anything outside [edges[0],
    edges[-1])) count nowhere — NaN-padded lanes are free. Counts are
    exact integers in f32: bit-identical to the ref and stable under
    cross-shard psum.
    """
    interpret = interpret_mode(interpret)
    e = jnp.asarray(edges, jnp.float32).reshape(1, -1)
    B = e.shape[1] - 1
    x2 = _pad_rows(x, float("nan"))
    rows = x2.shape[0]
    LAUNCHES["lane_histogram"] += 1
    out = pl.pallas_call(
        _hist_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
                  pl.BlockSpec((1, B + 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, B), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.float32),
        interpret=interpret,
    )(x2, e)
    return out[0]


def lane_quantiles(x: jax.Array, Q: int = 11, *,
                   interpret: bool | None = None) -> jax.Array:
    """(C,) f32 values -> (Q,) f32 order statistics at the evenly
    spaced quantile fractions (min, deciles, max for Q=11).

    ONE pallas launch: +inf padding keeps the real values in the first
    C order statistics, so the static nearest-rank selection is exact.
    Finite inputs only (NaNs sort after +inf and can displace top
    quantiles).
    """
    interpret = interpret_mode(interpret)
    C = x.shape[0]
    idx = quantile_indices(C, Q)        # static python ints
    x2 = _pad_rows(x, float("inf"))
    rows = x2.shape[0]

    def _quantile_kernel(x_ref, out_ref):
        b = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)
        # order-preserving key: flip the magnitude bits of negatives
        key = b ^ ((b >> 31) & 0x7FFFFFFF)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
        out = jnp.zeros((1, Q), jnp.int32)
        for q, r in enumerate(idx):
            kq = kth_smallest_key(key, r, axis=(0, 1))       # (1, 1)
            out = jnp.where(lane == q, kq, out)
        out = out ^ ((out >> 31) & 0x7FFFFFFF)                # key -> bits
        out_ref[...] = jax.lax.bitcast_convert_type(out, jnp.float32)

    LAUNCHES["lane_quantiles"] += 1
    out = pl.pallas_call(
        _quantile_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, Q), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Q), jnp.float32),
        interpret=interpret,
    )(x2)
    return out[0]
