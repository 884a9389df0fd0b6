"""Pallas TPU kernels for Δ-SGD's per-step param work.

The paper's step size needs two global reductions per local step
(‖g_k − g_{k−1}‖², ‖g_k‖² — the ‖Δx‖ term reuses the previous ‖g‖ since
Δx = −η·g for SGD updates). The reductions must complete before η is known,
so the update itself is a second pass.

Flat packed layout (the fast path — see ``repro.core.flat``): the whole
param pytree is ONE lane-aligned f32 buffer and the client axis leads a
client slab of shape ``(C, M, 128)``, ``N = M·128`` with ``M`` an exact
multiple of the row-block. The slab is the kernels' operand form and the
form the local-step scan carries, so no relayout copy sits between the
scan and the pair. The kernel pair runs a 2-D grid over (client,
row-block):

  batched_norms  — ONE HBM pass over (G, G_prev) producing BOTH partial
                   sums per block, accumulated across the sequential
                   row-block grid axis into per-client (C,) outputs held
                   in SMEM (a TPU stores scalars there, not in VMEM).
                   No vmap, no per-leaf loop: the client axis is a grid
                   dimension, so the kernel is vmap-free by construction.
  batched_apply  — P ← P − η_c·where(valid_c, G, 0) with per-client η
                   and validity, tiled through VMEM; P is aliased to the
                   output so the update is in-place. A lane whose
                   gradient went non-finite is zeroed here, inside the
                   kernel (0·NaN is NaN, so η = 0 alone cannot hold P),
                   and never as a separate pass over the slab. An
                   optional per-element round mask reproduces the
                   reference path's per-step bf16 rounding for sub-f32
                   leaves packed into the f32 buffer.

Launch-count math, per local step over a ``num_leaves``-leaf tree and
``C`` clients: the per-leaf path costs ``num_leaves × C × 2`` pallas
launches (norms + apply per leaf per client, under vmap) plus a
``_pad_2d`` concatenate copy per call; the packed path costs exactly
**2** launches — one ``batched_norms``, one ``batched_apply`` — for any
leaf count and any client count, with zero per-call padding (the layout
pre-pads once at pack time). Both paths read {G, G_prev} once and
read {P, G}/write {P} once, i.e. the HBM-bandwidth floor for the rule;
the packed path is the one that reaches it at small-leaf granularity.

The single-tensor ``norms`` / ``apply_update`` kernels below are the
legacy per-leaf path, kept as the benchmark baseline and for callers
that operate on individual tensors.
"""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of truth for the tile geometry: the packer pads layouts
# to exactly these block sizes, so both modules must agree
from repro.core.flat import BLOCK_ROWS, LANES

# trace-time launch accounting: incremented once per pallas_call *built*,
# i.e. launches per traced step (what the compiled program will execute).
LAUNCHES: Counter = Counter()


def reset_launch_count() -> None:
    LAUNCHES.clear()


def launch_count() -> int:
    return sum(LAUNCHES.values())


# --------------------------------------------------------------------------
# packed (C, M, 128) kernels — one launch per op for all leaves and clients
# --------------------------------------------------------------------------

# Scalar results live in SMEM as whole (C,) arrays: a TPU cannot store a
# scalar to VMEM, and a (1, 1) VMEM block breaks the (8, 128) tiling.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _batched_norms_kernel(g_ref, gp_ref, dg_ref, gg_ref):
    c = pl.program_id(0)
    j = pl.program_id(1)  # row-block axis: sequential, innermost
    g = g_ref[...].astype(jnp.float32)
    gp = gp_ref[...].astype(jnp.float32)
    d = g - gp

    @pl.when(j == 0)
    def _init():
        dg_ref[c] = 0.0
        gg_ref[c] = 0.0

    dg_ref[c] += jnp.sum(d * d)
    gg_ref[c] += jnp.sum(g * g)


def _applied(eta_ref, valid_ref, p_ref, g_ref):
    """p − η·where(valid, g, 0) in f32 for one (client, row-block)."""
    eta = eta_ref[0, 0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    g = jnp.where(valid_ref[0, 0, 0] > 0.0, g, jnp.float32(0.0))
    return p - eta * g


def _batched_apply_kernel(eta_ref, valid_ref, p_ref, g_ref, out_ref):
    out_ref[...] = _applied(eta_ref, valid_ref, p_ref,
                            g_ref).astype(out_ref.dtype)


def _batched_apply_masked_kernel(eta_ref, valid_ref, p_ref, g_ref,
                                 mask_ref, out_ref):
    r = _applied(eta_ref, valid_ref, p_ref, g_ref)
    # mask=1 elements belong to bf16 leaves: round exactly like the
    # per-leaf reference's astype(bf16) so flat K-step scans stay on par
    rounded = r.astype(jnp.bfloat16).astype(jnp.float32)
    out_ref[...] = jnp.where(mask_ref[...] > 0.0, rounded, r)


def _grid_shapes(slab: jax.Array):
    """(C, rows, blocks) of a (C, M, 128) client slab (no re-padding:
    FlatLayout guarantees M % rows == 0)."""
    assert slab.ndim == 3 and slab.shape[2] == LANES, (
        f"client slab must be (C, M, {LANES}), got {slab.shape}")
    C, m, _ = slab.shape
    rows = min(BLOCK_ROWS, m)
    assert m % rows == 0, f"{m} slab rows not row-block aligned"
    return C, rows, m // rows


def batched_norms(g: jax.Array, g_prev: jax.Array, *,
                  interpret: bool = False):
    """Per-client (sum((g-gp)^2), sum(g^2)) over (C, M, 128) client
    slabs.

    ONE pallas launch for all clients and all (packed) leaves; returns a
    pair of (C,) f32 vectors.
    """
    C, rows, blocks = _grid_shapes(g)
    LAUNCHES["batched_norms"] += 1
    dg, gg = pl.pallas_call(
        _batched_norms_kernel,
        grid=(C, blocks),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
                  pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0))],
        out_specs=[_SMEM, _SMEM],
        out_shape=[jax.ShapeDtypeStruct((C,), jnp.float32),
                   jax.ShapeDtypeStruct((C,), jnp.float32)],
        interpret=interpret,
    )(g, g_prev)
    return dg, gg


def batched_apply(p: jax.Array, g: jax.Array, eta: jax.Array, *,
                  valid: jax.Array | None = None,
                  mask: jax.Array | None = None,
                  interpret: bool = False) -> jax.Array:
    """P ← P − η_c·where(valid_c, G, 0) on (C, M, 128) client slabs with
    per-client η (C,) and validity (C,) bool (None: every lane valid).

    ONE pallas launch; P is donated to the output (in-place on TPU).
    ``mask`` is the optional (M, 128) round mask from
    FlatLayout.round_mask.
    """
    C, rows, blocks = _grid_shapes(p)
    scal = lambda x: x.astype(jnp.float32).reshape(C, 1, 1)
    eta3 = scal(eta)
    valid3 = scal(jnp.ones((C,), bool) if valid is None else valid)
    LAUNCHES["batched_apply"] += 1
    common = dict(
        grid=(C, blocks),
        out_specs=pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0)),
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        interpret=interpret,
    )
    scal_spec = pl.BlockSpec((1, 1, 1), lambda c, j: (c, 0, 0))
    buf_spec = pl.BlockSpec((1, rows, LANES), lambda c, j: (c, j, 0))
    if mask is None:
        return pl.pallas_call(
            _batched_apply_kernel,
            in_specs=[scal_spec, scal_spec, buf_spec, buf_spec],
            input_output_aliases={2: 0},
            **common,
        )(eta3, valid3, p, g)
    mask_spec = pl.BlockSpec((rows, LANES), lambda c, j: (j, 0))
    return pl.pallas_call(
        _batched_apply_masked_kernel,
        in_specs=[scal_spec, scal_spec, buf_spec, buf_spec, mask_spec],
        input_output_aliases={2: 0},
        **common,
    )(eta3, valid3, p, g, mask)


# --------------------------------------------------------------------------
# legacy per-leaf kernels (benchmark baseline / single-tensor callers)
# --------------------------------------------------------------------------

def _norms_kernel(g_ref, gp_ref, dg_ref, gg_ref):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)
    gp = gp_ref[...].astype(jnp.float32)
    d = g - gp
    dg = jnp.sum(d * d)
    gg = jnp.sum(g * g)

    @pl.when(i == 0)
    def _init():
        dg_ref[0] = 0.0
        gg_ref[0] = 0.0

    dg_ref[0] += dg
    gg_ref[0] += gg


def _apply_kernel(eta_ref, p_ref, g_ref, out_ref):
    eta = eta_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    out_ref[...] = (p - eta * g).astype(out_ref.dtype)


def _pad_2d(x: jax.Array):
    """Flatten to (M, LANES) with zero padding; returns (x2d, orig_size)."""
    n = x.size
    m = -(-n // LANES)
    pad = m * LANES - n
    flat = x.reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(m, LANES), n


def norms(g: jax.Array, g_prev: jax.Array, *, interpret: bool = False):
    """(sum((g-gp)^2), sum(g^2)) over one tensor, single HBM pass."""
    g2, _ = _pad_2d(g)
    gp2, _ = _pad_2d(g_prev)
    m = g2.shape[0]
    rows = min(BLOCK_ROWS, m)
    grid = -(-m // rows)
    if m % rows:
        extra = grid * rows - m
        g2 = jnp.pad(g2, ((0, extra), (0, 0)))
        gp2 = jnp.pad(gp2, ((0, extra), (0, 0)))
    LAUNCHES["norms_leaf"] += 1
    dg, gg = pl.pallas_call(
        _norms_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[_SMEM, _SMEM],
        out_shape=[jax.ShapeDtypeStruct((1,), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.float32)],
        interpret=interpret,
    )(g2, gp2)
    return dg[0], gg[0]


def apply_update(p: jax.Array, g: jax.Array, eta, *,
                 interpret: bool = False) -> jax.Array:
    """p ← p − η·g, tiled through VMEM. Same shape/dtype as p."""
    p2, n = _pad_2d(p)
    g2, _ = _pad_2d(g)
    m = p2.shape[0]
    rows = min(BLOCK_ROWS, m)
    grid = -(-m // rows)
    if m % rows:
        extra = grid * rows - m
        p2 = jnp.pad(p2, ((0, extra), (0, 0)))
        g2 = jnp.pad(g2, ((0, extra), (0, 0)))
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    LAUNCHES["apply_leaf"] += 1
    out = pl.pallas_call(
        _apply_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(p2.shape, p.dtype),
        interpret=interpret,
    )(eta_arr, p2, g2)
    return out.reshape(-1)[:n].reshape(p.shape)
