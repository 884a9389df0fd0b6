"""Mamba2 SSD chunk kernel for TPU (Pallas).

The SSD algorithm splits the recurrence into (i) an intra-chunk dense part
(an L×L masked matmul — MXU work) and (ii) a cheap inter-chunk state scan.
This kernel computes, per (batch·head, chunk):

    cs      = cumsum(dt * A)                      (L,)
    M[q,k]  = (C_q·B_k) · exp(cs_q − cs_k) · dt_k    for k ≤ q
    y_intra = M @ x                               (L, P)
    S_c     = Σ_k exp(cs_L − cs_k)·dt_k · x_k ⊗ B_k  (P, N)  chunk summary
    cd      = exp(cs_L)                           chunk decay

The inter-chunk combine (h ← cd·h + S_c; y += C·h_prev·exp(cs)) stays in
jnp — it is elementwise/small and keeps the sequential dependency out of
the kernel. So do the cumulative sum cs and the per-position exp(cs)
and chunk decay exp(cs_L), which are cheap elementwise work outside the
MXU. Chunk L=64 with P=64, N=64: VMEM working set < 200 KB; the L×L and
L×P matmuls are MXU-shaped.

The kernel works head-major, on (B, H, ·) arrays that the wrapper
transposes to: every block is a 2-D tile whose last two dims meet the
TPU's (8, 128) tiling rule. Per-position scalars (dt, cs) come in twice,
as an (L, 1) column and a (1, L) row, so the kernel needs no transpose.

Grid: (B, H, nc).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

CHUNK = 64


def _ssd_chunk_kernel(x_ref, b_ref, c_ref, dtc_ref, dtr_ref, csc_ref,
                      csr_ref, y_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # (L,P)
    Bm = b_ref[...].astype(jnp.float32)              # (L,N)
    Cm = c_ref[...].astype(jnp.float32)              # (L,N)
    dt_col, dt_row = dtc_ref[...], dtr_ref[...]      # (L,1), (1,L)
    cs_col, cs_row = csc_ref[...], csr_ref[...]
    L = x.shape[0]

    # intra-chunk masked decay matmul
    diff = cs_col - cs_row                           # (q,k)
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(cols <= rows, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    M = CB * decay * dt_row
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    # chunk summary state S_c = (w ⊙ x)^T-style outer-product sum -> (P,N)
    w = jnp.exp(cs_col[L - 1:L, :] - cs_col) * dt_col   # (L,1)
    S_c = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    s_ref[...] = S_c.astype(s_ref.dtype)


def ssd_chunks(x, dt, dA, Bh, Ch, *, chunk: int = CHUNK,
               interpret=None):
    """x: (B,S,H,P), dt/dA: (B,S,H), Bh/Ch: (B,S,H,N) (heads expanded).

    Returns (y_intra (B,S,H,P), S_c (B,nc,H,P,N), chunk_decay (B,nc,H),
    exp_cs (B,S,H))."""
    interpret = interpret_mode(interpret)
    B, S, H, P = x.shape
    N = Bh.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, f"seq {S} not divisible by chunk {L}"
    nc = S // L

    f32 = jnp.float32
    cs = jnp.cumsum(dA.astype(f32).reshape(B, nc, L, H), axis=2)
    cs_h = jnp.transpose(cs, (0, 3, 1, 2))                # (B,H,nc,L)
    dt_h = jnp.transpose(dt.astype(f32).reshape(B, nc, L, H), (0, 3, 1, 2))
    col = lambda a: a.reshape(B, H, S, 1)
    row = lambda a: a.reshape(B, H, nc, 1, L)
    hm = lambda a: jnp.swapaxes(a, 1, 2)                  # (B,H,S,·)

    tile = lambda w: pl.BlockSpec((None, None, L, w),
                                  lambda b, h, c: (b, h, c, 0))
    row_spec = pl.BlockSpec((None, None, None, 1, L),
                            lambda b, h, c: (b, h, c, 0, 0))
    y, S_c = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(B, H, nc),
        in_specs=[tile(P), tile(N), tile(N), tile(1), row_spec, tile(1),
                  row_spec],
        out_specs=[
            tile(P),
            pl.BlockSpec((None, None, None, P, N),
                         lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), f32),
            jax.ShapeDtypeStruct((B, H, nc, P, N), f32),
        ],
        interpret=interpret,
    )(hm(x), hm(Bh), hm(Ch), col(dt_h), row(dt_h), col(cs_h), row(cs_h))
    ecs = jnp.exp(cs)                                     # (B,nc,L,H)
    return (hm(y), jnp.swapaxes(S_c, 1, 2), ecs[:, :, L - 1],
            ecs.reshape(B, S, H))
