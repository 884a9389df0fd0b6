"""Flash attention for TPU (Pallas): causal GQA with optional sliding
window, online-softmax accumulation over KV blocks.

The kernel works head-major, on (B, H, S, hd): the wrapper transposes
in and out, so each block is a (block, hd) tile whose last two dims
meet the TPU's (8, 128) tiling rule (hd is the whole last dim).

Grid: (B, H, num_q_blocks, num_kv_blocks). TPU executes the grid
sequentially with the last dim innermost, so the (m, l, acc) running state
for one (b, h, qi) lives in VMEM scratch across the kv sweep:

  kv == 0      : init m = -inf, l = 0, acc = 0
  every block  : masked scores -> online-softmax update (MXU matmuls)
  kv == last   : out = acc / l

Block sizes default to (128, 128): q/k/v tiles of (128, hd) with
hd ∈ {64, 128} keep the working set ≤ ~¼ MB — far under the ~16 MB VMEM —
and are MXU-aligned (128×128 systolic array). GQA is handled in the index
map: kv head = h // (H // KV), so no KV duplication in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               causal: bool, window: Optional[int], block_q: int,
               block_k: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[...].astype(jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _final():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,S,H,hd), k/v: (B,T,KV,hd) -> (B,S,H,hd)."""
    interpret = interpret_mode(interpret)
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    # pad seq dims to block multiples
    Sp = -(-S // block_q) * block_q
    Tp = -(-T // block_k) * block_k
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        if not causal:
            raise ValueError("non-causal padding needs an explicit mask")
    nq, nk = Sp // block_q, Tp // block_k

    kernel = functools.partial(_fa_kernel, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, nk=nk)
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))   # head-major
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return jnp.swapaxes(out, 1, 2)[:, :S]
