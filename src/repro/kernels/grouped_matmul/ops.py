"""Grouped matrix product over the experts a MoE layer holds, with its
backward.

Rows arrive sorted by expert, the held experts' groups first and one
trailing group of rows that no held expert serves. The work follows the
rows actually routed to held experts: on a TPU the Pallas grouped
matmul (JAX's megablox ``gmm``/``tgmm``) visits only the row tiles of
the held groups, its grid sized at run time from ``group_sizes``, so
row tiles past them are never loaded or multiplied; off the TPU the jnp
reference of ``ref.py`` computes the same product.

``grouped_matmul(x, w, group_sizes)``: x (m, k), w (E_held, k, n),
group_sizes (E_held + 1,) int32 -> (m, n), rows of the trailing group
0. Under ``vmap`` (the round body's client axis) each kernel runs once
per batch row.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.grouped_matmul import ref

TM = 128            # row tile: the granularity at which empty rows skip


def _megablox():
    # the package's __init__ rebinds ``gmm`` to its custom-vjp wrapper
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def tiling(m, k, n):
    """(row, contraction, column) tiles: whole k and n up to 1024, else
    512-wide blocks (f32 blocks of at most 2 MB of VMEM each)."""
    return (TM, k if k <= 1024 else 512, n if n <= 1024 else 512)


def _pad_rows(x, group_sizes):
    """Rows padded to a multiple of the row tile; the padding joins the
    trailing (unserved) group."""
    pad = -x.shape[0] % TM
    if not pad:
        return x, group_sizes
    return (jnp.pad(x, ((0, pad), (0, 0))),
            group_sizes.at[-1].add(pad))


def _gmm(x, w, group_sizes, *, transpose_rhs, pallas, interpret):
    if not pallas:
        return ref.gmm(x, w, group_sizes, transpose_rhs)
    m = x.shape[0]
    xp, gs = _pad_rows(x, group_sizes)
    n = w.shape[1] if transpose_rhs else w.shape[2]
    out = _megablox().gmm(xp, w, gs, jnp.float32,
                          tiling(xp.shape[0], x.shape[1], n),
                          transpose_rhs=transpose_rhs, interpret=interpret)
    return out[:m].astype(x.dtype)


def _tgmm(x, dy, group_sizes, *, num_groups, pallas, interpret):
    if not pallas:
        return ref.tgmm(x, dy, group_sizes, num_groups)
    xp, gs = _pad_rows(x, group_sizes)
    dyp, _ = _pad_rows(dy, group_sizes)
    out = _megablox().tgmm(xp.T, dyp, gs, jnp.float32,
                           tiling(xp.shape[0], x.shape[1], dy.shape[1]),
                           None, num_groups, interpret=interpret)
    return out.astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _kernels(pallas, interpret, num_groups):
    """The forward product, its transpose and the weight gradient, each
    run once per row of a vmapped batch (the grid of a Pallas call with
    run-time sizes cannot take a batch axis)."""
    seq = jax.custom_batching.sequential_vmap
    kw = dict(pallas=pallas, interpret=interpret)
    fwd = seq(functools.partial(_gmm, transpose_rhs=False, **kw))
    bwd_x = seq(functools.partial(_gmm, transpose_rhs=True, **kw))
    bwd_w = seq(functools.partial(_tgmm, num_groups=num_groups, **kw))
    return fwd, bwd_x, bwd_w


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(x, w, group_sizes, pallas, interpret):
    fwd, _, _ = _kernels(pallas, interpret, w.shape[0])
    return fwd(x, w, group_sizes)


def _grouped_fwd(x, w, group_sizes, pallas, interpret):
    return (_grouped(x, w, group_sizes, pallas, interpret),
            (x, w, group_sizes))


def _grouped_bwd(pallas, interpret, res, dy):
    x, w, group_sizes = res
    _, bwd_x, bwd_w = _kernels(pallas, interpret, w.shape[0])
    return (bwd_x(dy, w, group_sizes), bwd_w(x, dy, group_sizes), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, group_sizes, *, pallas=None, interpret=None):
    """See the module doc. ``pallas`` None: the Pallas kernel on a TPU,
    the jnp reference elsewhere; ``pallas=True`` off the TPU runs the
    kernel in interpret mode (tests)."""
    if pallas is None:
        pallas = kernels.on_tpu()
    return _grouped(x, w, group_sizes.astype(jnp.int32), bool(pallas),
                    kernels.interpret_mode(interpret) if pallas else False)
