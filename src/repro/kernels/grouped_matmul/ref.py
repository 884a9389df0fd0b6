"""Plain jnp grouped matrix products: the reference the Pallas path is
held to, and the path off the TPU.

Rows of ``x`` come sorted by group: group g holds rows
[offset_g, offset_g + group_sizes[g]). ``w`` has one matrix per group
it computes; ``group_sizes`` may carry more groups than ``w`` (rows of
a trailing group that no matrix serves give 0)."""
from __future__ import annotations

import jax.numpy as jnp


def _row_group(group_sizes, m):
    """(m,) group of each row; rows past every group get len(sizes)."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def gmm(x, w, group_sizes, transpose_rhs=False):
    """(m, k) rows x group matrices (G, k, n) -> (m, n); with
    ``transpose_rhs`` the matrices are (G, n, k) and used transposed."""
    gid = _row_group(group_sizes, x.shape[0])
    n = w.shape[1] if transpose_rhs else w.shape[2]
    out = jnp.zeros((x.shape[0], n), jnp.float32)
    for g in range(w.shape[0]):
        wg = w[g].T if transpose_rhs else w[g]
        out = out + jnp.where((gid == g)[:, None], x @ wg, 0.0)
    return out.astype(x.dtype)


def tgmm(x, dy, group_sizes, num_groups):
    """Per group, x_gᵀ dy_g: (m, k), (m, n) -> (num_groups, k, n)."""
    gid = _row_group(group_sizes, x.shape[0])
    return jnp.stack([
        jnp.where((gid == g)[:, None], x, 0.0).T @ dy
        for g in range(num_groups)]).astype(x.dtype)
