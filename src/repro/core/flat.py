"""FlatParams: pack a param/grad pytree into ONE lane-aligned flat buffer.

The Δ-SGD local step is two global reductions plus an axpy (Eq. (4),
Alg. 1) — work that is bandwidth-bound and identical for every leaf and
every client. Launching it per leaf (and vmapping per client) pays
kernel-launch and padding overhead proportional to ``num_leaves ×
num_clients``. ``FlatLayout`` collapses both axes: the pytree becomes a
single ``(N,)`` f32 buffer (``N`` padded so the Pallas kernels never
re-pad), and the client axis becomes the leading dim of a dense ``(C, N)``
buffer that one 2-D-grid kernel sweeps in a single launch.

Layout is computed once per (treedef, shapes, dtypes) and cached; packing
is one concatenate, unpacking is slice + reshape + cast views. Tail
padding is zero-filled so global norm reductions over the padded buffer
are exact. Every pack and unpack runs under the named scope ``flat``, so
a device trace can tell the flat layer's passes from the work around
them (``perfbench/metrics/flat_ms_per_round.py`` reads it).

Mixed precision: the buffer is always f32. Leaves whose dtype is narrower
(bf16) are tracked by ``round_mask`` — a per-element mask the fused apply
kernel uses to reproduce the reference path's per-step
``(p32 − η·g32).astype(bf16)`` rounding bit-for-bit, so a flat K-step
scan matches the per-leaf pytree path.

Sharded layouts: under an SPMD mesh the N dim of the (C, N) buffer is
sharded over the fsdp/tp axes (``FederationSpec.flat_spec``). A layout
built with ``shards=S`` pads N so that N/S is itself lane- and
row-block-aligned — each device's contiguous slab is directly kernel-
ready, no re-padding inside ``shard_map``. All padding still lives in the
global tail (zero-filled), so global norm reductions stay exact. The
layout cache key includes ``shards``: switching meshes in one process can
never reuse a stale padded layout.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128          # TPU lane width; every buffer is a (M, LANES) grid
BLOCK_ROWS = 1024    # kernel row-block; kernels/delta_sgd imports these


class LeafSpec(NamedTuple):
    offset: int                # element offset into the flat buffer
    size: int                  # number of valid elements
    shape: Tuple[int, ...]     # original leaf shape (per client)
    dtype: Any                 # original leaf dtype


class FlatLayout(NamedTuple):
    treedef: Any
    leaves: Tuple[LeafSpec, ...]
    size: int                  # total valid elements
    padded_size: int           # N: multiple of shards*rows*LANES
    shards: int = 1            # N-dim shard count the padding aligns to


_LAYOUT_CACHE: dict = {}


def _padded(total: int, shards: int = 1) -> int:
    """Round ``total`` up so that each of ``shards`` equal contiguous
    slabs splits evenly into (rows, LANES) row blocks."""
    per = max(1, -(-total // shards))
    m0 = max(1, -(-per // LANES))
    rows = min(BLOCK_ROWS, m0)
    m = -(-m0 // rows) * rows
    return m * LANES * shards


def layout_of(tree, *, batched: bool = False, shards: int = 1) -> FlatLayout:
    """Flat layout for ``tree`` (cached). With ``batched=True`` the leaves
    carry a leading client axis which is excluded from the layout.
    ``shards`` is the N-dim shard count of the target mesh
    (``FederationSpec.flat_shards``); it is part of the cache key, so two
    meshes with different shard counts never share a padded layout."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape[1:] if batched else l.shape)
                   for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    key = (treedef, shapes, dtypes, int(shards))
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    specs, off = [], 0
    for shape, dtype in zip(shapes, dtypes):
        if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
            raise TypeError(f"FlatLayout supports f32/bf16 leaves, got "
                            f"{dtype}")
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        specs.append(LeafSpec(off, size, shape, dtype))
        off += size
    layout = FlatLayout(treedef, tuple(specs), off, _padded(off, shards),
                        int(shards))
    _LAYOUT_CACHE[key] = layout
    return layout


def round_mask(layout: FlatLayout) -> Optional[jax.Array]:
    """(N,) f32 mask, 1.0 where the element belongs to a sub-f32 leaf and
    must be rounded to that dtype after every update; None if all-f32."""
    if all(s.dtype == jnp.dtype(jnp.float32) for s in layout.leaves):
        return None
    m = np.zeros((layout.padded_size,), np.float32)
    for s in layout.leaves:
        if s.dtype != jnp.dtype(jnp.float32):
            m[s.offset:s.offset + s.size] = 1.0
    return jnp.asarray(m)


def pack(tree, layout: Optional[FlatLayout] = None) -> jax.Array:
    """Pytree -> (N,) f32 buffer (zero tail padding). One concatenate."""
    layout = layout or layout_of(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    with jax.named_scope("flat"):
        parts = [l.reshape(-1).astype(jnp.float32) for l in leaves]
        pad = layout.padded_size - layout.size
        if pad:
            parts.append(jnp.zeros((pad,), jnp.float32))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unpack(buf: jax.Array, layout: FlatLayout, *, cast: bool = True):
    """(N,) buffer -> pytree with original shapes/dtypes (slice views).

    ``cast=False`` keeps every leaf in the buffer's f32 — used by the
    async aggregation buffer, whose delta accumulator must not lose the
    sub-bf16 bits of a weighted delta sum."""
    with jax.named_scope("flat"):
        leaves = [buf[s.offset:s.offset + s.size].reshape(s.shape)
                  for s in layout.leaves]
        if cast:
            leaves = [l.astype(s.dtype)
                      for l, s in zip(leaves, layout.leaves)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def pack_batched(tree, layout: Optional[FlatLayout] = None) -> jax.Array:
    """Pytree with leading client axis C on every leaf -> (C, N) f32."""
    layout = layout or layout_of(tree, batched=True)
    leaves = jax.tree_util.tree_leaves(tree)
    C = leaves[0].shape[0]
    with jax.named_scope("flat"):
        parts = [l.reshape(C, -1).astype(jnp.float32) for l in leaves]
        pad = layout.padded_size - layout.size
        if pad:
            parts.append(jnp.zeros((C, pad), jnp.float32))
        return (jnp.concatenate(parts, axis=1) if len(parts) > 1
                else parts[0])


def unpack_batched(buf: jax.Array, layout: FlatLayout, *,
                   cast: bool = True):
    """(C, N) buffer -> pytree with (C, *shape) leaves, original dtypes.

    ``cast=False`` keeps every leaf in the buffer's f32 — used for the
    per-client EF21 error-feedback state (repro.compression), whose
    reconstruction tree must not lose sub-bf16 bits between rounds."""
    C = buf.shape[0]
    with jax.named_scope("flat"):
        leaves = [buf[:, s.offset:s.offset + s.size].reshape((C,) + s.shape)
                  for s in layout.leaves]
        if cast:
            leaves = [l.astype(s.dtype)
                      for l, s in zip(leaves, layout.leaves)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)
