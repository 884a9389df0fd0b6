"""FlatParams: pack a param/grad pytree into ONE lane-aligned flat buffer.

The Δ-SGD local step is two global reductions plus an axpy (Eq. (4),
Alg. 1) — work that is bandwidth-bound and identical for every leaf and
every client. Launching it per leaf (and vmapping per client) pays
kernel-launch and padding overhead proportional to ``num_leaves ×
num_clients``. ``FlatLayout`` collapses both axes: the pytree becomes a
single ``(N,)`` f32 buffer (``N`` padded so the Pallas kernels never
re-pad), and the client axis becomes the leading dim of a client slab
that one 2-D-grid kernel sweeps in a single launch.

The client slab is stored as ``(C, N/128, 128)`` (``slab_shape``): the
Δ-SGD kernel pair's own operand form. On a TPU a ``(C, N)`` f32 array
and that form keep their bytes in different tiled orders, so the two are
not views of each other: every conversion is a full copy of the slab.
The local-step scan therefore carries P, G and the previous gradients
in the slab form from the round-start broadcast to the round tail;
``pack_batched`` writes it and ``unpack_batched`` reads it. Server-side
``(N,)`` vectors and the round-tail consumers that want ``(C, N)``
(compression, robust aggregation) convert once a round at most.

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

Sharded layouts: under an SPMD mesh the N dim of a (C, N) buffer, and
the N/128 dim of a slab, is sharded over the fsdp/tp axes
(``FederationSpec.flat_spec``; a slab's lanes are never sharded). A
layout built with
``shards=S`` pads N so that N/S is itself lane- and row-block-aligned —
each device's contiguous slab is directly kernel-ready, no re-padding
inside ``shard_map``. All padding still lives in the
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


def slab_shape(num_clients: int, layout: FlatLayout) -> Tuple[int, ...]:
    """Shape of the client slab: ``(C, N/128, 128)``."""
    return (num_clients, layout.padded_size // LANES, LANES)


def round_mask(layout: FlatLayout) -> Optional[jax.Array]:
    """(N/128, 128) f32 mask in a slab row's form, 1.0 where the element
    belongs to a sub-f32 leaf and must be rounded to that dtype after
    every update; None if all-f32."""
    if all(s.dtype == jnp.dtype(jnp.float32) for s in layout.leaves):
        return None
    m = np.zeros((layout.padded_size,), np.float32)
    for s in layout.leaves:
        if s.dtype != jnp.dtype(jnp.float32):
            m[s.offset:s.offset + s.size] = 1.0
    return jnp.asarray(m.reshape(-1, LANES))


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
    """(N,) buffer, or one slab row ``(N/128, 128)`` (a mean over the
    client slab), -> pytree with original shapes/dtypes (slice views).

    ``cast=False`` keeps every leaf in the buffer's f32 — used by the
    async aggregation buffer, whose delta accumulator must not lose the
    sub-bf16 bits of a weighted delta sum."""
    if buf.ndim == 2:
        return jax.tree.map(lambda l: l[0],
                            unpack_batched(buf[None], layout, cast=cast))
    with jax.named_scope("flat"):
        leaves = [buf[s.offset:s.offset + s.size].reshape(s.shape)
                  for s in layout.leaves]
        if cast:
            leaves = [l.astype(s.dtype)
                      for l, s in zip(leaves, layout.leaves)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _row_runs(layout: FlatLayout):
    """The slab's rows in order, grouped into the runs ``pack_batched``
    writes whole, so that no ``(C, N)`` intermediate has to be relaid
    out: ``("leaf", i, start, n)`` is n full rows holding elements
    ``[start, start + n·128)`` of leaf i (``None``: the zero tail);
    ``("mixed", pieces)`` is whole rows shared by leaf ends, short leaves
    and the tail, ``pieces`` the ``(i, start, length)`` spans in order."""
    segs = [(i, s.size) for i, s in enumerate(layout.leaves)]
    segs.append((None, layout.padded_size - layout.size))
    runs, mixed, fill = [], [], 0
    for i, size in segs:
        start = 0
        while start < size:
            if fill == 0 and size - start >= LANES:
                if mixed:
                    runs.append(("mixed", tuple(mixed)))
                    mixed = []
                n = (size - start) // LANES
                runs.append(("leaf", i, start, n))
                start += n * LANES
                continue
            take = min(LANES - fill, size - start)
            mixed.append((i, start, take))
            fill = (fill + take) % LANES
            start += take
    if mixed:
        runs.append(("mixed", tuple(mixed)))
    return runs


def pack_batched(tree, layout: Optional[FlatLayout] = None) -> jax.Array:
    """Pytree with leading client axis C on every leaf -> the
    ``(C, N/128, 128)`` f32 client slab (zero tail padding). The slab is
    assembled from whole-row runs (``_row_runs``): a leaf's full rows
    come straight from the leaf, and only the rows it shares with its
    neighbours are built lane by lane. On a layout sharded over a mesh
    the (C, N) concatenate is split into rows instead, shard-locally."""
    layout = layout or layout_of(tree, batched=True)
    leaves = jax.tree_util.tree_leaves(tree)
    C = leaves[0].shape[0]
    with jax.named_scope("flat"):
        flats = [l.reshape(C, -1).astype(jnp.float32) for l in leaves]
        if layout.shards > 1:
            # rows sharded over the mesh: a (C, N) concatenate splits
            # into the shards' rows locally, row runs would not
            pad = layout.padded_size - layout.size
            if pad:
                flats.append(jnp.zeros((C, pad), jnp.float32))
            rows = (jnp.concatenate(flats, axis=1) if len(flats) > 1
                    else flats[0])
            return rows.reshape(slab_shape(C, layout))

        def span(i, start, length):
            if i is None:
                return jnp.zeros((C, length), jnp.float32)
            return flats[i][:, start:start + length]
        parts = []
        for run in _row_runs(layout):
            if run[0] == "leaf":
                _, i, start, n = run
                parts.append(span(i, start, n * LANES).reshape(C, n, LANES))
            else:
                spans = [span(*p) for p in run[1]]
                rows = (jnp.concatenate(spans, axis=1) if len(spans) > 1
                        else spans[0])
                parts.append(rows.reshape(C, -1, LANES))
        return (jnp.concatenate(parts, axis=1) if len(parts) > 1
                else parts[0])


def unpack_batched(buf: jax.Array, layout: FlatLayout, *,
                   cast: bool = True):
    """Client slab ``(C, N/128, 128)`` -> pytree with (C, *shape)
    leaves, original dtypes. Each leaf reads only the slab rows it
    spans (on a layout sharded over a mesh, the rows are first merged
    shard-locally into (C, N)). A ``(C, N)`` buffer (the EF21 carry)
    holds the same elements and is read the same way.

    ``cast=False`` keeps every leaf in the buffer's f32 — used for the
    per-client EF21 error-feedback state (repro.compression), whose
    reconstruction tree must not lose sub-bf16 bits between rounds."""
    C = buf.shape[0]
    with jax.named_scope("flat"):
        if layout.shards > 1:
            # rows sharded over the mesh: merging them into (C, N) stays
            # on each shard, while a leaf's row span may straddle shards
            rows = buf.reshape(C, -1)
            spans = [(rows, s.offset) for s in layout.leaves]
        else:
            slab = buf.reshape(slab_shape(C, layout))
            spans = []
            for s in layout.leaves:
                r0 = s.offset // LANES
                r1 = -(-(s.offset + s.size) // LANES)
                spans.append((slab[:, r0:r1].reshape(C, -1),
                              s.offset - r0 * LANES))
        leaves = [rows[:, a:a + s.size].reshape((C,) + s.shape)
                  for (rows, a), s in zip(spans, layout.leaves)]
        if cast:
            leaves = [l.astype(s.dtype)
                      for l, s in zip(leaves, layout.leaves)]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)
