"""Apply a CompressionSpec to the packed (C, N) flat delta.

``compress_flat`` is the simulate-the-wire primitive: it maps each
client's flat delta row to the value the SERVER would reconstruct after
the client shipped the compressed representation (int8 values + scales,
or top-k value/index pairs). The compressed form itself never needs to
materialize as a host object — quantize/dequantize run back to back on
device, and the wire cost is accounted analytically
(``CompressionSpec.wire_bytes``).

Per-client bandwidth levels: a bandwidth-heterogeneous scenario draws a
(C,) level vector each round (repro.federation.scenarios); each client
lane then gets the compressor of ITS level (0=none, 1=int8, 2=topk) via
a lane select — same pattern as the compute axis's η=0 lane masks, no
extra launches per lane.

``compress_flat_sharded`` is the mesh-native variant: every op is
chunk-local (chunk = LANES elements, and per-shard slabs are whole
row blocks by FlatLayout construction), so the whole compressor runs
inside ``shard_map`` on each device's local slab with ZERO cross-shard
traffic. Compression therefore happens strictly BEFORE the client-mean
psum: the only full-precision tensor that crosses the client shard
boundary afterwards is the (N_shard,) aggregated mean — machine-checked
by ``repro.sharding.hlo.assert_no_fullprec_delta_collective``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.compression.spec import CompressionSpec


def _kernels(backend: str, interpret: Optional[bool]):
    """(quant_dequant, topk) callables for the backend. ``pallas`` uses
    the fused kernels (interpret mode off-TPU), ``xla`` the pure-jnp
    oracle — identical math, which is what meshed/pjit callers use."""
    if backend == "pallas":
        from repro.kernels import interpret_mode
        from repro.kernels.compress import compress as k
        ip = interpret_mode(interpret)

        def qdq(x):
            return k.dequantize_int8(*k.quantize_int8(x, interpret=ip),
                                     interpret=ip)

        return qdq, (lambda x, kk: k.topk_mask(x, kk, interpret=ip))
    from repro.kernels.compress import ref as r
    return (lambda x: r.dequantize_int8_ref(*r.quantize_int8_ref(x)),
            lambda x, kk: r.topk_mask_ref(x, kk))


def compress_flat(delta: jax.Array, spec: CompressionSpec, *,
                  levels: Optional[jax.Array] = None,
                  backend: str = "xla",
                  interpret: Optional[bool] = None) -> jax.Array:
    """(C, N) f32 delta -> (C, N) f32 server-side reconstruction.

    ``levels`` is the optional (C,) int32 per-client bandwidth draw
    (None = every client at ``spec.kind``). Deterministic and
    chunk-local, so sharded and replicated rounds agree exactly.
    """
    qdq, topk = _kernels(backend, interpret)
    if levels is None:
        if spec.kind == "int8":
            return qdq(delta)
        if spec.kind == "topk":
            return topk(delta, spec.k)
        return delta
    # per-client level select: compute each enabled representation once
    # for the whole buffer, then pick per client lane
    out = jnp.where((levels == 1)[:, None], qdq(delta), delta)
    return jnp.where((levels == 2)[:, None], topk(delta, spec.k), out)


def compress_flat_sharded(delta: jax.Array, spec: CompressionSpec, *,
                          mesh, pspec,
                          levels: Optional[jax.Array] = None,
                          backend: str = "xla",
                          interpret: Optional[bool] = None) -> jax.Array:
    """``compress_flat`` on a mesh-sharded (C, N) buffer: the compressor
    runs inside ``shard_map`` on each device's (C_loc, N_loc) slab —
    chunk locality guarantees no collective is emitted, so compression
    completes strictly before the client-mean psum."""
    from jax.sharding import PartitionSpec as PS

    ca = pspec[0] if len(pspec) > 0 else None
    na = pspec[1] if len(pspec) > 1 else None
    buf, vec = PS(ca, na), PS(ca)
    with_levels = levels is not None

    def local(d, *rest):
        lv = rest[0] if with_levels else None
        return compress_flat(d, spec, levels=lv, backend=backend,
                             interpret=interpret)

    ins, specs = [delta], [buf]
    if with_levels:
        ins.append(levels)
        specs.append(vec)
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=buf, check_vma=False)
    return fn(*ins)
