"""Post-SPMD HLO inspection for sharding assertions.

After SPMD partitioning, every instruction in ``compiled.as_text()``
carries PER-DEVICE (local) shapes. If the client buffer of the sharded
flat engine (core/flat.py + FederationSpec.flat_spec) is kept sharded
end to end, its full global shape can never appear in the compiled
module, in either of its forms — any ``f32[C,N]`` or
``f32[C,N/128,128]`` (the client slab) hit means some op (an
all-gather, a resharding copy, a rematerialized concatenate) rebuilt
the unsharded buffer on one device. ``flat_buffer_report`` counts those
hits, which is the machine-checkable form of the ROADMAP open item "the
packed (C, N) buffer stays client-sharded end to end".
"""
from __future__ import annotations

import re
from typing import Dict, Sequence


def full_shape_lines(hlo_text: str, shape: Sequence[int],
                     dtype: str = "f32"):
    """HLO lines mentioning the full (global) ``dtype[shape]`` tensor."""
    dims = ",".join(str(int(d)) for d in shape)
    pat = re.compile(rf"\b{re.escape(dtype)}\[{dims}\]")
    return [ln for ln in hlo_text.splitlines() if pat.search(ln)]


def flat_buffer_report(hlo_text: str, C: int, N: int) -> Dict:
    """Count involuntary rematerializations of the packed client buffer.

    Returns {"full_shape": #lines with the global f32[C,N] or
             f32[C,N/128,128] shape,
             "gather_or_copy": #those lines that are all-gather/copy ops,
             "sample": first few offending lines}. A sharded round must
    report full_shape == 0 (the replicated engine reports dozens).
    """
    full = set(full_shape_lines(hlo_text, (C, N)))
    full |= set(full_shape_lines(hlo_text, (C, N // 128, 128)))
    lines = [ln for ln in hlo_text.splitlines() if ln in full]
    bad = [ln for ln in lines
           if "all-gather" in ln or re.search(r"\bcopy\(", ln)]
    return {"full_shape": len(lines), "gather_or_copy": len(bad),
            "sample": [ln.strip()[:160] for ln in lines[:4]]}


def assert_flat_buffer_sharded(compiled, C: int, N: int) -> Dict:
    """Raise AssertionError if the compiled module ever materializes the
    full (C, N) flat buffer; returns the report otherwise."""
    rep = flat_buffer_report(compiled.as_text(), C, N)
    assert rep["full_shape"] == 0, (
        f"packed ({C}, {N}) flat buffer rematerialized in compiled HLO: "
        f"{rep}")
    return rep


# ---------------------------------------------------------------------------
# compressed-round boundary check (repro.compression): no full-precision
# client delta may cross the CLIENT shard boundary (the simulated wire)
# ---------------------------------------------------------------------------
# the opcode of an HLO instruction is the token directly before its "(";
# operand references (%all-reduce.5) are %-prefixed and never match
_COLLECTIVE_OP_RE = re.compile(
    r"(?<![%.\w-])(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter)(?:-start|-done)?\(")
_F32_SHAPE_RE = re.compile(r"\bf32\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"(?:replica_groups|source_target_pairs)="
                        r"\{((?:\{[\d,]*\},?)*)\}")
_GROUP_RE = re.compile(r"\{([\d,]*)\}")


def _elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _parse_groups(line: str):
    """Device groups of a collective line, or None if unparseable (e.g.
    the iota replica-group format) — callers treat None as spanning.
    ``replica_groups={}`` (= one group of ALL devices) also returns
    None: it spans every client shard."""
    m = _GROUPS_RE.search(line)
    if not m:
        return None
    groups = [tuple(int(d) for d in g.group(1).split(",") if d)
              for g in _GROUP_RE.finditer(m.group(1))]
    return groups or None


def _client_coords(mesh, client_axes) -> Dict:
    """device id -> its coordinates along the CLIENT mesh axes."""
    import numpy as np
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    axis_idx = [mesh.axis_names.index(a) for a in client_axes]
    return {int(ids[idx]): tuple(idx[i] for i in axis_idx)
            for idx in np.ndindex(ids.shape)}


def fullprec_collective_report(hlo_text: str, *, max_elems: int,
                               client_coord_of: Dict = None) -> Dict:
    """Collectives that move >= ``max_elems`` f32 elements ACROSS client
    shards.

    Post-SPMD HLO shapes are per-device, so a collective that ships a
    client-indexed full-precision delta slab over the client axes shows
    up as an all-reduce/all-gather/permute of >= (C_local, N_local) f32
    elements whose replica groups mix devices with different client
    coordinates. Collectives whose groups stay WITHIN one client
    coordinate (``client_coord_of``) are intra-client reshards of the
    flat dim (the pack/unpack seam), not wire traffic, and are exempt;
    unparseable groups are conservatively treated as client-crossing.
    Returns {"collectives": #collective instructions, "fullprec":
    #violations, "sample": first few}.
    """
    lines = [ln for ln in hlo_text.splitlines()
             if _COLLECTIVE_OP_RE.search(ln)]
    bad = []
    for ln in lines:
        if not any(_elems(m.group(1)) >= max_elems
                   for m in _F32_SHAPE_RE.finditer(ln)):
            continue
        if client_coord_of is not None:
            groups = _parse_groups(ln)
            if groups is not None and all(
                    len({client_coord_of.get(d) for d in g}) <= 1
                    for g in groups):
                continue    # stays within one client coordinate
        bad.append(ln)
    return {"collectives": len(lines), "fullprec": len(bad),
            "sample": [ln.strip()[:160] for ln in bad[:4]]}


# ---------------------------------------------------------------------------
# fleet memory ceiling (repro.core.fed_loop.make_fleet_loop): only the
# sampled cohort's client state may be materialized wider than a scalar
# ---------------------------------------------------------------------------
_ANY_SHAPE_RE = re.compile(r"\b(?:f32|bf16|f16|s32|u32|s8|u8|pred)"
                           r"\[([0-9,]+)\]")


def cohort_materialization_report(hlo_text: str, num_registered: int,
                                  *, max_cols: int = 1) -> Dict:
    """Tensors wider than O(C_registered) scalars in the compiled HLO.

    The fleet loop's memory contract is that per-REGISTERED-client state
    stays 1-D: the arena's (C_registered,) scalar rows are the ONLY
    tensors allowed to carry the registered dimension, while everything
    two-dimensional (parameter slabs, gradients, batches) is bounded by
    the COHORT size C << C_registered. Any shape that contains the
    registered dim alongside >= ``max_cols + 1`` other elements (default:
    anything beyond a flat vector) means per-registered-client wide
    state leaked into the program — e.g. a (C_registered, N) gather the
    scheduler or arena scatter accidentally materialized. Returns
    {"vectors": #O(C_registered) 1-D hits, "wide": #violations,
    "sample": first few offending lines}.
    """
    vectors = wide = 0
    sample = []
    for ln in hlo_text.splitlines():
        worst = None
        for m in _ANY_SHAPE_RE.finditer(ln):
            dims = [int(d) for d in m.group(1).split(",") if d]
            if num_registered not in dims:
                continue
            cols = _elems(m.group(1)) // num_registered
            worst = max(worst or 0, cols)
        if worst is None:
            continue
        if worst > max_cols:
            wide += 1
            if len(sample) < 4:
                sample.append(ln.strip()[:160])
        else:
            vectors += 1
    return {"vectors": vectors, "wide": wide, "sample": sample}


def assert_cohort_only_materialization(compiled, num_registered: int, *,
                                       max_cols: int = 1) -> Dict:
    """Raise AssertionError if the compiled fleet program materializes
    any tensor wider than O(C_registered) scalars along the registered-
    client dimension; returns the report otherwise.

    ``max_cols`` relaxes the bound when wider per-registered-client
    state is intentional (e.g. an EF21 arena slab is (C_registered, N)
    by design — pass ``max_cols=N`` there, or skip the check: the
    ceiling being asserted is exactly that NO such slab exists in the
    EF-free configuration).
    """
    rep = cohort_materialization_report(compiled.as_text(),
                                        num_registered, max_cols=max_cols)
    assert rep["wide"] == 0, (
        f"fleet memory ceiling violated: tensor(s) wider than "
        f"({num_registered},)x{max_cols} materialized along the "
        f"registered-client dim: {rep}")
    return rep


def assert_no_fullprec_delta_collective(compiled, C: int, N: int, *,
                                        mesh, federation,
                                        max_payload_elems=None) -> Dict:
    """Assert the compiled compressed sharded round ships no
    full-precision (C, N) client delta across the client shard boundary
    — the machine-checkable form of "compression happens before the
    client-mean psum".

    In a correctly compressed round the largest legitimate f32 payload
    crossing the client axes is the (N/n_shards,) aggregated client
    mean (the compressors are chunk-local and run strictly before that
    psum). A client-crossing collective carrying >=
    (C_local, N/n_shards) f32 elements therefore means an uncompressed
    per-client delta slab went over the simulated wire. Needs
    C_local >= 2 to tell the two apart (raises ValueError otherwise —
    e.g. one-client-per-shard production specs).

    ``max_payload_elems`` optionally TIGHTENS the bound: a robust-
    aggregation round (repro.federation.faults) can declare its largest
    legitimate client-crossing payload — e.g. ``2 * n_loc`` for the
    aggregated mean plus the bucketed robust partial — so the check
    trips on anything bigger even when it is smaller than a full
    (C_local, N_local) slab. The default keeps the PR 4 compression
    bound.
    """
    import numpy as np
    client_axes, _ = federation.flat_axes(mesh)
    c_shards = int(np.prod([mesh.shape[a] for a in client_axes])) or 1
    n_shards = federation.flat_shards(mesh)
    c_loc, n_loc = C // max(1, c_shards), N // max(1, n_shards)
    if c_loc < 2:
        raise ValueError(
            "assert_no_fullprec_delta_collective needs >= 2 clients per "
            f"client shard to separate a delta slab from the aggregated "
            f"mean (C={C}, client shards={c_shards})")
    max_elems = c_loc * n_loc
    if max_payload_elems is not None:
        if max_payload_elems < 1:
            raise ValueError(
                f"max_payload_elems must be >= 1, got {max_payload_elems}")
        max_elems = min(max_elems, int(max_payload_elems) + 1)
    rep = fullprec_collective_report(
        compiled.as_text(), max_elems=max_elems,
        client_coord_of=_client_coords(mesh, client_axes))
    assert rep["fullprec"] == 0, (
        f"full-precision client delta (>= ({c_loc}, {n_loc}) f32) "
        f"crossed the client shard boundary: {rep}")
    return rep
