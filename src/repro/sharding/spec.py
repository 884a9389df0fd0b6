"""FederationSpec + sharding rules: how FL roles map onto mesh axes.

Production mesh (launch/mesh.py): (pod, data, model) = (2, 16, 16) multi-pod
or (data, model) = (16, 16) single-pod.

FL mapping:
  client_axes — mesh axes that enumerate simultaneously-trained clients
                (the FedAvg aggregation all-reduces over these);
  fsdp_axes   — within-client param/optimizer sharding (ZeRO-style);
  tp_axes     — tensor parallel (heads / experts / ffn).

Two stock specs:
  * cross_device : clients over (pod, data) — many small clients
    (tinyllama-class models, one model replica per (pod,data) coordinate,
    sharded over `model`).
  * cross_silo   : clients over (pod,) — 2 giant silos; each silo trains
    FSDP over `data` × TP over `model` (deepseek-class models).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class FederationSpec:
    client_axes: Tuple[str, ...]
    fsdp_axes: Tuple[str, ...]
    tp_axes: Tuple[str, ...] = ("model",)

    def clients_on(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.client_axes])) or 1

    # -- flat (C, N) buffer layout (core/flat.py packed engine) ------------
    def flat_axes(self, mesh: Mesh):
        """(client_axes, param_shard_axes) for the packed (C, N) buffer:
        C over the client axes, N over every remaining fsdp/tp axis present
        in the mesh. Disjoint by construction."""
        ca = tuple(a for a in self.client_axes if a in mesh.shape)
        na = tuple(a for a in self.fsdp_axes + self.tp_axes
                   if a in mesh.shape and a not in ca)
        return ca, na

    def flat_spec(self, mesh: Mesh) -> P:
        """PartitionSpec for the packed (C, N) flat buffer: clients over
        the client axes, the flat param dim over fsdp+tp axes. The layout
        must be built with ``shards=self.flat_shards(mesh)`` so every
        device's slab stays lane/row-block aligned."""
        ca, na = self.flat_axes(mesh)
        return P(ca if ca else None, na if na else None)

    def flat_client_spec(self, mesh: Mesh) -> P:
        """PartitionSpec for per-client (C,) vectors (η, θ, ‖g‖)."""
        ca, _ = self.flat_axes(mesh)
        return P(ca if ca else None)

    def flat_shards(self, mesh: Mesh) -> int:
        """Number of shards of the flat param dim N under flat_spec."""
        _, na = self.flat_axes(mesh)
        return int(np.prod([mesh.shape[a] for a in na])) or 1


def cross_device(mesh: Mesh) -> FederationSpec:
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return FederationSpec(client_axes=axes, fsdp_axes=())


def cross_silo(mesh: Mesh) -> FederationSpec:
    if "pod" in mesh.shape:
        return FederationSpec(client_axes=("pod",), fsdp_axes=("data",))
    # single-pod: the pod IS the silo -> one client, FSDP+TP inside it.
    return FederationSpec(client_axes=(), fsdp_axes=("data",))


def get_federation_spec(kind: str, mesh: Mesh) -> FederationSpec:
    return {"cross_device": cross_device, "cross_silo": cross_silo}[kind](mesh)


# ---------------------------------------------------------------------------
# Param sharding rules: regex on the param path -> PartitionSpec (rightmost
# dims). Leading stacked-layer axes are padded with None automatically.
# ---------------------------------------------------------------------------
def _param_rules(spec: FederationSpec):
    fsdp = spec.fsdp_axes or None
    tp = spec.tp_axes or None
    f = fsdp[0] if fsdp else None
    t = tp[0] if tp else None
    return [
        # embeddings / head
        (r"embed$",                    (t, f)),
        (r"lm_head$",                  (f, t)),
        # attention
        (r"attn/wq$",                  (f, t, None)),
        (r"attn/w[kv]$",               (f, "kv", None)),
        (r"attn/wo$",                  (t, None, f)),
        (r"attn/b[qkv]$",              (None, None)),
        # MLA
        (r"attn/wq_a$",                (f, None)),
        (r"attn/wq_b$",                (None, t, None)),
        (r"attn/wkv_a$",               (f, None)),
        (r"attn/w[kv]_b$",             (None, t, None)),
        # cross attention
        (r"xattn/wq$",                 (f, t, None)),
        (r"xattn/w[kv]$",              (f, "kv", None)),
        (r"xattn/wo$",                 (t, None, f)),
        # dense mlp
        (r"mlp/w_(gate|in)$",          (f, t)),
        (r"mlp/w_out$",                (t, f)),
        (r"mlp/b_in$",                 (t,)),
        (r"mlp/b_out$",                (None,)),
        # moe
        (r"moe/router$",               (f, None)),
        # routed experts are stored sharded and gathered whole where the
        # grouped product runs (models/moe.py: no expert parallelism)
        (r"moe/w_(gate|in)$",          (t, f, None)),
        (r"moe/w_out$",                (t, None, f)),
        (r"moe/shared/w_(gate|in)$",   (f, t)),
        (r"moe/shared/w_out$",         (t, f)),
        # mamba2
        (r"mixer/w_zx$",               (f, t)),
        (r"mixer/w_dt$",               (f, "heads_t")),
        (r"mixer/conv_w$",             (None, t)),
        (r"mixer/conv_b$",             (t,)),
        (r"mixer/(A_log|dt_bias|D_skip)$", ("heads_t",)),
        (r"mixer/norm$",               (t,)),
        (r"mixer/w_out$",              (t, f)),
        # mlstm / slstm
        (r"mixer/w_up$",               (f, t)),
        (r"mixer/w[qkv]$",             (t, None)),
        (r"mixer/w_if$",               (t, None)),
        (r"mixer/w_x$",                (f, t)),
        (r"mixer/r$",                  (None, "hd_t", None)),
        (r"mixer/ff_gate$",            (f, t)),
        (r"mixer/ff_out$",             (t, f)),
        # mtp
        (r"mtp/proj$",                 (f, t)),
    ]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_pspec(spec: FederationSpec, path: str, leaf) -> P:
    """PartitionSpec for one param leaf. Axis names 'kv'/'heads_t'/'hd_t'
    mean: use tp if the dim is divisible by the tp size, else None."""
    rules = _param_rules(spec)
    for pat, dims in rules:
        if re.search(pat, path):
            nd = leaf.ndim
            dims = tuple(dims)
            if len(dims) > nd:     # un-stacked rule longer than leaf rank
                dims = dims[-nd:]
            pad = (None,) * (nd - len(dims))
            return P(*(pad + dims))
    return P(*((None,) * leaf.ndim))


def _resolve_conditional(pspec: P, shape, mesh: Mesh, tp_axis: str) -> P:
    """Resolve 'kv'/'heads_t'/'hd_t' placeholders to tp-or-None based on
    divisibility; also drop any tp/fsdp assignment that doesn't divide."""
    out = []
    for dim, name in zip(shape, pspec):
        if name in ("kv", "heads_t", "hd_t"):
            name = tp_axis
        if name is None:
            out.append(None)
            continue
        axes = name if isinstance(name, tuple) else (name,)
        size = int(np.prod([mesh.shape.get(a, 1) for a in axes]))
        out.append(name if size and dim % size == 0 else None)
    return P(*out)


def make_param_shardings(spec: FederationSpec, mesh: Mesh, params_shape):
    """NamedSharding pytree matching a params shape-pytree."""
    tp_axis = spec.tp_axes[0] if spec.tp_axes else None

    def one(path, leaf):
        ps = param_pspec(spec, _path_str(path), leaf)
        ps = _resolve_conditional(ps, leaf.shape, mesh, tp_axis)
        ps = _dedupe(ps)
        return NamedSharding(mesh, ps)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def _dedupe(ps: P) -> P:
    """A mesh axis may appear at most once in a PartitionSpec."""
    seen = set()
    out = []
    for name in ps:
        axes = name if isinstance(name, tuple) else (name,)
        if name is not None and any(a in seen for a in axes):
            out.append(None)
        else:
            out.append(name)
            seen.update(a for a in axes if a)
    return P(*out)


# ---------------------------------------------------------------------------
# Batch / cache / state shardings
# ---------------------------------------------------------------------------
def batch_shardings(spec: FederationSpec, mesh: Mesh, batch_shape):
    """FL round batches: leaves (C, K, b, ...): C over client axes, b over
    fsdp axes."""
    ca = spec.client_axes if len(spec.client_axes) > 1 else \
        (spec.client_axes[0] if spec.client_axes else None)
    fa = spec.fsdp_axes[0] if spec.fsdp_axes else None

    def one(leaf):
        dims = [ca, None, fa] + [None] * (leaf.ndim - 3)
        return NamedSharding(mesh, P(*dims[:leaf.ndim]))

    return jax.tree.map(one, batch_shape)


def serve_batch_shardings(mesh: Mesh, batch_shape, *, data_axes=("data",)):
    """Serving: batch dim over all data-like axes present in the mesh."""
    axes = tuple(a for a in ("pod",) + tuple(data_axes) if a in mesh.shape)
    axes = axes if len(axes) > 1 else (axes[0] if axes else None)

    def one(leaf):
        dims = [axes] + [None] * (leaf.ndim - 1)
        # tiny batch (long_500k B=1): replicate instead
        if leaf.ndim == 0 or (leaf.shape and leaf.shape[0] == 1):
            dims[0] = None
        return NamedSharding(mesh, P(*dims[:max(leaf.ndim, 1)])
                             if leaf.ndim else P())

    return jax.tree.map(one, batch_shape)


def cache_shardings(spec: FederationSpec, mesh: Mesh, cache_shape, *,
                    batch_size: int, seq_shard: bool = False):
    """Decode caches: shard batch dim over data axes when divisible; for
    B=1 long-context, shard the sequence/state dim over `model`.

    seq_shard=True (beyond-paper §Perf): ALSO shard the cache sequence dim
    over `model` — for MQA/GQA archs whose few KV heads leave the tensor
    axis idle during decode, each device then reads only 1/tp of the cache
    (softmax over the sharded length lowers to small stat all-reduces)."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) or 1
    tp = spec.tp_axes[0] if spec.tp_axes else None
    tsize = mesh.shape.get(tp, 1) if tp else 1

    def one(path, leaf):
        p = _path_str(path)
        if leaf.ndim == 0 or p.endswith(("t", "positions")):
            return NamedSharding(mesh, P(*((None,) * leaf.ndim)))
        dims = [None] * leaf.ndim
        # stacked layer axis first, batch second for run caches
        bdim = 1 if p.startswith("runs/") or "enc_kv" in p else 0
        if leaf.ndim > bdim and leaf.shape[bdim] == batch_size \
                and batch_size % dsize == 0 and dsize > 1:
            dims[bdim] = data_axes if len(data_axes) > 1 else data_axes[0]
            if seq_shard and leaf.ndim > bdim + 1 and tp \
                    and leaf.shape[bdim + 1] % tsize == 0 \
                    and leaf.shape[bdim + 1] >= 1024:
                dims[bdim + 1] = tp
        elif leaf.ndim > bdim + 1 and tp and leaf.shape[bdim + 1] % tsize == 0:
            # B too small: shard the next (seq/state) dim over model
            dims[bdim + 1] = tp
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


# ---------------------------------------------------------------------------
# Logical-activation rules (installed via models.common.logical_rules)
# ---------------------------------------------------------------------------
class LogicalRules:
    """Maps logical activation axis names to mesh axes and applies
    with_sharding_constraint. Works under the client vmap too: jax inserts
    UNCONSTRAINED for the batched (client) dim, so client sharding is free
    to propagate from the batch inputs (verified empirically).

    serve=True maps the batch dim over all data-like axes (global serving
    batch); serve=False maps it over the within-client fsdp axes."""

    def __init__(self, spec: FederationSpec, mesh: Mesh, *,
                 serve: bool = False, seq_shard: bool = False):
        fsdp = spec.fsdp_axes[0] if spec.fsdp_axes else None
        tp = spec.tp_axes[0] if spec.tp_axes else None
        if serve:
            data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
            batch = (data_axes if len(data_axes) > 1 else
                     (data_axes[0] if data_axes else None))
        else:
            batch = fsdp
        self.mesh = mesh
        # seq_shard (beyond-paper, Megatron-SP analog): keep the residual
        # stream sharded over the tensor axis along SEQUENCE between blocks
        # so row-parallel matmul epilogues lower to reduce-scatter instead
        # of all-reduce (and norms compute on 1/tp of the tokens).
        self.map = {"batch": batch, "seq": tp if seq_shard else None,
                    "embed": None, "heads": tp, "kv_heads": None,
                    "ffn": tp, "vocab": tp}
        if seq_shard:
            # heads/ffn/vocab constraints would conflict with seq on the
            # same axis inside blocks; keep only the residual-stream rule.
            self.map.update(heads=None, ffn=None, vocab=None)

    def constrain(self, x, names):
        dims = [self.map.get(n) if n else None for n in names]
        if len(dims) != x.ndim:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, _dedupe(P(*dims))))
