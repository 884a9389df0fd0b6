"""Sharding rules: PartitionSpecs are valid (divisible, deduped) for every
architecture's param tree on the production mesh *shape* (validated
structurally — the real 512-device lowering is the dry-run's job)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.sharding.spec import (FederationSpec, _dedupe, param_pspec,
                                 _resolve_conditional, _path_str)


class FakeMesh:
    """Duck-typed mesh: only .shape (dict) is needed by the rules."""
    def __init__(self, shape):
        self.shape = shape


MESHES = {
    "single": FakeMesh({"data": 16, "model": 16}),
    "multi": FakeMesh({"pod": 2, "data": 16, "model": 16}),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh_id", ["single", "multi"])
def test_param_specs_divide(arch, mesh_id):
    import jax.numpy as jnp
    cfg = get_config(arch)
    mesh = MESHES[mesh_id]
    spec = FederationSpec(client_axes=("data",), fsdp_axes=(),
                          tp_axes=("model",))
    model = build_model(cfg, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.key(0))

    def check(path, leaf):
        ps = param_pspec(spec, _path_str(path), leaf)
        ps = _resolve_conditional(ps, leaf.shape, mesh, "model")
        ps = _dedupe(ps)
        assert len(ps) == leaf.ndim
        seen = set()
        for dim, name in zip(leaf.shape, ps):
            if name is None:
                continue
            axes = name if isinstance(name, tuple) else (name,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (arch, _path_str(path), leaf.shape, ps)
            for a in axes:
                assert a not in seen
                seen.add(a)

    jax.tree_util.tree_map_with_path(check, shapes)


@pytest.mark.parametrize("mesh_id,kind,want_c,want_n,want_shards", [
    ("single", "cross_device", ("data",), ("model",), 16),
    ("multi", "cross_device", ("pod", "data"), ("model",), 16),
    ("single", "cross_silo", None, ("data", "model"), 256),
    ("multi", "cross_silo", ("pod",), ("data", "model"), 256),
])
def test_flat_spec_maps_clients_and_param_shards(mesh_id, kind, want_c,
                                                 want_n, want_shards):
    """flat_spec: C over the client axes, N over the remaining fsdp/tp
    axes; flat_shards is the N-dim shard count the packer pads to."""
    from repro.sharding.spec import get_federation_spec
    mesh = MESHES[mesh_id]
    spec = get_federation_spec(kind, mesh)
    ps = spec.flat_spec(mesh)
    assert len(ps) == 2
    # compared as PartitionSpecs: jax may store a one-axis tuple entry
    # as the bare axis name
    assert ps == P(want_c, want_n)
    assert spec.flat_shards(mesh) == want_shards
    # client and param-shard axes never overlap
    ca, na = spec.flat_axes(mesh)
    assert not set(ca) & set(na)
    cs = spec.flat_client_spec(mesh)
    assert len(cs) <= 1 and (len(cs) == 0 or cs == P(want_c))


def test_dedupe():
    assert tuple(_dedupe(P("model", "model"))) == ("model", None)
    assert tuple(_dedupe(P(("pod", "data"), "data"))) == (("pod", "data"),
                                                          None)


def test_big_weights_are_sharded():
    """No single >100M-element tensor may end up fully replicated."""
    import jax.numpy as jnp
    cfg = get_config("deepseek-v3-671b")
    mesh = MESHES["multi"]
    spec = FederationSpec(client_axes=("pod",), fsdp_axes=("data",),
                          tp_axes=("model",))
    model = build_model(cfg, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.key(0))

    def check(path, leaf):
        n = int(np.prod(leaf.shape))
        if n < 100_000_000:
            return
        ps = _dedupe(_resolve_conditional(
            param_pspec(spec, _path_str(path), leaf), leaf.shape, mesh,
            "model"))
        assert any(a is not None for a in ps), (_path_str(path), leaf.shape)

    jax.tree_util.tree_map_with_path(check, shapes)
