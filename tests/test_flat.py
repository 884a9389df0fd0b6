"""Flat-parameter Δ-SGD engine: packer round-trips, batched kernel
parity, and full multi-round equivalence against the per-leaf pytree
oracle (core.delta_sgd.delta_sgd_update) in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat as fp
from repro.core.delta_sgd import (delta_sgd_init, delta_sgd_reset,
                                  delta_sgd_update, flat_delta_sgd_init,
                                  flat_delta_sgd_step)
from repro.kernels.delta_sgd import delta_sgd as dk
from repro.kernels.delta_sgd import ref as dref
from repro.launch.mesh import make_mesh

GAMMA, DELTA, ETA0, THETA0 = 2.0, 0.1, 0.2, 1.0


def _mixed_tree(rng, scale=1.0):
    """bf16 params / f32 params mixed in one tree (odd, non-lane shapes)."""
    return {"emb": jnp.asarray(rng.normal(size=(33, 7)) * scale,
                               jnp.bfloat16),
            "w": jnp.asarray(rng.normal(size=(129,)) * scale, jnp.float32),
            "b": jnp.asarray(rng.normal(size=(5, 3, 2)) * scale,
                             jnp.float32)}


# ------------------------------------------------------------------ packer
def test_pack_unpack_roundtrip_mixed_dtypes(rng):
    tree = _mixed_tree(rng)
    layout = fp.layout_of(tree)
    buf = fp.pack(tree, layout)
    assert buf.shape == (layout.padded_size,)
    assert layout.padded_size % fp.LANES == 0
    # tail padding is zero (exact global reductions over the buffer)
    assert float(jnp.sum(jnp.abs(buf[layout.size:]))) == 0.0
    back = fp.unpack(buf, layout)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))


def test_pack_unpack_batched_roundtrip(rng):
    C = 4
    tree = {"a": jnp.asarray(rng.normal(size=(C, 17, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(C, 40)), jnp.bfloat16)}
    layout = fp.layout_of(tree, batched=True)
    buf = fp.pack_batched(tree, layout)
    assert buf.shape == fp.slab_shape(C, layout)
    assert buf.shape == (C, layout.padded_size // fp.LANES, fp.LANES)
    # every element sits at its flat offset: row r, lane l holds element
    # r·128 + l of the client's (N,) buffer
    one = fp.layout_of(jax.tree.map(lambda x: x[0], tree))
    want = np.stack([np.asarray(fp.pack(jax.tree.map(lambda x: x[c], tree),
                                        one)) for c in range(C)])
    np.testing.assert_array_equal(np.asarray(buf).reshape(C, -1), want)
    back = fp.unpack_batched(buf, layout)
    for k in tree:
        assert back[k].shape == tree[k].shape
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))


def test_layout_cached_per_treedef(rng):
    t1 = _mixed_tree(rng)
    t2 = _mixed_tree(rng, scale=3.0)
    assert fp.layout_of(t1) is fp.layout_of(t2)


def test_round_mask_marks_bf16_segments(rng):
    tree = _mixed_tree(rng)
    layout = fp.layout_of(tree)
    mask = fp.round_mask(layout)
    assert mask is not None
    n_bf16 = sum(s.size for s in layout.leaves
                 if s.dtype == jnp.dtype(jnp.bfloat16))
    assert float(jnp.sum(mask)) == n_bf16
    f32_tree = {"x": jnp.zeros((7,), jnp.float32)}
    assert fp.round_mask(fp.layout_of(f32_tree)) is None


# ---------------------------------------------------------- batched kernels
@pytest.mark.parametrize("C,n_leaves", [(1, 1), (3, 5), (8, 2)])
def test_batched_norms_matches_ref(C, n_leaves, rng):
    tree = {f"w{i}": jnp.asarray(rng.normal(size=(C, 50 + 13 * i)),
                                 jnp.float32) for i in range(n_leaves)}
    layout = fp.layout_of(tree, batched=True)
    g = fp.pack_batched(tree, layout)
    gp = g * -0.3 + 0.1
    dg, gg = dk.batched_norms(g, gp, interpret=True)
    dg_r, gg_r = dref.batched_norms_ref(g, gp)
    np.testing.assert_allclose(dg, dg_r, rtol=1e-5)
    np.testing.assert_allclose(gg, gg_r, rtol=1e-5)


def test_batched_apply_per_client_eta_and_mask(rng):
    C = 3
    tree = {"a": jnp.asarray(rng.normal(size=(C, 200)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(size=(C, 77)), jnp.float32)}
    layout = fp.layout_of(tree, batched=True)
    p = fp.pack_batched(tree, layout)
    g = p * 0.2 + 0.05
    eta = jnp.asarray([0.1, 0.5, 1.3], jnp.float32)
    mask = fp.round_mask(layout)
    assert mask.shape == p.shape[1:]
    out = dk.batched_apply(p, g, eta, mask=mask, interpret=True)
    ref = dref.batched_apply_ref(p, g, eta, mask)
    assert out.shape == p.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # masked segments are exactly bf16-representable
    seg = fp.unpack_batched(out, layout)["a"]
    rows = out.reshape(C, -1)
    np.testing.assert_array_equal(
        np.asarray(rows[:, :200].astype(jnp.bfloat16), np.float32),
        np.asarray(seg, np.float32))


@pytest.mark.parametrize("masked", [False, True])
def test_batched_apply_invalid_lane_holds_p(masked, rng):
    """A lane whose ``valid`` is off keeps its P bit for bit even with
    NaN in its G (the zeroing happens inside the kernel); every other
    lane is p − η·g bit for bit, as with no flag at all."""
    C = 4
    tree = {"a": jnp.asarray(rng.normal(size=(C, 300)), jnp.bfloat16),
            "b": jnp.asarray(rng.normal(size=(C, 77)), jnp.float32)}
    layout = fp.layout_of(tree, batched=True)
    p = fp.pack_batched(tree, layout)
    g = p * 0.2 + 0.05
    g = g.at[2].set(jnp.nan)
    eta = jnp.asarray([0.1, 0.5, 0.0, 1.3], jnp.float32)
    valid = jnp.asarray([True, True, False, True])
    mask = fp.round_mask(layout) if masked else None
    out = dk.batched_apply(p, g, eta, valid=valid, mask=mask,
                           interpret=True)
    ref = jax.jit(lambda p, g, e, v: dref.batched_apply_ref(
        p, g, e, mask, valid=v))(p, g, eta, valid)
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(p[2]))
    np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(p[2]))
    # p − η·g as the platform computes it, on a gradient with no NaN
    g_clean = jnp.nan_to_num(g)
    want = jax.jit(lambda p, g, e: dref.batched_apply_ref(p, g, e, mask))(
        p, g_clean, eta)
    no_flag = dk.batched_apply(p, g_clean, eta, mask=mask, interpret=True)
    healthy = [0, 1, 3]
    for got in (out, ref, no_flag):
        np.testing.assert_array_equal(np.asarray(got)[healthy],
                                      np.asarray(want)[healthy])


# -------------------------------------------------- full-round parity oracle
def test_flat_step_matches_oracle_multi_round_mixed_dtype(rng):
    """Satellite acceptance: fused flat path == delta_sgd_update oracle
    (interpret mode) over TWO full K=3 rounds — covers the k=0 reset
    branch — on a mixed bf16/f32 tree, tolerance ≤ 1e-5."""
    C, K, R = 3, 3, 2
    tree = _mixed_tree(rng)
    layout = fp.layout_of(tree)
    mask = fp.round_mask(layout)
    N = layout.padded_size

    # per-step per-client synthetic grads in the leaf dtypes
    grad_seq = [[jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), tree)
        for _ in range(K)] for _ in range(C)]

    # oracle: per-client pytree loop with round-start resets
    ref_params, ref_etas = [], []
    for c in range(C):
        p = tree
        s = delta_sgd_init(p, eta0=ETA0, theta0=THETA0)
        for r in range(R):
            s = delta_sgd_reset(s, eta0=ETA0, theta0=THETA0)
            for k in range(K):
                p, s = delta_sgd_update(p, grad_seq[c][k], s, gamma=GAMMA,
                                        delta=DELTA, eta0=ETA0)
        ref_params.append(p)
        ref_etas.append(float(s.eta))

    # flat engine: one (C, N/128, 128) slab, two launches per step
    slab = fp.slab_shape(C, layout)
    P = jnp.stack([fp.pack(tree, layout)] * C).reshape(slab)
    for r in range(R):
        S = flat_delta_sgd_init(C, layout, eta0=ETA0, theta0=THETA0)
        assert S.prev_grads.shape == slab
        for k in range(K):
            G = jnp.stack([fp.pack(grad_seq[c][k], layout)
                           for c in range(C)]).reshape(slab)
            P, S = flat_delta_sgd_step(P, G, S, gamma=GAMMA, delta=DELTA,
                                       eta0=ETA0, mask=mask,
                                       backend="pallas", interpret=True)

    got = fp.unpack_batched(P, layout)
    for c in range(C):
        for key in tree:
            np.testing.assert_allclose(
                np.asarray(got[key][c], np.float32),
                np.asarray(ref_params[c][key], np.float32),
                rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(S.eta[c]), ref_etas[c], rtol=1e-5)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_flat_round_engine_matches_vmap_engine(backend, rng):
    """make_fl_round(flat=...) == the vmapped per-client engine."""
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    D, C, K = 5, 3, 4

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    results = {}
    for eng in (False, backend):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat=eng))
        st = init_fl_state({"x": x0}, sopt)
        for _ in range(2):
            st, m, loc = rnd(st, batches)
        results[eng] = (np.asarray(st.params["x"]), float(m["eta_mean"]),
                        float(m["loss"]), np.asarray(loc["x"]))
    for a, b in zip(results[False], results[backend]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_flat_round_weighted_matches_vmap(rng):
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    D, C, K = 4, 3, 2

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    w = jnp.asarray([0.7, 0.2, 0.1], jnp.float32)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    out = {}
    for eng in (False, "xla"):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    weighted=True, flat=eng))
        st = init_fl_state({"x": jnp.zeros((D,), jnp.float32)}, sopt)
        st, _, _ = rnd(st, batches, client_weights=w)
        out[eng] = np.asarray(st.params["x"])
    np.testing.assert_allclose(out["xla"], out[False], rtol=1e-5)


def test_flat_round_two_launches_per_local_step(rng):
    """Launch-count acceptance: the scan body is traced once, so tracing
    one flat round builds exactly 2 pallas calls — i.e. every local step
    executes 2 launches — independent of leaf count, client count, and
    K."""
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)

    def quad(params, batch):
        r = batch["A"] @ params["x"] + batch["A"] @ params["y"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    for C, K, D in ((2, 3, 4), (5, 2, 6)):
        batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)),
                                    jnp.float32),
                   "b": jnp.asarray(rng.normal(size=(C, K, 8)),
                                    jnp.float32)}
        rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="pallas")
        st = init_fl_state({"x": jnp.zeros((D,), jnp.float32),
                            "y": jnp.zeros((D,), jnp.float32)}, sopt)
        dk.reset_launch_count()
        jax.eval_shape(lambda s, b: rnd(s, b), st, batches)
        assert dk.launch_count() == 2, (C, K, dict(dk.LAUNCHES))


def test_flat_engine_rejects_non_delta_sgd():
    from repro.core import get_client_opt, get_server_opt, make_fl_round
    with pytest.raises(ValueError):
        make_fl_round(lambda *a: (0.0, {}), get_client_opt("sgd"),
                      get_server_opt("fedavg"), num_rounds=1, flat=True)


# ------------------------------------------------------------- sharded
# 8 virtual CPU devices come from conftest's XLA_FLAGS default; a
# user-provided XLA_FLAGS may override it, so the mesh tests skip when
# fewer devices are available.
needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")


def _mesh8():
    return make_mesh((4, 2), ("data", "model"))


def _fl_problem(rng, C=8, K=3, D=300, E=40):
    """Quadratic FL problem with a mixed f32/bf16 param tree."""
    def quad(params, batch):
        x32 = params["x"].astype(jnp.float32)
        e32 = params["e"].astype(jnp.float32)
        r = batch["A"] @ x32 - batch["b"] + jnp.sum(e32) * 0.01
        return 0.5 * jnp.mean(r * r) + 0.05 * jnp.mean(e32 * e32), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32),
              "e": jnp.asarray(rng.normal(size=E), jnp.bfloat16)}
    return quad, params, batches


def test_layout_cache_key_includes_shard_count(rng):
    """Bugfix: switching meshes (shard counts) in one process must never
    reuse a stale padded layout."""
    tree = _mixed_tree(rng)
    l1 = fp.layout_of(tree)
    l2 = fp.layout_of(tree, shards=2)
    l8 = fp.layout_of(tree, shards=8)
    assert l1 is not l2 and l2 is not l8
    assert l1.shards == 1 and l2.shards == 2 and l8.shards == 8
    for l in (l2, l8):
        per = l.padded_size // l.shards
        assert l.padded_size % l.shards == 0
        assert per % fp.LANES == 0          # every slab lane-aligned
        m = per // fp.LANES
        rows = min(fp.BLOCK_ROWS, m)
        assert m % rows == 0                # ... and row-block aligned
        assert l.padded_size >= l.size
    # same shard count again -> cache hit, not a new object
    assert fp.layout_of(tree, shards=2) is l2
    # back to the unsharded layout: still the original, not the stale one
    assert fp.layout_of(tree) is l1


@needs8
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.slow
def test_sharded_step_matches_replicated_flat(backend, rng):
    """flat_delta_sgd_step_sharded == flat_delta_sgd_step over a K-step
    run on an 8-device mesh, incl. the bf16 round-mask path."""
    from repro.core.delta_sgd import flat_delta_sgd_step_sharded
    from repro.sharding.spec import cross_device
    mesh = _mesh8()
    spec = cross_device(mesh)
    pspec = spec.flat_spec(mesh)
    C = 8
    tree = _mixed_tree(rng)
    lay_s = fp.layout_of(tree, shards=spec.flat_shards(mesh))
    lay_r = fp.layout_of(tree)
    Ps = jnp.stack([fp.pack(tree, lay_s)] * C).reshape(
        fp.slab_shape(C, lay_s))
    Pr = jnp.stack([fp.pack(tree, lay_r)] * C).reshape(
        fp.slab_shape(C, lay_r))
    Ss = flat_delta_sgd_init(C, lay_s, eta0=ETA0, theta0=THETA0)
    Sr = flat_delta_sgd_init(C, lay_r, eta0=ETA0, theta0=THETA0)
    kw = dict(gamma=GAMMA, delta=DELTA, eta0=ETA0)
    interp = backend == "pallas" or None
    for _ in range(3):
        gt = jax.tree.map(
            lambda l: jnp.asarray(rng.normal(size=(C,) + l.shape), l.dtype),
            tree)
        Gs = fp.pack_batched(gt, fp.layout_of(gt, batched=True,
                                              shards=lay_s.shards))
        Gr = fp.pack_batched(gt, fp.layout_of(gt, batched=True))
        Ps, Ss = flat_delta_sgd_step_sharded(
            Ps, Gs, Ss, mask=fp.round_mask(lay_s), mesh=mesh, pspec=pspec,
            backend=backend, interpret=interp, **kw)
        Pr, Sr = flat_delta_sgd_step(Pr, Gr, Sr, mask=fp.round_mask(lay_r),
                                     backend=backend, interpret=interp,
                                     **kw)
    got, ref = fp.unpack_batched(Ps, lay_s), fp.unpack_batched(Pr, lay_r)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k], np.float32),
                                   np.asarray(ref[k], np.float32),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Ss.eta), np.asarray(Sr.eta),
                               rtol=1e-5)


@needs8
@pytest.mark.parametrize("fed", ["cross_device", "cross_silo"])
@pytest.mark.slow
def test_sharded_round_matches_replicated_flat(fed, rng):
    """Tentpole acceptance: sharded pack -> K-step scan -> unpack matches
    the replicated flat engine to <= 1e-5 on an 8-device host mesh, for
    both stock federation specs, incl. the bf16 round-mask path."""
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    from repro.sharding.spec import get_federation_spec
    mesh = _mesh8()
    spec = get_federation_spec(fed, mesh)
    quad, params, batches = _fl_problem(rng)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    out = {}
    for name, kw in (("repl", {}),
                     ("shard", dict(mesh=mesh, federation=spec))):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat="xla", **kw))
        st = init_fl_state(params, sopt)
        for _ in range(2):
            st, m, loc = rnd(st, batches)
        out[name] = (np.asarray(st.params["x"]),
                     np.asarray(st.params["e"], dtype=np.float32),
                     np.asarray([m["eta_mean"], m["eta_min"], m["eta_max"],
                                 m["loss"]], dtype=np.float32),
                     np.asarray(loc["x"]))
    for a, b in zip(out["repl"], out["shard"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@needs8
@pytest.mark.slow
def test_sharded_round_hlo_never_materializes_full_buffer(rng):
    """Acceptance: the compiled sharded round contains NO involuntary
    resharding copies (or any other rematerialization) of the full
    (C, N) buffer — every instruction that touches it is on local
    slabs. The replicated engine (sanity) does materialize it."""
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    from repro.sharding.hlo import assert_flat_buffer_sharded, \
        flat_buffer_report
    from repro.sharding.spec import cross_device
    mesh = _mesh8()
    spec = cross_device(mesh)
    quad, params, batches = _fl_problem(rng)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    C = 8
    st = init_fl_state(params, sopt)

    rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="xla",
                        mesh=mesh, federation=spec)
    lay = fp.layout_of(params, shards=spec.flat_shards(mesh))
    compiled = jax.jit(rnd).lower(st, batches).compile()
    rep = assert_flat_buffer_sharded(compiled, C, lay.padded_size)
    assert rep["gather_or_copy"] == 0

    # sanity: the check has teeth — the replicated engine's HLO is full
    # of (C, N)-shaped instructions
    rnd0 = make_fl_round(loss, copt, sopt, num_rounds=10, flat="xla")
    lay0 = fp.layout_of(params)
    txt0 = jax.jit(rnd0).lower(st, batches).compile().as_text()
    assert flat_buffer_report(txt0, C, lay0.padded_size)["full_shape"] > 0


@needs8
def test_sharded_round_two_launches_per_local_step(rng):
    """The shard_map step keeps the 2-launches-per-local-step property:
    tracing one sharded flat round builds exactly 2 pallas calls."""
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    from repro.sharding.spec import cross_device
    mesh = _mesh8()
    spec = cross_device(mesh)
    quad, params, batches = _fl_problem(rng)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="pallas",
                        mesh=mesh, federation=spec)
    st = init_fl_state(params, sopt)
    dk.reset_launch_count()
    jax.eval_shape(lambda s, b: rnd(s, b), st, batches)
    assert dk.launch_count() == 2, dict(dk.LAUNCHES)


def test_eta_metrics_nan_for_non_delta_and_finite_for_delta(rng):
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    D, C, K = 4, 2, 2

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    for opt, finite in (("sgd", False), ("delta_sgd", True)):
        rnd = jax.jit(make_fl_round(loss, get_client_opt(opt, lr=0.05),
                                    sopt, num_rounds=10))
        st = init_fl_state({"x": jnp.zeros((D,), jnp.float32)}, sopt)
        _, m, _ = rnd(st, batches)
        for key in ("eta_mean", "eta_min", "eta_max"):
            assert key in m
            assert np.isfinite(float(m[key])) == finite, (opt, key)
        if finite:
            assert float(m["eta_min"]) <= float(m["eta_mean"]) \
                <= float(m["eta_max"])


# ----------------------------------------------------- property testing
# pack/unpack roundtrip identity across random pytree shapes, bf16/f32
# mixes, and shard counts. Runs under real hypothesis when installed and
# under the vendored deterministic fallback otherwise (conftest).
from hypothesis import given, settings, strategies as st  # noqa: E402


def _prop_tree(sizes, bf16_mask, cdim=None):
    """Deterministic tree from drawn leaf sizes: mixed ranks (0-D/1-D/
    2-D), mixed f32/bf16 per the mask bits, values seeded by the draw."""
    rng = np.random.default_rng(sum(sizes) * 31 + bf16_mask + 7)
    tree = {}
    for i, size in enumerate(sizes):
        if size == 1 and i % 2:
            shape = ()                      # scalar leaf
        elif size > 12 and size % 3 == 0:
            shape = (3, size // 3)
        else:
            shape = (size,)
        if cdim is not None:
            shape = (cdim,) + shape
        dtype = jnp.bfloat16 if (bf16_mask >> i) & 1 else jnp.float32
        tree[f"l{i}"] = jnp.asarray(rng.normal(size=shape) * 3.0, dtype)
    return tree


@settings(max_examples=25, deadline=None)
@given(sizes=st.lists(st.integers(1, 400), min_size=1, max_size=6),
       bf16_mask=st.integers(0, 63), shards=st.integers(1, 4))
@pytest.mark.slow
def test_pack_unpack_roundtrip_property(sizes, bf16_mask, shards):
    tree = _prop_tree(sizes, bf16_mask)
    layout = fp.layout_of(tree, shards=shards)
    # shard alignment: each of the `shards` contiguous slabs is itself
    # lane-aligned, and all padding lives in the zero-filled global tail
    assert layout.padded_size % (shards * fp.LANES) == 0
    assert layout.size == sum(
        int(np.prod(l.shape, dtype=np.int64)) if l.shape else 1
        for l in jax.tree_util.tree_leaves(tree))
    buf = fp.pack(tree, layout)
    assert buf.shape == (layout.padded_size,)
    assert float(jnp.sum(jnp.abs(buf[layout.size:]))) == 0.0
    back = fp.unpack(buf, layout)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        assert back[k].shape == tree[k].shape
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))
    # round_mask marks exactly the sub-f32 lanes
    mask = fp.round_mask(layout)
    n_bf16 = sum(s.size for s in layout.leaves
                 if s.dtype == jnp.dtype(jnp.bfloat16))
    assert (mask is None and n_bf16 == 0) or \
        float(jnp.sum(mask)) == n_bf16


@settings(max_examples=15, deadline=None)
@given(sizes=st.lists(st.integers(1, 300), min_size=1, max_size=5),
       bf16_mask=st.integers(0, 31), shards=st.integers(1, 4),
       cdim=st.integers(1, 5))
@pytest.mark.slow
def test_pack_unpack_batched_roundtrip_property(sizes, bf16_mask, shards,
                                                cdim):
    tree = _prop_tree(sizes, bf16_mask, cdim=cdim)
    layout = fp.layout_of(tree, batched=True, shards=shards)
    buf = fp.pack_batched(tree, layout)
    assert buf.shape == fp.slab_shape(cdim, layout)
    rows = buf.reshape(cdim, -1)
    assert float(jnp.sum(jnp.abs(rows[:, layout.size:]))) == 0.0
    back = fp.unpack_batched(buf, layout)
    raw = fp.unpack_batched(buf, layout, cast=False)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        assert raw[k].dtype == jnp.float32      # cast=False keeps f32
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))
    # the (treedef, shapes, dtypes, shards) cache key: same draw hits
    # the cached layout object
    assert fp.layout_of(tree, batched=True, shards=shards) is layout
