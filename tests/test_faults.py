"""Fault injection + robust aggregation (repro.federation.faults).

PR-6 acceptance tier: deterministic fault draws off axis 4 of the round
key, the always-on numerical guards (NaN lane latching + ETA_CLAMP), the
RobustAgg ladder (mean/clip/trimmed/median, replicated + Pallas +
bucketed sharded), byzantine-defense behavior (plain mean measurably
diverges under 10% corruption while clip/trimmed stay within 10% of the
clean final loss), quorum degradation (a skipped round leaves params
bit-identical and increments the skipped counter in the host AND fused
engines), the 2-launches-per-local-step invariant with guards + faults
active, and fused-vs-host bit-exactness under active faults."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (flatten_fl_state, get_client_opt, get_server_opt,
                        init_fl_state, make_fl_loop, make_fl_round,
                        make_loss, unflatten_fl_state)
from repro.core import flat as fp
from repro.core.delta_sgd import (ETA_CLAMP, FlatDeltaSGDState,
                                  flat_delta_sgd_init, flat_delta_sgd_step)
from repro.federation import get_scenario
from repro.federation.faults import (FaultModel, RobustAgg,
                                     robust_aggregate,
                                     robust_aggregate_sharded)
from repro.launch.mesh import make_mesh

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")


def _lanes_equal(a, b):
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))


# ------------------------------------------------------------ fault draws
def test_fault_draw_deterministic():
    """Same key -> identical lanes (reproducible from (seed, round));
    a different round key perturbs them."""
    fm = FaultModel(drop_rate=0.4, nan_rate=0.2, byzantine_rate=0.3,
                    overstale_rate=0.3)
    key = jax.random.key(7)
    a, b = fm.draw(key, 64, 8), fm.draw(key, 64, 8)
    _lanes_equal(a, b)
    c = fm.draw(jax.random.fold_in(key, 1), 64, 8)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, c))
    # dropped clients die strictly mid-round: 1 <= drop_step < K
    ds = np.asarray(a.drop_step)
    assert ds.shape == (64,) and ds.dtype == np.int32
    assert np.all((ds == 8) | ((ds >= 1) & (ds < 8)))
    assert np.any(ds < 8)


def test_fault_draw_rate_extremes():
    key = jax.random.key(0)
    clean = FaultModel()
    assert not clean.active
    lanes = clean.draw(key, 16, 4)
    assert np.all(np.asarray(lanes.drop_step) == 4)
    assert np.all(np.asarray(lanes.nan_step) == 4)
    assert not np.any(np.asarray(lanes.byzantine))
    assert not np.any(np.asarray(lanes.overstale))
    allbad = FaultModel(drop_rate=1.0, nan_rate=1.0, byzantine_rate=1.0,
                        overstale_rate=1.0)
    lanes = allbad.draw(key, 16, 4)
    assert np.all(np.asarray(lanes.drop_step) < 4)
    assert np.all(np.asarray(lanes.nan_step) < 4)
    assert np.all(np.asarray(lanes.byzantine))
    assert np.all(np.asarray(lanes.overstale))


def test_fault_and_robust_specs_validated():
    with pytest.raises(ValueError):
        FaultModel(drop_rate=1.5)
    with pytest.raises(ValueError):
        FaultModel(nan_rate=-0.1)
    with pytest.raises(KeyError):
        RobustAgg(kind="bogus")
    with pytest.raises(ValueError):
        RobustAgg(trim_frac=0.5)
    with pytest.raises(ValueError):
        RobustAgg(clip_norm=0.0)
    with pytest.raises(ValueError):
        get_scenario("sync_iid", quorum=-1)


# ------------------------------------------------- in-step numerical guards
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_nan_guard_latches_and_freezes_lane(backend, rng):
    """A non-finite gradient drops the lane (η=0, params untouched),
    latches ``valid`` off for the rest of the round, and never leaks NaN
    into the client slab or the lane's scalar state (the rolled
    prev_grads keep the raw gradient, which the latched lane never
    applies)."""
    params = {"x": jnp.asarray(rng.normal(size=40), jnp.float32)}
    layout = fp.layout_of(params)
    C = 4
    slab = fp.slab_shape(C, layout)
    P = jnp.broadcast_to(fp.pack(params, layout).reshape(slab[1:])[None],
                         slab)
    S = flat_delta_sgd_init(C, layout, eta0=0.1, theta0=1e8)
    G = jnp.asarray(rng.normal(size=slab), jnp.float32)
    G_bad = G.at[2].set(jnp.nan)
    kw = dict(gamma=2.0, delta=0.1, eta0=0.1, backend=backend)
    P1, S1 = flat_delta_sgd_step(P, G_bad, S, **kw)
    assert np.all(np.isfinite(np.asarray(P1)))
    np.testing.assert_array_equal(np.asarray(P1[2]), np.asarray(P[2]))
    assert np.asarray(S1.valid).tolist() == [True, True, False, True]
    # prev_grads roll the RAW gradient (the valid-lane zeroing happens
    # inside the apply); the latched lane's NaN reaches no scalar state
    np.testing.assert_array_equal(np.asarray(S1.prev_grads),
                                  np.asarray(G_bad))
    for v in (S1.eta, S1.theta, S1.prev_grad_norm):
        assert np.all(np.isfinite(np.asarray(v)))
    # a clean step afterwards must NOT resurrect the lane (latching)
    P2, S2 = flat_delta_sgd_step(P1, G, S1, **kw)
    assert np.asarray(S2.valid).tolist() == [True, True, False, True]
    np.testing.assert_array_equal(np.asarray(P2[2]), np.asarray(P[2]))
    for v in (S2.eta, S2.theta, S2.prev_grad_norm):
        assert np.all(np.isfinite(np.asarray(v)))
    assert np.all(np.isfinite(np.asarray(P2)))
    # healthy lanes moved
    assert float(jnp.max(jnp.abs(P2[0] - P[0]))) > 0.0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_eta_clamp_counts_clips(backend, rng):
    """A runaway η (near-zero gradient difference on a non-first step)
    is clamped to ETA_CLAMP and counted per client in ``clips``."""
    params = {"x": jnp.asarray(rng.normal(size=32), jnp.float32)}
    layout = fp.layout_of(params)
    C = 3
    slab = fp.slab_shape(C, layout)
    P = jnp.broadcast_to(fp.pack(params, layout).reshape(slab[1:])[None],
                         slab)
    G = jnp.asarray(rng.normal(size=slab), jnp.float32)
    # prev_grads ~ G: dg_norm tiny -> cand1 explodes; η_prev above the
    # ceiling keeps cand2 over it too, so the clamp must fire
    S = FlatDeltaSGDState(
        prev_grads=G + 1e-7, eta=jnp.full((C,), 2.0 * ETA_CLAMP),
        theta=jnp.ones((C,)), prev_grad_norm=jnp.ones((C,)),
        k=jnp.asarray(1, jnp.int32), valid=jnp.ones((C,), bool),
        clips=jnp.zeros((C,), jnp.int32))
    P1, S1 = flat_delta_sgd_step(P, G, S, gamma=2.0, delta=0.1, eta0=0.1,
                                 backend=backend)
    np.testing.assert_allclose(np.asarray(S1.eta), ETA_CLAMP)
    assert np.asarray(S1.clips).tolist() == [1, 1, 1]
    assert np.all(np.asarray(S1.valid))
    assert np.all(np.isfinite(np.asarray(P1)))


# ---------------------------------------------------- robust agg (direct)
def test_robust_aggregate_mean_and_clip_values(rng):
    C, N = 6, 32
    delta = jnp.asarray(rng.normal(size=(C, N)), jnp.float32)
    valid = jnp.asarray([True, True, False, True, True, True])
    d, v = np.asarray(delta), np.asarray(valid, np.float32)
    agg, info = robust_aggregate(delta, RobustAgg("mean"), valid)
    np.testing.assert_allclose(
        np.asarray(agg), (v[:, None] * d).sum(0) / v.sum(), rtol=1e-6)
    assert info == {}
    spec = RobustAgg("clip", clip_norm=2.0)
    agg, info = robust_aggregate(delta, spec, valid)
    z = d * v[:, None]
    norms = np.sqrt((z * z).sum(1))
    f = np.minimum(1.0, 2.0 / np.maximum(norms, 1e-12))
    np.testing.assert_allclose(
        np.asarray(agg), (z * f[:, None]).sum(0) / v.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(info["agg_clip_rate"]),
                               ((f < 1.0) * v).sum() / v.sum())


@pytest.mark.parametrize("kind", ["trimmed", "median"])
def test_robust_aggregate_order_statistics(kind, rng):
    C, N = 10, 256        # N lane-aligned: the Pallas kernel requires it
    delta = jnp.asarray(rng.normal(size=(C, N)), jnp.float32)
    valid = jnp.ones((C,), bool).at[3].set(False)
    spec = RobustAgg(kind, trim_frac=0.2)
    t = spec.trim_count(C)
    assert t == (2 if kind == "trimmed" else 4)
    z = np.asarray(delta) * np.asarray(valid, np.float32)[:, None]
    s = np.sort(z, axis=0)
    expect = s[t:C - t].mean(0)
    agg, _ = robust_aggregate(delta, spec, valid)
    np.testing.assert_allclose(np.asarray(agg), expect, rtol=1e-6,
                               atol=1e-7)
    # Pallas bitonic kernel (interpret off-TPU) agrees with the jnp sort
    agg_k, _ = robust_aggregate(delta, spec, valid, backend="pallas")
    np.testing.assert_allclose(np.asarray(agg_k), expect, rtol=1e-6,
                               atol=1e-7)


def test_robust_aggregate_outlier_resistance(rng):
    """One byzantine row scaled ×(−50) poisons the mean but not the
    clipped/trimmed/median rungs."""
    C, N = 10, 16
    # honest deltas have l2 norm ~0.4 < clip_norm: only the byzantine
    # row (norm ~20) trips the clip
    base = 0.1 * jnp.asarray(rng.normal(size=(1, N)), jnp.float32)
    delta = base + 0.001 * jnp.asarray(rng.normal(size=(C, N)),
                                       jnp.float32)
    delta = delta.at[4].multiply(-50.0)
    truth = np.asarray(base)[0]
    mean, _ = robust_aggregate(delta, RobustAgg("mean"))
    assert np.max(np.abs(np.asarray(mean) - truth)) > 0.2
    for spec in (RobustAgg("clip", clip_norm=0.5),
                 RobustAgg("trimmed", trim_frac=0.2),
                 RobustAgg("median")):
        agg, _ = robust_aggregate(delta, spec)
        assert np.max(np.abs(np.asarray(agg) - truth)) < 0.1, spec.kind


@needs8
def test_robust_aggregate_sharded_bucketed(rng):
    """Mesh-native ladder: clip matches the replicated result exactly in
    math (per-client norms are psum-exact); trimmed is the BUCKETED
    variant — shard-local trimmed means averaged across client shards."""
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    pspec = spec.flat_spec(mesh)
    C, N = 16, 256
    delta = jnp.asarray(rng.normal(size=(C, N)), jnp.float32)
    valid = jnp.asarray(rng.random(C) > 0.2)
    with mesh:
        clip = RobustAgg("clip", clip_norm=1.0)
        agg_s, info_s = jax.jit(
            lambda d, v: robust_aggregate_sharded(
                d, clip, v, mesh=mesh, pspec=pspec))(delta, valid)
        agg_r, info_r = robust_aggregate(delta, clip, valid)
        np.testing.assert_allclose(np.asarray(agg_s), np.asarray(agg_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(info_s["agg_clip_rate"]),
                                   np.asarray(info_r["agg_clip_rate"]))
        trim = RobustAgg("trimmed", trim_frac=0.25)
        agg_s, _ = jax.jit(
            lambda d, v: robust_aggregate_sharded(
                d, trim, v, mesh=mesh, pspec=pspec))(delta, valid)
    # expected: 4 client shards × 4 clients each, trim 1 per end locally
    z = np.asarray(delta) * np.asarray(valid, np.float32)[:, None]
    buckets = [np.sort(z[i:i + 4], axis=0)[1:3].mean(0)
               for i in range(0, C, 4)]
    np.testing.assert_allclose(np.asarray(agg_s),
                               np.mean(buckets, axis=0), rtol=1e-5,
                               atol=1e-6)


# ------------------------------------------------------ round-level tests
R, C, K, D = 4, 10, 3, 48


def _problem(rng, rounds=R, clients=C):
    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(
        rng.normal(size=(rounds, clients, K, 4, D)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(rounds, clients, K, 4)),
                         jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
    return quad, params, batches


def _host_rounds(loss, copt, sopt, params, batches, scn, **kw):
    rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                scenario=scn, num_clients=20, **kw))
    st = init_fl_state(params, sopt, scn)
    mets = []
    for r in range(rounds):
        st, m, _ = rnd(st, jax.tree.map(lambda x: x[r], batches))
        mets.append(m)
    return st, mets


def test_fault_free_robust_mean_is_legacy_bit_exact(rng):
    """The sync_iid preset (mean agg, zero fault rates, no quorum) takes
    the exact legacy round tail: bit-identical to scenario=None, with
    the guard telemetry reporting all-clean."""
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    st0, m0 = _host_rounds(loss, copt, sopt, params, batches, None,
                           flat="xla")
    st1, m1 = _host_rounds(loss, copt, sopt, params, batches,
                           get_scenario("sync_iid"), flat="xla")
    _assert_trees_equal(st0.params, st1.params)
    for a, b in zip(m0, m1):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)
        assert float(b["eta_clip_rate"]) == 0.0
        assert float(b["nan_guard_rate"]) == 0.0


def test_guarded_mean_tail_matches_legacy_closely(rng):
    """quorum > 0 with mean agg routes through the delta-space guarded
    tail — same math as the legacy mean up to summation order, so the
    trajectories must agree tightly (and nothing is skipped)."""
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    st0, _ = _host_rounds(loss, copt, sopt, params, batches, None,
                          flat="xla")
    scn = get_scenario("sync_iid", quorum=1)
    st1, m1 = _host_rounds(loss, copt, sopt, params, batches, scn,
                           flat="xla")
    np.testing.assert_allclose(np.asarray(st1.params["x"]),
                               np.asarray(st0.params["x"]), rtol=1e-5,
                               atol=1e-6)
    assert all(float(m["round_skipped"]) == 0.0 for m in m1)
    assert all(float(m["valid_count"]) == C for m in m1)


def test_launch_schedule_two_per_step_with_guards_and_faults(rng):
    """Faults + robust aggregation keep the flat engine's launch
    invariant: one traced round = 2 delta-sgd kernel launches (the fused
    pair), plus exactly ONE robust-agg kernel launch for the trimmed
    tail — fault lanes ride the existing η-mask, costing nothing."""
    from repro.kernels.delta_sgd import delta_sgd as dk
    from repro.kernels.robust_agg import robust_agg as rk
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario("sync_iid", drop_rate=0.2, nan_rate=0.1,
                       byzantine_rate=0.2, robust_agg="trimmed",
                       quorum=2)
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                scenario=scn, flat="pallas"))
    st = init_fl_state(params, sopt, scn)
    dk.reset_launch_count()
    rk.reset_launch_count()
    st, m, _ = rnd(st, jax.tree.map(lambda x: x[0], batches))
    jax.block_until_ready(st.params["x"])
    assert dk.launch_count() == 2, dict(dk.LAUNCHES)
    assert rk.launch_count() == 1, dict(rk.LAUNCHES)


def test_nan_fault_telemetry_all_lanes(rng):
    """nan_rate=1.0: every lane trips the guard — nan_guard_rate hits
    1.0, valid_count 0, and the round's params stay finite."""
    quad, params, batches = _problem(rng, rounds=1)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario("sync_iid", nan_rate=1.0)
    st, mets = _host_rounds(loss, copt, sopt, params, batches, scn,
                            flat="xla")
    m = mets[0]
    assert float(m["nan_guard_rate"]) == 1.0
    assert float(m["valid_count"]) == 0.0
    assert np.all(np.isfinite(np.asarray(st.params["x"])))


# ------------------------------------------------- byzantine acceptance
@pytest.mark.slow
def test_byzantine_defense_acceptance(rng):
    """ISSUE acceptance: at 10% byzantine corruption (−10× deltas),
    plain mean aggregation diverges by orders of magnitude while the
    trimmed mean stays within 10% of the clean final loss and clip
    within 15% (clip bounds the corrupted mass but cannot reject its
    flipped sign, so its plateau sits slightly higher). Same seed
    everywhere — identical batches and identical fault draws, only the
    aggregator changes."""
    rounds = 30
    quad, params, batches = _problem(rng, rounds=rounds, clients=20)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")

    def final_loss(agg):
        over = {} if agg is None else dict(
            byzantine_rate=0.1, byzantine_scale=-10.0, robust_agg=agg,
            clip_norm=1.0, trim_frac=0.2)
        st, _ = _host_rounds(loss, copt, sopt, params, batches,
                             get_scenario("sync_iid", **over), flat="xla")
        # global objective: mean loss over every client's last-round data
        b = jax.tree.map(lambda x: x[-1].reshape((-1,) + x.shape[3:]),
                         batches)
        return float(quad(st.params, b)[0])

    clean = final_loss(None)
    mean_byz = final_loss("mean")
    clip_byz = final_loss("clip")
    trim_byz = final_loss("trimmed")
    print(f"byzantine acceptance: clean={clean:.4f} mean={mean_byz:.4f} "
          f"clip={clip_byz:.4f} trimmed={trim_byz:.4f}")
    assert mean_byz > 100.0 * clean, (mean_byz, clean)
    assert clip_byz <= 1.15 * clean, (clip_byz, clean)
    assert trim_byz <= 1.1 * clean, (trim_byz, clean)


# ------------------------------------------------------ quorum degradation
def test_quorum_skip_host_engine(rng):
    """drop_rate=1.0: zero valid clients — the round is a lax.cond no-op
    leaving params/server state bit-identical while the skipped counter
    and round index advance."""
    quad, params, batches = _problem(rng, rounds=2)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario("sync_iid", drop_rate=1.0, quorum=1)
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                scenario=scn, flat="xla"))
    st0 = init_fl_state(params, sopt, scn)
    st1, m, _ = rnd(st0, jax.tree.map(lambda x: x[0], batches))
    np.testing.assert_array_equal(np.asarray(st1.params["x"]),
                                  np.asarray(st0.params["x"]))
    _assert_trees_equal(st1.server_state, st0.server_state)
    assert int(st1.round) == 1
    assert float(m["round_skipped"]) == 1.0
    assert float(m["valid_count"]) == 0.0
    assert float(m["drop_frac"]) == 1.0


def test_quorum_skip_fused_engine_matches_host(rng):
    """The same quorum-skipped rounds through the round-fused scan:
    params bit-identical to the init, every round's skipped flag set,
    and fused == host bit-exact on state and metrics."""
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario("sync_iid", drop_rate=1.0, quorum=1)
    st_h, mets_h = _host_rounds(loss, copt, sopt, params, batches, scn,
                                flat="xla")
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="xla",
                        scenario=scn, num_clients=20)
    fst = flatten_fl_state(init_fl_state(params, sopt, scn), loop.layout)
    fst, fmets = jax.jit(loop)(fst, batches)
    st_f = unflatten_fl_state(fst, loop.layout)
    np.testing.assert_array_equal(np.asarray(st_f.params["x"]),
                                  np.asarray(params["x"]))
    _assert_trees_equal(st_h.params, st_f.params)
    assert np.asarray(fmets["round_skipped"]).tolist() == [1.0] * R
    assert sum(float(m["round_skipped"]) for m in mets_h) == R
    assert int(st_f.round) == R


# ------------------------------------------- fused == host under faults
@pytest.mark.parametrize("scenario", ["dirichlet_dropouts",
                                      "byzantine_async"])
def test_fused_matches_host_under_faults(scenario, rng):
    """ISSUE acceptance: the fused multi-round scan equals the host loop
    bit for bit with the fault axis ACTIVE (drops, NaN lanes, byzantine
    scaling, staleness rejection, robust tails, quorum conds) — final
    state and every round's metrics row."""
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario(scenario)
    assert scn.faulty
    st, mets = _host_rounds(loss, copt, sopt, params, batches, scn,
                            flat="xla")
    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="xla",
                        scenario=scn, num_clients=20)
    fst = flatten_fl_state(init_fl_state(params, sopt, scn), loop.layout)
    fst, fmets = jax.jit(loop, donate_argnums=0)(fst, batches)
    st2 = unflatten_fl_state(fst, loop.layout)
    _assert_trees_equal(st, st2)
    assert int(st2.round) == R
    for r in range(R):
        for k in mets[r]:
            np.testing.assert_array_equal(
                np.asarray(mets[r][k], np.float32),
                np.asarray(jax.tree.map(lambda m: m[r], fmets)[k],
                           np.float32), err_msg=f"round {r} metric {k}")
    # faults actually fired somewhere in the window
    assert any(float(m["nan_guard_rate"]) > 0 or
               float(m.get("drop_frac", 0.0)) > 0 or
               float(m.get("byz_frac", 0.0)) > 0 for m in mets)


@needs8
@pytest.mark.slow
def test_sharded_faulty_round_matches_metrics_shape(rng):
    """8-device mesh smoke for the faulty sync tail: the sharded robust
    round runs under jit with the (C, N) buffer mesh-sharded, reports
    the same telemetry keys as the replicated engine, and the quorum
    cond keeps params finite."""
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    quad, params, batches = _problem(rng, rounds=1, clients=8)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = get_scenario("sync_iid", drop_rate=0.3, nan_rate=0.1,
                       byzantine_rate=0.2, robust_agg="trimmed",
                       trim_frac=0.3, quorum=2)
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                scenario=scn, flat="xla", mesh=mesh,
                                federation=spec))
    with mesh:
        st = init_fl_state(params, sopt, scn)
        st, m, _ = rnd(st, jax.tree.map(lambda x: x[0], batches))
    for k in ("eta_clip_rate", "nan_guard_rate", "valid_count",
              "round_skipped", "drop_frac", "byz_frac"):
        assert k in m, k
    assert np.all(np.isfinite(np.asarray(st.params["x"])))
    assert int(st.round) == 1
