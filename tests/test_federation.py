"""Federation scenario engine: schedulers, heterogeneous-K lane masking
(parity against a reference that literally runs K_c steps per client),
async buffered aggregation, and the sync-degenerate equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_round, make_loss)
from repro.core import flat as fp
from repro.core.delta_sgd import (delta_sgd_init, delta_sgd_update,
                                  flat_delta_sgd_init, flat_delta_sgd_step)
from repro.federation import (SCENARIOS, Scenario, buffer_init,
                              buffer_merge, buffer_step, cohort_size,
                              get_scenario, make_scheduler,
                              staleness_weights)
from repro.kernels.delta_sgd import delta_sgd as dk
from repro.launch.mesh import make_mesh

GAMMA, DELTA, ETA0, THETA0 = 2.0, 0.1, 0.2, 1.0
D = 5


def _quad(params, batch):
    r = batch["A"] @ params["x"] - batch["b"]
    return 0.5 * jnp.mean(r * r), {}


def _mk_batches(rng, C, K, n=8):
    return {"A": jnp.asarray(rng.normal(size=(C, K, n, D)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(C, K, n)), jnp.float32)}


# ------------------------------------------------------------- schedulers
@pytest.mark.parametrize("kind", ["uniform", "size_weighted", "zipf",
                                  "cyclic"])
def test_scheduler_shape_determinism_uniqueness(kind):
    m, C = 40, 10
    sizes = np.arange(1, m + 1, dtype=np.float32) * 10
    sch = make_scheduler(kind, num_clients=m, cohort=C, sizes=sizes)
    key = jax.random.key(0)
    ids1 = np.asarray(sch.sample(key, 3))
    ids2 = np.asarray(sch.sample(key, 3))
    assert ids1.shape == (C,) and ids1.dtype == np.int32
    np.testing.assert_array_equal(ids1, ids2)          # deterministic
    assert len(set(ids1.tolist())) == C                # w/o replacement
    assert ids1.min() >= 0 and ids1.max() < m
    ids3 = np.asarray(sch.sample(key, 4))
    assert not np.array_equal(np.sort(ids1), np.sort(ids3))


def test_zipf_scheduler_prefers_low_ranks():
    m, C = 50, 5
    sch = make_scheduler("zipf", num_clients=m, cohort=C, zipf_s=1.5)
    key = jax.random.key(1)
    h = np.zeros(m)
    for t in range(200):
        np.add.at(h, np.asarray(sch.sample(key, t)), 1)
    assert h[:10].sum() > h[10:].sum()     # head dominates the tail


def test_size_weighted_scheduler_prefers_big_clients():
    m, C = 30, 4
    sizes = np.ones(m, np.float32)
    sizes[:5] = 100.0
    sch = make_scheduler("size_weighted", num_clients=m, cohort=C,
                         sizes=sizes)
    key = jax.random.key(2)
    h = np.zeros(m)
    for t in range(100):
        np.add.at(h, np.asarray(sch.sample(key, t)), 1)
    assert h[:5].sum() > h[5:].sum()


def test_cyclic_scheduler_respects_window():
    m, C = 40, 4
    sch = make_scheduler("cyclic", num_clients=m, cohort=C,
                         window_frac=0.25)
    key = jax.random.key(3)
    win, stride = sch.window, sch.stride
    for t in (0, 1, 7):
        ids = np.asarray(sch.sample(key, t))
        start = (t * stride) % m
        assert np.all(((ids - start) % m) < win), (t, ids)
    # rotation: the reachable set changes across rounds
    all_ids = {int(i) for t in range(20)
               for i in np.asarray(sch.sample(key, t))}
    assert len(all_ids) > win


def test_cohort_size_shared_helper():
    """Satellite: FLConfig.clients_per_round and the pipeline draw use
    the SAME rounding (the seed repo truncated in one and rounded in the
    other — p=0.15, m=10 disagreed)."""
    from repro.configs.base import FLConfig
    from repro.data.pipeline import FederatedDataset
    from repro.data.synthetic import get_task
    assert cohort_size(0.15, 10) == 2          # round, not truncate
    fl = FLConfig(num_clients=10, participation=0.15)
    assert fl.clients_per_round == 2
    fed = FederatedDataset.build(get_task("easy"), num_clients=10,
                                 alpha=1.0, seed=0)
    batches, w, ids = fed.sample_round(0.15, 2, 4)
    assert batches["x"].shape[0] == fl.clients_per_round == len(ids)


def test_pipeline_cohort_matches_scenario_scheduler():
    """The ids the host pipeline gathers data for == the scenario's
    in-round scheduler draw (same key discipline)."""
    from repro.data.pipeline import FederatedDataset
    from repro.data.synthetic import get_task
    scn = get_scenario("zipf_async")
    fed = FederatedDataset.build(get_task("easy"), num_clients=30,
                                 alpha=1.0, seed=0, scenario=scn)
    _, _, ids = fed.sample_round(0.2, 2, 4, round_idx=7)
    sch = scn.make_scheduler(30, cohort_size(0.2, 30),
                             sizes=fed.client_sizes())
    expect = np.asarray(sch.sample(jax.random.key(scn.seed), 7))
    np.testing.assert_array_equal(ids, expect)


# -------------------------------------------------- speed models / masks
def test_speed_model_draws_in_range():
    from repro.federation import SpeedModel
    for kind in ("fixed", "uniform", "stragglers"):
        sm = SpeedModel(kind)
        ks = np.asarray(sm.draw(jax.random.key(0), 64, 8))
        assert ks.shape == (64,) and ks.min() >= 1 and ks.max() <= 8
    assert np.all(np.asarray(
        SpeedModel("fixed").draw(jax.random.key(0), 4, 6)) == 6)
    slow = np.asarray(SpeedModel("stragglers", straggler_frac=1.0)
                      .draw(jax.random.key(0), 16, 8))
    assert np.all(slow == 2)               # k_min = round(0.25·8)


def test_scenario_registry_and_overrides():
    assert {"sync_iid", "dirichlet_stragglers", "zipf_async"} \
        <= set(SCENARIOS)
    scn = get_scenario("zipf_async", buffer_size=16)
    assert scn.buffer_size == 16 and scn.is_async
    assert get_scenario(scn) is scn
    with pytest.raises(KeyError):
        get_scenario("nope")
    with pytest.raises(KeyError):
        Scenario("bad", aggregation="maybe")


# -------------------------------------- hetero-K parity (flat vs literal)
def _literal_reference(tree, grad_seq, step_counts):
    """Runs EXACTLY K_c oracle steps per client — no masking anywhere."""
    finals, etas = [], []
    for c, k_c in enumerate(step_counts):
        p = tree
        s = delta_sgd_init(p, eta0=ETA0, theta0=THETA0)
        for k in range(int(k_c)):
            p, s = delta_sgd_update(p, grad_seq[c][k], s, gamma=GAMMA,
                                    delta=DELTA, eta0=ETA0)
        finals.append(p)
        etas.append(float(s.eta))
    return finals, etas


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_flat_step_hetero_matches_literal_kc_reference(backend, rng):
    """Acceptance: the masked flat engine == a reference that literally
    runs K_c steps per client (≤1e-5), mixed bf16/f32 tree included."""
    C, K = 4, 5
    step_counts = np.array([1, 3, 5, 2], np.int64)
    tree = {"emb": jnp.asarray(rng.normal(size=(33, 7)), jnp.bfloat16),
            "w": jnp.asarray(rng.normal(size=(129,)), jnp.float32)}
    layout = fp.layout_of(tree)
    mask = fp.round_mask(layout)
    grad_seq = [[jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), p.dtype), tree)
        for _ in range(K)] for _ in range(C)]
    ref_params, ref_etas = _literal_reference(tree, grad_seq, step_counts)

    slab = fp.slab_shape(C, layout)
    P = jnp.stack([fp.pack(tree, layout)] * C).reshape(slab)
    S = flat_delta_sgd_init(C, layout, eta0=ETA0, theta0=THETA0)
    sc = jnp.asarray(step_counts, jnp.int32)
    for k in range(K):
        G = jnp.stack([fp.pack(grad_seq[c][k], layout)
                       for c in range(C)]).reshape(slab)
        P, S = flat_delta_sgd_step(
            P, G, S, gamma=GAMMA, delta=DELTA, eta0=ETA0, mask=mask,
            active=(k < sc), backend=backend,
            interpret=True if backend == "pallas" else None)
    got = fp.unpack_batched(P, layout)
    for c in range(C):
        for key in tree:
            np.testing.assert_allclose(
                np.asarray(got[key][c], np.float32),
                np.asarray(ref_params[c][key], np.float32),
                rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(S.eta[c]), ref_etas[c], rtol=1e-5)


@pytest.mark.parametrize("flat", [False, "xla", "pallas"])
def test_hetero_round_matches_literal_reference(flat, rng):
    """Round-level acceptance: make_fl_round under a straggler scenario
    == mean of per-client literal K_c-step oracles."""
    C, K = 4, 4
    # mixed draw: at least one masked lane AND one full-K lane, so the
    # parity test really exercises frozen clients next to running ones.
    # The first scenario seed from 3 up that draws one: which seeds do
    # depends on the PRNG's bit layout, which jax versions change.
    for seed in range(3, 64):
        scn = get_scenario("dirichlet_stragglers", straggler_frac=0.5,
                           seed=seed)
        step_counts = np.asarray(scn.draw_step_counts(0, C, K))
        if step_counts.min() < K and step_counts.max() == K:
            break
    assert step_counts.min() < K and step_counts.max() == K, step_counts
    batches = _mk_batches(rng, C, K)
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)

    # literal reference: grads recomputed exactly as the engine does
    tree = {"x": x0}
    grad_fn = jax.value_and_grad(
        lambda p, b: make_loss(_quad)(p, b, None, None), has_aux=True)
    finals = []
    for c in range(C):
        p = tree
        s = delta_sgd_init(p, eta0=ETA0, theta0=THETA0)
        for k in range(int(step_counts[c])):
            b = {"A": batches["A"][c, k], "b": batches["b"][c, k]}
            (_, _), g = grad_fn(p, b)
            p, s = delta_sgd_update(p, g, s, gamma=GAMMA, delta=DELTA,
                                    eta0=ETA0)
        finals.append(np.asarray(p["x"], np.float64))
    ref = np.mean(finals, axis=0)

    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    rnd = jax.jit(make_fl_round(make_loss(_quad), copt, sopt,
                                num_rounds=10, flat=flat, scenario=scn))
    st = init_fl_state(tree, sopt, scn)
    st, m, loc = rnd(st, batches)
    np.testing.assert_allclose(np.asarray(st.params["x"]), ref,
                               rtol=1e-5, atol=1e-5)
    for c in range(C):
        np.testing.assert_allclose(np.asarray(loc["x"][c]), finals[c],
                                   rtol=1e-5, atol=1e-5)
    assert float(m["k_eff_mean"]) == pytest.approx(step_counts.mean())


def test_sync_scenario_reproduces_seed_engines(rng):
    """Acceptance: a sync full-participation scenario reproduces the
    existing engines bit-for-bit (sync_iid takes the identical code
    path; a stragglers scenario with frac=0 exercises the masked path
    with an all-ones mask, ≤1e-5)."""
    C, K = 3, 4
    batches = _mk_batches(rng, C, K)
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(_quad)
    for flat in (False, "xla", "pallas"):
        base = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                     flat=flat))
        st0 = init_fl_state({"x": x0}, sopt)
        st0, m0, _ = base(st0, batches)
        # identical code path: exact equality
        scn = get_scenario("sync_iid")
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat=flat, scenario=scn))
        st1 = init_fl_state({"x": x0}, sopt, scn)
        st1, m1, _ = rnd(st1, batches)
        np.testing.assert_array_equal(np.asarray(st1.params["x"]),
                                      np.asarray(st0.params["x"]))
        # masked path with every client at K_max: ≤1e-5
        scn0 = get_scenario("dirichlet_stragglers", straggler_frac=0.0)
        rnd0 = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                     flat=flat, scenario=scn0))
        st2 = init_fl_state({"x": x0}, sopt, scn0)
        st2, m2, _ = rnd0(st2, batches)
        np.testing.assert_allclose(np.asarray(st2.params["x"]),
                                   np.asarray(st0.params["x"]),
                                   rtol=1e-5, atol=1e-6)
        assert float(m2["loss"]) == pytest.approx(float(m0["loss"]),
                                                  rel=1e-6)


def test_hetero_flat_round_two_launches_per_local_step(rng):
    """Fused-launch invariant (acceptance): heterogeneous-K rounds still
    trace exactly 2 pallas launches per local step — the lane mask rides
    the per-client η vector, not an extra kernel."""
    scn = get_scenario("dirichlet_stragglers")
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(_quad)
    for C, K in ((2, 3), (5, 2)):
        batches = _mk_batches(rng, C, K)
        rnd = make_fl_round(loss, copt, sopt, num_rounds=10,
                            flat="pallas", scenario=scn)
        st = init_fl_state({"x": jnp.zeros((D,), jnp.float32)}, sopt, scn)
        dk.reset_launch_count()
        jax.eval_shape(lambda s, b: rnd(s, b), st, batches)
        assert dk.launch_count() == 2, (C, K, dict(dk.LAUNCHES))


# ----------------------------------------------------------- async buffer
def test_staleness_weights_polynomial():
    w = np.asarray(staleness_weights(jnp.asarray([0, 1, 3]), 0.5))
    np.testing.assert_allclose(w, [1.0, 2 ** -0.5, 0.5], rtol=1e-6)


def test_buffer_merge_and_flush_counting():
    params = {"x": jnp.ones((4,), jnp.float32)}
    sopt = get_server_opt("fedavg")
    buf = buffer_init(params)
    stale = jnp.asarray([0, 0], jnp.int32)
    delta = {"x": jnp.full((4,), 2.0, jnp.float32)}  # pre-weighted sum
    buf = buffer_merge(buf, delta, jnp.float32(2.0), 2, stale)
    assert int(buf.count) == 2
    # below M: hold — params unchanged, buffer kept
    p, s, buf2, flushed = buffer_step(params, {}, buf, sopt, 4)
    assert float(flushed) == 0.0 and int(buf2.count) == 2
    np.testing.assert_array_equal(np.asarray(p["x"]),
                                  np.asarray(params["x"]))
    # reach M: flush applies params + delta/weight and resets
    buf3 = buffer_merge(buf2, delta, jnp.float32(2.0), 2, stale)
    p, s, buf4, flushed = buffer_step(params, {}, buf3, sopt, 4)
    assert float(flushed) == 1.0 and int(buf4.count) == 0
    np.testing.assert_allclose(np.asarray(p["x"]), 1.0 + 4.0 / 4.0)
    assert float(buf4.weight) == 0.0


def test_async_round_requires_flat_engine():
    scn = get_scenario("zipf_async")
    with pytest.raises(ValueError, match="flat engine"):
        make_fl_round(make_loss(_quad), get_client_opt("delta_sgd"),
                      get_server_opt("fedavg"), num_rounds=1,
                      scenario=scn)


def test_async_degenerate_equals_sync_fedavg(rng):
    """staleness ≡ 0 + M = C → flush every round with unit weights: the
    async path reproduces synchronous FedAvg."""
    C, K = 4, 3
    batches = _mk_batches(rng, C, K)
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(_quad)
    sync = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                 flat="xla"))
    scn = get_scenario("zipf_async", staleness_max=0, buffer_size=C,
                       speed="fixed")
    asy = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                flat="xla", scenario=scn))
    st_s = init_fl_state({"x": x0}, sopt)
    st_a = init_fl_state({"x": x0}, sopt, scn)
    for _ in range(3):
        st_s, _, _ = sync(st_s, batches)
        st_a, ma, _ = asy(st_a, batches)
        assert float(ma["flushed"]) == 1.0
    np.testing.assert_allclose(np.asarray(st_a.params["x"]),
                               np.asarray(st_s.params["x"]),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("server", ["fedavg", "fedadam"])
def test_async_round_buffers_and_flushes(server, rng):
    """M > C: the server holds for ⌈M/C⌉ rounds, then steps — with any
    ServerOpt — and the staleness metrics are populated."""
    C, K = 3, 2
    batches = _mk_batches(rng, C, K)
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt(server)
    scn = get_scenario("zipf_async", buffer_size=6)
    rnd = jax.jit(make_fl_round(make_loss(_quad), copt, sopt,
                                num_rounds=10, flat="xla", scenario=scn,
                                num_clients=12))
    st = init_fl_state({"x": x0}, sopt, scn)
    flushes = []
    for _ in range(4):
        st, m, _ = rnd(st, batches)
        flushes.append(float(m["flushed"]))
        assert 0.0 <= float(m["stale_mean"]) <= scn.staleness_max
        assert m["cohort_ids"].shape == (C,)
    assert flushes == [0.0, 1.0, 0.0, 1.0]
    # held rounds leave params untouched only for fedavg-like flushes;
    # in all cases the state stays finite
    assert np.all(np.isfinite(np.asarray(st.params["x"])))


def test_async_held_round_keeps_params(rng):
    C, K = 2, 2
    batches = _mk_batches(rng, C, K)
    x0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    sopt = get_server_opt("fedavg")
    scn = get_scenario("zipf_async", buffer_size=8)
    rnd = jax.jit(make_fl_round(make_loss(_quad),
                                get_client_opt("delta_sgd"), sopt,
                                num_rounds=10, flat="xla", scenario=scn))
    st = init_fl_state({"x": x0}, sopt, scn)
    st, m, _ = rnd(st, batches)
    assert float(m["flushed"]) == 0.0
    np.testing.assert_array_equal(np.asarray(st.params["x"]),
                                  np.asarray(x0))
    assert float(m["buffer_fill"]) == C


# ------------------------------------------------------------- sharded
needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")


def _fl_problem(rng, C=8, K=3, Dm=300, E=40):
    def quad(params, batch):
        x32 = params["x"].astype(jnp.float32)
        e32 = params["e"].astype(jnp.float32)
        r = batch["A"] @ x32 - batch["b"] + jnp.sum(e32) * 0.01
        return 0.5 * jnp.mean(r * r) + 0.05 * jnp.mean(e32 * e32), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, Dm)),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=Dm), jnp.float32),
              "e": jnp.asarray(rng.normal(size=E), jnp.bfloat16)}
    return quad, params, batches


@needs8
@pytest.mark.parametrize("scn_name", ["dirichlet_stragglers",
                                      "zipf_async"])
@pytest.mark.slow
def test_sharded_scenario_round_matches_replicated(scn_name, rng):
    """Acceptance: scenario rounds on the sharded flat engine == the
    replicated flat engine (≤1e-5) AND the packed (C, N) buffer never
    rematerializes in the compiled HLO (assert_flat_buffer_sharded)."""
    from repro.sharding.hlo import assert_flat_buffer_sharded
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    scn = get_scenario(scn_name)
    quad, params, batches = _fl_problem(rng)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    out = {}
    for name, kw in (("repl", {}),
                     ("shard", dict(mesh=mesh, federation=spec))):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat="xla", scenario=scn,
                                    num_clients=20, **kw))
        st = init_fl_state(params, sopt, scn)
        if name == "shard":
            lay = fp.layout_of(params, shards=spec.flat_shards(mesh))
            compiled = rnd.lower(st, batches).compile()
            assert_flat_buffer_sharded(compiled, 8, lay.padded_size)
        for _ in range(3):
            st, m, _ = rnd(st, batches)
        out[name] = (np.asarray(st.params["x"]),
                     np.asarray(st.params["e"], np.float32),
                     np.asarray(m["cohort_ids"]),
                     float(m["eta_mean"]), float(m["loss"]))
    for a, b in zip(out["repl"], out["shard"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
