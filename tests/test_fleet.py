"""Fleet-scale simulation (PR 7): the sharded client-state arena, the
10^5-candidate schedulers, and the block-level shard_map loop.

Contracts under test:
  * Gumbel-top-k schedulers stay deterministic and correctly skewed at
    C_registered = 10^5, and their sample trace never materializes a
    buffer wider than a few O(C_registered) vectors (no O(C_reg * N),
    no O(C_reg * cohort)).
  * Arena gather/scatter round-trips exactly: ``arena_take`` is plain
    row indexing, an identity ``arena_update`` is a bit-level no-op,
    and rows of never-sampled clients stay bit-identical through any
    number of scatters (property-tested).
  * ``make_fleet_loop`` with eta_carry off, EF off and no weights is
    BIT-EXACT against ``make_fl_loop`` on the same stacked data (it
    runs the identical flat round body), while its arena bookkeeping
    (rounds_seen / last_round / cohort_ids) replays exactly from the
    host-side scheduler draw.
  * Fleet memory ceiling: the compiled fleet program materializes
    nothing wider than O(C_registered) scalars along the registered
    dim (EF21 relaxes this by exactly its one (C_reg, N) slab).
  * The block-level shard_map loop (one shard_map around the whole
    R-round scan) matches the replicated engine, fuses bit-exactly
    (R=1 blocks vs one R-block), and passes both sharding HLO
    assertions on the SCANNED program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (flatten_fl_state, get_client_opt, get_server_opt,
                        init_fl_state, make_fl_loop, make_fleet_loop)
from repro.federation import (ClientArena, arena_init, arena_take,
                              arena_update, get_scenario, make_scheduler)
from repro.launch.mesh import make_mesh
from repro.sharding.hlo import (assert_cohort_only_materialization,
                                cohort_materialization_report)

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")

R, C, K, D, E = 4, 8, 3, 96, 18
M_BIG = 100_000


def _problem(rng, rounds=R):
    """Quadratic FL problem, mixed f32/bf16 tree, stacked rounds."""
    def quad(params, batch):
        x32 = params["x"].astype(jnp.float32)
        e32 = params["e"].astype(jnp.float32)
        r = batch["A"] @ x32 - batch["b"] + jnp.sum(e32) * 0.01
        return 0.5 * jnp.mean(r * r) + 0.05 * jnp.mean(e32 * e32), {}

    batches = {"A": jnp.asarray(rng.normal(size=(rounds, C, K, 4, D)),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(size=(rounds, C, K, 4)),
                                jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32),
              "e": jnp.asarray(rng.normal(size=E), jnp.bfloat16)}
    from repro.core import make_loss
    return make_loss(quad), params, batches


def _opts():
    return (get_client_opt("delta_sgd", gamma=2.0, eta0=0.2, theta0=1.0,
                           delta=0.1),
            get_server_opt("fedavg"))


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))


# ---------------------------------------------------------------------------
# schedulers at fleet scale
# ---------------------------------------------------------------------------

def _jaxpr_max_elems(closed):
    """Largest intermediate buffer (in elements) anywhere in a jaxpr,
    including sub-jaxprs (scan/cond/pjit bodies) — duck-typed so it
    works across jax versions without jax.core imports."""
    mx = 0
    stack = [closed.jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(getattr(v, "aval", None), "shape", None)
                if shape:
                    mx = max(mx, int(np.prod(shape)))
            for p in eqn.params.values():
                for q in (p if isinstance(p, (list, tuple)) else (p,)):
                    sub = getattr(q, "jaxpr", q)
                    if hasattr(sub, "eqns"):
                        stack.append(sub)
    return mx


@pytest.mark.parametrize("kind", ["uniform", "zipf", "cyclic",
                                  "size_weighted"])
def test_scheduler_100k_deterministic_distinct(kind):
    sizes = (jnp.ones((M_BIG,), jnp.float32)
             if kind == "size_weighted" else None)
    sch = make_scheduler(kind, num_clients=M_BIG, cohort=64, sizes=sizes)
    key = jax.random.key(3)
    a = np.asarray(sch.sample(key, 5))
    b = np.asarray(sch.sample(key, 5))
    c = np.asarray(sch.sample(key, 6))
    np.testing.assert_array_equal(a, b)          # same (key, t) -> same
    assert len(np.unique(a)) == 64               # without replacement
    assert a.min() >= 0 and a.max() < M_BIG
    assert not np.array_equal(a, c)              # fold_in(t) decorrelates


def test_zipf_100k_skew():
    sch = make_scheduler("zipf", num_clients=M_BIG, cohort=64)
    key = jax.random.key(0)
    samp = jax.jit(lambda t: sch.sample(key, t))
    ids = np.concatenate([np.asarray(samp(jnp.int32(t)))
                          for t in range(30)])
    # s=1.2 puts >80% of the mass on the first decile of ranks; a
    # uniform draw would land ~10% there
    frac_low = np.mean(ids < M_BIG // 10)
    assert frac_low > 0.5, frac_low
    assert ids.mean() < M_BIG / 4, ids.mean()


@pytest.mark.parametrize("kind", ["uniform", "zipf"])
def test_scheduler_100k_trace_stays_o_registered(kind):
    """The sample trace may hold a few (C_reg,) vectors (weights,
    gumbels, random bits) but nothing O(C_reg * cohort) or wider."""
    sch = make_scheduler(kind, num_clients=M_BIG, cohort=64)
    key = jax.random.key(0)
    closed = jax.make_jaxpr(lambda t: sch.sample(key, t))(jnp.int32(0))
    mx = _jaxpr_max_elems(closed)
    assert mx <= 4 * M_BIG, (
        f"scheduler trace materializes a {mx}-element buffer "
        f"(> 4 * C_registered = {4 * M_BIG})")


# ---------------------------------------------------------------------------
# arena gather/scatter round-trip (property tests — run under real
# hypothesis or the deterministic fallback in tests/_hypothesis_fallback)
# ---------------------------------------------------------------------------

def _rand_arena(r, m, with_ef):
    return ClientArena(
        jnp.asarray(r.normal(size=m), jnp.float32),
        jnp.asarray(r.integers(0, 5, size=m), jnp.int32),
        jnp.asarray(r.integers(-1, 7, size=m), jnp.int32),
        jnp.asarray(r.normal(size=(m, 6)), jnp.float32)
        if with_ef else None)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(m=st.integers(4, 64), k=st.integers(1, 8),
       seed=st.integers(0, 10_000), ef=st.integers(0, 1))
def test_arena_roundtrip_property(m, k, seed, ef):
    k = min(k, m)
    r = np.random.default_rng(seed)
    ids = jnp.asarray(r.choice(m, size=k, replace=False).astype(np.int32))
    arena = _rand_arena(r, m, bool(ef))
    rows = arena_take(arena, ids)
    # gather IS row indexing
    _assert_trees_equal(rows, jax.tree.map(lambda a: a[np.asarray(ids)],
                                           arena))
    # identity scatter is a bit-level no-op
    _assert_trees_equal(arena_update(arena, ids, rows), arena)
    # modified scatter touches exactly the sampled rows
    new_rows = jax.tree.map(lambda a: a + jnp.ones((), a.dtype), rows)
    upd = arena_update(arena, ids, new_rows)
    touched = np.zeros(m, bool)
    touched[np.asarray(ids)] = True
    for la, lu in zip(jax.tree_util.tree_leaves(arena),
                      jax.tree_util.tree_leaves(upd)):
        la, lu = np.asarray(la), np.asarray(lu)
        np.testing.assert_array_equal(lu[~touched], la[~touched])
        np.testing.assert_array_equal(lu[touched], la[touched] + 1)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(m=st.integers(8, 48), rounds=st.integers(1, 6),
       seed=st.integers(0, 10_000))
def test_arena_never_sampled_bit_identical_property(m, rounds, seed):
    """Clients outside every cohort keep bit-identical state through
    any sequence of scatters."""
    r = np.random.default_rng(seed)
    arena = _rand_arena(r, m, with_ef=True)
    ref = jax.tree.map(np.asarray, arena)
    ever = np.zeros(m, bool)
    for _ in range(rounds):
        k = int(r.integers(1, max(2, m // 3)))
        ids = r.choice(m, size=k, replace=False).astype(np.int32)
        ever[ids] = True
        rows = arena_take(arena, jnp.asarray(ids))
        arena = arena_update(arena, jnp.asarray(ids),
                             jax.tree.map(lambda a: a * 2 + 1, rows))
    for lr, la in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(arena)):
        np.testing.assert_array_equal(np.asarray(la)[~ever], lr[~ever])


# ---------------------------------------------------------------------------
# fleet loop: bit-exactness, bookkeeping, eta carry, EF, memory ceiling
# ---------------------------------------------------------------------------

def _fleet_setup(rng, m, *, rounds=R, seed=7, **kw):
    loss, params, batches = _problem(rng, rounds=rounds)
    copt, sopt = _opts()
    loop = make_fleet_loop(loss, copt, sopt, params_like=params,
                           num_rounds=100, num_registered=m, flat="xla",
                           seed=seed, **kw)
    f0 = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
    return loss, params, batches, copt, sopt, loop, f0


def test_fleet_matches_fused_loop_bit_exact(rng):
    """eta_carry off + EF off: the fleet loop IS make_fl_loop plus
    arena bookkeeping — global state must match bit for bit."""
    loss, params, batches, copt, sopt, loop, f0 = _fleet_setup(rng, 500)
    car = arena_init(500, eta0=loop.eta0)
    (ff, _), mf = jax.jit(loop)((f0, car), batches)
    ref_loop = make_fl_loop(loss, copt, sopt, params_like=params,
                            num_rounds=100, flat="xla")
    fr, mr = jax.jit(ref_loop)(f0, batches)
    np.testing.assert_array_equal(np.asarray(ff.P), np.asarray(fr.P))
    _assert_trees_equal(ff.server_state, fr.server_state)
    for k in ("loss", "eta_mean", "eta_min", "eta_max"):
        np.testing.assert_array_equal(np.asarray(mf[k]),
                                      np.asarray(mr[k]))


def test_fleet_arena_bookkeeping_replays_from_scheduler(rng):
    m, seed = 200, 11
    _, _, batches, _, _, loop, f0 = _fleet_setup(rng, m, seed=seed)
    car = arena_init(m, eta0=loop.eta0)
    (_, ar), mets = jax.jit(loop)((f0, car), batches)
    # the on-device draw replays exactly from the host-side scheduler
    sch = make_scheduler("uniform", num_clients=m, cohort=C)
    key = jax.random.key(seed)
    host_ids = np.stack([np.asarray(sch.sample(key, t))
                         for t in range(R)])
    np.testing.assert_array_equal(np.asarray(mets["cohort_ids"]),
                                  host_ids)
    counts = np.bincount(host_ids.ravel(), minlength=m)
    np.testing.assert_array_equal(np.asarray(ar.rounds_seen), counts)
    last = np.full(m, -1, np.int32)
    for t in range(R):
        last[host_ids[t]] = t
    np.testing.assert_array_equal(np.asarray(ar.last_round), last)
    # never-sampled clients: state bit-identical to arena_init
    never = counts == 0
    assert never.any()
    np.testing.assert_array_equal(np.asarray(ar.eta)[never],
                                  np.float32(loop.eta0))
    # first-round cohort has no returning clients
    assert float(mets["revisit_frac"][0]) == 0.0
    assert 0.0 <= float(mets["revisit_frac"][-1]) <= 1.0


@pytest.mark.slow
def test_fleet_eta_carry_warm_starts_returning_clients(rng):
    """With a small fleet every client returns; the warm-started eta0
    changes the trajectory (and the arena stores round-end etas)."""
    m, rounds = 12, 6
    loss, params, batches = _problem(rng, rounds=rounds)
    copt, sopt = _opts()
    kw = dict(params_like=params, num_rounds=100, num_registered=m,
              flat="xla", seed=7)
    loop_c = make_fleet_loop(loss, copt, sopt, eta_carry=True, **kw)
    loop_n = make_fleet_loop(loss, copt, sopt, eta_carry=False, **kw)
    f0 = flatten_fl_state(init_fl_state(params, sopt), loop_c.layout)
    car = arena_init(m, eta0=loop_c.eta0)
    (fc, ac), mc = jax.jit(loop_c)((f0, car), batches)
    (fn, _), _ = jax.jit(loop_n)((f0, car), batches)
    assert float(jnp.max(jnp.abs(fc.P - fn.P))) > 0.0
    sampled = np.asarray(ac.rounds_seen) > 0
    assert np.any(np.asarray(ac.eta)[sampled] != np.float32(loop_c.eta0))
    assert np.all(np.isfinite(np.asarray(mc["eta_carry_mean"])))


@pytest.mark.slow
def test_fleet_ef_lives_in_arena(rng):
    """EF21 state persists per REGISTERED client: sampled rows' EF
    slabs change, never-sampled rows stay exactly zero, and the carried
    FlatFLState keeps ef=None between rounds."""
    from repro.compression import CompressionSpec
    m = 64
    scn = get_scenario("bandwidth_tiered")
    comp = CompressionSpec(kind="int8", error_feedback=True)
    _, _, batches, _, _, loop, f0 = _fleet_setup(
        rng, m, rounds=2, scenario=scn, compression=comp)
    car = arena_init(m, eta0=loop.eta0,
                     ef_width=loop.layout.padded_size)
    (ff, ar), mets = jax.jit(loop)((f0, car), batches)
    assert ff.ef is None
    ef = np.asarray(ar.ef)
    sampled = np.asarray(ar.rounds_seen) > 0
    assert np.abs(ef[sampled]).max() > 0.0
    np.testing.assert_array_equal(ef[~sampled], 0.0)
    # missing EF slab is a loud error, not a silent reset
    with pytest.raises(ValueError, match="EF slab"):
        loop((f0, arena_init(m, eta0=loop.eta0)), batches)


def test_fleet_memory_ceiling_cohort_only(rng):
    """Compiled HLO check: nothing wider than O(C_registered) scalars
    along the registered dim (the ISSUE's 10^5-client enabler). With
    EF21 the one (C_reg, N) slab the algorithm requires appears — and
    the detector must SEE it (negative control)."""
    m = 5000
    _, _, batches, _, _, loop, f0 = _fleet_setup(rng, m)
    car = arena_init(m, eta0=loop.eta0)
    compiled = jax.jit(loop).lower((f0, car), batches).compile()
    rep = assert_cohort_only_materialization(compiled, m)
    assert rep["vectors"] > 0          # the arena rows themselves
    # negative control: the EF fleet program DOES carry a (m, N) slab
    from repro.compression import CompressionSpec
    scn = get_scenario("bandwidth_tiered")
    rng2 = np.random.default_rng(0)
    _, _, b2, _, _, loop_ef, f2 = _fleet_setup(
        rng2, m, rounds=2, scenario=scn,
        compression=CompressionSpec(kind="int8", error_feedback=True))
    car_ef = arena_init(m, eta0=loop_ef.eta0,
                        ef_width=loop_ef.layout.padded_size)
    c2 = jax.jit(loop_ef).lower((f2, car_ef), b2).compile()
    assert cohort_materialization_report(c2.as_text(), m)["wide"] > 0
    with pytest.raises(AssertionError):
        assert_cohort_only_materialization(c2, m)
    # ... and max_cols=N readmits exactly that slab
    assert_cohort_only_materialization(
        c2, m, max_cols=loop_ef.layout.padded_size)


# ---------------------------------------------------------------------------
# block-level shard_map: the whole R-round scan inside ONE shard_map
# ---------------------------------------------------------------------------

def _block_loops(loss, params, scenario=None, num_clients=None):
    from repro.sharding.spec import FederationSpec
    copt, sopt = _opts()
    mesh = make_mesh((4, 2), ("data", "model"))
    fed = FederationSpec(client_axes=("data",), fsdp_axes=(), tp_axes=())
    kw = dict(params_like=params, num_rounds=100, flat="xla",
              scenario=scenario)
    if num_clients is not None:
        kw["num_clients"] = num_clients
    rep = make_fl_loop(loss, copt, sopt, **kw)
    blk = make_fl_loop(loss, copt, sopt, mesh=mesh, federation=fed,
                       block_sharded=True, **kw)
    return rep, blk, mesh, fed, sopt


@needs8
@pytest.mark.slow
def test_block_sharded_matches_replicated(rng):
    loss, params, batches = _problem(rng)
    rep, blk, _, _, sopt = _block_loops(loss, params)
    f0 = flatten_fl_state(init_fl_state(params, sopt), rep.layout)
    fr, mr = jax.jit(rep)(f0, batches)
    fb, mb = jax.jit(blk)(f0, batches)
    assert float(jnp.max(jnp.abs(fr.P - fb.P))) <= 1e-5
    for k in ("loss", "eta_mean", "eta_min", "eta_max",
              "eta_clip_rate", "nan_guard_rate"):
        np.testing.assert_allclose(np.asarray(mr[k]), np.asarray(mb[k]),
                                   atol=1e-2)


@needs8
@pytest.mark.slow
def test_block_fused_bit_exact_and_hlo(rng):
    """R=1 blocks host-looped == one R-round block (bit-exact: the
    scan body IS the round), and both sharding assertions hold on the
    SCANNED block program."""
    from repro.sharding.hlo import (assert_flat_buffer_sharded,
                                    assert_no_fullprec_delta_collective)
    loss, params, batches = _problem(rng)
    _, blk, mesh, fed, sopt = _block_loops(loss, params)
    f0 = flatten_fl_state(init_fl_state(params, sopt), blk.layout)
    fb, _ = jax.jit(blk)(f0, batches)
    fh = f0
    for r in range(R):
        fh, _ = jax.jit(blk)(fh, jax.tree.map(lambda x, r=r: x[r:r + 1],
                                              batches))
    assert float(jnp.max(jnp.abs(fh.P - fb.P))) == 0.0
    N = blk.layout.padded_size
    compiled = jax.jit(blk).lower(f0, batches).compile()
    assert_flat_buffer_sharded(compiled, C, N)
    assert_no_fullprec_delta_collective(compiled, C, N, mesh=mesh,
                                        federation=fed)


@needs8
@pytest.mark.slow
@pytest.mark.parametrize("scenario,rounds", [
    ("dirichlet_stragglers", R), ("zipf_async", R),
    # int8 rounding tie-flips amplify through the eta min-branch over
    # long blocks (same bound as the sharded compression parity test)
    ("bandwidth_tiered", 2)])
def test_block_sharded_scenario_parity(scenario, rounds, rng):
    loss, params, batches = _problem(rng)
    batches = jax.tree.map(lambda x: x[:rounds], batches)
    scn = get_scenario(scenario)
    rep, blk, _, _, sopt = _block_loops(loss, params, scenario=scn,
                                        num_clients=64)
    s0 = flatten_fl_state(init_fl_state(params, sopt, scn), rep.layout)
    fr, mr = jax.jit(rep)(s0, batches)
    fb, mb = jax.jit(blk)(s0, batches)
    assert float(jnp.max(jnp.abs(fr.P - fb.P))) <= 1e-5
    np.testing.assert_array_equal(np.asarray(mr["cohort_ids"]),
                                  np.asarray(mb["cohort_ids"]))
    for k in mr:
        if k != "cohort_ids":
            np.testing.assert_allclose(np.asarray(mr[k]),
                                       np.asarray(mb[k]), atol=1e-3)
