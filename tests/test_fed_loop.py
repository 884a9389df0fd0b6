"""Round-fused training loop (repro.core.fed_loop): R fused rounds must
be BIT-EXACT vs R host-loop rounds for every flat engine × scenario
combination (sync, stragglers, async, bandwidth-tiered compression),
including the 8-device sharded mesh with both HLO assertions run on the
SCANNED computation; plus the donation contract (carried buffers update
in place, peak live memory independent of R) and the launch-schedule
invariant (the scan body traces the fused kernel pair once — 2·K
launches per block trace, an executed schedule of exactly 2·K·R)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (arena_gather, flatten_fl_state, get_client_opt,
                        get_server_opt, init_fl_state, make_fl_loop,
                        make_fl_round, make_loss, unflatten_fl_state)
from repro.core import flat as fp
from repro.launch.mesh import make_mesh

needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")

R, C, K, D, E = 4, 8, 3, 96, 18


def _problem(rng):
    """Quadratic FL problem, mixed f32/bf16 tree, R stacked rounds."""
    def quad(params, batch):
        x32 = params["x"].astype(jnp.float32)
        e32 = params["e"].astype(jnp.float32)
        r = batch["A"] @ x32 - batch["b"] + jnp.sum(e32) * 0.01
        return 0.5 * jnp.mean(r * r) + 0.05 * jnp.mean(e32 * e32), {}

    batches = {"A": jnp.asarray(rng.normal(size=(R, C, K, 4, D)),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(size=(R, C, K, 4)),
                                jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32),
              "e": jnp.asarray(rng.normal(size=E), jnp.bfloat16)}
    return quad, params, batches


def _scn(name):
    if name is None:
        return None
    from repro.federation import get_scenario
    return get_scenario(name)


def _comp(scenario_name):
    if scenario_name == "bandwidth_tiered":
        from repro.compression import CompressionSpec
        return CompressionSpec(kind="int8", error_feedback=True)
    return None


def _host_rounds(loss, copt, sopt, params, batches, scn, comp, **kw):
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                scenario=scn, num_clients=20,
                                compression=comp, **kw))
    st = init_fl_state(params, sopt, scn, compression=comp, cohort=C)
    mets = []
    for r in range(R):
        st, m, _ = rnd(st, jax.tree.map(lambda x: x[r], batches))
        mets.append(m)
    return st, mets


def _assert_states_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scenario", [None, "dirichlet_stragglers",
                                      "zipf_async", "bandwidth_tiered"])
def test_fused_matches_host_loop_bit_exact(backend, scenario, rng):
    """R fused rounds == R host-loop rounds, bit for bit: final state
    (params, server state, async buffer, EF21 state) AND every round's
    metrics row."""
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn, comp = _scn(scenario), _comp(scenario)
    st, mets = _host_rounds(loss, copt, sopt, params, batches, scn, comp,
                            flat=backend)

    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat=backend,
                        scenario=scn, num_clients=20, compression=comp)
    assert loop.state_form == "flat"
    fst = flatten_fl_state(
        init_fl_state(params, sopt, scn, compression=comp, cohort=C),
        loop.layout)
    fst, fmets = jax.jit(loop, donate_argnums=0)(fst, batches)
    st2 = unflatten_fl_state(fst, loop.layout)

    _assert_states_equal(st, st2)
    assert int(st2.round) == R
    for r in range(R):
        for k in mets[r]:
            np.testing.assert_array_equal(
                np.asarray(mets[r][k], np.float32),
                np.asarray(jax.tree.map(lambda m: m[r], fmets)[k],
                           np.float32), err_msg=f"round {r} metric {k}")


def test_fused_arena_gather_matches_stacked(rng):
    """The device-side arena gather path (stage arena once + ship
    (R, C, K, b) indices) produces the same batches — and therefore the
    same bit-exact trajectory — as pre-stacked batches."""
    quad, params, _ = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    # arena of examples; "batches" are rows gathered per (round, client)
    arena = {"A": jnp.asarray(rng.normal(size=(500, D)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(500,)), jnp.float32)}
    idx = jnp.asarray(rng.integers(0, 500, size=(R, C, K, 4)), jnp.int32)
    stacked = jax.tree.map(lambda a: a[idx], arena)

    loop_s = make_fl_loop(loss, copt, sopt, params_like=params,
                          num_rounds=10, rounds_per_call=R, flat="xla")
    fst = flatten_fl_state(init_fl_state(params, sopt), loop_s.layout)
    fst_s, mets_s = jax.jit(loop_s)(fst, stacked)

    loop_a = make_fl_loop(loss, copt, sopt, params_like=params,
                          num_rounds=10, rounds_per_call=R, flat="xla",
                          gather=arena_gather)
    fst = flatten_fl_state(init_fl_state(params, sopt), loop_a.layout)
    fst_a, mets_a = jax.jit(loop_a, static_argnums=())(fst, idx,
                                                       arena=arena)
    _assert_states_equal(fst_s, fst_a)
    _assert_states_equal(mets_s, mets_a)


def test_fused_requires_flat_engine():
    with pytest.raises(ValueError, match="flat engine"):
        make_fl_loop(lambda p, b, g, pl: (0.0, {}),
                     get_client_opt("delta_sgd"), get_server_opt("fedavg"),
                     params_like={"x": jnp.zeros(4)}, num_rounds=1,
                     flat=False)


def test_fused_state_donated_and_live_buffers_flat_in_R(rng):
    """Donation contract: jit(loop, donate_argnums=0) consumes the
    carried FlatFLState in place — every input buffer is deleted after
    the call, no donation warning fires, and the number of live device
    buffers after a block is the same for R=2 and R=8 (peak live state
    does not grow with R)."""
    import warnings
    quad, params, _ = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")

    def run_block(R_n):
        rng_n = np.random.default_rng(1)
        batches = {
            "A": jnp.asarray(rng_n.normal(size=(R_n, C, K, 4, D)),
                             jnp.float32),
            "b": jnp.asarray(rng_n.normal(size=(R_n, C, K, 4)),
                             jnp.float32)}
        loop = make_fl_loop(loss, copt, sopt, params_like=params,
                            num_rounds=10, rounds_per_call=R_n,
                            flat="xla")
        fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        donated = [fst.P, fst.round]
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # donation complaints -> fail
            out, mets = jax.jit(loop, donate_argnums=0)(fst, batches)
        jax.block_until_ready(out.P)
        for buf in donated:
            assert buf.is_deleted(), "carried buffer was NOT donated"
        del batches, mets
        live = [a for a in jax.live_arrays()
                if a.size >= params["x"].size]   # state-sized buffers
        return out, len(live)

    out2, live2 = run_block(2)
    n2 = int(out2.round)
    del out2
    out8, live8 = run_block(8)
    assert int(out8.round) == 8 and n2 == 2
    del out8
    # both measurements taken with one live block result in scope:
    # identical state-sized footprint regardless of R
    assert live2 == live8, (live2, live8)


def test_fused_launch_schedule_2K_per_block_trace(rng):
    """The 2-launches-per-local-step invariant under fusion: tracing one
    R-round block costs exactly 2 pallas launches — the double scan
    (R rounds × K local steps) traces the fused kernel pair ONCE, same
    as a single host round, so the EXECUTED schedule of a block is
    exactly 2·K·R launches: the single round's 2·K, scaled by exactly
    ×R, with no extra launches introduced by the fusion."""
    from repro.kernels.delta_sgd import delta_sgd as dk
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    # reference: one host round traces the same 2 launches
    rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="pallas")
    st = init_fl_state(params, sopt)
    dk.reset_launch_count()
    st, _, _ = jax.jit(rnd)(st, jax.tree.map(lambda x: x[0], batches))
    jax.block_until_ready(st.params["x"])
    per_round = dk.launch_count()
    assert per_round == 2, dict(dk.LAUNCHES)

    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="pallas")
    fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
    dk.reset_launch_count()
    fst, _ = jax.jit(loop)(fst, batches)
    jax.block_until_ready(fst.P)
    assert dk.launch_count() == per_round, dict(dk.LAUNCHES)


def test_flat_state_roundtrip_all_slots(rng):
    """flatten_fl_state/unflatten_fl_state round-trip every FLState slot
    (params, server state, async buffer, EF21 tree) bit-exactly."""
    from repro.compression import CompressionSpec
    quad, params, _ = _problem(rng)
    scn = _scn("zipf_async")
    comp = CompressionSpec(kind="int8", error_feedback=True)
    sopt = get_server_opt("fedadam")
    st = init_fl_state(params, sopt, scn, compression=comp, cohort=C)
    # make the buffer/ef non-trivial so the round-trip proves value
    # preservation, not just zeros
    st = st._replace(
        buffer=st.buffer._replace(delta=jax.tree.map(
            lambda d: d + 0.25, st.buffer.delta)),
        ef=jax.tree.map(lambda e: e - 1.5, st.ef))
    layout = fp.layout_of(params)
    back = unflatten_fl_state(flatten_fl_state(st, layout), layout)
    _assert_states_equal(st, back)


# --------------------------------------------------------- sharded mesh
@needs8
@pytest.mark.slow
@pytest.mark.parametrize("scenario", [None, "dirichlet_stragglers",
                                      "zipf_async"])
def test_sharded_fused_matches_sharded_host(scenario, rng):
    """8-device mesh: the fused scan (flat carry, sharded over the
    flat dim) == the sharded host loop bit-exact, and the packed (C, N)
    buffer never materializes in the SCANNED HLO."""
    from repro.sharding.hlo import assert_flat_buffer_sharded
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = _scn(scenario)
    st, _ = _host_rounds(loss, copt, sopt, params, batches, scn, None,
                         flat="xla", mesh=mesh, federation=spec)

    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="xla",
                        mesh=mesh, federation=spec, scenario=scn,
                        num_clients=20)
    assert loop.state_form == "flat"
    with mesh:
        fst = flatten_fl_state(init_fl_state(params, sopt, scn),
                               loop.layout)
        compiled = jax.jit(loop).lower(fst, batches).compile()
        fst, _ = compiled(fst, batches)
    st2 = unflatten_fl_state(fst, loop.layout)
    _assert_states_equal(st.params, st2.params)
    assert_flat_buffer_sharded(compiled, C, loop.layout.padded_size)


@needs8
@pytest.mark.slow
def test_sharded_fused_compressed_hlo_boundary(rng):
    """Compressed sharded fused loop: bit-exact vs the compressed
    sharded host loop, and BOTH HLO assertions hold on the scanned
    computation — the (C, N) buffer stays sharded and no full-precision
    client delta crosses the client shard boundary inside the scan."""
    from repro.compression import CompressionSpec
    from repro.sharding.hlo import (assert_flat_buffer_sharded,
                                    assert_no_fullprec_delta_collective)
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    quad, params, batches = _problem(rng)
    loss = make_loss(quad)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    scn = _scn("bandwidth_tiered")
    comp = CompressionSpec(kind="int8", error_feedback=True)
    st, _ = _host_rounds(loss, copt, sopt, params, batches, scn, comp,
                         flat="xla", mesh=mesh, federation=spec)

    loop = make_fl_loop(loss, copt, sopt, params_like=params,
                        num_rounds=10, rounds_per_call=R, flat="xla",
                        mesh=mesh, federation=spec, scenario=scn,
                        num_clients=20, compression=comp)
    with mesh:
        fst = flatten_fl_state(
            init_fl_state(params, sopt, scn, compression=comp, cohort=C),
            loop.layout)
        compiled = jax.jit(loop).lower(fst, batches).compile()
        fst, _ = compiled(fst, batches)
    st2 = unflatten_fl_state(fst, loop.layout)
    _assert_states_equal(st.params, st2.params)
    _assert_states_equal(st.ef, st2.ef)
    assert_flat_buffer_sharded(compiled, C, loop.layout.padded_size)
    assert_no_fullprec_delta_collective(compiled, C,
                                        loop.layout.padded_size,
                                        mesh=mesh, federation=spec)
