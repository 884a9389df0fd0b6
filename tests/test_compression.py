"""Delta-compression subsystem (repro.compression + kernels/compress):
kernel parity vs the pure-jnp oracle, int8/top-k contracts, EF21
round-level behavior, bit-exactness of the inert spec, and the sharded
compressed round (parity + both HLO assertions)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression import (CompressionSpec, compress_flat,
                               get_compression)
from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_round, make_loss)
from repro.core import flat as fp
from repro.kernels.compress import compress as ck
from repro.kernels.compress import ref as cr
from repro.launch.mesh import make_mesh

LANES = fp.LANES


def _buf(rng, C=3, chunks=5):
    return jnp.asarray(rng.normal(size=(C, chunks * LANES)), jnp.float32)


# ------------------------------------------------------------------ kernels
def test_quantize_int8_interpret_matches_ref(rng):
    x = _buf(rng)
    q, s = ck.quantize_int8(x, interpret=True)
    qr, sr = cr.quantize_int8_ref(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    dq = ck.dequantize_int8(q, s, interpret=True)
    dqr = cr.dequantize_int8_ref(qr, sr)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dqr),
                               rtol=1e-5, atol=1e-5)


def test_int8_dequant_error_bound(rng):
    """Satellite acceptance: |dequant(quant(x)) − x| ≤ scale/2 per chunk
    (symmetric rounding to 127 levels), and zero chunks are exact."""
    x = _buf(rng, C=2, chunks=4)
    x = x.at[1, :LANES].set(0.0)      # one all-zero chunk
    q, s = ck.quantize_int8(x, interpret=True)
    dq = ck.dequantize_int8(q, s, interpret=True)
    err = jnp.abs(dq - x).reshape(2, -1, LANES)
    bound = (s / 2.0 + 1e-7)[..., None]
    assert bool(jnp.all(err <= bound)), float(jnp.max(err - bound))
    assert float(jnp.max(jnp.abs(dq[1, :LANES]))) == 0.0


@pytest.mark.parametrize("k", [1, 32, LANES])
def test_topk_keeps_exactly_k_per_row(k, rng):
    """Satellite acceptance: exactly k slots survive per LANES-chunk —
    distinct magnitudes, full ties, and the k=LANES identity."""
    x = _buf(rng, C=2, chunks=3)
    out = ck.topk_mask(x, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(cr.topk_mask_ref(x, k)))
    kept = jnp.sum((out != 0.0).reshape(2, -1, LANES), axis=-1)
    assert bool(jnp.all(kept == k)), np.asarray(kept)
    if k < LANES:
        # kept entries are the largest: min kept |x| >= max dropped |x|
        a = jnp.abs(x).reshape(2, -1, LANES)
        keep = (out != 0.0).reshape(2, -1, LANES)
        min_kept = jnp.min(jnp.where(keep, a, jnp.inf), axis=-1)
        max_drop = jnp.max(jnp.where(keep, -jnp.inf, a), axis=-1)
        assert bool(jnp.all(min_kept >= max_drop))
    # ties: constant-magnitude chunk keeps the FIRST k lanes
    xc = jnp.ones((1, LANES), jnp.float32)
    tc = cr.topk_mask_ref(xc, min(k, 5))
    kept = np.flatnonzero(np.asarray(tc[0]))
    np.testing.assert_array_equal(kept, np.arange(min(k, 5)))


def test_topk_rejects_bad_k(rng):
    x = _buf(rng, C=1, chunks=1)
    for bad in (0, LANES + 1):
        with pytest.raises(ValueError):
            ck.topk_mask(x, bad, interpret=True)
        with pytest.raises(ValueError):
            cr.topk_mask_ref(x, bad)


def test_compress_flat_backends_agree_and_levels_select(rng):
    x = _buf(rng)
    spec = CompressionSpec(kind="int8")
    a = compress_flat(x, spec, backend="pallas", interpret=True)
    b = compress_flat(x, spec, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
    levels = jnp.asarray([0, 1, 2], jnp.int32)
    out = compress_flat(x, spec, levels=levels, backend="xla")
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x[0]))
    np.testing.assert_array_equal(
        np.asarray(out[1]),
        np.asarray(cr.dequantize_int8_ref(*cr.quantize_int8_ref(x))[1]))
    np.testing.assert_array_equal(
        np.asarray(out[2]), np.asarray(cr.topk_mask_ref(x, spec.k)[2]))


# --------------------------------------------------------------------- spec
def test_spec_validation_and_wire_math():
    with pytest.raises(KeyError):
        CompressionSpec(kind="fp4")
    with pytest.raises(ValueError):
        CompressionSpec(k_frac=0.0)
    spec = CompressionSpec(kind="int8", k_frac=0.25)
    assert spec.k == 32 and spec.level == 1
    n = 4 * LANES
    table = spec.level_wire_bytes(n)
    assert table[0] == 4 * n                       # f32
    assert table[1] == n + 4 * (n // LANES)        # int8 + scales
    assert table[2] == 5 * spec.k * (n // LANES)   # topk value+index
    wb = spec.wire_bytes(n, levels=jnp.asarray([0, 1, 2]))
    np.testing.assert_allclose(np.asarray(wb), table)
    wb_fixed = spec.wire_bytes(n, num_clients=3)
    np.testing.assert_allclose(np.asarray(wb_fixed), [table[1]] * 3)
    # inert vs active
    assert not CompressionSpec().active()
    assert CompressionSpec(error_feedback=True).active()
    assert get_compression("topk").active()
    from repro.federation import get_scenario
    assert CompressionSpec().active(get_scenario("bandwidth_tiered"))
    assert not CompressionSpec().active(get_scenario("sync_iid"))


def test_bandwidth_scenario_draws():
    from repro.federation import Scenario, get_scenario
    with pytest.raises(KeyError):
        Scenario("bad", bandwidth="dsl")
    with pytest.raises(ValueError):
        # tier_probs must cover the 3-level ladder exactly — a short or
        # long tuple would silently draw out-of-ladder levels
        Scenario("bad", bandwidth="tiered", tier_probs=(0.5, 0.5))
    scn = get_scenario("bandwidth_tiered")
    assert scn.bandwidth_heterogeneous
    l1 = scn.draw_compression_levels(3, 64)
    l2 = scn.draw_compression_levels(3, 64)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert l1.dtype == jnp.int32
    assert int(jnp.min(l1)) >= 0 and int(jnp.max(l1)) <= 2
    # different rounds draw different mixes
    l3 = scn.draw_compression_levels(4, 64)
    assert not np.array_equal(np.asarray(l1), np.asarray(l3))
    uni = get_scenario("bandwidth_tiered", bandwidth="uniform")
    lu = uni.draw_compression_levels(0, 256)
    assert set(np.unique(np.asarray(lu))) <= {0, 1, 2}
    assert not get_scenario("sync_iid").bandwidth_heterogeneous


# ------------------------------------------------------------- round engine
def _quad_problem(rng, D=300, C=4, K=3):
    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)) / np.sqrt(D),
                                jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
    return quad, params, batches


def test_inert_spec_bit_exact_all_engines(rng):
    """Acceptance: with compression="none" all three engines produce
    bit-identical states vs a round built without any compression."""
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    for eng in (False, "xla", "pallas"):
        r0 = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                   flat=eng))
        r1 = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                   flat=eng, compression="none"))
        s0, s1 = init_fl_state(params, sopt), init_fl_state(params, sopt)
        for _ in range(2):
            s0, m0, _ = r0(s0, batches)
            s1, m1, _ = r1(s1, batches)
        np.testing.assert_array_equal(np.asarray(s0.params["x"]),
                                      np.asarray(s1.params["x"]))
        assert "wire_bytes" not in m1     # inert spec: no telemetry
        assert s1.ef is None


def test_vmap_engine_rejects_active_compression(rng):
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(lambda p, b: (0.0, {}))
    with pytest.raises(ValueError):
        make_fl_round(loss, copt, sopt, num_rounds=1, compression="int8")
    with pytest.raises(ValueError):
        make_fl_round(loss, copt, sopt, num_rounds=1,
                      compression=CompressionSpec(error_feedback=True))


def test_ef_requires_allocated_state(rng):
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    spec = CompressionSpec(kind="int8", error_feedback=True)
    rnd = make_fl_round(make_loss(quad), copt, sopt, num_rounds=10,
                        flat="xla", compression=spec)
    st = init_fl_state(params, sopt)          # no ef allocated
    with pytest.raises(ValueError):
        jax.eval_shape(lambda s, b: rnd(s, b), st, batches)
    with pytest.raises(ValueError):
        init_fl_state(params, sopt, compression=spec)   # cohort missing


def test_ef21_roundtrip_int8_converges_to_none(rng):
    """Satellite acceptance: with EF21 error feedback the int8-compressed
    run tracks the uncompressed run's loss on the synthetic quad task —
    and EF keeps it strictly closer than naive int8 compression."""
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)

    def run(spec, ef):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=30,
                                    flat="xla", compression=spec))
        st = init_fl_state(params, sopt, compression=spec,
                           cohort=4 if ef else None)
        m = {}
        for _ in range(20):
            st, m, _ = rnd(st, batches)
        return float(m["loss"]), st

    l_none, _ = run(None, False)
    spec = CompressionSpec(kind="int8", error_feedback=True)
    l_int8, st = run(spec, True)
    l_raw, _ = run(CompressionSpec(kind="int8"), False)
    assert abs(l_int8 - l_none) <= 0.05 * abs(l_none) + 1e-6, \
        (l_int8, l_none)
    assert abs(l_int8 - l_none) <= abs(l_raw - l_none) + 1e-6
    # the EF tree tracks the last reconstructed delta: f32, (C,)+shape
    assert st.ef["x"].dtype == jnp.float32
    assert st.ef["x"].shape == (4, 300)
    assert float(jnp.max(jnp.abs(st.ef["x"]))) > 0.0


def test_compressed_round_telemetry_and_async(rng):
    """Wire telemetry in the metrics + compression composes with the
    FedBuff async buffer (deltas enter the buffer dequantized)."""
    from repro.federation import get_scenario
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    layout = fp.layout_of(params)
    spec = CompressionSpec(kind="topk", k_frac=0.25)
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                flat="xla", compression=spec))
    st = init_fl_state(params, sopt)
    st, m, _ = rnd(st, batches)
    C, chunks = 4, -(-layout.size // LANES)
    want = 5.0 * spec.k * chunks * C
    assert float(m["wire_bytes"]) == want
    np.testing.assert_allclose(
        float(m["comp_ratio"]),
        4.0 * layout.size * C / want, rtol=1e-6)

    scn = get_scenario("zipf_async", staleness_max=0, buffer_size=4)
    spec = CompressionSpec(kind="int8", error_feedback=True)
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                flat="xla", scenario=scn,
                                compression=spec))
    st = init_fl_state(params, sopt, scn, compression=spec, cohort=4)
    for _ in range(2):
        st, m, _ = rnd(st, batches)
    assert st.buffer is not None and st.ef is not None
    assert "wire_bytes" in m and float(m["flushed"]) == 1.0
    assert np.isfinite(float(m["loss"]))


def test_compression_launch_counts(rng):
    """int8 adds exactly 2 compress launches per traced round (quantize +
    dequantize), top-k exactly 1 — and the Δ-SGD step pair stays at 2."""
    from repro.kernels.delta_sgd import delta_sgd as dk
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    for kind, n_comp in (("int8", 2), ("topk", 1)):
        rnd = make_fl_round(loss, copt, sopt, num_rounds=10,
                            flat="pallas", compression=kind)
        st = init_fl_state(params, sopt)
        dk.reset_launch_count()
        ck.reset_launch_count()
        jax.eval_shape(lambda s, b: rnd(s, b), st, batches)
        assert dk.launch_count() == 2, dict(dk.LAUNCHES)
        assert ck.launch_count() == n_comp, dict(ck.LAUNCHES)


def test_bandwidth_hetero_round_mixes_levels(rng):
    """bandwidth_tiered: the per-client level draw selects compressors
    per lane — lanes at level 0 aggregate their exact delta."""
    from repro.federation import get_scenario
    quad, params, batches = _quad_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    scn = get_scenario("bandwidth_tiered")
    spec = CompressionSpec(kind="int8")
    rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10, flat="xla",
                                scenario=scn, compression=spec))
    st = init_fl_state(params, sopt, scn)
    st, m, _ = rnd(st, batches)
    levels = np.asarray(scn.draw_compression_levels(0, 4))
    want = float(jnp.sum(spec.wire_bytes(
        fp.layout_of(params).size, levels=jnp.asarray(levels))))
    assert float(m["wire_bytes"]) == want
    np.testing.assert_allclose(float(m["comp_level_mean"]),
                               levels.astype(np.float32).mean(), rtol=1e-6)
    # a bandwidth-hetero scenario implies compression even with no
    # compression= argument: the engine resolves the inert "none" spec
    # (level-0 clients pass through, level-1/2 get compressed) ...
    rnd0 = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                 flat="xla", scenario=scn))
    _, m0, _ = rnd0(init_fl_state(params, sopt, scn), batches)
    assert "wire_bytes" in m0 and "comp_level_mean" in m0
    # ... and, like async, it cannot run on the vmap engine
    with pytest.raises(ValueError):
        make_fl_round(loss, copt, sopt, num_rounds=10, scenario=scn)


# ---------------------------------------------------------------- sharded
needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices "
                                   "(XLA_FLAGS=--xla_force_host_platform"
                                   "_device_count=8)")


def _fl_problem(rng, C=8, K=3, D=300, E=40):
    """Mixed f32/bf16 quadratic FL problem (same shape as test_flat)."""
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


@needs8
@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_sharded_compressed_round_matches_replicated(kind, rng):
    """Tentpole acceptance: the compressed sharded round (compress
    before the client-mean psum, inside shard_map) matches the
    compressed replicated round to <= 1e-5, EF + bandwidth levels
    included."""
    from repro.federation import get_scenario
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    quad, params, batches = _fl_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    cspec = CompressionSpec(kind=kind, error_feedback=True)
    scn = get_scenario("bandwidth_tiered")
    out = {}
    for name, kw in (("repl", {}),
                     ("shard", dict(mesh=mesh, federation=spec))):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat="xla", scenario=scn,
                                    compression=cspec, **kw))
        st = init_fl_state(params, sopt, scn, compression=cspec, cohort=8)
        for _ in range(2):
            st, m, _ = rnd(st, batches)
        out[name] = (np.asarray(st.params["x"]),
                     np.asarray(st.ef["x"]),
                     np.asarray([m["loss"], m["wire_bytes"],
                                 m["comp_ratio"]], np.float64))
    for a, b in zip(out["repl"], out["shard"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@needs8
@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_sharded_compressed_round_hlo_assertions(kind, rng):
    """Acceptance: under the 8-device test mesh, for both int8 and
    top-k, the compiled compressed sharded round (a) never materializes
    the full (C, N) buffer and (b) ships no full-precision client delta
    across the client shard boundary."""
    from repro.federation import get_scenario
    from repro.sharding.hlo import (assert_flat_buffer_sharded,
                                    assert_no_fullprec_delta_collective)
    from repro.sharding.spec import cross_device
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    quad, params, batches = _fl_problem(rng)
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    loss = make_loss(quad)
    cspec = CompressionSpec(kind=kind, error_feedback=True)
    scn = get_scenario("bandwidth_tiered")
    rnd = make_fl_round(loss, copt, sopt, num_rounds=10, flat="xla",
                        scenario=scn, compression=cspec,
                        mesh=mesh, federation=spec)
    st = init_fl_state(params, sopt, scn, compression=cspec, cohort=8)
    lay = fp.layout_of(params, shards=spec.flat_shards(mesh))
    compiled = jax.jit(rnd).lower(st, batches).compile()
    assert_flat_buffer_sharded(compiled, 8, lay.padded_size)
    rep = assert_no_fullprec_delta_collective(compiled, 8,
                                              lay.padded_size,
                                              mesh=mesh, federation=spec)
    assert rep["collectives"] > 0     # the check actually saw traffic


@needs8
def test_fullprec_collective_report_has_teeth():
    """The boundary checker itself: client-crossing big f32 collectives
    are flagged, intra-client flat-dim reshards and operand-name
    mentions are not, unparseable groups are conservative."""
    from repro.sharding.hlo import (_client_coords,
                                    fullprec_collective_report)
    mesh = make_mesh((4, 2), ("data", "model"))
    coords = _client_coords(mesh, ("data",))
    cross = ('  %all-gather = f32[2,256]{1,0} all-gather(f32[2,64] %p), '
             'replica_groups={{0,2,4,6},{1,3,5,7}}, dimensions={1}')
    intra = ('  %all-reduce = f32[2,512]{1,0} all-reduce(f32[2,512] %p), '
             'replica_groups={{0,1},{2,3},{4,5},{6,7}}, to_apply=%add')
    small = ('  %all-reduce.2 = f32[256]{0} all-reduce(f32[256] %p), '
             'replica_groups={{0,2,4,6},{1,3,5,7}}, to_apply=%add')
    operand = ('  %f = f32[2,512]{1,0} fusion(f32[2,512] '
               '%all-gather.3), kind=kLoop')
    noparse = ('  %all-gather.9 = f32[2,256]{1,0} all-gather(f32[2,64] '
               '%p), replica_groups=[2,4]<=[8], dimensions={1}')
    allrep = ('  %all-reduce.7 = f32[2,256]{1,0} all-reduce(f32[2,256] '
              '%p), replica_groups={}, to_apply=%add')
    text = "\n".join([cross, intra, small, operand, noparse, allrep])
    rep = fullprec_collective_report(text, max_elems=2 * 256,
                                     client_coord_of=coords)
    assert rep["collectives"] == 5          # operand mention not counted
    # cross + unparseable + empty-groups (= ALL devices, spans clients)
    assert rep["fullprec"] == 3
    assert "all-gather" in rep["sample"][0]
