"""Benchmark harness — one function per paper table/figure, each emitting
``name,us_per_call,derived`` CSV rows (us_per_call = wall-µs per FL round;
derived = final test accuracy unless stated).

  table1   : optimizer × task × α grid (paper Table 1)
  table2b  : FedProx loss, α=0.01 (paper Table 2b)
  table3   : variable local dataset sizes + weighted FedAvg (paper Table 3)
  table4   : FedAdam server (paper Table 4)
  fig4     : Δ-SGD δ-sensitivity (paper Fig. 4)
  fig5     : local epochs E ∈ {1,2,3} (paper Fig. 5)
  convex   : Thm 5 numeric check (derived = final distance² / initial)
  kernels  : per-kernel µs/call in interpret mode (derived = max |err| vs
             the ref oracle — correctness, not TPU wall time)
  sharded  : flat Δ-SGD round on a host (data, model) mesh, sharded vs
             replicated (derived = max |param diff| between engines)
  scenarios: federation scenario presets (repro.federation) on the quick
             FL harness — sync_iid / dirichlet_stragglers / zipf_async
             (derived = final accuracy) plus cohort-skew, staleness and
             effective-K diagnostic rows
  compression: the flat_fed_compressed variant (repro.compression) on
             the quick FL harness — none/int8/topk delta compression
             with EF21 error feedback (derived = final accuracy) plus
             wire-bytes and compression-ratio rows, the
             bandwidth_tiered per-client-level scenario, and
             interpret-mode µs/call + max-err rows for the
             quantize/dequantize/top-k kernels
  faults   : chaos presets (repro.federation.faults) — dropouts + NaN
             gradients (dirichlet_dropouts) and byzantine + over-stale
             deltas (byzantine_async) under {mean, clip, trimmed}
             aggregation, plus a clean sync_iid anchor (derived = final
             accuracy; byzantine-under-mean rows document the
             undefended divergence) and round-health telemetry rows
  rounds_fused: the round-fused training loop (repro.core.fed_loop) vs
             the host loop at C=128 — us/round both ways (bit-exact,
             fused-row derived = max |param diff| must be 0) plus the
             host/fused speedup row (acceptance: >= 1.5x)
  fleet    : fleet regime (repro.core.fed_loop.make_fleet_loop +
             repro.federation.arena) — us/round at C_registered in
             {10^2, 10^3} (--quick; full adds {10^4, 10^5}) with a
             fixed 16-client cohort, each size compile-checked against
             the cohort-only memory ceiling
             (hlo.assert_cohort_only_materialization), plus one fused
             Gumbel-top-k cohort draw over 10^5 zipf candidates

Full protocol details: benchmarks/fl_common.py. Run everything:
  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# 8 virtual CPU devices so the `sharded` suite exercises a real mesh;
# must be set before jax initializes (all jax imports here are lazy).
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np

ROWS = []


def _timeit(fn, *a, n=3):
    """Interpret-mode µs/call: one warmup call, then the mean of n
    blocked calls. Returns (us, last_output)."""
    import jax
    fn(*a)
    t0 = time.time()
    for _ in range(n):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6, out


def emit(name, us, derived):
    # %.6g keeps small kernel parity errors exact (a fixed .4f would
    # round 1.4e-4 down past the bench guard's max_err thresholds)
    row = f"{name},{us:.1f},{derived:.6g}"
    ROWS.append(row)
    print(row, flush=True)


def table1(rounds):
    from benchmarks.fl_common import OPTS, run_fl, tuned_lrs
    lrs = tuned_lrs(rounds=min(rounds, 40))
    for task in ("easy", "medium", "hard"):
        for alpha in (1.0, 0.1, 0.01):
            for opt in OPTS:
                r = run_fl(opt, task, alpha=alpha, rounds=rounds,
                           lr=lrs[opt])
                emit(f"table1/{task}/alpha{alpha}/{opt}",
                     r["us_per_round"], r["acc"])


def table2b(rounds):
    from benchmarks.fl_common import OPTS, run_fl, tuned_lrs
    lrs = tuned_lrs(rounds=min(rounds, 40))
    for opt in OPTS:
        r = run_fl(opt, "medium", alpha=0.01, rounds=rounds, lr=lrs[opt],
                   fedprox_mu=0.1)
        emit(f"table2b/fedprox/medium/alpha0.01/{opt}", r["us_per_round"],
             r["acc"])


def table3(rounds):
    from benchmarks.fl_common import run_fl, tuned_lrs
    lrs = tuned_lrs(rounds=min(rounds, 40))
    for opt in ("sgd", "sgdm", "adam", "adagrad", "sps", "delta_sgd"):
        r = run_fl(opt, "medium", alpha=0.1, rounds=rounds, lr=lrs[opt],
                   variable_sizes=True, weighted=True)
        emit(f"table3/varsizes/medium/{opt}", r["us_per_round"], r["acc"])


def table4(rounds):
    from benchmarks.fl_common import OPTS, run_fl, tuned_lrs
    lrs = tuned_lrs(rounds=min(rounds, 40))
    for opt in OPTS:
        r = run_fl(opt, "medium", alpha=0.1, rounds=rounds, lr=lrs[opt],
                   server="fedadam")
        emit(f"table4/fedadam/medium/{opt}", r["us_per_round"], r["acc"])


def fig4(rounds):
    from benchmarks.fl_common import run_fl
    for delta in (0.01, 0.1, 1.0):
        for task in ("easy", "medium"):
            r = run_fl("delta_sgd", task, alpha=0.1, rounds=rounds,
                       delta=delta)
            emit(f"fig4/delta{delta}/{task}", r["us_per_round"], r["acc"])


def fig5(rounds):
    from benchmarks.fl_common import run_fl
    for E in (1, 2, 3):
        r = run_fl("delta_sgd", "medium", alpha=0.1, rounds=rounds,
                   local_epochs=E)
        emit(f"fig5/epochs{E}/medium", r["us_per_round"], r["acc"])


def convex(rounds=40):
    """Thm 5 numeric check on interpolation least squares."""
    sys.path.insert(0, "tests")
    from test_theory import _make_problem, _gi
    m, d = 4, 6
    As, bs, x_star = _make_problem(m, d)
    x = np.zeros(d, np.float32)
    xs_i = [x.copy() for _ in range(m)]
    xs_prev = [x.copy() for _ in range(m)]
    etas, thetas = [0.05] * m, [0.0] * m
    gs_prev = [_gi(As[i], bs[i], x) for i in range(m)]
    t0 = time.time()
    v0 = float(np.sum(x_star ** 2))
    v = v0
    for t in range(rounds):
        nxt, ne, nt = [], [], []
        for i in range(m):
            g = _gi(As[i], bs[i], xs_i[i])
            dg = np.linalg.norm(g - gs_prev[i])
            dx = np.linalg.norm(xs_i[i] - xs_prev[i])
            eta = min(dx / (2 * dg) if dg > 0 else np.inf,
                      np.sqrt(1 + thetas[i]) * etas[i])
            nxt.append(xs_i[i] - eta * g)
            nt.append(eta / etas[i])
            ne.append(eta)
            gs_prev[i] = g
        xs_prev, xs_i, etas, thetas = xs_i, nxt, ne, nt
        xm = np.mean(xs_i, axis=0)
        v = float(np.sum((xm - x_star) ** 2))
    emit("convex/dist_ratio_T40", (time.time() - t0) / rounds * 1e6, v / v0)


def kernels(rounds=None):
    del rounds
    import jax
    import jax.numpy as jnp
    from repro.kernels.delta_sgd import delta_sgd as dk, ref as dref
    from repro.kernels.flash_attention.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.mamba2_scan.ops import ssd_scan
    from repro.kernels.mamba2_scan.ref import ssd_ref
    rng = np.random.default_rng(0)
    timeit = _timeit

    g = jnp.asarray(rng.normal(size=(1 << 16,)), jnp.float32)
    gp = jnp.asarray(rng.normal(size=(1 << 16,)), jnp.float32)
    us, out = timeit(lambda a, b: dk.norms(a, b, interpret=True), g, gp)
    err = abs(float(out[0]) - float(dref.norms_ref(g, gp)[0]))
    emit("kernels/delta_sgd_norms_64k", us, err)

    # ---- flat fused Δ-SGD step: client-slab engine vs per-leaf path ----
    # 16-leaf tree, 64k elements total; one full local step (norms+apply).
    from repro.core import flat as fp
    from repro.core.delta_sgd import (delta_sgd_init, delta_sgd_update,
                                      flat_delta_sgd_init,
                                      flat_delta_sgd_step)
    GAMMA, DELTA, ETA0, THETA0 = 2.0, 0.1, 0.2, 1.0
    tree = {f"w{i}": jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
            for i in range(16)}
    grads = {k_: v * 0.1 for k_, v in tree.items()}
    gprev = {k_: v * -0.05 for k_, v in tree.items()}
    layout = fp.layout_of(tree)

    def perleaf_step(p, g, gp_):
        """Legacy schedule: norms + apply kernel per leaf (2×leaves
        launches per local step, per client)."""
        dg2 = gg2 = jnp.zeros((), jnp.float32)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(gp_)):
            x, y = dk.norms(a, b, interpret=True)
            dg2, gg2 = dg2 + x, gg2 + y
        eta = ETA0  # first-step branch: η fixed, apply still runs
        return {k2: dk.apply_update(p[k2], g[k2], eta, interpret=True)
                for k2 in p}, dg2, gg2

    def packed_step(P, G, S):
        return flat_delta_sgd_step(P, G, S, gamma=GAMMA, delta=DELTA,
                                   eta0=ETA0, interpret=True)

    slab1 = fp.slab_shape(1, layout)
    P1 = fp.pack(tree, layout).reshape(slab1)
    G1 = fp.pack(grads, layout).reshape(slab1)
    S1 = flat_delta_sgd_init(1, layout, eta0=ETA0, theta0=THETA0)
    S1 = S1._replace(prev_grads=fp.pack(gprev, layout).reshape(slab1))

    # launch accounting (trace-time): the packed step must cost exactly
    # 2 pallas launches independent of leaf count and client count
    for C in (1, 4):
        Pc = jnp.broadcast_to(P1, fp.slab_shape(C, layout))
        Gc = jnp.broadcast_to(G1, fp.slab_shape(C, layout))
        Sc = flat_delta_sgd_init(C, layout, eta0=ETA0, theta0=THETA0)
        dk.reset_launch_count()
        jax.block_until_ready(packed_step(Pc, Gc, Sc)[0])
        assert dk.launch_count() == 2, (C, dict(dk.LAUNCHES))
    dk.reset_launch_count()
    jax.block_until_ready(perleaf_step(tree, grads, gprev)[0]["w0"])
    perleaf_launches = dk.launch_count()  # 2 × leaves, per client
    print(f"# launches/local-step: per-leaf={perleaf_launches} "
          f"(x num_clients under vmap), flat_fused=2 (total)", flush=True)

    # parity vs the pytree oracle over a full first step
    s_ref = delta_sgd_init(tree, eta0=ETA0, theta0=THETA0)
    s_ref = s_ref._replace(prev_grads=gprev)
    ref_p, ref_s = delta_sgd_update(tree, grads, s_ref, gamma=GAMMA,
                                    delta=DELTA, eta0=ETA0)
    newP, newS = packed_step(P1, G1, S1)
    got_p = fp.unpack(newP[0].reshape(-1), layout)
    err = max(float(jnp.max(jnp.abs(got_p[k2] - ref_p[k2])))
              for k2 in ref_p)
    err = max(err, abs(float(newS.eta[0]) - float(ref_s.eta)))

    us_packed, _ = timeit(lambda a, b: packed_step(a, b, S1), P1, G1)
    us_perleaf, _ = timeit(lambda a, b: perleaf_step(a, b, gprev),
                           tree, grads)
    emit("kernels/delta_sgd_perleaf_64k", us_perleaf, 0.0)
    emit("kernels/delta_sgd_flat_fused", us_packed, err)
    assert us_packed <= us_perleaf, (us_packed, us_perleaf)

    # end-to-end round time, flat vs vmap engine (derived = accuracy)
    from benchmarks import fl_common
    for eng in ("vmap", "flat"):
        # fresh dataset per engine: round sampling is stateful, so a
        # shared cached dataset would feed the engines different batches
        fl_common._fed.cache_clear()
        r = fl_common.run_fl("delta_sgd", "easy", rounds=10,
                             num_clients=30, engine=eng)
        emit(f"kernels/fl_round_{eng}", r["us_per_round"], r["acc"])

    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    us, out = timeit(lambda a, b, c: flash_attention(
        a, b, c, block_q=64, block_k=64, interpret=True), q, k, v)
    err = float(jnp.max(jnp.abs(out - attention_ref(q, k, v))))
    emit("kernels/flash_attention_256", us, err)

    x = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, 128, 4)), jnp.float32)
    A = jnp.asarray(np.log(rng.uniform(1, 16, 4)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(1, 128, 1, 16)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(1, 128, 1, 16)), jnp.float32)
    us, out = timeit(lambda *a: ssd_scan(*a), x, dt, A, Bm, Cm)
    err = float(jnp.max(jnp.abs(out[0] - ssd_ref(x, dt, A, Bm, Cm)[0])))
    emit("kernels/mamba2_ssd_128", us, err)


def sharded(rounds=None):
    """Flat Δ-SGD rounds with the (C, N) buffer mesh-sharded per
    FederationSpec.flat_spec vs the replicated flat engine. Timing is
    host-mesh wall time (virtual CPU devices — layout/collective
    correctness, not TPU speed); derived of the sharded row = max
    |param diff| vs the replicated engine after 3 rounds.

    The flat_block_* rows time the round-fused loop both ways: the
    replicated fused loop vs the block-level shard_map
    (make_fl_loop(block_sharded=True) — ONE shard_map around the whole
    R-round lax.scan, so per-round dispatch overhead is paid once per
    block instead of once per round). Their us ratio is the dispatch-
    overhead figure baseline.json soft-guards (measured ~2.5-3x vs the
    replicated per-round engine; the limit adds headroom for shared-CPU
    timing noise)."""
    del rounds
    import jax
    import jax.numpy as jnp
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    from repro.sharding.spec import cross_device

    rng = np.random.default_rng(0)
    shape = (4, 2) if jax.device_count() >= 8 else (1, 1)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(shape, ("data", "model"))
    spec = cross_device(mesh)
    D, C, K = 4096, 8, 4

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    batches = {"A": jnp.asarray(rng.normal(size=(C, K, 8, D)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(C, K, 8)), jnp.float32)}
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    loss = make_loss(quad)
    finals = {}
    for name, kw in (("replicated", {}),
                     ("sharded", dict(mesh=mesh, federation=spec))):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=10,
                                    flat="xla", **kw))
        st = init_fl_state(params, sopt)
        st, _, _ = rnd(st, batches)          # compile + warm
        jax.block_until_ready(st.params["x"])
        st = init_fl_state(params, sopt)
        t0 = time.time()
        for _ in range(3):
            st, _, _ = rnd(st, batches)
        jax.block_until_ready(st.params["x"])
        us = (time.time() - t0) / 3 * 1e6
        finals[name] = np.asarray(st.params["x"])
        err = (0.0 if name == "replicated" else
               float(np.max(np.abs(finals["sharded"]
                                   - finals["replicated"]))))
        emit(f"sharded/flat_round_{name}_{shape[0]}x{shape[1]}", us, err)

    # ---- block-level shard_map: the fused R-round loop replicated vs
    # wrapped in ONE shard_map over the client axes (core.fed_loop
    # block_sharded=True). N stays replicated (flat_shards == 1); the
    # only client-crossing collective is the aggregate psum, so the
    # sharded block's per-round cost tracks the replicated loop's
    # instead of paying per-round SPMD dispatch ----
    from repro.core import flatten_fl_state, make_fl_loop
    from repro.sharding.spec import FederationSpec
    fedc = FederationSpec(client_axes=("data",), fsdp_axes=(), tp_axes=())
    R = 8
    data = {"A": jnp.asarray(rng.normal(size=(R, C, K, 8, D)),
                             jnp.float32),
            "b": jnp.asarray(rng.normal(size=(R, C, K, 8)), jnp.float32)}
    kwb = dict(params_like=params, num_rounds=4 * R, rounds_per_call=R,
               flat="xla")
    finals_b = {}
    data1 = jax.tree.map(lambda x: x[:1], data)
    for name, kw in (("block_replicated", {}),
                     ("block_sharded", dict(mesh=mesh, federation=fedc,
                                            block_sharded=True))):
        loop = make_fl_loop(loss, copt, sopt, **kwb, **kw)
        jloop = jax.jit(loop)
        f0 = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        fst, _ = jloop(f0, data)             # compile + warm
        jax.block_until_ready(fst.P)
        t0 = time.time()
        for _ in range(3):
            fst, _ = jloop(f0, data)
        jax.block_until_ready(fst.P)
        us = (time.time() - t0) / (3 * R) * 1e6
        # parity over ONE round: psum reassociation is ~1e-6/round, but
        # Δ-SGD's η min-branch can discretely amplify it over a long
        # block — the controlled-tolerance multi-round parity lives in
        # tests/test_fleet.py
        f1, _ = jloop(f0, data1)
        finals_b[name] = np.asarray(f1.P)
        err = (0.0 if name == "block_replicated" else
               float(np.max(np.abs(finals_b["block_sharded"]
                                   - finals_b["block_replicated"]))))
        emit(f"sharded/flat_{name}_{shape[0]}x{shape[1]}", us, err)


def scenarios(rounds=None):
    """Federation scenario presets on the quick FL harness. The accuracy
    rows (derived = acc) time the full scenario round incl. scheduler
    draw, lane masking, and (zipf_async) the buffered server path; the
    diagnostic rows surface the per-round cohort composition / staleness
    / effective-K telemetry in the benchmark CSV (satellite: report
    scenario stats in the CSV)."""
    del rounds
    from benchmarks import fl_common
    for name in ("sync_iid", "dirichlet_stragglers", "zipf_async"):
        # fresh dataset per scenario: round sampling and the scenario
        # pin are stateful on the cached FederatedDataset
        fl_common._fed.cache_clear()
        r = fl_common.run_fl("delta_sgd", "easy", rounds=10,
                             num_clients=30, scenario=name)
        emit(f"scenarios/{name}", r["us_per_round"], r["acc"])
        s = r["scenario"]
        emit(f"scenarios/{name}/cohort_top5_share", r["us_per_round"],
             s.get("cohort_top5_share", 0.0))
        if "k_eff_mean" in s:
            emit(f"scenarios/{name}/k_eff_mean", r["us_per_round"],
                 s["k_eff_mean"])
        if "stale_mean" in s:
            emit(f"scenarios/{name}/stale_mean", r["us_per_round"],
                 s["stale_mean"])


def compression(rounds=None):
    """Delta compression (repro.compression) on the quick FL harness:
    the `flat_fed_compressed` variant at each compression kind (with
    EF21 error feedback), its wire-bytes / compression-ratio columns,
    the bandwidth_tiered per-client-level scenario, and interpret-mode
    kernel rows (derived = max |err| vs the pure-jnp oracle)."""
    del rounds
    import jax.numpy as jnp
    from benchmarks import fl_common
    from repro.kernels.compress import compress as ck, ref as cr

    for kind in ("none", "int8", "topk"):
        # fresh dataset per run: round sampling is stateful on the
        # cached FederatedDataset
        fl_common._fed.cache_clear()
        r = fl_common.run_fl("delta_sgd", "easy", rounds=10,
                             num_clients=30, engine="flat",
                             compression=kind,
                             error_feedback=(kind != "none"))
        emit(f"compression/flat_fed_compressed/{kind}",
             r["us_per_round"], r["acc"])
        if kind != "none":
            c = r["compression"]
            emit(f"compression/flat_fed_compressed/{kind}/wire_bytes",
                 r["us_per_round"], c["wire_bytes_round"])
            emit(f"compression/flat_fed_compressed/{kind}/comp_ratio",
                 r["us_per_round"], c["comp_ratio"])

    # bandwidth axis: per-client levels drawn each round (tiered mix)
    fl_common._fed.cache_clear()
    r = fl_common.run_fl("delta_sgd", "easy", rounds=10, num_clients=30,
                         compression="int8", error_feedback=True,
                         scenario="bandwidth_tiered")
    emit("compression/bandwidth_tiered", r["us_per_round"], r["acc"])
    emit("compression/bandwidth_tiered/comp_ratio", r["us_per_round"],
         r["compression"]["comp_ratio"])
    emit("compression/bandwidth_tiered/level_mean", r["us_per_round"],
         r["compression"].get("level_mean", 0.0))

    # kernel rows: interpret-mode µs/call, derived = max err vs oracle
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 1 << 14)), jnp.float32)

    us, (q, s) = _timeit(lambda a: ck.quantize_int8(a, interpret=True), x)
    qr, sr = cr.quantize_int8_ref(x)
    err = max(float(jnp.max(jnp.abs(q.astype(jnp.int32)
                                    - qr.astype(jnp.int32)))),
              float(jnp.max(jnp.abs(s - sr))))
    emit("compression/quantize_int8_64k", us, err)
    us, dq = _timeit(lambda a, b: ck.dequantize_int8(a, b, interpret=True),
                    q, s)
    err = float(jnp.max(jnp.abs(dq - cr.dequantize_int8_ref(qr, sr))))
    emit("compression/dequantize_int8_64k", us, err)
    us, tk = _timeit(lambda a: ck.topk_mask(a, 32, interpret=True), x)
    err = float(jnp.max(jnp.abs(tk - cr.topk_mask_ref(x, 32))))
    emit("compression/topk_mask_64k", us, err)


def faults(rounds=None):
    """Chaos suite (repro.federation.faults): the two chaos scenario
    presets — dirichlet_dropouts (mid-round dropouts + NaN gradients,
    sync) and byzantine_async (−10x scaled deltas + over-stale updates,
    FedBuff async) — under the RobustAgg ladder {mean, clip, trimmed},
    next to the clean sync_iid anchor (derived = final accuracy; the
    mean rows under byzantine corruption are EXPECTED to crater — that
    contrast is what the suite documents, so baseline.json keeps every
    faults row soft). The telemetry rows surface the round-health
    counters: mean surviving clients, quorum skips, NaN-guard and
    η-clamp trigger rates."""
    del rounds
    from benchmarks import fl_common
    # cohort of 10 (participation 0.25 of 40): big enough that trimmed
    # aggregation has a real window (t=2) and the 10% byzantine rate
    # corrupts ~1 client per round
    kw = dict(rounds=10, num_clients=40, participation=0.25)
    fl_common._fed.cache_clear()
    clean = fl_common.run_fl("delta_sgd", "easy", engine="flat",
                             scenario="sync_iid", **kw)
    emit("faults/clean/sync_iid/mean", clean["us_per_round"],
         clean["acc"])
    for scen in ("dirichlet_dropouts", "byzantine_async"):
        for agg in ("mean", "clip", "trimmed"):
            fl_common._fed.cache_clear()
            r = fl_common.run_fl("delta_sgd", "easy", scenario=scen,
                                 robust_agg=agg, **kw)
            emit(f"faults/{scen}/{agg}", r["us_per_round"], r["acc"])
            if agg == "clip":     # one telemetry set per preset
                s = r["scenario"]
                for key in ("valid_mean", "skipped_rounds",
                            "nan_guard_rate", "eta_clip_rate"):
                    if key in s:
                        emit(f"faults/{scen}/{key}", r["us_per_round"],
                             s[key])


def rounds_fused(rounds=None):
    """Round-fused loop (repro.core.fed_loop) vs the host loop at a
    fleet-scale cohort (C=128, full participation) on the synthetic
    task, wide-MLP params (~45k): the host loop re-stages (C, K, b, ...)
    batches, re-dispatches the jitted round, and pays the per-round
    pack/unpack traffic — broadcast re-pack of the params at round
    start, the params-tree + (C, ...) new-locals unpack at round end —
    all scaling with C*N; the fused loop carries the state in persistent
    flat form across an 8-round lax.scan, stages the example arena on
    device once, and ships only (R, C, K, b) int32 gather indices per
    block. Rows: us/round for each loop (derived of the fused row = max
    |param diff| vs the host loop — must be 0.0, the loops are
    bit-exact) and the speedup row (derived = host/fused, the >= 1.5x
    acceptance figure)."""
    del rounds
    import jax
    import jax.numpy as jnp
    from repro.core import (arena_gather, flatten_fl_state, get_client_opt,
                            get_server_opt, init_fl_state, make_fl_loop,
                            make_fl_round, make_loss, unflatten_fl_state)
    from repro.data.pipeline import FederatedDataset
    from repro.data.synthetic import get_task
    from repro.models.small import MLPConfig, make_small_model, softmax_ce

    # C >= 64 at small per-client batches and the default K=2: the
    # regime the paper's fleet-scale heterogeneity experiments live in,
    # where per-round overhead (not the grad evals) dominates wall-clock
    T, R, B, K, part, m = 16, 8, 4, 2, 1.0, 128
    task = get_task("easy", seed=0)

    def build():
        return FederatedDataset.build(task, num_clients=m, alpha=1.0,
                                      seed=0)

    init_fn, logits_fn = make_small_model(
        MLPConfig("mlp-wide-fused", input_dim=32, hidden_dims=(1024,),
                  num_classes=10))
    loss_fn = make_loss(
        lambda p, b: (softmax_ce(logits_fn(p, b["x"]), b["y"]), {}))
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    params = init_fn(jax.random.key(0))

    def run_host(fed, rounds_n, rnd):
        # the launch/train.py host round: stage batches, dispatch the
        # jitted round, materialize the round's metrics row (telemetry)
        st = init_fl_state(params, sopt)
        for t in range(rounds_n):
            bat, _, _ = fed.sample_round(part, K, B, round_idx=t)
            st, met, _ = rnd(st, {"x": jnp.asarray(bat["x"]),
                                  "y": jnp.asarray(bat["y"])})
            jax.tree.map(np.asarray, met)
        jax.block_until_ready(st.params["l0"]["w"])
        return st

    rnd = jax.jit(make_fl_round(loss_fn, copt, sopt, num_rounds=T,
                                flat="xla"))
    run_host(build(), 1, rnd)               # compile warmup
    fed = build()
    t0 = time.time()
    st = run_host(fed, T, rnd)
    us_host = (time.time() - t0) / T * 1e6

    loop = make_fl_loop(loss_fn, copt, sopt, params_like=params,
                        num_rounds=T, rounds_per_call=R, flat="xla",
                        gather=arena_gather)
    jloop = jax.jit(loop, donate_argnums=0)

    def run_fused(fed, rounds_n):
        arena = jax.tree.map(jnp.asarray, fed.arena())
        fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        for t in range(0, rounds_n, R):
            idx, _, _ = fed.sample_block(part, K, B, round0=t,
                                         rounds=min(R, rounds_n - t))
            fst, met = jloop(fst, jnp.asarray(idx), arena=arena)
            jax.tree.map(np.asarray, met)   # R stacked rows, one fetch
        jax.block_until_ready(fst.P)
        return unflatten_fl_state(fst, loop.layout)

    run_fused(build(), R)                   # compile warmup
    fed = build()
    t0 = time.time()
    st2 = run_fused(fed, T)
    us_fused = (time.time() - t0) / T * 1e6

    import numpy as _np
    err = max(float(_np.max(_np.abs(_np.asarray(a, _np.float32)
                                    - _np.asarray(b, _np.float32))))
              for a, b in zip(jax.tree_util.tree_leaves(st.params),
                              jax.tree_util.tree_leaves(st2.params)))
    emit("rounds_fused/host_loop", us_host, 0.0)
    emit(f"rounds_fused/fused_r{R}", us_fused, err)
    emit("rounds_fused/speedup", us_fused, us_host / us_fused)


def fleet(rounds=None):
    """Fleet-scale suite (repro.core.fed_loop.make_fleet_loop +
    repro.federation.arena): the fleet loop at C_registered in {100,
    1000} (quick; the full run adds {10^4, 10^5}) with a FIXED cohort of
    C=16 — us/round must stay flat in C_registered because only the
    sampled cohort is ever materialized. Each size is compiled first and
    checked against the memory ceiling
    (repro.sharding.hlo.assert_cohort_only_materialization: no tensor
    wider than O(C_registered) scalars along the registered dim), so a
    row appearing at all means the ceiling held (derived = 0). The
    scheduler row times ONE fused Gumbel-top-k cohort draw over 10^5
    zipf candidates (derived = 0 when the draw is C distinct in-range
    ids)."""
    quick = rounds is not None and rounds <= 25
    import jax
    import jax.numpy as jnp
    from repro.core import (flatten_fl_state, get_client_opt,
                            get_server_opt, init_fl_state, make_fleet_loop,
                            make_loss)
    from repro.federation import arena_init
    from repro.federation.schedulers import make_scheduler
    from repro.sharding.hlo import assert_cohort_only_materialization

    rng = np.random.default_rng(0)
    D, C, K, B, R = 512, 16, 2, 4, 4

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    loss = make_loss(quad)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
    data = {"A": jnp.asarray(rng.normal(size=(R, C, K, B, D)),
                             jnp.float32),
            "b": jnp.asarray(rng.normal(size=(R, C, K, B)), jnp.float32)}
    for M in (100, 1000) if quick else (100, 1000, 10_000, 100_000):
        loop = make_fleet_loop(loss, copt, sopt, params_like=params,
                               num_rounds=4 * R, num_registered=M,
                               rounds_per_call=R, seed=7)
        car = arena_init(M, eta0=loop.eta0)
        fst = flatten_fl_state(init_fl_state(params, sopt), loop.layout)
        jloop = jax.jit(loop)
        compiled = jloop.lower((fst, car), data).compile()
        assert_cohort_only_materialization(compiled, M)
        out = jloop((fst, car), data)        # warm from the same exec
        jax.block_until_ready(out[0][0].P)
        t0 = time.time()
        for _ in range(3):
            out = jloop((fst, car), data)
        jax.block_until_ready(out[0][0].P)
        emit(f"fleet/loop_c{M}", (time.time() - t0) / (3 * R) * 1e6, 0.0)

    # scheduler scaling: one fused Gumbel-top-k draw over 10^5 heavy-
    # tailed candidates — no O(C_registered * N) host materialization
    M = 100_000
    sch = make_scheduler("zipf", num_clients=M, cohort=C)
    key = jax.random.key(0)
    samp = jax.jit(lambda t: sch.sample(key, t))
    us, ids = _timeit(samp, jnp.int32(0))
    ids = np.asarray(ids)
    ok = (len(np.unique(ids)) == C and ids.min() >= 0 and ids.max() < M)
    emit("fleet/sched_zipf_topk_100k", us, 0.0 if ok else 1.0)


def telemetry(rounds=None):
    """Telemetry plane suite (repro.telemetry + kernels/telemetry):
    kernel-vs-jnp-reference parity for the distribution kernels
    (derived = max |Δ|, exact 0 for integer histogram counts) and the
    non-perturbing cost contract — the same flat round timed with the
    telemetry plane off vs on. baseline.json normalizes
    telemetry/round_on by round_off with a soft ceiling, so a
    distribution reduction sneaking onto the step path (rather than
    riding the round-end values) shows up as an overhead regression."""
    del rounds
    import jax
    import jax.numpy as jnp
    from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                            make_fl_round, make_loss)
    from repro.kernels.telemetry import (lane_histogram, lane_histogram_ref,
                                         lane_quantiles, lane_quantiles_ref)
    from repro.telemetry import TelemetrySpec

    rng = np.random.default_rng(0)
    spec = TelemetrySpec(enabled=True)
    edges = jnp.asarray(spec.eta_edges())
    x = jnp.asarray(10.0 ** rng.uniform(-5.0, 2.0, size=256), jnp.float32)
    us, h = _timeit(jax.jit(lambda v: lane_histogram(v, edges)), x)
    emit("telemetry/lane_histogram_256", us,
         float(jnp.abs(h - lane_histogram_ref(x, edges)).max()))
    us, q = _timeit(jax.jit(lambda v: lane_quantiles(v)), x)
    emit("telemetry/lane_quantiles_256", us,
         float(jnp.abs(q - lane_quantiles_ref(x)).max()))

    # overhead contract: one jitted flat round, off vs on. D is large
    # enough that the grad evals dominate — the telemetry reductions
    # run over (C,) round-end values, so their cost must NOT scale
    # with the model and the ratio row stays near 1.0
    D, C, K, B, T = 8192, 64, 2, 8, 8

    def quad(params, batch):
        r = batch["A"] @ params["x"] - batch["b"]
        return 0.5 * jnp.mean(r * r), {}

    loss = make_loss(quad)
    copt = get_client_opt("delta_sgd")
    sopt = get_server_opt("fedavg")
    params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
    data = {"A": jnp.asarray(rng.normal(size=(C, K, B, D)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(C, K, B)), jnp.float32)}
    times = {}
    for tag, tele in (("round_off", False), ("round_on", True)):
        rnd = jax.jit(make_fl_round(loss, copt, sopt, num_rounds=T,
                                    flat="xla", telemetry=tele))
        st = init_fl_state(params, sopt)
        st, met, _ = rnd(st, data)              # compile warmup
        jax.block_until_ready(st.params["x"])
        t0 = time.time()
        for _ in range(T):
            st, met, _ = rnd(st, data)
        jax.block_until_ready(st.params["x"])
        times[tag] = (time.time() - t0) / T * 1e6
        # derived: distribution keys present exactly when enabled
        want = {"eta_hist", "loss_deciles"} <= set(met)
        emit(f"telemetry/{tag}", times[tag],
             0.0 if want == tele else 1.0)
    emit("telemetry/overhead_ratio", times["round_on"],
         times["round_on"] / times["round_off"])


def serving(rounds=None):
    """Serving-plane suite (repro.serving): the fused scan decode vs
    the legacy per-token host loop (derived on the fused row = token
    mismatches vs the host loop — must be 0), the load generator's
    throughput / latency percentiles / occupancy under a closed loop,
    and the checkpoint hot-swap stall (save two rounds into a tempdir,
    start serving round 1, publish round 2 mid-run: derived = swaps
    observed, must be 1; us = notice-to-serving stall)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import (DecodeEngine, ModelRegistry, Workload,
                               greedy_decode, run_load)

    quick = rounds is not None and rounds <= 25
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    B, S, G = 4, 32, 16 if quick else 32
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                (B, S)), jnp.int32)}
    cache_len = S + G
    prefill = jax.jit(lambda p, b: model.prefill(p, b,
                                                 cache_len=cache_len))
    logits, cache0 = prefill(params, batch)
    tok0 = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)

    # legacy host loop: one dispatch + one implicit sync per token
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t))

    def host_loop():
        c, t, out = cache0, tok0, [tok0]
        for _ in range(G - 1):
            lg, c = step(params, c, t)
            t = jnp.argmax(lg, -1).astype(jnp.int32)
            out.append(t)
        return jnp.concatenate(out, 1)

    us_host, ref = _timeit(host_loop, n=3)
    us_host /= G                          # per decoded token
    emit("serving/decode_host_loop", us_host, 0.0)

    fused = jax.jit(lambda p, c, t: greedy_decode(model, p, c, t, G - 1))
    us_fused, (toks, _, _) = _timeit(fused, params, cache0, tok0, n=3)
    us_fused /= G
    got = np.concatenate([np.asarray(tok0), np.asarray(toks)], axis=1)
    mismatch = int((np.asarray(ref) != got).sum())
    emit("serving/decode_fused", us_fused, float(mismatch))
    emit("serving/decode_fused_speedup", us_fused,
         us_host / max(us_fused, 1e-9))

    # load generator: closed loop at the pool's concurrency
    eng = DecodeEngine(model, params, slots=B, cache_len=cache_len,
                       flush_tokens=8)
    wl = Workload(num_requests=8 if quick else 16, arrival="closed",
                  concurrency=B, prompt_lens=(S // 2, S),
                  gen_lens=(G // 2, G), seed=0)
    rep = run_load(eng, wl, cfg.vocab_size)
    emit("serving/loadgen_tok_per_s", rep["wall_s"] * 1e6,
         rep["tok_per_s"])
    emit("serving/latency_p50", rep["p50_s"] * 1e6, 0.0)
    emit("serving/latency_p99", rep["p99_s"] * 1e6, 0.0)
    emit("serving/occupancy", rep["wall_s"] * 1e6, rep["occupancy"])

    # hot-swap stall: publish a newer round under live traffic
    tmp = tempfile.mkdtemp(prefix="bench_serving_ckpt_")
    try:
        from repro.checkpoint import save
        save(tmp, model.init(jax.random.key(1)), step=1)
        reg = ModelRegistry(tmp, params)
        eng = DecodeEngine(model, params, slots=B, cache_len=cache_len,
                           flush_tokens=4, registry=reg)
        for i in range(B):
            eng.submit(rng.integers(0, cfg.vocab_size, (S,))
                       .astype(np.int32), G)
        eng.step()
        save(tmp, model.init(jax.random.key(2)), step=2)
        eng.run_until_idle()
        m = eng.metrics()
        # swaps counts only the MID-RUN publish (round 1 was the
        # engine's initial version, staged before traffic)
        emit("serving/swap_stall", m["serve_swap_stall_max"] * 1e6,
             float(m["serve_swaps_total"]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


ALL = {"table1": table1, "table2b": table2b, "table3": table3,
       "table4": table4, "fig4": fig4, "fig5": fig5,
       # convex keeps its own T=40 protocol; kernels/sharded/scenarios/
       # compression ignore rounds
       "convex": lambda rounds: convex(),
       "kernels": kernels,
       "sharded": sharded,
       "scenarios": scenarios,
       "compression": compression,
       "faults": faults,
       "rounds_fused": rounds_fused,
       "fleet": fleet,
       "telemetry": telemetry,
       "serving": serving}


def _write_csv(path: str = "bench_results.csv") -> None:
    """Atomic write: never leave a truncated csv behind."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write("name,us_per_call,derived\n")
        if ROWS:
            f.write("\n".join(ROWS) + "\n")
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated exact suite names: "
                         + ",".join(ALL))
    args = ap.parse_args()
    rounds = args.rounds or (25 if args.quick else 60)
    only = args.only.split(",") if args.only else None
    if only:
        unknown = [n for n in only if n not in ALL]
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; choose from "
                     f"{list(ALL)}")
    print("name,us_per_call,derived")
    for name, fn in ALL.items():
        if only is not None and name not in only:
            continue
        fn(rounds)
    _write_csv()


if __name__ == "__main__":
    main()
