import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape) program on
the production mesh, with 512 placeholder host devices standing in for the
TPU chips. Proves the sharding config is coherent end-to-end and emits the
memory/cost/collective numbers the roofline analysis (§Roofline) reads.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import roofline
from repro.configs import ARCH_IDS, FLConfig, INPUT_SHAPES, get_config
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import (decode_specs, decode_window, federation_kind,
                                prefill_specs, train_specs)
from repro.launch.steps import (abstract_fl_state, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models.model import build_model
from repro.sharding.spec import (LogicalRules, batch_shardings,
                                 cache_shardings, get_federation_spec,
                                 make_param_shardings,
                                 serve_batch_shardings)
from jax.sharding import NamedSharding, PartitionSpec as P


def _state_shardings(mesh, spec, state_struct, param_sh):
    """FLState shardings: params per rules; adaptive server-state slots
    (m/v, param-shaped) reuse the param shardings; scalars replicated.
    The async scenario delta buffer (param-shaped) also reuses the param
    shardings; the EF21 error-feedback tree ((C,)+param-shaped) shards
    its leading cohort axis over the client mesh axes."""
    from repro.core.fed_round import FLState

    pstruct = jax.tree_util.tree_structure(state_struct.params)

    def srv_group(sub):
        if jax.tree_util.tree_structure(sub) == pstruct:
            return param_sh
        return jax.tree.map(
            lambda l: NamedSharding(mesh, P(*((None,) * l.ndim))), sub)

    ss = state_struct.server_state
    if isinstance(ss, dict):
        srv_sh = {k: srv_group(v) for k, v in ss.items()}
    else:
        srv_sh = jax.tree.map(
            lambda l: NamedSharding(mesh, P(*((None,) * l.ndim))), ss)
    buf_sh = None
    if state_struct.buffer is not None:
        from repro.federation.buffer import AsyncBufferState
        rep = NamedSharding(mesh, P())
        buf_sh = AsyncBufferState(delta=param_sh, weight=rep, count=rep,
                                  stale_sum=rep, stale_max=rep)
    ef_sh = None
    if getattr(state_struct, "ef", None) is not None:
        ca, _ = spec.flat_axes(mesh)
        ca = ca if len(ca) > 1 else (ca[0] if ca else None)
        ef_sh = jax.tree.map(
            lambda l: NamedSharding(mesh, P(*((ca,) + (None,) * (l.ndim - 1)))),
            state_struct.ef)
    return FLState(params=param_sh, server_state=srv_sh,
                   round=NamedSharding(mesh, P()), buffer=buf_sh,
                   ef=ef_sh)


def _shard_bytes(struct, shardings):
    """Exact per-device bytes of a pytree under its NamedShardings."""
    import numpy as np
    total = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(struct),
                        jax.tree_util.tree_leaves(
                            shardings, is_leaf=lambda x: isinstance(
                                x, jax.sharding.NamedSharding))):
        shp = sh.shard_shape(tuple(leaf.shape))
        total += int(np.prod(shp)) * leaf.dtype.itemsize
    return total


def analytic_memory(cfg, shape, spec, mesh, pstruct, param_sh, fl,
                    cache_struct=None, cache_sh=None):
    """Remat-aware per-device HBM estimate (bytes). The measured CPU-backend
    temp is a NO-REMAT upper bound (XLA CPU CSE eliminates jax.checkpoint —
    verified empirically); this is the capacity-planning number for TPU,
    where per-block remat holds: live set = params/opt + per-layer residual
    saves + ONE block's internals + logits."""
    import numpy as np
    tp = mesh.shape.get(spec.tp_axes[0], 1) if spec.tp_axes else 1
    fsdp = int(np.prod([mesh.shape[a] for a in spec.fsdp_axes])) or 1
    pdev = _shard_bytes(pstruct, param_sh)
    D, L = cfg.d_model, cfg.num_layers
    Vt = cfg.padded_vocab // tp if cfg.padded_vocab % tp == 0 \
        else cfg.padded_vocab
    out = {"params_dev": pdev}
    if shape.kind == "train":
        C = spec.clients_on(mesh)
        b = max(1, shape.global_batch // C)
        tok = b * shape.seq_len // fsdp          # per device, per client slot
        resid = L * tok * D * 2
        att = 3 * (shape.seq_len // 8) * shape.seq_len \
            * max(1, cfg.num_heads // tp) * 4 * b // fsdp
        blk = att
        if cfg.num_experts:
            # dropless dispatch: every token-expert pair has a row
            pairs = tok * cfg.num_experts_per_tok
            blk = max(blk, 3 * pairs * max(D, cfg.expert_d_ff) * 2)
        logits = 2 * tok * Vt * 4
        # global params + per-client local copy + grads + prev-grads(Δ-SGD)
        opt_copies = 4 if fl.client_opt == "delta_sgd" else 3
        out.update(residuals=resid, block_peak=blk, logits=logits,
                   total=pdev * opt_copies + resid + blk + logits)
    elif shape.kind == "prefill":
        data = int(np.prod([mesh.shape[a] for a in mesh.shape
                            if a != (spec.tp_axes[0] if spec.tp_axes
                                     else "")])) or 1
        bloc = max(1, shape.global_batch // data)
        cache = _shard_bytes(cache_struct, cache_sh) if cache_struct else \
            L * bloc * shape.seq_len * cfg.num_kv_heads * cfg.head_dim * 2 * 2
        att = 3 * (shape.seq_len // 8) * shape.seq_len \
            * max(1, cfg.num_heads // tp) * 4 * bloc
        out.update(cache=cache, block_peak=att,
                   total=pdev + cache + att + bloc * Vt * 4)
    else:
        cache = _shard_bytes(cache_struct, cache_sh) if cache_struct else 0
        out.update(cache=cache, total=pdev + cache + shape.global_batch
                   * Vt * 4)
    return out


def _compile_step(cfg, shape, mesh, spec, fl, *, unroll, remat,
                  use_pallas=False, seq_shard=False, quant_kv=False,
                  softmax_bf16=False, cache_seq_shard=False,
                  flat_fed=None, flat_sharded=False, scenario=None,
                  compression=None, clients=None, rounds_per_call=1):
    """Lower + compile one program variant. Returns (compiled, t_lower,
    t_compile, analytic). ``flat_sharded`` (flat_fed only) threads the
    mesh + FederationSpec into the round so the packed (C, N) buffer
    stays sharded per ``spec.flat_spec(mesh)``. ``scenario`` (preset
    name or Scenario) adds heterogeneous-K lane masks / async buffered
    aggregation to the round. ``compression`` (kind name or
    CompressionSpec) compresses the client deltas on the flat engine
    (repro.compression). ``clients`` overrides the cohort size C
    (default ``spec.clients_on(mesh)`` — one client per client-axis
    coordinate); a multiple of it stacks several clients per shard,
    which the compressed-boundary HLO assertion needs to tell a leaked
    delta slab from the aggregated mean. ``rounds_per_call`` > 1 (train
    shapes, flat_fed only) lowers the round-fused R-round ``lax.scan``
    loop (repro.core.fed_loop) instead of the single round — batches
    gain a leading R axis, the carried state is donated."""
    import repro.models.attention as _att
    from repro.models.common import logical_rules, unroll_scans
    _att.SOFTMAX_BF16 = softmax_bf16
    model = build_model(cfg, jnp.bfloat16)
    rules = LogicalRules(spec, mesh, serve=shape.kind != "train",
                         seq_shard=seq_shard)
    analytic = None
    t0 = time.time()
    with mesh, unroll_scans(unroll), logical_rules(rules):
        if shape.kind == "train" and rounds_per_call > 1:
            if not (flat_fed and flat_sharded):
                raise ValueError("rounds_per_call > 1 on a mesh requires "
                                 "the sharded flat engine (flat_fed=True, "
                                 "flat_sharded=True): the mesh-form loop "
                                 "carries the flat state whose "
                                 "shardings this driver derives")
            from repro.launch.steps import make_train_loop
            loop, sopt, scn, comp = make_train_loop(
                model, fl, rounds_per_call=rounds_per_call,
                use_pallas=use_pallas, remat=remat,
                mesh=mesh if flat_sharded else None,
                federation=spec if flat_sharded else None,
                scenario=scenario, compression=compression)
            C = clients or spec.clients_on(mesh)
            # the fused loop carries the FlatFLState: the (N,) params
            # and the (C, N) EF21 slab shard like the round buffer, the
            # rest keeps the single-round state shardings; batches just
            # gain the leading R axis
            from repro.core import flatten_fl_state
            state_struct = abstract_fl_state(model, sopt, scn, comp, C)
            R = rounds_per_call
            round_batch = train_specs(model, shape, fl, C)
            batch = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype),
                round_batch)
            param_sh = make_param_shardings(spec, mesh, state_struct.params)
            state_sh = _state_shardings(mesh, spec, state_struct, param_sh)
            batch_sh = jax.tree.map(
                lambda sh: NamedSharding(mesh, P(None, *sh.spec)),
                batch_shardings(spec, mesh, round_batch),
                is_leaf=lambda x: isinstance(x, NamedSharding))
            analytic = analytic_memory(cfg, shape, spec, mesh,
                                       state_struct.params, param_sh, fl)
            flat_struct = jax.eval_shape(
                lambda s: flatten_fl_state(s, loop.layout), state_struct)
            pspec = spec.flat_spec(mesh)
            flat_sh = flat_struct._replace(
                P=NamedSharding(mesh, P(pspec[1])),
                server_state=state_sh.server_state, round=state_sh.round,
                buffer=state_sh.buffer,
                ef=(NamedSharding(mesh, pspec)
                    if flat_struct.ef is not None else None))
            lowered = jax.jit(loop, in_shardings=(flat_sh, batch_sh),
                              donate_argnums=0
                              ).lower(flat_struct, batch)
        elif shape.kind == "train":
            step, sopt, scn, comp = make_train_step(
                model, fl, use_pallas=use_pallas, remat=remat, flat=flat_fed,
                mesh=mesh if (flat_fed and flat_sharded) else None,
                federation=spec if (flat_fed and flat_sharded) else None,
                scenario=scenario, compression=compression)
            C = clients or spec.clients_on(mesh)
            state_struct = abstract_fl_state(model, sopt, scn, comp, C)
            batch = train_specs(model, shape, fl, C)
            param_sh = make_param_shardings(spec, mesh, state_struct.params)
            state_sh = _state_shardings(mesh, spec, state_struct, param_sh)
            batch_sh = batch_shardings(spec, mesh, batch)
            analytic = analytic_memory(cfg, shape, spec, mesh,
                                       state_struct.params, param_sh, fl)
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)
                              ).lower(state_struct, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model, use_pallas=use_pallas)
            pstruct = jax.eval_shape(model.init, jax.random.key(0))
            batch = prefill_specs(model, shape)
            param_sh = make_param_shardings(spec, mesh, pstruct)
            batch_sh = serve_batch_shardings(mesh, batch)
            analytic = analytic_memory(cfg, shape, spec, mesh, pstruct,
                                       param_sh, fl)
            lowered = jax.jit(step, in_shardings=(param_sh, batch_sh)
                              ).lower(pstruct, batch)
        else:  # decode
            window = decode_window(cfg, shape)
            step = make_serve_step(model, window=window)
            pstruct = jax.eval_shape(model.init, jax.random.key(0))
            cache, tokens = decode_specs(model, shape, window,
                                         quant_kv=quant_kv)
            param_sh = make_param_shardings(spec, mesh, pstruct)
            cache_sh = cache_shardings(spec, mesh, cache,
                                       batch_size=shape.global_batch,
                                       seq_shard=cache_seq_shard)
            tok_sh = serve_batch_shardings(mesh, {"t": tokens})["t"]
            analytic = analytic_memory(cfg, shape, spec, mesh, pstruct,
                                       param_sh, fl, cache, cache_sh)
            lowered = jax.jit(step, in_shardings=(param_sh, cache_sh, tok_sh)
                              ).lower(pstruct, cache, tokens)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    _att.SOFTMAX_BF16 = False
    return compiled, t_lower, t_compile, analytic


def _calib_depths(cfg):
    """Two reduced depths (whole pattern cycles) for roofline calibration."""
    cyc = len(cfg.block_pattern)
    return cyc, 2 * cyc


def _at_depth(cfg, L):
    import dataclasses
    return dataclasses.replace(cfg, name=f"{cfg.name}@{L}", num_layers=L)


def lower_one(arch: str, shape_id: str, multi_pod: bool, *,
              fl: FLConfig = None, local_steps: int = 2,
              use_pallas: bool = False, remat: bool = True,
              fed_kind: str = None, verbose: bool = True,
              calibrate: bool = True):
    """One (arch, shape, mesh) dry-run:

    Pass A — FULL config, rolled scans: proves lower+compile coherence on
    the production mesh and yields memory_analysis (CPU backend = no-remat
    upper bound; see analytic_memory).

    Pass B (single-pod only) — the same program at two reduced depths with
    ALL structural scans unrolled, because XLA cost_analysis counts a
    while-loop body once regardless of trip count (verified). FLOPs/bytes/
    collective-bytes are exactly affine in depth, so two points give the
    per-layer slope and the full-depth roofline: m(L) = m1 + (L-L1)·slope.
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_id]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    fed_kind = fed_kind or federation_kind(cfg)
    spec = get_federation_spec(fed_kind, mesh)
    fl = fl or FLConfig(local_steps=local_steps)

    # ---- Pass A: full config, rolled ----
    compiled, t_lower, t_compile, analytic = _compile_step(
        cfg, shape, mesh, spec, fl, unroll=False, remat=remat,
        use_pallas=use_pallas)
    mem = roofline.memory_analysis_summary(compiled)

    # ---- Pass B: two-depth unrolled calibration (single-pod roofline) ----
    rl_summary = None
    calib = None
    if calibrate and not multi_pod:
        L1, L2 = _calib_depths(cfg)
        rls = []
        for L in (L1, L2):
            cL, *_ = _compile_step(_at_depth(cfg, L), shape, mesh, spec, fl,
                                   unroll=True, remat=remat,
                                   use_pallas=use_pallas)
            rls.append(roofline.analyze(cL, chips))
        rl = roofline.extrapolate(rls[0], rls[1], L1, L2, cfg.num_layers)
        rl_summary = rl.summary()
        calib = {"depths": [L1, L2],
                 "flops_at_depths": [rls[0].flops, rls[1].flops]}
    else:
        rl = roofline.analyze(compiled, chips)
        rl_summary = rl.summary()
        rl_summary["note"] = ("rolled-scan numbers (loop bodies counted "
                              "once); use the single-pod calibrated "
                              "roofline for this pair")

    tokens_per_step = (shape.global_batch * shape.seq_len * fl.local_steps
                       if shape.kind == "train" else
                       shape.global_batch * (shape.seq_len
                                             if shape.kind == "prefill"
                                             else 1))
    mf = roofline.model_flops(cfg, tokens_per_step)
    if shape.kind != "train":
        mf /= 3.0  # fwd only: 2·N·D
    total_hlo_flops = rl.flops * chips
    result = {
        "arch": arch, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "federation": fed_kind, "clients": spec.clients_on(mesh),
        "step_kind": shape.kind,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "analytic_memory": analytic,
        "roofline": rl_summary,
        "calibration": calib,
        "model_flops": mf,
        "hlo_flops_total": total_hlo_flops,
        "useful_flops_ratio": mf / total_hlo_flops if total_hlo_flops else 0,
    }
    if verbose:
        print(json.dumps(result, indent=2, default=float))
        print(f"memory_analysis: {mem}")
    return result


def scenario_smoke(verbose: bool = True):
    """CI scenario leg: compile the flat_fed_hetero / flat_fed_async /
    flat_fed_compressed rounds — plus the round-fused R-round scan
    (flat_fed_rounds_fused, repro.core.fed_loop) and the chaos variant
    flat_fed_faults (repro.federation.faults: dropouts + NaN + byzantine
    under trimmed aggregation and quorum) — of a reduced config on an
    8-virtual-device (4, 2) host mesh and assert the packed (C, N)
    buffer stays sharded under every variant; the compressed variants
    additionally assert no full-precision client delta crosses the
    client shard boundary, with the TIGHTENED ``2*n_loc`` payload bound
    on the robust round (the production-mesh versions run via
    ``launch/perf.py --variants flat_fed_hetero,flat_fed_async,
    flat_fed_compressed,flat_fed_rounds_fused``)."""
    from repro.configs.base import ShapeConfig
    from repro.core import flat as flatlib
    from repro.models.model import build_model
    from repro.sharding.hlo import (assert_flat_buffer_sharded,
                                    assert_no_fullprec_delta_collective)
    from repro.sharding.spec import cross_device

    cfg = get_config("tinyllama-1.1b").reduced(num_layers=2, d_model=256)
    shape = ShapeConfig("train_smoke", "train", 256, 8)
    mesh = make_mesh((4, 2), ("data", "model"))
    spec = cross_device(mesh)
    fl = FLConfig(local_steps=2, flat_engine=True)
    model = build_model(cfg, jnp.bfloat16)
    pstruct = jax.eval_shape(model.init, jax.random.key(0))
    layout = flatlib.layout_of(pstruct, shards=spec.flat_shards(mesh))
    from repro.compression import CompressionSpec
    from repro.federation import get_scenario
    # chaos variant: mid-round dropouts + NaN corruption + byzantine
    # scaling defended by trimmed aggregation under quorum Q=2, stacked
    # on int8+EF compression (repro.federation.faults)
    faults_scn = get_scenario("dirichlet_dropouts", robust_agg="trimmed",
                              quorum=2, byzantine_rate=0.1)
    n_loc = layout.padded_size // spec.flat_shards(mesh)
    for variant, scn, comp, rpc, cmul in (
            ("flat_fed_hetero", "dirichlet_stragglers", None, 1, 1),
            ("flat_fed_async", "zipf_async", None, 1, 1),
            # error_feedback=True allocates FLState.ef, so the compiled
            # program (and both HLO assertions) covers the EF sharding
            ("flat_fed_compressed", "bandwidth_tiered",
             CompressionSpec(kind="int8", error_feedback=True), 1, 2),
            # round-fused loop (repro.core.fed_loop): the sharded-buffer
            # assertion must hold on the SCANNED computation too
            ("flat_fed_rounds_fused", "dirichlet_stragglers", None, 4, 1),
            # chaos smoke: 4 clients per client shard, so the TIGHTENED
            # 2*n_loc robust-round bound sits strictly below the default
            # (C_loc, N_loc) slab bound and actually bites
            ("flat_fed_faults", faults_scn,
             CompressionSpec(kind="int8", error_feedback=True), 1, 4)):
        # the compressed variants stack >= 2 clients per client shard so
        # the boundary assertion can tell a leaked full-precision delta
        # slab (C_loc, N_loc) from the legitimate (N_loc,) client mean
        C = spec.clients_on(mesh) * cmul
        t0 = time.time()
        compiled, *_ = _compile_step(cfg, shape, mesh, spec, fl,
                                     unroll=False, remat=False,
                                     flat_fed=True, flat_sharded=True,
                                     scenario=scn, compression=comp,
                                     clients=C, rounds_per_call=rpc)
        rep = assert_flat_buffer_sharded(compiled, C, layout.padded_size)
        extra = ""
        if comp is not None:
            kw = ({"max_payload_elems": 2 * n_loc}
                  if variant == "flat_fed_faults" else {})
            brep = assert_no_fullprec_delta_collective(
                compiled, C, layout.padded_size, mesh=mesh,
                federation=spec, **kw)
            extra = (f", no full-precision delta over the client "
                     f"boundary ({brep['collectives']} collectives "
                     f"checked)")
        if verbose:
            sname = scn if isinstance(scn, str) else scn.name
            print(f"[scenario-smoke] {variant} ({sname}): compiled in "
                  f"{time.time() - t0:.1f}s, ({C}, {layout.padded_size}) "
                  f"flat buffer stays sharded "
                  f"(gather/copy={rep['gather_or_copy']}){extra}",
                  flush=True)
    print("scenario smoke passed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--remat", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="per-local-step activation checkpointing (default)")
    ap.add_argument("--fed-kind", default=None)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--scenario-smoke", action="store_true",
                    help="compile flat_fed_hetero + flat_fed_async + "
                         "flat_fed_compressed + flat_fed_rounds_fused + "
                         "flat_fed_faults on an 8-virtual-device mesh and "
                         "check the sharded-buffer + compressed-boundary "
                         "HLO assertions (CI scenario leg)")
    args = ap.parse_args()

    if args.scenario_smoke:
        scenario_smoke()
        return

    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_id in shapes:
            for multi in meshes:
                tag = f"{arch}_{shape_id}_{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (exists)")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    res = lower_one(arch, shape_id, multi,
                                    local_steps=args.local_steps,
                                    remat=args.remat,
                                    fed_kind=args.fed_kind, verbose=False)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=2, default=float)
                    rl = res["roofline"]
                    print(f"  ok: bottleneck={rl['bottleneck']} "
                          f"t_comp={rl['t_compute_s']:.3e} "
                          f"t_mem={rl['t_memory_s']:.3e} "
                          f"t_coll={rl['t_collective_s']:.3e} "
                          f"compile={res['compile_s']}s", flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"  FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
