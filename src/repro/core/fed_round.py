"""One jitted federated round (Algorithm 1, full loop body).

Communication pattern, expressed jax-natively:
  * the |S_t| participating clients form a leading pytree axis C, sharded
    over the mesh's client axes (FederationSpec);
  * each client runs K local steps (lax.scan) of its ClientOpt from the
    common round-start params (vmap over C — params broadcast);
  * server aggregation is a (weighted) mean over C — XLA lowers it to an
    all-reduce over the client mesh axes, i.e. the FedAvg collective;
  * the ServerOpt (FedAvg/FedAdam/...) finishes the round.

Batch layout: every leaf of ``client_batches`` is (C, K, ...) — K per-step
micro-batches of the client's *local* data.

Flat engine (``flat=`` argument, Δ-SGD only): instead of vmapping the
optimizer over C, the param pytree is packed ONCE at round start into a
lane-aligned flat buffer broadcast to a (C, N/128, 128) client slab
(repro.core.flat), the K-step scan runs entirely on slabs in the kernel
pair's own form — per step: one vmapped grad eval on the unpacked view,
then exactly two fused kernel launches
(batched norms + batched apply) for all leaves and all clients —
aggregation is a single mean over the packed C axis, and the result is
unpacked once at round end. ``flat="pallas"``/``True`` uses the batched
Pallas kernels, ``flat="xla"`` the same math as fused jnp ops (for
meshed/pjit callers).

Sharded flat engine (``mesh=`` + ``federation=`` arguments): the
(C, N/128, 128) client slab is mesh-sharded end to end like
``FederationSpec.flat_spec(mesh)`` — clients over the client axes, the
N/128 rows over the fsdp/tp axes and the lanes whole, with a per-shard
padded layout (``layout_of(..., shards=...)``) so every device's slab stays
lane-aligned. Pack/unpack run under ``with_sharding_constraint``, the
per-step kernel pair runs inside ``shard_map`` with a psum dual-norm
reduction (repro.core.delta_sgd.flat_delta_sgd_step_sharded), and the
round-end aggregation is a sharded mean over the client axes. The caller
must jit the returned round_fn (sharding constraints require a jit
context).

Scenario engine (``scenario=`` argument, repro.federation): a
``Scenario`` adds the heterogeneity the paper motivates Δ-SGD with —
  * compute heterogeneity: per-client step counts K_c ≤ K_max drawn each
    round (SpeedModel), lowered as per-step lane masks. The flat engine
    folds them into the fused kernel pair as η=0 lanes (scan stays
    fixed-shape, stragglers' dead lanes cost no extra launches); the
    vmap engine applies the same masking per leaf for parity.
  * async buffered aggregation (FedBuff-style, flat engine only): client
    deltas enter a staleness-weighted server buffer
    (repro.federation.buffer) and the ServerOpt only steps when M
    updates have accumulated. The buffer rides in ``FLState.buffer``.
  * cohort reporting: when ``num_clients`` is given the round reports
    the scheduler's cohort ids (the SAME draw the data pipeline used to
    gather the batches) plus staleness / effective-K metrics.
All scenario randomness flows from ``fold_in(key(scenario.seed),
state.round)``, so rounds are reproducible and host/device draws agree.

Delta compression (``compression=`` argument, repro.compression, flat
engine only): each client's round delta Δ_c = x_c^K − x_t is compressed
on the packed (C, N) buffer before ANY aggregation — int8 per-chunk
quantization or magnitude top-k, optionally behind EF21 error feedback
(state in ``FLState.ef``), with per-client bandwidth levels drawn by a
bandwidth-heterogeneous scenario. The sync tail averages
x_t + Δ̂_c, the async tail buffers the staleness-weighted Δ̂ sum, so
compression composes with every ServerOpt and with FedBuff. Under
meshes the compressors are chunk-local and run inside ``shard_map``
strictly before the client-mean psum: no full-precision per-client
delta ever crosses a shard boundary (machine-checked by
``repro.sharding.hlo.assert_no_fullprec_delta_collective``). An inert
spec (kind="none") takes the exact pre-compression code path — bit
exact.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import flat as flatlib
from repro.core.client_opt import ClientOpt
from repro.core.delta_sgd import (DeltaSGDState, flat_delta_sgd_init,
                                  flat_delta_sgd_step,
                                  flat_delta_sgd_step_sharded)
from repro.core.server_opt import ServerOpt


class FLState(NamedTuple):
    params: Any
    server_state: Any
    round: jax.Array
    buffer: Any = None      # AsyncBufferState under async scenarios
    ef: Any = None          # EF21 error-feedback state (compression):
                            # pytree like params with a leading cohort
                            # axis, f32 — each slot's reconstruction g_c


class RoundAux(NamedTuple):
    """Per-client round outputs the flat body hands back NEXT TO the new
    state — what the fleet arena (repro.federation.arena) scatters into
    per-registered-client storage after a round.

    ``P_locals`` (C, N/128, 128): round-end local params (the client
    slab form of the vmap engine's ``new_locals``). ``etas`` (C,):
    round-end Δ-SGD step sizes — the per-client adaptive state that
    persists across the rounds a client sits out when an arena carries
    it. ``valid`` (C,)
    bool: NaN-guard survivors (all True on fault-free rounds)."""
    P_locals: jax.Array
    etas: jax.Array
    valid: jax.Array


def init_fl_state(params, server_opt: ServerOpt, scenario=None,
                  compression=None, cohort: Optional[int] = None) -> FLState:
    """``scenario`` (repro.federation.Scenario): async scenarios allocate
    the server-side delta buffer; sync scenarios and None leave it out.
    ``compression`` (repro.compression.CompressionSpec) with
    ``error_feedback=True`` allocates the per-cohort-slot EF21
    reconstruction tree — ``cohort`` (= C, clients per round) is then
    required to size its leading axis."""
    buf = None
    if scenario is not None and scenario.is_async:
        from repro.federation.buffer import buffer_init
        buf = buffer_init(params)
    ef = None
    if compression is not None and compression.error_feedback:
        if cohort is None:
            raise ValueError("error-feedback compression needs cohort= "
                             "(clients per round) to size FLState.ef")
        ef = jax.tree.map(
            lambda p: jnp.zeros((cohort,) + p.shape, jnp.float32), params)
    return FLState(params, server_opt.init(params),
                   jnp.asarray(0, jnp.int32), buf, ef)


def _round_metrics(losses, etas, step_counts=None):
    """Shared metric block. ``losses`` is (C, K); ``etas`` is (C,) with
    NaN for clients whose optimizer has no scalar step-size state
    (non-Δ-SGD, groupwise). Under heterogeneous K the per-step losses of
    a finished client are evaluated at frozen params, so they are masked
    out of the mean and "last step" means the client's K_c-th step."""
    if step_counts is None:
        loss = jnp.mean(losses)
        last = jnp.mean(losses[:, -1])
    else:
        from repro.federation.heterogeneity import active_mask
        amask = active_mask(step_counts, losses.shape[1])
        loss = jnp.sum(losses * amask) / jnp.sum(amask)
        last = jnp.mean(jnp.take_along_axis(
            losses, (step_counts - 1)[:, None], axis=1)[:, 0])
    return {"loss": loss, "loss_last_step": last,
            "eta_mean": jnp.mean(etas),
            "eta_min": jnp.min(etas),
            "eta_max": jnp.max(etas)}


def _round_counts(counts):
    """The model's counters (``metrics["counts"]`` of its loss, (K, C)
    over the round's local steps and clients) as round totals
    (``models.common.counter_total``)."""
    from repro.models.common import counter_total
    return {k: counter_total(k, v) for k, v in counts.items()}


def _finish_round(state: FLState, agg, losses, etas,
                  server_opt: ServerOpt, *, step_counts=None, extra=None,
                  ef=None):
    """Shared synchronous round tail: server update + metrics. ``ef`` is
    the rolled EF21 state (compression); None keeps the incoming one."""
    params, sstate = server_opt.update(state.params, agg,
                                       state.server_state)
    metrics = _round_metrics(losses, etas, step_counts)
    if extra:
        metrics.update(extra)
    return FLState(params, sstate, state.round + 1, state.buffer,
                   state.ef if ef is None else ef), metrics


def _scenario_extras(scenario, round_idx, C, num_clients, client_sizes,
                     step_counts):
    """Cohort / effective-K metrics reported from inside the jitted round."""
    extra = {}
    if scenario is None:
        return extra
    if num_clients is not None:
        sch = scenario.make_scheduler(num_clients, C, sizes=client_sizes)
        extra["cohort_ids"] = sch.sample(jax.random.key(scenario.seed),
                                         round_idx)
    if step_counts is not None:
        sc = step_counts.astype(jnp.float32)
        extra.update(k_eff_mean=jnp.mean(sc), k_eff_min=jnp.min(sc),
                     k_eff_max=jnp.max(sc))
    return extra


def _halving_mean(x):
    """Mean over axis 0, summed by repeated halving: row i + row i+h.

    The order is fixed by the program. A ``jnp.mean`` leaves it to
    XLA:CPU, whose reduction emitter sums in a different order when the
    int8 compression chain is fused into the reduction than when it is
    not, so the per-round and the round-fused programs would round
    apart."""
    n = x.shape[0]
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        top = x[:h] + x[h:2 * h]
        x = jnp.concatenate([top, x[2 * h:]]) if x.shape[0] % 2 else top
    return x[0] / n


def make_fl_round(loss_fn, client_opt: ClientOpt, server_opt: ServerOpt, *,
                  num_rounds: int, weighted: bool = False,
                  flat=False, mesh=None, federation=None,
                  scenario=None, num_clients: Optional[int] = None,
                  client_sizes=None, compression=None, telemetry=None):
    """loss_fn(params, batch, global_params, prev_params)->(loss, metrics).

    Returns round_fn(state, client_batches, client_weights=None,
                     prev_local_params=None) -> (state, metrics).

    ``flat``: False (vmap engine), True/"pallas", or "xla" — the packed
    flat-buffer Δ-SGD engine (requires client_opt "delta_sgd", global
    rule).

    ``mesh`` + ``federation`` (FederationSpec): flat engine only — keep
    the client slab sharded per ``federation.flat_spec(mesh)`` for the
    whole round (see module docstring). Both or neither.

    ``scenario`` (repro.federation.Scenario): heterogeneous step counts
    (both engines) and async buffered aggregation (flat engine only).
    ``num_clients``/``client_sizes`` let the round also report the
    scheduler's cohort ids (see module docstring).

    ``compression`` (repro.compression.CompressionSpec, or a kind name):
    client->server delta compression on the flat engine — see the
    module docstring. An inert spec (kind="none", no error feedback, no
    bandwidth-heterogeneous scenario) leaves every engine on its exact
    pre-compression code path, so results stay bit-exact.

    ``telemetry`` (None/bool/repro.telemetry.TelemetrySpec): the in-scan
    distribution block — per-round η histogram over client lanes, per-
    client mean-loss deciles, absolute guard hit counts — added to the
    round metrics as fixed-shape device arrays. Strictly read-only over
    round-end values: the trajectory is bit-exact with telemetry on vs
    off (tests/test_telemetry.py).
    """
    from repro.telemetry.spec import resolve_telemetry, round_telemetry
    tele = resolve_telemetry(telemetry)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    if (mesh is None) != (federation is None):
        raise ValueError("mesh and federation must be given together")
    if mesh is not None and not flat:
        raise ValueError("mesh/federation sharding requires the flat "
                         "engine (flat=...)")
    if scenario is not None and scenario.is_async and not flat:
        raise ValueError(
            "async buffered aggregation requires the flat engine "
            "(flat=...): the staleness-weighted delta merge is one "
            "reduction over the packed (C, N) buffer")
    if scenario is not None and not flat and (
            scenario.faulty or scenario.robust or scenario.quorum > 0):
        raise ValueError(
            "fault injection / robust aggregation / quorum degradation "
            "require the flat engine (flat=...): faults are lowered as "
            "per-client lanes on the packed (C, N) buffer and the "
            "RobustAgg ladder runs on it (repro.federation.faults)")
    if compression is not None or (
            scenario is not None and scenario.bandwidth_heterogeneous):
        # a bandwidth-heterogeneous scenario implies compression even if
        # the caller passed none: resolve the inert kind="none" spec
        # (level 0 of the ladder) so the per-client level draws actually
        # happen — same resolution as the launch drivers and benchmarks
        from repro.compression import get_compression
        compression = get_compression(compression)
        if compression.active(scenario) and not flat:
            raise ValueError(
                "delta compression requires the flat engine (flat=...): "
                "the compressors operate on the packed (C, N) buffer")

    if flat:
        return _make_flat_round(grad_fn, client_opt, server_opt,
                                num_rounds=num_rounds, weighted=weighted,
                                backend="xla" if flat == "xla" else "pallas",
                                mesh=mesh, federation=federation,
                                scenario=scenario, num_clients=num_clients,
                                client_sizes=client_sizes,
                                compression=compression, telemetry=tele)

    hetero = scenario is not None and scenario.heterogeneous

    def one_client(global_params, round_frac, batch_c, prev_c, k_c):
        ostate = client_opt.reset(client_opt.init(global_params), round_frac)
        K = jax.tree_util.tree_leaves(batch_c)[0].shape[0]

        def step(carry, inp):
            b, k_idx = inp
            p, os = carry
            (l, _), g = grad_fn(p, b, global_params, prev_c)
            p_new, os_new = client_opt.update(p, g, os, l)
            if k_c is not None:
                # heterogeneous K: past this client's K_c budget the
                # candidate update is discarded — params and optimizer
                # state stay frozen (same semantics as the flat engine's
                # η=0 lane mask).
                act = k_idx < k_c
                p_new = jax.tree.map(
                    lambda a, o: jnp.where(act, a, o), p_new, p)
                os_new = jax.tree.map(
                    lambda a, o: jnp.where(act, a, o), os_new, os)
            return (p_new, os_new), l

        from repro.models.common import scan_unroll
        (p, os), losses = jax.lax.scan(
            step, (global_params, ostate),
            (batch_c, jnp.arange(K, dtype=jnp.int32)),
            unroll=scan_unroll())
        eta = (os.eta if isinstance(os, DeltaSGDState)
               and not isinstance(os.eta, dict)
               else jnp.asarray(jnp.nan, jnp.float32))
        return p, losses, eta

    def round_fn(state: FLState, client_batches, client_weights=None,
                 prev_local_params=None):
        """-> (new_state, metrics, new_local_params (C, ...))."""
        round_frac = state.round.astype(jnp.float32) / num_rounds
        gp = state.params
        C = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
        K = jax.tree_util.tree_leaves(client_batches)[0].shape[1]
        step_counts = (scenario.draw_step_counts(state.round, C, K)
                       if hetero else None)
        new_locals, losses, etas = jax.vmap(
            one_client, in_axes=(None, None, 0,
                                 0 if prev_local_params is not None
                                 else None,
                                 0 if hetero else None)
        )(gp, round_frac, client_batches, prev_local_params, step_counts)

        if weighted and client_weights is not None:
            w = client_weights / jnp.sum(client_weights)
            agg = jax.tree.map(
                lambda x: jnp.tensordot(w.astype(jnp.float32),
                                        x.astype(jnp.float32), axes=(0, 0)
                                        ).astype(x.dtype), new_locals)
        else:
            agg = jax.tree.map(
                lambda x: jnp.mean(x.astype(jnp.float32), axis=0
                                   ).astype(x.dtype), new_locals)

        extra = _scenario_extras(scenario, state.round, C, num_clients,
                                 client_sizes, step_counts)
        if tele.enabled:
            # η may be NaN for non-Δ-SGD optimizers: NaN counts in no
            # histogram bin, so the eta_hist simply reads all-zero there
            extra.update(round_telemetry(tele, etas, losses))
        new_state, metrics = _finish_round(state, agg, losses, etas,
                                           server_opt,
                                           step_counts=step_counts,
                                           extra=extra)
        return new_state, metrics, new_locals

    return round_fn


def _make_flat_round(grad_fn, client_opt: ClientOpt, server_opt: ServerOpt,
                     *, num_rounds: int, weighted: bool, backend: str,
                     mesh=None, federation=None, scenario=None,
                     num_clients=None, client_sizes=None,
                     compression=None, telemetry=None):
    """Flat-parameter Δ-SGD engine: one (C, N/128, 128) client slab
    carries every leaf of every client's params through the K-step scan
    in the kernel pair's own operand form; two fused kernel launches per
    local step total. With ``mesh``/``federation`` the slab additionally
    stays sharded like ``federation.flat_spec(mesh)`` (rows like N) for
    the whole round. Tail consumers that take (C, N) (compression,
    robust aggregation, the async buffer) read it converted once a
    round. With a ``scenario`` the K-step scan carries the per-client
    step-count lane mask, and async scenarios route the
    aggregate through the FedBuff delta buffer instead of the direct
    server update.

    Active ``compression`` (repro.compression) reshapes the round tail
    into the delta-communication form: Δ_c = x_c^K − x_t is compressed
    per client (optionally behind EF21 error feedback carried in
    ``FLState.ef``, and per-client bandwidth levels drawn by the
    scenario), and only the reconstructed Δ̂_c enters the aggregation —
    under meshes the compressors run shard-locally BEFORE the
    client-mean psum, so no full-precision per-client delta ever
    crosses a shard boundary. Wire-bytes / compression-ratio telemetry
    rides in the round metrics.

    The round logic lives in a flat-in/flat-out body working on
    ``repro.core.fed_loop.FlatFLState`` — the returned round_fn is a
    thin pack/unpack wrapper around it and additionally exposes it as
    ``round_fn.flat_body``, which is what the round-fused multi-round
    ``lax.scan`` (core/fed_loop.make_fl_loop) chains: fused and
    host-loop rounds are the same computation by construction."""
    from repro.telemetry.spec import resolve_telemetry, round_telemetry
    tele = resolve_telemetry(telemetry)
    hyper = client_opt.hyper
    if (client_opt.name != "delta_sgd" or hyper is None
            or hyper.get("groupwise")):
        raise ValueError("flat engine requires the global-rule delta_sgd "
                         f"client optimizer, got {client_opt.name!r}")
    gamma, delta = hyper["gamma"], hyper["delta"]
    eta0, theta0 = hyper["eta0"], hyper["theta0"]

    hetero = scenario is not None and scenario.heterogeneous
    is_async = scenario is not None and scenario.is_async
    bw_hetero = scenario is not None and scenario.bandwidth_heterogeneous
    comp = compression if (compression is not None
                           and compression.active(scenario)) else None
    use_ef = comp is not None and comp.error_feedback

    # fault / robustness axis (repro.federation.faults). All trace-time
    # flags: with everything off, every branch below is the exact legacy
    # code path, so the fault-free mean configuration stays bit-exact
    # against the golden trajectories by construction.
    fm = scenario.fault_model if scenario is not None else None
    faults_on = fm is not None and fm.active
    ragg = scenario.robust_model if scenario is not None else None
    robust_on = ragg is not None and ragg.robust
    quorum = scenario.quorum if scenario is not None else 0
    guard_tail = faults_on or robust_on or quorum > 0

    sharded = mesh is not None
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec as PS
        pspec = federation.flat_spec(mesh)          # (C, N) buffers
        sspec = PS(pspec[0], pspec[1], None)        # (C, N/128, 128)
        cspec = federation.flat_client_spec(mesh)   # (C,) vectors
        nspec = PS(pspec[1])                        # (N,) buffers
        mspec = PS(pspec[1], None)                  # (N/128, 128) mask
        shards = federation.flat_shards(mesh)

        def constrain(x, ps):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, ps))
    else:
        shards = 1

        def constrain(x, ps):
            return x

        pspec = sspec = cspec = nspec = mspec = None

    def flat_step(P, G, S, mask, active, eta0_step=None):
        """``eta0_step`` optionally overrides the scalar η₀ with a (C,)
        per-client vector (the fleet arena's Δ-SGD warm-start carry);
        the first-step rule broadcasts either form identically."""
        e0 = eta0 if eta0_step is None else eta0_step
        if sharded:
            return flat_delta_sgd_step_sharded(
                P, G, S, gamma=gamma, delta=delta, eta0=eta0, mesh=mesh,
                pspec=pspec, mask=mask, active=active, backend=backend)
        return flat_delta_sgd_step(P, G, S, gamma=gamma, delta=delta,
                                   eta0=e0, mask=mask, active=active,
                                   backend=backend)

    def flat_body(fstate, client_batches, layout, client_weights=None,
                  prev_local_params=None, gp=None, eta0_c=None):
        """One round on flat-form state (core.fed_loop.FlatFLState) ->
        (new_fstate, metrics, RoundAux). ``gp`` optionally passes
        the global params pytree when the caller still has it (the
        per-round wrapper); the fused loop leaves it None and the body
        reconstructs the views from the carried flat buffer. ``eta0_c``
        optionally replaces the scalar round-start η₀ with a (C,)
        per-client vector (the fleet loop's ``eta_carry`` warm start —
        non-sharded engines only)."""
        from repro.core.fed_loop import FlatFLState
        if eta0_c is not None and sharded:
            raise ValueError("per-client eta0 warm start (eta0_c) is not "
                             "supported on the per-round sharded engine — "
                             "the fleet loop runs un-meshed")
        if gp is None:
            gp = flatlib.unpack(fstate.P, layout)

        def pack1(tree):
            """Pytree -> (N,) f32 for the flat carry, sharded like the
            flat dim of the round buffer."""
            return constrain(flatlib.pack(tree, layout), nspec)
        mask = flatlib.round_mask(layout)
        if mask is not None:
            mask = constrain(mask, mspec)
        leaves = jax.tree_util.tree_leaves(client_batches)
        C, K = leaves[0].shape[0], leaves[0].shape[1]
        step_counts = (scenario.draw_step_counts(fstate.round, C, K)
                       if hetero else None)
        # fault lanes (repro.federation.faults): one deterministic draw
        # per round off axis 4 of the round key. Drops fold into the
        # SAME per-step lane mask heterogeneous K uses — a dropped client
        # simply runs out of budget at its drop step — so the scan stays
        # fixed-shape and the step stays at two kernel launches.
        lanes = (scenario.draw_faults(fstate.round, C, K)
                 if faults_on else None)
        drops_on = faults_on and fm.drop_rate > 0.0
        if drops_on:
            budget = (jnp.minimum(step_counts, lanes.drop_step)
                      if hetero else lanes.drop_step)
            # loss metrics mask on the effective budget; clamp ≥ 1 so a
            # step-0 drop (K=1) still indexes a defined "last step"
            mcounts = jnp.maximum(budget, 1)
        else:
            budget = mcounts = step_counts

        # broadcast the round-start params to the client slab; the carry
        # is already flat, so no per-round pytree re-pack happens here
        slab = flatlib.slab_shape(C, layout)
        with jax.named_scope("flat"):
            P = constrain(jnp.broadcast_to(
                fstate.P.reshape(slab[1:])[None], slab), sspec)
        # the tails that work in delta space read (C, N) rows
        P_start = (constrain(jnp.broadcast_to(
            fstate.P[None], (C, layout.padded_size)), pspec)
            if (is_async or comp is not None or guard_tail) else None)
        S = flat_delta_sgd_init(C, layout, eta0=eta0, theta0=theta0)
        if sharded:
            S = S._replace(prev_grads=constrain(S.prev_grads, sspec),
                           eta=constrain(S.eta, cspec),
                           theta=constrain(S.theta, cspec),
                           prev_grad_norm=constrain(S.prev_grad_norm,
                                                    cspec),
                           valid=constrain(S.valid, cspec),
                           clips=constrain(S.clips, cspec))

        # scan over local steps: batches (C, K, ...) -> (K, C, ...)
        batches_t = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1),
                                 client_batches)

        def step(carry, inp):
            batch_k, k_idx = inp
            P, S = carry
            params_c = flatlib.unpack_batched(P, layout)
            with jax.named_scope("client_grad"):
                (l, lm), g = jax.vmap(
                    grad_fn, in_axes=(0, 0, None,
                                      0 if prev_local_params is not None
                                      else None)
                )(params_c, batch_k, gp, prev_local_params)
            G = constrain(flatlib.pack_batched(g, layout), sspec)
            if faults_on and fm.nan_rate > 0.0:
                # NaN/Inf gradient corruption: from the drawn step on,
                # the client's packed lanes go non-finite. Injected on
                # the WIRE side of the guard — the in-step guard must
                # catch it (valid latches off, η=0, lane held).
                bad = k_idx >= lanes.nan_step
                G = constrain(jnp.where(bad[:, None, None],
                                        jnp.float32(jnp.nan), G), sspec)
            active = (k_idx < budget) if budget is not None else None
            P, S = flat_step(P, G, S, mask, active, eta0_c)
            return (P, S), (l, lm.get("counts", {}))

        from repro.models.common import scan_unroll
        (P, S), (losses, counts) = jax.lax.scan(
            step, (P, S), (batches_t, jnp.arange(K, dtype=jnp.int32)),
            unroll=scan_unroll())
        # everything past the local steps is the round tail
        with jax.named_scope("round_tail"):
            losses = losses.T  # (K, C) -> (C, K), same layout as vmap engine
            # (C, N) rows of the round-end slab for the delta-space tails:
            # one conversion a round, never one a local step
            P_rows = (constrain(P.reshape(C, layout.padded_size), pspec)
                      if P_start is not None else None)

            extra = _scenario_extras(scenario, fstate.round, C, num_clients,
                                     client_sizes, step_counts)
            extra.update(_round_counts(counts))
            # numerical-guard telemetry (always on for the flat engines):
            # how often η hit the ETA_CLAMP ceiling, and what fraction of
            # lanes the NaN guard dropped this round
            extra.update(
                eta_clip_rate=(jnp.sum(S.clips.astype(jnp.float32))
                               / jnp.float32(C * K)),
                nan_guard_rate=jnp.mean((~S.valid).astype(jnp.float32)))
            if tele.enabled:
                # in-scan distribution block (repro.telemetry): read-only
                # over round-end values, so the trajectory is unperturbed.
                # The Pallas kernels only run on the un-meshed pallas
                # engine; meshed/pjit rounds use the jnp ref math (sharding
                # constraints inside pallas_call don't compose), and the
                # counts are exact integers either way.
                extra.update(round_telemetry(
                    tele, S.eta, losses, S.clips, S.valid, backend=backend,
                    use_kernel=(backend == "pallas" and not sharded)))

            # survivor mask + byzantine factor for the fault/robust tails:
            # a client is excluded when its NaN guard latched, it dropped
            # mid-round, or (async, below) its update arrived over-stale
            byz = valid = None
            if guard_tail:
                valid = S.valid
                if drops_on:
                    valid = valid & (lanes.drop_step >= K)
                if faults_on and fm.byzantine_rate > 0.0:
                    byz = jnp.where(lanes.byzantine,
                                    jnp.float32(fm.byzantine_scale),
                                    jnp.float32(1.0))

            # delta compression (repro.compression): compress each client's
            # round delta before ANY aggregation — only the reconstructed
            # Δ̂_c (and, under meshes, the post-mean (N,) aggregate) exists
            # past this point. EF21: the client ships C(Δ_c − g_c) and both
            # sides roll g_c ← g_c + C(Δ_c − g_c), so Δ̂_c = new g_c and the
            # compression error does not accumulate across rounds.
            new_ef = None
            if comp is not None:
                from repro.compression.ops import (compress_flat,
                                                   compress_flat_sharded)
                levels = (scenario.draw_compression_levels(fstate.round, C)
                          if bw_hetero else None)
                delta = P_rows - P_start
                if byz is not None:
                    # byzantine corruption happens CLIENT-side, before the
                    # (honest) compression transport — the server only ever
                    # sees the reconstructed corrupted delta
                    delta = delta * byz[:, None]
                if use_ef:
                    if fstate.ef is None:
                        raise ValueError(
                            "error-feedback compression needs FLState.ef — "
                            "allocate it via init_fl_state(..., compression="
                            "spec, cohort=C)")
                    E = fstate.ef
                    if sharded:
                        E = constrain(E, pspec)
                    resid = delta - E
                else:
                    E, resid = None, delta
                if sharded:
                    chat = compress_flat_sharded(resid, comp, mesh=mesh,
                                                 pspec=pspec, levels=levels,
                                                 backend=backend)
                else:
                    chat = compress_flat(resid, comp, levels=levels,
                                         backend=backend)
                delta_hat = (E + chat) if E is not None else chat
                if sharded:
                    delta_hat = constrain(delta_hat, pspec)
                if use_ef:
                    new_ef = delta_hat      # (C, N) flat — the EF21 carry
                # wire accounting over the VALID elements (layout.size):
                # tail padding never ships, so sharded and replicated
                # layouts (different padded_size) report identical bytes
                wire = comp.wire_bytes(layout.size, levels=levels,
                                       num_clients=C)
                extra.update(
                    wire_bytes=jnp.sum(wire),
                    comp_ratio=(4.0 * layout.size * C) / jnp.sum(wire))
                if levels is not None:
                    extra["comp_level_mean"] = jnp.mean(
                        levels.astype(jnp.float32))
                # what the server aggregates: round-start params + the
                # reconstructed deltas (≡ P exactly when the spec is inert —
                # inert specs never reach this branch)
                P_agg = P_start + delta_hat
            else:
                delta_hat = None
                P_agg = P

            if not is_async and not guard_tail:
                # aggregate: single (weighted) mean over the packed client
                # axis — under the sharded engine XLA lowers this to the
                # FedAvg all-reduce over the client mesh axes; the (N,)
                # result keeps the flat-dim sharding. Without compression
                # the mean runs over the slab itself and its leaves are
                # read from the (N/128, 128) result as from a slab.
                if weighted and client_weights is not None:
                    w = client_weights / jnp.sum(client_weights)
                    agg_flat = jnp.tensordot(w.astype(jnp.float32), P_agg,
                                             axes=(0, 0))
                elif comp is not None and not sharded:
                    agg_flat = _halving_mean(P_agg)
                else:
                    agg_flat = jnp.mean(P_agg, axis=0)
                agg = flatlib.unpack(constrain(
                    agg_flat, nspec if agg_flat.ndim == 1 else mspec), layout)
                new_params, sstate = server_opt.update(gp, agg,
                                                       fstate.server_state)
                metrics = _round_metrics(losses, S.eta, step_counts)
                metrics.update(extra)
                new_fstate = FlatFLState(
                    pack1(new_params), sstate, fstate.round + 1,
                    fstate.buffer, fstate.ef if new_ef is None else new_ef)
            elif not is_async:
                # fault/robust synchronous tail: the server works in DELTA
                # space — the RobustAgg ladder (repro.federation.faults)
                # aggregates the survivors' deltas (clip / trimmed / median /
                # valid-masked mean) and the result re-anchors on the round-
                # start params. Under meshes the ladder runs inside
                # shard_map before/with the client-mean psum, so only (N_loc,)
                # aggregates ever cross the client shard boundary.
                from repro.federation.faults import (robust_aggregate,
                                                     robust_aggregate_sharded)
                delta_eff = (delta_hat if comp is not None
                             else P_rows - P_start)
                if byz is not None and comp is None:
                    delta_eff = delta_eff * byz[:, None]
                w_raw = (client_weights.astype(jnp.float32)
                         if weighted and client_weights is not None else None)
                if sharded:
                    agg_delta, rinfo = robust_aggregate_sharded(
                        delta_eff, ragg, valid, mesh=mesh, pspec=pspec,
                        weights=w_raw)
                else:
                    agg_delta, rinfo = robust_aggregate(
                        delta_eff, ragg, valid, weights=w_raw,
                        backend=backend)
                n_valid = jnp.sum(valid.astype(jnp.float32))
                # round-start flat params: the replicated engines carry them
                # exactly in the flat state; sharded re-derives them from the
                # (identical-row) broadcast buffer to stay on nspec sharding
                P0 = (constrain(jnp.mean(P_start, axis=0), nspec)
                      if sharded else fstate.P)
                agg = flatlib.unpack(constrain(P0 + agg_delta, nspec), layout)

                def do_update(_):
                    p, s = server_opt.update(gp, agg, fstate.server_state)
                    return pack1(p), s

                def skip_update(_):
                    return fstate.P, fstate.server_state

                if quorum > 0:
                    # quorum degradation: with < Q valid clients the round
                    # is a no-op carrying the previous params/server state
                    skipped = n_valid < quorum
                    newP, sstate = jax.lax.cond(skipped, skip_update,
                                                do_update, None)
                    if new_ef is not None:
                        new_ef = jnp.where(skipped, E, new_ef)
                else:
                    skipped = jnp.asarray(False)
                    newP, sstate = do_update(None)
                metrics = _round_metrics(losses, S.eta, mcounts)
                extra.update(rinfo)
                extra.update(valid_count=n_valid,
                             round_skipped=skipped.astype(jnp.float32))
                if drops_on:
                    extra["drop_frac"] = jnp.mean(
                        (lanes.drop_step < K).astype(jnp.float32))
                if byz is not None:
                    extra["byz_frac"] = jnp.mean(
                        lanes.byzantine.astype(jnp.float32))
                metrics.update(extra)
                new_fstate = FlatFLState(
                    newP, sstate, fstate.round + 1, fstate.buffer,
                    fstate.ef if new_ef is None else new_ef)
            elif not guard_tail:
                # FedBuff-style async aggregation: one staleness-weighted
                # reduction over the packed client axis produces the cohort's
                # delta sum; the server only steps when the buffer holds M
                # updates (repro.federation.buffer). The buffer keeps its
                # param-shaped f32 delta tree (layout-independent, and the
                # known-good form under SPMD meshes); only the params
                # re-enter the flat carry.
                from repro.federation.buffer import (buffer_merge, buffer_step,
                                                     staleness_weights)
                stale = scenario.draw_staleness(fstate.round, C)
                w = staleness_weights(stale, scenario.staleness_exp)
                if weighted and client_weights is not None:
                    w = w * client_weights.astype(jnp.float32)
                d = delta_hat if comp is not None else (P_rows - P_start)
                delta_flat = jnp.tensordot(w, d, axes=(0, 0))
                delta_tree = flatlib.unpack(constrain(delta_flat, nspec),
                                            layout, cast=False)
                buf = buffer_merge(fstate.buffer, delta_tree, jnp.sum(w), C,
                                   stale)
                params, sstate, buf, flushed = buffer_step(
                    gp, fstate.server_state, buf, server_opt,
                    scenario.buffer_size)
                metrics = _round_metrics(losses, S.eta, step_counts)
                sf = stale.astype(jnp.float32)
                extra.update(stale_mean=jnp.mean(sf), stale_max=jnp.max(sf),
                             buffer_fill=buf.count.astype(jnp.float32),
                             flushed=flushed)
                metrics.update(extra)
                new_fstate = FlatFLState(
                    pack1(params), sstate, fstate.round + 1, buf,
                    fstate.ef if new_ef is None else new_ef)
            else:
                # fault/robust async tail: over-stale updates are REJECTED
                # by the server (valid &= fresh enough), the RobustAgg
                # ladder aggregates the survivors' deltas, and the buffer
                # accumulates the robust mean scaled back to Σ wΔ form so
                # the flush's Σ wΔ / Σ w recovers it. Quorum failures skip
                # the merge entirely (buffer, params, server state frozen).
                from repro.federation.buffer import (buffer_merge, buffer_step,
                                                     staleness_weights)
                from repro.federation.faults import (robust_aggregate,
                                                     robust_aggregate_sharded)
                stale = scenario.draw_staleness(fstate.round, C)
                if faults_on and fm.overstale_rate > 0.0:
                    stale = jnp.where(lanes.overstale,
                                      jnp.int32(fm.overstale), stale)
                valid = valid & (stale <= scenario.staleness_max)
                w = staleness_weights(stale, scenario.staleness_exp)
                if weighted and client_weights is not None:
                    w = w * client_weights.astype(jnp.float32)
                d = delta_hat if comp is not None else (P_rows - P_start)
                if byz is not None and comp is None:
                    d = d * byz[:, None]
                if sharded:
                    rob, rinfo = robust_aggregate_sharded(
                        d, ragg, valid, mesh=mesh, pspec=pspec, weights=w)
                else:
                    rob, rinfo = robust_aggregate(d, ragg, valid, weights=w,
                                                  backend=backend)
                vf = valid.astype(jnp.float32)
                wsum = jnp.sum(w * vf)
                n_valid = jnp.sum(vf)
                delta_flat = rob * wsum
                delta_tree = flatlib.unpack(constrain(delta_flat, nspec),
                                            layout, cast=False)

                def do_round(_):
                    buf = buffer_merge(fstate.buffer, delta_tree, wsum,
                                       n_valid.astype(jnp.int32), stale)
                    params, sstate, buf, flushed = buffer_step(
                        gp, fstate.server_state, buf, server_opt,
                        scenario.buffer_size)
                    return pack1(params), sstate, buf, flushed

                def skip_round(_):
                    return (fstate.P, fstate.server_state, fstate.buffer,
                            jnp.float32(0.0))

                if quorum > 0:
                    skipped = n_valid < quorum
                    newP, sstate, buf, flushed = jax.lax.cond(
                        skipped, skip_round, do_round, None)
                    if new_ef is not None:
                        new_ef = jnp.where(skipped, E, new_ef)
                else:
                    skipped = jnp.asarray(False)
                    newP, sstate, buf, flushed = do_round(None)
                metrics = _round_metrics(losses, S.eta, mcounts)
                sf = stale.astype(jnp.float32)
                extra.update(stale_mean=jnp.mean(sf), stale_max=jnp.max(sf),
                             buffer_fill=buf.count.astype(jnp.float32),
                             flushed=flushed)
                extra.update(rinfo)
                extra.update(valid_count=n_valid,
                             round_skipped=skipped.astype(jnp.float32))
                if drops_on:
                    extra["drop_frac"] = jnp.mean(
                        (lanes.drop_step < K).astype(jnp.float32))
                if byz is not None:
                    extra["byz_frac"] = jnp.mean(
                        lanes.byzantine.astype(jnp.float32))
                if faults_on and fm.overstale_rate > 0.0:
                    extra["overstale_frac"] = jnp.mean(
                        lanes.overstale.astype(jnp.float32))
                metrics.update(extra)
                new_fstate = FlatFLState(
                    newP, sstate, fstate.round + 1, buf,
                    fstate.ef if new_ef is None else new_ef)

            return new_fstate, metrics, RoundAux(P, S.eta, S.valid)

    def round_fn(state: FLState, client_batches, client_weights=None,
                 prev_local_params=None):
        """-> (new_state, metrics, new_local_params (C, ...))."""
        from repro.core.fed_loop import (flatten_fl_state,
                                         unflatten_fl_state)
        layout = flatlib.layout_of(state.params, shards=shards)
        fstate = flatten_fl_state(state, layout)
        new_fstate, metrics, aux = flat_body(
            fstate, client_batches, layout, client_weights=client_weights,
            prev_local_params=prev_local_params, gp=state.params)
        new_state = unflatten_fl_state(new_fstate, layout)
        new_locals = flatlib.unpack_batched(aux.P_locals, layout)
        return new_state, metrics, new_locals

    round_fn.flat_body = flat_body
    return round_fn
