"""Round-fused training loop: a multi-round ``lax.scan`` on persistent
flat state.

The host-loop drivers (launch/train.py et al.) pay one dispatch per
round: a jitted single-round function is re-launched from Python, and
``_make_flat_round`` re-derives the flat (C, N) buffer from the param
pytree at the top of every call. At small models and fleet-scale client
counts — exactly the regime the paper's heterogeneity experiments live
in — that host round-trip dominates wall-clock.

``make_fl_loop(rounds_per_call=R)`` fuses R rounds into ONE jitted
computation:

  * the carried state is a ``FlatFLState`` — the param pytree packed
    into the (N,) flat buffer (repro.core.flat) and the EF21
    error-feedback tree packed to (C, N); inside a round the local-step
    scan carries the (C, N/128, 128) client slab. Packing happens once
    per R-round block (``flatten_fl_state``); unpacking only at
    eval/checkpoint cadence (``unflatten_fl_state``).
  * a ``lax.scan`` chains R rounds of the SAME flat round body the
    single-round engine runs (``fed_round`` attaches it to the returned
    round_fn as ``round_fn.flat_body``), so fused and host-loop rounds
    are bit-exact by construction.
  * cohort scheduling stays on device: the scenario schedulers
    (repro.federation) key every draw on ``(seed, round)`` and the round
    counter rides in the carry, so the in-scan draws equal the host
    pipeline's gather draw round for round.
  * per-round batches come either pre-stacked with a leading R axis, or
    — the fast path — as (R, C, K, b) int32 gather indices into a
    pre-staged device-resident example arena (``arena_gather``): the
    host ships a few hundred KB of indices per block instead of
    re-staging the full (C, K, b, ...) batch every round.
  * callers jit with ``donate_argnums=0`` so the carried flat buffers
    update in place: peak live memory does not grow with R.

The per-local-step kernel schedule is untouched: the scan body traces
the fused kernel pair once (2 launches per local step), and the
executed launch schedule of one R-round block is exactly R times the
single round's — 2·K·R launches, still independent of leaf and client
count.

Composition: everything the flat round engine supports — sharded meshes
(the HLO assertions hold on the scanned computation), heterogeneous K_c
lane masks, FedBuff async buffering, delta compression + EF21 — flows
through unchanged, because the scan body IS the single-round body.
Metrics come back stacked: every leaf gains a leading R axis.

Block-level shard_map (``make_fl_loop(block_sharded=True)``): the
per-round sharded engine re-enters the mesh at every kernel — one
``shard_map`` per local step plus pack/unpack resharding — which at toy
sizes costs ~45x the replicated round in pure dispatch. The block path
instead wraps the ENTIRE R-round ``lax.scan`` in ONE ``shard_map`` over
the mesh's client axes: each device carries its C_loc cohort rows
through all R rounds locally (full-N rows — the client-axes-only
regime, ``federation.flat_shards(mesh) == 1``), and the only
client-crossing collective is the per-round (N,) ``psum`` of the
(compressed) aggregate — so both HLO invariants (no resident f32[C, N],
no full-precision per-client delta across client shards) hold on the
block program, and per-client local math is bit-identical to the
replicated engine (aggregation differs only by psum reassociation).
Scenario draws for all R rounds are made ONCE at jit level and fed
through the shard_map as replicated (R, C) operands.

Fleet loop (``make_fleet_loop``): the registered-vs-sampled split. A
``repro.federation.arena.ClientArena`` holds per-REGISTERED-client
state (Δ-SGD η carry, EF21 reconstruction, participation history) in
(C_registered, ...) storage; each scanned round draws the cohort ids
on device (the scheduler's Gumbel-top-k over all C_registered
candidates), gathers ONLY those C rows (``arena_take``), runs the same
flat round body on the cohort slab, and scatters the updated rows back
(``arena_update``). Never-sampled clients' rows are never touched, and
with error feedback off no (C_registered, N) buffer ever exists —
machine-checked by ``repro.sharding.hlo
.assert_cohort_only_materialization`` on the compiled loop.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import flat as flatlib
from repro.core.fed_round import FLState, make_fl_round


class FlatFLState(NamedTuple):
    """FLState in persistent flat form — the scan carry of the fused
    loop, and the block-boundary checkpoint payload.

    ``P`` is the packed (N,) f32 global params; ``ef`` (EF21
    compression) is the packed (C, N) f32 reconstruction state.
    ``server_state`` and the async ``buffer`` keep their pytree form —
    the server's per-leaf dtypes are load-bearing for bit-exact
    arithmetic.
    """
    P: jax.Array
    server_state: Any
    round: jax.Array
    buffer: Any = None
    ef: Any = None


def flatten_fl_state(state: FLState, layout: flatlib.FlatLayout
                     ) -> FlatFLState:
    """Pack an FLState once per R-round block. Exact: params pack to the
    f32 buffer losslessly (bf16 -> f32 widens), and the ef tree is f32
    already, so pack/unpack round-trips bit-for-bit."""
    ef = state.ef
    if ef is not None:
        ef = flatlib.pack_batched(ef, layout)
        ef = ef.reshape(ef.shape[0], layout.padded_size)
    fstate = FlatFLState(flatlib.pack(state.params, layout),
                         state.server_state, state.round, state.buffer, ef)
    # donation hygiene: jax caches scalar constants, so two zero-valued
    # counters (e.g. FLState.round and the async buffer's count) can
    # alias ONE device buffer — a donating Execute rejects duplicate
    # buffers. Copy scalar leaves apart; the big buffers are fresh packs.
    return jax.tree.map(
        lambda x: jnp.array(x, copy=True) if getattr(x, "ndim", 1) == 0
        else x, fstate)


def unflatten_fl_state(fstate: FlatFLState, layout: flatlib.FlatLayout
                       ) -> FLState:
    """Back to pytree form — eval / checkpoint-interop cadence only."""
    ef = fstate.ef
    if ef is not None:
        ef = flatlib.unpack_batched(fstate.ef, layout, cast=False)
    return FLState(flatlib.unpack(fstate.P, layout), fstate.server_state,
                   fstate.round, fstate.buffer, ef)


def arena_gather(arena, idx: jax.Array):
    """Device-side per-round batch gather: ``idx`` (C, K, b) int32 rows
    index the staged example arena (leaves (num_examples, ...)), giving
    (C, K, b, ...) client batches — the on-device equivalent of the host
    pipeline's per-round numpy gather + transfer."""
    return jax.tree.map(lambda a: a[idx], arena)


def make_fl_loop(loss_fn, client_opt, server_opt, *, params_like,
                 num_rounds: int, rounds_per_call: int = 8,
                 weighted: bool = False, flat="xla", mesh=None,
                 federation=None, scenario=None,
                 num_clients: Optional[int] = None, client_sizes=None,
                 compression=None, gather=None,
                 block_sharded: bool = False, telemetry=None):
    """Build the R-round fused loop.

    Returns ``loop_fn(fstate, round_data, client_weights=None,
    arena=None) -> (fstate, metrics)`` where

      * ``fstate`` is a ``FlatFLState`` (``flatten_fl_state``); jit the
        loop with ``donate_argnums=0`` so its buffers update in place;
      * ``round_data`` leaves carry a leading R axis: stacked client
        batches (R, C, K, b, ...), or — with ``gather`` (e.g.
        ``arena_gather``) — per-round gather indices resolved against
        the device-resident ``arena``;
      * ``client_weights`` is an optional (R, C) weight block
        (``weighted`` aggregation);
      * ``metrics`` leaves come back stacked over the R rounds.

    ``params_like`` (a params pytree or its ShapeDtypeStructs) fixes the
    flat layout; the remaining knobs mirror ``make_fl_round`` — the loop
    requires the flat engine (``flat`` False is rejected) and composes
    with mesh sharding, scenarios, and compression exactly like the
    single-round engine, because the scan body IS that engine's round
    body. ``rounds_per_call`` is advisory: the actual R of a call is the
    leading axis of ``round_data`` (the tail block of a training run may
    be shorter).

    State form (``loop_fn.state_form``): the carry is the persistent
    ``FlatFLState`` ("flat"), with or without a mesh. Under
    ``mesh``/``federation`` the (N,) params carry is sharded like the
    flat dim of the (C, N) round buffer, which stays sharded inside the
    scan (the HLO assertions hold on the scanned computation).

    ``block_sharded=True`` (requires ``mesh``/``federation`` in the
    client-axes-only regime, ``federation.flat_shards(mesh) == 1``):
    fold the whole R-round scan inside ONE shard_map instead of
    re-entering the mesh per kernel — see the module docstring. The
    carry is then the persistent ``FlatFLState`` ("flat" state form):
    the (N,) flat params stay a plain replicated operand. Fault
    injection / robust aggregation / quorum are not supported on the
    block path (their order-statistic tails need cross-client data
    movement) — use the per-round sharded engine for those.

    ``telemetry`` (None/bool/repro.telemetry.TelemetrySpec): the
    in-scan distribution block rides the scanned metrics — extra
    fixed-shape leaves with a leading R axis, zero host syncs inside a
    block, trajectory bit-exact on vs off. On the block-sharded path
    the per-shard η-histogram counts join the existing packed per-round
    psum (exact integer sums — still 2 collectives per round, and the
    summed histogram equals the replicated engine's bit-for-bit);
    ``loss_deciles`` is skipped there (a cross-client sort has no
    shard-local form).
    """
    if not flat:
        raise ValueError("the round-fused loop requires the flat engine "
                         "(flat='xla'|'pallas'): the carry is the packed "
                         "flat buffer")
    if rounds_per_call < 1:
        raise ValueError(f"rounds_per_call must be >= 1, got "
                         f"{rounds_per_call}")
    if block_sharded:
        if mesh is None or federation is None:
            raise ValueError("block_sharded=True requires mesh= and "
                             "federation=")
        if federation.flat_shards(mesh) != 1:
            raise ValueError(
                "the block-level shard_map shards CLIENTS only — each "
                "device carries full-N rows for its C_loc clients, so "
                "the flat dim must be replicated: use a FederationSpec "
                "whose fsdp/tp axes are absent from the mesh "
                f"(flat_shards == 1, got "
                f"{federation.flat_shards(mesh)})")
        if scenario is not None and (scenario.faulty or scenario.robust
                                     or scenario.quorum > 0):
            raise ValueError(
                "fault injection / robust aggregation / quorum are not "
                "supported on the block-sharded path — their "
                "order-statistic tails need cross-client data movement; "
                "use the per-round sharded engine "
                "(make_fl_loop(mesh=..., block_sharded=False))")
        return _make_block_loop(
            loss_fn, client_opt, server_opt, params_like=params_like,
            num_rounds=num_rounds, rounds_per_call=rounds_per_call,
            weighted=weighted, flat=flat, mesh=mesh,
            federation=federation, scenario=scenario,
            num_clients=num_clients, client_sizes=client_sizes,
            compression=compression, gather=gather, telemetry=telemetry)
    round_fn = make_fl_round(loss_fn, client_opt, server_opt,
                             num_rounds=num_rounds, weighted=weighted,
                             flat=flat, mesh=mesh, federation=federation,
                             scenario=scenario, num_clients=num_clients,
                             client_sizes=client_sizes,
                             compression=compression, telemetry=telemetry)
    body = getattr(round_fn, "flat_body", None)
    if body is None:  # pragma: no cover - make_fl_round always attaches it
        raise ValueError("make_fl_round returned no flat round body")
    shards = federation.flat_shards(mesh) if federation is not None else 1
    layout = flatlib.layout_of(params_like, shards=shards)

    def loop_fn(carry, round_data, client_weights=None, arena=None):
        if gather is not None and arena is None:
            raise ValueError("this loop gathers batches from a staged "
                             "arena: pass arena=")

        def one_round(st, inp):
            data, w_r = inp
            w_r = w_r if has_w else None
            batches = gather(arena, data) if gather is not None else data
            st, metrics, _ = body(st, batches, layout, client_weights=w_r)
            return st, metrics

        # scan xs must be arrays: a missing weight block rides along as
        # a zero-size per-round placeholder
        R = jax.tree_util.tree_leaves(round_data)[0].shape[0]
        w = (client_weights if client_weights is not None
             else jnp.zeros((R, 0), jnp.float32))
        has_w = client_weights is not None
        return jax.lax.scan(one_round, carry, (round_data, w))

    loop_fn.layout = layout
    loop_fn.rounds_per_call = rounds_per_call
    loop_fn.state_form = "flat"
    return loop_fn


def _make_block_loop(loss_fn, client_opt, server_opt, *, params_like,
                     num_rounds: int, rounds_per_call: int,
                     weighted: bool, flat, mesh, federation,
                     scenario=None, num_clients=None, client_sizes=None,
                     compression=None, gather=None, telemetry=None):
    """One shard_map around the whole R-round scan (client-axes-only
    sharding). Each device runs its C_loc clients' full local math —
    grad eval, the fused Δ-SGD kernel pair, delta compression — on a
    local (C_loc, N/128, 128) slab; the mesh is entered once per BLOCK,
    and the client-crossing traffic is 2 collectives per round — one packed
    psum carrying the (compressed) aggregate plus every scalar metric
    sum ((N+5,), widening to (N+5+B,) when telemetry appends its B
    η-histogram bin counts), and one (2,) pmin for the η extrema.
    Per-client math is therefore bit-identical
    to the replicated flat engine; the aggregate differs only by psum
    reassociation (<= ~1e-5 at f32, same tolerance the per-round
    sharded parity tests use). Scenario draws for all R rounds happen
    ONCE at jit level and enter the shard_map as replicated (R, C)
    operands — every shard sees the full vectors (for wire accounting
    and FedBuff stats) and slices its local columns by mesh position.
    Jit ``loop_fn``; donate_argnums=0 works as usual."""
    from jax.sharding import PartitionSpec as PS

    from repro.core.delta_sgd import flat_delta_sgd_init, flat_delta_sgd_step
    from repro.federation.heterogeneity import active_mask
    from repro.kernels.telemetry import lane_histogram_ref
    from repro.models.common import scan_unroll
    from repro.telemetry.spec import resolve_telemetry

    tele = resolve_telemetry(telemetry)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    hyper = client_opt.hyper
    if (client_opt.name != "delta_sgd" or hyper is None
            or hyper.get("groupwise")):
        raise ValueError("flat engine requires the global-rule delta_sgd "
                         f"client optimizer, got {client_opt.name!r}")
    gamma, delta_h = hyper["gamma"], hyper["delta"]
    eta0, theta0 = hyper["eta0"], hyper["theta0"]
    backend = "xla" if flat == "xla" else "pallas"

    if compression is not None or (
            scenario is not None and scenario.bandwidth_heterogeneous):
        from repro.compression import get_compression
        compression = get_compression(compression)
    hetero = scenario is not None and scenario.heterogeneous
    is_async = scenario is not None and scenario.is_async
    bw_hetero = scenario is not None and scenario.bandwidth_heterogeneous
    comp = compression if (compression is not None
                           and compression.active(scenario)) else None
    use_ef = comp is not None and comp.error_feedback

    # client-axes-only regime: flat_shards == 1 (checked by the caller),
    # so the layout is the REPLICATED layout — bit-compatible with the
    # un-meshed engines and the fused host loop.
    layout = flatlib.layout_of(params_like, shards=1)
    N = layout.padded_size
    ca, _ = federation.flat_axes(mesh)
    centry = ca if ca else None
    n_shards = 1
    for a in ca:
        n_shards *= mesh.shape[a]

    def loop_fn(fstate: FlatFLState, round_data, client_weights=None,
                arena=None):
        if gather is not None and arena is None:
            raise ValueError("this loop gathers batches from a staged "
                             "arena: pass arena=")
        if use_ef and fstate.ef is None:
            raise ValueError("error-feedback compression needs "
                             "FlatFLState.ef (flatten an FLState built "
                             "with init_fl_state(..., compression=spec, "
                             "cohort=C))")
        leaves = jax.tree_util.tree_leaves(round_data)
        R, C, K = leaves[0].shape[0], leaves[0].shape[1], leaves[0].shape[2]
        if C % n_shards:
            raise ValueError(f"cohort C={C} must divide the "
                             f"{n_shards} client shards")
        C_loc = C // n_shards
        has_w = client_weights is not None

        # all R rounds' scenario draws, once, at jit level
        r_idx = fstate.round + jnp.arange(R, dtype=jnp.int32)
        draws = {}
        if hetero:
            draws["k"] = jax.vmap(
                lambda t: scenario.draw_step_counts(t, C, K))(r_idx)
        if is_async:
            draws["stale"] = jax.vmap(
                lambda t: scenario.draw_staleness(t, C))(r_idx)
        if bw_hetero:
            draws["lev"] = jax.vmap(
                lambda t: scenario.draw_compression_levels(t, C))(r_idx)
        w = (client_weights if has_w
             else jnp.zeros((R, 0), jnp.float32))

        def block(fst, data, w_all, draws_all, arena_l):
            """Runs on every device with LOCAL shards: data leaves
            (R, C_loc, K, ...); fst/w_all/draws_all/arena_l replicated
            except fst.ef (C_loc, N)."""
            def cpsum(x):
                return jax.lax.psum(x, ca) if ca else x

            def cpmin(x):
                return jax.lax.pmin(x, ca) if ca else x

            # this shard's client offset: axis 0 of a (C, ...) operand
            # partitioned over the tuple ``ca`` is blocked row-major in
            # axis order, so the linear block index is the mixed-radix
            # axis position
            if ca:
                bidx = jnp.int32(0)
                for a in ca:
                    bidx = bidx * mesh.shape[a] + jax.lax.axis_index(a)
                c_off = bidx * C_loc
            else:
                c_off = jnp.int32(0)

            def local_cols(full):
                return jax.lax.dynamic_slice_in_dim(full, c_off, C_loc, 0)

            mask = flatlib.round_mask(layout)
            Cf = jnp.float32(C)

            def one_round(st, xs):
                data_r, w_r, d_r = xs
                batches = (gather(arena_l, data_r) if gather is not None
                           else data_r)
                gp = flatlib.unpack(st.P, layout)
                slab = flatlib.slab_shape(C_loc, layout)
                with jax.named_scope("flat"):
                    P = jnp.broadcast_to(st.P.reshape(slab[1:])[None],
                                         slab)
                # the delta-space tails read (C_loc, N) rows
                P_start = (jnp.broadcast_to(st.P[None], (C_loc, N))
                           if (is_async or comp is not None) else None)
                S = flat_delta_sgd_init(C_loc, layout, eta0=eta0,
                                        theta0=theta0)
                k_full = d_r.get("k")
                budget = (local_cols(k_full) if k_full is not None
                          else None)
                batches_t = jax.tree.map(
                    lambda x: jnp.swapaxes(x, 0, 1), batches)

                def step(carry, inp):
                    batch_k, k_idx = inp
                    P, S = carry
                    params_c = flatlib.unpack_batched(P, layout)
                    with jax.named_scope("client_grad"):
                        (l, _), g = jax.vmap(
                            grad_fn, in_axes=(0, 0, None, None)
                        )(params_c, batch_k, gp, None)
                    G = flatlib.pack_batched(g, layout)
                    active = ((k_idx < budget) if budget is not None
                              else None)
                    P, S = flat_delta_sgd_step(
                        P, G, S, gamma=gamma, delta=delta_h, eta0=eta0,
                        mask=mask, active=active, backend=backend)
                    return (P, S), l

                (P, S), losses = jax.lax.scan(
                    step, (P, S),
                    (batches_t, jnp.arange(K, dtype=jnp.int32)),
                    unroll=scan_unroll())
                # everything past the local steps is the round tail
                with jax.named_scope("round_tail"):
                    losses = losses.T       # (C_loc, K)
                    # one conversion a round, never one a local step
                    P_rows = (P.reshape(C_loc, N) if P_start is not None
                              else None)

                    # collective budget: every client-crossing SUM rides
                    # ONE packed (N+5,) psum together with the round's
                    # aggregate, and both η extrema share ONE pmin — 2
                    # collectives per round total, which is what keeps the
                    # sharded block's per-round cost near the replicated
                    # loop's on rendezvous-priced meshes. The concat lives
                    # inside the shard_map body (a per-device program, no
                    # SPMD partitioner), so the 1-D packed-concat jit
                    # gotcha (core/flat.py) does not apply.
                    if k_full is not None:
                        am_l = active_mask(budget, K)
                        loss_num = jnp.sum(losses * am_l)
                        loss_den = jnp.sum(active_mask(k_full, K))
                        last_num = jnp.sum(jnp.take_along_axis(
                            losses, (budget - 1)[:, None], axis=1)[:, 0])
                    else:
                        loss_num = jnp.sum(losses)
                        loss_den = jnp.float32(C * K)
                        last_num = jnp.sum(losses[:, -1])
                    scal = jnp.stack([
                        loss_num, last_num, jnp.sum(S.eta),
                        jnp.sum(S.clips.astype(jnp.float32)),
                        jnp.sum((~S.valid).astype(jnp.float32))])
                    if tele.enabled:
                        # per-shard η-histogram counts ride the SAME packed
                        # psum (exact integer sums in f32, so the summed
                        # histogram is bit-identical to the replicated
                        # engine's) — the collective budget stays at 2/round
                        scal = jnp.concatenate([
                            scal,
                            lane_histogram_ref(
                                S.eta, jnp.asarray(tele.eta_edges()))])
                    ext = cpmin(jnp.stack([jnp.min(S.eta),
                                           -jnp.max(S.eta)]))
                    extra = {}
                    if k_full is not None:
                        kf = k_full.astype(jnp.float32)
                        extra.update(k_eff_mean=jnp.mean(kf),
                                     k_eff_min=jnp.min(kf),
                                     k_eff_max=jnp.max(kf))

                    new_ef = st.ef
                    if comp is not None:
                        from repro.compression.ops import compress_flat
                        lev_full = d_r.get("lev")
                        lev_loc = (local_cols(lev_full)
                                   if lev_full is not None else None)
                        delta_c = P_rows - P_start
                        resid = (delta_c - st.ef) if use_ef else delta_c
                        chat = compress_flat(resid, comp, levels=lev_loc,
                                             backend=backend)
                        delta_hat = (st.ef + chat) if use_ef else chat
                        if use_ef:
                            new_ef = delta_hat
                        # wire accounting on the FULL level vector — every
                        # shard reports the identical cohort-total bytes
                        wire = comp.wire_bytes(layout.size, levels=lev_full,
                                               num_clients=C)
                        extra.update(
                            wire_bytes=jnp.sum(wire),
                            comp_ratio=(4.0 * layout.size * C)
                            / jnp.sum(wire))
                        if lev_full is not None:
                            extra["comp_level_mean"] = jnp.mean(
                                lev_full.astype(jnp.float32))
                        P_agg = P_start + delta_hat
                    else:
                        delta_hat = None
                        P_agg = P

                    if not is_async:
                        if weighted and has_w:
                            wn = w_r.astype(jnp.float32)
                            wn = wn / jnp.sum(wn)
                            agg_local = jnp.tensordot(local_cols(wn), P_agg,
                                                      axes=(0, 0))
                            agg_div = jnp.float32(1.0)
                        else:
                            agg_local = jnp.sum(P_agg, axis=0)
                            agg_div = Cf
                        packed = cpsum(jnp.concatenate(
                            [agg_local.reshape(N), scal]))
                        scal_g = packed[N:]
                        agg = flatlib.unpack(packed[:N] / agg_div, layout)
                        new_params, sstate = server_opt.update(
                            gp, agg, st.server_state)
                        new_st = FlatFLState(
                            flatlib.pack(new_params, layout), sstate,
                            st.round + 1, st.buffer, new_ef)
                    else:
                        from repro.federation.buffer import (
                            buffer_merge, buffer_step, staleness_weights)
                        stale_full = d_r["stale"]
                        wst = staleness_weights(stale_full,
                                                scenario.staleness_exp)
                        if weighted and has_w:
                            wst = wst * w_r.astype(jnp.float32)
                        d = (delta_hat if comp is not None
                             else P_rows - P_start)
                        agg_local = jnp.tensordot(local_cols(wst), d,
                                                  axes=(0, 0))
                        packed = cpsum(jnp.concatenate([agg_local, scal]))
                        scal_g = packed[N:]
                        delta_tree = flatlib.unpack(packed[:N], layout,
                                                    cast=False)
                        # buffer math runs on the full replicated vectors,
                        # so the buffer state stays identical on every shard
                        buf = buffer_merge(st.buffer, delta_tree,
                                           jnp.sum(wst), C, stale_full)
                        new_params, sstate, buf, flushed = buffer_step(
                            gp, st.server_state, buf, server_opt,
                            scenario.buffer_size)
                        sf = stale_full.astype(jnp.float32)
                        extra.update(
                            stale_mean=jnp.mean(sf), stale_max=jnp.max(sf),
                            buffer_fill=buf.count.astype(jnp.float32),
                            flushed=flushed)
                        new_st = FlatFLState(
                            flatlib.pack(new_params, layout), sstate,
                            st.round + 1, buf, new_ef)
                    metrics = {
                        "loss": scal_g[0] / loss_den,
                        "loss_last_step": scal_g[1] / Cf,
                        "eta_mean": scal_g[2] / Cf,
                        "eta_min": ext[0], "eta_max": -ext[1],
                        "eta_clip_rate": scal_g[3] / jnp.float32(C * K),
                        "nan_guard_rate": scal_g[4] / Cf}
                    if tele.enabled:
                        metrics.update(eta_hist=scal_g[5:],
                                       eta_clip_count=scal_g[3],
                                       nan_guard_count=scal_g[4])
                    metrics.update(extra)
                    return new_st, metrics

            return jax.lax.scan(one_round, fst,
                                (data, w_all, draws_all))

        fspec = jax.tree.map(lambda _: PS(), fstate)
        if fstate.ef is not None:
            fspec = fspec._replace(ef=PS(centry, None))
        in_specs = (fspec,
                    jax.tree.map(lambda _: PS(None, centry), round_data),
                    jax.tree.map(lambda _: PS(), w),
                    jax.tree.map(lambda _: PS(), draws),
                    jax.tree.map(lambda _: PS(), arena))
        # out_specs: exact state tree + a PS() prefix for the metrics
        # dict (everything psum'd/derived-from-replicated inside)
        blk = jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                            out_specs=(fspec, PS()), check_vma=False)
        new_fstate, metrics = blk(fstate, round_data, w, draws, arena)

        if num_clients is not None and scenario is not None:
            sch = scenario.make_scheduler(num_clients, C,
                                          sizes=client_sizes)
            metrics["cohort_ids"] = jax.vmap(
                lambda t: sch.sample(jax.random.key(scenario.seed), t)
            )(r_idx)
        return new_fstate, metrics

    loop_fn.layout = layout
    loop_fn.rounds_per_call = rounds_per_call
    loop_fn.state_form = "flat"
    return loop_fn


def make_fleet_loop(loss_fn, client_opt, server_opt, *, params_like,
                    num_rounds: int, num_registered: int,
                    rounds_per_call: int = 8, weighted: bool = False,
                    flat="xla", scenario=None, client_sizes=None,
                    compression=None, gather=None, batch_index_fn=None,
                    eta_carry: bool = False, seed: int = 0,
                    telemetry=None):
    """Fleet-scale fused loop: C_registered clients, only the sampled
    cohort materialized per round.

    Returns ``loop_fn(carry, round_data, client_weights=None,
    arena=None) -> (carry, metrics)`` where ``carry`` is the pair
    ``(FlatFLState, repro.federation.arena.ClientArena)`` — the global
    training state plus the per-REGISTERED-client arena. Per scanned
    round the loop

      1. draws the cohort ids ON DEVICE: ``sch.sample(key, round)`` —
         the scheduler's Gumbel-top-k over all ``num_registered``
         candidates, the SAME (seed, round)-keyed draw the host data
         pipeline uses to gather batches, so data and state stay
         aligned without shipping ids;
      2. gathers the cohort's arena rows (``arena_take``) — EF21 slabs
         and η carry re-enter the round body through ``FLState.ef`` /
         ``eta0_c``;
      3. runs the standard flat round body (bit-identical to
         ``make_fl_loop``'s, because it IS that body);
      4. scatters updated rows back (``arena_update``): round-end η,
         participation count, last-seen round, new EF21 state. Rows of
         clients not in the cohort are untouched — a never-sampled
         client's state is bit-identical after any number of rounds.

    ``round_data`` modes mirror ``make_fl_loop`` — stacked batches
    (R, C, K, b, ...) or (R, C, K, b) gather indices resolved against
    ``arena`` via ``gather`` — plus a third, fleet-native mode:
    ``batch_index_fn(ids, round) -> (C, K, b)`` computes the gather
    indices ON DEVICE from the drawn cohort ids (e.g. id -> data
    partition row ranges), so the host ships nothing per block;
    ``round_data`` is then ignored except for its leading R axis (pass
    e.g. ``jnp.zeros((R, C, K, 0))``).

    ``eta_carry=True`` warm-starts a returning client's η₀ from its
    arena row (round-end η of its LAST participation) instead of the
    scalar η₀ — the locally-adaptive per-client state of Mukherjee et
    al.; the default False keeps Algorithm 1's per-round η reset (and
    bit-exactness against ``make_fl_loop``) intact.

    Memory ceiling: with error feedback off the arena holds only
    O(C_registered) per-client scalars — no (C_registered, N) buffer
    exists in the compiled program (``repro.sharding.hlo
    .assert_cohort_only_materialization``). Un-meshed by design: the
    cohort slab is the same (C, N) buffer the replicated engines run,
    and C (not C_registered) bounds the round's compute.

    ``seed`` keys the cohort draw when ``scenario`` is None (the data
    pipeline's fallback scheduler uses its own data seed there).
    """
    if not flat:
        raise ValueError("the fleet loop requires the flat engine "
                         "(flat='xla'|'pallas')")
    if num_registered < 1:
        raise ValueError(f"num_registered must be >= 1, got "
                         f"{num_registered}")
    from repro.federation.arena import ClientArena, arena_take, arena_update
    from repro.federation.schedulers import make_scheduler

    round_fn = make_fl_round(loss_fn, client_opt, server_opt,
                             num_rounds=num_rounds, weighted=weighted,
                             flat=flat, scenario=scenario,
                             compression=compression, telemetry=telemetry)
    body = round_fn.flat_body
    layout = flatlib.layout_of(params_like, shards=1)
    if compression is not None or (
            scenario is not None and scenario.bandwidth_heterogeneous):
        from repro.compression import get_compression
        compression = get_compression(compression)
    use_ef = (compression is not None and compression.error_feedback
              and compression.active(scenario))
    hyper = client_opt.hyper or {}
    eta0 = hyper.get("eta0", 0.0)

    def loop_fn(carry, round_data, client_weights=None, arena=None):
        fstate, car = carry
        if not isinstance(car, ClientArena):
            raise ValueError("fleet carry is (FlatFLState, ClientArena) "
                             "— build the arena with arena_init()")
        if use_ef and car.ef is None:
            raise ValueError("error-feedback compression needs the "
                             "arena's EF slab: arena_init(..., "
                             "ef_width=layout.padded_size)")
        if (gather is not None or batch_index_fn is not None) \
                and arena is None:
            raise ValueError("this loop gathers batches from a staged "
                             "arena: pass arena=")
        leaves = jax.tree_util.tree_leaves(round_data)
        R, C = leaves[0].shape[0], leaves[0].shape[1]
        sch = (scenario.make_scheduler(num_registered, C,
                                       sizes=client_sizes)
               if scenario is not None
               else make_scheduler("uniform", num_clients=num_registered,
                                   cohort=C))
        root_key = jax.random.key(scenario.seed if scenario is not None
                                  else seed)
        has_w = client_weights is not None

        def one_round(cr, xs):
            fst, ar = cr
            data_r, w_r = xs
            w_r = w_r if has_w else None
            ids = sch.sample(root_key, fst.round)       # (C,) int32
            rows = arena_take(ar, ids)
            if batch_index_fn is not None:
                g = gather if gather is not None else arena_gather
                batches = g(arena, batch_index_fn(ids, fst.round))
            elif gather is not None:
                batches = gather(arena, data_r)
            else:
                batches = data_r
            fst_in = fst._replace(ef=rows.ef if use_ef else None)
            new_fst, metrics, aux = body(
                fst_in, batches, layout, client_weights=w_r,
                eta0_c=rows.eta if eta_carry else None)
            # fleet telemetry from the arena rows (pre-update)
            seen = (rows.last_round >= 0).astype(jnp.float32)
            gap = jnp.where(rows.last_round >= 0,
                            fst.round - rows.last_round, 0
                            ).astype(jnp.float32)
            metrics.update(
                cohort_ids=ids,
                revisit_frac=jnp.mean(seen),
                realized_stale_mean=(jnp.sum(gap)
                                     / jnp.maximum(jnp.sum(seen), 1.0)),
                eta_carry_mean=jnp.mean(rows.eta))
            # scatter: η survives only through valid lanes (a latched
            # NaN guard keeps the previous carry), participation
            # bookkeeping always advances for sampled clients
            new_rows = ClientArena(
                jnp.where(aux.valid, aux.etas, rows.eta),
                rows.rounds_seen + 1,
                jnp.broadcast_to(fst.round, rows.last_round.shape
                                 ).astype(jnp.int32),
                new_fst.ef if use_ef else None)
            ar = arena_update(ar, ids, new_rows)
            # the carry keeps ef=None: per-client EF state lives in the
            # arena between rounds, not in cohort slots
            return (new_fst._replace(ef=None), ar), metrics

        w = (client_weights if has_w
             else jnp.zeros((R, 0), jnp.float32))
        (new_fstate, new_arena), metrics = jax.lax.scan(
            one_round, (fstate._replace(ef=None), car), (round_data, w))
        return (new_fstate, new_arena), metrics

    loop_fn.layout = layout
    loop_fn.rounds_per_call = rounds_per_call
    loop_fn.state_form = "fleet"
    loop_fn.eta0 = eta0
    return loop_fn
