"""Δ-SGD (DELTA-SGD): the paper's contribution. Eq. (4) + Algorithm 1.

    η_{t,k}^i = min( γ·‖x_k − x_{k−1}‖ / (2‖∇̃f_i(x_k) − ∇̃f_i(x_{k−1})‖),
                     sqrt(1 + δ·θ_{k−1})·η_{k−1} )
    θ_k = η_k / η_{k−1}

Implementation notes:
  * For plain SGD updates, ‖x_k − x_{k−1}‖ = η_{k−1}·‖g_{k−1}‖ exactly, so
    the state carries only the previous gradient (plus η, θ) — one extra
    param-sized buffer, matching the paper's memory claim (vs AdaAlter's 2×).
  * The previous gradient is *reused* for the step-size (paper §3: "we use
    the same batches to prevent additional gradient evaluations").
  * η₀, θ₀ are reset at the start of every round (Alg. 1 line 6).
  * All norms are global over the param pytree, computed in fp32 — under
    pjit these lower to small all-reduces on the client's submesh.
  * ``groupwise=True`` is a beyond-paper extension: one step size per
    top-level param group instead of one per client (ablated in
    EXPERIMENTS.md). Default is the faithful global rule.

The fused Pallas kernel (repro/kernels/delta_sgd) performs the update +
both norm accumulations in a single HBM pass; ``use_pallas`` switches it in.

Flat engine: ``FlatDeltaSGDState`` + ``flat_delta_sgd_step`` run the SAME
rule for all C participating clients at once on ``(C, N/128, 128)``
client slabs (repro.core.flat) — two kernel launches per local step
total, independent of leaf count and client count. The slab is the
kernels' own operand form, so the step relayouts nothing.
``backend="pallas"`` uses the batched Pallas kernels (interpret mode
off-TPU, ``repro.kernels.interpret_mode``); ``backend="xla"`` lowers the
identical math through plain jnp on the slabs, which is what meshed/pjit
callers use.

Sharded flat engine: ``flat_delta_sgd_step_sharded`` is the mesh-native
variant — the slab stays sharded like ``FederationSpec.flat_spec(mesh)``
(clients over the client axes, the N/128 rows over fsdp/tp axes, the
lanes whole) and the kernels run inside ``shard_map`` on each device's
local slab. The dual norm reduction completes with ONE psum of the two
partial sums over the N-shard axes (2·C_local floats on the wire); the
slab itself is never gathered, and the apply is purely shard-local.

The three flat entry points run under the named scope ``delta_sgd``: on a
device trace it holds the kernel pair and the per-client scalar math
around it (the η/θ rule and guards). The valid-lane zeroing happens
inside ``batched_apply``; no pass over the slab runs beside the pair.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import flat as flatlib

# Numerical guard ceiling on η (flat engines): Eq. (4)'s cand1 can blow
# up when ‖∇̃f(x_k) − ∇̃f(x_{k−1})‖ underflows on a flat local landscape,
# and a non-finite η from a corrupted gradient would poison the client
# slab irreversibly. η is clamped to this ceiling (counted per
# client in FlatDeltaSGDState.clips) and non-finite norms drop the lane
# to η=0 + latch FlatDeltaSGDState.valid off for the rest of the round.
# fp32 min against a finite ceiling is exact, so healthy trajectories
# are bit-identical with the guard on.
ETA_CLAMP = 1e3


class DeltaSGDState(NamedTuple):
    prev_grads: object      # pytree like params
    eta: jax.Array          # current step size (scalar f32, or per-group)
    theta: jax.Array        # η_k / η_{k-1}
    prev_grad_norm: jax.Array
    k: jax.Array            # local step counter (resets every round)


def _global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def _group_norms(tree):
    """One norm per top-level key (beyond-paper groupwise variant)."""
    return {k: _global_norm(v) for k, v in tree.items()}


def delta_sgd_init(params, *, eta0: float, theta0: float,
                   groupwise: bool = False) -> DeltaSGDState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p), params)
    if groupwise:
        eta = {k: jnp.asarray(eta0, jnp.float32) for k in params}
        theta = {k: jnp.asarray(theta0, jnp.float32) for k in params}
        pgn = {k: jnp.asarray(0.0, jnp.float32) for k in params}
    else:
        eta = jnp.asarray(eta0, jnp.float32)
        theta = jnp.asarray(theta0, jnp.float32)
        pgn = jnp.asarray(0.0, jnp.float32)
    return DeltaSGDState(zeros, eta, theta, pgn, jnp.asarray(0, jnp.int32))


def delta_sgd_reset(state: DeltaSGDState, *, eta0: float,
                    theta0: float) -> DeltaSGDState:
    """Round-start reset (Alg. 1 line 6): η ← η₀, θ ← θ₀, k ← 0."""
    eta = jax.tree.map(lambda e: jnp.full_like(e, eta0), state.eta)
    theta = jax.tree.map(lambda t: jnp.full_like(t, theta0), state.theta)
    pgn = jax.tree.map(lambda n: jnp.zeros_like(n), state.prev_grad_norm)
    return DeltaSGDState(state.prev_grads, eta, theta, pgn,
                         jnp.asarray(0, jnp.int32))


def _eta_rule(eta_prev, theta_prev, dx_norm, dg_norm, gamma, delta):
    """Eq. (4) with the δ-damped growth condition (Appendix B.1)."""
    cand1 = jnp.where(dg_norm > 0.0,
                      gamma * dx_norm / (2.0 * dg_norm),
                      jnp.asarray(jnp.inf, jnp.float32))
    cand2 = jnp.sqrt(1.0 + delta * theta_prev) * eta_prev
    eta = jnp.minimum(cand1, cand2)
    theta = eta / eta_prev
    return eta, theta


def delta_sgd_update(params, grads, state: DeltaSGDState, *, gamma: float,
                     delta: float, eta0: float, use_pallas: bool = False):
    """One local step: compute η via Eq. (4) (η₀ on the first local step),
    apply x ← x − η·g, and roll the state."""
    groupwise = isinstance(state.eta, dict)
    first = (state.k == 0)

    if groupwise:
        dg = {k: _global_norm(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                grads[k], state.prev_grads[k]))
              for k in params}
        gn = _group_norms(grads)
        new_eta, new_theta = {}, {}
        for k in params:
            dx = state.eta[k] * state.prev_grad_norm[k]
            e, t = _eta_rule(state.eta[k], state.theta[k], dx, dg[k],
                             gamma, delta)
            new_eta[k] = jnp.where(first, jnp.asarray(eta0, jnp.float32), e)
            new_theta[k] = jnp.where(first, state.theta[k], t)
        new_params = {k: jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - new_eta[k] * g.astype(jnp.float32)).astype(p.dtype),
            params[k], grads[k]) for k in params}
        new_state = DeltaSGDState(grads, new_eta, new_theta, gn, state.k + 1)
        return new_params, new_state

    if use_pallas:
        from repro.kernels.delta_sgd import ops as dsgd_ops
        return dsgd_ops.fused_delta_sgd_update(
            params, grads, state, gamma=gamma, delta=delta, eta0=eta0)

    # ‖x_k − x_{k-1}‖ = η_{k-1}·‖g_{k-1}‖ for SGD updates
    dx_norm = state.eta * state.prev_grad_norm
    # difference in f32 (exact for bf16 inputs) — matches the kernel paths
    dg_norm = _global_norm(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        grads, state.prev_grads))
    eta, theta = _eta_rule(state.eta, state.theta, dx_norm, dg_norm,
                           gamma, delta)
    eta = jnp.where(first, jnp.asarray(eta0, jnp.float32), eta)
    theta = jnp.where(first, state.theta, theta)
    eta = jnp.minimum(eta, ETA_CLAMP)   # same ceiling as the flat engines
    grad_norm = _global_norm(grads)
    new_params = jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - eta * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)
    return new_params, DeltaSGDState(grads, eta, theta, grad_norm,
                                     state.k + 1)


# --------------------------------------------------------------------------
# flat engine: all C clients' Δ-SGD state on (C, N/128, 128) slabs
# --------------------------------------------------------------------------

class FlatDeltaSGDState(NamedTuple):
    prev_grads: jax.Array       # (C, N/128, 128) previous gradients,
                                # f32, raw (a latched lane's may be
                                # non-finite: it reaches nothing)
    eta: jax.Array              # (C,) per-client step size
    theta: jax.Array            # (C,) η_k / η_{k-1}
    prev_grad_norm: jax.Array   # (C,)
    k: jax.Array                # local step counter (shared, resets/round)
    # numerical-guard outcomes (None on legacy 5-field constructions):
    valid: Optional[jax.Array] = None   # (C,) bool: lane still healthy —
                                        # LATCHES off on a non-finite
                                        # norm for the rest of the round
    clips: Optional[jax.Array] = None   # (C,) int32: η-clamp hits


def flat_delta_sgd_init(num_clients: int, layout: flatlib.FlatLayout, *,
                        eta0: float, theta0: float) -> FlatDeltaSGDState:
    with jax.named_scope("delta_sgd"):
        C = num_clients
        return FlatDeltaSGDState(
            jnp.zeros(flatlib.slab_shape(C, layout), jnp.float32),
            jnp.full((C,), eta0, jnp.float32),
            jnp.full((C,), theta0, jnp.float32),
            jnp.zeros((C,), jnp.float32),
            jnp.asarray(0, jnp.int32),
            jnp.ones((C,), bool),
            jnp.zeros((C,), jnp.int32))


def _guard(eta, dg_norm, grad_norm, valid_prev):
    """In-step numerical guard: non-finite norms drop the lane (η=0 via
    the activity mask, client excluded this round — ``valid`` latches)
    and runaway η is clamped to ETA_CLAMP. ``jnp.minimum`` against the
    finite ceiling and the all-True masks downstream are bit-exact
    identities on healthy lanes, so the guard is ALWAYS on.

    Returns (eta, valid, clip_hit). NaN η compares False against the
    ceiling, so a poisoned lane counts as a NaN-guard trip, not a clip.
    """
    finite = jnp.isfinite(dg_norm) & jnp.isfinite(grad_norm)
    valid = finite if valid_prev is None else (valid_prev & finite)
    clip_hit = eta > ETA_CLAMP
    return jnp.minimum(eta, ETA_CLAMP), valid, clip_hit


def _mask_inactive(active, eta, theta, grad_norm, state):
    """Heterogeneous-K lane masking (repro.federation.heterogeneity): a
    client past its K_c budget applies η=0 (P untouched — the bf16 round
    mask is idempotent on already-rounded lanes) and keeps its scalar
    state frozen. ``prev_grads`` is NOT re-selected: inactivity is a
    terminal prefix within the round, so a frozen client's stale norm
    state can never reach an applied update — skipping the slab select
    keeps the step at exactly two fused kernel launches.

    Returns (eta_applied, eta, theta, grad_norm)."""
    eta_applied = jnp.where(active, eta, jnp.float32(0.0))
    eta = jnp.where(active, eta, state.eta)
    theta = jnp.where(active, theta, state.theta)
    grad_norm = jnp.where(active, grad_norm, state.prev_grad_norm)
    return eta_applied, eta, theta, grad_norm


def flat_delta_sgd_step(P: jax.Array, G: jax.Array,
                        state: FlatDeltaSGDState, *, gamma: float,
                        delta: float, eta0: float,
                        mask: Optional[jax.Array] = None,
                        active: Optional[jax.Array] = None,
                        backend: str = "pallas",
                        interpret: Optional[bool] = None):
    """One Δ-SGD local step for ALL clients on client slabs.

    P, G: (C, N/128, 128) client slabs of params and grads (the form
    ``flat.pack_batched`` writes and the scan carries), ``mask`` the
    optional (N/128, 128) round mask. Exactly two Pallas launches
    (batched_norms + batched_apply) regardless of leaf count and client
    count, and no other pass over the slab; ``backend="xla"`` runs the
    same math as fused jnp ops for meshed callers. ``active`` is an
    optional (C,) bool lane mask for heterogeneous step counts: inactive
    clients apply η=0 and keep their state frozen, at no extra launch
    cost.

    A lane whose norms go non-finite latches ``valid`` off; the apply
    zeroes its gradient inside the kernel (η = 0 alone cannot stop a
    NaN: 0·NaN = NaN), so its P stays bit-identical. ``prev_grads``
    rolls to the raw G: a latched lane's η stays 0 and its scalars stay
    frozen for the rest of the round, so its non-finite ``prev_grads``
    reaches no applied update and no state. Healthy lanes are untouched
    by either. Returns (new_P, new_state).
    """
    with jax.named_scope("delta_sgd"):
        first = (state.k == 0)
        if backend == "pallas":
            from repro.kernels import interpret_mode
            from repro.kernels.delta_sgd import delta_sgd as k
            interpret = interpret_mode(interpret)
            dg2, gg2 = k.batched_norms(G, state.prev_grads,
                                       interpret=interpret)
        else:
            from repro.kernels.delta_sgd import ref as kref
            dg2, gg2 = kref.batched_norms_ref(G, state.prev_grads)
        dg_norm = jnp.sqrt(dg2)
        grad_norm = jnp.sqrt(gg2)
        dx_norm = state.eta * state.prev_grad_norm
        eta, theta = _eta_rule(state.eta, state.theta, dx_norm, dg_norm,
                               gamma, delta)
        eta = jnp.where(first, jnp.asarray(eta0, jnp.float32), eta)
        theta = jnp.where(first, state.theta, theta)
        eta, valid, clip_hit = _guard(eta, dg_norm, grad_norm, state.valid)
        act = valid if active is None else (active & valid)
        eta_applied, eta, theta, grad_norm = _mask_inactive(
            act, eta, theta, grad_norm, state)
        clips = (jnp.zeros_like(valid, jnp.int32) if state.clips is None
                 else state.clips) + (clip_hit & act).astype(jnp.int32)
        if backend == "pallas":
            new_P = k.batched_apply(P, G, eta_applied, valid=valid,
                                    mask=mask, interpret=interpret)
        else:
            new_P = kref.batched_apply_ref(P, G, eta_applied, mask,
                                           valid=valid)
        return new_P, FlatDeltaSGDState(G, eta, theta, grad_norm,
                                        state.k + 1, valid, clips)


# --------------------------------------------------------------------------
# sharded flat engine: the client slab stays mesh-sharded end to end
# --------------------------------------------------------------------------

def _axis_names(entry):
    """Flatten one PartitionSpec entry to a tuple of mesh axis names."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def flat_delta_sgd_step_sharded(P: jax.Array, G: jax.Array,
                                state: FlatDeltaSGDState, *, gamma: float,
                                delta: float, eta0: float, mesh, pspec,
                                mask: Optional[jax.Array] = None,
                                active: Optional[jax.Array] = None,
                                backend: str = "xla",
                                interpret: Optional[bool] = None):
    """One Δ-SGD local step on a mesh-sharded (C, N/128, 128) slab.

    ``pspec`` is ``FederationSpec.flat_spec(mesh)`` — clients over
    ``pspec[0]``, the flat param dim over ``pspec[1]``; the slab's rows
    are sharded like N and its lanes are whole (the layout must have
    been built with ``shards=FederationSpec.flat_shards(mesh)`` so each
    local slab stays row-block aligned). Per device: the kernel pair
    runs on the local (C_loc, N_loc/128, 128) slab; the per-client dual
    norms finish with a single psum over the N-shard axes, so η is exact
    while N is never gathered. ``active`` is the optional (C,)
    heterogeneous-K lane mask (sharded like the other per-client
    vectors). Returns (new_P, new_state) with unchanged shardings.
    """
    from jax.sharding import PartitionSpec as PS
    ca = pspec[0] if len(pspec) > 0 else None
    na = pspec[1] if len(pspec) > 1 else None
    na_names = _axis_names(na)
    buf, vec, rep = PS(ca, na, None), PS(ca), PS()
    if backend == "pallas":
        from repro.kernels import interpret_mode
        interpret = interpret_mode(interpret)
    with_mask = mask is not None
    with_active = active is not None

    def local_step(P_l, G_l, Gp_l, eta, theta, pgn, k_ctr, valid_p,
                   clips_p, *rest):
        rest = list(rest)
        mask_l = rest.pop(0) if with_mask else None
        active_l = rest.pop(0) if with_active else None
        if backend == "pallas":
            from repro.kernels.delta_sgd import delta_sgd as k
            dg2, gg2 = k.batched_norms(G_l, Gp_l, interpret=interpret)
        else:
            from repro.kernels.delta_sgd import ref as kref
            dg2, gg2 = kref.batched_norms_ref(G_l, Gp_l)
        if na_names:
            dg2 = jax.lax.psum(dg2, na_names)
            gg2 = jax.lax.psum(gg2, na_names)
        dg_norm = jnp.sqrt(dg2)
        grad_norm = jnp.sqrt(gg2)
        dx_norm = eta * pgn
        eta_n, theta_n = _eta_rule(eta, theta, dx_norm, dg_norm,
                                   gamma, delta)
        first = (k_ctr == 0)
        eta_n = jnp.where(first, jnp.asarray(eta0, jnp.float32), eta_n)
        theta_n = jnp.where(first, theta, theta_n)
        eta_n, valid_n, clip_hit = _guard(eta_n, dg_norm, grad_norm,
                                          valid_p)
        act = valid_n if active_l is None else (active_l & valid_n)
        st = FlatDeltaSGDState(Gp_l, eta, theta, pgn, k_ctr)
        eta_applied, eta_n, theta_n, grad_norm = _mask_inactive(
            act, eta_n, theta_n, grad_norm, st)
        clips_n = clips_p + (clip_hit & act).astype(jnp.int32)
        if backend == "pallas":
            new_P = k.batched_apply(P_l, G_l, eta_applied, valid=valid_n,
                                    mask=mask_l, interpret=interpret)
        else:
            new_P = kref.batched_apply_ref(P_l, G_l, eta_applied, mask_l,
                                           valid=valid_n)
        return new_P, eta_n, theta_n, grad_norm, valid_n, clips_n

    with jax.named_scope("delta_sgd"):
        C = P.shape[0]
        valid = (state.valid if state.valid is not None
                 else jnp.ones((C,), bool))
        clips = (state.clips if state.clips is not None
                 else jnp.zeros((C,), jnp.int32))
        ins = [P, G, state.prev_grads, state.eta, state.theta,
               state.prev_grad_norm, state.k, valid, clips]
        specs = [buf, buf, buf, vec, vec, vec, rep, vec, vec]
        if with_mask:
            ins.append(mask)
            specs.append(PS(na, None))
        if with_active:
            ins.append(active)
            specs.append(vec)
        # replication checking off: the Pallas kernels carry no rules for it
        fn = jax.shard_map(local_step, mesh=mesh, in_specs=tuple(specs),
                           out_specs=(buf, vec, vec, vec, vec, vec),
                           check_vma=False)
        new_P, eta, theta, grad_norm, valid, clips = fn(*ins)
        return new_P, FlatDeltaSGDState(G, eta, theta, grad_norm,
                                        state.k + 1, valid, clips)
