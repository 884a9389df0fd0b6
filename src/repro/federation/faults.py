"""Fault injection + robust server aggregation (the chaos axis).

No fleet of real clients returns only finite, timely, honest updates.
This module gives the scenario engine a deterministic FAULT axis and the
round tail a ROBUST-AGGREGATION ladder so the packed flat engine keeps
training through the failure modes the FL literature catalogues:

  * ``FaultModel`` — per-round, per-client fault draws, all flowing from
    ``fold_in(round_key, 4)`` exactly like the compute/staleness/
    bandwidth axes (repro.federation.scenarios), so host pipeline and
    jitted round agree and every fault is reproducible from (seed,
    round). Four failure modes, each lowered as per-client LANE state
    (η=0 lanes / lane-wise delta scaling) so the flat engine's
    2-launches-per-local-step invariant survives:
      - drop-mid-round: the client dies after ``drop_step < K`` local
        steps and never reports (lane goes inactive, client excluded);
      - NaN/Inf gradient corruption: from a drawn local step on, the
        client's packed gradient lanes are non-finite — caught by the
        in-step numerical guards (repro.core.delta_sgd), which zero the
        lane's η, sanitize its gradient, and latch its ``valid`` flag;
      - byzantine delta corruption: the client's reported round delta is
        scaled/sign-flipped by ``byzantine_scale`` (e.g. −10×) — NOT
        detectable client-side; the robust aggregators defend;
      - async over-staleness: the update arrives staler than the
        scenario's accepted bound and the server rejects it.

  * ``RobustAgg`` — the server-side aggregation ladder over packed
    (C, N) client deltas: ``mean`` (valid-masked mean), ``clip``
    (per-client l2 delta-norm clipping, then mean), ``trimmed``
    (coordinate-wise trimmed mean) and ``median`` (coordinate-wise
    median). Invalid clients (guard-tripped, dropped, rejected) are
    excluded: they carry zero weight under mean/clip and contribute a
    zero delta to the order-statistic aggregators. Under meshes the
    ladder runs inside ``shard_map`` strictly before/with the
    client-mean psum: clip norms finish with a tiny (C_loc,) psum over
    the N-shard axes, and trimmed/median aggregate SHARD-LOCALLY over
    each device's C_loc clients before a (N_loc,) mean across client
    shards (bucketed robust aggregation, Karimireddy et al. style) — so
    the only client-crossing payloads stay (N_loc,)-sized and PR 4's
    no-full-precision-delta wire guarantee keeps holding
    (repro.sharding.hlo.assert_no_fullprec_delta_collective, now with a
    tightenable payload bound).

With no faults drawn and ``kind="mean"`` the round engine never routes
through this module — the fault-free mean path stays bit-exact against
the golden trajectories by construction.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_RATE_FIELDS = ("drop_rate", "nan_rate", "byzantine_rate",
                "overstale_rate")


class FaultLanes(NamedTuple):
    """One round's per-client fault draws (all (C,))."""
    drop_step: jax.Array    # int32: local step the client dies at;
                            # k_max = runs to completion
    nan_step: jax.Array     # int32: first local step with non-finite
                            # grads; k_max = clean
    byzantine: jax.Array    # bool: delta scaled by byzantine_scale
    overstale: jax.Array    # bool: async update arrives over-stale


@dataclass(frozen=True)
class FaultModel:
    """Deterministic per-round fault injection rates (scenario axis)."""
    drop_rate: float = 0.0          # P(client drops mid-round)
    nan_rate: float = 0.0           # P(client's grads go non-finite)
    byzantine_rate: float = 0.0     # P(client's delta is corrupted)
    byzantine_scale: float = -10.0  # multiplier on corrupted deltas
    overstale_rate: float = 0.0     # P(async update arrives over-stale)
    overstale: int = 16             # staleness assigned to those updates

    def __post_init__(self):
        for f in _RATE_FIELDS:
            v = getattr(self, f)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{f} must be in [0, 1], got {v}")

    @property
    def active(self) -> bool:
        return any(getattr(self, f) > 0.0 for f in _RATE_FIELDS)

    def draw(self, key, num_clients: int, k_max: int) -> FaultLanes:
        """Per-client lanes for one round (jit-safe). Sub-keys are
        folded per fault mode so adding a mode never perturbs the
        others' draws."""
        C = num_clients
        ks = [jax.random.fold_in(key, i) for i in range(4)]
        full = jnp.full((C,), k_max, jnp.int32)

        if self.drop_rate > 0.0:
            dropped = jax.random.bernoulli(
                jax.random.fold_in(ks[0], 0), self.drop_rate, (C,))
            # die strictly mid-round: after >= 1 step when K allows it
            # (K == 1 drops before the only step — nothing to report)
            step = jax.random.randint(
                jax.random.fold_in(ks[0], 1), (C,), 1, max(k_max, 2),
                jnp.int32)
            step = jnp.minimum(step, k_max - 1)
            drop_step = jnp.where(dropped, step, full)
        else:
            drop_step = full

        if self.nan_rate > 0.0:
            corrupt = jax.random.bernoulli(
                jax.random.fold_in(ks[1], 0), self.nan_rate, (C,))
            step = jax.random.randint(
                jax.random.fold_in(ks[1], 1), (C,), 0, k_max, jnp.int32)
            nan_step = jnp.where(corrupt, step, full)
        else:
            nan_step = full

        byz = (jax.random.bernoulli(ks[2], self.byzantine_rate, (C,))
               if self.byzantine_rate > 0.0
               else jnp.zeros((C,), bool))
        over = (jax.random.bernoulli(ks[3], self.overstale_rate, (C,))
                if self.overstale_rate > 0.0
                else jnp.zeros((C,), bool))
        return FaultLanes(drop_step, nan_step, byz, over)


# ---------------------------------------------------------------------------
# robust server aggregation over packed (C, N) client deltas
# ---------------------------------------------------------------------------

ROBUST_AGG_KINDS = ("mean", "clip", "trimmed", "median")


@dataclass(frozen=True)
class RobustAgg:
    """Server aggregation rung over per-client round deltas."""
    kind: str = "mean"          # mean|clip|trimmed|median
    clip_norm: float = 10.0     # clip: max per-client l2 delta norm
    trim_frac: float = 0.2      # trimmed: fraction cut at EACH end

    def __post_init__(self):
        if self.kind not in ROBUST_AGG_KINDS:
            raise KeyError(f"unknown robust aggregation {self.kind!r}; "
                           f"kinds: {ROBUST_AGG_KINDS}")
        if not 0.0 <= self.trim_frac < 0.5:
            raise ValueError(
                f"trim_frac must be in [0, 0.5), got {self.trim_frac}")
        if self.clip_norm <= 0.0:
            raise ValueError(f"clip_norm must be > 0, got {self.clip_norm}")

    @property
    def robust(self) -> bool:
        return self.kind != "mean"

    def trim_count(self, num_clients: int) -> int:
        """Static per-end trim count: floor(trim_frac·C), clamped so at
        least one row survives. ``median`` trims to the middle 1 (odd C)
        or 2 (even C) rows — the coordinate-wise median."""
        C = num_clients
        if self.kind == "median":
            return (C - 1) // 2
        return min(int(self.trim_frac * C), (C - 1) // 2)


def _masked_mean(delta, vw):
    """Σ_c vw_c·Δ_c / Σ_c vw_c with a zero-safe denominator."""
    den = jnp.maximum(jnp.sum(vw), 1e-12)
    return jnp.tensordot(vw, delta, axes=(0, 0)) / den


def _clip_factors(norms, clip_norm):
    """min(1, clip/‖Δ_c‖) per client — zero-delta rows pass through."""
    return jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))


def _sorted_window_mean(zeroed, t: int):
    """Coordinate-wise mean of the sorted rows [t, C−t) — the trimmed
    mean (and, via RobustAgg.trim_count, the median). Invalid rows were
    zeroed by the caller: a zero delta is the 'no contribution' element
    and keeps the sort total over a static C."""
    C = zeroed.shape[0]
    s = jnp.sort(zeroed, axis=0)
    return jnp.mean(s[t:C - t], axis=0)


def robust_aggregate(delta, spec: RobustAgg, valid=None, *,
                     weights=None, backend: str = "xla",
                     interpret: Optional[bool] = None):
    """Aggregate packed (C, N) client deltas -> ((N,) delta, info dict).

    ``valid`` is the per-client (C,) bool survivor mask (guards + drops
    + staleness rejection): invalid clients are excluded — zero weight
    under mean/clip, a zeroed row under trimmed/median. ``weights`` are
    optional client weights (size-weighted FedAvg); order-statistic
    rungs ignore them (a weighted trimmed mean is not a sum — the
    bucketed sharded variant documents the same restriction).
    ``backend="pallas"`` routes trimmed/median through the fused
    bitonic-sort kernel (repro.kernels.robust_agg)."""
    C = delta.shape[0]
    v = (valid.astype(jnp.float32) if valid is not None
         else jnp.ones((C,), jnp.float32))
    zeroed = delta * v[:, None]
    info = {}
    if spec.kind in ("trimmed", "median"):
        t = spec.trim_count(C)
        if backend == "pallas":
            from repro.kernels import interpret_mode
            from repro.kernels.robust_agg import robust_agg as k
            agg = k.batched_trimmed_mean(zeroed, t,
                                         interpret=interpret_mode(interpret))
        else:
            agg = _sorted_window_mean(zeroed, t)
        return agg, info
    vw = v if weights is None else v * weights.astype(jnp.float32)
    if spec.kind == "clip":
        norms = jnp.sqrt(jnp.sum(zeroed * zeroed, axis=1))
        factors = _clip_factors(norms, spec.clip_norm)
        info["agg_clip_rate"] = (jnp.sum((factors < 1.0) * v)
                                 / jnp.maximum(jnp.sum(v), 1.0))
        zeroed = zeroed * factors[:, None]
    return _masked_mean(zeroed, vw), info


def robust_aggregate_sharded(delta, spec: RobustAgg, valid, *, mesh,
                             pspec, weights=None):
    """Mesh-native robust aggregation: the (C, N) delta buffer stays
    sharded per ``pspec`` (= FederationSpec.flat_spec(mesh)) and the
    ladder runs inside ``shard_map``. clip's per-client norms finish
    with ONE (C_loc,) psum over the N-shard axes; trimmed/median run
    shard-locally over each device's C_loc clients and the (N_loc,)
    shard aggregates are averaged across client shards (bucketed robust
    aggregation — with one client per shard this degenerates to the
    mean, so production specs should stack >= 2 clients per shard, the
    same requirement the wire-boundary HLO check has). No per-client
    data ever crosses the client shard boundary. Returns
    ((N,) delta, info dict)."""
    from jax.sharding import PartitionSpec as PS
    from repro.core.delta_sgd import _axis_names
    ca = pspec[0] if len(pspec) > 0 else None
    na = pspec[1] if len(pspec) > 1 else None
    c_names, na_names = _axis_names(ca), _axis_names(na)

    def psum_c(x):
        return jax.lax.psum(x, c_names) if c_names else x

    with_w = weights is not None

    def local(d_l, v_l, *rest):
        w_l = rest[0] if with_w else None
        vf = v_l.astype(jnp.float32)
        zeroed = d_l * vf[:, None]
        if spec.kind in ("trimmed", "median"):
            t = spec.trim_count(zeroed.shape[0])
            shard_agg = _sorted_window_mean(zeroed, t)
            n_shards = psum_c(jnp.float32(1.0))
            return psum_c(shard_agg) / n_shards, jnp.float32(0.0)
        vw = vf if w_l is None else vf * w_l.astype(jnp.float32)
        clip_rate = jnp.float32(0.0)
        if spec.kind == "clip":
            n2 = jnp.sum(zeroed * zeroed, axis=1)
            if na_names:
                n2 = jax.lax.psum(n2, na_names)
            factors = _clip_factors(jnp.sqrt(n2), spec.clip_norm)
            nv = jnp.maximum(psum_c(jnp.sum(vf)), 1.0)
            clip_rate = psum_c(jnp.sum((factors < 1.0) * vf)) / nv
            zeroed = zeroed * factors[:, None]
        part = jnp.tensordot(vw, zeroed, axes=(0, 0))
        den = jnp.maximum(psum_c(jnp.sum(vw)), 1e-12)
        return psum_c(part) / den, clip_rate

    ins = [delta, valid] + ([weights] if with_w else [])
    specs = [PS(ca, na), PS(ca)] + ([PS(ca)] if with_w else [])
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=(PS(na), PS()), check_vma=False)
    agg, clip_rate = fn(*ins)
    info = {}
    if spec.kind == "clip":
        info["agg_clip_rate"] = clip_rate
    return agg, info
