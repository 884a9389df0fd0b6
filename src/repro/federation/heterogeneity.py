"""Compute heterogeneity: per-client local step counts K_c ≤ K_max.

Real cohorts do not run in lockstep — "the computing power of each client
can greatly vary" is half of the paper's motivation for a tuning-free
client optimizer. The scenario engine models it as a per-round draw of
step counts ``K_c ∈ [K_min, K_max]`` per client, lowered onto the round
engines as **per-step lane masks**: the flat client slab keeps its
fixed shape through the K_max-step ``lax.scan``, and a client that has
finished its K_c steps simply rides along with η forced to 0 — its lanes
are dead but cost no extra kernel launches (the fused apply already takes
a per-client η vector, so masking is free).

Speed models:
  fixed      — K_c = K_max for everyone (the synchronous baseline; this
               model produces NO masks, so the engines take the exact
               seed code path).
  uniform    — K_c ~ U{K_min, …, K_max} iid per client per round.
  stragglers — a Bernoulli(straggler_frac) subset runs only K_min steps,
               the rest run K_max (the classic fast/slow device split).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

SPEED_MODELS = ("fixed", "uniform", "stragglers")


@dataclass(frozen=True)
class SpeedModel:
    kind: str = "fixed"
    k_min_frac: float = 0.25     # K_min = max(1, round(k_min_frac·K_max))
    straggler_frac: float = 0.3  # P(slow) under ``stragglers``

    def __post_init__(self):
        if self.kind not in SPEED_MODELS:
            raise KeyError(f"unknown speed model {self.kind!r}")

    @property
    def heterogeneous(self) -> bool:
        return self.kind != "fixed"

    def k_min(self, k_max: int) -> int:
        return max(1, min(k_max, int(round(self.k_min_frac * k_max))))

    def draw(self, key, num_clients: int, k_max: int) -> jax.Array:
        """(C,) int32 step counts in [K_min, K_max] (all K_max if fixed)."""
        if self.kind == "fixed":
            return jnp.full((num_clients,), k_max, jnp.int32)
        k_min = self.k_min(k_max)
        if self.kind == "uniform":
            return jax.random.randint(key, (num_clients,), k_min,
                                      k_max + 1, jnp.int32)
        slow = jax.random.bernoulli(key, self.straggler_frac,
                                    (num_clients,))
        return jnp.where(slow, jnp.int32(k_min), jnp.int32(k_max))


def step_active(step_idx, step_counts: jax.Array) -> jax.Array:
    """(C,) bool: is each client still running at local step ``step_idx``?

    Step counts are PREFIX masks — client c runs steps 0..K_c−1 and then
    stays frozen, so inactivity is terminal within a round. The engines
    rely on this: a frozen client's stale Δ-SGD norm state can never leak
    back into an applied update, because its η is forced to 0 at every
    later step.
    """
    return jnp.asarray(step_idx, jnp.int32) < step_counts


def active_mask(step_counts: jax.Array, k_max: int) -> jax.Array:
    """(C, K_max) f32 mask, mask[c, k] = 1.0 iff k < K_c. Used to weight
    per-step losses so metrics only average over steps that really ran."""
    k = jnp.arange(k_max, dtype=jnp.int32)
    return (k[None, :] < step_counts[:, None]).astype(jnp.float32)
