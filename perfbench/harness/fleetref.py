"""Plain reference of what a fleet round draws, and of the bookkeeping
the client arena keeps, from the keys the program documents.

The fleet regime registers many more clients than a round trains. Each
round t, with the scenario's seed s and the data seed d:

* cohort: C distinct registered ids by Gumbel-top-k over the Zipf
  log-weights log w_i = -z log(i + 1), i < M, with the key
  ``fold_in(key(s), t)`` (the Gumbel-top-k trick draws without
  replacement with P(i) proportional to w_i);
* step budgets: K_c uniform in [K_min, K_max], ``randint`` on the key
  ``fold_in(fold_in(key(s), t), 1)``, K_min = max(1, round(f K_max));
* partitions: P partitions of n examples each by the latent Dirichlet
  rule (Hsu et al. 2019) from ``default_rng(d)``: each class's example
  ids shuffled, class by class; then for each partition a label mix
  q ~ Dirichlet, alpha per class, class counts ~ Multinomial(n, q), the
  next unused ids of each class in class order (a class that runs out
  makes up the shortfall by a draw with replacement from all of its
  ids), and the partition's ids shuffled. The concentration is
  alpha K p, p the uniform prior over the K classes, computed in that
  order: alpha per class up to rounding, and the rounding decides the
  draw;
* examples: one generator ``default_rng([d + 17, t])`` per round; for
  each cohort slot in order it draws K_max x b distinct example ids of
  the slot's partition (registered id i trains on partition i mod P),
  whatever that client's budget;
* arena: every drawn client's participation count goes up by one and
  its last round becomes t; no other client's row changes.

Written from that description; it imports nothing of the program and
takes nothing it made: the examples come from the traffic's generator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def round_key(seed, t):
    return jax.random.fold_in(jax.random.key(seed), t)


def cohort(seed, t, num_registered, size, zipf_s):
    """(size,) int32 registered ids drawn for round ``t``."""
    logw = -zipf_s * jnp.log(jnp.arange(1, num_registered + 1,
                                        dtype=jnp.float32))
    g = jax.random.gumbel(round_key(seed, t), (num_registered,),
                          jnp.float32)
    _, ids = jax.lax.top_k(logw + g, size)
    return np.asarray(ids, np.int32)


def k_min(k_max, k_min_frac):
    return max(1, min(k_max, int(round(k_min_frac * k_max))))


def step_budgets(seed, t, size, k_max, k_min_frac):
    """(size,) int32 local step budgets K_c for round ``t``."""
    key = jax.random.fold_in(round_key(seed, t), 1)
    return np.asarray(jax.random.randint(
        key, (size,), k_min(k_max, k_min_frac), k_max + 1, jnp.int32))


def partitions(y, num_partitions, alpha, per_partition, data_seed):
    """``num_partitions`` int64 arrays of ``per_partition`` example ids
    into ``y``, the labels, by the latent Dirichlet rule."""
    rng = np.random.default_rng(data_seed)
    classes = int(y.max()) + 1
    pools = [np.flatnonzero(y == c) for c in range(classes)]
    for pool in pools:
        rng.shuffle(pool)
    used = [0] * classes
    out = []
    for _ in range(num_partitions):
        prior = np.full(classes, 1.0 / classes)
        mix = rng.dirichlet(alpha * prior * classes)
        picks = []
        for c, n in enumerate(rng.multinomial(per_partition, mix)):
            got = pools[c][used[c]:used[c] + n]
            if len(got) < n:
                got = np.concatenate([got,
                                      rng.choice(pools[c], n - len(got))])
            used[c] += n
            picks.append(got)
        ids = np.concatenate(picks)
        rng.shuffle(ids)
        out.append(ids.astype(np.int64))
    return out


def example_ids(data_seed, t, ids, partitions, k_max, batch):
    """(len(ids), k_max, batch) example ids of round ``t``'s cohort."""
    rng = np.random.default_rng([int(data_seed) + 17, int(t)])
    n = k_max * batch
    out = []
    for i in ids:
        part = partitions[int(i) % len(partitions)]
        take = rng.choice(part, size=n, replace=len(part) < n)
        out.append(take.reshape(k_max, batch))
    return np.stack(out)


def arena_book(cohorts, rounds, num_registered):
    """(rounds_seen, last_round) of every registered client after the
    given rounds, from a fresh arena (0 and -1)."""
    seen = np.zeros(num_registered, np.int64)
    last = np.full(num_registered, -1, np.int64)
    for t, ids in zip(rounds, cohorts):
        seen[ids] += 1
        last[ids] = t
    return seen, last


def cohort_gap(prog_ids, ref_ids):
    """The number of (round, slot) ids that differ."""
    return int(sum(np.sum(np.asarray(p) != np.asarray(r))
                   for p, r in zip(prog_ids, ref_ids)))


def arena_gap(prog_seen, prog_last, ref_seen, ref_last):
    """The number of registered clients whose participation count or
    last round differs."""
    bad = ((np.asarray(prog_seen) != ref_seen)
           | (np.asarray(prog_last) != ref_last))
    return int(bad.sum())
