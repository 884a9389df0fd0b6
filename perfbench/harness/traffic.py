"""The one traffic generator: it reads a mix's parameters from its data
file (``perfbench/traffic/<mix>.json``) and makes, from the seed, the
inputs of a run.

Every seed gets the same sizes, so the work of a run does not depend on
the seed; what the seed changes is the token ids and frames, or the
examples and the fleet's starting round, themselves.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed, stream):
    """A numpy generator for one named stream of one seed (seeds may be
    any non-negative integer)."""
    return np.random.default_rng([int(seed), int(stream)])


# ---------------------------------------------------------------------------
# federated fine-tune: a pool of distinct per-round client batches
# ---------------------------------------------------------------------------
def fedtune_pool(seed, mix, *, vocab, d_model, frames):
    """``mix["pool_rounds"]`` rounds of client batches, each a dict of
    host arrays with leaves (C, K, b, ...): ``tokens`` and ``labels``
    (b, seq) int32 (labels are the tokens shifted by one) and ``frames``
    (b, frames, d_model) float32 frame embeddings. Rows all differ."""
    C, K, b = mix["clients"], mix["local_steps"], mix["batch"]
    S = mix["seq"]
    rng = rng_for(seed, 1)
    pool = []
    for _ in range(mix["pool_rounds"]):
        toks = rng.integers(0, vocab, (C, K, b, S + 1), dtype=np.int32)
        fr = rng.standard_normal((C, K, b, frames, d_model),
                                 dtype=np.float32)
        pool.append({"tokens": toks[..., :-1], "labels": toks[..., 1:],
                     "frames": fr})
    return pool


def stack_rounds(pool, round0, n):
    """Rounds round0 .. round0+n-1 of the pool (cycling), stacked on a
    leading round axis, as host arrays."""
    rows = [pool[(round0 + i) % len(pool)] for i in range(n)]
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}


# ---------------------------------------------------------------------------
# fleet: the examples every registered client's partition is cut from
# ---------------------------------------------------------------------------
def gaussian_mixture(seed, task):
    """Training examples of a Gaussian-mixture task from the mix's
    ``task`` block: ``classes`` means of norm ``margin`` in ``dim``
    dimensions, ``scale``-wide noise, a random rotation, and with
    ``nonlinear`` the warp tanh(x) + 0.1 x^2. Returns (x (n, dim)
    float32, y (n,) int32), ``n_train`` rows."""
    rng = rng_for(seed, 2)
    k, d = task["classes"], task["dim"]
    means = rng.normal(size=(k, d)).astype(np.float32)
    means *= task["margin"] / np.linalg.norm(means, axis=1, keepdims=True)
    rot = np.linalg.qr(rng.normal(size=(d, d)))[0].astype(np.float32)
    y = rng.integers(0, k, task["n_train"]).astype(np.int32)
    x = means[y] + task["scale"] * rng.normal(
        size=(task["n_train"], d)).astype(np.float32)
    x = x @ rot
    if task["nonlinear"]:
        x = np.tanh(x) + 0.1 * x ** 2
    return x.astype(np.float32), y


def start_round(seed, mix):
    """The round the fleet's state starts at: drawn from the seed, so
    that each seed's rounds draw other cohorts and budgets while the
    compiled program, whose scenario seed is fixed, stays the same."""
    return int(rng_for(seed, 3).integers(0, mix["start_round_max"]))
