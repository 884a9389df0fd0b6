"""The one traffic generator: it reads a mix's parameters from its data
file (``perfbench/traffic/<mix>.json``) and makes, from the seed, the
inputs of a run.

Every seed gets the same sizes, so the work of a run does not depend on
the seed; what the seed changes is the token ids and frames
themselves.
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
