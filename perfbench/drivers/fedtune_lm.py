"""Federated fine-tune cells of a decoder-only language model: the
program's own fused block driver (``launch/train._run_fused``) over
``core.make_fl_loop`` with Δ-SGD clients, a FedAvg server and telemetry
off, as ``drivers/fedtune.py`` runs whisper's cell, on batches of token
ids alone.

The traffic draws token ids Zipf over the configuration's vocabulary
(the mix's ``zipf_exponent``; rank r with odds r^-s, ranks mapped to
ids by a permutation from the seed), so that an MoE router sees the
uneven token mix of natural text. The reference is the configuration's
``loss(params, batch, cfg)``; the switches its ``flags`` puts in the
batch make the calibration's planted faults.

Besides the fine-tune's counts, the result carries the program's MoE
counters over the window (``moe_pairs``: token–expert pairs routed to
the held experts, ``moe_load_max``: the largest held expert's pairs in
a local step's layer), and the operations of a round computed from the
routed pairs.
"""
from __future__ import annotations

import math
import time

import numpy as np

from drivers import fedtune
from harness import fedref, traffic


def lm_pool(seed, mix, vocab):
    """``mix["pool_rounds"]`` rounds of client batches, each a dict of
    host arrays with leaves (C, K, b, seq): ``tokens`` and ``labels``
    (the tokens shifted by one) int32."""
    C, K, b = mix["clients"], mix["local_steps"], mix["batch"]
    S = mix["seq"]
    rng = traffic.rng_for(seed, 1)
    odds = np.arange(1, vocab + 1, dtype=np.float64) ** -mix["zipf_exponent"]
    ids = rng.permutation(vocab).astype(np.int32)
    pool = []
    for _ in range(mix["pool_rounds"]):
        toks = ids[rng.choice(vocab, size=(C, K, b, S + 1),
                              p=odds / odds.sum())]
        pool.append({"tokens": toks[..., :-1], "labels": toks[..., 1:]})
    return pool


class Build(fedtune.Build):
    """``drivers/fedtune.Build`` with token-only batches and the
    language model's reference. The state between calls of the block
    driver lives in ``self.state`` alone, so that while a block runs the
    device holds its flat carry and not also the parameter tree it was
    packed from (``launch/train._run_fused`` lets go of the tree). The
    program's matmuls run at the configuration's ``matmul_precision``
    where it states one, JAX's default for float32 where not."""

    state = None

    def pool(self, seed):
        return lm_pool(seed, self.mix, self.cfg["vocab_size"])

    def _take_state(self):
        state, self.state = self.state, None
        return state

    def advance(self, rounds, pool, spans, rows=None):
        """``rounds`` rounds of ``self.state`` through the program's
        block driver; the state goes in as a temporary, so the driver
        holds the only reference to its tree."""
        import jax
        import jax.numpy as jnp
        from repro.launch.train import _run_fused

        def stage_block(round0, n):
            host = traffic.stack_rounds(pool, round0, n)
            return {k: jnp.asarray(v) for k, v in host.items()}, None

        def on_round(t, row):
            if rows is not None:
                rows.append({k: float(np.asarray(v))
                             for k, v in row.items() if np.ndim(v) == 0})

        with jax.default_matmul_precision(
                self.cfg.get("matmul_precision")):
            self.state = _run_fused(self.driver_args, self.loop,
                                    self._take_state(), rounds,
                                    stage_block, on_round, spans=spans)
        jax.block_until_ready(self.state.params)

    def first_block(self, seed, pool, spans):
        """Weights from the seed, then the first block: the program's
        loss and step-size rows and its per-leaf change; the state stays
        in ``self.state``. The starting weights are made again from the
        seed for the change, not kept through the block."""
        from repro.core import init_fl_state
        self.state = init_fl_state(self.weights(seed), self.sopt)
        rows = []
        self.advance(self.R, pool, spans, rows)
        change = np.asarray(fedref.change_norms(self.state.params,
                                                self.weights(seed)))
        return rows, change

    def reference(self, seed, pool, *, dtype=None, precision="highest",
                  half_batch=False, fault=None):
        """The reference over the first block's rounds, from the same
        seed and batches; ``dtype``, ``half_batch`` and ``fault`` make
        the calibration's controls."""
        import jax
        import jax.numpy as jnp
        ref, cfg, mix = self.ref, self.cfg, self.mix
        params0 = self.weights(seed)
        if dtype is not None:
            params0 = ref.cast(params0, dtype)

        def loss_fn(p, b):
            return ref.loss(p, b, cfg).astype(jnp.float32)

        lead = (mix["clients"], mix["local_steps"])
        switches = {k: jnp.full(lead, v, jnp.float32)
                    for k, v in ref.flags(fault, half_batch).items()}
        rounds = []
        for r in range(self.R):
            batch = {k: jnp.asarray(v) for k, v in pool[r % len(pool)].items()}
            batch["flags"] = switches
            rounds.append(batch if dtype is None else ref.cast(batch, dtype))
        with jax.default_matmul_precision(precision):
            P, rows, first = fedref.run_rounds(loss_fn, params0, rounds,
                                               self.hyper)
            del params0
            change = np.asarray(fedref.change_norms(P, self.weights(seed)))
        return rows, change, first


def counters(rows):
    """The MoE counters over rounds' metric rows: pairs summed, the
    largest load maxed (zeros without MoE layers)."""
    return {"pairs": sum(r.get("moe_pairs", 0.0) for r in rows),
            "load_max": max((r.get("moe_load_max", 0.0) for r in rows),
                            default=0.0)}


def flops_per_round(cell, pairs_per_round):
    """Model operations of a round: forward and backward of every
    client step, the held experts on the pairs the router sent them."""
    mix, cfg = cell.mix, cell.config
    steps = mix["clients"] * mix["local_steps"]
    return steps * cell.reference.train_flops(
        cfg, mix["seq"], mix["batch"], pairs=pairs_per_round / steps)


def run(cell, args, ctx):
    """One run of a language-model fine-tune cell; see the module doc."""
    import jax
    from harness.common import memory_peak_bytes
    b = Build(cell)
    spans = ctx.spans
    pool = b.pool(args.seed)
    prog_rows, prog_change = b.first_block(args.seed, pool, spans)
    t0 = time.perf_counter()
    b.advance(b.R, pool, spans)
    t_block = time.perf_counter() - t0
    ctx.setup_done()

    seconds = ctx.window_seconds(args.seconds)
    blocks = max(2, math.ceil(seconds / t_block))
    rounds = blocks * b.R
    spans.reset()
    rows = []
    with ctx.window():
        t0 = time.perf_counter()
        b.advance(rounds, pool, spans, rows)
        wall = time.perf_counter() - t0
    peak = memory_peak_bytes(ctx.devices)
    b.state = None

    ref_rows, ref_change, ref_first = b.reference(args.seed, pool)
    nums = fedref.compare(prog_rows, prog_change, ref_rows, ref_change,
                          ref_first)
    moe = counters(rows)
    pairs = moe["pairs"] / rounds
    n_params = int(sum(np.prod(s.shape)
                       for s in jax.tree.leaves(b.shapes)))
    rows128 = b.loop.layout.padded_size // 128
    return {
        "end_to_end": {"rounds_per_s": rounds / wall},
        "compared": {k: nums[k] for k in cell.limits},
        "attempted": rounds, "failed": 0,
        "memory_peak_bytes": peak, "window_s": wall,
        "counts": {"rounds": rounds, "wall_s": wall,
                   "flops_per_round": flops_per_round(cell, pairs),
                   "expert_flops_per_round": 3.0 * cell.reference
                   .expert_flops(cell.config, pairs),
                   "moe_pairs_per_round": pairs,
                   "moe_load_max": moe["load_max"],
                   "pair_bytes_per_round": fedtune.pair_bytes_per_round(
                       cell, n_params),
                   "pair_shape": f"f32[{b.mix['clients']},{rows128},128]",
                   "spans": dict(spans.totals)},
    }
