"""Federated fine-tune cells: the program's own fused block driver
(``launch/train._run_fused``) over the loop ``launch/train.train_lm``
builds (``core.make_fl_loop``), with Δ-SGD clients, a FedAvg server and
telemetry off, on one chip. The Δ-SGD hyperparameters are the mix's
(``delta_sgd`` in its traffic file): the program and the reference both
take them from there.

Set-up builds one loop and one state, drives them through the first
block from the seed (the rounds the reference follows), runs one more
block to time it, and hands the same loop and state to the window. The
window is one call of the block driver over whole blocks; it ends in
the driver's host sync. Staging each block's batches onto the device
happens inside it, as in a training run.
"""
from __future__ import annotations

import dataclasses
import math
import time
import types

import numpy as np

from harness import fedref, traffic


def program_config(cfg_json):
    """The program's ModelConfig for a configuration file: the named
    architecture with every mapped size taken from the file."""
    from repro.configs import get_config
    prog = cfg_json["program"]
    base = get_config(prog["arch"])
    return dataclasses.replace(base, **{f: cfg_json[k] for f, k
                                        in prog["fields"].items()})


class Build:
    """The program objects of one cell: model, loop, optimizers."""

    def __init__(self, cell):
        import jax
        import jax.numpy as jnp
        from repro.compression import CompressionSpec
        from repro.configs import FLConfig
        from repro.core import (get_client_opt, get_server_opt,
                                make_fl_loop, make_loss)
        from repro.kernels import flat_backend, on_tpu
        from repro.models import build_model
        self.cell, self.mix, self.cfg = cell, cell.mix, cell.config
        self.ref = cell.reference
        mix = self.mix
        self.mcfg = program_config(self.cfg)
        self.model = build_model(self.mcfg, jnp.float32)
        self.hyper = {k: float(mix["delta_sgd"][k])
                      for k in ("gamma", "delta", "eta0", "theta0")}
        fl = FLConfig(local_steps=mix["local_steps"],
                      client_opt="delta_sgd", server_opt="fedavg",
                      num_clients=mix["clients"], **self.hyper)
        self.copt = get_client_opt("delta_sgd", fl, use_pallas=on_tpu())
        self.sopt = get_server_opt("fedavg")
        model = self.model
        loss_fn = make_loss(lambda p, b: model.loss(p, b))
        self.shapes = jax.eval_shape(model.init, jax.random.key(0))
        self.R = mix["rounds_per_call"]
        self.loop = make_fl_loop(
            loss_fn, self.copt, self.sopt, params_like=self.shapes,
            num_rounds=mix["num_rounds"], rounds_per_call=self.R,
            flat=flat_backend(), scenario=None,
            num_clients=mix["clients"],
            compression=CompressionSpec(kind="none"), telemetry=False)
        self.driver_args = types.SimpleNamespace(
            rounds_per_call=self.R, ckpt_dir=None, ckpt_every=10 ** 9,
            profile=0)
        self._init = jax.jit(lambda k: self.ref.init_params(self.shapes,
                                                            k))

    def weights(self, seed):
        import jax
        return self._init(jax.random.key(seed % 2 ** 32))

    def pool(self, seed):
        return traffic.fedtune_pool(seed, self.mix,
                                    vocab=self.cfg["vocab_size"],
                                    d_model=self.cfg["d_model"],
                                    frames=self.cfg["max_source_positions"])

    def run_blocks(self, state, rounds, pool, spans, rows=None):
        """``rounds`` rounds through the program's block driver."""
        import jax
        import jax.numpy as jnp
        from repro.launch.train import _run_fused

        def stage_block(round0, n):
            host = traffic.stack_rounds(pool, round0, n)
            return {k: jnp.asarray(v) for k, v in host.items()}, None

        def on_round(t, row):
            if rows is not None:
                rows.append({k: float(np.asarray(v))
                             for k, v in row.items()
                             if np.ndim(v) == 0})

        out = _run_fused(self.driver_args, self.loop, state, rounds,
                         stage_block, on_round, spans=spans)
        jax.block_until_ready(out.params)
        return out

    def first_block(self, seed, pool, spans):
        """Weights from the seed, then the first block: the program's
        loss and step-size rows, its per-leaf change, and the state."""
        from repro.core import init_fl_state
        params0 = self.weights(seed)
        state = init_fl_state(params0, self.sopt)
        rows = []
        state = self.run_blocks(state, self.R, pool, spans, rows)
        change = np.asarray(fedref.change_norms(state.params, params0))
        return state, rows, change

    def reference(self, seed, pool, *, dtype=None, precision="highest",
                  half_batch=False):
        """The reference over the first block's rounds, from the same
        seed and batches; ``dtype`` / faults make the controls."""
        import jax
        import jax.numpy as jnp
        ref, vocab = self.ref, self.cfg["vocab_size"]
        params0 = self.weights(seed)
        if dtype is not None:
            params0 = ref.cast(params0, dtype)

        def loss_fn(p, b):
            if half_batch:
                half = b["tokens"].shape[-1] // 2
                lg = ref.logits(p, b["tokens"], b["frames"], vocab)
                lse = jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
                pick = jnp.take_along_axis(
                    lg.astype(jnp.float32), b["labels"][..., None],
                    axis=-1)[..., 0]
                return jnp.mean((lse - pick)[..., :half])
            return ref.loss(p, b, vocab).astype(jnp.float32)

        rounds = []
        for r in range(self.R):
            host = pool[r % len(pool)]
            dev = {k: jnp.asarray(v) for k, v in host.items()}
            if dtype is not None:
                dev = ref.cast(dev, dtype)
            rounds.append(dev)
        with jax.default_matmul_precision(precision):
            P, rows, first = fedref.run_rounds(
                loss_fn, params0, rounds, self.hyper)
            change = np.asarray(fedref.change_norms(
                P, self.weights(seed)))
        return rows, change, first


def flops_per_round(cell):
    mix, cfg = cell.mix, cell.config
    per_step = cell.reference.train_flops(
        cfg, mix["seq"], cfg["max_source_positions"], mix["batch"])
    return per_step * mix["clients"] * mix["local_steps"]


def pair_bytes_per_round(cell, n_params):
    """Bytes the Δ-SGD kernel pair must move per round: per local step
    the norms read G and G_prev, the apply reads P and G and writes P,
    each (C, N) float32."""
    mix = cell.mix
    return 5.0 * mix["clients"] * n_params * 4 * mix["local_steps"]


def run(cell, args, ctx):
    """One run of a fine-tune cell; see the module doc."""
    from harness.common import memory_peak_bytes
    b = Build(cell)
    spans = ctx.spans
    pool = b.pool(args.seed)
    state, prog_rows, prog_change = b.first_block(args.seed, pool, spans)
    t0 = time.perf_counter()
    state = b.run_blocks(state, b.R, pool, spans)
    t_block = time.perf_counter() - t0
    ctx.setup_done()

    seconds = ctx.window_seconds(args.seconds)
    blocks = max(2, math.ceil(seconds / t_block))
    rounds = blocks * b.R
    spans.reset()
    with ctx.window():
        t0 = time.perf_counter()
        state = b.run_blocks(state, rounds, pool, spans)
        wall = time.perf_counter() - t0
    peak = memory_peak_bytes(ctx.devices)
    del state

    ref_rows, ref_change, ref_first = b.reference(args.seed, pool)
    nums = fedref.compare(prog_rows, prog_change, ref_rows, ref_change,
                          ref_first)
    import jax
    n_params = int(sum(np.prod(s.shape) for s in jax.tree.leaves(b.shapes)))
    rows128 = b.loop.layout.padded_size // 128
    return {
        "end_to_end": {"rounds_per_s": rounds / wall},
        "compared": {k: nums[k] for k in cell.limits},
        "attempted": rounds, "failed": 0,
        "memory_peak_bytes": peak, "window_s": wall,
        "counts": {"rounds": rounds, "wall_s": wall,
                   "flops_per_round": flops_per_round(cell),
                   "pair_bytes_per_round": pair_bytes_per_round(
                       cell, n_params),
                   "pair_shape": f"f32[{b.mix['clients']},{rows128},128]",
                   "spans": dict(spans.totals)},
    }
