"""Fleet cells: the paper's cross-device regime through the program's
fleet path, as ``launch/train.train_paper_task`` builds it for a run
with registered clients: the dataset (``FederatedDataset.build`` with
the scenario and the registered count), the loop (``make_fleet_loop``
with ``gather=arena_gather``), the client arena (``arena_init`` over
every registered client) and the staging (the dataset's own
``sample_block``), driven by the program's block driver
(``launch/train._run_fused`` with ``fleet_arena``).

The scenario's seed is fixed by the mix, as it is part of the compiled
program; the seed of a run makes the examples, the weights, the data
seed of the partitions and example draws, and the round the state
starts at, so each seed trains other cohorts on other data through the
same program.

Set-up builds one loop and one state, drives them through the first
block from the seed (the rounds the reference follows), runs one more
block to time it, and hands the same loop, state and arena to the
window: whole blocks over ``--seconds``, ending in the block driver's host
sync. The block driver keeps the arena it carries to itself; set-up
reads it back from the checkpoint the block driver writes at the end of a
call (``<checkout>/.bench_ckpt``), and the window writes none. So the
compared first block and the timed blocks run the same compiled loop
through the same driver, and differ only in that final checkpoint.

The reference takes nothing the program made: it makes the examples
with the traffic's generator and cuts its own partitions from them
(``fleetref.partitions``).
"""
from __future__ import annotations

import dataclasses
import math
import os
import shutil
import time
import types

import numpy as np

from harness import fedref, fleetref, traffic
from harness.common import CHECKOUT


def data_seed(seed):
    """The program's data seed for a run's seed: the partitions and the
    per-round example draws are keyed on it."""
    return seed % 2 ** 31


class Build:
    """The seed-free program objects of one cell: model, optimizers,
    scenario and the fleet loop."""

    def __init__(self, cell, ckpt_root=CHECKOUT):
        import jax
        import jax.numpy as jnp
        from repro.compression import CompressionSpec
        from repro.configs import FLConfig
        from repro.configs.paper_tasks import MLP_SMALL
        from repro.core import (arena_gather, get_client_opt,
                                get_server_opt, make_fleet_loop, make_loss)
        from repro.federation import get_scenario
        from repro.kernels import flat_backend
        from repro.models.small import make_small_model, softmax_ce
        self.cell, self.mix, self.cfg = cell, cell.mix, cell.config
        self.ref = cell.reference
        mix, cfg = self.mix, self.cfg
        mcfg = dataclasses.replace(
            MLP_SMALL, input_dim=cfg["input_dim"],
            hidden_dims=tuple(cfg["hidden_dims"]),
            num_classes=cfg["num_classes"])
        init_fn, logits_fn = make_small_model(mcfg)
        self.hyper = {k: float(mix["delta_sgd"][k])
                      for k in ("gamma", "delta", "eta0", "theta0")}
        sc = mix["scenario"]
        self.scn_mix = sc
        self.scn = get_scenario(
            sc["preset"], seed=sc["seed"], scheduler=sc["scheduler"],
            zipf_s=sc["zipf_s"], speed=sc["speed"],
            k_min_frac=sc["k_min_frac"], aggregation=sc["aggregation"],
            alpha=mix["alpha"])
        self.M = mix["registered"]
        self.fl = FLConfig(client_opt="delta_sgd", server_opt="fedavg",
                           num_clients=mix["partitions"],
                           participation=mix["participation"],
                           num_registered_clients=self.M,
                           scenario=sc["preset"], **self.hyper)
        self.C = self.fl.clients_per_round
        self.R = mix["rounds_per_call"]
        self.b = mix["batch"]
        self.K = mix["samples_per_partition"] // self.b
        self.copt = get_client_opt("delta_sgd", self.fl)
        self.sopt = get_server_opt("fedavg")
        self.comp = CompressionSpec(kind="none")
        loss_fn = make_loss(
            lambda p, b: (softmax_ce(logits_fn(p, b["x"]), b["y"]), {}))
        self.shapes = jax.eval_shape(init_fn, jax.random.key(0))
        sizes = np.full(self.M, mix["samples_per_partition"], np.float32)
        self.loop = make_fleet_loop(
            loss_fn, self.copt, self.sopt, params_like=self.shapes,
            num_rounds=mix["num_rounds"], num_registered=self.M,
            rounds_per_call=self.R, flat=flat_backend(), scenario=self.scn,
            client_sizes=jnp.asarray(sizes), compression=self.comp,
            gather=arena_gather, eta_carry=mix["eta_carry"],
            seed=sc["seed"], telemetry=mix["telemetry"])
        self.ckpt_dir = os.path.join(ckpt_root, ".bench_ckpt", cell.name)
        self._init = jax.jit(lambda k: self.ref.init_params(self.shapes,
                                                            k))

    def weights(self, seed):
        import jax
        return self._init(jax.random.key(seed % 2 ** 32))

    def dataset(self, seed):
        """The program's dataset over the examples the traffic makes
        from the seed: the Dirichlet partitions, the registered fleet,
        the per-round example draws."""
        from repro.data.pipeline import FederatedDataset
        from repro.data.synthetic import TaskData
        mix = self.mix
        x, y = traffic.gaussian_mixture(seed, mix["task"])
        task = TaskData("medium", x, y, x[:0], y[:0],
                        mix["task"]["classes"])
        fed = FederatedDataset.build(
            task, num_clients=mix["partitions"], alpha=mix["alpha"],
            samples_per_client=mix["samples_per_partition"],
            seed=data_seed(seed), scenario=self.scn,
            num_registered=self.M)
        if fed.epoch_steps(self.b) != self.K:
            raise ValueError(f"the program's epoch is "
                             f"{fed.epoch_steps(self.b)} steps, the mix's "
                             f"{self.K}")
        return fed

    def arena0(self):
        from repro.federation import arena_init
        return arena_init(self.M, eta0=self.loop.eta0)

    def run_blocks(self, state, car, rounds, fed, ex_arena, spans,
                   rows=None, keep_arena=True):
        """``rounds`` rounds through the program's block driver; returns
        the state and, with ``keep_arena``, the arena it carried."""
        import jax
        import jax.numpy as jnp
        from repro.checkpoint import restore
        from repro.launch.train import _arena_dir, _run_fused

        def stage_block(round0, n):
            idx, _, _ = fed.sample_block(self.fl.participation, self.K,
                                         self.b, round0=round0, rounds=n)
            return jnp.asarray(idx), ex_arena

        def on_round(t, row):
            if rows is not None:
                rows.append({"loss": float(row["loss"]),
                             "eta_mean": float(row["eta_mean"]),
                             "cohort_ids": np.asarray(row["cohort_ids"])})

        args = types.SimpleNamespace(
            rounds_per_call=self.R, ckpt_every=10 ** 9, profile=0,
            ckpt_dir=self.ckpt_dir if keep_arena else None)
        out = _run_fused(args, self.loop, state, rounds, stage_block,
                         on_round, fleet_arena=car, spans=spans)
        jax.block_until_ready(out.params)
        if not keep_arena:
            return out, None
        car, _ = restore(_arena_dir(self.ckpt_dir), like=self.arena0(),
                         step=int(out.round))
        return out, car

    def start(self, seed):
        """Weights, dataset, example arena and the fresh state at the
        seed's starting round."""
        import jax
        import jax.numpy as jnp
        from repro.core import init_fl_state
        fed = self.dataset(seed)
        ex_arena = jax.tree.map(jnp.asarray, fed.arena())
        params0 = self.weights(seed)
        state = init_fl_state(params0, self.sopt, self.scn,
                              compression=self.comp, cohort=self.C)
        t0 = traffic.start_round(seed, self.mix)
        state = state._replace(round=jnp.asarray(t0, jnp.int32))
        return fed, ex_arena, params0, state, t0

    def first_block(self, seed, spans):
        """The first block from the seed: the program's rows (loss, step
        size, cohort), its per-leaf change, the arena's bookkeeping, and
        what the window goes on from."""
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        fed, ex_arena, params0, state, t0 = self.start(seed)
        rows = []
        state, car = self.run_blocks(state, self.arena0(), self.R, fed,
                                     ex_arena, spans, rows)
        change = np.asarray(fedref.change_norms(state.params, params0))
        book = (np.asarray(car.rounds_seen), np.asarray(car.last_round))
        return {"fed": fed, "ex_arena": ex_arena, "state": state,
                "arena": car, "rows": rows, "change": change,
                "cohorts": [r["cohort_ids"] for r in rows], "book": book,
                "t0": t0}

    def partitions(self, seed, y):
        """The reference's own partitions of the examples ``y`` labels."""
        mix = self.mix
        return fleetref.partitions(y, mix["partitions"], mix["alpha"],
                                   mix["samples_per_partition"],
                                   data_seed(seed))

    def reference(self, seed, t0, *, dtype=None, cohort_shift=0,
                  full_budgets=False, skip_scatter=False, half_batch=False):
        """The reference over the first block's rounds: each round's
        cohort, budgets and examples derived anew, Δ-SGD for K_c steps
        per client, the mean of the clients' parameters; and the arena's
        bookkeeping. ``dtype`` and the faults make the controls."""
        import jax
        import jax.numpy as jnp
        ref, sc = self.ref, self.scn_mix
        rounds = [t0 + r for r in range(self.R)]
        cohorts = [fleetref.cohort(sc["seed"], t + cohort_shift, self.M,
                                   self.C, sc["zipf_s"]) for t in rounds]
        budgets = [np.full(self.C, self.K) if full_budgets else
                   fleetref.step_budgets(sc["seed"], t, self.C, self.K,
                                         sc["k_min_frac"])
                   for t in rounds]
        x, y = traffic.gaussian_mixture(seed, self.mix["task"])
        parts = self.partitions(seed, y)
        batches = []
        for t, ids in zip(rounds, cohorts):
            take = fleetref.example_ids(data_seed(seed), t, ids, parts,
                                        self.K, self.b)
            dev = {"x": jnp.asarray(x[take]), "y": jnp.asarray(y[take])}
            batches.append(ref.cast(dev, dtype) if dtype is not None
                           else dev)
        params0 = self.weights(seed)
        if dtype is not None:
            params0 = ref.cast(params0, dtype)
        loss = ref.loss
        if half_batch:
            def loss(p, bt):
                return ref.loss(p, jax.tree.map(
                    lambda a: a[: a.shape[0] // 2], bt))
        with jax.default_matmul_precision("highest"):
            P, rows, first = fedref.run_rounds(
                loss, params0, batches, self.hyper, step_counts=budgets)
            change = np.asarray(fedref.change_norms(P, self.weights(seed)))
        book = fleetref.arena_book([] if skip_scatter else cohorts, rounds,
                                   self.M)
        return {"rows": rows, "change": change, "first": first,
                "cohorts": cohorts, "book": book}


def compare(prog, ref):
    """The numbers a fleet run (or a control put in its place) holds to
    its limits against the reference: ``loss``, ``eta``
    and ``change`` as ``fedref.compare`` takes them, ``cohort`` the
    (round, slot) ids that differ, ``arena`` the registered clients
    whose bookkeeping differs."""
    nums = fedref.compare(prog["rows"], prog["change"], ref["rows"],
                          ref["change"], ref["first"])
    nums["cohort"] = fleetref.cohort_gap(prog["cohorts"], ref["cohorts"])
    nums["arena"] = fleetref.arena_gap(*prog["book"], *ref["book"])
    return nums


def counts(b):
    """Work of a round at C x K_max client steps: a client past its
    budget still rides through every lane of the local-step scan."""
    steps = b.C * b.K
    n_params = b.ref.num_params(b.cfg)
    return {"flops_per_round": b.ref.train_flops(b.cfg, b.b) * steps,
            "pair_bytes_per_round": 5.0 * n_params * 4 * steps,
            "pair_shape": f"f32[{b.C},{b.loop.layout.padded_size // 128},"
                          f"128]"}


def run(cell, args, ctx):
    """One run of a fleet cell; see the module doc."""
    from harness.common import memory_peak_bytes
    b = Build(cell)
    spans = ctx.spans
    prog = b.first_block(args.seed, spans)
    spans.reset()
    t0 = time.perf_counter()
    state, car = b.run_blocks(prog["state"], prog["arena"], b.R,
                              prog["fed"], prog["ex_arena"], spans)
    # the block's time without the checkpoint set-up reads the arena from
    t_block = time.perf_counter() - t0 - spans.totals["ckpt"][0]
    ctx.setup_done()

    seconds = ctx.window_seconds(args.seconds)
    blocks = max(2, math.ceil(seconds / t_block))
    rounds = blocks * b.R
    spans.reset()
    with ctx.window():
        t0 = time.perf_counter()
        state, _ = b.run_blocks(state, car, rounds, prog["fed"],
                                prog["ex_arena"], spans, keep_arena=False)
        wall = time.perf_counter() - t0
    peak = memory_peak_bytes(ctx.devices)
    del state, car, prog["state"], prog["arena"], prog["ex_arena"]
    shutil.rmtree(b.ckpt_dir, ignore_errors=True)

    ref = b.reference(args.seed, prog["t0"])
    nums = compare(prog, ref)
    return {
        "end_to_end": {"rounds_per_s": rounds / wall},
        "compared": {k: nums[k] for k in cell.limits},
        "attempted": rounds, "failed": 0,
        "memory_peak_bytes": peak, "window_s": wall,
        "counts": dict(counts(b), rounds=rounds, wall_s=wall,
                       spans=dict(spans.totals)),
    }
