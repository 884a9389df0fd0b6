"""Shared harness for the paper-reproduction benchmarks.

Protocol = the paper's (§4), at laptop scale on the synthetic task ladder:
100 clients (60 in quick mode), 10% participation, E=1 local epoch
(K = n_i/b steps), FedAvg server unless stated. Step sizes for the
baseline optimizers are grid-searched on ONE task (medium, α=0.1) and then
*reused everywhere* — exactly the transfer protocol whose failure mode
Δ-SGD is designed to avoid. Δ-SGD always runs with the paper defaults
γ=2, η0=0.2, θ0=1, δ=0.1 — no tuning, ever.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_tasks import CNN_PAPER, MLP_SMALL, MLP_WIDE
from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_round, make_loss)
from repro.data.pipeline import FederatedDataset
from repro.data.synthetic import get_task
from repro.models.small import accuracy, make_small_model, softmax_ce

# paper grids (§4 Hyperparameters)
GRIDS = {
    "sgd": [0.01, 0.05, 0.1, 0.5],
    "sgd_decay": [0.01, 0.05, 0.1, 0.5],
    "sgdm": [0.01, 0.05, 0.1, 0.5],
    "sgdm_decay": [0.01, 0.05, 0.1, 0.5],
    "adam": [0.001, 0.01, 0.1],
    "adagrad": [0.001, 0.01, 0.1],
    "sps": [None],          # official defaults (c=0.5, f*=0)
    "delta_sgd": [None],    # paper defaults, never tuned
}

MODELS = {"mlp": MLP_SMALL, "mlp-wide": MLP_WIDE, "cnn": CNN_PAPER}


@functools.lru_cache(maxsize=32)
def _fed(task_id: str, alpha: float, num_clients: int, seed: int,
         variable_sizes: bool = False):
    task = get_task(task_id, seed=seed)
    vs = None
    if variable_sizes:
        vs = np.random.default_rng(seed + 5).integers(100, 501, num_clients)
    return FederatedDataset.build(task, num_clients=num_clients, alpha=alpha,
                                  samples_per_client=500, seed=seed,
                                  variable_sizes=vs)


def run_fl(opt_name: str, task_id: str, *, alpha: Optional[float] = None,
           rounds: int = 60, lr: Optional[float] = None,
           model: str = "mlp", server: str = "fedavg",
           fedprox_mu: float = 0.0, delta: float = 0.1,
           local_epochs: int = 1, batch: int = 64, num_clients: int = 60,
           participation: float = 0.1, weighted: bool = False,
           variable_sizes: bool = False, seed: int = 0,
           engine: str = "vmap", scenario: Optional[str] = None,
           compression: Optional[str] = None,
           error_feedback: bool = False,
           robust_agg: Optional[str] = None,
           quorum: Optional[int] = None,
           telemetry: bool = False) -> Dict:
    """One FL training run; returns final test accuracy + timing.

    ``engine="flat"`` switches Δ-SGD runs onto the packed flat-parameter
    round engine (core/fed_round flat path). ``scenario`` names a
    federation preset (repro.federation.scenarios) — participation
    scheduling, heterogeneous K_c, async buffering; its Dirichlet-α hint
    is used when ``alpha`` is not given, and async scenarios force the
    flat engine. Scenario runs also return cohort/staleness/K_eff
    telemetry (see launch/report.scenario_summary).

    ``compression`` names a delta-compression kind (repro.compression:
    "none"/"int8"/"topk"; ``error_feedback`` adds EF21); active
    compression forces the flat engine too, and the run returns
    wire-bytes / compression-ratio telemetry under ``"compression"``.

    ``robust_agg`` / ``quorum`` override the scenario's robust server
    aggregation and quorum threshold (repro.federation.faults; None =
    keep the preset's choice — an explicit "mean" DOWNGRADES a robust
    preset to plain averaging, which the faults suite uses to show the
    undefended byzantine divergence). They promote a scenario-less run
    to ``sync_iid``; faulty/robust scenarios force the flat engine.

    ``telemetry=True`` turns on the in-scan distribution plane
    (repro.telemetry) — non-perturbing by contract, so the telemetry
    bench suite times its overhead against this same run with it off."""
    scn = None
    scn_overrides = {}
    if robust_agg is not None:
        scn_overrides["robust_agg"] = robust_agg
    if quorum is not None:
        scn_overrides["quorum"] = quorum
    if scenario is not None or scn_overrides:
        from repro.federation import get_scenario
        # run seed threaded into the scenario: multi-seed sweeps must
        # vary the cohort / K_c / staleness draws too
        scn = get_scenario(scenario or "sync_iid", seed=seed,
                           **scn_overrides)
        if alpha is None:
            alpha = scn.alpha
    comp = None
    if (compression is not None or error_feedback
            or (scn is not None and scn.bandwidth_heterogeneous)):
        # a bandwidth-heterogeneous scenario activates even a kind="none"
        # spec (per-client level draws) — same resolution as the launch
        # drivers, so the preset behaves identically from either entry
        from repro.compression import get_compression
        comp = get_compression(compression, error_feedback=error_feedback)
    comp_active = comp is not None and comp.active(scn)
    alpha = 0.1 if alpha is None else alpha
    fed = _fed(task_id, alpha, num_clients, seed, variable_sizes)
    fed.scenario = scn        # _fed is lru_cached: (re)pin per run
    fed._round = 0
    init_fn, logits_fn = make_small_model(MODELS[model])
    loss_fn = make_loss(
        lambda p, b: (softmax_ce(logits_fn(p, b["x"]), b["y"]), {}),
        fedprox_mu=fedprox_mu)
    kw = {}
    if lr is not None:
        kw["lr"] = lr
    if opt_name == "delta_sgd":
        kw["delta"] = delta
    copt = get_client_opt(opt_name, **kw)
    sopt = get_server_opt(server)
    flat = False
    if (engine == "flat"
            or (scn is not None and (scn.is_async or scn.faulty
                                     or scn.robust or scn.quorum > 0))
            or comp_active) and opt_name == "delta_sgd":
        # pallas kernels on TPU; identical fused math via XLA elsewhere
        # (interpret-mode pallas in the round loop would distort timing)
        from repro.kernels import flat_backend
        flat = flat_backend()
    rnd = jax.jit(make_fl_round(
        loss_fn, copt, sopt, num_rounds=rounds, weighted=weighted,
        flat=flat, scenario=scn, num_clients=num_clients,
        client_sizes=fed.client_sizes() if scn is not None else None,
        compression=comp, telemetry=telemetry))
    from repro.federation.schedulers import cohort_size
    state = init_fl_state(init_fn(jax.random.key(seed)), sopt, scn,
                          compression=comp,
                          cohort=cohort_size(participation, num_clients))
    K = fed.epoch_steps(batch) * local_epochs
    ids_rounds, mrows, crows = [], [], []
    t0 = time.time()
    metrics = {}
    for t in range(rounds):
        batches, w, ids = fed.sample_round(participation, K, batch,
                                           round_idx=t)
        state, metrics, _ = rnd(
            state, {"x": jnp.asarray(batches["x"]),
                    "y": jnp.asarray(batches["y"])},
            client_weights=jnp.asarray(w) if weighted else None)
        if scn is not None:
            ids_rounds.append(np.asarray(ids))
            mrows.append({k: float(metrics[k]) for k in
                          ("stale_mean", "stale_max", "k_eff_mean",
                           "k_eff_min", "k_eff_max", "flushed",
                           # round-health telemetry
                           # (repro.federation.faults)
                           "eta_clip_rate", "nan_guard_rate",
                           "valid_count", "round_skipped", "drop_frac",
                           "byz_frac", "overstale_frac", "agg_clip_rate")
                          if k in metrics})
        if comp_active:
            crows.append({k: float(metrics[k]) for k in
                          ("wire_bytes", "comp_ratio", "comp_level_mean")
                          if k in metrics})
    wall = time.time() - t0
    xt, yt = fed.test_batch(2000)
    acc = float(accuracy(logits_fn(state.params, jnp.asarray(xt)),
                         jnp.asarray(yt)))
    out = {"acc": acc, "wall_s": wall, "us_per_round": wall / rounds * 1e6,
           "eta": float(metrics.get("eta_mean", np.nan)),
           "loss": float(metrics.get("loss", np.nan))}
    if scn is not None:
        from repro.launch.report import scenario_summary
        out["scenario"] = scenario_summary(scn.name, ids_rounds,
                                           num_clients, mrows)
    if crows:
        out["compression"] = {
            "wire_bytes_round": float(np.mean([r["wire_bytes"]
                                               for r in crows])),
            "comp_ratio": float(np.mean([r["comp_ratio"] for r in crows]))}
        if any("comp_level_mean" in r for r in crows):
            out["compression"]["level_mean"] = float(np.mean(
                [r["comp_level_mean"] for r in crows
                 if "comp_level_mean" in r]))
    return out


_TUNED: Dict[str, Optional[float]] = {}


def tuned_lrs(rounds: int = 40, seed: int = 0) -> Dict[str, Optional[float]]:
    """Grid-search every baseline on the tuning task (medium, α=0.1, MLP —
    the task where baselines actually converge, mirroring the paper's
    choice of CIFAR-10/ResNet-18 as the tuning anchor)."""
    if _TUNED:
        return _TUNED
    for opt, grid in GRIDS.items():
        best_lr, best_acc = None, -1.0
        for lr in grid:
            acc = run_fl(opt, "medium", alpha=0.1, rounds=rounds, lr=lr,
                         seed=seed)["acc"]
            if acc > best_acc:
                best_acc, best_lr = acc, lr
        _TUNED[opt] = best_lr
    return _TUNED


OPTS = list(GRIDS)
