"""Step-function builders shared by dryrun/train/serve drivers."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_loop, make_fl_round, make_fleet_loop,
                        make_loss)
from repro.models.model import Model


def _resolve_scenario(fl: FLConfig, scenario):
    """Resolve ``scenario`` (Scenario | preset name | None, defaulting to
    ``fl.scenario``) and fold in the FLConfig robust-aggregation
    overrides. ``robust_agg="mean"`` / ``quorum=0`` are inert; non-
    default values need a Scenario to live on, so they promote a bare
    config to the ``sync_iid`` preset."""
    if scenario is None and fl.scenario:
        scenario = fl.scenario
    overrides = {}
    if fl.robust_agg != "mean":
        overrides["robust_agg"] = fl.robust_agg
    if fl.quorum:
        overrides["quorum"] = fl.quorum
    if scenario is None and not overrides:
        return None
    if scenario is not None and hasattr(scenario, "is_async") \
            and not overrides:
        return scenario
    from repro.federation import get_scenario
    return get_scenario(scenario if scenario is not None else "sync_iid",
                        **overrides)


def make_train_step(model: Model, fl: FLConfig, *, num_rounds: int = 1000,
                    use_pallas: bool = False, remat: bool = False,
                    flat: Optional[bool] = None, mesh=None,
                    federation=None, scenario=None, compression=None):
    """One federated round over the (C, K, b, ...) batch layout.

    ``flat`` switches in the flat-parameter Δ-SGD engine (defaults to
    ``fl.flat_engine``); under meshes the kernels lower through XLA unless
    ``use_pallas`` is also set. ``mesh`` + ``federation`` (flat engine
    only) keep the packed (C, N) buffer sharded per
    ``federation.flat_spec(mesh)`` for the whole round. ``scenario`` (a
    repro.federation.Scenario or preset name; defaults to
    ``fl.scenario``) adds heterogeneous step counts and/or async
    buffered aggregation; async scenarios auto-enable the flat engine
    (the delta buffer is one reduction over the packed client axis).
    ``compression`` (a repro.compression.CompressionSpec or kind name;
    defaults to ``fl.compression_spec``) compresses the client deltas on
    the flat engine and auto-enables it when active.

    Returns (train_step, sopt, scenario, compression) — the resolved
    scenario/compression so the caller can allocate a matching
    ``init_fl_state``.
    """
    copt = get_client_opt(fl.client_opt, fl, use_pallas=use_pallas)
    sopt = get_server_opt(fl.server_opt)
    scenario = _resolve_scenario(fl, scenario)
    from repro.compression import get_compression
    compression = get_compression(compression if compression is not None
                                  else fl.compression_spec)
    if flat is None:
        flat = fl.flat_engine
    if scenario is not None and (scenario.is_async or scenario.faulty
                                 or scenario.robust or scenario.quorum > 0):
        flat = True
    if compression.active(scenario):
        flat = True
    flat_mode = False
    if flat:
        if fl.client_opt != "delta_sgd":
            raise ValueError("flat engine requires client_opt='delta_sgd', "
                             f"got {fl.client_opt!r}")
        flat_mode = "pallas" if use_pallas else "xla"

    def base_loss(params, batch):
        from repro.models.common import remat_blocks
        with remat_blocks(remat):
            return model.loss(params, batch, use_pallas=use_pallas)

    loss_fn = make_loss(base_loss, fedprox_mu=fl.fedprox_mu)
    round_fn = make_fl_round(loss_fn, copt, sopt, num_rounds=num_rounds,
                             weighted=fl.weighted_agg, flat=flat_mode,
                             mesh=mesh, federation=federation,
                             scenario=scenario,
                             num_clients=fl.num_clients,
                             compression=compression)

    def train_step(state, client_batches):
        new_state, metrics, _ = round_fn(state, client_batches)
        return new_state, metrics

    return train_step, sopt, scenario, compression


def make_train_loop(model: Model, fl: FLConfig, *, num_rounds: int = 1000,
                    rounds_per_call: int = 8, use_pallas: bool = False,
                    remat: bool = False, mesh=None, federation=None,
                    scenario=None, compression=None):
    """R rounds fused into one jitted call (core.fed_loop.make_fl_loop):
    ``lax.scan`` over the flat round body on a persistent flat carry —
    batches arrive with a leading R axis (stacked, or arena gather
    indices via ``repro.core.arena_gather``), metrics come back stacked.

    Same resolution rules as ``make_train_step``, except the flat engine
    is REQUIRED (the loop carries the packed flat state), so
    ``fl.client_opt`` must be ``delta_sgd``. Returns
    (train_loop, sopt, scenario, compression); the loop exposes
    ``.layout`` (for flatten/unflatten at block boundaries). Jit the loop with ``donate_argnums=0`` so the
    carried buffers update in place.
    """
    if fl.client_opt != "delta_sgd":
        raise ValueError("the round-fused loop requires client_opt="
                         f"'delta_sgd', got {fl.client_opt!r}")
    copt = get_client_opt(fl.client_opt, fl, use_pallas=use_pallas)
    sopt = get_server_opt(fl.server_opt)
    scenario = _resolve_scenario(fl, scenario)
    from repro.compression import get_compression
    compression = get_compression(compression if compression is not None
                                  else fl.compression_spec)

    def base_loss(params, batch):
        from repro.models.common import remat_blocks
        with remat_blocks(remat):
            return model.loss(params, batch, use_pallas=use_pallas)

    loss_fn = make_loss(base_loss, fedprox_mu=fl.fedprox_mu)
    params_like = jax.eval_shape(model.init, jax.random.key(0))
    train_loop = make_fl_loop(loss_fn, copt, sopt, params_like=params_like,
                              num_rounds=num_rounds,
                              rounds_per_call=rounds_per_call,
                              weighted=fl.weighted_agg,
                              flat="pallas" if use_pallas else "xla",
                              mesh=mesh, federation=federation,
                              scenario=scenario,
                              num_clients=fl.num_clients,
                              compression=compression)
    return train_loop, sopt, scenario, compression


def make_fleet_train_loop(model: Model, fl: FLConfig, *,
                          num_rounds: int = 1000, rounds_per_call: int = 8,
                          use_pallas: bool = False, remat: bool = False,
                          scenario=None, compression=None,
                          client_sizes=None, gather=None,
                          batch_index_fn=None, eta_carry: bool = False,
                          seed: int = 0):
    """Fleet-scale variant of ``make_train_loop``
    (core.fed_loop.make_fleet_loop): the loop's carry is
    ``(FlatFLState, repro.federation.arena.ClientArena)`` — global
    training state plus per-REGISTERED-client rows — and each scanned
    round draws its cohort ids on device over all
    ``fl.registered_clients`` candidates, gathers only those rows, and
    scatters them back.

    Requires ``fl.num_registered_clients`` (the fleet regime) and the
    flat Δ-SGD engine. Same scenario/compression resolution as
    ``make_train_step``; ``client_sizes`` should be the
    (C_registered,) per-registered-client sizes (e.g.
    ``FederatedDataset.registered_sizes()``) when the scenario's
    scheduler is size-weighted. Returns
    (train_loop, sopt, scenario, compression); build the arena half of
    the carry with ``repro.federation.arena_init(fl.registered_clients,
    eta0=train_loop.eta0, ...)``.
    """
    if not fl.fleet:
        raise ValueError("make_fleet_train_loop needs the fleet regime: "
                         "set FLConfig.num_registered_clients")
    if fl.client_opt != "delta_sgd":
        raise ValueError("the fleet loop requires client_opt='delta_sgd', "
                         f"got {fl.client_opt!r}")
    copt = get_client_opt(fl.client_opt, fl, use_pallas=use_pallas)
    sopt = get_server_opt(fl.server_opt)
    scenario = _resolve_scenario(fl, scenario)
    from repro.compression import get_compression
    compression = get_compression(compression if compression is not None
                                  else fl.compression_spec)

    def base_loss(params, batch):
        from repro.models.common import remat_blocks
        with remat_blocks(remat):
            return model.loss(params, batch, use_pallas=use_pallas)

    loss_fn = make_loss(base_loss, fedprox_mu=fl.fedprox_mu)
    params_like = jax.eval_shape(model.init, jax.random.key(0))
    train_loop = make_fleet_loop(loss_fn, copt, sopt,
                                 params_like=params_like,
                                 num_rounds=num_rounds,
                                 num_registered=fl.registered_clients,
                                 rounds_per_call=rounds_per_call,
                                 weighted=fl.weighted_agg,
                                 flat="pallas" if use_pallas else "xla",
                                 scenario=scenario,
                                 client_sizes=client_sizes,
                                 compression=compression, gather=gather,
                                 batch_index_fn=batch_index_fn,
                                 eta_carry=eta_carry, seed=seed)
    return train_loop, sopt, scenario, compression


def make_prefill_step(model: Model, *, window: Optional[int] = None,
                      use_pallas: bool = False):
    def prefill_step(params, batch):
        logits, cache = model.prefill(params, batch, window=window,
                                      use_pallas=use_pallas)
        return logits, cache

    return prefill_step


def make_serve_step(model: Model, *, window: Optional[int] = None,
                    greedy: bool = True):
    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens,
                                          window=window)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    return serve_step


def abstract_fl_state(model: Model, sopt, scenario=None, compression=None,
                      cohort=None):
    """FLState ShapeDtypeStructs without allocating params (incl. the
    async delta buffer when ``scenario`` is an async Scenario, and the
    EF21 error-feedback tree when ``compression`` carries error
    feedback — ``cohort`` sizes its leading axis)."""
    pstruct = jax.eval_shape(model.init, jax.random.key(0))
    return jax.eval_shape(
        lambda p: init_fl_state(p, sopt, scenario, compression, cohort),
        pstruct)
