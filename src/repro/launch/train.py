"""End-to-end federated training driver.

Trains an assigned architecture (reduced or full) federatedly on synthetic
LM data with any client/server optimizer, or a paper-task model (MLP/CNN)
on the synthetic classification suite. This is the (b) end-to-end example
driver: ~100M-class models for a few hundred rounds on CPU, or the full
configs on a real TPU mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --rounds 50 --client-opt delta_sgd
  PYTHONPATH=src python -m repro.launch.train --task hard --model mlp \
      --rounds 200 --client-opt delta_sgd --alpha 0.1
  PYTHONPATH=src python -m repro.launch.train --task medium --model mlp \
      --rounds 100 --scenario zipf_async

``--scenario`` selects a federation scenario preset
(repro.federation.scenarios): participation scheduling, per-client
compute heterogeneity (K_c ≤ K lane masks), and/or FedBuff-style async
buffered aggregation. Async scenarios require (and auto-enable) the
flat Δ-SGD engine. The driver prints a per-run scenario report (cohort
histogram, staleness, effective-K) and appends it to the ``--out``
artifact.

``--compression`` (+ ``--k-frac``, ``--error-feedback``) compresses the
client->server deltas on the flat engine (repro.compression: int8
per-chunk quantization or magnitude top-k, optional EF21 error
feedback); the round log and the report gain wire-bytes /
compression-ratio telemetry. Combine with ``--scenario
bandwidth_tiered`` to draw per-client compression levels each round.

``--rounds-per-call R`` (R > 1) switches the training loop onto the
round-fused engine (repro.core.fed_loop): R rounds run as ONE jitted
``lax.scan`` on the persistent flat state, with donated buffers. The
paper-task driver stages the example arena on device once and ships
only (R, C, K, b) gather indices per block; the LM driver stacks R
rounds of synthetic batches. Metrics are bit-exact vs the host loop on
the flat engine (``--flat`` forces it for a host-loop parity run);
checkpoints land on block boundaries (still keyed on the round
counter, so fused and host-loop checkpoints interoperate), and eval /
state unpacking happens only at block cadence. Requires
``--client-opt delta_sgd``.

``--num-registered M`` (paper tasks) switches on the FLEET regime
(repro.core.fed_loop.make_fleet_loop + repro.federation.arena): M
registered clients known to the server, cohorts of |S_t| =
``--participation``·M drawn over ALL of them each round, per-client
state (round-end η, participation counters, EF21 residuals) in a
device-sharded ClientArena indexed by registered id. Registered client
i trains on data partition ``i % num_clients``, so fleet scale never
multiplies dataset memory. The ``fleet_uniform`` / ``fleet_zipf``
scenario presets carry hints (M=100k, p=0.05%) that apply when the
flags are not given; ``--eta-carry`` warm-starts returning clients
from their arena row.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, FLConfig, get_config
from repro.core import (get_client_opt, get_server_opt, init_fl_state,
                        make_fl_round, make_loss)
from repro.data.pipeline import FederatedDataset, lm_round_batches
from repro.data.synthetic import get_task
from repro.kernels import flat_backend, on_tpu


def _resolve_scenario(args):
    """Preset with the run's --seed threaded in, so multi-seed sweeps
    actually vary the cohort / K_c / staleness draws. --robust-agg /
    --quorum fold onto the preset (and promote a bare run to sync_iid
    so the robust tail has a Scenario to live on)."""
    overrides = {}
    if getattr(args, "robust_agg", "mean") != "mean":
        overrides["robust_agg"] = args.robust_agg
    if getattr(args, "quorum", 0):
        overrides["quorum"] = args.quorum
    if not args.scenario and not overrides:
        return None
    from repro.federation import get_scenario
    return get_scenario(args.scenario or "sync_iid", seed=args.seed,
                        **overrides)


def _resolve_compression(args):
    """CompressionSpec from the --compression/--k-frac/--error-feedback
    flags (repro.compression); inert kind="none" specs leave the round
    engines bit-exact."""
    from repro.compression import CompressionSpec
    return CompressionSpec(kind=args.compression, k_frac=args.k_frac,
                           error_feedback=args.error_feedback)


def _resolve_fleet(args, scn):
    """(num_registered, participation) for the run. Explicit
    --num-registered / --participation win; otherwise a fleet preset's
    ``registered_hint`` / ``participation_hint`` apply (so
    ``--scenario fleet_uniform`` alone turns on the fleet regime);
    otherwise legacy: registered == num_clients, participation 0.1."""
    m = getattr(args, "num_registered", None)
    if m is None and scn is not None:
        m = scn.registered_hint
    p = getattr(args, "participation", None)
    if p is None and scn is not None and scn.participation_hint:
        p = scn.participation_hint
    return m, (0.1 if p is None else p)


class _ScenarioStats:
    """Per-run accumulator for the scenario report (launch/report.py):
    cohort ids per round + every metric the round emits, routed through
    the repro.telemetry.schema registry instead of a hardcoded key
    whitelist — an unregistered producer key warns ONCE (the old KEYS
    tuple silently discarded it) and is still kept, so nothing a round
    reports can vanish between the engine and the report."""

    def __init__(self, scenario, num_clients):
        self.scenario, self.num_clients = scenario, num_clients
        self.ids, self.metrics = [], []

    def update(self, ids, metrics):
        from repro.telemetry import schema
        if ids is not None:
            self.ids.append(np.asarray(ids))
        elif "cohort_ids" in metrics:
            self.ids.append(np.asarray(metrics["cohort_ids"]))
        row = {}
        for k, v in metrics.items():
            if k == "cohort_ids":
                continue        # carried in the ids stream above
            spec = schema.get(k)
            if spec is None:
                schema.warn_unregistered(k, producer="round metrics")
            if spec is not None and spec.shape != "()":
                row[k] = np.asarray(v, np.float64)
            else:
                row[k] = float(v)
        self.metrics.append(row)

    def summary(self):
        from repro.launch.report import scenario_summary
        name = self.scenario.name if self.scenario else "none"
        return scenario_summary(name, self.ids,
                                self.num_clients, self.metrics)

    def report(self, out_path=None, extra=None):
        s = self.summary()
        if extra:
            s.update(extra)
        print("scenario report:", json.dumps(s, indent=2, default=float))
        if out_path:
            import os
            os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(s, f, indent=2, default=float)
        return s


class _RoundLog:
    """Buffered round log for the HOST loops: per-round metric rows stay
    device arrays and are converted with ONE batched ``jax.device_get``
    per ``--log-every`` interval, instead of the old per-round blocking
    ``float(...)`` fan (which forced a host sync on every round, ~20
    scalars at a time, right in the dispatch hot path). The converted
    rows then feed the scenario stats and the JSONL event log."""

    def __init__(self, log_every, stats=None, events=None):
        self.log_every = max(1, int(log_every))
        self.stats, self.events = stats, events
        self._buf = []

    def push(self, t, metrics, ids=None):
        self._buf.append((t, ids, metrics))
        if len(self._buf) >= self.log_every:
            self.flush()

    def flush(self):
        if not self._buf:
            return
        rows = jax.device_get([m for _, _, m in self._buf])
        for (t, ids, _), row in zip(self._buf, rows):
            if self.stats is not None:
                self.stats.update(ids, row)
            if self.events is not None:
                self.events.emit("round", t=t, **row)
        if self.events is not None:
            self.events.flush()
        self._buf.clear()


def _resolve_events(args):
    """EventLog from --events (repro.telemetry.events), header stamped
    with the full CLI config."""
    if not getattr(args, "events", None):
        return None
    from repro.telemetry import EventLog
    return EventLog(args.events, config=vars(args))


def _log_every(args):
    """--log-every N, 0 = legacy cadence (~10 intervals per run)."""
    n = getattr(args, "log_every", 0)
    return n if n > 0 else max(1, args.rounds // 10)


def _finish_run(events, spans):
    """Common tail: span summary into the event log + stdout."""
    if spans is not None and spans.summary():
        print(f"spans: {spans}", flush=True)
    if events is not None:
        if spans is not None:
            events.emit("spans", **spans.summary())
        events.close()
        print(f"event log: {events.path} "
              f"({events.events_written} events)", flush=True)


def _health_str(m):
    """Compact round-health suffix for the round log. Fault-free legacy
    rounds emit none of the guard keys, so this stays empty and the log
    format is unchanged."""
    if "valid_count" not in m:
        return ""
    s = f" valid {int(float(m['valid_count']))}"
    ng = float(m.get("nan_guard_rate", 0.0))
    if ng > 0:
        s += f" nan {ng:.2f}"
    if float(m.get("round_skipped", 0.0)) > 0:
        s += " SKIPPED(quorum)"
    return s


def _run_fused(args, loop, state, rounds, stage_block, on_round,
               fleet_arena=None, events=None, spans=None):
    """Drive the round-fused loop (repro.core.fed_loop) in R-round
    blocks on donated flat state. ``stage_block(round0, n) ->
    (round_data, arena)`` stages one block's batches (or arena gather
    indices); ``on_round(t, row)`` consumes one round's metrics row.
    The flat carry is unpacked ONLY at block boundaries — that is the
    checkpoint cadence of a fused run: saves land on the first boundary
    at or after each ``--ckpt-every`` hit, keyed on the round counter
    like the host loop's (so fused and host-loop checkpoints
    interoperate via --resume). Returns the final FLState.

    ``fleet_arena`` switches to the fleet carry
    (core.fed_loop.make_fleet_loop): the loop carries
    (FlatFLState, ClientArena). Checkpoints save BOTH halves: the
    FLState lands in ``--ckpt-dir`` and the arena in its ``arena/``
    subdirectory (invisible to latest_step/GC of the FLState stream —
    they match only ``step_*`` entries), keyed on the same round so a
    --resume restores η warm-starts, participation counters, and the
    EF21 slab along with the params (see _maybe_resume_arena; the
    resume-parity test in tests/test_serving.py pins bit-exactness
    across a mid-run restart).

    Staging runs one block ahead: the first block stages up front, and
    every later block's ``stage_block`` call comes right after the
    dispatch of the block before it, while that block runs on the
    device, so the host's staging overlaps the device's work. The calls
    come one a block, in round order, each with its block's ``(round0,
    n)``, and never past ``rounds``; the wait, fetch, ``on_round``,
    events and checkpoint of block n all run before block n+1 is
    dispatched.

    Observability (repro.telemetry): the block is the host-sync
    boundary — the ONLY host transfer per block is the single batched
    metrics device_get after the block executes, and the JSONL
    ``events`` sink flushes exactly there (tests/test_telemetry.py runs
    a block under ``jax.transfer_guard("disallow")`` to pin this).
    ``spans`` accumulates host wall-clock per span, once a block for
    ``stage`` (the block's batches onto the device), ``dispatch`` (the
    enqueue of the block program, and any compile), ``wait`` (the host
    waiting for the device) and ``fetch`` (the metrics transfer);
    ``stage_ahead``, nested inside ``stage``, marks each staging that
    runs while the block before it is in flight: that of every block
    but the first and the one after a profiled block (the profiler's
    trace has waited for that block to end); plus ``pack``/``unpack``
    once a run and ``ckpt`` per save; under a ``jax.profiler`` trace
    each span is also a ``repro.<name>`` host event
    (telemetry.SpanTimer). ``--profile r`` profiles the block
    containing (1-based) round r: an HLO-derived static telemetry row —
    collective count + payload bytes per round
    (roofline.parse_collectives), Pallas launch counts per namespace —
    is emitted at compile time via an AOT
    lower+compile (one extra XLA compile, profiling runs only), and the
    block executes under a ``jax.profiler`` trace written to
    ``--profile-dir``."""
    from repro.checkpoint import save
    from repro.core import flatten_fl_state, unflatten_fl_state
    from repro.telemetry import (SpanTimer, kernel_launch_snapshot,
                                 reset_kernel_launches, static_telemetry,
                                 trace_block)
    if spans is None:
        spans = SpanTimer()
    R = max(1, args.rounds_per_call)
    layout = loop.layout
    jloop = jax.jit(loop, donate_argnums=0)
    with spans.span("pack"):
        fstate = flatten_fl_state(state, layout)
    car = fleet_arena
    base, t = int(state.round), 0
    # the flat carry replaces the tree: a caller that keeps no reference
    # of its own gets the tree's device memory back for the run
    del state
    profile_round = getattr(args, "profile", 0)
    profiled = False

    def stage(t0, ahead):
        n0 = min(R, rounds - t0)
        with spans.span("stage"):
            if not ahead:
                return stage_block(base + t0, n0)
            with spans.span("stage_ahead"):
                return stage_block(base + t0, n0)

    staged = stage(0, ahead=False) if rounds > 0 else None
    while t < rounds:
        n = min(R, rounds - t)
        data, arena = staged

        do_profile = (profile_round > 0 and not profiled
                      and t <= profile_round - 1 < t + n)
        if do_profile:
            reset_kernel_launches()
            with spans.span("compile"):
                if car is not None:
                    lowered = jloop.lower((fstate, car), data, arena=arena)
                else:
                    lowered = jloop.lower(fstate, data, arena=arena)
                launches = kernel_launch_snapshot()
                compiled = lowered.compile()
            static = static_telemetry(compiled, rounds=n,
                                      launches=launches)
            print("static telemetry:",
                  json.dumps(static, default=str), flush=True)
            if events is not None:
                events.emit("static", **static)

        def call(fs=fstate, c=car, d=data, a=arena):
            if c is not None:
                return jloop((fs, c), d, arena=a)
            return jloop(fs, d, arena=a)

        with spans.span("dispatch"):
            if do_profile:
                out = trace_block(call, getattr(args, "profile_dir",
                                                "experiments/profile"))
                profiled = True
            else:
                out = call()
        if car is not None:
            (fstate, car), mets = out
        else:
            fstate, mets = out
        # the next block's batches while this one runs (a profiled
        # block has already run: its trace waited for it)
        if t + n < rounds:
            staged = stage(t + n, ahead=not do_profile)
        # the block boundary is the host-sync point: wait for the device,
        # then ONE batched device_get for all R rounds' metric rows
        with spans.span("wait"):
            jax.block_until_ready(mets)
        with spans.span("fetch"):
            mets = jax.device_get(mets)
        for r in range(n):
            row = {k: v[r] for k, v in mets.items()}
            on_round(t + r, row)
            if events is not None:
                events.emit("round", t=t + r, round=base + t + r, **row)
        if events is not None:
            events.flush()
        t += n
        cadence_hit = any(t0 % args.ckpt_every == 0
                          for t0 in range(t - n, t))
        if args.ckpt_dir and (cadence_hit or t >= rounds):
            with spans.span("ckpt"):
                boundary = unflatten_fl_state(fstate, layout)
                save(args.ckpt_dir, boundary, step=int(boundary.round))
                if car is not None:
                    save(_arena_dir(args.ckpt_dir), car,
                         step=int(boundary.round))
    if profile_round > 0 and not profiled:
        print(f"--profile {profile_round}: no block contained that "
              f"round (run is {rounds} rounds); no trace captured",
              flush=True)
    with spans.span("unpack"):
        return unflatten_fl_state(fstate, layout)


def train_lm(args):
    from repro.models import build_model
    if getattr(args, "num_registered", None):
        raise SystemExit("--num-registered (the fleet regime) is a "
                         "paper-task feature: synthetic LM batches have "
                         "no per-client partitions to map registered "
                         "ids onto — use --task, not --arch")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=args.layers, d_model=args.d_model)
    model = build_model(cfg, jnp.float32)
    scn = _resolve_scenario(args)
    telemetry = getattr(args, "telemetry", False)
    fl = FLConfig(local_steps=args.local_steps, client_opt=args.client_opt,
                  server_opt=args.server_opt, lr=args.lr,
                  fedprox_mu=args.fedprox_mu, scenario=args.scenario,
                  num_clients=args.num_clients)
    copt = get_client_opt(fl.client_opt, fl, use_pallas=on_tpu())
    sopt = get_server_opt(fl.server_opt)
    loss_fn = make_loss(lambda p, b: model.loss(p, b),
                        fedprox_mu=fl.fedprox_mu)
    comp = _resolve_compression(args)
    comp_active = comp.active(scn)
    flat = (flat_backend() if (args.flat or (scn is not None
                                           and scn.is_async)
                               or comp_active) else False)
    params = model.init(jax.random.key(args.seed))
    state = init_fl_state(params, sopt, scn, compression=comp,
                          cohort=args.clients_per_round)
    state = _maybe_resume(args, state)
    # synthetic-data rng is derived PER ROUND from (seed, round): a
    # --resume at any round boundary replays the exact batch stream an
    # uninterrupted run would see (a single sequential stream would
    # restart from the beginning after a crash)
    round_rng = lambda r: np.random.default_rng((args.seed, int(r)))
    stats = (_ScenarioStats(scn, args.num_clients)
             if (scn or comp_active or telemetry) else None)
    events = _resolve_events(args)
    from repro.telemetry import SpanTimer
    spans = SpanTimer()

    extras = {}
    if cfg.encoder_layers:
        extras["frames"] = (cfg.encoder_seq, cfg.d_model)
    if cfg.num_image_tokens:
        extras["image_embeds"] = (cfg.num_image_tokens, cfg.d_model)

    t0 = time.time()

    def print_round(t, metrics):
        if t % max(1, args.rounds // 10) == 0 or t == args.rounds - 1:
            wire = (f" wire {float(metrics['wire_bytes'])/1e6:.2f}MB "
                    f"(x{float(metrics['comp_ratio']):.2f})"
                    if "wire_bytes" in metrics else "")
            print(f"round {t:4d} loss {float(metrics['loss']):.4f} "
                  f"eta {float(metrics['eta_mean']):.4f}{wire}"
                  f"{_health_str(metrics)} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    def log_round(t, metrics):
        # fused-path consumer: rows arrive host-side already (one
        # batched device_get per block in _run_fused)
        if stats:
            stats.update(None, metrics)
        print_round(t, metrics)

    if args.rounds_per_call > 1:
        from repro.core import make_fl_loop
        loop = make_fl_loop(loss_fn, copt, sopt, params_like=params,
                            num_rounds=args.rounds,
                            rounds_per_call=args.rounds_per_call,
                            flat=flat_backend(),
                            scenario=scn, num_clients=args.num_clients,
                            compression=comp, telemetry=telemetry)

        def stage_block(round0, n):
            blocks = [lm_round_batches(round_rng(round0 + i),
                                       clients=args.clients_per_round,
                                       local_steps=fl.local_steps,
                                       batch=args.batch, seq=args.seq,
                                       vocab=cfg.vocab_size,
                                       extras=extras)
                      for i in range(n)]
            stacked = {k: jnp.asarray(np.stack([b[k] for b in blocks]))
                       for k in blocks[0]}
            return stacked, None

        state = _run_fused(args, loop, state, args.rounds, stage_block,
                           log_round, events=events, spans=spans)
        if stats:
            stats.report(args.out)
        _finish_run(events, spans)
        return state

    round_fn = jax.jit(make_fl_round(loss_fn, copt, sopt,
                                     num_rounds=args.rounds, flat=flat,
                                     scenario=scn,
                                     num_clients=args.num_clients,
                                     compression=comp,
                                     telemetry=telemetry))
    rlog = _RoundLog(_log_every(args), stats=stats, events=events)
    for t in range(args.rounds):
        # keyed on state.round, not the loop index, for the same
        # resume-replay reason as the paper-task cohort draw below
        batches = lm_round_batches(round_rng(int(state.round)),
                                   clients=args.clients_per_round,
                                   local_steps=fl.local_steps,
                                   batch=args.batch, seq=args.seq,
                                   vocab=cfg.vocab_size, extras=extras)
        batches = jax.tree.map(jnp.asarray, batches)
        state, metrics, _ = round_fn(state, batches)
        # metric rows stay on device: _RoundLog batches the host
        # conversion once per --log-every interval; only the sparse
        # print cadence below touches individual scalars
        rlog.push(t, metrics)
        print_round(t, metrics)
        _maybe_ckpt(args, state, t, final=(t == args.rounds - 1))
    rlog.flush()
    if stats:
        stats.report(args.out)
    _finish_run(events, None)
    return state


def _arena_dir(ckpt_dir):
    """Fleet-arena checkpoints live in a subdirectory of the FLState
    checkpoint dir: latest_step/_gc only match ``step_*`` entries, so
    the two streams never see each other."""
    return os.path.join(ckpt_dir, "arena")


def _maybe_ckpt(args, state, t, final=False, arena=None):
    """Periodic checkpoint, plus ALWAYS the final round: with
    ``T % ckpt_every != 0`` the last periodic save would otherwise
    predate round T and a --resume would silently redo (and a reader
    silently lose) up to ckpt_every-1 rounds.

    Saves are keyed on ``state.round`` (completed rounds), NOT the loop
    index: after a --resume the loop restarts at t=0 while the round
    counter continues, and loop-index steps would sort BELOW the
    pre-resume checkpoints — save()'s keep-newest GC would delete the
    new saves and latest_step would restore stale pre-resume state.

    ``arena`` (fleet runs) rides along into ``<ckpt_dir>/arena`` at the
    same step, so warm per-client state survives a --resume."""
    if args.ckpt_dir and (t % args.ckpt_every == 0 or final):
        from repro.checkpoint import save
        save(args.ckpt_dir, state, step=int(state.round))
        if arena is not None:
            save(_arena_dir(args.ckpt_dir), arena, step=int(state.round))


def _maybe_resume(args, state):
    from repro.checkpoint import latest_step, restore
    if args.ckpt_dir and args.resume and latest_step(args.ckpt_dir) is not None:
        state, step = restore(args.ckpt_dir, like=state)
        print(f"resumed from checkpoint step {step} "
              f"(round {int(state.round)})")
    return state


def _maybe_resume_arena(args, arena, round_):
    """Restore the fleet arena saved alongside the FLState checkpoint
    at round ``round_`` (the round _maybe_resume restored). Falls back
    to the cold arena — with a warning — when the checkpoint predates
    arena persistence or was saved by a non-fleet run; raises if the
    arena on disk has a different shape (e.g. the run was resumed with
    a different --num-registered or --error-feedback setting)."""
    from repro.checkpoint import latest_step, restore
    if not (args.ckpt_dir and args.resume):
        return arena
    adir = _arena_dir(args.ckpt_dir)
    steps_seen = latest_step(adir)
    if steps_seen is None:
        return arena
    if not os.path.isdir(os.path.join(adir, f"step_{round_:08d}")):
        warnings.warn(f"no arena checkpoint at round {round_} under "
                      f"{adir} (latest is {steps_seen}): resuming with "
                      f"a cold arena — η warm-starts and participation "
                      f"counters reset")
        return arena
    arena, step = restore(adir, like=arena, step=round_)
    print(f"resumed fleet arena from step {step}")
    return arena


def train_paper_task(args):
    from repro.configs.paper_tasks import CNN_PAPER, MLP_SMALL, MLP_WIDE
    from repro.models.small import accuracy, make_small_model, softmax_ce
    task = get_task(args.task, seed=args.seed)
    scn = _resolve_scenario(args)
    telemetry = getattr(args, "telemetry", False)
    num_reg, participation = _resolve_fleet(args, scn)
    fed = FederatedDataset.build(task, num_clients=args.num_clients,
                                 alpha=args.alpha, seed=args.seed,
                                 scenario=scn, num_registered=num_reg)
    mcfg = {"mlp": MLP_SMALL, "mlp-wide": MLP_WIDE, "cnn": CNN_PAPER}[
        args.model]
    init_fn, logits_fn = make_small_model(mcfg)
    fl = FLConfig(client_opt=args.client_opt, server_opt=args.server_opt,
                  lr=args.lr, fedprox_mu=args.fedprox_mu,
                  scenario=args.scenario, num_clients=args.num_clients,
                  participation=participation,
                  num_registered_clients=num_reg)
    copt = get_client_opt(fl.client_opt, fl)
    sopt = get_server_opt(fl.server_opt)
    loss_fn = make_loss(
        lambda p, b: (softmax_ce(logits_fn(p, b["x"]), b["y"]), {}),
        fedprox_mu=fl.fedprox_mu)
    K = fed.epoch_steps(args.batch)
    comp = _resolve_compression(args)
    comp_active = comp.active(scn)
    flat = (flat_backend() if (args.flat or (scn is not None
                                           and scn.is_async)
                               or comp_active) else False)
    state = init_fl_state(init_fn(jax.random.key(args.seed)), sopt, scn,
                          compression=comp, cohort=fl.clients_per_round)
    state = _maybe_resume(args, state)
    stats = (_ScenarioStats(scn, fl.registered_clients)
             if (scn or comp_active or fl.fleet or telemetry)
             else None)
    events = _resolve_events(args)
    from repro.telemetry import SpanTimer
    spans = SpanTimer()
    t0 = time.time()

    def log_fused_round(t, row):
        if stats:
            stats.update(None, row)
        if t % max(1, args.rounds // 10) == 0 or t == args.rounds - 1:
            fleet = (f" revisit {float(row['revisit_frac']):.2f}"
                     if "revisit_frac" in row else "")
            print(f"round {t:4d} loss {float(row['loss']):.4f} "
                  f"eta {float(row['eta_mean']):.4f}{fleet}"
                  f"{_health_str(row)} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    if fl.fleet:
        # fleet regime: the loop carries (FlatFLState, ClientArena) and
        # draws its cohort over all C_registered candidates ON DEVICE —
        # the same (seed, round)-keyed scheduler draw sample_block uses
        # to gather data, so staged indices and arena rows agree. The
        # host ships only (R, C, K, b) gather indices per block; the
        # arena holds O(C_registered) scalars (plus the EF21 slab only
        # under --error-feedback).
        from repro.core import arena_gather, make_fleet_loop
        from repro.federation import arena_init
        loop = make_fleet_loop(
            loss_fn, copt, sopt,
            params_like=jax.eval_shape(init_fn, jax.random.key(args.seed)),
            num_rounds=args.rounds, num_registered=fl.registered_clients,
            rounds_per_call=max(1, args.rounds_per_call),
            flat=flat_backend(), scenario=scn,
            client_sizes=(jnp.asarray(fed.registered_sizes())
                          if scn else None),
            compression=comp, gather=arena_gather,
            eta_carry=getattr(args, "eta_carry", False), seed=fed.seed,
            telemetry=telemetry)
        use_ef = comp.error_feedback and comp.active(scn)
        car = arena_init(fl.registered_clients, eta0=loop.eta0,
                         ef_width=(loop.layout.padded_size if use_ef
                                   else None))
        car = _maybe_resume_arena(args, car, int(state.round))
        arena = jax.tree.map(jnp.asarray, fed.arena())

        def stage_block(round0, n):
            idx, _, _ = fed.sample_block(fl.participation, K, args.batch,
                                         round0=round0, rounds=n)
            return jnp.asarray(idx), arena

        state = _run_fused(args, loop, state, args.rounds, stage_block,
                           log_fused_round, fleet_arena=car,
                           events=events, spans=spans)
        with spans.span("eval"):
            xt, yt = fed.test_batch(2000)
            acc = float(accuracy(logits_fn(state.params, jnp.asarray(xt)),
                                 jnp.asarray(yt)))
        print(f"final test-acc {acc:.4f}", flush=True)
        if stats:
            stats.report(args.out, extra={"final_acc": acc})
        _finish_run(events, spans)
        return state

    if args.rounds_per_call > 1:
        # round-fused path: stage the example arena on device ONCE and
        # ship only (R, C, K, b) gather indices per block — the in-scan
        # gather (repro.core.arena_gather) replaces the per-round host
        # gather + transfer, and the cohort index stream is the same
        # rng stream sample_round consumes, so metrics/params stay
        # bit-exact vs the host loop on the flat engine.
        from repro.core import arena_gather, make_fl_loop
        loop = make_fl_loop(
            loss_fn, copt, sopt,
            params_like=jax.eval_shape(init_fn, jax.random.key(args.seed)),
            num_rounds=args.rounds, rounds_per_call=args.rounds_per_call,
            flat=flat_backend(), scenario=scn,
            num_clients=args.num_clients,
            client_sizes=fed.client_sizes() if scn else None,
            compression=comp, gather=arena_gather,
            telemetry=telemetry)
        arena = jax.tree.map(jnp.asarray, fed.arena())

        def stage_block(round0, n):
            idx, _, _ = fed.sample_block(fl.participation, K, args.batch,
                                         round0=round0, rounds=n)
            return jnp.asarray(idx), arena

        state = _run_fused(args, loop, state, args.rounds, stage_block,
                           log_fused_round, events=events, spans=spans)
        with spans.span("eval"):
            xt, yt = fed.test_batch(2000)
            acc = float(accuracy(logits_fn(state.params, jnp.asarray(xt)),
                                 jnp.asarray(yt)))
        print(f"final test-acc {acc:.4f}", flush=True)
        if stats:
            stats.report(args.out, extra={"final_acc": acc})
        _finish_run(events, spans)
        return state

    round_fn = jax.jit(make_fl_round(
        loss_fn, copt, sopt, num_rounds=args.rounds, flat=flat,
        scenario=scn, num_clients=args.num_clients,
        client_sizes=fed.client_sizes() if scn else None,
        compression=comp, telemetry=telemetry))
    rlog = _RoundLog(_log_every(args), stats=stats, events=events)
    for t in range(args.rounds):
        # key the host-side cohort draw on the ROUND COUNTER IN THE
        # STATE, not the loop index: after --resume the loop restarts at
        # 0 but state.round continues, and the jitted round's scenario
        # draws (step counts, staleness, reported cohort_ids) are keyed
        # on state.round — this keeps the gathered data and the in-round
        # draws agreeing across resumes.
        batches, w, ids = fed.sample_round(fl.participation, K, args.batch,
                                           round_idx=int(state.round))
        batches = {"x": jnp.asarray(batches["x"]),
                   "y": jnp.asarray(batches["y"])}
        state, metrics, _ = round_fn(state, batches)
        # device rows buffer in _RoundLog (one batched device_get per
        # --log-every interval); only the sparse eval/print cadence
        # below syncs individual scalars
        rlog.push(t, metrics, ids=ids)
        _maybe_ckpt(args, state, t, final=(t == args.rounds - 1))
        if t % max(1, args.rounds // 10) == 0 or t == args.rounds - 1:
            with spans.span("eval"):
                xt, yt = fed.test_batch(2000)
                acc = accuracy(logits_fn(state.params, jnp.asarray(xt)),
                               jnp.asarray(yt))
            print(f"round {t:4d} loss {float(metrics['loss']):.4f} "
                  f"test-acc {float(acc):.4f} "
                  f"eta {float(metrics['eta_mean']):.4f}"
                  f"{_health_str(metrics)} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    rlog.flush()
    if stats:
        xt, yt = fed.test_batch(2000)
        acc = float(accuracy(logits_fn(state.params, jnp.asarray(xt)),
                             jnp.asarray(yt)))
        stats.report(args.out, extra={"final_acc": acc})
    _finish_run(events, None)
    return state


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--task", default=None,
                    choices=["easy", "medium", "hard", "image", "lm"])
    ap.add_argument("--model", default="mlp",
                    choices=["mlp", "mlp-wide", "cnn"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients-per-round", type=int, default=4)
    ap.add_argument("--num-clients", type=int, default=100)
    ap.add_argument("--num-registered", type=int, default=None,
                    help="fleet regime (paper tasks): C_registered "
                         "clients known to the server, sampled over by "
                         "the schedulers; registered client i trains on "
                         "data partition i %% num_clients. Defaults to "
                         "the scenario's registered_hint (the fleet_* "
                         "presets set 100k), else legacy "
                         "registered == num_clients.")
    ap.add_argument("--participation", type=float, default=None,
                    help="participation rate p (|S_t| = p*C_registered); "
                         "defaults to the scenario's participation_hint, "
                         "else 0.1")
    ap.add_argument("--eta-carry", action="store_true",
                    help="fleet: warm-start a returning client's eta0 "
                         "from its arena row instead of the scalar eta0 "
                         "(off = Algorithm 1's per-round reset)")
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--client-opt", default="delta_sgd")
    ap.add_argument("--server-opt", default="fedavg")
    ap.add_argument("--scenario", default=None,
                    help="federation scenario preset "
                         "(repro.federation.scenarios: sync_iid, "
                         "dirichlet_stragglers, zipf_async, ...)")
    ap.add_argument("--out", default=None,
                    help="write the scenario report JSON here")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"],
                    help="client->server delta compression on the flat "
                         "engine (repro.compression); auto-enables it")
    ap.add_argument("--k-frac", type=float, default=0.25,
                    help="topk: fraction of each 128-lane chunk kept")
    ap.add_argument("--error-feedback", action="store_true",
                    help="EF21 error feedback (per-cohort-slot state in "
                         "FLState.ef)")
    ap.add_argument("--robust-agg", default="mean",
                    choices=["mean", "clip", "trimmed", "median"],
                    help="robust server aggregation on the flat engine "
                         "(repro.federation.faults); overrides the "
                         "scenario preset's choice")
    ap.add_argument("--quorum", type=int, default=0,
                    help="skip the server update when fewer than Q "
                         "clients survive the round's faults")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--fedprox-mu", type=float, default=0.0)
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="R > 1 fuses R rounds into one jitted lax.scan "
                         "on persistent flat state (repro.core.fed_loop); "
                         "requires --client-opt delta_sgd")
    ap.add_argument("--flat", action="store_true",
                    help="force the flat Δ-SGD engine in the host loop "
                         "(the engine --rounds-per-call fuses, for "
                         "bit-exact parity runs)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="in-scan telemetry block (repro.telemetry): "
                         "per-round eta histogram, loss deciles, guard "
                         "hit counts ride the round metrics — "
                         "trajectory stays bit-exact")
    ap.add_argument("--log-every", type=int, default=0,
                    help="host-loop metric conversion interval (rounds "
                         "per batched device_get); 0 = ~10 per run")
    ap.add_argument("--events", default=None,
                    help="write a structured JSONL event log here "
                         "(header: config hash, git sha, jax versions; "
                         "flushed once per block boundary)")
    ap.add_argument("--profile", type=int, default=0,
                    help="profile the fused block containing this "
                         "(1-based) round: jax.profiler trace to "
                         "--profile-dir + an HLO-derived static "
                         "telemetry row (collectives/round, pallas "
                         "launch counts); needs --rounds-per-call > 1")
    ap.add_argument("--profile-dir", default="experiments/profile",
                    help="jax.profiler trace output directory")
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.profile and args.rounds_per_call <= 1:
        ap.error("--profile needs the round-fused engine: pass "
                 "--rounds-per-call > 1")
    if args.arch:
        train_lm(args)
    elif args.task:
        train_paper_task(args)
    else:
        ap.error("pass --arch or --task")


if __name__ == "__main__":
    main()
