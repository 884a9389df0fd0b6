"""Smoke run of the main path on a TPU, through the entry points' own
functions.

    python chip_smoke.py              # one chip: fleet, lm, serve
    python chip_smoke.py --chips 4    # four chips: the client-mesh phase only

Phases on one chip:

  fleet  ``launch/train.py`` on the paper task in the fleet regime
         (``--scenario fleet_zipf``: 10^5 registered clients), fused
         loop, telemetry on. The loss must be finite and the Δ-SGD
         kernel pair must have been built.
  lm     ``launch/train.py`` federating whisper-tiny at its published
         widths and depth with random weights, fused loop; finite losses
         and a written checkpoint.
  serve  ``launch/serve.py`` on that checkpoint answers 8 load-generator
         requests at one prompt length; every request returns its
         tokens, and one request's tokens equal that prompt decoded
         alone.

``--chips 4`` runs only the block-sharded fused loop on a 4-device
client mesh against the replicated fused loop on the default device;
final params must agree within 1e-5.

Each phase prints one JSON line: wall and compile seconds, persistent
cache hits and misses, the device's peak bytes in use, and the Pallas
launches per kernel namespace with the mode they ran in. The last line
is ``{"ok": true, "device": {...}}``. Without a TPU the script exits 1
before any phase; a failed phase makes it exit 1 after the others ran.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FLEET_ARGS = ["--task", "medium", "--model", "mlp",
              "--scenario", "fleet_zipf", "--rounds", "8",
              "--rounds-per-call", "4", "--telemetry"]
# batch 1 per client step: at batch 2 the fused block's program needs
# 14.6 GB of the v5e's 15.75 GB, leaving no room for the live state
LM_ARGS = ["--arch", "whisper-tiny", "--rounds", "4",
           "--rounds-per-call", "2", "--clients-per-round", "4",
           "--local-steps", "2", "--batch", "1", "--seq", "448"]
SERVE_ARGS = ["--arch", "whisper-tiny", "--loadgen", "8",
              "--arrival", "closed", "--batch", "4", "--prompt-len", "64",
              "--gen", "16"]
MESH_TOL = 1e-5


class _CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (a compile that hits the cache still counts
    its retrieval time)."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _kernels():
    """Pallas launches built per namespace, and the mode they ran in."""
    from repro.kernels import interpret_mode
    from repro.telemetry import kernel_launch_snapshot
    per_ns = {}
    for key, n in kernel_launch_snapshot().items():
        ns, op = key.split("/", 1)
        per_ns.setdefault(ns, {})[op] = n
    return {"launches": per_ns,
            "mode": "interpret" if interpret_mode() else "compiled"}


def _losses(events_path):
    from repro.telemetry.events import load_events
    _, events = load_events(events_path)
    return [float(e["loss"]) for e in events if e["kind"] == "round"]


def phase_fleet(out, argv=FLEET_ARGS):
    from repro.launch.train import build_parser, train_paper_task
    events = os.path.join(out, "fleet_events.jsonl")
    args = build_parser().parse_args(list(argv) + ["--events", events])
    train_paper_task(args)
    losses = _losses(events)
    kern = _kernels()
    pair = kern["launches"].get("delta_sgd", {})
    if not losses or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"fleet losses not finite: {losses}")
    if not (pair.get("batched_norms") and pair.get("batched_apply")):
        raise AssertionError(f"Δ-SGD kernel pair not built: {kern}")
    return {"rounds": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1], "kernels": kern}


def phase_lm(out, argv=LM_ARGS):
    from repro.checkpoint import latest_step
    from repro.launch.train import build_parser, train_lm
    events = os.path.join(out, "lm_events.jsonl")
    ckpt = os.path.join(out, "lm_ckpt")
    args = build_parser().parse_args(
        list(argv) + ["--events", events, "--ckpt-dir", ckpt])
    state = train_lm(args)
    losses = _losses(events)
    if not losses or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"lm losses not finite: {losses}")
    step = latest_step(ckpt)
    if step != int(state.round):
        raise AssertionError(f"checkpoint step {step} != round "
                             f"{int(state.round)}")
    return {"rounds": len(losses), "loss_first": losses[0],
            "loss_last": losses[-1], "ckpt_step": step, "ckpt_dir": ckpt,
            "kernels": _kernels()}


def _isolated_decode(args, prompt, extras):
    """The prompt decoded alone: B=1 prefill, then lockstep greedy
    decode, on the parameters the server loaded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import restore_params
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import greedy_decode
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, jnp.float32)
    params, _ = restore_params(args.ckpt_dir,
                               model.init(jax.random.key(args.seed)))
    batch = {"tokens": jnp.asarray(prompt[None])}
    for k, v in (extras or {}).items():
        batch[k] = jnp.asarray(v)[None]
    cache_len = args.prompt_len + args.gen
    logits, cache = jax.jit(lambda p, b: model.prefill(
        p, b, cache_len=cache_len))(params, batch)
    tok0 = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    toks, _, _ = greedy_decode(model, params, cache, tok0, args.gen - 1)
    return np.concatenate([np.asarray(tok0)[0], np.asarray(toks)[0]])


def phase_serve(out, ckpt_dir, argv=SERVE_ARGS):
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import _row_extras, build_parser, run
    from repro.serving import Workload, make_requests
    args = build_parser().parse_args(list(argv) + ["--ckpt-dir", ckpt_dir])
    res = run(args)
    done = res["completed"][:args.loadgen]
    if len(done) != args.loadgen:
        raise AssertionError(f"{len(done)} of {args.loadgen} requests "
                             f"answered")
    short = [c.request_id for c in done if len(c.tokens) != args.gen]
    if short:
        raise AssertionError(f"requests {short} did not return "
                             f"{args.gen} tokens")
    # the request stream and the extras run() drew, rebuilt from the seed
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    extras = _row_extras(cfg, np.random.default_rng(args.seed))
    wl = Workload(num_requests=args.loadgen, arrival=args.arrival,
                  rate=args.rate, concurrency=args.slots or args.batch,
                  prompt_lens=(args.prompt_len,), gen_lens=(args.gen,),
                  seed=args.seed)
    prompt = make_requests(wl, cfg.vocab_size)[0][0]
    first = min(done, key=lambda c: c.request_id)
    alone = _isolated_decode(args, prompt, extras)
    if not np.array_equal(first.tokens, alone):
        raise AssertionError(f"request {first.request_id} decoded "
                             f"{first.tokens.tolist()} in the pool, "
                             f"{alone.tolist()} alone")
    rep = res["report"]
    return {"requests": len(done), "gen": args.gen,
            "prompt_len": args.prompt_len,
            "tok_per_s": rep["tok_per_s"], "p50_s": rep["p50_s"],
            "p99_s": rep["p99_s"], "isolated_match": True,
            "kernels": _kernels()}


def phase_mesh(devices, rounds=2, clients=32, local_steps=2, batch=32):
    """Block-sharded fused loop on a client mesh over ``devices`` vs the
    replicated fused loop on the default device, paper-task MLP."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.paper_tasks import MLP_SMALL
    from repro.core import (arena_gather, flatten_fl_state, get_client_opt,
                            get_server_opt, init_fl_state, make_fl_loop,
                            make_loss)
    from repro.data.pipeline import FederatedDataset
    from repro.data.synthetic import get_task
    from repro.kernels import flat_backend
    from repro.launch.mesh import make_mesh
    from repro.models.small import make_small_model, softmax_ce
    from repro.sharding.spec import FederationSpec

    task = get_task("medium", seed=0)
    fed = FederatedDataset.build(task, num_clients=clients, alpha=0.1,
                                 seed=0)
    init_fn, logits_fn = make_small_model(MLP_SMALL)
    loss = make_loss(lambda p, b: (softmax_ce(logits_fn(p, b["x"]),
                                              b["y"]), {}))
    copt, sopt = get_client_opt("delta_sgd"), get_server_opt("fedavg")
    params = init_fn(jax.random.key(0))
    idx, _, _ = fed.sample_block(1.0, local_steps, batch, round0=0,
                                 rounds=rounds)
    batches = arena_gather(jax.tree.map(jnp.asarray, fed.arena()),
                           jnp.asarray(idx))
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    spec = FederationSpec(client_axes=("data",), fsdp_axes=(), tp_axes=())
    kw = dict(params_like=params, num_rounds=rounds, flat=flat_backend())
    rep = make_fl_loop(loss, copt, sopt, **kw)
    blk = make_fl_loop(loss, copt, sopt, mesh=mesh, federation=spec,
                       block_sharded=True, **kw)
    state = init_fl_state(params, sopt)
    fr, mr = jax.jit(rep)(flatten_fl_state(state, rep.layout), batches)
    fb, mb = jax.jit(blk)(flatten_fl_state(state, blk.layout), batches)
    err = float(jnp.max(jnp.abs(fr.P - fb.P)))
    losses = np.asarray(mb["loss"]).tolist()
    if not (err <= MESH_TOL and all(math.isfinite(x) for x in losses)):
        raise AssertionError(f"block vs replicated max|ΔP| {err} "
                             f"(tol {MESH_TOL}), losses {losses}")
    return {"devices": len(devices), "clients": clients, "rounds": rounds,
            "max_abs_param_err": err, "tol": MESH_TOL,
            "loss_last": losses[-1], "kernels": _kernels()}


def _run_phase(name, fn, stats, results):
    from repro.telemetry import reset_kernel_launches
    reset_kernel_launches()
    s0, h0, m0 = stats.snapshot()
    t0 = time.perf_counter()
    row = {"phase": name}
    try:
        row.update(fn())
        row["ok"] = True
    except Exception:
        traceback.print_exc()
        row["ok"] = False
    s1, h1, m1 = stats.snapshot()
    row.update(wall_s=time.perf_counter() - t0, compile_s=s1 - s0,
               cache_hits=h1 - h0, cache_misses=m1 - m0,
               peak_bytes_in_use=_peak_bytes())
    print(json.dumps(row, default=str), flush=True)
    results[name] = row
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "experiments",
                                                  "chip_smoke"),
                    help="event logs and the lm checkpoint")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print("device:", json.dumps(device), flush=True)
    if device["platform"] != "tpu":
        print("no TPU found: nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print("compile cache:", enable_compile_cache(), flush=True)
    os.makedirs(args.out, exist_ok=True)
    stats = _CompileStats()
    results = {}
    if args.chips == 4:
        _run_phase("mesh", lambda: phase_mesh(devs[:4]), stats, results)
    else:
        _run_phase("fleet", lambda: phase_fleet(args.out), stats, results)
        lm = _run_phase("lm", lambda: phase_lm(args.out), stats, results)
        if lm["ok"]:
            _run_phase("serve", lambda: phase_serve(args.out,
                                                    lm["ckpt_dir"]),
                       stats, results)
        else:
            results["serve"] = {"ok": False}
            print(json.dumps({"phase": "serve", "ok": False,
                              "skipped": "lm phase failed"}), flush=True)
    if not all(r["ok"] for r in results.values()):
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
