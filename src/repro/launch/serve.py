"""Serving driver: continuous-batching greedy decode on the
:mod:`repro.serving` engine, for any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b --reduced \
      --batch 4 --prompt-len 64 --gen 32

The engine replaces the old per-token host loop: decode runs in fused
``flush_tokens``-step ``lax.scan`` blocks with ONE device_get per
flush (see ``repro/serving/engine.py``). ``--ckpt-dir`` loads params
from a checkpoint (``repro.checkpoint.restore_params`` — training
FLState checkpoints work directly: the manifest's ``params/...`` keys
match the serving template) AND keeps watching the directory through a
:class:`~repro.serving.registry.ModelRegistry`: a newer round saved
mid-run hot-swaps at the next flush boundary. ``--ckpt-step`` pins a
step (default: latest) — pinning disables the watch.

``--loadgen N`` switches from the one-batch demo to the load
generator: N requests (Poisson or closed-loop arrival), reporting
tokens/s, p50/p99 latency, occupancy, and swap stall. ``--personalize
K`` registers K synthetic client deltas and routes a fraction of
load-gen traffic through the personalized-decode overlay (real fleet
deltas come from ``PersonalizationStore.from_arena`` on a training
arena checkpoint). ``--events`` streams per-flush serving telemetry
(schema-checked JSONL, ``docs/TELEMETRY.md`` rows).

``--window`` must cover the full request (image tokens + prompt + gen)
unless ``--roll-cache`` is passed, in which case the KV cache is sized
to the window and rolls as a ring buffer (tokens beyond the window are
evicted). Silently truncating the cache below the request length — the
old behaviour — corrupts decode state and is now an error.

``run(args)`` is the driver body; it returns the generated token batch
plus timing so tests can call it in-process.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--roll-cache", action="store_true",
                    help="with --window smaller than the full request, "
                         "size the cache to the window and roll it as a "
                         "ring buffer instead of erroring")
    ap.add_argument("--slots", type=int, default=None,
                    help="KV-pool slots (default: --batch)")
    ap.add_argument("--flush-tokens", type=int, default=8,
                    help="decode tokens fused per host flush")
    ap.add_argument("--ckpt-dir", default=None,
                    help="load params from this checkpoint dir "
                         "(training FLState checkpoints work: the "
                         "'params/' manifest prefix is matched) and "
                         "hot-swap when newer rounds appear")
    ap.add_argument("--ckpt-step", type=int, default=None,
                    help="checkpoint step to load (default: latest; "
                         "pinning disables the hot-swap watch)")
    ap.add_argument("--loadgen", type=int, default=0,
                    help="run the load generator with N requests "
                         "instead of the one-batch demo")
    ap.add_argument("--arrival", choices=("poisson", "closed"),
                    default="poisson")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="poisson arrival rate (req/s)")
    ap.add_argument("--personalize", type=int, default=0,
                    help="register N synthetic client deltas; load-gen "
                         "traffic is partly routed through them")
    ap.add_argument("--events", default=None,
                    help="write per-flush serving telemetry JSONL here")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _row_extras(cfg, rng):
    ex = {}
    if cfg.encoder_layers:
        ex["frames"] = rng.normal(
            size=(cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    if cfg.num_image_tokens:
        ex["image_embeds"] = rng.normal(
            size=(cfg.num_image_tokens, cfg.d_model)).astype(np.float32)
    return ex or None


def run(args) -> dict:
    """Serve one batch (or a load-gen stream); returns {"tokens":
    (B, gen) int32 array, "tok_per_s": float, "ckpt_step": int | None,
    "metrics": engine counters, "report": load-gen report | None,
    "completed": every Completion, load-gen requests first}."""
    from repro.serving import (DecodeEngine, ModelRegistry,
                               PersonalizationStore, Workload, run_load)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, jnp.float32)
    params = model.init(jax.random.key(args.seed))

    ckpt_step, registry = None, None
    if args.ckpt_dir:
        from repro.checkpoint import restore_params
        params, ckpt_step = restore_params(args.ckpt_dir, params,
                                           step=args.ckpt_step)
        print(f"loaded params from {args.ckpt_dir} step {ckpt_step}")
        if args.ckpt_step is None:        # unpinned: watch for new rounds
            registry = ModelRegistry(args.ckpt_dir, params)
            registry.version = ckpt_step

    B, S, gen = args.batch, args.prompt_len, args.gen
    full_len = (cfg.num_image_tokens or 0) + S + gen
    window = args.window
    if window and window < full_len:
        if not args.roll_cache:
            raise SystemExit(
                f"--window {window} is smaller than the full request "
                f"({full_len} = image tokens + prompt + gen): the KV "
                f"cache would be silently truncated and decode state "
                f"corrupted. Pass --roll-cache to serve with a rolling "
                f"ring-buffer cache, or raise --window.")
        cache_len = window
    else:
        cache_len = full_len

    rng = np.random.default_rng(args.seed)
    store = None
    if args.personalize:
        store = PersonalizationStore(params, scale=1.0)
        for cid in range(args.personalize):
            store.set_delta(cid, jnp.asarray(
                rng.normal(scale=1e-3, size=(store.layout.padded_size,)),
                jnp.float32))
    events = None
    if args.events:
        from repro.telemetry import EventLog
        events = EventLog(args.events, config={
            "arch": args.arch, "mode": "serve", "slots":
            args.slots or B, "flush_tokens": args.flush_tokens})

    engine = DecodeEngine(model, params, slots=args.slots or B,
                          cache_len=cache_len,
                          flush_tokens=args.flush_tokens, window=window,
                          version=ckpt_step or 0, registry=registry,
                          personalization=store, events=events)

    report = None
    if args.loadgen:
        wl = Workload(num_requests=args.loadgen, arrival=args.arrival,
                      rate=args.rate, concurrency=engine.slots,
                      prompt_lens=(S,), gen_lens=(gen,),
                      personalized_frac=0.25 if store else 0.0,
                      client_ids=tuple(store.client_ids()) if store
                      else (0,), seed=args.seed)
        report = run_load(engine, wl, cfg.vocab_size,
                          extras=_row_extras(cfg, rng))
        print(f"loadgen: {report['requests']} requests, "
              f"{report['tok_per_s']:.1f} tok/s, "
              f"p50 {report['p50_s'] * 1e3:.1f}ms "
              f"p99 {report['p99_s'] * 1e3:.1f}ms, "
              f"occupancy {report['occupancy']:.2f}, "
              f"swaps {report['swaps']}")

    # the one-batch demo (also the deterministic surface tests rely on)
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    rids = [engine.submit(prompts[i], gen, extras=_row_extras(cfg, rng))
            for i in range(B)]
    t0 = time.time()
    done = {c.request_id: c.tokens for c in engine.run_until_idle()}
    dt = time.time() - t0
    toks = np.stack([done[r] for r in rids])
    print(f"decoded {gen} tokens x {B} in {dt:.2f}s "
          f"({gen * B / max(dt, 1e-9):.1f} tok/s, "
          f"{engine.stats['flushes']} flushes)")
    print("sample:", toks[0][:16].tolist())
    if events is not None:
        events.close()
    return {"tokens": toks, "tok_per_s": gen * B / max(dt, 1e-9),
            "ckpt_step": ckpt_step, "metrics": engine.metrics(),
            "report": report, "history": engine.history,
            "completed": engine.completed}


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
