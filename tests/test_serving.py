"""Serving plane (repro.serving): fused-scan decode parity, continuous
batching exactness, checkpoint hot-swap atomicity + KV reuse,
personalized decode, load-generator metrics, train->serve round trip,
and warm fleet-arena resume.

The parity tests all reduce to the same contract: the engine is an
OPTIMIZATION of one-request-at-a-time greedy decode, never a different
decoder. Greedy argmax over f32 logits is deterministic, so every
comparison here is exact token equality, not a tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import save
from repro.configs import get_config
from repro.models import build_model
from repro.serving import (DecodeEngine, ModelRegistry,
                           PersonalizationStore, Workload, greedy_decode,
                           make_requests, run_load)

ARCH = "tinyllama-1.1b"


def _setup(arch=ARCH, seed=0):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, jnp.float32)
    return cfg, model, model.init(jax.random.key(seed))


def _prefill(model, params, prompt, cache_len):
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=cache_len))(
        params, {"tokens": jnp.asarray(np.asarray(prompt)[None])})
    return jnp.argmax(logits[:, -1:], -1).astype(jnp.int32), cache


def _isolated_decode(model, params, prompt, gen, cache_len):
    """Reference: the request decoded alone, fused lockstep."""
    tok0, cache = _prefill(model, params, prompt, cache_len)
    toks, _, _ = greedy_decode(model, params, cache, tok0, gen - 1)
    return np.concatenate([np.asarray(tok0)[0], np.asarray(toks)[0]])


# ---------------------------------------------------------------- fused
def test_fused_decode_token_exact_vs_host_loop():
    """Satellite 1: the lax.scan decode emits token-identical output to
    the legacy per-token host loop it replaced."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    B, S, G = 2, 16, 8
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    logits, cache = jax.jit(lambda p, b: model.prefill(
        p, b, cache_len=S + G))(params, batch)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)

    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
    c, t, out = cache, tok, [tok]
    for _ in range(G - 1):
        lg, c = step(params, c, t)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        out.append(t)
    host = np.asarray(jnp.concatenate(out, 1))

    toks, _, _ = greedy_decode(model, params, cache, tok, G - 1)
    fused = np.concatenate([np.asarray(tok), np.asarray(toks)], axis=1)
    np.testing.assert_array_equal(host, fused)


# ------------------------------------------------- continuous batching
def test_continuous_batching_token_exact_vs_isolated():
    """A request admitted into a busy pool — different prompt lengths,
    staggered admission, slots freed and reused — decodes exactly the
    tokens it gets alone (per-slot positions/ring slots really are
    independent)."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(1)
    eng = DecodeEngine(model, params, slots=3, cache_len=64,
                       flush_tokens=4)
    prompts = [rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (16, 9, 23, 5)]
    gens = [8, 11, 5, 9]
    rids = [eng.submit(prompts[0], gens[0]),
            eng.submit(prompts[1], gens[1])]
    done = eng.step()                       # staggered: 2 running...
    rids.append(eng.submit(prompts[2], gens[2]))   # ...then a 3rd
    done += eng.step()
    rids.append(eng.submit(prompts[3], gens[3]))   # reuses a freed slot
    done += eng.run_until_idle()
    got = {c.request_id: c.tokens for c in done}
    assert sorted(got) == sorted(rids)
    for rid, p, g in zip(rids, prompts, gens):
        np.testing.assert_array_equal(
            got[rid], _isolated_decode(model, params, p, g, 64),
            err_msg=f"request {rid} diverged in the shared pool")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["zamba2-7b", "xlstm-1.3b",
                                  "deepseek-v3-671b"])
def test_continuous_batching_other_archs(arch):
    """Same exactness through SSM (mamba2/mlstm/slstm) state pools and
    the MLA latent cache. (deepseek's MoE routing drops no token, so
    a request's experts do not depend on what shares its batch.)"""
    cfg, model, params = _setup(arch)
    rng = np.random.default_rng(2)
    eng = DecodeEngine(model, params, slots=2, cache_len=48,
                       flush_tokens=4)
    prompts = [rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32)
               for s in (12, 7)]
    gens = [6, 9]
    rids = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    got = {c.request_id: c.tokens for c in eng.run_until_idle()}
    for rid, p, g in zip(rids, prompts, gens):
        np.testing.assert_array_equal(
            got[rid], _isolated_decode(model, params, p, g, 48))


def test_submit_rejects_oversized_request():
    cfg, model, params = _setup()
    eng = DecodeEngine(model, params, slots=2, cache_len=16)
    with pytest.raises(ValueError, match="cache_len"):
        eng.submit(np.zeros(12, np.int32), 8)   # 12 + 8 > 16, no window


# ------------------------------------------------------------ hot swap
def test_hot_swap_atomic_per_flush(tmp_path):
    """A checkpoint published mid-request swaps in at exactly ONE flush
    boundary: the request's token stream is prefix-exact under the old
    params and suffix-exact under the new params WITH THE OLD KV CACHE
    (shape-compatible swap reuses the pool), and the engine records one
    swap with a positive stall."""
    cfg, model, params = _setup(seed=0)
    params2 = model.init(jax.random.key(1))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    G, F = 9, 4                      # 1 prefill token + 2 flushes of 4

    save(str(tmp_path), params, step=1)
    reg = ModelRegistry(str(tmp_path), params)
    eng = DecodeEngine(model, params, slots=2, cache_len=32,
                       flush_tokens=F, registry=reg)
    assert eng.version == 1          # initial version staged at build
    eng.submit(prompt, G)
    done = eng.step()                # flush 0: tokens 2..5 under v1
    assert not done
    save(str(tmp_path), params2, step=2)
    done = eng.run_until_idle()      # flush 1 swaps, tokens 6..9 v2
    assert len(done) == 1

    m = eng.metrics()
    assert m["serve_swaps_total"] == 1
    assert m["serve_swap_stall_max"] > 0.0
    assert m["kv_reuse_swaps"] == 1          # slot was live at swap
    assert [h["version"] for h in eng.history] == [1, 2]
    assert done[0].versions == (1, 2)

    # replay: v1 prefill + v1 flush, then v2 continues on the SAME cache
    tok0, cache = _prefill(model, params, prompt, 32)
    t1, cache, last = greedy_decode(model, params, cache, tok0, F)
    t2, _, _ = greedy_decode(model, params2, cache, last, F)
    ref = np.concatenate([np.asarray(tok0)[0], np.asarray(t1)[0],
                          np.asarray(t2)[0]])
    np.testing.assert_array_equal(done[0].tokens, ref)


def test_swap_shape_gate():
    """Same-shape params swap in; a different architecture is refused
    (the KV pool cannot be reused across an architecture change)."""
    cfg, model, params = _setup(seed=0)
    eng = DecodeEngine(model, params, slots=2, cache_len=32)
    eng.swap(model.init(jax.random.key(1)), 5)
    assert eng.version == 5
    other = build_model(get_config(ARCH).reduced(num_layers=1,
                                                 d_model=128),
                        jnp.float32).init(jax.random.key(0))
    with pytest.raises(ValueError, match="hot-swap refused"):
        eng.swap(other, 6)
    assert eng.version == 5          # refused swap left version alone


# ----------------------------------------------------- personalization
def test_personalized_decode_parity():
    """Engine decode under a registered client's delta == decoding
    under the manually overlaid params, and != the global decode when
    the delta is non-trivial (acceptance: personalized differs from
    global exactly by the client's arena delta)."""
    from repro.core.flat import pack, unpack
    cfg, model, params = _setup()
    rng = np.random.default_rng(4)
    store = PersonalizationStore(params, scale=1.0)
    delta = jnp.asarray(rng.normal(scale=5e-2,
                                   size=(store.layout.padded_size,)),
                        jnp.float32)
    store.set_delta(7, delta)
    params_c = unpack(pack(params, store.layout) + delta, store.layout)

    prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    eng = DecodeEngine(model, params, slots=3, cache_len=32,
                       flush_tokens=4, personalization=store)
    r_pers = eng.submit(prompt, 8, client_id=7)
    r_glob = eng.submit(prompt, 8)
    r_unkn = eng.submit(prompt, 8, client_id=99)  # no delta -> global
    got = {c.request_id: c.tokens for c in eng.run_until_idle()}

    ref_pers = _isolated_decode(model, params_c, prompt, 8, 32)
    ref_glob = _isolated_decode(model, params, prompt, 8, 32)
    np.testing.assert_array_equal(got[r_pers], ref_pers)
    np.testing.assert_array_equal(got[r_glob], ref_glob)
    np.testing.assert_array_equal(got[r_unkn], ref_glob)
    assert not np.array_equal(got[r_pers], got[r_glob])


def test_personalization_store_from_arena():
    """from_arena lifts the fleet arena's EF21 slab into per-client
    deltas (row i -> client i) and rejects arenas without one or with
    a mismatched layout width."""
    from repro.federation import arena_init
    _, model, params = _setup()
    store0 = PersonalizationStore(params)
    N = store0.layout.padded_size
    arena = arena_init(4, eta0=0.1, ef_width=N)
    ef = np.zeros((4, N), np.float32)
    ef[2, :5] = 1.5
    arena = arena._replace(ef=jnp.asarray(ef))
    store = PersonalizationStore.from_arena(arena, params)
    assert store.client_ids() == [0, 1, 2, 3]
    np.testing.assert_array_equal(
        np.asarray(store._deltas[2]), ef[2])
    with pytest.raises(ValueError, match="no EF21 slab"):
        PersonalizationStore.from_arena(arena_init(4, eta0=0.1), params)
    bad = arena._replace(ef=jnp.zeros((4, N + 128)))
    with pytest.raises(ValueError, match="EF width"):
        PersonalizationStore.from_arena(bad, params)


# ------------------------------------------------------ load generator
def test_loadgen_metrics_sane():
    cfg, model, params = _setup()
    eng = DecodeEngine(model, params, slots=3, cache_len=32,
                       flush_tokens=4)
    wl = Workload(num_requests=6, arrival="closed", concurrency=3,
                  prompt_lens=(8, 12), gen_lens=(4, 6), seed=0)
    rep = run_load(eng, wl, cfg.vocab_size)
    assert rep["requests"] == 6
    assert rep["tok_per_s"] > 0
    assert rep["p99_s"] >= rep["p50_s"] > 0
    assert 0 < rep["occupancy"] <= 1
    assert rep["swaps"] == 0


def test_loadgen_request_stream_deterministic():
    wl = Workload(num_requests=10, arrival="poisson", rate=50.0,
                  prompt_lens=(8, 16), gen_lens=(4, 8),
                  personalized_frac=0.5, client_ids=(0, 1), seed=3)
    a, b = make_requests(wl, 512), make_requests(wl, 512)
    assert len(a) == 10
    for (pa, ga, ca, ta), (pb, gb, cb, tb) in zip(a, b):
        np.testing.assert_array_equal(pa, pb)
        assert (ga, ca, ta) == (gb, cb, tb)
    arrivals = [t for *_, t in a]
    assert arrivals == sorted(arrivals)


def test_engine_emits_flush_events(tmp_path):
    """Per-flush JSONL telemetry: one serve_flush row per flush with
    the schema-registered fields, flushed at the flush boundary."""
    from repro.telemetry import EventLog, load_events
    from repro.telemetry.schema import REGISTRY
    for name in ("serve_tokens", "serve_occupancy", "serve_version",
                 "serve_swapped", "serve_swap_stall_s",
                 "serve_tok_per_s", "serve_latency_p50_s",
                 "serve_latency_p99_s"):
        assert name in REGISTRY, f"{name} missing from telemetry schema"
    cfg, model, params = _setup()
    path = str(tmp_path / "events.jsonl")
    events = EventLog(path, config={"mode": "serve"})
    eng = DecodeEngine(model, params, slots=2, cache_len=32,
                       flush_tokens=4, events=events)
    eng.submit(np.zeros(8, np.int32), 6)
    eng.run_until_idle()
    events.close()
    _, evs = load_events(path)
    rows = [e for e in evs if e["kind"] == "serve_flush"]
    assert len(rows) == eng.stats["flushes"] > 0
    assert rows[0]["serve_tokens"] > 0
    assert rows[0]["serve_version"] == 0


# ------------------------------------------- train -> serve round trip
@pytest.mark.slow
def test_train_serve_round_trip(tmp_path):
    """Two fused training blocks checkpoint rounds 2 and 4; the
    registry serves the LATEST round, and a newer checkpoint published
    mid-serve triggers exactly one hot swap."""
    from repro.launch.train import build_parser, train_lm
    ckpt = str(tmp_path / "ckpt")
    args = build_parser().parse_args(
        ["--arch", ARCH, "--reduced", "--layers", "2",
         "--d-model", "256", "--rounds", "4", "--rounds-per-call", "2",
         "--clients-per-round", "2", "--local-steps", "1",
         "--batch", "2", "--seq", "16", "--ckpt-dir", ckpt,
         "--ckpt-every", "2"])
    final = train_lm(args)

    cfg, model, params = _setup()
    reg = ModelRegistry(ckpt, params)
    eng = DecodeEngine(model, params, slots=2, cache_len=32,
                       flush_tokens=4, registry=reg)
    assert eng.version == 4          # latest round staged at build
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    rid = eng.submit(prompt, 9)
    eng.step()
    # a newer round lands mid-request -> exactly one swap next flush
    save(ckpt, final.params, step=6)
    done = {c.request_id: c for c in eng.run_until_idle()}
    assert eng.metrics()["serve_swaps_total"] == 1
    assert done[rid].versions == (4, 6)
    # and the trained params really drive decode: fresh-init differs
    fresh = _isolated_decode(model, params, prompt, 9, 32)
    assert not np.array_equal(done[rid].tokens, fresh)


# -------------------------------------------------- warm fleet resume
def _fleet_args(ckpt, rounds, resume=False):
    from repro.launch.train import build_parser
    argv = ["--task", "easy", "--rounds", str(rounds),
            "--rounds-per-call", "2", "--clients-per-round", "4",
            "--num-clients", "8", "--num-registered", "32",
            "--participation", "0.25", "--eta-carry",
            "--local-steps", "1", "--batch", "16", "--ckpt-dir", ckpt,
            "--ckpt-every", "2", "--seed", "0"]
    if resume:
        argv.append("--resume")
    return build_parser().parse_args(argv)


@pytest.mark.slow
def test_fleet_arena_resume_bit_exact(tmp_path):
    """Satellite 2 acceptance: a fleet run (--num-registered, η carry)
    interrupted at round 2 and --resume'd matches the uninterrupted
    run bit for bit — params AND the restored client arena (η carry,
    participation counters). Requires (a) the arena riding the
    checkpoint under <ckpt_dir>/arena and (b) the data pipeline's
    within-client draws being (seed, round)-keyed, not stream-stateful."""
    from repro.checkpoint import restore
    from repro.federation import arena_init
    from repro.launch.train import train_paper_task
    ref, cut = str(tmp_path / "ref"), str(tmp_path / "cut")
    straight = train_paper_task(_fleet_args(ref, 4))
    train_paper_task(_fleet_args(cut, 2))
    resumed = train_paper_task(_fleet_args(cut, 2, resume=True))
    assert int(straight.round) == int(resumed.round) == 4
    for a, b in zip(jax.tree_util.tree_leaves(straight.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    like = arena_init(32, eta0=0.05)
    ar, _ = restore(os.path.join(ref, "arena"), like=like, step=4)
    ac, _ = restore(os.path.join(cut, "arena"), like=like, step=4)
    for a, b in zip(jax.tree_util.tree_leaves(ar),
                    jax.tree_util.tree_leaves(ac)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(ac.rounds_seen).sum()) > 0   # warm, not cold