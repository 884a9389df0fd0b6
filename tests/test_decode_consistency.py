"""Serving-path correctness: prefill + decode_step must reproduce the full
forward's last-position logits for every architecture, including the
sliding-window ring-buffer path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model

# heavyweight tier: CI runs -m 'not slow' first (scripts/ci.sh)
pytestmark = pytest.mark.slow

B, S = 2, 33


def _mk(cfg, rng):
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    extra = {}
    if cfg.encoder_layers:
        extra["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.num_image_tokens:
        extra["image_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_image_tokens, cfg.d_model)),
            jnp.float32)
    return toks, extra


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_full(arch, rng):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    toks, extra = _mk(cfg, rng)
    full, _ = model.apply(params, {"tokens": toks, **extra})
    cache_len = S + (cfg.num_image_tokens or 0)
    _, cache = model.prefill(params, {"tokens": toks[:, :-1], **extra},
                             cache_len=cache_len)
    dec, _ = model.decode_step(params, cache, toks[:, -1:])
    a, b = np.asarray(full[:, -1]), np.asarray(dec[:, 0])
    err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert err < 2e-3, err


def test_sliding_window_ring_buffer(rng):
    """Decode with a ring buffer of W slots == full attention restricted to
    the last W positions."""
    cfg = get_config("tinyllama-1.1b").reduced()
    import dataclasses
    W = 16
    cfg = dataclasses.replace(cfg, sliding_window=W)
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    T = 40
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)

    # reference: full forward with window masking
    full, _ = model.apply(params, {"tokens": toks})  # apply has no window
    # decode from scratch through the ring buffer
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, window=W))
    cache = model.init_cache(B, W)
    for j in range(T):
        logits, cache = step(params, cache, toks[:, j:j + 1])
    # windowed reference via prefill(window=W) of first T-1 then one step
    _, cache2 = model.prefill(params, {"tokens": toks[:, :-1]}, window=W)
    logits2, _ = model.decode_step(params, cache2, toks[:, -1:], window=W)
    a = np.asarray(logits[:, 0])
    b = np.asarray(logits2[:, 0])
    err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert err < 2e-3, err


def test_multistep_decode_matches_full(rng):
    """Greedy-decode 8 steps vs teacher-forced full forwards."""
    cfg = get_config("zamba2-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(3))
    T0, Tn = 16, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T0 + Tn)),
                       jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks[:, :T0]},
                             cache_len=T0 + Tn)
    step = jax.jit(model.decode_step)
    for j in range(Tn):
        dec, cache = step(params, cache, toks[:, T0 + j:T0 + j + 1])
        full, _ = model.apply(params, {"tokens": toks[:, :T0 + j + 1]})
        a = np.asarray(full[:, -1])
        b = np.asarray(dec[:, 0])
        err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
        assert err < 2e-3, (j, err)
