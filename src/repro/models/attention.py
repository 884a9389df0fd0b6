"""Attention blocks: GQA/MQA, MLA (DeepSeek-V3), cross-attention, sliding
window, and ring-buffer KV caches for decode.

Two modes everywhere:
  * full : whole-sequence causal attention (train / prefill). Optionally
           returns a freshly-built KV cache.
  * step : one new token against an existing cache (decode).

The quadratic jnp path here is the reference; the Pallas flash kernel
(repro/kernels/flash_attention) is plugged in via ``use_pallas``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import (apply_rope, dense_init, shard_logical,
                                 split_keys, zeros_init)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def init_attention(key, cfg, dtype):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, hd), dtype, fan_in=D),
        "wk": dense_init(ks[1], (D, KV, hd), dtype, fan_in=D),
        "wv": dense_init(ks[2], (D, KV, hd), dtype, fan_in=D),
        "wo": dense_init(ks[3], (H, hd, D), dtype, fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    return p


def init_mla(key, cfg, dtype):
    D = cfg.d_model
    H = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = split_keys(key, 6)
    if qr:
        q = {"wq_a": dense_init(ks[0], (D, qr), dtype, fan_in=D),
             "q_norm": jnp.ones((qr,), dtype),
             "wq_b": dense_init(ks[1], (qr, H, dn + dr), dtype, fan_in=qr)}
    else:                   # no query LoRA (DeepSeek-V2-Lite, Kanana-2)
        q = {"wq": dense_init(ks[0], (D, H, dn + dr), dtype, fan_in=D)}
    return {
        **q,
        "wkv_a": dense_init(ks[2], (D, kvr + dr), dtype, fan_in=D),
        "kv_norm": jnp.ones((kvr,), dtype),
        "wk_b": dense_init(ks[3], (kvr, H, dn), dtype, fan_in=kvr),
        "wv_b": dense_init(ks[4], (kvr, H, dv), dtype, fan_in=kvr),
        "wo": dense_init(ks[5], (H, dv, D), dtype, fan_in=H * dv),
    }


def init_cross_attention(key, cfg, dtype):
    return init_attention(key, cfg, dtype)


# ---------------------------------------------------------------------------
# Core scaled-dot-product with GQA grouping, query-chunked.
#
# The (S,T) score matrix is never materialised whole: queries are processed
# in NQ chunks (lax.scan), so the peak intermediate is (B,KV,G,S/NQ,T) —
# the pure-JAX analogue of the flash-attention tiling the Pallas kernel
# (repro/kernels/flash_attention) implements natively. The dry-run unrolls
# the chunk scan (models.common.unroll_scans) so cost analysis stays honest.
# ---------------------------------------------------------------------------
_NQ_TARGET = 8

# Beyond-paper perf knob (§Perf): store softmax weights in bf16 between the
# two attention matmuls — halves the dominant train/prefill HBM term; the
# max-subtracted exponent keeps values in [0,1] where bf16's 8 mantissa
# bits give ~3 decimal digits (validated vs f32 in tests).
SOFTMAX_BF16 = False


def _sdpa_block(qc, k, v, rows, *, causal, window):
    """qc: (B,L,H,hd), k/v: (B,T,H,hd), rows: (L,) absolute positions."""
    scale = 1.0 / np.sqrt(qc.shape[-1])
    T = k.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", qc.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], T), 1)
    mask = jnp.ones((rows.shape[0], T), bool)
    if causal:
        mask &= cols <= rows[:, None]
    if window is not None:
        mask &= (rows[:, None] - cols) < window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if SOFTMAX_BF16:
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m).astype(jnp.bfloat16)
        denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        out = jnp.einsum("bhst,bthd->bshd", p,
                         v.astype(jnp.bfloat16)).astype(jnp.float32)
        out = out / denom.swapaxes(1, 2)
        return out.astype(v.dtype)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", w, v.astype(jnp.float32))
    return out.astype(v.dtype)


def _sdpa(q, k, v, *, causal=True, window=None, q_offset=0,
          remat_chunks=False):
    """q: (B,S,KV,G,hd)  k,v: (B,T,KV,hd) -> (B,S,KV,G,hd).

    ``remat_chunks``: the backward recomputes each query chunk's scores
    instead of keeping every chunk's softmax weights (and mask) for it,
    so a layer's backward holds one chunk's (B,H,S/NQ,T) at a time.

    K/V are repeated to the full H = KV*G heads before the score einsum so
    the heads dim shards cleanly over the tensor-parallel axis (a sharded
    (KV, G) axis split confuses SPMD propagation and replicates the score
    chunks). The repeat is cheap (K/V ≪ scores); the Pallas kernel avoids
    it entirely via its index map.
    """
    from repro.models.common import scan_unroll
    B, S, KV, G, hd = q.shape
    H = KV * G
    qq = q.reshape(B, S, H, hd)
    kk = jnp.repeat(k, G, axis=2) if G > 1 else k
    vv = jnp.repeat(v, G, axis=2) if G > 1 else v
    qq = shard_logical(qq, ("batch", "seq", "heads", None))
    kk = shard_logical(kk, ("batch", "seq", "heads", None))
    vv = shard_logical(vv, ("batch", "seq", "heads", None))
    hv = v.shape[-1]  # MLA: value head dim can differ from q/k head dim
    nq = _NQ_TARGET if (S % _NQ_TARGET == 0 and S >= 2048) else 1
    if nq == 1:
        rows = q_offset + jnp.arange(S, dtype=jnp.int32)
        out = _sdpa_block(qq, kk, vv, rows, causal=causal, window=window)
        return out.reshape(B, S, KV, G, hv)
    L = S // nq
    qs = jnp.moveaxis(qq.reshape(B, nq, L, H, hd), 1, 0)
    block = functools.partial(_sdpa_block, causal=causal, window=window)
    if remat_chunks:
        block = jax.checkpoint(block)

    def body(_, xs):
        qc, ci = xs
        rows = q_offset + ci * L + jnp.arange(L, dtype=jnp.int32)
        return 0, block(qc, kk, vv, rows)

    _, out = jax.lax.scan(body, 0, (qs, jnp.arange(nq, dtype=jnp.int32)),
                          unroll=scan_unroll())
    return jnp.moveaxis(out, 0, 1).reshape(B, S, KV, G, hv)


def _sdpa_masked(q, k, v, mask):
    """Single-block SDPA with an explicit mask (decode: S=1, tiny)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v.astype(jnp.float32))
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA attention: full-sequence mode
# ---------------------------------------------------------------------------
def gqa_full(params, x, cfg, *, positions, window=None, build_cache=False,
             use_pallas=False):
    """x: (B,S,D). Returns (out, cache|None)."""
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_logical(q, ("batch", "seq", "heads", None))
    k = shard_logical(k, ("batch", "seq", "kv_heads", None))
    if use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        qg = q.reshape(B, S, KV, H // KV, hd)
        out = _sdpa(qg, k, v, causal=True, window=window
                    ).reshape(B, S, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    cache = {"k": k, "v": v} if build_cache else None
    return y, cache


def _cache_write(buf, val, slot, vec):
    """Write one decode entry into a (B, W, ...) ring buffer.

    Scalar ``slot``: the legacy lockstep write — every batch row stores
    at the same index (dynamic_update_slice). Vector ``slot`` (B,): the
    per-slot serving form — row b writes at its OWN index slot[b]."""
    if vec:
        return buf.at[jnp.arange(buf.shape[0]), slot].set(val[:, 0])
    return jax.lax.dynamic_update_slice_in_dim(buf, val, slot, axis=1)


def gqa_step(params, x, cfg, cache, *, t, slot, positions_buf, window=None):
    """One decode step. x: (B,1,D); cache k/v: (B,W,KV,hd) ring buffer.

    t: scalar absolute position of the new token. slot: write index in the
    ring buffer. positions_buf: (W,) absolute position of each slot (-1 =
    empty), already updated by the caller for this step.

    Vectorized (continuous-batching) form: ``t``/``slot`` may be (B,)
    int32 with ``positions_buf`` (B, W) — every batch row then decodes
    at its OWN absolute position, writes its OWN ring slot, and masks
    against its OWN position row (the serving engine's per-slot
    sequence state). Scalar inputs take the original lockstep path
    unchanged.
    """
    B = x.shape[0]
    vec = jnp.ndim(t) > 0
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.rope_theta:
        pos = t[:, None] if vec else jnp.full((B, 1), t, jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    quant = "k_scale" in cache
    if quant:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        ck = _cache_write(cache["k"], kq, slot, vec)
        cv = _cache_write(cache["v"], vq, slot, vec)
        cks = _cache_write(cache["k_scale"], ks, slot, vec)
        cvs = _cache_write(cache["v_scale"], vs, slot, vec)
        kd = (ck.astype(jnp.float32)
              * cks.astype(jnp.float32)[..., None]).astype(k.dtype)
        vd = (cv.astype(jnp.float32)
              * cvs.astype(jnp.float32)[..., None]).astype(v.dtype)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        kd = _cache_write(cache["k"], k, slot, vec)
        vd = _cache_write(cache["v"], v, slot, vec)
        new_cache = {"k": kd, "v": vd}
    tt = t[:, None] if vec else t
    valid = (positions_buf >= 0) & (positions_buf <= tt)
    if window is not None:
        valid &= (tt - positions_buf) < window
    qg = q.reshape(B, 1, KV, H // KV, hd)
    mask = (valid[:, None, None, None, :] if vec
            else valid[None, None, None, None, :])
    out = _sdpa_masked(qg, kd, vd, mask).reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


def init_gqa_cache(cfg, B, cache_len, dtype, *, quant: bool = False):
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if quant:
        # Beyond-paper perf knob (§Perf): int8 KV entries + per-entry f16
        # scales -> ~2x less decode HBM traffic on the cache-read term.
        return {"k": jnp.zeros((B, cache_len, KV, hd), jnp.int8),
                "v": jnp.zeros((B, cache_len, KV, hd), jnp.int8),
                "k_scale": jnp.zeros((B, cache_len, KV), jnp.float16),
                "v_scale": jnp.zeros((B, cache_len, KV), jnp.float16)}
    return {"k": jnp.zeros((B, cache_len, KV, hd), dtype),
            "v": jnp.zeros((B, cache_len, KV, hd), dtype)}


def _quantize(x):
    """x: (B,1,KV,hd) -> (int8 values, f16 scales (B,1,KV))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(scale, 1e-8)[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): compressed KV latent cache
# ---------------------------------------------------------------------------
def _mla_query(params, x):
    """(B,S,H,dn+dr) queries: through the query LoRA where the config
    has one, else one projection ``wq``."""
    if "wq" in params:
        return jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    from repro.models.common import rmsnorm
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]),
                 params["q_norm"])
    return jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])


def mla_full(params, x, cfg, *, positions, window=None, build_cache=False,
             use_pallas=False):
    """Expanded (training/prefill) form; cache stores the latent only."""
    with jax.named_scope("mla"):
        return _mla_full(params, x, cfg, positions=positions, window=window,
                         build_cache=build_cache)


def _mla_full(params, x, cfg, *, positions, window, build_cache):
    B, S, D = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    from repro.models.common import remat_on, rmsnorm
    q = _mla_query(params, x)                                # (B,S,H,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])       # (B,S,kvr+dr)
    c_kv = rmsnorm(kv[..., :kvr], params["kv_norm"])         # latent
    k_rope = kv[..., kvr:][:, :, None, :]                    # (B,S,1,dr)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        cfg.rope_interleave)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta,
                        cfg.rope_interleave)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["wv_b"])
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate([k_nope,
                          jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
    qg = qf.reshape(B, S, H, 1, dn + dr)
    out = _sdpa(qg, kf, v, causal=True, window=window,
                remat_chunks=remat_on()).reshape(B, S, H, dv)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    cache = ({"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}
             if build_cache else None)
    return y, cache


def mla_step(params, x, cfg, cache, *, t, slot, positions_buf, window=None):
    """Absorbed decode form: attention runs directly against the latent cache
    (c_kv, k_rope) without expanding per-head K/V for the whole history —
    the memory- and bandwidth-saving MLA inference trick. Accepts the
    same scalar (lockstep) or (B,)-vector (per-slot) ``t``/``slot`` as
    :func:`gqa_step`."""
    B = x.shape[0]
    vec = jnp.ndim(t) > 0
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    from repro.models.common import rmsnorm
    q = _mla_query(params, x)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_new = rmsnorm(kv[..., :kvr], params["kv_norm"])        # (B,1,kvr)
    kr_new = kv[..., kvr:][:, :, None, :]
    pos = t[:, None] if vec else jnp.full((B, 1), t, jnp.int32)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta, cfg.rope_interleave)
    kr_new = apply_rope(kr_new, pos, cfg.rope_theta,
                        cfg.rope_interleave)[:, :, 0, :]
    c_kv = _cache_write(cache["c_kv"], c_new, slot, vec)
    k_rope = _cache_write(cache["k_rope"], kr_new, slot, vec)
    # absorb W_uk into the query: q_abs (B,H,kvr)
    q_abs = jnp.einsum("bshk,rhk->bhr", q_nope, params["wk_b"])
    scores = (jnp.einsum("bhr,btr->bht", q_abs.astype(jnp.float32),
                         c_kv.astype(jnp.float32))
              + jnp.einsum("bshk,btk->bht", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32)))
    scores *= 1.0 / np.sqrt(dn + dr)
    tt = t[:, None] if vec else t
    valid = (positions_buf >= 0) & (positions_buf <= tt)
    if window is not None:
        valid &= (tt - positions_buf) < window
    scores = jnp.where(valid[:, None, :] if vec else valid[None, None, :],
                       scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", w, c_kv.astype(jnp.float32))
    out = jnp.einsum("bhr,rhk->bhk", ctx.astype(x.dtype), params["wv_b"])
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None, :]
    return y, {"c_kv": c_kv, "k_rope": k_rope}


def init_mla_cache(cfg, B, cache_len, dtype):
    return {"c_kv": jnp.zeros((B, cache_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((B, cache_len, cfg.qk_rope_head_dim), dtype)}


# ---------------------------------------------------------------------------
# Cross attention (Whisper decoder). K/V come from the encoder output and
# are precomputed once at prefill time; no rope.
# ---------------------------------------------------------------------------
def cross_kv(params, enc_out, cfg):
    k = jnp.einsum("btd,dhk->bthk", enc_out, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, params["wv"])
    if cfg.qkv_bias:
        k, v = k + params["bk"], v + params["bv"]
    return {"xk": k, "xv": v}


def cross_attend(params, x, cfg, kv):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    qg = q.reshape(B, S, KV, H // KV, hd)
    out = _sdpa(qg, kv["xk"], kv["xv"], causal=False).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])
