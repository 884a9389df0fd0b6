"""Model facade: build any assigned architecture from its ModelConfig.

Exposes a uniform interface consumed by the FL runtime, the serving driver
and the dry-run:

    model = build_model(cfg, dtype)
    params = model.init(key)
    logits, aux = model.apply(params, batch)                  # train fwd
    loss, metrics = model.loss(params, batch)                 # CE (+aux)
    logits, cache = model.prefill(params, batch, cache_len)   # inference
    logits, cache = model.decode_step(params, cache, tokens)  # 1 token

Batch dict keys: tokens (B,S) int32, labels (B,S) int32, and for the stub
frontends: frames (B,encoder_seq,D) [audio] or image_embeds (B,N_img,D)
[vlm] — precomputed embeddings per the assignment carve-out.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.common import (embed_init, dense_init, init_norm,
                                 apply_norm, shard_logical,
                                 sinusoidal_positions, split_keys, tree_size)


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    dtype: Any = jnp.float32

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        cfg, dtype = self.cfg, self.dtype
        ks = split_keys(key, 8)
        params = {
            "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model),
                                dtype),
            "final_norm": init_norm(ks[1], cfg, dtype),
            "stack": tfm.init_stack(ks[2], cfg, dtype,
                                    decoder=cfg.cross_attention),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[3], (cfg.d_model,
                                                   cfg.padded_vocab), dtype)
        if cfg.encoder_layers:
            params["encoder"] = {
                "stack": tfm.init_stack(
                    ks[4], cfg, dtype,
                    layer_types=("attn",) * cfg.encoder_layers),
                "norm": init_norm(ks[5], cfg, dtype),
            }
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": dense_init(ks[6], (2 * cfg.d_model, cfg.d_model),
                                   dtype),
                "block": tfm.init_block(ks[7], cfg, cfg.layer_types[-1],
                                        dtype),
                "norm": init_norm(ks[5], cfg, dtype),
            }
        return params

    # ------------------------------------------------------------- embedding
    def _embed(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = params["embed"][tokens]
        if cfg.num_image_tokens and "image_embeds" in batch:
            img = batch["image_embeds"].astype(x.dtype)
            x = jnp.concatenate([img, x], axis=1)
        x = shard_logical(x, ("batch", "seq", "embed"))
        if not cfg.rope_theta:  # absolute sinusoidal positions (whisper)
            S = x.shape[1]
            pos = jnp.asarray(sinusoidal_positions(S, cfg.d_model),
                              x.dtype)
            x = x + pos[None]
        return x

    def _encode(self, params, batch):
        """Whisper encoder over stub frame embeddings."""
        cfg = self.cfg
        frames = batch["frames"].astype(self.dtype)
        S = frames.shape[1]
        x = frames + jnp.asarray(sinusoidal_positions(S, cfg.d_model),
                                 frames.dtype)[None]
        positions = jnp.arange(S)[None]
        x, _, _, _ = tfm.stack_full(
            params["encoder"]["stack"], x, cfg,
            layer_types=("attn",) * cfg.encoder_layers,
            positions=positions, causal=False)
        return apply_norm(params["encoder"]["norm"], x, cfg)

    def _cross_kv(self, params, enc_out):
        """Per-decoder-layer cross K/V, stacked (num_layers, ...)."""
        from repro.models.attention import cross_kv
        cfg = self.cfg
        runs = tfm.segment_runs(cfg.layer_types)
        assert len(runs) == 1, "enc-dec assumes a uniform decoder stack"
        p = params["stack"]["run0"]
        if runs[0][1] == 1:  # single-layer run: params are unstacked
            kv = cross_kv(p["xattn"], enc_out, cfg)
            return jax.tree.map(lambda e: e[None], kv)
        return jax.vmap(lambda pl: cross_kv(pl["xattn"], enc_out, cfg))(p)

    def _project_vocab(self, params, x):
        """Vocab projection over the PADDED table; padding logits -inf."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        if cfg.padded_vocab != cfg.vocab_size:
            vid = jax.lax.broadcasted_iota(jnp.int32, (cfg.padded_vocab,), 0)
            logits = jnp.where(vid < cfg.vocab_size, logits, -1e30)
        return logits

    def _head(self, params, x):
        x = apply_norm(params["final_norm"], x, self.cfg)
        return self._project_vocab(params, x)

    # ----------------------------------------------------------------- train
    def apply(self, params, batch, *, use_pallas=False):
        """Full causal forward. Returns (logits over token positions, aux)."""
        logits, aux, _ = self._forward(params, batch, use_pallas=use_pallas)
        return logits, aux

    def _forward(self, params, batch, *, use_pallas=False):
        """``apply`` and the stack's counters (``moe.COUNTERS`` for a
        stack with MoE layers)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.arange(S)[None]
        enc_kv = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch)
            enc_kv = self._cross_kv(params, enc_out)
        x, _, aux, counters = tfm.stack_full(params["stack"], x, cfg,
                                             positions=positions,
                                             enc_kv=enc_kv,
                                             use_pallas=use_pallas)
        if cfg.num_image_tokens and "image_embeds" in batch:
            x = x[:, cfg.num_image_tokens:]  # logits for text positions only
        logits = self._head(params, x)
        logits = shard_logical(logits, ("batch", "seq", "vocab"))
        if cfg.mtp_depth and "labels" in batch:
            aux = aux + self._mtp_loss(params, x, batch)
        return logits, aux, counters

    def _mtp_loss(self, params, h, batch, weight: float = 0.3):
        """DeepSeek-V3 style multi-token prediction: predict token t+2 from
        [h_t ; emb(token_{t+1})] through one extra block."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        nxt = params["embed"][tokens[:, 1:]]
        hcat = jnp.concatenate([h[:, :-1], nxt], axis=-1)
        x = jnp.einsum("bsd,dk->bsk", hcat, params["mtp"]["proj"])
        positions = jnp.arange(x.shape[1])[None]
        x, _, aux, _ = tfm.block_full(params["mtp"]["block"], x, cfg,
                                      cfg.layer_types[-1],
                                      positions=positions)
        x = apply_norm(params["mtp"]["norm"], x, cfg)
        logits = self._project_vocab(params, x)
        # targets: token t+2 == labels shifted by one
        tgt = labels[:, 1:]
        ll = _ce(logits, tgt)
        return weight * ll + aux

    def loss(self, params, batch, *, use_pallas=False):
        """(CE + aux, metrics); a stack with MoE layers adds its
        counters under ``metrics["counts"]``. A config with ``remat``
        set turns per-block remat on for its loss
        (``common.remat_blocks``)."""
        from repro.models.common import remat_blocks, remat_on
        with remat_blocks(remat_on() or self.cfg.remat):
            logits, aux, counters = self._forward(params, batch,
                                                  use_pallas=use_pallas)
        ce = _ce(logits, batch["labels"])
        metrics = {"ce": ce, "aux": aux}
        if counters:
            metrics["counts"] = counters
        return ce + aux, metrics

    # ------------------------------------------------------------- inference
    def cache_len_for(self, seq_len: int, window: Optional[int]) -> int:
        return min(seq_len, window) if window else seq_len

    def prefill(self, params, batch, *, cache_len=None, window=None,
                use_pallas=False):
        """Forward + build decode cache. Returns (last-position logits,
        cache)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.arange(S)[None]
        enc_kv = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch)
            enc_kv = self._cross_kv(params, enc_out)
        x, caches, _, _ = tfm.stack_full(params["stack"], x, cfg,
                                      positions=positions, window=window,
                                      build_cache=True, enc_kv=enc_kv,
                                      use_pallas=use_pallas)
        logits = self._head(params, x[:, -1:])
        cache_len = cache_len or self.cache_len_for(S, window)
        cache = self._assemble_cache(caches, B, S, cache_len, window)
        if enc_kv is not None:
            cache["enc_kv"] = enc_kv
        return logits, cache

    def _assemble_cache(self, built, B, S, cache_len, window):
        """Pad/crop per-layer prefill caches to the decode cache length and
        attach position bookkeeping. When cropping (ring buffer), entries are
        rolled so absolute position p sits at slot p % W — decode_step then
        always overwrites the oldest entry."""
        cfg = self.cfg
        runs_spec = tfm.segment_runs(cfg.layer_types)

        def fit(leaf):  # kv-like leaves: (n, B, S, ...)
            if S >= cache_len:
                out = leaf[:, :, S - cache_len:]
                return jnp.roll(out, shift=S % cache_len, axis=2)
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, cache_len - S)
            return jnp.pad(leaf, pad)

        runs = {}
        for i, (btype, n) in enumerate(runs_spec):
            c = built[f"run{i}"]
            if btype in ("attn", "moe", "shared_attn"):
                runs[f"run{i}"] = jax.tree.map(fit, c)
            else:  # recurrent states are already O(1)
                runs[f"run{i}"] = c
        if S >= cache_len:
            pos = jnp.roll(jnp.arange(S - cache_len, S, dtype=jnp.int32),
                           S % cache_len)
        else:
            pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                                   jnp.full((cache_len - S,), -1, jnp.int32)])
        return {"runs": runs, "t": jnp.asarray(S, jnp.int32),
                "positions": pos}

    def init_cache(self, B, cache_len, *, window=None, quant_kv=False):
        """Empty decode cache (serving from scratch). quant_kv=True stores
        int8 KV entries (beyond-paper decode-bandwidth optimization)."""
        cfg, dtype = self.cfg, self.dtype
        from repro.models import attention as attn
        from repro.models import ssm
        runs_spec = tfm.segment_runs(cfg.layer_types)
        runs = {}
        for i, (btype, n) in enumerate(runs_spec):
            if btype in ("attn", "moe", "shared_attn"):
                one = (attn.init_mla_cache(cfg, B, cache_len, dtype)
                       if cfg.use_mla else
                       attn.init_gqa_cache(cfg, B, cache_len, dtype,
                                           quant=quant_kv))
            elif btype == "mamba2":
                one = ssm.init_mamba2_cache(cfg, B, dtype)
            elif btype == "mlstm":
                one = ssm.init_mlstm_cache(cfg, B, dtype)
            else:
                one = ssm.init_slstm_cache(cfg, B, dtype)
            runs[f"run{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)
        return {"runs": runs, "t": jnp.asarray(0, jnp.int32),
                "positions": jnp.full((cache_len,), -1, jnp.int32)}

    def decode_step(self, params, cache, tokens, *, window=None):
        """tokens: (B,1) -> (logits (B,1,V), cache). ``window`` must match
        the value used at prefill/init_cache (a static config, not state).

        Two cache forms, selected by ``cache["t"]``'s rank:
          * scalar ``t`` + (W,) ``positions`` — the legacy LOCKSTEP
            cache (every batch row at the same position; serve's fixed
            batch, the decode-consistency tests);
          * (B,) ``t`` + (B, W) ``positions`` — the PER-SLOT pool cache
            (repro.serving.engine): each row decodes at its own
            position/ring slot, so a continuous-batching pool can admit
            and retire sequences independently per row.
        """
        cfg = self.cfg
        t = cache["t"]
        vec = t.ndim > 0
        W = cache["positions"].shape[-1]
        slot = (t % W).astype(jnp.int32)
        if vec:
            rows = jnp.arange(t.shape[0])
            positions_buf = cache["positions"].at[rows, slot].set(t)
        else:
            positions_buf = cache["positions"].at[slot].set(t)
        x = params["embed"][tokens]
        if not cfg.rope_theta:  # absolute sinusoidal positions (whisper)
            from repro.models.common import sinusoidal_position_at
            if vec:
                pe = jax.vmap(
                    lambda ti: sinusoidal_position_at(ti, cfg.d_model))(t)
                x = x + pe[:, None, :].astype(x.dtype)
            else:
                x = x + sinusoidal_position_at(t, cfg.d_model).astype(x.dtype)
        enc_kv = cache.get("enc_kv")
        x, runs = tfm.stack_step(params["stack"], x, cfg,
                                 cache["runs"], t=t, slot=slot,
                                 positions_buf=positions_buf, window=window,
                                 enc_kv=enc_kv)
        logits = self._head(params, x)
        new_cache = {"runs": runs, "t": t + 1, "positions": positions_buf}
        if enc_kv is not None:
            new_cache["enc_kv"] = enc_kv
        return logits, new_cache


def build_model(cfg: ModelConfig, dtype=jnp.float32) -> Model:
    return Model(cfg, dtype)


def _ce(logits, labels):
    """Cross-entropy that stays sharded over the vocab dim: the label
    log-prob is a one-hot contraction (partial-sum + tiny all-reduce under
    SPMD) instead of take_along_axis (which would all-gather the logits)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(lse - ll)


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact param count via abstract init (no allocation)."""
    model = build_model(cfg, jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    total = tree_size(shapes)
    if active_only and cfg.num_experts:
        # replace dense-expert count with routed-active + shared experts
        E, K = cfg.num_experts, cfg.num_experts_per_tok
        per_expert = 3 * cfg.d_model * cfg.expert_d_ff
        n_moe = sum(1 for t in cfg.layer_types if t == "moe")
        total -= n_moe * (E - K) * per_expert
    return int(total)
