"""Continuous-batching decode engine: a fixed-slot KV-cache pool with
per-slot sequence state, flush-interval decode blocks, and block-
boundary checkpoint hot-swap.

Design (mirrors the training engine's host-sync discipline):

  * POOL — one vectorized decode cache for S slots built from
    ``model.init_cache``: every ``runs`` leaf keeps its batch axis
    (axis 1), ``t`` becomes (S,) and ``positions`` (S, W). Slot s is
    row s of every leaf; ``model.decode_step`` branches on ``t``'s rank
    and runs each row at its OWN position / ring slot
    (``attention._cache_write``), so admitting or retiring one sequence
    never touches another row's state.
  * DECODE BLOCK — ``flush_tokens`` greedy steps fused into ONE jitted
    ``lax.scan`` (no per-token host sync; the per-token Python loop in
    the old ``launch/serve.py`` paid one dispatch + implicit sync per
    token). Inactive slots are masked OUT of the carry by
    ``_merge_cache`` — their cache rows, t, and last token are
    bit-frozen while the active rows advance. The host reads ONE
    device_get per flush (the stacked (S, flush_tokens) token matrix),
    exactly the ``_RoundLog`` cadence of the training loop.
  * ADMIT / EVICT — at flush boundaries only. Admission prefuills the
    request alone (B=1, jitted per prompt length) and scatters the
    resulting cache rows into the pool; eviction just frees the host-
    side slot record (the pool row is garbage until the next admit
    overwrites it).
  * HOT SWAP — ``step()`` polls the :class:`~repro.serving.registry.
    ModelRegistry` once per flush and applies a staged version BEFORE
    the next decode block: every token of every flush is produced by
    exactly one params version (atomicity is asserted in
    tests/test_serving.py by replaying the per-flush version schedule).
    The KV pool is REUSED across the swap — valid because the cache
    stores activations keyed only by model config, and the swap is
    shape-gated: params that do not match the serving template
    leaf-for-leaf are refused (build a new engine for a new
    architecture).
  * PERSONALIZATION — a request with a client id known to the
    :class:`~repro.serving.personalize.PersonalizationStore` decodes
    under ``unpack(pack(params) + scale·delta_c)``. Active slots are
    grouped by overlay identity each flush; every group reuses the ONE
    compiled decode block (params are traced arguments), so per-client
    models cost one axpy + unpack, cached until the next swap.

MoE blocks route each token on its own and drop none (models/moe.py),
so continuous batching is exact (vs isolated decode) for MoE stacks as
for dense and SSM ones.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# fused greedy decode (lockstep): serve.py's jitted tail
# ---------------------------------------------------------------------------
def greedy_decode(model, params, cache, tok, n, *, window=None):
    """n greedy decode steps as ONE ``lax.scan`` — the fused form of the
    legacy per-token host loop, token-exact against it (same per-step
    ops, one dispatch total). Works on both cache forms (lockstep and
    per-slot pool). Returns (tokens (B, n) int32, cache, last token)."""
    def body(carry, _):
        cache, tok = carry
        logits, cache = model.decode_step(params, cache, tok,
                                          window=window)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, tok), tok[:, 0]

    (cache, tok), toks = jax.lax.scan(body, (cache, tok), None, length=n)
    return jnp.moveaxis(toks, 0, 1), cache, tok


# ---------------------------------------------------------------------------
# masked decode block (per-slot): the engine's flush interval
# ---------------------------------------------------------------------------
def _bcast(mask, ndim, axis):
    shape = [1] * ndim
    shape[axis] = mask.shape[0]
    return mask.reshape(shape)


def _merge_cache(active, new, old):
    """Keep ``new`` state only on active rows; inactive rows stay
    bit-identical to ``old`` (runs leaves carry batch on axis 1; t /
    positions on axis 0; enc_kv is per-slot static, passed through)."""
    out = {"runs": jax.tree.map(
        lambda n_, o: jnp.where(_bcast(active, n_.ndim, 1), n_, o),
        new["runs"], old["runs"]),
        "t": jnp.where(active, new["t"], old["t"]),
        "positions": jnp.where(active[:, None], new["positions"],
                               old["positions"])}
    if "enc_kv" in new:
        out["enc_kv"] = new["enc_kv"]
    return out


def _decode_block(model, params, cache, tok, active, n, window):
    """n masked greedy steps; returns (cache, tok, tokens (S, n))."""
    def body(carry, _):
        cache, tok = carry
        logits, new_cache = model.decode_step(params, cache, tok,
                                              window=window)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active[:, None], nxt, tok)
        return (_merge_cache(active, new_cache, cache), nxt), nxt[:, 0]

    (cache, tok), toks = jax.lax.scan(body, (cache, tok), None, length=n)
    return cache, tok, jnp.moveaxis(toks, 0, 1)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------
@dataclass
class Request:
    prompt: np.ndarray                 # (S,) int32 token ids
    max_new_tokens: int
    client_id: Optional[int] = None
    request_id: int = 0
    extras: Optional[Dict[str, np.ndarray]] = None  # frames/image_embeds
    submit_time: float = field(default_factory=time.time)


class Completion(NamedTuple):
    request_id: int
    tokens: np.ndarray                 # (max_new_tokens,) int32
    client_id: Optional[int]
    latency_s: float
    versions: tuple                    # params version per flush touched


class _Slot(NamedTuple):
    req: Request
    remaining: int
    out: List[int]
    overlay: Optional[int]             # personalization key (client id)
    versions: List[int]


class DecodeEngine:
    """Fixed-slot continuous-batching greedy decode; see module doc."""

    def __init__(self, model, params, *, slots: int = 4,
                 cache_len: int = 64, flush_tokens: int = 8,
                 window: Optional[int] = None, version: int = 0,
                 registry=None, personalization=None, events=None):
        self.model, self.slots = model, int(slots)
        self.cache_len, self.flush_tokens = int(cache_len), int(flush_tokens)
        self.window = window
        self.registry = registry
        self.store = personalization
        self.events = events
        self._params = params
        self._shapes = jax.tree.map(
            lambda a: (jnp.shape(a), str(jnp.result_type(a))), params)
        self._params_flat = None       # packed lazily (personalization)
        self._overlays: Dict[int, Any] = {}
        self.version = int(version)
        self._ids = itertools.count()
        self.queue: List[Request] = []
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self.pool = self._init_pool()
        self._tok = jnp.zeros((self.slots, 1), jnp.int32)
        self._block = jax.jit(
            lambda p, c, tok, act: _decode_block(
                self.model, p, c, tok, act, self.flush_tokens,
                self.window))
        self._insert = jax.jit(self._insert_impl)
        self._prefills: Dict[Any, Any] = {}
        self.history: List[dict] = []
        self.completed: List[Completion] = []
        self.stats = {"tokens": 0, "flushes": 0, "occupancy_sum": 0.0,
                      "swaps": 0, "swap_stalls": [], "kv_reuse_swaps": 0,
                      "admitted": 0, "completed": 0}
        if self.registry is not None:
            staged = self.registry.poll()   # initial version, if any
            if staged is not None:
                self._params = staged.params
                self._params_flat = None
                self.version = staged.step

    # --------------------------------------------------------------- pool
    def _init_pool(self):
        cache = self.model.init_cache(self.slots, self.cache_len,
                                      window=self.window)
        cache["t"] = jnp.zeros((self.slots,), jnp.int32)
        cache["positions"] = jnp.full((self.slots, self.cache_len), -1,
                                      jnp.int32)
        return cache

    def _insert_impl(self, pool, tok_pool, c1, tok0, s):
        """Scatter a B=1 prefill cache into pool row s (one jit; ``s``
        is a traced scalar, so every admission reuses the compile)."""
        row = jax.tree.map(lambda pl, cl: pl.at[:, s].set(cl[:, 0]),
                           pool["runs"], c1["runs"])
        new = dict(pool)
        new["runs"] = row
        new["t"] = pool["t"].at[s].set(c1["t"])
        new["positions"] = pool["positions"].at[s].set(c1["positions"])
        if "enc_kv" in pool:
            new["enc_kv"] = jax.tree.map(
                lambda pl, cl: pl.at[:, s].set(cl[:, 0]),
                pool["enc_kv"], c1["enc_kv"])
        return new, tok_pool.at[s].set(tok0[0])

    # ------------------------------------------------------------ params
    def _client_params(self, overlay_key):
        if overlay_key is None:
            return self._params
        if overlay_key not in self._overlays:
            if self._params_flat is None:
                from repro.core.flat import pack
                self._params_flat = pack(self._params, self.store.layout)
            self._overlays[overlay_key] = self.store.overlay(
                self._params_flat, overlay_key)
        return self._overlays[overlay_key]

    def swap(self, params, step: int, *, seen_at: Optional[float] = None):
        """Hot-swap the serving params at this block boundary. Shape-
        gated: the new tree must match the serving template leaf-for-
        leaf (shape AND dtype) — that is the condition under which the
        in-flight KV pool remains valid and is reused."""
        shapes = jax.tree.map(
            lambda a: (jnp.shape(a), str(jnp.result_type(a))), params)
        if shapes != self._shapes:
            raise ValueError(
                "hot-swap refused: new params do not match the serving "
                "template's shapes/dtypes — the KV pool cannot be "
                "reused across an architecture change; build a new "
                "DecodeEngine")
        self._params = params
        self._params_flat = None
        self._overlays.clear()
        self.version = int(step)
        self.stats["swaps"] += 1
        if any(s is not None for s in self._slots):
            self.stats["kv_reuse_swaps"] += 1
        stall = (time.time() - seen_at) if seen_at is not None else 0.0
        self.stats["swap_stalls"].append(stall)
        return stall

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens: int, *, client_id=None,
               extras=None) -> int:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be (S,), got {prompt.shape}")
        need = (prompt.shape[0] + max_new_tokens
                + (self.model.cfg.num_image_tokens or 0))
        if self.window is None and need > self.cache_len:
            raise ValueError(
                f"request needs {need} cache entries > pool cache_len "
                f"{self.cache_len} (pass a sliding window to roll)")
        rid = next(self._ids)
        self.queue.append(Request(prompt=prompt,
                                  max_new_tokens=int(max_new_tokens),
                                  client_id=client_id, request_id=rid,
                                  extras=extras))
        return rid

    # ------------------------------------------------------------- admit
    def _admit(self, completions):
        for s in range(self.slots):
            if not self.queue:
                break
            if self._slots[s] is not None:
                continue
            req = self.queue.pop(0)
            overlay = (req.client_id
                       if (self.store is not None
                           and self.store.has(req.client_id)) else None)
            sig = (req.prompt.shape[0],
                   tuple(sorted((req.extras or {}).keys())))
            fn = self._prefills.get(sig)
            if fn is None:
                fn = jax.jit(lambda p, b: self.model.prefill(
                    p, b, cache_len=self.cache_len, window=self.window))
                self._prefills[sig] = fn
            batch = {"tokens": jnp.asarray(req.prompt[None])}
            for k, v in (req.extras or {}).items():
                batch[k] = jnp.asarray(v)[None]
            logits, c1 = fn(self._client_params(overlay), batch)
            tok0 = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            if "enc_kv" in c1 and "enc_kv" not in self.pool:
                self.pool["enc_kv"] = jax.tree.map(
                    lambda e: jnp.zeros(
                        (e.shape[0], self.slots) + e.shape[2:], e.dtype),
                    c1["enc_kv"])
            self.pool, self._tok = self._insert(self.pool, self._tok, c1,
                                                tok0, jnp.int32(s))
            first = int(tok0[0, 0])
            slot = _Slot(req=req, remaining=req.max_new_tokens - 1,
                         out=[first], overlay=overlay,
                         versions=[self.version])
            self.stats["admitted"] += 1
            if slot.remaining == 0:
                completions.append(self._finish_slot(slot))
            else:
                self._slots[s] = slot

    def _finish_slot(self, slot: _Slot) -> Completion:
        self.stats["completed"] += 1
        c = Completion(request_id=slot.req.request_id,
                       tokens=np.asarray(slot.out, np.int32),
                       client_id=slot.req.client_id,
                       latency_s=time.time() - slot.req.submit_time,
                       versions=tuple(dict.fromkeys(slot.versions)))
        self.completed.append(c)
        return c

    # -------------------------------------------------------------- step
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self._slots)

    def step(self) -> List[Completion]:
        """One flush interval: swap (if staged) -> admit -> one fused
        decode block per overlay group -> ONE device_get -> harvest.
        Returns the requests completed this flush."""
        completions: List[Completion] = []
        swapped, stall = 0, 0.0
        if self.registry is not None:
            staged = self.registry.poll()
            if staged is not None:
                stall = self.swap(staged.params, staged.step,
                                  seen_at=staged.seen_at)
                swapped = 1
        self._admit(completions)
        groups: Dict[Optional[int], List[int]] = {}
        for s, sl in enumerate(self._slots):
            if sl is not None:
                groups.setdefault(sl.overlay, []).append(s)
        order = list(groups.items())
        mats = []
        for key, idxs in order:
            act = np.zeros((self.slots,), bool)
            act[idxs] = True
            self.pool, self._tok, toks = self._block(
                self._client_params(key), self.pool, self._tok,
                jnp.asarray(act))
            mats.append(toks)
        mats = jax.device_get(mats)    # the ONE host sync of the flush
        emitted = 0
        for (key, idxs), mat in zip(order, mats):
            for s in idxs:
                sl = self._slots[s]
                take = min(sl.remaining, self.flush_tokens)
                sl.out.extend(int(x) for x in mat[s, :take])
                sl.versions.append(self.version)
                emitted += take
                sl = sl._replace(remaining=sl.remaining - take)
                self._slots[s] = sl
                if sl.remaining == 0:
                    self._slots[s] = None
                    completions.append(self._finish_slot(sl))
        occ = sum(len(v) for v in groups.values()) / self.slots
        self.stats["tokens"] += emitted
        self.stats["flushes"] += 1
        self.stats["occupancy_sum"] += occ
        self.history.append({"flush": self.stats["flushes"] - 1,
                             "version": self.version,
                             "groups": {k: list(v)
                                        for k, v in groups.items()},
                             "swapped": swapped, "swap_stall_s": stall,
                             "tokens": emitted, "occupancy": occ})
        if self.events is not None:
            self.events.emit("serve_flush",
                             t=self.stats["flushes"] - 1,
                             serve_tokens=emitted, serve_occupancy=occ,
                             serve_version=self.version,
                             serve_swapped=swapped,
                             serve_swap_stall_s=stall)
            self.events.flush()
        return completions

    def run_until_idle(self, max_flushes: int = 100_000
                       ) -> List[Completion]:
        out: List[Completion] = []
        while self.has_work():
            out.extend(self.step())
            if self.stats["flushes"] >= max_flushes:
                raise RuntimeError("run_until_idle: flush budget "
                                   "exhausted with work pending")
        return out

    # ------------------------------------------------------------ report
    def metrics(self) -> dict:
        f = max(1, self.stats["flushes"])
        stalls = self.stats["swap_stalls"]
        return {"serve_tokens_total": self.stats["tokens"],
                "serve_occupancy_mean": self.stats["occupancy_sum"] / f,
                "serve_swaps_total": self.stats["swaps"],
                "serve_swap_stall_mean": (float(np.mean(stalls))
                                          if stalls else 0.0),
                "serve_swap_stall_max": (float(np.max(stalls))
                                         if stalls else 0.0),
                "kv_reuse_swaps": self.stats["kv_reuse_swaps"],
                "requests_completed": self.stats["completed"]}
