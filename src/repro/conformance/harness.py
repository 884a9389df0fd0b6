"""Differential-run harness: one ``Harness`` per ConfPoint builds the
shared quadratic FL problem (mixed f32/bf16 tree, stacked (R, C, K, b)
batches — the tests' canonical fixture at conformance scale) and knows
how to run it through every engine the oracles compare:

  host(backend)          R host-loop make_fl_round calls
  fused(backend)         one make_fl_loop scan block
  tree()                 the legacy per-client (vmapped) engine
  resume(backend)        host loop with a checkpoint save/restore at R//2
  replicated() / block() un-meshed vs block-level shard_map fused loops
  serve_pool/_isolated   continuous-batching vs one-at-a-time decode

Every run is memoised on the harness, so a config evaluated by many
oracles pays for each (engine, knobs) variant once — the xla host run is
the baseline of most oracles and runs exactly once per config. Runs
return flat ``{name: np.float32 array}`` trajectories (final-state
leaves + per-round metric rows) that ``diff_trajectories`` compares.

Engines are rebuilt from scratch per call (fresh closures, fresh jit
cache entries) so a mutation installed via repro.conformance.mutation
is picked up at trace time — that is what gives the fuzzer teeth.
"""
from __future__ import annotations

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from .space import ConfPoint

_SEN = "__cfg__"       # "use the ConfPoint's own value" sentinel


# ------------------------------------------------------------ trajectories
def _flat_tree(prefix: str, tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[prefix + jax.tree_util.keystr(path)] = np.asarray(
            leaf, np.float32)
    return out


def _stack_metrics(mets) -> dict:
    """Per-round metric dicts -> {'met.<k>': (R, ...)} rows."""
    if not mets:
        return {}
    keys = set(mets[0])
    for m in mets[1:]:
        keys &= set(m)
    return {f"met.{k}": np.stack([np.asarray(m[k], np.float32)
                                  for m in mets]) for k in sorted(keys)}


def _stacked_metrics(fmets) -> dict:
    """Already-stacked fused-loop metrics -> the same naming."""
    return {f"met.{k}": np.asarray(v, np.float32)
            for k, v in dict(fmets).items()}


def diff_trajectories(a: dict, b: dict, *, bitexact: bool,
                      tol: float = 0.0, keys=None, max_report: int = 6):
    """Violation strings for every differing entry. State entries must
    exist on both sides; ``met.*`` entries are compared on the key
    intersection (engines legitimately report different extras)."""
    if keys is None:
        keys = sorted(set(a) | set(b))
    out = []
    for k in keys:
        if k not in a or k not in b:
            if not k.startswith("met."):
                out.append(f"{k}: missing on one side "
                           f"(a={k in a} b={k in b})")
            continue
        x, y = a[k], b[k]
        if x.shape != y.shape:
            out.append(f"{k}: shape {x.shape} vs {y.shape}")
            continue
        if bitexact:
            ok = np.array_equal(x, y, equal_nan=True)
        else:
            ok = np.allclose(x, y, rtol=tol, atol=tol, equal_nan=True)
        if not ok:
            err = float(np.nanmax(np.abs(x - y))) if x.size else 0.0
            out.append(f"{k}: max|Δ|={err:.3e} "
                       f"({'bit-exact' if bitexact else f'tol={tol:g}'})")
        if len(out) >= max_report:
            out.append("... (report truncated)")
            break
    return out


# ---------------------------------------------------------------- harness
class Harness:
    def __init__(self, cfg: ConfPoint):
        from repro.core import get_client_opt, get_server_opt, make_loss
        self.cfg = cfg
        c = cfg
        rng = np.random.default_rng(np.uint64(c.seed) + 17)
        R, C, K, B, D, E = (c.rounds, c.clients, c.local_steps, c.batch,
                            c.dim, c.bf16_dim)
        self.params = {"x": jnp.asarray(rng.normal(size=D), jnp.float32)}
        if E:
            self.params["e"] = jnp.asarray(rng.normal(size=E) * 0.5,
                                           jnp.bfloat16)
        self.batches = {
            "A": jnp.asarray(rng.normal(size=(R, C, K, B, D)),
                             jnp.float32),
            "b": jnp.asarray(rng.normal(size=(R, C, K, B)), jnp.float32)}
        self.weights = (jnp.asarray(rng.uniform(0.5, 1.5, size=(R, C)),
                                    jnp.float32) if c.weighted else None)
        has_e = E > 0

        def quad(params, batch):
            x32 = params["x"].astype(jnp.float32)
            r = batch["A"] @ x32 - batch["b"]
            if has_e:
                e32 = params["e"].astype(jnp.float32)
                r = r + jnp.sum(e32) * 0.01
                return (0.5 * jnp.mean(r * r)
                        + 0.05 * jnp.mean(e32 * e32), {})
            return 0.5 * jnp.mean(r * r), {}

        self.loss = make_loss(quad)
        self.copt = get_client_opt("delta_sgd")
        self.sopt = get_server_opt(c.server_opt)
        self.num_clients = 2 * C          # registered pool for schedulers
        self.num_rounds = max(8, R)       # scheduler horizon (shared)
        self._cache = {}

    # ---- config resolution ----------------------------------------------
    def scenario(self, name=_SEN):
        from repro.federation import get_scenario
        c = self.cfg
        if name is _SEN:
            name = c.scenario
        if name is None:
            return None
        ov = {"seed": c.seed % 1013}
        if name == c.scenario:
            if c.robust_agg is not None:
                ov["robust_agg"] = c.robust_agg
            if c.quorum is not None:
                ov["quorum"] = c.quorum
        return get_scenario(name, **ov)

    def compression(self, kind=_SEN):
        from repro.compression import CompressionSpec
        c = self.cfg
        if kind is not _SEN:
            return (CompressionSpec(kind=kind) if kind is not None
                    else None)
        if c.compression == "none" and not c.error_feedback:
            return None
        return CompressionSpec(kind=c.compression, k_frac=c.k_frac,
                               error_feedback=c.error_feedback)

    # ---- train engines ---------------------------------------------------
    def _round_fn(self, backend, scn, comp, telemetry):
        from repro.core import make_fl_round
        return jax.jit(make_fl_round(
            self.loss, self.copt, self.sopt, num_rounds=self.num_rounds,
            weighted=self.cfg.weighted, flat=backend, scenario=scn,
            num_clients=self.num_clients, compression=comp,
            telemetry=telemetry))

    def _init(self, scn, comp):
        from repro.core import init_fl_state
        return init_fl_state(self.params, self.sopt, scn,
                             compression=comp, cohort=self.cfg.clients)

    def _host_rounds(self, rnd, st, restore_at=None):
        from repro.checkpoint import restore, save
        mets = []
        for r in range(self.cfg.rounds):
            if restore_at is not None and r == restore_at:
                with tempfile.TemporaryDirectory() as d:
                    save(d, st, step=r)
                    st, _ = restore(d, jax.tree.map(jnp.zeros_like, st),
                                    step=r)
            b_r = jax.tree.map(lambda x, r=r: x[r], self.batches)
            kw = ({"client_weights": self.weights[r]}
                  if self.weights is not None else {})
            st, m, _ = rnd(st, b_r, **kw)
            mets.append(m)
        return st, mets

    def host(self, backend="xla", *, telemetry=None, scenario=_SEN,
             compression=_SEN):
        key = ("host", backend, bool(telemetry), scenario,
               "cfg" if compression is _SEN else compression)
        if key not in self._cache:
            scn = self.scenario(scenario)
            comp = self.compression(compression)
            rnd = self._round_fn(backend, scn, comp, telemetry)
            st, mets = self._host_rounds(rnd, self._init(scn, comp))
            self._cache[key] = (_flat_tree("state", st)
                                | _stack_metrics(mets))
        return self._cache[key]

    def tree_engine(self):
        """Legacy per-client engine (flat=False): sync, uncompressed."""
        key = ("tree",)
        if key not in self._cache:
            rnd = self._round_fn(False, None, None, None)
            st, mets = self._host_rounds(rnd, self._init(None, None))
            self._cache[key] = (_flat_tree("state", st)
                                | _stack_metrics(mets))
        return self._cache[key]

    def resume(self, backend="xla"):
        key = ("resume", backend)
        if key not in self._cache:
            scn, comp = self.scenario(), self.compression()
            rnd = self._round_fn(backend, scn, comp, None)
            st, mets = self._host_rounds(rnd, self._init(scn, comp),
                                         restore_at=self.cfg.rounds // 2)
            self._cache[key] = (_flat_tree("state", st)
                                | _stack_metrics(mets))
        return self._cache[key]

    def fused(self, backend="xla", *, telemetry=None):
        from repro.core import (flatten_fl_state, make_fl_loop,
                                unflatten_fl_state)
        key = ("fused", backend, bool(telemetry))
        if key not in self._cache:
            scn, comp = self.scenario(), self.compression()
            loop = make_fl_loop(
                self.loss, self.copt, self.sopt, params_like=self.params,
                num_rounds=self.num_rounds,
                rounds_per_call=self.cfg.rounds,
                weighted=self.cfg.weighted, flat=backend, scenario=scn,
                num_clients=self.num_clients, compression=comp,
                telemetry=telemetry)
            fst = flatten_fl_state(self._init(scn, comp), loop.layout)
            if self.weights is not None:
                fst, fmets = jax.jit(loop)(fst, self.batches,
                                           client_weights=self.weights)
            else:
                fst, fmets = jax.jit(loop)(fst, self.batches)
            st = unflatten_fl_state(fst, loop.layout)
            self._cache[key] = (_flat_tree("state", st)
                                | _stacked_metrics(fmets))
        return self._cache[key]

    # ---- mesh engines (8 virtual devices) --------------------------------
    def _mesh_loops(self):
        from repro.core import make_fl_loop
        from repro.sharding.spec import FederationSpec
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        fed = FederationSpec(client_axes=("data",), fsdp_axes=(),
                             tp_axes=())
        kw = dict(params_like=self.params, num_rounds=self.num_rounds,
                  rounds_per_call=self.cfg.rounds, flat="xla",
                  weighted=self.cfg.weighted, scenario=self.scenario(),
                  num_clients=self.num_clients)
        rep = make_fl_loop(self.loss, self.copt, self.sopt, **kw)
        blk = make_fl_loop(self.loss, self.copt, self.sopt, mesh=mesh,
                           federation=fed, block_sharded=True, **kw)
        return rep, blk

    def _run_mesh(self, which):
        from repro.core import flatten_fl_state
        key = ("mesh", which)
        if key not in self._cache:
            rep, blk = self._mesh_loops()
            loop = rep if which == "replicated" else blk
            fst = flatten_fl_state(self._init(self.scenario(), None),
                                   loop.layout)
            if self.weights is not None:
                fst, mets = jax.jit(loop)(fst, self.batches,
                                          client_weights=self.weights)
            else:
                fst, mets = jax.jit(loop)(fst, self.batches)
            self._cache[key] = ({"state.P": np.asarray(fst.P, np.float32)}
                                | _stacked_metrics(mets))
        return self._cache[key]

    def replicated(self):
        return self._run_mesh("replicated")

    def block(self):
        return self._run_mesh("block")

    # ---- serving ---------------------------------------------------------
    def _serve_setup(self):
        from repro.configs import get_config
        from repro.models import build_model
        key = ("serve_setup",)
        if key not in self._cache:
            s = self.cfg.serve
            cfg = get_config(s.arch).reduced()
            model = build_model(cfg, jnp.float32)
            params = model.init(jax.random.key(s.seed))
            rng = np.random.default_rng(np.uint64(s.seed) + 3)
            prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(
                np.int32) for n in s.prompt_lens]
            self._cache[key] = (model, params, prompts)
        return self._cache[key]

    def serve_pool(self):
        from repro.serving import DecodeEngine
        key = ("serve_pool",)
        if key not in self._cache:
            s = self.cfg.serve
            model, params, prompts = self._serve_setup()
            eng = DecodeEngine(model, params, slots=s.slots,
                               cache_len=s.cache_len,
                               flush_tokens=s.flush_tokens)
            # staggered admission: half up front, the rest interleaved
            # with steps so freed slots get reused
            rids, done = [], []
            up_front = max(1, len(prompts) // 2)
            for p, g in zip(prompts[:up_front], s.gens[:up_front]):
                rids.append(eng.submit(p, g))
            for p, g in zip(prompts[up_front:], s.gens[up_front:]):
                done += eng.step()
                rids.append(eng.submit(p, g))
            done += eng.run_until_idle()
            got = {c.request_id: c.tokens for c in done}
            self._cache[key] = {
                f"tokens[{i}]": np.asarray(got[rid], np.float32)
                for i, rid in enumerate(rids)}
        return self._cache[key]

    def serve_isolated(self):
        from repro.serving import greedy_decode
        key = ("serve_iso",)
        if key not in self._cache:
            s = self.cfg.serve
            model, params, prompts = self._serve_setup()
            out = {}
            for i, (p, g) in enumerate(zip(prompts, s.gens)):
                logits, cache = jax.jit(
                    lambda pr, b: model.prefill(
                        pr, b, cache_len=s.cache_len))(
                    params, {"tokens": jnp.asarray(np.asarray(p)[None])})
                tok0 = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
                toks, _, _ = greedy_decode(model, params, cache, tok0,
                                           g - 1)
                out[f"tokens[{i}]"] = np.concatenate(
                    [np.asarray(tok0)[0], np.asarray(toks)[0]]).astype(
                    np.float32)
            self._cache[key] = out
        return self._cache[key]
