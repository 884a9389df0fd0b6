"""Table-driven kernel parity matrix: every Pallas kernel namespace
swept pallas-interpret vs its pure-jnp reference across a dtype × shape
grid. One ``KernelCell`` = one (kernel, dtype, shape) point returning
``(got, want, rtol, atol)``; the same table backs both the parametrized
test (tests/test_conformance_kernels.py) and the per-namespace
conformance oracles (``kernel:<ns>`` in repro.conformance.oracles), so
a planted kernel perturbation trips the fuzzer through exactly the gate
the ROADMAP's XLA-fallback parity item describes.

Tolerances mirror the hand-written sweeps in tests/test_kernels.py,
test_compression.py and test_telemetry.py — the matrix widens their
coverage, it does not relax it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

import numpy as np

NAMESPACES = ("delta_sgd", "compress", "robust_agg", "telemetry",
              "flash_attention", "mamba2_scan")


@dataclass(frozen=True)
class KernelCell:
    ns: str                       # kernel namespace
    cid: str                      # cell id, unique within the namespace
    run: Callable[[int], Tuple]   # seed -> (got, want, rtol, atol)

    @property
    def key(self) -> str:
        return f"{self.ns}:{self.cid}"


def _rng(seed):
    return np.random.default_rng(np.uint64(seed) + 101)


# ---------------------------------------------------------------- delta_sgd
def _delta_norms(shape, dtype):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.delta_sgd import delta_sgd as dk
        from repro.kernels.delta_sgd import ref as dref
        r = _rng(seed)
        g = jnp.asarray(r.normal(size=shape), dtype)
        gp = jnp.asarray(r.normal(size=shape), dtype)
        got = jnp.stack(dk.norms(g, gp, interpret=True))
        want = jnp.stack(dref.norms_ref(g, gp))
        return got, want, 3e-3, 0.0
    return run


def _delta_apply(shape, dtype):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.delta_sgd import delta_sgd as dk
        from repro.kernels.delta_sgd import ref as dref
        r = _rng(seed)
        p = jnp.asarray(r.normal(size=shape), dtype)
        g = jnp.asarray(r.normal(size=shape), dtype)
        return (dk.apply_update(p, g, 0.37, interpret=True),
                dref.apply_ref(p, g, 0.37), 2e-2, 2e-2)
    return run


def _delta_batched_norms(C, N):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.delta_sgd import delta_sgd as dk
        from repro.kernels.delta_sgd import ref as dref
        r = _rng(seed)
        g = jnp.asarray(r.normal(size=(C, N // 128, 128)), jnp.float32)
        gp = g * -0.3 + 0.1
        got = jnp.stack(dk.batched_norms(g, gp, interpret=True))
        want = jnp.stack(dref.batched_norms_ref(g, gp))
        return got, want, 1e-5, 0.0
    return run


def _delta_batched_apply(C, N, masked):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.delta_sgd import delta_sgd as dk
        from repro.kernels.delta_sgd import ref as dref
        r = _rng(seed)
        slab = (C, N // 128, 128)
        p = jnp.asarray(r.normal(size=slab), jnp.float32)
        g = jnp.asarray(r.normal(size=slab), jnp.float32)
        eta = jnp.asarray(r.uniform(0.01, 1.0, C), jnp.float32)
        valid = jnp.asarray(r.uniform(size=C) > 0.2)
        mask = (jnp.asarray(r.integers(0, 2, slab[1:]), jnp.float32)
                if masked else None)
        return (dk.batched_apply(p, g, eta, valid=valid, mask=mask,
                                 interpret=True),
                dref.batched_apply_ref(p, g, eta, mask=mask, valid=valid),
                1e-5, 1e-6)
    return run


# ----------------------------------------------------------------- compress
def _compress(kind, C, chunks):
    def run(seed):
        import jax.numpy as jnp
        from repro.core.flat import LANES
        from repro.kernels.compress import compress as ck
        from repro.kernels.compress import ref as cr
        r = _rng(seed)
        x = jnp.asarray(r.normal(size=(C, chunks * LANES)), jnp.float32)
        if kind == "int8":
            q, s = ck.quantize_int8(x, interpret=True)
            qr, sr = cr.quantize_int8_ref(x)
            return (ck.dequantize_int8(q, s, interpret=True),
                    cr.dequantize_int8_ref(qr, sr), 1e-5, 1e-5)
        k = max(1, LANES // 4)
        return (ck.topk_mask(x, k, interpret=True),
                cr.topk_mask_ref(x, k), 0.0, 0.0)
    return run


# --------------------------------------------------------------- robust_agg
def _trimmed(C, N, t):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.robust_agg import ref as rr
        from repro.kernels.robust_agg import robust_agg as rk
        r = _rng(seed)
        x = jnp.asarray(r.normal(size=(C, N)), jnp.float32)
        return (rk.batched_trimmed_mean(x, t, interpret=True),
                rr.batched_trimmed_mean_ref(x, t), 1e-6, 1e-7)
    return run


# ---------------------------------------------------------------- telemetry
def _telemetry(which, n):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.telemetry import (lane_histogram,
                                             lane_histogram_ref,
                                             lane_quantiles,
                                             lane_quantiles_ref)
        r = _rng(seed)
        x = jnp.asarray(r.normal(size=n), jnp.float32)
        if which == "hist":
            from repro.telemetry import TelemetrySpec
            edges = jnp.asarray(TelemetrySpec(eta_bins=16).eta_edges())
            return (lane_histogram(jnp.abs(x), edges, interpret=True),
                    lane_histogram_ref(jnp.abs(x), edges), 0.0, 0.0)
        return (lane_quantiles(x, Q=11, interpret=True),
                lane_quantiles_ref(x, Q=11), 0.0, 0.0)
    return run


# ---------------------------------------------------------- flash_attention
def _flash(B, S, H, KV, hd, causal, window, dtype):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.flash_attention.flash_attention import (
            flash_attention)
        from repro.kernels.flash_attention.ref import attention_ref
        r = _rng(seed)
        q = jnp.asarray(r.normal(size=(B, S, H, hd)), dtype)
        k = jnp.asarray(r.normal(size=(B, S, KV, hd)), dtype)
        v = jnp.asarray(r.normal(size=(B, S, KV, hd)), dtype)
        got = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
        want = attention_ref(q, k, v, causal=causal, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        return got, want, tol, tol
    return run


# -------------------------------------------------------------- mamba2_scan
def _mamba2(B, S, H, P, G, N):
    def run(seed):
        import jax.numpy as jnp
        from repro.kernels.mamba2_scan.ops import ssd_scan
        from repro.kernels.mamba2_scan.ref import ssd_ref
        r = _rng(seed)
        x = jnp.asarray(r.normal(size=(B, S, H, P)), jnp.float32)
        dt = jnp.asarray(r.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
        A_log = jnp.asarray(np.log(r.uniform(1, 16, (H,))), jnp.float32)
        Bm = jnp.asarray(r.normal(size=(B, S, G, N)), jnp.float32)
        Cm = jnp.asarray(r.normal(size=(B, S, G, N)), jnp.float32)
        y, h = ssd_scan(x, dt, A_log, Bm, Cm)
        yr, hr = ssd_ref(x, dt, A_log, Bm, Cm)
        return (jnp.concatenate([y.ravel(), h.ravel()]),
                jnp.concatenate([yr.ravel(), hr.ravel()]), 1e-3, 1e-4)
    return run


def _build_matrix():
    import jax.numpy as jnp
    cells = []
    for shape in ((7,), (257, 33), (1000,)):
        for dt, dn in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            cells.append(KernelCell(
                "delta_sgd", f"norms-{'x'.join(map(str, shape))}-{dn}",
                _delta_norms(shape, dt)))
    for shape in ((5,), (130, 7)):
        for dt, dn in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            cells.append(KernelCell(
                "delta_sgd", f"apply-{'x'.join(map(str, shape))}-{dn}",
                _delta_apply(shape, dt)))
    for C, N in ((3, 256), (4, 128)):
        cells.append(KernelCell("delta_sgd", f"bnorms-{C}x{N}",
                                _delta_batched_norms(C, N)))
    for C, N, masked in ((3, 256, False), (4, 128, True)):
        cells.append(KernelCell(
            "delta_sgd", f"bapply-{C}x{N}{'-mask' if masked else ''}",
            _delta_batched_apply(C, N, masked)))
    for kind in ("int8", "topk"):
        for C, chunks in ((2, 3), (3, 5)):
            cells.append(KernelCell("compress", f"{kind}-{C}x{chunks}",
                                    _compress(kind, C, chunks)))
    for C, N, t in ((5, 256, 1), (8, 128, 2)):
        cells.append(KernelCell("robust_agg", f"trimmed-{C}x{N}-t{t}",
                                _trimmed(C, N, t)))
    for which, n in (("hist", 257), ("hist", 64), ("quant", 77),
                     ("quant", 130)):
        cells.append(KernelCell("telemetry", f"{which}-{n}",
                                _telemetry(which, n)))
    for args in ((1, 64, 2, 2, 16, True, 16),
                 (1, 128, 4, 1, 64, True, None),     # MQA
                 (2, 128, 4, 4, 32, False, None)):   # bidirectional
        for dt, dn in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
            B, S, H, KV, hd, causal, window = args
            cells.append(KernelCell(
                "flash_attention",
                f"{B}x{S}x{H}x{KV}x{hd}-{'c' if causal else 'b'}"
                f"{f'-w{window}' if window else ''}-{dn}",
                _flash(*args, dt)))
    for args in ((1, 64, 2, 16, 1, 8), (2, 64, 4, 32, 1, 16)):
        cells.append(KernelCell(
            "mamba2_scan", "ssd-" + "x".join(map(str, args)),
            _mamba2(*args)))
    return tuple(cells)


KERNEL_MATRIX: Tuple[KernelCell, ...] = _build_matrix()


def cells_for(ns: str) -> Tuple[KernelCell, ...]:
    return tuple(c for c in KERNEL_MATRIX if c.ns == ns)


def check_cell(cell: KernelCell, seed: int = 0):
    """Violation strings for one cell (empty = parity holds)."""
    got, want, rtol, atol = cell.run(seed)
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if g.shape != w.shape:
        return [f"{cell.key}: shape {g.shape} vs {w.shape}"]
    if rtol == 0.0 and atol == 0.0:
        ok = np.array_equal(g, w, equal_nan=True)
    else:
        ok = np.allclose(g, w, rtol=rtol, atol=atol, equal_nan=True)
    if ok:
        return []
    return [f"{cell.key}: max|Δ|={float(np.nanmax(np.abs(g - w))):.3e} "
            f"(rtol={rtol:g} atol={atol:g})"]
