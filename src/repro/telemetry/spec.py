"""TelemetrySpec: the trace-time switch for the in-scan telemetry
block, plus the device-side metric computation it gates.

Non-perturbing by construction: :func:`round_telemetry` only READS
round-end values (``S.eta``, the loss matrix, the guard latches) and
adds new keys to the metrics dict — it never touches the update path,
so trajectories are bit-exact with telemetry on vs off
(tests/test_telemetry.py pins this on the host, fused, and 8-device
block engines). All outputs are fixed-shape, so they ride as extra
leaves of the fused loop's scanned (R, ·) metrics block with zero host
syncs inside a block.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import numpy as np


class TelemetrySpec(NamedTuple):
    """In-scan telemetry configuration (trace-time constants).

    ``eta_bins`` log-spaced η bins between ``eta_lo`` and ``eta_hi``
    (first bin catches [0, eta_lo), last [eta_hi, inf) — Δ-SGD's η is
    nonnegative); ``loss_deciles`` adds the per-client mean-loss order
    statistics (skipped on the block-sharded path, where deciles would
    need a cross-client sort)."""
    enabled: bool = False
    eta_bins: int = 16
    eta_lo: float = 1e-4
    eta_hi: float = 10.0
    loss_deciles: bool = True
    quantiles: int = 11

    def eta_edges(self) -> np.ndarray:
        """(eta_bins+1,) ascending f32 bin edges: 0, log-spaced
        interior, +inf."""
        if self.eta_bins < 3:
            raise ValueError(f"eta_bins must be >= 3 (underflow + >=1 "
                             f"log bin + overflow), got {self.eta_bins}")
        interior = np.logspace(np.log10(self.eta_lo),
                               np.log10(self.eta_hi),
                               self.eta_bins - 1)
        return np.concatenate([[0.0], interior, [np.inf]]
                              ).astype(np.float32)


def resolve_telemetry(telemetry: Union[None, bool, TelemetrySpec]
                      ) -> TelemetrySpec:
    """None/False -> disabled spec; True -> enabled defaults; a spec
    passes through."""
    if isinstance(telemetry, TelemetrySpec):
        return telemetry
    if telemetry is None or telemetry is False:
        return TelemetrySpec()
    if telemetry is True:
        return TelemetrySpec(enabled=True)
    raise ValueError(f"telemetry must be None, bool, or TelemetrySpec, "
                     f"got {telemetry!r}")


def round_telemetry(tele: TelemetrySpec, etas, losses, clips=None,
                    valid=None, *, backend: str = "xla",
                    use_kernel: Optional[bool] = None) -> dict:
    """The in-scan telemetry block for one round: η histogram over
    client lanes, per-client mean-loss deciles, absolute guard/clip hit
    counts. Pure read-only function of round-end values — adding it to
    a metrics dict cannot perturb the trajectory.

    ``use_kernel`` selects the Pallas kernels (kernels/telemetry, own
    LAUNCHES counter); default: only on the un-meshed pallas engine —
    jnp ref math elsewhere (meshed/pjit callers and ``backend="xla"``),
    mirroring how the Δ-SGD engines pick their backend."""
    import jax.numpy as jnp

    if not tele.enabled:
        return {}
    from repro.kernels import telemetry as tk
    if use_kernel is None:
        use_kernel = backend == "pallas"
    edges = jnp.asarray(tele.eta_edges())
    out = {}
    if use_kernel:
        out["eta_hist"] = tk.lane_histogram(etas, edges)
    else:
        out["eta_hist"] = tk.lane_histogram_ref(etas, edges)
    if tele.loss_deciles:
        client_loss = jnp.mean(losses.astype(jnp.float32), axis=1)
        if use_kernel:
            out["loss_deciles"] = tk.lane_quantiles(client_loss,
                                                    tele.quantiles)
        else:
            out["loss_deciles"] = tk.lane_quantiles_ref(client_loss,
                                                        tele.quantiles)
    if clips is not None:
        out["eta_clip_count"] = jnp.sum(clips.astype(jnp.float32))
    if valid is not None:
        out["nan_guard_count"] = jnp.sum((~valid).astype(jnp.float32))
    return out
