"""Profiler hooks: compile-time static telemetry + one-block traces.

``static_telemetry`` turns a compiled fused loop into a telemetry row
at COMPILE time — no execution needed: Pallas launch counts (from the
per-namespace trace-time counters), collective instruction count and
payload bytes per round (``roofline.parse_collectives`` over the
compiled HLO). The launch drivers emit it as a ``"static"`` event so a
perf regression shows up in the JSONL artifact even when the run
itself is too short to time.

``trace_block`` wraps one block execution in a ``jax.profiler`` trace
(uploaded as a CI artifact). A failed trace raises: the block's carry is
donated, so running it a second time would read deleted buffers.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional


def static_telemetry(compiled, *, rounds: int = 1,
                     launches: Optional[Dict[str, int]] = None) -> Dict:
    """Compile-time telemetry row for a compiled executable covering
    ``rounds`` rounds: collective count / payload bytes per round, plus
    any trace-time launch counters the caller snapshotted."""
    from repro import roofline

    hlo = compiled.as_text()
    colls = roofline.parse_collectives(hlo)
    rounds = max(rounds, 1)
    row = {
        "rounds": rounds,
        "collective_count": len(colls),
        "collectives_per_round": len(colls) / rounds,
        "collective_bytes": int(sum(c.bytes for c in colls)),
        "collective_bytes_per_round": sum(c.bytes for c in colls) / rounds,
        "collective_wire_bytes": float(sum(c.wire_bytes for c in colls)),
        "collective_kinds": sorted({c.kind for c in colls}),
    }
    if launches is not None:
        row["pallas_launches"] = dict(launches)
        row["pallas_launches_per_round"] = {
            k: v / rounds for k, v in launches.items()}
    return row


def kernel_launch_snapshot() -> Dict[str, int]:
    """Merged view of every kernel namespace's trace-time LAUNCHES
    counter, keys prefixed by namespace."""
    out: Dict[str, int] = {}
    from repro.kernels import telemetry as tk
    from repro.kernels.compress import compress as ck
    from repro.kernels.delta_sgd import delta_sgd as dk
    for ns, counter in (("delta_sgd", dk.LAUNCHES),
                        ("compress", ck.LAUNCHES),
                        ("telemetry", tk.LAUNCHES)):
        for k, v in counter.items():
            out[f"{ns}/{k}"] = int(v)
    return out


def reset_kernel_launches() -> None:
    from repro.kernels import telemetry as tk
    from repro.kernels.compress import compress as ck
    from repro.kernels.delta_sgd import delta_sgd as dk
    dk.reset_launch_count()
    ck.LAUNCHES.clear()
    tk.reset_launch_count()


def trace_block(fn: Callable, logdir: str):
    """Run ``fn()`` under a ``jax.profiler`` trace written to
    ``logdir``; returns fn's result once it is ready."""
    import jax

    with jax.profiler.trace(logdir):
        out = fn()
        jax.block_until_ready(out)
    return out
