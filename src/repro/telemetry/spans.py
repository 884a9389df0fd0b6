"""Lightweight span timing for the launch drivers.

Wall-clock accounting over named phases (compile / pack / stage /
stage_ahead / dispatch / wait / fetch / eval / ckpt;
``launch/train._run_fused`` says what each of the block driver's spans
holds) with near-zero overhead: one ``perf_counter`` pair per span,
accumulated in a dict. The summary
lands in the event log's ``spans`` event and the end-of-run print.
Each span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so a ``--profile`` trace carries the host spans on
the device trace's clock; with no trace running the annotation costs
a check of a flag.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict

import jax

PREFIX = "repro."


class SpanTimer:
    """Accumulating span timer: ``with spans.span("dispatch"): ...``."""

    def __init__(self):
        self._acc: Dict[str, list] = {}

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(PREFIX + name):
                yield
        finally:
            dt = time.perf_counter() - t0
            cell = self._acc.setdefault(name, [0.0, 0])
            cell[0] += dt
            cell[1] += 1

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {k: {"s": round(v[0], 6), "n": v[1]}
                for k, v in self._acc.items()}

    def __str__(self) -> str:
        return " ".join(f"{k} {v[0]:.2f}s/{v[1]}"
                        for k, v in sorted(self._acc.items()))
