"""Deliberate engine perturbations ("mutations") for fuzzer-teeth
testing: a named mutation monkeypatches one engine with an epsilon-size
numerical defect, so the differential oracles MUST flag it — proving
the conformance plane detects real divergence, not just agreeing with
itself.

Mutations are data, not code state: the active mutation's name is
recorded in every violation artifact, and ``replay.py`` re-installs it
before re-running the shrunk config, so a mutation-induced failure is
reproducible from the JSON artifact alone (in a fresh process — an
in-memory monkeypatch would not survive the subprocess boundary).
The ``REPRO_CONFORMANCE_MUTATION`` env var provides the same hook for
CI legs that want to smoke-test the teeth end to end.

The patch target matters: ``core.delta_sgd.flat_delta_sgd_step``
resolves ``k.batched_apply`` through the kernel MODULE at trace time,
so patching the module attribute perturbs the pallas backend (and only
it) in every engine built afterwards — the harness builds fresh
closures per run, so the mutation is picked up without cache games.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

MUTATIONS: Dict[str, Callable[[], Callable[[], None]]] = {}


def register(name: str):
    def deco(installer):
        MUTATIONS[name] = installer
        return installer
    return deco


@register("delta_sgd.pallas_apply:1e-3")
def _pallas_apply_eps():
    """Shift the pallas batched_apply output by 1e-3: the pallas flat
    engine drifts off the xla engine by ~1e-3/step — far outside the
    1e-5 engine-parity tolerance, and outside the delta_sgd kernel
    matrix tolerance too."""
    from repro.kernels.delta_sgd import delta_sgd as dk
    orig = dk.batched_apply

    def perturbed(p, g, eta, **kw):
        return orig(p, g, eta, **kw) + 1e-3

    dk.batched_apply = perturbed

    def undo():
        dk.batched_apply = orig
    return undo


@register("telemetry.hist_offbyone")
def _hist_off_by_one():
    """Add one phantom count to the first histogram bin: invisible to
    trajectories, caught only by the kernel:telemetry parity cells."""
    from repro.kernels import telemetry as tns
    from repro.kernels.telemetry import telemetry as tk
    orig = tk.lane_histogram

    def perturbed(x, edges, *, interpret=None):
        h = orig(x, edges, interpret=interpret)
        return h.at[0].add(1.0)

    tk.lane_histogram = perturbed
    tns.lane_histogram = perturbed

    def undo():
        tk.lane_histogram = orig
        tns.lane_histogram = orig
    return undo


class active_mutation:
    """Context manager: install a named mutation (or none for name in
    (None, "", "none")) and restore the pristine engine on exit."""

    def __init__(self, name: Optional[str]):
        self.name = name if name not in (None, "", "none") else None
        self._undo = None

    def __enter__(self):
        if self.name is not None:
            try:
                installer = MUTATIONS[self.name]
            except KeyError:
                raise KeyError(
                    f"unknown mutation {self.name!r}; "
                    f"registered: {sorted(MUTATIONS)}") from None
            self._undo = installer()
        return self

    def __exit__(self, *exc):
        if self._undo is not None:
            self._undo()
            self._undo = None
        return False
