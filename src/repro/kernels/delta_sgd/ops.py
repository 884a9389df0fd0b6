"""Kernel-backed Δ-SGD local step over a param pytree.

The pytree is packed into one lane-aligned flat buffer (repro.core.flat)
and the step delegates to the batched flat engine on a one-client slab —
two pallas launches total, replacing the old per-leaf Python loops
(``num_leaves × 2`` launches plus a pad-concatenate copy per call).
Falls back to interpret mode off-TPU.
"""
from __future__ import annotations

from repro.core import flat as flatlib


def fused_delta_sgd_update(params, grads, state, *, gamma: float,
                           delta: float, eta0: float):
    """Drop-in replacement for core.delta_sgd.delta_sgd_update (global
    variant): the flat engine's step on one-client (1, N/128, 128)
    slabs. The rolled ``prev_grads`` is the raw ``grads`` tree, as the
    flat engine's is the raw slab."""
    from repro.core.delta_sgd import (DeltaSGDState, FlatDeltaSGDState,
                                      flat_delta_sgd_step)
    layout = flatlib.layout_of(params)
    mask = flatlib.round_mask(layout)
    shape = flatlib.slab_shape(1, layout)

    def slab(tree):
        return flatlib.pack(tree, layout).reshape(shape)
    fstate = FlatDeltaSGDState(
        slab(state.prev_grads), state.eta[None], state.theta[None],
        state.prev_grad_norm[None], state.k)
    P, fstate = flat_delta_sgd_step(slab(params), slab(grads), fstate,
                                    gamma=gamma, delta=delta, eta0=eta0,
                                    mask=mask)
    new_params = flatlib.unpack(P.reshape(-1), layout)
    return new_params, DeltaSGDState(grads, fstate.eta[0], fstate.theta[0],
                                     fstate.prev_grad_norm[0], fstate.k)
