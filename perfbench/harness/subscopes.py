"""Device time of the model's own scopes, nested inside the round
body's ``client_grad`` (``harness/scopes.LAYERS`` keeps reading
``client_grad`` as a whole).

The model names its parts with ``jax.named_scope``: ``mla`` (latent
attention, ``models/attention.mla_full``), ``moe_route`` (router,
sigmoid, biased top-k, weights, the dispatch sort and gather),
``moe_experts`` (the grouped product over the held experts),
``moe_combine`` (the weighted scatter-add) and ``shared_experts``
(``models/moe.apply_moe``). An op belongs to the innermost of these in
its ``op_name`` path, read from the HLO the xplane keeps through
``scopes.scoped``; the backward of a part carries the part's name
inside JAX's transform wrappers (``transpose(jvp(moe_experts))``).
A trace of a program that has no such scope reads None.
"""
from __future__ import annotations

from harness import scopes, trace

PARTS = ("mla", "moe_route", "moe_experts", "moe_combine",
         "shared_experts")


def part_of(path):
    """The innermost of ``PARTS`` in an ``op_name`` path, or None."""
    for comp in reversed(path.split("/")):
        m = scopes._IDENT.match(comp)
        if m and m.group(1) in PARTS:
            return m.group(1)
    return None


def part_time_ns(tr, part):
    """Device time of the ops whose innermost part is ``part``: the
    union of their intervals inside the window, averaged over the
    devices; None where no op of any device is in ``part``."""
    devs = list(tr["devices"].values())
    if not devs or not tr["window"]:
        return None
    t, found = 0.0, False
    for d in devs:
        hits = [e for e, p in zip(d["ops"], d["scopes"])
                if part_of(p) == part]
        found = found or bool(hits)
        t += trace.total(trace.union(trace.clip(hits, tr["window"])))
    return t / len(devs) if found else None


def ms_per_round(ctx, part):
    """A reader's number: ``part``'s device time per round of the
    traced window, or None where the trace carries no such part."""
    rounds = ctx["counts"].get("rounds")
    tr = scopes.scoped(ctx["trace"])
    if tr is None or not rounds:
        return None
    ns = part_time_ns(tr, part)
    return None if ns is None else ns / 1e6 / rounds
