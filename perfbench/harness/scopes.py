"""Device time by the program's layer scopes.

The round bodies put ``jax.named_scope`` names around their layers
(``client_grad``, ``flat``, ``delta_sgd``, ``round_tail``); XLA keeps
them in each HLO instruction's ``op_name`` metadata. On a TPU the
profiler's op events carry no such stat, but the xplane holds the HLO
of every program it ran (the ``Hlo Proto`` stats of its
``/host:metadata`` plane), and the device plane's ``XLA Modules`` line
says which program each op ran in. This module reads both and keeps,
beside each device's ``ops`` (the form of ``harness/trace.from_xplane``,
which keeps only each op's HLO text), a parallel list ``scopes`` of the
ops' ``op_name`` paths ("" where an op has none).

An op belongs to the innermost layer scope of its path; a fusion's path
is that of its own metadata, which XLA takes from the fusion's root.

The per-layer readers get the reduced trace of ``run.py`` and not the
file, so ``scoped(tr)`` finds the newest xplane under the checkout's
``.bench_trace`` and takes it only where its window is ``tr``'s.
"""
from __future__ import annotations

import glob
import os
import re

from harness import trace

LAYERS = ("client_grad", "flat", "delta_sgd", "round_tail")
MODULE_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"
TRACE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".bench_trace")
_IDENT = re.compile(r"^(?:[A-Za-z_][\w.\-]*\()*([\w.\-]*)\)*$")
# the last xplane read, keyed by path and mtime: the four scope readers
# of one run read the same file
_CACHE = {}


def layer_of(path):
    """The innermost of ``LAYERS`` in an ``op_name`` path, or None. A
    component may be wrapped by JAX transforms (``jvp(flat)``)."""
    for comp in reversed(path.split("/")):
        m = _IDENT.match(comp)
        if m and m.group(1) in LAYERS:
            return m.group(1)
    return None


def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint, the bytes (a memoryview) otherwise."""
    buf, pos = memoryview(buf), 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {kind} not read here")
        yield key >> 3, val


def _first(buf, num, default=b""):
    return next((v for n, v in fields(buf) if n == num), default)


def hlo_op_names(xspace):
    """{program name: {instruction name: op_name}} from the HLO the
    profiler kept of each program (XSpace planes 1, XPlane name 2 /
    event_metadata 4 / stat_metadata 5, XEventMetadata name 2 / stats
    5, XStat metadata_id 1 / bytes_value 6; HloProto hlo_module 1,
    HloModuleProto computations 3, HloComputationProto instructions 2,
    HloInstructionProto name 1 / metadata 7, OpMetadata op_name 2)."""
    out = {}
    for num, plane in fields(xspace):
        if num != 1 or bytes(_first(plane, 2)) != b"/host:metadata":
            continue
        stat_ids = set()
        for n, entry in fields(plane):
            if n == 5:
                md = _first(entry, 2)
                if bytes(_first(md, 2)) == HLO_STAT.encode():
                    stat_ids.add(_first(md, 1, 0))
        for n, entry in fields(plane):
            if n != 4:
                continue
            md = _first(entry, 2)
            for k, stat in fields(md):
                if k != 5 or _first(stat, 1, 0) not in stat_ids:
                    continue
                names = out.setdefault(bytes(_first(md, 2)).decode(), {})
                module = _first(_first(stat, 6), 1)
                for c, comp in fields(module):
                    if c != 3:
                        continue
                    for i, ins in fields(comp):
                        if i == 2:
                            names[bytes(_first(ins, 1)).decode()] = bytes(
                                _first(_first(ins, 7), 2)).decode()
    return out


def instruction(name):
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def from_xplane(path, device_prefix="/device:TPU:", op_line="XLA Ops"):
    """The form of ``trace.from_xplane`` with each device's ``scopes``
    beside its ``ops``, and the window; host spans are left out."""
    import bisect

    import jax
    with open(path, "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    devices, wins = {}, []
    for p in data.planes:
        if p.name.startswith("/host:"):
            wins.extend([e.start_ns, e.start_ns + e.duration_ns]
                        for line in p.lines for e in line.events
                        if e.name == trace.WINDOW_SPAN)
            continue
        if not p.name.startswith(device_prefix):
            continue
        rest = p.name[len(device_prefix):]
        if not rest.isdigit():
            continue
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in p.lines if line.name == MODULE_LINE
                      for e in line.events)
        starts = [r[0] for r in runs]
        rows = []
        for line in p.lines:
            if line.name != op_line:
                continue
            for e in line.events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                prog = runs[i][2] if i >= 0 and e.start_ns < runs[i][1] \
                    else ""
                rows.append(([e.name, e.start_ns, e.duration_ns],
                             op_names.get(prog, {}).get(
                                 instruction(e.name), "")))
        rows.sort(key=lambda r: r[0][1])
        devices[rest] = {"ops": [r[0] for r in rows],
                         "scopes": [r[1] for r in rows]}
    return {"devices": devices, "host": [],
            "window": min(wins) if wins else None}


def has_scopes(tr):
    devs = list(tr["devices"].values())
    return bool(devs) and all("scopes" in d for d in devs)


def scoped(tr, root=None):
    """``tr`` where its devices carry ``scopes``; else the newest xplane
    under ``root`` read with its scopes, where its window is ``tr``'s;
    else None."""
    if has_scopes(tr):
        return tr
    if not tr.get("window"):
        return None
    paths = glob.glob(os.path.join(root or TRACE_ROOT, "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = from_xplane(path)
    got = _CACHE[key]
    if got["window"] != list(tr["window"]) or not has_scopes(got):
        return None
    return got


def scope_time_ns(tr, scope):
    """Device time of the ops whose innermost layer scope is ``scope``:
    the union of their intervals inside the window, averaged over the
    devices. None where no op of any device is in ``scope``."""
    devs = list(tr["devices"].values())
    if not devs or not tr["window"]:
        return None
    t, found = 0.0, False
    for d in devs:
        hits = [e for e, p in zip(d["ops"], d["scopes"])
                if layer_of(p) == scope]
        found = found or bool(hits)
        t += trace.total(trace.union(trace.clip(hits, tr["window"])))
    return t / len(devs) if found else None


def ms_per_round(ctx, scope):
    """A reader's number: ``scope``'s device time per round of the
    traced window, or None where the trace carries no such scope."""
    rounds = ctx["counts"].get("rounds")
    tr = scoped(ctx["trace"])
    if tr is None or not rounds:
        return None
    ns = scope_time_ns(tr, scope)
    return None if ns is None else ns / 1e6 / rounds
