"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

Two stages, so that the arithmetic can be checked on a small recorded
trace without a chip:

1. ``from_xplane(path)`` turns the ``.xplane.pb`` the JAX profiler
   wrote into a plain dict: per device its op events, the harness's
   host spans (``bench.*``), and the window, all as ``[name, start_ns,
   duration_ns]`` on one clock. On a TPU an op event's name is the
   op's HLO text (``%fusion.3 = f32[..] fusion(...)``), and a loop op's
   event contains the events of the ops of its body.
2. The functions below reduce that dict: busy time as the union of op
   intervals, idle share, summed time of chosen ops, the longest idle
   gaps named by the host span that covers them, and the ops that took
   the most time of their own.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
_KIND = re.compile(r"[\s)]([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def from_xplane(path, device_prefix="/device:TPU:", op_line="XLA Ops"):
    """Read one xplane file into the plain form described above.

    A device plane is one named ``device_prefix`` and a number
    (``/device:TPU:0``). Its ops come from the line named ``op_line``,
    """
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    devices = {}
    for p in planes:
        if not p.name.startswith(device_prefix):
            continue
        rest = p.name[len(device_prefix):]
        if not rest.isdigit():
            continue
        ops = []
        for line in p.lines:
            if line.name == op_line:
                ops.extend([e.name, e.start_ns, e.duration_ns]
                           for e in line.events)
        devices[rest] = {"ops": sorted(ops, key=lambda e: e[1])}
    host = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append([e.name, e.start_ns, e.duration_ns])
    host.sort(key=lambda e: e[1])
    wins = [h for h in host if h[0] == WINDOW_SPAN]
    window = [wins[0][1], wins[0][1] + wins[0][2]] if wins else None
    return {"devices": devices, "host": host, "window": window}


# ---------------------------------------------------------------------------
# op names
# ---------------------------------------------------------------------------
def op_kind(name):
    """The HLO opcode of an op event (``fusion``, ``while``,
    ``custom-call``, ``all-reduce-start`` ...), or the name itself where
    it is not HLO text."""
    if " = " not in name:
        return name
    m = _KIND.search(" " + name.split(" = ", 1)[1])
    return m.group(1) if m else name


def short_name(name):
    """``%fusion.3 = ... fusion(...)`` -> ``fusion.3 fusion``; a custom
    call also names its target."""
    if " = " not in name:
        return name
    lhs = name.split(" = ", 1)[0].lstrip("%")
    kind = op_kind(name)
    t = _TARGET.search(name)
    return f"{lhs} {kind}" + (f" {t.group(1)}" if t else "")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def clip(events, window):
    """Events as (start, end) intervals cut to ``window``."""
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e[1], lo), min(e[1] + e[2], hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    merged = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if t > merged[-1][1]:
                merged[-1][1] = t
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def total(intervals):
    return float(sum(t - s for s, t in intervals))


def subtract(intervals, cover):
    """Parts of ``intervals`` that no interval of ``cover`` overlaps."""
    cover = union(cover)
    out = []
    for s, t in union(intervals):
        cur = s
        for c0, c1 in cover:
            if c1 <= cur:
                continue
            if c0 >= t:
                break
            if c0 > cur:
                out.append((cur, c0))
            cur = max(cur, c1)
            if cur >= t:
                break
        if cur < t:
            out.append((cur, t))
    return out


def self_times(events):
    """[name, start, self_ns, leaf] per event, in start order: its
    duration less that of the events nested directly inside it, and
    whether any is."""
    rows = sorted(([e[0], e[1], e[2]] for e in events),
                  key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, s, d in rows:
        while stack and stack[-1][1] + stack[-1][4] <= s:
            stack.pop()
        if stack:
            stack[-1][2] -= d
            stack[-1][3] = False
        row = [name, s, d, True, d]
        out.append(row)
        stack.append(row)
    return [[n, s, max(own, 0), leaf] for n, s, own, leaf, _ in out]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def window_ns(tr):
    lo, hi = tr["window"]
    return float(hi - lo)


def busy_ns(dev, window):
    return total(union(clip(dev["ops"], window)))


def busy_s(tr):
    """Seconds in which an op ran, averaged over the devices."""
    devs = list(tr["devices"].values())
    if not devs or not tr["window"]:
        return None
    return sum(busy_ns(d, tr["window"]) for d in devs) / len(devs) / 1e9


def idle_share(tr):
    """1 - busy / window, averaged over the devices; None without a
    device or a window."""
    if not tr["devices"] or not tr["window"]:
        return None
    w = window_ns(tr)
    shares = [1.0 - busy_ns(d, tr["window"]) / w
              for d in tr["devices"].values()]
    return sum(shares) / len(shares)


def op_time_ns(tr, pred):
    """Device time of the ops whose name satisfies ``pred`` (the union
    of their intervals, so nested matches count once), averaged over
    the devices, within the window; and the events per device."""
    devs = list(tr["devices"].values())
    if not devs or not tr["window"]:
        return 0.0, 0
    t, n = 0.0, 0
    for d in devs:
        hits = [e for e in d["ops"] if pred(e[0])]
        t += total(union(clip(hits, tr["window"])))
        n += len(hits)
    return t / len(devs), n / len(devs)


def top_ops(tr, n=10):
    """[[op, seconds]] of the ops that took the most device time of
    their own (nested ops' time taken out) within the window, averaged
    over the devices."""
    devs = list(tr["devices"].values())
    if not tr["window"]:
        return []
    lo, hi = tr["window"]
    acc = {}
    for d in devs:
        cut = [[e[0], max(e[1], lo), min(e[1] + e[2], hi) - max(e[1], lo)]
               for e in d["ops"] if e[1] < hi and e[1] + e[2] > lo]
        for name, _, own, _ in self_times(cut):
            k = short_name(name)
            acc[k] = acc.get(k, 0.0) + own
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(1, len(devs)) / 1e9] for k, v in rows]


def idle_gaps(tr, n=10, device=None):
    """[[host span, seconds]] of the longest idle gaps of one device,
    each named by the harness span that overlaps it most (the shorter
    span wins a tie), or ``"none"`` where no span covers it."""
    if not tr["devices"] or not tr["window"]:
        return []
    key = device if device is not None else sorted(tr["devices"])[0]
    lo, hi = tr["window"]
    busy = union(clip(tr["devices"][key]["ops"], tr["window"]))
    gaps = subtract([(lo, hi)], busy)
    spans = [h for h in tr["host"] if h[0] != WINDOW_SPAN]
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_ov, best_len = "none", 0.0, None
        for name, hs, hd in spans:
            ov = min(t, hs + hd) - max(s, hs)
            if ov <= 0:
                continue
            if ov > best_ov or (ov == best_ov and hd < best_len):
                best, best_ov, best_len = name, ov, hd
        out.append([best, (t - s) / 1e9])
    return out


def breakdown(tr):
    return {"device_ops": top_ops(tr), "idle_gaps": idle_gaps(tr)}
