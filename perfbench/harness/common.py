"""What every cell shares: finding a cell by name, the compile cache,
compile counting, host spans, the device and its peaks, percentiles.

Nothing here imports the program; drivers do that.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import one file by path (file names may hold dots)."""
    name = name or "perfbench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything found by its names:
    the configuration's file and its reference module, the traffic mix,
    the cell's limits, and the metric lists."""

    def __init__(self, bench, name, bench_dir=BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        root = os.path.dirname(bench_dir)
        self.config = load_json(os.path.join(root,
                                             self.config_entry["file"]))
        cfg_py = os.path.join(bench_dir, "configs",
                              self.entry["config"] + ".py")
        self.reference = load_module(cfg_py)
        self.traffic_name = self.entry["traffic"]
        self.mix = load_json(os.path.join(bench_dir, "traffic",
                                          self.traffic_name + ".json"))
        self.limits = load_json(os.path.join(bench_dir, "cells",
                                             name + ".json"))["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def load_cell(name, bench_dir=BENCH_DIR):
    root = os.path.dirname(bench_dir)
    return Cell(load_json(os.path.join(root, "BENCHMARK.json")), name,
                bench_dir)


def enable_compile_cache(checkout=CHECKOUT):
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache`` (a fixed path: the path is part of the
    cache key). Every program is written, however fast it compiled, so a
    warm run finds all of them."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend compiles (count and seconds) and persistent-cache hits and
    misses, from JAX's monitoring events. A compile that hits the cache
    still reports its retrieval as a backend compile event."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """Host spans of the harness: each ``span(name)`` is recorded on the
    host clock and, while a trace runs, also as a ``TraceAnnotation``
    named ``bench.<name>`` so the trace reduction can say what the host
    was doing in each device gap. It has the ``span`` method the
    program's block driver calls, so the driver's own span points
    (stage, block_execute, convert, pack, unpack) land here too."""

    PREFIX = "bench."

    def __init__(self):
        self.tracing = False
        self.totals = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        ann = (jax.profiler.TraceAnnotation(self.PREFIX + name)
               if self.tracing else contextlib.nullcontext())
        with ann:
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                s, n = self.totals.get(name, (0.0, 0))
                self.totals[name] = (s + dt, n + 1)

    def reset(self):
        self.totals = {}


def device_info(chips):
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": int(chips)}


def require_chips(chips):
    """The cell's chips, or an error: no CPU fallback, no fewer chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found (platform {devs[0].platform!r}); "
                         f"this benchmark measures the chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices``, or None where
    the backend reports no memory statistics."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind):
    """The chip's published peaks; a device missing from the table is
    an error, not a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no row in "
                       f"perfbench/peaks.json")
    return table[kind]


def quantile(values, q):
    """The q-quantile (0..1) with linear interpolation between ranks."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
