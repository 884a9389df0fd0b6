"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration (``perfbench/configs/<config>.json`` with its reference
``<config>.py``), its traffic mix (``perfbench/traffic/<mix>.json``,
whose ``kind`` names the driver in ``perfbench/drivers/``), its limits
(``perfbench/cells/<workload>.json``) and each per-layer metric's reader
(``perfbench/metrics/<metric>.py``).

The run makes its inputs and weights from the seed, warms every shape
it uses (set-up), measures for ``--seconds`` with nothing compiling,
then checks what the timed path produced against the plain reference.
With ``--trace 1`` the window is traced and the per-layer metrics are
read from the trace and the harness's spans. The last line of standard
output is one JSON object; the numbers compared, each with its limit,
end standard error and the result line (key ``compared``).

Exits 1 without a TPU or with fewer chips than the cell needs, and when
the program it measures is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

# the TPU runtime would otherwise write its logs to a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


class Context:
    """What a driver gets besides its cell: the devices, the harness's
    spans, and the set-up/window bookkeeping (set-up time, compiles in
    the window, the trace)."""

    def __init__(self, devices, trace, trace_dir, stats, trace_seconds):
        from harness.common import Spans
        self.devices = devices
        self.spans = Spans()
        self.trace = trace
        self.trace_dir = trace_dir
        self.stats = stats
        self.setup_s = None
        self.setup_compile = None
        self.window_compiles = None
        self.trace_seconds = trace_seconds

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START
        self.setup_compile = self.stats.snapshot()

    def window_seconds(self, seconds):
        """The window's length: the whole of ``--seconds``, or with
        ``--trace 1`` the mix's shorter ``trace_seconds``."""
        if self.trace and self.trace_seconds:
            return min(float(seconds), float(self.trace_seconds))
        return float(seconds)

    @contextlib.contextmanager
    def window(self):
        import jax
        before = self.stats.snapshot()["compiles"]
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self.spans.tracing = True
        try:
            with self.spans.span("window"):
                yield
        finally:
            if self.trace:
                self.spans.tracing = False
                jax.profiler.stop_trace()
            self.window_compiles = self.stats.snapshot()["compiles"] - before


def read_per_layer(cell, result, tr, peaks, chips):
    from harness.common import load_module
    ctx = {"trace": tr, "counts": result["counts"], "mix": cell.mix,
           "peaks": peaks, "chips": chips}
    out = {}
    for m in cell.per_layer:
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        value = load_module(path).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(cell, args, devices, trace_root=None, keep_trace=False):
    """Set-up, window and check of one run of ``cell`` on ``devices``;
    returns the result line as a dict."""
    from harness.common import (CompileStats, device_info,
                                enable_compile_cache, load_module,
                                peaks_for)
    cache_dir = enable_compile_cache(ROOT)
    stats = CompileStats()
    device = device_info(cell.chips)
    trace_dir = os.path.join(trace_root or ROOT, ".bench_trace", cell.name)
    ctx = Context(devices, args.trace, trace_dir, stats,
                  cell.mix.get("trace_seconds"))
    driver = load_module(os.path.join(HERE, "drivers",
                                      cell.mix["kind"] + ".py"))
    result = driver.run(cell, args, ctx)

    compared = [{"name": k, "value": v, "limit": cell.limits[k]}
                for k, v in result["compared"].items()]
    compared.append({"name": "compiles_in_window",
                     "value": ctx.window_compiles, "limit": 0})
    compared.append({"name": "failed", "value": result["failed"],
                     "limit": 0})
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared)
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        from harness import trace as trace_lib
        tr = trace_lib.from_xplane(trace_lib.newest_xplane(trace_dir))
        peaks = peaks_for(device["kind"])
        line["metrics"] = read_per_layer(cell, result, tr, peaks,
                                         cell.chips)
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = trace_lib.window_ns(tr) / 1e9
        line["device"] = device
        line["breakdown"] = trace_lib.breakdown(tr)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": result["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        line["metrics"] = metrics
        line["device"] = device
    line["setup"] = {"compile": ctx.setup_compile, "cache_dir": cache_dir}
    line["compared"] = compared
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 1
    from harness.common import load_cell, require_chips
    cell = load_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except SystemExit as e:
        print(str(e), file=sys.stderr)
        return 1
    line = measure(cell, args, devices)
    for c in line["compared"]:
        print(f"compared {c['name']}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
