"""Run one traced run of a cell, keep its trace, and print what the
profiler recorded: planes, lines, event counts and the most frequent
event names. Writes the reduced trace (``harness.trace.from_xplane``)
to ``<out>/<workload>.trace.json`` (``--out``, default ``.bench_trace``).

    python3 perfbench/tools/inspect_trace.py --workload <name> \
        --seed <n> --seconds <s> [--out <dir>]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

# the TPU runtime would otherwise write its logs to a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as bench_run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=".bench_trace")
    args = ap.parse_args()
    args.trace = 1
    from harness import trace as trace_lib
    from harness.common import load_cell, require_chips
    cell = load_cell(args.workload)
    devices = require_chips(cell.chips)
    out = os.path.join(bench_run.ROOT, args.out)
    os.makedirs(out, exist_ok=True)
    line = bench_run.measure(cell, args, devices, keep_trace=True)
    print(json.dumps(line)[:4000], flush=True)
    import jax
    path = trace_lib.newest_xplane(os.path.join(bench_run.ROOT,
                                                ".bench_trace", cell.name))
    data = jax.profiler.ProfileData.from_file(path)
    for p in data.planes:
        print("PLANE", p.name)
        for ln in p.lines:
            names = collections.Counter(e.name for e in ln.events)
            total = sum(names.values())
            print(f"  LINE {ln.name!r} events {total}")
            for name, n in names.most_common(12):
                print(f"      {n:7d} {name[:100]}")
            shown = 0
            for e in ln.events:
                if shown < 4 and ("custom-call" in e.name
                                  or "all-reduce" in e.name):
                    stats = {k: str(v)[:300] for k, v in e.stats}
                    print(f"      STATS {e.name[:80]!r} {stats}")
                    shown += 1
    tr = trace_lib.from_xplane(path)
    with open(os.path.join(out, cell.name + ".trace.json"), "w") as f:
        json.dump(tr, f)


if __name__ == "__main__":
    main()
