"""The fleet cell's BENCHMARK.json entries, held back in
``data/fleet_entries.json`` while its rounds_per_s spreads wider than
the metric's bound (PERF.md section 7), merged into the committed file:
the tests drive the cell as the harness will once the entries are in."""
import os

from harness.common import BENCH_DIR, Cell, load_json

NAME = "fleet.mlp_small.zipf100k"


def bench_with_fleet():
    bench = load_json(os.path.join(os.path.dirname(BENCH_DIR),
                                   "BENCHMARK.json"))
    add = load_json(os.path.join(BENCH_DIR, "tests", "data",
                                 "fleet_entries.json"))
    for group in ("configs", "workloads", "per_layer"):
        bench[group] = bench[group] + add[group]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in add["joins"]:
            m["workloads"] = m["workloads"] + [NAME]
    return bench


def fleet_cell():
    return Cell(bench_with_fleet(), NAME)
