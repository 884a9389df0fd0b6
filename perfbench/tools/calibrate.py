"""Readings that the limits of a cell are set from, on the chip at the
cell's own size, many seeds in one process (one set-up, one compile):

* ``program``: the timed path's numbers against the reference, as a run
  compares them (the first block through the program's block driver);
* ``control``: the reference in bfloat16 put in the program's place,
  compared the same way (the configuration states float32);
* ``half_batch``: a fault planted in the reference put in the program's
  place, the loss over half of each batch's tokens;
* fleet cells: ``control`` as above, and the faults ``cohort_shifted``
  (each round trains the cohort drawn for the next), ``budgets_ignored``
  (every client runs K_max steps) and ``scatter_skipped`` (the arena
  keeps its rows) and ``half_batch``, each planted in the reference put
  in the program's place.

    python3 perfbench/tools/calibrate.py --workload <name> \
        --seeds 1 2 3 ... [--controls]

One JSON line per seed, with the seconds the first block and the
reference took; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the TPU runtime would otherwise write its logs to a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def fedtune(cell, seeds, controls):
    import jax.numpy as jnp
    from drivers.fedtune import Build
    from harness import fedref
    from harness.common import Spans
    b = Build(cell)
    spans = Spans()
    for seed in seeds:
        pool = b.pool(seed)
        t0 = time.perf_counter()
        state, rows, change = b.first_block(seed, pool, spans)
        del state
        t1 = time.perf_counter()
        ref = b.reference(seed, pool)
        t2 = time.perf_counter()
        out = {"seed": seed,
               "program": fedref.compare(rows, change, *ref),
               "block_s": t1 - t0, "reference_s": t2 - t1}
        if controls:
            c_rows, c_change, _ = b.reference(seed, pool,
                                              dtype=jnp.bfloat16)
            out["control"] = fedref.compare(c_rows, c_change, *ref)
            h_rows, h_change, _ = b.reference(seed, pool, half_batch=True)
            out["half_batch"] = fedref.compare(h_rows, h_change, *ref)
        print(json.dumps(out), flush=True)


def fleet(cell, seeds, controls):
    import jax.numpy as jnp
    from drivers.fleet import Build, compare
    from harness.common import Spans
    b = Build(cell)
    faults = {"control": {"dtype": jnp.bfloat16},
              "cohort_shifted": {"cohort_shift": 1},
              "budgets_ignored": {"full_budgets": True},
              "scatter_skipped": {"skip_scatter": True},
              "half_batch": {"half_batch": True}}
    for seed in seeds:
        t0 = time.perf_counter()
        prog = b.first_block(seed, Spans())
        t1 = time.perf_counter()
        ref = b.reference(seed, prog["t0"])
        t2 = time.perf_counter()
        out = {"seed": seed, "start_round": prog["t0"],
               "program": compare(prog, ref), "block_s": t1 - t0,
               "reference_s": t2 - t1}
        if controls:
            for name, kw in faults.items():
                got = b.reference(seed, prog["t0"], **kw)
                out[name] = compare(got, ref)
        print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args()
    from harness.common import enable_compile_cache, load_cell
    from harness.common import require_chips
    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    if cell.mix["kind"] == "fedtune":
        fedtune(cell, args.seeds, args.controls)
    elif cell.mix["kind"] == "fleet":
        fleet(cell, args.seeds, args.controls)
    else:
        raise SystemExit(f"no calibration for kind {cell.mix['kind']!r}")


if __name__ == "__main__":
    main()
