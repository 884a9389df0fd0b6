"""Readings that the limits of a language-model fine-tune cell
(``fedtune_lm`` traffic) are set from, on the chip at the cell's own
size, many seeds in one process:

* ``program``: the timed path's numbers against the reference, as a run
  compares them (the first block through the program's block driver);
* ``control``: the reference in bfloat16 put in the program's place,
  compared the same way (the configuration states float32);
* ``half_batch``: the loss over the first half of each sequence;
* the planted MoE faults, each in the reference put in the program's
  place: ``capacity`` (each held expert keeps 1.25 T K / E tokens of a
  sequence, the rest dropped), ``bias_ignored`` (selection by the
  sigmoid scores alone), ``no_norm`` and ``no_scale`` (the selected
  weights not renormalised, not scaled by the routing factor) and
  ``wrong_share`` (the held weights used as experts [8, 16)).

    python3 perfbench/tools/calibrate_lm.py --workload <name> \
        --seeds 1 2 3 ... [--controls] [--faults a b ...]

One JSON line per seed, with the seconds the first block and each
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

FAULTS = ("control", "half_batch", "capacity", "bias_ignored", "no_norm",
          "no_scale", "wrong_share")


def fault_kwargs(name):
    import jax.numpy as jnp
    if name == "control":
        return {"dtype": jnp.bfloat16}
    if name == "half_batch":
        return {"half_batch": True}
    return {"fault": name}


def calibrate(cell, seeds, faults=()):
    """One dict per seed: the program's numbers and each fault's."""
    from drivers.fedtune_lm import Build, counters
    from harness import fedref
    from harness.common import Spans
    b = Build(cell)
    for seed in seeds:
        pool = b.pool(seed)
        t0 = time.perf_counter()
        rows, change = b.first_block(seed, pool, Spans())
        b.state = None
        t1 = time.perf_counter()
        ref = b.reference(seed, pool)
        t2 = time.perf_counter()
        out = {"seed": seed, "program": fedref.compare(rows, change, *ref),
               "counters": counters(rows), "block_s": t1 - t0,
               "reference_s": t2 - t1}
        for name in faults:
            t = time.perf_counter()
            f_rows, f_change, _ = b.reference(seed, pool,
                                              **fault_kwargs(name))
            out[name] = fedref.compare(f_rows, f_change, *ref)
            out[name]["seconds"] = time.perf_counter() - t
        yield out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--faults", nargs="*", default=list(FAULTS))
    args = ap.parse_args()
    from harness.common import enable_compile_cache, load_cell
    from harness.common import require_chips
    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    for out in calibrate(cell, args.seeds,
                         args.faults if args.controls else ()):
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
