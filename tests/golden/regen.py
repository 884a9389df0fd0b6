"""Regenerate the golden-trajectory fixtures (tests/test_golden.py).

    PYTHONPATH=src python tests/golden/regen.py

Run this ONLY when a numeric change to the round engines is intended —
the fixture diff is the review artifact that makes the change visible.
Fixtures record the jax version they were generated under; the test
asserts them bit-exact, so regenerate them when the pinned jax moves.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import jax
    from _golden_common import CASES, fixture_path, run_case
    for name in CASES:
        trace = run_case(name)
        trace["jax"] = jax.__version__
        path = fixture_path(name)
        with open(path, "w") as f:
            json.dump(trace, f, indent=2)
            f.write("\n")
        print(f"wrote {path}: loss[0]={trace['loss'][0]:.6f} "
              f"loss[-1]={trace['loss'][-1]:.6f}")


if __name__ == "__main__":
    main()
