"""CI benchmark-regression guard.

Compares ``bench_results.csv`` rows against a committed baseline JSON
(``benchmarks/baseline.json``). For every baseline entry present in the
csv the row must

  * keep its ``derived`` column (kernel max |err| vs the oracle) at or
    below ``max_err``,
  * not regress its cost by more than ``max_regression`` (e.g. 1.25 =
    +25%). When the entry names a ``normalize_by`` row, cost is the
    RATIO us(row) / us(normalize_by) from the SAME run — runner speed
    cancels out, so the guard is meaningful across CI machines; the raw
    us_per_call is only reported.

Breaches are bucketed three ways and the run ends with ONE
machine-readable summary line (``bench guard summary: {...json...}``
with hard/soft/advisory counts — CI and humans parse the same line):

  * hard     — breaches of normal entries; the only bucket that can
               fail the run (exit 1, mode=hard only)
  * soft     — breaches of entries marked ``"level": "soft"`` (fresh
               scenario/faults rows whose baselines haven't stabilized
               across runners yet); always advisory-only
  * advisory — rows missing from the csv (newly added baseline rows
               must not brick older result files or ``--only``
               subsets), malformed csv lines, and baseline entries that
               error while being checked (each entry is evaluated in
               its own try/except, so one bad row cannot take down the
               whole guard)

Modes: ``hard`` exits 1 on any hard breach (the CI default),
``soft`` prints breaches but exits 0, ``off`` skips entirely.

  python -m benchmarks.compare bench_results.csv benchmarks/baseline.json \
      --mode hard
"""
from __future__ import annotations

import argparse
import json
import sys


def read_results(path: str):
    """-> (rows, parse_advisories). Malformed lines are reported, not
    fatal: a partially written csv should degrade to advisories."""
    rows, bad = {}, []
    with open(path) as f:
        header = f.readline()
        if not header.startswith("name,"):
            raise SystemExit(f"{path}: not a bench_results csv")
        for ln, line in enumerate(f, start=2):
            line = line.strip()
            if not line:
                continue
            try:
                name, us, derived = line.split(",")
                rows[name] = (float(us), float(derived))
            except ValueError:
                bad.append(f"{path}:{ln}: malformed row {line!r} "
                           f"(skipped)")
    return rows, bad


def _check_entry(name, spec, results):
    """-> (breach_msgs, advisory_msgs, report_line_or_None) for ONE
    baseline entry."""
    breaches, advisories = [], []
    if name not in results:
        return [], [f"{name}: row missing from results (skipped)"], None
    us, derived = results[name]
    max_err = spec.get("max_err")
    if max_err is not None and derived > max_err:
        breaches.append(f"{name}: derived {derived:g} > "
                        f"max_err {max_err:g}")
    norm = spec.get("normalize_by")
    if norm is not None:
        if norm not in results:
            advisories.append(f"{name}: normalize_by row {norm!r} "
                              f"missing from results (skipped)")
            return breaches, advisories, None
        cost, base = us / results[norm][0], spec["ratio"]
        kind = f"ratio vs {norm}"
    else:
        cost, base = us, spec["us_per_call"]
        kind = "us_per_call"
    limit = base * spec.get("max_regression", 1.25)
    line = (f"{name}: {kind} {cost:.4g} (baseline {base:.4g}, "
            f"limit {limit:.4g}, raw {us:.0f}us"
            + (", soft" if spec.get("level") == "soft" else "") + ")")
    if cost > limit:
        breaches.append(f"{name}: {kind} {cost:.4g} regressed past "
                        f"{limit:.4g} (baseline {base:.4g})")
    return breaches, advisories, line


def check(results: dict, baseline: dict):
    """-> (hard, soft, advisories, report_lines).

    Entries with ``level: soft`` route ALL their breaches to the soft
    bucket; missing rows and per-entry evaluation errors are advisory.
    Only the hard bucket can fail the run."""
    hard, soft, advisories, report = [], [], [], []
    for name, spec in baseline.items():
        try:
            breaches, advs, line = _check_entry(name, spec, results)
        except Exception as e:  # one bad entry must not kill the guard
            advisories.append(f"{name}: entry check errored "
                              f"({e.__class__.__name__}: {e}) — "
                              f"advisory only")
            continue
        advisories.extend(advs)
        (soft if spec.get("level") == "soft" else hard).extend(breaches)
        if line is not None:
            report.append(line)
    return hard, soft, advisories, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("results")
    ap.add_argument("baseline")
    ap.add_argument("--mode", choices=["hard", "soft", "off"],
                    default="hard")
    ap.add_argument("--summary-out", default=None,
                    help="also write the JSON guard summary to this "
                         "path")
    args = ap.parse_args()
    if args.mode == "off":
        print("bench guard: off")
        return
    with open(args.baseline) as f:
        baseline = json.load(f)
    results, parse_advs = read_results(args.results)
    hard, soft, advisories, report = check(results, baseline)
    advisories = parse_advs + advisories
    for line in report:
        print("bench guard:", line)
    for a in advisories:
        print("bench guard ADVISORY:", a)
    for s in soft:
        print("bench guard SOFT:", s)
    for v in hard:
        print("bench guard VIOLATION:", v)
    summary = {"mode": args.mode, "rows_checked": len(report),
               "hard": len(hard), "soft": len(soft),
               "advisory": len(advisories),
               "ok": not (hard and args.mode == "hard")}
    print("bench guard summary:", json.dumps(summary, sort_keys=True))
    if args.summary_out:
        with open(args.summary_out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
    if hard and args.mode == "hard":
        sys.exit(1)


if __name__ == "__main__":
    main()
