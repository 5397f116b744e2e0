#!/usr/bin/env python3
"""The spread of a cell's end-to-end metrics over sets of runs, as the
driver measures it, and the bound that spread leads to.

    python3 benchmark/tools/spread.py --sets 2 run1.out run2.out ... run12.out

Each file is the standard output of one ``run.py`` run (its last line is
the result).  The files are split in order into ``--sets`` equal sets.
Per metric: each set's median and spread (the distance between the
quartiles over the median), the wider spread, how far the second set's
median is from the first's, and five times the wider spread (the rule for
a bound, never under 1%).  ``--json`` prints the same as one object."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import stats  # noqa: E402


def last_result(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def table(files: list, sets: int) -> dict:
    results = [last_result(p) for p in files]
    size = len(results) // sets
    out: dict = {}
    for name in results[0]["metrics"]:
        rows = []
        for s in range(sets):
            values = [r["metrics"][name]["value"]
                      for r in results[s * size:(s + 1) * size]]
            rows.append({"median": stats.median(values),
                         "spread": stats.spread(values), "values": values})
        widest = max(r["spread"] for r in rows)
        out[name] = {
            "unit": results[0]["metrics"][name]["unit"], "sets": rows,
            "widest_spread": widest,
            "second_over_first": rows[-1]["median"] / rows[0]["median"] - 1,
            "five_times": max(0.01, 5 * widest)}
    out["_all_correct"] = all(r["correct"] and r["failed"] == 0
                              for r in results)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("files", nargs="+")
    args = ap.parse_args()
    t = table(args.files, args.sets)
    if args.json:
        print(json.dumps(t))
        return 0
    for name, m in t.items():
        if name.startswith("_"):
            continue
        sets = "  ".join(f"median {s['median']:.6g} spread "
                         f"{100 * s['spread']:.2f}%" for s in m["sets"])
        print(f"{name} [{m['unit']}]: {sets}  | widest "
              f"{100 * m['widest_spread']:.2f}%  second/first "
              f"{100 * m['second_over_first']:+.2f}%  5x -> "
              f"{100 * m['five_times']:.1f}%")
    print("all correct:", t["_all_correct"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
