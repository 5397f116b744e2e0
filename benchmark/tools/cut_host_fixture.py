#!/usr/bin/env python3
"""Cut a small fixture, host lines included, from the newest trace of a
``--trace 1`` run.

    python3 benchmark/tools/cut_host_fixture.py <cell> --points a,b,c --calls 16 --out fixture.json [--span REGEX]

Keeps, of the first traced window of each named point, the first
``--calls`` calls (a call is as many top-level events of the window
matching ``--span`` as the point launches programs a call: one, or one a
bucket of a step): the issuing thread's events up to the end of the last of
them, a line a thread as ``harness/hostspans.py`` loads them (the other
threads' events of that time too), the device's program runs and ops
of those calls (by count, as ``tracered`` tells them apart), the window's
``bench.issue`` span cut to end with its last kept call, and its
``bench.sync``.  ``calls`` in the fixture is the kept count, so the
k-spans check holds on it; ``launches`` are the issuing thread's launch
events of the kept calls, from which the programs a call are read.  What lay between the kept calls and the sync
is gone: the device reads as idle there, under ``bench.round``."""
import argparse
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import hostspans, tracered  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--points", required=True)
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--out", required=True)
    ap.add_argument("--recorded", default="")
    ap.add_argument("--span", default=r"^(otpu\.coll\.|PjitFunction\()")
    args = ap.parse_args()
    log_dir = os.path.join(os.path.dirname(BENCH_DIR), ".bench_out", "trace",
                           args.cell)
    path = tracered.find_xplane(log_dir)
    events = tracered.load_xplane(path)
    events["host_lines"] = hostspans.load_host_lines(path)
    with open(os.path.join(log_dir, "calls.json"), encoding="utf-8") as f:
        said = json.load(f)
    events["calls"] = said.get("calls", said)   # the calls alone before PR 32
    run = hostspans.Run(events)
    wanted = args.points.split(",")
    call_re = re.compile(args.span)
    first = {}
    for i, (point, _, _) in enumerate(run.windows):
        if point in wanted and point not in first:
            first[point] = i
    starts, at = [], 0              # index of each window's first run
    programs = tracered.window_programs(events)     # as the trace shows
    for count in programs:
        starts.append(at)
        at += count
    syncs = [e for e in events["host"] if e[0] == tracered.SYNC]
    main = next(k for k, evs in events["host_lines"].items()
                if any(n == tracered.ROUND for n, _, _ in evs))
    host, ranges = [], []
    modules = {d: [] for d in events["modules"]}
    device = {d: [] for d in events["device"]}
    for point in sorted(first, key=first.get):
        i, n = first[point], args.calls
        issue = run.issues[i]
        per = programs[i] // events["calls"][point]     # programs a call
        last = [c for c in issue.children
                if call_re.search(c.name)][n * per - 1]
        host += [[issue.name, issue.start, last.end - issue.start + 1],
                 syncs[i]]
        ranges.append((issue.start, last.end))
        for d in modules:
            runs = events["modules"][d][starts[i]:starts[i] + n * per]
            modules[d] += runs
            lo, hi = runs[0][1], max(s + dur for _, s, dur in runs)
            device[d] += [op for op in events["device"][d]
                          if lo <= op[1] < hi]
    lo = min(s for _, s, _ in host) - 1000
    hi = max(s + d for _, s, d in host) + 1000
    host.append([tracered.ROUND, lo, hi - lo])
    lines = {}
    for key, evs in events["host_lines"].items():
        kept = [e for e in evs if not e[0].startswith(tracered.HOST_PREFIX)
                and any(a <= e[1] and e[1] + e[2] <= b for a, b in ranges)]
        if key == main:
            kept += host
        if kept:
            lines[key] = sorted(kept, key=lambda e: (e[1], -e[2]))
    out = {"recorded": args.recorded,
           "calls": {p: args.calls for p in first},
           "host": sorted(host, key=lambda e: e[1]),
           "launches": [e for e in events["launches"]
                        if any(a <= e[1] and e[1] + e[2] <= b
                               for a, b in ranges)],
           "host_lines": lines, "modules": modules, "device": device}
    hostspans.Run(out)              # the cut still accounts for itself
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f, separators=(",", ":"))
    print(args.out, os.path.getsize(args.out), "bytes;",
          {k: len(v) for k, v in lines.items()}, "host events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
