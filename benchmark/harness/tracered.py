"""From a profiler trace to numbers: device busy time, idle gaps named by
what the host was doing, and device time per measured point.

The reduction works on a neutral form so that it can be checked against a
small recorded trace (``tests/fixtures``) without a profiler::

    {"device":  {"0": [[name, start_ns, dur_ns], ...], ...},  # XLA ops
     "modules": {"0": [[name, start_ns, dur_ns], ...], ...},  # program runs
     "host":    [[name, start_ns, dur_ns], ...],              # bench.* spans
     "launches": [[name, start_ns, dur_ns], ...],   # the issuing thread's
     "calls":   {point: k}}                 # calls a window, by the harness

``load_xplane`` makes the first four from the ``.xplane.pb`` the JAX
profiler writes; the harness adds ``calls``.

Which device op belongs to which point is settled by **count, not by
clock**: the device runs programs in the order they were launched, every
launch is one program run (one event of the ``XLA Modules`` line), and
the harness closes a window before it opens the next.  How many programs
a window launched is **observed, not assumed** (PR 32): a call may be a
step of several launches (one a gradient bucket) or, one day, several
calls one launch, so ``window_programs`` counts the launch events
(``launch.json`` names them: ``PjitFunction(...)``) that the issuing
thread wrote inside each ``bench.issue.<point>`` span.  Window w takes
that many of the device's runs, in order; the sum over the windows has
to be the number of runs on every device, and a window's count a whole
multiple of its k, or nothing is read.  The quotient is the point's
**programs a call**.  A neutral form recorded before PR 32 holds no
``launches``: its windows are read at one program a call, as they were
read then, and the count of runs on every device still has to agree.
The first trace looked at by hand (v5e, PR 23) showed why: the device's
timeline ran about 1 ms ahead of the host's, which would have moved four
or five calls of every window into its neighbour.  For naming idle gaps
by host span the device timeline is shifted so that no window's first run
starts before the host began to issue it.  XLA names its ops itself
(``all-reduce.3``, ``fusion.7``); nothing here depends on a name except
where a reader passes a pattern.
"""
from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
ROUND = "bench.round"           # the spans protocol.py writes
ISSUE = "bench.issue."
SYNC = "bench.sync"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "launch.json"), encoding="utf-8") as _f:
    LAUNCH_RE = re.compile(json.load(_f)["launch"])     # JAX's name: data


_HLO_RE = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def short_op(name: str) -> str:
    """The profiler names a device op by its whole HLO line
    (``%copy.1 = f32[2]{0:T(128)} copy(f32[2]{0:T(128)} %bitcast.1)``);
    keep the op's name and its result's type and shape
    (``copy.1 f32[2]``), which tells one program's ``copy.1`` from
    another's and stays readable in a ledger line."""
    m = _HLO_RE.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """The neutral form of one ``.xplane.pb``: the ``XLA Ops`` and ``XLA
    Modules`` lines of every TPU device plane, the harness's own spans
    from the host, and the launch events of the line that holds them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict = {}
    modules: dict = {}
    host: list = []
    launches: list = []
    for plane in data.planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = device if line.name == OPS_LINE else modules
                    into[m.group(1)] = sorted(
                        ([short_op(e.name), int(e.start_ns),
                          int(e.duration_ns)] for e in line.events),
                        key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                mine, issuing = [], False
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
                        issuing = issuing or e.name == ROUND
                    elif LAUNCH_RE.search(e.name):
                        mine.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
                if issuing:     # JAX writes them where the spans are
                    launches += mine
    host.sort(key=lambda e: e[1])
    launches.sort(key=lambda e: (e[1], -e[2]))
    return {"device": device, "modules": modules, "host": host,
            "launches": launches}


def describe_xplane(path: str, limit: int = 12) -> list:
    """Planes, lines and the first event names: for looking at a trace
    by hand before trusting a reduction of it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            names: dict = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(events), "names": top})
    return out


def merge(intervals) -> list:
    """Union of (start, end) intervals as a sorted list of disjoint
    ones.  Nested and overlapping ops count once."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def window_of(host: list) -> tuple:
    """The traced window: first ``bench.round`` start to last end."""
    rounds = [(s, s + d) for n, s, d in host if n == ROUND]
    if not rounds:
        raise ValueError("the trace holds no bench.round span")
    return min(s for s, _ in rounds), max(e for _, e in rounds)


def gaps_of(busy: list, lo: int, hi: int) -> list:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def attribute_gaps(gaps: list, host: list) -> dict:
    """Seconds of idle device time by the harness span the host was in.
    A gap is split among the leaf spans (everything but ``bench.round``)
    it overlaps; what no leaf span covers goes to ``bench.round`` (the
    harness between two windows)."""
    leaves = sorted((s, s + d, n) for n, s, d in host if n != ROUND)
    out: dict = {}
    i = 0
    for gs, ge in gaps:
        while i < len(leaves) and leaves[i][1] <= gs:
            i += 1
        covered = 0
        j = i
        while j < len(leaves) and leaves[j][0] < ge:
            ov = min(ge, leaves[j][1]) - max(gs, leaves[j][0])
            if ov > 0:
                out[leaves[j][2]] = out.get(leaves[j][2], 0) + ov
                covered += ov
            j += 1
        if ge - gs > covered:
            out[ROUND] = out.get(ROUND, 0) + (ge - gs - covered)
    return {n: ns / 1e9 for n, ns in out.items()}


def windows_of(host: list) -> list:
    """(point, start_ns, end_ns) of every measured window in the trace:
    a ``bench.issue.<point>`` span up to the end of the next
    ``bench.sync``."""
    out = []
    open_issue = None
    for n, s, d in host:
        if n.startswith(ISSUE):
            open_issue = (n[len(ISSUE):], s)
        elif n == SYNC and open_issue is not None:
            out.append((open_issue[0], open_issue[1], s + d))
            open_issue = None
    return out


def window_programs(events: dict) -> list:
    """The program runs each traced window launched, in the windows'
    order: the outermost launch events (JAX writes ``PjitFunction(f)``
    twice, one inside the other: that is one launch) that start inside
    the window's ``bench.issue.<point>`` span.  A count that is not a
    whole multiple of the window's k is refused."""
    calls = events["calls"]
    issues = [(n[len(ISSUE):], s, s + d) for n, s, d in events["host"]
              if n.startswith(ISSUE)]
    if "launches" not in events:        # recorded before PR 32
        return [calls[p] for p, _, _ in issues]
    out, i, open_until = [], 0, -1
    launches = sorted(events["launches"], key=lambda e: (e[1], -e[2]))
    for point, lo, hi in issues:
        count = 0
        while i < len(launches) and launches[i][1] < hi:
            _, s, d = launches[i]
            if s >= lo and s >= open_until:
                count += 1
                open_until = s + d
            i += 1
        if count == 0 or count % calls[point]:
            raise ValueError(
                f"window {len(out)} ({point}): {count} launches inside "
                f"its issue span are not a whole multiple of its k = "
                f"{calls[point]} calls")
        out.append(count)
    return out


def programs_per_call(events: dict) -> dict:
    """{point: programs one call launches}, observed in the trace.  A
    point whose windows disagree is refused."""
    out: dict = {}
    issued = [n[len(ISSUE):] for n, _, _ in events["host"]
              if n.startswith(ISSUE)]
    for point, count in zip(issued, window_programs(events)):
        per = count // events["calls"][point]
        if out.setdefault(point, per) != per:
            raise ValueError(f"{point}: one window launched {out[point]} "
                             f"programs a call, another {per}")
    return out


def device_windows(windows: list, runs: list, programs: list) -> list:
    """(point, start_ns, end_ns) on the device's own clock for every
    host window: the program runs in order, ``programs[w]`` to window w
    (``window_programs``)."""
    if len(programs) != len(windows) or len(runs) != sum(programs):
        raise ValueError(f"the trace holds {len(runs)} program runs on a "
                         f"device; the issuing thread launched "
                         f"{sum(programs)} programs in {len(programs)} "
                         f"issue spans of {len(windows)} windows")
    out, at = [], 0
    for (point, _, _), count in zip(windows, programs):
        mine = runs[at:at + count]
        at += count
        out.append((point, mine[0][1], max(s + d for _, s, d in mine)))
    return out


def reduce_trace(events: dict) -> dict:
    """Everything the readers and the result line take from a trace.

    ``busy_s`` is the union of device-op intervals inside the window,
    averaged over the device planes; ``points`` gives, per measured
    point, the windows and calls seen, the busy seconds and the seconds
    by op name, both averaged over the devices."""
    host, device = events["host"], events["device"]
    if not device:
        raise ValueError("the trace holds no TPU device plane with an "
                         f"{OPS_LINE!r} line")
    lo, hi = window_of(host)
    ndev = len(device)
    busy_ns = 0
    op_ns: dict = {}
    points: dict = {}
    windows = windows_of(host)
    programs = window_programs(events)
    first_gaps = None
    for dev in sorted(device, key=int):
        dev_windows = device_windows(windows, events["modules"][dev],
                                     programs)
        # no run starts before the host issued it: shift a device
        # timeline that says otherwise
        shift = max([0] + [hs - ds for (_, hs, _), (_, ds, _)
                           in zip(windows, dev_windows)])
        ops = [(n, s + shift, s + d + shift) for n, s, d in device[dev]]
        inside = [o for o in ops if o[2] > lo and o[1] < hi]
        busy = clip(merge((s, e) for _, s, e in inside), lo, hi)
        busy_ns += total(busy)
        if first_gaps is None:
            first_gaps = gaps_of(busy, lo, hi)
        for n, s, e in inside:
            op_ns[n] = op_ns.get(n, 0) + (min(e, hi) - max(s, lo))
        i = 0
        for point, ds, de in dev_windows:
            ds, de = ds + shift, de + shift
            while i < len(ops) and ops[i][1] < ds:
                i += 1
            j = i
            while j < len(ops) and ops[j][1] < de:
                j += 1
            p = points.setdefault(point, {"windows": 0, "busy_ns": 0,
                                          "ops": {}})
            p["busy_ns"] += total(merge((s, e) for _, s, e in ops[i:j]))
            for n, s, e in ops[i:j]:
                p["ops"][n] = p["ops"].get(n, 0) + (e - s)
            i = j
    for point, _, _ in windows:
        points[point]["windows"] += 1
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / ndev / 1e9,
        "devices": ndev,
        "device_ops": sorted(((n, ns / ndev / 1e9)
                              for n, ns in op_ns.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(attribute_gaps(first_gaps, host).items(),
                            key=lambda kv: -kv[1]),
        "points": {
            name: {"windows": p["windows"],
                   "calls": p["windows"] * events["calls"][name],
                   "busy_s": p["busy_ns"] / ndev / 1e9,
                   "ops": {n: ns / ndev / 1e9
                           for n, ns in p["ops"].items()}}
            for name, p in points.items()},
    }
