"""The host's side of a traced run: every event of every host thread,
nested by containment, laid over the device's idle gaps.

``tracered.load_xplane`` keeps only the harness's own ``bench.*`` spans of
the host planes.  Below them, on the same thread's line and on the same
clock, the profiler holds what splits a call: the program's
``otpu.coll.<slot>`` span, JAX's ``PjitFunction(<program>)``, the
runtime's ``PJRT_LoadedExecutable_Execute`` and what is under it.  This
module loads those, a line a thread, and adds them to the neutral form as

    "host_lines": {line: [[name, start_ns, dur_ns], ...], ...}

so that a small recorded trace checks the readers without a profiler.
Events of one line nest by containment (a thread's spans open and close
in order).  A span's **self time** is its duration less the part its
children cover.

The device's timeline is shifted onto the host's exactly as
``tracered.reduce_trace`` shifts it (same windows, same count of program
runs, same rule), so a device gap lands on the same host event here as it
lands on a ``bench.*`` span there.

No name of the program, of JAX or of libtpu is written here: every
pattern is a parameter in a metric's data file.  Where a pattern matches
nothing (the parent of the PR that added a span; a libtpu that renamed an
event), the reader returns None and the metric is left out.
"""
from __future__ import annotations

import bisect
import json
import os
import re

from harness import stats
from harness import tracered as tr

OUT_NAME = ".bench_out"         # as run.py's

SAME_THREAD = 0.01              # see issuing_thread

_loaded: dict = {}              # xplane path -> Run: every metric's reader
                                # is loaded anew, so the cache lives here


def load_host_lines(path: str) -> dict:
    """Every event of every line of the host planes, by name, start and
    duration, sorted by start: ``{"<plane>/<line>/<index>": [...]}``."""
    from jax.profiler import ProfileData

    lines: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events = sorted(([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events),
                            key=lambda e: (e[1], -e[2]))
            if events:
                lines[f"{plane.name}/{line.name}/{i}"] = events
    return lines


class Node:
    """One host event with the events it contains."""

    __slots__ = ("name", "start", "end", "children")

    def __init__(self, name: str, start: int, end: int) -> None:
        self.name, self.start, self.end = name, start, end
        self.children: list = []

    @property
    def dur(self) -> int:
        return self.end - self.start

    def self_ns(self) -> int:
        return self.dur - tr.total(tr.merge(
            (c.start, c.end) for c in self.children))

    def outermost(self, pattern) -> list:
        """The descendants that match and have no matching ancestor below
        this node (JAX writes ``PjitFunction(f)`` twice, one inside the
        other: that is one call)."""
        out = []
        for c in self.children:
            if pattern.search(c.name):
                out.append(c)
            else:
                out += c.outermost(pattern)
        return out


def nest(events: list) -> tuple:
    """The events of one thread as a tree under a nameless root, and how
    many of them **cross** an earlier one (start inside it, end after
    it).  An event lies inside the nearest earlier one that has not
    ended before it ends; one that crosses its neighbour becomes its
    sibling.  A thread's own spans open and close in order, so they
    never cross."""
    root = Node("", -(1 << 62), 1 << 62)
    stack = [root]
    crossings = 0
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        node = Node(name, start, start + dur)
        while len(stack) > 1 and not (stack[-1].start <= node.start
                                      and node.end <= stack[-1].end):
            crossings += node.start < stack[-1].end
            stack.pop()
        stack[-1].children.append(node)
        stack.append(node)
    return root, crossings


def _crossing_share(base: list, own: int, line: list) -> float:
    """The share of ``line``'s events that cross one of ``base``'s when
    laid over it (``own``: the crossings ``base`` has by itself)."""
    return (nest(base + line)[1] - own) / len(line)


def issuing_thread(lines: dict) -> tuple:
    """(events, workers): every event of the thread the harness issues
    from, and the lines of the threads it forks and joins.

    The profiler gives the issuing thread more than one line: Python and
    JAX write to the line that holds ``bench.round``, and the PJRT
    plugin, a library with a recorder of its own, writes the same
    thread's events to another.  A line is the thread's if laying it
    over the first makes no more than ``SAME_THREAD`` of its events cross
    one of the thread's: another thread's events fall where they fall,
    and cross at every turn.  One kind of other thread passes that test:
    on several chips the runtime hands each device's launch to a thread
    of its own and waits for all of them inside one of its spans.  Each
    of those nests cleanly, but they run beside one another, so two of
    them cross each other: a line that crosses another line that passed
    is a **worker**.  Workers stay out of the tree (a thread's tree has
    no two events open side by side); ``split_point`` looks into them
    for a ``part`` by time."""
    main = [evs for evs in lines.values()
            if any(n == tr.ROUND for n, _, _ in evs)]
    if len(main) != 1:
        raise ValueError(f"{len(main)} host lines hold a {tr.ROUND} span; "
                         "the harness issues from one thread")
    events = list(main[0])
    lo, hi = tr.window_of(events)
    own = nest(events)[1]
    passed = []
    for evs in lines.values():
        inside = [e for e in evs if e[1] >= lo and e[1] + e[2] <= hi]
        if evs is not main[0] and inside \
                and _crossing_share(events, own, inside) <= SAME_THREAD:
            passed.append(inside)
    # beside one another?  The first window is enough to tell.
    _, w_lo, w_hi = tr.windows_of(
        [e for e in events if e[0].startswith(tr.HOST_PREFIX)])[0]
    head = [[e for e in evs if w_lo <= e[1] < w_hi] for evs in passed]
    workers = []
    for i, evs in enumerate(passed):
        beside = any(head[i] and head[j] and _crossing_share(
            head[j], nest(head[j])[1], head[i]) > SAME_THREAD
            for j in range(len(passed)) if j != i)
        if beside:
            workers.append(evs)
        else:
            events += evs
    return events, workers


def self_segments(node: Node, path: tuple = ()) -> list:
    """(start, end, path) for every stretch of the line in which
    ``path[-1]`` is the innermost open event; disjoint and in order."""
    out = []
    path = path + (node.name,) if node.name else path
    at = node.start
    for c in node.children:
        if path and c.start > at:
            out.append((at, c.start, path))
        out += self_segments(c, path)
        at = max(at, c.end)
    if path and node.end > at:
        out.append((at, node.end, path))
    return out


class Run:
    """One traced run: its issuing thread as a tree, the worker threads'
    events by start, its windows, and the first device's idle gaps on the
    host's clock."""

    def __init__(self, events: dict) -> None:
        host = events["host"]
        self.windows = tr.windows_of(host)
        thread, workers = issuing_thread(events["host_lines"])
        self.root, _ = nest(thread)
        self.workers = sorted((s, s + d, n) for evs in workers
                              for n, s, d in evs)
        self.issues = [n for n in self.root.outermost(
            re.compile("^" + re.escape(tr.ISSUE)))]
        if [n.name[len(tr.ISSUE):] for n in self.issues] != \
                [p for p, _, _ in self.windows]:
            raise ValueError("the main line's issue spans are not the "
                             "trace's windows")
        lo, hi = tr.window_of(host)
        dev = min(events["device"], key=int)    # as reduce_trace: the first
        self.programs = tr.programs_per_call(events)    # observed, a point
        dev_windows = tr.device_windows(self.windows, events["modules"][dev],
                                        tr.window_programs(events))
        shift = max([0] + [hs - ds for (_, hs, _), (_, ds, _)
                           in zip(self.windows, dev_windows)])
        busy = tr.clip(tr.merge((s + shift, s + d + shift)
                                for _, s, d in events["device"][dev]),
                       lo, hi)
        self.gaps = tr.gaps_of(busy, lo, hi)
        self._idle = None

    def in_workers(self, pattern, start: int, end: int) -> list:
        """(start, end) of the workers' events matching ``pattern`` that
        lie inside [start, end]."""
        i = bisect.bisect_left(self.workers, (start,))
        out = []
        while i < len(self.workers) and self.workers[i][0] < end:
            s, e, name = self.workers[i]
            if e <= end and pattern.search(name):
                out.append((s, e))
            i += 1
        return out

    def idle_by_innermost(self) -> list:
        """[(path, idle ns)] over the traced rounds: every idle
        nanosecond of the first device put down to the innermost host
        event open at that time (what no event covers goes to ``()``)."""
        if self._idle is None:
            self._idle = self._attribute_idle()
        return self._idle

    def _attribute_idle(self) -> list:
        segments = self_segments(self.root)
        out: dict = {}
        i = 0
        for gs, ge in self.gaps:
            while i < len(segments) and segments[i][1] <= gs:
                i += 1
            covered, j = 0, i
            while j < len(segments) and segments[j][0] < ge:
                s, e, path = segments[j]
                ov = min(ge, e) - max(gs, s)
                if ov > 0:
                    out[path] = out.get(path, 0) + ov
                    covered += ov
                j += 1
            if ge - gs > covered:
                out[()] = out.get((), 0) + (ge - gs - covered)
        return sorted(out.items(), key=lambda kv: -kv[1])


def _out_dir(reader_file: str) -> str:
    """``.bench_out`` of the checkout a reader's file lies in."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file)))), OUT_NAME)


def run_of(ctx: dict, reader_file: str):
    """The traced run a reader is asked about, or None with a printed
    reason.  The trace is where ``run.py`` wrote it, under the checkout
    the reader's own file lies in (a test's temporary root too)."""
    log_dir = os.path.join(_out_dir(reader_file), "trace",
                           ctx["run"]["workload"])
    try:
        path = tr.find_xplane(log_dir)
        if path not in _loaded:
            events = tr.load_xplane(path)
            events["host_lines"] = load_host_lines(path)
            events["calls"] = {r["name"]: r["k"] for r in ctx["points"]}
            _loaded.clear()             # one run a process: keep one
            _loaded[path] = Run(events)
        return _loaded[path]
    except (OSError, ValueError, KeyError, IndexError) as e:
        print(f"hostspans: no host spans to read: {e}", flush=True)
        return None


def write_table(ctx: dict, reader_file: str, name: str, table) -> None:
    """A reader's full table, beside the run's other rows."""
    out_dir = _out_dir(reader_file)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"{ctx['run']['workload']}.{name}.json"), "w",
            encoding="utf-8") as f:
        json.dump(table, f, indent=1)


def split_point(run: Run, point: str, k: int, span, part=None, child=None,
                per: str = "call"):
    """One part of a call, over every call of ``point``'s traced
    windows: per call, the spans matching ``part`` inside the one
    matching ``span`` (the span itself without ``part``), less what
    matches ``child`` inside them.  ``part`` is also looked for in the
    worker threads the call waited for (``issuing_thread``), and what
    is counted is the time of the call in which the thread or any
    worker was in a matching event (workers run beside one another:
    wall time, not thread time).  With ``per`` ``"launch"`` the span is
    one a program launched, not one a call (a step of B buckets holds B
    of them), and a value is one launch's.  Returns a row, or a string
    saying why not: a window that does not hold exactly ``k`` spans
    (``k`` times the point's observed programs a call where the span is
    per launch), a ``part`` or a ``child`` that matches nothing
    anywhere."""
    values, issue_ns, windows = [], 0, 0
    found_part = found_child = False
    if per not in ("call", "launch"):
        return f"{point}: per is 'call' or 'launch', not {per!r}"
    want = k * run.programs.get(point, 1) if per == "launch" else k
    for issue in run.issues:
        if issue.name != tr.ISSUE + point:
            continue
        calls = issue.outermost(span)
        if len(calls) != want:
            return (f"{point}: a traced window holds {len(calls)} spans "
                    f"matching {span.pattern!r}, the harness issued {k} "
                    f"calls" + (f" of {want // k} programs each"
                                if per == "launch" else ""))
        windows += 1
        issue_ns += issue.dur
        for call in calls:
            whole = call.outermost(part) if part else [call]
            spans = [(n.start, n.end) for n in whole]
            if part:
                spans += run.in_workers(part, call.start, call.end)
            ns = tr.total(tr.merge(spans))
            found_part = found_part or ns > 0
            if child:
                inner = [c for n in whole for c in n.outermost(child)]
                found_child = found_child or bool(inner)
                ns -= sum(c.dur for c in inner)
            values.append(ns)
    if not windows:
        return f"{point}: no traced window"
    if part and not found_part:
        return f"{point}: nothing matches {part.pattern!r}"
    if child and not found_child:
        return f"{point}: nothing matches {child.pattern!r}"
    return {"point": point, "k": k, "windows": windows,
            "calls": len(values),
            "median_us": stats.median(values) / 1e3,
            "mean_us": sum(values) / len(values) / 1e3,
            "issue_us_per_call": issue_ns / len(values) / 1e3}
