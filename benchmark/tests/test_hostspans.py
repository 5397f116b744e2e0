"""The host side of a trace: nesting, self time, the k-spans check and
idle attribution, against hand-made events with hand-worked answers and
against a small trace recorded on the chip
(``fixtures/rank1_hostlines_v5e.json``, cut by ``tools/cut_host_fixture.py``
from a ``--trace 1`` run of ``rank1-mix``)."""
import json
import math
import os
import re

import pytest

from harness import hostspans as hs
from harness import manifest as mf
from harness import protocol as pt
from harness import tracered as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "rank1_hostlines_v5e.json")

# test_tracered's HAND (one round, windows a: 2 calls, b: 1 call, two
# devices) with the issuing thread's line under it.  Times in ns.
#   call a1: otpu.coll.x 110-190 > PjitFunction(otpu_a) 120-185 (twice, as
#            JAX writes it) > PJRT_Execute 130-180 > Allocate 135-155
#   call a2: otpu.coll.x 200-290 > Pjit 215-280 > PJRT 230-270
#            > Allocate 232-242 and 250-260
#   call b1: otpu.coll.y 610-690 > Pjit 620-690 > PJRT 640-680 (no Allocate)
LINE = [
    ["bench.round", 0, 1000],
    ["bench.issue.a", 100, 200],
    ["otpu.coll.x", 110, 80], ["PjitFunction(otpu_a)", 120, 65],
    ["PjitFunction(otpu_a)", 121, 63], ["PJRT_Execute", 130, 50],
    ["Allocate", 135, 20],
    ["otpu.coll.x", 200, 90], ["PjitFunction(otpu_a)", 215, 65],
    ["PjitFunction(otpu_a)", 216, 63], ["PJRT_Execute", 230, 40],
    ["Allocate", 232, 10], ["Allocate", 250, 10],
    ["bench.sync", 300, 200],
    ["bench.issue.b", 600, 100],
    ["otpu.coll.y", 610, 80], ["PjitFunction(otpu_b)", 620, 70],
    ["PJRT_Execute", 640, 40],
    ["bench.sync", 700, 300],
]
HAND = {
    "calls": {"a": 2, "b": 1},
    "modules": {
        "0": [["jit_a(1)", 150, 100], ["jit_a(1)", 300, 100],
              ["jit_b(2)", 650, 300]],
        "1": [["jit_a(1)", 150, 100], ["jit_a(1)", 300, 100],
              ["jit_b(2)", 700, 200]],
    },
    "host": [e for e in LINE if e[0].startswith("bench.")],
    "host_lines": {"/host:CPU/python3/0": LINE,
                   "/host:CPU/other/1": [["elsewhere", 0, 5000]]},
    "device": {
        "0": [["fusion.1", 150, 100], ["all-reduce.2", 300, 100],
              ["all-reduce.2", 650, 300]],
        "1": [["fusion.1", 150, 100], ["all-reduce.2", 300, 100],
              ["all-reduce.2", 700, 200]],
    },
}
COLL, PJIT = re.compile(r"^otpu\.coll\."), re.compile(r"^PjitFunction\(otpu_")
PJRT, ALLOC = re.compile("^PJRT_Execute$"), re.compile("Allocate")


def test_nesting_and_self_time():
    root, crossings = hs.nest(LINE)
    assert crossings == 0
    (rnd,) = root.children
    assert [c.name for c in rnd.children] == [
        "bench.issue.a", "bench.sync", "bench.issue.b", "bench.sync"]
    issue_a = rnd.children[0]
    assert [c.name for c in issue_a.children] == ["otpu.coll.x"] * 2
    call = issue_a.children[0]
    assert call.dur == 80 and call.self_ns() == 80 - 65
    # the doubled PjitFunction is one call: outermost matches only
    assert [n.dur for n in call.outermost(PJIT)] == [65]
    assert [n.dur for n in issue_a.outermost(PJRT)] == [50, 40]
    assert issue_a.self_ns() == 200 - 80 - 90
    # an event that only overlaps its neighbour is its sibling
    root, crossings = hs.nest([["p", 0, 100], ["q", 50, 100], ["r", 60, 10]])
    assert crossings == 1
    assert [c.name for c in root.children] == ["p", "q"]
    assert [c.name for c in root.children[1].children] == ["r"]


def test_split_by_hand():
    run = hs.Run(HAND)
    fw = hs.split_point(run, "a", 2, COLL, child=PJIT)
    assert fw["calls"] == 2 and fw["windows"] == 1
    assert math.isclose(fw["mean_us"], ((80 - 65) + (90 - 65)) / 2 / 1e3)
    assert math.isclose(fw["median_us"], 20 / 1e3)
    assert math.isclose(fw["issue_us_per_call"], 200 / 2 / 1e3)
    pjit = hs.split_point(run, "a", 2, PJIT, child=PJRT)
    assert math.isclose(pjit["mean_us"], ((65 - 50) + (65 - 40)) / 2 / 1e3)
    pjrt = hs.split_point(run, "a", 2, PJRT)
    assert math.isclose(pjrt["mean_us"], (50 + 40) / 2 / 1e3)
    alloc = hs.split_point(run, "a", 2, PJRT, part=ALLOC)
    assert math.isclose(alloc["mean_us"], (20 + 20) / 2 / 1e3)
    # the parts are the whole: what is left of the issue span is the
    # harness's own loop
    whole = fw["mean_us"] + pjit["mean_us"] + pjrt["mean_us"]
    assert math.isclose(whole, (80 + 90) / 2 / 1e3)


def test_the_k_spans_check_and_missing_patterns():
    run = hs.Run(HAND)
    why = hs.split_point(run, "a", 4, COLL, child=PJIT)
    assert isinstance(why, str) and "holds 2 spans" in why and "4" in why
    assert "nothing matches" in hs.split_point(run, "b", 1, PJRT, part=ALLOC)
    assert "nothing matches" in hs.split_point(
        run, "a", 2, COLL, child=re.compile("^no such$"))
    assert "holds 0 spans" in hs.split_point(
        run, "a", 2, re.compile("^no such$"))
    assert "no traced window" in hs.split_point(run, "zzz", 1, COLL)


# a step of two buckets: one otpu.part.step span a call (no such span in
# the program: a name for the test), two launches inside it.  k = 2.
STEP_LINE = [
    ["bench.round", 0, 1000],
    ["bench.issue.s", 100, 500],
    ["otpu.part.step", 110, 200],
    ["PjitFunction(otpu_a)", 120, 60], ["PjitFunction(otpu_a)", 121, 58],
    ["PJRT_Execute", 130, 40],
    ["PjitFunction(otpu_a)", 220, 80], ["PJRT_Execute", 230, 60],
    ["otpu.part.step", 350, 220],
    ["PjitFunction(otpu_a)", 360, 70], ["PJRT_Execute", 370, 50],
    ["PjitFunction(otpu_a)", 460, 90], ["PJRT_Execute", 470, 70],
    ["bench.sync", 600, 100],
]
STEP = {
    "calls": {"s": 2},
    "host": [e for e in STEP_LINE if e[0].startswith("bench.")],
    "launches": [e for e in STEP_LINE if e[0].startswith("PjitFunction(")],
    "host_lines": {"/host:CPU/python3/0": STEP_LINE},
    "modules": {"0": [["jit_a(1)", 140 + 100 * i, 30] for i in range(4)]},
    "device": {"0": [["copy.1", 140 + 100 * i, 30] for i in range(4)]},
}


def test_a_span_a_launch_is_counted_k_times_the_programs_a_call(monkeypatch):
    run = hs.Run(STEP)
    assert run.programs == {"s": 2}
    step = re.compile(r"^otpu\.part\.")
    # a span that is one a call stays k a window
    whole = hs.split_point(run, "s", 2, step)
    assert whole["calls"] == 2
    assert math.isclose(whole["mean_us"], (200 + 220) / 2 / 1e3)
    # a span that is one a launch: k x 2, a value a launch
    pjrt = hs.split_point(run, "s", 2, PJRT, per="launch")
    assert pjrt["calls"] == 4 and pjrt["windows"] == 1
    assert math.isclose(pjrt["mean_us"], (40 + 60 + 50 + 70) / 4 / 1e3)
    assert math.isclose(pjrt["median_us"], 55 / 1e3)
    pjit = hs.split_point(run, "s", 2, PJIT, child=PJRT, per="launch")
    assert math.isclose(pjit["mean_us"], 20 / 1e3)
    # read as one a call it is refused, and says what it found
    why = hs.split_point(run, "s", 2, PJRT)
    assert isinstance(why, str) and "holds 4 spans" in why
    why = hs.split_point(run, "s", 2, step, per="launch")
    assert "holds 2 spans" in why and "2 programs each" in why
    assert "per is" in hs.split_point(run, "s", 2, PJRT, per="bucket")
    # the readers: a launch's PJRT time through host_span_split, and the
    # rest of the issue span a bucket, (500 - 300) / (2 x 2) ns
    monkeypatch.setattr(hs, "run_of", lambda ctx, f: run)
    written = {}
    monkeypatch.setattr(hs, "write_table",
                        lambda ctx, f, name, t: written.update({name: t}))
    rows = [{"name": "s", "e2e": "step_us", "k": 2,
             "collectives_per_call": 2}]
    ctx = {"points": rows, "run": {"workload": "hand"},
           "trace": tr.reduce_trace(STEP)}
    split = pt.load_module("readers", "host_span_split", mf.BENCH_DIR)
    rest = pt.load_module("readers", "host_issue_rest", mf.BENCH_DIR)
    params = {"span": PJRT.pattern, "select": {"e2e": "step_us"},
              "table": "t"}
    assert split.read(ctx, params) is None              # one a call: no
    assert math.isclose(split.read(ctx, {**params, "per": "launch"}), 0.055)
    got = rest.read(ctx, {"inside": r"^PjitFunction\(", "table": "r",
                          "over_field": "collectives_per_call",
                          "select": {"e2e": "step_us"}})
    assert math.isclose(got, (500 - 300) / 4 / 1e3)
    assert written["r"][0]["spans_inside"] == 4
    assert math.isclose(written["r"][0]["issue_us_per_unit"], 0.125)
    # a call, where no column divides further
    assert math.isclose(rest.read(ctx, {"inside": r"^PjitFunction\(",
                                        "table": "r"}), 200 / 2 / 1e3)
    assert rest.read(ctx, {"inside": "^nothing$", "table": "r"}) is None
    assert rest.read({**ctx, "trace": None},
                     {"inside": "^x$", "table": "r"}) is None


def test_idle_attribution_sums_to_the_idle_total():
    run = hs.Run(HAND)
    # device 0, as tracered: busy 150-250, 300-400, 650-950 of 0-1000
    assert run.gaps == [[0, 150], [250, 300], [400, 650], [950, 1000]]
    idle = dict(run.idle_by_innermost())
    assert sum(idle.values()) == 500
    by_name: dict = {}
    for path, ns in idle.items():
        by_name[path[-1]] = by_name.get(path[-1], 0) + ns
    # 0-150: round 100, issue.a 10, coll.x 10 (110-120), pjit outer 1,
    # pjit inner 9 (121-130), PJRT 5 (130-135), Allocate 15 (135-150)
    # 250-300: Allocate 10, PJRT 10, pjit inner 9, outer 1, coll.x 10,
    # issue.a 10;  400-650: sync 100, round 100, issue.b 10, coll.y 10,
    # pjit 20, PJRT 10;  950-1000: sync 50
    assert by_name["bench.round"] == 100 + 100            # 0-100, 500-600
    assert by_name["bench.sync"] == 100 + 50
    assert by_name["PJRT_Execute"] == 5 + 10 + 10
    assert by_name["otpu.coll.x"] == 10 + 10              # 110-120, 280-290
    assert by_name["Allocate"] == 15 + 10                 # 135-150, 250-260
    assert by_name["otpu.coll.y"] == 10                   # 610-620
    # the harness's span names agree with tracered's coarser attribution
    reduced = tr.reduce_trace(HAND)
    assert math.isclose(sum(s for _, s in reduced["idle_gaps"]), 500e-9)


def test_threads_the_call_waits_for_are_workers_not_the_thread():
    """On several chips the runtime launches each device from a thread
    of its own and the issuing thread waits inside one span.  Each such
    line nests cleanly, but two of them cross each other: they stay out
    of the tree, and a part is looked for in them by time, counted once
    where they run beside one another."""
    line = [["bench.round", 0, 1000], ["bench.issue.a", 100, 200],
            ["otpu.coll.x", 110, 180], ["PjitFunction(otpu_a)", 120, 160],
            ["PJRT_Execute", 130, 140], ["bench.sync", 300, 200]]
    w1 = [["Scheduled", 140, 100], ["Allocate", 150, 40]]       # 150-190
    w2 = [["Scheduled", 145, 110], ["Allocate", 170, 50]]       # 170-220
    stranger = [["Done", 125, 10], ["Done", 265, 10]]   # crosses the thread's
    events = {
        "calls": {"a": 1},
        "modules": {"0": [["jit_a(1)", 250, 100]]},
        "device": {"0": [["fusion.1", 250, 100]]},
        "host": [e for e in line if e[0].startswith("bench.")],
        "host_lines": {"py": line, "w1": w1, "w2": w2, "s": stranger},
    }
    thread, workers = hs.issuing_thread(events["host_lines"])
    assert thread == line and workers == [w1, w2]
    run = hs.Run(events)
    row = hs.split_point(run, "a", 1, PJRT, part=ALLOC)
    assert math.isclose(row["mean_us"], (220 - 150) / 1e3)
    # the tree holds the thread alone: idle falls to its own events
    assert {path[-1] for path, _ in run.idle_by_innermost()} <= \
        {e[0] for e in line}
    assert sum(ns for _, ns in run.idle_by_innermost()) == 900
    # one launch thread alone runs beside nothing: it is laid in the tree
    alone = {**events, "host_lines": {"py": line, "w1": w1}}
    thread, workers = hs.issuing_thread(alone["host_lines"])
    assert workers == [] and len(thread) == len(line) + len(w1)
    assert math.isclose(hs.split_point(hs.Run(alone), "a", 1, PJRT,
                                       part=ALLOC)["mean_us"], 40 / 1e3)


def test_readers_on_the_hand_trace(monkeypatch):
    rows = [{"name": "a", "set": "s", "k": 2}, {"name": "b", "set": "s",
                                                "k": 1}]
    ctx = {"points": rows, "run": {"workload": "hand"},
           "trace": tr.reduce_trace(HAND), "device_kind": "TPU v5 lite"}
    monkeypatch.setattr(hs, "run_of", lambda ctx, f: hs.Run(HAND))
    written = {}
    monkeypatch.setattr(hs, "write_table",
                        lambda ctx, f, name, t: written.update({name: t}))
    split = pt.load_module("readers", "host_span_split", mf.BENCH_DIR)
    idle = pt.load_module("readers", "idle_by_host_span", mf.BENCH_DIR)
    share = pt.load_module("readers", "trace_op_share", mf.BENCH_DIR)
    got = split.read(ctx, {"span": COLL.pattern, "child": PJIT.pattern,
                           "select": {"set": "s"}, "table": "t"})
    # a: median of 15, 25 ns; b: 80 - 70 = 10 ns
    assert math.isclose(got, math.sqrt(0.020 * 0.010))
    assert [t["point"] for t in written["t"]] == ["a", "b"]
    # one point lacks the part: nothing is read for the set
    assert split.read(ctx, {"span": PJRT.pattern, "part": "Allocate",
                            "select": {"set": "s"}, "table": "t"}) is None
    assert math.isclose(split.read(
        ctx, {"span": PJRT.pattern, "part": "Allocate",
              "select": {"name": "a"}, "table": "t"}), 0.020)
    assert split.read(ctx, {"span": "^nothing$", "select": {"set": "s"},
                            "table": "t"}) is None
    assert split.read({**ctx, "trace": None},
                      {"span": COLL.pattern, "table": "t"}) is None
    # idle: in the framework's own spans 30 of 500 ns; under PjitFunction
    # and below 30 in each of the three gaps that hold a call
    fw = idle.read(ctx, {"pattern": r"^otpu\.", "table": "i"})
    assert math.isclose(fw, 100 * 30 / 500)
    launch = idle.read(ctx, {"pattern": r"^PjitFunction\(", "below": True,
                             "table": "i"})
    assert math.isclose(launch, 100 * 90 / 500)
    assert fw + launch <= 100
    assert written["i"]["by_innermost_event"][0][0] == "bench.round"
    assert idle.read(ctx, {"pattern": "^nothing$", "table": "i"}) is None
    # ops: all-reduce.2 is 100 of a's 200 ns and all of b's 250
    got = share.read(ctx, {"pattern": "^all-reduce", "select": {"set": "s"},
                           "table": "o"})
    assert math.isclose(got, 100 * (100 + 250) / (200 + 250))
    assert share.read(ctx, {"pattern": "^otpu_", "table": "o"}) is None


def test_program_counter_reader():
    from ompi_tpu.runtime import spc

    reader = pt.load_module("readers", "program_counter", mf.BENCH_DIR)
    if not spc.counters():
        spc.init()
    base = spc.read("device_program_builds")
    spc.record("device_program_builds", 3)
    assert reader.read({}, {"name": "device_program_builds"}) == base + 3
    assert reader.read({}, {"name": "device_program_builds",
                            "scale": 0.5}) == (base + 3) / 2
    assert reader.read({}, {"name": "no_such_counter"}) is None
    spc.record("device_slow_path", 1)
    spc.bump_device(8)
    got = reader.read({}, {"name": "device_slow_path",
                           "over": "device_collectives", "scale": 100})
    assert 0 < got <= 100 * spc.read("device_slow_path")


def test_a_run_the_harness_cannot_account_for_is_refused():
    two = {**HAND, "host_lines": {"x": LINE, "y": LINE}}
    with pytest.raises(ValueError, match="2 host lines"):
        hs.Run(two)
    moved = {**HAND, "host_lines": {"x": [e for e in LINE
                                          if e[0] != "bench.issue.b"]}}
    with pytest.raises(ValueError, match="not the trace's windows"):
        hs.Run(moved)


@pytest.fixture(scope="module")
def recorded():
    return json.load(open(FIXTURE, encoding="utf-8"))


def test_the_recorded_threads_lines_are_laid_together(recorded):
    """On the v5e the issuing thread has two lines (Python and JAX write
    to one, the PJRT plugin to another) and a third thread completes the
    launches: the first two nest without a crossing, the third crosses at
    every turn and stays out."""
    lines = recorded["host_lines"]
    main = next(k for k, v in lines.items()
                if any(n == tr.ROUND for n, _, _ in v))
    merged, workers = hs.issuing_thread(lines)
    assert workers == []                    # one chip: no launch threads
    names = {e[0] for e in merged}
    plugin = next(k for k, v in lines.items()
                  if any(n == "PJRT_LoadedExecutable_Execute"
                         for n, _, _ in v))
    other = next(k for k in lines if k not in (main, plugin))
    assert plugin != main
    assert len(merged) == len(lines[main]) + len(lines[plugin])
    assert hs.nest(merged)[1] == 0
    assert not names & {e[0] for e in lines[other]} - \
        {e[0] for e in lines[main] + lines[plugin]}
    crossings = hs.nest(lines[main] + lines[other])[1]
    assert crossings > 0.2 * len(lines[other])


def test_split_of_the_recorded_trace(recorded):
    """Eight calls of three points: a collective slot, the persistent
    handle, and a stack reduction (no collective, so no otpu.coll span)."""
    run = hs.Run(recorded)
    coll = re.compile(r"^otpu\.coll\.")
    pjit = re.compile(r"^PjitFunction\(otpu_")
    pjrt = re.compile(r"^PJRT_LoadedExecutable_Execute$")
    alloc = re.compile(r"DeferredTpuAllocator::Allocate")
    for point, lo_us, hi_us in (("allreduce.sum.f32.1KiB", 5, 25),
                                ("allreduce_init.sum.f32.8B", 1, 6)):
        fw = hs.split_point(run, point, 8, coll, child=pjit)
        jit = hs.split_point(run, point, 8, pjit, child=pjrt)
        launch = hs.split_point(run, point, 8, pjrt)
        out = hs.split_point(run, point, 8, pjrt, part=alloc)
        assert fw["calls"] == jit["calls"] == launch["calls"] == 8
        assert lo_us < fw["median_us"] < hi_us
        assert 15 < jit["median_us"] < 45
        assert 120 < launch["median_us"] < 260
        assert 50 < out["median_us"] < launch["median_us"]
        # the three parts are the whole of the call's span, and the call's
        # span is most of the harness's issue time a call
        issue = next(n for n in run.issues if n.name == tr.ISSUE + point)
        calls = issue.outermost(coll)
        assert math.isclose(
            fw["mean_us"] + jit["mean_us"] + launch["mean_us"],
            sum(c.dur for c in calls) / 8 / 1e3)
        assert 0.85 < (fw["mean_us"] + jit["mean_us"] + launch["mean_us"]) \
            / fw["issue_us_per_call"] <= 1.0
    # the handle skips the slot's key and cache probe
    assert hs.split_point(run, "allreduce_init.sum.f32.8B", 8, coll,
                          child=pjit)["median_us"] < 0.5 * hs.split_point(
        run, "allreduce.sum.f32.1KiB", 8, coll, child=pjit)["median_us"]
    stack = "stack_reduce.prod.f32.4x4MiB"
    assert "holds 0 spans" in hs.split_point(run, stack, 8, coll, child=pjit)
    assert hs.split_point(run, stack, 8, pjrt)["calls"] == 8
    assert "holds 8 spans" in hs.split_point(run, stack, 4, pjrt)


def test_idle_of_the_recorded_trace_is_all_named(recorded):
    run = hs.Run(recorded)
    idle = run.idle_by_innermost()
    lo, hi = tr.window_of(recorded["host"])
    busy = tr.total(tr.clip(tr.merge(
        (s, s + d) for _, s, d in recorded["device"]["0"]), lo, hi))
    assert sum(ns for _, ns in idle) == tr.total(run.gaps)
    # the shift moves the device's timeline, not what it holds
    assert abs(tr.total(run.gaps) - ((hi - lo) - busy)) < 0.01 * (hi - lo)
    assert all(path for path, _ in idle)    # the round covers the window
    # the same total as tracered's coarser attribution of the same gaps
    reduced = tr.reduce_trace(recorded)
    assert math.isclose(sum(s for _, s in reduced["idle_gaps"]),
                        tr.total(run.gaps) / 1e9)
    by_name: dict = {}
    for path, ns in idle:
        by_name[path[-1]] = by_name.get(path[-1], 0) + ns
    # what was cut away between the kept calls and the sync reads as the
    # round's; of the rest the allocation of the output is the most
    rest = {n: ns for n, ns in by_name.items() if n != tr.ROUND}
    assert max(rest, key=rest.get) == "DeferredTpuAllocator::Allocate"
    fw = sum(ns for n, ns in by_name.items() if n.startswith("otpu."))
    launch = sum(ns for path, ns in idle
                 if any(n.startswith("PjitFunction(") for n in path))
    assert 0 < fw < 0.1 * launch


def test_the_recorded_steps_are_counted_from_their_launches():
    """Three steps of four buckets and three single calls of 64 MiB,
    recorded on one v5e (``fixtures/rank1_steps_v5e.json``, PR 32): the
    programs a call come from JAX's own launch events, the device's 15
    runs fall to the two windows as 12 and 3, and a part that is one a
    launch is read over 12 launches where one a call is refused."""
    events = json.load(open(os.path.join(os.path.dirname(FIXTURE),
                                         "rank1_steps_v5e.json"),
                            encoding="utf-8"))
    step, single = "pallreduce.sum.f32.4x25MiB", "allreduce.sum.f32.64MiB"
    assert events["calls"] == {step: 3, single: 3}
    assert len(events["launches"]) == 30        # 15, each written twice
    assert tr.window_programs(events) == [12, 3]
    run = hs.Run(events)
    assert run.programs == {step: 4, single: 1}
    reduced = tr.reduce_trace(events)
    assert reduced["points"][step]["calls"] == 3
    # a one-rank psum leaves one copy of the bucket a launch
    assert list(reduced["points"][step]["ops"]) == ["copy.1 f32[6553600]"]
    assert list(reduced["points"][single]["ops"]) == ["copy.1 f32[16777216]"]
    per_bucket = reduced["points"][step]["busy_s"] / 12
    assert 70e-6 < per_bucket < 90e-6           # 25 MiB in and out
    pjrt = re.compile(r"^PJRT_LoadedExecutable_Execute$")
    alloc = re.compile("DeferredTpuAllocator::Allocate")
    row = hs.split_point(run, step, 3, pjrt, per="launch")
    assert row["calls"] == 12 and 150 < row["median_us"] < 400
    part = hs.split_point(run, step, 3, pjrt, part=alloc, per="launch")
    assert 50 < part["median_us"] < row["median_us"]
    assert "holds 12 spans" in hs.split_point(run, step, 3, pjrt)
    # where a call is one launch the two ways agree
    assert hs.split_point(run, single, 3, pjrt, per="launch") == \
        hs.split_point(run, single, 3, pjrt)
    # the persistent handle writes its span once a bucket
    handle = hs.split_point(run, step, 3, re.compile(r"^otpu\.coll\."),
                            child=re.compile(r"^PjitFunction\(otpu_"),
                            per="launch")
    assert handle["calls"] == 12 and 1 < handle["median_us"] < 10


def test_load_host_lines_reads_every_name(tmp_path):
    """A profiler session on the CPU: the loader keeps every event of the
    Python thread's line, nested as the readers need them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.ones(4)
    f(x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("otpu.coll.test", coll="x"):
                jax.block_until_ready(f(x))
    finally:
        jax.profiler.stop_trace()
    lines = hs.load_host_lines(tr.find_xplane(str(tmp_path)))
    (main,) = [evs for evs in lines.values()
               if any(n == "bench.round" for n, _, _ in evs)]
    root, crossings = hs.nest(main)
    assert crossings == 0
    (call,) = root.outermost(re.compile(r"^otpu\.coll\."))
    assert call.outermost(re.compile(r"^PjitFunction\(")) != []
    assert 0 < call.self_ns() < call.dur
