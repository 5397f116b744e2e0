"""The trace reduction: against hand-made events with hand-worked
answers, and against one small trace recorded on the chip
(``fixtures/rank1_small_v5e.json``, cut from a ``--trace 1`` run of the
``small-set`` points on one chip by ``tools/describe_trace.py --events``)."""
import json
import math
import os

import pytest

from harness import tracered as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "rank1_small_v5e.json")


def test_merge_clip_total_gaps():
    busy = tr.merge([(10, 20), (15, 30), (40, 50), (42, 45), (50, 55)])
    assert busy == [[10, 30], [40, 55]]           # nested ops count once
    assert tr.total(busy) == 35
    assert tr.clip(busy, 25, 45) == [[25, 30], [40, 45]]
    assert tr.gaps_of(busy, 0, 60) == [[0, 10], [30, 40], [55, 60]]


# one round, two windows on two devices.  Times in ns.
#   window a: issue 100..300, sync 300..500; ops at 150-250 and 300-400
#   window b: issue 600..700, sync 700..1000; one op 650-950 on dev 0,
#   700-900 on dev 1
HAND = {
    "calls": {"a": 2, "b": 1},
    "modules": {
        "0": [["jit_a(1)", 150, 100], ["jit_a(1)", 300, 100],
              ["jit_b(2)", 650, 300]],
        "1": [["jit_a(1)", 150, 100], ["jit_a(1)", 300, 100],
              ["jit_b(2)", 700, 200]],
    },
    "host": [["bench.round", 0, 1000],
             ["bench.issue.a", 100, 200], ["bench.sync", 300, 200],
             ["bench.issue.b", 600, 100], ["bench.sync", 700, 300]],
    "device": {
        "0": [["fusion.1", 150, 100], ["all-reduce.2", 300, 100],
              ["all-reduce.2", 650, 300]],
        "1": [["fusion.1", 150, 100], ["all-reduce.2", 300, 100],
              ["all-reduce.2", 700, 200]],
    },
}


def test_reduction_by_hand():
    r = tr.reduce_trace(HAND)
    assert r["devices"] == 2
    assert math.isclose(r["window_s"], 1000e-9)
    # busy: dev 0 100+100+300 = 500, dev 1 100+100+200 = 400; mean 450
    assert math.isclose(r["busy_s"], 450e-9)
    assert math.isclose(100 * (1 - r["busy_s"] / r["window_s"]), 55.0)
    ops = dict(r["device_ops"])
    assert math.isclose(ops["all-reduce.2"], (400 + 300) / 2 * 1e-9)
    assert math.isclose(ops["fusion.1"], 100e-9)
    assert r["device_ops"][0][0] == "all-reduce.2"      # most time first
    a, b = r["points"]["a"], r["points"]["b"]
    assert a["windows"] == 1 and b["windows"] == 1
    assert a["calls"] == 2 and b["calls"] == 1
    assert math.isclose(a["busy_s"], 200e-9)
    assert math.isclose(b["busy_s"], 250e-9)
    assert math.isclose(a["ops"]["fusion.1"], 100e-9)
    assert math.isclose(b["ops"]["all-reduce.2"], 250e-9)
    # idle gaps of device 0: 0-150, 250-300, 400-650, 950-1000
    gaps = dict(r["idle_gaps"])
    assert math.isclose(gaps["bench.issue.a"], (50 + 50) * 1e-9)
    assert math.isclose(gaps["bench.sync"], (100 + 50) * 1e-9)
    assert math.isclose(gaps["bench.issue.b"], 50e-9)
    assert math.isclose(gaps["bench.round"], (100 + 100 + 0) * 1e-9)
    assert math.isclose(sum(gaps.values()), 500e-9)     # all idle named


def test_points_are_told_apart_by_count_not_by_clock():
    """A device timeline that runs ahead of the host's (about 1 ms on the
    v5e) must not move a window's calls into its neighbour."""
    early = {**HAND,
             "device": {d: [[n, s - 120, dur] for n, s, dur in ops]
                        for d, ops in HAND["device"].items()},
             "modules": {d: [[n, s - 120, dur] for n, s, dur in ops]
                         for d, ops in HAND["modules"].items()}}
    r, want = tr.reduce_trace(early), tr.reduce_trace(HAND)
    for point in ("a", "b"):
        assert r["points"][point]["busy_s"] == want["points"][point][
            "busy_s"]
        assert r["points"][point]["ops"] == want["points"][point]["ops"]
    # device 0 is shifted by 70 ns, so that a's first run (150 - 120)
    # starts when the host began to issue it (100): busy stays 500 ns
    assert math.isclose(r["busy_s"], 450e-9)


def test_a_trace_the_harness_cannot_account_for_is_an_error():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce_trace({**HAND, "device": {}})
    with pytest.raises(ValueError, match="bench.round"):
        tr.reduce_trace({**HAND, "host": []})
    with pytest.raises(ValueError, match="3 program runs .* launched 4"):
        tr.reduce_trace({**HAND, "calls": {"a": 2, "b": 2}})


def test_readers_on_the_hand_trace():
    from harness import manifest as mf
    from harness import protocol as pt

    reduced = tr.reduce_trace(HAND)
    rows = [{"name": "a", "set": "s", "bus_bytes": 1000.0,
             "moved_bytes": 819.0},
            {"name": "b", "set": "s", "bus_bytes": 4000.0,
             "moved_bytes": 819.0}]
    ctx = {"points": rows, "trace": reduced, "device_kind": "TPU v5 lite"}
    rate = pt.load_module("readers", "trace_rate", mf.BENCH_DIR)
    idle = pt.load_module("readers", "trace_idle", mf.BENCH_DIR)
    # a: 2 calls x 1000 B / 200 ns = 10 GB/s; b: 4000 B / 250 ns = 16
    got = rate.read(ctx, {"bytes_field": "bus_bytes",
                          "select": {"set": "s"}})
    assert math.isclose(got, math.sqrt(10 * 16))
    # one point alone: a, 2 calls x 1000 B / 200 ns
    got = rate.read(ctx, {"bytes_field": "bus_bytes",
                          "select": {"name": "a"}})
    assert math.isclose(got, 10.0)
    # roofline share: 819 B / 250 ns = 3.276 GB/s of 819 GB/s = 0.4 %
    got = rate.read(ctx, {"bytes_field": "moved_bytes",
                          "select": {"name": "b"},
                          "percent_of_peak": "hbm_bytes_per_s"})
    assert math.isclose(got, 0.4)
    assert math.isclose(idle.read(ctx, {}), 55.0)
    # nothing to read: nothing returned
    assert rate.read({**ctx, "trace": None}, {"bytes_field": "bus_bytes"}) \
        is None
    assert rate.read(ctx, {"bytes_field": "bus_bytes",
                           "select": {"name": "zzz"}}) is None
    assert idle.read({"trace": None}, {}) is None


def test_reduction_of_the_recorded_trace():
    """Three windows of 256 calls each on one v5e chip: the ops do not
    overlap there, so plain sums are an independent check of the union,
    the per-point attribution and the gap arithmetic."""
    events = json.load(open(FIXTURE, encoding="utf-8"))
    r = tr.reduce_trace(events)
    ops = events["device"]["0"]
    assert r["devices"] == 1 and len(ops) == 768
    lo, hi = tr.window_of(events["host"])
    assert math.isclose(r["window_s"], (hi - lo) / 1e9)
    assert math.isclose(r["busy_s"], sum(d for _, _, d in ops) / 1e9)
    idle = 100 * (1 - r["busy_s"] / r["window_s"])
    assert 99.0 < idle < 100.0          # the host is the whole of it
    assert list(r["points"]) == ["allreduce.sum.f32.1KiB",
                                 "reduce_scatter.sum.f32.4KiB",
                                 "allgather.f32.1KiB"]
    # the device's timeline runs about 1 ms ahead of the host's here
    host_windows = tr.windows_of(events["host"])
    dev_windows = tr.device_windows(host_windows, events["modules"]["0"],
                                    tr.window_programs(events))
    for (_, hs, _), (_, ds, _) in zip(host_windows, dev_windows):
        assert 0.9e6 < hs - ds < 1.2e6
    for i, (point, _, _) in enumerate(host_windows):
        inside = [(n, d) for n, s, d in ops[256 * i:256 * (i + 1)]]
        p = r["points"][point]
        assert p["windows"] == 1 and p["calls"] == 256
        assert math.isclose(p["busy_s"], sum(d for _, d in inside) / 1e9)
        assert len(p["ops"]) == 1       # one program, one op: a copy
        assert next(iter(p["ops"])).startswith("copy.1 f32[")
    # kernel time by name: XLA's names, shortened to name and result
    by_name = dict(r["device_ops"])
    assert set(by_name) == {"copy.1 f32[1,1024]", "copy.1 f32[1,256]",
                            "copy.1 f32[256]"}
    for name, seconds in by_name.items():
        assert math.isclose(
            seconds, sum(d for n, _, d in ops if n == name) / 1e9)
    # every idle nanosecond is named, nearly all by an issue loop
    gaps = dict(r["idle_gaps"])
    assert math.isclose(sum(gaps.values()), r["window_s"] - r["busy_s"])
    assert set(gaps) <= {e[0] for e in events["host"]}
    issue = sum(s for n, s in gaps.items() if n.startswith(tr.ISSUE))
    assert issue / sum(gaps.values()) > 0.98


# A call may be a step (PR 32).  One round, three windows on one device:
#   window s (k = 3 steps of 4 launches): issue 100..1300, sync to 1400
#   window b (k = 2 calls of 1 launch):   issue 1500..1700, sync to 1800
#   window s again:                       issue 1900..3100, sync to 3200
# Every launch is written twice, one inside the other, as JAX writes it;
# one launch lies outside every issue span (the harness's own, between
# two windows) and has no run in the trace's window.  Each run on the
# device lasts 50 ns and starts 20 ns after its launch.
def _steps(k, per, t0, gap=100):
    return [["PjitFunction(otpu_x)", t0 + gap * i, 60]
            for i in range(k * per)]


def _step_trace():
    launches = _steps(3, 4, 100) + _steps(2, 1, 1500) + _steps(3, 4, 1900)
    nested = [[n, s + 1, d - 2] for n, s, d in launches]
    runs = [["jit_x(1)", s + 20, 50] for _, s, _ in launches]
    return {
        "calls": {"s": 3, "b": 2},
        "host": [["bench.round", 0, 3300],
                 ["bench.issue.s", 100, 1200], ["bench.sync", 1300, 100],
                 ["bench.issue.b", 1500, 200], ["bench.sync", 1700, 100],
                 ["bench.issue.s", 1900, 1200], ["bench.sync", 3100, 100]],
        "launches": launches + nested + [["PjitFunction(mine)", 1450, 10]],
        "modules": {"0": runs},
        "device": {"0": [["all-reduce.1", s, d] for _, s, d in runs]},
    }


def test_a_windows_program_runs_are_counted_not_assumed():
    events = _step_trace()
    assert tr.window_programs(events) == [12, 2, 12]
    assert tr.programs_per_call(events) == {"s": 4, "b": 1}
    r = tr.reduce_trace(events)
    s, b = r["points"]["s"], r["points"]["b"]
    assert (s["windows"], s["calls"]) == (2, 6)         # calls, not launches
    assert (b["windows"], b["calls"]) == (1, 2)
    assert math.isclose(s["busy_s"], 24 * 50e-9)        # 24 runs are s's
    assert math.isclose(b["busy_s"], 2 * 50e-9)
    assert math.isclose(r["busy_s"], 26 * 50e-9)
    host_windows = tr.windows_of(events["host"])
    dev = tr.device_windows(host_windows, events["modules"]["0"],
                            tr.window_programs(events))
    assert [(p, lo) for p, lo, _ in dev] == [("s", 120), ("b", 1520),
                                             ("s", 1920)]
    assert dev[0][2] == 100 + 11 * 100 + 20 + 50        # its twelfth run


def test_a_run_too_many_on_the_device_is_refused_with_the_count():
    events = _step_trace()
    events["modules"]["0"].append(["jit_x(1)", 3150, 10])
    with pytest.raises(ValueError, match="27 program runs .* launched 26 "
                                         "programs in 3 issue spans"):
        tr.reduce_trace(events)
    # and one too few: a launch the device never ran
    events = _step_trace()
    del events["modules"]["0"][-1]
    with pytest.raises(ValueError, match="25 program runs .* launched 26"):
        tr.reduce_trace(events)


def test_launches_that_are_no_multiple_of_k_are_refused():
    events = _step_trace()
    events["launches"].append(["PjitFunction(otpu_x)", 1270, 20])
    events["modules"]["0"].append(["jit_x(1)", 1280, 10])
    events["modules"]["0"].sort(key=lambda e: e[1])
    with pytest.raises(ValueError, match=r"window 0 \(s\): 13 launches "
                                         ".* multiple of its k = 3"):
        tr.reduce_trace(events)
    # no launch event at all (JAX renamed it): every window is refused
    with pytest.raises(ValueError, match="0 launches"):
        tr.reduce_trace({**_step_trace(), "launches": []})
    # a point whose windows disagree: 4 programs a call, then 3
    events = _step_trace()
    events["launches"] = [e for e in events["launches"]
                          if not 2800 <= e[1] < 3100]
    with pytest.raises(ValueError, match="4 programs a call, another 3"):
        tr.programs_per_call(events)


def test_the_recorded_traces_reduce_as_before_pr_32():
    """The two fixtures were recorded when a call was one program, and
    hold no ``launches``: their reduction is byte for byte what the
    reader of PR 31's tree made of them (the digests are of its
    ``json.dumps(reduce_trace(events))``).  The second holds the issuing
    thread's lines: with the launch events taken from them, the count is
    observed, and is one program a call at every point."""
    import hashlib

    was = {"rank1_small_v5e.json": "4d79e66bc7f39160cba22f58485b1032511da9"
                                   "14392a1cf6f8df5d777b9e94e5",
           "rank1_hostlines_v5e.json": "f5ee443155ca64fa76d9b8b16719ebd873"
                                       "dd3a157549810af73baffce6abd606"}
    for name, digest in was.items():
        events = json.load(open(os.path.join(os.path.dirname(FIXTURE), name),
                                encoding="utf-8"))
        assert "launches" not in events
        assert tr.programs_per_call(events) == dict.fromkeys(
            events["calls"], 1)
        text = json.dumps(tr.reduce_trace(events))
        assert hashlib.sha256(text.encode()).hexdigest() == digest, name
    main = next(evs for evs in events["host_lines"].values()
                if any(n == tr.ROUND for n, _, _ in evs))
    events["launches"] = [e for e in main if tr.LAUNCH_RE.search(e[0])]
    assert len(events["launches"]) > 24         # written twice, nested
    assert tr.window_programs(events) == [8, 8, 8]
    assert tr.programs_per_call(events) == dict.fromkeys(events["calls"], 1)
    assert hashlib.sha256(json.dumps(tr.reduce_trace(events)).encode()
                          ).hexdigest() == digest


def test_short_op_names():
    assert tr.short_op("%copy.1 = f32[2]{0:T(128)} copy(f32[2]{0:T(128)} "
                       "%bitcast.1)") == "copy.1 f32[2]"
    assert tr.short_op("%all-reduce.1 = f32[16777216]{0:T(1024)} "
                       "all-reduce(%x)") == "all-reduce.1 f32[16777216]"
    assert tr.short_op("%fusion = (f32[4,8]{1,0}, s32[]) fusion(%a)") \
        == "fusion f32[4,8]"
    assert tr.short_op("no hlo here") == "no hlo here"
