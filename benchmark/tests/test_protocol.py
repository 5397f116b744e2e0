"""The window loop and a whole run at tiny sizes on four virtual CPU
devices, through ``run_cell``'s arguments.  Nothing here is a
measurement; the command itself still refuses anything but a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run
from harness import manifest as mf
from harness import protocol as pt


def _rehearse(root, cell, trace=False):
    return run.run_cell(cell, seed=3, seconds=0.3, trace=trace,
                        platform="cpu", root=root, min_window_s=0.002)


@pytest.mark.parametrize("cell,metrics", [
    ("osu-2x2-mix", {"small_msg_us", "allreduce_busbw", "coll_busbw",
                     "setup_s"}),
    ("rank1-mix", {"small_msg_us", "reduce_local_bw", "setup_s"}),
    ("rank1-blocking-xl", {"reduce_local_bw", "setup_s"}),
    ("rank1-partitioned", {"small_msg_us", "setup_s"}),
])
def test_rehearsal_of_every_cell(tiny_root, cell, metrics, capsys):
    result = _rehearse(tiny_root, cell)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == metrics
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"      # and says so
    out = capsys.readouterr().out
    rows = [json.loads(line[6:]) for line in out.splitlines()
            if line.startswith("point ")]
    facts = [json.loads(line[4:]) for line in out.splitlines()
             if line.startswith("run ")][0]
    points = mf.traffic_points(
        mf.by_name(mf.load(tiny_root)["workloads"], cell, "cell")["traffic"],
        os.path.join(tiny_root, "benchmark"))
    assert [r["name"] for r in rows] == [p["name"] for p in points]
    for r in rows:
        assert r["windows"] >= 1 and r["k"] & (r["k"] - 1) == 0
        assert r["pool"] >= 2
    # every timed call and every check is attempted; the program's own
    # count of device collectives equals the harness's
    timed = sum(r["windows"] * r["k"] for r in rows)
    assert result["attempted"] == timed + 2 * len(points)
    # each check prints the number it compared beside its limit
    checks = [ln for ln in out.splitlines() if ln.startswith("check ")]
    assert len(checks) == 2 * len(points)
    assert all(" 0 of " in ln and ln.endswith("limit 0") for ln in checks)
    # no trace, so no programs a call; the collectives a call always
    assert not any("programs_per_call" in r for r in rows)
    buckets = {p["name"]: p.get("buckets") for p in points}
    assert all(r["collectives_per_call"] == (buckets[r["name"]] or
                                             min(1, r["collectives_per_call"]))
               for r in rows)
    assert facts["hold"] == pt.HOLD
    saved = os.path.join(tiny_root, ".bench_out",
                         f"{cell}.seed3.trace0.json")
    assert json.load(open(saved))["result"] == result


def test_setup_s_stops_before_the_harness_checks(tiny_root, monkeypatch,
                                                 capsys):
    """``setup_s`` runs from the open device to the first call that could
    be timed: the harness's own check of every point against the
    reference comes after it, as ``check_s`` (PR 63).  A check that sleeps
    shows in ``check_s`` and in nothing of ``setup_s``; it still runs
    before the measured windows and still decides ``correct``."""
    import time

    naps, real_check = [], pt.check

    def slow_check(pr, rng):
        naps.append((time.perf_counter(), len(pr.windows)))
        time.sleep(0.4)
        return real_check(pr, rng)

    monkeypatch.setattr(pt, "check", slow_check)
    result = _rehearse(tiny_root, "rank1-partitioned")
    facts = [json.loads(line[4:])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("run ")][0]
    points = len(naps) // 2
    assert result["correct"] is True and points >= 2
    # the first round of checks saw no timed window, the second all
    assert all(w == 0 for _, w in naps[:points])
    assert all(w >= 1 for _, w in naps[points:])
    phases = facts["setup_phases"]
    assert facts["check_s"] == phases["check_s"] >= 0.4 * points
    made = (phases["program_import_s"] + facts["init_s"]
            + phases["inputs_s"] + phases["warm_s"])
    assert made <= facts["setup_s"] < made + 0.3
    assert result["metrics"]["setup_s"]["value"] == facts["setup_s"]


def test_a_build_metric_counts_to_the_end_of_set_up(monkeypatch):
    """The six ``compile.*`` metrics of the program's build record move
    ``setup_s``, so they read the counters as ``run.py`` marked them when
    set-up ended (``until: setup``): the reference programs that the
    harness's check builds after it are no part of them.  A metric
    without the key reads the counters as they stand."""
    from harness import counters
    from ompi_tpu.runtime import spc

    reader = pt.load_module("readers", "program_counter", mf.BENCH_DIR)
    for name in ("trace_s", "lower_s", "own_backend_s", "cache_hit_share",
                 "own_programs", "other_s"):
        assert mf.metric_spec("compile." + name)["params"]["until"] \
            == "setup"
    assert "until" not in mf.metric_spec("compile.first_call_s")["params"]
    other = mf.metric_spec("compile.other_s")["params"]
    live = {"device_other_build_us": 9e6}
    monkeypatch.setattr(spc, "counters", lambda: dict(live))
    monkeypatch.setattr(counters, "AT_SETUP", {})
    assert reader.read({}, other) == 9.0        # before the mark: live
    counters.mark_setup()
    live["device_other_build_us"] = 14e6        # the check built more
    assert reader.read({}, other) == 9.0
    assert reader.read({}, {k: v for k, v in other.items()
                            if k != "until"}) == 14.0


def test_the_checks_time_is_a_per_layer_metric_of_every_cell():
    """``harness.check_s`` keeps what ``setup_s`` held until PR 63: read
    by ``run_value`` from the run's own ``check_s``, listed without
    ``workloads`` (every cell), under a layer of the benchmark's own."""
    real = mf.load()
    (m,) = [x for x in real["per_layer"] if x["name"] == "harness.check_s"]
    assert m == {"name": "harness.check_s", "unit": "s", "better": "lower",
                 "source": "host_clock", "moves": "setup_s",
                 "layer": "harness (benchmark's own work)"}
    spec = mf.metric_spec("harness.check_s")
    assert (spec["reader"], spec["params"]) == ("run_value",
                                                {"key": "check_s"})
    reader = pt.load_module("readers", "run_value", mf.BENCH_DIR)
    assert reader.read({"run": {"check_s": 1.25}}, spec["params"]) == 1.25
    assert reader.read({"run": {}}, spec["params"]) is None
    for cell in real["workloads"]:
        assert m in mf.metrics_of(real, "per_layer", cell["name"])


@pytest.mark.parametrize("gone", ["moe." + "expert_share",
                                  "moe." + "local_expert_share"])
def test_a_metric_that_read_nothing_is_in_no_file(gone):
    """Both matched XLA's ``ragged-dot`` alone and have read nothing since
    the grouped matmuls run on a Pallas kernel (PR 47); ``moe.gmm_share``
    matches either and lists their cells."""
    real = mf.load()
    assert gone not in [m["name"] for m in real["per_layer"]]
    for folder, _, files in os.walk(mf.BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for fn in files:
            with open(os.path.join(folder, fn), encoding="utf-8",
                      errors="replace") as f:
                assert gone not in f.read(), os.path.join(folder, fn)
    gmm = mf.by_name(real["per_layer"], "moe.gmm_share", "metric")
    assert {"olmoe-train-1chip", "joyai-train-1chip"} <= set(
        gmm["workloads"])
    assert mf.metric_spec("moe.gmm_share")["params"]["pattern"] \
        == "^(otpu_gmm|ragged-dot)"


def test_a_wrong_result_is_counted(tiny_root, monkeypatch):
    kind = pt.load_module("kinds", "bcast",
                          os.path.join(tiny_root, "benchmark"))
    path = mf.code_file("kinds", "bcast",
                        os.path.join(tiny_root, "benchmark"))
    with open(path, "a", encoding="utf-8") as f:    # root 0, not n-1
        f.write("\n\ndef reference(point, n, x):\n"
                "    import numpy as np\n"
                "    return np.broadcast_to(x[0], x.shape).copy()\n")
    assert kind.reference({}, 4, np.eye(4, dtype=np.float32))[0, 3] == 1
    result = _rehearse(tiny_root, "rank1-mix")
    assert result["correct"] is False and result["failed"] == 2


def test_window_counts_and_holds(tiny_root):
    seen = []

    def call(x):
        seen.append(x)
        return np.float32(x)

    total, issue = pt.window(call, [1, 2, 3], 7)
    assert seen == [1, 2, 3, 1, 2, 3, 1] and 0 < issue <= total


def test_the_mean_counts_a_stall_the_median_hides():
    """``per_call_mean_us`` is all the windows' seconds over all the
    calls, as OSU reports total time over iterations; the median of the
    windows is blind to one slow window in five."""
    pr = pt.PointRun.__new__(pt.PointRun)
    pr.k = 4
    pr.windows = [(0.004, 0.001)] * 4 + [(0.024, 0.001)]
    assert pr.per_call_s() == pytest.approx(0.001)
    assert pr.mean_call_s() == pytest.approx(0.040 / 20)


def test_sample_positions_cover_the_edges():
    rng = np.random.default_rng(0)
    assert np.array_equal(pt.sample_positions(100, rng), np.arange(100))
    pos = pt.sample_positions(16 << 20, rng)
    assert pos[0] == 0 and pos[-1] == (16 << 20) - 1
    # one length whatever the seed: one shape, one compiled gather
    assert np.all(np.diff(pos) >= 0) and len(pos) == pt.SAMPLE
    assert len(pt.sample_positions(16 << 20, np.random.default_rng(1))) \
        == pt.SAMPLE
    assert set(range(pt.EDGE)) <= set(pos[:pt.EDGE].tolist())


def _snapshot(bench, only=None):
    """{path: content} of every file under ``bench`` (of the paths in
    ``only``, where given: files added since do not count)."""
    paths = only if only is not None else [
        os.path.join(dirpath, fn)
        for dirpath, _, files in os.walk(bench) for fn in files]
    return {p: open(p, "rb").read() for p in paths}


def test_a_new_cell_is_data_alone(tiny_root):
    """A later PR adds a cell, a traffic mix, a configuration and a
    per-point metric as new files and new entries, and edits no file."""
    bench = os.path.join(tiny_root, "benchmark")
    before = _snapshot(bench)

    def write(rel, obj):
        with open(os.path.join(bench, rel), "w", encoding="utf-8") as f:
            json.dump(obj, f)

    write("traffic/throwaway-set.json", {"why": "a test", "points": [
        {"name": "allreduce.sum.f32.2KiB", "kind": "allreduce",
         "dtype": "float32", "bytes": 2048, "op": "SUM"},
        {"name": "stack_reduce.bor.i32.4x1KiB", "kind": "stack_reduce",
         "dtype": "int32", "bytes": 1024, "op": "BOR", "rows": 4}]})
    write("configs/throwaway.json", {"name": "throwaway", "ranks": 4,
                                     "chips": 4, "source": "a test"})
    write("cells/throwaway-cell.json", {
        "pool_bytes_per_point": 8192, "pool_max": 4, "trace_rounds": 1})
    write("metrics/throwaway.allreduce_2KiB_us.json", {
        "reader": "point_geomean", "params": {
            "field": "per_call_us",
            "select": {"name": "allreduce.sum.f32.2KiB"}}})
    path = os.path.join(tiny_root, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["configs"].append({
        "name": "throwaway", "source": "a test",
        "file": "benchmark/configs/throwaway.json", "reduced": [],
        "why": "a test"})
    manifest["workloads"].append({
        "name": "throwaway-cell", "config": "throwaway",
        "traffic": "throwaway-set", "chips": 4, "why": "a test"})
    manifest["end_to_end"].append({
        "name": "throwaway.allreduce_2KiB_us", "unit": "us",
        "better": "lower", "bound": 0.05, "source": "host_clock",
        "workloads": ["throwaway-cell"]})
    json.dump(manifest, open(path, "w"))
    assert mf.validate_harness(manifest, tiny_root) == []

    result = _rehearse(tiny_root, "throwaway-cell")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"throwaway.allreduce_2KiB_us",
                                      "setup_s"}
    assert _snapshot(bench, only=before) == before, "a file was edited"


def test_tagged_points_in_a_new_file_reach_an_old_metric(tiny_root, capsys):
    """What PR 26 made room for: a later PR's cell is a traffic file of
    tagged points, a cell file and its name appended to a ``workloads``
    list; it reports ``small_msg_us`` from its own points, and no file
    of ``benchmark/`` is edited."""
    from harness import stats

    bench = os.path.join(tiny_root, "benchmark")
    before = _snapshot(bench)
    tagged = ["allreduce.sum.f32.2KiB", "bcast.f32.512B",
              "blocking.allgather.f32.1KiB"]
    with open(os.path.join(bench, "traffic", "throwaway-set.json"), "w",
              encoding="utf-8") as f:
        json.dump({"why": "a test", "points": [
            {"name": tagged[0], "kind": "allreduce", "dtype": "float32",
             "bytes": 2048, "op": "SUM", "e2e": "small_msg_us"},
            {"name": tagged[1], "kind": "bcast", "dtype": "float32",
             "bytes": 512, "e2e": "small_msg_us"},
            # a wrapped kind runs through the whole of a run too
            {"name": tagged[2], "kind": "blocking", "inner": "allgather",
             "dtype": "float32", "bytes": 1024, "e2e": "small_msg_us"},
            {"name": "stack_reduce.bor.i32.4x1KiB", "kind": "stack_reduce",
             "dtype": "int32", "bytes": 1024, "op": "BOR", "rows": 4}]}, f)
    with open(os.path.join(bench, "cells", "throwaway-cell.json"), "w",
              encoding="utf-8") as f:
        json.dump({"pool_bytes_per_point": 8192, "pool_max": 4,
                   "trace_rounds": 1}, f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["workloads"].append({
        "name": "throwaway-cell", "config": "rank-local-1chip",
        "traffic": "throwaway-set", "chips": 4, "why": "a test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("small_msg_us", "dispatch.fw_self_us"):
            m["workloads"].append("throwaway-cell")
    json.dump(manifest, open(path, "w"))
    assert mf.validate_harness(manifest, tiny_root) == []
    assert mf.points_by_metric(
        manifest, "throwaway-cell",
        mf.traffic_points("throwaway-set", bench), bench) == {
            "small_msg_us": tagged, "dispatch.fw_self_us": tagged}

    result = _rehearse(tiny_root, "throwaway-cell")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"small_msg_us", "setup_s"}
    rows = {r["name"]: r for r in (
        json.loads(line[6:]) for line in capsys.readouterr().out.splitlines()
        if line.startswith("point "))}
    assert len(rows) == 4 and rows["stack_reduce.bor.i32.4x1KiB"][
        "e2e"] is None
    assert result["metrics"]["small_msg_us"]["value"] == pytest.approx(
        stats.geomean(rows[n]["per_call_us"] for n in tagged))
    assert _snapshot(bench, only=before) == before, "a file was edited"


THROWAWAY_KIND = '''
"""A step of PARTS programs over a set of arrays, for the tests: the set
is cut out of one generated array in set-up, the call adds 1 to each
part in a program of its own and issues one collective a part."""
import numpy as np
from harness import collkit

ELEMENTWISE_LAST_AXIS = True
{tolerance}
seen = {{"prepared": 0}}


def input_shape(point, n):
    return (n, point["parts"], collkit.elems(point, point["parts"]))


def input_sharding(env):
    return env.rank_sharding


def collectives_per_call(point):
    return point["parts"]


def prepare(env, point, x):
    seen["prepared"] += 1
    return [x[:, i] for i in range(point["parts"])]


def inputs_of(parts):
    return parts


def bind(env, point, parts):
    assert isinstance(parts, list) and len(parts) == point["parts"]
    return (lambda parts: [env.world.allreduce_array(p) + {offset}
                           for p in parts]), 0


def reference(point, n, xs):
    return [x.sum(axis=0, dtype=x.dtype) + np.float32(1) for x in xs]


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return 0
'''


def _throwaway_step_cell(root, tolerance="", offset="1"):
    """A cell of one tagged point of a throw-away step kind, and one old
    kind's point without a tag, added as new files and entries."""
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "kinds", "throwaway_step.py"), "w",
              encoding="utf-8") as f:
        f.write(THROWAWAY_KIND.format(tolerance=tolerance, offset=offset))
    with open(os.path.join(bench, "traffic", "throwaway-steps.json"), "w",
              encoding="utf-8") as f:
        json.dump({"why": "a test", "points": [
            {"name": "step.f32.3x256B", "kind": "throwaway_step",
             "dtype": "float32", "bytes": 768, "parts": 3,
             "e2e": "small_msg_us"},
            {"name": "allreduce.sum.f32.2KiB", "kind": "allreduce",
             "dtype": "float32", "bytes": 2048, "op": "SUM"}]}, f)
    with open(os.path.join(bench, "cells", "throwaway-cell.json"), "w",
              encoding="utf-8") as f:
        json.dump({"pool_bytes_per_point": 4096, "pool_max": 4,
                   "trace_rounds": 1}, f)
    path = os.path.join(root, "BENCHMARK.json")
    manifest = json.load(open(path))
    manifest["workloads"].append({
        "name": "throwaway-cell", "config": "dp-grad-buckets-1chip",
        "traffic": "throwaway-steps", "chips": 4, "why": "a test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("small_msg_us", "part.step_mean_us"):
            m["workloads"].append("throwaway-cell")
    json.dump(manifest, open(path, "w"))
    return manifest


def test_a_kind_may_prepare_its_inputs_and_count_its_collectives(
        tiny_root, capsys):
    """The three optional hooks through a throw-away kind, and what PR 32
    made room for: a later PR's step kind reports an end-to-end metric
    from its own tagged point, with new files and appended entries
    alone."""
    bench = os.path.join(tiny_root, "benchmark")
    before = _snapshot(bench)
    manifest = _throwaway_step_cell(tiny_root)
    assert mf.validate_harness(manifest, tiny_root) == []
    assert mf.points_by_metric(
        manifest, "throwaway-cell",
        mf.traffic_points("throwaway-steps", bench), bench)[
            "part.step_mean_us"] == ["step.f32.3x256B"]
    result = _rehearse(tiny_root, "throwaway-cell")
    # correct holds SPC device_collectives to 3 a step, 1 a plain call
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"small_msg_us", "setup_s"}
    out = capsys.readouterr().out
    rows = {r["name"]: r for r in (json.loads(line[6:])
                                   for line in out.splitlines()
                                   if line.startswith("point "))}
    step = rows["step.f32.3x256B"]
    assert step["collectives_per_call"] == 3
    assert rows["allreduce.sum.f32.2KiB"]["collectives_per_call"] == 1
    assert result["metrics"]["small_msg_us"]["value"] == pytest.approx(
        step["per_call_us"])
    # the pool is sized by the generated array (4096 // 768 = 5, at most
    # pool_max) and every entry went through prepare once
    assert step["pool"] == 4
    assert "check step.f32.3x256B: 0 of 192 positions differ; limit 0" \
        in out
    assert _snapshot(bench, only=before) == before, "a file was edited"


@pytest.mark.parametrize("offset,correct", [
    ("1.004", True),            # inside rtol 1e-2 of values from 1 up
    ("1.5", False),             # outside it
])
def test_a_kind_may_state_how_its_result_is_compared(tiny_root, capsys,
                                                     offset, correct):
    tolerance = ('TOLERANCE = {"rtol": 1e-2, "atol": 5e-3, '
                 '"why": "a test: a float matmul is not bit-exact"}')
    manifest = _throwaway_step_cell(tiny_root, tolerance, offset)
    assert mf.validate_harness(manifest, tiny_root) == []
    result = _rehearse(tiny_root, "throwaway-cell")
    assert result["correct"] is correct
    assert result["failed"] == (0 if correct else 2)
    out = capsys.readouterr().out
    rows = {r["name"]: r for r in (json.loads(line[6:])
                                   for line in out.splitlines()
                                   if line.startswith("point "))}
    assert rows["step.f32.3x256B"]["tolerance"]["rtol"] == 1e-2
    assert "tolerance" not in rows["allreduce.sum.f32.2KiB"]
    assert "positions lie outside rtol 0.01 atol 0.005; limit 0" in out
    saved = json.load(open(os.path.join(
        tiny_root, ".bench_out", "throwaway-cell.seed3.trace0.json")))
    assert saved["points"][0]["tolerance"]["why"].startswith("a test")
    # the same kind without a tolerance is held to every bit
    if correct:
        _throwaway_step_cell_again = THROWAWAY_KIND.format(
            tolerance="", offset=offset)
        with open(os.path.join(tiny_root, "benchmark", "kinds",
                               "throwaway_step.py"), "w") as f:
            f.write(_throwaway_step_cell_again)
        assert _rehearse(tiny_root, "throwaway-cell")["correct"] is False


def test_mismatches_bit_for_bit_and_within_a_tolerance():
    a = np.array([1.0, 2.0, 4.0], np.float32)
    assert pt.mismatches(a, a.copy(), None) == 0
    assert pt.mismatches(a, a + np.float32(1e-7), None) == 1    # an ulp at 1
    tol = {"rtol": 1e-3, "atol": 0.0, "why": "x"}
    assert pt.mismatches(a, a * np.float32(1.0005), tol) == 0
    assert pt.mismatches(a, a * np.float32(1.002), tol) == 3
    # another dtype or shape fails at every position, tolerance or not
    assert pt.mismatches(a, a.astype(np.float64), tol) == 3
    assert pt.mismatches(a, a[:2], None) == 3


def test_a_released_bucket_cannot_be_released_twice(tiny_root):
    """The configuration's guarantees beside the sum: a bucket released
    twice in an epoch raises, and so does a Pready before start()."""
    import ompi_tpu
    from ompi_tpu.api.errors import MpiError

    world = ompi_tpu.init()
    try:
        env = pt.Env(world, __import__("jax").devices())
        point = {"name": "p", "kind": "pallreduce", "dtype": "float32",
                 "bytes": 3 * 64, "buckets": 3, "op": "SUM"}
        pr = pt.PointRun(env, point, pt.load_kind(
            point, os.path.join(tiny_root, "benchmark")), 5, 4096, 4, False)
        assert pr.collectives_per_call == 3 and pr.bind_collectives == 3
        assert len(pr.pool) == 4 and all(len(b) == 3 for b in pr.pool)
        assert pr.chip_bytes == 3 * 64          # the generated array's
        out = pr.call(pr.pool[1])
        want = pr.kind.reference(point, env.n,
                                 [np.asarray(b) for b in pr.pool[1]])
        assert all(np.array_equal(w, np.asarray(g))
                   for w, g in zip(want, out))
        assert pt.check(pr, np.random.default_rng(0)) is True
        req = world.pallreduce_init(pr.pool[0])
        with pytest.raises(MpiError, match="inactive"):
            req.pready(0)
        req.start(pr.pool[0])
        req.pready(2)
        with pytest.raises(MpiError, match="already released"):
            req.pready(2)
        req.pready(1)
        req.pready(0)
        req.wait()
        assert len(req.result) == 3
    finally:
        ompi_tpu.finalize()


def test_a_blocking_call_is_ready_when_it_returns(tiny_root, monkeypatch):
    """Kind ``blocking`` wraps the kind a point names and closes every
    call by its own sync: what comes back has already been computed."""
    bench = os.path.join(tiny_root, "benchmark")
    point = {"name": "b", "kind": "blocking", "inner": "allgather",
             "dtype": "float32", "bytes": 1024}
    kind = pt.load_kind(point, bench)
    assert kind.input_shape(point, 4) == (4, 64)
    assert kind.input_sharding.__module__ == "bench_kinds_allgather"
    assert kind.COLLECTIVES_PER_CALL == 1 and not hasattr(kind, "bind_raw")
    x = np.arange(8, dtype=np.float32).reshape(4, 2)
    assert np.array_equal(kind.reference(point, 4, x), x)
    synced = []

    class World:
        def allgather_array(self, x):
            return ("out", x)

    class Env:
        world = World()

    import jax

    monkeypatch.setattr(jax, "block_until_ready", synced.append)
    call, spent = kind.bind(Env, point, None)
    assert spent == 0 and call(7) == ("out", 7)
    assert synced == [("out", 7)]
    # any kind can be wrapped; everything but the call stays its own
    stack = pt.load_kind({**point, "inner": "stack_reduce"}, bench)
    assert stack.COLLECTIVES_PER_CALL == 0
    assert pt.load_kind({"kind": "bcast"}, bench).bind is not kind.bind


def test_the_command_refuses_a_cpu():
    """Off a TPU the command exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"),
         "--workload", "rank1-mix", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "needs 1 tpu device" in proc.stderr
    assert proc.stdout.strip() == ""


def test_the_command_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    root = str(tmp_path)
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.REPO_ROOT, "BENCHMARK.json"), root)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rank1-mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "is not in this directory" in proc.stderr
    assert proc.stdout.strip() == ""


def test_a_compile_inside_the_measured_time_voids_the_run(tiny_root,
                                                          monkeypatch):
    import jax
    import jax.numpy as jnp

    real = pt.measure

    def measure_and_compile(points, *args, **kwargs):
        real(points, *args, **kwargs)
        jax.jit(lambda x: jnp.sin(x) * 3.25)(jnp.ones(7)).block_until_ready()

    monkeypatch.setattr(pt, "measure", measure_and_compile)
    with pytest.raises(SystemExit, match="built inside the measured time"):
        _rehearse(tiny_root, "rank1-mix")
