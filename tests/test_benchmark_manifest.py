"""The benchmark's manifest inside the tier-1 run: ``BENCHMARK.json`` and
every file it names pass the rules that code can check
(``benchmark/harness/manifest.validate`` and ``validate_harness``: what
``benchmark/check_manifest.py`` runs by hand), and the cell ``rank1-ddt``
is rehearsed at tiny sizes on the CPU devices through ``run_cell``, in a
copied tree and a process of its own (``run_cell`` boots and finalizes the
program, freezes the collector and sets JAX's cache options, none of which
a test worker should keep).  Nothing here is a measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "rank1-ddt"
DEVICES = 8

# a point's own parameters, cut to a rehearsal; bytes follow from them
TINY = {"grid": {514: 18, 130: 10}, "n": {4096: 16, 8192: 64},
        "atoms": {33554432: 4096}, "sent": {4194304: 512}}

REHEARSAL = """
import json, sys
sys.path[:0] = [{bench!r}, {repo!r}]
import run
result = run.run_cell({cell!r}, seed=2147483999, seconds=0.3, trace=False,
                      platform="cpu", root={root!r}, min_window_s=0.002)
from ompi_tpu.runtime import spc
print("counters " + json.dumps({{k: v for k, v in spc.counters().items()
                                 if k.startswith("device_ddt_")}}))
print("result " + json.dumps(result))
"""


@pytest.fixture(scope="module")
def mf():
    sys.path.insert(0, BENCH)
    try:
        from harness import manifest
        yield manifest
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def real(mf):
    return mf.load(REPO)


def test_the_real_manifest_passes_every_rule(mf, real):
    raw = os.path.getsize(os.path.join(REPO, "BENCHMARK.json"))
    assert mf.validate(real, REPO, raw_bytes=raw) == []


def test_every_name_resolves_to_its_file(mf, real):
    assert mf.validate_harness(real, REPO) == []


def test_a_broken_manifest_is_refused(mf, real):
    broken = json.loads(json.dumps(real))
    broken["workloads"][-1]["why"] = "x" * 201
    assert any("why" in e for e in mf.validate(broken, REPO))
    broken = json.loads(json.dumps(real))
    broken["end_to_end"][0]["workloads"].append("no-such-cell")
    assert mf.validate(broken, REPO)


def test_rank1_ddt_is_one_chip_under_the_two_one_chip_metrics(mf, real):
    cell = mf.by_name(real["workloads"], CELL, "workload")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "ddt-device-1chip", "ddt-face-transpose-mix")
    assert [m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)] \
        == ["small_msg_us", "reduce_local_bw", "setup_s"]
    points = mf.traffic_points(cell["traffic"], BENCH)
    chosen = mf.points_by_metric(real, CELL, points, BENCH)
    assert len(points) == 13
    assert len(chosen["small_msg_us"]) == 7
    assert len(chosen["reduce_local_bw"]) == 6
    assert chosen["ddt.roofline"] == chosen["reduce_local_bw"]
    assert mf.raw_points(real, CELL, points, BENCH) \
        == set(chosen["ddt.vs_manual"])


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One run of the cell at tiny sizes on ``DEVICES`` CPU devices, in a
    copy of the benchmark and a process of its own."""
    root = str(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))

    def edit(path, fn):
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
        fn(obj)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(obj, f)

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    edit(os.path.join(root, "BENCHMARK.json"), lambda m: [
        w.update(chips=DEVICES) for w in m["workloads"]])
    edit(os.path.join(bench, "configs", "ddt-device-1chip.json"),
         lambda c: c.update(ranks=DEVICES, chips=DEVICES))
    edit(os.path.join(bench, "cells", CELL + ".json"),
         lambda c: c.update(pool_bytes_per_point=64 << 10))

    def cut(mix):
        for p in mix["points"]:
            for key, small in TINY.items():
                if key in p:
                    p[key] = small[p[key]]
            p["bytes"] = 4 * ((p["grid"] - 2) ** 2 if "grid" in p else
                              2 * p["n"] ** 2 if "n" in p else 3 * p["sent"])
    edit(os.path.join(bench, "traffic", "ddt-face-transpose-mix.json"), cut)

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={DEVICES}"))
    done = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(
            bench=BENCH, repo=REPO, cell=CELL, root=root)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()

    def tagged(tag):
        return [json.loads(ln[len(tag) + 1:]) for ln in lines
                if ln.startswith(tag + " ")]
    return {"points": {p["name"]: p for p in tagged("point")},
            "run": tagged("run")[0], "counters": tagged("counters")[0],
            "result": tagged("result")[0]}


def test_the_rehearsal_is_correct_at_every_point(rehearsal):
    result = rehearsal["result"]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 13 * 2
    assert result["device"]["count"] == DEVICES
    assert len(rehearsal["points"]) == 13


def test_the_rehearsal_reports_the_cells_end_to_end_metrics(rehearsal):
    metrics = rehearsal["result"]["metrics"]
    assert set(metrics) == {"small_msg_us", "reduce_local_bw", "setup_s"}
    assert all(m["value"] > 0 for m in metrics.values())


def test_moved_bytes_is_twice_the_packed_size(rehearsal):
    for row in rehearsal["points"].values():
        assert row["moved_bytes"] == 2 * row["bytes"], row["name"]
        assert "bus_bytes" not in row


def test_plans_and_programs_are_built_in_set_up(rehearsal):
    """One plan a datatype object and count: every point commits its own
    datatype, so 13 (equal regular maps share the plan object, not the
    build); one of them an index list.  The two typed slots are the only
    collectives."""
    assert rehearsal["counters"]["device_ddt_plan_builds"] == 13
    assert rehearsal["counters"]["device_ddt_index_plans"] == 1
    assert rehearsal["counters"]["device_ddt_packs"] > 0
    assert rehearsal["counters"]["device_ddt_unpacks"] > 0
    calls = sum(p["k"] * p["windows"] for p in rehearsal["points"].values()
                if p["kind"] == "ddt_to_self")
    assert rehearsal["run"]["spc_device_collectives"] > calls
