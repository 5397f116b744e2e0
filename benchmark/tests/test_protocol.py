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
    assert facts["hold"] == pt.HOLD
    saved = os.path.join(tiny_root, ".bench_out",
                         f"{cell}.seed3.trace0.json")
    assert json.load(open(saved))["result"] == result


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


def test_a_new_cell_is_data_alone(tiny_root):
    """A later PR adds a cell, a traffic mix, a configuration and a
    per-point metric as new files and new entries, and edits no file."""
    bench = os.path.join(tiny_root, "benchmark")
    before = {}
    for dirpath, _, files in os.walk(bench):
        for fn in files:
            p = os.path.join(dirpath, fn)
            before[p] = open(p, "rb").read()

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
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


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
