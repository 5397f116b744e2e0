"""The benchmark's own tests run on the CPU with four virtual devices,
in seconds: ``python -m pytest benchmark/tests -q`` from the repo root.
Must set the platform before jax is imported anywhere."""
import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, REPO_ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pytest  # noqa: E402

TINY_BYTES = 4096       # every point larger than this is cut to it


def _write(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of the benchmark in a temporary root, cut to a rehearsal:
    every point at most 4 KiB, pools of 64 KiB, every cell and
    configuration on the four virtual devices.  Tests add data files to
    it; nothing in it is a measurement."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.load(open(os.path.join(REPO_ROOT, "BENCHMARK.json")))
    for w in manifest["workloads"]:
        w["chips"] = 4
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    for fn in os.listdir(os.path.join(bench, "configs")):
        path = os.path.join(bench, "configs", fn)
        cfg = json.load(open(path))
        cfg["ranks"] = cfg["chips"] = 4
        _write(path, cfg)
    for fn in os.listdir(os.path.join(bench, "traffic")):
        path = os.path.join(bench, "traffic", fn)
        mix = json.load(open(path))
        for p in mix.get("points", []):
            p["bytes"] = min(p["bytes"], TINY_BYTES)
            if "buckets" in p:      # a set of equal buckets stays one
                p["bytes"] = p["buckets"] * 64
        _write(path, mix)
    for fn in os.listdir(os.path.join(bench, "cells")):
        path = os.path.join(bench, "cells", fn)
        cell = json.load(open(path))
        cell["pool_bytes_per_point"] = 64 << 10
        _write(path, cell)
    return root
