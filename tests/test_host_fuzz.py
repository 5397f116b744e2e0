"""Randomized host-path shakes, CI-pinned seeds.

Two tpurun-driven workers replay seed-deterministic plans on every
rank and check against replicated numpy models:

- ``fuzz_hostcoll_worker.py``: random collectives (allreduce/bcast/
  reduce/gather/allgatherv/alltoallv) + wildcard p2p + strided-vector
  datatype sends — the sweep that found the untyped-alltoallv
  inconsistency.
- ``fuzz_osc_worker.py``: fence-epoch RMA schedules (put/accumulate/
  fetch_and_op/get, disjoint per-origin regions) + a passive-target
  lock token ring.  Epochs separate with a barrier AFTER each rank
  checks its exposure epoch (mapped-window puts may land early — MPI
  makes epoch separation the program's job).
"""
from pathlib import Path

import pytest

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent


def _run(worker, n, env_extra, timeout=420):
    return tpurun(n, REPO / "tests" / worker, timeout=timeout,
                  env=env_extra)


@pytest.mark.parametrize("seed", [11, 47])
def test_fuzz_host_collectives(seed):
    # OTPU_SANITIZE arms the hard-assertion mode for the designed
    # worst-case seeds: staging double-release/aliasing, tcp framing
    # desync, and memchecker's frozen in-flight send buffers all fail
    # loudly at the faulty operation instead of corrupting downstream
    r = _run("fuzz_hostcoll_worker.py", 4,
             {"HF_SEED": str(seed), "HF_ITERS": "15", "OTPU_SANITIZE": "1"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert r.stdout.count("randomized iterations OK") == 4


@pytest.mark.parametrize("seed", [5, 31])
def test_fuzz_osc_epochs(seed):
    r = _run("fuzz_osc_worker.py", 4,
             {"OF_SEED": str(seed), "OF_EPOCHS": "8"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert "osc fuzz ok" in r.stdout


@pytest.mark.parametrize("seed", [9, 21])
def test_fuzz_shmem_epochs(seed):
    r = _run("fuzz_shmem_worker.py", 4,
             {"SF_SEED": str(seed), "SF_EPOCHS": "8"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert "shmem fuzz ok" in r.stdout


@pytest.mark.parametrize("seed", [3, 27])
def test_fuzz_io_views(seed, tmp_path):
    r = _run("fuzz_io_worker.py", 4,
             {"IOF_SEED": str(seed), "IOF_ITERS": "6",
              "IOF_PATH": str(tmp_path / "fuzz.bin")})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert "io fuzz ok" in r.stdout


@pytest.mark.parametrize("seed", [7, 19])
def test_fuzz_algorithm_menus(seed):
    """Every tuned-menu algorithm for every collective must agree with
    numpy on random payloads — the decision ladder may pick any entry."""
    r = _run("fuzz_algs_worker.py", 4,
             {"AF_SEED": str(seed), "OTPU_SANITIZE": "1"}, timeout=520)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert r.stdout.count("menus agree") == 4
