"""The names PR 27 added, pinned beside ``test_manifest.py`` (whose
``test_names_are_the_issues`` pins the exact list of cells and reads one
more now: PERF.md section 7), and the arithmetic of the new cell's
traffic file."""
import json
import os

import pytest

from harness import ddtkit
from harness import manifest as mf

CELL = "rank1-ddt"
POINTS = [
    "ddt_pack.mg_x.f32.514", "ddt_pack.mg_y.f32.514", "ddt_pack.mg_z.f32.514",
    "ddt_to_self.mg_x.f32.514",
    "ddt_pack.mg_x.f32.130", "ddt_pack.mg_y.f32.130", "ddt_pack.mg_z.f32.130",
    "ddt_pack.fft2.c8.4096", "ddt_pack.fft2.c8.8192",
    "ddt_unpack.fft2.c8.4096", "ddt_unpack.fft2.c8.8192",
    "ddt_to_self.fft2.c8.4096", "ddt_pack.lammps_atomic.f32.4Mof32M"]
LAYER = ("datatype engine (datatype/convertor device plans behind "
         "mca/accelerator)")


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def points(real):
    cell = mf.by_name(real["workloads"], CELL, "workload")
    return mf.traffic_points(cell["traffic"])


def test_the_cell_and_its_configuration_are_the_entries_after_pr_26s(real):
    """The fourth cell, the third configuration and four per-layer
    entries in a row (later PRs append after them)."""
    assert real["workloads"][3]["name"] == CELL
    assert real["workloads"][3]["chips"] == 1
    assert real["configs"][2]["name"] == "ddt-device-1chip"
    assert real["configs"][2]["reduced"] == ["ranks", "patterns"]
    at = [m["name"] for m in real["per_layer"]].index("ddt.roofline")
    assert [m["name"] for m in real["per_layer"][at:at + 4]] == [
        "ddt.roofline", "ddt.vs_manual", "ddt.fw_self_us", "ddt.plan_builds"]
    assert {m["layer"] for m in real["per_layer"][at:at + 4]} == {LAYER}


def test_the_thirteen_points_letter_for_letter(points):
    assert [p["name"] for p in points] == POINTS
    assert [p["e2e"] for p in points] == (
        ["small_msg_us"] * 7 + ["reduce_local_bw"] * 6)


def test_bytes_is_the_packed_size(points):
    for p in points:
        assert p["bytes"] == 4 * ddtkit.packed_elems(p), p["name"]
    sizes = {p["name"]: p["bytes"] for p in points}
    assert sizes["ddt_pack.mg_x.f32.514"] == 1 << 20
    assert sizes["ddt_pack.mg_x.f32.130"] == 64 << 10
    assert sizes["ddt_pack.fft2.c8.8192"] == 512 << 20
    assert sizes["ddt_pack.lammps_atomic.f32.4Mof32M"] == 48 << 20


def test_the_metrics_it_joins_and_the_ones_it_leaves(real):
    mine = {m["name"] for s in ("end_to_end", "per_layer")
            for m in mf.metrics_of(real, s, CELL)}
    assert {"small_msg_us", "reduce_local_bw", "setup_s",
            "device.idle_share", "device.idle_in_framework",
            "device.idle_in_launch", "launch.pjit_us", "launch.pjrt_us",
            "launch.alloc_us"} <= mine
    assert not {"kernel.reduce_roofline", "kernel.vs_xla",
                "kernel.in_kernel_share", "dispatch.fw_self_us"} & mine


def test_the_atom_list_is_the_configurations_not_the_seeds():
    point = {"name": "ddt_pack.lammps_atomic.f32.4Mof32M", "atoms": 4096,
             "sent": 512}
    ids = ddtkit.atom_ids(point["name"], 4096, 512)
    assert len(set(ids.tolist())) == 512 and (ids[1:] > ids[:-1]).all()
    assert (ids == ddtkit.atom_ids(point["name"], 4096, 512)).all()


def test_the_sizing_names_the_measured_peak():
    with open(mf.data_file("cells", CELL), encoding="utf-8") as f:
        cell = json.load(f)
    assert "GB" in cell["sizing"] and "measured" in cell["sizing"].lower()
    assert os.path.isfile(mf.code_file("kinds", "ddt_pack"))
