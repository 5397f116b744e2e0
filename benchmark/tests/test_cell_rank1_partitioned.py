"""The names PR 32 added, pinned beside ``test_manifest.py``'s prefix
check, and the arithmetic of the new cell's traffic and sizing."""
import pytest

from harness import collkit
from harness import manifest as mf
from harness import protocol as pt

CELL = "rank1-partitioned"
MIB = 1 << 20
PART = ("partitioned collectives (api/comm pallreduce_init, mca/part/pcoll, "
        "coll/xla partitioned_coll)")
PART_METRICS = [
    "part.launches_per_bucket", "part.host_outside_launch_us",
    "part.step_over_single", "part.step_mean_us"]
JOINED = ["device.idle_share", "device.idle_in_framework",
          "device.idle_in_launch", "launch.pjit_us", "launch.pjrt_us",
          "launch.alloc_us", "compile.programs_built"]


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def points(real):
    cell = mf.by_name(real["workloads"], CELL, "workload")
    return mf.traffic_points(cell["traffic"])


def test_the_cell_and_its_configuration(real):
    cell = real["workloads"][4]
    assert (cell["name"], cell["chips"], cell["config"], cell["traffic"]) \
        == (CELL, 1, "dp-grad-buckets-1chip", "grad-bucket-steps")
    config = real["configs"][3]
    assert config["name"] == "dp-grad-buckets-1chip"
    assert config["reduced"] == ["ranks", "producer"]
    body = mf.load_json(mf.REPO_ROOT + "/" + config["file"])
    assert body["ranks"] == 1 and body["source"] == config["source"]
    assert "bit for bit" in body["guarantees"] and len(body["assumed"]) >= 3


def test_no_step_us_and_why(real):
    """``step_us`` (all window seconds over all steps) was measured and
    left out: a step point counts under ``small_msg_us`` and the
    all-seconds figure is per-layer.  No bound that was there moved."""
    names = [m["name"] for m in real["end_to_end"]]
    assert names == ["small_msg_us", "allreduce_busbw", "coll_busbw",
                     "reduce_local_bw", "setup_s"]
    assert [m["bound"] for m in real["end_to_end"]] == [0.06, 0.03, 0.02,
                                                        0.045, 0.1]
    assert [m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)] \
        == ["small_msg_us", "setup_s"]
    assert mf.metric_spec("part.step_mean_us")["params"] == {
        "field": "per_call_mean_us", "select": {"e2e": "small_msg_us"}}
    assert "4891-5323 us in three" in mf.metric_spec(
        "part.step_mean_us")["doc"]         # why it holds no bound


def test_the_per_layer_entries(real):
    at = [m["name"] for m in real["per_layer"]].index(PART_METRICS[0])
    mine = real["per_layer"][at:at + 4]
    assert [m["name"] for m in mine] == PART_METRICS
    assert all(m["moves"] == "small_msg_us" and m["workloads"] == [CELL]
               and m["layer"] == PART for m in mine)
    by_name = {m["name"]: m for m in real["per_layer"]}
    for name in JOINED:             # appended to, never first; the step
        #                             cells of later PRs came behind it
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) >= 1
        assert all(c.endswith("-train-1chip")
                   for c in cells[cells.index(CELL) + 1:])
    # a launch's parts are one a launch: k x the programs a call observed
    for name in ("launch.pjit_us", "launch.pjrt_us", "launch.alloc_us"):
        assert mf.metric_spec(name)["params"]["per"] == "launch"
    assert "per" not in mf.metric_spec("dispatch.fw_self_us")["params"]
    reported = {m["name"] for m in mf.metrics_of(real, "per_layer", CELL)}
    build = {"compile.trace_s", "compile.lower_s", "compile.own_backend_s",
             "compile.cache_hit_share", "compile.own_programs",
             "compile.other_s"}          # PR 53's, of every cell
    assert reported == set(PART_METRICS) | set(JOINED) | build | {
        "boot.init_s", "compile.backend_s", "harness.check_s"}


def test_the_four_points_letter_for_letter(points):
    assert [(p["name"], p["kind"], p.get("buckets"), p["bytes"],
             p.get("e2e")) for p in points] == [
        ("pallreduce.sum.f32.4x25MiB", "pallreduce", 4, 100 * MIB,
         "small_msg_us"),
        ("pallreduce.sum.f32.51x25MiB", "pallreduce", 51, 1275 * MIB,
         "small_msg_us"),
        ("pallreduce.sum.f32.32x2MiB", "pallreduce", 32, 64 * MIB,
         "small_msg_us"),
        ("allreduce.sum.f32.64MiB", "allreduce", None, 64 * MIB, None)]
    kind = pt.load_module("kinds", "pallreduce", mf.BENCH_DIR)
    for p in points[:3]:
        n, buckets, elems = kind.input_shape(p, 1)
        assert (n, buckets) == (1, p["buckets"])
        assert 4 * elems * buckets == p["bytes"]
        assert 4 * elems in (25 * MIB, 2 * MIB)
        assert kind.collectives_per_call(p) == p["buckets"]
        assert kind.bus_bytes(p, 1) == 0.0
        assert kind.bus_bytes(p, 4) == 1.5 * p["bytes"]
    assert not hasattr(kind, "COLLECTIVES_PER_CALL")
    assert not hasattr(kind, "TOLERANCE")           # bit for bit
    assert collkit.elems(points[3]) == 16 * MIB
    assert {p["values"] for p in points} == {"fine16"}


def test_a_kind_never_states_its_programs(points):
    """How many programs a step launches is read from the trace: no kind
    and no data file of the cell says it, and no environment variable."""
    import os
    import re

    for kind in {p["kind"] for p in points}:
        with open(mf.code_file("kinds", kind), encoding="utf-8") as f:
            text = f.read()
        assert not re.search(r"(?im)^\s*\w*(programs|launches)\w*\s*=", text)
        assert "environ" not in text
    assert not any("program" in key or "launch" in key
                   for p in points for key in p)
    assert os.path.isfile(os.path.join(mf.BENCH_DIR, "harness",
                                       "launch.json"))


def test_the_pools_fill_the_chip_to_the_floor(real, points):
    """16 sets of 100 MiB, 2 of 1.245 GiB, 16 + 16 of 64 MiB: over the
    4 GiB a cell must hold, under the chip."""
    spec = mf.load_json(mf.data_file("cells", CELL))
    assert spec["trace_rounds"] == 2
    pools = [max(2, min(spec["pool_max"],
                        spec["pool_bytes_per_point"] // p["bytes"]))
             for p in points]
    assert pools == [16, 2, 16, 16]
    held = sum(n * p["bytes"] for n, p in zip(pools, points))
    assert 4 << 30 < held < 8 << 30
