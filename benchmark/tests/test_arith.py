"""Bus factors and S per collective against hand-worked values; the
median, geometric-mean and spread arithmetic; the peaks table."""
import math

import pytest

from harness import collkit, peaks, readerkit, stats

MiB = 1 << 20


@pytest.mark.parametrize("coll,n,factor", [
    ("allreduce", 4, 1.5), ("allreduce", 2, 1.0), ("allreduce", 8, 1.75),
    ("allgather", 4, 0.75), ("reduce_scatter", 4, 0.75),
    ("alltoall", 4, 0.75), ("alltoall", 8, 0.875),
    ("bcast", 4, 1.0), ("bcast", 8, 1.0),
])
def test_bus_factor_is_nccl_tests(coll, n, factor):
    assert collkit.BUS_FACTOR[coll](n) == factor


def test_bus_bytes_by_hand():
    p = {"name": "p", "dtype": "float32", "bytes": 64 * MiB}
    # allreduce 64 MiB on 4 ranks: 2*3/4 * 64 MiB = 96 MiB on the bus
    assert collkit.bus_bytes("allreduce", p, 4) == 96 * MiB
    assert collkit.bus_bytes("allgather", p, 4) == 48 * MiB
    assert collkit.bus_bytes("bcast", p, 4) == 64 * MiB
    # one rank: nothing crosses a link, so no bandwidth is reported
    assert collkit.bus_bytes("allreduce", p, 1) == 0


def test_elements_from_s():
    p = {"name": "p", "dtype": "float32", "bytes": 4096}
    assert collkit.elems(p) == 1024          # allreduce: S is the buffer
    assert collkit.elems(p, 4) == 256        # allgather: S/n is sent
    with pytest.raises(ValueError):
        collkit.elems({"name": "p", "dtype": "float32", "bytes": 8}, 4)


def test_kinds_state_s_as_nccl_tests():
    import os

    from harness import manifest as mf
    from harness import protocol as pt

    def kind(name):
        return pt.load_module("kinds", name, mf.BENCH_DIR)

    p = {"name": "p", "dtype": "float32", "bytes": 4096, "rows": 4,
         "op": "SUM"}
    assert kind("allreduce").input_shape(p, 4) == (4, 1024)
    assert kind("bcast").input_shape(p, 4) == (4, 1024)
    assert kind("allgather").input_shape(p, 4) == (4, 256)
    assert kind("reduce_scatter").input_shape(p, 4) == (4, 4, 256)
    assert kind("alltoall").input_shape(p, 4) == (4, 4, 256)
    assert kind("stack_reduce").input_shape(p, 1) == (4, 1024)
    # a stack of 4 rows: 4 read, 1 written
    assert kind("stack_reduce").moved_bytes(p, 1) == 5 * 4096
    assert kind("stack_reduce").bus_bytes(p, 4) == 0
    assert kind("allreduce_init").bus_bytes(p, 4) == 1.5 * 4096
    assert os.path.isfile(mf.code_file("kinds", "allreduce"))


def test_median_geomean_spread():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 2, 3]) == 2.5
    assert math.isclose(stats.geomean([1, 100]), 10)
    assert math.isclose(stats.geomean([2, 8, 4]), 4)
    with pytest.raises(ValueError):
        stats.geomean([1, 0])
    with pytest.raises(ValueError):
        stats.geomean([])
    # six runs 100..105: quartiles 101.25 and 103.75 (inclusive), median
    # 102.5
    values = [100, 101, 102, 103, 104, 105]
    assert stats.quartiles(values) == (101.25, 103.75)
    assert math.isclose(stats.spread(values), 2.5 / 102.5)


def test_select_rows():
    rows = [{"name": "a", "set": "s", "kind": "allreduce", "op": "SUM"},
            {"name": "b", "set": "s", "kind": "allreduce", "op": "PROD"},
            {"name": "c", "set": "t", "kind": "bcast", "op": "SUM"}]
    pick = lambda **p: [r["name"] for r in readerkit.select(rows, p)]
    assert pick(select={"set": "s"}) == ["a", "b"]
    assert pick(select={"set": "s"},
                exclude={"kind": "allreduce", "op": "SUM"}) == ["b"]
    assert pick(select={"name": ["a", "c"]}) == ["a", "c"]
    assert pick() == ["a", "b", "c"]


def test_peaks_by_exact_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in v5e["source"]
    for unknown in ("cpu", "TPU v5", "tpu v5 lite", "TPU v5 lite "):
        with pytest.raises(KeyError):
            peaks.peaks(unknown)
