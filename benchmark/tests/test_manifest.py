"""The manifest self-check: the real manifest passes, and each rule that
has already cost a PR (or would) fails a manifest that breaks it."""
import copy
import json
import os

import pytest

from harness import manifest as mf


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


def test_real_manifest_passes(real):
    raw = os.path.getsize(os.path.join(mf.REPO_ROOT, "BENCHMARK.json"))
    assert mf.validate(real, raw_bytes=raw) == []
    assert mf.validate_harness(real) == []


def test_names_are_the_issues(real):
    """The entries PR 23 to 26 made, in their order, and whatever later
    PRs appended after them (a prefix, not the whole list: a PR that
    adds a cell appends, and pins its own names in a file of its own,
    as ``test_cell_rank1_ddt.py`` and ``test_cell_rank1_partitioned.py``
    do)."""
    assert [w["name"] for w in real["workloads"]][:3] == [
        "osu-2x2-mix", "rank1-mix", "rank1-blocking-xl"]
    assert [w["name"] for w in real["workloads"] if w["chips"] == 4] == [
        "osu-2x2-mix"]
    assert [c["name"] for c in real["configs"]][:2] == [
        "osu-coll-2x2", "rank-local-1chip"]
    assert [m["name"] for m in real["end_to_end"]][:5] == [
        "small_msg_us", "allreduce_busbw", "coll_busbw", "reduce_local_bw",
        "setup_s"]
    # PR 23's nine less the two PR 26 retired (dispatch.issue_us and
    # dispatch.fw_over_raw: PR 24's replace them), and PR 24's ten
    assert [m["name"] for m in real["per_layer"]][:17] == [
        "boot.init_s", "compile.backend_s", "xla_coll.fw_over_raw_large",
        "xla_coll.device_busbw", "kernel.reduce_roofline", "kernel.vs_xla",
        "device.idle_share",
        "dispatch.fw_self_us", "dispatch.slow_path_share", "launch.pjit_us",
        "launch.pjrt_us", "launch.alloc_us", "device.idle_in_framework",
        "device.idle_in_launch", "compile.programs_built",
        "compile.first_call_s", "kernel.in_kernel_share"]


def _break(real, fn):
    m = copy.deepcopy(real)
    fn(m)
    return mf.validate(m)


@pytest.mark.parametrize("label,fn", [
    ("source a list", lambda m: m["configs"][0].update(
        source=[m["configs"][0]["source"]])),
    ("source an object", lambda m: m["configs"][0].update(
        source={"url": "x"})),
    ("source empty", lambda m: m["configs"][0].update(source="")),
    ("source 201 long", lambda m: m["configs"][0].update(source="x" * 201)),
    ("source with a newline", lambda m: m["configs"][0].update(
        source="OSU\nMicro")),
    ("source with a tab", lambda m: m["configs"][0].update(
        source="OSU\tMicro")),
    ("source with a long dash", lambda m: m["configs"][0].update(
        source="OSU — Micro")),
    ("source with a section sign", lambda m: m["configs"][0].update(
        source="SURVEY §2")),
    ("why not ASCII", lambda m: m["workloads"][0].update(
        why="8 B → 32 KiB")),
    ("why too long", lambda m: m["workloads"][0].update(why="y" * 201)),
    ("unit with a space", lambda m: m["end_to_end"][0].update(
        unit="GB per second")),
    ("unit with a Greek mu", lambda m: m["end_to_end"][0].update(
        unit="µs")),
    ("unit too long", lambda m: m["end_to_end"][0].update(unit="u" * 17)),
    ("name with a slash", lambda m: m["per_layer"][0].update(name="a/b")),
    ("name starting with a dot", lambda m: m["per_layer"][0].update(
        name=".a")),
    ("name 65 long", lambda m: m["per_layer"][0].update(name="n" * 65)),
    ("two metrics of one name", lambda m: m["per_layer"][0].update(
        name="setup_s")),
    ("run_seconds 9", lambda m: m.update(run_seconds=9)),
    ("run_seconds 52", lambda m: m.update(run_seconds=52)),
    ("run_seconds a float", lambda m: m.update(run_seconds=20.0)),
    ("a bound over the limit", lambda m: m["end_to_end"][0].update(
        bound=0.11)),
    ("a bound under 1%", lambda m: m["end_to_end"][0].update(bound=0.005)),
    ("an absolute bound", lambda m: m["end_to_end"][0].update(bound=3)),
    # one in four cells may ask for four chips: of twelve, three
    ("a fourth four-chip cell", lambda m: [w.update(chips=4)
                                           for w in m["workloads"][1:4]]),
    ("chips 2", lambda m: m["workloads"][1].update(chips=2)),
    ("an extra key on a metric", lambda m: m["end_to_end"][0].update(
        why="because")),
    ("an extra top-level key", lambda m: m.update(notes="x")),
    ("no setup_s", lambda m: m["end_to_end"].pop()),
    ("moves no end-to-end metric", lambda m: m["per_layer"][0].update(
        moves="nothing")),
    ("per-layer where its moved metric is not", lambda m: mf.by_name(
        m["per_layer"], "xla_coll.device_busbw", "metric").update(
        workloads=["rank1-mix"])),
    ("a cell of an unknown configuration", lambda m: m["workloads"][
        0].update(config="nope")),
    ("the same pair twice", lambda m: m["workloads"].append(
        {**m["workloads"][1], "name": "again"})),
    ("a configuration file outside paths", lambda m: m["configs"][
        0].update(file="tests/x.json")),
    ("a reduced key with no reason in the file", lambda m: m["configs"][
        0].update(reduced=["ranks", "something_else"])),
    ("a command outside paths", lambda m: m.update(
        command=["python3", "chip_smoke.py"])),
    ("an absolute command path", lambda m: m.update(
        command=["python3", "/root/repo/benchmark/run.py"])),
    ("source_type e2e", lambda m: m["end_to_end"][0].update(
        source="program_counter")),
    ("better sideways", lambda m: m["end_to_end"][0].update(
        better="sideways")),
])
def test_broken_manifest_fails(real, label, fn):
    assert _break(real, fn), f"{label}: no rule caught it"


@pytest.mark.parametrize("text,caught", [
    ('TOLERANCE = {"rtol": 1e-2, "atol": 1e-3, "why": "bf16 matmuls"}\n',
     None),
    ('X = 1\n', None),                                  # none: bit for bit
    ('TOLERANCE = {"rtol": 1e-2, "atol": 1e-3, "why": ""}\n', "why"),
    ('TOLERANCE = {"rtol": 1e-2, "atol": 1e-3, "why": "   "}\n', "why"),
    ('TOLERANCE = {"rtol": 1e-2, "atol": 1e-3}\n', "exactly"),
    ('TOLERANCE = {"rtol": -1, "atol": 0, "why": "x"}\n', "exactly"),
    ('TOLERANCE = {"rtol": 1e-2, "atol": 0, "why": "x", "p": 1}\n',
     "exactly"),
    ('TOLERANCE = dict(rtol=1e-2, atol=0, why="x")\n', "literal"),
])
def test_a_kinds_tolerance_needs_its_reason(real, copy_root, text, caught):
    """A kind compares bit for bit unless it states a tolerance and why:
    ``validate_harness`` reads the kind's file (it does not run it)."""
    path = os.path.join(copy_root, "benchmark", "kinds", "allreduce.py")
    with open(path, "a", encoding="utf-8") as f:
        f.write("\n" + text)
    errors = mf.validate_harness(real, copy_root)
    if caught is None:
        assert errors == []
    else:
        assert len(errors) == 1 and "'allreduce'" in errors[0] \
            and caught in errors[0]


def _edit(path, fn):
    with open(path, "r+", encoding="utf-8") as f:
        obj = json.load(f)
        fn(obj)
        f.seek(0)
        json.dump(obj, f)
        f.truncate()


@pytest.fixture
def copy_root(tmp_path):
    import shutil

    root = str(tmp_path)
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def _traffic(root, name):
    return os.path.join(root, "benchmark", "traffic", name + ".json")


def _metric(root, name):
    return os.path.join(root, "benchmark", "metrics", name + ".json")


def test_harness_rules_catch_missing_files(real, copy_root):
    root = copy_root
    assert mf.validate_harness(real, root) == []
    os.remove(_metric(root, "coll_busbw"))
    os.remove(os.path.join(root, "benchmark", "kinds", "alltoall.py"))
    os.remove(os.path.join(root, "benchmark", "cells", "rank1-mix.json"))
    _edit(_traffic(root, "small-set"), lambda mix: mix.update(why="café"))
    text = "\n".join(mf.validate_harness(real, root))
    for needle in ("coll_busbw", "alltoall", "rank1-mix",
                   "outside printable ASCII"):
        assert needle in text


# -- membership by tag (PR 26) ---------------------------------------------
@pytest.mark.parametrize("label,edit_manifest,edit_files,needle", [
    ("a tag naming a metric that does not list the cell",
     None,
     lambda root: _edit(_traffic(root, "reduce-local-xl-set"),
                        lambda t: t["points"][0].update(e2e="small_msg_us")),
     "is tagged 'small_msg_us'"),
    ("a tag naming no end-to-end metric",
     None,
     lambda root: _edit(_traffic(root, "reduce-local-set"),
                        lambda t: t["points"][0].update(e2e="kernel.vs_xla")),
     "is tagged 'kernel.vs_xla'"),
    ("an end-to-end metric that lists a cell and selects none of its points",
     lambda m: mf.by_name(m["end_to_end"], "small_msg_us", "metric")[
         "workloads"].append("rank1-blocking-xl"),
     None,
     "'small_msg_us' lists cell 'rank1-blocking-xl' and selects none"),
    ("a per-layer metric that lists a cell and selects none of its points",
     lambda m: mf.by_name(m["per_layer"], "dispatch.fw_self_us", "metric")[
         "workloads"].append("rank1-blocking-xl"),
     None,
     "'dispatch.fw_self_us' lists cell 'rank1-blocking-xl' and selects "
     "none"),
    ("every tag of a metric's points taken away",
     None,
     lambda root: _edit(_traffic(root, "large-set"), lambda t: [
         p.pop("e2e") for p in t["points"]
         if p["e2e"] == "allreduce_busbw"]),
     "'allreduce_busbw' lists cell 'osu-2x2-mix' and selects none"),
    ("an end-to-end metric selecting on set",
     None,
     lambda root: _edit(_metric(root, "reduce_local_bw"),
                        lambda d: d["params"].update(
                            select={"set": "reduce-local-set"})),
     "selects by the points' e2e tag, not by set"),
])
def test_tag_rules(real, copy_root, label, edit_manifest, edit_files,
                   needle):
    manifest = copy.deepcopy(real)
    assert mf.validate_harness(manifest, copy_root) == []
    if edit_manifest:
        edit_manifest(manifest)
    if edit_files:
        edit_files(copy_root)
    errors = mf.validate_harness(manifest, copy_root)
    assert any(needle in e for e in errors), f"{label}: {errors}"


def test_a_point_without_a_tag_is_per_layer_only(real, copy_root):
    _edit(_traffic(copy_root, "reduce-local-set"),
          lambda t: t["points"][0].pop("e2e"))
    assert mf.validate_harness(real, copy_root) == []
    points = mf.traffic_points("small-reduce-local-mix",
                               os.path.join(copy_root, "benchmark"))
    chosen = mf.points_by_metric(real, "rank1-mix", points,
                                 os.path.join(copy_root, "benchmark"))
    assert len(chosen["reduce_local_bw"]) == 5
    assert len(chosen["kernel.vs_xla"]) == 5


def test_the_old_cells_read_the_points_they_read(real):
    """PR 26 moved every metric's selection from the file's name to the
    points' tags; the fixture holds what PR 23-25's files selected."""
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "old_cells_points.json"), encoding="utf-8") as f:
        before = json.load(f)
    assert sorted(before) == ["osu-2x2-mix", "rank1-mix"]
    for cell, by_metric in before.items():
        traffic = mf.by_name(real["workloads"], cell, "cell")["traffic"]
        now = mf.points_by_metric(real, cell, mf.traffic_points(traffic))
        assert now == by_metric, cell


def test_no_end_to_end_metric_selects_on_set(real):
    for m in real["end_to_end"]:
        params = mf.metric_spec(m["name"]).get("params", {})
        assert "set" not in params.get("select", {}), m["name"]
        assert "exclude" not in params, m["name"]


@pytest.mark.parametrize("cell,count,one", [
    ("osu-2x2-mix", 3, "allreduce.sum.f32.64MiB"),
    ("rank1-mix", 6, "stack_reduce.prod.f32.4x64MiB"),
    ("rank1-blocking-xl", 6, "stack_reduce.band.i32.4x256MiB"),
])
def test_a_twin_is_built_only_where_a_metric_reads_it(real, cell, count,
                                                      one):
    """A traced run times a raw twin at the points that a metric of the
    cell reads one from (``fw_over_raw``), and at no small point."""
    traffic = mf.by_name(real["workloads"], cell, "cell")["traffic"]
    twins = mf.raw_points(real, cell, mf.traffic_points(traffic))
    assert len(twins) == count and one in twins
    assert not any("8B" in n or "KiB" in n for n in twins)
