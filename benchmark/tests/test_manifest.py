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
    assert [w["name"] for w in real["workloads"]] == [
        "osu-2x2-mix", "rank1-mix"]
    assert [w["name"] for w in real["workloads"] if w["chips"] == 4] == [
        "osu-2x2-mix"]
    assert [c["name"] for c in real["configs"]] == [
        "osu-coll-2x2", "rank-local-1chip"]
    assert [m["name"] for m in real["end_to_end"]] == [
        "small_msg_us", "allreduce_busbw", "coll_busbw", "reduce_local_bw",
        "setup_s"]
    assert len(real["per_layer"]) == 9


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
    ("a second four-chip cell", lambda m: m["workloads"][1].update(
        chips=4)),
    ("chips 2", lambda m: m["workloads"][1].update(chips=2)),
    ("an extra key on a metric", lambda m: m["end_to_end"][0].update(
        why="because")),
    ("an extra top-level key", lambda m: m.update(notes="x")),
    ("no setup_s", lambda m: m["end_to_end"].pop()),
    ("moves no end-to-end metric", lambda m: m["per_layer"][0].update(
        moves="nothing")),
    ("per-layer where its moved metric is not", lambda m: m["per_layer"][
        4].update(workloads=["rank1-mix"])),
    ("a cell of an unknown configuration", lambda m: m["workloads"][
        0].update(config="nope")),
    ("the same pair twice", lambda m: m["workloads"].append(
        {**m["workloads"][1], "name": "again"})),
    ("a configuration file outside paths", lambda m: m["configs"][
        0].update(file="tests/x.json")),
    ("a reduced key with no reason in the file", lambda m: m["configs"][
        0].update(reduced=["ranks", "something_else"])),
    ("a command outside paths", lambda m: m.update(
        command=["python3", "bench.py"])),
    ("an absolute command path", lambda m: m.update(
        command=["python3", "/root/repo/benchmark/run.py"])),
    ("source_type e2e", lambda m: m["end_to_end"][0].update(
        source="program_counter")),
    ("better sideways", lambda m: m["end_to_end"][0].update(
        better="sideways")),
])
def test_broken_manifest_fails(real, label, fn):
    assert _break(real, fn), f"{label}: no rule caught it"


def test_harness_rules_catch_missing_files(real, tmp_path):
    import shutil

    root = str(tmp_path)
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    assert mf.validate_harness(real, root) == []
    os.remove(os.path.join(root, "benchmark", "metrics", "coll_busbw.json"))
    os.remove(os.path.join(root, "benchmark", "kinds", "alltoall.py"))
    os.remove(os.path.join(root, "benchmark", "cells", "rank1-mix.json"))
    with open(os.path.join(root, "benchmark", "traffic", "small-set.json"),
              "r+", encoding="utf-8") as f:
        mix = json.load(f)
        mix["why"] = "café"
        f.seek(0)
        json.dump(mix, f)
        f.truncate()
    text = "\n".join(mf.validate_harness(real, root))
    for needle in ("coll_busbw", "alltoall", "rank1-mix",
                   "outside printable ASCII"):
        assert needle in text
