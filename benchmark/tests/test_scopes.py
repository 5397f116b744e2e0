"""``harness/scopes.py`` and the reader on it, against a hand-made neutral
form with hand-worked answers: a ``while`` that holds two ops of different
scopes, a mixed fusion, an inherited op, an op the map has no entry for, and
a point of two windows beside a point that is no step."""
import json
import math
import os

import pytest

from harness import protocol, scopes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one round; point "s" (a step, k = 1) has two windows, point "c" one.
# A step's run starts at +100 and lasts 1000 ns:
#   while.1   100..600   holds fusion.a 150..350 and fusion.b 400..550
#                        (self: 500 - 350 = 150)
#   fusion.m  600..900   a mixed fusion
#   rdot.3    900..950   inherited
#   copy.9    950..1000  no entry in the map
# so 900 ns of ops, every one of them busy.  Point "c" runs another
# program, whose one op shares a name with the step's.
STEP = [["while.1 (s32[], f32[8])", 100, 500], ["fusion.a f32[8]", 150, 200],
        ["fusion.b f32[8]", 400, 150], ["fusion.m f32[8]", 600, 300],
        ["rdot.3 f32[8]", 900, 50], ["copy.9 f32[8]", 950, 50]]
shift = lambda ops, by: [[n, s + by, d] for n, s, d in ops]
EVENTS = {
    "calls": {"s": 1, "c": 1},
    "host": [["bench.round", 0, 7000],
             ["bench.issue.s", 50, 100], ["bench.sync", 150, 1000],
             ["bench.issue.c", 2000, 100], ["bench.sync", 2100, 500],
             ["bench.issue.s", 4000, 100], ["bench.sync", 4100, 1100]],
    "modules": {"0": [["jit_step(7)", 100, 900],
                      ["jit_coll(8)", 2100, 300],
                      ["jit_step(7)", 4100, 900]]},
    "device": {"0": shift(STEP, 0) + [["fusion.a f32[2]", 2100, 300]]
               + shift(STEP, 4000)},
}
op = lambda chain, which, **more: {
    "chain": chain, "pass": which, "mixed": False, "inherited": False,
    "opcode": "fusion", **more}
MAPS = {"jit_step": {"module": "jit_step", "unknown": [], "ops": {
    "while.1": op(["otpu_layers"], "forward", opcode="while"),
    "fusion.a": op(["otpu_layers", "otpu_mla"], "forward"),
    "fusion.b": op(["otpu_layers", "otpu_moe"], "remat"),
    "fusion.m": op(["otpu_head"], "backward", mixed=True,
                   kinds=["otpu_head:backward", "otpu_stats:forward"]),
    "rdot.3": op(["otpu_layers", "otpu_moe"], "backward", inherited=True,
                 opcode="custom-call")}}}
NS = 1e-6       # ns as ms


@pytest.fixture(scope="module")
def table():
    return scopes.reduce_scopes(EVENTS, MAPS, ["s"])


def test_self_time_counts_a_loop_once():
    assert sorted(scopes.self_times(STEP)) == sorted([
        ("while.1 (s32[], f32[8])", 100, 150), ("fusion.a f32[8]", 150, 200),
        ("fusion.b f32[8]", 400, 150), ("fusion.m f32[8]", 600, 300),
        ("rdot.3 f32[8]", 900, 50), ("copy.9 f32[8]", 950, 50)])


def test_the_rows_are_the_step_by_chain_and_pass(table):
    assert (table["steps"], table["devices"], table["points"]) == (2, 1,
                                                                   ["s"])
    rows = {("/".join(r["chain"]), r["pass"]): r for r in table["rows"]}
    want = {("otpu_layers", "forward"): 150,
            ("otpu_layers/otpu_mla", "forward"): 200,
            ("otpu_layers/otpu_moe", "remat"): 150,
            ("otpu_head", "backward"): 300,
            ("otpu_layers/otpu_moe", "backward"): 50,
            ("", None): 50}
    assert set(rows) == set(want)
    for key, ns in want.items():
        assert math.isclose(rows[key]["ms_per_step"], ns * NS), key
    # most time first; a row's ops by name, the other program's
    # fusion.a (point "c") not among them
    assert table["rows"][0]["chain"] == ["otpu_head"]
    (name, ms), = rows[("otpu_layers/otpu_mla", "forward")]["top_ops"]
    assert name == "fusion.a f32[8]" and math.isclose(ms, 200 * NS)
    assert math.isclose(rows[("otpu_head", "backward")]["mixed_ms_per_step"],
                        300 * NS)


def test_the_rows_sum_to_the_busy_time(table):
    assert math.isclose(table["busy_ms_per_step"], 900 * NS)
    assert math.isclose(table["rows_ms_per_step"], 900 * NS)
    assert math.isclose(sum(r["share_pct"] for r in table["rows"]), 100.0)
    assert math.isclose(sum(table["by_pass_ms_per_step"].values()), 900 * NS)
    assert math.isclose(table["by_scope_ms_per_step"]["otpu_layers"],
                        550 * NS)       # nested scopes' rows overlap


def test_what_is_marked(table):
    for kind, ns, name in (("mixed", 300, "fusion.m f32[8]"),
                           ("inherited", 50, "rdot.3 f32[8]"),
                           ("unnamed", 50, "copy.9 f32[8]"),
                           ("no_entry", 50, "copy.9 f32[8]"),
                           ("compiler", 0, None)):
        assert math.isclose(table[kind + "_ms_per_step"], ns * NS), kind
        assert [n for n, _ in table[kind + "_top_ops"]] == (
            [name] if name else [])
    (mix, ms), = table["mixed_by_kinds_ms_per_step"]
    assert mix == "otpu_head:backward + otpu_stats:forward"
    assert math.isclose(ms, 300 * NS)


@pytest.mark.parametrize("params,ns", [
    ({"scopes": ["otpu_layers"]}, 550), ({"scopes": ["otpu_moe"]}, 200),
    ({"scopes": ["otpu_mla", "otpu_head"]}, 500), ({"pass": "remat"}, 150),
    ({"scopes": ["otpu_moe"], "pass": "backward"}, 50),
    ({"scopes": ["otpu_adamw"]}, 0), ({"marked": "unnamed"}, 50),
    ({"marked": "mixed"}, 300)])
def test_a_metric_is_a_share_of_the_busy_time(table, params, ns):
    assert math.isclose(scopes.share(table, params), 100.0 * ns / 900,
                        abs_tol=1e-12)


def test_a_name_outside_the_data_file_is_no_scope():
    maps = json.loads(json.dumps(MAPS))
    maps["jit_step"]["ops"]["fusion.a"]["chain"] = ["otpu_layers", "other"]
    maps["jit_step"]["ops"]["fusion.b"]["pass"] = "sideways"
    rows = {("/".join(r["chain"]), r["pass"])
            for r in scopes.reduce_scopes(EVENTS, maps, ["s"])["rows"]}
    assert ("otpu_layers", "forward") in rows
    assert ("otpu_layers/otpu_moe", None) in rows
    assert set(scopes.DATA["update_scopes"]) <= set(scopes.DATA["scopes"])


def test_a_trace_of_ones_own_is_read_by_the_programs_runs(table):
    """No harness span, no ``calls``: every run of a program that has a
    map is a step."""
    own = {"device": EVENTS["device"], "modules": EVENTS["modules"],
           "host": []}
    got = scopes.reduce_scopes(own, MAPS)
    assert got["steps"] == 2 and got["points"] == []
    assert got["rows"] == table["rows"]
    assert got["busy_ms_per_step"] == table["busy_ms_per_step"]
    assert scopes.reduce_scopes(own, {"jit_other": MAPS["jit_step"]}) is None


def test_a_point_that_is_no_step_reads_nothing():
    assert scopes.reduce_scopes(EVENTS, MAPS, []) is None
    other = scopes.reduce_scopes(EVENTS, MAPS, ["c"])
    assert other["no_entry_ms_per_step"] == other["busy_ms_per_step"]


def test_the_reader_reads_nothing_without_a_map(monkeypatch, tmp_path):
    """The parent commit's program has no ``scopes_of_built_steps``:
    ``program_maps`` gives None, and every metric on the reader is left
    out; so is a run without a trace, and a cell without a step."""
    reader = protocol.load_module("readers", "trace_scope_share", BENCH)
    params = {"select": {"kind": ["train_step"]}, "scopes": ["otpu_head"]}
    ctx = {"run": {"workload": "cell"}, "trace": {"points": {}},
           "points": [{"name": "s", "kind": "train_step", "k": 1},
                      {"name": "c", "kind": "allreduce", "k": 1}]}
    asked = []
    monkeypatch.setattr(scopes, "program_maps",
                        lambda: asked.append(1) and None)
    scopes._loaded.clear()
    assert reader.read(ctx, params) is None
    assert reader.read(ctx, {**params, "marked": "mixed"}) is None
    assert asked == [1]                 # asked once a run, not a metric
    assert reader.read({**ctx, "trace": None}, params) is None
    assert reader.read(ctx, {**params, "select": {"kind": "x"}}) is None
    scopes._loaded.clear()


def test_the_program_without_the_function_gives_no_map(monkeypatch, capsys):
    from ompi_tpu.parallel import train

    monkeypatch.delattr(train, "scopes_of_built_steps")
    assert scopes.program_maps() is None
    assert "gives no scope map" in capsys.readouterr().out


def test_every_step_metric_names_the_reader_and_the_two_kinds():
    names = [n[:-5] for n in os.listdir(os.path.join(BENCH, "metrics"))
             if n.startswith("step.")]
    assert sorted(names) == [
        "step.attention_share", "step.attn_proj_share",
        "step.expert_block_share", "step.hbm_peak_share", "step.head_share",
        "step.mixed_share", "step.optimizer_share", "step.remat_share",
        "step.unnamed_share"]
    names.remove("step.hbm_peak_share")     # PR 53's, reader step_memory
    for name in names:
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["reader"] == "trace_scope_share"
        assert spec["params"]["select"] == {
            "kind": ["train_step", "train_step_share"]}
        assert set(spec["params"].get("scopes", ())) <= set(
            scopes.DATA["scopes"])
        assert spec["params"].get("pass", "remat") in scopes.DATA["passes"]
