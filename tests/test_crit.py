"""otpu-crit: causal flow keys and cross-rank critical-path attribution.

Four layers of coverage:

* flow layer units: flow events' Chrome schema (ids, binding point),
  the flow-disabled identity, per-comm collective sequence agreement,
  and pml span flow-key stamping on a loopback send/recv;
* critical-path units on synthetic timelines: barrier edges blame the
  last-arriving rank, message edges jump send-complete -> recv, the
  critical exposed-comm fraction counts only on-path comm, and the
  report diffs;
* ``--suggest-ladder``: the draft rules file is schema-valid for
  ``coll/tuned._load_rules``, versioned, and skips colls with no
  ladder;
* THE acceptance run — a chaos ``delay:ms=40,rank=2,site=step`` 3-rank
  job: ``--critical-path`` attributes >= 90% of steps to rank 2 with a
  per-stage blame breakdown, flow events link >= 95% of pml sends to
  their recvs in the merged Chrome export, and ``--suggest-ladder``
  emits a loadable draft rules file.
"""
import json
import os
import sys
from pathlib import Path

import pytest

from ompi_tpu.base.var import registry
from ompi_tpu.runtime import trace

import launch

REPO = Path(__file__).resolve().parent.parent
WORKER = Path(__file__).resolve().parent / "crit_worker.py"


@pytest.fixture
def tracer():
    registry.set("otpu_trace_enable", True)
    registry.set("otpu_trace_flow", True)
    trace.reset_for_testing()
    yield trace
    registry.set("otpu_trace_enable", False)
    registry.set("otpu_trace_flow", True)
    trace.reset_for_testing()


# ------------------------------------------------------ flow layer units

def test_flow_events_chrome_schema(tracer):
    t0 = trace.now()
    trace.span("send", "pml", t0, args={"fid": (7, 0, 1, 3)})
    trace.flow_start("pml_msg", (7, 0, 1, 3))
    trace.flow_finish("pml_msg", "7.0.1.3")
    evs = trace.chrome_events()
    s = next(e for e in evs if e["ph"] == "s")
    f = next(e for e in evs if e["ph"] == "f")
    # tuple and string keys render the same documented id format
    assert s["id"] == f["id"] == "7.0.1.3"
    assert s["cat"] == f["cat"] == "flow"
    assert s["name"] == f["name"] == "pml_msg"
    assert f["bp"] == "e"           # binds to the enclosing recv slice
    assert "dur" not in s and "dur" not in f
    # the whole payload JSON round-trips
    json.loads(json.dumps(trace.chrome_payload(0)))


def test_flow_disabled_is_identity(tracer):
    registry.set("otpu_trace_flow", False)
    assert trace.enabled is True and trace.flow_enabled is False
    before = trace.recorded_count()
    trace.flow_start("pml_msg", (1, 0, 1, 0))
    trace.flow_finish("pml_msg", (1, 0, 1, 0))
    assert trace.recorded_count() == before
    # tracing off forces flow off regardless of the var
    registry.set("otpu_trace_flow", True)
    registry.set("otpu_trace_enable", False)
    assert trace.flow_enabled is False
    registry.set("otpu_trace_enable", True)
    assert trace.flow_enabled is True


def test_coll_seq_counts_per_comm(tracer):
    assert trace.next_coll_seq(4) == 0
    assert trace.next_coll_seq(4) == 1
    assert trace.next_coll_seq(9) == 0
    assert trace.next_coll_seq(4) == 2
    trace.reset_for_testing()
    assert trace.next_coll_seq(4) == 0      # counters reset with state


def test_coll_wrapper_stamps_cseq(tracer):
    class _FakeComm:
        cid = 11

        def __init__(self):
            self.c_coll = {}

    import numpy as np

    comm = _FakeComm()
    comm.c_coll["allreduce"] = lambda c, x: x
    trace.wrap_coll_table(comm)
    x = np.ones(16, np.float32)
    for _ in range(3):
        comm.c_coll["allreduce"](comm, x)
    spans = [e for e in trace.chrome_events()
             if e["name"] == "allreduce"]
    assert [e["args"]["cseq"] for e in spans] == [0, 1, 2]
    # flow off: no cseq stamped, span otherwise identical
    registry.set("otpu_trace_flow", False)
    comm.c_coll["allreduce"](comm, x)
    last = [e for e in trace.chrome_events()
            if e["name"] == "allreduce"][-1]
    assert "cseq" not in last["args"] and last["args"]["cid"] == 11


def test_pml_spans_carry_flow_key_on_loopback():
    """A self send/recv crosses the full pml datapath: the send and
    recv spans must share the stamped flow key and the s/f flow events
    must link on the same id."""
    import numpy as np

    import ompi_tpu
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    registry.set("otpu_trace_enable", True)
    registry.set("otpu_trace_flow", True)
    trace.reset_for_testing()
    try:
        w = ompi_tpu.init()
        x = np.ones(64, np.float32)
        buf = np.empty_like(x)
        a, b = w.as_rank(0), w.as_rank(1)
        a.send(x, dest=1, tag=3)
        b.recv(buf, source=0, tag=3)
        evs = trace.chrome_events()
        sends = [e for e in evs if e.get("name") == "send"
                 and e.get("cat") == "pml"]
        recvs = [e for e in evs if e.get("name") == "recv"
                 and e.get("cat") == "pml"]
        assert sends and recvs
        sfid = tuple(sends[-1]["args"]["fid"])
        rfid = tuple(recvs[-1]["args"]["fid"])
        assert sfid == rfid
        flow_s = {e["id"] for e in evs if e["ph"] == "s"}
        flow_f = {e["id"] for e in evs if e["ph"] == "f"}
        assert flow_s & flow_f
    finally:
        registry.set("otpu_trace_enable", False)
        trace.reset_for_testing()
        rt.reset_for_testing()


# --------------------------------------------- critical path (synthetic)

def _span(pid, name, cat, ts, dur, args=None):
    e = {"ph": "X", "pid": pid, "tid": 1, "name": name, "cat": cat,
         "ts": float(ts), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _slow_rank_timeline(steps=5, slow=2, nranks=3):
    """Back-to-back steps: fast ranks enter the allreduce at +10, the
    slow rank computes until +100 and everyone releases at +120."""
    events = []
    for k in range(steps):
        t0 = k * 125.0
        for r in range(nranks):
            late = r == slow
            events.append(_span(r, "step", "step", t0,
                                121.0 if late else 122.0, {"step": k}))
            events.append(_span(
                r, "allreduce", "coll",
                t0 + (100 if late else 10),
                20.0 if late else 110.0,
                {"cid": 0, "cseq": k, "nbytes": 4096}))
    events.sort(key=lambda e: e["ts"])
    return events


def test_critical_path_blames_last_arrival():
    from ompi_tpu.tools import otpu_analyze as oa

    rep = oa.analyze(_slow_rank_timeline(), critical_path=True)
    cp = rep["critical_path"]
    assert len(cp["steps"]) == 5
    assert cp["bound_by"]["rank"] == 2
    assert cp["bound_by"]["fraction"] == 1.0
    # only ON-path comm counts: the fast ranks sit ~90% of the step
    # inside the collective, but the path runs through rank 2's compute
    assert cp["critical_exposed_comm"] < 0.3
    naive = rep["exposed_comm"]
    assert float(naive["0"]) > 0.8        # the naive number the crit
    #                                       fraction exists to correct
    assert cp["top_blockers"][0]["rank"] == 2
    assert "allreduce/4k" in cp["coll_critical_us"]
    step = cp["steps"][0]
    assert step["bound_by"] == 2
    assert step["buckets"]["compute"] > step["buckets"]["coll"]
    assert "2" in step["on_path_us"]


def test_critical_path_follows_message_edges():
    """P2P-only workload: rank 1's recv waits on rank 0's late send —
    the walk must jump the message edge and land the blame on rank 0's
    compute, with the recv wait counted as on-path comm."""
    from ompi_tpu.tools import otpu_analyze as oa

    events = []
    for k in range(4):
        t0 = k * 1000.0
        # rank 0: long compute, send completes at +200
        events.append(_span(0, "step", "step", t0, 205.0, {"step": k}))
        events.append(_span(0, "send", "pml", t0 + 190, 10.0,
                            {"cid": 0, "fid": [0, 0, 1, k],
                             "nbytes": 4096}))
        # rank 1: posts the recv immediately, waits until +202
        events.append(_span(1, "step", "step", t0, 206.0, {"step": k}))
        events.append(_span(1, "recv", "pml", t0 + 2, 200.0,
                            {"cid": 0, "fid": [0, 0, 1, k],
                             "nbytes": 4096}))
    events.sort(key=lambda e: e["ts"])
    rep = oa.analyze(events, critical_path=True)
    cp = rep["critical_path"]
    assert cp["bound_by"]["rank"] == 0, cp
    # rank 0 owns most of the path (its compute); rank 1 only the
    # post-send delivery tail
    top = {row["rank"]: row["on_path_us"] for row in cp["top_blockers"]}
    assert top[0] > 3 * top.get(1, 0.1)


def test_critical_path_without_steps_notes_it():
    from ompi_tpu.tools import otpu_analyze as oa

    events = [_span(0, "allreduce", "coll", 0.0, 5.0,
                    {"cid": 0, "cseq": 0, "nbytes": 64}),
              _span(1, "allreduce", "coll", 1.0, 4.0,
                    {"cid": 0, "cseq": 0, "nbytes": 64})]
    rep = oa.analyze(events, critical_path=True)
    assert rep["critical_path"]["steps"] == []
    assert "step" in rep["critical_path"]["note"]


def test_diff_reports_tracks_critical_path():
    from ompi_tpu.tools import otpu_analyze as oa

    old = oa.analyze(_slow_rank_timeline(slow=2), critical_path=True)
    new = oa.analyze(_slow_rank_timeline(slow=1), critical_path=True)
    d = oa.diff_reports(old, new)
    assert d["critical_bound_by_changed"] is True
    assert d["critical_bound_by"] == [2, 1]
    assert "critical_exposed_comm_delta" in d
    assert "allreduce/4k" in d["coll_critical_us_delta"]
    same = oa.diff_reports(old, old)
    assert same["critical_bound_by_changed"] is False


# ------------------------------------------------------- suggest-ladder

def _apply_rules(rules, coll, nbytes):
    """First-match-wins evaluation, exactly tuned._pick's rule scan."""
    for rcoll, _max_size, max_bytes, alg, _seg in rules:
        if rcoll != coll:
            continue
        if max_bytes and nbytes > max_bytes:
            continue
        return alg
    return None


def test_suggest_ladder_is_schema_valid_and_behavior_identical(tmp_path):
    from ompi_tpu.mca.coll.tuned import (_MENUS, _load_rules,
                                         default_algorithm)
    from ompi_tpu.tools import otpu_analyze as oa

    rep = oa.analyze(_slow_rank_timeline(), critical_path=True)
    text = oa.suggest_ladder(rep, comm_size=3)
    assert text.startswith("# otpu-crit suggested tuning ladder v1")
    out = tmp_path / "draft.rules"
    out.write_text(text)
    rules = _load_rules(str(out))       # tuned's own loader accepts it
    assert rules
    coll, max_size, max_bytes, alg, seg = rules[0]
    assert coll == "allreduce" and max_size == 3
    assert alg in _MENUS["allreduce"]
    assert "critical_us=" in text       # annotated with measurements
    # loading the draft must change NO pick: every covered size gets
    # exactly the fixed ladder's incumbent, and uncovered sizes fall
    # through to the fixed ladder itself
    for nb in (0, 1, 64, 2048, 4096, 4097, 8191, 65536, 1 << 19,
               1 << 21, 8 << 20):
        got = _apply_rules(rules, "allreduce", nb)
        if got is not None:
            assert got == default_algorithm("allreduce", 3, nb), nb


def test_dynamic_rules_skipped_for_noncommutative_ops():
    """A machine-generated (or hand-written) rules file cannot express
    commutativity; tuned must never let it route a non-commutative
    reduction onto an operand-reordering algorithm (the fixed ladder's
    :77-80 exclusions stay authoritative)."""
    from ompi_tpu.mca.coll.tuned import COMPONENT, TunedModule

    if not hasattr(COMPONENT, "_force"):
        COMPONENT._force = {}
        COMPONENT._seg = {}
    saved = COMPONENT.rules
    COMPONENT.rules = [("allreduce", 0, 0, "ring", 0)]
    try:
        m = TunedModule(COMPONENT)
        # commutative traffic takes the rule
        assert m._pick("allreduce", 4, 1024, "recursive_doubling",
                       commute=True) == ("ring", 0)
        # non-commutative traffic ignores it (ring reorders operands)
        assert m._pick("allreduce", 4, 1024, "recursive_doubling",
                       commute=False) == ("recursive_doubling", 0)
    finally:
        COMPONENT.rules = saved


def test_suggest_ladder_skips_unladdered_colls():
    from ompi_tpu.tools import otpu_analyze as oa

    report = {"critical_path": {
        "steps": [{}],
        "coll_critical_us": {"allreduce_array/4k": 100.0},
        "_coll_critical_nbytes": {"allreduce_array/4k": 4096},
    }}
    text = oa.suggest_ladder(report, comm_size=3)
    assert "allreduce_array" not in text.replace(
        "# (no collective time on the critical path)", "")
    assert "no collective time" in text


def test_ladder_rules_reproduce_fixed_ladder():
    """``tuned.ladder_rules`` (what --suggest-ladder emits per coll)
    is breakpoint-exact: first-match-wins over its rows equals
    ``default_algorithm`` for every covered size, fall-through above —
    including alltoall's per-block (non-pow2) threshold."""
    from ompi_tpu.mca.coll.tuned import default_algorithm, ladder_rules

    probes = (0, 1, 255, 256, 767, 768, 769, 1023, 4096, 4097, 8191,
              65535, 65536, (1 << 19) - 1, 1 << 19, (4 << 20) - 1,
              4 << 20, 1 << 25)
    for coll in ("allreduce", "bcast", "alltoall", "barrier",
                 "reduce_scatter"):
        for size in (2, 3, 8):
            for commute in (True, False):
                rows = ladder_rules(coll, size, 1 << 23, commute)
                for nb in probes:
                    want = default_algorithm(coll, size, nb, commute)
                    got = next((alg for mx, alg in rows
                                if not (mx and nb > mx)), None)
                    assert got in (None, want), (coll, size, commute,
                                                 nb, got, want)


def test_default_algorithm_matches_ladder_shape():
    """The extracted pure ladder keeps the dispatch methods' exact
    boundaries (the suggest-ladder draft must name the incumbent the
    running system would actually pick)."""
    from ompi_tpu.mca.coll.tuned import _MENUS, default_algorithm

    assert default_algorithm("allreduce", 4, 4096) == \
        "recursive_doubling"            # boundary inclusive
    assert default_algorithm("allreduce", 4, 4097) == "rabenseifner"
    assert default_algorithm("allreduce", 4, 1 << 20) == "ring"
    assert default_algorithm("allreduce", 4, 8 << 20) == "ring_segmented"
    assert default_algorithm("allreduce", 2, 64, commute=False) == \
        "nonoverlapping"
    assert default_algorithm("bcast", 8, 1024) == "binomial"
    assert default_algorithm("bcast", 8, 4096) == "scatter_allgather"
    assert default_algorithm("barrier", 4, 0) == "recursive_doubling"
    assert default_algorithm("barrier", 5, 0) == "bruck"
    assert default_algorithm("alltoall", 4, 512) == "bruck"
    assert default_algorithm("alltoall", 4, 4096) == "pairwise"
    with pytest.raises(KeyError):
        default_algorithm("nope", 4, 0)
    # every pick is a real menu entry for its collective
    for coll in _MENUS:
        for size in (2, 3, 8):
            for nb in (0, 512, 4096, 1 << 17, 1 << 21, 8 << 20):
                assert default_algorithm(coll, size, nb) in _MENUS[coll]
                assert default_algorithm(coll, size, nb,
                                         commute=False) in _MENUS[coll]


# ----------------------------------------------- ring overflow honesty

def test_analyzer_report_pins_ring_overflow(tmp_path, tracer):
    """The ring-wrap counter travels: ring -> payload metadata ->
    load_run meta -> report header (text and parsable) — a silent wrap
    would make critical paths lie."""
    from ompi_tpu.tools import otpu_analyze as oa

    n = trace._ring_n
    extra = 137
    for i in range(n + extra):
        trace.span("s", "coll", trace.now(),
                   args={"cid": 0, "nbytes": 0})
    payload = trace.chrome_payload(0)
    assert payload["metadata"]["events_overwritten"] == extra
    p = tmp_path / "trace_rank0.json"
    p.write_text(json.dumps(payload))
    events, profiles, meta = oa.load_run([str(p)])
    assert meta["events_overwritten"] == {0: extra}
    rep = oa.analyze(events, profiles=profiles, meta=meta)
    assert rep["events_overwritten"]["total"] == extra
    assert rep["events_overwritten"]["per_rank"] == {"0": extra}
    text = oa.render_text(rep)
    assert "WARNING" in text and str(extra) in text
    parsable = oa.render_text(rep, parsable=True)
    assert f"events_overwritten:{extra}:" in parsable


def test_analyze_includes_zero_span_payload_ranks(tmp_path):
    """A rank whose payload carries zero spans (crash bundle) still
    appears in the report's rank list instead of silently vanishing."""
    from ompi_tpu.tools import otpu_analyze as oa

    (tmp_path / "trace_rank0.json").write_text(json.dumps({
        "traceEvents": [_span(0, "allreduce", "coll", 10.0, 5.0,
                              {"cid": 0, "nbytes": 64})],
        "metadata": {"rank": 0, "clock_offset_us": 0.0}}))
    (tmp_path / "trace_rank1.json").write_text(json.dumps({
        "traceEvents": [],
        "metadata": {"rank": 1, "clock_offset_us": -250.0}}))
    events, profiles, meta = oa.load_run([str(tmp_path)])
    assert meta["payload_ranks"] == [0, 1]
    rep = oa.analyze(events, profiles=profiles, meta=meta)
    assert rep["ranks"] == [0, 1]


# ------------------------------------------------- THE acceptance run

def test_critical_path_acceptance_designed_slow_rank(tmp_path):
    """THE otpu-crit acceptance (ISSUE 14): chaos
    ``delay:ms=40,rank=2,site=step`` on a 3-rank job — the critical
    path attributes >= 90% of steps to rank 2 with a per-stage blame
    breakdown, flow events link >= 95% of pml sends to their recvs in
    the merged Chrome export, and --suggest-ladder emits a draft rules
    file coll/tuned can load."""
    tdir = tmp_path / "trace"
    env = dict(os.environ, JAX_PLATFORMS="cpu", CW_ITERS="20")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    env.pop("OTPU_COORD", None)
    cmd = [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
           "--mca", "otpu_chaos_spec", "delay:ms=40,p=1,rank=2,site=step",
           "--mca", "otpu_trace_enable", "1",
           "--mca", "otpu_trace_dir", str(tdir),
           # collectives through the pml datapath so sends are spanned
           "--mca", "otpu_coll_sm_coll_priority", "0",
           sys.executable, str(WORKER)]
    r = launch.run(cmd, 300, env)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert out.count("CRIT WORKER DONE") == 3, out
    merged = json.load(open(tdir / "trace_merged.json"))
    evs = merged["traceEvents"]
    # flow arrows present and >= 95% of pml sends link to a recv
    s_ids = {e["id"] for e in evs if e.get("ph") == "s"}
    f_ids = {e["id"] for e in evs if e.get("ph") == "f"}
    sends = [e for e in evs
             if e.get("cat") == "pml" and e.get("name") == "send"]
    assert sends and s_ids, "no pml flow starts in the merged export"
    assert len(s_ids & f_ids) / len(s_ids) >= 0.95, (
        len(s_ids), len(s_ids & f_ids))
    from ompi_tpu.tools import otpu_analyze as oa

    events, profiles, meta = oa.load_run([str(tdir)])
    rep = oa.analyze(events, profiles=profiles, meta=meta,
                     critical_path=True)
    cp = rep["critical_path"]
    assert len(cp["steps"]) >= 18, len(cp["steps"])
    assert cp["bound_by"]["rank"] == 2, cp["bound_by"]
    assert cp["bound_by"]["fraction"] >= 0.90, cp["bound_by"]
    # per-stage blame breakdown: every step row carries the buckets
    for step in cp["steps"]:
        assert set(step["buckets"]) == {"compute", "send", "recv",
                                        "coll"}
    assert cp["top_blockers"][0]["rank"] == 2
    # the slow rank's time is its own compute (the pace delay), NOT
    # comm: critical exposed-comm sits well under the fast ranks'
    # naive exposed-comm fraction
    naive_fast = max(float(rep["exposed_comm"].get("0", 0)),
                     float(rep["exposed_comm"].get("1", 0)))
    assert cp["critical_exposed_comm"] < naive_fast
    # --suggest-ladder end to end through the CLI
    ladder = tmp_path / "draft.rules"
    rep_path = tmp_path / "report.json"
    rc = oa.main([str(tdir), "--critical-path",
                  "--suggest-ladder", str(ladder),
                  "--json", str(rep_path)])
    assert rc == 0
    from ompi_tpu.mca.coll.tuned import _load_rules

    rules = _load_rules(str(ladder))
    assert rules and any(c == "allreduce" for c, *_ in rules), rules
    again = json.loads(rep_path.read_text())
    assert again["critical_path"]["bound_by"]["rank"] == 2
    assert oa.diff_reports(again, rep)[
        "critical_bound_by_changed"] is False
