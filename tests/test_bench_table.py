"""The committed bench tables must stay trustworthy regression guards.

Round-4 verdict: the global [0.8, 1.25] band would pass a systematic
20% dispatch regression on every collective, and the sm-RGET ratio slip
(2.38 -> 2.06) sailed through unremarked.  So every committed row is
now pinned individually in ``tests/bench_pins.json`` (written from the
table being committed): refreshing the tables with a regressed build
fails the matching pin, and an intentional perf change must update the
pins in the same commit — which is exactly the review surface we want.

Tolerances: multidev ratios ±20% relative (virtual-CPU ratios carry
noise but a real regression moves them further), host latency pins ±2x
absolute (CI-host load), host bandwidth ≥0.5x pin, rget speedups ≥0.8x
pin (and the sm rows must stay >1.5x: RGET exists because it wins).
"""
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    with open(os.path.join(REPO, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pins():
    return _load(os.path.join("tests", "bench_pins.json"))


def test_committed_8dev_table_per_row_pins(pins):
    table = _load("BENCH_SWEEP_8DEV.json")
    rows = {f"{r['coll']}/{r['nbytes']}": r for r in table["results"]
            if "ratio" in r}
    assert rows, "8-device table is empty"
    checked = 0
    for key, pin in pins["multidev_ratio"].items():
        assert key in rows, f"pinned row {key} vanished from the table"
        got = rows[key]["ratio"]
        assert got >= 0.8 * pin, (
            f"{key}: ratio {got} fell >20% below its pin {pin} — "
            f"dispatch/selection regression (update bench_pins.json "
            f"only with an explanation)")
        assert got <= 1.3 * pin, (
            f"{key}: ratio {got} rose >30% above its pin {pin} — the "
            f"raw baseline diverged from the framework program shape")
        checked += 1
    assert checked >= 5, f"only {checked} pinned multidev rows"


def test_committed_host_rows_pinned(pins):
    sweep = _load("BENCH_SWEEP.json")
    rows = {f"{r.get('coll')}/{r.get('nbytes', 0)}": r
            for r in sweep["results"]}
    for key, pin in pins["host_lat_us"].items():
        r = rows.get(key)
        assert r is not None, f"pinned host row {key} vanished"
        got = r["fw_lat_us"]
        assert got <= 2.0 * pin, (
            f"{key}: {got}us vs pin {pin}us — >2x latency regression")
    for key, pin in pins["host_bw_gbs"].items():
        r = rows.get(key)
        assert r is not None, f"pinned pt2pt row {key} vanished"
        got = r["fw_bw_gbs"]
        assert got >= 0.5 * pin, (
            f"{key}: {got} GB/s vs pin {pin} — >2x bandwidth collapse")


def test_rget_speedup_pinned(pins):
    """sm-RGET must keep beating the FRAG stream decisively: the round-4
    slip (2.38 -> 2.06) stays visible, a further slide fails."""
    sweep = _load("BENCH_SWEEP.json")
    rows = {f"{r.get('coll')}/{r.get('nbytes', 0)}": r
            for r in sweep["results"]}
    for key, pin in pins["rget_speedup"].items():
        r = rows.get(key)
        assert r is not None, f"pinned rget row {key} vanished"
        got = r["ratio"]
        assert got >= 0.8 * pin, (
            f"{key}: speedup {got} fell >20% below pin {pin}")
        if "_sm/" in key:
            # fastpath (PR 4) made the FRAG stream itself faster
            # (zero-copy convertor views + schedule caches), so RGET's
            # margin legitimately narrowed; it must still WIN
            assert got > 1.3, (
                f"{key}: sm RGET speedup {got} no longer decisive — "
                f"the zero-copy path degraded")


def test_serving_rows_pinned(pins):
    """The serving benchmark rows (bench.py --serving: Poisson driver
    against the continuous-batching engine) must stay in the committed
    sweep with sane throughput/latency.  Wide tolerances — an open-loop
    queueing benchmark on a loaded CI host is noisy — but a collapse
    (4x latency, 4x throughput loss) fails."""
    sweep = _load("BENCH_SWEEP.json")
    rows = {r.get("coll"): r for r in sweep["results"]}
    for key, pin in pins["serving_tokens_per_s"].items():
        r = rows.get(key)
        assert r is not None, f"pinned serving row {key} vanished"
        assert r.get("ok", True), f"{key}: serving bench FAILED"
        got = r["tokens_per_s"]
        assert got >= 0.25 * pin, (
            f"{key}: {got} tokens/s vs pin {pin} — >4x throughput "
            "collapse in the serving engine")
    for key, pin in pins["serving_p99_ms"].items():
        r = rows[key]
        got = r["p99_ms"]
        assert got <= 4.0 * pin, (
            f"{key}: p99 {got}ms vs pin {pin}ms — >4x tail-latency "
            "regression")
        # the histogram estimator must agree with the driver's exact
        # sample to within its one-log2-bin contract
        assert r["p99_ms"] <= 2.0 * r["p99_exact_ms"] + 1.0
        assert r["p99_exact_ms"] <= 2.0 * r["p99_ms"] + 1.0


def test_serving_stage_medians_pinned(pins):
    """Every serving row must carry the otpu-req per-request stage
    decomposition (all six stages present; a vanished column means the
    --serving run stopped arming otpu_trace_requests or the analyzer
    stopped decomposing), the decomposed count must cover the row's
    requests, and the decode median — the dominant compute stage —
    must not collapse by more than the same wide open-loop band the
    p99 pins use."""
    sweep = _load("BENCH_SWEEP.json")
    rows = {r.get("coll"): r for r in sweep["results"]}
    for key, pin in pins["serving_stage_median_ms"].items():
        r = rows.get(key)
        assert r is not None, f"pinned serving row {key} vanished"
        assert r.get("ok", True), f"{key}: serving bench FAILED"
        med = r.get("stage_median_ms")
        assert med, f"{key}: stage_median_ms column vanished"
        assert set(med) >= {"queue", "dispatch", "prefill", "kv",
                            "decode", "stream"}, (
            f"{key}: incomplete stage decomposition {sorted(med)}")
        # fleet rows share one fleet-wide decomposition, so the floor
        # is per-run, not per-tenant
        assert r.get("req_decomposed", 0) >= 0.5 * r["nbytes"], (
            f"{key}: only {r.get('req_decomposed')} of {r['nbytes']} "
            "requests decomposed")
        got = med["decode"]
        assert 0.0 < got <= 4.0 * pin, (
            f"{key}: decode median {got}ms vs pin {pin}ms — >4x "
            "regression in the per-request decode stage")


def test_frontdoor_rows_pinned(pins):
    """The front-door rows (bench.py --serving: speculative-decode A/B
    and the sustained-overload contract) must stay in the committed
    sweep.  The multiplier pin is the whole point of speculation — at
    matched chips the k=4 leg must emit tokens FASTER than plain
    decode, or the draft/verify machinery is a net loss.  The overload
    row pins the SLO contract itself: interactive exact p99 held under
    `otpu_serving_slo_p99_ms` while the door sheds (with every shed
    retried), and batch degrades — never the other way around."""
    sweep = _load("BENCH_SWEEP.json")
    rows = {r.get("coll"): r for r in sweep["results"]}
    fd = pins["frontdoor"]
    mult = rows.get("serving_spec_multiplier")
    assert mult is not None, "serving_spec_multiplier row vanished"
    assert mult.get("ok", True), "spec A/B bench FAILED"
    got = mult["multiplier"]
    assert got > 1.0, (
        f"speculative decode multiplier {got} <= 1 — draft/verify is "
        "a net loss at matched chips")
    assert got >= 0.5 * fd["spec_multiplier"], (
        f"multiplier {got} fell >2x below pin {fd['spec_multiplier']}")
    k4 = rows.get("serving_spec_k4")
    assert k4 is not None and k4.get("ok", True)
    assert k4["tokens_per_s"] >= 0.25 * fd["spec_k4_tokens_per_s"], (
        f"spec k=4 {k4['tokens_per_s']} tokens/s vs pin "
        f"{fd['spec_k4_tokens_per_s']} — >4x collapse")
    inter = rows.get("serving_overload_interactive")
    batch = rows.get("serving_overload_batch")
    assert inter is not None and inter.get("ok", True), (
        "serving_overload_interactive row vanished")
    assert batch is not None and batch.get("ok", True), (
        "serving_overload_batch row vanished")
    assert inter["p99_exact_ms"] <= fd["overload_slo_p99_ms"], (
        f"interactive p99 {inter['p99_exact_ms']}ms breached the "
        f"{fd['overload_slo_p99_ms']}ms SLO under overload")
    assert inter["p99_exact_ms"] <= 4.0 * fd[
        "overload_interactive_p99_ms"], (
        f"interactive p99 {inter['p99_exact_ms']}ms vs pin "
        f"{fd['overload_interactive_p99_ms']}ms — >4x regression")
    assert batch["p99_exact_ms"] >= inter["p99_exact_ms"], (
        "overload degraded INTERACTIVE past batch — the SLO tiers "
        "inverted")
    for r in (inter, batch):
        assert r["shed"] > 0, (
            f"{r['coll']}: overload drive shed nothing — the bench "
            "is no longer above capacity")
        assert r["retried"] >= r["shed"], (
            f"{r['coll']}: {r['shed']} sheds but only {r['retried']} "
            "retries — the driver stopped honoring retry-after")


def test_recovery_rows_pinned(pins):
    """The recovery benchmark row (bench.py --recovery: elastic
    train-through-failure, detect→resume latency over 3 chaos-scheduled
    rank kills) must stay in the committed sweep with sane latency.
    Very wide tolerance — the agree/shrink phases carry scheduler
    throttles and CI-host noise — but an order-of-magnitude collapse
    (a recovery path that started blocking on a timeout) fails."""
    sweep = _load("BENCH_SWEEP.json")
    rows = {r.get("coll"): r for r in sweep["results"]}
    for key, pin in pins["recovery_p99_ms"].items():
        r = rows.get(key)
        assert r is not None, f"pinned recovery row {key} vanished"
        assert r.get("ok", True), f"{key}: recovery bench FAILED"
        assert r["nbytes"] >= 3, f"{key}: fewer than 3 recovery samples"
        got = r["p99_ms"]
        assert got <= 25.0 * pin, (
            f"{key}: p99 {got}ms vs pin {pin}ms — recovery latency "
            "collapsed by >25x (a recovery phase is blocking on a "
            "timeout instead of completing)")
        # phase accounting must cover the recovery it reports
        assert set(r.get("phase_median_ms", {})) >= {
            "revoke", "agree", "shrink", "restore"}


def test_mfu_rows_structure():
    """The MFU section (single-chip FLOPs utilization) must exist with
    all three rows once a sweep has been produced by a bench new enough
    to emit them; device-grade rows must carry a real mfu value."""
    sweep = _load("BENCH_SWEEP.json")
    mfu = sweep.get("mfu")
    if mfu is None:
        pytest.skip("committed sweep predates mfu rows")
    names = {r["metric"] for r in mfu}
    assert {"mfu_train_step", "mfu_flash_attention",
            "mfu_matmul_bf16"} <= names, names
    for r in mfu:
        assert r["tflops"] >= 0 and r["model_flops"] > 0
        if r["grade"] == "device":
            assert r["mfu"] is not None and 0 < r["mfu"] <= 1.0, r
