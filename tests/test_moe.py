"""parallel/moe — expert parallelism over the ragged tier (ISSUE 17).

Acceptance coverage: gating is a pure deterministic function (same
seed + inputs ⇒ identical assignment across PYTHONHASHSEED-randomized
processes; dropped-token counts exactly reconcile with the capacity
factor), the expert-sharded host trainer is bit-exact against the
single-process oracle through checkpoint/restore AND a 2-process
tpurun, a chaos kill mid-train recovers elastically with the experts
re-sharded over the survivors, a designed-imbalance run's hot-expert
home rank bounds >= 90% of steps under ``otpu_analyze
--critical-path``, the device-tier expert FFN over the ('expert',)
mesh axis is bit-stable, the int8-quantized dispatch stays inside the
``otpu_quant_budget`` band through the REAL ragged device kernel, and
the fused coll/tuned DEVICE ladder cell matches its unfused fallback.
"""
import json
import math
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errors import MpiError
from ompi_tpu.parallel import moe
from ompi_tpu.parallel.elastic import partition

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ gating (pure)

def test_gate_weights_dyadic_and_exact():
    for k in range(1, 6):
        w = moe.gate_weights(k)
        assert len(w) == k
        # dyadic rationals summing to EXACTLY 1.0 — combines stay
        # bit-exact no matter how the weighted rows are folded
        assert math.fsum(w) == 1.0 and sum(w) == 1.0
        assert all(x > 0 for x in w)
        assert list(w[1:]) == sorted(w[1:], reverse=True)
    assert moe.gate_weights(3) == (0.625, 0.25, 0.125)


def test_capacity_formula():
    assert moe.capacity_for(64, 8, 2, 1.25) == \
        math.ceil(1.25 * 64 * 2 / 8)
    assert moe.capacity_for(2, 8, 1, 0.01) == 1      # never below 1
    assert moe.capacity_for(48, 6, 2, 3.0) == 48


def test_plan_is_deterministic_and_total():
    a = moe.plan_step(5, 64, 8, 2, 1.25, seed=3)
    b = moe.plan_step(5, 64, 8, 2, 1.25, seed=3)
    assert a.to_json() == b.to_json()
    # every (token, slot) pair lands exactly once, kept or dropped
    assert len(a.kept) + len(a.dropped) == 64 * 2
    # loads ARE the per-expert kept counts, all within capacity
    counts = [0] * 8
    for asn in a.kept:
        assert asn.pos == counts[asn.expert]   # slots fill in order
        counts[asn.expert] += 1
    assert tuple(counts) == a.loads
    assert max(a.loads) <= a.capacity
    with pytest.raises(ValueError):
        moe.plan_step(0, 16, 4, 5, 1.25)


def test_drop_counts_reconcile_with_capacity_factor():
    """The satellite-3 accounting check: dropped == overflow demand.
    Demand is recomputed INDEPENDENTLY from the raw gate scores, so
    the plan's capacity loop is checked against the closed form
    ``sum_e max(0, demand_e - capacity)``."""
    T, E, k, cf = 96, 8, 2, 0.75
    plan = moe.plan_step(7, T, E, k, cf, seed=11)
    s = moe.gate_scores(7, T, E, 11)
    key = s * E + (E - 1 - np.arange(E, dtype=np.int64))[None, :]
    order = np.argsort(-key, axis=1, kind="stable")[:, :k]
    demand = np.bincount(order.ravel(), minlength=E)
    cap = moe.capacity_for(T, E, k, cf)
    assert plan.capacity == cap
    assert len(plan.dropped) == int(np.maximum(demand - cap, 0).sum())
    assert plan.loads == tuple(np.minimum(demand, cap).tolist())
    # a capacity factor of E/k * slack admits every assignment
    full = moe.plan_step(7, T, E, k, float(E), seed=11)
    assert not full.dropped and len(full.kept) == T * k


def test_hot_expert_skews_load():
    base = moe.plan_step(2, 128, 8, 2, 4.0, seed=0)
    hot = moe.plan_step(2, 128, 8, 2, 4.0, seed=0, hot_expert=5,
                        hot_boost=0.6)
    assert int(np.argmax(hot.loads)) == 5
    assert hot.imbalance() > base.imbalance()
    # the boosted token set is STEP-independent: the same rank stays
    # hot every step (what makes the critical-path blame stable)
    hot2 = moe.plan_step(3, 128, 8, 2, 4.0, seed=0, hot_expert=5,
                         hot_boost=0.6)
    assert int(np.argmax(hot2.loads)) == 5


def test_gating_identical_across_hash_seeds():
    """Satellite 3: same seed + inputs ⇒ byte-identical assignment in
    processes with randomized PYTHONHASHSEED."""
    prog = ("from ompi_tpu.parallel import moe; "
            "print(moe.plan_step(3, 96, 8, 2, 1.25, seed=11, "
            "hot_expert=5, hot_boost=0.3).to_json())")
    outs = []
    for hs in ("0", "4242", "random"):
        env = dict(os.environ, PYTHONHASHSEED=hs)
        r = subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, timeout=120,
                           cwd=REPO)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip())
    assert outs[0] and outs[0] == outs[1] == outs[2]
    assert outs[0] == moe.plan_step(3, 96, 8, 2, 1.25, seed=11,
                                    hot_expert=5,
                                    hot_boost=0.3).to_json()


def test_reference_run_is_expert_sharding_invariant():
    """The oracle folds every kept row in plan order; dyadic weights +
    integer grads make the result independent of HOW experts are
    grouped — the property the re-shard acceptance leans on."""
    w = moe.reference_moe_run(np.zeros(32), 0, 6, tokens=16,
                              n_experts=4, expert_dim=8, seed=5)
    again = moe.reference_moe_run(np.zeros(32), 0, 6, tokens=16,
                                  n_experts=4, expert_dim=8, seed=5)
    assert w.tobytes() == again.tobytes()
    assert np.isfinite(w).all() and np.abs(w).sum() > 0


# ------------------------------------------- host trainer (in-process)

def test_moe_trainer_matches_reference_in_process(tmp_path, monkeypatch):
    """Single-rank ProcRte world: expert-parallel train / checkpoint /
    restore / replay is bit-exact against the oracle, and the SPC +
    report dispatch accounting reconciles with the plans."""
    from ompi_tpu.rte.coord import CoordServer
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import spc

    srv = CoordServer(1)
    monkeypatch.setenv("OTPU_COORD", f"{srv.addr[0]}:{srv.addr[1]}")
    monkeypatch.setenv("OTPU_RANK", "0")
    monkeypatch.setenv("OTPU_NPROCS", "1")
    rt.reset_for_testing()
    try:
        w = ompi_tpu.init()
        spc0 = spc.read("moe_dispatch_tokens")
        tr = moe.MoeTrainer(w, str(tmp_path / "ck"), n_experts=6,
                            expert_dim=8, tokens_per_step=24,
                            top_k=2, capacity_factor=0.9,
                            ckpt_every=4, seed=3)
        got = tr.train(9)
        ref = moe.reference_moe_run(np.zeros(48), 0, 9, tokens=24,
                                    n_experts=6, expert_dim=8,
                                    capacity_factor=0.9, seed=3)
        assert got.tobytes() == ref.tobytes()
        # accounting: dispatched/dropped are exactly the plan totals
        kept = dropped = 0
        for s in range(9):
            p = moe.plan_step(s, 24, 6, 2, 0.9, seed=3)
            kept += len(p.kept)
            dropped += len(p.dropped)
        rep = tr.report()
        assert rep["dispatched"] == kept
        assert rep["dropped"] == dropped and dropped > 0
        assert rep["experts"] == [0, 6]
        assert rep["imbalance_max"] >= 1.0
        assert spc.read("moe_dispatch_tokens") - spc0 == kept
        assert moe._TELEM["steps"] >= 9
        # restore from the expert-boundary checkpoint and replay
        step = tr.latest_complete_step()
        assert step == 8
        tr._restore(step)
        assert tr.step == 8
        assert tr.train(9).tobytes() == ref.tobytes()
        # drop_policy=error: the same overflow is a loud ERR_TRUNCATE
        tr2 = moe.MoeTrainer(w, str(tmp_path / "ck2"), n_experts=6,
                             expert_dim=8, tokens_per_step=24,
                             capacity_factor=0.9, drop_policy="error",
                             seed=3)
        with pytest.raises(MpiError):
            tr2.train(9)
    finally:
        rt.reset_for_testing()
        srv.close()


def test_trainer_rejects_bogus_drop_policy():
    with pytest.raises(MpiError):
        moe.MoeTrainer(None, "unused", drop_policy="bogus")


# --------------------------------------------- multi-process (tpurun)

_MOE_JOB = textwrap.dedent("""
    import json, sys
    import ompi_tpu
    from ompi_tpu.parallel.moe import MoeTrainer

    w = ompi_tpu.init()
    conf = json.loads(sys.argv[2])
    steps = conf.pop("steps")
    tr = MoeTrainer(w, sys.argv[1], **conf)
    tr.train(steps)
    rep = tr.report()
    print("MOERANK %d " % w.rank + json.dumps(
        {"dispatched": rep["dispatched"],
         "dropped": rep["dropped"]}), flush=True)
    if w.rank == 0:
        print("MOE " + json.dumps(rep), flush=True)
    ompi_tpu.finalize()
""")


def test_mp_moe_train_bit_exact_and_reconciled(tmp_path):
    """The 2-process acceptance run: expert-parallel training over the
    ragged host collectives lands bit-exact on the oracle, and the
    per-rank dispatch/drop counters sum to the global plan totals."""
    script = tmp_path / "job.py"
    script.write_text(_MOE_JOB)
    conf = {"steps": 10, "n_experts": 6, "expert_dim": 8,
            "tokens_per_step": 24, "capacity_factor": 0.9,
            "ckpt_every": 4, "seed": 3}
    r = tpurun(2, [sys.executable, str(script), str(tmp_path / "ckpt"),
                   json.dumps(conf)], timeout=300)
    line = next((ln for ln in r.stdout.splitlines()
                 if "MOE " in ln and "MOERANK" not in ln), None)
    assert line is not None, r.stdout + r.stderr
    rep = json.loads(line.split("MOE ", 1)[1])
    assert rep["world_size"] == 2 and rep["recoveries"] == []
    ref = moe.reference_moe_run(np.zeros(48), 0, 10, tokens=24,
                                n_experts=6, expert_dim=8,
                                capacity_factor=0.9, seed=3)
    assert np.array(rep["w"], np.float64).tobytes() == ref.tobytes()
    # cross-rank reconciliation: token ranges partition the batch, so
    # per-rank counters must SUM to the global plan totals
    per_rank = [json.loads(ln.split("MOERANK ", 1)[1].split(" ", 1)[1])
                for ln in r.stdout.splitlines()
                if "MOERANK " in ln]
    assert len(per_rank) == 2
    kept = dropped = 0
    for s in range(10):
        p = moe.plan_step(s, 24, 6, 2, 0.9, seed=3)
        kept += len(p.kept)
        dropped += len(p.dropped)
    assert sum(d["dispatched"] for d in per_rank) == kept
    assert sum(d["dropped"] for d in per_rank) == dropped


def test_moe_chaos_kill_reshards_over_survivors(tmp_path):
    """The elastic acceptance: kill an expert-heavy rank mid-train;
    recovery shrinks, the survivors re-shard the expert table among
    themselves (ownership is recomputed from the live comm — no extra
    code path), and the finished run is bit-exact to the oracle."""
    conf = {"steps": 12, "ckpt_dir": str(tmp_path / "ckpt"),
            "n_experts": 6, "expert_dim": 8, "tokens_per_step": 24,
            "ckpt_every": 4, "seed": 3}
    r = tpurun(3, [sys.executable, "-m", "ompi_tpu.parallel.moe",
                   json.dumps(conf)], timeout=300,
               extra=("--enable-recovery",
                      "--mca", "otpu_chaos_spec", "kill:rank=2,step=5"))
    line = next((ln for ln in r.stdout.splitlines()
                 if "MOE " in ln), None)
    assert line is not None, r.stdout + r.stderr
    rep = json.loads(line.split("MOE ", 1)[1])
    assert rep["world_size"] == 2, rep
    assert len(rep["recoveries"]) == 1
    rec = rep["recoveries"][0]
    assert rec["failed"] == [2]
    assert "shrink_ms" in rec and "restore_ms" in rec
    # rank 0's expert slice under the SHRUNKEN world: re-sharded from
    # the 3-way split [0,2) to the 2-way split [0,3)
    assert rep["experts"] == list(partition(0, 2, 6)) == [0, 3]
    ref = moe.reference_moe_run(np.zeros(48), 0, 12, tokens=24,
                                n_experts=6, expert_dim=8, seed=3)
    assert np.array(rep["w"], np.float64).tobytes() == ref.tobytes()


def test_moe_critical_path_blames_hot_expert_rank(tmp_path):
    """The observability acceptance: a designed-imbalanced run
    (hot_expert=5 homes on rank 2 of 3; pacing makes received load
    wall-clock) must have ``otpu_analyze --critical-path`` name the
    hot expert's home rank as bounding >= 90% of steps."""
    from ompi_tpu.tools import otpu_analyze as oa

    tdir = tmp_path / "trace"
    conf = {"steps": 12, "ckpt_dir": str(tmp_path / "ckpt"),
            "n_experts": 6, "expert_dim": 8, "tokens_per_step": 48,
            "capacity_factor": 3.0, "hot_expert": 5, "hot_boost": 0.8,
            "compute_us_per_token": 2000, "ckpt_every": 50, "seed": 0}
    assert partition(2, 3, 6) == (4, 6)      # expert 5 homes on rank 2
    r = tpurun(3, [sys.executable, "-m", "ompi_tpu.parallel.moe",
                   json.dumps(conf)], timeout=300,
               extra=("--mca", "otpu_trace_enable", "1",
                      "--mca", "otpu_trace_dir", str(tdir)))
    assert any("MOE " in ln for ln in r.stdout.splitlines()), \
        r.stdout + r.stderr
    events, profiles, meta = oa.load_run([str(tdir)])
    rep = oa.analyze(events, profiles=profiles, meta=meta,
                     critical_path=True)
    cp = rep["critical_path"]
    assert len(cp["steps"]) >= 10, cp
    assert cp["bound_by"]["rank"] == 2, cp["bound_by"]
    assert cp["bound_by"]["fraction"] >= 0.90, cp["bound_by"]


# --------------------------------------- device tier ('expert' axis)

def test_device_moe_dryrun_bit_stable():
    """The expert-sharded FFN over the ('expert',) mesh axis composed
    with dp: compiles under shard_map (check_vma), descends, and two
    fresh builds produce byte-identical loss curves."""
    import jax

    if len(jax.devices()) != 8:
        pytest.skip("needs 8 virtual devices")
    losses = moe.run_moe_training_step(steps=3)
    assert len(losses) == 3
    assert losses[-1] < losses[0]


def test_moe_param_specs_shard_experts_only():
    from jax.sharding import PartitionSpec as P

    spec = moe.MeshSpec(dp=2, ep=4)
    specs = moe.moe_param_specs(P, spec)
    assert specs["wr"] == P(None, None)
    assert specs["we1"] == P("expert", None, None)
    assert specs["we2"] == P("expert", None, None)
    # ep=1 collapses to fully-replicated (no 'expert' axis in the mesh)
    flat = moe.moe_param_specs(P, moe.MeshSpec(dp=2))
    assert flat["we1"] == P(None, None, None)


# ----------------------------------------- quantized dispatch (PR 15)

def test_dispatch_codec_roundtrip_band():
    rng = np.random.default_rng(9)
    x = rng.standard_normal((3, 5, 512)).astype(np.float32)
    y = np.asarray(moe.encode_dispatch_int8(x))
    assert y.shape == (3, 5, 512 // 4 + 128)
    back = np.asarray(moe.decode_dispatch_int8(y, 512))
    # per-128-block absmax/127 scales: error <= scale/2 per element
    blocks = x.reshape(3, 5, 4, 128)
    bound = (np.abs(blocks).max(axis=-1, keepdims=True) / 127.0) \
        * 0.5 + 1e-7
    assert (np.abs((back.reshape(3, 5, 4, 128) - blocks)) <=
            bound).all()
    with pytest.raises(ValueError):
        moe.encode_dispatch_int8(np.zeros((2, 100), np.float32))


def test_quant_dispatch_tolerance_acceptance():
    """Int8 dispatch through the REAL ragged device kernel stays
    inside the int8 accuracy band (the PR 15 contract on the
    alltoallv slot)."""
    rep = moe.run_quant_dispatch_check(nranks=4, sizes=(1 << 14,))
    assert rep and all(r <= 1.0 / 127 for r in rep.values()), rep


def test_dispatch_tokens_budget_gated():
    """``dispatch_tokens`` engages the int8 codec ONLY under an
    explicit ``otpu_quant_budget`` admitting it, decodes within band,
    and falls back to raw f32 for widths the packer cannot block."""
    from ompi_tpu.runtime import init as rt
    from ompi_tpu.runtime import spc

    rt.reset_for_testing()
    w = ompi_tpu.init()
    try:
        if w.size != 8:
            pytest.skip("needs 8 virtual devices")
        n, R, W = 8, 4, 512
        rng = np.random.default_rng(21)
        x = rng.standard_normal((n, n, R, W)).astype(np.float32)
        counts = rng.integers(0, R + 1, (n, n)).astype(np.int32)
        counts[2] = 0           # a rank that sends nothing
        counts[:, 6] = 0        # a rank that receives nothing
        outs, codec = moe.dispatch_tokens(w, x, counts)
        assert codec is None    # no budget, no codec
        np.testing.assert_array_equal(
            np.asarray(outs[0][3]), x[3, 0, :int(counts[3, 0])])
        w.info.set("otpu_quant_budget", "0.02")
        enc0 = spc.read("quant_encodes")
        outs, codec = moe.dispatch_tokens(w, x, counts)
        assert codec == "int8"
        assert spc.read("quant_encodes") - enc0 == n * n
        atol = float(np.abs(x).max()) / 127.0
        for i in range(n):
            for j in range(n):
                c = int(counts[j][i])
                blk = np.asarray(outs[i][j])
                assert blk.shape == (c, W)
                np.testing.assert_allclose(blk, x[j, i, :c],
                                           atol=atol)
        assert all(np.asarray(b).shape[0] == 0 for b in outs[6])
        # width not blockable by the 128-lane packer: raw fallback
        thin = rng.standard_normal((n, n, R, 128)).astype(np.float32)
        _outs, codec = moe.dispatch_tokens(w, thin, counts)
        assert codec is None
    finally:
        w.info.delete("otpu_quant_budget")
        rt.reset_for_testing()


# ------------------------------------- fused device ladder (coll/tuned)

def test_expert_ffn_fused_matches_unfused():
    """The coll/tuned DEVICE ladder: the fused matmul+allreduce cell
    and the unfused einsum contraction agree, and the one force-var
    governs the device tier ('off' disables the cells)."""
    import jax
    from ompi_tpu.base.var import registry
    from ompi_tpu.mca.coll import tuned

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devs[:4]), ("expert",))
    rng = np.random.default_rng(3)
    a = rng.standard_normal((4, 8, 16)).astype(np.float32)
    b = rng.standard_normal((4, 16, 8)).astype(np.float32)
    assert tuned.device_cell("matmul_allreduce") is not None
    fused = np.asarray(moe.expert_ffn_fused(a, b, mesh))
    try:
        registry.set("otpu_coll_tuned_fused_cells", "off")
        assert tuned.device_cell("matmul_allreduce") is None
        unfused = np.asarray(moe.expert_ffn_fused(a, b, mesh))
        # forcing the OTHER cell also disables this one
        registry.set("otpu_coll_tuned_fused_cells",
                     "matmul_reduce_scatter")
        assert tuned.device_cell("matmul_allreduce") is None
        assert tuned.device_cell("matmul_reduce_scatter") is not None
    finally:
        registry.set("otpu_coll_tuned_fused_cells", "")
    ref = np.einsum("nmk,nko->mo", a, b)
    np.testing.assert_allclose(fused, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(unfused, ref, rtol=2e-4, atol=2e-4)
    with pytest.raises(KeyError):
        tuned.device_cell("bogus_cell")


# ------------------------------------------ expert-sharded serving

@pytest.fixture(scope="module")
def world():
    from ompi_tpu.mca.part import part_framework
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    if w.size != 8:
        pytest.skip("needs 8 virtual devices")
    part_framework().open()
    yield w
    rt.reset_for_testing()


def test_blocking_probe_raises_on_peer_failure(world):
    """The FT hole the MoE dispatch exposed: coll/basic's alltoallv
    probes each peer before sizing the recv, and a BLOCKING probe is
    not a posted request — ``_peer_failed`` cannot complete it in
    error, so without a liveness poll in the pml loop the survivors of
    a chaos kill spin in ``progress()`` forever.  ULFM semantics: a
    probe naming a failed source raises ERR_PROC_FAILED."""
    from ompi_tpu.api.errors import ProcFailedError
    from ompi_tpu.ft import state as ft_state

    c0 = world.as_rank(0)
    res = {}

    def _probe():
        try:
            c0.probe(source=7, tag=333)      # nobody ever sends this
        except MpiError as exc:
            res["exc"] = exc

    th = threading.Thread(target=_probe, daemon=True)
    th.start()
    time.sleep(0.2)                  # the probe is inside its spin loop
    w7 = c0.group.world_rank(7)
    ft_state.mark_failed(w7)
    try:
        th.join(timeout=30)
        assert not th.is_alive(), "blocking probe hung past peer death"
        assert isinstance(res.get("exc"), ProcFailedError), res
    finally:
        ft_state._failed.discard(w7)  # don't poison the module world


def test_router_expert_affinity_and_prefix_priority(world):
    """Routing order on an expert-sharded pool: prefix-cache hit wins
    (a hit skips the prefill outright), else the request's expert home
    rank, else least-loaded; rebind re-shards the table."""
    from ompi_tpu.serving import prefix_cache
    from ompi_tpu.serving.router import Router
    from ompi_tpu.serving.scheduler import ServeRequest

    reg = prefix_cache.PrefixRegistry()
    router = Router(world.as_rank(0), workers=[1, 2, 3],
                    prefix_registry=reg, experts=6)
    table = router.expert_table()
    assert sorted(table) == list(range(6))
    assert set(table.values()) == {1, 2, 3}
    # expert_of is pure content hashing — no Python hash() anywhere
    # (one full prefix block long, so the registry can hold its hash)
    prompt = [(5 * i + 3) % 97 for i in range(prefix_cache.block_size())]
    req = ServeRequest(len(prompt), 4, rid=101, prompt=prompt)
    e = router.expert_of(req)
    assert e == router.expert_of(req)
    pre, dec, extra = router._stage_split()
    router._assign(req, dec, extra, pre)
    assert req.worker == table[e]
    # a registered prefix on a DIFFERENT worker beats the expert home
    other = next(w for w in (1, 2, 3) if w != table[e])
    hashes = prefix_cache.block_hashes(prompt)
    reg.insert(hashes, other, generation=1)
    req2 = ServeRequest(len(prompt), 4, rid=102, prompt=prompt)
    router._assign(req2, dec, extra, pre)
    assert req2.worker == other
    # rebind to a shrunken pool: the table re-covers ALL experts over
    # the survivors (contiguous partition slices, the trainer's rule)
    router.rebind(world.as_rank(0), [1, 2])
    t2 = router.expert_table()
    assert sorted(t2) == list(range(6))
    assert set(t2.values()) == {1, 2}


def test_fleet_expert_sharded_pool_end_to_end(world):
    """Fleet pool with ``experts=``: fresh admissions land on their
    expert's home worker, completions are bit-exact, and stats publish
    the expert → worker table."""
    import threading

    from ompi_tpu.serving import FleetController, PoolSpec, ShardWorker
    from ompi_tpu.serving.worker import toy_token

    workers = [ShardWorker(world.as_rank(r), router=0) for r in (1, 2)]
    threads = [threading.Thread(target=wk.serve, daemon=True)
               for wk in workers]
    for t in threads:
        t.start()
    fleet = FleetController(world.as_rank(0), pools=[
        PoolSpec("m_moe", [1, 2], max_batch=4, max_batch_tokens=4096,
                 experts=4)])
    router = fleet.routers["m_moe"]
    table = router.expert_table()
    assert sorted(table) == [0, 1, 2, 3]
    assert set(table.values()) == {1, 2}
    prompts = [[i, 3 * i + 1, 7] for i in range(8)]
    reqs = [fleet.submit("t0", "m_moe", prompt_len=len(p),
                         max_new_tokens=2, prompt=p, rid=200 + i)
            for i, p in enumerate(prompts)]
    homes = {r.rid: table[router.expert_of(r)] for r in reqs}
    deadline = time.monotonic() + 60
    while len(fleet.completed()) < len(reqs):
        fleet.tick()
        assert time.monotonic() < deadline, "fleet did not drain"
        time.sleep(0.002)
    st = fleet.stats()
    fleet.shutdown()
    for t in threads:
        t.join(timeout=10)
    for req in fleet.completed():
        assert req.worker == homes[req.rid], (req.rid, req.worker)
        assert req.tokens == [toy_token(req.rid, i)
                              for i in range(req.max_new_tokens)]
    assert st["pools"]["m_moe"]["experts"] == \
        {str(e): w for e, w in table.items()}


# --------------------------------------------- the seeded plan, pinned

def test_seeded_plan_imbalance_pinned():
    """The load-imbalance factor is a pure function of the seeded
    gating plan, so it is pinned EXACTLY: a drift is a gating change,
    not noise.  E=8, T=256, 28 steps at the default top_k and
    capacity factor (the sizes a 2-rank MoeTrainer run used; the plan
    does not depend on the number of ranks)."""
    plans = [moe.plan_step(s, 256, 8, int(moe._top_k_var.value),
                           float(moe._capacity_factor_var.value), 0)
             for s in range(28)]
    assert max(p.imbalance() for p in plans) == 1.1875
    assert sum(len(p.dropped) for p in plans) == 0
