"""otpu_info introspection tool + monitoring interposition components."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def _run_info(*args, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.otpu_info", *args],
        capture_output=True, text=True, timeout=60, cwd=REPO, env=env)


def test_info_all_lists_components_and_vars():
    r = _run_info("--all")
    assert r.returncode == 0, r.stderr
    # frameworks + components with priorities
    for needle in ("mca coll: tuned (priority 30)",
                   "mca coll: xla (priority 90)",
                   "mca btl: sm",
                   "mca pml: ob1",
                   "mca io: ompio",
                   "mca coll: han (priority 40)"):
        assert needle in r.stdout, needle
    # vars with values and sources
    assert "otpu_coll_tuned_allreduce_algorithm" in r.stdout
    assert "source default" in r.stdout


def test_info_param_filter_and_source_tracking():
    r = _run_info("--param", "coll", "tuned",
                  env_extra={"OTPU_MCA_coll_tuned_priority": "77"})
    assert r.returncode == 0, r.stderr
    assert "otpu_coll_tuned_priority: 77" in r.stdout.replace("  ", " ") \
        or "77 (type int, source env" in r.stdout
    # filtered: no btl vars in coll/tuned output
    assert "otpu_btl_sm" not in r.stdout


def test_info_parsable():
    r = _run_info("--all", "--parsable")
    assert r.returncode == 0
    assert any(line.startswith("mca coll:") for line in r.stdout.splitlines())


def test_monitoring_p2p_matrix_and_coll_counters(tmp_path):
    """pml/coll monitoring records per-peer byte matrices the way the
    reference's common/monitoring does (common_monitoring.h:48-91)."""
    script = tmp_path / "mon.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.runtime import monitoring
        w = ompi_tpu.init()
        r = w.rank
        assert monitoring.enabled()
        # directed traffic: rank 0 -> 1 (two msgs), 1 -> 0 (one msg)
        if r == 0:
            w.send(np.zeros(100, np.uint8), 1, tag=1)
            w.send(np.zeros(28, np.uint8), 1, tag=2)
            buf = np.zeros(4, np.uint8)
            w.recv(buf, 1, tag=3)
        else:
            b1 = np.zeros(100, np.uint8); w.recv(b1, 0, tag=1)
            b2 = np.zeros(28, np.uint8); w.recv(b2, 0, tag=2)
            w.send(np.zeros(4, np.uint8), 0, tag=3)
        w.allreduce(np.ones(16, np.float32))
        msgs, byts = monitoring.p2p_matrix(2)
        if r == 0:
            assert msgs[0, 1] >= 2 and byts[0, 1] >= 128, (msgs, byts)
        else:
            assert msgs[1, 0] >= 1 and byts[1, 0] >= 4, (msgs, byts)
        colls = monitoring.coll_counters()
        assert colls.get("allreduce", (0, 0))[0] == 1, colls
        assert colls["allreduce"][1] == 64   # 16 x float32
        print(f"monitoring OK rank {r}")
    """))
    r = _tpurun(2, [sys.executable, str(script)],
                extra=("--mca", "monitoring_enable", "1"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("monitoring OK") == 2
    # the otpu-top satellite: each rank publishes its matrices into the
    # coord KV at finalize and tpurun prints ONE job-wide matrix — both
    # directions summed into the same table, coll totals across ranks
    assert "job-wide p2p matrix" in r.stderr, r.stderr
    assert "0 -> 1:" in r.stderr and "1 -> 0:" in r.stderr, r.stderr
    assert "coll allreduce: 2 calls" in r.stderr, r.stderr


def test_monitoring_disabled_by_default(tmp_path):
    script = tmp_path / "nomon.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.runtime import monitoring
        w = ompi_tpu.init()
        assert not monitoring.enabled()
        w.allreduce(np.ones(1))
        assert monitoring.coll_counters() == {}
        print("nomon OK")
    """))
    r = _tpurun(2, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("nomon OK") == 2


def test_info_telemetry_lists_schema_and_vars():
    """--telemetry enumerates the declared sample schema, the sampler
    vars, and the flight-recorder settings (registry-enumerated, also
    under --all/--parsable)."""
    from ompi_tpu.runtime import telemetry

    r = _run_info("--telemetry")
    assert r.returncode == 0, r.stderr
    for key in telemetry.SCHEMA:
        assert f"telemetry key {key}:" in r.stdout, key
    for var in ("otpu_telemetry_interval_ms", "otpu_telemetry_jitter",
                "otpu_flight_enable", "otpu_flight_dir",
                "otpu_flight_events"):
        assert var in r.stdout, var
    # under --all and --parsable too
    r_all = _run_info("--all", "--parsable")
    assert r_all.returncode == 0
    assert "telemetry key spc:" in r_all.stdout
    assert "telemetry var otpu_flight_dir:" in r_all.stdout


def test_info_trace_lists_categories_and_vars():
    """--trace enumerates the declared span categories, the flow-key
    categories, and the ring/export/flow vars (registry-enumerated,
    also under --all/--parsable)."""
    from ompi_tpu.runtime import trace

    r = _run_info("--trace")
    assert r.returncode == 0, r.stderr
    for cat in trace.CATEGORIES:
        assert f"trace category {cat}:" in r.stdout, cat
    for fcat in trace.FLOW_CATEGORIES:
        assert f"trace flow key {fcat}:" in r.stdout, fcat
    for var in ("otpu_trace_enable", "otpu_trace_dir",
                "otpu_trace_buffer_events", "otpu_trace_flow"):
        assert var in r.stdout, var
    # under --all and --parsable too
    r_all = _run_info("--all", "--parsable")
    assert r_all.returncode == 0
    assert "trace category pml:" in r_all.stdout
    assert "trace flow key pml_msg:" in r_all.stdout
    assert "trace var otpu_trace_flow:" in r_all.stdout


def test_topo_explicit_only():
    """--all must NOT boot the accelerator runtime for topology; --topo
    opts in (regression guard for the lazy-init guarantee)."""
    r_all = _run_info("--all")
    assert "topo: host" not in r_all.stdout   # "mca topo:" rows still list
    r_topo = _run_info("--topo")
    assert "topo: host" in r_topo.stdout
