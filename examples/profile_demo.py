"""otpu-prof demo — where does a message's latency actually go?

Self-launching: run this script directly (no tpurun needed) and it

1. runs a 3-rank loopback allreduce job with the per-message stage
   clocks and the sampling profiler armed (``--mca otpu_profile_stages
   1 --mca otpu_profile_interval_ms 10``), collectives routed over the
   pml/btl datapath the clocks instrument,
2. runs ``otpu_analyze`` over the trace directory and prints the
   per-rank host-overhead table: the per-message
   pack/queue/wire/parse/deliver breakdown, the exposed-host fraction,
   and the profiler's phase/GIL estimates.

Inside a real job the same data is produced by::

    tpurun -n N --mca otpu_profile_stages 1 ... app.py
    python -m ompi_tpu.tools.otpu_analyze <otpu_trace_dir>
"""
import os
import subprocess
import sys
import tempfile


def main() -> int:
    from ompi_tpu.tools import otpu_analyze

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "telemetry_worker.py")
    tdir = tempfile.mkdtemp(prefix="otpu-prof-demo-")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TW_ITERS="30")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)

    print("== 1. 3-rank loopback allreduce job, stage clocks armed ==")
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
         "--mca", "otpu_trace_enable", "1",
         "--mca", "otpu_trace_dir", tdir,
         "--mca", "otpu_profile_stages", "1",
         "--mca", "otpu_profile_interval_ms", "10",
         "--mca", "otpu_coll_sm_coll_priority", "0",
         sys.executable, worker],
        env=env, capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        print(r.stdout + r.stderr)
        return 1

    print("== 2. per-message breakdown (otpu_analyze) ==")
    otpu_analyze.main([tdir])
    return 0


if __name__ == "__main__":
    sys.exit(main())
