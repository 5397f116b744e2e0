"""otpu-crit test worker: a fixed number of step-spanned rounds, each
one chaos-paceable ('delay:ms=40,rank=2,site=step' designs ONE slow
rank), mixing a collective with a p2p ring exchange so the merged
timeline carries both barrier edges (coll round keys) and message
edges (pml flow keys)."""
import os

import numpy as np

import ompi_tpu
from ompi_tpu.api import op
from ompi_tpu.ft import chaos
from ompi_tpu.runtime import trace

w = ompi_tpu.init()
x = np.ones(1024, np.float32)          # 4KB payload
inbuf = np.empty_like(x)
right = (w.rank + 1) % w.size
left = (w.rank - 1) % w.size

for i in range(int(os.environ.get("CW_ITERS", "20"))):
    t0 = trace.now() if trace.enabled else 0
    if chaos.enabled:
        # the designed-straggler pacing point: the delay lands INSIDE
        # the step window, so the critical path must attribute the
        # step to the paced rank's own timeline
        chaos.pace("step")
    w.allreduce(x, op.SUM)
    w.sendrecv(x, right, inbuf, source=left, sendtag=5, recvtag=5)
    if trace.enabled:
        trace.span("step", "step", t0, args={"step": i})
print(f"CRIT WORKER DONE {w.rank}", flush=True)
ompi_tpu.finalize()
