"""Telemetry-plane test worker: a steady loop of 4KB allreduces.

Two modes (env-selected):

- ``TW_SECS`` (default 4.0): run for a wall-clock window — the live
  otpu_top attach test needs a job that outlives several sampler
  intervals;
- ``TW_ITERS``: run exactly N rounds instead — the otpu_analyze
  straggler test needs a deterministic round count on every rank.
"""
import os
import time

import numpy as np

import ompi_tpu
from ompi_tpu.api import op
from ompi_tpu.ft import chaos

w = ompi_tpu.init()
x = np.ones(1024, np.float32)          # 4KB payload

iters = os.environ.get("TW_ITERS")
if iters is not None:
    for _ in range(int(iters)):
        if chaos.enabled:
            # the designed-straggler pacing point: 'delay:ms=40,rank=2,
            # site=step' makes rank 2 arrive late at every collective
            chaos.pace("step")
        w.allreduce(x, op.SUM)
else:
    # time-based mode with a COLLECTIVE exit decision: rank 0 owns the
    # deadline and the continue-flag allreduce (MIN) keeps every rank
    # doing the same number of rounds — per-rank deadlines would leave
    # finished ranks' peers blocked in a collective nobody else enters
    deadline = time.monotonic() + float(os.environ.get("TW_SECS", "4.0"))
    cont = np.ones(1, np.float32)
    while True:
        if w.rank == 0 and time.monotonic() >= deadline:
            cont = np.zeros(1, np.float32)
        flag = np.asarray(w.allreduce(cont, op.MIN))
        if float(flag[0]) < 0.5:
            break
        if chaos.enabled:
            chaos.pace("step")
        w.allreduce(x, op.SUM)
print(f"TELEMETRY WORKER DONE {w.rank}", flush=True)
ompi_tpu.finalize()
