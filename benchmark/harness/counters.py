"""Counters the benchmark reads: JAX's own compile events (a copy of
``chip_smoke.CompileCounters``) and the program's SPC device counters."""
from __future__ import annotations


class CompileCounters:
    """What JAX itself reports about compilation: seconds in the backend
    compiler, compile requests, and persistent-cache hits and writes.
    ``builds`` counts every program built or fetched: it must not move
    inside a measured window."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = self.requests = self.hits = self.writes = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1     # recorded where an entry is written

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    @property
    def builds(self) -> int:
        return self.compiles + self.requests

    def as_dict(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "requests": self.requests, "hits": self.hits,
                "writes": self.writes}


#: the program's SPC counters as they stood when set-up ended: what a
#: metric that moves ``setup_s`` may count (``run.py`` marks it once, before
#: the harness's own check builds its reference programs; a run is a
#: process, so one mark a process)
AT_SETUP: dict = {}


def mark_setup() -> None:
    from ompi_tpu.runtime import spc

    AT_SETUP.clear()
    AT_SETUP.update(spc.counters())


def device_collectives() -> int:
    """The program's count of device collectives issued (two integer
    adds per call in ``runtime/spc.bump_device``)."""
    from ompi_tpu.runtime import spc

    return int(spc.read("device_collectives"))
