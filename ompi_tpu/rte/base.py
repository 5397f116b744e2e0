"""RTE adapters: the process-model abstraction under the runtime.

Equivalent of the PMIx client surface used by the reference
(``ompi/runtime/ompi_rte.c:568`` ``PMIx_Init``; modex put/get; fences;
events): an Rte provides identity (rank/size), the wire-up KV space, barriers
outside MPI, locality, and — TPU-native — the device mesh that the coll/xla
component compiles against.
"""
from __future__ import annotations

import os
import socket
import threading
from typing import Any, Optional

import numpy as np


class Rte:
    """Interface. ``my_world_rank``/``world_size`` are process identity."""

    my_world_rank: int = 0
    world_size: int = 1
    is_device_world: bool = False

    def modex_put(self, key: str, value: Any) -> None:
        raise NotImplementedError

    def modex_get(self, rank: int, key: str, wait: bool = True) -> Any:
        """Fetch a peer's modexed value; ``wait=False`` returns None
        instead of blocking when the key hasn't been published yet."""
        raise NotImplementedError

    def fence(self) -> None:
        """Out-of-band barrier + modex publication (``PMIx_Fence``)."""
        raise NotImplementedError

    def locality_color(self, split_type: str) -> int:
        return 0  # single host / single slice

    def node_of(self, world_rank: int) -> Optional[Any]:
        """Node identity of a peer (None if unknown) — the shared
        locality lookup han/coll-sm/osc-rdma/treematch all need."""
        return None

    def event_notify(self, event: str, payload: Any) -> None:
        pass

    def finalize(self) -> None:
        pass

    # device resources ---------------------------------------------------
    @property
    def mesh(self):
        return None

    def device_of(self, world_rank: int):
        return None


class DeviceWorldRte(Rte):
    """TPU-native SPMD world: ranks = devices of a 1-D mesh in one process.

    The controller drives all ranks ("conductor" model): host p2p between
    device-ranks runs through the in-process matching engine, device
    collectives compile to one XLA program over the ICI mesh axis.  This is
    the analog of `mpirun -n N --oversubscribe` on one node (every BTL is
    btl/self-reachable) but with the ranks being real accelerator devices.
    """

    is_device_world = True

    def __init__(self, devices=None, axis_name: str = "world") -> None:
        import jax

        from ompi_tpu.base.jaxenv import compile_cache_dir

        compile_cache_dir()   # before this world's first compile
        if devices is None:
            devices = jax.devices()
        self.devices = list(devices)
        self.axis_name = axis_name
        self.world_size = len(self.devices)
        self.my_world_rank = 0  # the conductor acts for every rank
        from jax.sharding import Mesh

        self._mesh = Mesh(np.array(self.devices), (axis_name,))
        self._kv: dict[tuple[int, str], Any] = {}
        self._lock = threading.Lock()

    @property
    def mesh(self):
        return self._mesh

    def device_of(self, world_rank: int):
        return self.devices[world_rank]

    def modex_put(self, key: str, value: Any, rank: Optional[int] = None) -> None:
        with self._lock:
            self._kv[(self.my_world_rank if rank is None else rank, key)] = value

    def modex_get(self, rank: int, key: str, wait: bool = True) -> Any:
        # wait is part of the modex signature (ProcRte blocks on missing
        # keys); in-process KV has nothing to wait for
        with self._lock:
            return self._kv.get((rank, key))

    def fence(self) -> None:
        pass  # single process: nothing to synchronize out-of-band

    def locality_color(self, split_type: str) -> int:
        return 0


def detect() -> Rte:
    """Pick the RTE for this process (``ompi_rte_init`` equivalent).

    Launched under ``tpurun`` (OTPU_RANK/OTPU_NPROCS in env) → the
    multi-process ProcRte (``ompi_tpu.rte.proc``).  Otherwise the
    device-world SPMD model over local jax devices — and a JAX that
    cannot open them raises: a size-1 host world in their place would
    quietly take every ``*_array`` call off the device path.
    """
    if "OTPU_RANK" in os.environ and "OTPU_NPROCS" in os.environ:
        from ompi_tpu.rte.proc import ProcRte

        return ProcRte()
    return DeviceWorldRte()
