"""Hardware topology discovery + process binding (the hwloc analog).

Reference: ``/root/reference/opal/mca/hwloc/`` wraps external hwloc to
answer two questions the runtime keeps asking: (a) what does this host
look like (cores, NUMA nodes) so ranks can be *bound*, and (b) how local
are two peers (same node / same socket) so transports and hierarchical
collectives can be *selected*.  TPU-native, question (b) grows a third
tier: the ICI interconnect — device coordinates in the physical torus
(``jax`` TPU devices expose ``.coords``/``.core_on_chip``), which is what
topo/treematch reordering and coll/han's low/up split key on.

No external library: host facts come from ``os``/``/sys``, device facts
from the jax device list.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import socket
from typing import Optional

# locality flags, opal_hwloc_locality_t analog (monotone: each implies
# the ones above it)
LOC_DIFFERENT_NODE = 0
LOC_SAME_NODE = 1
LOC_SAME_NUMA = 2
LOC_SAME_CORE = 3


@dataclasses.dataclass(frozen=True)
class HostTopology:
    hostname: str
    ncpus_online: int
    cpus_allowed: tuple     # affinity mask of this process
    numa_nodes: tuple       # tuple of (node_id, cpu_tuple)

    @property
    def nnuma(self) -> int:
        return max(1, len(self.numa_nodes))


@dataclasses.dataclass(frozen=True)
class TpuDevice:
    index: int
    platform: str
    coords: Optional[tuple]       # ICI torus coordinates, None off-TPU
    core_on_chip: int


def _read_numa() -> tuple:
    nodes = []
    for path in sorted(glob.glob("/sys/devices/system/node/node[0-9]*")):
        nid = int(os.path.basename(path)[4:])
        try:
            with open(os.path.join(path, "cpulist")) as f:
                cpus = _parse_cpulist(f.read().strip())
        except OSError:
            cpus = ()
        nodes.append((nid, cpus))
    return tuple(nodes)


def _parse_cpulist(s: str) -> tuple:
    cpus = []
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            a, b = part.split("-")
            cpus.extend(range(int(a), int(b) + 1))
        else:
            cpus.append(int(part))
    return tuple(cpus)


_host_cache: Optional[HostTopology] = None
_orig_affinity: Optional[tuple] = None   # pre-binding mask, captured once


def _current_affinity() -> tuple:
    try:
        return tuple(sorted(os.sched_getaffinity(0)))
    except AttributeError:              # non-Linux
        return tuple(range(os.cpu_count() or 1))


def host_topology(refresh: bool = False) -> HostTopology:
    global _host_cache, _orig_affinity
    if _orig_affinity is None:
        _orig_affinity = _current_affinity()
    if _host_cache is None or refresh:
        _host_cache = HostTopology(
            hostname=socket.gethostname(),
            ncpus_online=os.cpu_count() or 1,
            cpus_allowed=_current_affinity(),
            numa_nodes=_read_numa(),
        )
    return _host_cache


def device_topology(devices=None) -> list:
    """Describe the jax device list (ICI coords on real TPU)."""
    if devices is None:
        import jax

        devices = jax.devices()
    out = []
    for i, d in enumerate(devices):
        out.append(TpuDevice(
            index=i,
            platform=getattr(d, "platform", "unknown"),
            coords=tuple(d.coords) if getattr(d, "coords", None) is not None
            else None,
            core_on_chip=int(getattr(d, "core_on_chip", 0) or 0),
        ))
    return out


def ici_mesh_shape(devices=None) -> Optional[tuple]:
    """Infer the physical ICI torus extent from device coordinates.

    The treematch/coll-han analog of reading the node hierarchy: the
    (x, y, z) extents let callers lay mesh axes along physical rings.
    """
    devs = device_topology(devices)
    coords = [d.coords for d in devs if d.coords is not None]
    if not coords:
        return None
    dims = len(coords[0])
    return tuple(max(c[i] for c in coords) + 1 for i in range(dims))


def compute_binding(rank: int, nranks: int,
                    topo: Optional[HostTopology] = None) -> tuple:
    """Contiguous block partition of allowed CPUs for local rank i of n.

    The ``--bind-to core`` policy (PRRTE's default for np <= 2): each
    rank gets floor(ncpus/nranks) cores, NUMA-contiguous because
    cpus_allowed is sorted.  Returns the cpu tuple (possibly all CPUs
    when there are fewer cores than ranks — oversubscription unbinds,
    like the reference's --oversubscribe).

    Without an explicit ``topo``, partitions the ORIGINAL process mask
    (captured before any bind_self), so init→finalize→init re-binding
    doesn't partition an already-narrowed mask into ever-smaller blocks."""
    if topo is not None:
        cpus = topo.cpus_allowed
    else:
        host_topology()            # ensures _orig_affinity is captured
        cpus = _orig_affinity
    per = len(cpus) // nranks
    if per == 0:
        return cpus
    return cpus[rank * per:(rank + 1) * per]


def bind_self(cpus) -> bool:
    """Apply a CPU binding to this process; False if unsupported."""
    try:
        os.sched_setaffinity(0, set(cpus))
        return True
    except (AttributeError, OSError):
        return False


def locality(a_host: str, b_host: str, a_cpus=None, b_cpus=None,
             numa_nodes=None, ncpus: Optional[int] = None) -> int:
    """Locality tier between two ranks from their modexed facts.

    Overlapping masks only mean SAME_CORE when the ranks are actually
    *bound* (mask smaller than the whole host) — two unbound ranks
    trivially share the full mask and say nothing about core sharing."""
    if a_host != b_host:
        return LOC_DIFFERENT_NODE
    if a_cpus and b_cpus:
        sa, sb = set(a_cpus), set(b_cpus)
        total = ncpus if ncpus is not None else (os.cpu_count() or 1)
        bound = len(sa) < total and len(sb) < total
        if bound and sa & sb:
            return LOC_SAME_CORE
        for _nid, node_cpus in (numa_nodes or ()):
            nc = set(node_cpus)
            if sa & nc and sb & nc and bound:
                return LOC_SAME_NUMA
    return LOC_SAME_NODE


def summary() -> str:
    t = host_topology()
    lines = [f"host: {t.hostname}  cpus: {t.ncpus_online} "
             f"(allowed {len(t.cpus_allowed)})  numa: {t.nnuma}"]
    # device facts are best-effort: an info tool must not require (or
    # boot) an accelerator runtime just to print host topology
    try:
        devs = device_topology()
        mesh = ici_mesh_shape(None)
    except Exception as exc:
        lines.append(f"  devices: unavailable ({type(exc).__name__})")
        return "\n".join(lines)
    for d in devs:
        lines.append(f"  device[{d.index}] {d.platform} coords={d.coords} "
                     f"core={d.core_on_chip}")
    if mesh:
        lines.append(f"ici mesh shape: {mesh}")
    return "\n".join(lines)
