"""ompi_tpu.instance — the runtime instance behind MPI-4 Sessions.

Re-design of ``ompi/instance/instance.c``: Open MPI 5.x made the
*instance* the true owner of runtime boot — ``MPI_Session_init`` and
world-model ``MPI_Init`` both just acquire the one underlying instance,
a refcount tracks how many owners (open sessions + the implicit world)
are alive, and only the LAST release tears the RTE down
(``ompi_mpi_instance_init``/``_finalize`` with ``instance_lock`` +
``ompi_instance_count``).  Consequences this module is careful to keep:

* N sessions and world init share ONE RTE/coord boot (one modex fence,
  one pml selection) — acquiring an already-booted instance is a
  refcount bump, nothing else;
* ``MPI_Init`` after ``MPI_Finalize`` works: when the count hits zero
  the boot state machine returns to ground and the next acquire boots
  fresh (the MPI-4 relaxation of the old once-per-process rule);
* process sets are an instance-level concept that exists BEFORE any
  communicator does: builtin ``mpi://WORLD`` / ``mpi://SELF`` plus
  whatever the coordination service advertises (per-host sets, user
  ``tpurun --pset`` sets, dynamic sets published on spawn/shrink).

TPU hat: the instance also owns the *device world*.  On boot under
``tpurun --device-world`` it initializes ``jax.distributed`` —
coordinator address from the coord service KV, ``process_id`` from the
job rank map — so the global device mesh spans processes and ``coll/
xla`` device collectives finally cross process boundaries (the
PMIx-shaped role of ``ompi_rte.c:568`` worn by the device path).
"""
from __future__ import annotations

import atexit
import os
import threading
from typing import Optional

from ompi_tpu.base import mca
from ompi_tpu.base.var import mark_runtime_initialized, registry

#: MPI-4 builtin process-set names (MPI 4.0 §11.3.2)
PSET_WORLD = "mpi://WORLD"
PSET_SELF = "mpi://SELF"

_lock = threading.RLock()
_refcount = 0
_instance: Optional["Instance"] = None
_atexit_armed = False


class Instance:
    """The booted runtime instance: RTE + selected pml + pset access.

    Never constructed directly — :func:`acquire` boots (or refcounts)
    the process-wide instance; :func:`release` drops one reference and
    tears down on the last.
    """

    def __init__(self) -> None:
        self.rte = None
        self.pml = None
        self._fenced = False
        self._torn_down = False

    # -- boot ------------------------------------------------------------
    def _boot(self, argv=None, devices=None, rte=None) -> None:
        from ompi_tpu.runtime import interlib, spc, trace

        if argv:
            registry.parse_cli(argv)
        t_boot = trace.now()

        # RTE wire-up (ompi_mpi_init.c:516 → PMIx_Init equivalent); a
        # ProcRte constructor is the coord-service connect
        from ompi_tpu.rte import base as rte_base

        t0 = trace.now()
        if rte is not None:
            self.rte = rte
        elif devices is not None:
            self.rte = rte_base.DeviceWorldRte(devices)
        else:
            self.rte = rte_base.detect()
        trace.span("coord_connect", "boot", t0)

        spc.init()
        # otpu-trace (span ring buffer + latency-histogram pvars); the
        # enable cvar was applied at registration from env/file and
        # again from the CLI parse above
        trace.init()

        # a re-boot after a prior teardown may use the work pool again
        from ompi_tpu.mca.threads import base as _threads_reopen

        _threads_reopen.reopen_pool()

        # record the booting thread (MPI_Is_thread_main anchor —
        # overrides any earlier library register() from a worker thread)
        interlib.note_main_thread(force=True)

        # CPU binding + topology modex (hwloc analog; the reference does
        # binding in PRRTE pre-exec, we do it first thing at boot)
        from ompi_tpu.base import hwloc

        if os.environ.get("OTPU_BIND_POLICY") == "core" and \
                hasattr(self.rte, "my_world_rank"):
            local_n = int(os.environ.get("OTPU_LOCAL_NRANKS", "1"))
            cpus = hwloc.compute_binding(
                self.rte.my_world_rank % max(1, local_n), max(1, local_n))
            hwloc.bind_self(cpus)
        if hasattr(self.rte, "modex_put"):
            topo = hwloc.host_topology(refresh=True)
            self.rte.modex_put("cpus", list(topo.cpus_allowed))

        # device-world boot: jax.distributed over the job's processes
        # (before the modex fence, so the fence also orders device boot)
        t0 = trace.now()
        self._boot_device_world()
        trace.span("jax_distributed_init", "boot", t0)

        # pml selection (ompi_mpi_init.c:630)
        pml_fw = mca.framework("pml", "point-to-point messaging layer")
        pml_comp = pml_fw.select()
        if pml_comp is None:
            raise RuntimeError("no pml component available")
        pml_module = pml_comp.get_module(self.rte)

        # pml/monitoring interposition (per-peer traffic matrices)
        from ompi_tpu.runtime import monitoring

        pml_module = monitoring.maybe_wrap_pml(pml_module)

        # vprotocol/pessimist interposition (message-event logging)
        from ompi_tpu.mca.pml import vprotocol

        pml_module = vprotocol.maybe_wrap_pml(pml_module, self.rte)
        self.pml = pml_module

        # modex exchange of endpoints (ompi_mpi_init.c:682-701)
        t0 = trace.now()
        self.rte.fence()
        trace.span("modex_fence", "boot", t0)

        # CIDs 0/1 belong to the predefined WORLD/SELF comms whether or
        # not the world model ever initializes — a session-built comm
        # grabbing cid 0 before a later MPI_Init would alias the
        # revocation key space (the reference likewise pre-reserves the
        # predefined communicators' ids)
        from ompi_tpu.runtime import init as _rt

        _rt.reserve_cid(0)
        _rt.reserve_cid(1)

        mark_runtime_initialized(True)

        # live telemetry plane + crash-time flight recorder: both are
        # no-ops unless their vars/triggers arm them, and both need the
        # coord client this boot just established
        from ompi_tpu.runtime import flight, profile, telemetry

        if getattr(self.rte, "client", None) is not None:
            flight.arm(self.rte)
            telemetry.start(self.rte)
        # otpu-prof needs no coord service: stage clocks are var-armed,
        # the sampling profiler publishes through telemetry if running
        profile.start(self.rte)
        trace.span("instance_boot", "boot", t_boot)

    def _boot_device_world(self) -> None:
        """Initialize ``jax.distributed`` for a multi-process device
        world (opt-in: the launcher sets ``OTPU_DEVICE_WORLD``).

        The coordinator address is read from the coord service KV
        (``__jax_coord__``, published by tpurun) with the env var
        ``OTPU_JAX_COORD`` as fallback; ``process_id`` comes from the
        job rank map (a spawned job would need its own coordinator, so
        only the primary job boots one).  On the CPU backend the gloo
        collectives implementation is selected — the stock CPU client
        rejects multiprocess computations outright.
        """
        rte = self.rte
        if os.environ.get("OTPU_DEVICE_WORLD", "") in ("", "0"):
            return
        if rte.is_device_world or getattr(rte, "job", "0") != "0":
            return
        # env override first: a KV wait would stall 30 s before the
        # documented fallback is even consulted
        addr = os.environ.get("OTPU_JAX_COORD")
        client = getattr(rte, "client", None)
        if not addr and client is not None:
            try:
                addr = client.get(-1, "__jax_coord__", wait=True,
                                  timeout=30.0)
            except Exception:
                addr = None
        if not addr:
            raise RuntimeError(
                "OTPU_DEVICE_WORLD is set but no jax coordinator address "
                "was published (launch with tpurun --device-world)")
        import jax

        from ompi_tpu.base.jaxenv import compile_cache_dir

        compile_cache_dir()   # before this world's first compile
        if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        procs = list(getattr(rte, "job_ranks", range(rte.world_size)))
        from jax._src import distributed as _jd

        if getattr(_jd.global_state, "client", None) is None:
            jax.distributed.initialize(
                str(addr), num_processes=len(procs),
                process_id=procs.index(rte.my_world_rank))
        rte.device_world_booted = True
        rte.global_devices = jax.devices()
        rte.local_devices = jax.local_devices()

    # -- teardown --------------------------------------------------------
    def _fence_final(self) -> None:
        """Pre-teardown synchronisation (ompi_mpi_finalize's barrier) —
        one-shot: a fast-exiting rank must not unlink shared segments a
        slower peer is still attaching during ITS boot."""
        if self._fenced:
            return
        self._fenced = True
        fence_final = getattr(self.rte, "fence_final", None)
        if fence_final is not None:
            try:
                fence_final()
            except Exception:
                pass   # coord gone / timeout: peers are exiting too

    def _teardown(self) -> None:
        if self._torn_down:
            return
        self._torn_down = True
        try:
            self._fence_final()
            # trace export needs the coord client (KV publish + clock
            # offset), so it runs before rte.finalize tears it down
            from ompi_tpu.runtime import flight as _flight
            from ompi_tpu.runtime import monitoring as _monitoring
            from ompi_tpu.runtime import telemetry as _telemetry
            from ompi_tpu.runtime import trace as _trace

            try:
                _trace.finalize_export(self.rte)
            except Exception:
                pass   # observability must never break teardown
            try:
                # survivor post-mortem: if this job saw peer failures,
                # the ring now holds the whole recovery — dump it for
                # the launcher's flight bundle
                _flight.maybe_dump_postmortem(self.rte)
            except Exception:
                pass
            try:
                _monitoring.finalize_publish(self.rte)
            except Exception:
                pass
            try:
                _telemetry.stop()
                _flight.disarm()
            except Exception:
                pass
            try:
                from ompi_tpu.runtime import profile as _profile

                _profile.stop()
            except Exception:
                pass
            # release per-comm coll resources of any communicator the
            # user never freed (ompi_mpi_finalize destroys remaining
            # comms the same way) — shared segments must unmap here, not
            # in interpreter-exit GC where exported views race __del__
            from ompi_tpu.api import comm as _comm_mod

            for c in _comm_mod.live_comms():
                if not getattr(c, "freed", False):
                    try:
                        c.release_coll_modules()
                    except Exception:
                        pass
            if self.pml is not None:
                fin = getattr(self.pml, "finalize", None)
                if fin is not None:
                    try:
                        fin()
                    except Exception:
                        pass   # a dead peer/coord must not wedge teardown
            if self.rte is not None:
                try:
                    self.rte.finalize()
                except Exception:
                    pass
        finally:
            # ground state must be restored even if a step above threw:
            # the next boot in this process (tests, re-init) depends on
            # the pool/mca/CID/registry flags being reset
            from ompi_tpu.mca.threads import base as _threads_base

            _threads_base.shutdown_pool(permanent=True)
            mca.close_all()
            from ompi_tpu.runtime import init as _rt
            from ompi_tpu.runtime import progress

            progress.reset_for_testing()
            _rt.clear_cid_space()
            mark_runtime_initialized(False)

    # -- process sets ----------------------------------------------------
    def pset_names(self) -> list:
        """Every process-set name this instance can resolve: the MPI-4
        builtins plus whatever the coord service advertises."""
        names = [PSET_WORLD, PSET_SELF]
        client = getattr(self.rte, "client", None)
        if client is not None:
            try:
                for row in client.pset_list():
                    if row["name"] not in names:
                        names.append(row["name"])
            except Exception:
                pass   # coord gone: the builtins still resolve
        return names

    def pset_members(self, name: str) -> list:
        """World ranks of a named pset (raises on an unknown name)."""
        from ompi_tpu.api.errors import ErrorClass, MpiError

        rte = self.rte
        if name == PSET_WORLD:
            return list(getattr(rte, "job_ranks",
                                range(rte.world_size)))
        if name == PSET_SELF:
            return [rte.my_world_rank]
        client = getattr(rte, "client", None)
        entry = None
        if client is not None:
            try:
                entry = client.pset_get(name)
            except Exception:
                entry = None
        if entry is None:
            raise MpiError(ErrorClass.ERR_ARG,
                           f"unknown process set {name!r}")
        return [int(m) for m in entry["members"]]

    def pset_source(self, name: str) -> str:
        if name in (PSET_WORLD, PSET_SELF):
            return "builtin"
        client = getattr(self.rte, "client", None)
        if client is not None:
            try:
                entry = client.pset_get(name)
                if entry is not None:
                    return str(entry.get("source", "coord"))
            except Exception:
                pass
        return "unknown"

    def pset_info(self, name: str):
        """``MPI_Session_get_pset_info``: at least ``mpi_size`` (MPI-4
        §11.3.3), plus membership and origin for introspection."""
        from ompi_tpu.api.info import Info

        members = self.pset_members(name)
        return Info({
            "mpi_size": str(len(members)),
            "otpu_members": ",".join(str(m) for m in members),
            "otpu_source": self.pset_source(name),
        })


# -- module-level acquire/release (the ompi_instance_count discipline) --

def acquire(argv=None, devices=None, rte=None) -> Instance:
    """Acquire the process-wide instance, booting the RTE on the first
    reference.  ``argv``/``devices``/``rte`` only matter for the boot;
    an already-booted instance ignores them (document over surprise:
    the first owner decides the process model, like the reference)."""
    global _refcount, _instance, _atexit_armed
    with _lock:
        if _instance is None:
            inst = Instance()
            inst._boot(argv=argv, devices=devices, rte=rte)
            _instance = inst
            if not _atexit_armed:
                _atexit_armed = True
                atexit.register(_atexit_teardown)
        _refcount += 1
        return _instance


def release() -> int:
    """Drop one reference; the last release tears the runtime down.
    Returns the remaining reference count."""
    global _refcount, _instance
    with _lock:
        if _instance is None:
            return 0
        _refcount -= 1
        if _refcount > 0:
            return _refcount
        inst, _instance = _instance, None
        _refcount = 0
        inst._teardown()
        return 0


def current() -> Optional[Instance]:
    """The booted instance, or None — never boots as a side effect."""
    return _instance


def refcount() -> int:
    with _lock:
        return _refcount


def _atexit_teardown() -> None:
    """Interpreter exit with sessions still open: drain them (the
    world's own atexit finalize ran first — atexit is LIFO and the world
    registers after the instance boots)."""
    global _refcount, _instance
    with _lock:
        if _instance is None:
            return
        inst, _instance = _instance, None
        _refcount = 0
    try:
        inst._teardown()
    except Exception:
        pass


def reset_for_testing() -> None:
    """Force-release every reference and tear down (tests only)."""
    _atexit_teardown()
