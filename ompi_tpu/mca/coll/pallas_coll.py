"""coll/pallas — explicit remote-DMA ring collectives (ICI p2p path).

Slots in below coll/xla (priority 85 < 90): XLA's compiler-scheduled
collectives stay the default, and this component is the explicit-schedule
alternative — ring allreduce / reduce-scatter / all-gather / pipelined
bcast / neighbor permute written directly against the interconnect with
``pltpu.make_async_remote_copy`` (``ompi_tpu/ops/pallas_collectives.py``).
Reductions cover sum/max/min/prod; payloads above ``vmem_max_bytes``
use the segmented HBM-resident kernels (bounded VMEM window), so the
size ceiling is HBM (``max_bytes``), not VMEM; ``bidirectional`` routes
fused-size all-reduces over both ICI directions at once.  Raise
``--mca coll_pallas_priority 95`` to make it own these slots; any call
shape it does not cover (MINLOC/user ops, general permutations) delegates
to the next module in the comm's stack, the way coll/tuned falls through
to coll/basic.

Capability probe: real multi-chip TPU runs the compiled kernels;
elsewhere (tests, virtual CPU meshes) they run in Pallas interpreter
mode — override with ``--mca coll_pallas_interpret 0/1``.

Reference slot: the explicit BTL RDMA transport
(``opal/mca/btl/btl.h:949,987``) + its ring schedules
(``coll_base_allreduce.c:341``), per SURVEY.md §2.6's "Pallas remote
DMA" row.
"""
from __future__ import annotations

import numpy as np

from ompi_tpu.api import op as op_mod
from ompi_tpu.base.mca import Component
from ompi_tpu.base.var import VarType


#: MPI op name -> ring-kernel fold name (ompi_tpu/ops/pallas_collectives)
_RING_OPS = {"SUM": "sum", "MAX": "max", "MIN": "min", "PROD": "prod"}

#: per-rank payload ceiling when the kernels run in the Pallas
#: interpreter (tests, virtual meshes): the interpreter executes the
#: segment loop in Python, so routing arbitrarily large payloads to it
#: would turn sub-second coll/xla calls into minutes — above this,
#: delegate regardless of max_bytes
_INTERPRET_MAX_BYTES = 16 << 20


class PallasCollModule:
    def __init__(self, comm, devices, axis_name: str, interpret: bool,
                 max_bytes: int, vmem_max_bytes: int,
                 seg_bytes: int, bidirectional: bool,
                 min_bytes: int = 0, wire16: bool = False) -> None:
        import jax
        from jax.sharding import Mesh

        self.devices = list(devices)
        self.axis = axis_name
        self.mesh = Mesh(np.array(self.devices), (axis_name,))
        self.n = len(self.devices)
        self.interpret = interpret
        self.max_bytes = max_bytes
        self.min_bytes = min_bytes
        self.vmem_max_bytes = vmem_max_bytes
        self.seg_bytes = seg_bytes
        self.bidirectional = bidirectional
        self.wire16 = wire16
        self._jax_array = jax.Array
        self._fallback = None   # resolved at comm_enable

    def comm_enable(self, comm) -> None:
        # next-lower provider of the device-array slots (normally
        # coll/xla): unsupported calls fall through to it
        from ompi_tpu.mca.coll.xla import XlaCollModule

        self._fallback = next(
            (m for m in comm.coll_modules if isinstance(m, XlaCollModule)),
            None)

    # -- helpers ---------------------------------------------------------
    def _delegate(self, name, comm, x, *args, **kw):
        if self._fallback is None:
            from ompi_tpu.api.errors import ErrorClass, MpiError

            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           f"coll/pallas cannot run {name} and no "
                           "fallback module is present")
        return getattr(self._fallback, name)(comm, x, *args, **kw)

    def _place(self, comm, x):
        if isinstance(x, self._jax_array):
            return x
        if self._fallback is not None:
            return self._fallback._check(comm, x)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(
            np.asarray(x), NamedSharding(self.mesh, P(self.axis)))

    def _size_ok(self, x) -> bool:
        cap = self.max_bytes
        if self.interpret:
            cap = min(cap, _INTERPRET_MAX_BYTES)
        per_rank = x.nbytes // max(1, self.n)
        return self.min_bytes <= per_rank <= cap

    def _supported(self, x) -> bool:
        return x.dtype.kind == "f" and self._size_ok(x)

    def _route(self, x):
        """Pick the accumulator regime from the per-rank payload size:
        fused VMEM kernel below ``vmem_max_bytes``, segmented HBM kernel
        (bounded VMEM window of ``seg_bytes``) above — the selection the
        reference's tuned ladder does between its linear and segmented
        rings (``coll_base_allreduce.c:618``)."""
        per_rank = x.nbytes // max(1, self.n)
        if per_rank > self.vmem_max_bytes:
            seg_elems = max(1, self.seg_bytes // x.dtype.itemsize)
            return (("seg_bidi" if self.bidirectional else "seg"),
                    seg_elems)
        if self.bidirectional:
            return "bidi", None
        return "fused", None

    def _allreduce_variant(self, x, ring_op):
        """ONE routing rule for one-shot AND persistent allreduce (a
        persistent handle must never diverge numerically from the
        one-shot slot it mirrors)."""
        variant, seg_elems = self._route(x)
        if (self.wire16 and ring_op == "sum"
                and str(x.dtype) == "float32" and variant == "fused"):
            # opt-in compressed wire (f32 acc, bf16 bytes); only the
            # fused regime has a wire16 kernel so far
            variant = "wire16"
        return variant, seg_elems

    def _reduce_scatter_variant(self, x, ring_op):
        """ONE routing rule for one-shot AND persistent reduce_scatter
        (same never-diverge contract as ``_allreduce_variant``)."""
        variant, seg_elems = self._route(x)
        if variant == "bidi":        # no bidi reduce-scatter kernel (yet)
            variant, seg_elems = "fused", None
        elif variant == "seg_bidi":  # ...so large payloads keep the
            variant = "seg"          # segmented HBM bound unidirectional
        if (self.wire16 and ring_op == "sum"
                and str(x.dtype) == "float32" and variant == "fused"):
            variant = "wire16"       # same opt-in codec as allreduce
        return variant, seg_elems

    # -- collective slots ------------------------------------------------
    def allreduce_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        x = self._place(comm, x)
        ring_op = _RING_OPS.get(op.name)
        if ring_op is None or not self._supported(x):
            return self._delegate("allreduce_array", comm, x, op)
        from ompi_tpu.ops import pallas_collectives as pc

        variant, seg_elems = self._allreduce_variant(x, ring_op)
        return pc.all_reduce(x, self.mesh, self.axis, ring_op,
                             interpret=self.interpret, variant=variant,
                             seg_elems=seg_elems)

    def allgather_array(self, comm, x):
        x = self._place(comm, x)
        if not self._supported(x):
            return self._delegate("allgather_array", comm, x)
        from ompi_tpu.ops import pallas_collectives as pc

        # same duplex opt-in as the reduce rings: both ICI directions
        # carry blocks, ceil((n-1)/2) steps instead of n-1
        variant = "bidi" if self.bidirectional else "ring"
        return pc.all_gather(x, self.mesh, self.axis,
                             interpret=self.interpret, variant=variant)

    def reduce_scatter_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        x = self._place(comm, x)
        ring_op = _RING_OPS.get(op.name)
        if ring_op is None or not self._supported(x):
            return self._delegate("reduce_scatter_array", comm, x, op)
        from ompi_tpu.ops import pallas_collectives as pc

        variant, seg_elems = self._reduce_scatter_variant(x, ring_op)
        return pc.reduce_scatter(x, self.mesh, self.axis, ring_op,
                                 interpret=self.interpret, variant=variant,
                                 seg_elems=seg_elems)

    def alltoall_array(self, comm, x, **typed):
        if typed:       # derived datatypes: coll/xla's one typed program
            return self._delegate("alltoall_array", comm, x, **typed)
        x = self._place(comm, x)
        # pure DMA, no arithmetic: any dtype qualifies — only size and
        # the (n, n, *S) layout gate (a malformed shape must surface as
        # coll/xla's MpiError, not an out-of-bounds remote DMA)
        if (not self._size_ok(x) or x.ndim < 2
                or x.shape[0] != self.n or x.shape[1] != self.n):
            return self._delegate("alltoall_array", comm, x)
        from ompi_tpu.ops import pallas_collectives as pc

        return pc.all_to_all(x, self.mesh, self.axis,
                             interpret=self.interpret)

    def alltoallv_array(self, comm, x, counts):
        """True ragged alltoallv: per-pair explicit chunked DMAs sized
        by the runtime counts table (``ops.pallas_collectives.
        all_to_all_v``) instead of coll/xla's padded all_to_all +
        host-side slicing — wire bytes follow the raggedness, the MoE/
        EP dispatch contract (``coll_base_alltoall.c`` pairwise)."""
        x = self._place(comm, x)
        if (not self._size_ok(x) or x.ndim != 4
                or x.shape[0] != self.n or x.shape[1] != self.n
                or x.shape[3] % 128 != 0):
            return self._delegate("alltoallv_array", comm, x, counts)
        import numpy as np

        if np.asarray(counts).shape != (self.n, self.n):
            # same error contract as coll/xla: malformed counts surface
            # as MpiError, never as a bad SMEM table / IndexError
            from ompi_tpu.api.errors import ErrorClass, MpiError

            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"alltoallv needs an ({self.n}, {self.n}) counts "
                f"table, got {np.asarray(counts).shape}")
        from ompi_tpu.ops import pallas_collectives as pc

        full = pc.all_to_all_v(x, np.asarray(counts, np.int32),
                               self.mesh, self.axis,
                               interpret=self.interpret)
        # same return contract as coll/xla's alltoallv_array: sliced
        # zero-copy views, out[i][j] = what rank i received from j
        return [[full[i, j, :int(counts[j][i])] for j in range(self.n)]
                for i in range(self.n)]

    def allgatherv_array(self, comm, x, counts):
        """True ragged allgatherv: the ring forwards each block as
        count-sized chunked DMAs (``ops.pallas_collectives.
        all_gather_v``) instead of coll/xla's padded all_gather —
        wire bytes follow the raggedness."""
        x = self._place(comm, x)
        if (not self._size_ok(x) or x.ndim != 3
                or x.shape[0] != self.n or x.shape[2] % 128 != 0):
            return self._delegate("allgatherv_array", comm, x, counts)
        if len(counts) != self.n:
            # coll/xla's error contract (allgatherv_array)
            from ompi_tpu.api.errors import ErrorClass, MpiError

            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"allgatherv needs {self.n} counts, got {len(counts)}")
        from ompi_tpu.ops import pallas_collectives as pc

        full = pc.all_gather_v(x, list(counts), self.mesh, self.axis,
                               interpret=self.interpret)
        # coll/xla return contract: per-rank views sliced to counts[i]
        return [full[i, :int(counts[i])] for i in range(self.n)]

    def persistent_coll(self, comm, coll: str, template, *args):
        """MPI_*_init analog bound to the CACHED pallas jitted program:
        when this component owns the slot, the persistent handle
        dispatches the explicit-DMA ring, not the coll/xla program.
        Shapes/ops the ring does not serve bind through the fallback
        provider (same per-call delegation discipline as the one-shot
        slots)."""
        from ompi_tpu.mca.coll.xla import PersistentColl

        template = self._place(comm, template)
        op = args[0] if args else op_mod.SUM
        ring_op = _RING_OPS.get(getattr(op, "name", "SUM"))
        supported = (coll in ("allreduce", "reduce_scatter")
                     and ring_op is not None
                     and self._supported(template)) or \
                    (coll == "bcast" and self._size_ok(template)) or \
                    (coll == "allgather" and self._supported(template))
        if not supported:
            return self._delegate("persistent_coll", comm, coll,
                                  template, *args)
        # bind through the PUBLIC wrappers: they own the n==1 fast
        # path, padding, and the lru-cached jitted program (so repeated
        # start() is a cache hit, not a retrace)
        from ompi_tpu.ops import pallas_collectives as pc

        if coll == "allreduce":
            variant, seg_elems = self._allreduce_variant(template,
                                                         ring_op)

            def fn(x, v=variant, s=seg_elems):
                return pc.all_reduce(x, self.mesh, self.axis, ring_op,
                                     interpret=self.interpret,
                                     variant=v, seg_elems=s)
        elif coll == "reduce_scatter":
            variant, seg_elems = self._reduce_scatter_variant(template,
                                                              ring_op)

            def fn(x, v=variant, s=seg_elems):
                return pc.reduce_scatter(x, self.mesh, self.axis,
                                         ring_op,
                                         interpret=self.interpret,
                                         variant=v, seg_elems=s)
        elif coll == "allgather":
            # same routing as the one-shot slot (never-diverge contract)
            variant = "bidi" if self.bidirectional else "ring"

            def fn(x, v=variant):
                return pc.all_gather(x, self.mesh, self.axis,
                                     interpret=self.interpret, variant=v)
        else:   # bcast: root baked into the handle, one shared program
            root = int(args[0]) % self.n if args else 0
            seg_elems = max(1, self.seg_bytes // template.dtype.itemsize)

            def fn(x, r=root, s=seg_elems):
                return pc.bcast(x, self.mesh, self.axis, root=r,
                                interpret=self.interpret, seg_elems=s)
        fn(template)    # build + cache + validate now, not at start()
        return PersistentColl(fn, coll, int(template.nbytes))

    def bcast_array(self, comm, x, root: int = 0):
        x = self._place(comm, x)
        # pure DMA, no arithmetic: any dtype qualifies — only size gates
        if not self._size_ok(x):
            return self._delegate("bcast_array", comm, x, root)
        from ompi_tpu.ops import pallas_collectives as pc

        seg_elems = max(1, self.seg_bytes // x.dtype.itemsize)
        return pc.bcast(x, self.mesh, self.axis, root=root,
                        interpret=self.interpret, seg_elems=seg_elems)

    def psum_scatter_array(self, comm, x):
        # the SUM reduce-scatter by another name (coll/xla parity)
        return self.reduce_scatter_array(comm, x, op_mod.SUM)

    def ppermute_array(self, comm, x, perm, **typed):
        if typed:       # derived datatypes: coll/xla's one typed program
            return self._delegate("ppermute_array", comm, x, perm, **typed)
        perm = tuple((int(s), int(d)) for s, d in perm)
        rot = tuple((i, (i + 1) % self.n) for i in range(self.n))
        x = self._place(comm, x)
        if perm != rot or not self._supported(x):
            return self._delegate("ppermute_array", comm, x, perm)
        from ompi_tpu.ops import pallas_collectives as pc

        return pc.right_permute(x, self.mesh, self.axis,
                                interpret=self.interpret)


class PallasCollComponent(Component):
    name = "pallas"
    priority = 85

    def register_vars(self, fw) -> None:
        self._prio = self.register_var(
            "priority", vtype=VarType.INT, default=85,
            help="Selection priority of coll/pallas (explicit remote-DMA "
                 "ring collectives); raise above coll/xla's 90 to select")
        self._interpret = self.register_var(
            "interpret", vtype=VarType.STRING, default="auto",
            help="Run kernels in Pallas interpreter mode: auto = only off "
                 "real TPU devices, 0/1 to force")
        self._min = self.register_var(
            "min_bytes", vtype=VarType.SIZE, default="0",
            help="Smallest per-rank payload routed to the DMA ring; "
                 "smaller calls fall through to coll/xla (latency-bound "
                 "small collectives are usually better "
                 "compiler-scheduled; no crossover has been "
                 "measured on a chip)")
        self._max = self.register_var(
            "max_bytes", vtype=VarType.SIZE, default="1g",
            help="Largest per-rank payload routed to the DMA ring; "
                 "bigger calls fall through to coll/xla.  Large payloads "
                 "use the segmented HBM-resident kernels, so this bounds "
                 "HBM, not VMEM")
        self._vmem_max = self.register_var(
            "vmem_max_bytes", vtype=VarType.SIZE, default="8m",
            help="Per-rank payload crossover from the fused all-VMEM "
                 "ring kernel to the segmented HBM-resident one "
                 "(bounded VMEM window).  The default is the "
                 "Mosaic-measured ceiling: on a v5e-8 topology the "
                 "fused kernel's acc+recv footprint compiles at 8MB "
                 "per-rank payload and is VMEM-exhausted at 16MB "
                 "(pallas_aot round-5 probe)")
        self._seg = self.register_var(
            "seg_bytes", vtype=VarType.SIZE, default="512k",
            help="VMEM window size per buffer for the segmented ring "
                 "kernels (two double-buffered windows this size)")
        self._bidi = self.register_var(
            "bidirectional", vtype=VarType.BOOL, default=False,
            help="Use the bidirectional (duplex) ring schedules: "
                 "all-reduce carries half the payload in each ICI "
                 "direction per step (fused sizes; seg_bidi above the "
                 "VMEM bound), and allgather ships blocks both ways in "
                 "ceil((n-1)/2) steps instead of n-1")
        self._wire16 = self.register_var(
            "wire16", vtype=VarType.BOOL, default=False,
            help="Opt-in wire compression for float32 SUM allreduce: "
                 "f32 accumulation, bf16 bytes on the ICI — halves "
                 "per-step wire time at bf16 value precision "
                 "(bit-identical across ranks; worst-case error "
                 "O(n*2^-8) relative to partial magnitudes).  Changes "
                 "numerics, so never on by default")
        self._axis = self.register_var(
            "axis_name", default="mpi",
            help="Mesh axis name for coll/pallas kernels")

    def _interpret_mode(self, devices) -> bool:
        v = str(self._interpret.value or "auto").strip().lower()
        if v in ("0", "false", "no"):
            return False
        if v in ("1", "true", "yes"):
            return True
        from ompi_tpu.base.jaxenv import pallas_interpret

        return pallas_interpret(devices)

    def comm_query(self, comm):
        rte = comm.rte
        if rte is None or not rte.is_device_world:
            return None
        try:
            devices = [rte.device_of(r) for r in comm.group.world_ranks]
        except Exception:
            return None
        if not devices or any(d is None for d in devices):
            return None
        return self._prio.value, PallasCollModule(
            comm, devices, self._axis.value,
            self._interpret_mode(devices), int(self._max.value),
            vmem_max_bytes=int(self._vmem_max.value),
            seg_bytes=int(self._seg.value),
            bidirectional=bool(self._bidi.value),
            min_bytes=int(self._min.value),
            wire16=bool(self._wire16.value))


COMPONENT = PallasCollComponent()
