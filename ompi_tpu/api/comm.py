"""Communicators: group + CID + per-comm collective vtable + p2p dispatch.

Re-design of ``/root/reference/ompi/communicator/communicator.h`` /
``comm.c`` / ``comm_cid.c``: a communicator owns its group, a context id
agreed across members (``comm_cid.c:53-93``; carries an FT epoch ``:78``),
and a per-comm collective vtable ``c_coll`` filled by priority vote of the
coll components (``coll_base_comm_select.c``).  Point-to-point dispatches to
the selected pml module the way ``MPI_Send`` does
(``ompi/mpi/c/send.c:93`` → ``MCA_PML_CALL``).  ULFM state (revoked flag,
failure checks before communication, ``comm_ft.c``) is carried here.
"""
from __future__ import annotations

import threading
from typing import Any, Optional, Sequence

import numpy as np

from ompi_tpu.api import op as op_mod
from ompi_tpu.api.attributes import AttributeHost
from ompi_tpu.api.errhandler import ERRORS_ARE_FATAL, Errhandler
from ompi_tpu.api.errors import ErrorClass, MpiError, RevokedError
from ompi_tpu.api.group import Group
from ompi_tpu.api.info import Info
from ompi_tpu.api.request import CompletedRequest, Request, waitall
from ompi_tpu.api.status import ANY_SOURCE, ANY_TAG, PROC_NULL, Status
from ompi_tpu.datatype import Datatype, from_numpy_dtype

_ft_state_mod = None


def _ft_state():
    """Cached ft.state module ref (import is lazy to avoid a cycle, but a
    sys.modules lookup per _check_state would cost ~0.2us on the device
    fast path)."""
    global _ft_state_mod
    if _ft_state_mod is None:
        from ompi_tpu.ft import state

        _ft_state_mod = state
    return _ft_state_mod

# collective function slots a coll module can fill (``mca/coll/coll.h``
# module struct equivalent; *_array are the TPU device-buffer entry points)
COLL_FUNCTIONS = (
    "barrier", "bcast", "gather", "gatherv", "scatter", "scatterv",
    "allgather", "allgatherv", "alltoall", "alltoallv", "alltoallw",
    "reduce", "allreduce", "reduce_scatter", "reduce_scatter_block",
    "scan", "exscan",
    "ibarrier", "ibcast", "igather", "iscatter", "iallgather", "ialltoall",
    "ireduce", "iallreduce", "ireduce_scatter", "iscan", "iexscan",
    "allreduce_array", "bcast_array", "allgather_array",
    "reduce_scatter_array", "alltoall_array", "ppermute_array",
    "psum_scatter_array", "reduce_array", "gather_array", "scatter_array",
    "allgatherv_array", "alltoallv_array", "scan_array", "exscan_array",
    "persistent_coll", "partitioned_coll", "device_barrier",
    "agree", "iagree",
    "neighbor_allgather", "neighbor_alltoall",
)


def as_buffer(buf) -> tuple[np.ndarray, int, Datatype]:
    """Normalize a user buffer to (ndarray, count, datatype).

    Accepts an ndarray (count/type inferred), or an explicit
    ``(ndarray, count, Datatype)`` triple for derived layouts.
    """
    if isinstance(buf, tuple):
        arr, count, dt = buf
        return np.asarray(arr), count, dt
    arr = np.asarray(buf)
    return arr, arr.size, from_numpy_dtype(arr.dtype)


#: live-communicator registry for debugger introspection
#: (``runtime.debugger.comm_table`` — the handle-table walk of
#: ``ompi/debuggers/ompi_common_dll.c``).  Weak: registration must not
#: keep freed communicators alive.
_live_comms: "weakref.WeakSet" = None  # initialized lazily below


def _register_live(comm) -> None:
    global _live_comms
    import weakref

    if _live_comms is None:
        _live_comms = weakref.WeakSet()
    _live_comms.add(comm)


def live_comms() -> list:
    """Snapshot of live communicators (debugger support)."""
    return sorted(_live_comms or [], key=lambda c: (c.cid, c.epoch))


#: per-(members, tag) invocation counters for the sessions-model CID
#: bootstrap: create_from_group is collective over the group, so every
#: member's N-th call with the same (members, tag) pairs up — the count
#: keys successive agreements apart without any pre-existing channel
_group_cid_seq: dict = {}
_group_cid_lock = threading.Lock()


def _agree_group_cid(client, group, tag: str) -> int:
    """Coord-assisted CID agreement for parent-less construction: first
    member through publishes a bridge-range CID (globally unique, so no
    per-member freeness confirmation is needed) via atomic
    put-if-absent; every member adopts the winner."""
    base = (tuple(group.world_ranks), str(tag))
    with _group_cid_lock:
        seq = _group_cid_seq.get(base, 0)
        _group_cid_seq[base] = seq + 1
    from ompi_tpu import dpm

    proposed = dpm._new_bridge_cid(client)
    key = f"__group_cid__:{base!r}:{seq}"
    return int(client.put_new(-1, key, proposed))


class Comm(AttributeHost):
    _cid_lock = threading.Lock()

    def __init__(
        self,
        group: Group,
        cid: int,
        rte,
        name: str = "",
        epoch: int = 0,
        parent: Optional["Comm"] = None,
        remote_group: Optional[Group] = None,
    ) -> None:
        self.group = group
        self.cid = cid
        self.epoch = epoch  # FT epoch: revoked CIDs can't be confused on reuse
        self.rte = rte
        self.name = name or f"comm#{cid}"
        self.c_coll: dict[str, Any] = {}
        self.coll_modules: list = []
        self.errhandler: Errhandler = ERRORS_ARE_FATAL
        self.info = Info()
        self.topo = None          # set by topo framework (cart/graph/dist_graph)
        self.revoked = False
        self.freed = False
        self.remote_group = remote_group  # inter-communicator remote side
        self.pml = None           # selected pml module (set at selection time)
        self._rev_key = None      # lazy (ft_scope, cid, epoch) probe key
        self._rank = group.rank_of(rte.my_world_rank) if rte else 0
        if parent is not None:
            self.errhandler = parent.errhandler
        _register_live(self)

    # -- accessors -------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def size(self) -> int:
        return self.group.size

    @property
    def is_inter(self) -> bool:
        return self.remote_group is not None

    @property
    def remote_size(self) -> int:
        return self.remote_group.size if self.remote_group else 0

    def world_rank(self, rank: int) -> int:
        return self.group.world_rank(rank)

    def as_rank(self, rank: int) -> "Comm":
        """Conductor-model facade: this communicator acting as ``rank``.

        In the device-world (single-controller) model the one process hosts
        every rank; p2p issued through ``as_rank(i)`` carries i as the
        source — the in-process analog of ``mpirun --oversubscribe`` rank
        multiplexing.  Shares all communicator state with self.
        """
        import copy

        if not 0 <= rank < self.size:
            raise MpiError(ErrorClass.ERR_RANK, f"invalid rank {rank}")
        view = copy.copy(self)
        view._rank = rank
        return view

    def get_name(self) -> str:
        return self.name

    def set_name(self, name: str) -> None:
        self.name = name

    def set_errhandler(self, eh: Errhandler) -> None:
        self.errhandler = eh

    def get_errhandler(self) -> Errhandler:
        return self.errhandler

    def call_errhandler(self, errorcode) -> None:
        """``MPI_Comm_call_errhandler`` (fatal default handler aborts,
        ERRORS_RETURN raises the MpiError to the caller)."""
        try:
            cls = ErrorClass(int(errorcode))
        except ValueError:
            cls = ErrorClass.ERR_OTHER
        self._err(MpiError(cls, f"user-raised code {int(errorcode)}"))

    def set_info(self, info: Info) -> None:
        """``MPI_Comm_set_info``: replace the comm's info hints."""
        self.info = info.dup()

    def get_info(self) -> Info:
        """``MPI_Comm_get_info``."""
        return self.info.dup()

    def _check_state(self, peer: Optional[int] = None) -> None:
        # NOTE: allreduce_array inlines the peer=None predicate
        # (freed + is_revoked) on its fast path — mirror any new
        # comm-wide check added here into that method too
        if self.freed:
            raise MpiError(ErrorClass.ERR_COMM, "communicator was freed")
        if self.is_revoked():
            self._err(RevokedError(f"{self.name} revoked"))
        if peer is not None and peer not in (ANY_SOURCE, PROC_NULL):
            if not 0 <= peer < (self.remote_size if self.is_inter else self.size):
                raise MpiError(ErrorClass.ERR_RANK, f"invalid rank {peer}")
            # ULFM early liveness check (send.c:84); an intercomm peer
            # rank indexes the remote group
            from ompi_tpu.ft import state as ft_state

            peer_world = (self.remote_group if self.is_inter
                          else self.group).world_rank(peer)
            if ft_state.is_failed(peer_world):
                from ompi_tpu.api.errors import ProcFailedError

                self._err(ProcFailedError(
                    f"peer {peer} has failed", (peer,)))

    def _err(self, error: MpiError) -> None:
        self.errhandler.invoke(self, error)
        raise error  # ERRORS_RETURN handler already raised; fatal aborts

    # -- coll dispatch ---------------------------------------------------
    def _coll(self, name: str):
        fn = self.c_coll.get(name)
        if fn is None:
            raise MpiError(
                ErrorClass.ERR_UNSUPPORTED_OPERATION,
                f"no coll component provides '{name}' on {self.name}")
        return fn

    # blocking host collectives (numpy buffers) -------------------------
    def barrier(self) -> None:
        self._check_state()
        self._coll("barrier")(self)

    def bcast(self, buf, root: int = 0):
        self._check_state()
        return self._coll("bcast")(self, buf, root)

    def reduce(self, sendbuf, op: op_mod.Op = op_mod.SUM, root: int = 0):
        self._check_state()
        return self._coll("reduce")(self, sendbuf, op, root)

    def allreduce(self, sendbuf, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("allreduce")(self, sendbuf, op)

    def gather(self, sendbuf, root: int = 0):
        self._check_state()
        return self._coll("gather")(self, sendbuf, root)

    def gatherv(self, sendbuf, root: int = 0):
        self._check_state()
        return self._coll("gatherv")(self, sendbuf, root)

    def scatter(self, sendbuf, root: int = 0):
        self._check_state()
        return self._coll("scatter")(self, sendbuf, root)

    def scatterv(self, sendbufs, root: int = 0):
        self._check_state()
        return self._coll("scatterv")(self, sendbufs, root)

    def allgather(self, sendbuf):
        self._check_state()
        return self._coll("allgather")(self, sendbuf)

    def allgatherv(self, sendbuf):
        self._check_state()
        return self._coll("allgatherv")(self, sendbuf)

    def alltoall(self, sendbuf):
        self._check_state()
        return self._coll("alltoall")(self, sendbuf)

    def alltoallv(self, sendbufs):
        """``MPI_Alltoallv``: ``sendbufs[r]`` goes to rank r; returns a
        list where entry r is rank r's block, typed as
        ``sendbufs[r].dtype`` (symmetric exchanges — use ``alltoallw``
        with ``recvtypes`` when pairs exchange different dtypes)."""
        self._check_state()
        return self._coll("alltoallv")(self, sendbufs)

    def alltoallw(self, sendbufs, recvtypes=None):
        """``MPI_Alltoallw``: per-peer buffers and per-peer datatypes
        (recvtypes: numpy dtype per source rank)."""
        self._check_state()
        return self._coll("alltoallw")(self, sendbufs, recvtypes)

    def reduce_scatter(self, sendbuf, recvcounts=None,
                       op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("reduce_scatter")(self, sendbuf, recvcounts, op)

    def reduce_scatter_block(self, sendbuf, op: op_mod.Op = op_mod.SUM):
        """``MPI_Reduce_scatter_block``: equal-sized blocks — sendbuf has
        size*blockcount elements, each rank receives its reduced block."""
        self._check_state()
        arr = np.asarray(sendbuf)
        lead = arr.shape[-1] if arr.ndim else arr.size
        n = self.size
        if lead % n:
            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"reduce_scatter_block needs length divisible by {n}, "
                f"got {lead}")
        out = self._coll("reduce_scatter")(self, sendbuf,
                                           [lead // n] * n, op)
        if (isinstance(out, list) and len(out) == n
                and self.rte is not None and self.rte.is_device_world):
            return np.stack(out)   # single-controller: the whole table
        return out                  # multiprocess: my block

    def scan(self, sendbuf, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("scan")(self, sendbuf, op)

    def exscan(self, sendbuf, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("exscan")(self, sendbuf, op)

    # nonblocking variants ----------------------------------------------
    def ibarrier(self) -> Request:
        self._check_state()
        return self._coll("ibarrier")(self)

    def ibcast(self, buf, root: int = 0) -> Request:
        self._check_state()
        return self._coll("ibcast")(self, buf, root)

    def iallreduce(self, sendbuf, op: op_mod.Op = op_mod.SUM) -> Request:
        self._check_state()
        return self._coll("iallreduce")(self, sendbuf, op)

    def iallgather(self, sendbuf) -> Request:
        self._check_state()
        return self._coll("iallgather")(self, sendbuf)

    def ialltoall(self, sendbuf) -> Request:
        self._check_state()
        return self._coll("ialltoall")(self, sendbuf)

    def ireduce(self, sendbuf, op: op_mod.Op = op_mod.SUM,
                root: int = 0) -> Request:
        self._check_state()
        return self._coll("ireduce")(self, sendbuf, op, root)

    def _icompleted(self, fn, *args) -> Request:
        """Eager "nonblocking" form for slots without an overlapped
        schedule: runs the collective NOW and returns a born-complete
        request.  LIMITATION vs MPI locality: the call blocks until the
        collective finishes, so a program that interleaves one of these
        with dependent point-to-point before waiting can deadlock where
        a true nonblocking implementation would not (libnbc-backed slots
        — iallreduce/ibcast/iscan/... — do overlap properly)."""
        self._check_state()
        r = CompletedRequest()
        r.result = fn(*args)
        return r

    def _icoll(self, name: str, blocking, *args) -> Request:
        """Route to a module-provided overlapped schedule (libnbc) when
        one filled the slot; eager completed-request form otherwise."""
        fn = self.c_coll.get(name)
        if fn is not None:
            self._check_state()
            return fn(self, *args)
        return self._icompleted(blocking, *args)

    def iscan(self, sendbuf, op: op_mod.Op = op_mod.SUM) -> Request:
        return self._icoll("iscan", self.scan, sendbuf, op)

    def iexscan(self, sendbuf, op: op_mod.Op = op_mod.SUM) -> Request:
        return self._icoll("iexscan", self.exscan, sendbuf, op)

    def igather(self, sendbuf, root: int = 0) -> Request:
        return self._icoll("igather", self.gather, sendbuf, root)

    def igatherv(self, sendbuf, root: int = 0) -> Request:
        return self._icompleted(self.gatherv, sendbuf, root)

    def iscatter(self, sendbuf, root: int = 0) -> Request:
        return self._icoll("iscatter", self.scatter, sendbuf, root)

    def iscatterv(self, sendbufs, root: int = 0) -> Request:
        return self._icompleted(self.scatterv, sendbufs, root)

    def iallgatherv(self, sendbuf) -> Request:
        return self._icompleted(self.allgatherv, sendbuf)

    def ialltoallv(self, sendbufs) -> Request:
        return self._icompleted(self.alltoallv, sendbufs)

    def ialltoallw(self, sendbufs, recvtypes=None) -> Request:
        return self._icompleted(self.alltoallw, sendbufs, recvtypes)

    def ireduce_scatter(self, sendbuf, recvcounts=None,
                        op: op_mod.Op = op_mod.SUM) -> Request:
        return self._icoll("ireduce_scatter", self.reduce_scatter,
                           sendbuf, recvcounts, op)

    def ireduce_scatter_block(self, sendbuf,
                              op: op_mod.Op = op_mod.SUM) -> Request:
        return self._icompleted(self.reduce_scatter_block, sendbuf, op)

    def ineighbor_allgather(self, sendbuf) -> Request:
        return self._icompleted(self.neighbor_allgather, sendbuf)

    def ineighbor_allgatherv(self, sendbuf) -> Request:
        return self._icompleted(self.neighbor_allgatherv, sendbuf)

    def ineighbor_alltoall(self, sendbufs) -> Request:
        return self._icompleted(self.neighbor_alltoall, sendbufs)

    def ineighbor_alltoallv(self, sendbufs) -> Request:
        return self._icompleted(self.neighbor_alltoallv, sendbufs)

    def ineighbor_alltoallw(self, sendbufs, recvtypes=None) -> Request:
        return self._icompleted(self.neighbor_alltoallw, sendbufs,
                                recvtypes)

    # device-array collectives (jax.Array over the ICI mesh) ------------
    def allreduce_array(self, x, op: op_mod.Op = op_mod.SUM):
        # THE hot call of the framework (DP gradient sync): inline the
        # state check and skip the _coll indirection — one dict probe on
        # the per-comm vtable, then straight into the module fast path
        if self.freed or self.is_revoked():
            self._check_state()
        fn = self.c_coll.get("allreduce_array")
        if fn is None:
            return self._coll("allreduce_array")(self, x, op)  # raise path
        return fn(self, x, op)

    def bcast_array(self, x, root: int = 0):
        self._check_state()
        return self._coll("bcast_array")(self, x, root)

    def allgather_array(self, x):
        self._check_state()
        return self._coll("allgather_array")(self, x)

    def reduce_scatter_array(self, x, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("reduce_scatter_array")(self, x, op)

    def reduce_array(self, x, op: op_mod.Op = op_mod.SUM, root: int = 0):
        self._check_state()
        return self._coll("reduce_array")(self, x, op, root)

    def gather_array(self, x, root: int = 0):
        self._check_state()
        return self._coll("gather_array")(self, x, root)

    def scatter_array(self, x, root: int = 0):
        self._check_state()
        return self._coll("scatter_array")(self, x, root)

    def allgatherv_array(self, x, counts):
        self._check_state()
        return self._coll("allgatherv_array")(self, x, counts)

    def alltoallv_array(self, x, counts):
        self._check_state()
        return self._coll("alltoallv_array")(self, x, counts)

    def scan_array(self, x, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("scan_array")(self, x, op)

    def exscan_array(self, x, op: op_mod.Op = op_mod.SUM):
        self._check_state()
        return self._coll("exscan_array")(self, x, op)

    #: blocking collectives coll_init may bind (MPI_*_init set)
    _PCOLL_NAMES = frozenset({
        "barrier", "bcast", "reduce", "allreduce", "gather", "gatherv",
        "scatter", "scatterv", "allgather", "allgatherv", "alltoall",
        "alltoallv", "alltoallw", "reduce_scatter",
        "reduce_scatter_block", "scan", "exscan"})

    def coll_init(self, coll: str, template=None, *args):
        """Persistent collective (MPI_Allreduce_init & friends, MPI-4 /
        the reference's mpiext/pcollreq): ONE interface on every path —
        a restartable request (``start()``/``wait()``/``.result``).  On
        the device path each start() re-dispatches the pre-compiled
        program bound at init; on host paths it re-runs the selected
        algorithm (schedule reuse, which is what pcollreq provides).
        ``template=None`` binds zero-argument collectives (barrier).
        For the bare callable compiled-program handle on device arrays,
        use ``allreduce_array_init``."""
        self._check_state()
        from ompi_tpu.api.request import PersistentP2P

        fn = self.c_coll.get("persistent_coll")
        if fn is not None and template is not None:
            handle = fn(self, coll, template, *args)

            def _start_dev():
                r = CompletedRequest()
                r.result = handle(template)
                return r

            return PersistentP2P(_start_dev)
        if coll not in self._PCOLL_NAMES:
            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           f"no persistent binding for '{coll}'")
        blocking = getattr(self, coll)
        call_args = () if template is None and not args \
            else (template, *args)

        def _start():
            r = CompletedRequest()
            r.result = blocking(*call_args)
            return r

        return PersistentP2P(_start)

    def allreduce_array_init(self, template, op: op_mod.Op = op_mod.SUM):
        """Low-level persistent DEVICE collective: the bound compiled
        program as a bare callable handle (``h(x)`` = one SPC bump + the
        XLA dispatch).  ``coll_init`` wraps the same binding in the
        uniform MPI request interface."""
        fn = self.c_coll.get("persistent_coll")
        if fn is None:
            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           "no device persistent-collective provider on "
                           f"{self.name}; use coll_init for the host "
                           "persistent request form")
        return fn(self, "allreduce", template, op)

    def alltoall_array(self, x, sendtype=None, recvtype=None,
                       count: int = 1):
        """``x[i, j]`` moves to ``result[j, i]``.  With ``sendtype`` /
        ``recvtype`` (derived datatypes; None is contiguous) each block is
        packed and unpacked on the device inside the slot's one program:
        ``count`` elements of ``sendtype`` out of each ``x[i, j]``, landing
        through ``recvtype`` in a buffer zero outside its type map."""
        self._check_state()
        if sendtype is None and recvtype is None:
            return self._coll("alltoall_array")(self, x)
        return self._coll("alltoall_array")(
            self, x, sendtype=sendtype, recvtype=recvtype, count=count)

    def ppermute_array(self, x, perm: Sequence[tuple], sendtype=None,
                       recvtype=None, count: int = 1):
        """Row s of ``x`` moves to row d for each ``(s, d)`` of ``perm``;
        typed as :meth:`alltoall_array`.  On a one-rank communicator
        ``perm=((0, 0),)`` is a send to self."""
        self._check_state()
        if sendtype is None and recvtype is None:
            return self._coll("ppermute_array")(self, x, perm)
        return self._coll("ppermute_array")(
            self, x, perm, sendtype=sendtype, recvtype=recvtype, count=count)

    # -- p2p dispatch (→ selected pml, like MCA_PML_CALL) ---------------
    def send(self, buf, dest: int, tag: int = 0) -> None:
        self._check_state(dest)
        if dest == PROC_NULL:
            return
        self.pml.send(self, buf, dest, tag)

    def recv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status:
        self._check_state(source)
        if source == PROC_NULL:
            return Status(source=PROC_NULL, tag=ANY_TAG)
        return self.pml.recv(self, buf, source, tag)

    def isend(self, buf, dest: int, tag: int = 0) -> Request:
        self._check_state(dest)
        if dest == PROC_NULL:
            return CompletedRequest()
        return self.pml.isend(self, buf, dest, tag)

    def irecv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Request:
        self._check_state(source)
        if source == PROC_NULL:
            return CompletedRequest(Status(source=PROC_NULL, tag=ANY_TAG))
        return self.pml.irecv(self, buf, source, tag)

    def ssend(self, buf, dest: int, tag: int = 0) -> None:
        """``MPI_Ssend``: returns only after the receiver matched."""
        self.issend(buf, dest, tag).wait()

    def issend(self, buf, dest: int, tag: int = 0) -> Request:
        self._check_state(dest)
        if dest == PROC_NULL:
            return CompletedRequest()
        return self.pml.isend(self, buf, dest, tag, sync=True)

    def rsend(self, buf, dest: int, tag: int = 0) -> None:
        """``MPI_Rsend``: the caller asserts the recv is posted; with a
        posted recv it behaves exactly like send (MPI guarantees nothing
        extra), so it shares the standard path like pml/ob1 does."""
        self.send(buf, dest, tag)

    def irsend(self, buf, dest: int, tag: int = 0) -> Request:
        return self.isend(buf, dest, tag)

    def bsend(self, buf, dest: int, tag: int = 0) -> None:
        """``MPI_Bsend``: copies into the attached buffer space and
        returns immediately; the user's buffer is reusable on return."""
        self.ibsend(buf, dest, tag)   # ibsend is already locally complete

    def ibsend(self, buf, dest: int, tag: int = 0) -> Request:
        from ompi_tpu.api import buffer as _bsend

        self._check_state(dest)
        if dest == PROC_NULL:
            return CompletedRequest()
        arr = np.ascontiguousarray(buf)
        _bsend.claim(arr.nbytes)
        try:
            inner = self.pml.isend(self, arr.copy(), dest, tag)
        except Exception:
            _bsend.release(arr.nbytes)   # claim must not leak
            raise
        _bsend.track(inner, arr.nbytes)
        # buffered semantics: the returned request is LOCALLY complete —
        # the message lives in the (conceptual) attach buffer; only
        # Buffer_detach waits for the real delivery.  A rendezvous-size
        # inner request must not leak to the caller or bsend-then-wait-
        # then-recv pairs would deadlock (the pattern Bsend exists for).
        return CompletedRequest()

    # -- persistent point-to-point (``MPI_Send_init``/``Recv_init``) ----
    def send_init(self, buf, dest: int, tag: int = 0) -> Request:
        from ompi_tpu.api.request import CompletedRequest as _CR, \
            PersistentP2P

        self._check_state(dest)
        if dest == PROC_NULL:
            return PersistentP2P(lambda: _CR())
        return PersistentP2P(lambda: self.pml.isend(self, buf, dest, tag))

    def ssend_init(self, buf, dest: int, tag: int = 0) -> Request:
        from ompi_tpu.api.request import CompletedRequest as _CR, \
            PersistentP2P

        self._check_state(dest)
        if dest == PROC_NULL:
            return PersistentP2P(lambda: _CR())
        return PersistentP2P(
            lambda: self.pml.isend(self, buf, dest, tag, sync=True))

    def bsend_init(self, buf, dest: int, tag: int = 0) -> Request:
        """``MPI_Bsend_init``: persistent buffered-mode send — every
        start() claims attach-buffer space and completes locally."""
        from ompi_tpu.api.request import PersistentP2P

        self._check_state(dest)
        return PersistentP2P(lambda: self.ibsend(buf, dest, tag))

    def rsend_init(self, buf, dest: int, tag: int = 0) -> Request:
        """``MPI_Rsend_init``: ready mode shares the standard path (with
        a posted recv they are identical, like pml/ob1)."""
        return self.send_init(buf, dest, tag)

    def recv_init(self, buf, source: int = ANY_SOURCE,
                  tag: int = ANY_TAG) -> Request:
        from ompi_tpu.api.request import CompletedRequest as _CR, \
            PersistentP2P

        self._check_state(source)
        if source == PROC_NULL:
            return PersistentP2P(
                lambda: _CR(Status(source=PROC_NULL, tag=ANY_TAG)))
        return PersistentP2P(lambda: self.pml.irecv(self, buf, source, tag))

    # -- partitioned point-to-point (MPI-4 ``MPI_Psend_init`` family) ----
    def psend_init(self, buf, partitions: int, dest: int,
                   tag: int = 0) -> Request:
        """``MPI_Psend_init``: a partitioned persistent send.  After
        ``start()``, each of the ``partitions`` equal slices of ``buf``
        is released for transfer by ``req.pready(p)`` (or
        ``pready_range``/``pready_list``); the request completes once
        every partition was readied and sent.  Ready runs are aggregated
        onto fewer wire messages under the
        ``otpu_part_persist_min_partitions`` var (``mca/part/persist``).
        """
        from ompi_tpu.mca.part import part_module

        self._check_state(dest)
        return part_module().psend_init(self, buf, partitions, dest, tag)

    def precv_init(self, buf, partitions: int, source: int,
                   tag: int = 0) -> Request:
        """``MPI_Precv_init``: the receive side of a partitioned pairing.
        ``req.parrived(p)`` reports per-partition arrival — exact even
        when the sender used a different partition count (byte-framed
        wire protocol).  Wildcards are not supported (MPI-4)."""
        from ompi_tpu.mca.part import part_module

        self._check_state(source)
        return part_module().precv_init(self, buf, partitions, source, tag)

    def pallreduce_init(self, buckets, op: op_mod.Op = op_mod.SUM) -> Request:
        """Partitioned persistent allreduce (the ``MPI_Pallreduce_init``
        analog of MPI-4's partitioned model applied to a collective):
        ``buckets`` are bound once; ``req.pready(i)`` releases bucket i.
        On the device path the launches are planned here, once: runs of
        consecutive buckets that together hold less than 64 MiB of one
        rank (``coll/xla`` ``PGROUP_MIN_BYTES``) share ONE
        pre-compiled program, dispatched when the run's last member is
        released, and a bucket at or over that size is dispatched at
        its own ``pready`` — so a group's reduction overlaps the
        computation still producing the next group's buckets (bucketed
        gradient overlap) and a step of many small buckets does not pay
        a launch each.  Nothing is compiled after this returns.
        ``req.parrived(i)`` tests bucket completion (and, like
        ``req.test()``, dispatches a released bucket that still waits
        for its group through the bucket's own program: a caller that
        polls between ``pready``s is back to a launch a polled bucket,
        and its launch count depends on when it polls); after all
        preadys the request is complete and ``req.result[i]`` holds
        bucket i's reduction.  On host comms without a device binding
        each pready runs the blocking allreduce (every rank must pready
        in the same order)."""
        self._check_state()
        from ompi_tpu.mca.part.pcoll import PartitionedCollRequest

        fn = self.c_coll.get("partitioned_coll")
        handles, plan = fn(self, "allreduce", buckets, op) \
            if fn is not None else (None, None)
        return PartitionedCollRequest(self, "allreduce", buckets, (op,),
                                      handles, plan)

    def sendrecv_replace(self, buf, dest: int, source: int = ANY_SOURCE,
                         sendtag: int = 0, recvtag: int = ANY_TAG) -> Status:
        """``MPI_Sendrecv_replace``: the received message overwrites the
        sent buffer (staged through a copy, like the reference).  ``buf``
        must be a writable ndarray — replacement into a list/tuple would
        be silently lost."""
        if not isinstance(buf, np.ndarray) or not buf.flags.writeable:
            raise MpiError(ErrorClass.ERR_BUFFER,
                           "sendrecv_replace needs a writable ndarray")
        arr = np.ascontiguousarray(buf)
        st = self.sendrecv(arr.copy(), dest, arr, source, sendtag, recvtag)
        if buf is not arr:
            np.copyto(buf, arr)
        return st

    def sendrecv(self, sendbuf, dest: int, recvbuf, source: int = ANY_SOURCE,
                 sendtag: int = 0, recvtag: int = ANY_TAG) -> Status:
        self._check_state(dest)
        sreq = self.isend(sendbuf, dest, sendtag) if dest != PROC_NULL else None
        st = self.recv(recvbuf, source, recvtag)
        if sreq is not None:
            sreq.wait()
        return st

    def probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status:
        self._check_state(source)
        return self.pml.probe(self, source, tag, blocking=True)

    def iprobe(self, source: int = ANY_SOURCE,
               tag: int = ANY_TAG) -> tuple[bool, Optional[Status]]:
        self._check_state(source)
        return self.pml.probe(self, source, tag, blocking=False)

    def mprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check_state(source)
        return self.pml.mprobe(self, source, tag, blocking=True)

    def improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
        self._check_state(source)
        return self.pml.mprobe(self, source, tag, blocking=False)

    def send_obj(self, obj: Any, dest: int, tag: int = 0) -> None:
        from ompi_tpu.api.request import waitall

        waitall(self.isend_obj(obj, dest, tag))

    def isend_obj(self, obj: Any, dest: int, tag: int = 0) -> list:
        """Nonblocking ``send_obj``: returns the requests to waitall.

        The payload buffer is referenced by the returned requests, so the
        caller only needs to keep the request list alive.
        """
        import pickle

        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        hdr = np.array([payload.size], dtype=np.int64)
        return [self.isend(hdr, dest, tag), self.isend(payload, dest, tag)]

    def bcast_obj(self, obj: Any = None, root: int = 0) -> Any:
        """Broadcast an arbitrary picklable object (size agreed first)."""
        import pickle

        if self.rank == root:
            payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
            self.bcast(np.array([payload.size], np.int64), root=root)
            self.bcast(payload, root=root)
            return obj
        hdr = np.asarray(self.bcast(np.zeros(1, np.int64), root=root))
        payload = np.asarray(self.bcast(
            np.zeros(int(hdr[0]), np.uint8), root=root))
        return pickle.loads(payload.tobytes())

    def recv_obj(self, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Any:
        import pickle

        hdr = np.zeros(1, dtype=np.int64)
        st = self.recv(hdr, source, tag)
        payload = np.zeros(int(hdr[0]), dtype=np.uint8)
        self.recv(payload, st.source, tag)
        return pickle.loads(payload.tobytes())

    # -- management ------------------------------------------------------
    def _next_cid(self) -> int:
        """Agree on the next free CID across members (``comm_cid.c:53``).

        Multi-round like the reference: each member proposes its first
        locally-free id (unreserved), the group takes the MAX, then a
        second allreduce confirms the winner is free on *every* member
        (it may not be: group-scoped create_group allocations make
        bitmaps diverge).  On conflict, re-propose above the loser.
        """
        from ompi_tpu.runtime import init as rt

        if self.rte is not None and self.rte.is_device_world:
            # single process backs every co-located rank: one bitmap,
            # local find-and-set IS the agreement
            return rt.next_local_cid()
        floor = 0
        while True:
            local = rt.candidate_cid(floor)
            agreed = int(np.asarray(self.allreduce(
                np.array([local], dtype=np.int64), op_mod.MAX)).ravel()[0])
            ok = 1 if rt.is_cid_free(agreed) else 0
            all_ok = int(np.asarray(self.allreduce(
                np.array([ok], dtype=np.int64), op_mod.MIN)).ravel()[0])
            if all_ok:
                rt.reserve_cid(agreed)
                return agreed
            floor = agreed + 1

    # -- sessions-model construction (MPI-4, ``ompi/communicator``
    # ``ompi_comm_create_from_group`` / ``ompi_intercomm_create_from_groups``)
    @classmethod
    def create_from_group(cls, group: Group, tag: str = "",
                          info: Optional[Info] = None,
                          errhandler=None, name: str = "") -> Optional["Comm"]:
        """``MPI_Comm_create_from_group``: a communicator from a bare
        group — NO parent communicator, NO MPI_Init required; the active
        instance (opened by a Session or by world init) supplies the pml
        and the CID machinery.  Collective over the group's members;
        ``tag`` disambiguates concurrent creations from overlapping
        groups (MPI-4's string tag).

        CID path: the classic agreement needs a communicator to run
        over, which is exactly what doesn't exist yet — the reference
        solves the bootstrap with a PMIx-assisted exchange; here the
        coord service plays PMIx: the first member through publishes a
        CID drawn from the globally-unique bridge range under an
        atomic put-if-absent keyed by (members, tag, invocation), and
        everyone adopts the winner.  Single-process instances (device
        world / singleton) allocate locally.
        """
        from ompi_tpu import instance as inst_mod
        from ompi_tpu.runtime import init as rt

        inst = inst_mod.current()
        if inst is None:
            raise MpiError(
                ErrorClass.ERR_SESSION,
                "no active instance: open a Session (Session.init) or "
                "call init() before create_from_group")
        rte = inst.rte
        if not rte.is_device_world and \
                group.rank_of(rte.my_world_rank) < 0:
            return None   # not a member (the conductor hosts every rank)
        client = getattr(rte, "client", None)
        if client is None or rte.is_device_world:
            cid = rt.next_local_cid()
        else:
            cid = _agree_group_cid(client, group, tag)
            rt.reserve_cid(cid)
        newcomm = cls(group, cid, rte,
                      name=name or f"from_group~{tag or cid}")
        if info is not None:
            newcomm.info = info.dup()
        if errhandler is not None:
            newcomm.errhandler = errhandler
        cls._wire_new_comm(newcomm, inst.pml)
        return newcomm

    @classmethod
    def create_intercomm_from_groups(cls, local_group: Group,
                                     local_leader: int,
                                     remote_group: Group,
                                     remote_leader: int, tag: str = "",
                                     info: Optional[Info] = None,
                                     errhandler=None) -> Optional["Comm"]:
        """``MPI_Intercomm_create_from_groups``: an intercommunicator
        from two disjoint groups with no parent and no bridge comm.
        The local intracomm (the collective channel every intercomm
        carries) is built first via :meth:`create_from_group`; the
        bridge CID is agreed through the coord service under a key both
        sides derive identically from the UNION of the groups + tag."""
        from ompi_tpu import instance as inst_mod
        from ompi_tpu.runtime import init as rt

        inst = inst_mod.current()
        if inst is None:
            raise MpiError(
                ErrorClass.ERR_SESSION,
                "no active instance: open a Session (Session.init) or "
                "call init() before create_intercomm_from_groups")
        rte = inst.rte
        overlap = set(local_group.world_ranks) & \
            set(remote_group.world_ranks)
        if overlap:
            raise MpiError(ErrorClass.ERR_GROUP,
                           f"groups overlap on ranks {sorted(overlap)}")
        local = cls.create_from_group(local_group, tag=f"{tag}//local",
                                      info=info)
        if local is None:
            return None
        client = getattr(rte, "client", None)
        if client is None or rte.is_device_world:
            cid = rt.next_local_cid()
        else:
            union = Group(sorted(set(local_group.world_ranks)
                                 | set(remote_group.world_ranks)))
            cid = _agree_group_cid(client, union, f"{tag}//inter")
            rt.reserve_cid(cid)
        inter = cls(local_group, cid, rte,
                    name=f"from_groups~{tag or cid}",
                    remote_group=remote_group)
        if errhandler is not None:
            inter.errhandler = errhandler
        inter.local_comm = local
        local._finish_create(inter)
        return inter

    # comm_compare results (``mpi.h`` MPI_IDENT family)
    IDENT = 0
    CONGRUENT = 1
    SIMILAR = 2
    UNEQUAL = 3

    def dup(self) -> "Comm":
        self._check_state()
        newcomm = Comm(self.group, self._next_cid(), self.rte,
                       name=f"{self.name}~dup", epoch=self.epoch, parent=self)
        self._attrs_copy_to(newcomm)
        newcomm.info = self.info.dup()
        self._finish_create(newcomm)
        return newcomm

    def idup(self) -> tuple["Comm", Request]:
        """``MPI_Comm_idup``: the dup itself is collective-synchronous
        here (CID agreement), so the request is born complete."""
        newcomm = self.dup()
        req = CompletedRequest()
        req.result = newcomm
        return newcomm, req

    def dup_with_info(self, info: Info) -> "Comm":
        """``MPI_Comm_dup_with_info``: dup, with the new comm's hints
        REPLACED by ``info`` instead of inherited."""
        newcomm = self.dup()
        newcomm.info = info.dup()
        return newcomm

    def compare(self, other: "Comm") -> int:
        """``MPI_Comm_compare``: IDENT (same object), CONGRUENT (same
        group(s) + order, different context), SIMILAR (same members,
        other order), UNEQUAL.  Intercomms compare local AND remote
        groups; an intercomm never matches an intracomm."""
        if self is other:
            return Comm.IDENT
        if self.is_inter != other.is_inter:
            return Comm.UNEQUAL
        mine = list(self.group.world_ranks)
        theirs = list(other.group.world_ranks)
        if self.is_inter:
            rm = list(self.remote_group.world_ranks)
            rt = list(other.remote_group.world_ranks)
            if mine == theirs and rm == rt:
                return Comm.CONGRUENT
            if sorted(mine) == sorted(theirs) and sorted(rm) == sorted(rt):
                return Comm.SIMILAR
            return Comm.UNEQUAL
        if mine == theirs:
            return Comm.CONGRUENT
        if sorted(mine) == sorted(theirs):
            return Comm.SIMILAR
        return Comm.UNEQUAL

    def split(self, color, key=0) -> Optional["Comm"]:
        """``MPI_Comm_split``.

        Multi-process model: each rank passes its (color, key); the table is
        exchanged with an allgather over the parent.  Device-world
        (conductor) model: color/key may be scalars or (size,) arrays of
        per-rank values; the table is local.  Returns the subcommunicator
        containing this (facade) rank, or None for color < 0 (UNDEFINED).
        """
        self._check_state()
        if self.rte is not None and self.rte.is_device_world:
            colors = np.broadcast_to(np.asarray(color, np.int64), (self.size,))
            keys = np.broadcast_to(np.asarray(key, np.int64), (self.size,))
            table = np.stack([colors, keys,
                              np.arange(self.size, dtype=np.int64)], 1)
        else:
            mine = np.array([color, key, self.rank], dtype=np.int64)
            table = np.asarray(self.allgather(mine)).reshape(self.size, 3)
        # one CID per distinct non-negative color, allocated in sorted order
        # so every member observes the same assignment (comm_cid.c agreement)
        distinct = sorted({int(c) for c, _, _ in table if c >= 0})
        cids = {c: self._next_cid() for c in distinct}
        my_color = int(table[self.rank, 0])
        if my_color < 0:  # MPI_UNDEFINED
            return None
        members = sorted((int(k), int(r)) for c, k, r in table
                         if c == my_color)
        ranks = [self.group.world_rank(r) for _, r in members]
        newcomm = Comm(Group(ranks), cids[my_color], self.rte,
                       name=f"{self.name}~split", epoch=self.epoch,
                       parent=self)
        self._finish_create(newcomm)
        return newcomm

    def split_type(self, split_type: str = "shared", key: int = 0) -> "Comm":
        """``MPI_Comm_split_type``: 'shared' = same host/ICI domain."""
        color = self.rte.locality_color(split_type)
        return self.split(color, key)

    def create(self, group: Group) -> Optional["Comm"]:
        self._check_state()
        cid = self._next_cid()
        if group.rank_of(self.rte.my_world_rank) < 0:
            return None
        newcomm = Comm(group, cid, self.rte, name=f"{self.name}~create",
                       epoch=self.epoch, parent=self)
        self._finish_create(newcomm)
        return newcomm

    def create_group(self, group: Group, tag: int = 0) -> Optional["Comm"]:
        """Non-collective over the parent: only group members participate.

        The CID must still be agreed across the *group* (a purely local
        allocation can hand members of the same new comm different CIDs),
        so members run the multi-round agreement over parent p2p on a
        reserved tag (the reference's comm_create_group activation uses
        tagged parent traffic the same way).
        """
        if group.rank_of(self.rte.my_world_rank) < 0:
            return None
        from ompi_tpu.runtime import init as rt

        if self.rte is not None and self.rte.is_device_world:
            cid = rt.next_local_cid()
        else:
            cid = self._agree_cid_group(group, tag)
        newcomm = Comm(group, cid, self.rte,
                       name=f"{self.name}~create_group", epoch=self.epoch,
                       parent=self)
        self._finish_create(newcomm)
        return newcomm

    def _agree_cid_group(self, group: Group, tag: int) -> int:
        """Multi-round CID agreement among group members via parent p2p."""
        from ompi_tpu.runtime import init as rt

        members = [self.group.rank_of(w) for w in group.world_ranks]
        leader = members[0]
        t = -(1 << 20) - tag  # reserved internal tag space

        def xchg(value: int, combine) -> int:
            buf = np.array([value], dtype=np.int64)
            if self.rank == leader:
                acc = value
                got = np.zeros(1, dtype=np.int64)
                for m in members[1:]:
                    self.recv(got, m, t)
                    acc = combine(acc, int(got[0]))
                out = np.array([acc], dtype=np.int64)
                for m in members[1:]:
                    self.send(out, m, t)
                return acc
            self.send(buf, leader, t)
            got = np.zeros(1, dtype=np.int64)
            self.recv(got, leader, t)
            return int(got[0])

        floor = 0
        while True:
            agreed = xchg(rt.candidate_cid(floor), max)
            all_ok = xchg(1 if rt.is_cid_free(agreed) else 0, min)
            if all_ok:
                rt.reserve_cid(agreed)
                return agreed
            floor = agreed + 1

    @staticmethod
    def _wire_new_comm(newcomm: "Comm", pml) -> None:
        """The one post-construction wiring sequence every new comm gets
        (parented or sessions-model alike): pml attach + coll selection."""
        from ompi_tpu.mca.coll.base import comm_select

        newcomm.pml = pml
        if pml is not None:
            add = getattr(pml, "add_comm", None)
            if add is not None:
                add(newcomm)
        comm_select(newcomm)

    def _finish_create(self, newcomm: "Comm") -> None:
        Comm._wire_new_comm(newcomm, self.pml)

    def topo_test(self) -> str:
        """``MPI_Topo_test``: "cart" | "graph" | "dist_graph" |
        "undefined"."""
        if self.topo is None:
            return "undefined"
        return self.topo.kind   # every topo class defines it; fail loudly

    # -- process topologies (``ompi/mca/topo``) -------------------------
    def cart_create(self, dims: Sequence[int], periods=None,
                    reorder: bool = False) -> Optional["Comm"]:
        """``MPI_Cart_create``.

        ``reorder=True`` in the device-world model maps the grid onto the
        ICI mesh device order (the treematch hardware-mapping analog) —
        cart neighbors then sit one ICI hop apart.
        """
        from ompi_tpu.mca.topo import CartTopo

        dims = list(dims)
        if periods is None:
            periods = [False] * len(dims)
        grid = int(np.prod(dims)) if dims else 1
        if grid > self.size:
            raise MpiError(ErrorClass.ERR_DIMS,
                           f"grid {dims} larger than comm size {self.size}")
        # ranks beyond the grid are excluded (MPI_COMM_NULL).  reorder=True
        # keeps device order in the conductor model: the device world is
        # built from jax.devices() order, which enumerates the ICI mesh
        # row-major — already matching our row-major cart convention.
        if self.rte is not None and self.rte.is_device_world:
            # conductor split needs the whole color table, not my scalar
            color = np.array([0 if r < grid else -1
                              for r in range(self.size)])
            key = np.arange(self.size)
        else:
            color = 0 if self.rank < grid else -1
            key = self.rank
            if reorder:
                # treematch-style hardware mapping (the reference's
                # topo/treematch, topo_treematch_dist_graph_create.c):
                # order ranks by node so row-major cart neighbors — the
                # highest-traffic pairs in halo patterns — land on the
                # same node wherever possible.  The reorder decision must
                # be COLLECTIVE: a rank with unresolved locality must not
                # fall back alone while its peers reorder (membership of
                # the grid would diverge)
                order = self._node_major_order()
                ok = 1 if order is not None else 0
                from ompi_tpu.api import op as _op

                all_ok = int(np.asarray(self.allreduce(
                    np.array([ok], np.int64), op_mod.MIN)).ravel()[0])
                if all_ok and order is not None:
                    key = order.index(self.rank)
                    color = 0 if key < grid else -1
        sub = self.split(color, key)
        if sub is None:
            return None
        sub.topo = CartTopo(dims, periods)
        sub.name = f"{self.name}~cart"
        return sub

    def cart_map(self, dims: Sequence[int], periods=None) -> int:
        """``MPI_Cart_map``: the rank this process WOULD get in a
        reordered cart over ``dims`` — UNDEFINED when it would be left
        out (``ompi/mpi/c/cart_map.c``; base mapping + the node-major
        treematch ordering cart_create(reorder=True) uses)."""
        from ompi_tpu.api.status import UNDEFINED

        dims = list(dims)
        grid = int(np.prod(dims)) if dims else 1
        if grid > self.size:
            raise MpiError(ErrorClass.ERR_DIMS,
                           f"grid {dims} larger than comm size {self.size}")
        order = self._node_major_order()
        newrank = order.index(self.rank) if order is not None else self.rank
        return newrank if newrank < grid else UNDEFINED

    def graph_map(self, index: Sequence[int], edges: Sequence[int]) -> int:
        """``MPI_Graph_map``: identity-family mapping like the base
        component (``mca/topo/base/topo_base_graph_map.c``)."""
        from ompi_tpu.api.status import UNDEFINED

        nnodes = len(index)
        return self.rank if self.rank < nnodes else UNDEFINED

    def _node_major_order(self) -> Optional[list]:
        """Comm ranks sorted by (node, rank); None if locality unknown."""
        rte = self.rte
        if rte is None:
            return None
        nodes = [rte.node_of(w) for w in self.group.world_ranks]
        if any(n is None for n in nodes):
            return None
        return sorted(range(self.size), key=lambda r: (str(nodes[r]), r))

    def cart_coords(self, rank: Optional[int] = None) -> list:
        self._require_topo("cart")
        return self.topo.coords_of(self.rank if rank is None else rank)

    def cart_rank(self, coords) -> int:
        self._require_topo("cart")
        return self.topo.rank_of(coords)

    def cart_shift(self, direction: int, disp: int = 1) -> tuple:
        self._require_topo("cart")
        return self.topo.shift(self.rank, direction, disp)

    def cart_get(self) -> tuple:
        self._require_topo("cart")
        return (list(self.topo.dims), list(self.topo.periods),
                self.cart_coords())

    def cart_sub(self, remain_dims) -> Optional["Comm"]:
        """``MPI_Cart_sub``: keep the axes where remain_dims is true."""
        self._require_topo("cart")
        from ompi_tpu.mca.topo import CartTopo

        coords = self.cart_coords()
        dropped = tuple(c for c, keep in zip(coords, remain_dims)
                        if not keep)

        # one color per combination of dropped coordinates
        def color_of(rank: int) -> int:
            c0 = 0
            for c, dim, keep in zip(self.topo.coords_of(rank),
                                    self.topo.dims, remain_dims):
                if not keep:
                    c0 = c0 * dim + c
            return c0

        if self.rte is not None and self.rte.is_device_world:
            color = np.array([color_of(r) for r in range(self.size)])
            key = np.arange(self.size)
        else:
            color, key = color_of(self.rank), self.rank
        sub = self.split(color, key)
        if sub is None:
            return None
        sub.topo = CartTopo(
            [d for d, keep in zip(self.topo.dims, remain_dims) if keep],
            [p for p, keep in zip(self.topo.periods, remain_dims) if keep])
        sub.name = f"{self.name}~sub{dropped}"
        return sub

    def graph_create(self, index, edges,
                     reorder: bool = False) -> Optional["Comm"]:
        from ompi_tpu.mca.topo import GraphTopo

        nnodes = len(index)
        if self.rte is not None and self.rte.is_device_world:
            color = np.array([0 if r < nnodes else -1
                              for r in range(self.size)])
            key = np.arange(self.size)
        else:
            color, key = (0 if self.rank < nnodes else -1), self.rank
        sub = self.split(color, key)
        if sub is None:
            return None
        sub.topo = GraphTopo(index, edges)
        sub.name = f"{self.name}~graph"
        return sub

    def dist_graph_create_adjacent(self, sources, destinations,
                                   sourceweights=None, destweights=None,
                                   reorder: bool = False) -> "Comm":
        from ompi_tpu.mca.topo import DistGraphTopo

        sub = self.dup()
        sub.topo = DistGraphTopo(sources, destinations, sourceweights,
                                 destweights)
        sub.name = f"{self.name}~distgraph"
        return sub

    def _require_topo(self, kind: str) -> None:
        if self.topo is None or self.topo.kind != kind:
            raise MpiError(ErrorClass.ERR_TOPOLOGY,
                           f"{self.name} has no {kind} topology")

    # neighbor collectives (``coll_base_neighbor_*``): p2p compositions
    # over the attached topology's (sources, destinations)
    def neighbor_allgather(self, sendbuf) -> list:
        if self.topo is None:
            raise MpiError(ErrorClass.ERR_TOPOLOGY,
                           f"{self.name} has no topology")
        srcs, dsts = self.topo.neighbors(self.rank)
        if self.rte is not None and self.rte.is_device_world:
            # conductor model: leading axis of sendbuf indexes ranks
            table = np.asarray(sendbuf)
            return [None if s == PROC_NULL else np.array(table[s], copy=True)
                    for s in srcs]
        arr = np.ascontiguousarray(sendbuf)
        reqs = [self.isend(arr, d, tag=-3) for d in dsts if d != PROC_NULL]
        out = []
        for s in srcs:
            if s == PROC_NULL:
                out.append(None)
            else:
                buf = np.empty_like(arr)
                self.recv(buf, s, tag=-3)
                out.append(buf)
        waitall(reqs)
        return out

    def neighbor_alltoall(self, sendbufs) -> list:
        if self.topo is None:
            raise MpiError(ErrorClass.ERR_TOPOLOGY,
                           f"{self.name} has no topology")
        srcs, dsts = self.topo.neighbors(self.rank)
        if self.rte is not None and self.rte.is_device_world:
            # conductor model: sendbufs[r][k] is rank r's buffer for its
            # k-th destination.  Pair inbound slots with senders' outbound
            # slots FIFO per (src, dst) channel — the per-source ordering
            # real message passing gives, correct even when a neighbor
            # appears twice (periodic size-2 ring)
            from collections import defaultdict, deque

            chan: dict = defaultdict(deque)
            for r in range(self.size):
                _, r_dsts = self.topo.neighbors(r)
                for k, d in enumerate(r_dsts):
                    if d != PROC_NULL:
                        chan[(r, d)].append(np.asarray(sendbufs[r][k]))
            return [None if s == PROC_NULL
                    else np.array(chan[(s, self.rank)].popleft(), copy=True)
                    for s in srcs]
        if len(sendbufs) != len(dsts):
            raise MpiError(ErrorClass.ERR_ARG,
                           f"need {len(dsts)} send buffers, got "
                           f"{len(sendbufs)}")
        reqs = []
        template = None  # all blocks are same-sized (MPI neighbor semantics)
        for d, buf in zip(dsts, sendbufs):
            if d != PROC_NULL:
                arr = np.ascontiguousarray(buf)
                template = arr
                reqs.append(self.isend(arr, d, tag=-4))
        out = []
        for s in srcs:
            if s == PROC_NULL:
                out.append(None)
            elif template is None:
                raise MpiError(ErrorClass.ERR_ARG,
                               "cannot size receive blocks: no real "
                               "destination buffer to mirror")
            else:
                buf = np.empty_like(template)
                self.recv(buf, s, tag=-4)
                out.append(buf)
        waitall(reqs)
        return out

    # neighbor v/w variants: per-neighbor sizes (and dtypes for w) ride
    # the object channel — FIFO per (src, dst) pair like the fixed-size
    # forms, with the single-controller table model mirrored
    def neighbor_allgatherv(self, sendbuf) -> list:
        self._require_any_topo()
        srcs, dsts = self.topo.neighbors(self.rank)
        if self.rte is not None and self.rte.is_device_world:
            table = sendbuf   # table[r] = rank r's (arbitrary-size) buffer
            return [None if s == PROC_NULL else np.asarray(table[s]).copy()
                    for s in srcs]
        from ompi_tpu.api.request import waitall

        arr = np.ascontiguousarray(sendbuf)
        reqs = [r for d in dsts if d != PROC_NULL
                for r in self.isend_obj(arr, d, tag=-6)]
        out = [None if s == PROC_NULL else self.recv_obj(s, tag=-6)
               for s in srcs]
        waitall(reqs)
        return out

    def neighbor_alltoallv(self, sendbufs) -> list:
        self._require_any_topo()
        srcs, dsts = self.topo.neighbors(self.rank)
        if self.rte is not None and self.rte.is_device_world:
            from collections import defaultdict, deque

            chan: dict = defaultdict(deque)
            for r in range(self.size):
                _, r_dsts = self.topo.neighbors(r)
                for k, d in enumerate(r_dsts):
                    if d != PROC_NULL:
                        chan[(r, d)].append(np.asarray(sendbufs[r][k]))
            return [None if s == PROC_NULL
                    else chan[(s, self.rank)].popleft().copy()
                    for s in srcs]
        if len(sendbufs) != len(dsts):
            raise MpiError(ErrorClass.ERR_ARG,
                           f"need {len(dsts)} send buffers, got "
                           f"{len(sendbufs)}")
        from ompi_tpu.api.request import waitall

        reqs = [r for b, d in zip(sendbufs, dsts) if d != PROC_NULL
                for r in self.isend_obj(np.ascontiguousarray(b), d,
                                        tag=-6)]
        out = [None if s == PROC_NULL else self.recv_obj(s, tag=-6)
               for s in srcs]
        waitall(reqs)
        return out

    def neighbor_alltoallw(self, sendbufs, recvtypes=None) -> list:
        """Per-neighbor buffers AND per-neighbor receive dtypes."""
        out = self.neighbor_alltoallv(sendbufs)
        if recvtypes is None:
            return out
        typed = []
        for j, b in enumerate(out):
            if b is None:
                typed.append(None)
                continue
            rt_ = recvtypes[j] if isinstance(recvtypes, (list, tuple)) \
                else recvtypes
            typed.append(np.ascontiguousarray(b).reshape(-1)
                         .view(np.uint8).view(np.dtype(rt_)))
        return typed

    def _require_any_topo(self) -> None:
        if self.topo is None:
            raise MpiError(ErrorClass.ERR_TOPOLOGY,
                           f"{self.name} has no topology")

    def release_coll_modules(self) -> None:
        """Tear down per-comm coll module state (shared segments etc.).

        Called from free(); also from runtime finalize for WORLD/SELF,
        which the user never frees (ompi_mpi_finalize does the same)."""
        for mod in self.coll_modules:
            close = getattr(mod, "comm_unquery", None)
            if close is not None:
                try:
                    close(self)
                except Exception:
                    pass
        self.coll_modules = []

    def free(self) -> None:
        if self.freed:
            # double-free must not touch a newer communicator's state
            # (release/del_comm are keyed by bare cid)
            return
        self._attrs_delete_all()
        self.release_coll_modules()
        if self.pml is not None:
            del_comm = getattr(self.pml, "del_comm", None)
            if del_comm is not None:
                del_comm(self)
        if self.cid > 1:
            from ompi_tpu.runtime import init as rt

            rt.retire_cid(self.cid)
        self.freed = True

    # -- dynamic process management (``ompi/dpm``) ----------------------
    def spawn(self, command, maxprocs: int, root: int = 0) -> "Comm":
        from ompi_tpu import dpm

        return dpm.spawn(self, command, maxprocs, root)

    def spawn_multiple(self, commands, maxprocs, root: int = 0) -> "Comm":
        from ompi_tpu import dpm

        return dpm.spawn_multiple(self, commands, maxprocs, root)

    def create_intercomm(self, local_leader: int, bridge_comm: "Comm",
                         remote_leader: int, tag: int = 0) -> "Comm":
        """``MPI_Intercomm_create``: join two disjoint intracomms into an
        intercommunicator through leaders that share ``bridge_comm``
        (``ompi/communicator/comm.c`` ``ompi_intercomm_create``).

        Leaders exchange group membership + a proposed CID over the
        bridge (MAX wins), then EVERY member of both groups confirms the
        winner is locally free — per-process CID bitmaps diverge, so the
        multi-round confirm of ``_next_cid``/``create_group`` is needed
        here too; on a conflict both sides re-propose above the loser.
        """
        from ompi_tpu.runtime import init as rt

        self._check_state()
        btag = -(1 << 22) - (int(tag) % (1 << 20))
        remote = None
        floor = 0
        while True:
            if self.rank == local_leader:
                proposed = rt.candidate_cid(floor)
                bridge_comm.send_obj(
                    {"cid": proposed,
                     "ranks": list(self.group.world_ranks)},
                    remote_leader, tag=btag)
                theirs = bridge_comm.recv_obj(remote_leader, tag=btag)
                payload = {"cid": max(int(proposed), int(theirs["cid"])),
                           "remote": theirs["ranks"]}
            else:
                payload = None
            payload = self.bcast_obj(payload, root=local_leader)
            cid = int(payload["cid"])
            remote = payload["remote"]
            ok = 1 if rt.is_cid_free(cid) else 0
            grp_ok = int(np.asarray(self.allreduce(
                np.array([ok], np.int64), op_mod.MIN)).ravel()[0])
            if self.rank == local_leader:
                bridge_comm.send_obj(grp_ok, remote_leader, tag=btag)
                their_ok = int(bridge_comm.recv_obj(remote_leader,
                                                    tag=btag))
                both = min(grp_ok, their_ok)
            else:
                both = None
            both = int(self.bcast_obj(both, root=local_leader))
            if both:
                break
            floor = cid + 1
        rt.reserve_cid(cid)
        inter = Comm(self.group, cid, self.rte,
                     name=f"{self.name}~inter", epoch=self.epoch,
                     parent=self, remote_group=Group(
                         [int(r) for r in remote]))
        inter.local_comm = self
        self._finish_create(inter)
        return inter

    def accept(self, port: str, root: int = 0) -> "Comm":
        from ompi_tpu import dpm

        return dpm.accept(self, port, root)

    def connect(self, port: str, root: int = 0) -> "Comm":
        from ompi_tpu import dpm

        return dpm.connect(self, port, root)

    def merge(self, high: bool = False) -> "Comm":
        from ompi_tpu import dpm

        return dpm.merge(self, high)

    def abort(self, errorcode: int = 1) -> None:
        from ompi_tpu.runtime import init as rt

        rt.abort(self, errorcode)

    # -- ULFM FT API (``ompi/mpiext/ftmpi``) ----------------------------
    def revoke(self) -> None:
        from ompi_tpu.ft import revoke as ft_revoke

        ft_revoke.revoke(self)

    def shrink(self) -> "Comm":
        from ompi_tpu.ft import shrink as ft_shrink

        return ft_shrink.shrink(self)

    def agree(self, flag: int) -> int:
        # NOT _check_state: ULFM's agreement is the recovery primitive and
        # must keep working on a revoked communicator (like shrink)
        if self.freed:
            raise MpiError(ErrorClass.ERR_COMM, "communicator was freed")
        return self._coll("agree")(self, flag)

    def get_failed(self) -> Group:
        from ompi_tpu.ft import state as ft_state

        failed = [r for r in self.group.world_ranks if ft_state.is_failed(r)]
        return Group(failed)

    def ack_failed(self, num_to_ack: Optional[int] = None) -> int:
        """``MPIX_Comm_ack_failed``: acknowledge known failures.

        Acknowledged ranks stop tripping ``agree`` into ProcFailedError.
        Returns the number of failures acknowledged.
        """
        from ompi_tpu.ft import state as ft_state

        failed = [r for r in self.group.world_ranks if ft_state.is_failed(r)]
        if num_to_ack is not None:
            failed = failed[:num_to_ack]
        self._acked_failed = frozenset(failed) | getattr(
            self, "_acked_failed", frozenset())
        return len(self._acked_failed)

    @property
    def ft_scope(self) -> str:
        """Revocation scope: job-local CIDs are scoped to the job (a
        dpm-spawned job's cid-0 COMM_WORLD must not inherit the parent
        job's revoked cid 0); bridge CIDs (>= 2^20) are globally unique
        and share one scope."""
        if self.cid >= (1 << 20):
            return "#bridge"
        return str(getattr(self.rte, "job", "0"))

    def is_revoked(self) -> bool:
        if not self.revoked:
            # hot path (every _check_state): prebuilt key + cached module
            # ref, one set-membership probe
            key = self._rev_key
            if key is None:
                key = self._rev_key = (self.ft_scope, self.cid, self.epoch)
            if _ft_state().is_revoked_key(key):
                self.revoked = True
        return self.revoked

    def __repr__(self) -> str:
        return (f"Comm({self.name}, cid={self.cid}, rank={self.rank}/"
                f"{self.size})")
