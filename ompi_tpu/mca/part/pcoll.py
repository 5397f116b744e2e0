"""pcoll — partitioned persistent collectives (MPI-4's partitioned
model applied to collectives; the ``Pallreduce_init`` analog).

A partitioned collective binds a LIST of buckets once; each
``pready(i)`` releases bucket i for reduction.  On the device path the
launches of a step are planned at bind time (``coll/xla``
``partitioned_coll``): the buckets are cut into runs of consecutive
indices, a group each, and a group is dispatched as ONE pre-compiled
program the moment its last member is released — a launch costs the
host more than a small bucket's reduction costs the device, so B
``Pready``s are a launch a group and not B launches.  A bucket at or
over the planner's bar is a group of one and is dispatched at its own
``pready``, as every bucket once was.  XLA's async dispatch means a
group's reduction runs while the application is still producing the
next group's buckets, which is exactly the bucketed-gradient-overlap
pattern.  Any release order is correct; a group whose members
are released one after another (last to first, or first to last)
launches with its last member, so every launch of a step is issued
before the last ``pready`` returns.  ``parrived(i)`` and ``test()`` are
progress calls: a released bucket whose group is still waiting for
members is dispatched then and there through its own program.  So a
caller that polls between ``Pready``s trades the group's one launch for
a launch a polled bucket, and its launch count depends on when it
polls; one that only releases and waits launches exactly the plan.

On host comms without a device binding each pready runs the blocking
collective, so every rank must pready in the same order (the
trainer's deterministic late-layer-first schedule satisfies this).
"""
from __future__ import annotations

import threading

import numpy as np

from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.api.request import Request, RequestState
from ompi_tpu.api.status import Status
from ompi_tpu.runtime import spc, trace


def _nbytes(x) -> int:
    return int(getattr(x, "nbytes", 0) or 0)


class PartitionedCollRequest(Request):
    """Restartable partitioned collective: start()/pready(i)/parrived(i)
    /wait(), with ``result[i]`` = bucket i's reduction."""

    side = "coll"

    def __init__(self, comm, coll: str, buckets, args=(), handles=None,
                 plan=None):
        super().__init__(persistent=True)
        buckets = list(buckets)
        if not buckets:
            raise MpiError(ErrorClass.ERR_ARG,
                           "partitioned collective needs >= 1 bucket")
        self._comm = comm
        self._coll = coll
        self._buckets = buckets
        self._args = tuple(args)
        self._handles = handles      # device bindings, or None (host)
        self.partitions = len(buckets)
        # the launches of a step: plan[g] = (members, their one bound
        # program), or (members, None) for a group of one, which goes
        # through its own handle (on a host comm every bucket does)
        self._plan = plan if plan is not None else \
            [((i,), None) for i in range(self.partitions)]
        self._group_of = [g for g, (members, _) in enumerate(self._plan)
                          for _ in members]
        self._sizes = [_nbytes(b) for b in buckets]
        self.result: list = [None] * self.partitions
        self._plock = threading.Lock()

    def start(self, buckets=None) -> None:
        """``MPI_Start`` with optional data rebinding: device arrays are
        immutable, so a new round passes fresh buckets matching the
        bound templates (the ``PersistentColl.start(x)`` convention)."""
        if buckets is not None:
            buckets = list(buckets)
            if len(buckets) != self.partitions:
                raise MpiError(
                    ErrorClass.ERR_ARG,
                    f"rebind needs {self.partitions} buckets, got "
                    f"{len(buckets)}")
            self._buckets = buckets
            if self._handles is None:
                # a bound program refuses a bucket that is not its
                # template's size; the host path takes any
                self._sizes = [_nbytes(b) for b in buckets]
        super().start()

    def _start(self) -> None:
        with self._plock:
            self._done = [False] * self.partitions
            # per group: members not yet released, and members released
            # that no launch has taken yet
            self._left = [len(members) for members, _ in self._plan]
            self._waiting = [[] for _ in self._plan]
            self._ndone = 0
            self.result = [None] * self.partitions

    def _check_partition(self, p) -> int:
        if not isinstance(p, (int, np.integer)) or not \
                0 <= p < self.partitions:
            raise MpiError(
                ErrorClass.ERR_ARG,
                f"bucket {p!r} out of range [0, {self.partitions})")
        return int(p)

    def pready(self, partition) -> None:
        """Release one bucket.  The bucket that completes its group
        dispatches the group's one program before this returns; any
        other only joins its group's waiting members."""
        spc.record("part_pready")
        t0 = trace.now() if trace.enabled else None
        if self.state is not RequestState.ACTIVE:
            raise MpiError(ErrorClass.ERR_REQUEST,
                           "Pready on an inactive partitioned collective "
                           "(call start() first)")
        p = self._check_partition(partition)
        g = self._group_of[p]
        claim = None
        with self._plock:
            if self._done[p]:
                raise MpiError(ErrorClass.ERR_ARG,
                               f"bucket {p} was already released in "
                               "this epoch")
            self._done[p] = True
            self._waiting[g].append(p)
            self._left[g] -= 1
            if not self._left[g]:
                claim, self._waiting[g] = self._waiting[g], []
        if claim is not None:
            self._launch(g, claim, p)
        if t0 is not None:
            trace.span("pready", "part", t0,
                       args={"partition": p, "nbytes": self._sizes[p],
                             "cid": self._comm.cid, "coll": self._coll})

    def _single(self, i: int):
        x = self._buckets[i]
        if self._handles is not None:
            return self._handles[i](x)         # async device dispatch
        return getattr(self._comm, self._coll)(x, *self._args)

    def _launch(self, g: int, claim: list, trigger=None) -> None:
        """Dispatch ``claim``, released members of group g that the
        caller took off the group's waiting list: the whole group as its
        one program, a part of it (a ``parrived`` poll got ahead of the
        group) each member through its own.  All or nothing: a dispatch
        that raises (e.g. a rebind whose bucket mismatches the bound
        template) must not wedge the request, so the bucket whose
        ``pready`` triggered it is NOT released — un-marked, the epoch
        stays restartable and a corrected ``pready`` can retry — and the
        others wait again.  Where a poll's dispatch fails and no
        ``pready`` is left to take the members up again (the group's
        last member was released meanwhile), the request completes in
        error, so ``wait()`` raises instead of spinning."""
        members, grouped = self._plan[g]
        t0 = trace.now() if trace.enabled else None
        try:
            if grouped is not None and len(claim) == len(members):
                claim = members
                outs = grouped([self._buckets[i] for i in members])
                launches = 1
            else:
                outs = [self._single(i) for i in claim]
                launches = len(claim)
        except Exception as e:
            with self._plock:
                if trigger is not None:
                    self._done[trigger] = False
                    self._left[g] += 1
                self._waiting[g] = [i for i in claim if i != trigger] \
                    + self._waiting[g]
                stuck = not self._left[g]
            if stuck:
                self.complete(e if isinstance(e, MpiError) else MpiError(
                    ErrorClass.ERR_OTHER,
                    f"dispatch of buckets {claim} failed: {e!r}"))
            raise
        nbytes = sum(self._sizes[i] for i in claim)
        spc.record("part_bytes", nbytes)
        spc.record("part_group_launches", launches)
        with self._plock:
            for i, out in zip(claim, outs):
                self.result[i] = out
            self._ndone += len(claim)
            done = self._ndone == self.partitions
        if t0 is not None:
            trace.span("pgroup", "part", t0,
                       args={"members": len(claim), "launches": launches,
                             "nbytes": nbytes, "cid": self._comm.cid,
                             "coll": self._coll})
        if done:
            self.status = Status(_nbytes=sum(self._sizes))
            self.complete()

    def _progress_group(self, g: int) -> None:
        """Dispatch what group g has released and not launched."""
        with self._plock:
            claim, self._waiting[g] = self._waiting[g], []
        if claim:
            self._launch(g, claim)

    def parrived(self, partition) -> bool:
        """Bucket released AND its device result materialized (host
        results are synchronous, so released == arrived there).  A
        progress call: a released bucket whose group still waits for
        members is dispatched now, with the group's other released
        members, each through its own program (and a dispatch error is
        raised here)."""
        spc.record("part_parrived")
        p = self._check_partition(partition)
        if self.persistent and self.state is RequestState.INACTIVE:
            raise MpiError(ErrorClass.ERR_REQUEST,
                           "Parrived on a never-started partitioned "
                           "collective")
        with self._plock:
            released = self._done[p]
            out = self.result[p]
        if not released:
            return False
        if out is None:
            # waiting for its group, or in another thread's launch
            self._progress_group(self._group_of[p])
            out = self.result[p]
            if out is None:
                return False
        is_ready = getattr(out, "is_ready", None)
        return True if is_ready is None else bool(is_ready())

    def test(self):
        """``MPI_Test``, and a progress call as ``parrived`` is: every
        released bucket still waiting for its group is dispatched."""
        if self.state is RequestState.ACTIVE:
            for g, waiting in enumerate(self._waiting):
                if waiting:
                    self._progress_group(g)
        return super().test()
