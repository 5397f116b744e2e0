"""coll/xla ★ — device-buffer collectives lowering to XLA over the ICI mesh.

The north star (BASELINE.json): MPI_Allreduce / Bcast / Allgather /
Reduce_scatter / Alltoall on TPU-resident buffers lower to ``lax.psum`` /
``ppermute`` / ``all_gather`` / ``psum_scatter`` / ``all_to_all`` inside
``shard_map`` on the communicator's mesh — compiler-scheduled collectives,
no progress engine, no staging.  Slots into the coll framework the way
``coll/cuda``/``coll/hcoll`` do (``/root/reference/ompi/mca/coll/cuda/
coll_cuda_allreduce.c:30-69`` stages D2H→coll→H2D; here the collective runs
ON device instead).

Data model (single-controller SPMD): a communicator of size N over an
N-device mesh; device arrays carry a leading rank axis of global size N
sharded over the mesh axis (``x[i]`` lives on device-rank i's HBM).

Hot-path design: compiled programs are cached per (coll, op, shape, dtype)
— the trace-time analog of per-call MCA selection (SURVEY.md §7 hard part
#1) — and a cache *hit* is one unlocked dict probe + relaxed SPC bump +
the XLA dispatch, nothing else; argument validation is memoized with the
program (same key ⇒ already validated).  ``persistent()`` exposes the
bound compiled program directly — the MPI-4 persistent-collective
(``MPI_Allreduce_init``) analog.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ompi_tpu.api import op as op_mod
from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.api.request import CompletedRequest
from ompi_tpu.base.mca import Component
from ompi_tpu.base.var import VarType
from ompi_tpu.mca.coll import quant as quant_mod
from ompi_tpu.runtime import spc, trace


def _ar_key(x, op):
    """Allreduce program-cache key — the hot-path inline form of
    ``_keyfor("allreduce", ...)``; the two MUST stay in sync."""
    return ("allreduce", op.name, x.shape, x.dtype)


def _program_name(coll: str, variant=None) -> str:
    """The ``__name__`` a collective's program is jitted under, from the
    cache key's first fields: ``otpu_allreduce_sum``, ``otpu_bcast_psum``,
    ``otpu_alltoall``.  The profiler shows it as ``PjitFunction(<name>)``
    on the host and ``jit_<name>`` on the device."""
    if variant is None:
        return f"otpu_{coll}"
    return f"otpu_{coll}_{getattr(variant, 'name', variant).lower()}"


def _root_only_psum(t, ax, root: int):
    """Root's ``t`` on every rank of ``ax``: one all-reduce to which the
    others contribute zeros (psum widens bool; every other dtype comes
    back as it went)."""
    import jax
    import jax.numpy as jnp

    contrib = jnp.where(jax.lax.axis_index(ax) == root, t, jnp.zeros_like(t))
    return jax.lax.psum(contrib, ax).astype(t.dtype)


def _spanned(name: str, coll: str, x, fn, *args):
    """``fn(*args)``, inside an ``otpu.coll.<name>`` span while a profiler
    session is open.  For the paths that are not hot: the span carries
    the collective and the buffer's shape and dtype (the program-cache
    key's variable part), which is what an operator needs to see when a
    step recompiles inside their own ``jax.profiler.trace(...)``."""
    if trace.profiler_on():
        with trace.profiler_span("otpu.coll." + name, coll=coll,
                                 shape=str(tuple(x.shape)),
                                 dtype=str(x.dtype)):
            return fn(*args)
    return fn(*args)


def _first_call(fn, coll: str, x):
    """One-shot wrapper around the FIRST call of a newly built program —
    trace, lower, compile or cache load, first dispatch — counted into
    ``device_program_first_call_us`` and, under a profiler session, an
    ``otpu.coll.first_call`` span.  The cache holds ``fn`` itself."""
    def first(arg):
        t0 = time.perf_counter()
        try:
            return _spanned("first_call", coll, x, fn, arg)
        finally:
            spc.record("device_program_first_call_us",
                       (time.perf_counter() - t0) * 1e6)
    return first


def _freed(coll: str):
    def fn(_x):
        raise MpiError(ErrorClass.ERR_REQUEST,
                       f"persistent {coll} handle was freed")
    return fn


class PersistentColl:
    """A bound, pre-compiled collective program (MPI_*_init analog).

    ``__call__`` runs it eagerly; ``start`` returns a request completing
    with the result (device dispatch is already asynchronous, so the
    request is born complete — the XLA stream is the progress engine).
    The handle bypasses ``c_coll``, so it writes its own
    ``otpu.coll.<coll>_init`` span while a profiler session is open.
    """

    __slots__ = ("fn", "coll", "_nbytes", "_bump", "_profiling", "_span")

    def __init__(self, fn, coll: str, nbytes: int) -> None:
        self.fn = fn
        self.coll = coll
        self._nbytes = nbytes
        self._bump = spc.bump_device   # pre-bound: ~sub-µs steady state
        trace.bind_profiler()          # fn is a jitted program: jax is in
        trace.bind_builds()
        self._profiling = trace.profiler_on
        self._span = f"otpu.coll.{coll}_init"

    def __call__(self, x):
        self._bump(self._nbytes)
        if self._profiling():
            with trace.profiler_span(self._span):
                return self.fn(x)
        return self.fn(x)

    def start(self, x):
        r = CompletedRequest()
        r.result = self(x)
        return r

    def free(self) -> None:
        self.fn = _freed(self.coll)


class GroupedColl:
    """One bound, pre-compiled program for a run of buckets of a
    partitioned collective (``partitioned_coll``): the members in, their
    results out, one launch.  SPC ``device_collectives`` counts
    collectives, not launches, so a call moves it by the member count.
    Under a profiler session it writes ``otpu.coll.<coll>_pgroup``."""

    __slots__ = ("fn", "count", "nbytes", "_profiling", "_span")

    def __init__(self, fn, coll: str, count: int, nbytes: int) -> None:
        self.fn = fn
        self.count = count
        self.nbytes = nbytes
        trace.bind_profiler()
        trace.bind_builds()
        self._profiling = trace.profiler_on
        self._span = f"otpu.coll.{coll}_pgroup"

    def __call__(self, xs):
        spc.bump_device(self.nbytes, self.count)
        if self._profiling():
            with trace.profiler_span(self._span):
                return self.fn(*xs)
        return self.fn(*xs)


#: One rank's bytes at which ``plan_groups`` closes a group of buckets of
#: a partitioned allreduce.  It stands for the most reduction work a
#: bucket may hold back while it waits for its neighbours: 64 MiB keep
#: one v5e chip busy for 204 us, about what one launch costs the host
#: (215 us), and each bucket that joins a group saves the host 60-130 us
#: (PERF.md section 6, PR 34).  PROVISIONAL: read on one chip with no
#: producer between the Preadys, where a larger bar always reads faster
#: (fewer launches, nothing to overlap); what it delays on four chips
#: under a backward pass no cell measures yet (PERF.md section 7).
PGROUP_MIN_BYTES = 64 << 20


def plan_groups(sizes, min_bytes: int) -> list:
    """The launches of a partitioned collective, planned once: walk the
    buckets in index order and close a group as soon as its bytes reach
    ``min_bytes``; the tail is the last group.  A bucket that reaches
    the bar alone is a group of one.  Static: the plan, and so the
    launch count of a step that only releases and waits, does not depend
    on timing."""
    groups, run, acc = [], [], 0
    for i, size in enumerate(sizes):
        run.append(i)
        acc += size
        if acc >= min_bytes:
            groups.append(tuple(run))
            run, acc = [], 0
    if run:
        groups.append(tuple(run))
    return groups


class XlaCollModule:
    def __init__(self, comm, devices, axis_name: str = "mpi",
                 bcast_sa_min_bytes: int = 256 << 10,
                 pgroup_min_bytes: int = PGROUP_MIN_BYTES) -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.devices = list(devices)
        self.axis = axis_name
        self.mesh = Mesh(np.array(self.devices), (axis_name,))
        self.n = len(self.devices)
        self.bcast_sa_min_bytes = int(bcast_sa_min_bytes)
        self.pgroup_min_bytes = int(pgroup_min_bytes)
        self._cache: dict = {}
        self._lock = threading.Lock()
        self._P = P
        self._sharded = NamedSharding(self.mesh, P(axis_name))
        self._replicated = NamedSharding(self.mesh, P())
        self._jax_array = jax.Array   # fast isinstance gate for _fast
        trace.bind_profiler()
        trace.bind_builds()

    # -- helpers ---------------------------------------------------------
    def _check(self, comm, x, inner_n: bool = False):
        """Validate + place a buffer (slow path, memoized by program key)."""
        import jax

        if not isinstance(x, jax.Array):
            x = self.make_world_array(x)
        if x.shape[0] != self.n:
            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"device collective needs leading rank axis {self.n}, "
                f"got shape {x.shape}")
        if inner_n and (x.ndim < 2 or x.shape[1] != self.n):
            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"this collective needs shape (n, n, ...), got {x.shape}")
        return x

    def reshard(self, x):
        """Reshard a device array to the row-per-rank layout (XLA moves)."""
        import jax

        return jax.device_put(x, self._sharded)

    def make_world_array(self, host_stack):
        """Place a (size, ...) host stack so row i lives on device-rank i."""
        import jax

        arr = np.asarray(host_stack)
        if arr.ndim == 0 or arr.shape[0] != self.n:
            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"world array needs leading rank axis {self.n}, got shape "
                f"{arr.shape}")
        return jax.device_put(arr, self._sharded)

    def _fast(self, key):
        """Steady-state probe: the compiled program under ``key``, or
        None on miss.  Callers gate on ``isinstance(x, self._jax_array)``
        first (host inputs need _check's sharded placement) and dispatch
        the returned fn directly.  Bumps SPC on hit."""
        entry = self._cache.get(key)
        if entry is None:
            return None
        spc.bump_device(entry[1])
        return entry[0]

    def _get(self, comm, key, x, builder, inner_n: bool = False):
        """Every call that left ``_fast``: probe again; build+validate
        under the lock on miss.  Counted (``device_slow_path``) and,
        under a profiler session, an ``otpu.coll.get`` span.

        Host (numpy) inputs always go through _check for explicit sharded
        placement — a warm cache must not hand a raw host array to the
        compiled program."""
        spc.record("device_slow_path")
        return _spanned("get", key[0], x, self._lookup,
                        comm, key, x, builder, inner_n)

    def _lookup(self, comm, key, x, builder, inner_n: bool):
        checked = isinstance(x, np.ndarray)
        if checked:
            x = self._check(comm, x, inner_n)
        entry = self._cache.get(key)
        first = None
        if entry is None:
            if not checked:
                x = self._check(comm, x, inner_n)
            with self._lock:
                entry = self._cache.get(key)
                if entry is None:
                    spc.record("device_program_builds")
                    entry = (_spanned("build", key[0], x, builder),
                             x.nbytes)
                    self._cache[key] = entry
                    first = _first_call(entry[0], key[0], x)
        fn, nbytes = entry
        spc.bump_device(nbytes)
        return first or fn, x

    def _shard_map(self, fn, in_specs, out_specs, check_vma: bool = False,
                   *, name: str):
        # check_vma off by default: several collective results (all_gather,
        # gather+fold) are replicated in ways jax 0.9's static varying-mesh-
        # axes checker cannot infer; correctness is covered by tests/test_coll.
        # ``name`` is what the profiler shows: PjitFunction(<name>) on the
        # host line, jit_<name> on the device's XLA Modules line.
        import jax

        from jax import shard_map

        mapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=check_vma)
        mapped.__name__ = mapped.__qualname__ = name
        return jax.jit(mapped)

    def _reduce_in_shard(self, op: op_mod.Op):
        """Per-shard reduction body: native collective or gather+fold."""
        import jax

        ax = self.axis
        if op.jax_reduce == "psum":
            return lambda t: jax.lax.psum(t, ax)
        if op.jax_reduce == "pmax":
            return lambda t: jax.lax.pmax(t, ax)
        if op.jax_reduce == "pmin":
            return lambda t: jax.lax.pmin(t, ax)
        def body(t):
            gathered = jax.lax.all_gather(t, ax)  # (n, *S)
            # fused one-pass stack reduction (pallas on TPU) when a
            # component provides one; else chained folds
            stack = op_mod.jax_stack_reduce(op, t.dtype)
            if stack is not None:
                # handed over with rank >= 3: a gathered stack lies a row
                # at a time on the chip (T(1,128)), which reduce_stack's
                # (k, rows, 128) blocks read as it stands; its 2-D block
                # is for a program input's T(4,128) and costs a copy here
                return stack(gathered[:, None])[0]
            fold = op_mod.jax_fold(op, t.dtype)
            acc = gathered[0]
            for i in range(1, self.n):
                acc = fold(gathered[i], acc)
            return acc

        return body

    # -- collective slots ------------------------------------------------
    def allreduce_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        # coll/quant tier: an EXPLICIT per-comm accuracy budget (the
        # info key) routes eligible (dtype, size) cells onto the
        # block-quantized program.  The `in` probe is one dict get;
        # comms that never declared a budget pay nothing else.
        if quant_mod.BUDGET_KEY in comm.info and op.jax_reduce == "psum":
            codec = quant_mod.pick(comm, "allreduce",
                                   getattr(x, "dtype", None),
                                   int(getattr(x, "nbytes", 0)), op)
            if codec is not None:
                return self._quant_allreduce(comm, x, op, codec)
        # steady-state fast path: one dict probe, then straight into the
        # compiled program
        if isinstance(x, self._jax_array):
            fn = self._fast(_ar_key(x, op))
            if fn is not None:
                return fn(x)
        P = self._P
        fn, x = self._get(
            comm, self._keyfor("allreduce", x, op), x,
            lambda: self._shard_map(
                lambda t: self._reduce_in_shard(op)(t[0]),
                P(self.axis), P(), name=_program_name("allreduce", op)))
        return fn(x)

    def _quant_allreduce(self, comm, x, op: op_mod.Op, codec: str):
        """Block-quantized allreduce: per-shard encode (pallas), gather
        the int8 payloads + per-block scales over the mesh axis, and a
        fused dequant-accumulate kernel folds them — the encoded bytes
        (~3.9x fewer for int8, 2x for bf16) are what cross the links."""
        import jax
        import jax.numpy as jnp

        P = self._P
        ax = self.axis

        def body(t):  # (1, *S) -> (*S), replicated like allreduce
            from ompi_tpu.ops import pallas_quant as pq

            flat = t[0].reshape(-1)
            if codec == "bf16":
                g = jax.lax.all_gather(flat.astype(jnp.bfloat16), ax)
                return jnp.sum(g.astype(jnp.float32),
                               axis=0).reshape(t[0].shape)
            q, s = pq.encode_int8(flat)
            qg = jax.lax.all_gather(q, ax)
            sg = jax.lax.all_gather(s, ax)
            out = pq.dequant_accumulate(qg, sg)
            return out.reshape(-1)[:flat.shape[0]].reshape(t[0].shape)

        # pick() already required a real dtype, so x carries shape/dtype
        fn, x = self._get(
            comm, ("allreduce_quant", codec, op.name, x.shape, x.dtype),
            x, lambda: self._shard_map(
                body, P(self.axis), P(),
                name=_program_name("allreduce_quant", codec)))
        return fn(x)

    def reduce_array(self, comm, x, op: op_mod.Op = op_mod.SUM,
                     root: int = 0):
        """Reduction lands in root's row; other rows are zero (their
        content is undefined per MPI — zeros make misuse visible).

        Binomial ppermute tree toward root (the device-native shape of
        ``coll_base_reduce.c``'s binomial algorithm): log2(n) halving
        rounds, each sender transmitting its partial exactly once, so
        total wire traffic is (n-1)·S — an allreduce-then-mask would
        move ~2x that and an all_gather construction n²·S."""
        import jax
        import jax.numpy as jnp

        P = self._P
        n, ax = self.n, self.axis
        fold = op_mod.jax_fold(op, None)

        def body(t):  # (1, *S)
            me = jax.lax.axis_index(ax)
            rel = jnp.mod(me - root, n)
            cur = t[0]
            k = 1
            while k < n:           # largest power of two below n
                k *= 2
            k //= 2
            while k >= 1:
                # senders rel in [k, min(2k, n)) -> receivers rel - k;
                # after the round the active set halves to [0, k)
                pairs = [((root + r) % n, (root + r - k) % n)
                         for r in range(k, min(2 * k, n))]
                recvd = jax.lax.ppermute(cur, ax, pairs)
                # ppermute delivers zeros to non-targets: mask the fold
                # (max/min/prod would corrupt on a zero fill)
                is_recv = (rel < k) & (rel + k < n)
                cur = jnp.where(is_recv, fold(cur, recvd), cur)
                k //= 2
            return jnp.where(me == root, cur, jnp.zeros_like(cur))[None]

        fn, x = self._get(
            comm, self._keyfor("reduce", x, op, root), x,
            lambda: self._shard_map(body, P(self.axis), P(self.axis),
                                    name=_program_name("reduce", op)))
        return fn(x)

    def bcast_array(self, comm, x, root: int = 0):
        """Broadcast with the reference's two-regime selection
        (``coll_base_bcast.c`` + the tuned bcast ladder):

        * small payloads — binomial ppermute tree, log2(n) rounds
          (XLA's CollectivePermute disallows one-to-many pairs, so the
          tree is explicit), latency-optimal;
        * payloads ≥ ``bcast_sa_min_bytes`` — one masked all-reduce over
          the shard as it arrives (``otpu_bcast_psum``): every rank but
          root contributes zeros, so XLA lowers one tuned all-reduce,
          2(n-1)/n x S per chip's links, and nothing else.  The sum is
          the dtype's own, so a float payload's ``-0.0`` arrives as
          ``+0.0`` (on the TPU subnormals flush and NaN payloads are
          canonical too, as in any float all-reduce); summing the bits
          as integers is exact but costs a pass more (PERF.md, PR 25).
        """
        if isinstance(x, self._jax_array):
            fn = self._fast(self._keyfor("bcast", x, root))
            if fn is not None:
                return fn(x)
        import jax
        import jax.numpy as jnp

        P = self._P
        n, ax = self.n, self.axis
        per_payload = (int(np.prod(x.shape[1:])) *
                       np.dtype(x.dtype).itemsize)

        def body_tree(t):  # t: (1, *S)
            me = jax.lax.axis_index(ax)
            rel = (me - root) % n
            cur = t
            k = 1
            while k < n:
                perm = [((root + i) % n, (root + i + k) % n)
                        for i in range(min(k, n - k))]
                recvd = jax.lax.ppermute(cur, ax, perm)
                newly = (rel >= k) & (rel < 2 * k)
                cur = jnp.where(newly, recvd, cur)
                k *= 2
            return cur

        def body_psum(t):  # t: (1, *S)
            return _root_only_psum(t, ax, root)

        body = (body_psum if per_payload >= self.bcast_sa_min_bytes
                else body_tree)
        fn, x = self._get(
            comm, self._keyfor("bcast", x, root), x,
            lambda: self._shard_map(
                body, P(self.axis), P(self.axis),
                name=_program_name(
                    "bcast", "psum" if body is body_psum else "tree")))
        return fn(x)

    def allgather_array(self, comm, x):
        # coll/quant tier: same explicit-budget gate as allreduce —
        # each rank's block travels encoded and decodes at every
        # receiver (within the codec band)
        if quant_mod.BUDGET_KEY in comm.info:
            codec = quant_mod.pick(comm, "allgather",
                                   getattr(x, "dtype", None),
                                   int(getattr(x, "nbytes", 0)))
            if codec is not None:
                return self._quant_allgather(comm, x, codec)
        if isinstance(x, self._jax_array):
            fn = self._fast(self._keyfor("allgather", x))
            if fn is not None:
                return fn(x)
        import jax

        P = self._P
        fn, x = self._get(
            comm, self._keyfor("allgather", x), x,
            lambda: self._shard_map(
                lambda t: jax.lax.all_gather(t[0], self.axis),
                P(self.axis), P(), name=_program_name("allgather")))
        return fn(x)

    def _quant_allgather(self, comm, x, codec: str):
        """Block-quantized allgather: encode per shard, gather the
        encoded payloads, decode all rows locally (pallas dequant)."""
        import jax
        import jax.numpy as jnp

        P = self._P
        ax = self.axis
        n = self.n

        def body(t):  # (1, *S) -> (n, *S), replicated
            from ompi_tpu.ops import pallas_quant as pq

            flat = t[0].reshape(-1)
            if codec == "bf16":
                g = jax.lax.all_gather(flat.astype(jnp.bfloat16), ax)
                return g.astype(jnp.float32).reshape(
                    (n,) + t[0].shape)
            q, s = pq.encode_int8(flat)
            qg = jax.lax.all_gather(q, ax)        # (n, rows, 128)
            sg = jax.lax.all_gather(s, ax)        # (n, rows, 1)
            dec = pq.decode_int8(qg, sg)          # (n, rows, 128) f32
            return dec.reshape(n, -1)[:, :flat.shape[0]].reshape(
                (n,) + t[0].shape)

        fn, x = self._get(
            comm, ("allgather_quant", codec, x.shape, x.dtype), x,
            lambda: self._shard_map(
                body, P(self.axis), P(),
                name=_program_name("allgather_quant", codec)))
        return fn(x)

    def allgatherv_array(self, comm, x, counts):
        """Padded allgatherv: blocks padded to a common (max) first dim.

        Ragged shapes don't exist under XLA's static-shape model, so the
        v-variant is allgather of padded blocks + zero-copy host-side
        views: returns a list of per-rank arrays sliced to ``counts[i]``.
        """
        counts = tuple(int(c) for c in counts)
        if len(counts) != self.n:
            raise MpiError(ErrorClass.ERR_BUFFER,
                           f"allgatherv needs {self.n} counts, got "
                           f"{len(counts)}")
        full = self.allgather_array(comm, x)  # (n, Smax, ...)
        return [full[i, :counts[i]] for i in range(self.n)]

    def gather_array(self, comm, x, root: int = 0):
        """Gathered rows land at root; non-root rows are zero.

        Binomial ppermute tree toward root (``coll_base_gather.c``
        binomial): at round k each sender forwards its accumulated
        k-block subtree window once, so total wire traffic is
        O(n·log n·S/2) — an all_gather-then-mask would move n²·S.  The
        window is a static (k, *S) slice per round (XLA needs static
        shapes); boundary subtrees clamp identically on both sides of a
        pair and the overlap adds zeros, so the add-paste is exact."""
        import jax
        import jax.numpy as jnp

        P = self._P
        n, ax = self.n, self.axis

        def body(t):  # (1, *S) -> (1, n, *S)
            me = jax.lax.axis_index(ax)
            rel = jnp.mod(me - root, n)
            zero_starts = (0,) * (t.ndim - 1)
            buf = jnp.zeros((n,) + t.shape[1:], t.dtype)
            buf = jax.lax.dynamic_update_slice(
                buf, t, (rel,) + zero_starts)   # my block at slot rel
            k = 1
            while k < n:
                # senders rel ≡ k (mod 2k) own the k-block window
                # [rel, rel+k); the receiver rel-k pastes it at the
                # same global slots.  dynamic_slice clamps both sides
                # to n-k in lockstep (receiver start rel+k == sender
                # start), and clamp-overlapped slots are still zero on
                # the sending side, so buf + contrib never collides.
                pairs = [((root + r) % n, (root + r - k) % n)
                         for r in range(k, n, 2 * k)]
                win = jax.lax.dynamic_slice(
                    buf, (rel,) + zero_starts, (k,) + t.shape[1:])
                recvd = jax.lax.ppermute(win, ax, pairs)
                contrib = jax.lax.dynamic_update_slice(
                    jnp.zeros_like(buf), recvd,
                    (rel + k,) + zero_starts)
                buf = buf + contrib   # non-receivers add ppermute zeros
                k *= 2
            out = jnp.roll(buf, root, axis=0)   # slot rel -> rank order
            return jnp.where(me == root, out, jnp.zeros_like(out))[None]

        fn, x = self._get(
            comm, self._keyfor("gather", x, root), x,
            lambda: self._shard_map(body, P(self.axis), P(self.axis),
                                    name=_program_name("gather")))
        return fn(x)

    def reduce_scatter_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        """Each rank contributes (n, *S); rank i receives the reduced block i.

        Result: global (n, *S) sharded over the rank axis.
        """
        if isinstance(x, self._jax_array):
            fn = self._fast(self._keyfor("reduce_scatter", x, op))
            if fn is not None:
                return fn(x)
        import jax

        P = self._P
        if op.jax_reduce == "psum":
            def body(t):  # t: (1, n, *S)
                return jax.lax.psum_scatter(
                    t[0], self.axis, scatter_dimension=0, tiled=False)[None]
        else:
            reduce_body = self._reduce_in_shard(op)

            def body(t):
                full = reduce_body(t[0])          # (n, *S) reduced
                i = jax.lax.axis_index(self.axis)
                return jax.lax.dynamic_index_in_dim(full, i, 0)

        fn, x = self._get(
            comm, self._keyfor("reduce_scatter", x, op), x,
            lambda: self._shard_map(
                body, P(self.axis), P(self.axis),
                name=_program_name("reduce_scatter", op)),
            inner_n=True)
        return fn(x)

    def psum_scatter_array(self, comm, x):
        return self.reduce_scatter_array(comm, x, op_mod.SUM)

    def alltoall_array(self, comm, x, sendtype=None, recvtype=None,
                       count: int = 1):
        """x[i, j] moves to result[j, i] (rank j receives x[:, j]).  With
        ``sendtype`` each x[i, j] is a described buffer of which ``count``
        elements of the type are sent; with ``recvtype`` each block lands
        through that type (:meth:`_typed`).  One program either way."""
        if sendtype is not None or recvtype is not None:
            return self._typed(comm, "alltoall", x, sendtype, recvtype,
                               count)
        if isinstance(x, self._jax_array):
            fn = self._fast(self._keyfor("alltoall", x))
            if fn is not None:
                return fn(x)
        fn, x = self._get(
            comm, self._keyfor("alltoall", x), x,
            lambda: self._shard_map(self._alltoall_body, self._P(self.axis),
                                    self._P(self.axis),
                                    name=_program_name("alltoall")),
            inner_n=True)
        return fn(x)

    def _alltoall_body(self, t):  # (1, n, *S)
        import jax
        import jax.numpy as jnp

        y = jax.lax.all_to_all(t, self.axis, split_axis=1, concat_axis=0)
        return jnp.swapaxes(y, 0, 1)  # (1, n, *S): row = my received blocks

    def alltoallv_array(self, comm, x, counts):
        """Padded alltoallv: x (n, n, Smax, ...), counts[i][j] = rows rank j
        receives from rank i.  Returns list-of-lists of sliced views."""
        full = self.alltoall_array(comm, x)  # row i = blocks received by i
        return [[full[i, j, :int(counts[j][i])] for j in range(self.n)]
                for i in range(self.n)]

    def ppermute_array(self, comm, x, perm, sendtype=None, recvtype=None,
                       count: int = 1):
        """Row s of x moves to row d for each (s, d) of ``perm``.  With
        ``sendtype`` / ``recvtype`` the rows are described buffers
        (:meth:`_typed`); on a one-rank world ``((0, 0),)`` is a send to
        self.  One program either way."""
        import jax

        P = self._P
        perm = tuple((int(s), int(d)) for s, d in perm)
        if sendtype is not None or recvtype is not None:
            return self._typed(comm, "ppermute", x, sendtype, recvtype,
                               count, perm)
        fn, x = self._get(
            comm, self._keyfor("ppermute", x, perm), x,
            lambda: self._shard_map(
                lambda t: jax.lax.ppermute(t, self.axis, perm),
                P(self.axis), P(self.axis),
                name=_program_name("ppermute")))
        return fn(x)

    def _typed(self, comm, coll: str, x, sendtype, recvtype, count: int,
               *args):
        """``ppermute`` / ``alltoall`` with derived datatypes, as ONE
        program: each rank's buffer (each of its n blocks, for alltoall)
        is packed through ``sendtype``'s device plan, the packed streams
        cross, and each lands through ``recvtype``'s plan in a new buffer
        that is zero outside the type map.  A type left None is
        contiguous: the rows are (sendtype None), or come back as
        (recvtype None), packed streams.  ``count`` elements of each
        type, whose packed sizes must agree.  Keyed in the program cache
        by the plans' keys: two datatypes of one regular map share a
        program."""
        import jax

        from ompi_tpu.datatype.plan import plan_for

        plans = [None if t is None else plan_for(t, count)
                 for t in (sendtype, recvtype)]
        send, recv = plans
        if send is not None and recv is not None \
                and send.packed != recv.packed:
            raise MpiError(
                ErrorClass.ERR_TRUNCATE,
                f"typed {coll}: sendtype packs {send.packed} elements, "
                f"recvtype {recv.packed}")
        key = self._keyfor(coll, x, *args) + tuple(
            None if p is None else p.key for p in plans)
        batch = 2 if coll == "alltoall" else 1    # leading axes of a row

        def build():
            ax, P = self.axis, self._P
            if coll == "alltoall":
                cross = self._alltoall_body
            else:
                def cross(t):
                    return jax.lax.ppermute(t, ax, args[0])
            index = tuple(
                () if p is None else p.index_args(which, self._replicated)
                for p, which in zip(plans, ("pack", "unpack")))
            n_send = len(index[0])
            index = index[0] + index[1]

            def body(t, *index):
                pack = None if send is None else (
                    lambda b: send.pack(b, *index[:n_send]))
                unpack = None if recv is None else (
                    lambda b: recv.unpack(b, *index[n_send:]))
                for _ in range(batch):  # one buffer a (rank[, block])
                    pack = pack and jax.vmap(pack)
                    unpack = unpack and jax.vmap(unpack)
                if pack is not None:
                    t = pack(t)
                t = cross(t)
                return t if unpack is None else unpack(t)

            prog = self._shard_map(
                body, (P(ax),) + (P(),) * len(index), P(ax),
                name=_program_name(coll, "ddt"))
            if not index:
                return prog
            return lambda t: prog(t, *index)

        fn, x = self._get(comm, key, x, build, inner_n=batch == 2)
        return fn(x)

    def scatter_array(self, comm, x, root: int = 0):
        """Scatter root's buffer: x (n, n, *S) where row root holds
        root's n blocks; rank i receives block i.

        Binomial ppermute tree outward from root — the exact mirror of
        :meth:`gather_array`'s tree (``coll_base_scatter.c`` binomial):
        at round k (descending) each holder forwards the half of its
        subtree window it does not keep, so total wire traffic is
        O(n·log n·S/2) where the previous all_to_all construction moved
        every rank's dead-freight row (n²·S).  Same static-window +
        clamp-lockstep discipline as the gather tree, halving instead
        of doubling."""
        import jax
        import jax.numpy as jnp

        P = self._P
        n, ax = self.n, self.axis
        kmax = 1
        while kmax * 2 < n:
            kmax *= 2

        def body(t):  # (1, n, *S) -> (1, *S)
            me = jax.lax.axis_index(ax)
            rel = jnp.mod(me - root, n)
            blk = t[0]                      # (n, *S); valid at root only
            zero_starts = (0,) * (blk.ndim - 1)
            # slot-rotate so the tree runs in rel space: buf slot s =
            # block of rel s (root holds all, everyone else zeros)
            buf = jnp.where(rel == 0, jnp.roll(blk, -root, axis=0),
                            jnp.zeros_like(blk))
            k = kmax
            while k >= 1:
                # holders rel ≡ 0 (mod 2k) own window [rel, rel+2k);
                # they forward the upper half [rel+k, rel+2k) to rel+k
                pairs = [((root + r) % n, (root + r + k) % n)
                         for r in range(0, n - k, 2 * k)]
                win = jax.lax.dynamic_slice(
                    buf, (rel + k,) + zero_starts,
                    (k,) + blk.shape[1:])
                recvd = jax.lax.ppermute(win, ax, pairs)
                contrib = jax.lax.dynamic_update_slice(
                    jnp.zeros_like(buf), recvd, (rel,) + zero_starts)
                buf = buf + contrib   # non-receivers add ppermute zeros
                k //= 2
            return jax.lax.dynamic_index_in_dim(buf, rel, 0)

        fn, x = self._get(
            comm, self._keyfor("scatter", x, root), x,
            lambda: self._shard_map(body, P(self.axis), P(self.axis),
                                    name=_program_name("scatter")),
            inner_n=True)
        return fn(x)

    def scan_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        """Inclusive scan over ranks: row i = reduce(rows 0..i)."""
        import jax

        P = self._P

        def body(t):  # (1, *S)
            # scans want a fold XLA can fuse into associative_scan
            fold = op_mod.jax_fold(op, t.dtype, fusable=True)
            g = jax.lax.all_gather(t[0], self.axis)        # (n, *S)
            # fold convention: acc = in (op) acc, rank-ordered
            s = jax.lax.associative_scan(lambda a, b: fold(a, b), g, axis=0)
            i = jax.lax.axis_index(self.axis)
            return jax.lax.dynamic_index_in_dim(s, i, 0)

        fn, x = self._get(
            comm, self._keyfor("scan", x, op), x,
            lambda: self._shard_map(body, P(self.axis), P(self.axis),
                                    name=_program_name("scan", op)))
        return fn(x)

    def exscan_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        """Exclusive scan; rank 0's row is zeros (MPI: undefined)."""
        import jax
        import jax.numpy as jnp

        P = self._P

        def body(t):
            fold = op_mod.jax_fold(op, t.dtype, fusable=True)
            g = jax.lax.all_gather(t[0], self.axis)
            s = jax.lax.associative_scan(lambda a, b: fold(a, b), g, axis=0)
            i = jax.lax.axis_index(self.axis)
            prev = jax.lax.dynamic_index_in_dim(
                s, jnp.maximum(i - 1, 0), 0, keepdims=False)
            return jnp.where(i == 0, jnp.zeros_like(prev), prev)[None]

        fn, x = self._get(
            comm, self._keyfor("exscan", x, op), x,
            lambda: self._shard_map(body, P(self.axis), P(self.axis),
                                    name=_program_name("exscan", op)))
        return fn(x)

    # -- persistent collectives (MPI_Allreduce_init analog) --------------
    def persistent_coll(self, comm, coll: str, template, *args):
        """Pre-bind a compiled collective for a template buffer.

        Runs the named collective once eagerly (building + caching the
        program, validating the template) and returns a ``PersistentColl``
        whose ``__call__``/``start`` skip everything but the XLA dispatch.
        """
        method = getattr(self, coll + "_array", None)
        if method is None:
            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           f"no device collective '{coll}'")
        return self._bind(method, comm, coll,
                          self._check(comm, template), args)

    def _bind(self, method, comm, coll: str, template, args):
        method(comm, template, *args)   # build + cache + validate
        fn, nbytes = self._cache[self._keyfor(coll, template, *args)]
        return PersistentColl(fn, coll, nbytes)

    def partitioned_coll(self, comm, coll: str, buckets, *args):
        """Device side of the partitioned persistent collective (MPI-4
        ``Pallreduce_init`` analog, ``api/comm.py pallreduce_init``):
        the launches of a step, planned and compiled once.  Every bucket
        is bound as its own persistent collective (what a ``Parrived``
        poll falls back on), then ``plan_groups`` cuts the buckets into
        runs of at least ``pgroup_min_bytes`` (one rank's bytes) and each
        run of two or more gets ONE program, members in and members
        out, so B ``Pready``s cost a launch a group and not a launch a
        bucket.  Returns ``(handles, plan)``: ``plan[g]`` is ``(members,
        GroupedColl)``, or ``(members, None)`` for a group of one, which
        launches through ``handles`` as it always did."""
        if coll != "allreduce":
            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           f"no partitioned device collective '{coll}'")
        buckets = [self._check(comm, b) for b in buckets]
        handles = [self._bind(self.allreduce_array, comm, coll, b, args)
                   for b in buckets]
        op = args[0] if args else op_mod.SUM
        plan = []
        for members in plan_groups([b.nbytes // self.n for b in buckets],
                                   self.pgroup_min_bytes):
            grouped = None
            if len(members) > 1:
                fn, nbytes = self._group_program(
                    coll, op, [buckets[i] for i in members])
                grouped = GroupedColl(fn, coll, len(members), nbytes)
            plan.append((members, grouped))
        return handles, plan

    def _group_program(self, coll: str, op: op_mod.Op, templates):
        """The program of one group and the bytes a call moves, cached
        by the members' shapes and dtypes (equal groups share one
        executable).  A miss builds it (``_group_fn``) and runs it once
        on the templates, so nothing compiles inside a step; that call
        counts no collective."""
        key = ("pgroup", coll, op.name,
               tuple((t.shape, t.dtype) for t in templates))
        entry = self._cache.get(key)
        first = None
        if entry is None:
            with self._lock:
                entry = self._cache.get(key)
                if entry is None:
                    spc.record("device_program_builds")
                    fn = _spanned(
                        "build", key[0], templates[0],
                        lambda: self._group_fn(op, len(templates)))
                    entry = (fn, sum(t.nbytes for t in templates))
                    self._cache[key] = entry
                    first = _first_call(lambda ts: fn(*ts), key[0],
                                        templates[0])
        if first is not None:
            first(templates)
        return entry

    def _group_fn(self, op: op_mod.Op, m: int):
        """The jitted program of a group of m members: the per-bucket
        body applied to each, every reduction made to wait for the one
        before it.  Left independent, XLA's all-reduce combiner merges
        the members' psums into ONE all-reduce, which sums in another
        order than a bucket's own program: on four v5e chips a third of
        the positions then differ from the per-bucket results (PERF.md
        section 6, PR 34).  Chained through an optimization barrier each
        member keeps its own all-reduce, the per-bucket program's op at
        the per-bucket size, and its results bit for bit."""
        import jax

        P = self._P
        body = self._reduce_in_shard(op)

        def group(*ts):
            ts, outs = list(ts), []
            for i in range(m):
                out = body(ts[i][0])
                if i + 1 < m:
                    out, ts[i + 1] = jax.lax.optimization_barrier(
                        (out, ts[i + 1]))
                outs.append(out)
            return tuple(outs)

        return self._shard_map(group, (P(self.axis),) * m, (P(),) * m,
                               name=_program_name("pallreduce", op))

    def _keyfor(self, coll: str, x, *args):
        """Single source of truth for program-cache keys (used by the
        *_array methods and persistent_coll alike).  Kept closure-free:
        this runs on every collective call."""
        if coll == "allreduce":
            return _ar_key(x, args[0] if args else op_mod.SUM)
        if coll == "reduce":
            op = args[0] if args else op_mod.SUM
            root = args[1] if len(args) > 1 else 0
            return (coll, op.name, root, x.shape, x.dtype)
        if coll in ("bcast", "gather", "scatter"):
            return (coll, args[0] if args else 0, x.shape, x.dtype)
        if coll in ("reduce_scatter", "scan", "exscan"):
            return (coll, (args[0] if args else op_mod.SUM).name,
                    x.shape, x.dtype)
        if coll in ("allgather", "alltoall"):
            return (coll, x.shape, x.dtype)
        if coll == "ppermute":
            perm = tuple((int(s), int(d)) for s, d in args[0])
            return (coll, perm, x.shape, x.dtype)
        raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                       f"no persistent binding for '{coll}'")

    def device_barrier(self, comm) -> None:
        import jax

        P = self._P
        tok = self.make_world_array(np.zeros((self.n, 1), np.float32))
        fn, tok = self._get(
            comm, ("barrier",), tok,
            lambda: self._shard_map(
                lambda t: jax.lax.psum(t, self.axis), P(self.axis), P(),
                name=_program_name("barrier")))
        jax.block_until_ready(fn(tok))

    def barrier(self, comm) -> None:
        self.device_barrier(comm)


class XlaMpCollModule:
    """coll/xla for the MULTI-PROCESS device world: the communicator's
    ranks are processes of a ``jax.distributed``-booted job, and one
    compiled program spans every member's devices (the cross-process
    collectives VERDICT round 5 named as the PMIx-shaped hole).

    Data model (multi-controller SPMD — the inverse of the conductor
    model's stacked rows): every member calls the same collective with
    ITS OWN local contribution, no leading rank axis.  The module builds
    a global array whose leading axis is the comm-rank axis — row i
    lives on member i's devices, replicated across that member's local
    shards — and dispatches a jitted ``shard_map`` over a (members ×
    local-devices) mesh that every member executes.  Results of
    allreduce/bcast/allgather are replicated (fully addressable on
    every member); reduce_scatter returns the rank-sharded global array
    (my block is my addressable shard).

    Same hot-path discipline as :class:`XlaCollModule`: compiled
    programs cached per (coll, op/root, shape, dtype); a hit is one
    dict probe + relaxed SPC bump + the per-call row placement + the
    XLA dispatch.
    """

    def __init__(self, comm, rte, axis_name: str = "mpi") -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        procs = [rte.device_world_process(w)
                 for w in comm.group.world_ranks]
        by_proc: dict = {}
        for d in rte.global_devices:
            by_proc.setdefault(d.process_index, []).append(d)
        rows = [by_proc[p] for p in procs]
        width = min(len(r) for r in rows)
        if width < 1 or any(len(r) != width for r in rows):
            raise MpiError(ErrorClass.ERR_UNSUPPORTED_OPERATION,
                           "uneven per-process device counts")
        self.n = len(procs)
        self.axis = axis_name
        self.mesh = Mesh(np.array([r[:width] for r in rows]),
                         (axis_name, "device"))
        self._P = P
        self._row_sharding = NamedSharding(self.mesh, P(axis_name))
        self._cache: dict = {}
        self._lock = threading.Lock()

    # -- helpers ---------------------------------------------------------
    def make_world_array(self, local):
        """Global (n, *S) array from this member's local contribution:
        my row on my devices (replicated across local shards), every
        other row on its owner's devices."""
        import jax

        arr = np.asarray(local)
        return jax.make_array_from_process_local_data(
            self._row_sharding, arr[None], (self.n,) + arr.shape)

    def _shard_map(self, fn, in_specs, out_specs, *, name: str):
        import jax

        from jax import shard_map

        mapped = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        mapped.__name__ = mapped.__qualname__ = name
        return jax.jit(mapped)

    def _reduce_body(self, op: op_mod.Op):
        import jax

        ax = self.axis
        if op.jax_reduce == "psum":
            return lambda t: jax.lax.psum(t, ax)
        if op.jax_reduce == "pmax":
            return lambda t: jax.lax.pmax(t, ax)
        if op.jax_reduce == "pmin":
            return lambda t: jax.lax.pmin(t, ax)

        def body(t):
            gathered = jax.lax.all_gather(t, ax)      # (n, *S)
            fold = op_mod.jax_fold(op, t.dtype)
            acc = gathered[0]
            for i in range(1, self.n):
                acc = fold(gathered[i], acc)
            return acc

        return body

    def _get(self, key, builder):
        entry = self._cache.get(key)
        if entry is None:
            with self._lock:
                entry = self._cache.get(key)
                if entry is None:
                    entry = self._cache[key] = builder()
        return entry

    # -- collective slots ------------------------------------------------
    def allreduce_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        xg = self.make_world_array(x)
        P = self._P
        fn = self._get(
            ("allreduce", op.name, xg.shape, str(xg.dtype)),
            lambda: self._shard_map(
                lambda t: self._reduce_body(op)(t[0]),
                P(self.axis), P(), name=_program_name("allreduce", op)))
        spc.bump_device(xg.nbytes)
        return fn(xg)

    def bcast_array(self, comm, x, root: int = 0):
        xg = self.make_world_array(x)
        P = self._P
        ax = self.axis
        fn = self._get(   # one ring phase, replicated result
            ("bcast", int(root), xg.shape, str(xg.dtype)),
            lambda: self._shard_map(
                lambda t: _root_only_psum(t[0], ax, root), P(ax), P(),
                name=_program_name("bcast", "psum")))
        spc.bump_device(xg.nbytes)
        return fn(xg)

    def allgather_array(self, comm, x):
        import jax

        xg = self.make_world_array(x)
        P = self._P
        fn = self._get(
            ("allgather", xg.shape, str(xg.dtype)),
            lambda: self._shard_map(
                lambda t: jax.lax.all_gather(t[0], self.axis),
                P(self.axis), P(), name=_program_name("allgather")))
        spc.bump_device(xg.nbytes)
        return fn(xg)

    def reduce_scatter_array(self, comm, x, op: op_mod.Op = op_mod.SUM):
        """Each member contributes (n, *S); the result is the global
        (n, *S) array sharded over members — my reduced block is my
        addressable shard."""
        import jax

        arr = np.asarray(x)
        if arr.ndim < 1 or arr.shape[0] != self.n:
            raise MpiError(
                ErrorClass.ERR_BUFFER,
                f"reduce_scatter needs a leading rank axis {self.n}, "
                f"got shape {arr.shape}")
        xg = self.make_world_array(arr)     # (n, n, *S)
        P = self._P

        if op.jax_reduce == "psum":
            def body(t):
                return jax.lax.psum_scatter(
                    t[0], self.axis, scatter_dimension=0,
                    tiled=False)[None]
        else:
            reduce_body = self._reduce_body(op)

            def body(t):
                full = reduce_body(t[0])
                i = jax.lax.axis_index(self.axis)
                return jax.lax.dynamic_index_in_dim(full, i, 0)

        fn = self._get(
            ("reduce_scatter", op.name, xg.shape, str(xg.dtype)),
            lambda: self._shard_map(
                body, P(self.axis), P(self.axis),
                name=_program_name("reduce_scatter", op)))
        spc.bump_device(xg.nbytes)
        return fn(xg)

    def psum_scatter_array(self, comm, x):
        return self.reduce_scatter_array(comm, x, op_mod.SUM)

    def device_barrier(self, comm) -> None:
        import jax

        tok = self.allreduce_array(
            comm, np.zeros(1, np.float32), op_mod.SUM)
        jax.block_until_ready(tok)


class XlaCollComponent(Component):
    name = "xla"
    priority = 90

    def register_vars(self, fw) -> None:
        self._prio = self.register_var(
            "priority", vtype=VarType.INT, default=90,
            help="Selection priority of coll/xla (device collectives)")
        self._axis = self.register_var(
            "axis_name", default="mpi",
            help="Mesh axis name used for coll/xla collective programs")
        self._bcast_sa = self.register_var(
            "bcast_sa_min_bytes", vtype=VarType.SIZE, default="256k",
            help="Payloads at least this large broadcast as one masked "
                 "all-reduce (2(n-1)/n x S per chip's links, one "
                 "pipelined program) instead of the binomial ppermute "
                 "tree (log2(n) serial full-S hops) — the large-message "
                 "switch of the reference's coll_bcast_decision ladder "
                 "(coll_tuned_decision_fixed.c bcast rules)")

    def comm_query(self, comm):
        rte = comm.rte
        if rte is None:
            return None
        if not rte.is_device_world:
            # multi-process device world: comm ranks are processes of a
            # jax.distributed-booted job — select the cross-process
            # module (host colls keep their own slots; this only fills
            # the *_array entry points)
            if not getattr(rte, "device_world_booted", False):
                return None
            if comm.is_inter:
                return None
            # a booted device world that cannot build its module must
            # say so, not silently lose its device collectives
            return self._prio.value, XlaMpCollModule(
                comm, rte, self._axis.value)
        try:
            devices = [rte.device_of(r) for r in comm.group.world_ranks]
        except Exception:
            return None
        if not devices or any(d is None for d in devices):
            return None
        return self._prio.value, XlaCollModule(
            comm, devices, self._axis.value,
            bcast_sa_min_bytes=int(self._bcast_sa.value))


COMPONENT = XlaCollComponent()
