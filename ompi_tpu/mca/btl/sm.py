"""btl/sm — shared-memory transport for same-host ranks.

Re-design of ``/root/reference/opal/mca/btl/sm/`` (per-peer lock-free FIFOs
over a mapped segment, ``btl_sm_component.c:71-77``): each receiver owns one
SPSC byte ring per sender in a ``multiprocessing.shared_memory`` segment
(layout: head u64 | tail u64 | data[cap]), published through the modex.
Writers append length-prefixed pickled fragments when space allows and queue
the rest for retry from the progress loop; readers drain from progress.
8-byte aligned head/tail updates order the SPSC handoff (x86/ARM64
single-writer semantics; the native C++ core provides the fenced variant).
Latency sits between btl/self and btl/tcp, so bml prefers sm for co-located
peers — the reference's exact ordering.
"""
from __future__ import annotations

import os
import pickle
import socket
import struct
import numpy as np
from multiprocessing import shared_memory, resource_tracker
from typing import Optional

from ompi_tpu.base.containers import Fifo
from ompi_tpu.base.var import VarType
from ompi_tpu.ft import chaos
from ompi_tpu.mca.btl.base import CTL, Btl, Endpoint, Frag, owned_bytes
from ompi_tpu.runtime import profile, trace
from ompi_tpu.runtime.hotpath import hot_path

_HDR = struct.Struct("<QQ")  # head, tail
_LEN = struct.Struct("<I")
_DATA_OFF = _HDR.size


def _as_u8(payload) -> np.ndarray:
    """Zero-copy uint8 view of any contiguous bytes-like payload."""
    if isinstance(payload, np.ndarray):
        return payload.reshape(-1).view(np.uint8)
    return np.frombuffer(payload, np.uint8)


def _frame_hdr(frag: Frag) -> bytes:
    """Pickle the fragment's metadata WITHOUT the payload: the payload
    rides raw after the header so large messages never pay the pickle
    round trip (2 extra full-size copies at 512KB+)."""
    return pickle.dumps(
        (frag.cid, frag.src, frag.dst, frag.tag, frag.seq, frag.kind,
         frag.total_len, frag.offset, frag.meta),
        protocol=pickle.HIGHEST_PROTOCOL)


def _unframe(buf: np.ndarray) -> Frag:
    """Rebuild a Frag from one popped frame; ``data`` is a zero-copy view
    of the ring's REUSED scratch buffer, so the frag is ``borrowed``:
    valid until the next pop — queue points must call ``own_data()``."""
    (hlen,) = _LEN.unpack_from(buf, 0)
    cid, src, dst, tag, seq, kind, total_len, offset, meta = \
        pickle.loads(memoryview(buf)[_LEN.size:_LEN.size + hlen])
    return Frag(cid, src, dst, tag, seq, kind,
                buf[_LEN.size + hlen:], total_len, offset, meta,
                borrowed=True)


class _Ring:
    """SPSC byte ring over a shared memory buffer.

    push/pop run through the native C++ twins (``ompi_tpu.native``
    ring ops, the ``opal_fifo`` analog) when the library is built; the
    layout is identical either way so mixed processes interoperate.
    """

    def __init__(self, shm: shared_memory.SharedMemory, owner: bool):
        self.shm = shm
        self.owner = owner
        self.cap = len(shm.buf) - _DATA_OFF
        if owner:
            _HDR.pack_into(shm.buf, 0, 0, 0)
        self._addr = None
        self._popbuf = None
        self._framebuf = None
        try:
            from ompi_tpu import native

            if native.available():
                import ctypes

                self._native = native
                self._addr = ctypes.addressof(
                    ctypes.c_char.from_buffer(shm.buf))
        except Exception:
            self._addr = None

    def _load(self) -> tuple[int, int]:
        return _HDR.unpack_from(self.shm.buf, 0)

    def push_frame(self, hdr: bytes, payload) -> bool:
        """Push one [u32 hlen][hdr][payload] frame; payload is any
        bytes-like (ndarray views welcome — the gather-push copies them
        straight into the ring, no Python-side concatenation)."""
        a = _LEN.pack(len(hdr)) + hdr
        if self._addr is not None:
            return self._native.ring_push2(
                self._addr, self.cap, np.frombuffer(a, np.uint8),
                _as_u8(payload))
        return self.push(a + owned_bytes(payload))

    def pop_frame(self) -> Optional[np.ndarray]:
        """Pop one frame into a REUSED scratch buffer; returns a view.

        The view is valid until the next pop on this ring — receivers
        must consume it synchronously or take an owned copy (the popped
        Frag is marked ``borrowed`` accordingly).  Reuse matters: a fresh
        1MB numpy allocation per frame costs more in page faults than the
        copy itself."""
        if self._addr is not None:
            n = self._native.ring_peek_len(self._addr, self.cap)
            if n < 0:
                return None
            buf = self._framebuf
            if buf is None or len(buf) < n:
                buf = self._framebuf = np.empty(
                    max(n, 64 * 1024), np.uint8)
            if self._native.ring_pop(self._addr, self.cap, buf) < 0:
                return None
            return buf[:n]
        payload = self.pop()
        if payload is None:
            return None
        return np.frombuffer(payload, np.uint8)

    def push(self, payload: bytes) -> bool:
        if self._addr is not None:
            return self._native.ring_push(
                self._addr, self.cap,
                np.frombuffer(payload, np.uint8))
        head, tail = self._load()
        need = _LEN.size + len(payload)
        free = self.cap - (tail - head)
        if need > free:
            return False
        frame = _LEN.pack(len(payload)) + payload
        pos = tail % self.cap
        first = min(len(frame), self.cap - pos)
        self.shm.buf[_DATA_OFF + pos:_DATA_OFF + pos + first] = frame[:first]
        if first < len(frame):
            self.shm.buf[_DATA_OFF:_DATA_OFF + len(frame) - first] = \
                frame[first:]
        struct.pack_into("<Q", self.shm.buf, 8, tail + len(frame))
        return True

    def pop(self) -> Optional[bytes]:
        if self._addr is not None:
            if self._popbuf is None:   # lazy: outbound rings never pop
                self._popbuf = np.empty(self.cap, np.uint8)
            n = self._native.ring_pop(self._addr, self.cap, self._popbuf)
            if n < 0:
                return None
            return self._popbuf[:n].tobytes()
        head, tail = self._load()
        if tail - head < _LEN.size:
            return None
        pos = head % self.cap

        def read(off: int, n: int) -> bytes:
            p = (pos + off) % self.cap
            first = min(n, self.cap - p)
            out = bytes(self.shm.buf[_DATA_OFF + p:_DATA_OFF + p + first])
            if first < n:
                out += bytes(self.shm.buf[_DATA_OFF:_DATA_OFF + n - first])
            return out

        (n,) = _LEN.unpack(read(0, _LEN.size))
        if tail - head < _LEN.size + n:
            return None  # writer mid-frame
        payload = read(_LEN.size, n)
        struct.pack_into("<Q", self.shm.buf, 0, head + _LEN.size + n)
        return payload


def _attach(name: str) -> shared_memory.SharedMemory:
    shm = shared_memory.SharedMemory(name=name)
    # CPython's resource tracker would unlink segments we merely attached
    # to; the owner is responsible for cleanup (well-known workaround).
    try:
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    return shm


class SmBtl(Btl):
    name = "sm"
    priority = 50
    # shared memory pays per-handoff (scheduling + matching) cost, not
    # per-byte: with the zero-copy send path a single big eager frame is
    # one ring write, while RNDV costs 3 handoffs — measured ~2x on the
    # 512KB pingpong (a one-core CPU host, PR 4; no chip number).  The
    # 4MB ring comfortably holds two in-flight 512KB frames per peer.
    eager_limit = 512 * 1024
    rndv_eager_limit = 512 * 1024
    max_send_size = 1024 * 1024
    latency = 10          # below tcp (100), above self (0)
    bandwidth = 10000

    def __init__(self) -> None:
        super().__init__()
        self._rte = None
        self._rings_in: dict[int, _Ring] = {}    # per-sender, I own these
        self._rings_out: dict[int, _Ring] = {}   # per-receiver, attached
        self._pending: dict[int, Fifo] = {}
        self._db_rx: Optional[socket.socket] = None   # my doorbell
        self._db_tx: Optional[socket.socket] = None   # ring peers' bells
        self._db_addr: dict[int, str] = {}            # rank -> bell address
        # node identity, not raw hostname: OTPU_NODE_ID partitions ranks
        # into emulated nodes (tpurun --fake-nodes / multi-host launchers),
        # and shared memory must not be offered across that boundary so
        # inter-node traffic honestly exercises the DCN (tcp) path
        self._hostname = os.environ.get("OTPU_NODE_ID", socket.gethostname())
        self._ring_size = 4 << 20
        # doorbell registered with the native reactor (MODE_DRAIN): the
        # epoll thread consumes the dgrams and its notify eventfd wakes
        # idle_wait — the Python drain loop in progress() is skipped
        self._db_reactor = False

    def _clamped(self, limit: int) -> int:
        """A frame larger than the ring can NEVER be pushed (push would
        retry forever) — bound protocol limits to half the capacity minus
        framing/pickle slack, so two in-flight max frags always fit
        (btl.h's limits are likewise bounded by transport buffer sizes)."""
        return min(int(limit), max(1024, self._ring_size // 2 - 4096))

    def register_vars(self, fw) -> None:
        self.register_var(
            "ring_size", vtype=VarType.SIZE, default="4m",
            help="Per-peer shared-memory FIFO capacity (takes effect at "
                 "setup; rings are not resized after init)",
            on_set=lambda v: setattr(self, "_ring_size", int(v)))
        self.register_var(
            "eager_limit", vtype=VarType.SIZE, default="512k",
            help="Max eager message size over sm",
            on_set=lambda v: setattr(self, "eager_limit", self._clamped(v)))

    def setup(self, rte) -> bool:
        if rte.is_device_world or rte.world_size <= 1:
            return False
        if not hasattr(rte, "modex_put"):
            return False
        self._rte = rte
        self.max_send_size = self._clamped(self.max_send_size)
        self.eager_limit = self._clamped(self.eager_limit)
        self.rndv_eager_limit = self._clamped(self.rndv_eager_limit)
        me = rte.my_world_rank
        job = os.environ.get("OTPU_COORD", "local").replace(":", "_") \
            .replace(".", "_")
        names = {}
        # inbound rings for my job's peers (global ranks under dpm); a
        # cross-job peer has no preallocated ring and `reachable` declines
        # it, falling back to btl/tcp
        for src in getattr(rte, "job_ranks", range(rte.world_size)):
            if src == me:
                continue
            name = f"otpu_{job}_{src}_{me}_{os.getpid() & 0xffff}"
            shm = shared_memory.SharedMemory(
                name=name, create=True, size=self._ring_size + _DATA_OFF)
            self._rings_in[src] = _Ring(shm, owner=True)
            names[src] = name
        # doorbell: an abstract unix dgram socket peers ping after pushing
        # a frame, so an idle receiver blocked in progress.idle_wait wakes
        # immediately instead of sleeping out its backoff (the wakeup role
        # the reference gets from libevent + btl_sm's fifo signalling)
        db_name = None
        try:
            from ompi_tpu.runtime import progress as progress_mod

            db = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
            db.setblocking(False)
            db_name = f"\0otpu_db_{job}_{me}_{os.getpid() & 0xffff}"
            db.bind(db_name)
            self._db_rx = db
            self._db_tx = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
            self._db_tx.setblocking(False)
            from ompi_tpu.runtime import reactor as reactor_mod

            self._db_reactor = reactor_mod.engage() and reactor_mod.add(
                db.fileno(), reactor_mod.MODE_DRAIN,
                self._on_doorbell_record)
            if not self._db_reactor:
                progress_mod.register_waiter(db)
        except OSError:
            self._db_rx = self._db_tx = None
            db_name = None
        rte.modex_put("btl_sm_rings", {"host": self._hostname,
                                       "names": names, "db": db_name})
        return True

    def _ring_doorbell(self, rank: int, info: Optional[dict] = None) -> None:
        if self._db_tx is None:
            return
        db = info.get("db") if info is not None else self._db_addr.get(rank)
        if db is None:
            return
        try:
            self._db_tx.sendto(b"x", db)
        except OSError:
            pass  # full/absent: receiver still polls on its own cadence

    def reachable(self, world_rank: int, rte) -> Optional[Endpoint]:
        if self._rte is None or world_rank == rte.my_world_rank:
            return None
        # non-blocking probe: same-job peers are guaranteed published by
        # the init fence; a peer that hasn't published (a 1-rank dpm job
        # never runs sm setup at all) must not stall the bml — tcp is the
        # universal fallback
        info = rte.modex_get(world_rank, "btl_sm_rings", wait=False)
        if info is None or info["host"] != self._hostname:
            return None
        if rte.my_world_rank not in info["names"]:
            return None   # peer has no inbound ring for me (cross-job)
        return Endpoint(self, world_rank, addr=info)

    def _ring_to(self, rank: int, info: dict) -> _Ring:
        ring = self._rings_out.get(rank)
        if ring is None:
            name = info["names"][self._rte.my_world_rank]
            ring = _Ring(_attach(name), owner=False)
            self._rings_out[rank] = ring
            if info.get("db") is not None:
                self._db_addr[rank] = info["db"]
        return ring

    @hot_path
    def send(self, ep: Endpoint, frag: Frag) -> None:
        chaos_dup = False
        if chaos.enabled:
            rule = chaos.wire_send("sm", frag.kind == CTL)
            if rule is not None:
                fault = rule["fault"]
                if fault == "drop":
                    return          # best-effort CTL frame lost
                if fault == "delay":
                    chaos.sleep_ms(rule)
                chaos_dup = fault == "dup"
        ring = self._ring_to(ep.world_rank, ep.addr)
        # stage clock: header build + enqueue attempt is send.queue;
        # the ring write itself (the sm "wire") is send.wire
        _pt = profile.now() if profile.enabled else 0
        hdr = _frame_hdr(frag)
        if chaos_dup:
            # framing-level duplicate of an idempotent CTL frame
            if not ring.push_frame(hdr, frag.data):
                self._pending.setdefault(ep.world_rank, Fifo()).push(
                    (hdr, owned_bytes(frag.data)))
        if profile.enabled:
            profile.stage_span("send.queue", _pt)
        # the ring write is sm's "wire": traced like tcp's btl_sendmsg
        # so the critical path's wire bucket sees same-host traffic too
        # (the frame header carries the flow key ride-along — the full
        # pickled (src, seq) match header, see _frame_hdr)
        _t0 = trace.now() if (trace.enabled or profile.enabled) else 0
        if not ring.push_frame(hdr, frag.data):
            # defer with an OWNED payload copy: the caller's request may
            # complete (eager) and the user reuse the buffer before the
            # retry fires from the progress loop
            self._pending.setdefault(ep.world_rank, Fifo()).push(
                (hdr, owned_bytes(frag.data)))
        if trace.enabled or profile.enabled:
            t1 = trace.now()
            if trace.enabled:
                nb = getattr(frag.data, "nbytes", None)
                if nb is None:
                    nb = len(frag.data)
                trace.span("btl_ringpush", "btl", _t0, t1,
                           args={"nbytes": int(nb),
                                 "peer": ep.world_rank})
                trace.hist_record("btl_ringpush", int(nb), t1 - _t0)
            if profile.enabled:
                profile.stage_span("send.wire", _t0, t1)
        self._ring_doorbell(ep.world_rank, ep.addr)

    def _on_doorbell_record(self, etype: int, payload) -> int:
        """Reactor DOORBELL record: the dgrams were already consumed on
        the epoll thread and the notify eventfd woke any idle waiter —
        the ring drain below runs on this same progress tick, so there
        is nothing left to do here (the record IS the wakeup)."""
        return 0

    @hot_path
    def progress(self) -> int:
        events = 0
        # drain doorbell pings (edge signal only; frames carry the
        # data); with the reactor engaged the epoll thread consumed them
        if self._db_rx is not None and not self._db_reactor:
            while True:
                try:
                    self._db_rx.recv(512)
                except (BlockingIOError, InterruptedError):
                    break
                except OSError:
                    break
        # drain incoming rings
        for ring in self._rings_in.values():
            while True:
                buf = ring.pop_frame()
                if buf is None:
                    break
                if self._recv_cb is not None:
                    _pt = profile.now() if profile.enabled else 0
                    frag = _unframe(buf)
                    if profile.enabled:
                        profile.stage_span("recv.parse", _pt)
                    if chaos.enabled:
                        rule = chaos.wire_recv("sm", frag.kind == CTL)
                        if rule is not None:
                            fault = rule["fault"]
                            if fault == "delay":
                                chaos.sleep_ms(rule)
                            elif fault == "drop" and frag.kind == CTL:
                                continue   # delivery withheld
                            elif fault == "dup" and frag.kind == CTL:
                                self._recv_cb(_unframe(buf))
                    self._recv_cb(frag)
                    events += 1
        # retry pending writes
        for rank, fifo in self._pending.items():
            ring = self._rings_out.get(rank)
            if ring is None:
                continue
            while len(fifo):
                hdr, payload = fifo.pop()
                if not ring.push_frame(hdr, payload):
                    # put it back at the front by re-queueing a marker fifo
                    newf = Fifo()
                    newf.push((hdr, payload))
                    while len(fifo):
                        newf.push(fifo.pop())
                    self._pending[rank] = newf
                    break
                self._ring_doorbell(rank)
                events += 1
        return events

    # -- one-sided RMA (btl.h:949 put / :987 get) ------------------------
    #
    # Same-host "RDMA" is a mapped-segment copy: prepare_src stages the
    # contiguous bytes into a shared-memory segment (one copy); the peer's
    # get() copies straight into its destination buffer (one copy).  Two
    # copies and ONE ring handoff total — the rendezvous stream costs
    # three copies and a frame per max_send_size.  Segments are POOLED by
    # size class and peers CACHE their attachments (a registration cache,
    # opal rcache's role): creating + faulting a fresh multi-MB mapping
    # per message costs more than the copies themselves.
    rdma = True
    _RMA_POOL_CAP = 8

    def prepare_src(self, ep: Endpoint, arr) -> dict:
        src = _as_u8(arr)
        # pow2 size class with a 64KB floor
        size = 1 << max(16, (int(len(src)) - 1).bit_length())
        pool = getattr(self, "_rma_pool", None)
        if pool is None:
            pool = self._rma_pool = {}
            self._exposed = {}
        shm = None
        free = pool.get(size)
        if free:
            shm = free.pop()
        if shm is None:
            seq = self._expose_seq = getattr(self, "_expose_seq", 0) + 1
            name = (f"otpu_rg_{self._rte.my_world_rank}_"
                    f"{os.getpid() & 0xffff}_{seq}")
            shm = shared_memory.SharedMemory(name=name, create=True,
                                             size=size)
        np.copyto(np.frombuffer(shm.buf, np.uint8, count=len(src)), src)
        self._exposed[shm.name] = shm
        return {"btl": "sm", "seg": shm.name, "size": size,
                "nbytes": int(len(src))}

    def release_src(self, key: dict) -> None:
        shm = getattr(self, "_exposed", {}).pop(key["seg"], None)
        if shm is None:
            return
        pool = self._rma_pool.setdefault(key["size"], [])
        if len(pool) < self._RMA_POOL_CAP:
            pool.append(shm)   # keep warm: name is stable, peers stay
            return             # attached across reuses
        try:
            shm.close()
            shm.unlink()
        except OSError:
            pass

    def _rma_attach(self, name: str) -> shared_memory.SharedMemory:
        cache = getattr(self, "_attached", None)
        if cache is None:
            cache = self._attached = {}
        shm = cache.get(name)
        if shm is None:
            shm = cache[name] = _attach(name)
            while len(cache) > 4 * self._RMA_POOL_CAP:
                oldest = next(iter(cache))   # insertion order: never the
                if oldest == name:           # entry just added
                    break
                old = cache.pop(oldest)
                try:
                    old.close()
                except OSError:
                    pass
        return shm

    def get(self, ep: Endpoint, local, remote_key: dict) -> None:
        dst = _as_u8(local)
        n = min(len(dst), remote_key["nbytes"])
        shm = self._rma_attach(remote_key["seg"])
        np.copyto(dst[:n], np.frombuffer(shm.buf, np.uint8, count=n))

    def put(self, ep: Endpoint, local, remote_key: dict) -> None:
        src = _as_u8(local)
        n = min(len(src), remote_key["nbytes"])
        shm = self._rma_attach(remote_key["seg"])
        np.copyto(np.frombuffer(shm.buf, np.uint8, count=n), src[:n])

    def close(self) -> None:
        # Flush queued writes before teardown: a request may complete once
        # its frags are packed, so exiting with a non-empty pending queue
        # would silently drop delivered-but-unsent data (the receiver is
        # still draining its ring — give it a bounded window).
        import time as _time

        from ompi_tpu.ft import state as _ft_state

        def _undeliverable(rank: int) -> bool:
            return _ft_state.is_failed(rank)

        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            live_pending = {r: f for r, f in self._pending.items()
                            if len(f) and not _undeliverable(r)}
            if not live_pending:
                break
            if self.progress() == 0:
                _time.sleep(0.0005)
        if self._db_rx is not None:
            if self._db_reactor:
                from ompi_tpu.runtime import reactor as reactor_mod

                reactor_mod.remove(self._db_rx.fileno())
                self._db_reactor = False
            else:
                from ompi_tpu.runtime import progress as progress_mod

                progress_mod.unregister_waiter(self._db_rx)
            try:
                self._db_rx.close()
            except OSError:
                pass
            self._db_rx = None
        if self._db_tx is not None:
            try:
                self._db_tx.close()
            except OSError:
                pass
            self._db_tx = None
        for ring in self._rings_out.values():
            try:
                ring.shm.close()
            except Exception:
                pass
        for ring in self._rings_in.values():
            try:
                ring.shm.close()
                ring.shm.unlink()
            except Exception:
                pass
        self._rings_in.clear()
        self._rings_out.clear()
        for shm in getattr(self, "_attached", {}).values():
            try:
                shm.close()
            except OSError:
                pass
        if hasattr(self, "_attached"):
            self._attached.clear()
        pool_segs = [s for segs in getattr(self, "_rma_pool", {}).values()
                     for s in segs]
        for shm in list(getattr(self, "_exposed", {}).values()) + pool_segs:
            try:
                shm.close()
                shm.unlink()
            except OSError:
                pass
        if hasattr(self, "_exposed"):
            self._exposed.clear()
        if hasattr(self, "_rma_pool"):
            self._rma_pool.clear()


COMPONENT = SmBtl()
