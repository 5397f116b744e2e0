"""Datatype descriptions: named types, constructors, flattened type maps.

Re-design of ``/root/reference/opal/datatype/opal_datatype.h`` +
``ompi/datatype/ompi_datatype.h``: a datatype is a *type map* — an ordered
list of (byte offset, elementary type, count) runs — with MPI extent
semantics (lb/ub, true extent, resizing).  Construction-time coalescing of
memory-adjacent same-type runs mirrors ``opal_datatype_optimize.c``.
Elementary types are numpy dtypes, which gives vectorized host pack/unpack
and direct interop with ``jax.Array`` host buffers; ``bfloat16`` (via
ml_dtypes) is a first-class named type for TPU payloads.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

import numpy as np

from ompi_tpu.api.attributes import AttributeHost

try:  # ml_dtypes ships with jax; gives numpy bfloat16
    import ml_dtypes

    _BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    _BF16 = np.dtype(np.uint16)  # bit-compatible fallback

ORDER_C = 0
ORDER_FORTRAN = 1
DISTRIBUTE_BLOCK = 0
DISTRIBUTE_CYCLIC = 1
DISTRIBUTE_NONE = 2
DISTRIBUTE_DFLT_DARG = -1


@dataclass(frozen=True)
class Segment:
    """One elementary run: ``count`` items of ``dtype`` at byte ``offset``."""

    offset: int
    dtype: np.dtype
    count: int

    @property
    def nbytes(self) -> int:
        return self.count * self.dtype.itemsize

    @property
    def end(self) -> int:
        return self.offset + self.nbytes


def _coalesce(segments: Iterable[Segment]) -> tuple[Segment, ...]:
    """Merge runs adjacent both in type-map order and in memory."""
    out: list[Segment] = []
    for seg in segments:
        if seg.count == 0:
            continue
        if out and out[-1].dtype == seg.dtype and out[-1].end == seg.offset:
            prev = out.pop()
            seg = Segment(prev.offset, prev.dtype, prev.count + seg.count)
        out.append(seg)
    return tuple(out)


def _coalesce_runs(offs: np.ndarray, counts: np.ndarray,
                   itemsize: int) -> tuple[np.ndarray, np.ndarray]:
    """:func:`_coalesce` for runs of ONE elementary type held as arrays
    (byte offsets, item counts): the same merge, with no Python loop, so
    a type map of millions of blocks costs a few passes of numpy."""
    keep = counts != 0
    if not keep.all():
        offs, counts = offs[keep], counts[keep]
    if len(offs) < 2:
        return offs, counts
    joined = offs[:-1] + counts[:-1] * itemsize == offs[1:]
    if not joined.any():
        return offs, counts
    first = np.flatnonzero(np.concatenate(([True], ~joined)))
    return offs[first], np.add.reduceat(counts, first)


def ramp(counts: np.ndarray) -> np.ndarray:
    """0 .. n-1 for each n of ``counts``, end to end: the index of every
    item within its own run, with no loop over runs."""
    first = np.cumsum(counts) - counts
    return np.arange(int(counts.sum()), dtype=np.int64) \
        - np.repeat(first, counts)


def _as_runs(segments) -> Optional[tuple]:
    """``(offsets, counts, dtype)`` arrays for a sequence of Segments of
    one elementary type; None for a heterogeneous or empty sequence."""
    segments = tuple(segments)
    if not segments or any(s.dtype != segments[0].dtype for s in segments):
        return None
    return (np.fromiter((s.offset for s in segments), np.int64,
                        len(segments)),
            np.fromiter((s.count for s in segments), np.int64,
                        len(segments)),
            segments[0].dtype)


class Datatype(AttributeHost):
    """An MPI-style datatype: committed type map + extent bookkeeping.

    Hosts attributes (``MPI_Type_set_attr`` family) via AttributeHost,
    like communicators and windows."""

    def __init__(
        self,
        segments: Sequence[Segment] = (),
        lb: Optional[int] = None,
        ub: Optional[int] = None,
        name: str = "",
        combiner: str = "named",
        contents: tuple = (),
        runs: Optional[tuple] = None,
    ) -> None:
        # A homogeneous type map is held as arrays (``runs``: byte
        # offsets, item counts, the one elementary dtype), so that an
        # index list of millions of blocks is built, coalesced and read
        # by the device plan without one Python object a block; the
        # ``segments`` tuple is made from them on first use.  A
        # heterogeneous map (struct of different types) stays a tuple.
        if runs is None:
            runs = _as_runs(segments)
        if runs is not None:
            offs, counts, dtype = runs
            offs, counts = _coalesce_runs(
                np.asarray(offs, np.int64), np.asarray(counts, np.int64),
                dtype.itemsize)
            if not len(offs):
                runs = None
                segments = ()
        self._runs = None
        self._segments: Optional[tuple] = None
        if runs is not None:
            self._runs = (offs, counts, dtype)
            nbytes = counts * dtype.itemsize
            self.size = int(nbytes.sum())
            self.true_lb = int(offs.min())
            self.true_ub = int((offs + nbytes).max())
        else:
            self._segments = _coalesce(segments)
            self.size = sum(s.nbytes for s in self._segments)
            if self._segments:
                self.true_lb = min(s.offset for s in self._segments)
                self.true_ub = max(s.end for s in self._segments)
            else:
                self.true_lb = self.true_ub = 0
        self.lb = self.true_lb if lb is None else lb
        self.ub = self.true_ub if ub is None else ub
        self.name = name
        self.combiner = combiner
        self.contents = contents
        self.committed = False
        # single contiguous run starting at lb covering the whole extent
        self.is_contiguous = (
            self.nseg <= 1
            and self.lb == self.true_lb
            and self.extent == self.size
        )

    @property
    def segments(self) -> tuple[Segment, ...]:
        """The flattened, coalesced type map as a tuple of Segments."""
        if self._segments is None:
            offs, counts, dtype = self._runs
            self._segments = tuple(
                Segment(o, dtype, c)
                for o, c in zip(offs.tolist(), counts.tolist()))
        return self._segments

    @property
    def runs(self) -> Optional[tuple]:
        """``(byte offsets, item counts, dtype)`` of the coalesced type
        map as int64 arrays, or None where it holds more than one
        elementary type (or nothing).  What the device plan reads."""
        return self._runs

    @property
    def nseg(self) -> int:
        return len(self._runs[0]) if self._runs is not None \
            else len(self._segments)

    # -- MPI accessors ---------------------------------------------------
    @property
    def extent(self) -> int:
        return self.ub - self.lb

    @property
    def true_extent(self) -> int:
        return self.true_ub - self.true_lb

    def commit(self) -> "Datatype":
        self.committed = True
        return self

    def free(self) -> None:
        self.committed = False

    def dup(self) -> "Datatype":
        d = Datatype(self._segments or (), self.lb, self.ub, self.name,
                     "dup", (self,), runs=self._runs)
        d.committed = self.committed
        self._attrs_copy_to(d)   # MPI_Type_dup runs the keyval copy fns
        return d

    def get_envelope(self) -> tuple[str, tuple]:
        """(combiner, contents) — the decode API (``MPI_Type_get_envelope``)."""
        return self.combiner, self.contents

    def get_contents(self) -> tuple:
        """``MPI_Type_get_contents``: the constructor arguments."""
        return self.contents

    def set_name(self, name: str) -> None:
        """``MPI_Type_set_name``."""
        self.name = name

    def get_name(self) -> str:
        """``MPI_Type_get_name``."""
        return self.name

    # -- helpers used by the convertor and coll/op layers ---------------
    @property
    def elementary(self) -> Optional[np.dtype]:
        """The single elementary numpy dtype, if homogeneous (op kernels)."""
        if self._runs is not None:
            return self._runs[2]
        dtypes = {s.dtype for s in self._segments}
        return next(iter(dtypes)) if len(dtypes) == 1 else None

    def element_count(self, nbytes: int) -> int:
        """How many elementary items fit in ``nbytes`` of packed stream."""
        if self.size == 0:
            return 0
        full, rem = divmod(nbytes, self.size)
        if self._runs is not None and not rem:
            return full * int(self._runs[1].sum())
        n = full * sum(s.count for s in self.segments)
        for s in self.segments:
            if rem <= 0:
                break
            take = min(rem, s.nbytes)
            n += take // s.dtype.itemsize
            rem -= take
        return n

    def __repr__(self) -> str:
        return (f"Datatype({self.name or self.combiner}, size={self.size}, "
                f"extent={self.extent}, nseg={self.nseg})")

    def __eq__(self, other) -> bool:
        if not (isinstance(other, Datatype) and self.lb == other.lb
                and self.ub == other.ub and self.size == other.size
                and self.nseg == other.nseg):
            return False
        if self._runs is None or other._runs is None:
            return self.segments == other.segments
        return (self._runs[2] == other._runs[2]
                and np.array_equal(self._runs[0], other._runs[0])
                and np.array_equal(self._runs[1], other._runs[1]))

    def __hash__(self) -> int:
        return hash((self.size, self.nseg, self.true_lb, self.true_ub,
                     self.lb, self.ub))


def _named(np_dtype, name: str) -> Datatype:
    dt = np.dtype(np_dtype)
    return Datatype([Segment(0, dt, 1)], name=name).commit()


# Named types (``ompi/datatype/ompi_datatype_internal.h`` table equivalent;
# fixed-width only — TPU-native set includes bf16/f16).
BYTE = _named(np.uint8, "BYTE")
PACKED = _named(np.uint8, "PACKED")
BOOL = _named(np.bool_, "BOOL")
INT8 = _named(np.int8, "INT8")
INT16 = _named(np.int16, "INT16")
INT32 = _named(np.int32, "INT32")
INT64 = _named(np.int64, "INT64")
UINT8 = _named(np.uint8, "UINT8")
UINT16 = _named(np.uint16, "UINT16")
UINT32 = _named(np.uint32, "UINT32")
UINT64 = _named(np.uint64, "UINT64")
FLOAT16 = _named(np.float16, "FLOAT16")
BFLOAT16 = _named(_BF16, "BFLOAT16")
FLOAT32 = _named(np.float32, "FLOAT32")
FLOAT64 = _named(np.float64, "FLOAT64")
COMPLEX64 = _named(np.complex64, "COMPLEX64")
COMPLEX128 = _named(np.complex128, "COMPLEX128")


def _pair(first: np.dtype, name: str) -> Datatype:
    """MINLOC/MAXLOC pair types: C-struct layout of (value, int32 index)."""
    struct = np.dtype([("v", first), ("i", np.int32)], align=True)
    segs = [
        Segment(struct.fields["v"][1], np.dtype(first), 1),
        Segment(struct.fields["i"][1], np.dtype(np.int32), 1),
    ]
    return Datatype(segs, lb=0, ub=struct.itemsize, name=name).commit()


FLOAT_INT = _pair(np.float32, "FLOAT_INT")
DOUBLE_INT = _pair(np.float64, "DOUBLE_INT")
LONG_INT = _pair(np.int64, "LONG_INT")
SHORT_INT = _pair(np.int16, "SHORT_INT")
TWO_INT = _pair(np.int32, "TWO_INT")

NAMED_TYPES: dict[str, Datatype] = {
    t.name: t
    for t in (
        BYTE, PACKED, BOOL, INT8, INT16, INT32, INT64, UINT8, UINT16,
        UINT32, UINT64, FLOAT16, BFLOAT16, FLOAT32, FLOAT64, COMPLEX64,
        COMPLEX128, FLOAT_INT, DOUBLE_INT, LONG_INT, SHORT_INT, TWO_INT,
    )
}

_SIMPLE_NP: dict[str, Datatype] = {}
for _t in (BOOL, INT8, INT16, INT32, INT64, UINT8, UINT16, UINT32, UINT64,
           FLOAT16, BFLOAT16, FLOAT32, FLOAT64, COMPLEX64, COMPLEX128):
    _SIMPLE_NP.setdefault(np.dtype(_t.segments[0].dtype).str, _t)


def from_numpy_dtype(dt) -> Datatype:
    """Map a numpy dtype (simple or structured) to a Datatype."""
    dt = np.dtype(dt)
    if dt.fields:
        segs: list[Segment] = []
        for fname in dt.names:
            fdt, off = dt.fields[fname][0], dt.fields[fname][1]
            sub = from_numpy_dtype(fdt)
            for s in sub.segments:
                segs.append(Segment(off + s.offset, s.dtype, s.count))
        return Datatype(segs, lb=0, ub=dt.itemsize, name=str(dt),
                        combiner="struct")
    if dt.subdtype is not None:
        base, shape = dt.subdtype
        sub = from_numpy_dtype(base)
        return contiguous(math.prod(shape), sub)
    named = _SIMPLE_NP.get(dt.str)
    if named is not None:
        return named
    if dt.itemsize >= 1 and dt.kind in ("V", "S", "U"):
        return contiguous(dt.itemsize, BYTE)
    raise TypeError(f"unsupported numpy dtype {dt}")


# ---------------------------------------------------------------------------
# Constructors (``ompi/datatype/ompi_datatype_create_*.c`` equivalents)
# ---------------------------------------------------------------------------

def _replicate(old: Datatype, displacements_bytes, blocklens=1):
    """Place ``blocklens`` consecutive copies of ``old`` at each
    displacement (one length for all, or one a displacement).  Returns
    ``Datatype`` keywords: ``runs`` arrays where ``old`` has one
    elementary type (numpy broadcasting, no loop over blocks), else
    ``segments``."""
    disps = np.asarray(displacements_bytes, np.int64).reshape(-1)
    uniform = np.ndim(blocklens) == 0
    ext = old.extent
    if old.runs is None:
        bls = [int(blocklens)] * len(disps) if uniform else list(blocklens)
        return {"segments": [
            Segment(int(d) + b * ext + s.offset, s.dtype, s.count)
            for d, bl in zip(disps, bls) for b in range(bl)
            for s in old.segments]}
    offs, counts, dtype = old.runs
    if len(offs) == 1 and ext == old.size:
        # gap-free old: a block of n copies is one run of n x its items
        bl = np.asarray(blocklens, np.int64)
        return {"runs": (disps + offs[0],
                         np.broadcast_to(bl * counts[0], disps.shape),
                         dtype)}
    if uniform:
        starts = (disps[:, None]
                  + np.arange(int(blocklens), dtype=np.int64) * ext
                  ).reshape(-1)
    else:
        bl = np.asarray(blocklens, np.int64)
        starts = np.repeat(disps, bl) + ramp(bl) * ext
    return {"runs": ((starts[:, None] + offs).reshape(-1),
                     np.tile(counts, len(starts)), dtype)}


def _bounds(old: Datatype, displacements_bytes,
            blocklens) -> tuple[Optional[int], Optional[int]]:
    """MPI lb/ub rules: propagate explicit bounds through constructors."""
    disps = np.asarray(displacements_bytes, np.int64).reshape(-1)
    if not len(disps):
        return 0, 0
    bl = np.asarray(blocklens, np.int64)
    return (int(disps.min()) + old.lb,
            int((disps + bl * old.extent).max()) + old.lb)


def contiguous(count: int, old: Datatype) -> Datatype:
    return Datatype(lb=old.lb, ub=old.lb + count * old.extent,
                    combiner="contiguous", contents=(count, old),
                    **_replicate(old, [0], count))


def vector(count: int, blocklength: int, stride: int, old: Datatype) -> Datatype:
    return _hvector(count, blocklength, stride * old.extent, old, "vector",
                    (count, blocklength, stride, old))


def hvector(count: int, blocklength: int, stride_bytes: int,
            old: Datatype) -> Datatype:
    return _hvector(count, blocklength, stride_bytes, old, "hvector",
                    (count, blocklength, stride_bytes, old))


def _hvector(count, blocklength, stride_bytes, old, combiner, contents):
    disps = np.arange(count, dtype=np.int64) * stride_bytes
    lb, ub = _bounds(old, disps, blocklength)
    return Datatype(lb=lb, ub=ub, combiner=combiner, contents=contents,
                    **_replicate(old, disps, blocklength))


def _as_tuple(values) -> tuple:
    return tuple(np.asarray(values).tolist())


def indexed(blocklengths: Sequence[int], displacements: Sequence[int],
            old: Datatype) -> Datatype:
    disps = np.asarray(displacements, np.int64) * old.extent
    return _hindexed(blocklengths, disps, old, "indexed",
                     (_as_tuple(blocklengths), _as_tuple(displacements),
                      old))


def hindexed(blocklengths: Sequence[int], displacements_bytes: Sequence[int],
             old: Datatype) -> Datatype:
    return _hindexed(blocklengths, displacements_bytes, old, "hindexed",
                     (_as_tuple(blocklengths),
                      _as_tuple(displacements_bytes), old))


def _hindexed(blocklengths, disps, old, combiner, contents):
    n = min(len(blocklengths), len(disps))      # zip's rule, as before
    bls = np.asarray(blocklengths, np.int64)[:n]
    disps = np.asarray(disps, np.int64)[:n]
    lb, ub = _bounds(old, disps, bls)
    return Datatype(lb=lb, ub=ub, combiner=combiner, contents=contents,
                    **_replicate(old, disps, bls))


def hindexed_block(blocklength: int, displacements_bytes: Sequence[int],
                   old: Datatype) -> Datatype:
    """``MPI_Type_create_hindexed_block``: equal-length blocks at byte
    displacements (``ompi/mpi/c/type_create_hindexed_block.c``)."""
    return _hindexed(np.full(len(displacements_bytes), blocklength),
                     displacements_bytes, old, "hindexed_block",
                     (blocklength, _as_tuple(displacements_bytes), old))


def indexed_block(blocklength: int, displacements: Sequence[int],
                  old: Datatype) -> Datatype:
    return indexed(np.full(len(displacements), blocklength, np.int64),
                   displacements, old)


def create_struct(blocklengths: Sequence[int],
                  displacements_bytes: Sequence[int],
                  types: Sequence[Datatype]) -> Datatype:
    parts, lbs, ubs = [], [], []
    for bl, d, t in zip(blocklengths, displacements_bytes, types):
        parts.append(Datatype(**_replicate(t, [d], bl)))
        lbs.append(d + t.lb)
        ubs.append(d + t.lb + bl * t.extent)
    lb = min(lbs) if lbs else 0
    ub = max(ubs) if ubs else 0
    kinds = {p.elementary for p in parts if p.size}
    if len(kinds) == 1 and None not in kinds:   # one elementary type
        body = {"runs": (np.concatenate([p.runs[0] for p in parts if p.size]),
                         np.concatenate([p.runs[1] for p in parts if p.size]),
                         kinds.pop())}
    else:
        body = {"segments": [s for p in parts for s in p.segments]}
    return Datatype(lb=lb, ub=ub, combiner="struct", **body,
                    contents=(tuple(blocklengths), tuple(displacements_bytes),
                              tuple(types)))


def resized(old: Datatype, lb: int, extent: int) -> Datatype:
    return Datatype(old._segments or (), lb=lb, ub=lb + extent,
                    combiner="resized", contents=(old, lb, extent),
                    runs=old.runs)


def subarray(sizes: Sequence[int], subsizes: Sequence[int],
             starts: Sequence[int], order: int, old: Datatype) -> Datatype:
    """n-dim subarray (``MPI_Type_create_subarray``), built as nested hvectors."""
    ndims = len(sizes)
    if order == ORDER_FORTRAN:
        sizes, subsizes, starts = sizes[::-1], subsizes[::-1], starts[::-1]
    ext = old.extent
    # strides (bytes) of each dim in the full array, C order
    strides = [ext] * ndims
    for d in range(ndims - 2, -1, -1):
        strides[d] = strides[d + 1] * sizes[d + 1]
    t = contiguous(subsizes[-1], old)
    for d in range(ndims - 2, -1, -1):
        t = hvector(subsizes[d], 1, strides[d], t)
    offset = sum(starts[d] * strides[d] for d in range(ndims))
    shifted = create_struct([1], [offset], [t])
    full = ext * math.prod(sizes)
    out = resized(shifted, 0, full)
    out.combiner = "subarray"
    out.contents = (tuple(sizes), tuple(subsizes), tuple(starts), order, old)
    return out


def darray(size: int, rank: int, gsizes: Sequence[int],
           distribs: Sequence[int], dargs: Sequence[int],
           psizes: Sequence[int], order: int, old: Datatype) -> Datatype:
    """Distributed array filetype (``MPI_Type_create_darray``).

    Built by computing this rank's global element indices per dimension
    (block / cyclic(k) / none) with numpy and emitting coalesced runs —
    correct by construction; intended for I/O file views at test/checkpoint
    scale (guarded at 2^22 local elements).
    """
    ndims = len(gsizes)
    if math.prod(psizes) != size:
        raise ValueError("prod(psizes) != size")
    # rank -> process grid coords (C order: last dim fastest, MPI standard)
    coords = []
    r = rank
    for d in range(ndims - 1, -1, -1):
        coords.append(r % psizes[d])
        r //= psizes[d]
    coords = coords[::-1]

    def dim_indices(d: int) -> np.ndarray:
        n, p, c = gsizes[d], psizes[d], coords[d]
        dist, darg = distribs[d], dargs[d]
        if dist == DISTRIBUTE_NONE:
            return np.arange(n)
        if dist == DISTRIBUTE_BLOCK:
            bs = darg if darg != DISTRIBUTE_DFLT_DARG else (n + p - 1) // p
            if bs * p < n:
                raise ValueError(
                    f"darray dim {d}: block size {bs} x {p} procs < {n} "
                    f"global elements (MPI_ERR_ARG)")
            lo = c * bs
            hi = min(lo + bs, n)
            return np.arange(lo, max(lo, hi))
        if dist == DISTRIBUTE_CYCLIC:
            bs = darg if darg != DISTRIBUTE_DFLT_DARG else 1
            idx = np.arange(n)
            return idx[(idx // bs) % p == c]
        return np.arange(n)

    per_dim = [dim_indices(d) for d in range(ndims)]
    nlocal = math.prod(len(ix) for ix in per_dim)
    if nlocal > (1 << 22):
        raise ValueError("darray too large for explicit-map construction")
    ext = old.extent
    if order == ORDER_FORTRAN:
        strides = [ext * math.prod(gsizes[:d]) for d in range(ndims)]
    else:
        strides = [ext * math.prod(gsizes[d + 1:]) for d in range(ndims)]
    grids = np.meshgrid(*per_dim, indexing="ij")
    lin = sum(g.astype(np.int64) * s for g, s in zip(grids, strides))
    lin = np.sort(lin.ravel())
    out = Datatype(lb=0, ub=ext * math.prod(gsizes), combiner="darray",
                   contents=(size, rank, tuple(gsizes), tuple(distribs),
                             tuple(dargs), tuple(psizes), order, old),
                   **_replicate(old, lin))
    return out


def match_size(typeclass: str, size: int) -> Datatype:
    """``MPI_Type_match_size``: the named type of ``typeclass``
    ("integer" | "real" | "complex") with exactly ``size`` bytes
    (``ompi/mpi/c/type_match_size.c``)."""
    table = {
        "integer": {1: INT8, 2: INT16, 4: INT32, 8: INT64},
        "real": {2: BFLOAT16, 4: FLOAT32, 8: FLOAT64},
        "complex": {8: COMPLEX64, 16: COMPLEX128},
    }
    try:
        return table[str(typeclass).lower()][int(size)]
    except KeyError:
        raise ValueError(
            f"no {typeclass!r} type of {size} bytes") from None
