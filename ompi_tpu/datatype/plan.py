"""Device plans: a datatype's type map compiled for buffers in HBM.

The host convertor walks byte offsets; a device buffer is an array of
elements under a tiled layout, so the same type map is compiled once, for
a datatype and a count, into one of two forms, chosen from the map and
never from the constructor that wrote it:

* **regular**: the map is a nest of constant strides (any depth of
  ``vector`` / ``hvector`` / ``subarray`` / ``contiguous`` / ``resized``,
  or an ``indexed`` that happens to be one).  Where the strides divide
  one another and the blocks do not straddle a row, the described buffer
  is viewed as an n-dimensional array of the strides, and a pack is a
  reshape, a slice and a transpose, which XLA runs as one strided copy
  that reads only the tiles it needs; an unpack is the same backwards
  (a pad into zeros, or a ``dynamic_update_slice`` into ``into``).
* **index list**: everything else, with two executions of a pack, chosen
  at build from the index array alone.  *Gathered*: a device ``int32``
  array of block starts in elements, ``lax.gather``: 19-21 ns an index on
  a v5e whatever the list looks like.  *Streamed*: where the starts are
  sorted and distinct, the type is 4 bytes wide and the list is dense
  (128 packed elements come from at most ``STREAM_SLABS`` x 8 consecutive
  128-lane rows of the buffer: about one element in 128 or more), the
  span is read once through VMEM and compacted
  there (``ops/pallas_ddt.compact``): the plan holds, for every packed
  element, its source row within its output row's run and its lane as
  one ``int32``, and the index list itself reaches the device only when
  an unpack asks for it.  Unsorted, overlapping, sparse and 2- or 8-byte
  lists are gathered as before; an unpack is a scatter either way.

A plan traces; it owns no program.  ``mca/accelerator/jax_acc`` jits it
for ``pack_array`` / ``unpack_array``, ``mca/coll/xla`` traces it inside a
typed slot's one program.  Plans are cached on the datatype (as the host
convertor's segment tables are); two datatypes whose maps are one regular
nest share one plan object.
"""
from __future__ import annotations

import itertools
import threading
from typing import Optional

import numpy as np

from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.datatype.core import ramp
from ompi_tpu.runtime import spc, trace

_serial = itertools.count()
_lock = threading.Lock()
_shared: dict = {}              # a regular plan's key -> the plan: twins share one


def _nest(offs: np.ndarray):
    """``(base, [(n, stride), ...])`` outermost first, for block starts
    that are a nest of constant strides in type-map order; None where
    they are not.  Reads the array a level at a time (the period of the
    innermost run, then the starts of the runs), never a block."""
    dims = []
    cur = offs
    while len(cur) > 1:
        d = np.diff(cur)
        if (d == d[0]).all():
            dims.append((len(cur), int(d[0])))
            cur = cur[:1]
            break
        period = int(np.argmax(d != d[0])) + 1
        if len(cur) % period:
            return None
        rows = cur.reshape(-1, period)
        if not np.array_equal(rows - rows[:, :1],
                              np.broadcast_to(rows[0] - rows[0, 0],
                                              rows.shape)):
            return None
        dims.append((period, int(d[0])))
        cur = rows[:, 0]
    return int(cur[0]), dims[::-1]


def _normalise(dims: list, block: int):
    """Drop unit dims, merge a dim into the one inside it where the two
    are one run of strides, and the innermost into the block where the
    blocks touch."""
    dims = [(n, s) for n, s in dims if n != 1]
    merged = True
    while merged:
        merged = False
        if dims and dims[-1][1] == block:
            block *= dims.pop()[0]
            merged = True
        for i in range(len(dims) - 1):
            (n1, s1), (n2, s2) = dims[i], dims[i + 1]
            if s1 == n2 * s2:
                dims[i:i + 2] = [(n1 * n2, s2)]
                merged = True
                break
    return dims, block


class Plan:
    """One datatype and count, compiled for device buffers.

    ``dtype``: the elementary type, which the packed stream keeps;
    ``packed``: elements in the packed stream; ``need``: elements the
    described buffer must hold; ``hi``: elements of the buffer an unpack
    with no ``into`` returns; ``key``: what a program cache may key on
    (equal keys trace equal programs)."""

    form = ""
    stream = None       # an index list's streaming tables, where it has them

    def __init__(self, dtype, packed: int, need: int, hi: int, key) -> None:
        self.dtype = np.dtype(dtype)
        self.packed = packed
        self.need = need
        self.hi = hi
        self.key = key
        self.programs: dict = {}        # jax_acc's jitted pack / unpack

    def index_args(self, which: str, sharding=None) -> tuple:
        """Device arrays the traced ``which`` (``"pack"`` or ``"unpack"``)
        takes after the buffer (an index list is an argument, not a
        constant of the program)."""
        return ()

    def _flat(self, x, length: Optional[int] = None):
        if np.dtype(x.dtype) != self.dtype:
            raise MpiError(
                ErrorClass.ERR_TYPE,
                f"device buffer of {x.dtype} under a datatype of "
                f"{self.dtype}: a pack moves bits, convert first")
        flat = x.reshape(-1)
        have = flat.shape[0]
        if have < self.need if length is None else have != length:
            raise MpiError(
                ErrorClass.ERR_TRUNCATE,
                f"buffer of {have} elements where the datatype and count "
                f"need {self.need if length is None else length}")
        return flat


def _rect(start: int, length: int, sizes: list):
    """A run of ``length`` indices from ``start`` along one axis that has
    been split into ``sizes`` (most significant first): the (start, size)
    of each part if the run is a box of them (a stretch of one part, the
    parts after it whole, the parts before it fixed), else None."""
    whole = 1
    for q in reversed(range(len(sizes))):
        if start % whole == 0 and length % whole == 0:
            lo, n = start // whole, length // whole
            if q:
                lo %= sizes[q]
            if q == 0 or lo + n <= sizes[q]:
                fixed, rest = [], start // (whole * sizes[q]) if q else 0
                for c in reversed(sizes[1:q]):
                    fixed.append((rest % c, 1))
                    rest //= c
                if q:
                    fixed.append((rest, 1))
                return (fixed[::-1] + [(lo, n)]
                        + [(0, c) for c in sizes[q + 1:]])
        whole *= sizes[q]
    return None


class RegularPlan(Plan):
    form = "regular"

    def __init__(self, dtype, packed, need, hi, strides, starts, sizes,
                 perm) -> None:
        # the nest sorted by stride, largest first: axis k walks ``sizes[k]``
        # steps of ``strides[k]`` from ``starts[k]``; the last entry of
        # starts and sizes is the block's (stride 1).  ``perm`` takes the
        # axes to pack order.
        self.strides = tuple(strides)
        self.starts, self.sizes = tuple(starts), tuple(sizes)
        self.perm = tuple(perm)
        super().__init__(dtype, packed, need, hi,
                         ("regular", np.dtype(dtype).str, hi, self.strides,
                          self.starts, self.sizes, self.perm))

    def _layout(self, shape, length: int):
        """The buffer as an array in which the nest is one box: ``(view
        shape, starts, sizes, axes of each nest axis)``.  The view's axes
        are cut at the nest's strides and, where ``shape`` is given, at
        the buffer's own too, so that a buffer which already has the
        nest's shape is sliced as it stands and never reshaped (a reshape
        that merges or splits tiled dimensions is a copy of the whole
        buffer on a TPU).  None where the two sets of strides do not make
        one array."""
        cuts = set(self.strides)
        if shape is not None:
            cuts |= {int(np.prod(shape[i:])) for i in range(1, len(shape))}
        cuts.discard(1)
        chain = sorted(cuts, reverse=True)
        if any(a % b for a, b in zip(chain, chain[1:])):
            return None
        if not chain:
            return (length,), [self.starts[-1]], [self.sizes[-1]], [[0]]
        rows = -(-length // chain[0])
        view = [rows] + [a // b for a, b in zip(chain, chain[1:] + [1])]
        edges = (float("inf"),) + self.strides + (1,)
        starts, sizes, groups = [], [], []
        for k in range(len(self.sizes)):
            axes = [i for i, u in enumerate(chain + [1])
                    if edges[k + 1] <= u < edges[k]]
            box = _rect(self.starts[k], self.sizes[k],
                        [view[i] for i in axes])
            if box is None:
                return None
            starts += [lo for lo, _ in box]
            sizes += [n for _, n in box]
            groups.append(axes)
        return tuple(view), starts, sizes, groups

    def _viewed(self, x, flat):
        """The buffer ``x`` (``flat`` when raveled) as the view of
        :meth:`_layout`, tried with its own shape first; padded where the
        view's last row is short."""
        import jax.numpy as jnp

        length = flat.shape[0]
        layout = (x.ndim > 1 and self._layout(x.shape, length)) \
            or self._layout(None, length)
        view = layout[0]
        if view == tuple(x.shape):
            return x, layout
        total = int(np.prod(view))
        if total > length:      # the last row is cut short: pad it
            flat = jnp.pad(flat, (0, total - length))
        return flat.reshape(view), layout

    def _order(self, groups):
        return [i for k in self.perm for i in groups[k]]

    def _blocks(self, length: int):
        """``(rows, cols, b)`` where the plan is the transpose of a whole
        rows x cols matrix of blocks of b that ``ops/pallas_ddt`` takes
        (XLA would pad a minor dimension of b to 128 lanes), else None."""
        from ompi_tpu.ops import pallas_ddt

        if len(self.sizes) != 3 or self.perm != (1, 0, 2) \
                or any(self.starts):
            return None
        rows, cols, b = self.sizes
        if self.strides != (cols * b, b) or length != rows * cols * b \
                or not pallas_ddt.supported(rows, cols, b, self.dtype):
            return None
        return rows, cols, b

    def pack(self, x):
        from jax import lax

        flat = self._flat(x)
        blocks = self._blocks(flat.shape[0])
        if blocks is not None:
            from ompi_tpu.ops import pallas_ddt

            rows, cols, b = blocks
            return pallas_ddt.transpose_blocks(
                x if x.shape == (rows, cols * b) else flat, rows, cols, b)
        view, (_, starts, sizes, groups) = self._viewed(x, flat)
        part = lax.slice(view, starts,
                         [a + n for a, n in zip(starts, sizes)])
        return part.transpose(self._order(groups)).reshape(-1)

    def unpack(self, packed, into=None):
        import jax.numpy as jnp
        from jax import lax

        packed = self._flat(packed, self.packed)
        length = self.hi if into is None else int(np.prod(into.shape))
        blocks = self._blocks(length)
        if blocks is not None:      # covers its extent; its own inverse
            from ompi_tpu.ops import pallas_ddt

            rows, cols, b = blocks
            out = pallas_ddt.transpose_blocks(packed, cols, rows, b)
            return out if into is None else out.reshape(into.shape)
        if into is None:
            into = jnp.zeros((self.hi,), self.dtype)
        shape = into.shape
        view, (_, starts, sizes, groups) = self._viewed(into,
                                                        self._flat(into))
        order = self._order(groups)
        part = packed.reshape([sizes[i] for i in order]) \
            .transpose([int(i) for i in np.argsort(order)])
        out = lax.dynamic_update_slice(view, part, starts)
        if out.shape == shape:
            return out
        return out.reshape(-1)[:length].reshape(shape)


# What decides how an index list packs.  XLA:TPU's gather costs 19-21 ns
# an index sorted or not, by slice or by element (12,582,912 elements in
# 239.7-270.9 ms on a v5e, PR 27 chip runs; 267.5 ms, PR 30), so a row of
# 128 packed elements costs it about 2.5 us.  The streaming kernel pays
# for a row about 4 ns a slab of 8 source rows (98,304 rows: 1.76 ms at 2
# slabs, 2.17 at 3, 8 rows a pass; 1.10 ms at 2 slabs and 32 rows a pass:
# PR 30 chip runs) and 0.7 ns a 128-lane row it streams past.  At
# STREAM_SLABS a row is still under 0.1 us where the gather's is 2.5; the
# limit is where the unrolled kernel body stops being small, not where
# the gather would win.
STREAM_SLABS = 16       # slabs of 8 source rows one output row may span
STREAM_TILE = 256       # output rows a grid step, halved until a step's
STREAM_WINDOW = 4096    # source rows fit (2 MiB, held twice in VMEM)


def _stream_rows(length: int, height: int) -> int:
    """The 128-lane rows the streaming kernel sees of a buffer of
    ``length`` elements: whole (8, 128) tiles, at least one window."""
    return max(-(-length // 1024) * 8, height)


class IndexPlan(Plan):
    form = "index"

    def __init__(self, dtype, need, hi, starts: np.ndarray,
                 block: int) -> None:
        self.index = np.ascontiguousarray(starts, np.int32)
        self.block = block
        gaps = np.diff(starts)
        self.sorted = bool((gaps >= 0).all())
        self.unique = bool(self.sorted and (gaps >= block).all())
        self._device: dict = {}
        super().__init__(dtype, len(starts) * block, need, hi,
                         ("index", next(_serial)))
        self.stream = self._stream_tables()
        if self.stream is not None:
            spc.record("device_ddt_stream_plans")

    def _stream_tables(self):
        """``((windows, bases, table), {tile, height, slabs})``, the
        arrays and the keywords of ``ops/pallas_ddt.compact``, where the
        list streams (the module docstring says when), else None.
        Whole-array numpy: nothing a block in Python."""
        if not self.unique or self.dtype.itemsize != 4:
            return None
        from ompi_tpu.ops.pallas_ddt import LANES

        out_rows = -(-self.packed // LANES)
        tile = STREAM_TILE
        while tile > 8 and tile // 2 >= out_rows:
            tile //= 2          # a short list: one step, no larger than it
        # every packed element's source, in whole steps of output rows
        # (the last element repeated to the end)
        table = np.empty(-(-out_rows // tile) * tile * LANES, np.int32)
        np.add(self.index[:, None], np.arange(self.block, dtype=np.int32),
               out=table[:self.packed].reshape(-1, self.block))
        table[self.packed:] = table[self.packed - 1]
        table = table.reshape(-1, LANES)
        # source rows of each output row's first and last element
        first, last = table[:, 0] >> 7, table[:, -1] >> 7
        slabs = int((last - first).max()) // 8 + 1
        if slabs > STREAM_SLABS:
            return None

        def span(tile):         # each step's first row, the tallest window
            windows = first[::tile] & ~7
            return windows, (int((last[tile - 1::tile] - windows).max())
                             + 8) & ~7

        windows, height = span(tile)
        while height > STREAM_WINDOW:   # ends: 8 rows of 16 slabs are 1032
            tile //= 2
            windows, height = span(tile)
        # the buffer as the kernel sees it: whole (8, 128) tiles, at least
        # one window; the last windows start early enough to end inside
        # it, and a row's slabs inside its window
        windows = np.minimum(windows,
                             _stream_rows(self.need, height) - height)
        origin = np.repeat(windows, tile)
        bases = np.minimum(first - origin, height - 8 * slabs)
        table -= (origin + bases)[:, None] << 7
        return (windows, bases, table), dict(tile=tile, height=height,
                                             slabs=slabs)

    def index_args(self, which: str, sharding=None) -> tuple:
        """A streamed pack takes its three tables, everything else the
        index list; each on the device once a placement, from the first
        call that needs it."""
        import jax

        stream = which == "pack" and self.stream is not None
        args = self._device.get((stream, sharding))
        if args is None:
            host = self.stream[0] if stream else (self.index,)
            args = self._device[stream, sharding] = tuple(
                jax.device_put(a, sharding) for a in host)
        return args

    def _dnums(self):
        from jax import lax

        return (lax.GatherDimensionNumbers(
                    offset_dims=(1,), collapsed_slice_dims=(),
                    start_index_map=(0,)),
                lax.ScatterDimensionNumbers(
                    update_window_dims=(1,), inserted_window_dims=(),
                    scatter_dims_to_operand_dims=(0,)))

    def pack(self, x, *tables):
        flat = self._flat(x)
        if self.stream is not None:
            import jax.numpy as jnp

            from ompi_tpu.ops.pallas_ddt import LANES, compact

            geometry = self.stream[1]
            rows = _stream_rows(flat.shape[0], geometry["height"])
            if rows * LANES != flat.shape[0]:   # not whole tiles: a copy
                flat = jnp.pad(flat, (0, rows * LANES - flat.shape[0]))
            out = compact(flat.reshape(1, rows, LANES), *tables,
                          **geometry).reshape(-1)
            return out if out.shape[0] == self.packed else out[:self.packed]
        from jax import lax

        out = lax.gather(
            flat, tables[0][:, None], self._dnums()[0],
            slice_sizes=(self.block,), indices_are_sorted=self.sorted,
            unique_indices=self.unique,
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return out.reshape(-1)

    def unpack(self, packed, index, into=None):
        import jax.numpy as jnp
        from jax import lax

        packed = self._flat(packed, self.packed)
        flat = jnp.zeros((self.hi,), self.dtype) if into is None \
            else self._flat(into)
        out = lax.scatter(
            flat, index[:, None], packed.reshape(-1, self.block),
            self._dnums()[1], indices_are_sorted=self.sorted,
            unique_indices=self.unique,
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        return out if into is None else out.reshape(into.shape)


def _regular_plan(dtype, base, dims, block, packed, need, hi):
    """The regular plan for a normalised nest, or None where the strides
    do not make an array view (they do not divide one another, two are
    equal, one is not positive, or a block would straddle a row)."""
    order = sorted(range(len(dims)), key=lambda i: -dims[i][1])
    sizes = [dims[i][0] for i in order] + [block]
    strides = [dims[i][1] for i in order]
    if strides and (strides[-1] <= 0 or any(
            a % b or a == b for a, b in zip(strides, strides[1:]))):
        return None
    starts, rest = [], base
    for s in strides:
        starts.append(rest // s)
        rest %= s
    starts.append(rest)
    inner = [a // b for a, b in zip(strides, strides[1:] + [1])]
    if any(a + n > f for a, n, f in zip(starts[1:], sizes[1:], inner)):
        return None
    # pack order: the dims as the type map walks them, then the block
    position = {axis: k for k, axis in enumerate(order)}
    perm = [position[i] for i in range(len(dims))] + [len(dims)]
    return RegularPlan(dtype, packed, need, hi, strides, starts, sizes,
                       perm)


def _build(datatype, count: int) -> Plan:
    runs = datatype.runs
    if runs is None:
        if datatype.size == 0:
            raise MpiError(ErrorClass.ERR_COUNT,
                           "an empty datatype packs nothing on a device")
        raise MpiError(
            ErrorClass.ERR_TYPE,
            "a datatype of more than one elementary type has no device "
            "plan: a jax.Array holds one dtype.  Pack each field's type "
            "on its own, or stage to the host (Convertor on a numpy "
            "buffer)")
    if count < 1:
        raise MpiError(ErrorClass.ERR_COUNT,
                       f"count {count}: nothing to pack on a device")
    offs, counts, dtype = runs
    item = dtype.itemsize
    extent = datatype.extent
    if (offs % item).any() or (count > 1 and extent % item):
        raise MpiError(
            ErrorClass.ERR_TYPE,
            f"type map not aligned to its {item}-byte elements: a device "
            "buffer is addressed by element")
    if datatype.true_lb < 0 or (count > 1 and extent < 0):
        raise MpiError(ErrorClass.ERR_BUFFER,
                       "type map reaches below the buffer's first byte")
    offs = offs // item
    extent //= item
    packed = count * datatype.size // item
    need = (count - 1) * extent + datatype.true_ub // item
    hi = max(need, (datatype.lb + count * datatype.extent) // item)
    if hi >= 1 << 31:
        raise MpiError(ErrorClass.ERR_COUNT,
                       "described buffer of 2**31 elements or more")
    block = int(np.gcd.reduce(counts))
    if (counts == block).all():
        nest = _nest(offs)
        if nest is not None:
            base, dims = nest
            dims, block_n = _normalise([(count, extent)] + dims, block)
            plan = _regular_plan(dtype, base, dims, block_n, packed, need,
                                 hi)
            if plan is not None:
                with _lock:
                    return _shared.setdefault(plan.key, plan)
        starts = offs
    else:       # runs of several lengths (neighbours merged): cut them
        # into blocks of their common divisor, one index a block
        per = counts // block
        starts = np.repeat(offs, per) + block * ramp(per)
    if count > 1:
        starts = (np.arange(count, dtype=np.int64)[:, None] * extent
                  + starts).reshape(-1)
    spc.record("device_ddt_index_plans")
    return IndexPlan(dtype, need, hi, starts, block)


def plan_for(datatype, count: int = 1) -> Plan:
    """The device plan of ``count`` elements of ``datatype``, built on
    first use and kept on the datatype."""
    plans = datatype.__dict__.get("_device_plans")
    if plans is None:
        plans = datatype.__dict__.setdefault("_device_plans", {})
    plan = plans.get(count)
    if plan is None:
        spc.record("device_ddt_plan_builds")
        trace.bind_profiler()
        trace.bind_builds()
        if trace.profiler_on():
            with trace.profiler_span("otpu.ddt.plan", count=count,
                                     nseg=datatype.nseg):
                plan = _build(datatype, count)
        else:
            plan = _build(datatype, count)
        plans[count] = plan
    return plan
