"""Datatype engine: described-layout memory + stateful pack/unpack convertor.

TPU-native re-design of the reference datatype stack
(``/root/reference/opal/datatype/`` — 8,249 LoC — and ``ompi/datatype/``):
MPI named types and the full constructor set build a *type map* that is
flattened and coalesced into elementary segments
(``opal_datatype_optimize.c`` equivalent); the :class:`Convertor` is the
stateful pack/unpack iterator with partial-buffer resume and repositioning
(``opal_convertor.c`` — 780 lines; ``opal_datatype_pack.c`` state machine),
plus heterogeneous/external32 conversion and checksums.  TPU-first additions:
``bfloat16``/``float16`` as first-class named types, and a device path (the
analog of ``CONVERTOR_CUDA``, ``opal_convertor.h:50-57``): a convertor bound
to a ``jax.Array`` packs and unpacks on the device through the datatype's
device plan (:mod:`ompi_tpu.datatype.plan`), behind :func:`pack_array` and
:func:`unpack_array`.
"""
import numpy as np

from ompi_tpu.datatype.core import (  # noqa: F401
    Datatype,
    BYTE,
    PACKED,
    BOOL,
    INT8,
    INT16,
    INT32,
    INT64,
    UINT8,
    UINT16,
    UINT32,
    UINT64,
    FLOAT16,
    BFLOAT16,
    FLOAT32,
    FLOAT64,
    COMPLEX64,
    COMPLEX128,
    FLOAT_INT,
    DOUBLE_INT,
    LONG_INT,
    SHORT_INT,
    TWO_INT,
    NAMED_TYPES,
    from_numpy_dtype,
    contiguous,
    vector,
    hvector,
    indexed,
    hindexed,
    hindexed_block,
    indexed_block,
    create_struct,
    subarray,
    darray,
    resized,
    ORDER_C,
    ORDER_FORTRAN,
    DISTRIBUTE_BLOCK,
    DISTRIBUTE_CYCLIC,
    DISTRIBUTE_NONE,
    DISTRIBUTE_DFLT_DARG,
)
from ompi_tpu.datatype.convertor import Convertor, ConvertorFlags  # noqa: F401


def _host_only(buf, entry: str) -> None:
    from ompi_tpu.datatype.convertor import _is_device

    if not isinstance(buf, np.ndarray) and _is_device(buf):
        raise TypeError(
            f"datatype.{entry} returns bytes and writes host memory in "
            f"place, which a device array cannot be: use datatype."
            f"{entry}_array (a jax.Array in, a jax.Array out)")


def pack_array(x, count, datatype):
    """``MPI_Pack`` of a device buffer: ``count`` elements of ``datatype``
    out of the ``jax.Array`` ``x`` (any shape; the type map addresses its
    elements in C order from the first), as a 1-D ``jax.Array`` of the
    datatype's elementary dtype.  No host copy, one program a call.

    The datatype must hold one elementary type, which is ``x``'s dtype
    (a pack moves bits).  A ``create_struct`` of different types raises
    ``MpiError``: a ``jax.Array`` has one dtype, and a byte stream on the
    device would cost a relayout pass a field."""
    return Convertor(datatype, count, x).pack()


def unpack_array(packed, count, datatype, into=None):
    """``MPI_Unpack`` on the device, functional since a ``jax.Array``
    cannot be written in place: returns ``into`` (any shape) with exactly
    the type map's elements replaced by the packed stream and every other
    element kept (a caller that jits around this may donate ``into``),
    or, with no ``into``, a new 1-D buffer spanning ``count`` extents,
    zero outside the type map."""
    return Convertor(datatype, count, into,
                     flags=ConvertorFlags.DEVICE).unpack(packed)


def pack(buf, count, datatype, external32: bool = False) -> bytes:
    """``MPI_Pack`` (/ ``MPI_Pack_external``): described memory → a
    contiguous byte stream, via the convertor (``ompi/mpi/c/pack.c``).
    Host buffers; a device buffer goes through :func:`pack_array`."""
    _host_only(buf, "pack")
    flags = ConvertorFlags.EXTERNAL32 if external32 else ConvertorFlags.NONE
    # user-facing MPI_Pack keeps the documented bytes contract; the hot
    # path (pml/btl) consumes the convertor's zero-extra-copy array form
    return Convertor(datatype, count, buf, flags=flags).pack().tobytes()


def unpack(data, buf, count, datatype, external32: bool = False) -> int:
    """``MPI_Unpack``: byte stream → described memory; returns the bytes
    consumed.  Host buffers; a device buffer goes through
    :func:`unpack_array`."""
    _host_only(buf, "unpack")
    _host_only(data, "unpack")
    flags = ConvertorFlags.EXTERNAL32 if external32 else ConvertorFlags.NONE
    return Convertor(datatype, count, buf, flags=flags).unpack(data)


def pack_size(count, datatype, external32: bool = False) -> int:
    """``MPI_Pack_size``: an upper bound on pack()'s output size."""
    return count * datatype.size


def reduce_local(inbuf, inoutbuf, op) -> None:
    """``MPI_Reduce_local``: inoutbuf = inbuf (op) inoutbuf — the op
    kernel applied locally (``ompi/mpi/c/reduce_local.c``; kernel table
    ≅ ``ompi/mca/op``)."""
    op(inbuf, inoutbuf)
