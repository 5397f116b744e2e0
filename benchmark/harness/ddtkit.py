"""What the derived-datatype call kinds share: a point's access pattern as
an MPI datatype (built by the program's own constructors, as a user
would), the shape of the buffer it describes, and two things written
independently of the program's datatype engine: the plain numpy indexing
that a pattern's published definition gives (the reference), and the
``jnp`` slicing a user would write by hand (ddtbench's "manual pack", the
raw twin).

Patterns (ddtbench, Schneider et al., EuroMPI 2012; C order, the last
axis fastest, which is ddtbench's Fortran ``x``):

* ``mg_x`` / ``mg_y`` / ``mg_z``: the interior face of a cubic grid of
  ``grid`` points a side with one ghost layer, normal to the last, the
  middle and the first axis: ``subarray`` of (g-2, g-2, 1), (g-2, 1, g-2),
  (1, g-2, g-2) at (1, 1, 1);
* ``fft2``: the transpose of an ``n`` x ``n`` matrix of 8-byte elements
  (two float32): ``n`` elements of ``resized(vector(n, 2, 2n, FLOAT), 0,
  8)``, one column each;
* ``lammps_atomic``: ``sent`` of ``atoms`` atoms, three float32 each, by
  an index list: ``indexed_block(3, 3 x ids, FLOAT)``.  The ids are part
  of the configuration, not data: drawn once from the point's name,
  sorted and distinct.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import data

FACES = {"mg_x": 2, "mg_y": 1, "mg_z": 0}      # the axis a face is normal to


@functools.lru_cache(maxsize=None)
def atom_ids(name: str, atoms: int, sent: int) -> np.ndarray:
    rng = np.random.default_rng(data.stable_hash(name))
    return np.sort(rng.choice(atoms, sent, replace=False))


def engine():
    """The program's datatype package, if it has a device path.  Asked
    before any input is drawn, so that a program without one (a parent
    of PR 27) leaves the cell at once and not after its pools."""
    from ompi_tpu import datatype as dt

    if not hasattr(dt, "pack_array"):
        raise SystemExit("benchmark: this program has no device datatype "
                         "engine (ompi_tpu.datatype.pack_array); the "
                         "derived-datatype kinds cannot run. Nothing was "
                         "run.")
    return dt


def shape(point: dict) -> tuple:
    """The described buffer of one rank."""
    engine()
    pat = point["pattern"]
    if pat in FACES:
        return (point["grid"],) * 3
    if pat == "fft2":
        return (point["n"], 2 * point["n"])
    if pat == "lammps_atomic":
        return (3 * point["atoms"],)
    raise ValueError(f"point {point['name']}: no pattern {pat!r}")


def packed_elems(point: dict) -> int:
    engine()
    pat = point["pattern"]
    if pat in FACES:
        return (point["grid"] - 2) ** 2
    if pat == "fft2":
        return 2 * point["n"] ** 2
    return 3 * point["sent"]


def datatype(point: dict):
    """(the committed datatype, count)."""
    dt = engine()
    pat = point["pattern"]
    if pat in FACES:
        g = point["grid"]
        sub = [g - 2] * 3
        sub[FACES[pat]] = 1
        return dt.subarray((g, g, g), sub, (1, 1, 1), dt.ORDER_C,
                           dt.FLOAT32).commit(), 1
    if pat == "fft2":
        n = point["n"]
        return dt.resized(dt.vector(n, 2, 2 * n, dt.FLOAT32), 0,
                          8).commit(), n
    ids = atom_ids(point["name"], point["atoms"], point["sent"])
    return dt.indexed_block(3, 3 * ids, dt.FLOAT32).commit(), 1


def _face(point: dict):
    index = [slice(1, -1)] * 3
    index[FACES[point["pattern"]]] = 1
    return tuple(index)


def pack_reference(point: dict, x: np.ndarray) -> np.ndarray:
    """The packed stream by numpy indexing, from the pattern's definition."""
    pat = point["pattern"]
    if pat in FACES:
        return x[_face(point)].ravel()
    if pat == "fft2":
        n = point["n"]
        return x.reshape(n, n, 2).transpose(1, 0, 2).ravel()
    ids = atom_ids(point["name"], point["atoms"], point["sent"])
    return x.reshape(-1, 3)[ids].ravel()


def unpack_reference(point: dict, packed: np.ndarray) -> np.ndarray:
    """The described buffer, zero outside the pattern, flat."""
    pat = point["pattern"]
    if pat == "fft2":       # covers its whole extent; its own inverse
        n = point["n"]
        return packed.reshape(n, n, 2).transpose(1, 0, 2).ravel()
    if pat in FACES:
        out = np.zeros(shape(point), packed.dtype)
        side = point["grid"] - 2
        out[_face(point)] = packed.reshape(side, side)
        return out.ravel()
    ids = atom_ids(point["name"], point["atoms"], point["sent"])
    out = np.zeros((int(ids[-1]) + 1, 3), packed.dtype)  # to the last atom
    out[ids] = packed.reshape(-1, 3)
    return out.ravel()


def manual_pack(point: dict):
    """``(fn, extra arguments)``: what a user would write in ``jnp`` for
    one buffer, ``fn(x, *extra)`` the packed stream.

    A face is a slice.  The transpose is NOT ``x.reshape(n, n, 2)
    .transpose(1, 0, 2)``: on a TPU a minor dimension of 2 is padded to
    128 lanes, and that program holds 8.1 GiB of temporaries at n = 4096
    and cannot be built at 8192 (PERF.md section 6, PR 27).  The twin is
    what a user who has met that writes next: whole-matrix transposes and
    row-strided slices only.  The atoms are NOT ``x.reshape(-1, 3)[ids]``
    either (a minor dimension of 3: 16.5 GB, refused by the compiler):
    the twin gathers by element."""
    pat = point["pattern"]
    if pat in FACES:
        index = _face(point)
        return (lambda x: x[index].reshape(-1)), ()
    import jax.numpy as jnp

    if pat == "fft2":
        n = point["n"]

        def transpose(x):
            t = x.reshape(n, 2 * n).T           # (2n, n): rows 2j + p
            planes = [t[p::2].T for p in range(2)]      # (n, n): x[i, 2j+p]
            rows = jnp.stack(planes, axis=1).reshape(2 * n, n)  # 2i + p
            return rows.T.reshape(-1)
        return transpose, ()
    ids = atom_ids(point["name"], point["atoms"], point["sent"])
    elems = jnp.asarray((3 * ids[:, None] + np.arange(3)).reshape(-1)
                        .astype(np.int32))
    return (lambda x, i: x[i]), (elems,)


def manual_unpack(point: dict):
    """The same for an unpack into zeros; only the transpose has one (it
    covers its extent and is its own inverse; the cell unpacks nothing
    else)."""
    if point["pattern"] != "fft2":
        raise ValueError(f"point {point['name']}: no hand-written unpack")
    return manual_pack(point)
