"""What the collective call kinds share: element counts from a point's
bytes, the raw ``jit(shard_map(...))`` twin on the program's own mesh, and
the bus-bandwidth arithmetic.

Bus bandwidth is NVIDIA nccl-tests' (doc/PERFORMANCE.md): the factor
times S over the time, where S, the size nccl-tests prints, is

* allreduce: the bytes of one rank's buffer; factor 2(n-1)/n;
* allgather: the bytes every rank ends with, n times what it sent;
  factor (n-1)/n;
* reduce_scatter: the bytes one rank puts in, n times what it gets
  back; factor (n-1)/n;
* alltoall: the bytes one rank puts in (n blocks); factor (n-1)/n;
* broadcast: the bytes of the message; factor 1.

A point's ``bytes`` is S.  (The old ``bench.py``, deleted in PR 29, gave
broadcast (n-1)/n; nccl-tests gives it 1, and the benchmark follows
nccl-tests.)
"""
from __future__ import annotations

import numpy as np

BUS_FACTOR = {
    "allreduce": lambda n: 2.0 * (n - 1) / n,
    "allgather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "alltoall": lambda n: (n - 1) / n,
    "bcast": lambda n: 1.0,
}


def elems(point: dict, parts: int = 1) -> int:
    """Elements of ``point['dtype']`` in one of ``parts`` equal parts
    of the point's S bytes."""
    item = np.dtype(point["dtype"]).itemsize
    count, rest = divmod(point["bytes"], item * parts)
    if rest or count < 1:
        raise ValueError(f"point {point['name']}: {point['bytes']} bytes do "
                         f"not split into {parts} parts of {point['dtype']}")
    return count


def bus_bytes(coll: str, point: dict, n: int) -> float:
    """Bus factor times S: the bytes nccl-tests' bus bandwidth counts
    for one call.  0 on one rank, where nothing crosses a link."""
    return BUS_FACTOR[coll](n) * point["bytes"] if n > 1 else 0.0


def op_of(point: dict):
    from ompi_tpu.api import op as op_mod

    return op_mod.BUILTIN_OPS[point.get("op", "SUM")]


def raw_program(env, body, replicated_out: bool):
    """``jit(shard_map(body))`` on the program's own mesh and axis, rows
    in, and out either replicated or one row a rank: the twin a user
    would write by hand, next to which the framework's call is timed."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(
        body, mesh=env.mesh, in_specs=P(env.axis),
        out_specs=P() if replicated_out else P(env.axis),
        check_vma=False))


def jnp_reduce(op_name: str):
    """The plain ``jnp`` reduction along axis 0 for an op: what the raw
    twins use where the op has no native collective."""
    import jax.numpy as jnp

    return {"SUM": jnp.sum, "PROD": jnp.prod, "MAX": jnp.max,
            "MIN": jnp.min, "BAND": jnp.bitwise_and.reduce,
            "BOR": jnp.bitwise_or.reduce,
            "BXOR": jnp.bitwise_xor.reduce}[op_name]


NUMPY_REDUCE = {
    "SUM": lambda x: x.sum(axis=0, dtype=x.dtype),
    "PROD": lambda x: x.prod(axis=0, dtype=x.dtype),
    "MAX": lambda x: x.max(axis=0),
    "MIN": lambda x: x.min(axis=0),
    "BAND": np.bitwise_and.reduce,
    "BOR": np.bitwise_or.reduce,
    "BXOR": np.bitwise_xor.reduce,
}
