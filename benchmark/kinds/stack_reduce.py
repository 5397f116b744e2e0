"""Call kind ``stack_reduce``: the function ``api/op.jax_stack_reduce(op,
dtype)`` returns, which is what ``mca/op`` selects on these devices (on a
TPU ``ops/pallas_reduce.reduce_stack``, already jitted), on a stack of
``rows`` rows of S bytes each, as a gather over ``rows`` ranks hands it
over.  No collective: one kernel on one chip.  Its twin is the plain
jitted ``jnp`` reduction of the same stack.

Bytes moved, from the shapes: (rows + 1) x S, every row read once and one
row written.  The kernel's own padding and its ``ravel()[:per]`` copy are
not counted, so they show as a lower roofline share."""
import numpy as np

from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 0


def input_shape(point, n):
    return (point["rows"], collkit.elems(point))


def input_sharding(env):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(env.devices[0])


def bind(env, point, template):
    from ompi_tpu.api import op as op_mod

    op = collkit.op_of(point)
    fn = op_mod.jax_stack_reduce(op, np.dtype(point["dtype"]))
    if fn is None:
        raise RuntimeError(f"mca/op selects no stack reduction for "
                           f"{op.name} {point['dtype']} on these devices")
    return fn, 0


def bind_raw(env, point, template):
    import jax

    reduce = collkit.jnp_reduce(point.get("op", "SUM"))
    return jax.jit(lambda x: reduce(x, axis=0))


def reference(point, n, x):
    return collkit.NUMPY_REDUCE[point.get("op", "SUM")](x)


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return (point["rows"] + 1) * point["bytes"]
