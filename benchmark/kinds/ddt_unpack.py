"""Call kind ``ddt_unpack``: ``datatype.unpack_array(packed, count,
datatype)`` with no ``into``: a packed stream in HBM into a new described
buffer, zero outside the type map.  The input is the packed stream.  No
collective.  Its twin is the ``jnp`` a user would write by hand.

``bytes`` is the packed size; bytes moved 2 x ``bytes``, as ``ddt_pack``."""
from harness import ddtkit

ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 0


def input_shape(point, n):
    return (ddtkit.packed_elems(point),)


def input_sharding(env):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(env.devices[0])


def bind(env, point, template):
    from ompi_tpu import datatype as dt

    dtype, count = ddtkit.datatype(point)
    return (lambda packed: dt.unpack_array(packed, count, dtype)), 0


def bind_raw(env, point, template):
    import jax

    fn, extra = ddtkit.manual_unpack(point)
    prog = jax.jit(fn)
    return (lambda x: prog(x, *extra)) if extra else prog


def reference(point, n, x):
    return ddtkit.unpack_reference(point, x)


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return 2 * point["bytes"]
