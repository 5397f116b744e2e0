"""Call kind ``ddt_pack``: ``datatype.pack_array(x, count, datatype)`` on a
buffer in HBM: the access pattern of the point (``harness/ddtkit``) out of
the described buffer, as a packed stream.  No collective: one program on
one chip.  Its twin is the ``jnp`` slicing a user would write by hand.

``bytes`` is the packed size.  Bytes moved: 2 x ``bytes``, the stream read
once and written once; whatever else a program reads (whole tiles for one
lane) or writes (a second pass) shows as a lower rate."""
from harness import ddtkit

ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 0


def input_shape(point, n):
    return ddtkit.shape(point)


def input_sharding(env):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(env.devices[0])


def bind(env, point, template):
    from ompi_tpu import datatype as dt

    dtype, count = ddtkit.datatype(point)
    return (lambda x: dt.pack_array(x, count, dtype)), 0


def bind_raw(env, point, template):
    import jax

    fn, extra = ddtkit.manual_pack(point)
    prog = jax.jit(fn)
    return (lambda x: prog(x, *extra)) if extra else prog


def reference(point, n, x):
    return ddtkit.pack_reference(point, x)


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return 2 * point["bytes"]
