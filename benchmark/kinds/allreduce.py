"""Call kind ``allreduce``: ``world.allreduce_array(x, op)`` on a buffer
of S bytes a rank, through the normal entry.  The op is the point's
(SUM has a native collective; PROD and the bitwise ops gather and then
run the fold ``mca/op`` selects)."""
import functools

from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 1


def input_shape(point, n):
    return (n, collkit.elems(point))


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    """(the callable one call goes through, collectives binding cost)"""
    op = collkit.op_of(point)
    if op.name == "SUM":
        return env.world.allreduce_array, 0
    return functools.partial(env.world.allreduce_array, op=op), 0


def bind_raw(env, point, template):
    """What a user would write by hand: ``lax.psum`` in a ``shard_map``;
    for an op with no native collective, ``all_gather`` and the plain
    ``jnp`` reduction."""
    import jax

    op = collkit.op_of(point)
    ax = env.axis
    if op.jax_reduce == "psum":
        return collkit.raw_program(env, lambda t: jax.lax.psum(t[0], ax),
                                   replicated_out=True)
    reduce = collkit.jnp_reduce(op.name)
    return collkit.raw_program(
        env, lambda t: reduce(jax.lax.all_gather(t[0], ax), axis=0),
        replicated_out=True)


def reference(point, n, x):
    return collkit.NUMPY_REDUCE[point.get("op", "SUM")](x)


def bus_bytes(point, n):
    return collkit.bus_bytes("allreduce", point, n)


def moved_bytes(point, n):
    return 0
