"""Call kind ``allgather``: ``world.allgather_array(x)``.  S is what
every rank ends with; a rank sends S/n."""
from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 1


def input_shape(point, n):
    return (n, collkit.elems(point, n))


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    return env.world.allgather_array, 0


def bind_raw(env, point, template):
    import jax

    return collkit.raw_program(
        env, lambda t: jax.lax.all_gather(t[0], env.axis),
        replicated_out=True)


def reference(point, n, x):
    return x.copy()


def bus_bytes(point, n):
    return collkit.bus_bytes("allgather", point, n)


def moved_bytes(point, n):
    return 0
