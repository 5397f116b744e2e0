"""Call kind ``reduce_scatter``: ``world.reduce_scatter_array(x)``, op
SUM.  S is what one rank puts in, n blocks of S/n; it gets one back."""
from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 1


def input_shape(point, n):
    return (n, n, collkit.elems(point, n))


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    if point.get("op", "SUM") != "SUM":
        raise ValueError("kind reduce_scatter measures op SUM only")
    return env.world.reduce_scatter_array, 0


def bind_raw(env, point, template):
    import jax

    return collkit.raw_program(
        env, lambda t: jax.lax.psum_scatter(
            t[0], env.axis, scatter_dimension=0, tiled=False)[None],
        replicated_out=False)


def reference(point, n, x):
    return x.sum(axis=0, dtype=x.dtype)


def bus_bytes(point, n):
    return collkit.bus_bytes("reduce_scatter", point, n)


def moved_bytes(point, n):
    return 0
