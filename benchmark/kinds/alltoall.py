"""Call kind ``alltoall``: ``world.alltoall_array(x)``.  S is what one
rank puts in, n blocks of S/n, one for each rank."""
from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 1


def input_shape(point, n):
    return (n, n, collkit.elems(point, n))


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    return env.world.alltoall_array, 0


def bind_raw(env, point, template):
    import jax
    import jax.numpy as jnp

    def body(t):  # (1, n, *S)
        y = jax.lax.all_to_all(t, env.axis, split_axis=1, concat_axis=0)
        return jnp.swapaxes(y, 0, 1)

    return collkit.raw_program(env, body, replicated_out=False)


def reference(point, n, x):
    import numpy as np

    return np.swapaxes(x, 0, 1).copy()


def bus_bytes(point, n):
    return collkit.bus_bytes("alltoall", point, n)


def moved_bytes(point, n):
    return 0
