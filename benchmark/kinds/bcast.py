"""Call kind ``bcast``: ``world.bcast_array(x, root=n-1)``, a message of
S bytes from the last rank.  Under ``bcast_sa_min_bytes`` the program is
a binomial ``ppermute`` tree, from there on scatter + allgather."""
import functools

from harness import collkit

ELEMENTWISE_LAST_AXIS = True
COLLECTIVES_PER_CALL = 1


def input_shape(point, n):
    return (n, collkit.elems(point))


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    return functools.partial(env.world.bcast_array, root=env.n - 1), 0


def bind_raw(env, point, template):
    """The same two regimes written by hand (a twin of another shape
    would make the ratio say nothing about dispatch), with the program's
    own threshold."""
    import jax
    import jax.numpy as jnp

    n, ax, root = env.n, env.axis, env.n - 1
    scatter_allgather = point["bytes"] >= env.module.bcast_sa_min_bytes

    def body_tree(t):  # (1, *S)
        rel = (jax.lax.axis_index(ax) - root) % n
        cur, k = t, 1
        while k < n:
            perm = [((root + i) % n, (root + i + k) % n)
                    for i in range(min(k, n - k))]
            recvd = jax.lax.ppermute(cur, ax, perm)
            cur = jnp.where((rel >= k) & (rel < 2 * k), recvd, cur)
            k *= 2
        return cur

    def body_sa(t):  # (1, *S)
        me = jax.lax.axis_index(ax)
        flat = jnp.where(me == root, t[0], jnp.zeros_like(t[0])).reshape(-1)
        size = flat.shape[0]
        blk = -(-size // n)
        if blk * n != size:
            flat = jnp.pad(flat, (0, blk * n - size))
        part = jax.lax.psum_scatter(flat.reshape(n, blk), ax,
                                    scatter_dimension=0, tiled=False)
        full = jax.lax.all_gather(part, ax)
        return full.reshape(-1)[:size].reshape(t.shape)

    return collkit.raw_program(
        env, body_sa if scatter_allgather else body_tree,
        replicated_out=False)


def reference(point, n, x):
    import numpy as np

    return np.broadcast_to(x[n - 1], x.shape).copy()


def bus_bytes(point, n):
    return collkit.bus_bytes("bcast", point, n)


def moved_bytes(point, n):
    return 0
