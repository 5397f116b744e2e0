"""Call kind ``ddt_to_self``: ``to_self.c``'s exchange on the program's
typed slots, the pattern as ``sendtype`` and a contiguous ``recvtype``:
every rank sends its described buffer to itself and receives the packed
stream.  Pack, collective and unpack are one program.

* pattern ``fft2``: ``world.alltoall_array(x, sendtype=t, count=n)`` on
  x of (ranks, ranks, n, 2n), as the transpose of a distributed 2-D FFT
  goes;
* a face: ``world.ppermute_array(x, ((0, 0), ...), sendtype=t)`` on x of
  (ranks, g, g, g), every rank to itself.

The twin packs by hand-written ``jnp`` slicing around the same collective
in ``jit(shard_map(...))``.  ``bytes`` is one rank's packed size; bytes
moved 2 x ``bytes`` a rank; nothing crosses a link."""
import numpy as np

from harness import collkit, ddtkit

ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 1


def _blocks(point, n):
    return (n, n) if point["pattern"] == "fft2" else (n,)


def input_shape(point, n):
    return _blocks(point, n) + ddtkit.shape(point)


def input_sharding(env):
    return env.rank_sharding


def bind(env, point, template):
    dtype, count = ddtkit.datatype(point)
    world = env.world
    if point["pattern"] == "fft2":
        return (lambda x: world.alltoall_array(x, sendtype=dtype,
                                               count=count)), 0
    perm = tuple((i, i) for i in range(env.n))
    return (lambda x: world.ppermute_array(x, perm, sendtype=dtype,
                                           count=count)), 0


def bind_raw(env, point, template):
    import jax
    import jax.numpy as jnp

    fn, extra = ddtkit.manual_pack(point)
    if extra:
        raise ValueError(f"point {point['name']}: no twin for an index list")
    axis, n = env.axis, env.n
    if point["pattern"] == "fft2":
        def body(t):    # (1, n, N, 2N)
            packed = jax.vmap(jax.vmap(fn))(t)
            y = jax.lax.all_to_all(packed, axis, split_axis=1, concat_axis=0)
            return jnp.swapaxes(y, 0, 1)
    else:
        perm = tuple((i, i) for i in range(n))

        def body(t):    # (1, g, g, g)
            return jax.lax.ppermute(jax.vmap(fn)(t), axis, perm)
    return collkit.raw_program(env, body, replicated_out=False)


def reference(point, n, x):
    if point["pattern"] == "fft2":      # out[i, j] is what j sent to i
        return np.stack([np.stack([ddtkit.pack_reference(point, x[j, i])
                                   for j in range(n)]) for i in range(n)])
    return np.stack([ddtkit.pack_reference(point, x[i]) for i in range(n)])


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return 2 * point["bytes"]
