"""Call kind ``pallreduce``: one epoch of the partitioned persistent
allreduce ``world.pallreduce_init(buckets, op)`` returns (MPI-4's
partitioned model on a collective: ``MPI_Pallreduce_init``).  A call is
a **step**: ``req.start(buckets)`` rebinds the epoch's gradient set (a
device array is immutable, so every iteration's gradients are new
arrays), ``req.pready(i)`` releases bucket i, from the last bucket to
the first as the backward pass fills them (PyTorch DDP's order),
``req.wait()`` completes the request, and ``req.result`` is the list of
the B reductions.  ``wait()`` completes on the host: the results are
still in flight, and the window's closing sync holds all of them.

A point gives ``buckets`` (B) and ``bytes``, S as nccl-tests has it for
an allreduce: the bytes of one rank's whole gradient set, B equal
buckets.  The set is generated as one ``(n, B, S/B)`` array and cut into
its B buckets once, in set-up (``prepare``): one program a shape, not B
slices inside every step the harness times.  How many programs a step
launches is nowhere in this file: the trace shows it
(``tracered.programs_per_call``)."""
import functools

from harness import collkit

ELEMENTWISE_LAST_AXIS = True


def input_shape(point, n):
    return (n, point["buckets"], collkit.elems(point, point["buckets"]))


def input_sharding(env):
    return env.rank_sharding


def collectives_per_call(point):
    """SPC ``device_collectives`` moves once a bucket released."""
    return point["buckets"]


@functools.cache
def _cut(buckets: int, sharding):
    import jax

    return jax.jit(lambda x: tuple(x[:, i] for i in range(buckets)),
                   out_shardings=(sharding,) * buckets)


def prepare(env, point, x):
    """The B buckets of one generated set, each ``(n, S/B)`` with row i
    on the device of rank i, as ``make_world_array`` would place it."""
    return list(_cut(point["buckets"], env.rank_sharding)(x))


def inputs_of(buckets):
    return buckets


def bind(env, point, buckets):
    """One request a point, bound to the first set of the pool; binding
    runs each bucket's collective once."""
    req = env.world.pallreduce_init(buckets, collkit.op_of(point))
    start, pready, wait = req.start, req.pready, req.wait
    order = tuple(reversed(range(point["buckets"])))

    def step(buckets):
        start(buckets)
        for i in order:
            pready(i)
        wait()
        return req.result

    return step, point["buckets"]


def reference(point, n, xs):
    reduce = collkit.NUMPY_REDUCE[point.get("op", "SUM")]
    return [reduce(x) for x in xs]


def bus_bytes(point, n):
    return collkit.bus_bytes("allreduce", point, n)


def moved_bytes(point, n):
    return 0
