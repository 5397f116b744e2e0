"""The Pallas kernels of Mamba-2's chunked scan (``ops/ssd_scan``) in
interpret mode at small tiles against ``parallel/mamba.ssd_chunked``'s XLA
form and the recurrence one position at a time, with and without a packed
row's documents, and which of the two ``mamba_mixer`` builds where."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import ssd_scan as ss
from ompi_tpu.parallel import granite_reference as ref
from ompi_tpu.parallel import mamba
from ompi_tpu.runtime import spc
from test_grouped_matmul import _primitives

#: chunks of 8 positions, heads of 64 (two a lane block), a state of 128
CHUNK, P, N = 8, 64, 128


def scan_inputs(seed, s, h, g=1, bt=1):
    """x, dt > 0, a < 0, b, c as the scan reads them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (bt, s, h, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (bt, s, h))),
            -jnp.exp(0.3 * jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (bt, s, g, N)) * N ** -0.5,
            jax.random.normal(ks[4], (bt, s, g, N)))


def doc_of(bt, s, starts):
    """(bt, s) int32: each further row's boundaries one position later."""
    at = np.zeros((bt, s), np.int32)
    for row in range(bt):
        for t in starts:
            at[row, min(t + row, s - 1):] += 1
    return jnp.asarray(at)


def by_positions(x, dt, a, b, c, doc):
    """The reference's recurrence, a group's B and C read by its heads."""
    g = b.shape[2]
    r = x.shape[2] // g
    doc = jnp.zeros(x.shape[:2], jnp.int32) if doc is None else doc
    return jnp.concatenate([ref.recurrence(
        x[:, :, k * r:(k + 1) * r], dt[:, :, k * r:(k + 1) * r],
        a[k * r:(k + 1) * r], b[:, :, k], c[:, :, k], doc)
        for k in range(g)], axis=2)


def near(got, want, rel, what=""):
    """Within ``rel`` of the largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=0, err_msg=what,
                               atol=rel * max(1e-30, np.abs(want).max()))


flat = lambda t: t.reshape(t.shape[:2] + (-1,))


@jax.jit
def oracles(x, dt, a, b, c, doc, weight, skip=None):
    """(y of the XLA form, with ``skip`` plus the skip term, its gradients
    under ``weight``, skip's last, y of the recurrence): one program a
    shape."""
    skip = jnp.zeros_like(a) if skip is None else skip
    y, pull = jax.vjp(
        lambda x, dt, a, b, c, skip: mamba.ssd_chunked(
            x, dt, a, b, c, CHUNK, doc) + skip[:, None] * x,
        x, dt, a, b, c, skip)
    with jax.default_matmul_precision("highest"):
        return y, pull(weight), \
            by_positions(x, dt, a, b, c, doc) + skip[:, None] * x


def kernels(x, dt, a, b, c, doc, weight, tile, skip=None):
    """(y, the states kept, (dx, ddt, da, db, dc, dskip)) of the two
    kernels on the scan's own (bt, s, heads, .) operands."""
    how = dict(chunk=CHUNK, p=P, groups=b.shape[2], tile=tile,
               interpret=True)
    y, kept = ss.scan_forward(flat(x), flat(b), flat(c), dt, a, doc, skip,
                              states=True, **how)
    dx, db, dc, ddt, da, dskip = ss.scan_backward(
        flat(x), flat(b), flat(c), dt, a, doc, skip, kept, flat(weight),
        **how)
    return y.reshape(x.shape), kept, (
        dx.reshape(x.shape), ddt, da, db.reshape(b.shape),
        dc.reshape(c.shape), dskip)


@pytest.mark.parametrize("s,h,g,tile,bt,starts", [
    (32, 4, 1, 2, 1, None), (27, 4, 1, 4, 1, None),
    (32, 4, 1, 2, 2, [8]), (32, 4, 1, 2, 1, [15]), (32, 4, 1, 4, 1, [12]),
    (32, 4, 1, 2, 1, [9, 13]), (45, 4, 1, 2, 2, [3, 36]),
    (21, 4, 1, 2, 1, [20]),
    (16, 16, 1, 16, 1, [5]), (16, 32, 1, 8, 1, None),
    (24, 8, 2, 2, 1, [7, 17])],
    ids=["no-documents", "a-length-that-pads", "a-chunks-first",
         "a-chunks-last", "mid-chunk", "two-in-one-chunk",
         "longer-than-three-chunks-and-pads", "the-rows-last-position",
         "a-group-of-16-heads", "a-group-of-32-heads",
         "two-groups-two-tiles-each"])
def test_the_kernels_are_the_xla_form_and_the_recurrence(s, h, g, tile, bt,
                                                          starts):
    """y against both, and the gradients of x, dt, a, b and c against
    autodiff's through the XLA form; under documents with the skip term
    in the kernels, and its factors' gradient."""
    args = scan_inputs(s + h, s, h, g, bt)
    doc = None if starts is None else doc_of(bt, s, starts)
    skip = None if starts is None else jnp.linspace(-1.0, 2.0, h)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want, want_grads, recurrence = oracles(*args, doc, weight, skip)
    y, kept, grads = kernels(*args, doc, weight, tile, skip)
    assert y.dtype == jnp.float32
    near(y, want, 2e-6, "XLA form")
    near(y, recurrence, 2e-5, "recurrence")
    assert (grads[-1] is None) == (skip is None)
    for name, got, wanted in zip(("dx", "ddt", "da", "db", "dc", "dskip"),
                                 grads[:5 + (skip is not None)], want_grads):
        assert np.all(np.isfinite(np.asarray(got))), name
        near(got, wanted, 2e-5, name)
    assert kept.shape == (bt, -(-s // CHUNK), N, h * P)
    assert float(jnp.max(jnp.abs(kept[:, 0]))) == 0.0


def test_the_operands_are_read_where_the_convolution_left_them():
    """One array [x | B | C] given three times with the lane blocks, as
    ``mamba._scan_views`` names them, in chunks of two lane blocks' width
    as Granite's are (a chunk's matrices are made a block at a time, on
    and under the diagonal); the output with and without what is kept."""
    h, g, s, chunk = 4, 2, 300, 256
    x, dt, a, b, c = scan_inputs(3, s, h, g)
    dt = dt * 0.1       # a running sum over 256 positions near its entries
    xbc = jnp.concatenate([flat(x), flat(b), flat(c)], axis=-1)
    doc = doc_of(1, s, [40, 127, 128, 200, 256, 290])
    views, how = mamba._scan_views(xbc, h, P, g, chunk)
    assert how["at"] == (0, 2, 4)
    y, kept = ss.scan_forward(*views, dt, a, doc, states=True, tile=2,
                              interpret=True, **how)
    # against chunks of 8: the scan is the same whatever the chunk, to
    # the rounding of a running sum over thirty-two times the positions
    near(y, flat(jax.jit(lambda *args: mamba.ssd_chunked(*args, 8, doc))(
        x, dt, a, b, c)), 1e-5)
    near(ss.scan_forward(*views, dt, a, doc, tile=2, interpret=True, **how),
         y, 0, "with and without the states")
    weight = jax.random.normal(jax.random.PRNGKey(1), y.shape)
    want = jax.jit(jax.grad(lambda xbc: jnp.sum(flat(mamba.ssd_chunked(
        xbc[..., :h * P].reshape(x.shape), dt, a,
        xbc[..., h * P:h * P + g * N].reshape(b.shape),
        xbc[..., h * P + g * N:].reshape(c.shape), 8, doc)) * weight)))(xbc)
    got = ss.scan_backward(*views, dt, a, doc, None, kept, weight, tile=2,
                           interpret=True, **how)
    near(jnp.concatenate(got[:3], axis=-1), want, 2e-5)


def test_a_document_that_starts_forgets_and_one_that_goes_on_remembers():
    """With the first chunk's x changed, the positions of its document in
    later chunks move as the recurrence's do and every other document's
    stay as they were to the bit."""
    x, dt, a, b, c = scan_inputs(11, 40, 4)
    dt = dt * 0.05                                 # a slow decay
    doc = doc_of(1, 40, [19])                      # chunks 0-2 | 2-4
    other = x.at[:, :CHUNK].multiply(-2.0)
    run = lambda x: ss.scan_forward(
        flat(x), flat(b), flat(c), dt, a, doc, chunk=CHUNK, p=P, tile=2,
        interpret=True).reshape(x.shape)
    got, moved = run(x), run(other)
    assert float(jnp.max(jnp.abs(moved[:, 16:19] - got[:, 16:19]))) > 1e-3
    assert float(jnp.max(jnp.abs(moved[:, 19:] - got[:, 19:]))) == 0.0
    near(moved, oracles(other, dt, a, b, c, doc, other)[2], 2e-5)


def test_a_decay_is_taken_from_one_set_of_running_sums():
    """Where ``dt a`` is large its running sum over a chunk is hundreds and
    one float32 ulp of it is 1e-5 to 1e-4 of a decay's exponent: the sums
    by head are the sums by position transposed exactly, so that a
    position's decay by itself is 1 and a neighbour's carries the rounding
    of the steps between, not of two sums made apart (that read 0.45 units
    of the kit's ``ssm_y`` on the chip, PR 70).  Against the recurrence in
    float64 the kernel is as near as the XLA form."""
    s, h, chunk = 256, 2, 128
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, s, h, P)).astype(np.float32)
    b = (rng.standard_normal((1, s, 1, N)) * N ** -0.5).astype(np.float32)
    c = rng.standard_normal((1, s, 1, N)).astype(np.float32)
    dt = rng.uniform(0.05, 0.3, (1, s, h)).astype(np.float32)
    a = np.asarray([-16.0, -32.0], np.float32)
    state, want = np.zeros((h, P, N)), np.zeros((1, s, h, P))
    for t in range(s):
        state = np.exp(dt[0, t].astype(np.float64) * a)[:, None, None] * state \
            + (dt[0, t, :, None] * x[0, t].astype(np.float64))[:, :, None] \
            * b[0, t, 0].astype(np.float64)
        want[0, t] = state @ c[0, t, 0].astype(np.float64)
    xla = mamba.ssd_chunked(x, dt, a, b, c, chunk)
    got = ss.scan_forward(flat(x), flat(b), flat(c), jnp.asarray(dt),
                          jnp.asarray(a), chunk=chunk, p=P, interpret=True)
    off = lambda y: float(np.max(np.abs(np.asarray(y).reshape(want.shape)
                                        - want)))
    assert off(got) <= 2 * off(xla) + 1e-7, (off(got), off(xla))


def test_which_shapes_have_tiles():
    assert ss.supported(256, 64, 128, 32, 1, 16384)    # Granite's cell
    assert ss.supported(128, 64, 128, 16, 1, 8192)     # Nemotron's
    assert (ss.heads_a_step(256, 64, 32), ss.heads_a_step(128, 64, 16)) \
        == (8, 16)
    assert ss.supported(128, 64, 128, 16, 2, 27)
    assert ss.supported(128, 128, 128, 3, 1, 8192)
    assert not ss.supported(128, 64, 128, 3, 1, 8192)  # half a lane block
    assert not ss.supported(128, 64, 64, 16, 1, 8192)  # a state of 64
    assert not ss.supported(128, 48, 128, 16, 1, 8192)
    assert not ss.supported(64, 64, 128, 16, 1, 8192)  # a chunk of 64
    assert not ss.supported(8, 64, 128, 4, 1, 32)      # these tests' own
    assert not ss.supported(1024, 64, 128, 16, 1, 8192)
    assert not ss.supported(128, 64, 128, 16, 3, 8192)


def small_mixer(chunk):
    """Granite's mixer at a width of 64 with two of four heads held, rows
    of 256 positions: (cfg, p, x)."""
    from ompi_tpu.parallel import config

    cfg = config.load_model_config(
        "benchmark/configs/granite-4.0-h-micro-train-1chip.json",
        hidden_size=64, mamba_n_heads=4, mamba_heads_here=2,
        mamba_chunk_size=chunk, seq_len=256, micro_batch=1,
        compute_dtype="float32")
    assert (cfg.mamba_head_dim, cfg.ssm_state_size) == (P, N)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    p = {k: 0.3 * jax.random.normal(next(keys), v)
         for k, v in mamba.TYPED_MIXER.shapes(cfg).items()}
    return cfg, p, jax.random.normal(next(keys), (1, 256, 64))


@pytest.mark.parametrize("documents", [False, True],
                         ids=["one-document", "documents"])
def test_the_mixer_on_the_kernels_is_the_mixer(documents, monkeypatch):
    """``mamba_mixer`` on the kernels (interpreted here, which takes the
    kernels' callers being told so) gives the XLA form's output, the same
    ``seen`` and the same gradient of every parameter and of x, through
    ``_kernel_scan``'s rule: the skip term inside, [dx | dB | dC] back in
    the convolution's order, ``a``'s, dt's and D's gradients."""
    for name in ("scan_forward", "scan_backward"):
        monkeypatch.setattr(ss, name, functools.partial(
            getattr(ss, name), interpret=True))
    cfg, p, x = small_mixer(128)
    doc = doc_of(1, 256, [100, 128, 255]) if documents else None
    weight = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def loss(p, x, interpret):
        y, _, seen = mamba.mamba_mixer(p, x, cfg, interpret=interpret,
                                       doc=doc)
        return jnp.sum(y * weight), (y, seen)

    both = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True),
                   static_argnums=2)
    (_, (y, seen)), grads = both(p, x, False)
    (_, (want, want_seen)), want_grads = both(p, x, True)
    near(y, want, 1e-5, "y")
    for name in want_seen:
        near(seen[name], want_seen[name], 1e-5, name)
    for name in p:
        near(grads[0][name], want_grads[0][name], 5e-5, name)
    near(grads[1], want_grads[1], 5e-5, "dx")


@pytest.mark.parametrize("chunk,interpret,documents,on_kernel", [
    (128, True, False, False),     # the CPU's choice
    (128, True, True, False),
    (8, False, True, False),       # a chunk that is no tile, anywhere
    (128, False, False, True),     # where Mosaic compiles: Nemotron's way
    (128, False, True, True)])     # and Granite's
def test_which_scan_the_mixer_builds_and_counts(chunk, interpret, documents,
                                                on_kernel):
    """On the CPU, and at a shape without tiles anywhere, the mixer's
    program holds ``ssd_chunked``'s scan and no ``pallas_call``; where
    Mosaic compiles and the shape has tiles it holds the two kernels and
    no scan.  The mixer's plan says what was built and, where the kernels
    are refused, the clause; tracing moves neither SPC counter."""
    cfg, p, x = small_mixer(chunk)
    spc.init()
    doc = doc_of(1, 256, [100]) if documents else None
    mixer = lambda p, x: mamba.mamba_mixer(p, x, cfg, interpret=interpret,
                                           doc=doc)[0]
    before = (spc.read("ssm_scan_built"), spc.read("ssm_scan_kernel_built"))
    names = _primitives(jax.make_jaxpr(mixer)(p, x).jaxpr)
    names |= _primitives(jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(mixer(p, x)), (0, 1)))(p, x).jaxpr)
    assert ("pallas_call" in names) == on_kernel
    assert ("scan" in names) == (not on_kernel)
    assert (spc.read("ssm_scan_built"),
            spc.read("ssm_scan_kernel_built")) == before
    held = mamba.TYPED_MIXER.plan(cfg, *x.shape[:2], interpret)
    assert held["impl"] == ("kernel" if on_kernel else "xla")
    assert held["counts"]["ssm_scan_built"] == 1
    assert held["counts"].get("ssm_scan_kernel_built", 0) == on_kernel
    if not on_kernel:
        assert held["why"] == "scan: " + (
            "interpret: Mosaic does not compile here" if interpret else
            "a chunk of 8 positions is not 1 to 4 lane blocks of 128")
