"""Sliding-window attention through both flash kernels
(``ops/flash_attention.flash_causal_forward`` and ``attn_block_backward``
with a static ``window``, under the Pallas interpreter) and through their
``jnp`` twins (``parallel/causal.causal_flash_attention``) against a dense
masked softmax and its autodiff: key j is visible to query i iff 0 <= i -
j < window.  Windows of 1, 2 and all blocks, grouped key-value heads (7
query heads a key-value head, SmallThinker's), head widths of 64 and 128;
the far tile's all-masked rows; the pairs both walk; that ``window`` None
leaves the callers' programs as they were; the counters."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.parallel import causal
from ompi_tpu.runtime import spc


BLOCK = 128


def _qkv(d, s, h, n_kv, seed=0, b=1, dt=jnp.float32):
    rng = np.random.default_rng(seed)
    draw = lambda n: jnp.asarray(rng.normal(0, 1, (b, n, s, d)), dt)
    return draw(h), draw(n_kv), draw(n_kv)


def dense(q, k, v, window):
    """``softmax(q k^T / sqrt(d) + mask) v`` over (s, s) scores, the
    key-value heads repeated, the window as its inequality: (o,
    logsumexp)."""
    s, rep = q.shape[2], q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(q.shape[-1])
    away = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    mask = (away >= 0) & (away < (window or s))
    sc = jnp.where(mask, sc, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o, jax.nn.logsumexp(sc, axis=-1)


def walk_backward(q, k, v, do, o, lse, window, pairs=None):
    """(dq, dk, dv) by ``attn_block_backward`` over ``pairs`` (the
    model's own walk if None), interpreted."""
    nb = q.shape[2] // BLOCK
    w = None if window is None else window // BLOCK
    delta = jnp.sum(do * o, axis=-1)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    for ij in (pairs or causal._window_pairs(nb, w)):
        acc = fa.attn_block_backward(
            jnp.asarray(ij, jnp.int32), q, k, v, do, lse, delta, *acc,
            block=BLOCK, interpret=True, window=window)
    return acc


CASES = [(7, 1, 128, 1), (7, 1, 128, 2), (14, 2, 64, 2), (4, 4, 64, 1),
         (7, 1, 64, 3)]
IDS = ["7on1-128-w1", "7on1-128-w2", "14on2-64-w2", "4on4-64-w1",
       "7on1-64-w3"]


@pytest.mark.parametrize("h,n_kv,d,w", CASES, ids=IDS)
def test_the_forward_kernel_under_a_window_is_the_dense_softmax(h, n_kv, d,
                                                                w):
    """Four blocks and a window of ``w`` of them: o and the logsumexp of
    the kernel and of its twin."""
    q, k, v = _qkv(d, 4 * BLOCK, h, n_kv)
    want = dense(q, k, v, w * BLOCK)
    got = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                  window=w * BLOCK)
    twin = causal._causal_fwd_blocks(q, k, v, BLOCK, True, w * BLOCK)
    for g, t, x in zip(got, twin, want):
        np.testing.assert_allclose(g, x, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(t, x, rtol=2e-5, atol=2e-5)
        assert np.all(np.isfinite(g)) and np.all(np.isfinite(t))


@pytest.mark.parametrize("h,n_kv,d,w", CASES, ids=IDS)
def test_the_backward_kernel_under_a_window_is_autodiff(h, n_kv, d, w):
    """dq, dk and dv of the fused block pairs, walked as the model walks
    them, and of the ``jnp`` twins, against the dense softmax's own
    gradient; a key-value head's sum over its 7 query heads among them."""
    q, k, v = _qkv(d, 4 * BLOCK, h, n_kv, seed=1)
    window = w * BLOCK
    do = jnp.asarray(np.random.default_rng(2).normal(0, 1, q.shape),
                     jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, window)[0] * do),
                    (0, 1, 2)))(q, k, v)
    o, lse = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                     window=window)
    got = walk_backward(q, k, v, do, o, lse, window)
    twin = jax.jit(jax.grad(lambda *a: jnp.sum(causal.causal_flash_attention(
        *a, BLOCK, True, window) * do), (0, 1, 2)))(q, k, v)
    for name, g, t, x in zip("qkv", got, twin, want):
        scale = float(jnp.abs(x).max())
        np.testing.assert_allclose(g, x, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg="kernel d" + name)
        np.testing.assert_allclose(t, x, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg="twin d" + name)
        assert np.all(np.isfinite(g))


@pytest.mark.parametrize("blocks", [5, 8], ids=["unrolled", "scanned"])
def test_the_twins_backward_walks_agree_beyond_the_unrolled_blocks(blocks):
    """``_causal_bwd`` unrolls up to ``UNROLLED_BLOCKS`` blocks and scans
    beyond: with a small block both walks meet the dense gradient."""
    block, window = 16, 32
    q, k, v = _qkv(32, blocks * block, 4, 2, seed=3)
    do = jnp.asarray(np.random.default_rng(4).normal(0, 1, q.shape),
                     jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, window)[0] * do),
                    (0, 1, 2)))(q, k, v)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(causal.causal_flash_attention(
        *a, block, True, window) * do), (0, 1, 2)))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(x).max()))


def test_a_window_of_all_blocks_is_full_attention_bit_for_bit():
    """A window that covers the sequence is no window: the same branches,
    so the same bits, forward and backward, and nothing counted as one."""
    q, k, v = _qkv(64, 4 * BLOCK, 4, 2, seed=5)
    run = lambda *window: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(causal.causal_flash_attention(
            *a, BLOCK, True, *window) ** 2), (0, 1, 2)))(q, k, v)
    plain, covered, longer = run(), run(4 * BLOCK), run(4096)
    for other in (covered, longer):
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(other)):
            np.testing.assert_array_equal(a, b)
    for window in (4 * BLOCK, 4096):
        counts = causal.pass_counts(1, 4, 2, 4 * BLOCK, BLOCK, window)
        assert counts["attn_window_built"] == 0
        assert counts == causal.pass_counts(1, 4, 2, 4 * BLOCK, BLOCK)


def test_the_far_tiles_all_masked_rows_stay_finite():
    """The far tile comes first and its last query row sees nothing of
    it: with huge scores everywhere (a max that would make exp(-inf -
    -inf) where nothing guards it) o, the logsumexp and the gradients are
    finite, and the last row of a block reads its own block alone."""
    q, k, v = _qkv(64, 3 * BLOCK, 2, 1, seed=6)
    q = q * 30.0
    window = BLOCK
    o, lse = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                     window=window)
    twin = causal._causal_fwd_blocks(q, k, v, BLOCK, True, window)
    want = dense(q, k, v, window)
    for g, t, x in zip((o, lse), twin, want):
        assert np.all(np.isfinite(g)) and np.all(np.isfinite(t))
        np.testing.assert_allclose(g, x, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(t, x, rtol=1e-4, atol=1e-4)
    # the far tile holds NaN where the window's last row cannot see: lost
    # on no row but those that can
    row = 2 * BLOCK - 1                     # block 1's last query row
    poisoned = k.at[:, :, :BLOCK].set(jnp.nan)
    o2, _ = fa.flash_causal_forward(q, poisoned, v, block=BLOCK,
                                    interpret=True, window=window)
    assert np.all(np.isnan(o2[:, :, BLOCK:row]))        # they read tile 0
    np.testing.assert_array_equal(o2[:, :, row], o[:, :, row])
    do = jnp.ones(q.shape, jnp.float32)
    for g in walk_backward(q, k, v, do, o, lse, window):
        assert np.all(np.isfinite(g))


@pytest.mark.parametrize("nb,w,pairs", [(16, 4, 70), (16, None, 136),
                                        (8, 4, 30), (4, 1, 7), (4, 3, 10)])
def test_the_walk_holds_the_pairs_a_window_reaches(nb, w, pairs):
    """At 16 blocks and a window of 4 a pass walks 70 pairs where a full
    one walks 136 (the cell's shape); at 8 blocks 30 of 36."""
    walk = causal._window_pairs(nb, w)
    assert len(walk) == len(set(walk)) == pairs
    reach = nb if w is None else w
    assert set(walk) == {(i, j) for i in range(nb) for j in range(nb)
                         if 0 <= i - j <= reach}
    assert walk == sorted(walk)


def test_the_kernels_walk_the_twins_pairs():
    """A pair the walk leaves out adds nothing the window lets through:
    every pair beyond the far one is wholly masked, so the kernel's walk
    over ``_window_pairs`` is the dense gradient (above) and one pair
    fewer is not."""
    q, k, v = _qkv(64, 4 * BLOCK, 2, 1, seed=7)
    window = 2 * BLOCK
    do = jnp.ones(q.shape, jnp.float32)
    o, lse = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                     window=window)
    pairs = causal._window_pairs(4, 2)
    whole = walk_backward(q, k, v, do, o, lse, window)
    fewer = walk_backward(q, k, v, do, o, lse, window, pairs[:-1])
    assert float(jnp.abs(whole[0] - fewer[0]).max()) > 1e-3
    grid = {}       # the forward's grid: q tile i, step n -> kv tile
    for i in range(4):
        for n in range(3):
            if i - 2 + n >= 0:
                grid[(i, i - 2 + n)] = True
    assert sorted(grid) == pairs


def _text(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def test_without_a_window_the_callers_programs_are_what_they_were():
    """``window`` None: the jaxpr of both kernels' callers is the text of
    the call without the argument, forward and backward, kernels and
    twins."""
    q, k, v = _qkv(64, 2 * BLOCK, 4, 2, seed=8, dt=jnp.bfloat16)
    for interpret in (True, False):
        loss = lambda *window: lambda *a: jnp.sum(
            causal.causal_flash_attention(*a, BLOCK, interpret, *window))
        assert _text(jax.grad(loss(), (0, 1, 2)), q, k, v) \
            == _text(jax.grad(loss(None), (0, 1, 2)), q, k, v)
    fwd = lambda **kw: lambda *a: fa.flash_causal_forward(
        *a, block=BLOCK, interpret=True, **kw)
    assert _text(fwd(), q, k, v) == _text(fwd(window=None), q, k, v)
    assert "far" not in _text(fwd(), q, k, v)
    o, lse = fwd()(q, k, v)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    bwd = lambda **kw: lambda ij, *a: fa.attn_block_backward(
        ij, *a, block=BLOCK, interpret=True, **kw)
    args = (jnp.asarray((1, 0), jnp.int32), q, k, v, o.astype(q.dtype), lse,
            lse, *acc)
    assert _text(bwd(), *args) == _text(bwd(window=None), *args)
    assert _text(bwd(), *args) != _text(bwd(window=BLOCK), *args)


@pytest.mark.parametrize("window", [100, 0, 2 * BLOCK, 3 * BLOCK],
                         ids=["no-multiple", "zero", "the-sequence",
                              "beyond-it"])
def test_a_window_the_kernels_cannot_walk_is_refused(window):
    """The kernels take a window of whole blocks inside the sequence (the
    model hands a longer one over as None); the twins refuse a window
    that is no whole number of blocks."""
    q, k, v = _qkv(64, 2 * BLOCK, 2, 2)
    with pytest.raises(ValueError, match="window"):
        fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                window=window)
    if window == 100:
        with pytest.raises(ValueError, match="window"):
            causal.causal_flash_attention(q, k, v, BLOCK, True, window)


def test_the_counters_count_what_was_built():
    """A layer application under a window of 1 block of 4, then one
    without (``causal.pass_counts``, from the shapes: the backward rule is
    no second application): ``attn_window_built`` 1 of ``attn_built`` 2,
    ``attn_pairs_walked`` 7 + 10 of ``attn_pairs_causal`` 2 x 10; and
    tracing a pass moves no counter."""
    if "attn_built" not in spc.counters():
        spc.init()
    names = ("attn_built", "attn_window_built", "attn_pairs_walked",
             "attn_pairs_causal", "attn_shared_kv_built")
    before = [spc.read(n) for n in names]
    q, k, v = _qkv(32, 64, 4, 2, seed=9)
    moved = dict.fromkeys(names, 0)
    for window in (16, None):
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            causal.causal_flash_attention(*a, 16, True, window)),
            (0, 1, 2)))(q, k, v)
        for n, by in causal.pass_counts(1, 4, 2, 64, 16, window).items():
            moved[n] += by
    assert [moved[n] for n in names] == [2, 1, 7 + 10, 2 * 10, 2]
    assert [spc.read(n) for n in names] == before
