"""Attention under block diffusion's mask through both flash kernels
(``ops/flash_attention.flash_causal_forward`` and ``attn_block_backward``
with a static ``bd``, under the Pallas interpreter) and through their
``jnp`` twins (``parallel/causal.block_diffusion_flash_attention``) against
a dense ``(2L, 2L)`` masked softmax written from the four rules and its
autodiff: block lengths of 1, 4, 32, the tile and the whole sequence,
halves of three tiles, grouped key-value heads; the two ends of the
family (``B`` = ``L``: the noisy half attends to all of itself and to no
clean row; ``B`` = 1: a noisy row sees itself and the clean rows strictly
before it); the pairs both passes walk; the counters; and that
``causal_flash_attention`` without a description compiles to what it did.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.parallel import causal
from ompi_tpu.runtime import spc


BLOCK = 128


def _qkv(d, rows, h, n_kv, seed=0, b=1, dt=jnp.float32):
    rng = np.random.default_rng(seed)
    draw = lambda n: jnp.asarray(rng.normal(0, 1, (b, n, rows, d)), dt)
    return draw(h), draw(n_kv), draw(n_kv)


def dense_mask(length: int, bl: int) -> np.ndarray:
    """The ``(2L, 2L)`` mask from the four rules, rows ``[xt ; x0]``."""
    blk = np.arange(length) // bl
    i, j = blk[:, None], blk[None, :]
    return np.block([[i == j, j < i],
                     [np.zeros((length, length), bool), j <= i]])


def dense(q, k, v, bl):
    """``softmax(q k^T / sqrt(d) + mask) v`` over the (2L, 2L) scores, the
    key-value heads repeated: (o, logsumexp)."""
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(q.shape[-1])
    sc = jnp.where(dense_mask(q.shape[2] // 2, bl), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o, jax.nn.logsumexp(sc, axis=-1)


def walk_backward(q, k, v, do, o, lse, bl, block=BLOCK):
    """(dq, dk, dv) by ``attn_block_backward`` over ``bd_pairs``,
    interpreted."""
    delta = jnp.sum(do * o, axis=-1)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    for ijk in fa.bd_pairs(q.shape[2] // block, block, bl):
        acc = fa.attn_block_backward(
            jnp.asarray(ijk, jnp.int32), q, k, v, do, lse, delta, *acc,
            block=block, interpret=True, bd=bl)
    return acc


# (query heads, key-value heads, head width, tiles a half, block length)
CASES = [(8, 1, 128, 2, 4), (4, 2, 64, 3, 1), (4, 2, 64, 3, 32),
         (4, 4, 64, 2, BLOCK), (8, 1, 64, 3, 3 * BLOCK), (2, 1, 64, 3, 12)]
IDS = ["8on1-128-B4", "4on2-64-3tiles-B1", "4on2-64-3tiles-B32",
       "4on4-64-Btile", "8on1-64-3tiles-BL", "2on1-64-3tiles-B12"]


@pytest.mark.parametrize("h,n_kv,d,tiles,bl", CASES, ids=IDS)
def test_the_forward_kernel_under_the_mask_is_the_dense_softmax(
        h, n_kv, d, tiles, bl):
    """o and the logsumexp of the kernel and of its twin against the dense
    masked softmax, a half of ``tiles`` tiles (three is no power of two)."""
    q, k, v = _qkv(d, 2 * tiles * BLOCK, h, n_kv)
    want = dense(q, k, v, bl)
    got = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                  bd=bl)
    twin = causal._causal_fwd_blocks(q, k, v, BLOCK, True, bd=bl)
    for g, t, x in zip(got, twin, want):
        np.testing.assert_allclose(g, x, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(t, x, rtol=2e-5, atol=2e-5)
        assert np.all(np.isfinite(g)) and np.all(np.isfinite(t))


@pytest.mark.parametrize("h,n_kv,d,tiles,bl", CASES, ids=IDS)
def test_the_backward_kernel_under_the_mask_is_autodiff(h, n_kv, d, tiles,
                                                        bl):
    """dq, dk and dv of the fused block pairs, walked as the model walks
    them, and of the ``jnp`` twins, against the dense softmax's own
    gradient."""
    q, k, v = _qkv(d, 2 * tiles * BLOCK, h, n_kv, seed=1)
    do = jnp.asarray(np.random.default_rng(2).normal(0, 1, q.shape),
                     jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, bl)[0] * do),
                    (0, 1, 2)))(q, k, v)
    o, lse = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                     bd=bl)
    got = walk_backward(q, k, v, do, o, lse, bl)
    twin = jax.jit(jax.grad(lambda *a: jnp.sum(
        causal.block_diffusion_flash_attention(*a, BLOCK, True, bl) * do),
        (0, 1, 2)))(q, k, v)
    for name, g, t, x in zip("qkv", got, twin, want):
        scale = float(jnp.abs(x).max())
        np.testing.assert_allclose(g, x, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg="kernel d" + name)
        np.testing.assert_allclose(t, x, rtol=1e-4, atol=2e-5 * scale,
                                   err_msg="twin d" + name)
        assert np.all(np.isfinite(g))


@pytest.mark.parametrize("blocks,bl", [(4, 4), (6, 8), (8, 16), (8, 1)],
                         ids=["unrolled", "scanned-6", "scanned-8",
                              "scanned-B1"])
def test_the_twins_backward_walks_agree_beyond_the_unrolled_blocks(blocks,
                                                                   bl):
    """``_causal_bwd`` unrolls up to ``UNROLLED_BLOCKS`` blocks and scans
    beyond: with a small block both walks meet the dense gradient."""
    block = 16
    q, k, v = _qkv(32, blocks * block, 4, 2, seed=3)
    do = jnp.asarray(np.random.default_rng(4).normal(0, 1, q.shape),
                     jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, bl)[0] * do),
                    (0, 1, 2)))(q, k, v)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(
        causal.block_diffusion_flash_attention(*a, block, True, bl) * do),
        (0, 1, 2)))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(x).max()))


def test_a_kernel_tile_of_several_backward_tiles_is_masked_by_position():
    """A block of two backward tiles (``BWD_TILE`` cut to 64 here): the q
    tile's and the kv tile's places inside the block enter the mask."""
    q, k, v = _qkv(64, 4 * BLOCK, 2, 1, seed=5)
    do = jnp.asarray(np.random.default_rng(6).normal(0, 1, q.shape),
                     jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, 4)[0] * do),
                    (0, 1, 2)))(q, k, v)
    o, lse = dense(q, k, v, 4)
    old = fa.BWD_TILE
    fa.BWD_TILE = 64
    try:
        fa.attn_block_backward.clear_cache()
        got = walk_backward(q, k, v, do, o, lse, 4)
    finally:
        fa.BWD_TILE = old
        fa.attn_block_backward.clear_cache()
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, rtol=1e-4,
                                   atol=2e-5 * float(jnp.abs(x).max()))


def test_at_a_block_of_the_whole_length_the_noisy_half_sees_itself_alone():
    """``B`` = ``L``: every noisy row attends to the whole noisy half and
    to no clean row (changing the clean half's k and v moves no noisy
    output); the clean half attends to itself in full."""
    length = 2 * BLOCK
    q, k, v = _qkv(64, 2 * length, 2, 1, seed=7)
    o = causal.block_diffusion_flash_attention(q, k, v, BLOCK, True, length)
    full = lambda sl: jax.nn.softmax(
        jnp.einsum("bhqd,bhkd->bhqk", q[:, :, sl], jnp.repeat(k, 2, 1)[
            :, :, sl], precision="highest") / 8.0, -1) @ jnp.repeat(
        v, 2, 1)[:, :, sl]
    np.testing.assert_allclose(o[:, :, :length], full(slice(0, length)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(o[:, :, length:], full(slice(length, None)),
                               rtol=2e-5, atol=2e-5)
    k2 = k.at[:, :, length:].add(1.0)
    v2 = v.at[:, :, length:].add(1.0)
    o2 = causal.block_diffusion_flash_attention(q, k2, v2, BLOCK, True,
                                                length)
    assert np.array_equal(o[:, :, :length], o2[:, :, :length])


def test_at_a_block_of_one_a_noisy_row_sees_itself_and_the_clean_past():
    """``B`` = 1: noisy row t sees noisy row t and clean rows 0 .. t - 1
    and nothing else, which with every row masked is next-token prediction
    by a causal model; the clean half is plain causal attention."""
    length = 2 * BLOCK
    q, k, v = _qkv(64, 2 * length, 2, 2, seed=8)
    o = causal.block_diffusion_flash_attention(q, k, v, BLOCK, True, 1)
    mask = dense_mask(length, 1)
    t = np.arange(length)
    assert np.array_equal(mask[:length, :length], np.eye(length, dtype=bool))
    assert np.array_equal(mask[:length, length:], t[None, :] < t[:, None])
    clean = causal.causal_flash_attention(
        q[:, :, length:], k[:, :, length:], v[:, :, length:], BLOCK, True)
    np.testing.assert_allclose(o[:, :, length:], clean, rtol=2e-5, atol=2e-5)
    # noisy row 0 sees itself alone: its output is its own value row
    np.testing.assert_allclose(o[:, :, 0], v[:, :, 0], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bl,pairs,masked", [
    (1, 80, 24), (4, 80, 24), (32, 80, 24), (1024, 72, 0), (8192, 128, 0)],
    ids=["B1", "B4", "B32", "Btile", "BL"])
def test_the_pairs_walked_at_the_cells_shape(bl, pairs, masked):
    """16 tiles of 1,024 (``L`` 8,192): 36 + 36 + 8 = 80 of the 136 a
    causal walk has at a block length inside the tile, 24 of them masked
    inside; at the tile the strictly-earlier diagonal holds nothing and
    nothing is masked; at ``L`` each half meets itself whole."""
    got = fa.bd_pairs(16, 1024, bl)
    assert len(got) == pairs
    assert sum(kind == fa.BD_MASKED for _, _, kind in got) == masked
    assert not any(i >= 8 > j for i, j, _ in got)       # clean sees no noisy
    steps, kv_of, kinds = fa._bd_table(16, 1024, bl)
    assert kv_of.shape == kinds.shape == (16 * steps,)
    assert int((np.asarray(kinds) > 0).sum()) == pairs


def test_the_visible_pairs_are_counted_from_the_shapes():
    assert causal.bd_visible_pairs(8192, 4) == 67_141_632 == (
        16 * 2048 * 2049 // 2 + 16 * 2048 * 2047 // 2 + 8192 * 4)
    for length, bl in ((64, 4), (48, 1), (96, 32), (32, 32)):
        assert causal.bd_visible_pairs(length, bl) == int(
            dense_mask(length, bl).sum())


def test_the_counters_of_a_pass_under_the_mask():
    """What one layer application under the mask counts, from the shapes
    (``causal.pass_counts``: two sequences of 2 x 64 rows, blocks of 4 in
    tiles of 16); tracing the pass, forward and backward, moves none."""
    if "bd_built" not in spc.counters():
        spc.init()
    names = ("attn_built", "attn_pairs_walked", "attn_pairs_causal",
             "bd_built", "bd_pairs_visible", "bd_pairs_causal")
    before = {n: spc.read(n) for n in names}
    q, k, v = _qkv(32, 8 * 16, 2, 1, seed=9, b=2)
    jax.jit(jax.grad(lambda q: jnp.sum(causal.block_diffusion_flash_attention(
        q, k, v, 16, True, 4))))(q)
    assert {n: spc.read(n) for n in names} == before
    moved = causal.pass_counts(2, 2, 1, 8 * 16, 16, bd=4)
    walked = len(fa.bd_pairs(8, 16, 4))
    assert {n: moved[n] for n in names} == {
        "attn_built": 1, "attn_pairs_walked": walked,
        "attn_pairs_causal": 36, "bd_built": 1,
        "bd_pairs_visible": 2 * causal.bd_visible_pairs(64, 4),
        "bd_pairs_causal": 2 * 128 * 129 // 2}


def test_a_half_that_is_no_whole_blocks_is_refused():
    q, k, v = _qkv(32, 6 * 16, 2, 1)
    with pytest.raises(ValueError, match="whole"):
        jax.jit(jax.grad(
            lambda q: jnp.sum(causal.block_diffusion_flash_attention(
                q, k, v, 32, True, 4))))(q)


def _text(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def test_without_a_description_the_callers_programs_are_what_they_were():
    """``causal_flash_attention`` takes no description (the mask has an
    entry of its own beside ``selected_flash_attention``; PR 69 gave it
    the scores' ``scale``, None for every model but one); with ``bd``
    None the jaxpr of both kernels' callers is the text of the call
    without the argument; under the mask the kernels carry it in their
    names, as ``_select_`` is carried."""
    import inspect

    assert list(inspect.signature(
        causal.causal_flash_attention.__wrapped__).parameters) == [
        "q", "k", "v", "block", "interpret", "window", "scale"]
    q, k, v = _qkv(64, 2 * BLOCK, 4, 2, seed=8, dt=jnp.bfloat16)
    fwd = lambda **kw: lambda *a: fa.flash_causal_forward(
        *a, block=BLOCK, interpret=True, **kw)
    assert _text(fwd(), q, k, v) == _text(fwd(bd=None), q, k, v)
    assert "otpu_flash_bd_forward" not in _text(fwd(), q, k, v)
    assert "otpu_flash_bd_forward" in _text(fwd(bd=4), q, k, v)
    o, lse = fwd()(q, k, v)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    bwd = lambda **kw: lambda ij, *a: fa.attn_block_backward(
        ij, *a, block=BLOCK, interpret=True, **kw)
    args = (q, k, v, o.astype(q.dtype), lse, lse, *acc)
    pair = jnp.asarray((1, 0), jnp.int32)
    assert _text(bwd(), pair, *args) == _text(bwd(bd=None), pair, *args)
    assert "otpu_attn_bd_backward" not in _text(bwd(), pair, *args)
    assert "otpu_attn_bd_backward" in _text(
        bwd(bd=4), jnp.asarray((1, 1, fa.BD_MASKED), jnp.int32), *args)
    for interpret in (True, False):
        loss = lambda *a: jnp.sum(causal.causal_flash_attention(
            *a, BLOCK, interpret))
        assert "_bd_" not in _text(jax.grad(loss, (0, 1, 2)), q, k, v)
