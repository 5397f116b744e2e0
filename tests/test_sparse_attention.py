"""Learned sparse attention's pieces (``parallel/dsa.dsa_attention``'s):
the exact selection by counting passes against ``jnp.sort``, ties and rows
before ``topk`` among them; the index/select kernel
(``ops/sparse_attention.index_select``) under the Pallas interpreter
against its ``jnp`` twin; both flash kernels and their twins under a
selection's tiles (``flash_causal_forward`` / ``attn_block_backward`` with
``select``) against a dense masked softmax and its autodiff, for a
selection that empties whole tiles; the alignment loss's kernel
(``index_loss``) and twin against the loss written as its definition; that
without a selection the callers' programs are what they were; the
counters.  The selection travels packed, eight keys a byte
(``pack_selection`` / ``unpack_selection``; ``selection_bytes`` is what a
check reads): the packing against ``jnp.packbits`` at lengths that are and
are not whole groups of 1,024 keys."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.ops import sparse_attention as sa
from ompi_tpu.parallel import causal, dsa
from ompi_tpu.runtime import spc


BLOCK = 128


def _indexer(s, heads=4, di=64, b=1, seed=0, dt=jnp.bfloat16, ties=True):
    rng = np.random.default_rng(seed)
    qi = jnp.asarray(rng.normal(0, 1, (b, heads, s, di)), dt)
    ki = jnp.asarray(rng.normal(0, 1, (b, s, di)), dt)
    if ties:        # ten keys alike: their scores tie in every row
        ki = ki.at[:, 10:20].set(ki[:, 5:6])
    w = jnp.asarray(rng.normal(0, 0.1, (b, s, heads)), jnp.float32)
    return qi, ki, w


def _qkv(s, h=4, n_kv=2, d=128, b=1, seed=1, dt=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    draw = lambda n: jnp.asarray(rng.normal(0, 1, (b, n, s, d)), dt)
    return draw(h), draw(n_kv), draw(n_kv)


def sorted_selection(scores, topk):
    """The selection by a whole sort, a row at a time: the ``min(t + 1,
    topk)`` largest of ``scores[t, :t + 1]``, a tie to the earlier key."""
    key = np.asarray(sa.ordered_bits(scores)).astype(np.int64)
    out = np.zeros(key.shape, bool)
    for b in range(key.shape[0]):
        for t in range(key.shape[1]):
            order = np.lexsort((np.arange(t + 1), -key[b, t, :t + 1]))
            out[b, t, order[:min(t + 1, topk)]] = True
    return out


@pytest.mark.parametrize("topk", [1, 7, 48, 300], ids=lambda k: f"top{k}")
def test_the_counting_selection_is_the_sorts(topk):
    """Rows before ``topk`` take every earlier key; later rows exactly
    ``topk``, none scored below a key left out, ties to the earlier."""
    qi, ki, w = _indexer(256)
    scores = dsa.index_scores(qi, ki, w)
    got = np.asarray(dsa.select_topk(scores, 0, topk))
    np.testing.assert_array_equal(got, sorted_selection(scores, topk))
    t = np.arange(256)
    np.testing.assert_array_equal(got.sum(-1)[0], np.minimum(t + 1, topk))
    sc = np.where(np.tril(np.ones((256, 256), bool)), np.asarray(scores[0]),
                  -np.inf)
    worst_in = np.where(got[0], sc, np.inf).min(-1)
    best_out = np.where(~got[0], sc, -np.inf).max(-1)
    assert (worst_in >= best_out).all()


def test_the_selection_breaks_a_tie_at_the_bar_by_position():
    """Scores that are all alike: the first ``topk`` keys win, exactly."""
    scores = jnp.zeros((1, 64, 64), jnp.float32)
    got = np.asarray(dsa.select_topk(scores, 0, 5))[0]
    for t in range(64):
        assert got[t].nonzero()[0].tolist() == list(range(min(t + 1, 5)))
    # and of signed zeros the positive ones stand above the negative
    signed = jnp.where(jnp.arange(64) % 2 == 0, -0.0, 0.0)[None, None] \
        * jnp.ones((1, 64, 1))
    got = np.asarray(dsa.select_topk(signed, 0, 3))[0]
    assert got[63].nonzero()[0].tolist() == [1, 3, 5]


@pytest.mark.parametrize("s,topk", [(256, 48), (512, 200)],
                         ids=["2tiles", "2chunks"])
def test_the_select_kernel_is_its_twin(s, topk):
    """The interpreted kernel against the twin's blocks and the sort: the
    same packed selection byte for byte (their scores are the same sums in
    the same order) and the same logsumexp over the selected scores."""
    qi, ki, w = _indexer(s, b=2)
    twin_sel, twin_lse = dsa._index_select_blocks(qi, ki, w, topk, 64,
                                                    True)
    sel, lse = sa.index_select(qi, ki, w, topk=topk, interpret=True)
    assert sel.dtype == jnp.int8 and sel.shape == (2, s, s // 8)
    np.testing.assert_array_equal(sel, twin_sel)
    np.testing.assert_allclose(lse, twin_lse, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        sa.unpack_selection(sel),
        sorted_selection(dsa.index_scores(qi, ki, w), topk))


def _mask(s, rows=24, b=2, seed=5):
    """A boolean mask (b, rows, s) with every bit plane of every group
    set somewhere and cleared somewhere."""
    return np.random.default_rng(seed).random((b, rows, s)) < 0.3


@pytest.mark.parametrize("s,what", [
    (s, what) for s in (16, 768, 2048, 2560)
    for what in ("inverse", "blocks", "bytes", "flags")],
    ids=lambda v: str(v))
def test_a_packed_selection_is_its_mask(s, what):
    """``pack_selection`` and ``unpack_selection`` are inverses, whole and
    by blocks (a traced one among them), at lengths that are whole groups
    of 1,024 keys (2,048), that are not (768 and 2,560: groups of 256 and
    512) and that are less than a byte's lanes (16);
    ``selection_bytes`` of the packed selection is ``jnp.packbits`` of the
    mask; the tiles' flags are the mask's."""
    mask = _mask(s)
    packed = jax.jit(sa.pack_selection)(mask)
    assert packed.dtype == jnp.int8 and packed.shape == (2, 24, s // 8)
    group = 8 * fa.select_lanes(s)
    assert s % group == 0 and (group == 1024 or s % (2 * group))
    if what == "inverse":
        np.testing.assert_array_equal(jax.jit(sa.unpack_selection)(packed),
                                      mask)
        # the layout: byte c of a group holds key lanes x m + c in bit m
        lanes = group // 8
        u = s - 3
        g, m, c = u // group, u % group // lanes, u % lanes
        one = jax.jit(sa.pack_selection)(np.arange(s)[None, None] == u)
        want = np.zeros(s // 8, np.uint8)
        want[g * lanes + c] = 1 << m
        np.testing.assert_array_equal(np.asarray(one)[0, 0].view(np.uint8),
                                      want)
    elif what == "blocks":
        # a block of whole groups, and (below a group) of whole planes
        for keys in {s, group, max(group // 4, 8)}:
            block = jax.jit(lambda p, f: sa.unpack_selection(p, f, keys))
            for first in range(0, s, keys):
                np.testing.assert_array_equal(block(packed, first),
                                              mask[..., first:first + keys])
        np.testing.assert_array_equal(      # and untraced, as the kernels'
            sa.unpack_selection(packed, s - keys, keys),    # callers do
            mask[..., s - keys:])
    elif what == "bytes":
        got = jax.jit(sa.selection_bytes)(packed)
        assert got.dtype == jnp.uint8
        np.testing.assert_array_equal(got, jnp.packbits(
            jnp.asarray(mask), axis=-1, bitorder="little"))
    else:
        # single keys here and there: some tile pairs hold none
        tile = max(group // 4, 8)
        nt = s // tile
        rng = np.random.default_rng(s)
        square = np.zeros((1, s, s), bool)
        square[0, rng.integers(0, s, nt * nt), rng.integers(0, s, nt * nt)] \
            = True
        got = jax.jit(lambda m: fa._tile_flags(sa.pack_selection(m), tile))(
            square)
        want = square.reshape(nt, tile, nt, tile).any(axis=(1, 3))
        np.testing.assert_array_equal(np.asarray(got).reshape(nt, nt), want)
        assert want.any() and not want.all()


def test_a_length_or_a_tile_the_packing_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="whole number of bytes"):
        sa.pack_selection(jnp.zeros((1, 4, 12), bool))
    with pytest.raises(ValueError, match="groups of 256"):
        sa.unpack_selection(jnp.zeros((1, 4, 96), jnp.int8), 0, 96)


def dense(q, k, v, sel):
    """``softmax(q k^T / sqrt(d) + mask) v`` over (s, s) scores under the
    selection, the key-value heads repeated: (o, logsumexp)."""
    rep = q.shape[1] // k.shape[1]
    f32 = lambda t: t.astype(jnp.float32)
    k, v = (jnp.repeat(f32(t), rep, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", f32(q), k,
                    precision=jax.lax.Precision.HIGHEST) \
        / math.sqrt(q.shape[-1])
    sc = jnp.where(sel[:, None] != 0, sc, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o, jax.nn.logsumexp(sc, axis=-1)


def _selection(s, topk, b=1, empty=True):
    """A selection of ``s`` positions as a mask (``pack_selection`` hands
    it to what is tested): the indexer's own; with ``empty``
    the rows of the last two blocks select nothing of block 1 (a whole
    tile pair, (2, 1) and (3, 1), is empty) and rows of block 2 nothing of
    their own block but the diagonal key."""
    sel = sa.unpack_selection(dsa._index_select_blocks(
        *_indexer(s, b=b), topk, 64, True)[0])
    if empty:
        sel = sel.at[:, 2 * BLOCK:, BLOCK:2 * BLOCK].set(False)
        sel = sel.at[:, 2 * BLOCK:3 * BLOCK, 2 * BLOCK:3 * BLOCK].set(False)
        sel = sel | jnp.eye(s, dtype=bool)[None]
    return sel


@pytest.mark.parametrize("h,n_kv,d,empty", [
    (8, 1, 128, True), (4, 2, 64, True), (4, 4, 128, False)],
    ids=["8on1-128-empty", "4on2-64-empty", "4on4-128"])
def test_the_forward_kernel_under_a_selection_is_the_dense_softmax(
        h, n_kv, d, empty):
    s = 4 * BLOCK
    q, k, v = _qkv(s, h, n_kv, d, dt=jnp.float32)
    sel = _selection(s, 100, empty=empty)
    packed = sa.pack_selection(sel)
    assert not empty or int(fa._tile_flags(packed, BLOCK).reshape(4, 4)[3, 1]
                            ) == 0
    want = dense(q, k, v, sel)
    got = fa.flash_causal_forward(q, k, v, block=BLOCK, interpret=True,
                                  select=packed)
    twin = causal._causal_fwd_blocks(q, k, v, BLOCK, True, select=packed)
    for g, t, x in zip(got, twin, want):
        np.testing.assert_allclose(g, x, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(t, x, rtol=2e-5, atol=2e-5)
        assert np.all(np.isfinite(g)) and np.all(np.isfinite(t))


def walk_backward(q, k, v, do, o, lse, sel):
    """(dq, dk, dv) by ``attn_block_backward`` over the causal pairs under
    the packed selection, interpreted."""
    delta = jnp.sum(do * o, axis=-1)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    select = (jnp.swapaxes(sel, 1, 2), fa._tile_flags(sel, BLOCK))
    for ij in causal._window_pairs(q.shape[2] // BLOCK, None):
        acc = fa.attn_block_backward(
            jnp.asarray(ij, jnp.int32), q, k, v, do, lse, delta, *acc,
            block=BLOCK, interpret=True, select=select)
    return acc


@pytest.mark.parametrize("h,n_kv,d,blocks", [
    (8, 1, 128, 4), (4, 2, 64, 4), (2, 2, 128, 6)],
    ids=["8on1-128", "4on2-64", "2on2-128-scanned"])
def test_the_backward_under_a_selection_is_autodiff(h, n_kv, d, blocks):
    """The kernel's walk and the twins' (unrolled up to four blocks,
    scanned beyond) against the dense softmax's autodiff, with an empty
    tile pair in the walk."""
    s = blocks * BLOCK
    q, k, v = _qkv(s, h, n_kv, d, dt=jnp.float32)
    sel = _selection(s, 100)
    rng = np.random.default_rng(3)
    do = jnp.asarray(rng.normal(0, 1, (1, h, s, d)), jnp.float32)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a, sel)[0] * do),
                    argnums=(0, 1, 2)))(q, k, v)
    o, lse = dense(q, k, v, sel)
    packed = sa.pack_selection(sel)
    twin = causal._causal_bwd(BLOCK, True, None, (q, k, v, o, lse), do,
                             select=packed)
    got = walk_backward(q, k, v, do, o, lse, packed)
    for g, t, x in zip(got, twin, want):
        np.testing.assert_allclose(g, x, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(t, x, rtol=3e-4, atol=3e-4)


def test_the_selected_attention_hands_no_gradient_to_its_logsumexp():
    """``selected_flash_attention`` returns (o, logsumexp); a loss that
    reads the logsumexp moves no gradient: what reads it reads a
    constant."""
    s = 2 * BLOCK
    q, k, v = _qkv(s, dt=jnp.float32)
    sel = sa.pack_selection(_selection(s, 50, empty=False))
    fn = lambda q, k, v: jnp.sum(causal.selected_flash_attention(
        q, k, v, sel, BLOCK, True, 50)[1])
    for g in jax.jit(jax.grad(fn, argnums=(0, 1, 2)))(q, k, v):
        assert not np.any(np.asarray(g))


def loss_by_definition(qi, ki, w, q, k, sel):
    """The alignment loss by row, dense: ``KL(pbar || softmax_S(I))``."""
    a = jax.nn.softmax(jnp.where(
        sel[:, None] != 0, jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32),
            jnp.repeat(k.astype(jnp.float32), q.shape[1] // k.shape[1], 1),
            precision=jax.lax.Precision.HIGHEST) / math.sqrt(q.shape[-1]),
        -jnp.inf), -1)
    pbar = jnp.mean(a, axis=1)
    scores = dsa.index_scores(qi, ki, w)
    logq = jax.nn.log_softmax(jnp.where(sel != 0, scores, -jnp.inf), -1)
    live = pbar > 0
    return jnp.sum(jnp.where(live, pbar * (jnp.log(jnp.where(
        live, pbar, 1.0)) - jnp.where(sel != 0, logq, 0.0)), 0.0), -1)


def test_the_alignment_loss_kernel_is_the_definition_and_its_autodiff():
    """The interpreted kernel and the twin: the loss by row and the
    gradients of its sum with respect to qI, kI and w, ``pbar`` read as a
    constant."""
    s, topk = 2 * sa.LOSS_TILE // 2, 60
    qi, ki, w = _indexer(s, b=2, dt=jnp.float32, ties=False)
    q, k, v = _qkv(s, 4, 2, 128, b=2, dt=jnp.float32)
    sel, ilse = dsa._index_select_blocks(qi, ki, w, topk, 64, True)
    _, lse = causal._causal_fwd_blocks(q, k, v, BLOCK, True, select=sel)
    mask = sa.unpack_selection(sel)
    want = loss_by_definition(qi, ki, w, q, k, mask)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(loss_by_definition(*a, q, k, mask)),
        argnums=(0, 1, 2)))(qi, ki, w)
    got = sa.index_loss(q, k, lse, qi, ki, w, ilse, sel, interpret=True)
    twin = dsa._index_loss_blocks(qi, ki, w, q, k, lse, ilse, sel, 64,
                                    True)
    for g, t, x in zip(got, twin, (want, *grads)):
        np.testing.assert_allclose(g, x, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(t, x, rtol=2e-4, atol=2e-5)


def test_the_alignment_loss_reaches_the_indexer_alone():
    """``index_alignment_loss``'s gradient with respect to q, k and the
    logsumexp is none at all, and its rows carry none."""
    s = 2 * BLOCK
    qi, ki, w = _indexer(s, dt=jnp.float32, ties=False)
    q, k, v = _qkv(s, dt=jnp.float32)
    sel, ilse = dsa._index_select_blocks(qi, ki, w, 40, 64, True)
    _, lse = causal._causal_fwd_blocks(q, k, v, BLOCK, True, select=sel)
    total = lambda *a: dsa.index_alignment_loss(*a, ilse, sel, 64, True)[0]
    grads = jax.jit(jax.grad(total, argnums=(0, 1, 2, 3, 4, 5)))(
        qi, ki, w, q, k, lse)
    assert all(np.any(np.asarray(g)) for g in grads[:3])
    assert not any(np.any(np.asarray(g)) for g in grads[3:])
    rows = lambda *a: jnp.sum(
        dsa.index_alignment_loss(*a, ilse, sel, 64, True)[1])
    assert not any(np.any(np.asarray(g)) for g in jax.jit(jax.grad(
        rows, argnums=(0, 1, 2)))(qi, ki, w, q, k, lse))


def test_without_a_selection_the_callers_programs_are_what_they_were():
    """``select`` None changes no instruction of either kernel's caller."""
    q, k, v = _qkv(2 * BLOCK, dt=jnp.float32)
    text = lambda fn, *a, **kw: jax.jit(fn, static_argnames=tuple(kw)).lower(
        *a, **kw).as_text()
    fwd = lambda q, k, v, **kw: fa.flash_causal_forward(
        q, k, v, block=BLOCK, interpret=True, **kw)
    assert text(fwd, q, k, v) == text(fwd, q, k, v, select=None)
    twin = lambda q, k, v, **kw: causal._causal_fwd_blocks(
        q, k, v, BLOCK, True, **kw)
    assert text(twin, q, k, v) == text(twin, q, k, v, select=None)


def test_the_counters_count_what_was_built():
    if "dsa_built" not in spc.counters():
        spc.init()
    s, topk = 2 * BLOCK, 100
    q, k, v = _qkv(s, dt=jnp.float32)
    mask = _selection(s, topk, empty=False)
    sel = sa.pack_selection(mask)
    before = {n: spc.read(n) for n in ("dsa_built", "dsa_keys_selected",
                                       "dsa_keys_causal", "dsa_mask_bytes",
                                       "attn_built")}
    jax.jit(jax.grad(lambda q: jnp.sum(causal.selected_flash_attention(
        q, k, v, sel, BLOCK, True, topk)[0])))(q)
    # a traced pass moves nothing: a layer application counts, from the
    # shapes (``causal.pass_counts``), fed once a built step
    assert {n: spc.read(n) for n in before} == before
    moved = causal.pass_counts(*q.shape[:2], k.shape[1], s, BLOCK, topk=topk)
    assert moved["dsa_built"] == moved["attn_built"] == 1
    selected = topk * (topk + 1) // 2 + (s - topk) * topk
    assert moved["dsa_keys_selected"] == moved["dsa_built"] * selected
    assert moved["dsa_keys_causal"] == moved["dsa_built"] * s * (s + 1) // 2
    assert moved["dsa_mask_bytes"] == moved["dsa_built"] * s * s // 8 \
        == moved["dsa_built"] * sel.size
    assert int(np.asarray(mask, np.int64).sum()) == selected
