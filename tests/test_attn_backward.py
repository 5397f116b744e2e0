"""The fused block pair of attention's backward pass
(``ops/flash_attention.attn_block_backward``) against its ``jnp`` twin
(``parallel/causal._bwd_pair``), the kernel itself under the Pallas
interpreter; and ``causal_flash_attention``'s gradient with both kernels
in place against full attention's, by both walks over the pairs."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.parallel import causal
from ompi_tpu.parallel.flagship import _full_attention



# (query heads, key-value heads, q's and k's width, v's)
_LAYOUTS = pytest.mark.parametrize("h,n_kv,d,hv", [
    (2, 2, 128, 128), (2, 2, 192, 128), (8, 2, 64, 64), (4, 1, 128, 128)],
    ids=["128-128", "192-128", "8on2-64", "4on1-128"])


def _case(d, hv, dt, block, nb, seed=0, b=1, h=2, n_kv=None):
    n_kv = n_kv or h
    rng = np.random.default_rng(seed)
    draw = lambda w, t=dt, n=h: jnp.asarray(
        rng.normal(0, 1, (b, n, nb * block, w)), t)
    q, k, v, do = draw(d), draw(d, n=n_kv), draw(hv, n=n_kv), draw(hv)
    o, lse = causal._causal_fwd_blocks(q, k, v, block, True)
    delta = jnp.sum(do.astype(jnp.float32) * o, -1)
    acc = [draw(d, jnp.float32), draw(d, jnp.float32, n_kv),
           draw(hv, jnp.float32, n_kv)]           # not zero: it accumulates
    return q, k, v, do, lse, delta, acc


def _twin(q, k, v, do, lse, delta, acc, block, i, j):
    """``_bwd_pair`` on k and v **repeated a query head**, its dk and dv
    parts summed over each group's query heads in float32, on the
    accumulators' blocks."""
    h, n_kv = q.shape[1], k.shape[1]
    cut = lambda a, n: a[:, :, n * block:(n + 1) * block]
    dq, dk, dv = causal._bwd_pair(
        cut(q, i), cut(jnp.repeat(k, h // n_kv, 1), j),
        cut(jnp.repeat(v, h // n_kv, 1), j), cut(do, i).astype(jnp.float32),
        cut(lse, i), cut(delta, i),
        causal._tri_bias(block) if i == j else None,
        1.0 / math.sqrt(q.shape[-1]), q.dtype)
    groups = lambda a: a.reshape(a.shape[0], n_kv, h // n_kv,
                                 *a.shape[2:]).sum(2)
    return [a.at[:, :, n * block:(n + 1) * block].add(part)
            for a, part, n in zip(acc, (dq, groups(dk), groups(dv)),
                                  (i, j, j))]


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pair", [(1, 0), (1, 1), (0, 0)],
                         ids=["plain", "diagonal", "first"])
@_LAYOUTS
def test_the_backward_kernel_is_its_twin(h, n_kv, d, hv, pair, dt):
    """One pair, plain and on the diagonal, at q and k as wide as v and
    wider, a key-value head a query head and query heads on fewer
    (LFM2's 4 a group at 64 wide, Nemotron's 4 on 1 at 128): the pair's
    terms land on the accumulators' blocks i and j, dk's and dv's the
    sum over each group of what k and v repeated a query head give, and
    every other block comes back as it went in."""
    block = 256
    q, k, v, do, lse, delta, acc = _case(d, hv, dt, block, 2, h=h,
                                         n_kv=n_kv, b=2 if h > n_kv else 1)
    got = fa.attn_block_backward(jnp.asarray(pair), q, k, v, do, lse,
                                 delta, *acc, block=block, interpret=True)
    want = _twin(q, k, v, do, lse, delta, acc, block, *pair)
    # p, ds round to bfloat16; a group's sum is h / n_kv terms long
    tol = 1e-5 if dt == jnp.float32 else 2e-4 * (h // n_kv)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
    other = 1 - pair[0]
    np.testing.assert_array_equal(
        got[0][:, :, other * block:(other + 1) * block],
        acc[0][:, :, other * block:(other + 1) * block])
    other = 1 - pair[1]
    for g, a in zip(got[1:], acc[1:]):
        np.testing.assert_array_equal(
            g[:, :, other * block:(other + 1) * block],
            a[:, :, other * block:(other + 1) * block])


@pytest.mark.parametrize("pair", [(1, 0), (1, 1)],
                         ids=["plain", "diagonal"])
def test_a_groups_folded_rows_are_its_heads_side_by_side(pair):
    """``_bwd_pair`` on the twins' layout (``_group_blocks``: a group's
    query heads folded into the rows of its one key-value head) is
    ``_bwd_pair`` on k and v repeated, summed by group; and the layout
    unfolds to what it was."""
    block, (i, j) = 128, pair
    q, k, v, do, lse, delta, acc = _case(64, 64, jnp.float32, block, 2,
                                         h=8, n_kv=2, b=2)
    fold = lambda a, n=2: causal._group_blocks(a, n, block)
    np.testing.assert_array_equal(causal._ungroup_blocks(fold(q), 8), q)
    np.testing.assert_array_equal(causal._ungroup_blocks(fold(lse), 8), lse)
    parts = causal._bwd_pair(
        fold(q)[i], fold(k)[j], fold(v)[j], fold(do.astype(jnp.float32))[i],
        fold(lse)[i], fold(delta)[i],
        causal._group_bias(block, 4) if i == j else None, 0.125, q.dtype)
    zero = [jnp.zeros_like(a) for a in acc]
    want = _twin(q, k, v, do, lse, delta, zero, block, i, j)
    for g, w, n, heads in zip(parts, want, (i, j, j), (8, 2, 2)):
        np.testing.assert_allclose(
            g.reshape(2, heads, block, 64),
            w[:, :, n * block:(n + 1) * block], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pair", [(1, 0), (1, 1)],
                         ids=["plain", "diagonal"])
def test_a_block_longer_than_a_tile_goes_by_tiles_and_strips(pair):
    """A block of two tiles: the diagonal pair's kv tile above the
    diagonal is skipped, the one on it goes by strips, the one under it
    whole; dk and dv gather both q tiles."""
    block = 2 * fa.BWD_TILE
    assert fa.BWD_TILE % fa.BWD_STRIP == 0 and fa.BWD_TILE > fa.BWD_STRIP
    q, k, v, do, lse, delta, acc = _case(192, 128, jnp.float32, block, 2,
                                         h=1)
    got = fa.attn_block_backward(jnp.asarray(pair), q, k, v, do, lse,
                                 delta, *acc, block=block, interpret=True)
    want = _twin(q, k, v, do, lse, delta, acc, block, *pair)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """``causal_flash_attention(..., interpret=False)`` takes the kernels
    and asks Mosaic for them; here the same calls run the same kernels
    under the interpreter."""
    for name in ("flash_causal_forward", "attn_block_backward"):
        monkeypatch.setattr(
            fa, name, lambda *a, _real=getattr(fa, name), **kw: _real(
                *a, **dict(kw, interpret=True)))


def _full_gradients(q, k, v, w):
    """Full attention's gradients with k and v repeated a query head by
    ``jnp.repeat``, whose transpose sums each group's."""
    rep = q.shape[1] // k.shape[1]
    return jax.jit(jax.grad(lambda q, k, v: jnp.sum(_full_attention(
        q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1), True) * w),
        argnums=(0, 1, 2)))(q, k, v)


def _gradients_agree(h, n_kv, d, hv, nb, dt, block, interpret, seed):
    """``causal_flash_attention``'s gradient in ``dt``, k and v with
    their own heads, against full attention's in float32 on k and v
    repeated a query head: dk and dv are the sums over each group."""
    rng = np.random.default_rng(seed)
    draw = lambda w, n=h: jnp.asarray(
        rng.normal(0, 1, (2, n, nb * block, w)), jnp.float32)
    q, k, v, w = draw(d), draw(d, n_kv), draw(hv, n_kv), draw(hv)
    got = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(causal.causal_flash_attention(
            q, k, v, block, interpret) * w), argnums=(0, 1, 2)))(
                q.astype(dt), k.astype(dt), v.astype(dt))
    rtol, atol = (1e-4, 2e-5) if dt == jnp.float32 else (0.06, 0.06)
    for g, x in zip(got, _full_gradients(q, k, v, w)):
        assert g.dtype == dt and g.shape == x.shape
        np.testing.assert_allclose(g.astype(jnp.float32), x, rtol=rtol,
                                   atol=atol * (h // n_kv))


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("nb", [2, 8], ids=["unrolled", "scanned"])
@_LAYOUTS
def test_attention_gradients_through_the_kernels(kernels_interpreted, h,
                                                 n_kv, d, hv, nb, dt):
    """Forward and backward kernel in ``causal_flash_attention``'s
    gradient, at 2 blocks (the unrolled walk) and at 8 (the scan)."""
    assert 2 <= causal.UNROLLED_BLOCKS < 8
    _gradients_agree(h, n_kv, d, hv, nb, dt, 128, False, 1)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("nb", [2, 8], ids=["unrolled", "scanned"])
@_LAYOUTS
def test_attention_gradients_through_the_twins(h, n_kv, d, hv, nb, dt):
    """The ``jnp`` twins the CPU runs, by both walks."""
    _gradients_agree(h, n_kv, d, hv, nb, dt, 64, True, 2)
