"""The fused block pair of attention's backward pass
(``ops/flash_attention.attn_block_backward``) against its ``jnp`` twin
(``parallel/model._bwd_pair``), the kernel itself under the Pallas
interpreter; and ``causal_flash_attention``'s gradient with both kernels
in place against full attention's, by both walks over the pairs."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.parallel import model
from ompi_tpu.parallel.flagship import _full_attention


def _case(d, hv, dt, block, nb, seed=0, b=1, h=2):
    rng = np.random.default_rng(seed)
    draw = lambda w, t=dt: jnp.asarray(
        rng.normal(0, 1, (b, h, nb * block, w)), t)
    q, k, v, do = draw(d), draw(d), draw(hv), draw(hv)
    o, lse = model._causal_fwd_blocks(q, k, v, block, True)
    delta = jnp.sum(do.astype(jnp.float32) * o, -1)
    acc = [draw(d, jnp.float32), draw(d, jnp.float32),
           draw(hv, jnp.float32)]                 # not zero: it accumulates
    return q, k, v, do, lse, delta, acc


def _twin(q, k, v, do, lse, delta, acc, block, i, j):
    cut = lambda a, n: a[:, :, n * block:(n + 1) * block]
    parts = model._bwd_pair(
        cut(q, i), cut(k, j), cut(v, j), cut(do, i).astype(jnp.float32),
        cut(lse, i), cut(delta, i),
        model._tri_bias(block) if i == j else None,
        1.0 / math.sqrt(q.shape[-1]), q.dtype)
    return [a.at[:, :, n * block:(n + 1) * block].add(part)
            for a, part, n in zip(acc, parts, (i, j, j))]


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pair", [(1, 0), (1, 1), (0, 0)],
                         ids=["plain", "diagonal", "first"])
@pytest.mark.parametrize("d,hv", [(128, 128), (192, 128)],
                         ids=["128-128", "192-128"])
def test_the_backward_kernel_is_its_twin(d, hv, pair, dt):
    """One pair, plain and on the diagonal, at q and k as wide as v and
    wider: the pair's terms land on the accumulators' blocks i and j,
    and every other block comes back as it went in."""
    block = 256
    q, k, v, do, lse, delta, acc = _case(d, hv, dt, block, 2)
    got = fa.attn_block_backward(jnp.asarray(pair), q, k, v, do, lse,
                                 delta, *acc, block=block, interpret=True)
    want = _twin(q, k, v, do, lse, delta, acc, block, *pair)
    tol = 1e-5 if dt == jnp.float32 else 2e-4   # p, ds round to bfloat16
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
    other = 1 - pair[0]
    np.testing.assert_array_equal(
        got[0][:, :, other * block:(other + 1) * block],
        acc[0][:, :, other * block:(other + 1) * block])


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


@pytest.mark.parametrize("nb", [2, 8], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("d,hv", [(128, 128), (192, 128)],
                         ids=["128-128", "192-128"])
def test_attention_gradients_through_the_kernels(kernels_interpreted, d, hv,
                                                 nb):
    """Forward and backward kernel in ``causal_flash_attention``'s
    gradient, at 2 blocks (the unrolled walk) and at 8 (the scan),
    against full attention's."""
    assert 2 <= model.UNROLLED_BLOCKS < 8
    block, rng = 128, np.random.default_rng(1)
    draw = lambda w: jnp.asarray(rng.normal(0, 1, (1, 2, nb * block, w)),
                                 jnp.float32)
    q, k, v, w = draw(d), draw(d), draw(hv), draw(hv)
    got = jax.grad(lambda q, k, v: jnp.sum(model.causal_flash_attention(
        q, k, v, block, False) * w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(_full_attention(
        q, k, v, True) * w), argnums=(0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        assert g.dtype == x.dtype
        np.testing.assert_allclose(g, x, rtol=1e-4, atol=2e-5)
