"""Causal attention's forward pass as one kernel
(``ops/flash_attention.flash_causal_forward``) against its ``jnp`` twin
(``parallel/causal._causal_fwd_blocks``), the kernel itself under the
Pallas interpreter: ``o`` and the logsumexp."""
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import flash_attention as fa
from ompi_tpu.parallel import causal


def _qkv(d, hv, dt, s, seed=0, b=2, h=2, n_kv=None):
    rng = np.random.default_rng(seed)
    draw = lambda w, n=h: jnp.asarray(rng.normal(0, 1, (b, n, s, w)), dt)
    return draw(d), draw(d, n_kv or h), draw(hv, n_kv or h)


def _agree(got, want, dt):
    # p rounds to bfloat16 for p v, in another order of additions
    tol = 1e-5 if dt == jnp.float32 else 4e-3
    for g, w, t in zip(got, want, (tol, 1e-5)):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=t, atol=t)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("nb", [1, 2, 8])
@pytest.mark.parametrize("h,n_kv,d,hv", [
    (2, 2, 128, 128), (2, 2, 192, 128), (8, 2, 64, 64), (4, 1, 128, 128)],
    ids=["128-128", "192-128", "8on2-64", "4on1-128"])
def test_the_forward_kernel_is_its_twin(h, n_kv, d, hv, nb, dt):
    """One tile a block, 1, 2 and 8 of them, two batch entries, q and k
    as wide as v and wider; a key-value head a query head, and query
    heads on fewer key-value heads (LFM2's 4 a group at a width of 64,
    Nemotron's 4 on 1 at 128): ``o`` and the logsumexp of the kernel on
    k and v as they are, against the twin on them as they are and on k
    and v repeated a query head."""
    block = 128
    q, k, v = _qkv(d, hv, dt, nb * block, h=h, n_kv=n_kv)
    got = fa.flash_causal_forward(q, k, v, block=block, interpret=True)
    _agree(got, causal._causal_fwd_blocks(q, k, v, block, True), dt)
    repeated = (jnp.repeat(t, h // n_kv, 1) for t in (k, v))
    _agree(got, causal._causal_fwd_blocks(q, *repeated, block, True), dt)


def test_query_heads_that_no_group_divides_are_refused():
    q, k, v = _qkv(64, 64, jnp.float32, 128, h=3, n_kv=2)
    with pytest.raises(ValueError, match="3 query heads on 2"):
        fa.flash_causal_forward(q, k, v, block=128, interpret=True)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_block_longer_than_a_tile_goes_by_tiles(dt):
    """A block of two tiles: q tile 1 meets kv tile 0 whole and kv tile
    1 under the mask, q tile 0 skips kv tile 1; the twin goes by the
    block."""
    block = 2 * fa.FWD_TILE
    q, k, v = _qkv(192, 128, dt, block, seed=1, b=1, h=1)
    got = fa.flash_causal_forward(q, k, v, block=block, interpret=True)
    _agree(got, causal._causal_fwd_blocks(q, k, v, block, True), dt)


def test_a_kv_tile_above_the_diagonal_changes_nothing():
    """k and v of the last tile are NaN: the q tiles before it come out
    as they do from clean inputs, bit for bit, and only the last is
    lost."""
    block, nb = 128, 4
    q, k, v = _qkv(192, 128, jnp.float32, nb * block)
    last = slice((nb - 1) * block, None)
    clean = fa.flash_causal_forward(q, k, v, block=block, interpret=True)
    got = fa.flash_causal_forward(
        q, k.at[:, :, last].set(jnp.nan), v.at[:, :, last].set(jnp.nan),
        block=block, interpret=True)
    for g, c in zip(got, clean):
        np.testing.assert_array_equal(g[:, :, :last.start],
                                      c[:, :, :last.start])
        assert np.all(np.isnan(g[:, :, last]))
