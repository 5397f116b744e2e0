"""The Pallas row scatter-add (``ops/row_scatter``) in interpret mode
against ``.at[].add``, and ``parallel/experts.local_expert_ffn`` with its
two scatter-adds on the kernel against the same on XLA's lines."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import grouped_matmul as gm
from ompi_tpu.ops import row_scatter as rs
from ompi_tpu.parallel import experts
from ompi_tpu.runtime import spc


F32 = jnp.float32
TOKENS = 96

#: name -> the chunk's group sizes as a function of its rows: what is
#: left of the rows lies past the live count
GROUPS = {
    "same_token_in_neighbouring_groups_of_one_row":
        lambda rows: (1, 1, 1, 1, 1, rows // 4, 1, 1),
    "an_empty_group": lambda rows: (rows // 3, 0, 0, rows // 4, 0),
    "no_live_row": lambda rows: (0, 0, 0),
    "a_live_count_that_is_no_multiple_of_the_block":
        lambda rows: (rows // 4 + 3, rows // 2 + 1, 5),
    "every_row_live_in_one_group": lambda rows: (0, rows),
    "groups_end_on_block_edges": lambda rows: (rows // 4, rows // 2,
                                               rows // 4),
}
SHAPES = [(256, 256), (2048, 2560)]


def _chunk(sizes, rows, d, tokens=TOKENS, seed=0):
    """(token, offsets, y, scale) of a chunk: a group's tokens distinct
    and ascending, a group of one row naming token 7 (so neighbouring
    groups meet on it), and behind the live rows a token that names no
    row and NaN, which the kernel may not touch."""
    rng = np.random.default_rng(seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    token = np.full(rows, 2 ** 30, np.int32)
    for lo, hi in zip(offsets[:-1], offsets[1:]):
        # more rows than tokens: no group is longer than the sums are tall
        assert hi - lo <= tokens
        token[lo:hi] = (7 if hi - lo == 1 else
                        np.sort(rng.choice(tokens, hi - lo, replace=False)))
    y = rng.standard_normal((rows, d)).astype(np.float32)
    y[offsets[-1]:] = np.nan
    scale = rng.standard_normal(rows).astype(np.float32)
    return (jnp.asarray(token), jnp.asarray(offsets), jnp.asarray(y),
            jnp.asarray(scale))


def _by_xla(acc, token, offsets, y, scale):
    live = int(offsets[-1])
    return acc.at[token[:live]].add(y[:live] * scale[:live, None])


def _by_kernel(acc, token, offsets, y, scale):
    return rs.as_rows(rs.row_scatter_add(
        rs.as_tiles(acc), token, offsets, y, scale, interpret=True))


@pytest.mark.parametrize("scaled", [True, False], ids=["scaled", "by_one"])
@pytest.mark.parametrize("rows,d", SHAPES)
@pytest.mark.parametrize("groups", list(GROUPS))
def test_kernel_agrees_with_at_add(groups, rows, d, scaled):
    """The kernel against ``.at[].add`` of the live rows, bit for bit
    under weights of one (the backward loop's) and to the rounding of a
    product elsewhere: a token's terms are added in the order of the
    rows."""
    tokens = max(TOKENS, rows)
    token, offsets, y, scale = _chunk(GROUPS[groups](rows), rows, d, tokens)
    acc = jnp.asarray(np.random.default_rng(1).standard_normal(
        (tokens, d)), F32)
    scale = scale if scaled else jnp.ones_like(scale)
    got = _by_kernel(acc, token, offsets, y, scale)
    want = _by_xla(acc, token, offsets, y, scale)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=0 if not scaled else 1e-5)
    if groups == "no_live_row":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(acc))


def test_a_token_of_several_groups_is_summed_in_the_rows_order():
    """Groups of one row that all name one token, large and small terms
    in turn: float32 sums that depend on their order, equal to
    ``.at[].add``'s one after the other."""
    rows, d = 32, 128
    terms = np.where(np.arange(rows) % 2 == 0, 1e8, 1.0).astype(np.float32)
    y = jnp.asarray(np.tile(terms[:, None], (1, d)))
    token = jnp.full((rows,), 3, jnp.int32)
    offsets = jnp.arange(rows + 1, dtype=jnp.int32)
    acc = jnp.zeros((8, d), F32)
    want = acc
    for i in range(rows):
        want = want.at[3].add(y[i])
    got = _by_kernel(acc, token, offsets, y, jnp.ones((rows,), F32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,d", SHAPES)
def test_sums_carried_through_a_loop_of_three_chunks(rows, d):
    """``acc`` as the carry of a ``fori_loop`` of three calls, as the
    experts' loop holds it: the same as three ``.at[].add``, and the
    loop's program holds one buffer of the sums' shape: the call's
    result is its operand (``input_output_aliases``)."""
    tokens = max(TOKENS, rows)
    chunks = [_chunk(GROUPS["an_empty_group"](rows), rows, d, tokens, seed)
              for seed in range(3)]
    token, offsets, y, scale = (jnp.stack(x) for x in zip(*chunks))
    acc = jnp.ones((tokens, d), F32)

    def loop(acc, token, offsets, y, scale):
        def body(i, acc):
            return rs.row_scatter_add(acc, token[i], offsets[i], y[i],
                                      scale[i], interpret=True)
        return rs.as_rows(jax.lax.fori_loop(0, 3, body, rs.as_tiles(acc)))

    got = jax.jit(loop)(acc, token, offsets, y, scale)
    want = acc
    for c in chunks:
        want = _by_xla(want, *c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)
    jaxpr = jax.make_jaxpr(functools.partial(
        rs.row_scatter_add.__wrapped__, interpret=False))(
            rs.as_tiles(acc), token[0], offsets[0], y[0], scale[0])
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    # the three tables, y, then the sums: operand 4 is result 0
    assert tuple(call.params["input_output_aliases"]) == ((4, 0),)


@pytest.mark.parametrize("rows,d,dtype,ok", [
    (2048, 2560, F32, True), (2048, 2048, F32, True),
    (2048, 1024, F32, True), (16, 128, F32, True), (32, 256, F32, True),
    (2048, 2048, jnp.bfloat16, False),      # the sums are float32
    (2048, 1000, F32, False), (2048, 64, F32, False),   # no lane tiles
    (2048 + 128, 2048, F32, False),         # no whole blocks
])
def test_supported_reads_the_shape_alone(rows, d, dtype, ok):
    assert rs.supported(rows, d, dtype) == ok


@pytest.mark.parametrize("d,shape", [
    (2048, (8, 256)), (1024, (8, 128)), (2560, (4, 640)), (128, (1, 128)),
    (1536, (4, 384)), (2304, (2, 1152))])
def test_a_row_is_whole_lane_tiles_over_sublanes_without_padding(d, shape):
    """A row is held over as many of a tile's 8 sublanes as divide its
    lane tiles: its lanes are whole tiles of 128, and the sublanes are a
    tiling the TPU has (8, 4, 2 or 1), so nothing is padded."""
    assert rs.tile_shape(d) == shape
    assert shape[1] % 128 == 0 and shape[0] in (1, 2, 4, 8)
    assert rs.as_tiles(jnp.zeros((16, d))).shape == (16,) + shape


@pytest.fixture
def interpreted(monkeypatch):
    """``experts`` takes the kernel paths (``interpret`` false) with the
    kernels interpreted: the test steers, the program has no option."""
    monkeypatch.setattr(rs, "row_scatter_add", functools.partial(
        rs.row_scatter_add, interpret=True))
    for name in ("gmm", "tgmm"):
        monkeypatch.setattr(gm, name, functools.partial(
            getattr(gm, name), interpret=True))


FORMS = {"gated": (experts.grouped_expert_ffn_vjp, 3),
         "relu2": (experts.grouped_relu2_ffn_vjp, 2)}


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("held", ["some_slots", "no_slot",
                                  "every_token_twice"])
def test_local_expert_ffn_on_the_row_kernel(held, form, interpreted):
    """``local_expert_ffn``'s value and every gradient in float32 with
    both scatter-adds on the row kernel (``interpret`` false) against
    the ``.at[].add`` lines (``interpret`` true), for both expert forms,
    over several chunks with rows past the last held slot; with every
    token sent to both held experts each token's two terms meet in one
    row of the sums from two groups."""
    t, d, f, k, e, here = 64, 128, 128, 2, 8, 2
    ffn, n_mats = FORMS[form]
    cfg = types.SimpleNamespace(num_experts_per_tok=k, num_experts=e,
                                compute_dtype=F32)
    rng = np.random.default_rng(3)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, F32)
    h, weights = normal(t, d), jnp.abs(normal(t, k))
    mats = tuple(normal(here, d, f) for _ in range(n_mats - 1)) + (
        normal(here, f, d),)
    # a token's experts are distinct, as ``lax.top_k``'s are
    chosen = {
        "some_slots": lambda: rng.permuted(
            np.tile(np.arange(e), (t, 1)), axis=1)[:, :k],
        "no_slot": lambda: rng.permuted(
            np.tile(np.arange(here, e), (t, 1)), axis=1)[:, :k],
        "every_token_twice": lambda: np.tile([1, 0], (t, 1)),
    }[held]()
    order, sizes = experts.local_dispatch(jnp.asarray(chosen), 0, here)
    rows = experts.chunk_rows(t, k, here, e)
    assert rs.supported(rows, d)
    if held == "every_token_twice":
        assert int(sizes.sum()) == t * k > 2 * rows

    def loss(interpret, h, weights, *mats):
        return jnp.sum(jnp.sin(experts.local_expert_ffn(
            h, order, weights, sizes, mats, cfg, ffn, interpret)))

    spc.init()
    before = (spc.read("moe_scatter_built"),
              spc.read("moe_scatter_kernel_built"))
    args = tuple(range(2 + n_mats))
    got = jax.jit(jax.value_and_grad(functools.partial(loss, False), args))(
        h, weights, *mats)
    want = jax.jit(jax.value_and_grad(functools.partial(loss, True), args))(
        h, weights, *mats)
    # neither trace moved a counter: the decision function says which
    # loop's adds went by the kernel, and a step's plan counts them
    assert (spc.read("moe_scatter_built"),
            spc.read("moe_scatter_kernel_built")) == before
    assert experts.scatter_on_kernel(False, rows, d, h.dtype) == (True, "")
    on, why = experts.scatter_on_kernel(True, rows, d, h.dtype)
    assert not on and why.startswith("interpret")
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=scale * 1e-5)
    if held == "no_slot":
        assert not any(np.asarray(g).any() for g in got[1])


def test_a_width_without_lane_tiles_keeps_the_at_add_lines():
    """A row of 96 floats is no lane tile: with ``interpret`` false the
    loop's program holds XLA's scatter-adds and no kernel."""
    t, d, f, k, e, here = 64, 96, 128, 2, 8, 2
    cfg = types.SimpleNamespace(num_experts_per_tok=k, num_experts=e,
                                compute_dtype=F32)
    chosen = jnp.asarray(np.random.default_rng(0).integers(0, e, (t, k)))
    order, sizes = experts.local_dispatch(chosen, 0, here)
    h, weights = jnp.ones((t, d), F32), jnp.ones((t, k), F32)
    mats = (jnp.ones((here, d, f), F32),) * 2 + (jnp.ones((here, f, d), F32),)
    text = str(jax.make_jaxpr(lambda h: experts.local_expert_ffn(
        h, order, weights, sizes, mats, cfg,
        experts.grouped_expert_ffn_vjp, False))(h))
    assert "scatter-add" in text and "pallas_call" not in text
