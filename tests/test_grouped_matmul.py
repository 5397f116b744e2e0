"""The Pallas grouped matmul (``ops/grouped_matmul``) in interpret mode
against ``lax.ragged_dot`` in float32, and which of the two
``parallel/experts._grouped_matmul`` builds where."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import grouped_matmul as gm
from ompi_tpu.ops import row_scatter
from ompi_tpu.parallel import experts
from ompi_tpu.runtime import spc


BF16, F32 = jnp.bfloat16, jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: name -> (group sizes, rows m, row tile): m - sum(sizes) rows lie past
#: the last group
GROUPS = {
    "uneven": ((70, 9, 130, 47), 256, 64),
    "empty_first": ((0, 100, 60, 96), 256, 64),
    "empty_middle": ((100, 0, 0, 156), 256, 64),
    "empty_last": ((128, 128, 0), 256, 64),
    "one_group_has_every_row": ((0, 256, 0), 256, 64),
    "rows_past_the_last_group": ((40, 0, 90, 21), 256, 64),
    "no_row_at_all": ((0, 0), 128, 64),
    "tile_straddles_two_groups": ((96, 160), 256, 64),
    "tile_straddles_three_groups": ((70, 20, 30, 136), 256, 128),
    "groups_end_on_tile_edges": ((64, 128, 64), 256, 64),
}
#: name -> (k, n, column tile or tiles): 14 and 21 tiles of 128 are
#: LFM2's and Nemotron's expert widths
WIDTHS = {
    "square": (128, 256, 128),
    "k14_tiles": (1792, 128, 128),
    "n14_tiles": (128, 1792, 896),
    "n21_tiles": (128, 2688, 896),
}


def _operands(sizes, m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), BF16)
    live = jnp.arange(m)[:, None] < sum(sizes)
    return (bf(m, k), bf(len(sizes), k, n), bf(m, n),
            jnp.asarray(sizes, jnp.int32), live)


def _ragged(a, w, sizes):
    return jax.lax.ragged_dot(a.astype(F32), w.astype(F32), sizes,
                              precision=HIGHEST)


def _close(got, want, what):
    # bfloat16 products are exact in float32; the sums differ by order
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6 * scale, err_msg=what)


CASES = [(g, "square") for g in GROUPS] + [("uneven", w) for w in WIDTHS
                                           if w != "square"]


@pytest.mark.parametrize("form", ["gmm", "gmm_transposed_rhs", "tgmm"])
@pytest.mark.parametrize("groups,widths", CASES)
def test_kernel_agrees_with_ragged_dot(groups, widths, form):
    """Each of the three products on the live rows; what lies past the
    last group is NaN on the way in where the product must not read it,
    and an empty group's weight gradient is zeros."""
    (sizes, m, tm), (k, n, tn) = GROUPS[groups], WIDTHS[widths]
    a, w, ct, sz, live = _operands(sizes, m, k, n)
    nan = lambda x: jnp.where(live, x, jnp.nan)
    if form == "tgmm":
        got = gm.tgmm(nan(a), nan(ct), sz, tiles=(tm, min(k, 896), tn),
                      interpret=True)
        want = jax.vjp(lambda w: _ragged(a, w, sz), w.astype(F32))[1](
            jnp.where(live, ct, 0).astype(F32))[0]
        assert not np.asarray(got)[np.asarray(sizes) == 0].any()
        return _close(got, want, form)
    if form == "gmm":
        got = gm.gmm(nan(a), w, sz, tiles=(tm, tn), interpret=True)
        want = _ragged(a, w, sz)
    else:
        got = gm.gmm(nan(ct), w, sz, transpose_rhs=True,
                     tiles=(tm, min(k, 896)), interpret=True)
        want = _ragged(ct, w.swapaxes(1, 2), sz)
    _close(jnp.where(live, got, 0), jnp.where(live, want, 0), form)


@pytest.mark.parametrize("groups", list(GROUPS))
def test_tgmm_adds_to_a_running_sum(groups):
    """``tgmm`` with a starting value is that value plus ``ragged_dot``'s
    transpose, for groups that are empty, whole tiles, that straddle a
    tile, with NaN in the rows past the last group; and the sum of a
    group with no row comes out bit for bit as it went in (the kernel
    does not visit it)."""
    (sizes, m, tm), (k, n, tn) = GROUPS[groups], WIDTHS["square"]
    a, w, ct, sz, live = _operands(sizes, m, k, n)
    nan = lambda x: jnp.where(live, x, jnp.nan)
    acc = jnp.asarray(np.random.default_rng(3).standard_normal(
        (len(sizes), k, n)), F32)
    got = gm.tgmm(nan(a), nan(ct), sz, acc, tiles=(tm, k, tn),
                  interpret=True)
    want = acc + jax.vjp(lambda w: _ragged(a, w, sz), w.astype(F32))[1](
        jnp.where(live, ct, 0).astype(F32))[0]
    _close(got, want, groups)
    empty = np.asarray(sizes) == 0
    np.testing.assert_array_equal(np.asarray(got)[empty],
                                  np.asarray(acc)[empty])
    assert not np.array_equal(np.asarray(got)[~empty],
                              np.asarray(acc)[~empty]) or empty.all()


@pytest.mark.parametrize("rows", [64, 128])
@pytest.mark.parametrize("groups", ["uneven", "rows_past_the_last_group",
                                    "tile_straddles_three_groups"])
def test_a_group_that_straddles_two_calls_sums_over_both(groups, rows):
    """The loop's use: the rows walked ``rows`` a call, each call handed
    the rows every group has in it and the sum so far.  A group whose
    rows lie in two calls is summed over both, and the whole is
    ``ragged_dot``'s transpose over all the rows at once."""
    (sizes, m, tm), (k, n, tn) = GROUPS[groups], WIDTHS["square"]
    a, w, ct, sz, live = _operands(sizes, m, k, n)
    nan = lambda x: jnp.where(live, x, jnp.nan)
    ends = jnp.cumsum(sz)
    acc = jnp.zeros((len(sizes), k, n), F32)
    calls = 0
    for lo in range(0, m, rows):
        here = jnp.clip(jnp.minimum(ends, lo + rows)
                        - jnp.maximum(ends - sz, lo), 0, rows)
        calls += int((here > 0).sum())
        acc = gm.tgmm(nan(a)[lo:lo + rows], nan(ct)[lo:lo + rows], here,
                      acc, tiles=(min(tm, rows), k, tn), interpret=True)
    assert calls > len(sizes) - sizes.count(0)      # a group in two calls
    want = jax.vjp(lambda w: _ragged(a, w, sz), w.astype(F32))[1](
        jnp.where(live, ct, 0).astype(F32))[0]
    _close(acc, want, groups)


@pytest.mark.parametrize("m,k,n", [
    (32768, 2048, 1792), (32768, 1792, 2048),      # LFM2
    (65536, 2048, 1024), (65536, 1024, 2048),      # OLMoE
    (8192, 2048, 768), (8192, 768, 2048),          # JoyAI
    (8192, 1024, 2688), (8192, 2688, 1024),        # Nemotron
    (32768, 2048, 512), (32768, 512, 2048),        # Qwen3-Next
    (8192, 4096, 14336), (64, 128, 128)])
def test_tiles_follow_the_shape_and_fit_vmem(m, k, n):
    """The tiles chosen for the cells' shapes, a far larger one and a
    tiny one divide them, are whole lanes wide and fit the VMEM the
    module allows itself, in all three forms; the library's (128, 128,
    128) is nobody's at a cell's shape."""
    assert gm.supported(m, k, n)
    for kk, nn in ((k, n), (n, k)):
        tm, tn = gm.gmm_tiles(m, kk, nn)
        assert m % tm == 0 and nn % tn == 0 and tn % 128 == 0
        assert 2 * (tm * kk * 2 + kk * tn * 2 + tm * tn * 4) <= gm.VMEM_TILES
        assert m < 8192 or (tm, tn) != (128, 128)
    tm, tk, tn = gm.tgmm_tiles(m, k, n)
    assert m % tm == 0 and k % tk == 0 and n % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0
    assert 2 * (tm * tk * 2 + tm * tn * 2 + tk * tn * 4) <= gm.VMEM_TILES


def test_shapes_without_tiles_stay_on_ragged_dot():
    assert not gm.supported(256, 96, 128)       # k: no whole lanes
    assert not gm.supported(256, 128, 200)      # n: no whole lanes
    assert not gm.supported(200, 128, 128)      # rows: no whole tile
    assert not gm.supported(8192, 8192, 28672)  # a contraction VMEM cannot hold


@pytest.fixture
def interpreted(monkeypatch):
    """``experts`` takes the kernel path (``interpret`` false) with the
    kernels interpreted: the test steers, the program has no option."""
    for name in ("gmm", "tgmm"):
        monkeypatch.setattr(gm, name, functools.partial(
            getattr(gm, name), interpret=True))
    monkeypatch.setattr(row_scatter, "row_scatter_add", functools.partial(
        row_scatter.row_scatter_add, interpret=True))


def _ffn_operands(ffn, sizes, m, d, f, seed=1):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, F32)
    g = len(sizes)
    mats = ((normal(g, d, f), normal(g, d, f), normal(g, f, d))
            if ffn is experts.grouped_expert_ffn
            else (normal(g, d, f), normal(g, f, d)))
    return normal(m, d), mats, jnp.asarray(sizes, jnp.int32)


def _relu2_value(*args):
    return experts.grouped_relu2_ffn_vjp(*args)[0]


@pytest.mark.parametrize("ffn", [experts.grouped_expert_ffn, _relu2_value],
                         ids=["grouped_expert_ffn", "grouped_relu2_ffn"])
def test_gradients_through_the_experts_ffn(ffn, interpreted):
    """``jax.grad`` through the SwiGLU and the relu2 experts on the
    kernel against the same on ``lax.ragged_dot``: values and the
    gradients of the rows and of every matrix, an empty expert's zeros."""
    sizes, m, d, f = (70, 0, 130, 30), 256, 128, 256
    xs, mats, sz = _ffn_operands(ffn, sizes, m, d, f)
    live = jnp.arange(m)[:, None] < sum(sizes)

    def loss(interpret, xs, *mats):
        y = ffn(jnp.where(live, xs, 0), *mats, sz, BF16, interpret)
        return jnp.sum(jnp.where(live, y, 0) ** 2)

    args = tuple(range(1 + len(mats)))
    got, want = (jax.jit(jax.value_and_grad(functools.partial(
        loss, interpret), args))(xs, *mats) for interpret in (False, True))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        # ragged_dot's transposes round their results to bfloat16
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=scale * 2.0 ** -7)
    for g in got[1][1:]:
        assert not np.asarray(g)[1].any()


@pytest.mark.parametrize("form", ["gated", "relu2"])
@pytest.mark.parametrize("held", ["some_slots", "no_slot"])
def test_local_expert_ffn_whole_on_the_kernel(held, form, interpreted):
    """``local_expert_ffn``'s result and gradients compared whole, for
    both expert forms: its chunks hold rows past the last held slot,
    which the kernel leaves unwritten and the function's masks cut off
    on both sides, and the held slots are several chunks, so the
    matrices' gradients are summed by the kernel over several trips."""
    import types

    t, d, f, k, e, here = 64, 128, 128, 2, 8, 2
    ffn, n_mats = FORMS[form]
    cfg = types.SimpleNamespace(num_experts_per_tok=k, num_experts=e,
                                compute_dtype=BF16)
    rng = np.random.default_rng(2)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, F32)
    h, weights = normal(t, d), jnp.abs(normal(t, k))
    mats = tuple(normal(here, d, f) for _ in range(n_mats - 1)) + (
        normal(here, f, d),)
    # a token's two experts are distinct, as ``lax.top_k``'s are (the
    # row kernel counts on it: ``ops/row_scatter``)
    if held == "some_slots":
        first = rng.integers(0, here, t)
        chosen = np.stack([first, (first + rng.integers(1, e, t)) % e], 1)
    else:
        chosen = np.stack([rng.integers(here, e - 1, t),
                           np.full(t, e - 1)], 1)
    order, sizes = experts.local_dispatch(jnp.asarray(chosen), 0, here)
    if held == "some_slots":
        assert int(sizes.sum()) > 2 * experts.chunk_rows(t, k, here, e)

    def loss(interpret, h, weights, *mats):
        return jnp.sum(experts.local_expert_ffn(
            h, order, weights, sizes, mats, cfg, ffn, interpret) ** 2)

    args = tuple(range(2 + n_mats))
    got = jax.jit(jax.value_and_grad(functools.partial(loss, False), args))(
        h, weights, *mats)
    want = jax.jit(jax.value_and_grad(functools.partial(loss, True), args))(
        h, weights, *mats)
    assert np.isfinite(got[0])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert np.isfinite(np.asarray(g)).all()
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=scale * 2.0 ** -6)


#: the loop's shapes in the cases below: 64 tokens of 2 slots, 2 of 8
#: experts held
LOOP = dict(t=64, k=2, e=8, here=2, d=128, f=128)
FORMS = {"gated": (experts.grouped_expert_ffn_vjp, 3),
         "relu2": (experts.grouped_relu2_ffn_vjp, 2)}


def _loop_rows():
    return experts.chunk_rows(LOOP["t"], LOOP["k"], LOOP["here"], LOOP["e"])


def _chosen(held: str):
    """(T, k) experts with the held slots the case names: the first so
    many slots go to the held experts in turn, the others to absent
    ones; ``hot``: every token's first choice is held expert 1."""
    t, k, e, here = (LOOP[x] for x in ("t", "k", "e", "here"))
    rows = _loop_rows()
    flat = here + np.arange(t * k) % (e - here)
    if held == "hot":
        chosen = flat.reshape(t, k)
        chosen[:, 0] = 1
        chosen[:3, 1] = 0
        return chosen, t + 3
    n = {"none": 0, "under_one_chunk": rows - 3, "two_chunks": 2 * rows,
         "two_chunks_and_a_row": 2 * rows + 1}[held]
    flat[:n] = np.arange(n) % here
    return flat.reshape(t, k), n


def _dense(form, h, weights, chosen, *mats):
    """The held experts' part by masks: every held expert on every row,
    weighted by what the row sent it."""
    out = 0.0
    for e in range(LOOP["here"]):
        if form == "gated":
            gate, up, down = (m[e] for m in mats)
            y = jnp.dot(jax.nn.silu(jnp.dot(h, gate, precision=HIGHEST))
                        * jnp.dot(h, up, precision=HIGHEST), down,
                        precision=HIGHEST)
        else:
            up, down = (m[e] for m in mats)
            y = jnp.dot(jnp.square(jax.nn.relu(
                jnp.dot(h, up, precision=HIGHEST))), down, precision=HIGHEST)
        out = out + y * jnp.sum(weights * (chosen == e), axis=1)[:, None]
    return out


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("held", ["none", "under_one_chunk", "two_chunks",
                                  "two_chunks_and_a_row", "hot"])
def test_the_loop_is_the_dense_masked_sum(held, form):
    """``local_expert_ffn``'s value and every gradient (the rows', the
    weights' and each matrix's) in float32 against every held expert
    run on every row under a mask, for both expert forms: at no held
    slot (no trip), under one chunk, at whole chunks, at whole chunks and
    one row, and with one hot expert whose group is longer than the
    batch is wide, which no chunk holds."""
    import types

    t, k, e, here, d, f = (LOOP[x] for x in "t k e here d f".split())
    ffn, n_mats = FORMS[form]
    cfg = types.SimpleNamespace(num_experts_per_tok=k, num_experts=e,
                                compute_dtype=F32)
    rng = np.random.default_rng(4)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, F32)
    h, weights = normal(t, d), jnp.abs(normal(t, k))
    mats = (normal(here, d, f),) * (n_mats - 1) + (normal(here, f, d),)
    mats = tuple(m + 0.01 * i for i, m in enumerate(mats))
    chosen, n_held = _chosen(held)
    chosen = jnp.asarray(chosen)
    order, sizes = experts.local_dispatch(chosen, 0, here)
    assert int(sizes.sum()) == n_held
    if held == "hot":
        assert int(sizes.max()) == t > 2 * _loop_rows()

    def loop(h, weights, *mats):
        return jnp.sum(jnp.sin(experts.local_expert_ffn(
            h, order, weights, sizes, mats, cfg, ffn)))

    def dense(h, weights, *mats):
        return jnp.sum(jnp.sin(_dense(form, h, weights, chosen, *mats)))

    args = tuple(range(2 + n_mats))
    got = jax.jit(jax.value_and_grad(loop, args))(h, weights, *mats)
    want = jax.jit(jax.value_and_grad(dense, args))(h, weights, *mats)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=scale * 1e-5)
    if held == "none":
        assert not any(np.asarray(g).any() for g in got[1])


def _model_configs():
    import glob
    import os

    from ompi_tpu.parallel.config import load_model_config

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a model without a router (PR 67's) holds no expert and has no loop
    return [path for path in sorted(glob.glob(os.path.join(
        here, "benchmark", "configs", "*-train-1chip.json")))
        if load_model_config(path).num_experts]


@pytest.mark.parametrize("path", _model_configs(),
                         ids=lambda p: p.rsplit("/", 1)[1][:-5])
def test_a_chunk_is_whole_row_tiles_at_every_cells_shapes(path):
    """``chunk_rows`` at a configuration's shapes is whole row tiles (or
    whole sublane tiles of 16 under one), so every trip's three products
    have tiles and take the kernel; it comes from the shapes alone."""
    from ompi_tpu.parallel import train

    cfg = train.load_model_config(path)
    t, d = cfg.micro_batch * cfg.seq_len, cfg.moe_latent_size or \
        cfg.hidden_size
    rows = experts.chunk_rows(t, cfg.num_experts_per_tok, cfg.n_experts_here,
                              cfg.num_experts)
    assert rows % (gm.ROW_TILE if rows >= gm.ROW_TILE else 16) == 0
    assert gm.supported(rows, d, cfg.expert_width)
    assert rows <= experts.CHUNK_TILES * gm.ROW_TILE <= t
    for tokens in (16, 64, 256, 1000, 4096):    # and at a test's shapes
        small = experts.chunk_rows(tokens, 2, 2, 8)
        assert small % 16 == 0 and small <= max(16, tokens // 2)


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


@pytest.mark.parametrize("dtype,interpret,on_kernel", [
    (BF16, True, False),       # the CPU's choice
    (F32, True, False),
    (F32, False, False),       # float32 anywhere
    (BF16, False, True)])      # where Mosaic compiles
def test_which_grouped_matmul_is_built_and_counted(dtype, interpret,
                                                   on_kernel):
    """On the CPU, and at ``compute_dtype`` float32 anywhere, the built
    program holds ``ragged_dot_general`` and no ``pallas_call``; bfloat16
    where Mosaic compiles holds the kernel and no ``ragged_dot``.  The
    decision function says which and why not; tracing moves neither SPC
    counter."""
    spc.init()
    xs, mats, sz = _ffn_operands(experts.grouped_expert_ffn,
                                 (70, 0, 130, 30), 256, 128, 256)
    before = (spc.read("moe_gmm_built"), spc.read("moe_gmm_kernel_built"))
    jaxpr = jax.make_jaxpr(jax.grad(lambda xs, *mats: jnp.sum(
        experts.grouped_expert_ffn(xs, *mats, sz, dtype, interpret)),
        (0, 1, 2, 3)))(xs, *mats)
    names = _primitives(jaxpr.jaxpr)
    assert ("pallas_call" in names) == on_kernel
    assert ("ragged_dot_general" in names) == (not on_kernel)
    assert (spc.read("moe_gmm_built"),
            spc.read("moe_gmm_kernel_built")) == before
    for a, w in ((xs, mats[0]), (xs, mats[1]), (xs @ mats[0][0], mats[2])):
        on, why = experts.gmm_on_kernel(interpret, dtype, *a.shape,
                                        w.shape[2])
        assert on == on_kernel and bool(why) == (not on)
        if not on:
            assert why.startswith("interpret" if interpret
                                  else "compute_dtype float32")
