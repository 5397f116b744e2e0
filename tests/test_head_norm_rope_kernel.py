"""The Pallas kernels of a head's way to the flash kernels, with a per-head
QK-norm and without (``ops/head_norm_rope``), in interpret mode against
the ``jnp`` lines of ``parallel/attention.normed_turned_heads`` and their
autodiff, and which of the two ``attention.normed_qk`` builds where."""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import head_norm_rope as hnr
from ompi_tpu.parallel import attention, train
from ompi_tpu.parallel.layers import rmsnorm_gain, rope, rope_tables
from ompi_tpu.runtime import spc

#: tiles of 16 positions: 24 and 40 end inside a tile, 32 is two whole
ROWS, HD, EPS, THETA = 16, 128, 1e-6, 1e6
#: (query or key-value heads, positions, head width): SDAR's and Keye's 32
#: on 4, one head, lengths a row tile does not divide, and a head of two
#: lane tiles
SHAPES = [(32, 24, HD), (4, 24, HD), (1, 40, HD), (4, 32, HD), (2, 24, 256)]
#: (heads, positions, head width, batch, whether the head has a gain): the
#: normed shapes, and a head that is turned and not normed: Ouro's 16 on
#: 16 over two sequences, SmallThinker's 28 on 4, one head (what a check
#: reads), whole tiles and lengths that end inside one
CASES = [(*shape, 1, True) for shape in SHAPES] + [
    (16, 32, HD, 2, False), (28, 24, HD, 1, False), (4, 40, HD, 1, False),
    (1, 40, HD, 1, False)]
SDAR = "benchmark/configs/sdar-30b-a3b-train-1chip.json"
KEYE = "benchmark/configs/keye-vl2-30b-a3b-train-1chip.json"
LFM2 = "benchmark/configs/lfm2-8b-a1b-train-1chip.json"
QWEN3NEXT = "benchmark/configs/qwen3-next-80b-a3b-train-1chip.json"
OURO = "benchmark/configs/ouro-2.6b-train-1chip.json"
SMALLTHINKER = "benchmark/configs/smallthinker-21b-a3b-train-1chip.json"

forward = functools.partial(hnr.heads_forward, eps=EPS, interpret=True)
backward = functools.partial(hnr.heads_backward, eps=EPS, interpret=True)


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """The module's tile, which follows the length alone, at ``ROWS``
    positions."""
    monkeypatch.setattr(hnr, "ROWS", ROWS)


def twin(x, gain, heads, dtype, positions=None):
    """The lines the kernels replace: the transposed heads, the norm, RoPE,
    the cast; of a head without a gain ``layers.rope`` between the split
    and the cast."""
    b, s, _ = x.shape
    cfg = types.SimpleNamespace(rms_norm_eps=EPS, rope_theta=THETA,
                                rotary_width=None)
    t = x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
    if gain is None:
        return rope(t, THETA, None, positions).astype(dtype)
    return attention.normed_turned_heads(t, gain, cfg, True,
                                         positions).astype(dtype)


def inputs(seed, heads, s, b=1, hd=HD, positions=None, normed=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    cos, sin = rope_tables(s, hd, THETA, positions)
    return (jax.random.normal(ks[0], (b, s, heads * hd)),
            1 + 0.2 * jax.random.normal(ks[1], (hd,)) if normed else None,
            cos, hnr.signed_sin(sin),
            jax.random.normal(ks[2], (b, heads, s, hd)))


def near(got, want, rel, what=""):
    """Within ``rel`` of the largest entry."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=0, err_msg=what,
                               atol=rel * max(1e-30, np.abs(want).max()))


@pytest.mark.parametrize("dtype,rel", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 8e-3)])
@pytest.mark.parametrize("heads,s,hd,b,normed", CASES)
def test_the_forward_kernel_is_the_twin(heads, s, hd, b, normed, dtype, rel):
    """The product read where it lies, (b, s, heads x hd), leaves as (b,
    heads, s, hd) in the operand's dtype, at lengths that are whole row
    tiles and that end inside one, under the norm and without one."""
    x, gain, cos, sin, _ = inputs(heads + s, heads, s, b, hd, normed=normed)
    got = forward(x, gain, cos, sin, heads=heads, dtype=dtype)
    assert got.dtype == dtype and got.shape == (b, heads, s, hd)
    near(got, twin(x, gain, heads, dtype), rel)


@pytest.mark.parametrize("dtype,rel", [(jnp.float32, 4e-6),
                                       (jnp.bfloat16, 8e-3)])
@pytest.mark.parametrize("heads,s,hd,b,normed", CASES)
def test_the_backward_kernel_is_autodiff_of_the_twin(heads, s, hd, b,
                                                     normed, dtype, rel):
    """The product's cotangent where the projection's transposes read it
    and the gain's gradient summed over rows and heads in float32, from
    the product, the gain and the cotangent alone; the cotangent comes in
    the operand's dtype and the product's leaves in it.  Of a head without
    a gain the cotangent and the tables are all that is read: no product
    is handed in."""
    x, gain, cos, sin, do = inputs(3 * heads + s, heads, s, b, hd,
                                   normed=normed)
    do = do.astype(dtype)
    want = jax.vjp(lambda x, g: twin(x, g, heads, dtype), x, gain)[1](do)
    dx, dg = backward(x if normed else None, gain, cos, sin, do, dtype=dtype)
    assert dx.dtype == dtype
    near(dx, want[0], rel, "dx")
    if not normed:
        assert dg is None
        return
    assert dg.dtype == jnp.float32
    near(dg, want[1], 1e-5 if dtype == jnp.float32 else rel, "dgain")


def test_both_halves_of_a_diffused_row_turn_at_its_position():
    """Under block diffusion the rows are a noisy copy of a sequence before
    its clean copy, both at positions 0 .. s / 2 - 1: the tables carry the
    positions, forward and through all three gradients' way back."""
    heads, s = 4, 48
    positions = jnp.tile(jnp.arange(s // 2), 2)
    x, gain, cos, sin, do = inputs(11, heads, s, positions=positions)
    want_fn = lambda x, g: twin(x, g, heads, jnp.float32, positions)
    got = forward(x, gain, cos, sin, heads=heads, dtype=jnp.float32)
    near(got, want_fn(x, gain), 2e-6)
    # a clean row's head is its noisy row's where the products are equal
    both = forward(jnp.tile(x[:, :s // 2], (1, 2, 1)), gain, cos, sin,
                   heads=heads, dtype=jnp.float32)
    near(both[:, :, s // 2:], both[:, :, :s // 2], 0)
    assert np.abs(np.asarray(got - twin(x, gain, heads, jnp.float32))
                  ).max() > 0.1
    dx, dg = backward(x, gain, cos, sin, do)
    want = jax.vjp(want_fn, x, gain)[1](do)
    near(dx, want[0], 4e-6, "dx")
    near(dg, want[1], 1e-5, "dgain")


@pytest.mark.parametrize("rows,sub", [(2048, 512), (32, 16), (64, 32)])
def test_the_modules_tile_and_others_give_the_same(rows, sub, monkeypatch):
    """The module's own tile (here all of a short length in whole sublane
    tiles, one piece), and tiles of 32 and 64 positions in pieces of 16
    and 32."""
    monkeypatch.setattr(hnr, "ROWS", rows)
    monkeypatch.setattr(hnr, "SUB_ROWS", sub)
    heads, s = 2, 72
    x, gain, cos, sin, do = inputs(5, heads, s, b=2)
    near(forward(x, gain, cos, sin, heads=heads, dtype=jnp.float32)[1:],
         twin(x[1:], gain, heads, jnp.float32), 2e-6)
    want = jax.vjp(lambda x, g: twin(x, g, heads, jnp.float32), x, gain)[1](do)
    got = backward(x, gain, cos, sin, do)
    for name, a, b in zip(("dx", "dgain"), got, want):
        near(a, b, 1e-5, name)


def test_the_tiles_are_what_the_module_says(monkeypatch):
    """Heads in whole tiles of 128 lanes, turned whole, no gate; 2,048
    positions a grid step, or all of a shorter length."""
    assert hnr.supported(128, None, False) and hnr.supported(128, 128, False)
    assert hnr.supported(256, None, False)
    assert not hnr.supported(64, None, False)       # lfm2: two heads a tile
    assert not hnr.supported(256, 64, False)        # qwen3_next: a quarter
    assert not hnr.supported(256, None, True)       # and a gate behind it
    monkeypatch.setattr(hnr, "ROWS", 2048)           # ``small_tiles``' back
    assert (hnr.row_tile(16384), hnr.row_tile(24)) == (2048, 32)


def layer(config, seed=0, **widths):
    """(cfg, a sublayer's projections and, where its model has a QK-norm,
    gains, its normed input) at small widths."""
    cfg = train.load_model_config(
        config, hidden_size=64, seq_len=32, micro_batch=1, attn_block=16,
        loss_block_rows=16, vocab_size=256, vocab_here=64,
        compute_dtype="float32", **widths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {name: (1 + 0.2 * jax.random.normal(k, shape)
                if name.endswith("norm") else
                0.2 * jax.random.normal(k, shape))
         for k, (name, shape) in zip(ks, attention.gqa_shapes(cfg).items())}
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 32, 64))
    return cfg, p, h


def old_lines(p, h, cfg, turned=True, positions=None):
    """q, k, the gate and what is seen as ``gqa_attention`` and
    ``dsa_attention`` each wrote them until PR 65, and as
    ``gqa_attention``'s branch for a ``layer_types`` model without a
    QK-norm wrote them until PR 68 (the same lines less the norm)."""
    b, s, _ = h.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    mm, gate = attention.matmul, None
    split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    turn = (lambda t: rope(t, cfg.rope_theta, cfg.rotary_width, positions)) \
        if turned else (lambda t: t)
    first = lambda a, c: jnp.concatenate(
        [a[:, 0], c[:, 0]], -1).reshape(b * s, -1)
    q_in, k_in = (split(mm(h, p[w], dt), n)
                  for w, n in (("wq", nh), ("wk", nkv)))
    if p["wq"].shape[-1] == 2 * p["wo"].shape[0]:
        q_in, gate = jnp.split(q_in, 2, axis=-1)
    norm = (lambda t, g: rmsnorm_gain(t, p[g], cfg.rms_norm_eps)) \
        if "q_norm" in p else (lambda t, g: t)
    q, k = (turn(norm(t, g))
            for t, g in ((q_in, "q_norm"), (k_in, "k_norm")))
    seen = {"attn_qk_in": first(q_in, k_in), "attn_qk": first(q, k)}
    return q.astype(dt), k.astype(dt), gate, seen


SMALL = dict(head_dim=16, num_attention_heads=4, num_key_value_heads=2)
TILED = dict(head_dim=128, num_attention_heads=2, num_key_value_heads=1)
#: (configuration file, widths, the step's interpret, whether RoPE turns
#: the layer, on the kernels)
WHERE = [
    (SDAR, dict(TILED, mask_token_here=63), True, True, False),  # the CPU's
    (SDAR, dict(SMALL, mask_token_here=63), False, True, False),  # no tile
    (LFM2, dict(head_dim=64, num_attention_heads=4,
                num_key_value_heads=2), False, True, False),  # two a tile
    (QWEN3NEXT, dict(head_dim=256, num_attention_heads=2,
                     num_key_value_heads=1), False, True, False),  # gated
    (SDAR, dict(TILED, mask_token_here=63), False, True, True),
    (KEYE, TILED, False, True, True),
    # a head that is turned and not normed: Ouro's every layer,
    # SmallThinker's window layers; its full layer is not turned
    (OURO, TILED, True, True, False),
    (OURO, SMALL, False, True, False),
    (SMALLTHINKER, TILED, True, True, False),
    (SMALLTHINKER, TILED, True, False, False),
    (SMALLTHINKER, TILED, False, False, False),
    (OURO, TILED, False, True, True),
    (SMALLTHINKER, TILED, False, True, True)]
IDS = ["tiles-cpu", "no-tiles-tpu", "lfm2-64-tpu", "qwen3next-gated-tpu",
       "sdar-tpu", "keye-tpu", "ouro-cpu", "ouro-no-tiles-tpu",
       "smallthinker-window-cpu", "smallthinker-full-cpu",
       "smallthinker-full-tpu", "ouro-tpu", "smallthinker-window-tpu"]
#: the rows of ``WHERE`` that take the lines
LINES = [n for n, where in enumerate(WHERE) if not where[-1]]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("config,widths,interpret,turned,on", WHERE,
                         ids=IDS)
def test_which_way_is_built_and_counted(config, widths, interpret, turned,
                                        on):
    """``normed_qk`` takes ``interpret`` from the step: on the CPU, at a
    head that is no whole tile, at LFM2's heads of 64, at Qwen3-Next's
    gated, quarter-turned head of 256 and in a layer RoPE does not turn
    (SmallThinker's full layer) the program holds the ``jnp`` lines; where
    Mosaic compiles and a head of 128 is turned whole it holds one kernel
    for q and one for k each way, the normed pair where the layer holds
    gains (SDAR, Keye) and the pair without a norm where it holds none
    (Ouro, SmallThinker's window layers).  The decision function and the
    plan's counts say what was built; tracing moves neither SPC
    counter."""
    spc.init()
    cfg, p, h = layer(config, **widths)
    before = (spc.read("attn_qk_built"), spc.read("attn_qk_kernel_built"))
    way = functools.partial(attention.normed_qk, cfg=cfg,
                            interpret=interpret, turned=turned)
    fwd = jax.make_jaxpr(way)(p, h)
    both = jax.make_jaxpr(jax.grad(
        lambda p, h: sum(jnp.sum(a) for a in way(p, h)[:2]), (0, 1)))(p, h)
    kernels = lambda jaxpr: sorted(
        eqn.params["name"] for eqn in _equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")
    name = "otpu_head_norm_rope" if "q_norm" in p else "otpu_head_rope"
    # forward q's, k's and, for ``attn_qk``, the first head's of each
    assert kernels(fwd) == [name + "_fwd"] * (4 * on)
    assert kernels(both) == [name + "_bwd"] * (2 * on) \
        + [name + "_fwd"] * (4 * on)
    assert (spc.read("attn_qk_built"),
            spc.read("attn_qk_kernel_built")) == before
    # the gate the plan reads off the configuration is the one the
    # sublayer reads off ``wq``
    assert cfg.attn_output_gate \
        == (p["wq"].shape[-1] == 2 * p["wo"].shape[0])
    (taken, why), moved = attention.qk_plan(cfg, interpret, turned)
    assert taken == bool(on) and bool(why) == (not on)
    assert moved == {"attn_qk_built": 2, "attn_qk_kernel_built": 2 * on}


@pytest.mark.parametrize("config,widths,interpret,turned,on",
                         [WHERE[n] for n in LINES],
                         ids=[IDS[n] for n in LINES])
def test_every_other_shape_takes_the_lines_bit_for_bit(config, widths,
                                                       interpret, turned,
                                                       on):
    """q, k, the gate and what a check reads are what the sublayers' own
    lines made until PR 65, and until PR 68 of a model without a QK-norm,
    to the bit: on the CPU, where a TPU's step meets a shape the kernels
    have no tile for, and in a layer that is not turned."""
    cfg, p, h = layer(config, **widths)
    got = attention.normed_qk(p, h, cfg, interpret=interpret, turned=turned)
    want = old_lines(p, h, cfg, turned)
    assert (got[2] is None) == (want[2] is None) == (config != QWEN3NEXT)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if not turned:      # left alone: what a check reads of such a layer
        np.testing.assert_array_equal(
            np.asarray(got[3]["attn_qk"]), np.asarray(got[3]["attn_qk_in"]))


@pytest.mark.parametrize("where,diffused", [
    ("keye-tpu", False), ("sdar-tpu", True), ("ouro-tpu", False),
    ("smallthinker-window-tpu", False)],
    ids=["keye", "sdar-diffused", "ouro", "smallthinker-window"])
def test_q_and_k_on_the_kernels_are_q_and_k(where, diffused, monkeypatch):
    """``normed_qk`` on the kernels (interpreted here, which takes the
    kernels being told so) gives the lines' q and k, the same
    ``attn_qk_in`` / ``attn_qk`` of the first query and key-value head
    (their products made again and the forward kernel over them), and the
    same gradient of both projections, both gains where the layer holds
    them, and the input."""
    for name in ("heads_forward", "heads_backward"):
        monkeypatch.setattr(hnr, name, functools.partial(
            getattr(hnr, name), interpret=True))
    config, widths = WHERE[IDS.index(where)][:2]
    cfg, p, h = layer(config, **widths)
    positions = jnp.tile(jnp.arange(16), 2) if diffused else None
    weights = [jax.random.normal(jax.random.PRNGKey(n), (1, heads, 32, HD))
               for n, heads in ((3, 2), (4, 1))]

    def loss(p, h, interpret):
        q, k, gate, seen = attention.normed_qk(
            p, h, cfg, interpret=interpret, positions=positions)
        assert gate is None
        return sum(jnp.sum(a * w) for a, w in zip((q, k), weights)), (
            q, k, seen)

    (_, got), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        p, h, False)
    (_, want), want_grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        p, h, True)
    for name, a, b in zip(("q", "k"), got, want):
        near(a, b, 2e-6, name)
    assert sorted(got[2]) == sorted(want[2]) == ["attn_qk", "attn_qk_in"]
    for name in got[2]:
        near(got[2][name], want[2][name], 2e-6, name)
    assert ("q_norm" in p) == (where in ("keye-tpu", "sdar-tpu"))
    for name in sorted(set(p) & {"wq", "wk", "q_norm", "k_norm"}):
        near(grads[0][name], want_grads[0][name], 2e-5, name)
    near(grads[1], want_grads[1], 2e-5, "dh")


@pytest.mark.parametrize("config", [KEYE, OURO, SMALLTHINKER],
                         ids=["keye", "ouro", "smallthinker-window"])
def test_what_a_check_reads_is_the_kernels_own(config, monkeypatch):
    """``attn_qk`` on the kernels is the forward kernel's output, not the
    lines': a kernel that made something else of a head shows there, next
    to an ``attn_qk_in`` that stays the product."""
    real = functools.partial(hnr.heads_forward, interpret=True)
    cfg, p, h = layer(config, **TILED)
    seen = {}
    for name, fn in (("right", real),
                     ("wrong", lambda *a, **k: 2 * real(*a, **k))):
        monkeypatch.setattr(hnr, "heads_forward", fn)
        seen[name] = attention.normed_qk(p, h, cfg, interpret=False)[3]
    near(seen["wrong"]["attn_qk"], 2 * seen["right"]["attn_qk"], 0)
    near(seen["wrong"]["attn_qk_in"], seen["right"]["attn_qk_in"], 0)
