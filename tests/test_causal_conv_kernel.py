"""The Pallas kernels of the DeltaNet convolution (``ops/causal_conv``)
in interpret mode against the XLA lines of ``parallel/model
.gated_delta_net`` and their autodiff, and which of the two
``gated_delta_net`` builds where."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import causal_conv as cc
from ompi_tpu.ops import gated_delta as gd
from ompi_tpu.parallel import gdn, train
from ompi_tpu.runtime import spc

#: tiles of 16 positions by 128 channels: 27, 40 and 200 positions end
#: inside a tile, 128 are eight whole tiles; 256 channels are two blocks
ROWS, LANES, CHANNELS = 16, 128, 256
LENGTHS = [27, 40, 128, 200]
CONFIG = "benchmark/configs/qwen3-next-80b-a3b-train-1chip.json"


def xla_form(x, w):
    """The convolution as ``gated_delta_net`` writes it for XLA."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + s] * w[j] for j in range(taps)))


def conv_inputs(seed, s, b=1, c=CHANNELS, taps=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, c)),
            0.5 * jax.random.normal(ks[1], (taps, c)),
            jax.random.normal(ks[2], (b, s, c)))


def near(got, want, rel, what=""):
    """Within ``rel`` of the largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=0, err_msg=what,
                               atol=rel * max(1e-30, np.abs(want).max()))


forward = functools.partial(cc.conv_forward, rows=ROWS, lanes=LANES,
                            interpret=True)
backward = functools.partial(cc.conv_backward, rows=ROWS, lanes=LANES,
                             interpret=True)


@pytest.mark.parametrize("taps", [2, 4, 9])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_forward_kernel_is_the_xla_form(length, taps):
    """At lengths that are whole row tiles and that end inside one, with
    two, four and the most taps a sublane tile carries."""
    x, w, _ = conv_inputs(length + taps, length, taps=taps)
    got = forward(x, w)
    assert got.dtype == jnp.float32
    near(got, xla_form(x, w), 1e-6)


@pytest.mark.parametrize("taps", [2, 4, 9])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_backward_kernel_is_autodiff_of_the_xla_form(length, taps):
    """dx and the taps' dw from x, w and the cotangent alone."""
    x, w, dy = conv_inputs(3 * length + taps, length, taps=taps)
    want = jax.vjp(xla_form, x, w)[1](dy)
    got = backward(x, w, dy)
    for name, a, b in zip(("dx", "dw"), got, want):
        near(a, b, 2e-6, name)


@pytest.mark.parametrize("rows,lanes", [(None, None), (64, 256), (8, 128)])
def test_the_default_tiles_and_others_give_the_same(rows, lanes):
    """The module's own choice of tile (all of a short length, the
    widest block that divides the channels), pieces of 64 rows, and the
    smallest tile there is."""
    x, w, dy = conv_inputs(7, 200, c=768)
    how = dict(rows=rows, lanes=lanes, interpret=True)
    near(cc.conv_forward(x, w, **how), xla_form(x, w), 1e-6)
    for name, a, b in zip(("dx", "dw"), cc.conv_backward(x, w, dy, **how),
                          jax.vjp(xla_form, x, w)[1](dy)):
        near(a, b, 2e-6, name)


def test_a_batch_of_two_is_never_mixed_and_dw_sums_over_it():
    """A row of the batch reads nothing of the other (the carry starts
    from zeros at each row's first tile, forward and backward), and dw is
    the sum of the rows' own."""
    x, w, dy = conv_inputs(5, 40, b=2)
    both = forward(x, w)
    for z in (0, 1):
        near(both[z:z + 1], forward(x[z:z + 1], w), 0, f"row {z}")
    dx, dw = backward(x, w, dy)
    alone = [backward(x[z:z + 1], w, dy[z:z + 1]) for z in (0, 1)]
    for z in (0, 1):
        near(dx[z:z + 1], alone[z][0], 0, f"dx of row {z}")
    near(dw, alone[0][1] + alone[1][1], 1e-6, "dw")
    near(dw, jax.vjp(xla_form, x, w)[1](dy)[1], 2e-6, "dw by autodiff")


def test_zeros_lie_before_the_start():
    """The first position reads its own tap alone, the second two."""
    x, w, _ = conv_inputs(2, 40)
    y = forward(x, w)
    near(y[:, 0], jax.nn.silu(x[:, 0] * w[3]), 1e-6)
    near(y[:, 1], jax.nn.silu(x[:, 0] * w[2] + x[:, 1] * w[3]), 1e-6)


@pytest.mark.parametrize("edge", [ROWS, 2 * ROWS, 4 * ROWS])
def test_the_carry_crosses_tiles_and_grid_steps(edge):
    """What a tile's last row holds is read by the next tile's first
    three, and their cotangent comes back to it: forward the carry goes
    to the next grid step, backward to the one before."""
    x, w, dy = conv_inputs(edge, 6 * ROWS)
    other = x.at[:, edge - 1].add(3.0)
    moved = forward(other, w) - forward(x, w)
    want = xla_form(other, w) - xla_form(x, w)
    assert float(jnp.min(jnp.max(jnp.abs(want[:, edge:edge + 3]), -1))) > 1e-3
    near(moved, want, 1e-6)
    assert float(jnp.max(jnp.abs(moved[:, edge + 3:]))) == 0.0
    # only the next tile's first row has a cotangent
    only = jnp.zeros_like(dy).at[:, edge].set(dy[:, edge])
    dx, dw = backward(x, w, only)
    wdx, wdw = jax.vjp(xla_form, x, w)[1](only)
    assert float(jnp.max(jnp.abs(wdx[:, edge - 1]))) > 1e-3
    near(dx, wdx, 2e-6, "dx")
    near(dw, wdw, 2e-6, "dw")
    assert float(jnp.max(jnp.abs(dx[:, :edge - 3]))) == 0.0


@pytest.mark.parametrize("taps,c,s,has", [
    (4, 8192, 16384, True),        # the cell's
    (4, 128, 1, True),
    (9, 256, 27, True),            # a sublane tile of rows carried
    (10, 256, 27, False),
    (0, 256, 27, False),
    (4, 64, 16384, False),         # no tile of lanes
    (4, 192, 16384, False),
    (4, 256, 0, False)])
def test_which_shapes_have_tiles(taps, c, s, has):
    assert cc.supported(taps, c, s) == has


def test_the_tiles_of_the_cells_shape():
    assert cc.row_tile(16384) == cc.ROWS and 16384 % cc.ROWS == 0
    assert cc.lane_block(8192) == cc.BLOCK_LANES
    assert (cc.row_tile(27), cc.lane_block(384)) == (32, 384)
    assert cc.lane_block(1152) == 384


def operator(**widths):
    """(cfg, one DeltaNet layer's parameters, x) at small widths."""
    cfg = train.load_model_config(
        CONFIG, hidden_size=64, seq_len=16, micro_batch=1, chunk_size=8,
        compute_dtype="float32", **widths)
    one = dataclasses.replace(cfg, layers_here=1, first_layer_here=0)
    (group,) = train.init_model_params(one, 0)["layers"].values()
    p = jax.tree.map(lambda a: a[0], group["gdn_moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64), jnp.float32)
    return cfg, p, x


#: head counts and widths of [q | k | v]: 512 channels and heads of a
#: tile's lanes, 512 channels and heads of half a tile, 96 channels
TILES = dict(linear_num_key_heads=1, linear_num_value_heads=2)
CONV_ONLY = dict(linear_num_key_heads=2, linear_num_value_heads=4,
                 linear_key_head_dim=64, linear_value_head_dim=64)
NO_TILES = dict(linear_num_key_heads=1, linear_num_value_heads=2,
                linear_key_head_dim=16, linear_value_head_dim=32)


@pytest.mark.parametrize("widths,interpret,conv_on,rule_on", [
    (TILES, True, False, False),           # the CPU's choice
    (NO_TILES, True, False, False),
    (NO_TILES, False, False, False),       # no tile of lanes, anywhere
    (CONV_ONLY, False, True, False),       # the convolution alone
    (TILES, False, True, True)],           # where Mosaic compiles
    ids=["tiles-cpu", "no-tiles-cpu", "no-tiles-tpu", "conv-only-tpu",
         "tiles-tpu"])
def test_which_convolution_is_built_and_counted(widths, interpret, conv_on,
                                                rule_on):
    """``gated_delta_net`` hands ``interpret`` down: on the CPU, and at a
    width that is no whole tiles anywhere, the layer's program holds
    XLA's lines; where Mosaic compiles and the channels are whole tiles
    it holds the convolution's kernels, beside the rule's where the heads
    are a tile wide too.  The layer's plan says what was built and, where
    a kernel is refused, the clause; tracing moves neither SPC counter."""
    spc.init()
    cfg, p, x = operator(**widths)
    before = (spc.read("gdn_conv_built"), spc.read("gdn_conv_kernel_built"))
    layer = functools.partial(gdn.gated_delta_net, cfg=cfg,
                              interpret=interpret)
    fwd = jax.make_jaxpr(layer)(p, x)
    both = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(layer(p, x)[0]),
                                   (0, 1)))(p, x)
    kernels = lambda jaxpr: sorted(
        eqn.params["name"] for eqn in _equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")
    conv = lambda names: [n for n in names if "conv" in n]
    assert conv(kernels(fwd)) == ["otpu_gdn_conv_fwd"] * conv_on
    # a backward pass with the rule's kernels makes [q | k | v] again
    assert conv(kernels(both)) == ["otpu_gdn_conv_bwd"] * conv_on \
        + ["otpu_gdn_conv_fwd"] * (conv_on + rule_on)
    assert ("otpu_gdn_rule_bwd" in kernels(both)) == rule_on
    assert bool(kernels(both)) == conv_on
    assert (spc.read("gdn_conv_built"),
            spc.read("gdn_conv_kernel_built")) == before
    held = gdn.GDN.plan(cfg, *x.shape[:2], interpret)
    parts, counts = held["parts"], held["counts"]
    assert (parts["conv"]["impl"], parts["rule"]["impl"]) == (
        "kernel" if conv_on else "xla", "kernel" if rule_on else "xla")
    assert counts == {
        "gdn_conv_built": 1, "gdn_rule_built": 1,
        **({"gdn_conv_kernel_built": 1} if conv_on else {}),
        **({"gdn_rule_kernel_built": 1} if rule_on else {})}
    assert held["impl"] == ("kernel" if conv_on and rule_on else "xla")
    if not conv_on:
        assert parts["conv"]["why"] == (
            "interpret: Mosaic does not compile here" if interpret
            else "96 channels are no whole lane tiles of 128")


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("widths", [TILES, CONV_ONLY],
                         ids=["with-the-rule", "conv-only"])
def test_the_operator_on_the_kernels_is_the_operator(widths, monkeypatch):
    """``gated_delta_net`` on the kernels (interpreted here, which takes
    the kernels' callers being told so) gives the XLA form's output, the
    same ``seen`` cut from the same [q | k | v], and the same gradient of
    every parameter and of x: through ``_kernel_conv`` alone where only
    the channels have tiles, through ``_kernel_conv_rule``, whose
    backward rule makes [q | k | v] again, where the heads have too."""
    for module, names in ((cc, ("conv_forward", "conv_backward")),
                          (gd, ("rule_forward", "rule_backward"))):
        for name in names:
            monkeypatch.setattr(module, name, functools.partial(
                getattr(module, name), interpret=True))
    # two chunks of 8 a grid step of the rule's, not 32: all 16 positions
    monkeypatch.setattr(gd, "STEP_ROWS", 16)
    cfg, p, x = operator(**widths)
    weight = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def loss(p, x, interpret):
        y, _, seen = gdn.gated_delta_net(p, x, cfg, interpret=interpret)
        return jnp.sum(y * weight), (y, seen)

    (_, (y, seen)), grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(p, x, False)
    (_, (want, want_seen)), want_grads = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(p, x, True)
    near(y, want, 1e-5, "y")
    assert sorted(seen) == sorted(want_seen)
    for name in seen:
        near(seen[name], want_seen[name], 1e-5, name)
    for name in p:
        near(grads[0][name], want_grads[0][name], 2e-3, name)
    near(grads[1], want_grads[1], 2e-4, "dx")
