"""The Pallas kernels of the chunked gated delta rule (``ops/gated_delta``)
in interpret mode against ``parallel/gdn.gated_delta_chunked``'s XLA
form and the recurrence one position at a time, and which of the two
``gated_delta_chunked`` builds where."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import gated_delta as gd
from ompi_tpu.parallel import gdn, layers
from ompi_tpu.parallel import qwen3next_reference as ref
from ompi_tpu.parallel.sublayer import INTERPRET
from ompi_tpu.runtime import spc
from test_grouped_matmul import _primitives

#: chunks of 8 positions, two a grid step: 27 and 40 positions pad to 32
#: and 48, 128 and 256 are whole steps (8 and 16 of them)
CHUNK, GROUP = 8, 2
LENGTHS = [27, 40, 128, 256]


def rule_inputs(seed, s, r, bt=1, hk=2, dk=128, dv=128, alike=0.0):
    """q, k as the rule reads them (k's rows ``alike`` parts of one
    direction, as a convolution's outputs are), v, g <= 0 and beta in
    (0, 1), at a head width of one tile's lanes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    hv = hk * r
    q = layers.l2norm(jax.random.normal(ks[0], (bt, s, hk, dk))) * dk ** -0.5
    k = layers.l2norm(jax.random.normal(ks[1], (bt, s, hk, dk))
                      + alike * jax.random.normal(ks[5], (bt, 1, hk, dk)))
    v = jax.random.normal(ks[2], (bt, s, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (bt, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bt, s, hv)))
    return q, k, v, g, beta


def by_positions(q, k, v, g, beta):
    """The reference's recurrence, a key head read by its value heads."""
    per_value = lambda t: jnp.repeat(t, v.shape[2] // t.shape[2], axis=2)
    return ref.delta_rule(per_value(q), per_value(k), v, g, beta)


def near(got, want, rel, what=""):
    """Within ``rel`` of the largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0, err_msg=what,
                               atol=rel * max(1e-30, np.abs(want).max()))


def forward(q, k, v, g, beta, chunk=CHUNK, group=GROUP, **kw):
    """``rule_forward`` of the rule's own (bt, s, heads, 128) operands."""
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    out = gd.rule_forward(flat(q), flat(k), flat(v), g, beta, chunk=chunk,
                          hk=k.shape[2], group=group, interpret=True, **kw)
    o, kept = out if kw.get("states") else (out, None)
    return (o.reshape(v.shape), kept) if kw.get("states") \
        else o.reshape(v.shape)


def backward(q, k, v, g, beta, kept, do, chunk=CHUNK, group=GROUP):
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    dq, dk, dv, dg, dbeta = gd.rule_backward(
        flat(q), flat(k), flat(v), g, beta, kept, flat(do), chunk=chunk,
        hk=k.shape[2], group=group, interpret=True)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), \
        dg, dbeta


@pytest.mark.parametrize("r", [1, 2], ids=["one-value-head", "two-value-heads"])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_forward_kernel_is_the_xla_form_and_the_recurrence(length, r):
    """At lengths that pad to whole grid steps and that do not, with one
    and two value heads a key head."""
    args = rule_inputs(length + r, length, r, alike=1.0)
    got = forward(*args)
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    near(got, gdn.gated_delta_chunked(*args, CHUNK), 2e-6, "XLA form")
    with jax.default_matmul_precision("highest"):
        near(got, by_positions(*args), 2e-5, "recurrence")


@pytest.mark.parametrize("r", [1, 2], ids=["one-value-head", "two-value-heads"])
@pytest.mark.parametrize("length", LENGTHS)
def test_the_backward_kernel_is_autodiff_of_the_xla_form(length, r):
    """dq, dk, dv, dg and dbeta from what the forward kernel kept."""
    args = rule_inputs(3 * length + r, length, r, alike=1.0)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(
        gdn.gated_delta_chunked(*a, CHUNK) * weight), range(5)))(*args)
    o, kept = forward(*args, states=True)
    near(o, forward(*args), 0, "the output with and without what is kept")
    got = backward(*args, kept, weight)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.shape == b.shape, name
        near(a, b, 2e-5, name)


def test_a_batch_and_a_chunk_of_a_tile_go_through_the_same_maps():
    """Two rows of a batch, and chunks of 64 as the cell's are (one
    (128, 128) inverse for a key head's two value heads), against the
    XLA form in chunks of 8: the rule is the same whatever the chunk."""
    args = rule_inputs(4, 128, 2, bt=2, hk=1, alike=2.0)
    weight = jax.random.normal(jax.random.PRNGKey(5), args[2].shape)
    want, pull = jax.vjp(lambda *a: gdn.gated_delta_chunked(*a, 8), *args)
    o, kept = forward(*args, chunk=64, group=1, states=True)
    near(o, want, 2e-6)
    got = backward(*args, kept, weight, chunk=64, group=1)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, pull(weight)):
        near(a, b, 2e-5, name)


@pytest.mark.parametrize("length,r", [(40, 2), (128, 1)])
def test_the_kernels_norm_q_and_k_read_where_the_convolution_left_them(
        length, r):
    """One array [q | k | v] given three times with the heads' lane
    blocks, q and k as the convolution left them: the kernels put their
    rows at unit length and q's times the scale, and the backward kernel
    hands back the gradient of the one array."""
    hk, hv, w = 2, 2 * r, 128
    qkv = jax.random.normal(jax.random.PRNGKey(length), (1, length,
                                                         (2 * hk + hv) * w))
    _, _, _, g, beta = rule_inputs(length, length, r)
    weight = jax.random.normal(jax.random.PRNGKey(2), (1, length, hv * w))
    unit, at = (1e-6, w ** -0.5), (0, hk, 2 * hk // r)

    def plain(qkv, g, beta):
        heads = lambda t, n: t.reshape(1, length, n, w)
        q = layers.l2norm(heads(qkv[..., :hk * w], hk), unit[0]) * unit[1]
        k = layers.l2norm(heads(qkv[..., hk * w:2 * hk * w], hk), unit[0])
        return gdn.gated_delta_chunked(
            q, k, heads(qkv[..., 2 * hk * w:], hv), g, beta, CHUNK
        ).reshape(1, length, -1)

    want, pull = jax.vjp(plain, qkv, g, beta)
    how = dict(chunk=CHUNK, hk=hk, at=at, unit=unit, group=GROUP,
               interpret=True)
    o, kept = gd.rule_forward(qkv, qkv, qkv, g, beta, states=True, **how)
    near(o, want, 2e-6)
    *d_qkv, dg, dbeta = gd.rule_backward(qkv, qkv, qkv, g, beta, kept, weight,
                                         **how)
    for name, a, b in zip(("dqkv", "dg", "dbeta"),
                          (jnp.concatenate(d_qkv, -1), dg, dbeta),
                          pull(weight)):
        near(a, b, 2e-5, name)


def test_the_state_is_never_reset_across_chunks_or_grid_steps():
    """What the first chunk wrote is read by the last: with the first
    chunk's v changed the last chunk's output moves as the recurrence's
    does, and what entered the chunks is the recurrence's state."""
    q, k, v, g, beta = rule_inputs(11, 64, 2)
    g = g * 0.05                                   # a slow decay
    other = v.at[:, :CHUNK].multiply(-2.0)
    got, kept = forward(q, k, v, g, beta, states=True)
    moved = forward(q, k, other, g, beta)
    last = np.s_[:, -CHUNK:]
    assert float(jnp.max(jnp.abs(moved[last] - got[last]))) > 1e-3
    with jax.default_matmul_precision("highest"):
        near(moved[last], by_positions(q, k, other, g, beta)[last], 2e-5)
    entered = kept[0]                              # (bt, hk, chunks, dk, r dv)
    assert entered.shape == (1, 2, 8, 128, 256)
    assert float(jnp.max(jnp.abs(entered[:, :, 0]))) == 0.0
    assert all(float(jnp.max(jnp.abs(entered[:, :, c]))) > 0
               for c in range(1, 8))


def test_the_inverse_by_blocks_is_the_inverse():
    """``(I + L)^-1`` by block widths 1, 4, 16, 64 against float64's, for
    rows that are much alike (the plain series ``sum (-L)^k`` would
    cancel terms far above its sum), one matrix and two down one
    diagonal; at 8 rows against ``gdn.unit_lower_inverse``'s forward
    substitution too."""
    rng = np.random.default_rng(3)
    k = rng.standard_normal((2, 64, 32)) + 3.0 * rng.standard_normal((2, 1, 32))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    low = np.tril(np.einsum("bid,bjd->bij", k, k), -1)
    want = np.linalg.inv(np.eye(64) + low)
    low = jnp.asarray(low, jnp.float32)
    near(jnp.stack([gd._unit_lower_inverse(m) for m in low]), want, 2e-6)
    near(jnp.stack(gd._unit_lower_inverses(list(low))), want, 2e-6)
    near(gd._unit_lower_inverse(low[0, :8, :8]),
         gdn.unit_lower_inverse(low[0, :8, :8]), 1e-6)


def test_which_shapes_have_tiles():
    assert gd.supported(64, 128, 128, 2, 16384)    # the cell's
    assert gd.supported(64, 128, 128, 1, 27)
    assert not gd.supported(64, 64, 64, 2, 16384)  # a 64-wide head
    assert not gd.supported(64, 128, 64, 2, 16384)
    assert not gd.supported(60, 128, 128, 2, 16384)
    assert not gd.supported(96, 128, 128, 2, 16384)
    assert gd.chunks_a_step(64) * 64 == gd.STEP_ROWS


@pytest.mark.parametrize("width,interpret,on_kernel", [
    (128, True, False),        # the CPU's choice
    (16, True, False),
    (16, False, False),        # a head that is no tile, anywhere
    (128, False, True)])       # where Mosaic compiles
def test_which_rule_is_built_and_counted(width, interpret, on_kernel):
    """On the CPU, and at a shape without tiles anywhere, the built
    program holds the scan and no ``pallas_call``; at 128-wide heads
    where Mosaic compiles it holds the two kernels and no scan.  The
    decision function says what was built and, where the kernels are
    refused, the clause; tracing moves neither SPC counter."""
    spc.init()
    args = rule_inputs(0, 40, 2, dk=width, dv=width)
    before = (spc.read("gdn_rule_built"), spc.read("gdn_rule_kernel_built"))
    rule = lambda *a: gdn.gated_delta_chunked(*a, 8, interpret)
    names = _primitives(jax.make_jaxpr(rule)(*args).jaxpr)
    names |= _primitives(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(rule(*a)), range(5)))(*args).jaxpr)
    assert ("pallas_call" in names) == on_kernel
    assert ("scan" in names) == (not on_kernel)
    assert (spc.read("gdn_rule_built"),
            spc.read("gdn_rule_kernel_built")) == before
    on, why = gdn.rule_on_kernels(interpret, 8, width, width, 2, 40)
    assert on == on_kernel and bool(why) == (not on)
    if not on:
        assert why == (INTERPRET if interpret
                       else "a key head is 16 wide, not 128")


def test_the_operator_hands_the_choice_down():
    """``gated_delta_net`` passes ``interpret`` to the rule: the layer's
    program holds the kernels where Mosaic compiles and the shape has
    tiles, and the scan on the CPU."""
    import dataclasses

    from ompi_tpu.parallel import train

    cfg = train.load_model_config(
        "benchmark/configs/qwen3-next-80b-a3b-train-1chip.json",
        hidden_size=64, linear_num_key_heads=1, linear_num_value_heads=2,
        seq_len=16, micro_batch=1, chunk_size=8, compute_dtype="float32")
    one = dataclasses.replace(cfg, layers_here=1, first_layer_here=0)
    (group,) = train.init_model_params(one, 0)["layers"].values()
    p = jax.tree.map(lambda a: a[0], group["gdn_moe"])
    x = jnp.zeros((1, 16, 64), jnp.float32)
    for interpret in (True, False):
        jaxpr = jax.make_jaxpr(functools.partial(
            gdn.gated_delta_net, cfg=cfg, interpret=interpret))(p, x)
        assert ("pallas_call" in _primitives(jaxpr.jaxpr)) == (not interpret)
