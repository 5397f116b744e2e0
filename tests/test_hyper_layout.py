"""The residual path on stream-major streams (``parallel/hyper.py``, PR 74):
``maps`` / ``read`` / ``write`` on a stream (b, n, s, d) against plain
``jnp.einsum`` lines in float32 at the highest precision, values and the
gradients of every input; one ``model.stream_layer`` application and its
gradient make no ``transpose``, ``pad`` or ``reshape`` of the whole stream;
``seen``'s reports are the old (T, n, d) rows at the rows it is handed.
Small shapes (s 16, d 32): the file costs seconds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import config, hyper, model, objective, train

from test_xing_train import PUBLISHED, SHARE, TRAIN

S, D = 16, 32
HIGHEST = jax.lax.Precision.HIGHEST
CLOSE = dict(rtol=2e-5, atol=2e-6)
SHAPES = [(n, b) for n in (2, 4) for b in (1, 2)]


def cfg_of(n, b):
    """``test_xing_train.py``'s small model at s 16, d 32 and one sweep
    (``sinkhorn`` is not this file's subject)."""
    return config.ModelConfig(
        compute_dtype="float32", num_experts=8,
        **{**PUBLISHED, "hidden_size": D, "hc_mult": n,
           "hc_sinkhorn_iters": 1}, **SHARE,
        **{**TRAIN, "seq_len": S, "micro_batch": b, "attn_block": 8,
           "loss_block_rows": 8})


def normal(rng, *shape):
    """Seeded by numpy: nothing for XLA to compile."""
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def leaves_of(cfg, rng):
    """One sublayer's path's leaves, ``phi`` wide enough to move the maps."""
    return {name: normal(rng, *shape) * (0.3 if name.endswith("phi") else 1.0)
            for name, shape in hyper.shapes(cfg, "hc1").items()}


def stream_of(n, b, rng):
    return normal(rng, b, n, S, D)


def plain_maps(p, x, cfg):
    """The module docstring's lines on the token's whole vector, an
    ``einsum`` each."""
    b, n, s, d = x.shape
    flat = jnp.einsum("bnsd->bsnd", x).reshape(b * s, n * d)
    normed = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg.rms_norm_eps)
    m = jnp.einsum("tk,km->mt", normed, p["hc1_phi"], precision=HIGHEST)
    alpha, off = p["hc1_alpha"], p["hc1_b"][:, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + off[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + off[n:2 * n])
    raw = (alpha[2] * m[2 * n:] + off[2 * n:]).reshape(n, n, b * s)
    mix = jnp.exp(jnp.clip(raw, cfg.mhc_h_res_clamp_min,
                           cfg.mhc_h_res_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters):
        mix = mix / (jnp.einsum("ijt->jt", mix)[None] + cfg.hc_eps)
        mix = mix / (jnp.einsum("ijt->it", mix)[:, None] + cfg.hc_eps)
    return pre, post, mix


def plain_read(pre, x):
    b, n, s, _ = x.shape
    return jnp.einsum("nbs,bnsd->bsd", pre.reshape(n, b, s), x,
                      precision=HIGHEST)


def plain_write(res, post, x, y):
    b, n, s, _ = x.shape
    return jnp.einsum("ijbs,bjsd->bisd", res.reshape(n, n, b, s), x,
                      precision=HIGHEST) \
        + jnp.einsum("ibs,bsd->bisd", post.reshape(n, b, s), y,
                     precision=HIGHEST)


def weighed(fn):
    """``(fn``'s results against fixed random weights, summed: a scalar whose
    gradient reaches every input through every result; the results)``."""
    def loss(*args):
        outs = jax.tree.leaves(fn(*args))
        rng = np.random.default_rng(5)
        return sum(jnp.sum(o * normal(rng, *o.shape)) for o in outs), outs
    return loss


def the_case(part, n, b):
    """(the path's function, the plain one, their arguments)."""
    cfg = cfg_of(n, b)
    rng = np.random.default_rng(n * 10 + b)
    x = stream_of(n, b, rng)
    if part == "maps":
        return (lambda p, x: hyper.maps(p, x, cfg, "hc1"),
                lambda p, x: plain_maps(p, x, cfg), (leaves_of(cfg, rng), x))
    t = b * S
    pre = normal(rng, n, t)
    if part == "read":
        return hyper.read, plain_read, (pre, x)
    return hyper.write, plain_write, (normal(rng, n, n, t), pre, x,
                                      normal(rng, b, S, D))


@pytest.mark.parametrize("n,b", SHAPES)
@pytest.mark.parametrize("part", ["maps", "read", "write"])
def test_a_part_is_the_plain_einsums_values_and_gradients(part, n, b):
    got_fn, want_fn, args = the_case(part, n, b)
    every = tuple(range(len(args)))
    (_, got), got_grads = jax.jit(jax.value_and_grad(
        weighed(got_fn), argnums=every, has_aux=True))(*args)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        weighed(want_fn), argnums=every, has_aux=True))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, **CLOSE)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale)


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


@pytest.mark.parametrize("which", ["forward", "gradient"])
@pytest.mark.parametrize("kind", ["dense", "layers"])
def test_a_layer_makes_no_view_of_the_whole_stream(kind, which):
    """One ``stream_layer`` application (a dense layer's, a sparse one's)
    and its gradient: no ``transpose``, ``pad`` or ``reshape`` reads or
    writes b n s d elements, the reports' rows included; the stream's
    cotangent is joined from slabs as the stream is (``concatenate``)."""
    n, b = 4, 2
    cfg = cfg_of(n, b)
    whole = b * n * S * D
    p = jax.tree.map(  # shapes alone: the test traces and runs nothing
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        jax.eval_shape(lambda: train.init_model_params(cfg, 0))[kind])
    x = jax.ShapeDtypeStruct((b, n, S, D), jnp.float32)
    at = objective.sample_rows(b * S)
    bias = jnp.zeros((cfg.num_experts,)) if kind == "layers" else None

    def apply(p, x):
        out, _, rows = model.decoder_layer(p, x, cfg, interpret=True,
                                           kind=kind, bias=bias, at=at)
        return jnp.sum(out * out), rows
    fn = apply if which == "forward" \
        else jax.grad(apply, argnums=(0, 1), has_aux=True)
    jaxpr = jax.make_jaxpr(fn)(p, x)
    seen = list(equations(jaxpr.jaxpr))
    sizes = lambda e: {int(np.prod(v.aval.shape))
                       for v in (*e.invars, *e.outvars)
                       if hasattr(v.aval, "shape")}
    assert len(seen) > 500
    bad = [e for e in seen
           if e.primitive.name in ("transpose", "pad", "reshape")
           and whole in sizes(e)]
    assert not bad, [str(e)[:200] for e in bad[:5]]
    joins = [e for e in seen if e.primitive.name == "concatenate"
             and whole in sizes(e)]
    # two sublayers' writes; in the gradient also their three splits'
    # transposes each (the maps', the read's, the write's)
    assert len(joins) >= (2 if which == "forward" else 8), len(joins)


@pytest.mark.parametrize("n,b", SHAPES)
@pytest.mark.parametrize("rows", ["sampled", "every"])
def test_the_reports_are_the_old_rows_at_the_sampled_positions(rows, n, b):
    cfg = cfg_of(n, b)
    rng = np.random.default_rng(3)
    x = stream_of(n, b, rng)
    pre, post = normal(rng, n, b * S), normal(rng, n, b * S)
    res = normal(rng, n, n, b * S)
    at = np.asarray([0, 5, b * S - 1]) if rows == "sampled" else None
    pick = (lambda v: v) if at is None else (lambda v: v[at])
    got = hyper.seen(pre, post, res, x, "hc1", at)
    assert sorted(got) == sorted(hyper.reports(cfg, "hc1"))
    old = {"hc1_in": x.transpose(0, 2, 1, 3).reshape(b * S, n, D),
           "hc1_pre": pre.T, "hc1_post": post.T,
           "hc1_res": res.transpose(2, 0, 1)}
    for key, want in old.items():
        np.testing.assert_array_equal(got[key], pick(want), err_msg=key)
