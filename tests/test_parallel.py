"""ompi_tpu.parallel: mesh factoring, ring attention, MoE, pipeline, train.

Numerical references are single-device jnp computations; the parallel
versions must match them exactly (same math, different schedule) — the
analog of the reference's coll algorithm-vs-basic cross-checks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ompi_tpu.parallel.mesh import MeshSpec, default_axis_sizes, make_mesh
from ompi_tpu.parallel.model import ring_attention
from ompi_tpu.parallel.pipeline import pipeline_apply
from ompi_tpu.parallel.train import build_train_step, init_params, model_dims


def test_default_axis_sizes():
    assert default_axis_sizes(8) == MeshSpec(dp=2, pp=1, sp=2, tp=2)
    assert default_axis_sizes(16) == MeshSpec(dp=2, pp=2, sp=2, tp=2)
    assert default_axis_sizes(1) == MeshSpec()
    assert default_axis_sizes(4).n == 4
    assert default_axis_sizes(12).n == 12


def _ref_attention(q, k, v):
    # q,k,v: (b, h, s, hd) global — plain softmax attention
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_ring_attention_matches_dense():
    n_sp = 4
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    rng = np.random.RandomState(0)
    b, h, s, hd = 2, 2, 8, 4
    q, k, v = (rng.normal(0, 1, (b, h, s, hd)).astype(np.float32)
               for _ in range(3))

    fn = jax.jit(shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp", n_sp),
        mesh=mesh, in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None), check_vma=False))
    out = fn(q, k, v)
    np.testing.assert_allclose(out, _ref_attention(q, k, v),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_matches_sequential():
    pp = 4
    mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))
    rng = np.random.RandomState(1)
    M, mb, d = 3, 2, 4
    x = rng.normal(0, 1, (M, mb, d)).astype(np.float32)
    w = rng.normal(0, 0.5, (pp, d, d)).astype(np.float32)

    def stage(wi, z):
        return jnp.tanh(z @ wi[0])

    fn = jax.jit(shard_map(
        # outputs live on the last stage only; psum over pp collects them
        lambda w_, x_: jax.lax.psum(pipeline_apply(stage, w_, x_, pp=pp),
                                    "pp"),
        mesh=mesh, in_specs=(P("pp", None, None), P()),
        out_specs=P(), check_vma=False))
    out = fn(w, x)

    ref = x
    for i in range(pp):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 4, 8])
def test_train_step_descends(n):
    mesh, spec = make_mesh(jax.devices()[:n])
    dims = model_dims(spec)
    step, place = build_train_step(mesh, spec)
    rng = np.random.RandomState(2)
    x = rng.normal(0, 1, (dims["batch"], dims["seq"], dims["d"]))
    params, xd = place(init_params(spec), x)
    p1, l1 = step(params, xd)
    _, l2 = step(p1, xd)
    assert np.isfinite(float(l1))
    assert float(l2) < float(l1)


@pytest.mark.parametrize("spec_text", ["dp=2,pp=1,sp=2,tp=2",
                                       "dp=1,pp=2,sp=2,tp=2"])
def test_update_is_one_gradient_step(spec_text):
    """The applied update is lr times THE gradient on every mesh: to
    first order one step lowers the loss by |delta|^2 / lr.  Taking the
    gradient w.r.t. the replicated params (whose transpose already
    psums over dp and sp) and then psum-ing it again took a
    dp*sp-times larger step — ratio 1/(dp*sp) here — and the widest
    meshes diverged within five steps."""
    from ompi_tpu.parallel.dryrun import parse_spec

    lr = 0.1
    spec = parse_spec(spec_text)
    mesh, spec = make_mesh(
        jax.devices()[:spec.dp * spec.pp * spec.sp * spec.tp], spec)
    dims = model_dims(spec)
    step, place = build_train_step(mesh, spec, lr=lr)
    x = np.random.RandomState(2).normal(
        0, 1, (dims["batch"], dims["seq"], dims["d"]))
    p0 = init_params(spec)
    params, xd = place(p0, x)
    p1, l0 = step(params, xd)
    _, l1 = step(p1, xd)
    delta2 = sum(float(np.sum((np.asarray(p1[k], np.float64) - p0[k]) ** 2))
                 for k in p0)
    ratio = (float(l1) - float(l0)) / (-delta2 / lr)
    assert 0.9 < ratio < 1.1, ratio


def test_widest_config_descends_on_the_four_device_mesh(monkeypatch):
    """The case the first four-chip run failed: at the widest
    configuration (``OTPU_MODEL_SCALE=64``) on the default four-device
    mesh (sp=2, tp=2) the summed loss at a fixed lr tripled at the
    fourth step and reached inf at the sixth — chip and CPU alike.
    The mean loss keeps the effective step from growing with batch,
    sequence and width, which all grow with the mesh and the scale."""
    from ompi_tpu.parallel.dryrun import make_step_and_args

    monkeypatch.setenv("OTPU_MODEL_SCALE", "64")
    step, (params, xd), spec = make_step_and_args(jax.devices()[:4])
    assert spec.sizes() == {"dp": 1, "pp": 1, "sp": 2, "tp": 2}
    losses = []
    for _ in range(6):
        params, loss = step(params, xd)
        losses.append(float(loss))
    assert all(b < a for a, b in zip(losses, losses[1:])), losses


def test_ulysses_matches_ring_and_full():
    """Ulysses (all-to-all SP) == ring attention == unsharded reference."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from ompi_tpu.parallel.model import (_full_attention, ring_attention,
                                         ulysses_attention)

    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    b, h, s, hd = 2, 2 * ndev, 4 * ndev, 8
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, hd), jnp.float32)

    spec = P(None, None, "sp", None)

    def run(fn):
        body = lambda qq, kk, vv: fn(qq, kk, vv, "sp", ndev)
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))(q, k, v)

    ref = _full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(run(ulysses_attention)),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(run(lambda *a: ring_attention(*a, use_flash=False))),
        np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_composed_step_with_active_pipeline_axis():
    """The 4-axis step with pp>=2 ACTIVE: loss descends on the
    {dp:1,pp:2,sp:2,tp:2} mesh (round-2 gap: the composed dp x pp x sp
    x tp program had only ever run with pp=1)."""
    from ompi_tpu.parallel.dryrun import make_step_and_args
    from ompi_tpu.parallel.mesh import MeshSpec

    step, (params, xd), spec = make_step_and_args(
        jax.devices()[:8], MeshSpec(dp=1, pp=2, sp=2, tp=2))
    assert spec.pp == 2
    p1, l1 = step(params, xd)
    _, l2 = step(p1, xd)
    assert np.isfinite(float(l1))
    assert float(l2) < float(l1), (float(l1), float(l2))


def test_pp2_matches_pp1_same_model():
    """Grad-sync equivalence: the SAME 2-layer model + input stepped on a
    pp=2 mesh (8 devices, one layer per stage) and a pp=1 mesh (4
    devices, both layers local) must produce the same loss and the same
    updated parameters — pipelining is an execution schedule, not a
    different function."""
    from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
    from ompi_tpu.parallel.train import (build_train_step, init_params,
                                         model_dims)

    rng = np.random.RandomState(7)
    spec2 = MeshSpec(dp=1, pp=2, sp=2, tp=2)
    spec1 = MeshSpec(dp=1, pp=1, sp=2, tp=2)
    dims = model_dims(spec2, layers=2)
    x = rng.normal(0, 1, (dims["batch"], dims["seq"], dims["d"]))
    params = init_params(spec2, seed=3, layers=2)

    results = {}
    for name, spec, ndev in (("pp2", spec2, 8), ("pp1", spec1, 4)):
        mesh, _ = make_mesh(jax.devices()[:ndev], spec)
        step, place = build_train_step(mesh, spec, layers=2)
        pd, xd = place(params, x)
        p1, l1 = step(pd, xd)
        results[name] = (float(l1), {k: np.asarray(v)
                                     for k, v in p1.items()})
    l2, p2 = results["pp2"]
    l1_, p1_ = results["pp1"]
    np.testing.assert_allclose(l2, l1_, rtol=1e-5)
    for k in p2:
        np.testing.assert_allclose(p2[k], p1_[k], rtol=1e-4, atol=1e-6,
                                   err_msg=f"param {k} diverged")


@pytest.mark.slow
def test_dryrun_spec_override_and_16dev():
    """The driver-facing dryrun accepts a mesh-spec override (pp=2 on 8
    devices) and the 16-device default mesh — where pp activates on its
    own — runs a descending composed step."""
    import __graft_entry__ as g

    g.dryrun_multichip(8, spec="dp=1,pp=2,sp=2,tp=2")
    g.dryrun_multichip(16)   # default_axis_sizes(16) -> all 4 axes active


def test_causal_ring_and_ulysses_match_masked_reference():
    """causal=True on both SP schemes == unsharded lower-triangle
    attention — the mask composes from GLOBAL positions across ring
    steps (shard-offset block bias), not local ones."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from ompi_tpu.parallel.model import (_full_attention, ring_attention,
                                         ulysses_attention)

    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    b, h, s, hd = 2, 2 * ndev, 4 * ndev, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, hd), jnp.float32)
    spec = P(None, None, "sp", None)

    def run(fn):
        body = lambda qq, kk, vv: fn(qq, kk, vv, "sp", ndev)
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))(q, k, v)

    ref = np.asarray(_full_attention(q, k, v, causal=True))
    got_ring = run(lambda *a: ring_attention(*a, use_flash=False,
                                             causal=True))
    np.testing.assert_allclose(np.asarray(got_ring), ref, rtol=2e-4,
                               atol=2e-5)
    got_ul = run(lambda *a: ulysses_attention(*a, causal=True))
    np.testing.assert_allclose(np.asarray(got_ul), ref, rtol=2e-4,
                               atol=2e-5)
    # flash path (interpreter off-TPU) agrees too
    got_flash = run(lambda *a: ring_attention(*a, use_flash=True,
                                              causal=True))
    np.testing.assert_allclose(np.asarray(got_flash), ref, rtol=2e-4,
                               atol=2e-5)


def test_causal_single_shard_and_gradients():
    """n_shards=1 causal == plain masked attention; gradients flow
    through the biased flash custom-VJP (recompute via the jnp twin)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.parallel.model import _full_attention, ring_attention

    b, h, s, hd = 1, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, hd), jnp.float32)
    ref = np.asarray(_full_attention(q, k, v, causal=True))
    for flash in (False, True):
        got = ring_attention(q, k, v, "sp", 1, use_flash=flash,
                             causal=True)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=2e-5)

    def loss(fn, flash):
        return lambda qq: jnp.sum(
            fn(qq, k, v, "sp", 1, use_flash=flash, causal=True) ** 2)

    g_flash = jax.grad(loss(ring_attention, True))(q)
    g_jnp = jax.grad(loss(ring_attention, False))(q)
    np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_jnp),
                               rtol=2e-4, atol=2e-5)


def test_causal_train_step_var():
    """--mca parallel_causal 1 flows into the composed train step and
    changes the loss trajectory (masked attention is a different
    program), while still descending."""
    import jax

    from ompi_tpu.base.var import registry
    from ompi_tpu.parallel.dryrun import parse_spec, run_training_step

    var = registry.lookup("otpu_parallel_causal")
    assert var is not None
    old = var.value
    try:
        devs = jax.devices()[:4]
        spec = parse_spec("dp=2,pp=1,sp=2,tp=1")
        var.set(False)
        base = run_training_step(devs, spec)
        var.set(True)
        causal = run_training_step(devs, spec)
        assert np.isfinite(causal)
        # masked attention is a genuinely different program: same init,
        # same data, different loss
        assert abs(causal - base) > 1e-6, (causal, base)
    finally:
        var.set(old)


def test_remat_var_matches_baseline_loss():
    """--mca parallel_remat 1 must change only WHERE activations come
    from (recompute vs store): the loss trajectory is bit-comparable."""
    import jax

    from ompi_tpu.base.var import registry
    from ompi_tpu.parallel.dryrun import parse_spec, run_training_step

    var = registry.lookup("otpu_parallel_remat")
    assert var is not None
    devs = jax.devices()[:4]
    spec = parse_spec("dp=2,pp=1,sp=2,tp=1")
    old = var.value
    try:
        var.set(False)
        base = run_training_step(devs, spec)
        var.set(True)
        remat = run_training_step(devs, spec)
        np.testing.assert_allclose(remat, base, rtol=1e-6)
    finally:
        var.set(old)


def test_compute_dtype_bf16_descends():
    """--mca parallel_compute_dtype bfloat16: the composed step still
    trains (finite loss, close to the f32 program) with half-width
    activations and per-block param casts — including combined with
    causal masking and remat (the production stack)."""
    import jax

    from ompi_tpu.base.var import registry
    from ompi_tpu.parallel.dryrun import parse_spec, run_training_step

    var = registry.lookup("otpu_parallel_compute_dtype")
    assert var is not None
    devs = jax.devices()[:4]
    spec = parse_spec("dp=2,pp=1,sp=2,tp=1")
    old = var.value
    causal = registry.lookup("otpu_parallel_causal")
    remat = registry.lookup("otpu_parallel_remat")
    old_c, old_r = causal.value, remat.value
    try:
        var.set("float32")
        base = run_training_step(devs, spec)
        var.set("bfloat16")
        lo = run_training_step(devs, spec)
        assert np.isfinite(lo)
        # bf16 rounding makes a different (but close) program
        np.testing.assert_allclose(lo, base, rtol=0.1)
        # the production combination: bf16 + causal + remat must
        # compose (regression: the f32 causal bias once promoted the
        # bf16 scan carry and broke lax.scan's type invariant)
        causal.set(True)
        remat.set(True)
        combo = run_training_step(devs, spec)
        assert np.isfinite(combo)
    finally:
        var.set(old)
        causal.set(old_c)
        remat.set(old_r)


def test_zero1_matches_baseline_and_shards_state():
    """--mca parallel_zero1 1: reduce-scatter grads, dp-sharded
    momentum, masked-psum param rebuild — loss parity with the
    allreduce baseline at momentum 0, and the state really is one
    (chunk,) block per (dp, pp, tp) shard."""
    import jax

    from ompi_tpu.base.var import registry
    from ompi_tpu.parallel.dryrun import (make_step_and_args, parse_spec,
                                          run_training_step)

    z = registry.lookup("otpu_parallel_zero1")
    mvar = registry.lookup("otpu_parallel_momentum")
    old_z, old_m = z.value, mvar.value
    devs = jax.devices()[:8]
    try:
        for s in ("dp=2,pp=2,sp=2,tp=1", "dp=2,pp=1,sp=2,tp=2"):
            spec = parse_spec(s)
            z.set(False)
            mvar.set(0.0)
            base = run_training_step(devs, spec)
            z.set(True)
            np.testing.assert_allclose(run_training_step(devs, spec),
                                       base, rtol=1e-6)
            mvar.set(0.9)
            assert np.isfinite(run_training_step(devs, spec))
        # structural: carried state is (params, m) with the sharded spec
        z.set(True)
        step, args, _ = make_step_and_args(
            devs, parse_spec("dp=2,pp=1,sp=2,tp=2"))
        (params, m), x = args
        assert tuple(m.sharding.spec) == (("dp", "pp", "tp"),)
        txt = step.lower(*args).as_text()
        assert "reduce-scatter" in txt or "reduce_scatter" in txt
    finally:
        z.set(old_z)
        mvar.set(old_m)
