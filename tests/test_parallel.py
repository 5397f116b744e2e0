"""ompi_tpu.parallel: mesh factoring, ring attention, MoE, pipeline, the
invented ("flagship") step, and the shape of the package's imports.

Numerical references are single-device jnp computations; the parallel
versions must match them exactly (same math, different schedule) — the
analog of the reference's coll algorithm-vs-basic cross-checks.
"""
import ast
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ompi_tpu.parallel.flagship import (_full_attention, build_flagship_step,
                                        init_params, model_dims,
                                        ring_attention)
from ompi_tpu.parallel.mesh import MeshSpec, default_axis_sizes, make_mesh
from ompi_tpu.parallel.pipeline import pipeline_apply

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_axis_sizes():
    assert default_axis_sizes(8) == MeshSpec(dp=2, pp=1, sp=2, tp=2)
    assert default_axis_sizes(16) == MeshSpec(dp=2, pp=2, sp=2, tp=2)
    assert default_axis_sizes(1) == MeshSpec()
    assert default_axis_sizes(4).n == 4
    assert default_axis_sizes(12).n == 12


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
    np.testing.assert_allclose(out, _full_attention(q, k, v),
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
    step, place = build_flagship_step(mesh, spec)
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
    step, place = build_flagship_step(mesh, spec, lr=lr)
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
    rng = np.random.RandomState(7)
    spec2 = MeshSpec(dp=1, pp=2, sp=2, tp=2)
    spec1 = MeshSpec(dp=1, pp=1, sp=2, tp=2)
    dims = model_dims(spec2, layers=2)
    x = rng.normal(0, 1, (dims["batch"], dims["seq"], dims["d"]))
    params = init_params(spec2, seed=3, layers=2)

    results = {}
    for name, spec, ndev in (("pp2", spec2, 8), ("pp1", spec1, 4)):
        mesh, _ = make_mesh(jax.devices()[:ndev], spec)
        step, place = build_flagship_step(mesh, spec, layers=2)
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


def test_causal_ring_matches_masked_reference():
    """causal=True on the ring == unsharded lower-triangle attention —
    the mask composes from GLOBAL positions across ring steps
    (shard-offset block bias), not local ones."""
    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    b, h, s, hd = 2, 2 * ndev, 4 * ndev, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, hd), jnp.float32)
    spec = P(None, None, "sp", None)
    body = lambda qq, kk, vv: ring_attention(qq, kk, vv, "sp", ndev,
                                             causal=True)
    got = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_full_attention(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-5)


def test_causal_single_shard_and_gradients():
    """n_shards=1 causal == plain masked attention, values and the
    gradient with respect to q."""
    b, h, s, hd = 1, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, hd), jnp.float32)
    ring = lambda qq: ring_attention(qq, k, v, "sp", 1, causal=True)
    full = lambda qq: _full_attention(qq, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ring(q)), np.asarray(full(q)),
                               rtol=2e-5, atol=2e-5)
    grad = lambda fn: jax.grad(lambda qq: jnp.sum(fn(qq) ** 2))(q)
    np.testing.assert_allclose(np.asarray(grad(ring)), np.asarray(grad(full)),
                               rtol=2e-4, atol=2e-5)


# -- the package's shape: one model path whose imports point one way ------
_FRESH = (
    "import sys; {imports}; "
    "from ompi_tpu.base.var import registry; "
    "print(sorted(m.rsplit('.', 1)[1] for m in sys.modules "
    "if m.startswith('ompi_tpu.parallel.'))); "
    "print(sorted(v.name for v in registry.all_vars() "
    "if v.name.startswith(('otpu_parallel_', 'otpu_moe_'))))")


def _fresh(imports: str) -> tuple:
    """(the ``ompi_tpu.parallel`` modules loaded, the ``parallel`` and
    ``moe`` options registered) after ``imports`` in a new interpreter."""
    out = subprocess.run(
        [sys.executable, "-c", _FRESH.format(imports=imports)],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        cwd=REPO, capture_output=True, text=True, timeout=300, check=True)
    loaded, options = out.stdout.strip().splitlines()[-2:]
    return ast.literal_eval(loaded), ast.literal_eval(options)


def test_importing_the_model_path_loads_nothing_beside_it():
    """``import ompi_tpu.parallel.train`` is all the benchmark's train
    kinds do: it loads the model path (mesh, layers, experts, model,
    train) and neither the invented step, the pipeline, the host
    trainers nor any option of theirs."""
    loaded, options = _fresh("import ompi_tpu.parallel.train")
    assert not {"flagship", "pipeline", "moe", "elastic",
                "checkpoint"} & set(loaded), loaded
    assert {"layers", "experts", "model", "train"} <= set(loaded)
    assert options == []


def test_the_invented_step_registers_no_option():
    """The flagship step has one variant: no ``otpu_parallel_*`` option
    exists after its module is imported either."""
    loaded, options = _fresh("import ompi_tpu.parallel.flagship")
    assert "flagship" in loaded and "pipeline" in loaded
    assert [o for o in options if o.startswith("otpu_parallel_")] == []


def test_build_train_step_needs_a_model():
    """``train.build_train_step`` builds a public model's step and no
    second program: without a configuration it is a ``TypeError``."""
    from ompi_tpu.parallel import build_train_step

    mesh, spec = make_mesh(jax.devices()[:1])
    with pytest.raises(TypeError, match="model"):
        build_train_step(mesh, spec)


#: the model path top down: a module imports only those before it (the
#: configuration, first, nothing of the package)
MODEL_PATH = ("config", "mesh", "layers", "sublayer", "experts", "causal",
              "attention", "dsa", "mamba", "short_conv", "gdn", "hyper",
              "model", "objective", "train")


def test_model_path_imports_point_one_way_at_module_top():
    """No ``ompi_tpu.parallel`` import below module level in the model
    path's files, and none of a module further down ``MODEL_PATH`` (so
    no cycle, and nothing of the package beside the path)."""
    for at, name in enumerate(MODEL_PATH):
        path = os.path.join(REPO, "ompi_tpu", "parallel", name + ".py")
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                # ``from ompi_tpu.parallel import x`` names modules
                names = [node.module] if node.module != "ompi_tpu.parallel" \
                    else [f"{node.module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            for mod in names:
                if not (mod or "").startswith("ompi_tpu.parallel"):
                    continue
                assert node in tree.body, \
                    f"{name}.py:{node.lineno}: {mod} imported below the top"
                assert mod.rsplit(".", 1)[1] in MODEL_PATH[:at], \
                    f"{name}.py:{node.lineno}: imports {mod}"
