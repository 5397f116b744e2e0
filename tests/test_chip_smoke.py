"""chip_smoke.py on the CPU: it must refuse to run without a TPU, its
phases must be sound (run here at OTPU_MODEL_SCALE=1 on the 8-virtual-
device mesh, so a broken phase is found before chip time is spent), and
the compile cache it reports must be placeable from outside."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_refuses_without_tpu_before_any_phase():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "['cpu']" in proc.stderr and "JAX_PLATFORMS='cpu'" in proc.stderr
    assert "Nothing was run" in proc.stderr
    # no phase output, and above all no result line
    assert proc.stdout.strip() == ""


@pytest.fixture
def world():
    import ompi_tpu
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    yield ompi_tpu.init()
    ompi_tpu.finalize()
    rt.reset_for_testing()


def test_phases_pass_small_on_cpu_mesh(world):
    import jax

    import chip_smoke

    devs = jax.devices()
    clock = chip_smoke.Clock()
    assert chip_smoke.boot(devs) is world
    chip_smoke.collectives(world, clock, platform="cpu",
                           primary_bytes=1 << 16, spot_bytes=1 << 14)
    chip_smoke.trainer(devs, clock, scale=1)
    # four devices add the pipeline-active mesh
    chip_smoke.trainer(devs[:4], clock, scale=1)
    chip_smoke.kernels(clock, expect_interpret=True,
                       flash_shape=(1, 2, 128, 128), dtype="float32",
                       reduce_elems=1 << 14, rope_shape=(1, 64, 2, 32),
                       ssm_shape=(1, 44, 4, 8, 2, 8, 16),
                       ssm_kernel_shapes=((1, 44, 4, 64, 2, 128, 8),))
    with pytest.raises(RuntimeError, match="interpret resolved to True"):
        chip_smoke.kernels(clock)
    assert clock.cold > 0 and clock.steady_calls > 0


_CACHE_PROBE = (
    "import jax; from ompi_tpu.base.jaxenv import compile_cache_dir; "
    "print(compile_cache_dir()); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_probe(env_dir, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=cwd, capture_output=True, text=True,
                         timeout=120, check=True).stdout.split()
    return out   # [what the helper returned, what jax is configured with]


def test_compile_cache_is_placeable_and_fixed(tmp_path):
    # placed from outside: JAX honours the variable, the code sets nothing
    placed = str(tmp_path / "cache")
    assert _cache_probe(placed, REPO) == [placed, placed]
    # not placed: one fixed path inside the checkout, whatever the
    # process and its working directory
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_probe(None, REPO) == [want, want]
    assert _cache_probe(None, str(tmp_path)) == [want, want]
