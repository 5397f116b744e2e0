"""What the program observes about its JAX installation: whether its
devices are TPUs, which devices a Pallas kernel is being built for, and
where compiled programs are cached.  Platform selection itself is stock
JAX (``JAX_PLATFORMS``)."""
from __future__ import annotations

import os

#: the checkout's own cache directory (``.gitignore`` lists it).  Derived
#: from the package location so every process of a checkout shares it:
#: the path is part of the cache key, and one that moves never hits.
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def require_tpu(who: str) -> list:
    """``jax.devices()``, every one a TPU — or exit non-zero at once,
    naming what was found.  For entry points whose output is only true
    of a chip (``chip_smoke.py``): nothing
    is measured or written from another platform.  Sets no platform
    itself."""
    import jax

    devs = jax.devices()
    found = sorted({d.platform for d in devs})
    if found != ["tpu"]:
        raise SystemExit(
            f"{who}: needs a TPU; jax found platform(s) {found} "
            f"(JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')!r}). "
            "Nothing was run, nothing written.")
    return devs


def pallas_interpret(devices=None) -> bool:
    """``interpret=`` for a Pallas kernel built for ``devices`` (a
    mesh's, or the process's default devices when there is no mesh):
    Mosaic when every one is a TPU, the interpreter otherwise.  Reading
    the platform off the devices rather than the process lets a CPU
    client compile for an offline TPU topology (``tools/pallas_aot``)
    take the choice a chip would."""
    if devices is None:
        import jax

        devices = jax.devices()
    return not all(d.platform == "tpu" for d in devices)


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside: JAX honours
    that variable itself, so then no directory is set in code.
    Otherwise the cache goes to ``<checkout>/.jax_cache``.  Call before
    the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.config.jax_compilation_cache_dir != _REPO_CACHE:
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE
