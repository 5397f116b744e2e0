"""What the model path's tests build, built once a process (a model's
parameters at test widths, a built step, a batch, a reference run as one
program) or once a session (a child process's result).  A test's cost does
not depend on which tests its worker ran before it, except that what one of
them built is there.

A program JAX compiled is kept by the function object it was compiled for,
so a second ``train.build_train_step`` of one configuration compiles the
same step again (seconds each), and a ``*_reference.py`` function run op by
op compiles a program a ``jnp`` line and shape: hundreds a call, 85 ms and
four or five memory mappings each, of which a process may hold 65,530
(``conftest.release_programs``).  Hence one ``step`` a (configuration,
devices) and one ``jax.jit`` a reference function.
"""
import dataclasses
import fcntl
import functools
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel import train
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh


#: the session's temporary directory, the one every xdist worker of a run
#: shares (``conftest.py`` sets it before the first test)
SESSION_DIR = None


def shared(name: str, make):
    """``make()``'s value, JSON's kinds of value only, made once a session:
    the worker that comes first makes it, holding ``name``'s lock in the
    session's temporary directory, and every other reads what it wrote.  For
    what costs a child process a minute or more (an offline compile's rows,
    a cell's rehearsal), whichever workers its tests are dealt to.  A
    ``make`` that raises writes nothing, and the next caller makes it."""
    path = os.path.join(SESSION_DIR, name + ".json")
    with open(path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(path):
            with open(path + ".part", "w") as f:
                json.dump(make(), f)
            os.replace(path + ".part", path)
        with open(path) as f:
            return json.load(f)


@functools.cache
def _drawn(cfg, seed, init):
    return jax.device_get((init or train.init_model_params)(cfg, seed))


def params(cfg, seed=0, init=None):
    """``train.init_model_params(cfg, seed)`` (or ``init(cfg, seed)``, a
    module-level function of a test file that draws them its own way) as
    fresh device arrays: drawn once a process, op by op as the program
    draws them (one program a drawn leaf's shape, and to the bit what a
    test's own call would hold), and kept on the host, so a step that
    donates what it was given spoils nothing for the next caller."""
    return jax.tree.map(jnp.array, _drawn(cfg, seed, init))


def fresh_step(cfg, devices=1):
    """``(step, place)`` of ``train.build_train_step`` for ``cfg`` on a
    mesh of the first ``devices`` CPU devices (``dp`` over them), built
    now and traced by its own first call: for what holds of a step that
    has never run (``scopes()`` raises, the first call feeds the
    ``*_built`` counters), for a step traced under a patch, and for a test
    of whether a second build gives the first's numbers."""
    mesh, spec = make_mesh(jax.devices()[:devices], MeshSpec(dp=devices))
    return train.build_train_step(mesh, spec, model=cfg)


_kept_step = functools.cache(fresh_step)


def step(cfg, devices=1):
    """``fresh_step(cfg, devices)``, one a process: its program is traced
    and compiled by its first call and by no later one, so whatever a test
    patches after that (``monkeypatch``, ``mock.patch``) it does not see,
    and which test makes the first call depends on the order: a test of
    either takes ``fresh_step``."""
    return _kept_step(cfg, devices)


def batch(cfg, seed, vocab=None):
    """(tokens (b, s), labels (b, s + 1): the next token and the one
    after) from s + 2 ids a row drawn from ``seed``, below ``vocab`` (the
    configuration's ``vocab_size`` if not given): the batch's form for every
    model, of which one without a next-next-token head reads the first s
    labels."""
    ids = np.random.default_rng(seed).integers(
        0, vocab or cfg.vocab_size,
        (cfg.micro_batch, cfg.seq_len + 2)).astype(np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def _is_static(value) -> bool:
    """What a call hands over that is no array: a configuration, a name, a
    switch, a count, nothing, or a tuple of such."""
    if isinstance(value, tuple):
        return all(_is_static(v) for v in value)
    return value is None or isinstance(value, (str, bool, int, float)) \
        or dataclasses.is_dataclass(value)


@functools.cache
def program(fn):
    """``fn`` as one program a call's static arguments: ``jax.jit(fn)``,
    made once a process, in which every argument that is no array (a
    ``ModelConfig``, a ``str``, a Python number, ``None``) is static.  A
    reference's ``train_steps``, ``loss`` or ``grads`` over whole arrays
    is compiled as one program, where a call op by op compiles one a line
    and shape.  The function is the reference's own and is not changed.
    What it reads is read when a shape is first traced: a call under a
    patch goes to the function itself (``programs.plain``)."""
    signature = inspect.signature(fn)
    jitted = functools.cache(
        lambda static: jax.jit(fn, static_argnames=static))

    @functools.wraps(fn)
    def call(*args, **kwargs):
        given = signature.bind(*args, **kwargs).arguments
        return jitted(tuple(name for name, value in given.items()
                            if _is_static(value)))(*args, **kwargs)

    return call


class programs:
    """A ``*_reference.py`` module with each of its functions as
    ``program`` of it: ``ref = built.programs(joyai_reference)``, then
    ``ref.grads(params, tokens, labels, cfg, bias)`` is one program.  A
    call that has to stay op by op goes to ``ref.plain``, the module
    itself, and says why."""

    def __init__(self, module):
        self.plain = module

    def __getattr__(self, name):
        value = getattr(self.plain, name)
        if inspect.isfunction(value) \
                and not inspect.isgeneratorfunction(value):
            return program(value)
        return value
