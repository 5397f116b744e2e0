"""What a kind of sublayer is declared by.  One ``Sublayer`` stands beside
each operator (``parallel/mamba.py``, ``attention.py``, ...) and each
feed-forward (``parallel/experts.py``); ``parallel/model.py`` gathers them
into the table that the parameter tree, the initialisation, AdamW, the
walk, a layer's checkpoint and a step's ``aux`` are read from.  Nothing of
``ompi_tpu.parallel`` is imported here.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

#: why a kernel is not taken where Mosaic does not compile (``interpret``:
#: a CPU): the first clause of every family's decision function
INTERPRET = "interpret: Mosaic does not compile here"


def on_mosaic(interpret: bool) -> tuple:
    """``(on_kernel, why)`` of a kernel that every shape has tiles for:
    taken wherever Mosaic compiles (``interpret`` false: a TPU)."""
    return (False, INTERPRET) if interpret else (True, "")


def held(counts: dict = None, written: dict = None, **parts) -> dict:
    """What one application of a sublayer holds (a ``Sublayer.plan``'s
    answer) from its ``parts``, each a decision function's ``(on_kernel,
    why)``: ``impl`` ``"kernel"`` where every part is on its Pallas
    kernels, else ``"xla"``; ``why`` the refused parts' clauses, ``part:
    clause`` each ("" where none is refused; a sublayer without a kernel
    says so); ``parts`` the same a part; ``counts`` the SPC counters one
    application moves, the zeros left out.  ``written``: the parts that
    are no kernel but a backward rule written out, each a decision
    function's ``(written, why)``: among ``parts`` as ``"written"``, or
    ``"xla"`` with the clause where the rule is autodiff's; the
    sublayer's ``impl`` and ``why`` speak of Pallas kernels alone."""
    each = {k: {"impl": "kernel" if on else "xla", "why": why}
            for k, (on, why) in parts.items()}
    refused = [f"{k}: {v['why']}" for k, v in each.items()
               if v["impl"] == "xla"]
    each.update({k: {"impl": "written" if on else "xla", "why": why}
                 for k, (on, why) in (written or {}).items()})
    return {"impl": "xla" if refused or not parts else "kernel",
            "why": "; ".join(refused) if parts
            else "the sublayer has no Pallas kernel",
            "parts": each,
            "counts": {k: v for k, v in (counts or {}).items() if v}}


@dataclasses.dataclass(frozen=True, eq=False)
class Sublayer:
    """One kind of sublayer: an operator on the residual stream, or the
    feed-forward behind one.  Neither ``run`` adds the residual."""
    #: the ``otpu_*`` scope it is traced under (``trace.STEP_SCOPES``)
    scope: str
    #: ``(p, x, cfg, *, interpret, at) -> (y, statistics, rows)`` of an
    #: operator, ``at`` the token rows a step samples; of a feed-forward
    #: ``(p, x, cfg, bias, *, interpret, routed)``, ``bias`` the router's
    #: balancing bias (E,) and ``routed`` (the rows it read, its logits)
    #: where it stands before the operator.  ``statistics`` are summed
    #: over a step's shards; ``rows`` is what a check reads by token row
    run: Callable
    #: ``cfg -> {leaf: shape}``, in the tree's order
    shapes: Callable
    #: its part of a layer's group in the parameter tree: an operator's and
    #: a feed-forward's joined by ``_`` where a layer has both ("": a
    #: stacked tree's layers go by the stack's name)
    group: str = ""
    #: what a configuration file calls it: the name a ``layer_types`` file
    #: gives it (``config.LAYER_TYPES``) or its letter in a
    #: ``hybrid_override_pattern`` (``config.HYBRID_LETTERS``); "" where a
    #: file names none (the stacked tree's layers, the feed-forwards a
    #: ``layer_types`` layer ends in)
    name: str = ""
    #: the leaves AdamW does not decay: gains, biases, a head's scalars.
    #: They start at one unless ``starts`` says how
    undecayed: tuple = ()
    #: ``{leaf: (key, shape, cfg) -> array}`` of the leaves that start
    #: neither so nor as normal(0, ``init_std``)
    starts: dict = dataclasses.field(default_factory=dict)
    #: ``cfg -> {key of rows: axes behind the token rows}``; a
    #: feed-forward's keys go into a step's ``sample`` behind ``router_``
    reports: Callable = lambda cfg: {}
    #: the ``checkpoint_name``s a walked layer's checkpoint keeps of it
    keeps: tuple = ()
    #: the gain (d,) of the norm behind it, ahead of its residual add, which
    #: a layer holds under ``cfg.sandwich_norm`` ("": the sublayer is in no
    #: such model); undecayed, it starts at one
    post_norm: str = ""
    #: ``(cfg, b, s, interpret) -> {"impl": "kernel" | "xla", "why",
    #: "parts", "counts"}`` (``held``): what one application of the
    #: sublayer to a residual stream (b, s, d) holds, from the
    #: configuration and the shapes alone, by the decision functions its
    #: ``run`` asks; ``counts`` are the SPC counters it moves
    #: (``train.plan_of`` adds them up over a step's layer applications).
    #: Pure: it traces nothing and calls nothing of ``jax``
    plan: Callable = lambda cfg, b, s, interpret: held()


def zeros(key, shape, cfg):
    return jnp.zeros(shape, jnp.float32)


def log_uniform_1_16(key, shape, cfg):
    """A state-space head's ``A_log`` (arXiv:2405.21060's code): the
    logarithm of a uniform draw from 1 to 16."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))


def uniform_taps(key, shape, cfg):
    """A depthwise convolution's taps and bias: uniform within 1 /
    sqrt(``conv_kernel``), its usual start."""
    bound = cfg.conv_kernel ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
