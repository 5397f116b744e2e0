"""The table of a public model's kinds of sublayer, and a decoder layer
made of them.  Each operator (``parallel/attention.py``,
``dsa.py``, ``mamba.py``, ``short_conv.py``, ``gdn.py``)
and each feed-forward (``parallel/experts.py``) declares itself once, as
a ``Sublayer`` beside its function; here they are gathered, joined into
the kinds of layer a configuration holds (``layer_kinds``), and run
(``decoder_layer``).  ``parallel/train.py`` reads the parameter tree, the
initialisation, what AdamW decays, a layer's checkpoint and a step's
``sample`` from here: a new operator is a new module and its name below.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax

from ompi_tpu.parallel import experts, hyper
from ompi_tpu.parallel.attention import (DIFFUSED, FULL, MLA, OLMOE,
                                         SHARED_KV, WINDOW)
from ompi_tpu.parallel.config import HYBRID_LETTERS, LAYER_TYPES
from ompi_tpu.parallel.gdn import GDN
from ompi_tpu.parallel.layers import rmsnorm_gain
from ompi_tpu.parallel.mamba import MIXER, TYPED_MIXER
from ompi_tpu.parallel.short_conv import CONV
from ompi_tpu.parallel.dsa import DSA
from ompi_tpu.parallel.sublayer import Sublayer

OPERATORS = (MIXER, TYPED_MIXER, SHARED_KV, CONV, FULL, GDN, WINDOW, DSA,
             DIFFUSED, OLMOE, MLA)
FEED_FORWARDS = (experts.DENSE, experts.SORTED, experts.SHARED_LOCAL,
                 experts.LATENT)
SUBLAYERS = OPERATORS + FEED_FORWARDS
#: a sublayer by what a configuration file calls it
NAMED = {e.name: e for e in SUBLAYERS if e.name}
#: the leaves AdamW does not decay, by their last name
UNDECAYED = frozenset(
    leaf for e in SUBLAYERS
    for leaf in e.undecayed + ((e.post_norm,) if e.post_norm else ())
) | frozenset(hyper.UNDECAYED)
#: what a walked layer's ``jax.checkpoint`` keeps for its backward pass
CHECKPOINT_KEEPS = tuple(dict.fromkeys(
    name for e in SUBLAYERS for name in e.keeps))


class LayerKind(NamedTuple):
    """A kind of layer: an operator, a feed-forward, or one behind the
    other, each behind its own norm and with its own residual add."""
    name: str                   # its group in the parameter tree
    operator: Sublayer | None
    feed_forward: Sublayer | None
    letter: str = ""            # what a pattern's walk goes by

    @property
    def parts(self) -> tuple:
        """Its sublayers, the operator before the feed-forward."""
        return tuple(e for e in (self.operator, self.feed_forward)
                     if e is not None)

    @property
    def routes(self) -> bool:
        """Whether the layer holds a router."""
        return self.feed_forward not in (None, experts.DENSE)

    def shapes(self, cfg) -> dict:
        """One layer's leaves, in the tree's order: each sublayer's own,
        under ``cfg.sandwich_norm`` the gain of the norm behind it, and
        under ``cfg.hc_mult`` > 1 the residual path's around it
        (``hyper.shapes``: ``hc1_*`` the operator's, ``hc2_*`` the
        feed-forward's)."""
        out = {}
        for part in self.parts:
            out.update(part.shapes(cfg))
            if cfg.sandwich_norm and part.post_norm:
                out[part.post_norm] = (cfg.hidden_size,)
            if cfg.hc_mult > 1:
                out.update(hyper.shapes(cfg, self.path_of(part)))
        return out

    def path_of(self, part) -> str:
        """The residual path's set of leaves around ``part``."""
        return hyper.SETS[part is not self.operator]


@functools.cache
def layer_kinds(cfg) -> dict:
    """The kinds of layer a configuration may hold, by name.  Under a
    ``hybrid_override_pattern`` (nemotron_h) a layer is **one** sublayer,
    by its letter.  Under ``layer_types`` it is an operator by its name,
    then a dense SwiGLU in the model's first ``first_k_dense_replace``
    layers (small letter) and the experts after them (capital).  Any other
    model's parameter tree is two stacks, ``dense`` and ``layers``:
    latent attention where ``kv_lora_rank`` is set, else OLMoE's, before
    the same two.  The experts are ``experts.SHARED_LOCAL`` where
    ``routes_to_held``, else OLMoE's ``experts.SORTED``."""
    if cfg.hybrid_override_pattern:
        return {e.group: LayerKind(e.group, *(
            (e, None) if e in OPERATORS else (None, e)), e.name)
            for e in map(NAMED.get, HYBRID_LETTERS)}
    sparse = experts.SHARED_LOCAL if cfg.routes_to_held else experts.SORTED
    if cfg.layer_types:
        held = sorted({NAMED[t] for t in cfg.layer_types},
                      key=lambda e: e.group)
        return {f"{op.group}_{ffn.group}": LayerKind(
            f"{op.group}_{ffn.group}", op, ffn,
            LAYER_TYPES[op.name] if ffn is experts.DENSE
            else LAYER_TYPES[op.name].upper())
            for op in held for ffn in (experts.DENSE, sparse)}
    attn = MLA if cfg.kv_lora_rank else OLMOE
    return {"dense": LayerKind("dense", attn, experts.DENSE),
            "layers": LayerKind("layers", attn, sparse)}


def kind_of_letter(cfg) -> dict:
    """A walked pattern's kinds of layer by their letters."""
    return {kind.letter: kind for kind in layer_kinds(cfg).values()}


def kinds_here(cfg) -> list:
    """The kind of every layer walked here, in the walk's order."""
    if cfg.pattern_here:
        return [kind_of_letter(cfg)[c] for c in cfg.pattern_here]
    kinds = layer_kinds(cfg)
    return [kinds["dense"]] * cfg.n_dense_here \
        + [kinds["layers"]] * cfg.n_sparse_here


def leaf_starts(cfg) -> dict:
    """``{leaf's last name: (key, shape, cfg) -> array}`` of the leaves of
    ``cfg``'s kinds of layer that start neither at one (the undecayed) nor
    as normal(0, ``init_std``): the sublayers', and under ``cfg.hc_mult``
    > 1 the residual path's gates and offsets."""
    starts = {leaf: start for kind in layer_kinds(cfg).values()
              for part in kind.parts for leaf, start in part.starts.items()}
    return {**starts, **hyper.STARTS} if cfg.hc_mult > 1 else starts


def sample_axes(cfg) -> dict:
    """``{key: axes behind the token rows}`` of what the walked layers
    report by row into a step's ``sample``: an operator's under its own
    names, a router's behind ``router_``, and under ``cfg.hc_mult`` > 1
    the residual path's around each (``hyper.reports``)."""
    out = {("" if part is kind.operator else "router_") + key: axes
           for kind in kinds_here(cfg) for part in kind.parts
           for key, axes in part.reports(cfg).items()}
    if cfg.hc_mult > 1:
        for kind in kinds_here(cfg):
            for part in kind.parts:
                out.update(hyper.reports(cfg, kind.path_of(part)))
    return out


def decoder_layer(p, x, cfg, *, interpret: bool, kind: str, bias=None,
                  at=None, doc=None):
    """One decoder layer of ``kind`` (``layer_kinds(cfg)``'s name of it) on
    the residual stream ``x`` (b, s, d): its operator's residual add, then
    its feed-forward's, as far as it has them; the router's product made
    from the layer's input, before the operator, where
    ``cfg.router_before_attention``.  Under ``cfg.sandwich_norm`` what a
    sublayer returns goes through an RMSNorm of its own (the sublayer's
    ``post_norm`` gain) before it is added, and what is added is
    ``cfg.residual_multiplier`` times it (granitemoehybrid's; at 1.0 the
    plain add).  ``bias`` is the router's balancing bias, ``at`` the token
    rows a step samples and ``doc`` (b, s) a packed row's documents, handed
    to the operator where the model resets at their starts (None: it is
    not handed on, and an operator that knows no documents is as it was).

    Under ``cfg.hc_mult`` n > 1 ``x`` is the n residual streams (b, n, s,
    d) and the layer is ``stream_layer``'s: the sublayers' ``run`` are the
    same, each still gets and returns (b, s, d).

    Returns (x, the sublayers' statistics, what they report by token row:
    a router's under its own keys, an operator's under its prefix)."""
    layer = layer_kinds(cfg)[kind]
    if cfg.hc_mult > 1:
        return stream_layer(p, x, cfg, layer, interpret=interpret, bias=bias,
                            at=at)
    op, ffn = layer.operator, layer.feed_forward
    stats, seen, routed = {}, {}, None
    scaled = (lambda y: y) if cfg.residual_multiplier == 1.0 \
        else (lambda y: cfg.residual_multiplier * y)
    docs = {} if doc is None else {"doc": doc}
    if cfg.router_before_attention and layer.routes:
        with jax.named_scope(ffn.scope):
            rows = x.reshape(-1, x.shape[-1])
            routed = (rows, experts.router_logits(p, rows))
    if op is not None:
        with jax.named_scope(op.scope):
            y, stats, seen = op.run(p, x, cfg, interpret=interpret, at=at,
                                    **docs)
            if cfg.sandwich_norm:
                y = rmsnorm_gain(y, p[op.post_norm], cfg.rms_norm_eps)
            y = scaled(y)
        x = x + y
    if ffn is not None:
        with jax.named_scope(ffn.scope):
            y, routing, made = ffn.run(p, x, cfg, bias, interpret=interpret,
                                       routed=routed)
            if cfg.sandwich_norm:
                y = rmsnorm_gain(y, p[ffn.post_norm], cfg.rms_norm_eps)
            y = scaled(y)
        x = x + y
        stats, seen = {**routing, **stats}, {**made, **seen}
    return x, stats, seen


def stream_layer(p, x, cfg, layer: LayerKind, *, interpret: bool, bias=None,
                 at=None):
    """One decoder layer on ``cfg.hc_mult`` residual streams ``x`` (b, n, s,
    d) float32, stream-major: a stream is the dense slab ``x[:, j]``
    (``parallel/hyper.py``: manifold-constrained hyper-connections).
    Around each sublayer ``F`` the path makes its three maps from the
    stream, ``F`` reads ``u = Hpre X`` (the router too, where ``F`` is the
    experts) and adds no residual, and the stream behind it is ``Hres X +
    Hpost^T F(u)``.  The path's own work lies under ``otpu_hc``,
    the sublayer's under its own scope beside it, the reports' sampled rows
    under ``otpu_stats``.  Returns what
    ``decoder_layer`` does, the path's reports (``hyper.reports``) among
    the rows, cut to the rows ``at`` already."""
    stats, rows = {}, {}
    for part in layer.parts:
        at_path = layer.path_of(part)
        with jax.named_scope("otpu_hc"):
            pre, post, res = hyper.maps(p, x, cfg, at_path)
            u = hyper.read(pre, x)
        with jax.named_scope("otpu_stats"):     # the sampled rows' gathers
            rows.update(hyper.seen(pre, post, res, x, at_path, at))
        with jax.named_scope(part.scope):
            if part is layer.operator:
                y, st, made = part.run(p, u, cfg, interpret=interpret, at=at)
            else:
                y, st, made = part.run(p, u, cfg, bias, interpret=interpret,
                                       routed=None)
        with jax.named_scope("otpu_hc"):
            x = hyper.write(res, post, x, y)
        stats, rows = {**st, **stats}, {**made, **rows}
    return x, stats, rows
