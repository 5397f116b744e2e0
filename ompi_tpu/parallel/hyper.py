"""The residual path of a model with several residual streams:
manifold-constrained hyper-connections (Xie et al., DeepSeek-AI, *mHC*,
arXiv:2512.24880 section 4, on Zhu et al., *Hyper-Connections*,
arXiv:2409.19606), Xing4.0-29B-A4B's ``hc_mult`` streams.  A token's
residual is a stream ``X`` in R^(n x d).  Around every sublayer ``F`` (which
holds its own pre-norm and adds no residual) the path makes three maps from
the stream itself, reads one row for the sublayer and writes its result
back into all n:

``x' = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)`` (no gain: it folds
into ``phi``); ``m = x' phi``, ``phi`` (n d, n^2 + 2 n), columns ordered
pre | post | res; ``Hpre = sigmoid(alpha_pre m[:n] + b_pre)``; ``Hpost = 2
sigmoid(alpha_post m[n:2n] + b_post)``; ``Hres = Sinkhorn(clip(alpha_res
mat(m[2n:]) + b_res, clamp_min, clamp_max))``, the matrix row major:
``M = exp(.)``, then ``hc_sinkhorn_iters`` times ``M <- M / (colsum(M) +
hc_eps)``, ``M <- M / (rowsum(M) + hc_eps)``, which leaves it doubly
stochastic up to the last sweep's column defect; ``u = Hpre X``; ``y =
F(u)``; **``X <- Hres X + Hpost^T y``**.

Everything here is float32 and elementwise but ``x' phi``, a float32
product at the highest precision (as a router's logits are): none of it is
a ``jnp.einsum`` or a dot at the default precision, which a TPU would make
in bfloat16.  The maps are held token-last, (n, T) and (n, n, T), so that a
sweep's sums run over leading axes and the token rows lie along the lanes.
There is no Pallas kernel: the lines are ``jnp`` that XLA fuses as it will.

**The stream is held stream-major, (b, n, s, d)**, from the embedding to
the head: a stream is ``x[:, j]``, a dense (b, s, d) slab whichever of s and
d the compiler lays along the lanes (on a v5e it chooses s, the maps'
axis).  ``maps``, ``read`` and ``write`` take the slabs one at a time
(``slabs``) and never make a (T, n d) or (T, n, d) view of the stream, pad
it or transpose it: held (b, s, n, d), the compiler's position-minor layout
made the maps' view a transposing copy of the whole stream (2.8 ms of 235
MB at Xing's shape, several a layer and pass) and reached single streams
through pads and broadcasts of all n (``PERF.md`` section 6, PR 74).
``phi``'s rows are stream-major too, so stream j's block is ``phi[j d:(j +
1) d]`` and ``x' phi`` the sum of n (T, d) x (d, n^2 + 2 n) products.

``parallel/model.decoder_layer`` applies it where ``cfg.hc_mult`` > 1,
under the scopes ``otpu_hc`` (whole), ``otpu_hc_maps``, ``otpu_hc_sinkhorn``,
``otpu_hc_read`` and ``otpu_hc_write``; the stream is made from the
embedding (n copies along axis 1) and summed over that axis before the head
by ``parallel/objective.model_loss``.  A layer holds a set of leaves a
sublayer: ``hc1_*`` the operator's, ``hc2_*`` the feed-forward's.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

#: why a plan's row says the path is XLA's
NO_KERNEL = "the residual path has no Pallas kernel"
#: the path's leaves by their last names' endings; ``alpha`` and ``b`` are
#: gates and offsets, which AdamW does not decay
PARTS = ("phi", "alpha", "b")
#: a layer's two sets, the operator's before the feed-forward's
SETS = ("hc1", "hc2")
UNDECAYED = tuple(f"{at}_{part}" for at in SETS for part in ("alpha", "b"))


def shapes(cfg, at: str) -> dict:
    """The leaves of the path around one sublayer (``at``: ``hc1`` or
    ``hc2``), in the tree's order."""
    n, d = cfg.hc_mult, cfg.hidden_size
    maps = n * n + 2 * n
    return {f"{at}_phi": (n * d, maps), f"{at}_alpha": (3,),
            f"{at}_b": (maps,)}


def _gate_start(key, shape, cfg):
    return jnp.full(shape, cfg.hc_gate_start, jnp.float32)


def _offset_start(key, shape, cfg):
    """``b``: normal(0, ``hc_offset_std``), and ``hc_res_diag`` on the
    mixing map's diagonal (at 0 and 0 all zeros: ``Hpre`` one half, ``Hpost``
    one, ``Hres`` the even mix but for what ``phi`` adds through the
    gates)."""
    n = cfg.hc_mult
    diag = jnp.concatenate([jnp.zeros(2 * n, jnp.float32),
                            cfg.hc_res_diag * jnp.eye(n).reshape(-1)])
    return cfg.hc_offset_std * jax.random.normal(key, shape, jnp.float32) \
        + diag


#: how the path's leaves start (``model.leaf_starts``); ``phi`` is a matrix
#: like any other, normal(0, ``init_std``)
STARTS = {f"{at}_{part}": start for at in SETS
          for part, start in (("alpha", _gate_start), ("b", _offset_start))}


def reports(cfg, at: str) -> dict:
    """What the path around one sublayer reports by token row (``{key:
    axes behind the rows}``): the stream it read, ``<at>_in`` (rows, n, d),
    and the three maps it made of it, ``<at>_pre`` and ``<at>_post`` (rows,
    n) and ``<at>_res`` (rows, n, n); ``seen`` cuts them to a step's
    sampled rows itself."""
    return {f"{at}_in": 2, f"{at}_pre": 1, f"{at}_post": 1, f"{at}_res": 2}


def sinkhorn(raw, cfg, iters=None):
    """``Hres`` (n, n, T) from the mixing map before its exponential,
    ``raw`` (n, n, T), rows first: the clamp, the exponential, then
    ``iters`` (``cfg.hc_sinkhorn_iters``) sweeps, each the columns' sums
    divided out, then the rows'."""
    m = jnp.exp(jnp.clip(raw, cfg.mhc_h_res_clamp_min,
                         cfg.mhc_h_res_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters if iters is None else iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + cfg.hc_eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)
    return m


def slabs(x):
    """The n streams of ``x`` (b, n, s, d), each a dense slab (b, s, d).
    A split, not n slices: its transpose is one concatenation on the major
    axis, as ``write``'s result is, where a slice's is a pad of the whole
    stream."""
    b, n, s, d = x.shape
    return [v.reshape(b, s, d) for v in jax.lax.split(x, (1,) * n, axis=1)]


def _total(terms):
    """The terms' sum, without ``sum``'s leading zero: an equation less a
    sum to trace, and the path is traced four times a step."""
    return functools.reduce(operator.add, terms)


def _rows(m, b, s):
    """The leading axis' rows of a token-last map ``m`` (k, T), each (b, s,
    1): a slab's scalar a token.  One split a map: k ``jnp`` indexings trace
    slower, and n^2 of them a write cost set-up 8% (``PERF.md`` section 6,
    PR 74)."""
    return [v.reshape(b, s, 1)
            for v in jax.lax.split(m, (1,) * m.shape[0], axis=0)]


def maps(p, x, cfg, at: str):
    """``(Hpre (n, T), Hpost (n, T), Hres (n, n, T))`` of the stream ``x``
    (b, n, s, d) float32 from the leaves ``<at>_phi``, ``<at>_alpha`` and
    ``<at>_b`` of ``p``, token-last, T = b s.  The norm's mean of squares
    is the n slabs' row sums', ``x' phi`` the sum of the n slabs' products
    with ``phi``'s row blocks (its rows are stream-major), and the norm's
    ``rsqrt``, a token's scalar, scales the (T, n^2 + 2 n) result."""
    b, n, s, d = x.shape
    phi, alpha, off = (p[f"{at}_{part}"] for part in PARTS)
    with jax.named_scope("otpu_hc_maps"):
        rows = [v.reshape(b * s, d) for v in slabs(x)]
        squares = _total(jnp.sum(v * v, axis=-1, keepdims=True)
                         for v in rows)
        m = _total(jnp.dot(v, phi[j * d:(j + 1) * d],
                           precision=jax.lax.Precision.HIGHEST)
                   for j, v in enumerate(rows))
        m = (m * jax.lax.rsqrt(squares / (n * d) + cfg.rms_norm_eps)).T
        gated = lambda i, lo, hi: alpha[i] * m[lo:hi] + off[lo:hi, None]
        pre = jax.nn.sigmoid(gated(0, 0, n))
        post = 2.0 * jax.nn.sigmoid(gated(1, n, 2 * n))
        raw = gated(2, 2 * n, 2 * n + n * n).reshape(n, n, b * s)
    with jax.named_scope("otpu_hc_sinkhorn"):
        res = sinkhorn(raw, cfg)
    return pre, post, res


def read(pre, x):
    """``u = Hpre X`` (b, s, d): a sublayer's input."""
    b, n, s, _ = x.shape
    with jax.named_scope("otpu_hc_read"):
        return _total(w * v for w, v in zip(_rows(pre, b, s), slabs(x)))


def write(res, post, x, y):
    """``Hres X + Hpost^T y`` (b, n, s, d): the stream behind a sublayer
    whose result is ``y`` (b, s, d), its n slabs made one by one and joined
    on the major axis."""
    b, n, s, _ = x.shape
    with jax.named_scope("otpu_hc_write"):
        y, xs = y.astype(jnp.float32), slabs(x)
        mix = _rows(res.reshape(n * n, -1), b, s)
        return jnp.stack([
            w * y + _total(m * v
                           for m, v in zip(mix[i * n:(i + 1) * n], xs))
            for i, w in enumerate(_rows(post, b, s))], axis=1)


def seen(pre, post, res, x, at: str, rows=None) -> dict:
    """What ``reports`` lists, at the token rows ``rows`` (an index into
    the T rows; None: every row).  The stream's rows are taken from each
    slab, so the report costs the rows it holds and not a view of the
    stream."""
    b, n, s, d = x.shape
    pick = (lambda v: v) if rows is None else (lambda v: v[rows])
    return {f"{at}_in": jnp.stack([pick(v.reshape(b * s, d))
                                   for v in slabs(x)], axis=1),
            f"{at}_pre": pick(pre.T), f"{at}_post": pick(post.T),
            f"{at}_res": pick(res.transpose(2, 0, 1))}


def defect(res):
    """The largest defect of mixing maps ``res`` (.., n, n) from doubly
    stochastic: of any row's sum or any column's from one."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, -1) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(res, -2) - 1.0)))


def plan(cfg) -> dict:
    """The path's entry in a plan's row (``train.plan_of``), around one
    sublayer: XLA's, and the SPC counters one application moves."""
    return {"scope": "otpu_hc", "impl": "xla", "why": NO_KERNEL, "parts": {},
            "counts": {"hc_built": 1,
                       "hc_sweeps_built": cfg.hc_sinkhorn_iters}}
