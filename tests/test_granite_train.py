"""granite-4.0-h-micro's padding-free training step on the normal path
(``parallel/train.py``'s model path under ``layer_types``: ``mamba`` and
``attention`` layers, each before a dense SwiGLU, behind four scalar
multipliers, the scan's state, the convolution and attention reset at the
document boundaries of a packed row) against the plain reference
(``parallel/granite_reference.py``: the recurrence one position at a time,
the convolution as shifted adds, dense softmax under the document mask) at
small widths on seeded random weights: hidden 64, 4 query heads of 16 on 2
key-value heads, 4 Mamba heads of 32 with a state of 16 and one B/C group,
chunks of 8, a feed-forward of 96, 64 of 256 ids, rows of 64 tokens; this
chip's share half the heads of each mixer.  Float32 compute meets the
reference at rtol 1e-5."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import granite_reference
from ompi_tpu.parallel import (attention, causal, config, experts, mamba,
                               model, objective, train)
from ompi_tpu.runtime import spc, trace

import built

ref = built.programs(granite_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs",
                      "granite-4.0-h-micro-train-1chip.json")
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             heads_here=2, mamba_n_heads=4, mamba_d_head=32,
             mamba_heads_here=2, mamba_d_state=16, mamba_chunk_size=8,
             shared_intermediate_size=96, vocab_size=256, vocab_here=64,
             eos_token_here=63, layers_here=3, first_layer_here=4, seq_len=64,
             micro_batch=2, attn_block=16, loss_block_rows=32, lr=1e-2,
             warmup_steps=1, compute_dtype="float32")
CLOSE = dict(rtol=1e-5, atol=1e-6)
EOS = 63


def small(**change) -> config.ModelConfig:
    """The cell's file at the tests' widths: layers 4 to 6, ``mam``."""
    return config.load_model_config(CONFIG, **{**SMALL, **change})


F32 = small()
WHOLE = small(heads_here=0, mamba_heads_here=0)     # every head of a layer


def packed(seed, lengths, rows=1, cfg=F32):
    """``rows`` rows of documents of ``lengths`` laid end to end, each but
    the row's last ending in the end-of-document id, two ids over: (inputs,
    labels) as a batch is cut."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, EOS, (rows, sum(lengths) + 2)).astype(np.int32)
    ids[:, np.cumsum(lengths)[:-1] - 1] = EOS
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def spread(cfg, seed):
    """Parameters drawn as ``init_model_params`` would, the matrices at
    0.2, so that every part of a layer moves the loss."""
    return train.init_model_params(
        dataclasses.replace(cfg, init_std=0.2), seed)


def close(got, want, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**CLOSE, **kw})


def near(got, want, rel=2e-5, err_msg=""):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel * 10, err_msg=err_msg,
                               atol=rel * max(1e-30, np.abs(want).max()))


def loss_of(cfg, tokens, labels):
    return lambda p: objective.model_loss(
        p, tokens, labels, cfg, interpret=True, n_global=tokens.size)


def layer_of(params, run: str, kind: str, i: int = 0):
    return jax.tree.map(lambda a: a[i], params["layers"][run][kind])


ref_grads = jax.jit(ref.grads, static_argnums=3)


# -- the file and the tree ------------------------------------------------------
def test_the_cells_file_loads_at_its_published_widths_to_the_parameter():
    cfg = config.load_model_config(CONFIG)
    assert cfg.pattern_here == "mmmmmammmm" \
        and cfg.segments == (("m", 5, 0), ("a", 1, 5), ("m", 4, 6)) \
        and cfg.n_sparse_here == 0 and cfg.num_experts == 0 \
        and cfg.rope_kinds == () and not cfg.qk_norm \
        and cfg.tie_word_embeddings \
        and (cfg.n_mamba_heads_here, cfg.n_groups_here) == (32, 1) \
        and (cfg.n_heads_here, cfg.n_kv_heads_here) == (16, 4) \
        and (cfg.mamba_head_dim, cfg.ssm_state_size, cfg.chunk_size,
             cfg.conv_kernel) == (64, 128, 256, 4) \
        and (cfg.embedding_multiplier, cfg.attention_scale,
             cfg.residual_multiplier, cfg.logits_scaling) \
        == (12, 0.015625, 0.22, 8) \
        and (cfg.vocab_rows, cfg.eos_token_here) == (12544, 12543) \
        and (cfg.seq_len, cfg.micro_batch) == (16384, 1)
    kinds = model.layer_kinds(cfg)
    assert kinds["mamba_dense"].operator is mamba.TYPED_MIXER \
        and kinds["attn_dense"].operator is attention.FULL \
        and all(kinds[k].feed_forward is experts.DENSE and not kinds[k].routes
                for k in ("mamba_dense", "attn_dense"))
    shapes = train.model_param_shapes(cfg)
    sizes = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert shapes["layers"]["l0"]["mamba_dense"]["in_proj"] == (5, 2048, 4384)
    assert sizes(shapes["layers"]["l5"]) == 55_578_624 \
        and sizes(shapes["layers"]["l6"]) == 4 * 63_522_144 \
        and sizes(shapes) == 652_970_080 and "head" not in shapes
    for name in ("l0.mamba_dense.A_log", "l0.mamba_dense.conv_b",
                 "l0.mamba_dense.gate_norm", "l5.attn_dense.ln1",
                 "final_norm"):
        assert not train.is_decayed(name), name
    assert train.is_decayed("l0.mamba_dense.conv_w") \
        and train.is_decayed("embed")
    # A_log starts at the held heads' numbers, in every layer
    a_log = built.params(F32, 0)["layers"]["l0"]["mamba_dense"][
        "A_log"]
    close(a_log, np.log([[1.0, 2.0]]))


@pytest.mark.parametrize("file,change,match", [
    ("lfm2-8b-a1b-train-1chip", dict(residual_multiplier=0.22),
     "residual_multiplier"),
    ("ouro-2.6b-train-1chip", dict(train=dict(logits_scaling=8)),
     "logits_scaling"),
    ("nemotron3-super-train-1chip", dict(eos_token_here=5), "eos_token_here"),
    ("granite-4.0-h-micro-train-1chip", dict(eos_token_here=12544),
     "eos_token_here"),
    ("granite-4.0-h-micro-train-1chip", dict(num_local_experts=8),
     "granitemoehybrid"),
    ("granite-4.0-h-micro-train-1chip",
     dict(position_embedding_type="rope"), "granitemoehybrid"),
    ("granite-4.0-h-micro-train-1chip", dict(mamba_heads_here=3),
     "mamba_heads_here"),
    ("lfm2-8b-a1b-train-1chip", dict(heads_here=8), "heads_here"),
], ids=["a-multiplier-elsewhere", "a-scaling-elsewhere", "documents-elsewhere",
        "eos-outside-the-slice", "local-experts", "rope", "a-split-no-group",
        "a-share-under-conv"])
def test_a_file_this_path_cannot_run_is_refused_by_its_key(tmp_path, file,
                                                           change, match):
    with open(os.path.join(BENCH, "configs", file + ".json"),
              encoding="utf-8") as f:
        body = json.load(f)
    for key, value in change.items():
        body[key] = {**body[key], **value} if key == "train" else value
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match=match):
        config.load_model_config(str(path))


def test_a_share_or_a_document_beside_an_operator_held_whole_is_refused():
    lfm2 = config.load_model_config(os.path.join(
        BENCH, "configs", "lfm2-8b-a1b-train-1chip.json"))
    for change in (dict(heads_here=8), dict(mamba_heads_here=2),
                   dict(eos_token_here=3)):
        with pytest.raises(NotImplementedError, match="conv"):
            dataclasses.replace(lfm2, **change)
    with pytest.raises(NotImplementedError, match="attention_multiplier"):
        dataclasses.replace(config.load_model_config(os.path.join(
            BENCH, "configs", "olmoe-1b-7b-train-1chip.json")),
            attention_multiplier=0.5)


# -- the scan, the convolution and attention under a row's documents ------------
def scan_inputs(seed, s, h=4, p=8, n=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (2, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, s, h))),
            -jnp.exp(0.3 * jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (2, s, 1, n)),
            jax.random.normal(ks[4], (2, s, 1, n)))


def doc_of(s, starts):
    """(2, s) int32: the second row's boundaries one position later."""
    at = np.zeros((2, s), np.int32)
    for t in starts:
        at[0, t:] += 1
        at[1, min(t + 1, s - 1):] += 1
    return jnp.asarray(at)


@pytest.mark.parametrize("s,starts", [
    (32, [8]), (32, [15]), (32, [12]), (32, [9, 13]), (32, []),
    (45, [3, 36]), (29, [16, 24]), (21, [20])],
    ids=["a-chunks-first", "a-chunks-last", "mid-chunk", "two-in-one-chunk",
         "none", "longer-than-three-chunks", "no-multiple-of-the-chunk",
         "the-rows-last-position"])
def test_the_chunked_scan_under_documents_is_the_recurrence(s, starts):
    x, dt, a, b, c = scan_inputs(s, s)
    doc = doc_of(s, starts)
    got, want, got_g, want_g = _scan_both(x, dt, a, b, c, doc)
    close(got, want, atol=1e-5)
    for one, wanted in zip(got_g, want_g):
        assert np.all(np.isfinite(np.asarray(one)))
        near(one, wanted, rel=5e-5)
    if not starts:      # one document: the scan without the argument
        close(got, mamba.ssd_chunked(x, dt, a, b, c, 8), atol=1e-5)


@jax.jit
def _scan_both(x, dt, a, b, c, doc):
    """The chunked scan and the recurrence under ``doc``, and both's
    gradients under one weighting: a program a length."""
    run = lambda x, dt, a, b, c: mamba.ssd_chunked(x, dt, a, b, c, 8, doc)
    want = lambda x, dt, a, b, c: ref.recurrence(
        x, dt, a, b[:, :, 0], c[:, :, 0], doc)
    weigh = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        return (run(x, dt, a, b, c), want(x, dt, a, b, c)) + tuple(
            jax.jit(jax.grad(lambda *args, f=f: jnp.sum(f(*args) * weigh),
                     argnums=range(5)))(x, dt, a, b, c) for f in (run, want))


def test_without_documents_the_scan_and_the_mixer_hold_no_mask():
    """With ``doc`` None the scan and the mixer compare no integer and sum
    over no axis: the program of Nemotron's cell."""
    x, dt, a, b, c = scan_inputs(0, 16)
    text = lambda *doc: str(jax.make_jaxpr(
        lambda *args: mamba.ssd_chunked(*args, 8, *doc))(x, dt, a, b, c))
    # the primitive, not a variable the printer happens to name ``eq``
    assert "= eq " not in text() and "= eq " in text(doc_of(16, [5]))
    nemo = config.load_model_config(
        os.path.join(BENCH, "configs", "nemotron3-super-train-1chip.json"),
        hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=1, heads_here=0, mamba_num_heads=8,
        mamba_head_dim=16, mamba_heads_here=4, n_groups=2, expand=2,
        ssm_state_size=8, chunk_size=8, n_routed_experts=8, experts_here=2,
        num_experts_per_tok=2, moe_latent_size=32, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=24, vocab_size=256, vocab_here=64,
        seq_len=32, compute_dtype="float32")
    shapes = mamba.MIXER.shapes(nemo)
    p = {k: 0.1 * jnp.ones(v) for k, v in shapes.items()}
    text = str(jax.make_jaxpr(lambda p, x: mamba.mamba_mixer(p, x, nemo)[0])(
        p, jnp.ones((1, 32, 64))))
    assert "= eq " not in text and "psum" not in text


def test_a_packed_rows_scan_convolution_and_attention_are_its_documents():
    """Outputs and gradients of the three operators on a packed row are
    those of its documents run one by one."""
    lengths = (21, 11, 32)
    cuts = np.cumsum((0,) + lengths)
    doc = jnp.asarray(np.repeat(np.arange(3), lengths)[None])
    x, dt, a, b, c = (t[:1] if t.ndim > 1 else t for t in scan_inputs(1, 64))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 8)) * 0.5
    xbc = x.reshape(1, 64, -1)[..., :8]
    q, k, v = (jax.random.normal(key, (1, n, 64, 16)) for key, n in zip(
        jax.random.split(jax.random.PRNGKey(3), 3), (4, 2, 2)))

    def ops(x, dt, b, c, xbc, q, k, v, doc):
        """The three operators under ``doc``; with None a row is one
        document: the scan and the taps without the argument, plain causal
        attention."""
        s = q.shape[2]
        return (mamba.ssd_chunked(x, dt, a, b, c, 8, doc),
                mamba.causal_taps(xbc, w, w[0], doc),
                causal.causal_flash_attention(q, k, v, s, True, None, 0.2)
                if doc is None else causal.selected_flash_attention(
                    q, k, v, causal.document_selection(doc), 16, True, None,
                    0.2)[0])

    args = (x, dt, b, c, xbc, q, k, v)
    seq_axis = (1, 1, 1, 1, 1, 2, 2, 2)
    weigh = [jax.random.normal(jax.random.PRNGKey(5 + i), s) for i, s in
             enumerate(((1, 64, 4, 8), (1, 64, 8), (1, 4, 64, 16)))]

    def both(doc, ws, *args):
        total = lambda *t: sum(jnp.sum(o * w) for o, w in zip(
            ops(*t, doc), ws))
        with jax.default_matmul_precision("highest"):
            return ops(*args, doc), jax.jit(jax.grad(
                total, argnums=range(8)))(*args)

    outs, grads = jax.jit(lambda *t: both(doc, weigh, *t))(*args)
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        cut = lambda t, ax: jax.lax.slice_in_dim(t, lo, hi, axis=ax)
        ws = [cut(wt, ax) for wt, ax in zip(weigh, (1, 1, 2))]
        alone, g_alone = jax.jit(lambda *t: both(None, ws, *t))(
            *(cut(t, ax) for t, ax in zip(args, seq_axis)))
        for got, want, ax in zip(outs, alone, (1, 1, 2)):
            close(cut(got, ax), want, atol=2e-5)
        for got, want, ax in zip(grads, g_alone, seq_axis):
            near(cut(got, ax), want, rel=5e-5)


# -- a sublayer and a layer against the reference --------------------------------
def test_the_mixer_the_attention_sublayer_and_a_layer_are_the_references():
    cfg = F32
    params = spread(cfg, 1)
    tokens, _ = packed(2, (21, 11, 32), rows=2)
    doc = ref.documents(tokens, EOS)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    m = layer_of(params, "l0", "mamba_dense")
    a = layer_of(params, "l1", "attn_dense")
    norm = lambda p, name: ref._norm(x, p[name], cfg.rms_norm_eps)
    attend = lambda c: attention.FULL.run(a, x, c, interpret=True, doc=doc)
    layer = lambda p, kind, c=cfg: model.decoder_layer(
        p, x, c, interpret=True, kind=kind, doc=doc)[0]

    @jax.jit
    def both():
        with jax.default_matmul_precision("highest"):
            return {
                "mixer": (mamba.mamba_mixer(m, x, cfg, doc=doc),
                          ref.mixer(m, norm(m, "norm"), doc, cfg)),
                "attention": (attend(cfg),
                              ref.attention(a, norm(a, "ln1"), doc, cfg)),
                "layers": [(layer(p, kind), ref.layer(letter, p, x, doc, cfg))
                           for p, kind, letter in ((m, "mamba_dense", "m"),
                                                   (a, "attn_dense", "a"))],
                # the scale is the file's 1 / 64, and not 1 / sqrt(16)
                "sharper": attend(dataclasses.replace(
                    cfg, attention_multiplier=0.25))[0],
                # the multiplier scales a sublayer's share of the stream
                "shares": [layer({**m, "down": m["down"] * 0}, "mamba_dense",
                                 c) - x for c in (cfg, dataclasses.replace(
                                     cfg, residual_multiplier=1.0))]}

    out = both()
    (got, _, seen), want = out["mixer"]
    close(got, want, atol=1e-5)
    assert set(seen) == set(mamba.TYPED_MIXER.reports(cfg))
    (got, _, seen), want = out["attention"]
    close(got, want, atol=1e-5)
    # no rotary embedding: q and k leave their projections as they are
    close(seen["attn_qk"], seen["attn_qk_in"], rtol=0, atol=0)
    for got, want in out["layers"]:
        close(got, want, atol=1e-5)
    assert np.abs(np.asarray(out["sharper"] - out["attention"][0][0])
                  ).max() > 1e-2
    close(out["shares"][0], 0.22 * out["shares"][1], rtol=1e-5, atol=1e-5)


# -- the share: a tensor-parallel pair's two members add up ----------------------
def mixer_shares(p, cfg):
    """The two head shares of an uncut mixer's leaves, stacked: B, C and
    their part of the convolution whole in both."""
    nh, hd, n = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    inner = nh * hd
    half = lambda t, i, per: t[..., i * per:(i + 1) * per]
    out = []
    for i in range(2):
        z, x = (half(p["in_proj"][:, at:at + inner], i, inner // 2)
                for at in (0, inner))
        bc = p["in_proj"][:, 2 * inner:2 * inner + 2 * n]
        dt = half(p["in_proj"][:, 2 * inner + 2 * n:], i, nh // 2)
        out.append({
            "norm": p["norm"],
            "in_proj": jnp.concatenate([z, x, bc, dt], -1),
            "conv_w": jnp.concatenate([half(p["conv_w"][:, :inner], i,
                                            inner // 2),
                                       p["conv_w"][:, inner:]], -1),
            "conv_b": jnp.concatenate([half(p["conv_b"][:inner], i,
                                            inner // 2),
                                       p["conv_b"][inner:]], -1),
            **{k: half(p[k], i, nh // 2) for k in ("dt_bias", "A_log", "D")},
            "gate_norm": half(p["gate_norm"], i, inner // 2),
            "out_proj": p["out_proj"][i * inner // 2:(i + 1) * inner // 2]})
    return jax.tree.map(lambda *t: jnp.stack(t), *out)


def attention_shares(p, cfg):
    hd = cfg.head_width
    q, kv = cfg.num_attention_heads * hd // 2, cfg.num_key_value_heads * hd // 2
    return jax.tree.map(lambda *t: jnp.stack(t), *[
        {"ln1": p["ln1"], "wq": p["wq"][:, i * q:(i + 1) * q],
         "wk": p["wk"][:, i * kv:(i + 1) * kv],
         "wv": p["wv"][:, i * kv:(i + 1) * kv],
         "wo": p["wo"][i * q:(i + 1) * q]} for i in range(2)])


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's share test: the two head shares of a Mamba layer under an
    axis named ``tp``, the gated norm's sums crossing it by ``psum``, B, C,
    their convolution and the SwiGLU counted once, add up to the uncut
    reference's layer; the attention layer's two shares likewise.  Without
    the ``psum`` each chip norms its own 64 channels and the sum is another
    function: its widest entry lies 0.1 of the layer's RMS and more from
    the reference's."""
    params = spread(WHOLE, 7)
    tokens, _ = packed(8, (21, 11, 32), rows=2)
    doc = ref.documents(tokens, EOS)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    m, a = layer_of(params, "l0", "mamba_dense"), \
        layer_of(params, "l1", "attn_dense")
    by = WHOLE.residual_multiplier
    over = lambda axis: jnp.sum(jax.vmap(
        lambda p: mamba.mamba_mixer(p, x, F32, doc=doc, tp_axis=axis)[0],
        axis_name="tp")(mixer_shares(m, WHOLE)), 0)

    @jax.jit
    def both():
        with jax.default_matmul_precision("highest"):
            x1 = x + by * over("tp")    # the shares' sum on the stream
            return {
                "pair": over("tp"), "apart": over(None),
                "mixer": ref.mixer(m, ref._norm(
                    x, m["norm"], WHOLE.rms_norm_eps), doc, WHOLE),
                # the whole layer: the SwiGLU once
                "layer": (x1 + by * experts.DENSE.run(
                    m, x1, WHOLE, None, interpret=True)[0],
                    ref.layer("m", m, x, doc, WHOLE)),
                # the reference's own pair says the same
                "ref_pair": jnp.sum(jax.vmap(lambda p: ref.mixer(
                    p, ref._norm(x, p["norm"], F32.rms_norm_eps), doc, F32,
                    "tp"), axis_name="tp")(mixer_shares(m, WHOLE)), 0),
                "heads": (jnp.sum(jax.vmap(lambda p: attention.FULL.run(
                    p, x, F32, interpret=True, doc=doc)[0])(
                        attention_shares(a, WHOLE)), 0), ref.attention(
                            a, ref._norm(x, a["ln1"], WHOLE.rms_norm_eps),
                            doc, WHOLE))}

    out = both()
    want = out["mixer"]
    close(out["pair"], want, atol=1e-5)
    close(out["ref_pair"], want, atol=1e-5)
    rms = float(jnp.sqrt(jnp.mean(want ** 2)))
    apart = float(jnp.max(jnp.abs(out["apart"] - want))) / rms
    assert apart > 0.1, apart
    close(*out["layer"], atol=1e-5)
    close(*out["heads"], atol=1e-5)


# -- the whole step ---------------------------------------------------------------
def test_the_loss_and_every_gradient_are_the_references():
    cfg = small(layers_here=4, first_layer_here=3)      # mmam: a scanned run
    assert cfg.segments == (("m", 2, 0), ("a", 1, 2), ("m", 1, 3))
    tokens, labels = packed(0, (21, 11, 30, 2), rows=2)
    params = spread(cfg, 3)
    (total, aux), g = jax.jit(jax.value_and_grad(
        loss_of(cfg, tokens, labels), has_aux=True))(params)
    (want, rows), g_want = ref_grads(params, tokens, labels, cfg)
    close(total, want)
    close(aux["losses"], [want, want, 0.0, 0.0])
    close(aux["rows"], np.asarray(rows).reshape(-1, 2), atol=1e-5)
    assert aux["loads"].shape == (0, 0) \
        and aux["experts"].shape == (0, 128, 0) \
        and aux["sample"]["ssm_y"].shape == (3, 16, 32) \
        and aux["sample"]["ssm_dt_seq"].shape == (3, 128) \
        and aux["sample"]["attn_qk"].shape == (1, 16, 32)
    np.testing.assert_array_equal(
        aux["doc"], [np.repeat(np.arange(4), (21, 11, 30, 2))] * 2)
    for name, path in train.leaf_names(cfg):
        near(train._leaf(g, path), train._leaf(g_want, path), err_msg=name)
    # the tied matrix's gradient is the sum of both uses': the gather's
    # alone and the head's alone add up to it
    embed = lambda e, h: ref.loss_parts(
        {**params, "embed": e}, tokens, labels, cfg)[0] if h is None else None

    def split(e_gather, e_head):
        doc = ref.documents(tokens, EOS)
        x = cfg.embedding_multiplier * e_gather[tokens]
        for letter, p in ref._layers_of(params, cfg, ref.KINDS):
            x = ref.layer(letter, p, x, doc, cfg)
        logits = ref._norm(x, params["final_norm"], cfg.rms_norm_eps) \
            @ e_head.T / cfg.logits_scaling
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.mean(lse - jnp.take_along_axis(
            logits, labels[:, :64, None], -1)[..., 0])

    with jax.default_matmul_precision("highest"):
        by_gather, by_head = jax.jit(jax.grad(split, argnums=(0, 1)))(
            params["embed"], params["embed"])
    near(g["embed"], by_gather + by_head)
    assert min(np.abs(np.asarray(t)).max() for t in (by_gather, by_head)) \
        > 1e-5


def test_a_packed_rows_loss_and_gradients_are_its_documents_one_by_one():
    lengths = (21, 11, 32)
    tokens, labels = packed(4, lengths)
    params = spread(F32, 5)
    # a row a document, one block of its own length
    one = dataclasses.replace(F32, eos_token_here=-1, attn_block=64)
    grad_of = lambda t, l, cfg=F32: jax.jit(jax.value_and_grad(
        loss_of(cfg, t, l), has_aux=True))(params)
    (total, aux), g = grad_of(tokens, labels)
    cuts = np.cumsum((0,) + lengths)
    parts = [grad_of(tokens[:, lo:hi], labels[:, lo:hi], one)
             for lo, hi in zip(cuts[:-1], cuts[1:])]
    share = [n / 64.0 for n in lengths]
    close(total, sum(w * p[0][0] for w, p in zip(share, parts)))
    close(aux["rows"], np.concatenate([np.asarray(p[0][1]["rows"])
                                       for p in parts]), atol=1e-5)
    for name, path in train.leaf_names(F32):
        near(train._leaf(g, path), sum(
            w * train._leaf(p[1], path) for w, p in zip(share, parts)),
            rel=5e-5, err_msg=name)
    # and the same row read as one document is another function
    assert abs(float(jax.jit(loss_of(one, tokens, labels))(params)[0])
               - float(total)) > 1e-4


def test_the_scopes_and_counters_are_named():
    """No new scope: the resets lie under the mixer's, the mask's packing
    under attention's, the documents under the embedding's."""
    spc.init()
    before = spc.read("doc_built")
    tokens, labels = packed(0, (21, 11, 32), rows=2)
    text = jax.jit(loss_of(F32, tokens, labels)).lower(
        built.params(F32, 0)).as_text(debug_info=True)
    for scope in ("otpu_ssm_scan", "otpu_ssm_conv", "otpu_attention",
                  "otpu_embed", "otpu_dense_mlp"):
        assert scope in text and scope in trace.STEP_SCOPES, scope
    # lowering moves no counter; the plan counts what every layer
    # application makes under the documents: two mixers' scan and
    # convolution and one attention layer's mask
    assert spc.read("doc_built") == before
    plan = train.plan_of(F32, *tokens.shape)
    assert [row["layers"] for row in plan["rows"]] == ["1", "2", "3"]
    assert plan["counts"]["doc_built"] == 2 * 2 + 1
    assert plan["counts"]["ssm_scan_built"] == 2


@pytest.mark.parametrize("dtype,written", [("bfloat16", True),
                                           ("float32", False)])
def test_the_feed_forwards_backward_rule_stays_under_its_scopes(dtype,
                                                                written):
    """``layers.swiglu``'s written backward rule (a ``compute_dtype``
    narrower than float32, PR 72): a layer's eight products, the two
    recomputed pre-activations among them, lie under ``otpu_dense_mlp`` in
    the backward pass with one ``optimization_barrier``, and none under
    ``rematted_computation``, where the operands' casts still are, under
    ``otpu_cast`` like their transposes.  Under float32 autodiff's
    recomputed pass makes the pre-activations as before."""
    cfg = small(compute_dtype=dtype)
    tokens, labels = packed(0, (21, 11, 32), rows=2)
    text = jax.jit(jax.grad(
        lambda ps: loss_of(cfg, tokens, labels)(ps)[0])).lower(
        built.params(cfg, 0)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    found = {}
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        path = locs.get(at.group(1), "") if at else ""
        if "/otpu_dense_mlp/" not in path:
            continue
        _, which, _ = trace.scope_of_path(path)
        op = path.rsplit("/", 1)[1]
        cast = "otpu_cast/" if "/otpu_cast/" in path else ""
        found[which, cast + op] = found.get((which, cast + op), 0) + 1
    layers_here = 3
    assert found["forward", "dot_general"] == 3 * layers_here
    assert found.get(("backward", "optimization_barrier"), 0) \
        == written * layers_here
    if written:
        assert found["backward", "dot_general"] == 8 * layers_here
        assert ("remat", "dot_general") not in found
    else:
        assert found["remat", "dot_general"] >= 2 * layers_here
        assert found["backward", "dot_general"] == 6 * layers_here
    # a layer's three matrices: cast in the forward pass, cast again in the
    # recomputed one, the gradients' casts back in the backward pass
    # (float32 leaves need none)
    for which in ("forward", "remat", "backward"):
        assert found.get((which, "otpu_cast/convert_element_type"), 0) \
            == written * 3 * layers_here, which
