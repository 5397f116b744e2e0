"""A public model's training step (OLMoE, JoyAI-LLM-Flash,
Nemotron-3-Super, LFM2-8B-A1B): widths from a configuration file, not
from the mesh; the kinds of sublayer from its published keys
(``ModelConfig``).  The
parameter tree and its initialisation, the loss over the walked layers
(``parallel/model.decoder_layer``), AdamW, the routers' bias update,
``build_train_step`` and what reads a finished step's ``aux``.  The batch
is sharded over ``dp`` alone; the ``pp`` / ``sp`` / ``tp`` shardings run
in the invented step of ``parallel/flagship.py``, which nothing here
imports.
"""
from __future__ import annotations

import dataclasses
import json
import time
import weakref
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ompi_tpu.base.jaxenv import pallas_interpret
from ompi_tpu.parallel import experts, model
from ompi_tpu.parallel.layers import matmul, rmsnorm_gain
from ompi_tpu.parallel.mesh import MeshSpec
from ompi_tpu.parallel.model import decoder_layer
from ompi_tpu.runtime import spc, trace

#: OLMoE's layer leaves, stacked over the layers this rank holds
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "router", "gate", "up", "down")
GAINS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm", "q_a_norm",
         "kv_a_norm", "enorm", "hnorm", "norm", "gate_norm")
#: a Mamba-2 mixer's leaves that are no matrices: like the gains they are
#: not decayed, and each starts as ``init_model_params`` says
UNDECAYED = GAINS + ("A_log", "D", "dt_bias", "conv_b")
#: a hybrid pattern's letters (nemotron_h) and the group a layer of each
#: kind goes by in the parameter tree; behind them the letters a
#: ``layer_types`` model's layers are walked by (lfm2_moe: an operator,
#: then a feed-forward): a gated short convolution or grouped-query
#: attention, before a dense SwiGLU (small letter) or the experts (capital)
PATTERN_KINDS = {"M": "mamba", "*": "attn", "E": "moe",
                 "c": "conv_dense", "a": "attn_dense",
                 "C": "conv_moe", "A": "attn_moe"}
#: the letters whose layer holds a router
EXPERT_LETTERS = "ECA"
#: ``layer_types``' names and the letter's lower case
OPERATOR_LETTERS = {"conv": "c", "full_attention": "a"}
#: what an operator's sublayer reports by token row goes by its own name
#: into a step's ``sample``; what a router does, behind ``router_``
OPERATOR_SAMPLES = ("ssm_", "conv_", "attn_")
PROBE = 64              # entries of each leaf that a step reports
SAMPLE_ROWS = 16        # token rows whose activations a step reports
#: what a step's ``aux`` holds: small raw statistics, for whoever reads
#: them outside the step (no unit, no scaling).  ``losses`` (the total,
#: cross-entropy, and the load-balancing and router z losses as weighted
#: into the total, then the next-next-token loss likewise where the model
#: has that module); ``loads`` (L, E) the slots an expert received, of
#: all the experts a router knows, a row a sparse layer (the module's
#: last); ``rows`` (T, 2) every row's logsumexp over the vocabulary and
#: its label's logit (``mtp_rows``: the module's head);
#: ``experts`` (L, T, k) the experts every token chose; ``local_slots``
#: the slots that went to experts held here, where the rank holds a
#: share of them; by leaf in ``leaf_names(cfg)``'s order ``grad_sq`` (the
#: gradient's sum of squares), ``grad_probe`` and ``param_probe`` (the
#: gradient and the updated parameter at ``probe_positions``); and
#: ``sample``, what went into and came out of the float32 parts at
#: ``sample_rows`` of each shard: ``router_in`` (L, R, d),
#: ``router_logits`` (L, R, E), ``router_lse`` (L, R) or, of a sigmoid
#: router, ``router_scores`` (L, R, E), ``router_weights`` (L, R, k),
#: ``head_in`` (R, d) (``mtp_head_in``), and of every Mamba-2 layer's
#: first held head what its scan read, whole (``ssm_dt_seq`` (M, T),
#: ``ssm_x_seq`` (M, T, p), ``ssm_b_seq``, ``ssm_c_seq`` (M, T, n)), and
#: made (``ssm_y`` (M, R, p)); of every gated short convolution's first
#: ``model.CONV_SAMPLE`` channels what its gates and taps read, whole
#: (``conv_bcu_seq`` (C, T, B | C | u)), and made (``conv_y`` (C, R, .));
#: of every RoPE attention layer's first query and first key-value head
#: the two side by side before the QK-norm (``attn_qk_in`` (A, R, 2 hd))
#: and behind RoPE (``attn_qk``), so that their precision can be read
#: from one step alone; under ``tie_word_embeddings``
#: ``embed_probe_read`` (PROBE,), whether a probed entry of ``embed``
#: lies in a row the step's tokens read (the others' gradient is the
#: head's alone)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A public model's widths (the keys of its published
    ``config.json``), how much of it this rank holds (``layers_here``:
    the leading dense layers, then sparse ones; ``experts_here`` routed
    experts from ``expert_share`` x ``experts_here`` on, 0 for all;
    ``vocab_here`` rows of the vocabulary, 0 for all) and how it is
    trained (the ``train`` group of the file).  Which sublayers a layer
    has follows from the published keys: ``kv_lora_rank`` set is latent
    attention; layers before ``first_k_dense_replace`` are dense;
    ``scoring_func`` and ``topk_method`` say how a router scores and
    chooses; ``n_shared_experts``; ``num_nextn_predict_layers``.

    nemotron_h's keys: ``hybrid_override_pattern`` set makes every layer
    **one** sublayer, by its letter (``M`` a Mamba-2 mixer, ``*``
    grouped-query attention without RoPE, ``E`` experts in a latent of
    ``moe_latent_size`` with relu2, beside a shared one); the rank holds
    the ``layers_here`` layers from ``first_layer_here`` on, and of each
    mixer the chip's share of its heads (``heads_here`` query heads with
    the key-value heads they read; ``mamba_heads_here`` Mamba heads with
    their B/C groups; 0 for all), as one member of a tensor-parallel
    group holds them.  ``mtp_here`` says how many of the published
    next-n modules are held (-1: all of them).

    lfm2_moe's keys: ``layer_types`` set gives every layer an operator by
    its name (``conv`` the gated short convolution of ``conv_kernel``
    taps, the file's ``conv_L_cache``; ``full_attention`` grouped-query
    attention with a per-head QK-norm and RoPE) and then a feed-forward:
    a dense SwiGLU in the model's first ``first_k_dense_replace`` layers
    (the file's ``num_dense_layers``), the routed experts with no shared
    one after them.  The rank holds the ``layers_here`` layers from
    ``first_layer_here`` on, whole but for the experts.
    ``tie_word_embeddings``: the head reads the embedding matrix, which
    is then the one leaf of both (any model's)."""
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    layers_here: int
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    norm_topk_prob: bool = False
    seq_len: int = 4096
    micro_batch: int = 2
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.001
    lr: float = 4e-4
    warmup_steps: int = 1           # lr rises linearly over these steps
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1
    init_std: float = 0.02
    compute_dtype: str = "bfloat16"
    attn_block: int = 1024
    loss_block_rows: int = 1024
    # DeepSeek-V3's keys (JoyAI-LLM-Flash); OLMoE's file has none of them
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0      # an expert's width, where the
    #                                     dense one is intermediate_size
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    routed_scaling_factor: float = 1.0
    num_nextn_predict_layers: int = 0
    experts_here: int = 0
    expert_share: int = 0
    vocab_here: int = 0
    mtp_loss_coef: float = 0.0
    bias_update_gamma: float = 0.0
    n_group: int = 1
    topk_group: int = 1
    mtp_here: int = -1
    # nemotron_h's keys (Nemotron-3-Super)
    hybrid_override_pattern: str = ""
    first_layer_here: int = 0
    heads_here: int = 0
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_heads_here: int = 0
    n_groups: int = 1               # a mixer's B/C groups (n_group: routers')
    ssm_state_size: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    mlp_hidden_act: str = "silu"
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    # lfm2_moe's keys (LFM2-8B-A1B)
    layer_types: tuple = ()
    tie_word_embeddings: bool = False

    @property
    def pattern_here(self) -> str:
        """The letters of the layers held here ("" without a pattern): a
        ``hybrid_override_pattern``'s own, or a ``layer_types`` model's
        (``PATTERN_KINDS``)."""
        first = self.first_layer_here
        if self.layer_types:
            return "".join(
                OPERATOR_LETTERS[kind] if i < self.first_k_dense_replace
                else OPERATOR_LETTERS[kind].upper()
                for i, kind in enumerate(self.layer_types)
            )[first:first + self.layers_here]
        return self.hybrid_override_pattern[first:first + self.layers_here]

    @property
    def segments(self) -> tuple:
        """The held pattern as runs of like layers, ``(unit, repeats,
        first layer)`` each: a unit is one letter or two different ones
        (``ME`` four times over, then ``M``, ``*``, ``E``), and a run of
        more than one repeat is walked by one ``lax.scan``.  A
        ``layer_types`` model's unit is one letter: its layer holds two
        sublayers already."""
        pattern, out, i = self.pattern_here, [], 0
        while i < len(pattern):
            best = (pattern[i], 1)
            for width in ((1,) if self.layer_types else (1, 2)):
                unit = pattern[i:i + width]
                if len(set(unit)) != width:
                    continue
                n = 1
                while pattern[i + n * width:i + (n + 1) * width] == unit:
                    n += 1
                if n > 1 and n * width > len(best[0]) * best[1]:
                    best = (unit, n)
            out.append(best + (i,))
            i += len(best[0]) * best[1]
        return tuple(out)

    @property
    def n_dense_here(self) -> int:
        return min(self.first_k_dense_replace, self.layers_here)

    @property
    def n_sparse_here(self) -> int:
        if self.pattern_here:
            return sum(c in EXPERT_LETTERS for c in self.pattern_here)
        return self.layers_here - self.n_dense_here

    @property
    def n_mtp_here(self) -> int:
        return self.num_nextn_predict_layers if self.mtp_here < 0 \
            else self.mtp_here

    @property
    def n_routers(self) -> int:
        """Sparse layers in the walk, the next-next-token module's too."""
        return self.n_sparse_here + self.n_mtp_here

    @property
    def n_heads_here(self) -> int:
        return self.heads_here or self.num_attention_heads

    @property
    def n_kv_heads_here(self) -> int:
        """The key-value heads the held query heads read."""
        per_kv = self.num_attention_heads // self.num_key_value_heads
        return max(1, self.n_heads_here // per_kv)

    @property
    def n_mamba_heads_here(self) -> int:
        return self.mamba_heads_here or self.mamba_num_heads

    @property
    def n_groups_here(self) -> int:
        """The B/C groups of the held Mamba heads (0 where the model has
        no mixer)."""
        return self.n_mamba_heads_here * self.n_groups \
            // max(1, self.mamba_num_heads)

    @property
    def n_experts_here(self) -> int:
        return self.experts_here or self.num_experts

    @property
    def first_expert_here(self) -> int:
        return self.expert_share * self.n_experts_here

    @property
    def vocab_rows(self) -> int:
        return self.vocab_here or self.vocab_size

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def __post_init__(self):
        hybrid = bool(self.hybrid_override_pattern)
        # a file's list; a tuple so that the configuration stays hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        typed = bool(self.layer_types)
        per_kv = self.num_attention_heads // max(1, self.num_key_value_heads)
        if not (hybrid or typed) and self.num_key_value_heads \
                != self.num_attention_heads:
            raise NotImplementedError(
                "num_key_value_heads: grouped-query attention is a "
                "hybrid_override_pattern or layer_types model's; this "
                "model's attention has a key-value head a query head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")
        if typed and (hybrid or set(self.layer_types)
                      - set(OPERATOR_LETTERS)):
            raise NotImplementedError(
                f"layer_types {sorted(set(self.layer_types))}: a layer's "
                f"operator is one of {sorted(OPERATOR_LETTERS)}, and the "
                "model has no hybrid_override_pattern beside them")
        if typed and (self.heads_here or self.kv_lora_rank
                      or self.n_shared_experts or self.scoring_func
                      != "sigmoid"):
            raise NotImplementedError(
                f"heads_here {self.heads_here} / kv_lora_rank "
                f"{self.kv_lora_rank} / n_shared_experts "
                f"{self.n_shared_experts} / scoring_func "
                f"{self.scoring_func}: a layer_types model holds its "
                "operators whole (no head is split), attends by grouped "
                "key-value heads, and routes by sigmoid scores under a "
                "bias to experts with no shared one beside them")
        if (hybrid or typed) and (
                self.num_attention_heads % self.num_key_value_heads
                or (self.n_heads_here % per_kv
                    and per_kv % self.n_heads_here)):
            raise NotImplementedError(
                f"heads_here {self.n_heads_here}: the held query heads "
                f"are neither whole key-value heads' ({per_kv} each) nor "
                "a whole part of one's; a key-value head split across "
                "chips is not run")
        if (hybrid or typed) and (
                set(self.pattern_here) - set("M*E" if hybrid else "caCA")
                or len(self.pattern_here) != self.layers_here):
            raise ValueError(
                f"layers_here {self.layers_here} from first_layer_here "
                f"{self.first_layer_here}: not layers of "
                "hybrid_override_pattern's letters ['*', 'E', 'M'] or of "
                "layer_types")
        if "M" in self.pattern_here and (
                self.n_mamba_heads_here * self.n_groups
                % self.mamba_num_heads):
            raise NotImplementedError(
                f"mamba_heads_here {self.n_mamba_heads_here}: not whole "
                f"B/C groups of {self.mamba_num_heads // self.n_groups} "
                "heads; a group split across chips is not run")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: "
                "the routers choose among one group of experts")
        if (hybrid or typed) and self.n_mtp_here:
            raise NotImplementedError(
                f"mtp_here {self.n_mtp_here}: the next-n module of a "
                "hybrid_override_pattern model (mtp_hybrid_override_"
                "pattern) or of a layer_types model is not run; hold 0 "
                "of them")
        if (self.mlp_hidden_act == "relu2") != bool(self.moe_latent_size):
            raise NotImplementedError(
                f"mlp_hidden_act {self.mlp_hidden_act} with moe_latent_size "
                f"{self.moe_latent_size}: relu2 experts are run in a "
                "latent, silu experts on the hidden width")
        if self.n_mtp_here > 1:
            raise NotImplementedError("more than one next-n module")
        if (self.scoring_func, self.topk_method) not in (
                ("softmax", "greedy"), ("sigmoid", "noaux_tc")):
            raise NotImplementedError(
                f"router {self.scoring_func} / {self.topk_method}")
        if self.first_expert_here + self.n_experts_here > self.num_experts:
            raise ValueError("the experts held here are not among the "
                             "router's")


def load_model_config(path: str, **overrides) -> ModelConfig:
    """The configuration file of a public model: the keys of its
    ``config.json`` at the top level, ``layers_here``, and a ``train``
    group; keys this dataclass does not know (the file's prose) are
    left alone.  A model this path cannot run raises."""
    with open(path, encoding="utf-8") as f:
        body = json.load(f)
    hybrid = "hybrid_override_pattern" in body
    typed = "layer_types" in body       # lfm2_moe: its file names no
    #                                     activation, its code runs silu
    act = body.get("mlp_hidden_act") if hybrid else body.get(
        "hidden_act", "silu" if typed else None)
    if act != ("relu2" if hybrid else "silu") or body.get("attention_bias") \
            or body.get("clip_qkv") or body.get("rope_scaling") \
            or body.get("moe_layer_freq", 1) != 1 \
            or ("kv_lora_rank" in body and not body.get("rope_interleave")):
        raise NotImplementedError(
            f"{path}: the model path runs silu experts (relu2 in a "
            "hybrid_override_pattern model), no biases, no clipping, "
            "plain RoPE (on interleaved pairs under latent attention) "
            "and every layer past the dense ones sparse")
    if typed and body.get("conv_bias"):
        raise NotImplementedError(
            f"{path}: conv_bias: the gated short convolution is run "
            "without a bias")
    if typed and not body.get("use_expert_bias"):
        raise NotImplementedError(
            f"{path}: use_expert_bias: a layer_types model's routers "
            "choose under a balancing bias (scoring_func sigmoid, "
            "topk_method noaux_tc)")
    if hybrid and (
            body.get("mamba_hidden_act") != "silu"
            or not body.get("use_conv_bias") or body.get("mamba_proj_bias")
            or body.get("use_bias") or body.get("mlp_bias")
            or body.get("moe_shared_expert_overlap")
            or body.get("sliding_window")
            or body.get("head_dim", 0) * body["num_attention_heads"]
            != body["hidden_size"]
            or body.get("expand", 0) * body["hidden_size"]
            != body["mamba_num_heads"] * body["mamba_head_dim"]):
        raise NotImplementedError(
            f"{path}: a hybrid_override_pattern model is run with silu in "
            "the mixer, a convolution bias and no other, no window, "
            "head_dim = hidden_size / heads and expand x hidden_size = "
            "mamba_num_heads x mamba_head_dim")
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    merged = {**body, **body.get("train", {}), **overrides}
    if "n_routed_experts" in merged:        # DeepSeek-V3's name for it
        merged.setdefault("num_experts", merged["n_routed_experts"])
    if "layer_norm_epsilon" in merged:      # nemotron_h's
        merged.setdefault("rms_norm_eps", merged["layer_norm_epsilon"])
    if typed:                               # lfm2_moe's
        for theirs, ours in (("norm_eps", "rms_norm_eps"),
                             ("num_dense_layers", "first_k_dense_replace"),
                             ("conv_L_cache", "conv_kernel")):
            if theirs in merged:
                merged.setdefault(ours, merged[theirs])
    return ModelConfig(**{k: v for k, v in merged.items() if k in known})


def attention_shapes(cfg: ModelConfig) -> dict:
    d, nh = cfg.hidden_size, cfg.num_attention_heads
    if not cfg.kv_lora_rank:
        return {"ln1": (d,), "wq": (d, d), "wk": (d, d), "wv": (d, d),
                "wo": (d, d), "q_norm": (d,), "k_norm": (d,)}
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {"ln1": (d,), "wq_a": (d, cfg.q_lora_rank),
            "q_a_norm": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, nh * qk),
            "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_a_norm": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (nh * cfg.v_head_dim, d)}


def sparse_layer_shapes(cfg: ModelConfig) -> dict:
    """One sparse layer's leaves: attention, the router over all the
    experts, the experts held here, the shared expert if any."""
    d, f, e = cfg.hidden_size, cfg.expert_width, cfg.n_experts_here
    mlp = {"ln2": (d,), "router": (d, cfg.num_experts),
           "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d)}
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        mlp.update(shared_gate=(d, fs), shared_up=(d, fs),
                   shared_down=(fs, d))
    return {**attention_shapes(cfg), **mlp}


def pattern_layer_shapes(cfg: ModelConfig) -> dict:
    """One layer's leaves by kind (``PATTERN_KINDS``'s) of a
    ``hybrid_override_pattern`` model, on this rank's share of the heads
    and of the experts.  ``mamba``: the pre-norm's gain, ``in_proj`` (d,
    z + x + B + C + dt), the convolution's taps (kernel, x + B + C) and
    bias, ``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's gain,
    ``out_proj``.  ``attn``: the gain, q and o over the held query
    heads, k and v over the key-value heads they read.  ``moe``: the
    gain, the router over all the experts, the latent's two projections,
    the held experts' two matrices in the latent, the shared expert's two
    on the hidden width.

    A ``layer_types`` model's kinds are an operator's leaves and a
    feed-forward's together.  ``conv_*``: the operator norm's gain,
    ``in_proj`` (d, B | C | u), the taps (kernel, d), ``out_proj``;
    ``attn_*``: the gain, q and o over the query heads, k and v over the
    key-value heads, the per-head QK-norm's two gains; ``*_dense``: the
    feed-forward norm's gain and SwiGLU's three matrices; ``*_moe``: the
    gain, the router over all the experts, the held experts' three."""
    d, e = cfg.hidden_size, cfg.n_experts_here
    if cfg.layer_types:
        hd = d // cfg.num_attention_heads
        kv, ff, f = cfg.num_key_value_heads * hd, cfg.intermediate_size, \
            cfg.expert_width
        ops = {"conv": {"ln1": (d,), "in_proj": (d, 3 * d),
                        "conv_w": (cfg.conv_kernel, d), "out_proj": (d, d)},
               "attn": {"ln1": (d,), "wq": (d, d), "wk": (d, kv),
                        "wv": (d, kv), "wo": (d, d), "q_norm": (hd,),
                        "k_norm": (hd,)}}
        ffns = {"dense": {"ln2": (d,), "gate": (d, ff), "up": (d, ff),
                          "down": (ff, d)},
                "moe": {"ln2": (d,), "router": (d, cfg.num_experts),
                        "gate": (e, d, f), "up": (e, d, f),
                        "down": (e, f, d)}}
        return {f"{op}_{ffn}": {**ops[op], **ffns[ffn]}
                for op in ops for ffn in ffns}
    nh, g = cfg.n_mamba_heads_here, cfg.n_groups_here
    inner, bc = nh * cfg.mamba_head_dim, 2 * g * cfg.ssm_state_size
    hd = d // cfg.num_attention_heads
    q, kv = cfg.n_heads_here * hd, cfg.n_kv_heads_here * hd
    lat, f = cfg.moe_latent_size, cfg.expert_width
    fs = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
    return {
        "mamba": {"norm": (d,), "in_proj": (d, 2 * inner + bc + nh),
                  "conv_w": (cfg.conv_kernel, inner + bc),
                  "conv_b": (inner + bc,), "dt_bias": (nh,), "A_log": (nh,),
                  "D": (nh,), "gate_norm": (inner,), "out_proj": (inner, d)},
        "attn": {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                 "wo": (q, d)},
        "moe": {"ln2": (d,), "router": (d, cfg.num_experts),
                "lat_down": (d, lat), "lat_up": (lat, d),
                "up": (e, lat, f), "down": (e, f, lat),
                "shared_up": (d, fs), "shared_down": (fs, d)}}


def model_param_shapes(cfg: ModelConfig) -> dict:
    """The parameter tree's shapes: ``embed``, ``dense`` (the leading
    dense layers, stacked; absent where there are none), ``layers`` (the
    sparse layers, stacked), ``mtp`` (the next-next-token module: two
    norms, the projection of their joined outputs, one sparse layer, a
    last norm; absent where the model has none), ``final_norm``,
    ``head`` (absent under ``tie_word_embeddings``: the head reads
    ``embed``).  Under a ``hybrid_override_pattern`` or ``layer_types``
    ``layers`` holds a group a run of like layers (``cfg.segments``),
    ``l<first layer>``, and in it a group a letter of the run's unit
    (``PATTERN_KINDS``) whose leaves are stacked over the run's repeats."""
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_rows
    stack = lambda n, shapes: {k: (n,) + s for k, s in shapes.items()}
    tree = {"embed": (v, d)}
    last = {"final_norm": (d,)} if cfg.tie_word_embeddings \
        else {"final_norm": (d,), "head": (d, v)}
    if cfg.pattern_here:
        kinds = pattern_layer_shapes(cfg)
        tree["layers"] = {
            f"l{first}": {PATTERN_KINDS[c]: stack(n, kinds[PATTERN_KINDS[c]])
                          for c in unit}
            for unit, n, first in cfg.segments}
        return {**tree, **last}
    if cfg.n_dense_here:
        tree["dense"] = stack(cfg.n_dense_here, {
            **attention_shapes(cfg), "ln2": (d,), "gate": (d, ff),
            "up": (d, ff), "down": (ff, d)})
    tree["layers"] = stack(cfg.n_sparse_here, sparse_layer_shapes(cfg))
    if cfg.n_mtp_here:
        tree["mtp"] = {"enorm": (d,), "hnorm": (d,), "proj": (2 * d, d),
                       **sparse_layer_shapes(cfg), "norm": (d,)}
    return {**tree, **last}


def is_gain(name: str) -> bool:
    """A norm's gain: starts at one, and is not decayed."""
    return name.rsplit(".", 1)[-1] in GAINS


def is_decayed(name: str) -> bool:
    """Whether AdamW decays the leaf: every matrix, and no gain, bias or
    per-head scalar of a mixer (``UNDECAYED``)."""
    return name.rsplit(".", 1)[-1] not in UNDECAYED


def leaf_names(cfg: ModelConfig = None) -> list:
    """(name, path) of every trained leaf in a fixed order.  A sparse
    layer's leaves go by their own names, a dense layer's and the
    module's by ``dense.<leaf>`` and ``mtp.<leaf>``, a pattern's by
    ``l<first layer>.<kind>.<leaf>``; without ``cfg``, OLMoE's."""
    if cfg is None:
        return [("embed", ("embed",))] + [
            (k, ("layers", k)) for k in LAYER_LEAVES] + [
            ("final_norm", ("final_norm",)), ("head", ("head",))]
    out = []

    def walk(sub, path):
        for key, below in sub.items():
            if isinstance(below, tuple):
                out.append((".".join(k for k in path + (key,)
                                     if k != "layers"), path + (key,)))
            else:
                walk(below, path + (key,))

    walk(model_param_shapes(cfg), ())
    return out


def _leaf(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set_leaf(tree, path, leaf) -> None:
    """Put ``leaf`` at ``path`` of a tree being built."""
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def probe_positions(name: str, size: int) -> np.ndarray:
    """The ``PROBE`` flat positions of leaf ``name`` that a step reports
    the gradient and the updated parameter at: drawn once from the
    leaf's name, so any other implementation finds the same ones."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return np.sort(rng.integers(0, size, PROBE)).astype(np.int64)


def sample_rows(rows: int) -> np.ndarray:
    """The ``SAMPLE_ROWS`` rows of a shard of ``rows`` token rows that a
    step reports activations at: evenly spaced, the last row among
    them."""
    n = min(SAMPLE_ROWS, rows)
    return (np.arange(1, n + 1) * rows) // n - 1


def init_model_params(cfg: ModelConfig, seed: int = 0) -> dict:
    """Float32 master parameters drawn on the default device from
    ``seed``: normal(0, ``init_std``) matrices, gains of one.  A Mamba-2
    mixer's other leaves as its authors start them (arXiv:2405.21060's
    code): ``D`` one; ``A_log`` the logarithm of a uniform draw from 1
    to 16; ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly between ``time_step_min`` and ``time_step_max`` (at
    least ``time_step_floor``); the convolution's taps and bias uniform
    within 1 / sqrt(``conv_kernel``), a depthwise convolution's usual
    start."""
    key = jax.random.PRNGKey(seed)

    def draw(name, shape):
        last = name.rsplit(".", 1)[-1]
        if is_gain(name) or last == "D":
            return jnp.ones(shape, jnp.float32)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if last == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1., 16.))
        if last == "dt_bias":
            step = jnp.maximum(cfg.time_step_floor, jnp.exp(
                jax.random.uniform(k, shape, jnp.float32,
                                   np.log(cfg.time_step_min),
                                   np.log(cfg.time_step_max))))
            return step + jnp.log(-jnp.expm1(-step))
        if last in ("conv_w", "conv_b"):
            bound = cfg.conv_kernel ** -0.5
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        return cfg.init_std * jax.random.normal(k, shape, jnp.float32)

    shapes, tree = model_param_shapes(cfg), {}
    for name, path in leaf_names(cfg):
        _set_leaf(tree, path, draw(name, _leaf(shapes, path)))
    return tree


def head_cross_entropy(h, w, labels, block_rows: int, compute_dtype):
    """Summed cross-entropy of ``softmax(h @ w)`` against ``labels``, by
    blocks of ``block_rows`` rows so that no (T, V) array is ever held.
    The forward pass also makes the two gradients (``softmax - onehot``
    is at hand in each block), so the backward pass only scales them:
    the head's logits are computed once a step, not twice.  Returns
    (the sum over rows, per row (logsumexp, the label's logit))."""
    t, d = h.shape
    nblk = t // block_rows
    if nblk * block_rows != t:
        raise ValueError(f"{t} rows are not whole blocks of {block_rows}")

    def run(h, w, labels):
        def block(carry, xs):
            total, dw = carry
            hb, lb = xs
            logits = matmul(hb, w, compute_dtype)            # (rows, V) f32
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
            dlogits = jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
                lb, logits.shape[-1], dtype=jnp.float32)
            dh = matmul(dlogits, w.T, compute_dtype)
            dw = dw + matmul(hb.T, dlogits, compute_dtype, weight=False)
            return (total + jnp.sum(lse - picked), dw), (
                dh, jnp.stack([lse, picked], axis=-1))

        vma = tuple(jax.typeof(h).vma | jax.typeof(labels).vma)
        zero = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
        if vma:         # the scan's carry varies as its inputs do
            zero = jax.lax.pcast(zero, vma, to="varying")
        (total, dw), (dh, rows) = jax.lax.scan(
            block, zero, (h.reshape(nblk, block_rows, d),
                          labels.reshape(nblk, block_rows)))
        return total, rows.reshape(t, 2), dh.reshape(t, d), dw

    @jax.custom_vjp
    def ce(h, w):
        total, rows, _, _ = run(h, w, labels)
        return total, rows

    def fwd(h, w):
        total, rows, dh, dw = run(h, w, labels)
        return (total, rows), (dh, dw)

    def bwd(res, ct):
        dh, dw = res
        return ct[0] * dh, ct[0] * dw

    ce.defvjp(fwd, bwd)
    return ce(h, w)


def layer_checkpoint_policy():
    """What a walked layer's ``jax.checkpoint`` keeps for its backward
    pass: the results an expert block names (``experts.CHECKPOINT_KEEPS``),
    causal attention's forward results (``model.CHECKPOINT_KEEPS``: o and
    the logsumexp) and nothing else, so a layer that names nothing is
    recomputed whole."""
    return jax.checkpoint_policies.save_only_these_names(
        *experts.CHECKPOINT_KEEPS, *model.CHECKPOINT_KEEPS)


def _walk_layers(run, stacked, x, bias, n: int):
    """``n`` like layers in turn: ``run(layer, x, bias row) -> (x,
    out)``; returns (x, the outs stacked).  More than one is a
    ``lax.scan`` over the stacked leaves, so the layer is traced and
    compiled once however many there are."""
    if n == 1:
        x, out = run(jax.tree.map(lambda a: a[0], stacked), x,
                     None if bias is None else bias[0])
        return x, jax.tree.map(lambda a: a[None], out)
    return jax.lax.scan(
        lambda x, xs: run(xs[0], x, xs[1]), x, (stacked, bias))


def _walk_pattern(run, layers, x, bias, cfg: ModelConfig):
    """The held layers of a ``hybrid_override_pattern`` or ``layer_types``
    model in turn, a run of like layers at a time (``cfg.segments``;
    ``layers`` holds a group a run): a run's unit is called once, or
    scanned over its repeats (``_walk_layers``), each of its layers
    through ``run``.  ``bias`` (the held expert layers, E) gives each
    layer with a router its row.  Returns (x, what the layers' ``run``
    gave: the routers' statistics and chosen experts and the sampled
    rows, each stacked in the layers' order over the layers that have
    it; a unit of two letters has no key in both)."""
    stats, chosen, sample, done = {}, [], {}, 0   # done: routers walked
    for unit, n, first in cfg.segments:
        def unit_run(group, x, bias_row, unit=unit):
            out = {}
            for letter in unit:
                x, out[letter] = run(
                    group[PATTERN_KINDS[letter]], x,
                    bias_row if letter in EXPERT_LETTERS else None)
            return x, out

        rows = None
        if set(unit) & set(EXPERT_LETTERS):
            rows, done = bias[done:done + n], done + n
        x, out = _walk_layers(unit_run, layers[f"l{first}"], x, rows, n)
        for letter in unit:
            st, experts, seen = out[letter]
            for into, part in ((stats, st), (sample, seen)):
                for k, v in part.items():
                    into.setdefault(k, []).append(v)
            if experts is not None:
                chosen.append(experts)
    with jax.named_scope("otpu_stats"):
        cat = lambda of: {k: jnp.concatenate(v) for k, v in of.items()}
        return x, (cat(stats), jnp.concatenate(chosen), cat(sample))


def model_loss(params, tokens, labels, cfg: ModelConfig, *, interpret: bool,
               n_global: int, axes: tuple = (), bias=None):
    """The training loss of one micro-batch shard and what a step
    reports of it.  ``n_global`` is the tokens of the whole batch and
    ``axes`` the mesh axes it is sharded over: sums cross them by
    ``psum``, so every shard returns the whole batch's loss.  ``bias``
    holds the routers' balancing biases where they choose under one
    (``layers`` (L, E) and ``mtp`` (1, E)); nothing is differentiated
    with respect to it.  Where the model has a next-next-token module,
    ``labels`` is one position longer than ``tokens``: ``labels[:, i]``
    follows ``tokens[:, i]`` and ``labels[:, i + 1]`` follows that."""
    psum = (lambda a: jax.lax.psum(a, axes)) if axes else (lambda a: a)
    b, s = tokens.shape
    at = sample_rows(b * s)
    bias = bias or {}

    def run(layer, x, bias_row):
        x, st, seen = decoder_layer(layer, x, cfg, interpret=interpret,
                                    bias=bias_row)
        experts = seen.pop("experts", None)
        with jax.named_scope("otpu_stats"):
            # a router's rows at the sampled ones; of a mixer's scan, or
            # a short convolution's input, the sequences whole (``_seq``)
            # and its result at the sampled; q and k around their norm
            # and RoPE at the sampled
            out = (jax.tree.map(psum, st), experts, {
                k if k.startswith(OPERATOR_SAMPLES) else "router_" + k:
                v if k.endswith("_seq") else v[at] for k, v in seen.items()})
        return x, out

    if cfg.layers_here + cfg.n_mtp_here > 1:
        # a layer's activations are recomputed in its backward pass, so
        # that one layer's are held at a time and not every layer's;
        # with one layer there is nothing to save.  Kept from the forward
        # pass are only an expert block's named routing results
        # (``experts.CHECKPOINT_KEEPS``) and causal attention's o and
        # logsumexp (``model.CHECKPOINT_KEEPS``)
        run = jax.checkpoint(run, policy=layer_checkpoint_policy())
    with jax.named_scope("otpu_embed"):
        x = params["embed"][tokens]                          # (b, s, d) f32
    with jax.named_scope("otpu_layers"):
        if cfg.pattern_here:
            x, (st, chosen, sample) = _walk_pattern(
                run, params["layers"], x, bias.get("layers"), cfg)
        else:
            if cfg.n_dense_here:
                x, _ = _walk_layers(run, params["dense"], x, None,
                                    cfg.n_dense_here)
            x, (st, chosen, sample) = _walk_layers(
                run, params["layers"], x, bias.get("layers"),
                cfg.n_sparse_here)
    head_rows = min(cfg.loss_block_rows, b * s)
    # a tied head reads the embedding matrix itself: one leaf, whose
    # gradient is the sum of the gather's and the cross-entropy's
    head = params["embed"].T if cfg.tie_word_embeddings else params["head"]
    with jax.named_scope("otpu_head"):
        h = rmsnorm_gain(x, params["final_norm"], cfg.rms_norm_eps)
        ce_sum, rows = head_cross_entropy(
            h.reshape(b * s, -1), head,
            labels[:, :s].reshape(b * s), head_rows, cfg.compute_dtype)
    routed = cfg.n_sparse_here * n_global   # rows of all routers' logits
    with jax.named_scope("otpu_loss"):
        ce = psum(ce_sum) / n_global
        lb = z = jnp.zeros((), jnp.float32)
        if "prob_sum" in st:
            # HF's load_balancing_loss_func: every layer's rows in one
            # mean
            slots, prob_sum = (jnp.sum(st["slots"], 0),
                               jnp.sum(st["prob_sum"], 0))
            lb = cfg.num_experts * jnp.sum((slots / routed)
                                           * (prob_sum / routed))
            z = jnp.sum(st["z_sum"], 0) / routed
        lb, z = cfg.aux_loss_coef * lb, cfg.z_loss_coef * z
        total = ce + lb + z
    losses, loads = [ce, lb, z], st["slots"]
    with jax.named_scope("otpu_stats"):
        sample["head_in"] = h.reshape(b * s, -1)[at]
    aux = {}
    if cfg.n_mtp_here:
        # DeepSeek-V3's multi-token prediction, depth one: the last
        # layer's output (before the final norm) joined with the next
        # token's embedding, one more sparse layer, the same embedding
        # and head, a cross-entropy against the token after the next
        mtp = params["mtp"]
        with jax.named_scope("otpu_mtp"):
            nxt = rmsnorm_gain(params["embed"][labels[:, :s]], mtp["enorm"],
                               cfg.rms_norm_eps)
            prev = rmsnorm_gain(x, mtp["hnorm"], cfg.rms_norm_eps)
            joined = jnp.concatenate([nxt, prev], -1).reshape(b * s, -1)
            x2 = matmul(joined, mtp["proj"], cfg.compute_dtype
                        ).reshape(b, s, -1)
            with jax.named_scope("otpu_layers"):
                x2, (st2, chosen2, sample2) = _walk_layers(
                    run, jax.tree.map(lambda a: a[None], {
                        k: v for k, v in mtp.items()
                        if k not in ("enorm", "hnorm", "proj", "norm")}),
                    x2, bias.get("mtp"), 1)
            with jax.named_scope("otpu_head"):
                h2 = rmsnorm_gain(x2, mtp["norm"], cfg.rms_norm_eps)
                ce2_sum, aux["mtp_rows"] = head_cross_entropy(
                    h2.reshape(b * s, -1), head,
                    labels[:, 1:].reshape(b * s), head_rows,
                    cfg.compute_dtype)
        with jax.named_scope("otpu_loss"):
            losses.append(cfg.mtp_loss_coef * psum(ce2_sum) / n_global)
            total = total + losses[-1]
        with jax.named_scope("otpu_stats"):
            loads = jnp.concatenate([loads, st2["slots"]])
            chosen = jnp.concatenate([chosen, chosen2])
            sample = {**{k: jnp.concatenate([sample[k], sample2[k]])
                         for k in sample2},
                      "head_in": sample["head_in"],
                      "mtp_head_in": h2.reshape(b * s, -1)[at]}
    if cfg.n_experts_here < cfg.num_experts:
        first = cfg.first_expert_here
        with jax.named_scope("otpu_stats"):
            aux["local_slots"] = jnp.sum(
                loads[:, first:first + cfg.n_experts_here])
    with jax.named_scope("otpu_stats"):
        losses = jnp.stack([total] + losses)
    return total, {"losses": losses, "loads": loads, "rows": rows,
                   "experts": chosen, "sample": sample, **aux}


def adamw(cfg: ModelConfig, name: str, p, g, m, v, t):
    """One AdamW update of one leaf in float32 (decoupled weight decay
    on every matrix: ``is_decayed``; ``t`` counts from 1; the learning
    rate rises linearly over the first ``warmup_steps``)."""
    m = cfg.adam_b1 * m + (1.0 - cfg.adam_b1) * g
    v = cfg.adam_b2 * v + (1.0 - cfg.adam_b2) * g * g
    mhat = m / (1.0 - cfg.adam_b1 ** t)
    vhat = v / (1.0 - cfg.adam_b2 ** t)
    step = mhat / (jnp.sqrt(vhat) + cfg.adam_eps)
    if is_decayed(name):
        step = step + cfg.weight_decay * p
    lr = cfg.lr * jnp.minimum(1.0, t / cfg.warmup_steps)
    return p - lr * step, m, v


def bias_update(cfg: ModelConfig, bias, loads):
    """The routers' balancing biases (L, E) after a step in which the
    experts received ``loads`` (L, E) slots of the whole batch: plus
    ``bias_update_gamma`` where an expert took fewer than the mean,
    minus where more (arXiv:2412.19437 section 2.1.2)."""
    mean = jnp.mean(loads, axis=-1, keepdims=True)
    return bias + cfg.bias_update_gamma * jnp.sign(mean - loads)


_ran_steps = weakref.WeakSet()      # the model steps that ran, while held


def build_train_step(mesh, spec: MeshSpec, model: ModelConfig):
    """Return (step, place) for the public model ``model`` on ``mesh``:
    ``step(state, tokens, labels) -> (state, aux)`` is one optimiser
    step (its program is ``step.jitted``; ``aux`` is described above
    ``ModelConfig``) and ``place(params, tokens, labels)`` puts the
    state and a batch on the mesh.  The widths, the learning rate, the
    compute dtype and what is recomputed are the configuration's."""
    cfg = model
    if spec.pp != 1 or spec.sp != 1 or spec.tp != 1 or spec.ep != 1:
        raise NotImplementedError(
            f"the model path shards the batch over dp only; {spec} asks "
            "for pp, sp, tp or ep > 1 (expert and tensor parallelism of a "
            "public model are a later cell's)")
    if cfg.micro_batch % spec.dp:
        raise ValueError(f"micro_batch {cfg.micro_batch} does not divide "
                         f"over dp = {spec.dp}")
    interpret = pallas_interpret(mesh.devices.flat)
    n_global = cfg.micro_batch * cfg.seq_len
    names = leaf_names(cfg)
    shapes = model_param_shapes(cfg)
    biased = cfg.topk_method == "noaux_tc"
    probes = {n: np.unravel_index(
        probe_positions(n, int(np.prod(_leaf(shapes, path)))),
        _leaf(shapes, path)) for n, path in names}

    def body(state, tokens, labels):
        params, mom, var, t, bias = state

        def loss_fn(ps):
            return model_loss(ps, tokens, labels, cfg, interpret=interpret,
                              n_global=n_global, axes=("dp",), bias=bias)

        # as in ``flagship``'s step: differentiate a per-shard view, so the
        # gradients come back as each shard's partial and the psum below
        # is the one sync
        local = jax.tree.map(
            lambda p: jax.lax.pcast(p, ("dp",), to="varying"), params)
        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(local)
        with jax.named_scope("otpu_grad_sync"):
            grads = jax.tree.map(lambda g: jax.lax.psum(g, "dp"), grads)
        with jax.named_scope("otpu_adamw"):
            t = t + 1
            tf = t.astype(jnp.float32)
        new_p, new_m, new_v = {}, {}, {}
        sq, g_probe, p_probe = [], [], []
        for name, path in names:
            g = _leaf(grads, path)
            with jax.named_scope("otpu_adamw"):
                p, m, v = adamw(cfg, name, _leaf(params, path), g,
                                _leaf(mom, path), _leaf(var, path), tf)
            for tree, leaf in ((new_p, p), (new_m, m), (new_v, v)):
                _set_leaf(tree, path, leaf)
            with jax.named_scope("otpu_stats"):
                sq.append(jnp.sum(g * g))
                g_probe.append(g[probes[name]])
                p_probe.append(p[probes[name]])
        with jax.named_scope("otpu_stats"):
            aux.update(grad_sq=jnp.stack(sq), grad_probe=jnp.stack(g_probe),
                       param_probe=jnp.stack(p_probe))
            if cfg.tie_word_embeddings:
                # which probed entries of the tied matrix lie in a row
                # the gather read this step: the others' gradient is the
                # head's alone
                read = jnp.any(tokens[..., None] == probes["embed"][0],
                               axis=(0, 1))
                aux["embed_probe_read"] = jax.lax.psum(
                    read.astype(jnp.float32), "dp") > 0
        if biased:
            # DeepSeek-V3's auxiliary-loss-free balancing: after the
            # step an expert that took more than the mean of the whole
            # batch's slots is chosen a little less readily, one that
            # took fewer a little more; a sign rule, not AdamW's
            with jax.named_scope("otpu_bias_update"):
                n = cfg.n_sparse_here       # the module's row is the last
                rows = {"layers": aux["loads"][:n], "mtp": aux["loads"][n:]}
                bias = {k: bias_update(cfg, b, rows[k])
                        for k, b in bias.items()}
        return (new_p, new_m, new_v, t, bias), aux

    rep = P()
    batch = P("dp", None)
    rows = P(None, "dp", None)
    sample = {"router_" + k: P(None, "dp") if k == "lse" else rows
              for k in (("in", "logits", "scores", "weights") if biased
                        else ("in", "logits", "lse", "weights"))}
    aux_specs = {"losses": rep, "loads": rep, "rows": batch,
                 "experts": rows, "grad_sq": rep, "grad_probe": rep,
                 "param_probe": rep, "sample": {**sample, "head_in": batch}}
    if "M" in cfg.pattern_here:
        aux_specs["sample"].update(
            {"ssm_" + k: rows for k in ("x_seq", "b_seq", "c_seq", "y")},
            ssm_dt_seq=P(None, "dp"))
    if cfg.layer_types:
        held = set(cfg.pattern_here.lower())
        aux_specs["sample"].update(
            {k: rows for letter, keys in (
                ("c", ("conv_bcu_seq", "conv_y")),
                ("a", ("attn_qk_in", "attn_qk")))
             if letter in held for k in keys})
    if cfg.tie_word_embeddings:
        aux_specs["embed_probe_read"] = rep
    if cfg.n_mtp_here:
        aux_specs["mtp_rows"] = aux_specs["sample"]["mtp_head_in"] = batch
    if cfg.n_experts_here < cfg.num_experts:
        aux_specs["local_slots"] = rep

    def otpu_train_step(state, tokens, labels):
        return shard_map(body, mesh=mesh, in_specs=(rep, batch, batch),
                         out_specs=(rep, aux_specs), check_vma=True)(
            state, tokens, labels)

    jitted = jax.jit(otpu_train_step, donate_argnums=(0,))
    slots = n_global * cfg.num_experts_per_tok * cfg.n_routers
    ssm_tokens = n_global * cfg.pattern_here.count("M")
    trace.bind_profiler()
    count = [0]
    avals = []          # the first call's arguments, as shapes: scopes()

    def step(state, tokens, labels):
        """One optimiser step: ``(state, aux)``; ``state`` is donated.
        Nothing here reads the device: what it computed comes back in
        ``aux`` (``record_step_stats`` reads it, outside any timing)."""
        count[0] += 1
        spc.record("train_steps")
        spc.record("train_tokens", n_global)
        spc.record("moe_token_slots", slots)
        if cfg.n_mtp_here:
            spc.record("train_mtp_tokens", n_global)
        if ssm_tokens:
            spc.record("train_ssm_layer_tokens", ssm_tokens)
        if biased:
            spc.record("moe_bias_updates", cfg.n_routers)
        if count[0] == 1:
            # the first call traces, lowers and compiles (or loads the
            # cached program): counted as every device program's is
            t0 = time.perf_counter()
            avals.append(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                (state, tokens, labels)))
            _ran_steps.add(step)
            out = jitted(state, tokens, labels)
            spc.record("device_program_builds")
            spc.record("device_program_first_call_us",
                       (time.perf_counter() - t0) * 1e6)
            return out
        if trace.profiler_on():
            with jax.profiler.StepTraceAnnotation("otpu.train.step",
                                                  step_num=count[0]):
                return jitted(state, tokens, labels)
        return jitted(state, tokens, labels)

    def scopes():
        """Which instruction of the step's compiled program belongs to
        which ``otpu_*`` scope and pass (``trace.scope_map`` of the
        optimised HLO text; ``trace.STEP_SCOPES`` is the vocabulary).
        Lowers and compiles the step again from the shapes of its first
        call, which holds no buffer; JAX answers both from what the
        first call left in memory, so no second program is loaded (on
        the v5e 0.1 s for OLMoE's step and 0.8 s for JoyAI's 8,563
        instructions, the device's bytes in use unchanged: PR 37).  For
        a reader of a profiler's trace, after the steps it traced:
        ``step()`` never calls it."""
        if not avals:
            raise RuntimeError("scopes(): the step has not run yet, so "
                               "its arguments' shapes are not known")
        return trace.scope_map(jitted.lower(*avals[0]).compile().as_text())

    step.jitted = jitted
    step.scopes = scopes

    def place(params, tokens, labels):
        """``(state, tokens, labels)`` on the mesh: the state is the
        parameters, AdamW's two moments at zero, the step count, and
        the routers' balancing biases at zero where they choose under
        one (not trained: the step moves them by ``bias_update``)."""
        put = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))
        params = jax.tree.map(lambda a: put(a, rep), params)
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        bias = {}
        if biased:
            row = lambda n: put(jnp.zeros((n, cfg.num_experts),
                                          jnp.float32), rep)
            bias = {"layers": row(cfg.n_sparse_here)}
            if cfg.n_mtp_here:
                bias["mtp"] = row(1)
        state = (params, zeros(), zeros(), put(jnp.zeros((), jnp.int32), rep),
                 bias)
        return state, put(tokens, batch), put(labels, batch)

    return step, place


def scopes_of_built_steps() -> list:
    """``step.scopes()`` of every model step this process built, ran
    and still holds: what a trace reader joins a device op to by the
    program's name and the instruction's.  It reads a whole program's
    text a step, so it is for after the measurement."""
    return [step.scopes() for step in list(_ran_steps)]


def record_step_stats(aux) -> int:
    """Read a finished step's expert loads (this blocks on the device:
    call it outside anything timed) and keep SPC ``moe_max_expert_load``
    at the fullest expert's slots of any step read so far.  Where the
    rank holds a share of the experts, the slots that went to held
    experts and to absent ones add to ``moe_local_slots`` and
    ``moe_absent_slots``; ``train_steps_read`` counts the steps read."""
    loads = np.asarray(aux["loads"])
    spc.record("train_steps_read")
    if "local_slots" in aux:
        here = int(np.asarray(aux["local_slots"]))
        spc.record("moe_local_slots", here)
        spc.record("moe_absent_slots", int(loads.sum()) - here)
    fullest = int(loads.max())
    seen = spc.read("moe_max_expert_load")
    if fullest > seen:
        spc.record("moe_max_expert_load", fullest - seen)
    return fullest
