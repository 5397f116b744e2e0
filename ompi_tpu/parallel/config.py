"""A public model's configuration: the keys of its published
``config.json``, how much of the model this rank holds and how it is
trained (``ModelConfig``), and the loader that reads a configuration file
into one (``load_model_config``).  Which sublayers a layer has is read off
the configuration by ``parallel/model.py``'s table of them; here stand the
names and letters a file gives them.  Nothing of ``ompi_tpu.parallel`` is
imported here, so that every module of the model path can name the type it
takes.
"""
from __future__ import annotations

import dataclasses
import json
import math

#: the operators a ``layer_types`` file names (``parallel/model.py``'s table
#: holds an entry under each), and the small letter a layer of each is
#: walked by before a dense SwiGLU (``first_k_dense_replace``); before the
#: experts it is the capital
LAYER_TYPES = {"conv": "c", "full_attention": "a", "linear_attention": "l",
               "sliding_attention": "w", "sparse_attention": "s",
               "block_diffusion_attention": "b", "mamba": "m"}
#: the ``layer_types`` operators that may hold a chip's share of their
#: heads (``heads_here``, ``mamba_heads_here``): each declares how it is
#: split (``attention.gqa_attention``: query heads with the key-value heads
#: they read; ``mamba.mamba_mixer``: heads with their B/C groups, a group
#: some of whose heads are held held whole).  A file's ``attention``
#: (granitemoehybrid's name) is ``full_attention`` (the loader's alias)
SHARE_TYPES = frozenset({"full_attention", "mamba"})
#: a ``hybrid_override_pattern``'s letters (nemotron_h): a layer is one
#: sublayer, a Mamba-2 mixer, attention or the experts
HYBRID_LETTERS = "M*E"
#: the published keys that only a granitemoehybrid file may give: scalars
#: on the embedding, the attention scores, the residual adds, the logits
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")
#: the published keys that only an xing4_0 file may give: the residual
#: path's (manifold-constrained hyper-connections, arXiv:2512.24880)
HYPER_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
              "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
#: the six numbers of a ``rope_scaling`` of type ``yarn``
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's ``0.1 mscale ln(scale) + 1`` (1 where nothing is stretched):
    DeepSeek-V3's ``yarn_get_mscale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A public model's widths (the keys of its published ``config.json``:
    each family's own stand under its comment below, and what a key means
    to a sublayer stands in that sublayer's docstring), how much of the
    model this rank holds and how it is trained (the ``train`` group of
    the file).

    What is held: ``layers_here`` layers from ``first_layer_here`` on (of
    a stacked tree the leading dense layers, then sparse ones);
    ``experts_here`` routed experts from ``expert_share`` x
    ``experts_here`` on (0: all); ``vocab_here`` rows of the vocabulary
    (0: all); of a ``hybrid_override_pattern`` model a chip's share of
    each mixer's heads, as one member of a tensor-parallel group holds
    them (``heads_here`` query heads with the key-value heads they read,
    ``mamba_heads_here`` Mamba heads with their B/C groups; 0: all);
    ``mtp_here`` of the published next-n modules (-1: all); of a stacked
    tree ``dense_here`` of the leading dense layers (-1: as many of the
    ``first_k_dense_replace`` as ``layers_here`` leaves room for), and
    under latent attention (``kv_lora_rank``) ``heads_here`` whole heads, a
    tensor-parallel group's member's share.  A
    ``layer_types`` model holds the same shares of its ``full_attention``
    and ``mamba`` layers (``SHARE_TYPES``: granitemoehybrid's, a
    tensor-parallel pair's member); every other ``layer_types`` operator
    (``conv``, ``linear_attention``, the window, the selection, block
    diffusion) is held whole, and a share beside one is refused.  A Mamba
    B/C group some of whose heads are held is held whole
    (``n_groups_here``).

    Which sublayers a layer has: ``hybrid_override_pattern`` makes every
    layer **one** sublayer, by its letter (``HYBRID_LETTERS``);
    ``layer_types`` gives every layer an operator by its name
    (``LAYER_TYPES``) and then a feed-forward, a dense SwiGLU in the
    model's first ``first_k_dense_replace`` layers and the routed experts
    after them; any other model's layers are attention (latent where
    ``kv_lora_rank`` is set) before the same two.  ``scoring_func`` and
    ``topk_method`` say how a router scores and chooses.  Under
    ``sandwich_norm`` every sublayer is normed behind as well as before,
    ahead of its residual add; under ``total_ut_steps`` the held layers
    are walked that many times over one set of leaves
    (``objective.model_loss``).  Under ``hc_mult`` n > 1 the residual is n
    streams, every sublayer reads a mix of them and writes into all of them
    (``parallel/hyper.py``)."""
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
    embed_init_std: float | None = None     # the embedding's rows, where
    #                                         they start wider than init_std
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
    conv_kernel: int = 4            # taps (lfm2's conv_L_cache,
    #                                 qwen3_next's linear_conv_kernel_dim)
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    mlp_hidden_act: str = "silu"
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    # lfm2_moe's keys (LFM2-8B-A1B)
    layer_types: tuple = ()
    tie_word_embeddings: bool = False   # the head reads ``embed``: one leaf
    # qwen3_next's keys (Qwen3-Next-80B-A3B)
    head_dim: int = 0               # 0: hidden_size / heads
    partial_rotary_factor: float = 1.0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    attn_output_gate: bool = False
    shared_expert_gate: bool = False
    # smallthinker's keys (SmallThinker-21BA3B)
    sliding_window: int = 0         # a sliding_attention layer's window
    # the kinds of layer whose q and k RoPE turns (the file's rope_layout)
    rope_kinds: tuple = ("full_attention", "sliding_attention",
                         "block_diffusion_attention")
    qk_norm: bool = True            # a layer_types model's attention
    router_before_attention: bool = False
    # KeyeVL2's keys (Keye-VL-2.0-30B-A3B): its ``sa_config``
    index_heads: int = 0            # the indexer's query heads
    index_head_dim: int = 0
    index_topk: int = 0             # the keys a query attends to
    index_q_chunk: int = 512        # the score blocks' sizes: they change
    index_kv_chunk: int = 512       # no number
    index_loss_coef: float = 1.0
    # sdar_moe's keys (SDAR-30B-A3B): block diffusion's training pass
    block_length: int = 0           # tokens a block (0: next-token training)
    mask_token_here: int = -1       # the mask token's row of ``vocab_rows``
    noise_seed: int = 0             # the step's noise is keyed by it
    t_min: float = 0.001            # a block's noise level: from here to 1
    # ouro's keys (Ouro-2.6B): a looped model, no router anywhere
    total_ut_steps: int = 0         # passes over the held layers, the final
    #                                 norm, the head and an exit gate behind
    #                                 each (0: one walk, no gate)
    exit_beta: float = 0.0          # the exit distribution's entropy bonus
    sandwich_norm: bool = False     # a norm behind a sublayer, ahead of its
    #                                 residual add
    # granitemoehybrid's keys (granite-4.0-h-micro): four scalars, and the
    # documents of a packed row
    embedding_multiplier: float = 1.0   # on the embedding's rows
    attention_multiplier: float = 0.0   # the scores' scale (0: 1 / sqrt of
    #                                     the head's width)
    residual_multiplier: float = 1.0    # on what a sublayer returns, ahead
    #                                     of its residual add
    logits_scaling: float = 1.0         # the logits are divided by it
    eos_token_here: int = -1        # the end-of-document id's row of
    #                                 ``vocab_rows``: the position behind one
    #                                 starts a document, across whose start
    #                                 no scan, convolution or attention reads
    #                                 (-1: a row is one document)
    # xing4_0's keys (Xing4.0-29B-A4B): the residual path's, YaRN's, and
    # which of the leading dense layers are held
    hc_mult: int = 1                # residual streams (1: ``x = x + y``)
    hc_sinkhorn_iters: int = 20     # sweeps, columns then rows each
    hc_eps: float = 1e-6            # in a sweep's two divisions
    mhc_h_res_clamp_min: float = -30.0  # on the mixing map, ahead of its
    mhc_h_res_clamp_max: float = 30.0   # exponential
    hc_gate_start: float = 0.01     # the path's three gates at step 0
    hc_offset_std: float = 0.0      # its offsets: normal(0, this), and
    hc_res_diag: float = 0.0        # this on the mixing map's diagonal
    rope_scaling: tuple = ()        # a file's ``yarn`` group as sorted
    #                                 (key, value) pairs (() : plain RoPE)
    dense_here: int = -1            # the leading dense layers held

    @property
    def pattern_here(self) -> str:
        """The letters of the layers held here ("" without a pattern): a
        ``hybrid_override_pattern``'s own, or a ``layer_types`` model's
        (``LAYER_TYPES``)."""
        first = self.first_layer_here
        if self.layer_types:
            return "".join(
                LAYER_TYPES[kind] if i < self.first_k_dense_replace
                else LAYER_TYPES[kind].upper()
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
        if self.dense_here >= 0:
            return self.dense_here
        return min(self.first_k_dense_replace, self.layers_here)

    @property
    def n_sparse_here(self) -> int:
        """The held layers with a router: a ``layer_types`` pattern's
        capitals, a ``hybrid_override_pattern``'s ``E``."""
        if self.layer_types:
            return sum(c.isupper() for c in self.pattern_here)
        if self.pattern_here:
            return self.pattern_here.count("E")
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
        no mixer): a group some of whose heads are held is held whole, as
        every holder of its heads holds it."""
        if not self.mamba_num_heads:
            return 0
        return max(1, self.n_mamba_heads_here * self.n_groups
                   // self.mamba_num_heads)

    @property
    def yarn(self):
        """The ``rope_scaling`` group of type ``yarn`` as a dict (None:
        plain RoPE): ``layers.yarn_inv_freq`` and ``yarn_mscale`` read it."""
        return dict(self.rope_scaling) or None

    @property
    def attention_scale(self):
        """The scale of an attention score where the file gives one
        (``attention_multiplier``), or YaRN moves it (DeepSeek-V3's: 1 /
        sqrt of q's width times ``mscale(factor, mscale_all_dim)`` squared);
        None: 1 / sqrt of the head's width."""
        if self.rope_scaling:
            yarn = self.yarn
            m = yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) \
                if yarn["mscale_all_dim"] else 1.0
            return m * m / math.sqrt(self.qk_nope_head_dim
                                     + self.qk_rope_head_dim)
        return self.attention_multiplier or None

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

    @property
    def head_width(self) -> int:
        """An attention head's width: the file's ``head_dim``, else the
        hidden width over the heads."""
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def rotary_width(self):
        """The leading entries of a head that RoPE turns (None: all)."""
        if self.partial_rotary_factor == 1.0:
            return None
        return int(self.head_width * self.partial_rotary_factor)

    @property
    def shared_width(self) -> int:
        """The shared experts' width together (0: none)."""
        return self.n_shared_experts * (
            self.moe_shared_expert_intermediate_size or self.expert_width)

    @property
    def routes_to_held(self) -> bool:
        """Whether a sparse layer is ``experts.moe_shared_local_block``
        (a share of the experts, the router's ``scores`` reported), not
        OLMoE's ``moe_sorted_block``."""
        return bool(self.layer_types) or self.scoring_func == "sigmoid"

    def __post_init__(self):
        hybrid = bool(self.hybrid_override_pattern)
        # a file's list; a tuple so that the configuration stays hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_kinds", tuple(self.rope_kinds))
        object.__setattr__(self, "rope_scaling", tuple(sorted(
            dict(self.rope_scaling or ()).items())))
        typed = bool(self.layer_types)
        per_kv = self.num_attention_heads // max(1, self.num_key_value_heads)
        if not (hybrid or typed) and self.num_key_value_heads \
                != self.num_attention_heads:
            raise NotImplementedError(
                "num_key_value_heads: grouped-query attention is a "
                "hybrid_override_pattern or layer_types model's; this "
                "model's attention has a key-value head a query head")
        if not (typed and self.head_dim) \
                and self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")
        if typed and (hybrid or set(self.layer_types)
                      - set(LAYER_TYPES)):
            raise NotImplementedError(
                f"layer_types {sorted(set(self.layer_types))}: a layer's "
                f"operator is one of {sorted(LAYER_TYPES)}, and the "
                "model has no hybrid_override_pattern beside them")
        whole = sorted(set(self.layer_types) - SHARE_TYPES)
        if typed and (self.kv_lora_rank or (
                (self.heads_here or self.mamba_heads_here) and whole)):
            raise NotImplementedError(
                f"heads_here {self.heads_here} / mamba_heads_here "
                f"{self.mamba_heads_here} / kv_lora_rank "
                f"{self.kv_lora_rank}: a layer_types model attends by "
                f"grouped key-value heads, and holds {whole} whole: only "
                f"{sorted(SHARE_TYPES)} declare how their heads are split")
        if self.head_dim and not (typed or self.kv_lora_rank) \
                and self.head_dim * self.num_attention_heads \
                != self.hidden_size:
            raise NotImplementedError(
                f"head_dim {self.head_dim}: only a layer_types model's "
                "attention heads have a width that is not hidden_size / "
                "heads (under kv_lora_rank the key names the rotary "
                "part and is not read)")
        if (self.attn_output_gate or self.shared_expert_gate
                or self.partial_rotary_factor != 1.0) and not typed:
            raise NotImplementedError(
                f"attn_output_gate {self.attn_output_gate} / "
                f"shared_expert_gate {self.shared_expert_gate} / "
                f"partial_rotary_factor {self.partial_rotary_factor}: "
                "only a layer_types model's attention and shared expert "
                "are gated, and only its RoPE turns a part of the head")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate without a shared expert")
        if "linear_attention" in self.layer_types and (
                min(self.linear_num_key_heads, self.linear_key_head_dim,
                    self.linear_value_head_dim) < 1
                or self.linear_num_value_heads
                % max(1, self.linear_num_key_heads)):
            raise ValueError(
                f"linear_num_value_heads {self.linear_num_value_heads}: "
                "not whole groups of linear_num_key_heads "
                f"{self.linear_num_key_heads} heads of a stated width")
        if (hybrid or typed) and (
                self.num_attention_heads % self.num_key_value_heads
                or (self.n_heads_here % per_kv
                    and per_kv % self.n_heads_here)):
            raise NotImplementedError(
                f"heads_here {self.n_heads_here}: the held query heads "
                f"are neither whole key-value heads' ({per_kv} each) nor "
                "a whole part of one's; a key-value head split across "
                "chips is not run")
        windowed = "sliding_attention" in self.layer_types
        if windowed != bool(self.sliding_window) or (
                windowed and self.sliding_window % self.attn_block):
            raise NotImplementedError(
                f"sliding_window {self.sliding_window}: a window is a "
                "layer_types model's sliding_attention layers', and a whole "
                f"number of attn_block {self.attn_block} positions")
        sparse = "sparse_attention" in self.layer_types
        if sparse != bool(self.index_topk) or (sparse and (
                min(self.index_heads, self.index_head_dim) < 1
                or self.index_head_dim % 2 or not self.qk_norm
                or self.attn_output_gate or windowed
                or self.partial_rotary_factor != 1.0)):
            raise NotImplementedError(
                f"index_topk {self.index_topk} (sa_config): a learned "
                "selection is a layer_types model's dsa "
                "layers', with an indexer of index_heads heads of an even "
                "index_head_dim, on QK-normed attention with RoPE over the "
                "whole head, no output gate and no sliding window beside it")
        diffuses = "block_diffusion_attention" in self.layer_types
        if diffuses != bool(self.block_length) or (diffuses and (
                set(self.layer_types) != {"block_diffusion_attention"}
                or not self.qk_norm or self.attn_output_gate or windowed
                or sparse or self.partial_rotary_factor != 1.0
                or self.tie_word_embeddings
                or self.seq_len % self.block_length
                or self.seq_len % min(self.attn_block, self.seq_len)
                or not 0 <= self.mask_token_here < self.vocab_rows
                or not 0.0 < self.t_min <= 1.0)):
            raise NotImplementedError(
                f"block_length {self.block_length}: block diffusion is a "
                "layer_types model's whose every layer is "
                "block_diffusion_attention (QK-normed attention with RoPE "
                "over the whole head, no gate, no window, no selection, an "
                "untied head), on sequences of whole blocks and whole "
                "attn_block tiles, with mask_token_here a row of the held "
                "vocabulary and t_min in (0, 1]")
        looped = bool(self.total_ut_steps)
        if (looped or self.sandwich_norm or self.exit_beta) and (
                not looped or set(self.layer_types) != {"full_attention"}
                or self.n_sparse_here or self.num_experts or self.qk_norm
                or self.attn_output_gate or self.tie_word_embeddings
                or self.total_ut_steps < 1 or self.exit_beta < 0.0):
            raise NotImplementedError(
                f"total_ut_steps {self.total_ut_steps} / exit_beta "
                f"{self.exit_beta} / sandwich_norm {self.sandwich_norm}: "
                "the looped walk, its exit gate and the norm behind a "
                "sublayer are an ouro model's: every layer full_attention "
                "without a QK-norm or a gate, then a dense SwiGLU, no "
                "router, an untied head, one pass or more")
        if not typed and (not self.qk_norm or self.router_before_attention):
            raise NotImplementedError(
                f"qk_norm {self.qk_norm} / router_before_attention "
                f"{self.router_before_attention}: only a layer_types "
                "model's attention goes without a QK-norm, and only its "
                "router reads the layer's input")
        if (hybrid and set(self.pattern_here) - set(HYBRID_LETTERS)) or (
                (hybrid or typed)
                and len(self.pattern_here) != self.layers_here):
            raise ValueError(
                f"layers_here {self.layers_here} from first_layer_here "
                f"{self.first_layer_here}: not layers of "
                "hybrid_override_pattern's letters ['*', 'E', 'M'] or of "
                "layer_types")
        mixes = "M" in self.pattern_here or "mamba" in self.layer_types
        per_group = self.mamba_num_heads // max(1, self.n_groups)
        if mixes and (
                min(self.mamba_num_heads, self.mamba_head_dim,
                    self.ssm_state_size, per_group) < 1
                or self.mamba_num_heads % self.n_groups
                or (self.n_mamba_heads_here % per_group
                    and (per_group % self.n_mamba_heads_here
                         or hybrid))):
            raise NotImplementedError(
                f"mamba_heads_here {self.n_mamba_heads_here}: neither whole "
                f"B/C groups of {per_group} heads nor (in a layer_types "
                "model) a whole part of one group's, which every holder of "
                "its heads then holds whole")
        scalars = (self.embedding_multiplier, self.attention_multiplier,
                   self.residual_multiplier, self.logits_scaling)
        if scalars != (1.0, 0.0, 1.0, 1.0) and (
                not typed or self.attention_multiplier < 0.0 or min(
                    self.embedding_multiplier, self.residual_multiplier,
                    self.logits_scaling) <= 0.0):
            raise NotImplementedError(
                f"{' / '.join(MULTIPLIERS)} {scalars}: the four scalars are "
                "a layer_types model's (granitemoehybrid's), each above 0")
        if self.eos_token_here != -1 and (
                whole or not typed or self.total_ut_steps
                or not 0 <= self.eos_token_here < self.vocab_rows):
            raise NotImplementedError(
                f"eos_token_here {self.eos_token_here}: the documents of a "
                "packed row are reset in a layer_types model's "
                f"{sorted(SHARE_TYPES)} layers alone ({whole} read across "
                "a document's start), in one walk of them, and the id is a "
                f"row of the held vocabulary's {self.vocab_rows}")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: "
                "the routers choose among one group of experts")
        if self.heads_here and not (hybrid or typed) and (
                not self.kv_lora_rank
                or self.num_attention_heads % self.heads_here):
            raise NotImplementedError(
                f"heads_here {self.heads_here}: a stacked tree holds a share "
                "of its heads under latent attention (kv_lora_rank) alone, "
                f"whole heads that divide the {self.num_attention_heads}")
        if self.rope_scaling and (
                not self.kv_lora_rank
                or {k for k, _ in self.rope_scaling} != set(YARN_KEYS)):
            raise NotImplementedError(
                f"rope_scaling {dict(self.rope_scaling)}: the rotary "
                "frequencies are scaled by YaRN alone, under latent attention "
                f"(kv_lora_rank), by its six numbers {list(YARN_KEYS)}")
        if self.dense_here != -1 and (
                hybrid or typed or not 0 <= self.dense_here <= min(
                    self.first_k_dense_replace, self.layers_here)):
            raise NotImplementedError(
                f"dense_here {self.dense_here}: the leading dense layers "
                "held are a stacked tree's, at most first_k_dense_replace "
                f"{self.first_k_dense_replace} and layers_here "
                f"{self.layers_here}")
        if self.hc_mult != 1:
            for key, on in (
                    ("layer_types", typed),
                    ("hybrid_override_pattern", hybrid),
                    ("total_ut_steps", self.total_ut_steps),
                    ("block_length", self.block_length),
                    ("eos_token_here", self.eos_token_here != -1),
                    ("sandwich_norm", self.sandwich_norm)):
                if on:
                    raise NotImplementedError(
                        f"hc_mult {self.hc_mult} with {key}: a stream of "
                        "hc_mult residuals is a stacked tree's, walked once "
                        "over next-token rows; no file has both and none is "
                        "guessed")
            if self.n_mtp_here:
                raise NotImplementedError(
                    f"hc_mult {self.hc_mult} with mtp_here "
                    f"{self.n_mtp_here}: how the next-n module joins a "
                    "stream of hc_mult is in neither the file nor "
                    "arXiv:2512.24880; hold 0 of them")
            if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1 \
                    or self.hc_eps <= 0.0 or self.mhc_h_res_clamp_min \
                    >= self.mhc_h_res_clamp_max:
                raise ValueError(
                    f"hc_mult {self.hc_mult} / hc_sinkhorn_iters "
                    f"{self.hc_sinkhorn_iters} / hc_eps {self.hc_eps} / "
                    f"mhc_h_res_clamp_min {self.mhc_h_res_clamp_min} / "
                    f"mhc_h_res_clamp_max {self.mhc_h_res_clamp_max}: one "
                    "stream or more, one sweep or more, a guard above 0 and "
                    "a clamp with room")
        if (hybrid or typed) and self.n_mtp_here:
            raise NotImplementedError(
                f"mtp_here {self.n_mtp_here}: the next-n module of a "
                "hybrid_override_pattern model (mtp_hybrid_override_"
                "pattern) or of a layer_types model is not run; hold 0 "
                "of them")
        if (self.mlp_hidden_act == "relu2") != bool(self.moe_latent_size) \
                or self.mlp_hidden_act not in ("relu2", "silu", "relu") \
                or (self.mlp_hidden_act == "relu"
                    and (not typed or self.first_k_dense_replace)):
            raise NotImplementedError(
                f"mlp_hidden_act {self.mlp_hidden_act} with moe_latent_size "
                f"{self.moe_latent_size}: relu2 experts are run in a "
                "latent, silu experts on the hidden width, relu-gated ones "
                "in a layer_types model with no dense layer")
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
    keye = body.get("model_type") == "KeyeVL2"
    sparse = body.get("sa_config")
    if sparse and (not keye or hybrid or "kv_lora_rank" in body
                   or "layer_types" in body or body.get("sliding_window")
                   or body.get("use_sliding_window")):
        raise NotImplementedError(
            f"{path}: sa_config: a learned selection is a KeyeVL2 model's, "
            "on grouped-query attention in every layer; sa_config beside a "
            "sliding_window, or in a latent-attention (kv_lora_rank), "
            "hybrid_override_pattern or other layer_types model is not run")
    next_ = body.get("model_type") == "qwen3_next"
    if next_:
        for key, runs in (("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("use_sliding_window", False)):
            if body.get(key, runs) != runs:
                raise NotImplementedError(
                    f"{path}: {key} {body[key]}: a qwen3_next model is run "
                    "with every layer sparse and no attention window")
        # the family's configuration class: every
        # ``full_attention_interval``-th layer attends in full
        every = body["full_attention_interval"]
        body.setdefault("layer_types", [
            "linear_attention" if (i + 1) % every else "full_attention"
            for i in range(body["num_hidden_layers"])])
    thinker = body.get("model_type") == "smallthinker"
    if thinker:
        windows, turned = body["sliding_window_layout"], body["rope_layout"]
        if not body.get("moe_primary_router_apply_softmax"):
            raise NotImplementedError(
                f"{path}: moe_primary_router_apply_softmax false: a sigmoid "
                "over the chosen logits is not run; the router scores by a "
                "softmax")
        if list(windows) != list(turned):
            raise NotImplementedError(
                f"{path}: rope_layout differs from sliding_window_layout: "
                "RoPE goes by a layer's kind, so a window layer without it "
                "or a full layer with it is a kind that is not run")
        body.setdefault("layer_types", [
            "sliding_attention" if on else "full_attention"
            for on in windows])
    elif "sliding_window_layout" in body or "sliding_window_size" in body \
            or body.get("sliding_window") or "rope_layout" in body:
        raise NotImplementedError(
            f"{path}: sliding_window / sliding_window_layout / rope_layout: "
            "an attention window and RoPE by layer are a smallthinker "
            "model's; a window in a hybrid_override_pattern, latent-"
            "attention or other layer_types model is not run")
    if keye:
        for key, runs in (("mlp_only_layers", []), ("decoder_sparse_step", 1)):
            if body.get(key, runs) != runs:
                raise NotImplementedError(
                    f"{path}: {key} {body[key]}: a KeyeVL2 model is run "
                    "with every layer sparse")
        if not sparse or sparse.get("indexer_num_kv_heads") != 1:
            raise NotImplementedError(
                f"{path}: sa_config {sparse}: a KeyeVL2 model is run under "
                "its learned selection, the indexer with one key a position "
                "(indexer_num_kv_heads 1)")
        body.setdefault("layer_types",
                        ["sparse_attention"] * body["num_hidden_layers"])
    sdar = body.get("model_type") == "sdar_moe"
    if sdar:
        for key, runs in (("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("use_sliding_window", False)):
            if body.get(key, runs) != runs:
                raise NotImplementedError(
                    f"{path}: {key} {body[key]}: an sdar_moe model is run "
                    "with every layer sparse and no attention window")
        if not body.get("block_length") or hybrid or "kv_lora_rank" in body \
                or "layer_types" in body or body.get("sliding_window") \
                or "mask_token_here" not in body:
            raise NotImplementedError(
                f"{path}: an sdar_moe model is trained by block diffusion: "
                "the file gives block_length and mask_token_here, and no "
                "layer_types, window, latent attention or hybrid pattern")
        body.setdefault("layer_types", ["block_diffusion_attention"]
                        * body["num_hidden_layers"])
    elif any(key in body or key in body.get("train", {}) for key in (
            "block_length", "mask_token_here", "noise_seed", "t_min")):
        raise NotImplementedError(
            f"{path}: block_length / mask_token_here / noise_seed / t_min: "
            "block diffusion's training pass is an sdar_moe model's; a "
            f"model_type {body.get('model_type')} model trains next-token")
    ouro = body.get("model_type") == "ouro"
    if ouro:
        layers = body["num_hidden_layers"]
        if body.get("layer_types", ["full_attention"] * layers) \
                != ["full_attention"] * layers or hybrid \
                or "kv_lora_rank" in body or body.get("use_sliding_window") \
                or body.get("total_ut_steps", 0) < 1 or any(
                    key in body for key in ("num_experts", "n_routed_experts",
                                            "num_local_experts")):
            raise NotImplementedError(
                f"{path}: an ouro model is run with every layer "
                "full_attention then a dense SwiGLU, no expert, no window, "
                "and total_ut_steps passes over its layers, one or more")
        body.setdefault("layer_types", ["full_attention"] * layers)
    elif any(key in body or key in body.get("train", {}) for key in (
            "total_ut_steps", "exit_beta", "sandwich_norm")):
        raise NotImplementedError(
            f"{path}: total_ut_steps / exit_beta / sandwich_norm: the looped "
            "walk, its exit gate and the norm behind a sublayer are an ouro "
            f"model's; a model_type {body.get('model_type')} model walks its "
            "layers once")
    granite = body.get("model_type") == "granitemoehybrid"
    if granite:
        # the family's file names its operators ``mamba`` and ``attention``;
        # the second is the path's ``full_attention``
        body["layer_types"] = [
            "full_attention" if kind == "attention" else kind
            for kind in body.get("layer_types", [])]
        if not body["layer_types"] or hybrid or "kv_lora_rank" in body \
                or body.get("num_local_experts") \
                or body.get("num_experts_per_tok") \
                or body.get("position_embedding_type") != "nope" \
                or body.get("normalization_function", "rmsnorm") \
                != "rmsnorm" or not body.get("mamba_conv_bias") \
                or body.get("mamba_proj_bias") \
                or body.get("mamba_expand", 0) * body["hidden_size"] \
                != body["mamba_n_heads"] * body["mamba_d_head"]:
            raise NotImplementedError(
                f"{path}: a granitemoehybrid model is run with its layers "
                "named by layer_types, a dense SwiGLU behind each (no "
                "local expert), no rotary embedding (nope), RMSNorm, a "
                "bias on the mixer's convolution and on no projection, and "
                "mamba_expand x hidden_size = mamba_n_heads x mamba_d_head")
    elif any(key in body or key in body.get("train", {})
             for key in MULTIPLIERS + ("eos_token_here",)):
        raise NotImplementedError(
            f"{path}: {' / '.join(MULTIPLIERS)} / eos_token_here: the four "
            "scalars and the documents of a packed row are a "
            f"granitemoehybrid model's; a model_type {body.get('model_type')}"
            " model has none")
    xing = body.get("model_type") == "xing4_0"
    if xing:
        if "kv_lora_rank" not in body or hybrid or "layer_types" in body \
                or any(key not in body for key in HYPER_KEYS):
            raise NotImplementedError(
                f"{path}: an xing4_0 model is run with latent attention "
                f"(kv_lora_rank) in every layer and its {list(HYPER_KEYS)} "
                "given, without layer_types or a hybrid_override_pattern")
        # the family's file is silent on it; DeepSeek-V3's keys otherwise,
        # whose modelling code turns interleaved pairs (``assumed``)
        body.setdefault("rope_interleave", True)
    else:
        for key in list(body) + list(body.get("train", {})):
            if key.startswith(("hc_", "mhc_")):
                raise NotImplementedError(
                    f"{path}: {key}: the residual path's keys "
                    f"{list(HYPER_KEYS)} (several residual streams under a "
                    "doubly stochastic mixing map) are an xing4_0 model's; a "
                    f"model_type {body.get('model_type')} model adds x + y")
    scaling = body.get("rope_scaling")
    yarn = bool(scaling) and "yarn" in (scaling.get("rope_type"),
                                        scaling.get("type"))
    if yarn:
        if "kv_lora_rank" not in body or set(scaling) - {
                "rope_type", "type"} != set(YARN_KEYS):
            raise NotImplementedError(
                f"{path}: rope_scaling {scaling}: RoPE under YaRN is run with "
                "latent attention (kv_lora_rank) alone, given its six numbers "
                f"{list(YARN_KEYS)} and no other key (truncate as its "
                "default, true; no attention_factor)")
        body["rope_scaling"] = {k: scaling[k] for k in YARN_KEYS}
    elif scaling:
        # M-RoPE's three position components are equal on a text token, so
        # on text ids ``default`` scaling with sections is plain RoPE
        kinds = {scaling.get("rope_type", "default"),
                 scaling.get("type", "default")}
        section = scaling.get("mrope_section")
        if kinds != {"default"} or set(scaling) - {
                "rope_type", "type", "mrope_section"} or not section \
                or 2 * sum(section) != body.get("head_dim"):
            raise NotImplementedError(
                f"{path}: rope_scaling {scaling}: only rope_type default "
                "with an mrope_section that sums to half of head_dim is "
                "run (plain RoPE on text ids), and YaRN under latent "
                "attention; every other scaling of the rotary frequencies "
                "is not")
        body["rope_scaling"] = None
    typed = "layer_types" in body       # lfm2_moe: its file names no
    #                                     activation, its code runs silu
    act = body.get("mlp_hidden_act") if hybrid else body.get(
        "hidden_act", "silu" if typed else None)
    if act != ("relu2" if hybrid else "silu") or body.get("attention_bias") \
            or body.get("clip_qkv") \
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
    if typed and bool(body.get("use_expert_bias")) != (
            body.get("scoring_func", "softmax") == "sigmoid"):
        raise NotImplementedError(
            f"{path}: use_expert_bias {body.get('use_expert_bias')} with "
            f"scoring_func {body.get('scoring_func', 'softmax')}: a "
            "layer_types model's routers choose by sigmoid scores under a "
            "balancing bias (topk_method noaux_tc) or by softmax scores "
            "under none")
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
    if typed:                               # lfm2_moe's, qwen3_next's
        for theirs, ours in (("norm_eps", "rms_norm_eps"),
                             ("num_dense_layers", "first_k_dense_replace"),
                             ("conv_L_cache", "conv_kernel"),
                             ("linear_conv_kernel_dim", "conv_kernel"),
                             ("shared_expert_intermediate_size",
                              "moe_shared_expert_intermediate_size")):
            if theirs in merged:
                merged.setdefault(ours, merged[theirs])
    if thinker:
        for theirs, ours in (("moe_num_primary_experts", "num_experts"),
                             ("moe_num_active_primary_experts",
                              "num_experts_per_tok"),
                             ("moe_ffn_hidden_size", "moe_intermediate_size"),
                             ("sliding_window_size", "sliding_window")):
            merged.setdefault(ours, merged[theirs])
        merged.setdefault("intermediate_size", merged["moe_intermediate_size"])
        merged.setdefault("rope_kinds", sorted(
            {t for t, on in zip(merged["layer_types"], turned) if on}))
        # the model's report's, no published key: no QK-norm, the router
        # reads the layer's input, the experts are relu-gated (ReGLU)
        merged.update(qk_norm=False, router_before_attention=True,
                      mlp_hidden_act="relu")
    if keye:
        for theirs, ours in (("indexer_num_heads", "index_heads"),
                             ("indexer_head_dim", "index_head_dim"),
                             ("topk", "index_topk"),
                             ("q_chunk_size", "index_q_chunk"),
                             ("kv_chunk_size", "index_kv_chunk")):
            if theirs in sparse:
                merged.setdefault(ours, sparse[theirs])
    if granite:
        for theirs, ours in (("mamba_n_heads", "mamba_num_heads"),
                             ("mamba_d_head", "mamba_head_dim"),
                             ("mamba_d_state", "ssm_state_size"),
                             ("mamba_n_groups", "n_groups"),
                             ("mamba_d_conv", "conv_kernel"),
                             ("mamba_chunk_size", "chunk_size"),
                             ("shared_intermediate_size",
                              "intermediate_size")):
            if ours not in overrides:   # a caller's cut goes by either name
                merged[ours] = merged[theirs]
        # what the file's keys imply: every layer's feed-forward the dense
        # SwiGLU and none routed, no QK-norm, and (nope) no layer turned
        merged.update(first_k_dense_replace=merged["num_hidden_layers"],
                      num_experts=0, num_experts_per_tok=0, qk_norm=False,
                      rope_kinds=())
    if ouro:
        # what the file's keys imply: every layer dense and none routed,
        # no QK-norm, RoPE on every layer, and (the report's, no published
        # key) a norm behind every sublayer as well as before it
        merged.update(first_k_dense_replace=merged["num_hidden_layers"],
                      num_experts=0, num_experts_per_tok=0, qk_norm=False,
                      rope_kinds=("full_attention",), sandwich_norm=True)
    if next_:       # its modelling code's, on which config.json is silent
        merged.setdefault("attn_output_gate", True)
        shared = bool(merged.get("moe_shared_expert_intermediate_size"))
        merged.setdefault("n_shared_experts", int(shared))
        merged.setdefault("shared_expert_gate", shared)
    return ModelConfig(**{k: v for k, v in merged.items() if k in known})


