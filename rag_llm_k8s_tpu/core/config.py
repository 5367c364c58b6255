"""Typed configuration for the whole framework.

The reference scatters its configuration across env vars and hardcoded constants
(survey: /root/reference/llm/rag.py:18-20,35-39,114,164,172; llm/download_model.py:5,14-25;
web/app.py:5). Here every knob lives in one dataclass tree; the defaults reproduce the
reference's behavior exactly, and ``AppConfig.from_env()`` applies the same env-var
overrides the reference supports (``MODEL_PATH``, ``LLM_SERVICE_URL``, ``HF_TOKEN``)
plus TPU-specific ones.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtype policy
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DTypePolicy:
    """TPU dtype policy: bf16 storage/compute, fp32 accumulation and logits.

    The MXU natively multiplies bf16 with fp32 accumulation; keeping weights and
    activations in bf16 halves HBM traffic (the usual TPU bottleneck) vs the
    reference's fp32-on-CPU (rag.py:24 loads fp32 ⇒ ~32 GB).
    """

    param_dtype: jnp.dtype = jnp.bfloat16
    compute_dtype: jnp.dtype = jnp.bfloat16
    accum_dtype: jnp.dtype = jnp.float32
    logits_dtype: jnp.dtype = jnp.float32

    @classmethod
    def fp32(cls) -> "DTypePolicy":
        """Full-precision policy for CPU-hosted numerics tests."""
        return cls(
            param_dtype=jnp.float32,
            compute_dtype=jnp.float32,
            accum_dtype=jnp.float32,
            logits_dtype=jnp.float32,
        )


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh over the TPU slice's ICI links.

    Axes (in order): ``dp`` (data parallel, batched concurrent requests),
    ``sp`` (sequence/context parallel — ring attention), ``tp`` (tensor
    parallel — the core sharding for Llama-3.1-8B over a v5e-8).

    The reference has no parallelism at all (survey §2c: replicas=1, one CPU
    process); here TP over ICI is the default and dp/sp are first-class.
    ``tp = -1`` means "all remaining devices".
    """

    dp: int = 1
    sp: int = 1
    tp: int = -1
    axis_names: Tuple[str, str, str] = ("dp", "sp", "tp")

    def resolved(self, n_devices: int) -> Tuple[int, int, int]:
        dp, sp, tp = self.dp, self.sp, self.tp
        if tp == -1:
            known = dp * sp
            if n_devices % known != 0:
                raise ValueError(
                    f"n_devices={n_devices} not divisible by dp*sp={known}"
                )
            tp = n_devices // known
        if dp * sp * tp != n_devices:
            raise ValueError(
                f"mesh {dp}x{sp}x{tp} != n_devices={n_devices}"
            )
        return dp, sp, tp


# ---------------------------------------------------------------------------
# model configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RopeScalingConfig:
    """Llama-3.1 NTK-by-parts RoPE scaling (matches HF ``rope_type="llama3"``)."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


@dataclass(frozen=True)
class LlamaConfig:
    """Llama-family decoder config.

    Defaults are Meta-Llama-3.1-8B-Instruct — the model the reference stages into
    the PVC and serves (download_model.py:5,17-20; rag.py:24).
    """

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    rope_scaling: Optional[RopeScalingConfig] = field(default_factory=RopeScalingConfig)
    max_seq_len: int = 131072
    tie_word_embeddings: bool = False
    # token ids from Llama-3.1-8B-Instruct generation_config / config.json
    bos_token_id: int = 128000
    eos_token_ids: Tuple[int, ...] = (128001, 128008, 128009)

    @classmethod
    def llama_3_1_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama_3_2_1b(cls) -> "LlamaConfig":
        """Llama-3.2-1B — a real family member that fits a single v5e chip in bf16."""
        return cls(
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=16,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            tie_word_embeddings=True,
        )

    @classmethod
    def llama_3_2_3b(cls) -> "LlamaConfig":
        """Llama-3.2-3B — single chip in bf16 (~6.4 GB) or int8 (~3.6 GB)."""
        return cls(
            hidden_size=3072,
            intermediate_size=8192,
            num_layers=28,
            num_heads=24,
            num_kv_heads=8,
            head_dim=128,
            tie_word_embeddings=True,
        )

    @classmethod
    def llama_3_1_70b(cls) -> "LlamaConfig":
        """Llama-3.1-70B — a tp=8 (v5e-8, int8: ~9 GB/chip) or multi-host
        deployment; every sharded dim divides tp=8 exactly like 8B."""
        return cls(
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            head_dim=128,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "LlamaConfig":
        """Miniature config for CPU tests: same code paths, toy shapes."""
        return cls(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            rope_scaling=None,
            max_seq_len=256,
            bos_token_id=1,
            eos_token_ids=(2,),
        )


    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context): what
        ``obs/goodput.py ledger_for`` asks of EVERY model configuration class,
        under this name and signature (duck-typed: it imports nothing of the
        package). The Llama arithmetic itself lives in ``obs/goodput.py
        llama_roofline_terms``, over plain numbers, because the simulator
        prices windows with it and may import no configuration."""
        from rag_llm_k8s_tpu.obs.goodput import llama_roofline_terms

        return llama_roofline_terms(
            self.num_layers, self.hidden_size, self.num_heads, self.num_kv_heads, self.head_dim,
            self.intermediate_size, self.vocab_size,
            weight_bytes_per_param=1.0 if weight_quant == "int8" else 2.0, kv_quant=kv_quant)


@dataclass(frozen=True)
class YarnScalingConfig:
    """YaRN RoPE scaling as the latent-attention family publishes it
    (``rope_scaling.type == "yarn"``): per-dimension blend of ``theta_i`` and
    ``theta_i / factor`` by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times in the original context, and a
    softmax-scale correction from ``mscale_all_dim``."""

    factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096


@dataclass(frozen=True)
class LatentMoEConfig:
    """The latent-attention, sparse-expert decoder family (``models/latent_moe.py``):
    multi-head latent attention (a per-token latent ``c_kv`` and one shared
    rotated key slice are all the cache holds), ``first_k_dense`` leading
    dense layers, then layers with a routed mixture of ``n_routed_experts``
    SwiGLU experts. Two published blocks are spanned, selected by the fields
    below; the defaults are the first:

    - ``sublayers_per_layer`` 1: norm, attention, norm, expert layer. Scores
      are ``sigmoid`` (``scoring_func``), choice is group-limited
      (``n_group`` / ``topk_group``), weights are normalised over the chosen
      (``norm_topk_prob``), ``n_shared_experts`` shared experts beside them.
    - ``sublayers_per_layer`` 2 (shortcut-connected): two attention-plus-
      dense-FFN sublayers a layer (``intermediate_size`` wide); the ONE
      expert layer branches off sublayer 0's normed stream and joins the
      residual after the last sublayer's FFN. With ``scoring_func``
      ``softmax`` the router scores ``n_routed_experts + zero_expert_num``
      outputs; the last ``zero_expert_num`` are zero-computation experts
      (identity: ``w * x``), replicated on every chip. ``n_group`` 1 is the
      no-groups case; ``n_shared_experts`` 0 builds no shared expert.
      ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` multiply the queries by
      ``(hidden / q_lora_rank) ** 0.5`` and the normed latent by ``(hidden /
      kv_lora_rank) ** 0.5``.

    ``num_layers`` counts LAYERS; the latent cache holds one plane an
    attention sublayer (``num_cache_planes``).

    ``ep_size``/``ep_rank`` state this chip's SHARE of an expert-parallel
    deployment: the router keeps its published width, and only the routed
    experts ``[ep_rank * held, (ep_rank + 1) * held)`` are held and computed
    here; what absent experts would add is left out (no exchange on one chip).

    Defaults are the published widths of the 672B-A37B decoder the
    ``dots-vlm1-ep16.closed8`` cell serves a share of; the shortcut block's
    are in ``benchmark/configs/longcat-flash-bf16-ep32-share.json``."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432  # the dense layers' SwiGLU width
    moe_intermediate_size: int = 2048  # every expert's SwiGLU width
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"  # "sigmoid" | "softmax" (over routed + zero outputs)
    zero_expert_num: int = 0  # router outputs past the routed experts: identity experts, ``w * x``
    sublayers_per_layer: int = 1  # attention + FFN sublayers a layer (2: shortcut-connected)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    ep_size: int = 1
    ep_rank: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScalingConfig] = field(default_factory=YarnScalingConfig)
    max_seq_len: int = 163840
    tie_word_embeddings: bool = False
    bos_token_id: int = 0
    eos_token_ids: Tuple[int, ...] = (1,)

    def __post_init__(self):
        if self.n_routed_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size={self.ep_size}, ep_rank={self.ep_rank}: the "
                f"{self.n_routed_experts} routed experts must divide evenly "
                "over the ranks and the rank must be one of them"
            )
        if self.n_routed_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError("n_group must divide n_routed_experts; topk_group <= n_group")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense must lie in [0, num_layers]")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func={self.scoring_func!r}: 'sigmoid' or 'softmax'")
        if self.zero_expert_num < 0:
            raise ValueError(f"zero_expert_num={self.zero_expert_num}: 0 or more")
        if self.zero_expert_num and self.n_group != 1:
            raise ValueError("zero-computation experts are routed without groups (n_group 1)")
        if self.sublayers_per_layer not in (1, 2):
            raise ValueError(f"sublayers_per_layer={self.sublayers_per_layer}: 1 or 2")
        if self.sublayers_per_layer == 2 and self.first_k_dense:
            raise ValueError("a shortcut-connected block (sublayers_per_layer 2) has no "
                             "leading dense layers: first_k_dense must be 0")
        if self.tie_word_embeddings:
            raise ValueError("the latent-MoE family serves an untied head only")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @property
    def first_held(self) -> int:
        return self.ep_rank * self.experts_held

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def num_cache_planes(self) -> int:
        """One latent plane an attention sublayer."""
        return self.num_layers * self.sublayers_per_layer

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed experts, then the zero ones."""
        return self.n_routed_experts + self.zero_expert_num

    norm_topk_eps = property(lambda self: 1e-20)  # in the normalising sum of the chosen scores

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_kv_heads(self) -> int:
        """As published (``num_key_value_heads`` = ``num_attention_heads``):
        every query head has keys and values of its own, rebuilt from the one
        latent; what divides over chips like a KV head count does."""
        return self.num_heads

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (the family is served in bf16 only:
        ``models/families.py`` refuses the rest, so neither argument is read).

        ``flops_per_token`` counts the parameters a token is multiplied by: the
        latent attention's projections, the dense layers' FFN, and a MoE layer's
        router, shared expert and the routed experts a balanced router sends to
        those HELD here (``num_experts_per_tok * held`` over the router's
        outputs, zero-computation ones included: those multiply nothing). A
        shortcut-connected layer (``sublayers_per_layer`` 2) has two attentions
        and a dense FFN beside each. ``weight_bytes`` is what a decode step
        streams at batch 1: attention, router, dense FFNs and shared expert
        whole, but only the held experts a token's choices hit, never all held
        (a batch hits more; bf16, 2 bytes). ``kv_bytes_per_token`` is one
        position's latent row over all cache planes."""
        d, H = int(self.hidden_size), int(self.num_heads)
        sub = self.sublayers_per_layer
        attn = (
            d * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
            + d * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim)
            + H * self.v_head_dim * d
        )
        expert = 3 * d * self.moe_intermediate_size
        dense_ffn = 3 * d * self.intermediate_size
        routed_here = self.num_experts_per_tok * self.experts_held / self.router_width
        moe_layer = sub * attn + d * self.router_width + (self.n_shared_experts + routed_here) * expert
        if sub > 1:
            moe_layer += sub * dense_ffn
        dense_layer = attn + dense_ffn
        active = (
            self.first_k_dense * dense_layer + self.num_moe_layers * moe_layer
            + self.vocab_size * d
        )
        return (2.0 * active, 2.0 * active,
                2.0 * self.num_cache_planes * (self.kv_lora_rank + self.qk_rope_head_dim))

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "LatentMoEConfig":
        """Miniature config for CPU tests: one leading dense layer, two MoE
        layers, 16 experts in 4 groups of which rank 1 of 2 holds 8."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_layers=3, first_k_dense=1, num_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2, ep_size=2, ep_rank=1,
            rope_scaling=YarnScalingConfig(factor=4.0, original_max_position_embeddings=64),
            max_seq_len=256, bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class RopeParameters:
    """One layer type's rotary table as ``rope_parameters`` publishes it:
    ``rope_type`` ``default`` (``theta`` alone) or ``yarn`` (the frequencies
    blended between ``theta_i`` and ``theta_i / factor`` by ``beta_fast`` /
    ``beta_slow`` turns in ``original_max_position_embeddings``, cos and sin
    times ``attention_factor``). ``partial_rotary_factor`` is the share of a
    head's dimensions that rotate (the first ones); the rest pass through."""

    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type={self.rope_type!r}: 'default' or 'yarn'")
        if not 0.0 < self.partial_rotary_factor <= 1.0:
            raise ValueError(f"partial_rotary_factor={self.partial_rotary_factor}: in (0, 1]")


@dataclass(frozen=True)
class WindowedMoEConfig:
    """The windowed-attention, sparse-expert decoder family
    (``models/windowed_moe.py``): GQA over per-head K/V planes whose layers
    differ in KIND (``layer_types``: ``full_attention``, or
    ``sliding_attention`` over the last ``sliding_window`` tokens), in query
    heads (``num_attention_heads_per_layer``) and in rotary table
    (``rope_parameters``, one a layer type), with a per-head gate on
    attention's output; ``mlp_layer_types`` says which layers' FFN is a dense
    SwiGLU (``intermediate_size``) and which a routed mixture of
    ``num_experts`` SwiGLU experts (``moe_intermediate_size``, sigmoid scores,
    top ``num_experts_per_tok``) beside a shared expert. Field names are the
    published ``config.json``'s.

    The layers' loop runs a PERIOD a trip, because parameter shapes differ by
    layer type: a leading run of dense layers sits outside it (``num_lead``),
    and what follows must be whole periods, each the same pattern of (layer
    type, heads) that ends with its full layer (``period``). ``ep_size`` / ``ep_rank``: this chip's share of the routed
    experts, as ``LatentMoEConfig``.

    Defaults are the published widths of the 118B decoder the
    ``laguna-s-ep16.closed8`` cell serves a share of, at the cell's depth."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    layer_types: Tuple[str, ...] = ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72, 48)
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 4
    rope_parameters: Tuple[Tuple[str, RopeParameters], ...] = (
        ("full_attention", RopeParameters(
            rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5, factor=128.0,
            original_max_position_embeddings=8192, beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.4852030263919618)),
        ("sliding_attention", RopeParameters(rope_theta=10000.0)),
    )
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    moe_router_logit_softcapping: float = 0.0
    moe_apply_router_weight_on_input: bool = False
    ep_size: int = 1
    ep_rank: int = 0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 1048576
    tie_word_embeddings: bool = False
    bos_token_id: int = 0
    eos_token_ids: Tuple[int, ...] = (1,)

    KINDS = ("full_attention", "sliding_attention")

    def __post_init__(self):
        L = len(self.layer_types)
        if not L or len(self.num_attention_heads_per_layer) != L or len(self.mlp_layer_types) != L:
            raise ValueError("layer_types, num_attention_heads_per_layer and mlp_layer_types "
                             "name every layer once")
        if set(self.layer_types) - set(self.KINDS) or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"layer_types are {self.KINDS}; mlp_layer_types 'dense' or 'sparse'")
        if any(h % self.num_kv_heads for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads are a whole number a KV head")
        lead = self.num_lead
        if "dense" in self.mlp_layer_types[lead:]:
            raise ValueError("mlp_layer_types: dense layers are a leading run (the layers' loop "
                             "is over sparse layers)")
        rest = tuple(zip(self.layer_types, self.num_attention_heads_per_layer))[lead:]
        if rest and (len(rest) % self.period or rest != rest[:self.period] * (len(rest) // self.period)):
            raise ValueError(
                "the layers behind the leading dense ones must repeat one pattern of (layer "
                f"type, heads), a period that ends with its full layer, a whole number of times; "
                f"{len(rest)} layers in periods of {self.period} do not")
        missing = set(self.layer_types) - {k for k, _ in self.rope_parameters}
        if missing:
            raise ValueError(f"rope_parameters has no table for {sorted(missing)}")
        if self.moe_router_logit_softcapping:
            raise ValueError("moe_router_logit_softcapping: the router's logits are not capped here (0)")
        if self.moe_apply_router_weight_on_input:
            raise ValueError("moe_apply_router_weight_on_input: the weights multiply the experts' OUTPUT")
        if self.num_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size={self.ep_size}, ep_rank={self.ep_rank}: the {self.num_experts} routed "
                "experts must divide evenly over the ranks and the rank must be one of them")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is a whole number of routed experts wide")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window={self.sliding_window}: at least 1")
        if self.tie_word_embeddings:
            raise ValueError("this family serves an untied head only")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def num_lead(self) -> int:
        """The leading run of dense layers, outside the layers' loop."""
        return next((i for i, t in enumerate(self.mlp_layer_types) if t != "dense"), self.num_layers)

    @property
    def period(self) -> int:
        """Layers a trip of the loop: a period runs up to and with its full
        layer (``sliding ... sliding full``); 1 where the layers behind the
        leading ones hold no full layer."""
        kinds = self.layer_types[self.num_lead:]
        return kinds.index("full_attention") + 1 if "full_attention" in kinds else 1

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.num_lead) // self.period

    @property
    def num_sliding_layers(self) -> int:
        return sum(t == "sliding_attention" for t in self.layer_types)

    def rope_of(self, kind: str) -> RopeParameters:
        return dict(self.rope_parameters)[kind]

    # what ``models/latent_moe.py``'s sparse FFN (SparseMLP, Experts) reads of
    # a configuration, under the names it reads them by
    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_lead

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.ep_size

    @property
    def first_held(self) -> int:
        return self.ep_rank * self.experts_held

    n_routed_experts = property(lambda self: self.num_experts)
    router_width = property(lambda self: self.num_experts)
    n_shared_experts = property(
        lambda self: self.shared_expert_intermediate_size // self.moe_intermediate_size)
    routed_scaling_factor = property(lambda self: self.moe_routed_scaling_factor)
    scoring_func = property(lambda self: "sigmoid")
    n_group = property(lambda self: 1)
    topk_group = property(lambda self: 1)
    zero_expert_num = property(lambda self: 0)
    norm_topk_eps = property(lambda self: 1e-20)

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read):
        every layer's attention at ITS head count (q, k, v, o and the per-head
        gate), a dense layer's FFN, a sparse layer's router, shared expert and
        the routed experts a balanced router sends to those held here (as
        ``LatentMoEConfig.roofline_terms``). ``kv_bytes_per_token`` is one
        position's K and V over all planes: every plane keeps every position,
        a sliding layer's too."""
        d, K, hd = int(self.hidden_size), int(self.num_kv_heads), int(self.head_dim)
        expert = 3 * d * self.moe_intermediate_size
        routed_here = self.num_experts_per_tok * self.experts_held / self.num_experts
        sparse_ffn = d * self.num_experts + expert * routed_here + 3 * d * self.shared_expert_intermediate_size
        active = self.vocab_size * d
        for heads, ffn in zip(self.num_attention_heads_per_layer, self.mlp_layer_types):
            active += 2 * d * heads * hd + 2 * d * K * hd + d * heads
            active += 3 * d * self.intermediate_size if ffn == "dense" else sparse_ffn
        return 2.0 * active, 2.0 * active, 2.0 * 2 * self.num_layers * K * hd

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "WindowedMoEConfig":
        """Miniature config for CPU tests: a dense full layer, then two
        periods of (two sliding layers of 9 heads a KV head, one full of 6);
        window 8; 16 experts of which rank 1 of 2 holds 8."""
        kinds = ("full_attention",) + ("sliding_attention", "sliding_attention", "full_attention") * 2
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_kv_heads=2, head_dim=16, sliding_window=8,
            layer_types=kinds,
            num_attention_heads_per_layer=tuple(18 if k == "sliding_attention" else 12 for k in kinds),
            mlp_layer_types=("dense",) + ("sparse",) * 6,
            rope_parameters=(
                ("full_attention", RopeParameters(
                    rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5, factor=4.0,
                    original_max_position_embeddings=32, attention_factor=1.1386)),
                ("sliding_attention", RopeParameters(rope_theta=10000.0)),
            ),
            num_experts=16, num_experts_per_tok=4, ep_size=2, ep_rank=1,
            max_seq_len=256, bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class BlockWindowConfig:
    """The block-window, pooled-summary decoder family
    (``models/block_window.py``): multi-head attention whose query at
    position ``t`` sees the exact keys of its own WINDOW (``t // window_size``,
    causally) and, for every complete CHUNK of ``chunk_size`` positions in an
    EARLIER window, one pooled key and one pooled value, all under one
    softmax; a float32 residual stream (``fp32_skip_add``), unit-offset norm
    scales (``norm_add_unit_offset``), float32 logits and ``num_pred_heads``
    next-position heads of ``vocab_size`` columns each (the head is
    ``[hidden, num_pred_heads * vocab_size]``, head-major; the served logits
    are head 0's). Field names are the published ``config.json``'s.

    Defaults are the published widths of the 6.5B byte-level decoder, at the
    depth of one stage of a four-stage pipeline."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 8
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    max_seq_len: int = 32768
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("this family is multi-head: one K/V head a query head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is a whole number of heads")
        if self.chunk_size < 1 or self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size={self.window_size} must be a whole number of chunks of {self.chunk_size}")
        if self.tie_word_embeddings:
            raise ValueError("this family serves an untied head only")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: self.num_hidden_layers)
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    chunks_per_window = property(lambda self: self.window_size // self.chunk_size)

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read): the
        dense decoder's matmuls with the head's ``num_pred_heads`` column
        blocks. ``kv_bytes_per_token`` is what one more position of context
        costs a decode step to read: a pooled key and value every
        ``chunk_size`` positions (the ring of the query's own window, at most
        ``window_size`` exact slots, does not grow with the context and is
        left out of this linear term)."""
        from rag_llm_k8s_tpu.obs.goodput import llama_roofline_terms

        flops, weight_bytes, kv_bytes = llama_roofline_terms(
            self.num_layers, self.hidden_size, self.num_heads, self.num_kv_heads, self.head_dim,
            self.intermediate_size, self.vocab_size * self.num_pred_heads)
        return flops, weight_bytes, kv_bytes / self.chunk_size

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "BlockWindowConfig":
        """Miniature config for CPU tests: windows of 32 positions in chunks
        of 4, 4 heads of 16, two layers, 8 next-position heads."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, window_size=32, chunk_size=4,
            num_pred_heads=8, max_seq_len=512, bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class HybridSSMConfig:
    """The hybrid state-space decoder family (``models/hybrid_ssm.py``): most
    layers mix tokens by a SELECTIVE STATE-SPACE recurrence (a depthwise
    causal convolution of ``mamba_d_conv`` taps, then a state ``[d_inner,
    mamba_d_state]`` a row carried from position to position in float32,
    with RMS norms on the time step, ``B`` and ``C`` projections), and every
    ``attn_layer_period``-th layer (those with ``i % attn_layer_period ==
    attn_layer_offset``) by grouped-query attention WITHOUT any position
    term. Every layer then has a dense SwiGLU. A state layer keeps no keys or
    values: its state has no position axis and is overwritten in place. Field
    names are the published ``config.json``'s.

    Defaults are the published widths and depth of the 3B model."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 262144
    tie_word_embeddings: bool = True
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is a whole number of heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is a whole number of groups of num_key_value_heads")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset names a layer of the period")
        if self.mamba_proj_bias or not self.mamba_conv_bias:
            raise ValueError("this family's state layers have a bias on the convolution and on no projection")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv: the convolution keeps at least one earlier input")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: self.num_hidden_layers)
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    d_inner = property(lambda self: self.mamba_expand * self.hidden_size)

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers) if self.is_attention(i))

    @property
    def num_attention_layers(self) -> int:
        return len(self.attention_layers)

    @property
    def num_state_layers(self) -> int:
        return self.num_hidden_layers - self.num_attention_layers

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read). A
        token's matmuls: every layer's SwiGLU, a state layer's four
        projections, an attention layer's four, the head.
        ``kv_bytes_per_token`` is what one more position of context costs a
        decode step to read: the attention layers' keys and values only (a
        state layer's state does not grow with the context; its bytes, read
        and written once a step, ride ``weight_bytes``)."""
        D, F, Di = self.hidden_size, self.intermediate_size, self.d_inner
        N, R = self.mamba_d_state, self.mamba_dt_rank
        H, K, hd = self.num_heads, self.num_kv_heads, self.head_dim
        ffn = 3 * D * F
        state = 2 * D * Di + Di * (R + 2 * N) + R * Di + Di * D
        attention = 2 * D * H * hd + 2 * D * K * hd
        M, Na = self.num_state_layers, self.num_attention_layers
        head = D * self.vocab_size
        params = self.num_layers * ffn + M * state + Na * attention + head
        state_bytes = M * 2 * (4 * N * Di + 2 * (self.mamba_d_conv - 1) * Di)  # read and written a step
        return 2.0 * params, 2.0 * params + state_bytes, 2.0 * Na * 2 * K * hd

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "HybridSSMConfig":
        """Miniature config for CPU tests: 8 layers, attention at 1 and 5,
        4 query heads over 1 KV head of 16, d_inner 128, state 16, dt rank 4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=1,
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, max_seq_len=512,
            bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class CrossDecoderConfig:
    """The decoder-hybrid-decoder family (``models/cross_decoder.py``): a
    SELF-decoder of state-space and attention layers that builds the caches,
    and a CROSS-decoder that keeps no state of its own and reads the
    self-decoder's. Every layer is ``x += mixer(LN(x))``, ``x +=
    SwiGLU(LN(x))`` with a LayerNorm that has a mean and a bias, and no layer
    has a position term. The mixer of layer ``i`` of ``L`` follows from the
    depth as the published code derives it (``kind_of``; ``mb_per_layer`` 2:
    every second layer a state-space slot): below ``L / 2`` even layers are
    Mamba-1 (plain: no norm on the time step, ``B`` or ``C``) and odd layers
    DIFFERENTIAL attention over a window of ``sliding_window`` keys; layer ``L
    / 2`` is a Mamba layer whose scan output in front of its gate is the
    MEMORY; layer ``L / 2 + 1`` is full differential attention whose keys and
    values are the one plane that grows with the context; above them even
    layers are gated memory units (``W_out (silu(W_in h) * memory)``, the
    memory at the same position) and odd layers cross-attention: queries of
    their own against layer ``L / 2 + 1``'s keys and values. Differential
    attention pairs heads up: two softmaxes of 64-wide query / key heads over
    the same 128-wide value pair, the second subtracted at a learned ``lambda``,
    a 128-wide RMS norm behind. Field names are the published ``config.json``'s;
    the state-space sizes are the family's published defaults (the file does
    not state them).

    Defaults are the published widths and depth of the 3.8B model."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_seq_len: int = 262144
    tie_word_embeddings: bool = True
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer: this family runs a state-space slot every second layer (2) only")
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError("num_hidden_layers is a whole number of fours, at least 8: half the depth is "
                             "(state, attention) pairs, the other half (memory unit, cross-attention) pairs")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is a whole number of heads")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs heads up: both head counts are even")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is a whole number of groups of num_key_value_heads")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv: the convolution keeps at least one earlier input")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: self.num_hidden_layers)
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    d_inner = property(lambda self: self.mamba_expand * self.hidden_size)
    # the cache's view of differential attention: a pair of key heads is one
    # head of twice the width, a pair of value heads too
    num_pair_heads = property(lambda self: self.num_key_value_heads // 2)
    pair_dim = property(lambda self: 2 * (self.hidden_size // self.num_attention_heads))
    # layers by kind: (state, window) pairs, then the memory's state layer
    # and the full layer, then (memory unit, cross-attention) pairs
    num_window_layers = property(lambda self: self.num_hidden_layers // 4)
    num_state_layers = property(lambda self: self.num_hidden_layers // 4 + 1)
    num_plane_layers = property(lambda self: self.num_hidden_layers // 4 + 1)  # layers that own a K/V plane
    num_cross_layers = property(lambda self: self.num_hidden_layers // 4 - 1)
    memory_layer = property(lambda self: self.num_hidden_layers // 2)
    shared_layer = property(lambda self: self.num_hidden_layers // 2 + 1)

    def kind_of(self, layer: int) -> str:
        """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``."""
        half = self.num_hidden_layers // 2
        if layer % 2 == 0:
            return "mamba" if layer <= half else "gmu"
        return "window" if layer < half else "full" if layer == half + 1 else "cross"

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read). A
        token's matmuls: every layer's SwiGLU, a state layer's four
        projections, a self-attention layer's four, a cross layer's two, a
        memory unit's two, the head. ``kv_bytes_per_token`` is what one more
        position of context costs a decode step to READ: the full layer's
        plane, once by its own layer and once by every cross layer (the window
        layers read ``sliding_window`` slots whatever the context, and the
        states do not grow: both ride ``weight_bytes``, read and, the states,
        written once a step). ONE plane grows by a position of context."""
        D, F, Di = self.hidden_size, self.intermediate_size, self.d_inner
        N, R = self.mamba_d_state, self.mamba_dt_rank
        plane = 2 * self.num_pair_heads * self.pair_dim  # a position's keys and values of one layer
        ffn = 3 * D * F
        state = 2 * D * Di + Di * (R + 2 * N) + R * Di + Di * D
        attention = 2 * D * D + D * plane
        cross, gmu = 2 * D * D, 2 * D * Di
        M, Na, Nc = self.num_state_layers, self.num_plane_layers, self.num_cross_layers
        params = self.num_layers * ffn + M * state + Na * attention + Nc * (cross + gmu) + D * self.vocab_size
        state_bytes = M * 2 * (4 * N * Di + 2 * (self.mamba_d_conv - 1) * Di)
        window_bytes = self.num_window_layers * 2 * plane * self.sliding_window
        return 2.0 * params, 2.0 * params + state_bytes + window_bytes, 2.0 * plane * (1 + Nc)

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "CrossDecoderConfig":
        """Miniature config for CPU tests: 12 layers (three (state, window)
        pairs, the memory's layer 6 and the full layer 7, two (memory unit,
        cross) pairs), 4 query heads over 2 KV heads of 16 (two query pairs
        over one key pair), window 8, d_inner 128, state 16, dt rank 4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, num_hidden_layers=12,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, max_seq_len=512,
            bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class ConvMoEConfig:
    """The gated short-convolution, sparse-expert decoder family
    (``models/conv_moe.py``). Every layer is ``h = x + Op(RMS(x))``, ``y = h +
    FFN(RMS(h))``. ``layer_types`` names each layer's operator: ``conv``, a
    GATED SHORT CONVOLUTION (``[B, C, u] = x W_in``, a depthwise causal
    convolution of ``conv_L_cache`` taps over ``B * u`` with no activation and
    no bias, ``W_out (C * c)``), which keeps the last ``conv_L_cache - 1``
    gated inputs a row and nothing by position; or ``full_attention``,
    grouped-query attention whose queries and keys are RMS-normed over the
    head (one scale for all heads) before they are rotated. The first
    ``num_dense_layers`` layers' FFN is a dense SwiGLU (``intermediate_size``),
    every later layer's a routed mixture of ``num_experts`` SwiGLU experts
    (``moe_intermediate_size``): sigmoid scores, the top
    ``num_experts_per_tok`` of score plus ``expert_bias`` chosen, the scores
    at the chosen normalised with the published ``1e-6`` (``norm_topk_eps``)
    and scaled; no shared expert. Field names are the published
    ``config.json``'s (``num_hidden_layers`` is ``len(layer_types)``).

    The layers' loop runs a PERIOD a trip, because operator shapes differ by
    kind: the dense layers sit outside it (``lead_<i>``), what follows is
    whole periods of one pattern of operators (``period``: the shortest), and
    a last part of a period, where the depth leaves one, sits behind the loop
    (``tail_<i>``: the published 40 layers are 2 + 9 periods of 4 + 2).
    ``ep_size`` / ``ep_rank``: this chip's share of the routed experts, as
    ``LatentMoEConfig`` (1: every expert held).

    Defaults are the published widths of the 24B-A2B model at the depth of
    stage 0 of a four-stage pipeline (the ``lfm2-24b-a2b-pp4.solo`` cell)."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    layer_types: Tuple[str, ...] = ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    ep_size: int = 1
    ep_rank: int = 0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_seq_len: int = 128000
    tie_word_embeddings: bool = True
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    KINDS = ("conv", "full_attention")

    def __post_init__(self):
        if not self.layer_types or set(self.layer_types) - set(self.KINDS):
            raise ValueError(f"layer_types names every layer once, each one of {self.KINDS}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError("num_dense_layers: a leading run of the layers")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("hidden_size is a whole number of heads of an even size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is a whole number of groups of num_key_value_heads")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache: the convolution keeps at least one earlier input")
        if self.conv_bias or not self.use_expert_bias:
            raise ValueError("this family's convolution has no bias and its router a selection bias")
        if self.num_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size={self.ep_size}, ep_rank={self.ep_rank}: the {self.num_experts} routed "
                "experts must divide evenly over the ranks and the rank must be one of them")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok: at most every expert")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: len(self.layer_types))
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    rms_norm_eps = property(lambda self: self.norm_eps)
    num_lead = property(lambda self: self.num_dense_layers)  # outside the layers' loop

    @property
    def period(self) -> int:
        """Layers a trip of the loop: the shortest pattern of operators that
        the layers behind the dense ones repeat (its last copy may be cut)."""
        rest = self.layer_types[self.num_lead:]
        return next((p for p in range(1, len(rest) + 1)
                     if all(rest[i] == rest[i % p] for i in range(len(rest)))), 1)

    @property
    def num_periods(self) -> int:
        return (self.num_layers - self.num_lead) // self.period

    @property
    def num_tail(self) -> int:
        """Layers of a last, cut period: behind the loop."""
        return (self.num_layers - self.num_lead) % self.period

    num_attention_layers = property(lambda self: self.layer_types.count("full_attention"))
    num_conv_layers = property(lambda self: self.layer_types.count("conv"))

    # what ``models/latent_moe.py``'s sparse FFN (SparseMLP, Experts) reads of
    # a configuration, under the names it reads them by
    num_moe_layers = property(lambda self: self.num_layers - self.num_lead)
    experts_held = property(lambda self: self.num_experts // self.ep_size)
    first_held = property(lambda self: self.ep_rank * self.experts_held)
    n_routed_experts = property(lambda self: self.num_experts)
    router_width = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: 0)
    scoring_func = property(lambda self: "sigmoid")
    n_group = property(lambda self: 1)
    topk_group = property(lambda self: 1)
    zero_expert_num = property(lambda self: 0)
    norm_topk_eps = property(lambda self: 1e-6)  # as published, in the normalising sum

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read). A
        token's matmuls: a conv operator's two projections or an attention
        operator's four, a dense layer's SwiGLU or a sparse layer's router
        and the ``num_experts_per_tok`` experts it chooses of those held here,
        the head. ``weight_bytes`` is what a decode step streams at batch 1:
        the experts a step HITS, never all that are held (a batch hits more),
        and the conv layers' kept inputs, read and written once a step.
        ``kv_bytes_per_token`` is one position's keys and values over the
        attention layers only: a conv layer's state does not grow."""
        D, H, K, hd = self.hidden_size, self.num_heads, self.num_kv_heads, self.head_dim
        conv, attention = 4 * D * D, 2 * D * H * hd + 2 * D * K * hd
        routed_here = self.num_experts_per_tok * self.experts_held / self.num_experts
        sparse_ffn = D * self.num_experts + routed_here * 3 * D * self.moe_intermediate_size
        active = (self.num_conv_layers * conv + self.num_attention_layers * attention
                  + self.num_lead * 3 * D * self.intermediate_size
                  + self.num_moe_layers * sparse_ffn + self.vocab_size * D)
        state_bytes = self.num_conv_layers * 2 * 2 * (self.conv_L_cache - 1) * D
        return 2.0 * active, 2.0 * active + state_bytes, 2.0 * self.num_attention_layers * 2 * K * hd

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "ConvMoEConfig":
        """Miniature config for CPU tests: two dense conv layers, then two
        periods of (attention, conv, conv, conv); 4 query heads over 2 KV
        heads of 16; 16 experts, all held, top 4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            layer_types=("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2,
            num_dense_layers=2, num_attention_heads=4, num_key_value_heads=2, num_experts=16,
            num_experts_per_tok=4, max_seq_len=512, bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class DeltaMoEConfig:
    """The gated delta-rule, sparse-expert decoder family
    (``models/delta_moe.py``). Every layer is ``h = x + Mixer(RMS(x))``, ``y =
    h + FFN(RMS(h))``, and a layer's mixer is one of two kinds, named by the
    published lists (1-indexed, as published):

    - ``kda_layers``: LINEAR ATTENTION by the gated delta rule
      (``ops/delta_rule.py``). ``q, k, v`` (``kda_num_heads`` heads of
      ``kda_head_dim``) each go through a depthwise causal convolution of
      ``short_conv_kernel_size`` taps and a SiLU; ``q`` and ``k`` are
      L2-normed over the head; a low-rank projection gives a log decay for
      EVERY key channel, ``g = -exp(A_log) * softplus(W_fb W_fa x + dt_bias)``,
      another the output gate, a third ``beta = sigmoid(W_b x)``. The state is
      a float32 ``[kda_head_dim, kda_head_dim]`` matrix a head: no position
      axis, overwritten in place. The output is RMS-normed over the head,
      gated by a sigmoid and projected.
    - ``full_attn_layers``: multi-head latent attention as
      ``LatentMoEConfig``'s with a DIRECT query projection (``q_lora_rank``
      is None, as published) and, under ``mla_use_nope``, no rotation of the
      shared key slice: the model has no position term at all.

    The first ``first_k_dense_replace`` layers' FFN is a dense SwiGLU, every
    later layer's ``models/latent_moe.py``'s sparse FFN: sigmoid scores over
    ``num_experts``, the top ``num_experts_per_token`` of score plus bias
    (``num_expert_group`` groups, ``topk_group`` kept), weights normalised
    over the chosen (``moe_renormalize``) times ``routed_scaling_factor``,
    ``num_shared_experts`` shared experts. ``ep_size`` / ``ep_rank``: this
    chip's share of the routed experts, as ``LatentMoEConfig``. Field names
    are the published ``config.json``'s (``linear_attn_config``'s keys flat:
    ``kda_num_heads``, ``kda_head_dim``).

    Defaults are the published widths and depth of the 48B-A3B model the
    ``kimi-linear-ep16.solo`` cell serves a share of."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128  # the two low-rank gates' inner width (not a published key: the head's size)
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    ep_size: int = 1
    ep_rank: int = 0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_seq_len: int = 1048576
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    def __post_init__(self):
        layers = tuple(range(1, self.num_hidden_layers + 1))
        if tuple(sorted(self.kda_layers + self.full_attn_layers)) != layers:
            raise ValueError("kda_layers and full_attn_layers name every layer 1..num_hidden_layers once")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace: a leading run of the layers")
        if any(i in self.full_attn_layers for i in range(1, self.first_k_dense_replace + 1)):
            raise ValueError("the leading dense layers are linear-attention layers")
        if self.q_lora_rank is not None:
            raise ValueError("this family's latent attention projects its queries directly (q_lora_rank null)")
        if self.short_conv_kernel_size < 2:
            raise ValueError("short_conv_kernel_size: the convolution keeps at least one earlier input")
        if self.num_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size={self.ep_size}, ep_rank={self.ep_rank}: the {self.num_experts} routed "
                "experts must divide evenly over the ranks and the rank must be one of them")
        if self.num_experts % self.num_expert_group or not 0 < self.topk_group <= self.num_expert_group:
            raise ValueError("num_expert_group must divide num_experts; topk_group <= num_expert_group")
        if self.tie_word_embeddings:
            raise ValueError("this family serves an untied head only")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: self.num_hidden_layers)
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_attention_heads)
    first_k_dense = property(lambda self: self.first_k_dense_replace)
    num_kda_layers = property(lambda self: len(self.kda_layers))
    num_mla_layers = property(lambda self: len(self.full_attn_layers))
    kda_width = property(lambda self: self.kda_num_heads * self.kda_head_dim)
    qk_head_dim = property(lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    num_cache_planes = property(lambda self: len(self.full_attn_layers))  # a latent plane a full layer

    def is_full(self, layer: int) -> bool:
        """Whether 0-indexed ``layer`` is a latent-attention layer."""
        return layer + 1 in self.full_attn_layers

    # what ``models/latent_moe.py``'s attention and sparse FFN (LatentAttention,
    # SparseMLP, Experts) read of a configuration, under the names they read
    num_moe_layers = property(lambda self: self.num_hidden_layers - self.first_k_dense_replace)
    experts_held = property(lambda self: self.num_experts // self.ep_size)
    first_held = property(lambda self: self.ep_rank * self.experts_held)
    n_routed_experts = property(lambda self: self.num_experts)
    router_width = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    norm_topk_prob = property(lambda self: self.moe_renormalize)
    n_group = property(lambda self: self.num_expert_group)
    scoring_func = property(lambda self: "sigmoid")
    zero_expert_num = property(lambda self: 0)
    norm_topk_eps = property(lambda self: 1e-20)  # in the normalising sum of the chosen scores
    rope_scaling = property(lambda self: None)
    mla_scale_q_lora = property(lambda self: False)
    mla_scale_kv_lora = property(lambda self: False)

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read). A
        token's matmuls: a linear-attention mixer's projections (q, k, v, o,
        the two low-rank gates, beta) or a latent one's, a dense layer's
        SwiGLU or a sparse layer's router, shared expert and the routed
        experts a balanced router sends to those HELD here, the head.
        ``weight_bytes`` is what a decode step streams at batch 1, and with it
        the linear-attention layers' state and kept convolution inputs, read
        and written once a step: they are CONSTANT in the context.
        ``kv_bytes_per_token`` is one position's latent row over the full
        layers' planes only."""
        D, W, R = self.hidden_size, self.kda_width, self.kda_gate_rank
        H = self.num_attention_heads
        kda = 4 * D * W + 2 * (D * R + R * W) + D * self.kda_num_heads
        mla = (D * H * self.qk_head_dim + D * (self.kv_lora_rank + self.qk_rope_head_dim)
               + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim) + H * self.v_head_dim * D)
        expert = 3 * D * self.moe_intermediate_size
        routed_here = self.num_experts_per_token * self.experts_held / self.num_experts
        sparse_ffn = D * self.num_experts + (self.num_shared_experts + routed_here) * expert
        active = (self.num_kda_layers * kda + self.num_mla_layers * mla
                  + self.first_k_dense_replace * 3 * D * self.intermediate_size
                  + self.num_moe_layers * sparse_ffn + self.vocab_size * D)
        state = 4 * self.kda_num_heads * self.kda_head_dim ** 2 + 2 * 3 * W * (self.short_conv_kernel_size - 1)
        return (2.0 * active, 2.0 * active + 2.0 * self.num_kda_layers * state,
                2.0 * self.num_mla_layers * (self.kv_lora_rank + self.qk_rope_head_dim))

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "DeltaMoEConfig":
        """Miniature config for CPU tests with the published pattern cut to
        eleven layers (1 dense linear layer, then K K M | K K K M | K K M):
        4 heads of 16 on both kinds, 16 experts of which rank 1 of 2 holds 8."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=11, first_k_dense_replace=1, full_attn_layers=(4, 8, 11),
            kda_layers=(1, 2, 3, 5, 6, 7, 9, 10), kda_num_heads=4, kda_head_dim=16, kda_gate_rank=16,
            num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=16, num_experts_per_token=4, ep_size=2, ep_rank=1, max_seq_len=512,
            bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class SSDMoEConfig:
    """The state-space-duality, latent-expert decoder family
    (``models/ssd_moe.py``). A layer is ONE thing, named by its letter of
    ``hybrid_override_pattern``, behind one pre-norm and one residual: ``x +
    F_k(RMS(x))``. No layer pairs a mixer with a feed-forward part.

    - ``M``: a MAMBA-2 mixer (``ops/ssd.py``). ``mamba_num_heads`` heads of
      ``mamba_head_dim`` channels, a state ``[mamba_head_dim,
      ssm_state_size]`` float32 a head, ONE decay a head (``A_log``, the time
      step's bias and ``D`` are ``[mamba_num_heads]``), ``B`` and ``C`` shared
      by the heads of a group (``n_groups`` groups), a depthwise causal
      convolution of ``conv_kernel`` taps (with a bias) over ``x | B | C``,
      the output gated by ``silu(z)`` and THEN RMS-normed a group.
    - ``*``: grouped-query attention (``num_attention_heads`` over
      ``num_key_value_heads`` heads of ``head_dim``) with no position term.
    - ``E``: a LATENT expert layer. The router scores the stream
      (``n_routed_experts`` sigmoid outputs, the ``num_experts_per_tok``
      largest of score plus bias, weights over their sum times
      ``routed_scaling_factor``); the stream is projected to
      ``moe_latent_size``, the experts (two matrices, ``relu`` squared
      between) work there, their weighted sum is projected back; a shared
      expert of ``moe_shared_expert_intermediate_size`` works on the stream.
      ``ep_size`` / ``ep_rank``: this chip's share of the routed experts, as
      ``LatentMoEConfig``.

    Field names are the published ``config.json``'s. Defaults are the
    published widths and depth of the 120B-A12B model the
    ``nemotron-3-super-ep4.solo`` cell serves a share of."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                                    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    ep_size: int = 1
    ep_rank: int = 0
    layer_norm_epsilon: float = 1e-5
    # the time step's INITIALISATION (``dt_bias`` is the inverse softplus of a
    # log-uniform draw over [min, max], floored): ranges of a draw, not clamps
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_ids: Tuple[int, ...] = (2,)

    KINDS = "M*E"  # a layer's kind is its letter's place here

    def __post_init__(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError("hybrid_override_pattern has one letter a layer")
        if set(self.hybrid_override_pattern) - set(self.KINDS):
            raise ValueError("hybrid_override_pattern: 'M' (Mamba-2), '*' (attention) and 'E' (experts) only; "
                             "a '-' (dense MLP) layer is not served")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads is a whole number of groups of n_groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is a whole number of groups of num_key_value_heads")
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel: the convolution keeps at least one earlier input")
        if self.n_routed_experts % self.ep_size or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"ep_size={self.ep_size}, ep_rank={self.ep_rank}: the {self.n_routed_experts} routed "
                "experts must divide evenly over the ranks and the rank must be one of them")
        if self.n_routed_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError("n_group must divide n_routed_experts; topk_group <= n_group")
        if self.tie_word_embeddings:
            raise ValueError("this family serves an untied head only")

    # the names the rest of the program reads a decoder's sizes by
    num_layers = property(lambda self: self.num_hidden_layers)
    num_heads = property(lambda self: self.num_attention_heads)
    num_kv_heads = property(lambda self: self.num_key_value_heads)
    max_seq_len = property(lambda self: self.max_position_embeddings)
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)
    d_inner = property(lambda self: self.mamba_num_heads * self.mamba_head_dim)
    conv_width = property(lambda self: self.d_inner + 2 * self.n_groups * self.ssm_state_size)  # x | B | C
    in_proj_width = property(lambda self: self.d_inner + self.conv_width + self.mamba_num_heads)  # z | xBC | dt
    layer_kinds = property(lambda self: tuple(self.KINDS.index(k) for k in self.hybrid_override_pattern))
    num_mamba_layers = property(lambda self: self.hybrid_override_pattern.count("M"))
    num_attention_layers = property(lambda self: self.hybrid_override_pattern.count("*"))
    num_moe_layers = property(lambda self: self.hybrid_override_pattern.count("E"))
    experts_held = property(lambda self: self.n_routed_experts // self.ep_size)
    first_held = property(lambda self: self.ep_rank * self.experts_held)

    def roofline_terms(self, weight_quant: str = "bf16", kv_quant: str = "bf16") -> Tuple[float, float, float]:
        """(FLOPs a token, weight bytes, KV bytes a position of context), as
        ``LlamaConfig.roofline_terms`` (bf16 only, neither argument read). A
        token's matmuls BY LAYER KIND: a Mamba-2 layer's two projections; an
        attention layer's four; an expert layer's router, its two latent
        projections, the shared expert and the routed experts a balanced
        router sends to those HELD here (``num_experts_per_tok * held /
        n_routed_experts`` of two matrices each); the head. ``weight_bytes``
        is what a decode step streams at batch 1, and with it the Mamba-2
        layers' float32 state and kept convolution inputs, read and written
        once a step: they are CONSTANT in the context. ``kv_bytes_per_token``
        is one position's keys and values over the ``*`` layers only."""
        D, Z = self.hidden_size, self.moe_latent_size
        mamba = D * self.in_proj_width + self.d_inner * D
        attention = 2 * D * self.num_heads * self.head_dim + 2 * D * self.num_kv_heads * self.head_dim
        routed_here = self.num_experts_per_tok * self.experts_held / self.n_routed_experts
        experts = (D * self.n_routed_experts + 2 * D * Z
                   + self.n_shared_experts * 2 * D * self.moe_shared_expert_intermediate_size
                   + routed_here * 2 * Z * self.moe_intermediate_size)
        active = (self.num_mamba_layers * mamba + self.num_attention_layers * attention
                  + self.num_moe_layers * experts + self.vocab_size * D)
        state = (4 * self.mamba_num_heads * self.mamba_head_dim * self.ssm_state_size
                 + 2 * (self.conv_kernel - 1) * self.conv_width)
        return (2.0 * active, 2.0 * active + 2.0 * self.num_mamba_layers * state,
                2.0 * self.num_attention_layers * 2 * self.num_kv_heads * self.head_dim)

    @classmethod
    def tiny(cls, vocab_size: int = 256, **overrides) -> "SSDMoEConfig":
        """Miniature config for CPU tests with the published structure: seven
        layers ``MEM*EME``, 8 Mamba-2 heads of 16 in 2 groups at state 16,
        chunks of 8, 4 query heads over 2 KV heads of 16, 16 experts top-3 in
        a latent of 64 of which rank 1 of 2 holds 8."""
        base = dict(
            vocab_size=vocab_size, hidden_size=128, num_hidden_layers=7, hybrid_override_pattern="MEM*EME",
            mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=48, moe_latent_size=64,
            moe_shared_expert_intermediate_size=96, ep_size=2, ep_rank=1, max_position_embeddings=512,
            bos_token_id=1, eos_token_ids=(2,),
        )
        base.update(overrides)
        return cls(**base)


@dataclass(frozen=True)
class EncoderConfig:
    """Bidirectional encoder config for the embedding model.

    Defaults are BAAI/bge-m3 (XLM-RoBERTa-large backbone) — the embedder the
    reference instantiates via SentenceTransformer (rag.py:33) with 1024-d
    L2-normalized dense vectors (rag.py:55,60).
    """

    vocab_size: int = 250002
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 8194
    type_vocab_size: int = 1
    layer_norm_eps: float = 1e-5
    pad_token_id: int = 1
    # XLM-R position ids start at pad_token_id + 1 for real tokens
    position_offset: int = 2
    embed_dim: int = 1024  # output dense-vector dimension (CLS pooled)
    max_encode_len: int = 8192

    @classmethod
    def bge_m3(cls) -> "EncoderConfig":
        return cls()

    @classmethod
    def tiny(cls, vocab_size: int = 256) -> "EncoderConfig":
        return cls(
            vocab_size=vocab_size,
            hidden_size=32,
            intermediate_size=64,
            num_layers=2,
            num_heads=4,
            max_position_embeddings=128,
            embed_dim=32,
            max_encode_len=64,
        )


# ---------------------------------------------------------------------------
# retrieval / sampling / engine / server
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RetrievalConfig:
    """Retrieval behavior; defaults replicate the reference exactly.

    chunk_size/overlap: rag.py:39 (word chunks of 1000, stride 800);
    k: rag.py:114 (search top-5); context_top_n: rag.py:164 (top-3 into the
    prompt); metric: embeddings are L2-normalized (rag.py:55) and searched by
    L2 (rag.py:61) which is monotone in cosine (L2² = 2 − 2·cos).
    """

    chunk_size: int = 1000
    chunk_overlap: int = 200
    k: int = 5
    context_top_n: int = 3
    embed_dim: int = 1024
    metric: str = "l2"  # "l2" | "cosine" — identical ranking on unit vectors


@dataclass(frozen=True)
class SamplingConfig:
    """Generation parameters; defaults replicate rag.py:172 exactly
    (max_new_tokens=150, temperature=0.7, top_p=0.9, sampling enabled by the
    model's bundled generation_config)."""

    max_new_tokens: int = 150
    temperature: float = 0.7
    top_p: float = 0.9
    do_sample: bool = True
    seed: int = 0


@dataclass(frozen=True)
class PrefixCacheConfig:
    """Cross-request device-resident KV prefix cache (engine/prefix_cache.py).

    Every /generate re-prefills the same fixed prompt head, and popular
    queries re-prefill the same retrieved chunks. The cache keeps those
    segments' KV on device, keyed by ``(segment_key, position_slot)`` —
    RoPE makes K position-dependent, so a cached block is reusable only at
    the exact token offset it was computed at (the *slot*). A request's
    matched prefix splices into its fresh cache via ``dynamic_update_slice``
    and prefill starts at the first non-shared token; misses fall back to
    normal chunked prefill (and populate the cache as they go).
    """

    # master switch (env TPU_RAG_PREFIX_CACHE). Off by default: the prefixed
    # serving path changes the /generate timings block and supersedes the
    # single-fetch device-assembly path — deployments opt in.
    enabled: bool = False
    # HBM budget for the cache's device bytes — segment blocks AND the
    # assembled full-prefix memo buffers — in MiB (env TPU_RAG_PREFIX_HBM_MB).
    # A cached token costs L*K*hd*2 bytes per plane and a block stores BOTH
    # K and V: 128 KiB/token at 8B bf16 (72 KiB int8-KV incl. fp32 scales),
    # so 512 MiB holds ~4k cached prefix tokens — a head + a few hot chunk
    # sets (docs/PREFIX_CACHE.md has the table). Assembled buffers evict
    # first (they only save re-splicing), then least-recently-used blocks;
    # the pinned head block never does.
    hbm_budget_mb: int = 512
    # static capacity (tokens) of the splice buffer every prefixed request
    # carries — also the largest prefix the cache can represent. Requests
    # whose head+chunks exceed it fall back to the cold path.
    max_prefix_tokens: int = 4096
    # segment blocks pad to these bucket lengths so build/splice executables
    # stay O(#buckets), not O(#distinct segment lengths)
    segment_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024, 1536, 2048)
    # suffix (the un-cached prompt tail) bucket ladder for the prefixed
    # generate executables — one executable per (suffix bucket, max_new),
    # NEVER one per hit pattern (prefix/suffix lengths are dynamic scalars)
    suffix_buckets: Tuple[int, ...] = (128, 512, 2048)
    # "exact": a chunk block is reused only when the ENTIRE preceding token
    # stream matches the one it was computed under — logits-exact (the
    # parity tests pin this). "slot": offset match alone suffices (HA-RAG-
    # style hotness reuse — K/V of layers > 0 carry the old left context,
    # an approximation those systems accept for the prefill savings).
    # "chunk" (env TPU_RAG_PREFIX_REUSE): chunk-granular reuse via attention
    # invariance (SIFT, docs/PREFIX_CACHE.md "chunk-granular reuse") — a hot
    # chunk's KV is computed ONCE at a canonical position and spliced into
    # any prompt at any offset by a closed-form RoPE re-rotation of the K
    # planes plus a bounded boundary-correction re-prefill of the chunk's
    # first ``boundary_tokens`` tokens (where cross-chunk attention actually
    # differs). Canonical-position, canonical-chain hits stay bit-identical;
    # shifted splices are tolerance-gated like the warm tier.
    reuse: str = "exact"  # "exact" | "slot" | "chunk"
    # chunk-reuse boundary-correction window (env
    # TPU_RAG_PREFIX_BOUNDARY_TOKENS): the first N tokens of every shifted
    # spliced chunk are re-prefilled with the TRUE left context — the slots
    # where attention over the changed composition measurably differs from
    # the canonical computation. 0 = pure re-rotation (fastest, most drift).
    boundary_tokens: int = 16
    # minimum decayed hit-frequency score before a chunk's canonical KV is
    # spliced at a SHIFTED position (env TPU_RAG_PREFIX_CHUNK_HOT_MIN):
    # cold/one-shot chunks keep the exact-chain/recompute path — the drift
    # budget is spent only where the prefill savings recur. The score comes
    # from the tiering HotnessTracker when tiering is on, else from a
    # cache-private tracker with the same decay grammar.
    chunk_hot_min: float = 2.0
    # bound on per-chunk canonical POOL registrations the paged engine
    # keeps (env TPU_RAG_PREFIX_CHUNK_POOL_REGS): size it to the hot chunk
    # set (+1 for the head) or the per-chunk assembly path thrashes —
    # least-recently-planned registrations evict past the cap
    chunk_pool_regs: int = 32
    # fully-assembled prefix buffers memoized per (segment-chain, length):
    # a repeated query re-splices nothing — its whole prefix is one device
    # handle. Small count cap (each buffer is max_prefix_tokens wide).
    assembled_cache_entries: int = 8


@dataclass(frozen=True)
class KVTieringConfig:
    """Hotness-aware KV tiering (engine/tiering.py + engine/prefix_cache.py
    — HA-RAG, PAPERS.md).

    Every cached chunk carries a decayed hit-frequency score (fed by
    prefix-cache resolve hits, lookahead joins, and pool prestage
    registrations). Tier policy over that one signal:

    - **hot** (score ≥ ``warm_below``): KV stays in the engine's native
      dtype in HBM — exactly the untiered behavior, byte-identical streams;
    - **warm** (``cold_below`` ≤ score < ``warm_below``): KV quantizes IN
      PLACE to int8 (+ per-(token, kv-head) fp32 scales — the ``_q8``
      kernel layout) with no re-prefill: the chunk's HBM bytes roughly
      halve and decoded streams stay within the pinned int8 logit
      tolerance;
    - **cold** (score < ``cold_below``): KV spills to host RAM (zero HBM)
      and swaps back in asynchronously ahead of admission — the lookahead
      pipeline's prestage is the prefetch trigger, so a swap-in overlaps
      the previous request's decode instead of stalling prefill.

    Off by default: tier transitions trade bounded quality drift (warm)
    and swap-in latency (cold) for effective cache capacity — deployments
    opt in. All knobs: env ``TPU_RAG_KV_TIERING*``.
    """

    # master switch (env TPU_RAG_KV_TIERING)
    enabled: bool = False
    # decayed-score demotion thresholds (env TPU_RAG_KV_TIERING_WARM_BELOW
    # / TPU_RAG_KV_TIERING_COLD_BELOW; cold_below must not exceed
    # warm_below). A score decays by half every half_life_s, so with the
    # defaults a chunk untouched for ~2 half-lives goes warm and one
    # untouched for ~4 goes cold.
    warm_below: float = 0.25
    cold_below: float = 0.0625
    # hit-frequency decay half-life, seconds (env
    # TPU_RAG_KV_TIERING_HALF_LIFE_S)
    half_life_s: float = 60.0
    # host-RAM budget for cold-spilled chunk KV, MiB (env
    # TPU_RAG_KV_TIERING_HOST_MB). Spills past it evict oldest-first —
    # a chunk falling off the host store recomputes on its next miss.
    host_spill_mb: int = 1024
    # minimum seconds between opportunistic retier sweeps on the resolve
    # path (env TPU_RAG_KV_TIERING_INTERVAL_S); retier(force=True) ignores
    # it (tests, maintenance)
    retier_interval_s: float = 5.0

    def validate(self) -> None:
        if self.cold_below > self.warm_below:
            raise ValueError(
                f"kv tiering: cold_below={self.cold_below} must not exceed "
                f"warm_below={self.warm_below}"
            )
        if self.half_life_s <= 0:
            raise ValueError(
                f"kv tiering: half_life_s={self.half_life_s}: expected > 0"
            )
        if self.host_spill_mb < 1:
            raise ValueError(
                f"kv tiering: host_spill_mb={self.host_spill_mb}: expected >= 1"
            )


@dataclass(frozen=True)
class GoodputConfig:
    """Goodput ledger: per-window chip-time attribution, roofline/MFU
    accounting, and cost-per-query (obs/goodput.py, docs/GOODPUT.md).

    ON BY DEFAULT: the ledger is pure host-side dict math per device sync
    window (no device work, no I/O). What that costs a decode step has
    not been measured on the chip (PERF.md §7, ``audits-on``) — as for
    the flight recorder it journals through.
    """

    # master switch for the step ledger (env TPU_RAG_GOODPUT)
    enabled: bool = True
    # chip rental price, USD per chip-hour — powers cost_usd in /generate
    # timings, rag_cost_* metrics and the /debug/goodput cost-per-query
    # percentiles; 0 keeps chip-time attribution on but omits dollar
    # figures (env TPU_RAG_CHIP_HOUR_USD)
    chip_hour_usd: float = 0.0
    # roofline peaks for MFU / bandwidth-utilization estimates; 0 = look
    # the serving device's kind up in obs/goodput.py DEVICE_PEAKS (TPU v5
    # lite: 197 bf16 TFLOP/s, 819 GB/s) — a kind that table does not hold
    # is an error at engine construction, so pin both for such a chip
    # (env TPU_RAG_GOODPUT_PEAK_TFLOPS / TPU_RAG_GOODPUT_HBM_GBS)
    peak_tflops: float = 0.0
    hbm_gbs: float = 0.0

    def validate(self) -> None:
        if self.chip_hour_usd < 0:
            raise ValueError(
                f"goodput: chip_hour_usd={self.chip_hour_usd}: expected >= 0"
            )
        if self.peak_tflops < 0 or self.hbm_gbs < 0:
            raise ValueError(
                "goodput: peak_tflops/hbm_gbs must be >= 0 (0 = default)"
            )


@dataclass(frozen=True)
class EngineConfig:
    """Serving-engine shape limits (no reference equivalent — the reference
    re-runs full HF generate per request, single-threaded)."""

    max_batch_size: int = 8
    # bucketed prompt lengths: each request pads to the next bucket so XLA
    # compiles a fixed, reusable executable per bucket instead of per-request
    prompt_buckets: Tuple[int, ...] = (256, 512, 1024, 2048, 4096)
    # hard cap on prompt bucket + generated tokens (KV-cache budget)
    max_seq_len: int = 4096 + 256
    # prompts longer than the largest bucket prefill through the cache in
    # bucket-sized chunks (chunk_prefill_attention) up to this many tokens;
    # beyond it the engine truncates LOUDLY (logged), never silently
    max_chunked_prompt: int = 16384
    # request scheduling: "coalesce" = group compatible requests at start
    # (engine/batching.py) — the default: one device program per batch.
    # The round-5 capture (before PR 1, in git history) had the DENSE slot
    # engine's device-only step several times slower than the one-shot loop
    # (more so at B=64 than at B=8), so "continuous" was kept for its
    # mid-stream admission semantics (requests join a running batch), not
    # as a performance choice. The paged engine that replaced the dense
    # slots has not been measured on the current machine (ROADMAP A3);
    # tune decode_sync_steps if used.
    batching: str = "coalesce"
    # attention backend: "auto" = fused Pallas kernels on TPU, XLA einsum
    # oracle elsewhere (see models.llama.Attention)
    attn_impl: str = "auto"
    # fuse q/k/v and gate/up projections into single matmuls at engine
    # construction (same HBM bytes, ~40% fewer kernels per decode step);
    # applies only when tp == 1 — a plain concat cannot be tp-sharded
    fuse_matmuls: bool = True
    # weight storage for serving: "bf16" (exact) or "int8" (weight-only
    # per-channel quantization at engine construction — halves the HBM bytes
    # every decode step streams, and fits 8B weights on one 16 GB chip;
    # see models.llama.quantize_llama_params). Training always stays bf16.
    weight_quant: str = "bf16"
    # speculative decoding for the one-shot engine's batch-1 path (the
    # single-request latency case): "prompt_lookup" proposes the spec_tokens
    # tokens that followed the most recent in-context repeat of the trailing
    # spec_ngram-gram (RAG answers quote their context, so repeats are
    # common), verifies all of them in ONE forward — decode is
    # weight-bandwidth-bound, so a k+1-wide verify step costs ~one decode
    # step. GREEDY requests accept the longest prefix matching the model's
    # own argmax (output token-IDENTICAL to the vanilla loop); SAMPLED
    # requests accept by rejection sampling against the draft (output
    # distribution IDENTICAL to vanilla temperature/top-p sampling —
    # tests/test_speculative.py). Batch>1 and chunked prompts fall back to
    # the vanilla loop. The default "auto" additionally self-disables when
    # MEASURED acceptance stays below spec_min_accept tokens/verify (a
    # model/workload where lookup never hits should not pay the verify
    # overhead), re-probing periodically; "off" is the escape hatch.
    # Env: TPU_RAG_SPECULATIVE.
    speculative: str = "auto"  # "off" | "prompt_lookup" | "auto"
    # match gram size: 2 fires far more often than 3 (any recurring BIGRAM
    # proposes), and the cost asymmetry favors firing — a fired-but-wrong
    # verify costs ~0.4 extra decode-steps (the k+1-wide forward's premium)
    # while a fired-and-right one saves up to k; public prompt-lookup
    # deployments likewise scan down to 2-grams
    spec_ngram: int = 2
    # proposals per verify step (k+1 = 16 fed tokens — one MXU lane tile).
    # Round-5 on-chip sweep at the 8B int8+kv8 behavioral point (bucket
    # 1024, solo /query p50): k=7 → 1353 ms (2.0 tok/verify), k=15 →
    # 1261 ms (2.15), k=19 → 1350, k=23 → 1276, k=31 → 1359. Wide spans
    # win when a match fires (long accepted runs amortize the verify),
    # and a fired-but-wrong verify still costs only the wide forward's
    # small premium — k=15 is the measured sweet spot and its width is
    # lane-aligned.
    spec_tokens: int = 15
    # "auto" keeps speculating only while the acceptance EMA stays above
    # this (tokens emitted per verify forward). Breakeven is the verify
    # forward's cost in decode steps — MEASURED 1.39 at width 8 (k=7,
    # round-5 A/B at acceptance 1.0: 56.6 vs 79.0 tok/s); width 16 adds
    # a little more (bandwidth-dominated, so width is nearly free) — the
    # default sits at the width-16 estimate so workloads where lookup
    # persistently under-delivers stop paying the verify overhead.
    spec_min_accept: float = 1.5
    # continuous engine: decode steps executed per host sync. 1 = admit and
    # retire between every step (lowest admission latency). >1 runs k steps
    # as ONE device program (lax.scan) and fetches the [k, B] token plane
    # once — amortizes per-step dispatch/fetch latency (decisive when the
    # device→host fetch is slow) at the cost
    # of up to k-1 wasted row-steps after a row finishes mid-window and up
    # to k steps of admission latency for a waiting request.
    decode_sync_steps: int = 1
    # warm every (batch, bucket) executable pair at startup instead of only
    # the largest bucket's batch ladder — for deployments expecting
    # concurrent bursts of short, context-free prompts (readiness arrives
    # later: one compile per pair). Env: TPU_RAG_WARM_FULL_LADDER=1.
    warm_full_ladder: bool = False
    # KV-cache storage: "bf16" (exact) or "int8" (one fp32 scale per
    # (token, kv-head) vector — halves the cache bytes every decode step
    # scans AND the cache HBM footprint; with a 4096-token prompt bucket the
    # cache is ~1/3 of step bandwidth. ops.attention.decode_attention_q8 is
    # the kernel; parity bounds in tests. Both engines support it — the
    # continuous engine threads the scale planes through its slot state.)
    kv_quant: str = "bf16"
    # single-fetch /query serving (survey §7 hard part (e) taken to its
    # conclusion): solo queries assemble their RAG prompt ON DEVICE from the
    # fused retrieve's top-k and the store's pre-tokenized chunk segments
    # (InferenceEngine.generate_rag) — retrieval output never leaves HBM
    # before generation, and the host pays ONE device→host fetch per query
    # (the output tokens; the ids fetch for the response's context text
    # overlaps generation). Prompt assembly is PIECEWISE in token space
    # (head ‖ chunk segments ‖ tail) with score-free chunk headers — both
    # properties hold identically on the host fallback path while this is
    # enabled, so solo and batched answers stay token-consistent; disable
    # for byte parity with the reference's whole-string prompt format
    # (rag.py:163-169). Concurrent bursts keep the batched host path.
    # Env: TPU_RAG_FUSED.
    rag_fused: bool = True
    # chunk-token sidecar cap: past this many live vectors the device token
    # matrix stops being worth its HBM (cap × row_len × 4B) and solo queries
    # fall back to the host path. 64k rows × 2k tokens ≈ 512 MB.
    rag_fused_max_vectors: int = 65536
    # paged KV cache for the CONTINUOUS engine (engine/kv_pool.py +
    # ops.attention paged kernels): the per-slot dense [B, T] cache becomes
    # a [num_blocks, block_size] block-pool arena with per-row block
    # tables — HBM and decode bandwidth scale with REAL tokens per row
    # instead of the full window (the B=64 occupancy unlock; vLLM /
    # JetStream design). Off by default: the dense path is untouched.
    # Env: TPU_RAG_KV_PAGED.
    kv_paged: bool = False
    # tokens per physical block. Must be a multiple of the Mosaic
    # second-to-minor tile for the arena dtype (16 bf16 / 32 int8) and must
    # divide every prompt bucket. Smaller blocks waste less tail (≤ one
    # block per row) but grow the tables and the grid; 16 is the bf16 tile
    # minimum and the measured sweet spot at 1B-8B scale.
    # Env: TPU_RAG_KV_BLOCK_SIZE.
    kv_block_size: int = 16
    # allocatable physical blocks in the pool (the +1 reserved null block
    # is added internally). 0 = "dense parity": max_batch_size * ceil(T /
    # block_size) — same worst-case HBM as the dense cache, but shared, so
    # real mixed-length traffic fits far more rows. Size it DOWN to trade
    # worst-case capacity for HBM (admission backpressures instead of
    # crashing when it runs out). NO tp rounding/padding applies to this
    # count: on a tp>1 mesh the arena shards its KV-HEAD axis (each device
    # holds num_kv_heads/tp heads of EVERY block — docs/KV_POOL.md
    # "tensor-parallel layout"), so the block count is tp-invariant and
    # per-device arena HBM is total/tp exactly; the divisibility that IS
    # required (num_kv_heads % tp == 0) is checked by validate_tp_layout
    # at engine construction. Env: TPU_RAG_KV_POOL_BLOCKS.
    kv_pool_blocks: int = 0
    # speculative decoding for the PAGED CONTINUOUS engine (the production
    # serving substrate; docs/SPECULATIVE.md). The scheduler drafts up to
    # spec_paged_tokens continuation tokens per row by prompt-lookup over
    # the row's OWN history (assembled prompt + emitted — grounded RAG
    # answers heavily copy their retrieved context, so the context is the
    # draft corpus; no draft model), and each sync window runs ONE
    # multi-token verify step through the block tables: K+1 fed tokens per
    # row, K+1 logit planes back, per-row longest-prefix acceptance
    # against the model's own (seed, position)-keyed targets — greedy AND
    # seeded sampled streams are BYTE-IDENTICAL to spec-off by
    # construction (tests/test_spec_paged.py pins it across mixed-length
    # admission groups, mid-flight admission, preemption/reset recovery,
    # prefix admissions and tp=2). Requires kv_paged=True (checked at
    # engine construction). Orthogonal to the one-shot engine's
    # `speculative` knob above, which keeps serving the batch-1 coalesce
    # path. Env: TPU_RAG_SPEC_PAGED.
    spec_paged: bool = False
    # drafted tokens per verify step (the verify forward feeds K+1 tokens
    # per row). Decode is weight-bandwidth-bound, so width is nearly free
    # on the device — the cost of a wide MISS is the extra logit planes
    # and junk KV writes, so the per-row adaptive controller (below)
    # shrinks K where acceptance is low. 7 (8 fed tokens) is the
    # continuous default: B rows verify TOGETHER, so the [B, K+1, V]
    # logit volume scales with batch — half the one-shot path's k=15.
    # Env: TPU_RAG_SPEC_PAGED_TOKENS.
    spec_paged_tokens: int = 7
    # per-row adaptive draft length: each verify window folds the row's
    # measured acceptance FRACTION (accepted / offered) into a decayed
    # EMA; below this floor the row degrades to K=1 (one probe token per
    # window — ~free, and the row recovers within a few windows when its
    # output starts quoting again), above it K scales with the EMA.
    # Env: TPU_RAG_SPEC_PAGED_MIN_ACCEPT.
    spec_paged_min_accept: float = 0.3
    # unified ragged sync windows for the PAGED CONTINUOUS engine
    # (docs/KV_POOL.md "Unified ragged sync windows"; Sarathi/vLLM-style
    # chunked prefill): every device step carries a token budget split
    # between decode lanes and admission-prefill CHUNKS, so a long prompt
    # prefills across N windows while decode never stops — TTFT under
    # load stops being hostage to batch-mate prompt lengths, and the
    # right-padded admission group's padding_bubble chip-time (measured
    # by obs/goodput.py) is reclaimed as prefill compute. Greedy AND
    # seeded streams stay byte-identical to the phase-separated
    # scheduler (tests/test_chunked_prefill.py pins it, incl. chaos
    # resets and tp=2). Requires kv_paged=True (validate_interleave).
    # Off by default: the phase-separated admission path is untouched.
    # Env: TPU_RAG_INTERLEAVE_PREFILL.
    interleave_prefill: bool = False
    # prefill tokens fed per row per mixed window (the static lane width
    # of the mixed executable — one compile per value). Smaller chunks
    # bound per-window decode stall tighter but pay more window
    # overheads per prompt; 64 amortizes well at 1B-8B scale while
    # keeping worst-case added inter-token latency ≈ one chunk forward.
    # Env: TPU_RAG_PREFILL_CHUNK_TOKENS.
    prefill_chunk_tokens: int = 64
    # total token budget per mixed window, split decode-first: active
    # decode lanes cost 1 each, the remainder is sliced into prefill
    # chunks of ≤ prefill_chunk_tokens. 0 = auto (max_batch_size +
    # prefill_chunk_tokens — every decode lane plus one full chunk).
    # Nonzero values must leave room for at least one decode lane per
    # row plus one prefill token (validate_interleave).
    # Env: TPU_RAG_WINDOW_TOKEN_BUDGET.
    window_token_budget: int = 0
    # cross-request KV prefix cache (see PrefixCacheConfig)
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)

    # goodput ledger (obs/goodput.py, docs/GOODPUT.md) — on by default
    goodput: GoodputConfig = field(default_factory=GoodputConfig)
    # hotness-aware KV tiering over the cached chunks (see KVTieringConfig;
    # needs prefix_cache.enabled to have anything to tier)
    kv_tiering: KVTieringConfig = field(default_factory=KVTieringConfig)

    def validate_tp_layout(self, tp: int, num_kv_heads: int) -> None:
        """Paged KV on a ``tp > 1`` mesh serves from a HEAD-sharded arena:
        each device holds ``num_kv_heads / tp`` heads of every physical
        block, so the kv-head count must tile the axis (the pool's BLOCK
        count needs no such rounding — see ``kv_pool_blocks`` above).
        Engines call this at construction so a bad pairing fails with the
        fix spelled out, not per-request."""
        if not self.kv_paged or tp <= 1:
            return
        if num_kv_heads % tp:
            raise ValueError(
                f"kv_paged on a tp={tp} mesh shards the arena's kv-head "
                f"axis: num_kv_heads={num_kv_heads} must be divisible by "
                f"tp — choose a tp that divides the head count, or serve "
                "this model dense on the mesh"
            )

    def validate_interleave(self) -> None:
        """Cross-field rules for unified ragged sync windows. Called from
        ``from_env`` (with the env applied) and at continuous-engine
        construction, so a bad pairing fails with the fix spelled out
        instead of as a shape error mid-admission."""
        if not self.interleave_prefill:
            return
        if not self.kv_paged:
            raise ValueError(
                "interleave_prefill=True requires kv_paged=True — chunked "
                "prefill writes through block tables; set "
                "TPU_RAG_KV_PAGED=1 or disable TPU_RAG_INTERLEAVE_PREFILL"
            )
        if self.prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens={self.prefill_chunk_tokens}: the "
                "mixed window must carry at least one prefill token per "
                "scheduled chunk"
            )
        if self.window_token_budget and (
            self.window_token_budget < self.max_batch_size + 1
        ):
            raise ValueError(
                f"window_token_budget={self.window_token_budget} cannot "
                f"cover max_batch_size={self.max_batch_size} decode lanes "
                "plus one prefill token — raise the budget or set 0 for "
                "auto (max_batch_size + prefill_chunk_tokens)"
            )


@dataclass(frozen=True)
class LookaheadConfig:
    """Retrieval lookahead pipeline (rag/lookahead.py — TeleRAG-style).

    Takes embed+KNN off the request critical path: retrieval for a request
    launches the moment its body is parsed (before the admission gate can
    queue it), runs on a bounded executor concurrently with in-flight
    decode, and the serving tail *joins* the already-launched future. When
    a retrieval resolves and the KV prefix cache is enabled, the resolved
    chunks' segment KV is pre-staged into prefix-cache entries (and, on a
    paged continuous engine, registered pool blocks) so admission splices
    instead of prefilling. Sessions (requests carrying ``session_id``)
    additionally speculate turn N+1's retrieval from the accumulating
    conversation state while turn N decodes. Results are always served
    from the SAME retrieval entry points the sequential path uses — greedy
    output streams are byte-identical with lookahead on or off
    (tests/test_lookahead.py / ``make lookahead-smoke``).
    """

    # master switch (env TPU_RAG_LOOKAHEAD). Off by default: lookahead
    # spends device time on speculation — deployments opt in.
    enabled: bool = False
    # executor worker threads running tokenize/embed+KNN joins (each worker
    # blocks in the retrieve coalescer, so embeds still batch with live
    # traffic's; env TPU_RAG_LOOKAHEAD_WORKERS)
    max_workers: int = 2
    # bound on launched-but-UNRESOLVED retrievals: launches beyond it are
    # SKIPPED, never queued — speculation must not pile up behind a slow
    # device. Resolved-but-unconsumed futures are bounded by ttl_s (the
    # sweeper), not by this knob. (env TPU_RAG_LOOKAHEAD_INFLIGHT)
    max_inflight: int = 8
    # unconsumed futures (and their pre-staged KV) expire after this long;
    # expiry is counted as waste (env TPU_RAG_LOOKAHEAD_TTL_S)
    ttl_s: float = 30.0
    # build/refresh the resolved chunks' prefix-cache KV the moment a
    # retrieval resolves, gated on pool/HBM headroom
    # (env TPU_RAG_LOOKAHEAD_PRESTAGE)
    prestage_kv: bool = True
    # speculate turn N+1's retrieval for sessions while turn N decodes
    # (env TPU_RAG_LOOKAHEAD_SESSIONS)
    session_pipelining: bool = True
    # how many trailing user turns feed the speculative next-turn query
    # (env TPU_RAG_LOOKAHEAD_SESSION_TURNS; the RUNBOOK's first remedy for
    # a superseded-dominated waste rate)
    session_context_turns: int = 2
    # LRU cap + idle TTL on tracked sessions (host memory bound; env
    # TPU_RAG_LOOKAHEAD_SESSION_MAX / TPU_RAG_LOOKAHEAD_SESSION_TTL_S)
    session_max: int = 256
    session_ttl_s: float = 600.0


@dataclass(frozen=True)
class ResilienceConfig:
    """Admission control, deadlines, and failure-recovery knobs (ISSUE 4 —
    rag_llm_k8s_tpu/resilience/). Defaults are sized for one pod of the
    reference deployment: concurrency ~2× the batch cap (keeps the coalescer
    fed), a queue a few seconds deep, and a 120 s default deadline matching
    the seed's only hardcoded timeout."""

    # concurrent requests allowed past the gate into the serving pipeline
    # (env TPU_RAG_ADMISSION_MAX_CONCURRENCY)
    admission_max_concurrency: int = 16
    # bounded wait line above the concurrency cap; request #(cap+queue+1)
    # is shed with 429 + Retry-After (env TPU_RAG_ADMISSION_MAX_QUEUE)
    admission_max_queue: int = 64
    # the Retry-After hint on queue_full sheds, seconds
    # (env TPU_RAG_ADMISSION_RETRY_AFTER_S)
    admission_retry_after_s: float = 1.0
    # default end-to-end request deadline when the client sends none
    # (body deadline_ms / x-request-deadline-ms header); replaces the
    # hardcoded th.join(timeout=120) (env TPU_RAG_DEADLINE_MS)
    deadline_ms: int = 120_000
    # circuit breaker: this many engine resets inside breaker_window_s
    # flips /healthz readiness to 503 so Kubernetes drains the pod
    # (env TPU_RAG_BREAKER_RESETS / TPU_RAG_BREAKER_WINDOW_S)
    breaker_reset_threshold: int = 3
    breaker_window_s: float = 300.0
    # reset recovery: resubmissions per in-flight request after an
    # EngineStateLost (0 restores fail-on-first-fault), and the jittered
    # backoff before the resubmitted prefills land on the device again
    # (env TPU_RAG_INFLIGHT_RETRIES / TPU_RAG_RETRY_BACKOFF_MS)
    inflight_retries: int = 1
    retry_backoff_ms: float = 50.0
    # graceful drain (resilience/lifecycle.py): how long in-flight work
    # gets to finish after SIGTERM / POST /drain before the coordinator
    # gives up, sheds the stragglers, and spools a drain_timeout incident.
    # Must fit INSIDE the pod's terminationGracePeriodSeconds with margin
    # for the persist step (env TPU_RAG_DRAIN_DEADLINE_S)
    drain_deadline_s: float = 25.0
    # the Retry-After hint on 503 reason="draining" sheds while the drain
    # runs — sized to a replica roll, not a breaker cool-down
    # (env TPU_RAG_DRAIN_RETRY_AFTER_S)
    drain_retry_after_s: float = 2.0


@dataclass(frozen=True)
class ServerConfig:
    """HTTP surface + storage paths; parity with rag.py:18-20,204 and
    web/app.py:5."""

    host: str = "0.0.0.0"
    port: int = 5001
    model_path: str = "/models"
    index_path: str = "/models/tpu_index"
    pdf_dir: str = "/pdfs"
    embedder_path: str = "/models/bge-m3"


@dataclass(frozen=True)
class SloConfig:
    """Burn-rate SLO objectives/thresholds (obs/slo.py::default_specs).

    Parsing is SAFE BY CONTRACT: these knobs are consumed on the scrape /
    ``GET /slo`` evaluation path, so a malformed or out-of-range env value
    falls back to the field default instead of raising — a typo'd
    objective must degrade a dashboard number, never 500 ``/metrics``.
    (Objectives must land strictly inside (0, 1) and latency thresholds
    strictly above 0 or ``SloSpec.__post_init__`` would reject them at
    evaluation time — exactly the failure mode this parse prevents.)
    """

    # fraction of requests that must be non-5xx
    # (env TPU_RAG_SLO_AVAILABILITY_OBJECTIVE)
    availability_objective: float = 0.999
    # end-to-end request latency SLO: objective fraction under threshold_s
    # (env TPU_RAG_SLO_REQUEST_P95_OBJECTIVE / TPU_RAG_SLO_REQUEST_P95_S)
    request_p95_objective: float = 0.95
    request_p95_s: float = 2.0
    # time-to-first-token SLO, continuous serving
    # (env TPU_RAG_SLO_TTFT_P95_OBJECTIVE / TPU_RAG_SLO_TTFT_P95_S)
    ttft_p95_objective: float = 0.95
    ttft_p95_s: float = 1.0
    # answer-quality SLO over the shadow auditor's audited requests
    # (obs/shadow.py): the objective fraction of audits whose measured
    # exact-vs-delivered logit error stays under the pinned approximation
    # tolerance — the same 0.15 the warm-tier and chunk-splice contracts
    # pin in tests, now observed on live traffic
    # (env TPU_RAG_SLO_QUALITY_OBJECTIVE / TPU_RAG_SLO_QUALITY_LOGIT_ERR)
    quality_objective: float = 0.99
    quality_logit_err: float = 0.15

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "SloConfig":
        env = dict(os.environ if env is None else env)

        def _f(var: str, dflt: float, lo: float, hi: float) -> float:
            raw = env.get(var)
            if raw is None:
                return dflt
            try:
                v = float(raw)
            except (TypeError, ValueError):
                return dflt
            return v if lo < v < hi else dflt

        inf = float("inf")
        return cls(
            availability_objective=_f(
                "TPU_RAG_SLO_AVAILABILITY_OBJECTIVE", 0.999, 0.0, 1.0
            ),
            request_p95_objective=_f(
                "TPU_RAG_SLO_REQUEST_P95_OBJECTIVE", 0.95, 0.0, 1.0
            ),
            request_p95_s=_f("TPU_RAG_SLO_REQUEST_P95_S", 2.0, 0.0, inf),
            ttft_p95_objective=_f(
                "TPU_RAG_SLO_TTFT_P95_OBJECTIVE", 0.95, 0.0, 1.0
            ),
            ttft_p95_s=_f("TPU_RAG_SLO_TTFT_P95_S", 1.0, 0.0, inf),
            quality_objective=_f(
                "TPU_RAG_SLO_QUALITY_OBJECTIVE", 0.99, 0.0, 1.0
            ),
            quality_logit_err=_f(
                "TPU_RAG_SLO_QUALITY_LOGIT_ERR", 0.15, 0.0, inf
            ),
        )


@dataclass(frozen=True)
class FlightConfig:
    """Engine flight recorder + incident bundles (obs/flight.py).

    The recorder is ON BY DEFAULT: it is the post-mortem signal, and its
    cost is a bounded ring append per scheduler decision (not measured on
    the chip: PERF.md §7, ``audits-on``).
    """

    # master switch for the in-process event journal (env TPU_RAG_FLIGHT)
    enabled: bool = True
    # ring capacity in events — the journal's memory bound; sized so a
    # breaker-flip bundle still holds the storm's whole causal prefix
    # (env TPU_RAG_FLIGHT_EVENTS)
    capacity: int = 4096
    # incident-bundle spool: directory, file cap (oldest pruned), and the
    # per-trigger cooldown that keeps a reset storm from writing a bundle
    # per reset (env TPU_RAG_FLIGHT_SPOOL / TPU_RAG_FLIGHT_SPOOL_MAX /
    # TPU_RAG_FLIGHT_COOLDOWN_S)
    spool_dir: str = "/tmp/tpu_rag_incidents"
    spool_max: int = 16
    cooldown_s: float = 30.0
    # arm the READ-ONLY debug surface (/debug/traces, /debug/timeline,
    # /debug/incidents) without arming fault injection: every /debug route
    # is 403 unless the process started with TPU_RAG_DEBUG=1 or
    # TPU_RAG_FAULTS set (the faults endpoint additionally requires
    # TPU_RAG_FAULTS itself — arming stays strictly opt-in)
    # (env TPU_RAG_DEBUG)
    debug_endpoints: bool = False
    # record prompt token ids on each arrival event (the replay trace
    # record, docs/REPLAY.md) — ON by default so a journal replays with
    # exact token streams; turn OFF when prompts are sensitive and a
    # shape-only replay (lengths, not ids) is enough
    # (env TPU_RAG_FLIGHT_ARRIVAL_IDS)
    arrival_ids: bool = True
    # durable flight WAL (obs/flight.py::FlightWAL): tee every journal
    # event onto disk as fsynced JSON lines so in-flight work survives
    # SIGKILL and a warm restart (server/main.py) can resume it. OFF by
    # default — the fsync-per-window tax only buys something where the
    # directory survives the pod (the deployment pins it on the PVC)
    # (env TPU_RAG_FLIGHT_WAL / TPU_RAG_FLIGHT_WAL_DIR)
    wal: bool = False
    wal_dir: str = "/tmp/tpu_rag_wal"
    # WAL bounds: events per segment file before rotation, and total
    # segment files kept across incarnations (oldest pruned) — the WAL is
    # a bounded flight journal, not an unbounded database
    # (env TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS / TPU_RAG_FLIGHT_WAL_SEGMENTS)
    wal_segment_events: int = 256
    wal_segments: int = 64
    # warm restart: scan the previous incarnation's WAL epoch on boot and
    # resubmit its in-flight requests through the scheduler's fold path
    # (env TPU_RAG_FLIGHT_WAL_RESTORE); cap on warmth-manifest entries
    # re-staged into the prefix cache first — 0 skips rehydration
    # (env TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS)
    wal_restore: bool = True
    wal_restore_chunks: int = 8

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "FlightConfig":
        env = dict(os.environ if env is None else env)
        out = cls()

        def _flag(var: str, field_name: str):
            nonlocal out
            if var in env:
                flag = env[var]
                if flag not in ("0", "1"):
                    raise ValueError(f"{var}={flag!r}: expected '0' or '1'")
                out = dataclasses.replace(out, **{field_name: flag == "1"})

        _flag("TPU_RAG_FLIGHT", "enabled")
        _flag("TPU_RAG_DEBUG", "debug_endpoints")
        _flag("TPU_RAG_FLIGHT_ARRIVAL_IDS", "arrival_ids")
        if "TPU_RAG_FLIGHT_EVENTS" in env:
            n = int(env["TPU_RAG_FLIGHT_EVENTS"])
            if n < 1:
                raise ValueError(f"TPU_RAG_FLIGHT_EVENTS={n}: expected >= 1")
            out = dataclasses.replace(out, capacity=n)
        if "TPU_RAG_FLIGHT_SPOOL" in env:
            out = dataclasses.replace(
                out, spool_dir=env["TPU_RAG_FLIGHT_SPOOL"]
            )
        if "TPU_RAG_FLIGHT_SPOOL_MAX" in env:
            n = int(env["TPU_RAG_FLIGHT_SPOOL_MAX"])
            if n < 1:
                raise ValueError(
                    f"TPU_RAG_FLIGHT_SPOOL_MAX={n}: expected >= 1"
                )
            out = dataclasses.replace(out, spool_max=n)
        if "TPU_RAG_FLIGHT_COOLDOWN_S" in env:
            v = float(env["TPU_RAG_FLIGHT_COOLDOWN_S"])
            if v < 0:
                raise ValueError(
                    f"TPU_RAG_FLIGHT_COOLDOWN_S={v}: expected >= 0"
                )
            out = dataclasses.replace(out, cooldown_s=v)
        _flag("TPU_RAG_FLIGHT_WAL", "wal")
        _flag("TPU_RAG_FLIGHT_WAL_RESTORE", "wal_restore")
        if "TPU_RAG_FLIGHT_WAL_DIR" in env:
            out = dataclasses.replace(out, wal_dir=env["TPU_RAG_FLIGHT_WAL_DIR"])
        if "TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS" in env:
            n = int(env["TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS"])
            if n < 1:
                raise ValueError(
                    f"TPU_RAG_FLIGHT_WAL_SEGMENT_EVENTS={n}: expected >= 1"
                )
            out = dataclasses.replace(out, wal_segment_events=n)
        if "TPU_RAG_FLIGHT_WAL_SEGMENTS" in env:
            n = int(env["TPU_RAG_FLIGHT_WAL_SEGMENTS"])
            if n < 2:
                raise ValueError(
                    f"TPU_RAG_FLIGHT_WAL_SEGMENTS={n}: expected >= 2"
                )
            out = dataclasses.replace(out, wal_segments=n)
        if "TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS" in env:
            n = int(env["TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS"])
            if n < 0:
                raise ValueError(
                    f"TPU_RAG_FLIGHT_WAL_RESTORE_CHUNKS={n}: expected >= 0"
                )
            out = dataclasses.replace(out, wal_restore_chunks=n)
        return out


@dataclass(frozen=True)
class ShadowConfig:
    """Shadow-traffic quality auditor (obs/shadow.py).

    Re-runs a sampled fraction of completed live requests on the EXACT
    serving path (no prefix reuse, no speculation, the engine's native KV
    dtype) and compares the shadow logits against the delivered stream —
    the online measurement of every lossy-by-contract approximation in
    the serving path (int8 warm tier, chunk splice/re-rotation, boundary
    correction, speculative verify). ON BY DEFAULT: the audit is one
    headroom-gated chunked forward per sampled request on the one-shot
    engine (never the serving pool); its cost to live traffic has not
    been measured on the chip (PERF.md §7, ``audits-on``).
    """

    # master switch (env TPU_RAG_SHADOW)
    enabled: bool = True
    # fraction of completed, audit-eligible requests re-run on the exact
    # path (env TPU_RAG_SHADOW_SAMPLE_RATE; the on-by-default cost bound
    # is stated at <= 0.05)
    sample_rate: float = 0.05
    # bounded audit queue: a sampled request arriving while this many
    # audits are already pending is SKIPPED (counted, never queued
    # unboundedly — audits must not pile up behind a busy device)
    # (env TPU_RAG_SHADOW_BACKLOG)
    backlog: int = 8
    # divergence-burst incident window: the SECOND diverged audit inside
    # this window spools a quality_divergence incident bundle (the same
    # second-event to a bundle discipline as the reset storm)
    # (env TPU_RAG_SHADOW_BURST_WINDOW_S)
    burst_window_s: float = 300.0

    def validate(self) -> None:
        if not 0.0 <= self.sample_rate <= 1.0:
            raise ValueError(
                f"ShadowConfig.sample_rate={self.sample_rate}: a sampling "
                "fraction must lie in [0, 1]"
            )
        if self.backlog < 1:
            raise ValueError(
                f"ShadowConfig.backlog={self.backlog}: expected >= 1"
            )
        if self.burst_window_s <= 0:
            raise ValueError(
                f"ShadowConfig.burst_window_s={self.burst_window_s}: "
                "expected > 0"
            )

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "ShadowConfig":
        env = dict(os.environ if env is None else env)
        out = cls()
        if "TPU_RAG_SHADOW" in env:
            flag = env["TPU_RAG_SHADOW"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_SHADOW={flag!r}: expected '0' or '1'"
                )
            out = dataclasses.replace(out, enabled=flag == "1")
        if "TPU_RAG_SHADOW_SAMPLE_RATE" in env:
            out = dataclasses.replace(
                out, sample_rate=float(env["TPU_RAG_SHADOW_SAMPLE_RATE"])
            )
        if "TPU_RAG_SHADOW_BACKLOG" in env:
            out = dataclasses.replace(
                out, backlog=int(env["TPU_RAG_SHADOW_BACKLOG"])
            )
        if "TPU_RAG_SHADOW_BURST_WINDOW_S" in env:
            out = dataclasses.replace(
                out, burst_window_s=float(env["TPU_RAG_SHADOW_BURST_WINDOW_S"])
            )
        out.validate()
        return out


@dataclass(frozen=True)
class TenantConfig:
    """Tenant attribution layer (obs/metrics.TenantTracker, obs/tenants.py).

    Extracts ``tenant_id`` (request body field / ``x-tenant-id`` header,
    default ``anon``) at the HTTP edge and interns it through a
    cardinality-bounded top-K tracker before it may become a metric label
    or event attr — ``rag_tenant_*`` families can never hold more than
    ``top_k``+1 tenant children (the +1 is the ``__other__`` overflow
    bucket), no matter the traffic. ON BY DEFAULT: attribution is a dict
    update per request edge/completion; its cost to a decode step has
    not been measured on the chip (PERF.md §7).
    """

    # master switch (env TPU_RAG_TENANTS)
    enabled: bool = True
    # tenants tracked by name; everything colder rides ``__other__``
    # (env TPU_RAG_TENANT_TOP_K)
    top_k: int = 8

    def validate(self) -> None:
        if self.top_k < 1:
            raise ValueError(
                f"TenantConfig.top_k={self.top_k}: expected >= 1"
            )

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "TenantConfig":
        env = dict(os.environ if env is None else env)
        out = cls()
        if "TPU_RAG_TENANTS" in env:
            flag = env["TPU_RAG_TENANTS"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_TENANTS={flag!r}: expected '0' or '1'"
                )
            out = dataclasses.replace(out, enabled=flag == "1")
        if "TPU_RAG_TENANT_TOP_K" in env:
            out = dataclasses.replace(
                out, top_k=int(env["TPU_RAG_TENANT_TOP_K"])
            )
        out.validate()
        return out


# ---------------------------------------------------------------------------
# top-level
# ---------------------------------------------------------------------------

SYSTEM_MESSAGE = (
    "You are a helpful assistant. Answer the user's question based ONLY on the "
    "given context.\nIf the context doesn't contain relevant information to the "
    "specific question, say 'I don't have enough information to answer that "
    "specific question.'\nDo not make up information or use general knowledge "
    "outside of the given context."
)
"""Verbatim parity with the reference's SYSTEM_MESSAGE (rag.py:35-37)."""


@dataclass(frozen=True)
class AppConfig:
    mesh: MeshConfig = field(default_factory=MeshConfig)
    dtypes: DTypePolicy = field(default_factory=DTypePolicy)
    # a LlamaConfig or a LatentMoEConfig: the engine builds the model and its
    # cache from the configuration's type (models/families.py)
    model: LlamaConfig = field(default_factory=LlamaConfig.llama_3_1_8b)
    encoder: EncoderConfig = field(default_factory=EncoderConfig.bge_m3)
    retrieval: RetrievalConfig = field(default_factory=RetrievalConfig)
    sampling: SamplingConfig = field(default_factory=SamplingConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    lookahead: LookaheadConfig = field(default_factory=LookaheadConfig)
    slo: SloConfig = field(default_factory=SloConfig)
    flight: FlightConfig = field(default_factory=FlightConfig)
    shadow: ShadowConfig = field(default_factory=ShadowConfig)
    tenants: TenantConfig = field(default_factory=TenantConfig)
    system_message: str = SYSTEM_MESSAGE

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "AppConfig":
        """Build config applying the reference's env-var surface plus TPU knobs.

        ``MODEL_PATH`` — rag.py:18; ``TPU_RAG_*`` — new framework overrides.
        """
        env = dict(os.environ if env is None else env)
        cfg = cls()
        server = cfg.server
        if "MODEL_PATH" in env:
            mp = env["MODEL_PATH"]
            server = dataclasses.replace(
                server,
                model_path=mp,
                index_path=os.path.join(mp, "tpu_index"),
                embedder_path=os.path.join(mp, "bge-m3"),
            )
        if "TPU_RAG_INDEX_PATH" in env:
            server = dataclasses.replace(server, index_path=env["TPU_RAG_INDEX_PATH"])
        if "TPU_RAG_PDF_DIR" in env:
            server = dataclasses.replace(server, pdf_dir=env["TPU_RAG_PDF_DIR"])
        if "TPU_RAG_PORT" in env:
            server = dataclasses.replace(server, port=int(env["TPU_RAG_PORT"]))
        mesh = cfg.mesh
        if "TPU_RAG_MESH" in env:
            # e.g. "dp=2,tp=4" or "tp=8"
            spec = env["TPU_RAG_MESH"]
            try:
                kv = dict(p.split("=", 1) for p in spec.split(","))
                overrides = {k: int(v) for k, v in kv.items() if k in ("dp", "sp", "tp")}
            except (ValueError, TypeError) as e:
                raise ValueError(
                    f"TPU_RAG_MESH={spec!r} is not of the form 'dp=N,sp=N,tp=N'"
                ) from e
            mesh = dataclasses.replace(mesh, **overrides)
        sampling = cfg.sampling
        if "TPU_RAG_MAX_NEW_TOKENS" in env:
            sampling = dataclasses.replace(
                sampling, max_new_tokens=int(env["TPU_RAG_MAX_NEW_TOKENS"])
            )
        engine = cfg.engine
        if "TPU_RAG_BATCHING" in env:
            mode = env["TPU_RAG_BATCHING"]
            if mode not in ("continuous", "coalesce"):
                raise ValueError(
                    f"TPU_RAG_BATCHING={mode!r}: expected 'continuous' or 'coalesce'"
                )
            engine = dataclasses.replace(engine, batching=mode)
        if "TPU_RAG_WEIGHT_QUANT" in env:
            wq = env["TPU_RAG_WEIGHT_QUANT"]
            if wq not in ("bf16", "int8"):
                raise ValueError(
                    f"TPU_RAG_WEIGHT_QUANT={wq!r}: expected 'bf16' or 'int8'"
                )
            engine = dataclasses.replace(engine, weight_quant=wq)
        if "TPU_RAG_KV_QUANT" in env:
            kvq = env["TPU_RAG_KV_QUANT"]
            if kvq not in ("bf16", "int8"):
                raise ValueError(
                    f"TPU_RAG_KV_QUANT={kvq!r}: expected 'bf16' or 'int8'"
                )
            engine = dataclasses.replace(engine, kv_quant=kvq)
        if "TPU_RAG_KV_PAGED" in env:
            flag = env["TPU_RAG_KV_PAGED"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_KV_PAGED={flag!r}: expected '0' or '1'"
                )
            engine = dataclasses.replace(engine, kv_paged=flag == "1")
        if "TPU_RAG_KV_BLOCK_SIZE" in env:
            bs = int(env["TPU_RAG_KV_BLOCK_SIZE"])
            if bs < 1:
                raise ValueError(f"TPU_RAG_KV_BLOCK_SIZE={bs}: expected >= 1")
            engine = dataclasses.replace(engine, kv_block_size=bs)
        if "TPU_RAG_KV_POOL_BLOCKS" in env:
            nb = int(env["TPU_RAG_KV_POOL_BLOCKS"])
            if nb < 0:
                raise ValueError(
                    f"TPU_RAG_KV_POOL_BLOCKS={nb}: expected >= 0 (0 = dense parity)"
                )
            engine = dataclasses.replace(engine, kv_pool_blocks=nb)
        if "TPU_RAG_SPEC_PAGED" in env:
            flag = env["TPU_RAG_SPEC_PAGED"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_SPEC_PAGED={flag!r}: expected '0' or '1'"
                )
            engine = dataclasses.replace(engine, spec_paged=flag == "1")
        if "TPU_RAG_SPEC_PAGED_TOKENS" in env:
            st = int(env["TPU_RAG_SPEC_PAGED_TOKENS"])
            if st < 1:
                raise ValueError(
                    f"TPU_RAG_SPEC_PAGED_TOKENS={st}: expected >= 1"
                )
            engine = dataclasses.replace(engine, spec_paged_tokens=st)
        if "TPU_RAG_SPEC_PAGED_MIN_ACCEPT" in env:
            ma = float(env["TPU_RAG_SPEC_PAGED_MIN_ACCEPT"])
            if not 0.0 <= ma <= 1.0:
                raise ValueError(
                    f"TPU_RAG_SPEC_PAGED_MIN_ACCEPT={ma}: an acceptance-"
                    "rate floor must lie in [0, 1]"
                )
            engine = dataclasses.replace(engine, spec_paged_min_accept=ma)
        if "TPU_RAG_INTERLEAVE_PREFILL" in env:
            flag = env["TPU_RAG_INTERLEAVE_PREFILL"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_INTERLEAVE_PREFILL={flag!r}: expected '0' or '1'"
                )
            engine = dataclasses.replace(engine, interleave_prefill=flag == "1")
        if "TPU_RAG_PREFILL_CHUNK_TOKENS" in env:
            ct = int(env["TPU_RAG_PREFILL_CHUNK_TOKENS"])
            if ct < 1:
                raise ValueError(
                    f"TPU_RAG_PREFILL_CHUNK_TOKENS={ct}: expected >= 1"
                )
            engine = dataclasses.replace(engine, prefill_chunk_tokens=ct)
        if "TPU_RAG_WINDOW_TOKEN_BUDGET" in env:
            wb = int(env["TPU_RAG_WINDOW_TOKEN_BUDGET"])
            if wb < 0:
                raise ValueError(
                    f"TPU_RAG_WINDOW_TOKEN_BUDGET={wb}: expected >= 0 "
                    "(0 = auto)"
                )
            engine = dataclasses.replace(engine, window_token_budget=wb)
        if "TPU_RAG_WARM_FULL_LADDER" in env:
            flag = env["TPU_RAG_WARM_FULL_LADDER"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_WARM_FULL_LADDER={flag!r}: expected '0' or '1'"
                )
            engine = dataclasses.replace(engine, warm_full_ladder=flag == "1")
        if "TPU_RAG_DO_SAMPLE" in env:
            flag = env["TPU_RAG_DO_SAMPLE"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_DO_SAMPLE={flag!r}: expected '0' or '1'"
                )
            sampling = dataclasses.replace(sampling, do_sample=flag == "1")
        if "TPU_RAG_SPECULATIVE" in env:
            spec = env["TPU_RAG_SPECULATIVE"]
            if spec not in ("off", "prompt_lookup", "auto"):
                raise ValueError(
                    f"TPU_RAG_SPECULATIVE={spec!r}: expected 'off', "
                    "'prompt_lookup' or 'auto'"
                )
            engine = dataclasses.replace(engine, speculative=spec)
        if "TPU_RAG_SYNC_STEPS" in env:
            k = int(env["TPU_RAG_SYNC_STEPS"])
            if k < 1:
                raise ValueError(f"TPU_RAG_SYNC_STEPS={k}: expected >= 1")
            engine = dataclasses.replace(engine, decode_sync_steps=k)
        if "TPU_RAG_FUSED" in env:
            flag = env["TPU_RAG_FUSED"]
            if flag not in ("0", "1"):
                raise ValueError(f"TPU_RAG_FUSED={flag!r}: expected '0' or '1'")
            engine = dataclasses.replace(engine, rag_fused=flag == "1")
        if "TPU_RAG_PREFIX_CACHE" in env:
            flag = env["TPU_RAG_PREFIX_CACHE"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_PREFIX_CACHE={flag!r}: expected '0' or '1'"
                )
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, enabled=flag == "1"
                ),
            )
        if "TPU_RAG_PREFIX_HBM_MB" in env:
            mb = int(env["TPU_RAG_PREFIX_HBM_MB"])
            if mb < 1:
                raise ValueError(f"TPU_RAG_PREFIX_HBM_MB={mb}: expected >= 1")
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, hbm_budget_mb=mb
                ),
            )
        if "TPU_RAG_PREFIX_REUSE" in env:
            policy = env["TPU_RAG_PREFIX_REUSE"]
            if policy not in ("exact", "slot", "chunk"):
                raise ValueError(
                    f"TPU_RAG_PREFIX_REUSE={policy!r}: expected "
                    "'exact', 'slot' or 'chunk'"
                )
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, reuse=policy
                ),
            )
        if "TPU_RAG_PREFIX_BOUNDARY_TOKENS" in env:
            bw = int(env["TPU_RAG_PREFIX_BOUNDARY_TOKENS"])
            if bw < 0:
                raise ValueError(
                    f"TPU_RAG_PREFIX_BOUNDARY_TOKENS={bw}: expected >= 0"
                )
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, boundary_tokens=bw
                ),
            )
        if "TPU_RAG_PREFIX_CHUNK_HOT_MIN" in env:
            hm = float(env["TPU_RAG_PREFIX_CHUNK_HOT_MIN"])
            if hm < 0:
                raise ValueError(
                    f"TPU_RAG_PREFIX_CHUNK_HOT_MIN={hm}: expected >= 0"
                )
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, chunk_hot_min=hm
                ),
            )
        if "TPU_RAG_PREFIX_CHUNK_POOL_REGS" in env:
            cr = int(env["TPU_RAG_PREFIX_CHUNK_POOL_REGS"])
            if cr < 1:
                raise ValueError(
                    f"TPU_RAG_PREFIX_CHUNK_POOL_REGS={cr}: expected >= 1"
                )
            engine = dataclasses.replace(
                engine,
                prefix_cache=dataclasses.replace(
                    engine.prefix_cache, chunk_pool_regs=cr
                ),
            )
        tiering = engine.kv_tiering
        if "TPU_RAG_KV_TIERING" in env:
            flag = env["TPU_RAG_KV_TIERING"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_KV_TIERING={flag!r}: expected '0' or '1'"
                )
            tiering = dataclasses.replace(tiering, enabled=flag == "1")
        if "TPU_RAG_KV_TIERING_WARM_BELOW" in env:
            tiering = dataclasses.replace(
                tiering, warm_below=float(env["TPU_RAG_KV_TIERING_WARM_BELOW"])
            )
        if "TPU_RAG_KV_TIERING_COLD_BELOW" in env:
            tiering = dataclasses.replace(
                tiering, cold_below=float(env["TPU_RAG_KV_TIERING_COLD_BELOW"])
            )
        if "TPU_RAG_KV_TIERING_HALF_LIFE_S" in env:
            tiering = dataclasses.replace(
                tiering, half_life_s=float(env["TPU_RAG_KV_TIERING_HALF_LIFE_S"])
            )
        if "TPU_RAG_KV_TIERING_HOST_MB" in env:
            tiering = dataclasses.replace(
                tiering, host_spill_mb=int(env["TPU_RAG_KV_TIERING_HOST_MB"])
            )
        if "TPU_RAG_KV_TIERING_INTERVAL_S" in env:
            tiering = dataclasses.replace(
                tiering,
                retier_interval_s=float(env["TPU_RAG_KV_TIERING_INTERVAL_S"]),
            )
        tiering.validate()  # cross-field rules once, with the env applied
        engine = dataclasses.replace(engine, kv_tiering=tiering)
        goodput = engine.goodput
        if "TPU_RAG_GOODPUT" in env:
            flag = env["TPU_RAG_GOODPUT"]
            if flag not in ("0", "1"):
                raise ValueError(
                    f"TPU_RAG_GOODPUT={flag!r}: expected '0' or '1'"
                )
            goodput = dataclasses.replace(goodput, enabled=flag == "1")
        if "TPU_RAG_CHIP_HOUR_USD" in env:
            goodput = dataclasses.replace(
                goodput, chip_hour_usd=float(env["TPU_RAG_CHIP_HOUR_USD"])
            )
        if "TPU_RAG_GOODPUT_PEAK_TFLOPS" in env:
            goodput = dataclasses.replace(
                goodput, peak_tflops=float(env["TPU_RAG_GOODPUT_PEAK_TFLOPS"])
            )
        if "TPU_RAG_GOODPUT_HBM_GBS" in env:
            goodput = dataclasses.replace(
                goodput, hbm_gbs=float(env["TPU_RAG_GOODPUT_HBM_GBS"])
            )
        goodput.validate()  # range rules once, with the env applied
        engine = dataclasses.replace(engine, goodput=goodput)
        engine.validate_interleave()  # cross-field rules, with the env applied
        resilience = cfg.resilience

        def _res_int(var: str, field_name: str, minimum: int):
            nonlocal resilience
            if var in env:
                v = int(env[var])
                if v < minimum:
                    raise ValueError(f"{var}={v}: expected >= {minimum}")
                resilience = dataclasses.replace(resilience, **{field_name: v})

        def _res_float(var: str, field_name: str, minimum: float):
            nonlocal resilience
            if var in env:
                v = float(env[var])
                if v < minimum:
                    raise ValueError(f"{var}={v}: expected >= {minimum}")
                resilience = dataclasses.replace(resilience, **{field_name: v})

        _res_int("TPU_RAG_ADMISSION_MAX_CONCURRENCY", "admission_max_concurrency", 1)
        _res_int("TPU_RAG_ADMISSION_MAX_QUEUE", "admission_max_queue", 0)
        _res_float("TPU_RAG_ADMISSION_RETRY_AFTER_S", "admission_retry_after_s", 0.0)
        _res_int("TPU_RAG_DEADLINE_MS", "deadline_ms", 1)
        _res_int("TPU_RAG_BREAKER_RESETS", "breaker_reset_threshold", 1)
        _res_float("TPU_RAG_BREAKER_WINDOW_S", "breaker_window_s", 1.0)
        _res_int("TPU_RAG_INFLIGHT_RETRIES", "inflight_retries", 0)
        _res_float("TPU_RAG_RETRY_BACKOFF_MS", "retry_backoff_ms", 0.0)
        _res_float("TPU_RAG_DRAIN_DEADLINE_S", "drain_deadline_s", 0.1)
        _res_float("TPU_RAG_DRAIN_RETRY_AFTER_S", "drain_retry_after_s", 0.0)
        lookahead = cfg.lookahead

        def _la_flag(var: str, field_name: str):
            nonlocal lookahead
            if var in env:
                flag = env[var]
                if flag not in ("0", "1"):
                    raise ValueError(f"{var}={flag!r}: expected '0' or '1'")
                lookahead = dataclasses.replace(
                    lookahead, **{field_name: flag == "1"}
                )

        def _la_num(var: str, field_name: str, minimum, cast):
            nonlocal lookahead
            if var in env:
                v = cast(env[var])
                if v < minimum:
                    raise ValueError(f"{var}={v}: expected >= {minimum}")
                lookahead = dataclasses.replace(lookahead, **{field_name: v})

        _la_flag("TPU_RAG_LOOKAHEAD", "enabled")
        _la_flag("TPU_RAG_LOOKAHEAD_PRESTAGE", "prestage_kv")
        _la_flag("TPU_RAG_LOOKAHEAD_SESSIONS", "session_pipelining")
        _la_num("TPU_RAG_LOOKAHEAD_WORKERS", "max_workers", 1, int)
        _la_num("TPU_RAG_LOOKAHEAD_INFLIGHT", "max_inflight", 1, int)
        _la_num("TPU_RAG_LOOKAHEAD_TTL_S", "ttl_s", 0.1, float)
        _la_num(
            "TPU_RAG_LOOKAHEAD_SESSION_TURNS", "session_context_turns", 1, int
        )
        _la_num("TPU_RAG_LOOKAHEAD_SESSION_MAX", "session_max", 1, int)
        _la_num(
            "TPU_RAG_LOOKAHEAD_SESSION_TTL_S", "session_ttl_s", 1.0, float
        )
        return dataclasses.replace(
            cfg, server=server, mesh=mesh, sampling=sampling, engine=engine,
            resilience=resilience, lookahead=lookahead,
            slo=SloConfig.from_env(env),
            flight=FlightConfig.from_env(env),
            shadow=ShadowConfig.from_env(env),
            tenants=TenantConfig.from_env(env),
        )
