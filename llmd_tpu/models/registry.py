"""Named model configurations.

Covers the model families the reference's guides deploy (SURVEY.md section 6
/ BASELINE.json configs): Llama-3 (8B/70B), Qwen2/Qwen3-class dense,
Mixtral 8x7B/8x22B and DeepSeek-style wide-EP MoE. Exact hyperparameters
follow the public HF configs for each family.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from llmd_tpu.config import ModelConfig, tiny_model_config

_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register_model(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_model_config(name: str, **overrides) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    if not overrides:
        return cfg
    # Rebuild so derived fields (head_dim, moe_intermediate_size) are
    # re-derived when their bases change, unless they were explicitly set.
    kw = dataclasses.asdict(cfg)
    if cfg.head_dim == cfg.hidden_size // cfg.num_heads and "head_dim" not in overrides:
        kw["head_dim"] = None
    if (
        cfg.moe_intermediate_size == cfg.intermediate_size
        and "moe_intermediate_size" not in overrides
    ):
        kw["moe_intermediate_size"] = None
    if cfg.holds_all_experts and "held_experts" not in overrides:
        kw["held_experts"] = None
    kw.update(overrides)
    return ModelConfig(**kw)


def list_models() -> list[str]:
    return sorted(_REGISTRY)


@register_model("tiny")
def _tiny() -> ModelConfig:
    return tiny_model_config()


@register_model("tiny-moe")
def _tiny_moe() -> ModelConfig:
    return tiny_model_config(
        name="tiny-moe", num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=64,
    )


@register_model("tiny-swa")
def _tiny_swa() -> ModelConfig:
    """Alternating sliding/full layers in miniature (gpt-oss layout) —
    the serving-level fixture for --kv-swa-ring and hybrid-APC paths."""
    return tiny_model_config(
        name="tiny-swa", sliding_window=64,
        layer_types=("sliding_attention", "full_attention"),
    )


@register_model("tiny-mla")
def _tiny_mla() -> ModelConfig:
    """CPU-testable MLA+MoE shape (DeepSeek architecture in miniature)."""
    return tiny_model_config(
        name="tiny-mla", kv_lora_rank=32, q_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, first_dense_layers=1,
        num_layers=3,
    )


@register_model("llama-3.2-3b")
def _llama32_3b() -> ModelConfig:
    return ModelConfig(
        name="llama-3.2-3b", vocab_size=128256, hidden_size=3072,
        intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
        head_dim=128, rope_theta=500000.0, max_model_len=8192,
        tie_word_embeddings=True,
    )


@register_model("llama-3-8b")
def _llama3_8b() -> ModelConfig:
    return ModelConfig(
        name="llama-3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=500000.0, max_model_len=8192,
    )


@register_model("llama-3-70b")
def _llama3_70b() -> ModelConfig:
    return ModelConfig(
        name="llama-3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        rope_theta=500000.0, max_model_len=8192,
    )


@register_model("qwen2-72b")
def _qwen2_72b() -> ModelConfig:
    return ModelConfig(
        name="qwen2-72b", vocab_size=152064, hidden_size=8192,
        intermediate_size=29568, num_layers=80, num_heads=64, num_kv_heads=8,
        rope_theta=1000000.0, max_model_len=32768, attention_bias=True,
        rms_norm_eps=1e-6,
    )


@register_model("qwen3-32b")
def _qwen3_32b() -> ModelConfig:
    """Qwen3-32B (HF Qwen/Qwen3-32B) — the reference's prefix-cache and
    tiered-offload benchmark model (SURVEY.md §6). QK-norm, no bias."""
    return ModelConfig(
        name="qwen3-32b", vocab_size=151936, hidden_size=5120,
        intermediate_size=25600, num_layers=64, num_heads=64, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, max_model_len=40960,
        qk_norm=True,
    )


@register_model("qwen3-30b-a3b")
def _qwen3_30b_a3b() -> ModelConfig:
    """Qwen3-30B-A3B (MoE): 128 experts, top-8, QK-norm."""
    return ModelConfig(
        name="qwen3-30b-a3b", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, rope_theta=1000000.0, max_model_len=40960,
        qk_norm=True,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
    )


@register_model("keye-vl-2.0-30b-a3b")
def _keye_vl2_30b_a3b() -> ModelConfig:
    """Keye-VL-2.0-30B-A3B's language model (HF Kwai-Keye/Keye-VL-2.0-
    30B-A3B, text only): Qwen3-MoE widths plus ``sa_config`` — an indexer
    of 16 heads x 64 over one shared key per token that picks the 2,048
    cached tokens a query token attends (docs/architecture/
    sparse-attention.md). The vision tower is not served."""
    return ModelConfig(
        name="keye-vl-2.0-30b-a3b", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, rope_theta=10000000.0, max_model_len=262144,
        rope_scaling={
            "mrope_section": [16, 24, 24], "rope_type": "default",
            "type": "default",
        },
        rms_norm_eps=1e-6, qk_norm=True,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        indexer_topk=2048, indexer_num_heads=16, indexer_head_dim=64,
    )


@register_model("tiny-dsa")
def _tiny_dsa() -> ModelConfig:
    """The sparse-attention architecture in miniature (CPU tests and the
    benchmark's rehearsal): 2 indexer heads x 8, top-32."""
    return tiny_model_config(
        name="tiny-dsa", qk_norm=True, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=64, max_model_len=512,
        indexer_topk=32, indexer_num_heads=2, indexer_head_dim=8,
    )


_LLLG = ("sliding_attention",) * 3 + ("full_attention",)


@register_model("k-exaone-236b-a23b")
def _k_exaone_236b_a23b() -> ModelConfig:
    """K-EXAONE-236B-A23B (HF LGAI-EXAONE/K-EXAONE-236B-A23B,
    ``model_type: exaone_moe``) as published: 64 q / 8 kv heads of 128 over
    hidden 6,144; three sliding layers (window 128, RoPE) to one full layer
    (no RoPE); first layer dense, then 128 experts top-8 + 1 shared, sigmoid
    scores with a selection-only bias, normalised, x 2.5. The one
    multi-token-prediction module is not served (ROADMAP M6). A rank of an
    expert-parallel deployment overrides ``held_experts`` /
    ``held_experts_first`` (docs/architecture/wide-ep.md)."""
    return ModelConfig(
        name="k-exaone-236b-a23b", vocab_size=153600, hidden_size=6144,
        intermediate_size=18432, num_layers=48, num_heads=64, num_kv_heads=8,
        head_dim=128, rope_theta=1000000.0, max_model_len=262144,
        rms_norm_eps=1e-5, qk_norm=True,
        sliding_window=128, layer_types=_LLLG * 12,
        rope_parameters={"full_attention": None},
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=2048,
        shared_expert_intermediate_size=2048, first_dense_layers=1,
        router_scoring="sigmoid", topk_method="group_top2",
        norm_topk_prob=True, routed_scaling_factor=2.5,
    )


@register_model("tiny-exaone")
def _tiny_exaone() -> ModelConfig:
    """K-EXAONE's architecture in miniature (CPU tests and the benchmark's
    rehearsal): dense prefix 1, ``LLLG`` with window 16, sigmoid top-2 of 16
    with bias and scaling, one shared expert, and a held share: this rank
    holds experts 4-7 of the 16 the router scores."""
    return tiny_model_config(
        name="tiny-exaone", num_layers=8, qk_norm=True, max_model_len=512,
        sliding_window=16, layer_types=_LLLG * 2,
        rope_parameters={"full_attention": None},
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=64,
        shared_expert_intermediate_size=64, first_dense_layers=1,
        router_scoring="sigmoid", topk_method="group_top2",
        norm_topk_prob=True, routed_scaling_factor=2.5,
        held_experts=4, held_experts_first=4,
    )


@register_model("mellum2-12b-a2.5b")
def _mellum2_12b_a2_5b() -> ModelConfig:
    """Mellum2-12B-A2.5B (HF JetBrains/Mellum2-12B-A2.5B-Instruct,
    ``model_type: mellum``) as published: 32 q / 4 kv heads of 128 over hidden
    2,304; three sliding layers (window 1,024) to one full layer, and a RoPE
    table per kind of layer: the sliding layers rotate under the plain table
    (theta 5e5), the full layers under YaRN (factor 16 over 8,192, with its
    factor on cos and sin). Every layer's FFN is 64 experts top-8 of width
    896, softmax over all, renormalised, no shared expert. QK-norm is the
    Qwen3-MoE convention whose key names the config uses (config.json has no
    key for it). The MTP head its card mentions has no key and is not served.
    A rank of an expert-parallel deployment overrides ``held_experts`` /
    ``held_experts_first`` (docs/architecture/wide-ep.md)."""
    return ModelConfig(
        name="mellum2-12b-a2.5b", vocab_size=98304, hidden_size=2304,
        intermediate_size=7168, num_layers=28, num_heads=32, num_kv_heads=4,
        head_dim=128, rope_theta=500000.0, max_model_len=131072,
        rms_norm_eps=1e-6, qk_norm=True,
        sliding_window=1024, layer_types=_LLLG * 7,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        },
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
        norm_topk_prob=True,
    )


@register_model("tiny-mellum2")
def _tiny_mellum2() -> ModelConfig:
    """Mellum2's architecture in miniature (CPU tests and the benchmark's
    rehearsal): ``LLLG`` x 2 with window 16, YaRN (factor 4 over 64
    positions) on the full layers and the plain table on the sliding ones,
    softmax top-2 of 16 renormalised, no shared expert, and a held share:
    this rank holds experts 4-7 of the 16 the router scores."""
    return tiny_model_config(
        name="tiny-mellum2", num_layers=8, qk_norm=True, max_model_len=512,
        rms_norm_eps=1e-6, sliding_window=16, layer_types=_LLLG * 2,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
                "original_max_position_embeddings": 64, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0},
        },
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=64,
        norm_topk_prob=True, held_experts=4, held_experts_first=4,
    )


# granitemoehybrid: one attention layer in ten, at index 5 of each period.
_MMMMMAMMMM = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@register_model("granite-4.0-h-small")
def _granite_4_h_small() -> ModelConfig:
    """granite-4.0-h-small (HF ibm-granite/granite-4.0-h-small,
    ``granitemoehybrid``, 32B-A9B): 36 Mamba-2 mixers (128 heads x 64, state
    128, conv 4) and 4 attention layers (GQA 32/8 x 128, NO positional
    encoding, softmax scale 1/128), every layer's FFN 72 experts top-10 of
    width 768 plus a shared GLU of 1,536, muP-style multipliers, tied
    embedding."""
    return ModelConfig(
        name="granite-4.0-h-small", vocab_size=100352, hidden_size=4096,
        intermediate_size=768, num_layers=40, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=10000.0, max_model_len=131072,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
        layer_types=_MMMMMAMMMM * 4, rope_layer_types=(),
        attention_multiplier=0.0078125, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4,
        num_experts=72, num_experts_per_tok=10, moe_intermediate_size=768,
        shared_expert_intermediate_size=1536, norm_topk_prob=True,
    )


@register_model("tiny-granite-hybrid")
def _tiny_granite_hybrid() -> ModelConfig:
    """granite-4.0-h-small's architecture in miniature (CPU tests and the
    benchmark's rehearsal): the same pattern of ten, 4 mixer heads x 8 with
    state 16 and ONE group, GQA 4/2 without rope at the model's own scale,
    top-2 of 8 experts with a shared GLU, the three multipliers, and a held
    share: this rank holds experts 0-3 of the 8 the router scores."""
    return tiny_model_config(
        name="tiny-granite-hybrid", num_layers=10, max_model_len=512,
        tie_word_embeddings=True,
        layer_types=_MMMMMAMMMM, rope_layer_types=(),
        attention_multiplier=1.0 / 16, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
        mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
        mamba_n_groups=1, mamba_d_conv=4,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=64, norm_topk_prob=True,
        held_experts=4, held_experts_first=0,
    )


# qwen3_next: three Gated DeltaNet layers, then one of full attention
# (``full_attention_interval`` 4).
_LLLF = ("linear_attention",) * 3 + ("full_attention",)


@register_model("qwen3-next-80b-a3b")
def _qwen3_next_80b_a3b() -> ModelConfig:
    """Qwen3-Next-80B-A3B-Instruct (HF Qwen/Qwen3-Next-80B-A3B-Instruct,
    ``qwen3_next``): 48 layers ``L L L F``: 36 Gated DeltaNet mixers (16 key
    heads x 128, 32 value heads x 128, conv 4) and 12 gated attention layers
    (GQA 16/2 x 256, the q projection twice as wide and its second half a
    sigmoid gate on the output, zero-centred QK-norm, RoPE over the first 64
    of 256 dimensions at theta 1e7); every layer's FFN 512 experts top-10 of
    width 512 plus a shared expert of 512 under its own sigmoid gate;
    zero-centred RMS norms; untied vocabulary. (Its MTP module is not served.)"""
    return ModelConfig(
        name="qwen3-next-80b-a3b", vocab_size=151936, hidden_size=2048,
        intermediate_size=5120, num_layers=48, num_heads=16, num_kv_heads=2,
        head_dim=256, rope_theta=1e7, max_model_len=262144,
        rms_norm_eps=1e-6, qk_norm=True, layer_types=_LLLF * 12,
        partial_rotary_factor=0.25, attn_output_gate=True,
        norm_zero_centered=True,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        num_experts=512, num_experts_per_tok=10, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, shared_expert_gate=True,
        norm_topk_prob=True,
    )


@register_model("tiny-qwen3-next")
def _tiny_qwen3_next() -> ModelConfig:
    """qwen3-next's architecture in miniature (CPU tests and the benchmark's
    rehearsal): two whole periods ``L L L F``, 2 key heads x 8 serving 4 value
    heads x 8, gated GQA 4/2 x 16 with a quarter-width rotation and
    zero-centred norms, top-2 of 8 experts with a gated shared one, an untied
    vocabulary, and a held share: experts 0-3 of the 8 the router scores."""
    return tiny_model_config(
        name="tiny-qwen3-next", num_layers=8, max_model_len=512,
        rms_norm_eps=1e-6, qk_norm=True, layer_types=_LLLF * 2,
        partial_rotary_factor=0.25, attn_output_gate=True,
        norm_zero_centered=True,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        linear_conv_kernel_dim=4,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, shared_expert_gate=True,
        norm_topk_prob=True, held_experts=4, held_experts_first=0,
    )


def nemotron_h_layers(pattern: str) -> tuple[tuple[str, ...], tuple[bool, ...]]:
    """``(layer_types, layer_ffn)`` of a ``nemotron_h``
    ``hybrid_override_pattern``: its blocks are ONE mixer each (``M`` a
    Mamba-2 mixer, ``*`` attention, ``E`` an expert FFN), ``x = x + Block(
    RMSNorm(x))``. A mixer and the ``E`` behind it are one layer of mixer +
    FFN, as every other model's; a mixer that another mixer follows is a
    layer WITHOUT FFN. 52 blocks are 29 layers."""
    types, ffn = [], []
    for ch in pattern:
        if ch in "M*":
            types.append("mamba" if ch == "M" else "attention")
            ffn.append(False)
        elif ch == "E" and ffn and not ffn[-1]:
            ffn[-1] = True
        else:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: an expert block has no "
                "mixer in front of it (or an unknown block)"
            )
    return tuple(types), tuple(ffn)


_NEMOTRON_3_NANO = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@register_model("nemotron-3-nano-30b-a3b")
def _nemotron_3_nano() -> ModelConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (HF nvidia/NVIDIA-Nemotron-3-Nano-30B-
    A3B-BF16, ``nemotron_h``, 31.6B-A3.2B): 52 blocks of one mixer each: 23
    Mamba-2 (64 heads x 64, state 128, B and C in 8 groups, conv 4), 23
    expert blocks (128 non-gated relu^2 experts top-6 of width 1,856 + one
    shared of 3,712; sigmoid scores, selection bias, normalised, x 2.5) and
    6 attention blocks (GQA 32/2 x 128, NO positional encoding); untied
    vocabulary. Served as 29 layers (``nemotron_h_layers``)."""
    types, ffn = nemotron_h_layers(_NEMOTRON_3_NANO)
    return ModelConfig(
        name="nemotron-3-nano-30b-a3b", vocab_size=131072, hidden_size=2688,
        intermediate_size=1856, num_layers=len(types), num_heads=32,
        num_kv_heads=2, head_dim=128, rope_theta=10000.0,
        max_model_len=262144, rms_norm_eps=1e-5,
        layer_types=types, layer_ffn=ffn, rope_layer_types=(),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=8, mamba_d_conv=4,
        num_experts=128, num_experts_per_tok=6, moe_intermediate_size=1856,
        shared_expert_intermediate_size=3712, moe_activation="relu2",
        router_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )


@register_model("tiny-nemotron-h")
def _tiny_nemotron_h() -> ModelConfig:
    """nemotron-3-nano's architecture in miniature (CPU tests and the
    benchmark's rehearsal): two whole cycles ``MEMEM*E`` and a tail
    ``MEM*E`` that is none (11 layers, 3 of them without FFN), 4 mixer heads
    x 8 with state 16 and B, C in TWO groups, GQA 4/2 without rope, 8
    non-gated relu^2 experts top-2 of width 24 (no multiple of a lane) + a
    shared one of 48, sigmoid scores with a selection bias, x 2.5, an untied
    vocabulary, and a held share: experts 0-3 of the 8 the router scores."""
    types, ffn = nemotron_h_layers("MEMEM*E" * 2 + "MEM*E")
    return tiny_model_config(
        name="tiny-nemotron-h", num_layers=len(types), max_model_len=512,
        rms_norm_eps=1e-5, layer_types=types, layer_ffn=ffn, rope_layer_types=(),
        mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
        mamba_n_groups=2, mamba_d_conv=4,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
        shared_expert_intermediate_size=48, moe_activation="relu2",
        router_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, held_experts=4, held_experts_first=0,
    )


@register_model("mixtral-8x7b")
def _mixtral_8x7b() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=1000000.0, max_model_len=32768,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=14336,
    )


@register_model("mixtral-8x22b")
def _mixtral_8x22b() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x22b", vocab_size=32768, hidden_size=6144,
        intermediate_size=16384, num_layers=56, num_heads=48, num_kv_heads=8,
        rope_theta=1000000.0, max_model_len=65536,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16384,
    )


@register_model("deepseek-moe-wide")
def _deepseek_wide() -> ModelConfig:
    """DeepSeek-R1-class wide-EP shape (GQA stand-in for MLA; 256 experts,
    top-8, shared expert) -- the BASELINE.json config-3 target geometry."""
    return ModelConfig(
        name="deepseek-moe-wide", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128, num_kv_heads=16,
        head_dim=64,
        rope_theta=10000.0, max_model_len=16384,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        shared_expert_intermediate_size=2048,
    )


@register_model("gpt-oss-20b")
def _gpt_oss_20b() -> ModelConfig:
    """gpt-oss-20b (HF openai/gpt-oss-20b): alternating sliding/full
    attention with per-head sinks, 32 experts top-4 with clamped-swiglu
    biased experts, yarn rope — the reference's flagship P/D benchmark
    model (guides/pd-disaggregation/README.md:600-615)."""
    return ModelConfig(
        name="gpt-oss-20b", vocab_size=201088, hidden_size=2880,
        intermediate_size=2880, num_layers=24, num_heads=64,
        num_kv_heads=8, head_dim=64, rope_theta=150000.0,
        max_model_len=131072,
        sliding_window=128,
        layer_types=tuple(
            "sliding_attention" if i % 2 == 0 else "full_attention"
            for i in range(24)
        ),
        attention_bias=True, attention_out_bias=True, attention_sinks=True,
        num_experts=32, num_experts_per_tok=4, moe_intermediate_size=2880,
        moe_activation="swiglu_oss", router_logit_bias=True,
        norm_topk_prob=True,
        rope_scaling={
            "rope_type": "yarn", "factor": 32.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "original_max_position_embeddings": 4096,
        },
    )


@register_model("deepseek-v2-lite")
def _deepseek_v2_lite() -> ModelConfig:
    """DeepSeek-V2-Lite (HF deepseek-ai/DeepSeek-V2-Lite): MLA without a
    query LoRA, 64 routed + 2 shared experts, first layer dense."""
    return ModelConfig(
        name="deepseek-v2-lite", vocab_size=102400, hidden_size=2048,
        intermediate_size=10944, num_layers=27, num_heads=16,
        num_kv_heads=16, rope_theta=10000.0, max_model_len=32768,
        kv_lora_rank=512, q_lora_rank=0,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=64, num_experts_per_tok=6, moe_intermediate_size=1408,
        shared_expert_intermediate_size=2816, first_dense_layers=1,
    )


@register_model("deepseek-v3.2")
def _deepseek_v32() -> ModelConfig:
    """DeepSeek-V3.2 (HF deepseek-ai/DeepSeek-V3.2, ``model_type:
    deepseek_v32``) as published: latent attention (q latent 1,536, cached
    row 512 + 64) under yarn x 40 with its softmax temperature, the
    lightning indexer of 64 heads x 128 over one key a token (queries from
    the query latent, 64 of its 128 dimensions rotated) picking the 2,048
    rows a query token reads (models/mla_dsa.py); first 3 layers dense, then
    256 experts top-8 in 8 groups of which 4 are kept, sigmoid scores with a
    selection-only bias, normalised, x 2.5, and one shared expert. The one
    multi-token-prediction module is not served (ROADMAP M6); the latent
    and the indexer key are cached in the served dtype, not FP8. A rank of
    an expert-parallel deployment overrides ``held_experts`` /
    ``held_experts_first`` (docs/architecture/wide-ep.md)."""
    return ModelConfig(
        name="deepseek-v3.2", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128,
        num_kv_heads=128, rope_theta=10000.0, max_model_len=163840,
        rms_norm_eps=1e-6,
        rope_scaling={
            "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096,
        },
        kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        indexer_topk=2048, indexer_num_heads=64, indexer_head_dim=128,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        shared_expert_intermediate_size=2048, first_dense_layers=3,
        router_scoring="sigmoid", topk_method="group_top2",
        n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5,
    )


@register_model("tiny-mla-dsa")
def _tiny_mla_dsa() -> ModelConfig:
    """DeepSeek-V3.2's architecture in miniature (CPU tests and the
    benchmark's rehearsal): one dense layer then two expert layers, a query
    latent, an indexer of 2 heads x 16 of which 8 dimensions rotate, top-32,
    yarn with its temperature, sigmoid top-2 of 16 experts in 4 groups of
    which 2 are kept, one shared expert, and a held share: this rank holds
    experts 4-7 of the 16 the router scores."""
    return tiny_model_config(
        name="tiny-mla-dsa", num_layers=3, num_kv_heads=4, max_model_len=512,
        rope_scaling={
            "type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 128,
        },
        kv_lora_rank=32, q_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        indexer_topk=32, indexer_num_heads=2, indexer_head_dim=16,
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=64,
        shared_expert_intermediate_size=64, first_dense_layers=1,
        router_scoring="sigmoid", topk_method="group_top2",
        n_group=4, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5,
        held_experts=4, held_experts_first=4,
    )


@register_model("deepseek-r1")
def _deepseek_r1() -> ModelConfig:
    """DeepSeek-V3/R1 (HF deepseek-ai/DeepSeek-R1): full MLA (q LoRA 1536,
    kv latent 512+64), 256 routed + 1 shared expert, top-8, first 3 layers
    dense -- the reference wide-EP headline model (SURVEY.md §3.3)."""
    return ModelConfig(
        name="deepseek-r1", vocab_size=129280, hidden_size=7168,
        intermediate_size=18432, num_layers=61, num_heads=128,
        num_kv_heads=128, rope_theta=10000.0, max_model_len=163840,
        kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        shared_expert_intermediate_size=2048, first_dense_layers=3,
    )
