"""Engine configuration.

The reference configures its engine (vLLM) via CLI flags on the model-server
Deployment (e.g. --tensor-parallel-size, --max-num-batched-tokens,
--max-model-len, --block-size; see reference
guides/pd-disaggregation/modelserver/tpu/v6/vllm/patch-decode.yaml and
docs/architecture/core/model-servers.md:3-25). Here the same knobs are
dataclasses consumed by the JAX engine; the serve CLI maps flag names 1:1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any


@dataclasses.dataclass
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer.

    Covers the dense Llama family (Llama-2/3, Qwen2) and MoE families
    (Mixtral, DeepSeek-style) via ``num_experts``.
    """

    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500000.0
    # HF-style rope_scaling dict (rope_type: llama3 | linear | default).
    # Llama-3.1+ checkpoints ship llama3 frequency scaling; loading them
    # without it silently degrades long-context quality.
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    max_model_len: int = 8192
    dtype: str = "bfloat16"
    # Weight quantization: None (full precision) | "int8" (symmetric
    # per-output-channel weights + dynamic per-token activations, native
    # int8 MXU matmuls — the TPU stand-in for the reference's FP8 DeepGEMM
    # serving path, docker/Dockerfile.cuda:69-70). Norms, embeddings,
    # routers, and biases stay full precision.
    quantization: str | None = None
    tie_word_embeddings: bool = False
    # Qwen2-style attention bias on QKV projections.
    attention_bias: bool = False
    # gpt-oss extras: bias on the o projection too, and per-q-head
    # attention SINKS — a learnable virtual-key logit appended to every
    # softmax (its value contribution is zero, so it only absorbs
    # probability mass).
    attention_out_bias: bool = False
    attention_sinks: bool = False
    # Qwen3-style per-head RMS norm on Q and K (applied before RoPE).
    qk_norm: bool = False
    # --- sliding-window attention (gpt-oss / Mistral / long-context Qwen) ---
    # sliding_window > 0 limits attention to the trailing N positions.
    # Which layers it applies to follows the HF conventions:
    #   layer_types set  -> per-layer "sliding_attention"/"full_attention"
    #                       (gpt-oss alternating pattern)
    #   max_window_layers >= 0 -> layers >= max_window_layers slide
    #                       (Qwen2 use_sliding_window semantics)
    #   neither          -> every layer slides (Mistral)
    sliding_window: int = 0
    layer_types: tuple | None = None
    max_window_layers: int | None = None
    # --- multi-LoRA serving (reference model-servers.md:78-89) ---
    # num_lora_adapters > 0 allocates that many adapter slots (rank
    # lora_rank, applied to the q and v projections); slot 0 is reserved
    # for "no adapter" (zero weights). Adapter NAMES live at the serving
    # layer; the model only sees integer slot ids per sequence.
    num_lora_adapters: int = 0
    lora_rank: int = 16
    # lora_dynamic turns the fixed slots into a PAGED ADAPTER POOL
    # (docs/architecture/multi-tenant-lora.md): num_lora_adapters bounds
    # only HBM residency; the serving registry (/v1/load_lora_adapter)
    # is unbounded, with LRU eviction of idle adapters and cold loads
    # parked at step boundaries instead of stalling the batch.
    lora_dynamic: bool = False
    # --- MoE (0 experts => dense MLP) ---
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int | None = None
    # Router variants across the MoE families:
    #   Mixtral/Qwen3Moe: softmax scores, plain top-k, renormalized.
    #   DeepSeek-V2:      softmax, optionally group-limited top-k (max per
    #                     group), usually NOT renormalized, scaled.
    #   DeepSeek-V3/R1:   sigmoid scores + learned correction bias for
    #                     selection (noaux_tc), top-2-sum group scores,
    #                     renormalized, scaled.
    router_scoring: str = "softmax"  # "softmax" | "sigmoid"
    topk_method: str = "greedy"  # "greedy" | "group_max" | "group_top2"
    # gpt-oss: the router bias is part of the LOGITS (selection by
    # logits+bias, weights = softmax over the selected logits — which our
    # softmax-topk-renormalize already equals once the bias is folded in),
    # unlike DeepSeek-V3's selection-only correction bias.
    router_logit_bias: bool = False
    # Expert MLP family: "silu" (Mixtral/Qwen/DeepSeek SwiGLU),
    # "swiglu_oss" (gpt-oss: interleaved-loaded gate/up WITH biases,
    # gate clamped to [-inf, limit], up to [-limit, limit],
    # glu = gate * sigmoid(alpha * gate), out = (up + 1) * glu) or
    # "relu2" (nemotron_h: NOT gated, down(relu(up x)^2): two matrices an
    # expert, the leaves ``we_up`` / ``we_down``; the shared expert alike).
    moe_activation: str = "silu"
    swiglu_limit: float = 7.0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # DeepSeek-style: first N layers use a dense MLP, the rest are MoE.
    first_dense_layers: int = 0
    # Shared expert intermediate size (DeepSeek V2/V3 style); 0 = none.
    shared_expert_intermediate_size: int = 0
    # --- MLA (multi-head latent attention, DeepSeek V2/V3/R1) ---
    # kv_lora_rank > 0 switches attention to MLA: the KV cache stores one
    # compressed latent per token (kv_lora_rank + qk_rope_head_dim wide)
    # instead of per-head K/V — the memory win that makes wide-EP decode
    # batches fit. q_lora_rank 0 = dense q projection (V2-Lite).
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # --- learned sparse attention (HF ``sa_config``; DeepSeek-Sparse-
    # Attention-style indexer over GQA, docs/architecture/sparse-attention.md) ---
    # indexer_topk > 0 turns it on: every layer carries an indexer of
    # ``indexer_num_heads`` x ``indexer_head_dim`` over ONE shared key per
    # token (cached in a plane beside K and V, under the same page ids),
    # and a query token attends only the ``indexer_topk`` earlier tokens
    # its index scores rank highest (all of them while that many or fewer
    # are cached). Nothing turns the path off for such a model.
    indexer_topk: int = 0
    indexer_num_heads: int = 0
    indexer_head_dim: int = 0
    # --- the held share of the routed experts (docs/architecture/wide-ep.md,
    # "One rank's share") ---
    # One rank of an expert-parallel deployment holds ``held_experts`` of the
    # ``num_experts`` the router scores, ids ``held_experts_first`` onward:
    # the router keeps its width, the expert leaves are [L, held, ..], a
    # pick outside the range gives no row, and the layer returns the held
    # experts' part of the sum (plus the shared expert). None = all of them,
    # which is every model that is served whole: the same rule, a range that
    # covers every pick.
    held_experts: int | None = None
    held_experts_first: int = 0
    # A RoPE table per KIND of layer (HF ``rope_parameters`` keyed by layer
    # type): ``layer_types`` value -> that kind's own ``{"rope_theta",
    # "rope_type", "factor", ...}``, or None: layers of that kind do not
    # rotate q and k at all. A kind that is not named takes the model's
    # table (``rope_theta`` / ``rope_scaling``). Mellum2 rotates its full
    # layers under YaRN and its sliding ones under the plain table; the
    # EXAONE-4.0 family rotates its sliding layers only
    # (``{"full_attention": None}``); granite's and Nemotron-H's attention
    # has no positional encoding (every kind None). ``rope_specs`` and
    # ``layer_rope`` are how the model reads it.
    rope_parameters: dict | None = None
    # The older spelling of "no table", folded into ``rope_parameters`` at
    # construction: the ``layer_types`` values that rotate (under the
    # model's table); every other kind gets None.
    rope_layer_types: tuple | None = None
    # Softmax scale of the attention layers; None = head_dim ** -0.5.
    # granitemoehybrid states its own (``attention_multiplier``).
    attention_multiplier: float | None = None
    # muP-style multipliers (granite): on the embedding's output, on every
    # residual branch (mixer and FFN), and the divisor of the logits.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # --- state-space mixers (Mamba-2; docs/architecture/kv-cache.md, "The
    # state pool") ---
    # ``layer_types`` entries "mamba" run a Mamba-2 mixer in place of
    # attention: ``mamba_n_heads`` x ``mamba_d_head`` channels, a state of
    # ``mamba_d_state`` a channel, B and C shared by the heads of a group,
    # a causal depthwise conv of ``mamba_d_conv`` taps in front. Such a
    # layer keeps a FIXED-size state a sequence (no pages): the SSM state
    # [heads, d_head, d_state] in float32 and the conv's last ``d_conv - 1``
    # inputs in the model's dtype.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # Which layers have an FFN behind their mixer; None = every layer. A
    # model whose blocks are ONE mixer each (nemotron_h: a mamba, an
    # attention or an expert block) is served as layers of mixer + FFN where
    # an expert block follows a mixer, and as a layer WITHOUT FFN where the
    # next block is a mixer again: the FFN leaves (post-norm, router,
    # experts) are stacked over the layers that have one.
    layer_ffn: tuple | None = None
    # --- gated delta-rule mixers (HF ``qwen3_next``'s Gated DeltaNet; docs/
    # architecture/kv-cache.md, "The state pool") ---
    # ``layer_types`` entries "linear_attention" run a gated delta-rule mixer
    # in place of attention: ``linear_num_value_heads`` heads, each a state
    # [``linear_key_head_dim``, ``linear_value_head_dim``] in float32 that is
    # READ before it is written (S = a S + b k (v - (a S)^T k)^T), fed by
    # ``linear_num_key_heads`` q and k heads (each serving value heads / key
    # heads consecutive value heads), a causal depthwise conv of
    # ``linear_conv_kernel_dim`` taps over q, k and v in front. Such a layer
    # keeps its state in the state pool as the Mamba-2 mixers do; a model has
    # one kind of recurrent mixer.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # The attention layers of the same family: the share of a head's
    # dimensions that RoPE rotates (the first ones, rotate-half among
    # themselves); a q projection of twice the width whose second half per
    # head gates the attention's output through a sigmoid; RMS norms whose
    # weight is stored zero-centred (applied as 1 + w, the block's, the final
    # and the q/k norms; a mixer's own gated norm keeps a plain weight); the
    # shared expert's output scaled by sigmoid(x . w), one number a token.
    partial_rotary_factor: float = 1.0
    attn_output_gate: bool = False
    norm_zero_centered: bool = False
    shared_expert_gate: bool = False

    def __post_init__(self) -> None:
        if self.quantization not in (None, "int8"):
            raise ValueError(
                f"quantization={self.quantization!r} not supported "
                "(None or 'int8')"
            )
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.moe_intermediate_size is None:
            self.moe_intermediate_size = self.intermediate_size
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries for "
                    f"{self.num_layers} layers"
                )
        if self.layer_ffn is not None:
            self.layer_ffn = tuple(bool(f) for f in self.layer_ffn)
            if len(self.layer_ffn) != self.num_layers:
                raise ValueError(
                    f"layer_ffn has {len(self.layer_ffn)} entries for "
                    f"{self.num_layers} layers"
                )
            if all(self.layer_ffn):
                self.layer_ffn = None
            elif not self.state_space:
                raise ValueError(
                    "layer_ffn (layers without FFN) is supported for models "
                    "whose layers differ in their mixer (layer_types with "
                    "\"mamba\") only"
                )
        if self.moe_activation not in ("silu", "swiglu_oss", "relu2"):
            raise ValueError(f"moe_activation={self.moe_activation!r} not supported")
        if self.held_experts is None:
            self.held_experts = self.num_experts
        if not (
            0 <= self.held_experts_first
            and self.held_experts_first + self.held_experts <= self.num_experts
            and (self.held_experts > 0 or self.num_experts == 0)
        ):
            raise ValueError(
                f"held experts [{self.held_experts_first}, "
                f"{self.held_experts_first + self.held_experts}) are not a "
                f"range of the router's {self.num_experts}"
            )
        if self.rope_layer_types is not None:
            if self.layer_types is None:
                raise ValueError("rope_layer_types needs layer_types")
            own = dict(self.rope_parameters or {})
            for t in dict.fromkeys(self.layer_types):
                if t not in self.rope_layer_types:
                    own[t] = None
                elif t in own and own[t] is None:
                    del own[t]
            self.rope_parameters, self.rope_layer_types = own, None
        if self.rope_parameters is not None and self.layer_types is None:
            raise ValueError("rope_parameters is keyed by layer_types' values")
        if self.state_space:
            if self.delta_rule and "mamba" in self.layer_types:
                raise ValueError(
                    "layer_types mixes \"mamba\" and \"linear_attention\": the "
                    "state pool holds one kind of recurrent state"
                )
            if self.delta_rule:
                hk, hv = self.linear_num_key_heads, self.linear_num_value_heads
                if min(hk, hv, self.linear_key_head_dim,
                       self.linear_value_head_dim) <= 0 or hv % hk:
                    raise ValueError(
                        "linear_attention layers need linear_num_key_heads, "
                        "linear_num_value_heads (a multiple of the key heads), "
                        "linear_key_head_dim and linear_value_head_dim"
                    )
            elif min(self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state) <= 0:
                raise ValueError(
                    "mamba layers need mamba_n_heads, mamba_d_head and "
                    "mamba_d_state"
                )
            elif self.mamba_n_groups < 1 or self.mamba_n_heads % self.mamba_n_groups:
                raise ValueError(
                    f"mamba_n_groups={self.mamba_n_groups} does not divide "
                    f"mamba_n_heads={self.mamba_n_heads}: a group's B and C "
                    "serve a whole number of heads"
                )
            for what, on in (
                ("MLA", self.kv_lora_rank > 0),
                ("sliding_window", self.sliding_window > 0),
                ("learned sparse attention", self.indexer_topk > 0),
                ("LoRA adapters", self.num_lora_adapters > 0),
                ("a dense layer prefix", self.first_dense_layers > 0),
            ):
                if on:
                    raise ValueError(
                        f"state-space layers are not supported with {what}"
                    )
        if self.partial_rotary_factor != 1.0 and not (
            0.0 < self.partial_rotary_factor < 1.0
            and self.rotary_dim > 0 and self.rotary_dim % 2 == 0
        ):
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor} of "
                f"head_dim={self.head_dim} is no even number of rotated "
                "dimensions"
            )
        if (self.attn_output_gate or self.rotary_dim < self.head_dim) and (
            self.kv_lora_rank > 0 or self.num_lora_adapters > 0
            or self.indexer_topk > 0
        ):
            raise ValueError(
                "attn_output_gate / partial_rotary_factor are not supported "
                "with MLA, LoRA adapters or learned sparse attention: those "
                "paths would rotate the whole head or drop the gate"
            )
        if self.shared_expert_gate and not self.shared_expert_intermediate_size:
            raise ValueError("shared_expert_gate needs a shared expert")
        if self.sliding_window > 0 and self.kv_lora_rank > 0:
            raise ValueError(
                "sliding_window is not supported with MLA (no known MLA "
                "architecture slides; the latent path would silently attend "
                "past the window)"
            )
        if self.kv_lora_rank > 0 and self.attention_bias:
            raise ValueError(
                "attention_bias is not supported with MLA (kv_lora_rank > 0): "
                "no known MLA architecture uses QKV biases and the MLA "
                "forward would silently ignore them"
            )
        if self.lora_dynamic and self.num_lora_adapters <= 0:
            raise ValueError(
                "lora_dynamic needs num_lora_adapters > 0 pool slots"
            )
        if self.kv_lora_rank > 0 and self.num_lora_adapters > 0:
            raise ValueError(
                "LoRA adapters are not supported on MLA models yet: the MLA "
                "attention path would silently serve base-model outputs for "
                "adapter requests"
            )
        if self.indexer_topk > 0:
            if self.indexer_num_heads <= 0 or self.indexer_head_dim <= 0:
                raise ValueError(
                    "indexer_topk > 0 needs indexer_num_heads and "
                    "indexer_head_dim (HF sa_config)"
                )
            for what, on in (
                ("sliding_window", self.sliding_window > 0),
                ("attention_sinks", self.attention_sinks),
                ("LoRA adapters", self.num_lora_adapters > 0),
            ):
                if on:
                    raise ValueError(
                        f"learned sparse attention (indexer_topk > 0) is not "
                        f"supported with {what}: that attention path would "
                        "silently attend past the indexer's selection"
                    )
            # Over a latent cache (HF ``deepseek_v32``) the indexer's queries
            # come from the normed query latent and it rotates
            # ``qk_rope_head_dim`` of its dimensions (models/mla_dsa.py); such
            # a model exists on the flat step only, which
            # EngineConfig.check_sparse_attention holds it to.
            if self.kv_lora_rank > 0 and not (
                self.q_lora_rank > 0
                and self.qk_rope_head_dim <= self.indexer_head_dim
            ):
                raise ValueError(
                    "learned sparse attention over a latent cache takes its "
                    "indexer's queries from the query latent (q_lora_rank > "
                    "0) and rotates qk_rope_head_dim <= indexer_head_dim of "
                    "their dimensions"
                )

    def window_for_layer(self, i: int) -> int:
        """Attention window for layer ``i`` (0 = full attention)."""
        if self.sliding_window <= 0:
            return 0
        if self.layer_types is not None:
            return (
                self.sliding_window
                if self.layer_types[i] == "sliding_attention"
                else 0
            )
        if self.max_window_layers is not None:
            return self.sliding_window if i >= self.max_window_layers else 0
        return self.sliding_window

    @property
    def layer_windows(self) -> tuple[int, ...]:
        return tuple(self.window_for_layer(i) for i in range(self.num_layers))

    def _rope_spec(self, own: dict) -> tuple:
        """(theta, scaling) of one entry of ``rope_parameters``; a plain
        table has no scaling, so that it is the model's own where the theta
        is."""
        scaling = {k: v for k, v in own.items() if k != "rope_theta"}
        if (scaling.get("rope_type") or scaling.get("type") or "default") == "default":
            scaling = None
        return float(own.get("rope_theta", self.rope_theta)), scaling

    @property
    def rope_specs(self) -> tuple:
        """The model's RoPE tables as (theta, scaling): the model's own
        first, then each other one that ``rope_parameters`` gives a kind of
        layer, once."""
        specs = [(float(self.rope_theta), self.rope_scaling)]
        for own in (self.rope_parameters or {}).values():
            if own is not None and (spec := self._rope_spec(own)) not in specs:
                specs.append(spec)
        return tuple(specs)

    @property
    def layer_rope(self) -> tuple:
        """Per layer the index of its table in ``rope_specs``; None: the
        layer's attention does not rotate q and k."""
        if not self.rope_parameters:
            return (0,) * self.num_layers
        specs, own = self.rope_specs, self.rope_parameters
        by_type = {
            t: None if p is None else specs.index(self._rope_spec(p))
            for t, p in own.items()
        }
        return tuple(by_type.get(t, 0) for t in self.layer_types)

    @property
    def state_space(self) -> bool:
        """Some layers are recurrent mixers with a fixed-size state a
        sequence (``layer_types`` "mamba" or "linear_attention")."""
        return self.delta_rule or (
            self.layer_types is not None and "mamba" in self.layer_types
        )

    @property
    def delta_rule(self) -> bool:
        """The recurrent mixers are gated delta-rule ones (``layer_types``
        "linear_attention"), not Mamba-2."""
        return self.layer_types is not None and "linear_attention" in self.layer_types

    @property
    def mamba_layers(self) -> tuple[int, ...]:
        """The layers whose state lies in the state pool (of either kind of
        recurrent mixer; the name is the first kind's)."""
        if not self.state_space:
            return ()
        return tuple(
            i for i, t in enumerate(self.layer_types)
            if t in ("mamba", "linear_attention")
        )

    @property
    def attention_layers(self) -> tuple[int, ...]:
        """The layers that cache K and V under page ids (all of them but the
        state-space mixers)."""
        m = set(self.mamba_layers)
        return tuple(i for i in range(self.num_layers) if i not in m)

    @property
    def state_shapes(self) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """A state-pool slot of ONE recurrent layer: (the recurrent state's
        shape, float32; the conv state's, the model's dtype)."""
        if self.delta_rule:
            return (
                (self.linear_num_value_heads, self.linear_key_head_dim,
                 self.linear_value_head_dim),
                (self.linear_conv_kernel_dim - 1, self.linear_conv_dim),
            )
        return (
            (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
            (self.mamba_d_conv - 1, self.mamba_conv_dim),
        )

    @property
    def linear_conv_dim(self) -> int:
        """Channels under the delta-rule mixer's conv: q, k and v."""
        return (
            2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim
        )

    @property
    def rotary_dim(self) -> int:
        """The leading dimensions of a head that RoPE rotates."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def ffn_layers(self) -> tuple[int, ...]:
        """The layers that have an FFN (all of them unless ``layer_ffn``)."""
        if self.layer_ffn is None:
            return tuple(range(self.num_layers))
        return tuple(i for i, f in enumerate(self.layer_ffn) if f)

    @property
    def moe_storage_width(self) -> int:
        """The width the expert leaves are STORED at. Non-gated experts of a
        width the grouped kernel cannot tile (no multiple of 128 lanes, under
        a hidden size that is one) are stored padded up to the next multiple
        with zero columns of ``we_up`` and zero rows of ``we_down``: exact,
        relu(0)^2 = 0 meets a zero row. No width of the model changes."""
        F = self.moe_intermediate_size
        if self.moe_activation == "relu2" and self.hidden_size % 128 == 0:
            return -(-F // 128) * 128
        return F

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels under the causal conv: x, B and C."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def sm_scale(self) -> float:
        if self.attention_multiplier is not None:
            return float(self.attention_multiplier)
        return self.head_dim ** -0.5

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def holds_all_experts(self) -> bool:
        return self.held_experts == self.num_experts

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def sparse_attention(self) -> bool:
        """Learned sparse attention: an indexer picks the cached tokens a
        query token may read (``indexer_topk`` > 0)."""
        return self.indexer_topk > 0

    @property
    def indexer_rope_dim(self) -> int:
        """The indexer's dimensions that RoPE rotates (the first ones, among
        themselves): ``qk_rope_head_dim`` of them over a latent cache (HF
        ``deepseek_v32``), the whole head over K and V (``sa_config``)."""
        return self.qk_rope_head_dim if self.is_mla else self.indexer_head_dim

    @property
    def mla_latent_dim(self) -> int:
        """Unpadded latent width cached per token."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_cache_heads(self) -> int:
        """KV head count in the paged cache (MLA: one latent 'head')."""
        return 1 if self.is_mla else self.num_kv_heads

    @property
    def kv_cache_entry_dim(self) -> int:
        """Last-axis width of one cache row: 2*head_dim for K/V pairs,
        the latent width padded to the 128 lane tiling for MLA."""
        if self.is_mla:
            return ((self.mla_latent_dim + 127) // 128) * 128
        return 2 * self.head_dim


@dataclasses.dataclass
class CacheConfig:
    """Paged KV cache geometry.

    The KV pool is a stack of jax.Arrays (one logical pool, layer-major)
    holding ``num_blocks`` pages of ``page_size`` tokens each -- the TPU
    analogue of vLLM's paged KV cache (reference
    docs/architecture/core/model-servers.md:5-7). ``page_size`` defaults to
    a lane-friendly 16 so (page, head_dim) tiles map onto (sublane, lane).
    """

    page_size: int = 16
    num_blocks: int = 512
    # "bfloat16" / "float32", or "int8" for the quantized pool (per-row
    # symmetric int8 data + f16 K/V-half scales, ops/quant_kv.py): HALF
    # the KV bytes per page — double the pages per HBM byte, half the
    # decode-attention read traffic. The reference's flagship path runs
    # a quantized cache the same way (FP8 KV, Dockerfile.cuda:69-70).
    dtype: str = "bfloat16"
    # Fraction of free HBM to use when num_blocks is derived automatically.
    hbm_utilization: float = 0.9
    enable_prefix_caching: bool = True
    # Ring-buffer KV pages for sliding-window layers (the reference's
    # hybrid KV cache manager, guides/pd-disaggregation/modelserver/gpu/
    # vllm/base/patch-decode.yaml:19 --no-disable-hybrid-kv-cache-manager):
    # sliding layers move to a SECOND, much smaller pool where each
    # sequence holds a fixed ring of pages reused circularly, instead of
    # full-length pages on every layer. For gpt-oss-class models (half the
    # layers slide at window 128) this halves KV bytes per long sequence.
    # Prefix caching becomes HYBRID while the ring is on: full-attention
    # pages stay hashed/reusable, and a hit is taken only when a retained
    # sliding-window section (swa_section_cache below) can seed the fresh
    # ring — a bare full-pool hit would skip sliding-layer KV the
    # transient rings don't hold. P/D KV transfer composes (ring
    # producers export a sliding-layer section; ring consumers import via
    # the request-preload path); tiered offload does not (host-cached
    # pages would lack sliding-layer KV) and is refused loudly.
    swa_ring: bool = False
    # Hybrid prefix caching under the ring (the reference's hybrid KV
    # cache manager semantics, pd gpu patch-decode.yaml:19): retain up to
    # this many per-prefix sliding-window SECTIONS (each ~window/page + 1
    # SWA-pool pages, captured at prefill completion) so a repeated
    # prefix seeds a fresh ring from the retained section and skips the
    # full prefill. 0 disables retention (ring hits then never shortcut).
    swa_section_cache: int = 8
    # How many retained sections (or, over a state pool, snapshots) the
    # second pool provisions; 0 = auto (``swa_section_count``: twice the
    # sequences the scheduler may run). A geometry whose sequences all come
    # back to their OWN retained state (resident decode) says one each.
    swa_sections: int = 0
    # Ring-pool page count; 0 = auto (max_num_seqs x ring_pages: one ring
    # per possible running sequence; P/D preloads allocate extra rings at
    # arrival and the scheduler reclaims waiting preloads' rings if the
    # pool runs short, so admission never starves).
    swa_blocks: int = 0

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    def max_pages_per_seq(self, max_model_len: int) -> int:
        return math.ceil(max_model_len / self.page_size)


@dataclasses.dataclass
class SchedulerConfig:
    """Continuous batching knobs (vLLM flag names kept 1:1)."""

    max_num_seqs: int = 64
    max_num_batched_tokens: int = 1024
    # Chunked prefill: a long prompt is processed in chunks of at most this
    # many tokens so decode seqs are never starved (reference agentic TPU
    # patch-vllm.yaml:39 uses --max-num-batched-tokens=8192 @ 262144 ctx).
    enable_chunked_prefill: bool = True
    # Token-count buckets used to pad jitted step shapes (compile-once).
    prefill_token_buckets: tuple[int, ...] = ()
    decode_batch_buckets: tuple[int, ...] = ()
    # Row buckets for PREFILL batches. Defaults to powers of two from 1
    # (vs decode's from 8): a lone prefill — the P/D TTFT-critical shape —
    # must not pad to 8 rows of max-chunk compute, while decode padding
    # is cheap (decode steps are dispatch/RTT-bound, not FLOPs-bound).
    prefill_batch_buckets: tuple[int, ...] = ()
    # Fused decode window: K decode iterations per jit call with on-device
    # token feedback (host sees one transfer per window). 1 = step-per-token.
    # Larger K amortizes dispatch latency at the cost of K-token streaming
    # granularity and bounded overrun past stop tokens. Inert on a
    # speculative engine (speculative_ngram), which verifies one-shot
    # every step.
    decode_window: int = 1
    # Model-free speculative decoding (prompt-lookup / n-gram drafting,
    # Saxena 2023; verified Leviathan-style in one pass): each decode row
    # drafts up to ``spec_ngram_k`` continuation tokens by matching the
    # tail of its token history against its own prompt+output, and the
    # runner scores all 1+k positions in ONE bucketed forward pass —
    # amortizing the per-step weight read that makes decode memory-bound.
    # Acceptance is exact: greedy rows accept while the draft equals the
    # argmax; seeded rows accept while the draft equals the token the
    # per-(seed, output-index) PRNG derivation samples — either way the
    # emitted stream is byte-identical to a non-speculative engine.
    # Rejected draft tokens' provisional KV writes are truncated before
    # any page commit, so rejected content never enters the prefix-cache
    # hash chain (docs/architecture/speculative-decoding.md).
    speculative_ngram: bool = False
    # Max draft tokens per row per step (the k in [B, 1+k] verify shapes;
    # one traced shape family per engine).
    spec_ngram_k: int = 4
    # Minimum n-gram match length before a draft is proposed: higher
    # values cut spurious drafts (wasted verify compute) on low-repetition
    # traffic at the cost of missing short genuine repeats.
    spec_ngram_min_match: int = 2
    # Unified single-dispatch step: pack an entire window=1 engine step —
    # chunked-prefill token runs, plain decode rows, and one-shot
    # [B, 1+k] verify rows — into ONE bucketed ragged program with one
    # coalesced readback, where the split engine launches up to three
    # (prefill groups, verify split, plain decode) plus one lockstep
    # broadcast each on multi-host. Greedy and seeded streams stay
    # byte-identical to the split engine; turning this off restores the
    # per-family dispatch paths (the split fallback). Fused decode
    # windows keep their own dispatch either way — they already amortize
    # the round-trip.
    unified_step: bool = True
    # Genuinely ragged flattened-token forward (`cu_q_lens`): the unified
    # step runs over the PACKED token stream itself — a decode row costs
    # 1 token, a verify row 1 + its own draft length (per-row adaptive
    # verify depth), a prefill chunk its chunk length — instead of every
    # row padding to the bucketed [B, Q] sub-row width. One flattened
    # program (T-bucketed, 16-token granules) serves every window=1 step
    # kind; greedy and seeded streams stay byte-identical to the
    # bucketed unified step and the split engine. Turning this off
    # restores the bucketed [B, Q] unified program. Effective only with
    # unified_step on and a non-MLA model (MLA latent writes keep the
    # bucketed layout).
    ragged_qlens: bool = True
    # Batch serving tier (docs/architecture/batch-processing.md): requests
    # at or below PriorityClass.BATCH ride the SAME continuous batch at a
    # strictly-backfill discipline — they only consume the token-budget /
    # page headroom interactive rows left unused this step, never
    # displace an interactive admission, and are the first
    # recompute-preemption victims the moment interactive load returns
    # (interactive streams stay byte-identical batch-on vs batch-off).
    # Off = batch-priority rows degrade to plain low-priority rows (no
    # backfill discipline, no interactive-pressure preemption).
    batch_backfill: bool = True
    # Cap on concurrently RUNNING batch-band rows (0 = no dedicated cap:
    # batch may fill whatever max_num_seqs slots interactive left idle —
    # interactive admission reclaims them by preemption either way).
    batch_max_seqs: int = 0
    # Engine-side admission watermark: new batch rows are admitted only
    # while main-pool KV utilization is at or below this fraction, so
    # backfill never pushes the pool into the preemption regime that
    # would thrash interactive rows (the EPP applies the same watermark
    # fleet-side in its batch-saturation-filter).
    batch_kv_watermark: float = 0.85

    def __post_init__(self) -> None:
        if not (0.0 < self.batch_kv_watermark <= 1.0):
            raise ValueError(
                f"batch_kv_watermark={self.batch_kv_watermark} must be in "
                "(0, 1] (fraction of KV pool utilization)"
            )
        if self.batch_max_seqs < 0:
            raise ValueError(
                f"batch_max_seqs={self.batch_max_seqs} must be >= 0 "
                "(0 = no dedicated cap)"
            )
        if self.speculative_ngram:
            if self.spec_ngram_k < 1:
                raise ValueError(
                    f"spec_ngram_k={self.spec_ngram_k} must be >= 1 when "
                    "speculative_ngram is enabled"
                )
            if self.spec_ngram_min_match < 1:
                raise ValueError(
                    f"spec_ngram_min_match={self.spec_ngram_min_match} "
                    "must be >= 1"
                )


@dataclasses.dataclass(frozen=True)
class SwaRingSpec:
    """Resolved geometry of the sliding-window ring pool.

    ``ring_pages`` (R) is the per-sequence ring length. Sizing invariant:
    within one engine step a sequence's sliding layers must hold every
    position in ``[first_query - window, last_write]`` simultaneously —
    the step WRITES its whole chunk before attention READS — so the live
    span is at most ``window + chunk`` tokens and
    ``R = ceil((window + chunk) / page) + 1`` (the +1 absorbs page-offset
    straddle). Older logical pages alias onto overwritten ring slots and
    are exactly the pages the attention kernels' window-skip never reads.
    """

    windows: tuple[int, ...]      # per-layer window (0 = full attention)
    full_layers: tuple[int, ...]  # layer ids with full attention
    swa_layers: tuple[int, ...]   # layer ids with a sliding window
    ring_pages: int               # R: pages per sequence ring
    num_swa_blocks: int           # ring-pool size (pages)
    # Per-sequence prefill chunk cap the scheduler enforces while the
    # ring is on (R is sized from it; chunking finer is always correct).
    chunk_tokens: int
    # The per-sequence state is K and V of a window: a section can be cut
    # out of the ring after the chunk that wrote it (StateSlotSpec: True).
    recurrent = False

    def section(self, prompt_len: int, page_size: int) -> tuple[int, int, int]:
        """Sliding-layer P/D export-section geometry: (n_pre, s0, count).

        The ONE definition both transfer sides use (producer export and
        consumer preload MUST agree byte-for-byte or the section lands at
        the wrong ring slots). ``n_pre`` is the preloadable full-page
        count (never the whole prompt — the last token is recomputed for
        logits); the section spans logical pages [s0, n_pre), the window
        before the continuation point.
        """
        n_pre = max(0, (prompt_len - 1) // page_size)
        wmax = max(self.windows[i] for i in self.swa_layers)
        s0 = max(0, (n_pre * page_size - wmax) // page_size)
        return n_pre, s0, n_pre - s0

    def max_section_pages(self, page_size: int) -> int:
        """Upper bound of a section's page count (retention budgeting):
        the window span plus one page of offset straddle."""
        wmax = max(self.windows[i] for i in self.swa_layers)
        return -(-wmax // page_size) + 1


def swa_section_count(cache: "CacheConfig", sched: "SchedulerConfig") -> int:
    """How many retained sliding sections a ring engine provisions: every
    sequence the scheduler may run leaves one for its own next turn, and
    as many again may be shared prefixes captured on demand;
    ``swa_section_cache`` is the floor (and 0 still turns retention off:
    the caller's test). Eight sections under 32 sessions evicted each
    turn's section before its next turn came. ``swa_sections`` > 0 says
    the count outright."""
    if cache.swa_sections > 0 and cache.swa_section_cache > 0:
        return cache.swa_sections
    return max(cache.swa_section_cache, 2 * sched.max_num_seqs)


# Per-seq prefill chunk cap that bounds the ring size independent of the
# BATCH token budget (the reference caps long prefills the same way:
# --long-prefill-token-threshold / --max-num-batched-tokens=8192 at 262k
# context, guides/agentic-serving/modelserver/tpu/vllm/patch-vllm.yaml:39).
_SWA_RING_CHUNK = 2048


def swa_ring_spec(
    model: "ModelConfig", cache: "CacheConfig", sched: "SchedulerConfig"
) -> SwaRingSpec | None:
    """Resolve the ring geometry, or None when the flag has no effect
    (disabled, no sliding layers, MLA, or rings as large as full tables)."""
    if not cache.swa_ring or model.sliding_window <= 0 or model.is_mla:
        return None
    windows = model.layer_windows
    swa = tuple(i for i, w in enumerate(windows) if w > 0)
    if not swa:
        return None
    full = tuple(i for i, w in enumerate(windows) if w == 0)
    wmax = max(windows[i] for i in swa)
    chunk = max(
        min(_SWA_RING_CHUNK, sched.max_num_batched_tokens),
        sched.decode_window,
        # Speculative verify writes 1 + k provisional positions per row
        # per step, so the ring's write-span invariant must cover them.
        1 + sched.spec_ngram_k if sched.speculative_ngram else 1,
    )
    ring = math.ceil((wmax + chunk) / cache.page_size) + 1
    max_pages = cache.max_pages_per_seq(model.max_model_len)
    if ring >= max_pages:
        return None  # ring would be as large as the full table: no win
    if cache.swa_blocks and cache.swa_blocks < ring:
        # A pool smaller than ONE ring can never admit a sequence — that
        # would livelock admission silently, not degrade it.
        raise ValueError(
            f"cache.swa_blocks={cache.swa_blocks} is smaller than one "
            f"ring ({ring} pages); no sequence could ever be admitted"
        )
    blocks = cache.swa_blocks or sched.max_num_seqs * ring
    return SwaRingSpec(windows, full, swa, ring, blocks, chunk)


@dataclasses.dataclass(frozen=True)
class StateSlotSpec:
    """Geometry of the state pool of a model with state-space layers, in the
    terms the engine already has for the other kind of per-sequence state,
    the sliding-window ring (``SwaRingSpec``): a running sequence holds a
    "ring" of ONE slot; a retained snapshot is a "section" of one slot, the
    state AT page boundary ``n_pre`` (``section``: ``s0 = n_pre - 1``), so
    the one retained-state cache, its keys, its eviction order and the
    scheduler's admission and release serve both kinds.

    What differs is WHEN a section can be taken. A ring holds the window
    before every page boundary of the chunk it has just written; a recurrent
    state holds every token up to the last one, so the scheduler of such a
    model ends a prefill chunk AT the boundaries a snapshot is wanted at (a
    prompt's last full page; the end of a run of full pages the main pool
    offered and the engine had to refuse): ``recurrent``."""

    kv_layers: tuple[int, ...]     # layers with K and V pages (attention)
    state_layers: tuple[int, ...]  # state-space layers
    num_swa_blocks: int            # slots in the pool (running + retained)
    ring_pages = 1                 # a "ring" of one slot
    chunk_tokens = 0               # no per-sequence chunk cap
    recurrent = True

    @property
    def full_layers(self) -> tuple[int, ...]:
        return self.kv_layers

    def section(self, prompt_len: int, page_size: int) -> tuple[int, int, int]:
        n_pre = max(0, (prompt_len - 1) // page_size)
        s0 = max(0, n_pre - 1)
        return n_pre, s0, n_pre - s0

    def max_section_pages(self, page_size: int) -> int:
        return 1


def state_slot_spec(
    model: "ModelConfig", sched: "SchedulerConfig"
) -> StateSlotSpec | None:
    """The state pool's geometry, or None for a model without state-space
    layers: one slot a sequence the scheduler may run (the engine adds the
    retained snapshots' slots, ``swa_section_count`` of them)."""
    if not model.state_space:
        return None
    return StateSlotSpec(
        model.attention_layers, model.mamba_layers, sched.max_num_seqs
    )


@dataclasses.dataclass
class ParallelConfig:
    """Device-mesh parallelism.

    The reference maps TP/DP/EP onto NCCL/NVSHMEM process groups
    (SURVEY.md section 2.4); here they are axes of one jax.sharding.Mesh and
    XLA inserts the collectives over ICI.
    """

    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    expert_parallel_size: int = 1  # folded over the same devices as tp*dp
    # Fuse the q|k|v and gate|up projections into single matmuls when the
    # layout allows (tp == 1, no LoRA, non-MLA): one activation
    # quantization + one bigger MXU dot instead of three. Measured
    # END-TO-END (back-to-back engine runs, llama-3.2-3b-class int8,
    # B=128): 4113 -> 4280 tok/s (+4%). Lossless: per-output-channel int8
    # scales concatenate exactly; bf16 concat is trivially exact.
    fuse_projections: bool = True
    # MoE execution path: "grouped" (default) = tokens sorted by expert
    # feed Pallas/XLA grouped GEMMs so each expert multiplies only its
    # routed rows (the DeepGEMM role); "dense" = one-hot combine running
    # every expert on every token (numerics oracle, E/top_k extra FLOPs);
    # "ep" = shard_map all-to-all dispatch/combine with grouped local
    # expert compute (deepep_low_latency analogue for wide-EP).
    moe_backend: str = "grouped"
    # EP dispatch capacity factor (send slots per destination shard relative
    # to a uniform split; tokens past capacity are dropped from the combine).
    ep_capacity_factor: float = 2.0
    # Skew-proof capacity: adapt ep_capacity_factor online from the
    # census's observed per-step max dispatch demand (EMA + hysteresis,
    # quantized onto eplb.AdaptiveCapacity.LADDER so recompiles stay
    # rare). Steps UP immediately when a step drops tokens; steps DOWN
    # only after a sustained run of low-skew steps. Each change rebuilds
    # the jitted forward programs at the new static capacity.
    ep_capacity_adaptive: bool = False
    # Microbatched overlapped EP dispatch (moe_ep.moe_block_ep overlap):
    # split each MoE layer's dispatch->grouped-GEMM->combine chain into N
    # independent microbatches so XLA's latency-hiding scheduler can issue
    # microbatch i+1's all-to-all while microbatch i's expert matmul still
    # runs. Byte-identical to the monolithic path at zero-drop capacity
    # (tests/test_wide_ep.py pins it).
    #
    # SUBSTRATE CONDITION (same graduate gate as enable_dbo): the overlap
    # only pays where collectives run asynchronously on a real ICI
    # fabric; on the virtual CPU mesh the extra a2a launches are pure
    # overhead — bench.py's moe_ep part records the on/off step-time
    # delta, and the flag graduates to default-on only when a real-slice
    # bench shows a win (docs/architecture/dbo.md discipline). 0/1 = off.
    moe_overlap: int = 0
    # EPLB (DeepSeek-V3 expert placement load balancing,
    # llmd_tpu.parallel.eplb): every eplb_interval_steps engine steps,
    # recompute the expert->shard placement from the census's measured
    # per-expert routed-token counts and remap the we_* param leaves at
    # the step boundary. 0 disables (the identity contiguous layout).
    eplb_interval_steps: int = 0
    # Extra physical expert slots PER SHARD for EPLB redundancy: the
    # hottest experts are replicated into these slots so their traffic
    # splits across shards (E_phys = E + world * eplb_redundancy).
    eplb_redundancy: int = 0
    # Dual-batch overlap (the reference's --enable-dbo, wide-ep
    # decode.yaml:125-126): split each step into two half-batch chains
    # after the KV write so the EP all-to-all of one half overlaps the
    # other half's attention compute. Needs an even batch; exact unless
    # EP capacity binds (half-batch calls carry full-batch capacity).
    #
    # SUBSTRATE CONDITION: the win exists ONLY where collectives run
    # asynchronously on a real inter-chip fabric (ICI/DCN) — XLA's
    # latency-hiding scheduler then executes one half's all-to-all
    # while the other half's attention computes. On the virtual CPU
    # mesh there is nothing to hide (all "devices" share the host
    # cores), so the split's fixed costs make steps ~1.6x SLOWER —
    # bench.py's dbo extras record exactly that, and the runner warns
    # when the flag is on without a TPU backend. Same story as the
    # reference: --enable-dbo ships default-off and is enabled only on
    # the multi-node GPU decode tier (decode.yaml:125-126).
    enable_dbo: bool = False
    # Context-parallel ring prefill (Ring Attention, Liu et al.): a long
    # prompt's chunk is sharded across the mesh "dp" axis and attention
    # runs as a ring — fresh K/V blocks rotate via jax.lax.ppermute over
    # ICI while each shard folds online-softmax partials, with causal
    # block skipping (~half the ring work). Must equal
    # data_parallel_size when > 1 (the ring rides the dp axis, which
    # idles during a lone long prefill anyway since B=1 never
    # dp-shards). 1 disables. Non-MLA models only; tolerance-pinned
    # against the monolithic chunked-prefill path by
    # tests/test_ring_prefill.py.
    cp_prefill: int = 1
    # Prefill rows shorter than this keep the monolithic path even when
    # cp_prefill > 1: tiny chunks are dispatch-bound and the ring's
    # collective latency would dominate.
    cp_prefill_min_tokens: int = 512

    def __post_init__(self) -> None:
        if self.cp_prefill < 1:
            raise ValueError(
                f"cp_prefill={self.cp_prefill} must be >= 1 (1 disables)"
            )
        if self.cp_prefill > 1 and self.cp_prefill != self.data_parallel_size:
            raise ValueError(
                f"cp_prefill={self.cp_prefill} must equal "
                f"data_parallel_size={self.data_parallel_size}: the ring "
                "shards the chunk's query axis over the mesh dp axis"
            )
        if self.cp_prefill_min_tokens < 1:
            raise ValueError(
                f"cp_prefill_min_tokens={self.cp_prefill_min_tokens} "
                "must be >= 1"
            )

    @property
    def world_size(self) -> int:
        return self.tensor_parallel_size * self.data_parallel_size


@dataclasses.dataclass
class OffloadConfig:
    """Tiered KV offload (HBM -> host DRAM -> FS).

    The reference's TPU tiering knobs (tiered-prefix-cache/README.md:41-48:
    25000 CPU chunks ~= 780GB on v7): ``cpu_chunks`` caps the host page
    cache; ``fs_dir`` enables the filesystem spill tier
    (kv-offloader.md:120-134 persistence).
    """

    enabled: bool = True
    cpu_chunks: int = 25_000
    fs_dir: str | None = None
    fs_max_pages: int = 100_000
    # Cross-slice shared store (Mooncake-Store role, kv-offloader.md:
    # 140-259): master URL enables the embedded-mode tier behind DRAM/FS.
    store_master_url: str | None = None
    store_segment_bytes: int = 8 << 30
    store_data_port: int = 0  # kvship port serving this segment (0 = auto)
    # Federation publish policy (docs/architecture/kv-federation.md):
    # "save" publishes every host-tier save (eager, the small-fleet
    # default — publish bandwidth is free next to a re-prefill);
    # "evict-hot" publishes only pages the device cache evicted after
    # >= publish_min_hits distinct uses (the Mooncake-shaped policy for
    # fleets where save-rate x replica-count would swamp the store);
    # "off" keeps the store read-only on this replica.
    publish_policy: str = "save"
    publish_min_hits: int = 2
    # Decode-time KV paging (docs/architecture/long-context.md): cold
    # page-ranges of a LIVE decode sequence — wholly below the attention
    # window minus the prefetch horizon — spill to the host tier and
    # their HBM pages are freed, bounding resident HBM per sequence by
    # window + horizon instead of context length. Pages stream back over
    # the group-framed scatter wire before the window reaches them; a
    # wire/tier failure refunds the sequence to recompute (byte-identical
    # output either way). Requires the offload tier, prefix caching, an
    # all-sliding-window model, and a single-host engine.
    decode_paging: bool = False
    # Prefetch horizon in tokens: pages within window + horizon of the
    # decode frontier stay resident; the pager restores a parked
    # sequence's pages down to this watermark before it is schedulable.
    pager_horizon_tokens: int = 256


@dataclasses.dataclass
class EngineConfig:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    seed: int = 0
    # Path to HF-format weights (safetensors); None => deterministic random init.
    weights_path: str | None = None
    tokenizer_path: str | None = None
    # KV transfer role for P/D disaggregation: None | kv_producer | kv_consumer
    # | kv_both (reference tpu patch-decode.yaml:17-20 TPUConnector roles).
    kv_role: str | None = None
    # Address advertised to consumers in kv_transfer_params (the pod IP in a
    # cluster deployment). The reference's side-channel and transfer ports
    # (TPU_SIDE_CHANNEL_PORT=9600 / TPU_KV_TRANSFER_PORT=9100) are folded
    # into ONE port here; kv_side_channel_port is kept as an accepted alias
    # for deployment-manifest compatibility but is not separately bound.
    kv_host: str = "127.0.0.1"
    kv_side_channel_port: int = 9600
    kv_transfer_port: int = 9100
    kv_lease_ms: int = 30_000  # operations-vllm.md:155-160
    kv_load_failure_policy: str = "recompute"  # "recompute" | "fail"
    # P/D transfer encoding: "auto" = pool dtype, byte-exact (default);
    # "int8" = per-row int8 + f16 scales quantized on device — halves both
    # staging legs (the TTFT floor when staging-bandwidth-bound) at ~0.4%
    # per-row error. Producer-side knob.
    kv_transfer_dtype: str = "auto"
    # Single-host xPyD fast path: consumers claim an in-process
    # producer's device snapshots directly — no HBM->host staging, no
    # wire bytes (the reference's single-host/pd deployment shape).
    kv_local_fastpath: bool = True
    # Layer-streamed P/D transfer (the v3 group-framed wire): exports
    # split into this many contiguous layer groups shipped group-major;
    # the consumer pipelines fetch -> CRC -> scatter per group and the
    # decode-side request is schedulable once group 0 is resident.
    # Clamped to the model's layer count; 1 disables (v2 chunk framing).
    # The LLMD_KV_STREAM_COMPAT_V2 / LLMD_KV_BUNDLE_COMPAT_V1 pins and
    # multi-host lockstep runners force 1.
    kv_stream_groups: int = 4
    # ZMQ pub endpoint for KV events (BlockStored/...); None disables.
    kv_events_endpoint: str | None = None
    # Tiered KV offload; None disables.
    offload: OffloadConfig | None = None

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def _refused_off_the_flat_step(self) -> list[str]:
        """What is ON of what a model refuses that exists on the flat step
        of one device only (learned sparse attention, state-space layers):
        every road that moves or reads a sequence's cache knowing pages, K
        and V only. An entry of one kind of model says so in its condition."""
        m, s, c, p = self.model, self.scheduler, self.cache, self.parallel
        refused = {
            "speculative decoding (speculative_ngram)": s.speculative_ngram,
            "fused decode windows (decode_window > 1)": s.decode_window > 1,
            "the bucketed or split step (unified_step / ragged_qlens off)":
                not (s.unified_step and s.ragged_qlens),
            "whole-prompt prefill (enable_chunked_prefill off)":
                m.state_space and not s.enable_chunked_prefill,
            "an int8 KV cache": c.quantized,
            "the sliding-window ring (swa_ring)": c.swa_ring,
            "prefix caching without retained snapshots (swa_section_cache=0)":
                m.state_space and c.enable_prefix_caching
                and c.swa_section_cache <= 0,
            "tiered KV offload": self.offload is not None and self.offload.enabled,
            "P/D KV transfer (kv_role)": bool(self.kv_role),
            "a sharded mesh (tp/dp/ep > 1)":
                p.world_size > 1 or p.expert_parallel_size > 1,
            "ring prefill or dual-batch overlap": p.cp_prefill > 1 or p.enable_dbo,
            "int8 weights": m.quantization is not None,
        }
        return [what for what, is_on in refused.items() if is_on]

    def check_sparse_attention(self) -> None:
        """Refuse, at start, what would drop or misread the indexer's key
        plane or attend past its selection (no silent fallback). The
        sparse path exists on the flat step of one device; everything
        that moves KV pages by another road knows K and V only."""
        if self.model.sparse_attention and (on := self._refused_off_the_flat_step()):
            raise ValueError(
                f"{self.model.name}: learned sparse attention (indexer top-"
                f"{self.model.indexer_topk}) does not run with " + "; ".join(on)
                + ": that path would drop the indexer's key plane or attend "
                "past its selection"
            )

    def check_state_space(self) -> None:
        """Refuse, at start, what would move or reinterpret a sequence's
        cache by a road that knows pages only (no silent fallback). A
        state-space layer keeps a fixed-size state a sequence in the state
        pool; it exists on the flat step of one device."""
        if self.model.state_space and (on := self._refused_off_the_flat_step()):
            raise ValueError(
                f"{self.model.name}: state-space layers do not run with "
                + "; ".join(on)
                + ": that path knows pages only and would drop, skip or "
                "misread the sequence's recurrent state"
            )


def tiny_model_config(**overrides: Any) -> ModelConfig:
    """A toy config small enough for CPU-mesh unit tests."""
    base = dict(
        name="tiny-llama",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10000.0,
        max_model_len=128,
        dtype="float32",
    )
    base.update(overrides)
    return ModelConfig(**base)
