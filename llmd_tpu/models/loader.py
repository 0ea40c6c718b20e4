"""HF checkpoint loading: config.json -> ModelConfig, safetensors -> params.

The serving framework must load trained checkpoints in the format the
reference's deployment flow assumes (HF model directories; reference
docs/architecture/core/model-servers.md:3-25, HF_TOKEN download flow in
guides/pd-disaggregation/README.md:94-103). This module maps HF names and
layouts onto this framework's stacked-layer param tree:

  - HF linear weights are [out, in] and applied as x @ W.T; ours are
    [in, out] applied as x @ W -> every projection transposes on load.
  - HF stores one tensor per layer (model.layers.{i}.*); ours are stacked
    along a leading L axis for the lax.scan over layers -> np.stack.
  - DeepSeek-family checkpoints store rope dims interleaved (HF permutes
    them at runtime, modeling_deepseek's q/k view(d//2, 2) transpose);
    we bake the permutation into the loaded projections so the runtime
    split-half `apply_rope` matches.

Supported architectures: LlamaForCausalLM, Qwen2ForCausalLM,
Qwen3ForCausalLM, MixtralForCausalLM, Qwen3MoeForCausalLM,
DeepseekV2ForCausalLM, DeepseekV3ForCausalLM, ExaoneMoEForCausalLM
(``model_type: exaone_moe``: the config's keys mapped; its tensor names are
taken to follow DeepSeek-V3's, which the key names follow),
MellumForCausalLM (``model_type: mellum``, Mellum2: its keys are Qwen3-MoE's,
whose QK-norm and tensor names it is taken to follow, plus ``layer_types``
and a ``rope_parameters`` keyed by layer type).
"""

from __future__ import annotations

import json
import logging
import math
import pathlib

import numpy as np

import jax.numpy as jnp

from llmd_tpu.config import ModelConfig

log = logging.getLogger(__name__)

_DENSE_ARCHS = {
    "LlamaForCausalLM",
    "MistralForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
}
_MOE_ARCHS = {
    "MixtralForCausalLM", "Qwen3MoeForCausalLM", "GptOssForCausalLM",
    "ExaoneMoEForCausalLM", "MellumForCausalLM",
}
_MLA_ARCHS = {"DeepseekV2ForCausalLM", "DeepseekV3ForCausalLM"}
SUPPORTED_ARCHS = _DENSE_ARCHS | _MOE_ARCHS | _MLA_ARCHS


def is_model_dir(path: str) -> bool:
    p = pathlib.Path(path)
    return p.is_dir() and (p / "config.json").is_file()


def config_from_hf(model_dir: str, **overrides) -> ModelConfig:
    """Build a ModelConfig from an HF model directory's config.json."""
    p = pathlib.Path(model_dir)
    with open(p / "config.json") as f:
        hf = json.load(f)
    archs = hf.get("architectures") or []
    arch = archs[0] if archs else ""
    if arch not in SUPPORTED_ARCHS:
        raise ValueError(
            f"unsupported architecture {arch!r} in {model_dir}; "
            f"supported: {sorted(SUPPORTED_ARCHS)}"
        )
    from llmd_tpu.models.common import SUPPORTED_ROPE_TYPES, rope_type

    rope_scaling = hf.get("rope_scaling")
    if rope_type(rope_scaling) not in SUPPORTED_ROPE_TYPES:
        raise ValueError(
            f"rope_scaling type {rope_type(rope_scaling)!r} "
            f"not supported (have: {SUPPORTED_ROPE_TYPES})"
        )
    rope_scaling = _yarn_original(rope_scaling, hf)
    # ``rope_parameters`` flat holds the model's theta; keyed by layer type
    # (Mellum2) it holds a table per kind of layer, and the model's own is
    # the plain one among them (else the first).
    rope_own = hf.get("rope_parameters") or {}
    rope_by_type = None
    if rope_own and all(isinstance(v, dict) for v in rope_own.values()):
        rope_by_type = {t: _yarn_original(v, hf) for t, v in rope_own.items()}
        for own in rope_by_type.values():
            if rope_type(own) not in SUPPORTED_ROPE_TYPES:
                raise ValueError(
                    f"rope_parameters type {rope_type(own)!r} "
                    f"not supported (have: {SUPPORTED_ROPE_TYPES})"
                )
        plain = [v for v in rope_by_type.values() if rope_type(v) == "default"]
        rope_own = (plain or list(rope_by_type.values()))[0]
    kw: dict = dict(
        name=p.name or str(p),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim"),
        rope_theta=float(
            hf.get("rope_theta") or rope_own.get("rope_theta", 10000.0)
        ),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_model_len=int(hf.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        # fp16 checkpoints run in bf16 on TPU (same exponent range as fp32;
        # fp16's narrower range under/overflows in softmax/logits). Newer
        # transformers writes the key as "dtype", older as "torch_dtype".
        dtype={
            "float32": "float32", "bfloat16": "bfloat16",
        }.get(str(hf.get("dtype") or hf.get("torch_dtype")), "bfloat16"),
    )
    # Sliding-window attention, in the HF conventions: Mistral-style
    # uniform windows, Qwen2's use_sliding_window + max_window_layers
    # (layers >= max_window_layers slide), and gpt-oss-style per-layer
    # layer_types ("sliding_attention"/"full_attention").
    if hf.get("sliding_window") and hf.get("use_sliding_window", True):
        kw["sliding_window"] = int(hf["sliding_window"])
        if hf.get("layer_types"):
            kw["layer_types"] = tuple(hf["layer_types"])
        elif "use_sliding_window" in hf:
            # Qwen2-style config: layers >= max_window_layers slide. A
            # checkpoint that omits the key inherits HF's class default
            # (Qwen2Config: 28) — falling through to uniform windows here
            # would silently slide layers the trained model didn't.
            kw["max_window_layers"] = int(hf.get("max_window_layers", 28))
    if rope_by_type is not None and kw.get("layer_types"):
        kw["rope_parameters"] = rope_by_type
    if arch == "Qwen2ForCausalLM":
        # Qwen2 uses bias on the QKV projections (no config flag).
        kw["attention_bias"] = True
    else:
        kw["attention_bias"] = bool(hf.get("attention_bias", False))
    if arch in ("Qwen3ForCausalLM", "Qwen3MoeForCausalLM", "MellumForCausalLM"):
        kw["qk_norm"] = True
    if arch == "MixtralForCausalLM":
        kw.update(
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["intermediate_size"],
        )
    elif arch in ("Qwen3MoeForCausalLM", "MellumForCausalLM"):
        kw.update(
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )
    elif arch == "GptOssForCausalLM":
        kw.update(
            # HF GptOssConfig defaults attention_bias to TRUE (unlike the
            # shared path's False default): pin the same default for both
            # the qkv and o biases so a config.json omitting the key
            # doesn't silently drop the qkv biases.
            attention_bias=bool(hf.get("attention_bias", True)),
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["intermediate_size"],
            moe_activation="swiglu_oss",
            swiglu_limit=float(hf.get("swiglu_limit") or 7.0),
            router_logit_bias=True,
            norm_topk_prob=True,  # softmax over the selected logits
            attention_out_bias=bool(hf.get("attention_bias", True)),
            attention_sinks=True,
        )
    elif arch == "ExaoneMoEForCausalLM":
        kw.update(exaone_moe_fields(hf))
    elif arch in _MLA_ARCHS:
        if arch == "DeepseekV3ForCausalLM":
            router_scoring, topk_method = "sigmoid", "group_top2"
        else:
            router_scoring = "softmax"
            topk_method = {
                "greedy": "greedy",
                "group_limited_greedy": "group_max",
            }[hf.get("topk_method", "greedy")]
        kw.update(
            kv_lora_rank=hf["kv_lora_rank"],
            q_lora_rank=hf.get("q_lora_rank") or 0,
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            num_experts=hf.get("n_routed_experts") or 0,
            num_experts_per_tok=hf.get("num_experts_per_tok") or 2,
            moe_intermediate_size=hf.get("moe_intermediate_size"),
            first_dense_layers=hf.get("first_k_dense_replace", 0),
            shared_expert_intermediate_size=(
                (hf.get("n_shared_experts") or 0)
                * (hf.get("moe_intermediate_size") or 0)
            ),
            router_scoring=router_scoring,
            topk_method=topk_method,
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            n_group=hf.get("n_group") or 1,
            topk_group=hf.get("topk_group") or 1,
        )
    kw.update(overrides)
    return ModelConfig(**kw)


def _yarn_original(scaling: dict | None, hf: dict) -> dict | None:
    """HF's _compute_yarn_parameters falls back to the model's
    max_position_embeddings when the original length is absent."""
    from llmd_tpu.models.common import rope_type

    if rope_type(scaling) != "yarn":
        return scaling
    return {
        "original_max_position_embeddings": hf.get("max_position_embeddings", 8192),
        **scaling,
    }


def exaone_moe_fields(hf: dict) -> dict:
    """ModelConfig fields from the keys of an ``exaone_moe`` config.json
    (K-EXAONE): sigmoid scores with a selection-only bias, top-k over the
    whole router (``n_group`` 1), normalised and scaled; one dense layer
    first; ``num_shared_experts`` shared experts of the routed width as one
    SiLU GLU; QK-norm, and RoPE on the sliding layers only (the EXAONE-4.0
    hybrid-attention convention; config.json has no key for either)."""
    return dict(
        qk_norm=True,
        rope_parameters={"full_attention": None},
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        first_dense_layers=hf.get("first_k_dense_replace", 0),
        shared_expert_intermediate_size=(
            (hf.get("num_shared_experts") or 0) * hf["moe_intermediate_size"]
        ),
        router_scoring=hf.get("scoring_func", "sigmoid"),
        topk_method="group_top2",  # noaux_tc; no group limit at n_group 1
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        n_group=hf.get("n_group") or 1,
        topk_group=hf.get("topk_group") or 1,
    )


class _Checkpoint:
    """Name-indexed view over a directory of .safetensors shards."""

    def __init__(self, model_dir: str) -> None:
        from safetensors import safe_open

        self.dir = pathlib.Path(model_dir)
        files = sorted(self.dir.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(f"no .safetensors files in {model_dir}")
        self._open = safe_open
        self._where: dict[str, pathlib.Path] = {}
        self._handles: dict[pathlib.Path, object] = {}
        for f in files:
            h = safe_open(str(f), framework="np")
            self._handles[f] = h
            for name in h.keys():
                self._where[name] = f
        self.used: set[str] = set()

    def names(self) -> set[str]:
        return set(self._where)

    def has(self, name: str) -> bool:
        return name in self._where

    def get(self, name: str) -> np.ndarray:
        f = self._where.get(name)
        if f is None:
            raise KeyError(f"checkpoint tensor {name!r} not found")
        self.used.add(name)
        # framework="np" maps bf16 to ml_dtypes.bfloat16 (a jax dep).
        return self._handles[f].get_tensor(name)


def _interleave_to_half(w: np.ndarray, rope_dim: int, axis: int = -1) -> np.ndarray:
    """Permute the trailing rope columns from interleaved (d0 d1 d0 d1 ...)
    to split-half (evens | odds) layout — HF DeepSeek's runtime q/k
    permutation, baked into the weights."""
    assert axis == -1
    head = w[..., : w.shape[-1] - rope_dim]
    tail = w[..., w.shape[-1] - rope_dim :]
    tail = np.concatenate([tail[..., 0::2], tail[..., 1::2]], axis=-1)
    return np.concatenate([head, tail], axis=-1)


def load_params(
    cfg: ModelConfig, model_dir: str, dtype: str | None = None
) -> dict:
    """Load an HF checkpoint into this framework's stacked param tree.

    Returns the same structure init_params produces (llmd_tpu/models/
    llama.py). LoRA adapter slots (serving-time state, not checkpoint
    weights) initialize empty: A random-free zeros => identity adapters.
    """
    ckpt = _Checkpoint(model_dir)
    dt = np.dtype(jnp.dtype(dtype or cfg.dtype))
    H, D = cfg.hidden_size, cfg.head_dim
    Nq, K, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers

    def get(name: str, transpose: bool = False) -> np.ndarray:
        w = ckpt.get(name)
        if transpose:
            w = w.T
        return np.ascontiguousarray(w).astype(dt)

    def stack(names: list[str], transpose: bool = False) -> np.ndarray:
        return np.stack([get(n, transpose) for n in names])

    def proj(i: int, name: str) -> str:
        return f"model.layers.{i}.{name}"

    def layer_stack(layer_ids: list[int], moe: bool) -> dict[str, np.ndarray]:
        layers: dict[str, np.ndarray] = {
            "input_norm": stack([proj(i, "input_layernorm.weight") for i in layer_ids]),
            "post_norm": stack(
                [proj(i, "post_attention_layernorm.weight") for i in layer_ids]
            ),
        }
        if cfg.is_mla:
            rope = cfg.qk_rope_head_dim
            nope = cfg.qk_nope_head_dim

            def q_rows(w: np.ndarray) -> np.ndarray:
                # [H_in, Nq*(nope+rope)]: permute each head's rope tail.
                w = w.reshape(w.shape[0], Nq, nope + rope)
                w = _interleave_to_half(w, rope)
                return w.reshape(w.shape[0], Nq * (nope + rope))

            layers["wkv_a"] = np.stack(
                [
                    _interleave_to_half(
                        get(proj(i, "self_attn.kv_a_proj_with_mqa.weight"), True),
                        rope,
                    )
                    for i in layer_ids
                ]
            )
            layers["kv_norm"] = stack(
                [proj(i, "self_attn.kv_a_layernorm.weight") for i in layer_ids]
            )
            layers["wkv_b"] = stack(
                [proj(i, "self_attn.kv_b_proj.weight") for i in layer_ids], True
            )
            layers["wo"] = stack(
                [proj(i, "self_attn.o_proj.weight") for i in layer_ids], True
            )
            if cfg.q_lora_rank > 0:
                layers["wq_a"] = stack(
                    [proj(i, "self_attn.q_a_proj.weight") for i in layer_ids], True
                )
                layers["q_norm"] = stack(
                    [proj(i, "self_attn.q_a_layernorm.weight") for i in layer_ids]
                )
                layers["wq_b"] = np.stack(
                    [
                        q_rows(get(proj(i, "self_attn.q_b_proj.weight"), True))
                        for i in layer_ids
                    ]
                )
            else:
                layers["wq"] = np.stack(
                    [
                        q_rows(get(proj(i, "self_attn.q_proj.weight"), True))
                        for i in layer_ids
                    ]
                )
        else:
            layers["wq"] = stack(
                [proj(i, "self_attn.q_proj.weight") for i in layer_ids], True
            )
            layers["wk"] = stack(
                [proj(i, "self_attn.k_proj.weight") for i in layer_ids], True
            )
            layers["wv"] = stack(
                [proj(i, "self_attn.v_proj.weight") for i in layer_ids], True
            )
            layers["wo"] = stack(
                [proj(i, "self_attn.o_proj.weight") for i in layer_ids], True
            )
            if cfg.attention_bias:
                layers["bq"] = stack(
                    [proj(i, "self_attn.q_proj.bias") for i in layer_ids]
                )
                layers["bk"] = stack(
                    [proj(i, "self_attn.k_proj.bias") for i in layer_ids]
                )
                layers["bv"] = stack(
                    [proj(i, "self_attn.v_proj.bias") for i in layer_ids]
                )
            if cfg.attention_out_bias:
                layers["bo"] = stack(
                    [proj(i, "self_attn.o_proj.bias") for i in layer_ids]
                )
            if cfg.attention_sinks:
                layers["sinks"] = np.stack(
                    [ckpt.get(proj(i, "self_attn.sinks")) for i in layer_ids]
                ).astype(np.float32)
            if cfg.qk_norm:
                layers["attn_q_norm"] = stack(
                    [proj(i, "self_attn.q_norm.weight") for i in layer_ids]
                )
                layers["attn_k_norm"] = stack(
                    [proj(i, "self_attn.k_norm.weight") for i in layer_ids]
                )
        if cfg.num_lora_adapters and not cfg.is_mla:
            # Serving-time adapter slots, not checkpoint weights: zeros
            # everywhere => every slot is the base model until
            # set_lora_weights installs a real adapter.
            A1, r = cfg.num_lora_adapters + 1, cfg.lora_rank
            n = len(layer_ids)
            layers["la_q"] = np.zeros((n, A1, H, r), dt)
            layers["la_v"] = np.zeros((n, A1, H, r), dt)
            layers["lb_q"] = np.zeros((n, A1, r, Nq * D), dt)
            layers["lb_v"] = np.zeros((n, A1, r, K * D), dt)
        if moe and ckpt.has(proj(layer_ids[0], "mlp.router.weight")):
            # gpt-oss: the router is mlp.router (weight [E, H] + bias) and
            # experts are FUSED per-layer parameter tensors (not Linear
            # modules): gate_up_proj [E, H, 2F] with gate/up INTERLEAVED
            # on the last axis (HF GptOssExperts: gate = [..., ::2]),
            # plus per-expert biases, and down_proj [E, F, H] — already
            # [in, out], so no transpose.
            layers["router"] = stack(
                [proj(i, "mlp.router.weight") for i in layer_ids], True
            )
            layers["router_bias"] = np.stack(
                [ckpt.get(proj(i, "mlp.router.bias")) for i in layer_ids]
            ).astype(np.float32)
            gu = np.stack(
                [ckpt.get(proj(i, "mlp.experts.gate_up_proj")) for i in layer_ids]
            )  # [L, E, H, 2F]
            gub = np.stack(
                [ckpt.get(proj(i, "mlp.experts.gate_up_proj_bias"))
                 for i in layer_ids]
            )  # [L, E, 2F]
            layers["we_gate"] = np.ascontiguousarray(gu[..., 0::2]).astype(dt)
            layers["we_up"] = np.ascontiguousarray(gu[..., 1::2]).astype(dt)
            layers["we_gate_b"] = np.ascontiguousarray(gub[..., 0::2]).astype(dt)
            layers["we_up_b"] = np.ascontiguousarray(gub[..., 1::2]).astype(dt)
            layers["we_down"] = np.stack(
                [ckpt.get(proj(i, "mlp.experts.down_proj")) for i in layer_ids]
            ).astype(dt)
            layers["we_down_b"] = np.stack(
                [ckpt.get(proj(i, "mlp.experts.down_proj_bias"))
                 for i in layer_ids]
            ).astype(dt)
        elif moe:
            held = range(
                cfg.held_experts_first,
                cfg.held_experts_first + cfg.held_experts,
            )
            if ckpt.has(proj(layer_ids[0], "block_sparse_moe.gate.weight")):
                # Mixtral naming: w1=gate, w3=up, w2=down
                gate_name = "block_sparse_moe.gate.weight"
                expert = "block_sparse_moe.experts.{e}.w{w}.weight"
                enames = {"gate": "1", "up": "3", "down": "2"}

                def ename(i, e, which):
                    return proj(i, expert.format(e=e, w=enames[which]))
            else:
                gate_name = "mlp.gate.weight"

                def ename(i, e, which):
                    return proj(i, f"mlp.experts.{e}.{which}_proj.weight")

            layers["router"] = stack(
                [proj(i, gate_name) for i in layer_ids], True
            )
            bias_name = "mlp.gate.e_score_correction_bias"
            if ckpt.has(proj(layer_ids[0], bias_name)):
                layers["router_bias"] = np.stack(
                    [ckpt.get(proj(i, bias_name)) for i in layer_ids]
                ).astype(np.float32)
            elif cfg.router_scoring == "sigmoid":
                layers["router_bias"] = np.zeros(
                    (len(layer_ids), cfg.num_experts), np.float32
                )
            for which, key in (("gate", "we_gate"), ("up", "we_up"), ("down", "we_down")):
                layers[key] = np.stack(
                    [
                        np.stack([get(ename(i, e, which), True) for e in held])
                        for i in layer_ids
                    ]
                )
            if cfg.shared_expert_intermediate_size:
                for which, key in (
                    ("gate", "ws_gate"), ("up", "ws_up"), ("down", "ws_down"),
                ):
                    layers[key] = stack(
                        [
                            proj(i, f"mlp.shared_experts.{which}_proj.weight")
                            for i in layer_ids
                        ],
                        True,
                    )
        else:
            layers["w_gate"] = stack(
                [proj(i, "mlp.gate_proj.weight") for i in layer_ids], True
            )
            layers["w_up"] = stack(
                [proj(i, "mlp.up_proj.weight") for i in layer_ids], True
            )
            layers["w_down"] = stack(
                [proj(i, "mlp.down_proj.weight") for i in layer_ids], True
            )
        return layers

    n_dense = cfg.first_dense_layers if cfg.is_moe else 0
    params: dict = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layer_stack(list(range(n_dense, L)), moe=cfg.is_moe),
        "final_norm": get("model.norm.weight"),
    }
    if n_dense:
        params["dense_layers"] = layer_stack(list(range(n_dense)), moe=False)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight", transpose=True)

    unused = {
        n for n in ckpt.names() - ckpt.used
        if not n.endswith((".inv_freq", "rotary_emb.inv_freq"))
    }
    if unused:
        log.warning(
            "checkpoint tensors not mapped (%d): %s%s",
            len(unused), sorted(unused)[:8], " ..." if len(unused) > 8 else "",
        )
    if cfg.quantization == "int8":
        # Post-load quantization (the reference ships pre-quantized FP8
        # checkpoints; TPU INT8 quantizes the bf16 checkpoint at load).
        # Host-side numpy: the bf16 tree must never be materialized on one
        # device — big models only fit AFTER tp-sharding the int8 leaves.
        from llmd_tpu.ops.quant import quantize_param_tree_host

        params = quantize_param_tree_host(params)
    return params


def load_lora_adapter(cfg: ModelConfig, adapter_dir: str) -> dict:
    """Load an HF PEFT LoRA adapter directory into set_lora_weights form.

    Reads adapter_config.json + adapter_model.safetensors and returns
    {la_q, lb_q, la_v, lb_v} stacked [num_layers, ...], with the PEFT
    alpha/r scaling folded into B and ranks zero-padded up to the slot
    rank (zero columns are exact no-ops). Only q_proj/v_proj targets are
    servable (the slot layout); anything else raises rather than silently
    serving a partial adapter.
    """
    p = pathlib.Path(adapter_dir)
    with open(p / "adapter_config.json") as f:
        acfg = json.load(f)
    raw_targets = acfg.get("target_modules") or []
    if isinstance(raw_targets, str):  # PEFT accepts a bare string/regex
        raw_targets = [raw_targets]
    targets = set(raw_targets)
    unsupported = targets - {"q_proj", "v_proj"}
    if unsupported:
        raise ValueError(
            f"adapter targets unsupported modules {sorted(unsupported)}; "
            "servable slots cover q_proj and v_proj"
        )
    if acfg.get("bias", "none") != "none":
        raise ValueError(
            f"adapter bias={acfg['bias']!r} is not servable (slots carry "
            "A/B factors only); trained biases would silently drop"
        )
    # Anything that changes the math beyond plain scaled A/B must fail
    # loudly rather than serve approximately-the-adapter.
    for feature in ("use_dora", "modules_to_save", "alpha_pattern", "rank_pattern"):
        if acfg.get(feature):
            raise ValueError(
                f"adapter uses {feature}={acfg[feature]!r}, which the slot "
                "layout cannot represent; the adapter would serve wrong"
            )
    r = int(acfg["r"])
    if r > cfg.lora_rank:
        raise ValueError(
            f"adapter rank {r} > slot rank {cfg.lora_rank}; raise --lora-rank"
        )
    alpha = float(acfg.get("lora_alpha", r))
    # rsLoRA stores alpha/sqrt(r) scaling semantics (PEFT use_rslora).
    scale = alpha / math.sqrt(r) if acfg.get("use_rslora") else alpha / r
    ckpt = _Checkpoint(str(p))
    names = ckpt.names()

    def find(layer: int, proj: str, half: str) -> str | None:
        # PEFT names vary by wrapper depth; match on the stable suffix.
        suffix = f"layers.{layer}.self_attn.{proj}.{half}.weight"
        for n in names:
            if n.endswith(suffix):
                return n
        return None

    H, D = cfg.hidden_size, cfg.head_dim
    Nq, K, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    dt = np.dtype(jnp.dtype(cfg.dtype))
    shapes = {
        "la_q": (H, cfg.lora_rank), "lb_q": (cfg.lora_rank, Nq * D),
        "la_v": (H, cfg.lora_rank), "lb_v": (cfg.lora_rank, K * D),
    }
    out = {k: np.zeros((L, *shape), dt) for k, shape in shapes.items()}
    for layer in range(L):
        for proj, a_key, b_key in (
            ("q_proj", "la_q", "lb_q"), ("v_proj", "la_v", "lb_v"),
        ):
            if proj not in targets:
                continue
            a_name = find(layer, proj, "lora_A")
            b_name = find(layer, proj, "lora_B")
            if a_name is None or b_name is None:
                raise KeyError(
                    f"adapter missing lora_A/lora_B for layer {layer} {proj}"
                )
            a = ckpt.get(a_name)  # [r, H]
            b = ckpt.get(b_name)  # [out, r]
            out[a_key][layer, :, :r] = a.T.astype(dt)
            out[b_key][layer, :r, :] = (b.T * scale).astype(dt)
    return out
