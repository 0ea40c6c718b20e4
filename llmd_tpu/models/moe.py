"""Mixture-of-experts block: router + expert FFNs.

Wide-EP target (reference docs/architecture/foundations/
wide-expert-parallelism.md:5-30): experts sharded over the flattened
(dp, tp) mesh axes, dispatch/combine as all-to-all over ICI replacing the
reference's DeepEP/NVSHMEM kernels.

Two paths behind ``moe_block``:

- dense combine (default inside jit): every token's hidden state is
  contracted against ALL experts with a top-k one-hot combine weight. With
  experts sharded over (dp, tp) XLA turns this into an all-gather of the
  token batch onto the expert shards plus local GEMMs -- the
  "high-throughput" shape of the reference's deepep_high_throughput mode.
  Numerically exact; compute cost E/topk over-work, acceptable at small E
  or big batches (prefill).
- ``moe_block_ep`` (llmd_tpu.parallel.moe_ep): explicit shard_map
  dispatch/combine with lax.all_to_all and per-expert grouped GEMM -- the
  deepep_low_latency analogue for decode. Used when the caller runs inside
  shard_map (wide-EP engine mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig


@jax.named_scope("llmd.moe.router")
def router_topk(
    h: jax.Array,
    w_router: jax.Array,
    top_k: int,
    cfg: ModelConfig | None = None,
    bias: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k expert routing covering the deployed MoE families.

    Default (cfg None): softmax-then-topk, renormalized (Mixtral-style).
    With cfg: scoring (softmax | sigmoid+bias-corrected selection),
    group-limited selection (DeepSeek V2 max-per-group / V3 top-2-sum),
    optional renormalization and routed scaling — mirroring HF
    DeepseekV2MoEGate / DeepseekV3TopkRouter semantics.

    h: [T, H]; returns (weights [T, k] f32, expert_ids [T, k] i32).
    """
    logits = (h.astype(jnp.float32) @ w_router.astype(jnp.float32))  # [T, E]
    if cfg is not None and cfg.router_logit_bias and bias is not None:
        # gpt-oss: the bias is part of the LOGITS — selection by
        # logits+bias AND weights from the (softmaxed) biased logits.
        # Softmax-topk-renormalize below is exactly softmax over the
        # selected biased logits, so fold it in and clear it.
        logits = logits + bias.astype(jnp.float32)
        bias = None
    if cfg is None:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, top_k)
        return weights / jnp.sum(weights, axis=-1, keepdims=True), ids

    T, E = logits.shape
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    # Selection scores may differ from combine weights (V3's correction
    # bias steers selection only; gathered weights stay uncorrected).
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if cfg.topk_method in ("group_max", "group_top2") and cfg.n_group > 1:
        g = cfg.n_group
        grouped = choice.reshape(T, g, E // g)
        if cfg.topk_method == "group_max":
            group_scores = jnp.max(grouped, axis=-1)
        else:  # top-2 sum per group (V3 noaux_tc)
            group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, group_idx = jax.lax.top_k(group_scores, cfg.topk_group)
        group_mask = jnp.zeros((T, g), bool).at[
            jnp.arange(T)[:, None], group_idx
        ].set(True)
        mask = jnp.repeat(group_mask, E // g, axis=-1)
        choice = jnp.where(mask, choice, 0.0 if cfg.router_scoring == "sigmoid" else -jnp.inf)
    _, ids = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, ids


@jax.named_scope("llmd.moe.shared")
def shared_expert_ffn(ht: jax.Array, lp: dict) -> jax.Array:
    """DeepSeek/Qwen2-MoE always-on shared expert (one place, three
    backends: dense / grouped / EP). Without a ``ws_gate`` leaf it is the
    non-gated one (``moe_activation`` "relu2"): down(relu(up x)^2); with a
    ``ws_sig`` leaf its output is scaled by sigmoid(x . ws_sig)."""
    from llmd_tpu.models.common import pdot

    if "ws_gate" not in lp:
        return pdot(relu2(pdot(ht, lp, "ws_up")), lp, "ws_down")
    g = jax.nn.silu(pdot(ht, lp, "ws_gate"))
    out = pdot(g * pdot(ht, lp, "ws_up"), lp, "ws_down")
    if "ws_sig" in lp:
        # qwen3_next (``shared_expert_gate``): the shared expert's output
        # under a learned gate, one number a token.
        out = jax.nn.sigmoid(ht @ lp["ws_sig"]) * out
    return out


def _expert_scales(lp: dict) -> tuple | None:
    """(gate, up, down) channel scales when the experts are int8."""
    if "we_gate_scale" not in lp:
        return None
    return (lp["we_gate_scale"], lp["we_up_scale"], lp["we_down_scale"])


def _expert_biases(lp: dict) -> tuple | None:
    """(gate, up, down) per-expert biases (gpt-oss experts carry them)."""
    if "we_gate_b" not in lp:
        return None
    return (lp["we_gate_b"], lp["we_up_b"], lp["we_down_b"])


def relu2(up: jax.Array) -> jax.Array:
    """The non-gated experts' nonlinearity (nemotron_h ``mlp_hidden_act``
    "relu2"): relu(x)^2 between an expert's two matrices."""
    return jnp.square(jax.nn.relu(up))


def expert_glu(gate: jax.Array, up: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The gated-unit nonlinearity per MoE family (pre-down-projection).

    silu: silu(gate) * up (Mixtral/Qwen/DeepSeek). swiglu_oss (gpt-oss
    GptOssExperts): gate clamped above, up clamped both sides,
    glu = gate * sigmoid(1.702 * gate), combined as (up + 1) * glu.
    """
    if cfg.moe_activation == "swiglu_oss":
        gate = jnp.minimum(gate, cfg.swiglu_limit)
        up = jnp.clip(up, -cfg.swiglu_limit, cfg.swiglu_limit)
        glu = gate * jax.nn.sigmoid(1.702 * gate)
        return (up + 1.0) * glu
    return jax.nn.silu(gate) * up


# The expert leaves that the layer scan does not slice (forward_hidden): the
# grouped kernel reads its layer of the stacked [L, E, ..] leaf in place.
# (Non-gated experts have no ``we_gate``.)
STACKED_EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def experts_of_layer(lp: dict, layer) -> dict:
    """``lp`` with one layer's ``[E, ..]`` of each stacked expert leaf: for
    the MoE paths whose consumers are XLA operations (the dense combine, the
    EP backend's shard_map), at the point of use."""
    from llmd_tpu.ops.grouped_gemm import layer_of

    return {
        **lp,
        **{k: layer_of(lp[k], layer) for k in STACKED_EXPERT_LEAVES if k in lp},
    }


def moe_block_grouped(
    h: jax.Array, lp: dict, cfg: ModelConfig, mesh=None,
    emit_census: bool = False, layer=None,
) -> jax.Array:
    """MoE FFN via grouped GEMM (DeepGEMM role): tokens sorted by expert,
    each expert multiplies only its routed rows. Numerically equivalent to
    the dense combine (same f32 weighted sum) at top_k/E of the FLOPs.
    ``lp``'s expert leaves are one layer's ``[E, ..]``, or all layers'
    ``[L, E, ..]`` with ``layer``, the index into them; E is the experts
    HELD here (``cfg.held_experts``), the router scores all
    ``cfg.num_experts``, and the result is the held experts' part of the
    sum plus the shared expert (docs/architecture/wide-ep.md).
    With ``emit_census`` the return is ``(y, census)``: this call's [4] i32
    line of the step's count (``ops.grouped_gemm.grouped_census``)."""
    from llmd_tpu.ops.grouped_gemm import moe_apply_grouped

    B, Q, H = h.shape
    T = B * Q
    ht = h.reshape(T, H)
    weights, ids = router_topk(
        ht, lp["router"], cfg.num_experts_per_tok, cfg, lp.get("router_bias")
    )
    out = moe_apply_grouped(
        ht, weights, ids, lp.get("we_gate"), lp["we_up"], lp["we_down"],
        scales=_expert_scales(lp), biases=_expert_biases(lp), cfg=cfg,
        mesh=mesh, emit_census=emit_census, layer=layer,
    )
    census = None
    if emit_census:
        out, census = out
    out = out.astype(h.dtype)
    if cfg.shared_expert_intermediate_size:
        out = out + shared_expert_ffn(ht, lp)
    out = out.reshape(B, Q, H)
    return (out, census) if emit_census else out


def moe_block(h: jax.Array, lp: dict, cfg: ModelConfig) -> jax.Array:
    """MoE FFN on [B, Q, H] -> [B, Q, H] (dense-combine path), over the
    experts held here as ``moe_block_grouped``."""
    from llmd_tpu.ops.grouped_gemm import held_slots

    B, Q, H = h.shape
    T = B * Q
    E, k = cfg.held_experts, cfg.num_experts_per_tok
    ht = h.reshape(T, H)
    weights, ids = router_topk(ht, lp["router"], k, cfg, lp.get("router_bias"))
    # combine[t, e] = sum_j weights[t, j] * (ids[t, j] == held expert e);
    # a pick of an expert held elsewhere lands in column E, dropped.
    slots = held_slots(ids, cfg, E)
    combine = jnp.zeros((T, E + 1), jnp.float32)
    combine = combine.at[jnp.arange(T)[:, None], slots].add(weights)[:, :E]

    # All experts on all tokens, the combine folded into the down
    # projection: weighting gate*up by combine[t, e] BEFORE contracting is
    # linearly equivalent to weighting per-expert outputs after, but
    # collapses combine+down-proj into ONE dot contracting {e, f}. With
    # experts EP-sharded over (dp, tp), GSPMD partitions that as a local
    # GEMM + psum over the expert axis; the old [E, T, H] per-expert
    # intermediate instead forced an involuntary full rematerialization
    # (all-gather of expert activations) every MoE layer.
    we_gate, we_up, we_down = lp.get("we_gate"), lp["we_up"], lp["we_down"]
    if "we_gate_scale" in lp:
        # Dense combine is the numerics oracle / GSPMD-fallback path:
        # dequantize in place (the serving int8 paths are grouped/EP).
        from llmd_tpu.ops.quant import dequantize

        we_gate = dequantize(we_gate, lp["we_gate_scale"], dtype=ht.dtype)
        we_up = dequantize(we_up, lp["we_up_scale"], dtype=ht.dtype)
        we_down = dequantize(we_down, lp["we_down_scale"], dtype=ht.dtype)
    biases = _expert_biases(lp)
    if we_gate is None:  # non-gated experts
        act = relu2(jnp.einsum("th,ehf->etf", ht, we_up))
    else:
        gate = jnp.einsum("th,ehf->etf", ht, we_gate)
        up = jnp.einsum("th,ehf->etf", ht, we_up)
        if biases is not None:
            gate = gate + biases[0][:, None, :]
            up = up + biases[1][:, None, :]
        act = expert_glu(gate, up, cfg)
    act = act * combine.T[:, :, None].astype(act.dtype)
    out = jnp.einsum(
        "etf,efh->th", act, we_down,
        preferred_element_type=jnp.float32,
    )
    if biases is not None:
        # Per-expert down bias, weighted by each token's combine weight.
        out = out + combine @ biases[2].astype(jnp.float32)
    out = out.astype(h.dtype)

    if cfg.shared_expert_intermediate_size:
        out = out + shared_expert_ffn(ht, lp)
    return out.reshape(B, Q, H)
