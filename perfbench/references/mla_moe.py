"""Plain reference: multi-head latent attention + sparse experts with shared
experts, the first layers dense (DeepSeek-V2 as published: HF
``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite). No query LoRA
when ``q_lora_rank`` is null. Keys and values are MATERIALISED per head from
the compressed latent (no weight absorption, no latent cache): the published
form, which the engine's absorbed form must equal. Yarn rotary scaling with
DeepSeek's mscale on the softmax scale.

Departure noted: HF rotates the rope sub-dimension in an interleaved layout
after a permutation of the projection's columns; with seeded random weights
that permutation is a relabelling, and this reference (like the engine) uses
the split-half layout directly.

One sequence, float32, ``highest`` precision, one layer at a time.

TOLERANCE (used as in ``gqa_moe.py``). Measured on the chip at published
widths, 1 dense + 8 expert layers, 14 seeds in one process, 8 prompts = 128
tokens a seed (``perfbench/tolerance_probe.py``, my chip run, PR 24):
|engine - reference| log-prob median 0.0166-0.0214 (mean 0.0194, sd 0.0014;
over the first 64 tokens alone 0.0144-0.0268), 90th percentile 0.038-0.062,
max 0.07-0.16; reference margin max 0.05-0.12 (64 experts top-6 plus shared
experts: fewer near-ties than Qwen3's 128 top-8). The same engine log-probs
against this reference with one expert fewer of the six in every layer (64
tokens a seed): median 0.032-0.045 (mean 0.037).
  LOGPROB_MEDIAN_ATOL 0.030: two further seeds, in whole runs, read 0.0229
    and 0.0226, over the probe's fourteen (seeds, not the warm-up: a probe
    seed read the same 0.0166 in a whole run, and eight seeds probed again
    after the warm-up read as before, with skipped-term medians over all
    128 tokens of 0.0320-0.0422). So: 1.3x the largest median of sixteen
    seeds, six sd over their mean, under every skipped-term median. (The
    first tolerance, 0.028 on 64 tokens, had a seed at 0.0268: 64 tokens
    cannot separate the two ranges, 128 can.) int8: not measured.
  LOGPROB_P90_ATOL 0.1: 1.6x the largest measured.
  LOGPROB_MAX_ATOL 0.4, MARGIN_ATOL 0.3: 2.6x the worst seen; the tail is
    short here (0.156, 0.144, 0.132, 0.128 in 14 seeds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "kv_lora_rank", "q_lora_rank", "rms_norm_eps", "rope_theta", "rope_scaling",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "n_shared_experts",
)
LOGPROB_MEDIAN_ATOL = 0.030
LOGPROB_P90_ATOL = 0.1
LOGPROB_MAX_ATOL = 0.4
MARGIN_ATOL = 0.3


def _attention(lp, i, h, positions, conf):
    nh = conf["num_attention_heads"]
    nope, rope, vd = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    rank, eps = conf["kv_lora_rank"], conf["rms_norm_eps"]
    scaling = conf.get("rope_scaling")
    t = h.shape[0]
    if conf.get("q_lora_rank"):
        q = c.rms_norm(h @ c.f32(lp["wq_a"][i]), lp["q_norm"][i], eps) @ c.f32(lp["wq_b"][i])
    else:
        q = h @ c.f32(lp["wq"][i])
    q = q.reshape(t, nh, nope + rope)
    q_pe = c.rope(q[..., nope:], positions, conf["rope_theta"], scaling)
    kv_a = h @ c.f32(lp["wkv_a"][i])
    c_kv = c.rms_norm(kv_a[:, :rank], lp["kv_norm"][i], eps)
    k_pe = c.rope(kv_a[:, None, rank:], positions, conf["rope_theta"], scaling)[:, 0]
    kv = (c_kv @ c.f32(lp["wkv_b"][i])).reshape(t, nh, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5 * c.yarn_softmax_mult(scaling)
    scores = (
        jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
        + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)
    ) * scale
    out = jnp.einsum("hqk,khd->qhd", c.causal_softmax(scores), v)
    return out.reshape(t, nh * vd) @ c.f32(lp["wo"][i])


@functools.partial(jax.jit, static_argnames=("dims", "moe"))
def _layer(lp, i, x, positions, dims, moe):
    conf = c.thaw(dims)
    eps = conf["rms_norm_eps"]
    x = x + _attention(lp, i, c.rms_norm(x, lp["input_norm"][i], eps), positions, conf)
    h = c.rms_norm(x, lp["post_norm"][i], eps)
    if not moe:
        return x + c.swiglu(h, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])
    y = c.routed_experts(h, lp, i, conf)
    if conf.get("n_shared_experts"):
        y = y + c.swiglu(h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])
    return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, x, tokens, eps):
    logits = c.rms_norm(x, params["final_norm"], eps) @ c.f32(params["lm_head"])
    return c.logprob_report(logits, tokens)


def forward(params: dict, tokens, conf: dict):
    """(log-prob of each next token, best log-prob) at positions 0..T-2."""
    dims = c.freeze(conf, KEYS)
    n_dense = conf.get("first_k_dense_replace", 0)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.arange(tokens.shape[0])
        x = c.f32(params["embed"][tokens])
        for i in range(n_dense):
            x = _layer(params["dense_layers"], jnp.int32(i), x, positions, dims, False)
        for i in range(conf["num_hidden_layers"] - n_dense):
            x = _layer(params["layers"], jnp.int32(i), x, positions, dims, True)
        return _head(params, x, tokens, conf["rms_norm_eps"])
