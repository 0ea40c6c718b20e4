"""Plain reference: grouped-query attention + sparse experts in every layer
(Qwen3-MoE as published: HF ``modeling_qwen3_moe.py``). QK-norm per head
before the rotary embedding, SwiGLU experts, softmax-top-k router,
untied output head.

One sequence, whole forward pass, float32, ``highest`` matmul precision; one
layer at a time (a jitted layer function indexed by the layer number), so
it fits beside the engine.

TOLERANCE (see ``perfbench/correctness.py`` for how each is used). The engine
computes in bfloat16 with float32 accumulation; on the same bfloat16 weights
this reference differs from it by the rounding of activations and, because
random router weights leave many tokens on a near-tie of the top-8 of 128, by
top-k flips in some layers of some tokens. Measured on the chip at published
widths, 8 layers, 24 seeds in one process, 8 prompts = 128 tokens a seed
(``perfbench/tolerance_probe.py``, my chip run, PR 24): |engine - reference|
log-prob median 0.020-0.034 (mean 0.028, sd 0.004; over the first 64 tokens
alone 0.016-0.043), 90th percentile 0.08-0.21, max 0.17-0.79 (four seeds of
24 over 0.5); reference margin max 0.08-0.80. The same engine log-probs
against this reference with ONE EXPERT FEWER of the eight in every layer
(what the comparison reads if either side skips a term; 64 tokens a seed):
median 0.054-0.131 (mean 0.091): 23 of the 24 fail the comparison even on
64 tokens.
  LOGPROB_MEDIAN_ATOL 0.055: six sd over the mean measured, 1.6x the largest
    median; under every skipped-term median. The MEAN cannot tell the two
    apart (flips inflate it), which is why the median is the tight one.
    Whether an int8 weight path would fail it: not measured.
  LOGPROB_P90_ATOL 0.35: 1.6x the largest measured over 128 tokens.
  LOGPROB_MAX_ATOL 2.0, MARGIN_ATOL 2.0: the worst flip is heavy-tailed
    (0.79, 0.76, 0.69, 0.62, 0.55 in 24 seeds; a first tolerance of 1.0 from
    five seeds failed one run of the driver's check); 2.0 is 2.5x the worst
    seen and still under the several units by which a wrong mask, position
    or layer moves a log-prob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
)
LOGPROB_MEDIAN_ATOL = 0.055
LOGPROB_P90_ATOL = 0.35
LOGPROB_MAX_ATOL = 2.0
MARGIN_ATOL = 2.0


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(lp, i, x, positions, dims):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps = conf["rms_norm_eps"]
    t = x.shape[0]
    h = c.rms_norm(x, lp["input_norm"][i], eps)
    q = (h @ c.f32(lp["wq"][i])).reshape(t, nq, d)
    k = (h @ c.f32(lp["wk"][i])).reshape(t, nk, d)
    v = (h @ c.f32(lp["wv"][i])).reshape(t, nk, d)
    q = c.rms_norm(q, lp["attn_q_norm"][i], eps)
    k = c.rms_norm(k, lp["attn_k_norm"][i], eps)
    q = c.rope(q, positions, conf["rope_theta"], None)
    k = c.rope(k, positions, conf["rope_theta"], None)
    rep = nq // nk
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    p = c.causal_softmax(jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5)
    x = x + jnp.einsum("hqk,khd->qhd", p, v).reshape(t, nq * d) @ c.f32(lp["wo"][i])
    h = c.rms_norm(x, lp["post_norm"][i], eps)
    return x + c.routed_experts(h, lp, i, conf)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, x, tokens, eps):
    logits = c.rms_norm(x, params["final_norm"], eps) @ c.f32(params["lm_head"])
    return c.logprob_report(logits, tokens)


def forward(params: dict, tokens, conf: dict):
    """(log-prob of each next token, best log-prob) at positions 0..T-2."""
    dims = c.freeze(conf, KEYS)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.arange(tokens.shape[0])
        x = c.f32(params["embed"][tokens])
        for i in range(conf["num_hidden_layers"]):
            x = _layer(params["layers"], jnp.int32(i), x, positions, dims)
        return _head(params, x, tokens, conf["rms_norm_eps"])
