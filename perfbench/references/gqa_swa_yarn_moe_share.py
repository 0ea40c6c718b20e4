"""Plain reference: Mellum2-12B-A2.5B (HF ``JetBrains/Mellum2-12B-A2.5B-Instruct``,
``model_type: mellum``) as ONE RANK of a four-chip host sees it: window and
full grouped-query attention three to one with a RoPE table PER LAYER TYPE,
every layer's FFN sparse, of whose routed experts this rank holds a share, and
a slice of the vocabulary. Written from the equations of ISSUE 51 / the
published ``config.json``, independent of ``llmd_tpu``. With x^ = RMSNorm(x),
eps 1e-6:

  block l:  h = x + Attn_l(x^);  y = h + MoE(h^);  final RMSNorm; untied head.
  Attn_l:   q = Wq x^ (32 x 128), k, v = Wk x^, Wv x^ (4 x 128), no bias;
            RMSNorm over each head's 128 of q and of k; rotate-half RoPE with
            the table ``rope_parameters[layer_types[l]]``:
              sliding_attention: inv_j = theta^(-2j/128), cos/sin x 1
              full_attention:    YaRN: inv_j / factor blended with inv_j by the
                                 linear ramp between the correction dims of
                                 beta_fast and beta_slow over the original
                                 length (HF ``_compute_yarn_parameters``),
                                 cos/sin x ``attention_factor``
            causal softmax(q k^T / sqrt(128)) v over the positions
            > i - sliding_window on sliding layers (a mask over the full causal
            mask: no ring, no cache), over all on full layers; Wo.
  MoE:      p = softmax(h^ Wr) over ALL published experts (64 logits);
            picks = the ``num_experts_per_tok`` largest; w_i = p_i / sum_picks p;
            out = sum over the picks i THAT THIS RANK HOLDS of w_i E_i(h^),
            E_i(x) = Wd_i(silu(Wg_i x) * Wu_i x). NO shared expert: a token
            none of whose picks is held gets no FFN term at all. What the
            absent ranks' experts would add is left out, here as in the
            program, and the partial sum is what goes on to the next layer.
  logits:   over the held vocabulary slice only (ids 0..vocab_size-1 of the
            file; a sliced vocabulary is a smaller vocabulary).

Which experts are held: as many as the expert leaves hold (``we_gate`` is
``[L, held, H, F]``), ids ``deployment.rank x held`` onward; the router's width
is the router leaf's.

DEPARTURES from the publication, each elementwise or an omission:
  * ``assumed`` (config.json is silent): pre-norm placement; QK-norm (the
    Qwen3-MoE convention whose key names the config uses); rotate-half
    pairing; the window as ``q - k < sliding_window``.
  * ``omitted``: the MTP head the model card mentions (no key in config.json);
    ``intermediate_size`` names no layer (``mlp_layer_types`` all sparse).
  * ``reduced``: ``num_experts`` (held of 64), ``vocab_size`` (a slice), and
    ``num_hidden_layers`` where the file cuts it.

One sequence, float32, ``highest`` matmul precision, one layer at a time, in
BLOCKS so that it fits beside the engine on a 16 GB chip: attention in blocks
of 256 queries, one expert at a time, the head over the compared positions
only.

THE COMPARISON (``perfbench/correctness.py`` draws prompts of 64-256 tokens;
``perfbench/topologies/engine_hybrid_yarn.py`` says how it is put to work).
``params["bound"]`` maps a prompt to the seeded CONTEXT the system served it
behind; ``forward`` prepends it and reports the positions of ``tokens`` only.
``first_full_layer_keys`` gives layer 3's rotated keys for every position of a
sequence: what the system's main pool holds of it, token by token, and the
one place where the YaRN table can be read without a softmax in between.

Probe keys in ``conf`` (``perfbench/tolerance_probe_yarn.py``; a run sets
none): ``rope_parameters`` / ``sliding_window`` as published but overridden,
``experts_used`` (the first n of the held experts), ``compute_dtype``
("bfloat16": the same equations with every product's sides and result rounded
to bfloat16).

TOLERANCES: beside the constants below, each with the readings it lies
between.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.references import _common as c
from perfbench.references.gqa_swa_moe_share import bound_context, first_held  # the same protocol, the same share

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "num_experts_per_tok", "norm_topk_prob", "compute_dtype",
)
Q_BLOCK = 256

# |system - reference| log-prob of the compared tokens, and the reference's
# margin: ``perfbench/correctness.py``'s 128 tokens (16 a prompt) and the
# topology's own pooled comparison (64 a prompt, 512 in all) are held to the
# same four limits. Readings on the chip (PERF.md section 6, PR 51:
# ``tolerance_probe_yarn.py``, all 28 layers, a context of 4,096; "sound" is 8
# probe seeds and 7 whole runs on seeds of their own, a control 3-4 seeds, each
# range over both comparisons).
# Median: sound 0.0079-0.0147. One held expert fewer 0.033-0.125, the plain
# table on the full layers 0.027-0.181, attention_factor 1 0.025-0.112, the
# YaRN table on the sliding layers 0.100-0.526, a window of 2,048 0.129-0.517,
# float8 weights (the nearest precision below the configuration's bfloat16)
# 2.8-3.7.
LOGPROB_MEDIAN_ATOL = 0.02
# 90th percentile: nine tokens in ten differ by ~0.01 and one in ten by
# 0.03-0.08 where a top-8-of-64 pick flipped across the edge of the held range
# between bfloat16 and float32 (the token gains or loses a whole expert term,
# and no shared expert cushions it). Sound 0.0205-0.0393; every control reads
# over 0.08 on the 128 or 512 tokens of a complete comparison (one held expert
# fewer 0.081-0.299, the plain table 0.092-0.324, attention_factor 1
# 0.100-0.238, float8 weights 3.6-4.0).
LOGPROB_P90_ATOL = 0.055
# Max and margin: loose guards against a gross fault (a flipped pick is
# heavy-tailed). Sound 0.034-0.093 and 0.006-0.079; a window of 2,048 reads
# 1.2-2.5 and 1.5-2.5, float8 weights 3.7-4.3 and 3.8-4.1.
LOGPROB_MAX_ATOL = 1.0
MARGIN_ATOL = 1.0
# Layer 3's cached keys against ``first_full_layer_keys``, per token
# |Ks - Kr|_F / |Kr|_F. Its median is bfloat16's rounding of three layers and
# of the key itself: sound 0.0096-0.0115 (60 bound prompts). One held expert
# fewer reads 0.024-0.179, attention_factor 1 0.2775 on every prompt (= the
# factor less one: every key is that much longer), the YaRN table on the
# sliding layers 0.44-0.48, a window of 2,048 0.73-0.91, the plain table on the
# full layers 0.92-1.07 (another rotation at 4k positions is another vector),
# float8 weights 0.95-0.96.
KEY_TOKEN_MEDIAN_RTOL = 0.016
# The share of the tokens, in percent, over KEY_TOKEN_FAR_RTOL: a token that
# gained or lost an expert term in layers 0-2 (its 99th percentile reads
# 0.08-0.10 in a sound run). Sound 2.4-6.0; one held expert fewer 18.4-99.8,
# float8 weights 100.
KEY_TOKEN_FAR_RTOL = 0.06
KEY_FAR_SHARE_MAX = 10.0
# NOT told from a sound run, by any limit: the reference's own products rounded
# to bfloat16 (``compute_dtype``; ISSUE 51 listed it as a control). It reads
# what the float32 reference reads (median 0.0113-0.0147, keys 0.0098-0.0114):
# the SYSTEM computes in bfloat16, so a bfloat16 reference is as far from it as
# a float32 one, by the same rounding. The precision control that can fail is
# the one below the configuration's: float8 weights, above.


def _inv_freq(dim: int, own: dict):
    """(inverse frequencies [dim / 2], the factor on cos and sin) of one
    entry of ``rope_parameters``, after HF's
    ``_compute_default_rope_parameters`` / ``_compute_yarn_parameters``."""
    half = dim // 2
    theta = float(own["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=c.F32) / half))
    kind = own.get("rope_type") or "default"
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"reference: rope type {kind!r}")
    factor, orig = float(own["factor"]), float(own["original_max_position_embeddings"])
    att = own.get("attention_factor")
    if att is None:
        att = 1.0 if factor <= 1.0 else 0.1 * math.log(factor) + 1.0

    def corr(rot: float) -> float:
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(own.get("beta_fast") or 32))), 0)
    high = min(math.ceil(corr(float(own.get("beta_slow") or 1))), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=c.F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp), float(att)


def _rope(x, positions, own: dict):
    """Rotate the last axis of [T, heads, dim]: rotate-half pairing."""
    dim = x.shape[-1]
    inv, att = _inv_freq(dim, own)
    ang = positions.astype(c.F32)[:, None] * inv
    cos, sin = (jnp.cos(ang) * att)[:, None, :], (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _round(conf: dict):
    """The identity, or a rounding to ``compute_dtype``'s precision (the
    probe's lower precision; values stay float32 arrays: bfloat16 arrays
    inside these blocks crash the chip's compiler in a fusion check)."""
    low = conf.get("compute_dtype")
    if not low:
        return lambda x: x
    info = jnp.finfo(jnp.dtype(low))
    return lambda x: jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _dot(conf: dict):
    """``x @ w`` in float32, or with both sides and the product rounded to
    ``compute_dtype`` (products of bfloat16 values summed in float32: what
    a bfloat16 matrix unit gives)."""
    rnd = _round(conf)
    return lambda x, w: rnd(rnd(x) @ rnd(c.f32(w)))


def _qkv(lp, i, x, conf: dict, own: dict):
    """(q, k, v) of one layer: projected, normed per head, rotated."""
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps, dot = conf["rms_norm_eps"], _dot(conf)
    t = x.shape[0]
    positions = jnp.arange(t)
    h = c.rms_norm(x, lp["input_norm"][i], eps)
    q = c.rms_norm(dot(h, lp["wq"][i]).reshape(t, nq, d), lp["attn_q_norm"][i], eps)
    k = c.rms_norm(dot(h, lp["wk"][i]).reshape(t, nk, d), lp["attn_k_norm"][i], eps)
    v = dot(h, lp["wv"][i]).reshape(t, nk, d)
    return _rope(q, positions, own), _rope(k, positions, own), v


@functools.partial(jax.jit, static_argnames=("dims", "window", "rope"))
def _attention(lp, i, x, dims, window: int, rope: tuple):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    t = x.shape[0]
    positions = jnp.arange(t)
    q, k, v = (_round(conf)(a) for a in _qkv(lp, i, x, conf, dict(rope)))
    k, v = jnp.repeat(k, nq // nk, axis=1), jnp.repeat(v, nq // nk, axis=1)
    outs = []
    for t0 in range(0, t, Q_BLOCK):
        rows = positions[t0:t0 + Q_BLOCK]
        mask = positions[None, :] <= rows[:, None]
        if window:
            mask &= positions[None, :] > rows[:, None] - window
        s = jnp.einsum("qhd,khd->hqk", q[t0:t0 + Q_BLOCK], k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v).reshape(-1, nq * d))
    return x + _dot(conf)(jnp.concatenate(outs), lp["wo"][i])


@functools.partial(jax.jit, static_argnames=("dims", "rope"))
def _keys(lp, i, x, dims, rope: tuple):
    # (the barrier: with the probe's rounding in front of it, the rotation's
    # concatenate as this program's ROOT fails a check in the chip's compiler)
    return jax.lax.optimization_barrier(_qkv(lp, i, x, c.thaw(dims), dict(rope))[1])


@functools.partial(jax.jit, static_argnames=("dims", "first", "held"))
def _sparse_ffn(lp, i, x, dims, first: int, held: int | None = None):
    """Router over every published expert; the terms of the experts held
    here (ids ``first`` onward, as many as the leaves hold, or the first
    ``held`` of them: the probe's rank with one expert fewer). Nothing else:
    the model has no shared expert."""
    conf = c.thaw(dims)
    dot = _dot(conf)
    h = c.rms_norm(x, lp["post_norm"][i], conf["rms_norm_eps"])
    probs = jax.nn.softmax(h @ c.f32(lp["router"][i]), axis=-1)  # [T, all experts]
    w, picks = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], picks].add(w)
    held = held or lp["we_gate"].shape[1]

    def one(e, acc):
        y = dot(jax.nn.silu(dot(h, lp["we_gate"][i, e])) * dot(h, lp["we_up"][i, e]), lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    return x + jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, x, tokens, eps):
    logits = c.rms_norm(x, final_norm, eps) @ c.f32(lm_head)
    return c.logprob_report(logits, tokens)


def layer_kinds(conf: dict) -> list:
    """Per layer of the cut depth: (window, the layer type's rope parameters
    as a hashable)."""
    types = list(conf["layer_types"])[: conf["num_hidden_layers"]]
    window = int(conf.get("sliding_window") or 0)
    tables = conf["rope_parameters"]
    return [(window if t == "sliding_attention" else 0, tuple(sorted(tables[t].items()))) for t in types]


def _stream(params: dict, full, conf: dict, upto: int | None = None, trace: list | None = None):
    """The residual stream of ``full`` in front of layer ``upto`` (behind the
    last layer without it)."""
    dims, first = c.freeze(conf, KEYS), first_held(params, conf)
    lp = params["layers"]
    x = c.f32(params["embed"][full])
    for l, (window, rope) in enumerate(layer_kinds(conf)[:upto]):
        if trace is not None:
            trace.append(x)
        x = _attention(lp, jnp.int32(l), x, dims, window, rope)
        x = _sparse_ffn(lp, jnp.int32(l), x, dims, first, conf.get("experts_used"))
    return x


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob) at positions 0..T-2 of
    ``tokens``, computed behind the context ``params["bound"]`` has for the
    prompt, where it has one. ``trace``, a list, receives each layer's input
    (the CPU tests)."""
    context = bound_context(params, tokens)
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = _stream(params, full, conf, trace=trace)
        # The head over the positions of ``tokens`` only.
        return _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):],
                     conf["rms_norm_eps"])


def first_full_layer_keys(params: dict, tokens, conf: dict):
    """The keys ``[t, Nk, D]`` that the FIRST full-attention layer (layer 3)
    caches for ``tokens`` (the whole sequence, a context in front included;
    padded to whatever one shape the caller likes: causal, so padding is
    inert), normed and rotated under the full layers' table. Behind them lie
    three sliding layers, their rings' worth of window and their held experts."""
    at = [w for w, _ in layer_kinds(conf)].index(0)
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray([int(t) for t in tokens], jnp.int32)
        x = _stream(params, full, conf, upto=at)
        return _keys(params["layers"], jnp.int32(at), x, c.freeze(conf, KEYS), layer_kinds(conf)[at][1])


def key_error(system_keys, reference_keys) -> dict:
    """How far the cached keys lie from the reference's, per TOKEN:
    ``|Ks - Kr|_F / |Kr|_F`` over a token's heads and dimensions, then the
    median over the tokens (what a wrong table or factor moves for every
    token) and the share of the tokens, in percent, that lie over
    ``KEY_TOKEN_FAR_RTOL`` (a term that some tokens gain or lose: a held
    expert, a flipped pick)."""
    import numpy as np

    ks, kr = (np.asarray(a, np.float64) for a in (system_keys, reference_keys))
    rel = np.sqrt(np.sum((ks - kr) ** 2, axis=(1, 2)) / np.maximum(np.sum(kr ** 2, axis=(1, 2)), 1e-30))
    return {"token_median": float(np.median(rel)), "token_p99": float(np.quantile(rel, 0.99)),
            "far_share": float(100.0 * np.mean(rel > KEY_TOKEN_FAR_RTOL))}
