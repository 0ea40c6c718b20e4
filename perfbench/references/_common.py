"""Pieces both plain references share. float32 throughout, matmuls at
``highest`` precision (a TPU otherwise multiplies float32 in bfloat16
passes), no kernels, no cache, no batching."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(x):
    return x.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(w)


def rope_inv_freq(dim: int, theta: float, scaling: dict | None):
    """Inverse frequencies and the factor on cos/sin, after HF's
    ``_compute_default_rope_parameters`` / ``_compute_yarn_parameters``."""
    half = dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    kind = (scaling or {}).get("rope_type") or (scaling or {}).get("type") or "default"
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"reference: rope scaling {kind!r}")
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def mscale(scale, m=1.0):
        return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0

    if scaling.get("mscale") and scaling.get("mscale_all_dim"):
        att = mscale(factor, float(scaling["mscale"])) / mscale(factor, float(scaling["mscale_all_dim"]))
    else:
        att = mscale(factor)
    fast, slow = float(scaling.get("beta_fast") or 32), float(scaling.get("beta_slow") or 1)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp), att


def yarn_softmax_mult(scaling: dict | None) -> float:
    """DeepSeek's yarn multiplies the softmax scale by mscale(factor,
    mscale_all_dim)^2 (HF DeepseekV2Attention.__init__)."""
    kind = (scaling or {}).get("rope_type") or (scaling or {}).get("type")
    if kind != "yarn" or not scaling.get("mscale_all_dim"):
        return 1.0
    m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(float(scaling["factor"])) + 1.0
    return m * m if float(scaling["factor"]) > 1.0 else 1.0


def rope(x, positions, theta, scaling):
    """Rotate the last axis of [T, heads, dim] (HF split-half layout)."""
    dim = x.shape[-1]
    inv, att = rope_inv_freq(dim, theta, scaling)
    ang = positions.astype(F32)[:, None] * inv
    c, s = (jnp.cos(ang) * att)[:, None, :], (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_softmax(scores):
    """scores [heads, T, T] -> probabilities under the causal mask."""
    t = scores.shape[-1]
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)


def routed_experts(x, lp, i, conf):
    """Sum over a token's chosen experts of weight x expert(x). Experts run
    one at a time over ALL tokens with the unchosen ones weighted 0: plain,
    and only one expert's weights are ever held in float32.

    Router: softmax over all experts, top-k, renormalised where the
    configuration says so, times the routed scaling factor (HF
    Qwen3MoeSparseMoeBlock; DeepseekV2MoEGate with topk_method greedy)."""
    k = conf["num_experts_per_tok"]
    scores = jax.nn.softmax(x @ f32(lp["router"][i]), axis=-1)  # [T, E]
    top_w, top_i = jax.lax.top_k(scores, k)
    if conf.get("norm_topk_prob"):
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * float(conf.get("routed_scaling_factor") or 1.0)
    n_exp = scores.shape[-1]
    combine = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], top_i].add(top_w)

    def one(e, acc):
        y = swiglu(x, lp["we_gate"][i, e], lp["we_up"][i, e], lp["we_down"][i, e])
        return acc + y * combine[:, e][:, None]

    return jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(x))


def freeze(conf: dict, keys: tuple) -> tuple:
    """The named keys of a configuration as a hashable (static) argument."""
    def fz(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v

    return tuple((k, fz(conf.get(k))) for k in keys)


def thaw(dims: tuple) -> dict:
    return {k: (dict(v) if isinstance(v, tuple) else v) for k, v in dims}


def logprob_report(logits, tokens):
    """For positions p = 0..T-2: the log-probability of tokens[p+1], and the
    largest log-probability, under logits[p]."""
    lp = jax.nn.log_softmax(logits[:-1], axis=-1)
    nxt = jnp.take_along_axis(lp, tokens[1:, None], axis=-1)[:, 0]
    return nxt, jnp.max(lp, axis=-1)
