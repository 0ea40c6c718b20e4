"""The Gated DeltaNet mixer of HF ``qwen3_next`` (its "linear_attention"
layers), on the flat step.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches a
"linear_attention" layer on, as ``mamba.KIND`` is for a Mamba-2 layer: the
weights it stacks, its cache (a layer's plane of the state pool) and ``mix``.

    [q | k | v | z] = u @ W_qkvz        widths Hk Dk | Hk Dk | Hv Dv | Hv Dv
    [b | a] = u @ W_ba                  Hv | Hv
    [q | k | v] = silu(causal_conv_4([q | k | v]))   no bias; the slot's conv
                                        state in front
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   per value head
    q = l2norm(q) * Dk^-0.5, k = l2norm(k)   over a head; key head j serves
                                        value heads j * Hv / Hk onward
    S' = exp(g_t) S;  d = beta_t (v_t - S'^T k_t);  S = S' + k_t d^T;  o_t = S^T q_t
    out = (w_norm * RMSNorm_Dv(o) * silu(z)) @ W_out   the norm a head

The published ``in_proj_qkvz`` interleaves its output per KEY head as (q[Dk],
k[Dk], v[Hv/Hk x Dv], z[Hv/Hk x Dv]) and ``in_proj_ba`` as (b[Hv/Hk], a[Hv/
Hk]); the leaves here hold the same columns as BLOCKS, q | k | v | z and b |
a (``from_published`` maps one to the other: a loader's step), so that every
slice of the projection's output is contiguous.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmd_tpu import ops
from llmd_tpu.config import ModelConfig
from llmd_tpu.models import mamba
from llmd_tpu.models.common import MixerKind, pdot, rms_norm
from llmd_tpu.ops import gdn, ssm

ROW_TOKENS = mamba.ROW_TOKENS
L2_EPS = 1e-6


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights (``mk(name, shape, scale=None)``
    draws a seeded leaf)."""
    Hd, Hv, K = cfg.hidden_size, cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim
    C = cfg.linear_conv_dim
    d_v = Hv * cfg.linear_value_head_dim
    return {
        "g_in": mk("g_in", (n, Hd, C + d_v)),
        "g_ba": mk("g_ba", (n, Hd, 2 * Hv)),
        "g_conv_w": mk("g_conv_w", (n, K, C), scale=K**-0.5),
        # A uniform on (0, 16) (HF's initialisation); the step log-spread over
        # [0.0001, 0.01] a head, its inverse softplus the bias (HF's constant
        # 1 makes exp(g) ~ 0 for most heads: a seeded model would carry no
        # state). Under a ~ N(0, 1) three heads in ten then keep more than a
        # hundredth of a state over 1,024 tokens, two in three over 256; the
        # Mamba-2 mixers' [0.001, 0.1] would leave 3 in 100.
        "g_A_log": jnp.log(jnp.clip(16.0 * jax.scipy.stats.norm.cdf(
            mk("g_A_log", (n, Hv), scale=1.0).astype(jnp.float32)
        ), 1e-3, 16.0)),
        "g_dt_bias": mamba._inv_softplus(0.001 * jnp.exp(1.15 * jnp.clip(
            mk("g_dt_bias", (n, Hv), scale=1.0).astype(jnp.float32), -2.0, 2.0
        ))),
        "g_norm": jnp.ones((n, cfg.linear_value_head_dim), dt),
        "g_out": mk("g_out", (n, d_v, Hd)),
    }


def from_published(w_qkvz, w_ba, cfg: ModelConfig):
    """``in_proj_qkvz`` [Hd, Hk (2 Dk + 2 R Dv)] and ``in_proj_ba`` [Hd, Hk 2
    R] in the published per-key-head order (R = value heads a key head) ->
    (``g_in``, ``g_ba``) in blocks."""
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    Dk, Dv, R = cfg.linear_key_head_dim, cfg.linear_value_head_dim, Hv // Hk
    w = w_qkvz.reshape(-1, Hk, 2 * Dk + 2 * R * Dv)
    parts = jnp.split(w, [Dk, 2 * Dk, 2 * Dk + R * Dv], axis=-1)
    ba = w_ba.reshape(-1, Hk, 2 * R)
    flat = lambda a: a.reshape(a.shape[0], -1)  # noqa: E731
    return (
        jnp.concatenate([flat(p) for p in parts], axis=-1),
        jnp.concatenate([flat(ba[..., :R]), flat(ba[..., R:])], axis=-1),
    )


def state_plan(mesh) -> str:
    """Which form of the state kernels (``ops/gdn.py``) the program being
    traced takes (``mamba.state_plan``'s decision, recorded under
    ``gdn_update``)."""
    if ops._decide("gdn_update", True, 1, mesh) == "xla":
        return "xla"
    return "interpret" if ops._interpret() else "pallas"


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def mix(h, lp, pool: ssm.StatePool, layer, rows: ssm.StateRows,
        cfg: ModelConfig, mesh=None, row_cap: int = ROW_TOKENS):
    """One mixer over the flat stream. ``h`` [T, 1, Hd] (normed); ``pool``
    the state pool, ``layer`` this mixer's plane of it; no row of the step
    is longer than ``row_cap``. Returns (out [T, 1, Hd], pool)."""
    T = h.shape[0]
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    Dk, Dv, C = cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_dim
    qkvz = pdot(h[:, 0], lp, "g_in")
    ba = pdot(h[:, 0], lp, "g_ba").astype(jnp.float32)
    z = qkvz[:, C:].reshape(T, Hv, Dv)
    with jax.named_scope("llmd.gdn.conv"):
        conv, conv_pool = ssm.causal_conv(
            qkvz[:, :C], lp["g_conv_w"], pool.conv, layer, rows
        )
    qkv = jax.nn.silu(conv)  # float32 from here to the gated norm
    q = l2norm(qkv[:, : Hk * Dk].reshape(T, Hk, Dk)) * Dk**-0.5
    k = l2norm(qkv[:, Hk * Dk : 2 * Hk * Dk].reshape(T, Hk, Dk))
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    v = qkv[:, 2 * Hk * Dk :].reshape(T, Hv, Dv)
    # A pad token moves no state: decay 1 and no delta.
    live = rows.live[:, None]
    beta = jnp.where(live, jax.nn.sigmoid(ba[:, :Hv]), 0.0)
    g = jnp.where(
        live,
        -jnp.exp(lp["g_A_log"]) * jax.nn.softplus(ba[:, Hv:] + lp["g_dt_bias"]),
        0.0,
    )
    plan = state_plan(mesh)
    state, y = gdn.gdn_update(pool.ssm, layer, rows, q, k, v, g, beta, plan)
    state, y = gdn.gdn_scan(state, layer, rows, q, k, v, g, beta, y, row_cap, plan)
    y = rms_norm(y, lp["g_norm"].astype(jnp.float32), cfg.rms_norm_eps)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = pdot(y.reshape(T, Hv * Dv).astype(h.dtype), lp, "g_out")
    return out[:, None, :], ssm.StatePool(state, conv_pool)


KIND = MixerKind(
    stack="gdn_layers", pool=1, init=init_layers,
    mix=lambda h, lp, pool, layer, inp, cfg, mesh: mix(
        h, lp, pool, layer, inp.state_rows, cfg, mesh
    ),
)
