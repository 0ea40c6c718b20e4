"""The Mamba-2 mixer of a hybrid state-space model (HF ``GraniteMoeHybrid``,
whose mixer is Bamba's, one group of B and C; HF ``NemotronH``, several), on
the flat step.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches a
"mamba" layer on: the weights it stacks (``init_layers``), its cache (the
state pool, a layer's plane of it) and ``mix``: normed input in, the mixer's
output and the updated pool out. Attention is the other kind and stays in
``llama.layer_body``.

    [z | xBC | dt] = u @ W_in                  widths d_in | d_in + 2GN | H
    xBC = silu(causal_conv_k(xBC) + b_conv)    the slot's conv state in front
    [x | B | C] = xBC                          d_in | GN | GN: B, C are [G, N],
                                               head h reads group h // (H / G)
    dt = softplus(dt + dt_bias), A = -exp(A_log)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T, y_t = H_t C_t + D x_t
    out = (RMSNorm_G(y * silu(z)) * w_norm) @ W_out   the norm over each of
                                               the G groups' d_in / G channels

G = ``mamba_n_groups`` is static: at G = 1 B and C are the ``[T, N]``
operands and the norm the one ``rms_norm`` call they always were (the traced
program of a one-group model does not depend on this file knowing groups).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmd_tpu import ops
from llmd_tpu.config import ModelConfig
from llmd_tpu.models.common import MixerKind, pdot, rms_norm
from llmd_tpu.ops import ssm

# The flat step cuts a prefill chunk into rows of at most this many tokens
# (engine/runner.py::_UNIFIED_ROW_TOKENS): the scan's chunk.
ROW_TOKENS = 64


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights (``mk(name, shape, scale=None)``
    draws a seeded leaf)."""
    Hd, Hh, K = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_conv
    d_in, C = cfg.mamba_d_inner, cfg.mamba_conv_dim
    heads = jnp.arange(1, Hh + 1, dtype=jnp.float32)
    return {
        "m_in": mk("m_in", (n, Hd, d_in + C + Hh)),
        "m_conv_w": mk("m_conv_w", (n, K, C), scale=K**-0.5),
        "m_conv_b": mk("m_conv_b", (n, C), scale=0.1),
        # Mamba-2's own initialisation: A in [1, 16]; dt log-spread over
        # [0.001, 0.1] a head (softplus^-1 of it is the bias), so that heads
        # remember from a few tokens to a thousand.
        "m_A_log": jnp.broadcast_to(
            jnp.log(1.0 + 15.0 * heads / Hh), (n, Hh)
        ).astype(jnp.float32),
        "m_dt_bias": _inv_softplus(0.01 * jnp.exp(1.15 * jnp.clip(
            mk("m_dt_bias", (n, Hh), scale=1.0).astype(jnp.float32), -2.0, 2.0
        ))),
        "m_D": jnp.ones((n, Hh), jnp.float32),
        "m_norm": jnp.ones((n, d_in), dt),
        "m_out": mk("m_out", (n, d_in, Hd)),
    }


def state_plan(mesh) -> str:
    """Which form of the state kernels (``ops/ssm.py``) the program being
    traced takes: the dispatch decision every op makes (``ops._decide``: the
    env lever, then the platform of the mesh), recorded under ``ssm_update``."""
    if ops._decide("ssm_update", True, 1, mesh) == "xla":
        return "xla"
    return "interpret" if ops._interpret() else "pallas"


def mix(h, lp, pool: ssm.StatePool, layer, rows: ssm.StateRows,
        cfg: ModelConfig, mesh=None, row_cap: int = ROW_TOKENS):
    """One mixer over the flat stream. ``h`` [T, 1, Hd] (normed); ``pool``
    the state pool, ``layer`` this mixer's plane of it; no row of the step
    is longer than ``row_cap``. Returns (out [T, 1, Hd], pool)."""
    T = h.shape[0]
    Hh, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    G, d_in, C = cfg.mamba_n_groups, cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxbcdt = pdot(h[:, 0], lp, "m_in")
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : d_in + C], zxbcdt[:, d_in + C :]
    conv, conv_pool = ssm.causal_conv(xbc, lp["m_conv_w"], pool.conv, layer, rows)
    xbc = jax.nn.silu(conv + lp["m_conv_b"].astype(jnp.float32)).astype(h.dtype)
    x = xbc[:, :d_in].reshape(T, Hh, P)
    Bm, Cm = xbc[:, d_in : d_in + G * N], xbc[:, d_in + G * N :]
    if G > 1:
        Bm, Cm = Bm.reshape(T, G, N), Cm.reshape(T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["m_dt_bias"])
    # A pad token moves no state: dt 0 is decay 1 and no input.
    dt = jnp.where(rows.live[:, None], dt, 0.0)
    dA = dt * -jnp.exp(lp["m_A_log"])
    plan = state_plan(mesh)
    ssm_pool, y = ssm.ssm_update(pool.ssm, layer, rows, x, dt, dA, Bm, Cm, plan)
    ssm_pool, y = ssm.ssm_scan(
        ssm_pool, layer, rows, x, dt, dA, Bm, Cm, y, row_cap, plan
    )
    y = y + lp["m_D"][None, :, None] * x.astype(jnp.float32)
    y = y.reshape(T, d_in) * jax.nn.silu(z.astype(jnp.float32))
    w_norm = lp["m_norm"].astype(jnp.float32)
    if G > 1:  # the gated norm over each group's channels
        y = rms_norm(
            y.reshape(T, G, d_in // G), w_norm.reshape(G, d_in // G),
            cfg.rms_norm_eps,
        ).reshape(T, d_in)
    else:
        y = rms_norm(y, w_norm, cfg.rms_norm_eps)
    out = pdot(y.astype(h.dtype), lp, "m_out")
    return out[:, None, :], ssm.StatePool(ssm_pool, conv_pool)


KIND = MixerKind(
    stack="mamba_layers", pool=1, init=init_layers,
    mix=lambda h, lp, pool, layer, inp, cfg, mesh: mix(
        h, lp, pool, layer, inp.state_rows, cfg, mesh
    ),
)
