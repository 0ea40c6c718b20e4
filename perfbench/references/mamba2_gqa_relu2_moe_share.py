"""Plain reference: NVIDIA-Nemotron-3-Nano-30B-A3B (HF ``nvidia/NVIDIA-Nemotron-3-
Nano-30B-A3B-BF16``, ``model_type: nemotron_h``) as ONE RANK of an 8-way
expert-parallel deployment sees it. Written from the equations of ISSUE 42 /
the published ``config.json`` (HF ``modeling_nemotron_h.py``), independent of
``llmd_tpu``. The model is a sequence of BLOCKS of ONE mixer each, named by
``hybrid_override_pattern`` cut to ``num_hidden_layers`` blocks:

  block i:  x <- x + Mixer_i(RMSNorm_i(x)), eps ``norm_eps``; final RMSNorm;
            logits = h @ W_head over the held vocabulary slice (untied).
  ``M``:    [z | xBC | dt] = u W_in, widths d_in | d_in + 2 G N | heads, d_in =
            ``mamba_num_heads`` x ``mamba_head_dim`` (NOT ``expand`` x hidden);
            xBC = silu(conv_k(xBC) + b), a causal depthwise conv of ``conv_kernel``
            taps, here as shifted adds; [x | B | C] = xBC with B, C ``[G, N]``
            (``n_groups`` G, ``ssm_state_size`` N): head h reads group
            h // (heads / G); dt = softplus(dt + dt_bias), A = -exp(A_log);
            H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T (H ``[d_head, N]`` a head,
            H_{-1} = 0), y_t = H_t C_t + D x_t: a ``lax.scan`` over tokens;
            out = (GroupRMSNorm_G(y * silu(z)) * w) W_out: gate first, then the
            norm over each group's d_in / G channels.
  ``E``:    s = sigmoid(u W_r) over ALL published experts; the
            ``num_experts_per_tok`` largest of s + bias; w = s[picked] /
            (sum + 1e-20) * ``routed_scaling_factor``; out = sum over the picks
            THIS RANK HOLDS of w_e (relu(u U_e)^2) D_e, NON-gated (two matrices an
            expert), + the shared expert (relu(u U_s)^2) D_s, which every rank
            computes alike. What the absent ranks' experts would add is left
            out, here as in the program.
  ``*``:    q, k, v = u W_q, u W_k, u W_v (``num_attention_heads`` /
            ``num_key_value_heads`` x ``head_dim``, no bias, NO rotation); causal
            softmax(q k^T / sqrt(head_dim)) v; W_o.

The served parameter tree stacks a mixer with the ``E`` block behind it as one
LAYER (``llmd_tpu.models.registry.nemotron_h_layers`` says how; this file only
needs the counting): ``layers.input_norm[l]`` is the norm of the l-th MIXER
block, ``mamba_layers`` / ``attn_layers`` hold the mixers of each kind in
order, and the ``E`` blocks' leaves (``post_norm``, ``router``,
``router_bias``, ``we_up``, ``we_down``, ``ws_up``, ``ws_down``) are stacked
over the ``E`` blocks in order. Which experts are held: as many as ``we_down``
holds (``[E blocks, held, F', H]``), ids ``deployment.rank x held`` onward.

DEPARTURES from the publication, each noted:
  * no positional encoding in attention (HF ``NemotronHAttention`` applies
    none; ``rope_theta`` is in the file, unread); ``dt`` unclamped
    (``time_step_*`` are initialisation ranges, ``time_step_limit`` (0, inf));
  * ``chunk_size`` 128 is HF's blocking of the same recurrence; here a scan;
  * ``n_group`` 1 / ``topk_group`` 1: the group-limited choice is the plain one;
  * the expert leaves may be STORED wider than ``moe_intermediate_size`` (zero
    columns of U_e, zero rows of D_e: relu(0)^2 = 0 meets a zero row; exact);
  * ``reduced``: ``num_hidden_layers`` (blocks), ``n_routed_experts`` (held of
    the published count), ``vocab_size`` (a slice).

One sequence, float32, ``highest`` matmul precision, one block at a time, in
BLOCKS so that it fits beside the engine on a 16 GB chip: attention in blocks
of 256 queries, one expert at a time, the head over the compared positions only.

THE COMPARISON: ``perfbench/topologies/engine_mixer.py`` says how it is put to
work; ``params["bound"]`` maps a prompt to the seeded CONTEXT the system served
it behind. TOLERANCES: beside the constants below, each with the readings it
lies between (``perfbench/tolerance_probe_mixer.py`` made them on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size", "norm_eps",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
    "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    # Wrong on purpose, for perfbench/tolerance_probe_mixer.py's controls
    # (absent from every configuration): the SSM state rounded to this dtype
    # after every token; ONE B and C (group 0's) for all heads; the gated norm
    # over all d_in channels at once; silu in place of relu^2; the router's
    # scores cut to the held experts' logits.
    "probe_state_dtype", "probe_one_group", "probe_norm_whole", "probe_silu", "probe_router_held",
)
Q_BLOCK = 256

# |system - reference| log-prob and the reference's margin over the compared
# tokens: perfbench/correctness.py's 128 (the first 16 of each prompt) and the
# topology's own longer decode (64 a prompt among other running rows, pooled:
# 256 then 512 tokens), held to the same four. These weights give logits of
# spread ~1 over the 16,384 held ids (an untied head, no logits scaling), so a
# bfloat16 program stands further from a float32 reference than granite's flat
# logits let it, and a top-1 flip between near-equal logits reads ~1: the
# limits are this reference's own, set between readings on the chip (PERF.md
# section 6, PR 42; perfbench/tolerance_probe_mixer.py: 6 probe seeds + 2
# whole runs sound; one_group and float8 on all 6 seeds, the other controls on
# 2). The control nearest below the configuration's bfloat16 is float8 weights;
# it fails every one of the four, and the state limits.
# Median: sound 0.0245-0.0450 over 128 tokens, 0.0281-0.0378 over 512;
# routed_scaling_factor 1 0.38-0.45, ONE B and C for all heads 0.89-0.99, the
# norm over all 4,096 channels 0.92-1.08, silu for relu^2 1.73-1.92, the router
# cut to the 16 held logits 2.24-2.30, float8 weights 2.71-2.99. (The SSM state
# in bfloat16 reads 0.024-0.042: the sound range; the state limits tell it.)
LOGPROB_MEDIAN_ATOL = 0.12
# 90th percentile: sound 0.103-0.264 over 128 tokens, 0.163-0.223 over 512;
# routed_scaling_factor 1 1.00-1.08, one group 1.67-2.06, float8 3.74-4.19.
LOGPROB_P90_ATOL = 0.55
# Max: sound 0.35-1.39 (heavy-tailed: the largest readings are top-1 flips of
# the reference's margin); float8 4.93-6.10, the router cut 4.59-5.37, silu
# 3.92-4.63. (routed_scaling_factor 1 reads 1.71-2.03 and falls by the median.)
LOGPROB_MAX_ATOL = 3.0
# Margin: sound 0.29-1.30; float8 4.95-6.70, one group 3.02-4.75, the router cut
# 4.45-5.27, routed_scaling_factor 1 1.89-2.02 (told by median and p90).
MARGIN_ATOL = 3.0
# The FIRST mixer's SSM state per head, |Hs - Hr|_F / |Hr|_F: median and max
# over the 64 heads (``state_error``), as granite's reference holds it.
# Readings on the chip (6 seeds x 5 states sound). Median over the heads:
# sound 0.0041-0.0046; float8 weights 0.69-0.76, one group 1.19-1.35. (A
# bfloat16 state reads 0.0061-0.0070: its median does not tell it.)
STATE_HEAD_MEDIAN_RTOL = 0.03
# Max over the heads: sound 0.0058-0.0127; the state rounded to bfloat16 after
# every token 0.031-0.082 in every slot and snapshot (2 seeds x 5 states),
# float8 1.08-2.78, one group 1.43-2.00.
STATE_HEAD_MAX_RTOL = 0.024


def blocks_of(conf: dict) -> str:
    return conf["hybrid_override_pattern"][: conf["num_hidden_layers"]]


def _eps(conf: dict) -> float:
    return conf["norm_eps"]


def _mixer_inputs(lp, mp, l, i, x, conf, frozen):
    """What mamba mixer ``i`` (its norm the ``l``-th mixer block's) feeds its
    recurrence from the residual stream ``x``: (z, x [t, heads, d_head], B, C
    [t, G, N], dt [t, heads], A [heads]). ``frozen`` [t] bool: positions that
    leave the state as it was (padding behind the live tokens)."""
    nh, p, n, g, k = (conf[s] for s in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
                                        "conv_kernel"))
    d_in = nh * p
    t = x.shape[0]
    u = c.rms_norm(x, lp["input_norm"][l], _eps(conf))
    zxbcdt = u @ c.f32(mp["m_in"][i])
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * g * n], zxbcdt[:, 2 * d_in + 2 * g * n:]
    w = c.f32(mp["m_conv_w"][i])  # [k, C]: tap j multiplies the input k - 1 - j back
    conv = sum(
        jnp.concatenate([jnp.zeros((j, xbc.shape[1]), c.F32), xbc[: t - j]]) * w[k - 1 - j] for j in range(k)
    )
    xbc = jax.nn.silu(conv + c.f32(mp["m_conv_b"][i]))
    xs = xbc[:, :d_in].reshape(t, nh, p)
    bs = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
    cs = xbc[:, d_in + g * n:].reshape(t, g, n)
    if conf.get("probe_one_group"):
        bs, cs = (jnp.broadcast_to(v[:, :1], v.shape) for v in (bs, cs))
    dt = jax.nn.softplus(dt + c.f32(mp["m_dt_bias"][i]))  # [t, nh]
    dt = jnp.where(frozen[:, None], 0.0, dt)
    return z, xs, bs, cs, dt, -jnp.exp(c.f32(mp["m_A_log"][i]))


def _recurrence(xs, bs, cs, dt, a, conf):
    """(H after the last token [heads, d_head, N], y [t, heads, d_head]): the
    recurrence token by token from H = 0, head h reading group h // (heads / G)."""
    nh, p, (g, n) = xs.shape[1], xs.shape[2], bs.shape[1:]
    mantissa = {"bfloat16": 7}.get(conf.get("probe_state_dtype"), 23)

    def step(h, tok):
        x_t, b_t, c_t, dt_t = tok
        b_h, c_h = jnp.repeat(b_t, nh // g, axis=0), jnp.repeat(c_t, nh // g, axis=0)  # [heads, N]
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=mantissa)
        return h, jnp.sum(h * c_h[:, None, :], axis=-1)

    return jax.lax.scan(step, jnp.zeros((nh, p, n), c.F32), (xs, bs, cs, dt))


@functools.partial(jax.jit, static_argnames=("dims",))
def _mamba(lp, mp, l, i, x, dims):
    conf = c.thaw(dims)
    t, g = x.shape[0], conf["n_groups"]
    z, xs, bs, cs, dt, a = _mixer_inputs(lp, mp, l, i, x, conf, jnp.zeros(t, bool))
    _, y = _recurrence(xs, bs, cs, dt, a, conf)
    y = (y + c.f32(mp["m_D"][i])[None, :, None] * xs).reshape(t, -1) * jax.nn.silu(z)
    w = c.f32(mp["m_norm"][i])
    if conf.get("probe_norm_whole"):
        y = c.rms_norm(y, w, _eps(conf))
    else:  # the norm over each group's channels, after the gate
        y = c.rms_norm(y.reshape(t, g, -1), w.reshape(g, -1), _eps(conf)).reshape(t, -1)
    return x + y @ c.f32(mp["m_out"][i])


@functools.partial(jax.jit, static_argnames=("dims",))
def _first_state(lp, mp, x, live, dims):
    conf = c.thaw(dims)
    frozen = jnp.arange(x.shape[0]) >= live
    _z, xs, bs, cs, dt, a = _mixer_inputs(lp, mp, jnp.int32(0), jnp.int32(0), x, conf, frozen)
    return _recurrence(xs, bs, cs, dt, a, conf)[0]


def first_mixer_state(params: dict, tokens, live: int, conf: dict, context_len: int = 0):
    """The SSM state ``[heads, d_head, N]`` of the FIRST block's mixer after
    ``tokens[:live]`` (``tokens`` padded to whatever one shape the caller
    likes; the padding moves nothing): nothing of the system's bfloat16
    arithmetic lies upstream of it but its own projection and conv."""
    del context_len
    if blocks_of(conf)[0] != "M":
        raise NotImplementedError("the first block is no state-space mixer")
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray([int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][ids])
        return _first_state(params["layers"], params["mamba_layers"], x, jnp.int32(live), c.freeze(conf, KEYS))


def state_error(system_state, reference_state) -> dict:
    """Per HEAD ``|Hs - Hr|_F / |Hr|_F``, then the median and the max over the
    heads (heads differ in how long they remember)."""
    import numpy as np

    hs, hr = (np.asarray(a, np.float64) for a in (system_state, reference_state))
    rel = np.sqrt(np.sum((hs - hr) ** 2, axis=(1, 2))) / np.maximum(np.sqrt(np.sum(hr ** 2, axis=(1, 2))), 1e-30)
    return {"head_median": float(np.median(rel)), "head_max": float(np.max(rel))}


@functools.partial(jax.jit, static_argnames=("dims",))
def _attention(lp, ap, l, i, x, dims):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    t = x.shape[0]
    positions = jnp.arange(t)
    h = c.rms_norm(x, lp["input_norm"][l], _eps(conf))
    q = (h @ c.f32(ap["wq"][i])).reshape(t, nq, d)
    k = (h @ c.f32(ap["wk"][i])).reshape(t, nk, d)
    v = (h @ c.f32(ap["wv"][i])).reshape(t, nk, d)
    k, v = jnp.repeat(k, nq // nk, axis=1), jnp.repeat(v, nq // nk, axis=1)
    outs = []
    for t0 in range(0, t, Q_BLOCK):
        mask = positions[None, :] <= positions[t0:t0 + Q_BLOCK][:, None]
        s = jnp.einsum("qhd,khd->hqk", q[t0:t0 + Q_BLOCK], k) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, v).reshape(-1, nq * d))
    return x + jnp.concatenate(outs) @ c.f32(ap["wo"][i])


def _act(u, conf):
    return jax.nn.silu(u) if conf.get("probe_silu") else jnp.square(jax.nn.relu(u))


@functools.partial(jax.jit, static_argnames=("dims", "first", "held", "shared"))
def _experts(lp, i, x, dims, first: int, held: int | None = None, shared: bool = True):
    """``E`` block ``i``: the router over every published expert; the terms of
    the experts held here (ids ``first`` onward, as many as the leaves hold, or
    the first ``held`` of them); the shared expert unless ``shared`` is False
    (the share test counts it once)."""
    conf = c.thaw(dims)
    h = c.rms_norm(x, lp["post_norm"][i], _eps(conf))
    n_held = lp["we_down"].shape[1]
    logits = h @ c.f32(lp["router"][i])
    scores = jax.nn.sigmoid(logits)  # [T, all experts]
    choice = scores + c.f32(lp["router_bias"][i])
    if conf.get("probe_router_held"):  # wrong: only the held experts' logits compete
        choice = jnp.where((jnp.arange(scores.shape[1]) >= first) & (jnp.arange(scores.shape[1]) < first + n_held),
                           choice, -jnp.inf)
    _, picks = jax.lax.top_k(choice, conf["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if conf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * float(conf.get("routed_scaling_factor") or 1.0)
    combine = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], picks].add(w)

    def one(e, acc):
        y = _act(h @ c.f32(lp["we_up"][i, e]), conf) @ c.f32(lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    y = jax.lax.fori_loop(0, held or n_held, one, jnp.zeros_like(x))
    if shared:
        y = y + _act(h @ c.f32(lp["ws_up"][i]), conf) @ c.f32(lp["ws_down"][i])
    return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, x, tokens, eps):
    return c.logprob_report(c.rms_norm(x, final_norm, eps) @ c.f32(lm_head), tokens)


def bound_context(params: dict, tokens) -> list:
    """The context ``params["bound"]`` has for the prompt ``tokens`` starts
    with (empty without an entry)."""
    for prompt, entry in (params.get("bound") or {}).items():
        if tuple(int(t) for t in tokens[: len(prompt)]) == prompt:
            return [int(t) for t in entry["context"]]
    return []


def first_held(params: dict, conf: dict) -> int:
    """The first expert id held: the deployment's rank times the experts a
    rank holds; 0 where the router is no wider than the leaves."""
    held = params["layers"]["we_down"].shape[1]
    if params["layers"]["router"].shape[-1] <= held:
        return 0
    return int((conf.get("deployment") or {}).get("rank", 0)) * held


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob) at positions 0..T-2 of
    ``tokens``, computed behind the context ``params["bound"]`` has for the
    prompt, where it has one. ``trace``, a list, receives each block's input."""
    first = first_held(params, conf)
    context = bound_context(params, tokens)
    dims = c.freeze(conf, KEYS)
    lp = params["layers"]
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][full])
        mixers, seen = -1, {"M": 0, "*": 0, "E": 0}
        for kind in blocks_of(conf):
            if trace is not None:
                trace.append(x)
            i = jnp.int32(seen[kind])
            seen[kind] += 1
            mixers += kind != "E"
            if kind == "M":
                x = _mamba(lp, params["mamba_layers"], jnp.int32(mixers), i, x, dims)
            elif kind == "*":
                x = _attention(lp, params["attn_layers"], jnp.int32(mixers), i, x, dims)
            else:
                x = _experts(lp, i, x, dims, first, conf.get("experts_used"))
        return _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):], _eps(conf))
