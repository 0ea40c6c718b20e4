"""Plain reference: granite-4.0-h-small (HF ``ibm-granite/granite-4.0-h-small``,
``model_type: granitemoehybrid``) as ONE RANK of a 2-way expert-parallel
deployment sees it: Mamba-2 state-space mixers with one grouped-query
attention layer in ten, every layer's FFN a share of the routed experts plus a
shared GLU, and a slice of the tied vocabulary. Written from the equations of
ISSUE 37 / the published ``config.json`` (HF ``GraniteMoeHybrid``, whose mixer
is Bamba's Mamba-2), independent of ``llmd_tpu``. With x^ = RMSNorm(x), eps 1e-5:

  x_0 = embedding_multiplier * embed[ids]
  block l:  h = x + r * Mixer_l(x^);  y = h + r * (MoE_l(h^) + Shared_l(h^))
            (r = residual_multiplier);  logits = (x_L^ @ embed^T) / logits_scaling.
  Mamba-2:  [z | xBC | dt] = u W_in, widths d_in | d_in + 2N | heads (no bias);
            xBC = silu(conv_k(xBC) + b), a causal depthwise conv of ``mamba_d_conv``
            taps, here as shifted adds (zeros before position 0);
            [x | B | C] = xBC (one group: B and C shared by all heads);
            dt = softplus(dt + dt_bias), A = -exp(A_log), per head;
            H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T  (H is [d_head, d_state]
            a head; H_{-1} = 0), y_t = H_t C_t + D x_t: a ``lax.scan`` over the
            tokens, no chunking; out = (RMSNorm_{d_in}(y * silu(z)) * w) W_out
            (gate first, then ONE norm over all d_in).
  Attention: q = Wq x^ (32 x 128), k, v = Wk x^, Wv x^ (8 x 128), no bias, NO
            positional encoding, no QK-norm; causal
            softmax(q k^T * attention_multiplier) v  (1/128, not 128^-0.5); Wo.
  MoE:      l = h^ W_r over ALL published experts (72 logits); the
            ``num_experts_per_tok`` largest of l; weights = softmax over those;
            out = sum over the picks i THAT THIS RANK HOLDS of w_i E_i(h^),
            E_i = Wd(silu(Wg h^) * Wu h^); what the absent rank's experts would
            add is left out, here as in the program, and the partial sum goes
            on to the next layer. Shared: the same GLU at ``shared_intermediate_size``.
  logits:   over the held vocabulary slice only (the tied embedding's rows).

Which experts are held: as many as the expert leaves hold (``we_gate`` is
``[L, held, H, F]``), ids ``deployment.rank x held`` onward; the router's width
is the router leaf's. Which mixer a layer has: ``layer_types`` cut to the
depth; a mixer's weights lie in its KIND's stack (``mamba_layers`` /
``attn_layers``) at the layer's index among the layers of its kind.

DEPARTURES from the publication, each elementwise or an omission:
  * HF fuses an expert's gate and up into ``input_linear`` (gate half first)
    and the shared GLU likewise; here they are two leaves each, the same numbers.
  * HF's router takes the top-k of the logits and a softmax over them; that
    equals a softmax over all logits renormalised over the top-k, which is how
    it is written here (one code path with the other softmax routers).
  * ``assumed`` (config.json is silent): the width of one expert is
    ``intermediate_size`` (the catalog's note); pre-norm placement.
  * ``time_step_limit`` (0, inf) is no clamp and is left out.
  * ``reduced``: ``num_hidden_layers`` (the first period of ten),
    ``num_local_experts`` (held of 72), ``vocab_size`` (a slice).

One sequence, float32, ``highest`` matmul precision, one layer at a time, in
BLOCKS so that it fits beside the engine on a 16 GB chip: attention in blocks
of 256 queries, one expert at a time, the head over the compared positions only.

THE COMPARISON (``perfbench/correctness.py`` draws prompts of 64-256 tokens;
``perfbench/topologies/engine_state.py`` says how it is put to work).
``params["bound"]`` maps a prompt to the seeded CONTEXT the system served it
behind; ``forward`` prepends it and reports the positions of ``tokens`` only.

TOLERANCES: beside the constants below, each with the readings it lies
between (``perfbench/tolerance_probe_state.py`` made them on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "hidden_size", "rms_norm_eps",
    "num_experts_per_tok", "attention_multiplier", "residual_multiplier",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
    # Wrong on purpose, for perfbench/tolerance_probe_state.py's controls
    # (absent from every configuration): the SSM state rounded to this dtype
    # after every token; the conv's inputs forgotten at every multiple of
    # this many positions and at every position from ``probe_conv_from`` on
    # (a conv state zeroed between steps: at the prefill chunks' boundaries,
    # and a decode token is a step of its own).
    "probe_state_dtype", "probe_conv_reset", "probe_conv_from",
)
Q_BLOCK = 256

# |system - reference| log-prob of 128 compared tokens, and the reference's
# margin (perfbench/correctness.py). THESE WEIGHTS GIVE NEARLY FLAT LOGITS (a
# 0.02 embedding, tied, under logits_scaling 16: a logit's spread is ~0.08 over
# 50,176 ids), so every difference is small and the limits are this
# reference's own, set between readings on the chip (PERF.md section 6, PR 37;
# perfbench/tolerance_probe_state.py: 3 probe seeds + 2 whole runs sound, every
# control on the 3 seeds).
# Median: sound 0.0019-0.0029; the router cut to the 36 held logits
# 0.0115-0.0199, the conv state zeroed between steps 0.030-0.097, float8 weights
# 0.174-0.206, residual_multiplier 1 1.13-1.19.
LOGPROB_MEDIAN_ATOL = 0.007
# 90th percentile: sound 0.0049-0.0061; router cut 0.0275-0.0374, conv zeroed
# 0.094-0.194, float8 0.25-0.41.
LOGPROB_P90_ATOL = 0.016
# Max: sound 0.0078-0.0153; router cut 0.044-0.051, conv zeroed 0.19-0.26,
# float8 0.33-0.51.
LOGPROB_MAX_ATOL = 0.035
# Margin: the reference's best pick IS the emitted token on every sound run
# (0.0: no top-1 flip among logits this flat is larger than their rounding);
# residual_multiplier 1 reads 0.22-0.28.
MARGIN_ATOL = 0.1
# The FIRST mixer's SSM state, read out of the state pool, against
# ``first_mixer_state``: per head |Hs - Hr|_F / |Hr|_F, its median and its max
# over the 128 heads (``state_error``; perfbench/topologies/engine_state.py
# (iv)). What tells a bfloat16 state and a stale snapshot from a sound run,
# which the four log-prob numbers cannot (a bfloat16 state reads 0.0024-0.0030 /
# 0.0051-0.0063 / 0.0074-0.0126 there, a stale snapshot 0.0022-0.0028 /
# 0.0050-0.0063 / 0.0092-0.0133: the sound range). Readings on the chip
# (PERF.md section 6, PR 37, review round: 7 seeds x 5 states sound, every
# control on 3 of the seeds x 5 states).
# Median over the heads: sound 0.0044-0.0066; a snapshot one page stale
# 1.12-1.18 at the snapshot, float8 weights 0.72-1.00. (A bfloat16 state reads
# 0.0060-0.0080: its median does not tell it.)
STATE_HEAD_MEDIAN_RTOL = 0.03
# Max over the heads: sound 0.0072-0.0121; the state rounded to bfloat16 after
# every token 0.040-0.176 in every slot and snapshot (its long-memory heads
# gather 2^-9 a step), a snapshot one page stale 3.8-7.0 at the snapshot and
# 0.037-0.41 in the slots behind it, float8 weights 1.55-4.76.
STATE_HEAD_MAX_RTOL = 0.024
# NOT told from a sound run, by the log-probs or by the first mixer's state
# (which lies upstream of it): the attention scale 128^-0.5 (one layer in ten,
# whose random scores are near-uniform under either scale; it reads 0.0032-0.0038
# / 0.0071-0.0088 / 0.0130-0.0132). The CPU tests hold that path to 5e-5 in
# float32 (tests/test_state_space_hybrid.py); PERF.md section 7 (ii).


def _mixer_inputs(lp, mp, l, i, x, conf, frozen):
    """What mixer ``i`` (layer ``l``) feeds its recurrence from the residual
    stream ``x``: (z, x [t, heads, d_head], B, C, dt [t, heads], A [heads]).
    ``frozen`` [t] bool: positions that leave the state as it was (dt 0: decay
    1, no input), as padding behind the live tokens does, and the probe's
    snapshot taken a page early."""
    nh, p, n, k = (conf[f"mamba_{s}"] for s in ("n_heads", "d_head", "d_state", "d_conv"))
    d_in = nh * p
    t = x.shape[0]
    u = c.rms_norm(x, lp["input_norm"][l], conf["rms_norm_eps"])
    zxbcdt = u @ c.f32(mp["m_in"][i])
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * n], zxbcdt[:, 2 * d_in + 2 * n:]
    w = c.f32(mp["m_conv_w"][i])  # [k, C]: tap j multiplies the input k - 1 - j back
    reset = conf.get("probe_conv_reset")
    seen = jnp.arange(t) % reset if reset else jnp.arange(t)  # inputs behind a token the conv may read
    if reset:
        seen = jnp.where(jnp.arange(t) >= conf["probe_conv_from"], 0, seen)
    conv = sum(
        jnp.where((seen >= j)[:, None], jnp.concatenate([jnp.zeros((j, xbc.shape[1]), c.F32), xbc[: t - j]]), 0.0)
        * w[k - 1 - j]
        for j in range(k)
    )
    xbc = jax.nn.silu(conv + c.f32(mp["m_conv_b"][i]))
    xs = xbc[:, :d_in].reshape(t, nh, p)
    bs, cs = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    dt = jax.nn.softplus(dt + c.f32(mp["m_dt_bias"][i]))  # [t, nh]
    dt = jnp.where(frozen[:, None], 0.0, dt)
    return z, xs, bs, cs, dt, -jnp.exp(c.f32(mp["m_A_log"][i]))


def _recurrence(xs, bs, cs, dt, a, conf):
    """(H after the last token [heads, d_head, d_state], y [t, heads, d_head]):
    the recurrence token by token from H = 0."""
    nh, p, n = xs.shape[1], xs.shape[2], bs.shape[1]
    # (reduce_precision, not a cast there and back, which the compiler drops)
    mantissa = {"bfloat16": 7}.get(conf.get("probe_state_dtype"), 23)

    def step(h, tok):
        x_t, b_t, c_t, dt_t = tok
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=mantissa)
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    return jax.lax.scan(step, jnp.zeros((nh, p, n), c.F32), (xs, bs, cs, dt))


@functools.partial(jax.jit, static_argnames=("dims",))
def _mamba(lp, mp, l, i, x, dims, stale=(0, 0)):
    """``x + r * Mixer(RMSNorm(x))`` for mixer ``i`` of the mamba stack at
    layer ``l`` of the shared stack. ``stale`` (the probe's): positions [a, b)
    leave the state as it was, as a snapshot taken a page early does."""
    conf = c.thaw(dims)
    t = jnp.arange(x.shape[0])
    z, xs, bs, cs, dt, a = _mixer_inputs(lp, mp, l, i, x, conf, (t >= stale[0]) & (t < stale[1]))
    _, y = _recurrence(xs, bs, cs, dt, a, conf)
    y = (y + c.f32(mp["m_D"][i])[None, :, None] * xs).reshape(x.shape[0], -1)
    y = c.rms_norm(y * jax.nn.silu(z), mp["m_norm"][i], conf["rms_norm_eps"])
    return x + conf["residual_multiplier"] * (y @ c.f32(mp["m_out"][i]))


@functools.partial(jax.jit, static_argnames=("dims",))
def _first_state(lp, mp, x, live, stale, dims):
    conf = c.thaw(dims)
    t = jnp.arange(x.shape[0])
    frozen = (t >= live) | ((t >= stale[0]) & (t < stale[1]))
    _z, xs, bs, cs, dt, a = _mixer_inputs(lp, mp, jnp.int32(0), jnp.int32(0), x, conf, frozen)
    return _recurrence(xs, bs, cs, dt, a, conf)[0]


def first_mixer_state(params: dict, tokens, live: int, conf: dict, context_len: int = 0):
    """The SSM state ``[heads, d_head, d_state]`` of the FIRST layer's mixer
    after ``tokens[:live]`` (``tokens`` padded to whatever one shape the caller
    likes; the padding moves nothing). The first layer's, because nothing of
    the system's bfloat16 arithmetic lies upstream of it but its own
    projection and conv: what the topology holds a slot of the state pool to
    (``perfbench/topologies/engine_state.py``). ``context_len``: where the
    seeded context ends, for the probe's stale snapshot and conv reset."""
    if list(conf["layer_types"])[0] != "mamba":
        raise NotImplementedError("the first layer is no state-space mixer")
    if conf.get("probe_conv_reset"):
        conf = dict(conf, probe_conv_from=live)  # no decode token is told from a prefill one here
    stale_n = int(conf.get("probe_stale_tokens") or 0) if context_len else 0
    stale = jnp.asarray([context_len - stale_n, context_len], jnp.int32)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray([int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][ids]) * float(conf["embedding_multiplier"])
        return _first_state(params["layers"], params["mamba_layers"], x, jnp.int32(live), stale,
                            c.freeze(conf, KEYS))


def state_error(system_state, reference_state) -> dict:
    """How far a slot's SSM state lies from the reference's, per HEAD (heads
    differ in how long they remember, and so in what rounding does to them):
    ``|Hs - Hr|_F / |Hr|_F`` of each head, then the median and the max over
    the heads."""
    import numpy as np

    hs, hr = (np.asarray(a, np.float64) for a in (system_state, reference_state))
    num = np.sqrt(np.sum((hs - hr) ** 2, axis=(1, 2)))
    den = np.sqrt(np.sum(hr ** 2, axis=(1, 2)))
    rel = num / np.maximum(den, 1e-30)
    return {"head_median": float(np.median(rel)), "head_max": float(np.max(rel))}


@functools.partial(jax.jit, static_argnames=("dims",))
def _attention(lp, ap, l, i, x, dims):
    conf = c.thaw(dims)
    nq, nk = conf["num_attention_heads"], conf["num_key_value_heads"]
    d = conf["hidden_size"] // nq
    t = x.shape[0]
    positions = jnp.arange(t)
    h = c.rms_norm(x, lp["input_norm"][l], conf["rms_norm_eps"])
    q = (h @ c.f32(ap["wq"][i])).reshape(t, nq, d)
    k = (h @ c.f32(ap["wk"][i])).reshape(t, nk, d)
    v = (h @ c.f32(ap["wv"][i])).reshape(t, nk, d)
    k, v = jnp.repeat(k, nq // nk, axis=1), jnp.repeat(v, nq // nk, axis=1)
    outs = []
    for t0 in range(0, t, Q_BLOCK):
        mask = positions[None, :] <= positions[t0:t0 + Q_BLOCK][:, None]
        s = jnp.einsum("qhd,khd->hqk", q[t0:t0 + Q_BLOCK], k) * conf["attention_multiplier"]
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, v).reshape(-1, nq * d))
    return x + conf["residual_multiplier"] * (jnp.concatenate(outs) @ c.f32(ap["wo"][i]))


@functools.partial(jax.jit, static_argnames=("dims", "first", "held"))
def _sparse_ffn(lp, i, x, dims, first: int, held: int | None = None):
    """Router over every published expert; the terms of the experts held
    here (ids ``first`` onward, as many as the leaves hold, or the first
    ``held`` of them); the shared GLU, which every rank computes alike."""
    conf = c.thaw(dims)
    h = c.rms_norm(x, lp["post_norm"][i], conf["rms_norm_eps"])
    scores = jax.nn.softmax(h @ c.f32(lp["router"][i]), axis=-1)  # [T, all experts]
    w, picks = jax.lax.top_k(scores, conf["num_experts_per_tok"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], picks].add(w)
    held = held or lp["we_gate"].shape[1]

    def one(e, acc):
        y = c.swiglu(h, lp["we_gate"][i, e], lp["we_up"][i, e], lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    y = y + c.swiglu(h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])
    return x + conf["residual_multiplier"] * y


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(final_norm, embed, x, tokens, eps, scaling):
    logits = (c.rms_norm(x, final_norm, eps) @ c.f32(embed).T) / scaling
    return c.logprob_report(logits, tokens)


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
    held = params["layers"]["we_gate"].shape[1]
    if params["layers"]["router"].shape[-1] <= held:
        return 0
    return int((conf.get("deployment") or {}).get("rank", 0)) * held


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob) at positions 0..T-2 of
    ``tokens``, computed behind the context ``params["bound"]`` has for the
    prompt, where it has one. ``trace``, a list, receives each layer's input
    (the CPU tests)."""
    first = first_held(params, conf)
    context = bound_context(params, tokens)
    if conf.get("probe_conv_reset"):  # the probe counts from the prompt's start
        conf = dict(conf, probe_conv_from=len(context) + conf["probe_conv_from"])
    dims = c.freeze(conf, KEYS)
    lp, kinds = params["layers"], list(conf["layer_types"])[: conf["num_hidden_layers"]]
    # The probe's stale snapshot: the state misses the context's last tokens.
    stale = (len(context) - int(conf.get("probe_stale_tokens") or 0), len(context)) if context else (0, 0)
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][full]) * float(conf["embedding_multiplier"])
        seen = {"mamba": 0, "attention": 0}
        for l, kind in enumerate(kinds):
            if trace is not None:
                trace.append(x)
            i = jnp.int32(seen[kind])
            seen[kind] += 1
            if kind == "mamba":
                x = _mamba(lp, params["mamba_layers"], jnp.int32(l), i, x, dims, jnp.asarray(stale, jnp.int32))
            else:
                x = _attention(lp, params["attn_layers"], jnp.int32(l), i, x, dims)
            x = _sparse_ffn(lp, jnp.int32(l), x, dims, first, conf.get("experts_used"))
        # The head over the positions of ``tokens`` only.
        return _head(params["final_norm"], params["embed"], x[len(context):], full[len(context):],
                     conf["rms_norm_eps"], float(conf["logits_scaling"]))
