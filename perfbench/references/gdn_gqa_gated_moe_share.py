"""Plain reference: Qwen3-Next-80B-A3B-Instruct (HF ``Qwen/Qwen3-Next-80B-A3B-
Instruct``, ``model_type: qwen3_next``) as ONE RANK of an 8-way expert-parallel
deployment sees it: Gated DeltaNet layers with one gated full-attention layer
in four, every layer's FFN a share of the 512 routed experts plus a shared
expert under its own sigmoid gate, and a slice of the untied vocabulary.
Written from the equations of ISSUE 44 / the published ``config.json``,
independent of ``llmd_tpu``. With x^ = (1 + w) x / rms(x), eps 1e-6 (the
ZERO-CENTRED norm: the block's, the final and the q/k norms):

  x_0 = embed[ids];  layer l:  h = x + Mixer_l(x^);  y = h + MoE_l(h^);
  logits = x_L^ @ lm_head.  Layer l is full attention where
  (l + 1) % full_attention_interval == 0, else Gated DeltaNet.
  Gated DeltaNet (Hk key heads x Dk, Hv value heads x Dv, conv of K taps):
            [q | k | v | z] = x^ W_qkvz, widths Hk Dk | Hk Dk | Hv Dv | Hv Dv;
            [b | a] = x^ W_ba, Hv | Hv;  [q | k | v] = silu(conv_K([q | k | v])),
            a causal depthwise conv without bias, here as shifted adds (zeros
            before position 0);  beta = sigmoid(b);  g = -exp(A_log) *
            softplus(a + dt_bias) per value head;  q, k L2-normalised over the
            head (eps 1e-6), q times Dk^-0.5, key head j serving value heads
            j Hv/Hk onward.  Per value head, S in R^{Dk x Dv} from S = 0:
              S' = exp(g_t) S;  d = beta_t (v_t - S'^T k_t);  S = S' + k_t d^T;
              o_t = S^T q_t
            as a ``lax.scan`` over the tokens, one token a step: no chunking.
            out = (w_n * (o / rms(o)) * silu(z)) W_out, the norm a head (PLAIN
            weight, eps 1e-6).
  Gated attention (Nq q / Nk kv heads x D): [q | gate] a head = x^ Wq (2 D a
            head); k, v = x^ Wk, x^ Wv; zero-centred RMS norm on q and k a
            head; rotate-half RoPE over the first D x partial_rotary_factor
            dimensions (theta 1e7); causal softmax(q k^T D^-0.5) v;
            attn * sigmoid(gate); Wo. No bias.
  MoE:      softmax over ALL published experts (512), the top-k (10) of it,
            renormalised to sum 1;  out = sum over the picks THIS RANK HOLDS
            of w_i E_i(h^), E_i = Wd(silu(Wg h^) * Wu h^);  what the absent
            ranks' experts would add is left out, here as in the program, and
            the partial sum goes on.  Plus sigmoid(h^ . w_sg) * Shared(h^),
            the same GLU, which every rank computes alike.
  logits:   over the held vocabulary slice only.

Which experts are held: as many as the expert leaves hold (``we_gate`` is
``[L, held, H, F]``), ids ``deployment.rank x held`` onward. A mixer's weights
lie in its KIND's stack (``gdn_layers`` / ``attn_layers``) at the layer's index
among the layers of its kind.

DEPARTURES from the publication, each a re-ordering of the same numbers or an
omission:
  * HF's ``in_proj_qkvz`` / ``in_proj_ba`` interleave their outputs per KEY
    head; the leaves hold the same columns as blocks q | k | v | z and b | a
    (``llmd_tpu/models/gdn.py::from_published`` is the map; tests/ hold it).
  * HF computes the recurrence in chunks of 64 (``chunk_gated_delta_rule``);
    here it is the recurrence itself.
  * ``assumed`` (config.json is silent): rotate-half pairing within the
    rotated dimensions; the seeded A_log, dt_bias and norm weights (the
    configuration file's ``assumed.weights``).
  * ``omitted``: the MTP module.  ``reduced``: ``num_hidden_layers`` (three
    whole periods), ``num_experts`` (held of 512), ``vocab_size`` (a slice).

One sequence, float32, ``highest`` matmul precision, one layer at a time, in
BLOCKS so that it fits beside the engine on a 16 GB chip: attention in blocks
of 256 queries, one expert at a time, the head over the compared positions only.

THE COMPARISON (``perfbench/correctness.py`` draws prompts of 64-256 tokens;
``perfbench/topologies/engine_gdn.py`` says how it is put to work).
``params["bound"]`` maps a prompt to the seeded CONTEXT the system served it
behind; ``forward`` prepends it and reports the positions of ``tokens`` only.

TOLERANCES: beside the constants below, each with the readings it lies
between (``perfbench/tolerance_probe_gdn.py`` made them on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_size", "rms_norm_eps",
    "num_experts_per_tok", "norm_topk_prob", "partial_rotary_factor", "rope_theta", "rope_scaling",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim",
    # Wrong on purpose, for perfbench/tolerance_probe_gdn.py's controls (absent
    # from every configuration): the state rounded to this dtype after every
    # token; beta = b without its sigmoid; the decay applied AFTER the update;
    # the attention's output gate left out; every dimension of a head rotated;
    # the shared expert without its gate.
    "probe_state_dtype", "probe_beta_raw", "probe_decay_after", "probe_no_attn_gate",
    "probe_full_rotation", "probe_no_shared_gate",
)
Q_BLOCK = 256
L2_EPS = 1e-6

# |system - reference| log-prob of the compared tokens, and the reference's
# margin (perfbench/correctness.py: 128 tokens, "first16"; engine_gdn's longer
# decode, pooled over the prompts so far: 256 then 512 tokens, "decode"). Set
# between readings on the chip (PERF.md section 6, PR 44: 8 seeds sound = 3 of
# perfbench/tolerance_probe_gdn.py + 5 whole runs; every control on ONE of the
# probe's seeds). THE NOISE FLOOR IS HIGH HERE: a top-10-of-512 pick flips on
# bfloat16 rounding in some layer for most tokens, a flip across the held
# range's edge adds or drops an expert's term and renormalises the other nine,
# so a sound run's MEDIAN is 0.05-0.06 where a top-8-of-128 share reads 0.02.
# Median: sound 0.0412-0.0673 (mean 0.054, sd 0.007 over 15 seeds); the decay
# applied after the update 0.141-0.210, the attention gate left out 0.213-0.355,
# the shared expert ungated 2.18-2.42, float8 weights 3.29-3.37, beta without
# its sigmoid NaN. The limit leaves a sound seed +5 sd of room: one run not
# ``correct`` refuses a PR. NOT told by it, nor by the three below: a
# full-width rotation (0.064-0.086 / p90 0.17-0.22: three layers of twelve,
# near-uniform scores over seeded weights) and one held expert fewer
# (0.063-0.109 / 0.15-0.30: over this limit or the next on five seeds of eight,
# inside the sound seeds' range on two; PR 33 found the same of this control):
# the cached KEYS below tell the rotation on every seed and the expert on six
# of the seven they were read on.
LOGPROB_MEDIAN_ATOL = 0.09
# 90th percentile: sound 0.111-0.167; decay after the update 0.456-0.511, no
# attention gate 0.731-0.749, a snapshot one page stale 1.28-1.36.
LOGPROB_P90_ATOL = 0.22
# Max: sound 0.23-0.68 (ONE token whose flipped pick carried a large weight:
# heavy-tailed, held loosely as in every held-share cell); a stale snapshot
# 3.65, the shared expert ungated 4.87, float8 6.27.
LOGPROB_MAX_ATOL = 2.0
# Margin: sound 0.19-0.50; a stale snapshot 3.30, ungated 4.7-5.4, float8 6.5.
MARGIN_ATOL = 2.0
# The FIRST layer's delta-rule state, read out of the state pool, against
# ``first_mixer_state``: per head |Ss - Sr|_F / |Sr|_F, its median and its max
# over the 32 value heads (``state_error``). What tells a bfloat16 state, which
# the log-probs cannot (it reads 0.075-0.085 / 0.188-0.219 there), and a stale
# snapshot, a raw beta and a late decay a second time. 8 seeds x 5 states sound.
# Median over the heads: sound 0.0038-0.0040 (the bfloat16 projection and conv
# in front of the state: the same on every seed); the state rounded to bfloat16
# after every token 0.0098-0.0102, decay after the update 0.0186-0.0213, a
# snapshot one page stale 0.66 at the snapshot (0.007-0.064 in the slots
# behind it), float8 weights 0.64-0.66, beta raw 1.0.
STATE_HEAD_MEDIAN_RTOL = 0.006
# Max over the heads: sound 0.0042-0.0045; a bfloat16 state 0.0285-0.0301 (its
# long-memory heads gather 2^-9 a step), decay after the update 0.145-0.431, a
# stale snapshot 2.02 at the snapshot and 0.14-0.22 behind it, float8 0.73-0.76.
STATE_HEAD_MAX_RTOL = 0.011
# The FIRST attention layer's cached keys of a bound prompt (context, prompt
# and decoded tokens: ~4,400 positions), read out of the system's pages,
# against ``first_attention_keys``: per token |Ks - Kr|_F / |Kr|_F over its 2
# heads x 256 dimensions (``key_error``). What tells a wrong rotation, which
# the log-probs cannot (three attention layers of twelve whose scores over
# seeded weights are near-uniform: it reads 0.065-0.086 / 0.17-0.22 there), and
# what found that the flat KV write lost the rows of a chunk's sub-row that
# ended inside a page (one token in sixteen here read 1.0: PERF.md section 6,
# PR 44). Readings on the chip: 7 seeds x 4 prompts sound; every control on 2
# seeds, three of them on 5 more.
# Median over the tokens: sound 0.0186-0.0210 (mean 0.0199, sd 0.0007 over the
# seeds: three layers of bfloat16 and the pool's own rounding); one held
# expert fewer 0.0286-0.0365 on six seeds and 0.0224 on the seventh (what a
# token loses in layers 0-2 its successors inherit through the recurrent
# state); the state in bfloat16 0.0344-0.0391, the decay after the update
# 0.067-0.110, the shared expert ungated 0.71-0.76, a full-width rotation
# 1.00-1.01, float8 weights 1.14, beta raw 1.38. The attention's output gate
# lies behind these keys and moves nothing here.
KEY_TOKEN_MEDIAN_RTOL = 0.024
# The share of the tokens, in percent, that lie over KEY_TOKEN_FAR_RTOL: what
# SOME tokens gain or lose. Sound 0.00-0.12 % (a pick of large weight flipped
# across the held edge); one held expert fewer 0.80-1.98 % (five seeds), a
# snapshot one page stale 4-6 %, rows the write lost 6.4-6.6 %.
KEY_TOKEN_FAR_RTOL = 0.12
KEY_FAR_SHARE_MAX = 0.4


def zc_norm(x, w, eps):
    """The zero-centred RMS norm: (1 + w) x / rms(x)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + c.f32(w))


def layer_kinds(conf: dict) -> list:
    every = int(conf["full_attention_interval"])
    return ["full_attention" if (l + 1) % every == 0 else "linear_attention"
            for l in range(conf["num_hidden_layers"])]


def _gdn_inputs(lp, gp, l, i, x, conf, frozen):
    """What mixer ``i`` (layer ``l``) feeds its recurrence from the residual
    stream ``x``: (z [t, Hv, Dv], q, k [t, Hv, Dk], v [t, Hv, Dv], g, beta [t,
    Hv]). ``frozen`` [t] bool: positions that leave the state as it was (decay
    1, no delta), as padding behind the live tokens does, and the probe's
    snapshot taken a page early."""
    hk, hv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv, kc = conf["linear_key_head_dim"], conf["linear_value_head_dim"], conf["linear_conv_kernel_dim"]
    t, ch = x.shape[0], 2 * hk * dk + hv * dv
    u = zc_norm(x, lp["input_norm"][l], conf["rms_norm_eps"])
    qkvz, ba = u @ c.f32(gp["g_in"][i]), u @ c.f32(gp["g_ba"][i])
    qkv, z = qkvz[:, :ch], qkvz[:, ch:].reshape(t, hv, dv)
    w = c.f32(gp["g_conv_w"][i])  # [K, C]: tap j multiplies the input K - 1 - j back
    qkv = jax.nn.silu(sum(
        jnp.concatenate([jnp.zeros((j, ch), c.F32), qkv[: t - j]]) * w[kc - 1 - j] for j in range(kc)
    ))
    l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    q = l2(qkv[:, : hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = l2(qkv[:, hk * dk: 2 * hk * dk].reshape(t, hk, dk))
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = ba[:, :hv] if conf.get("probe_beta_raw") else jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(c.f32(gp["g_A_log"][i])) * jax.nn.softplus(ba[:, hv:] + c.f32(gp["g_dt_bias"][i]))
    live = ~frozen[:, None]
    return z, q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)


def _recurrence(q, k, v, g, beta, conf):
    """(S after the last token [Hv, Dk, Dv], o [t, Hv, Dv]): the delta rule
    token by token from S = 0."""
    hv, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    # (reduce_precision, not a cast there and back, which the compiler drops)
    mantissa = {"bfloat16": 7}.get(conf.get("probe_state_dtype"), 23)
    after = bool(conf.get("probe_decay_after"))

    def step(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        a = jnp.exp(g_t)[:, None, None]
        if not after:
            s = a * s
        d = b_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * d[:, None, :]
        if after:
            s = a * s
        s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=mantissa)
        return s, jnp.sum(s * q_t[:, :, None], axis=1)

    return jax.lax.scan(step, jnp.zeros((hv, dk, dv), c.F32), (q, k, v, g, beta))


@functools.partial(jax.jit, static_argnames=("dims",))
def _gdn(lp, gp, l, i, x, dims, stale=(0, 0)):
    """``x + Mixer(x^)`` for mixer ``i`` of the delta-rule stack at layer ``l``
    of the shared stack. ``stale`` (the probe's): positions [a, b) leave the
    state as it was, as a snapshot taken a page early does."""
    conf = c.thaw(dims)
    t = jnp.arange(x.shape[0])
    z, q, k, v, g, beta = _gdn_inputs(lp, gp, l, i, x, conf, (t >= stale[0]) & (t < stale[1]))
    _, o = _recurrence(q, k, v, g, beta, conf)
    o = c.rms_norm(o, gp["g_norm"][i], conf["rms_norm_eps"]) * jax.nn.silu(z)
    return x + o.reshape(x.shape[0], -1) @ c.f32(gp["g_out"][i])


@functools.partial(jax.jit, static_argnames=("dims",))
def _first_state(lp, gp, x, live, stale, dims):
    conf = c.thaw(dims)
    t = jnp.arange(x.shape[0])
    frozen = (t >= live) | ((t >= stale[0]) & (t < stale[1]))
    _z, q, k, v, g, beta = _gdn_inputs(lp, gp, jnp.int32(0), jnp.int32(0), x, conf, frozen)
    return _recurrence(q, k, v, g, beta, conf)[0]


def first_mixer_state(params: dict, tokens, live: int, conf: dict, context_len: int = 0):
    """The state ``[Hv, Dk, Dv]`` of the FIRST layer's mixer after
    ``tokens[:live]`` (``tokens`` padded to whatever one shape the caller
    likes; the padding moves nothing). The first layer's, because nothing of
    the system's bfloat16 arithmetic lies upstream of it but its own
    projection and conv. ``context_len``: where the seeded context ends, for
    the probe's stale snapshot."""
    if layer_kinds(conf)[0] != "linear_attention":
        raise NotImplementedError("the first layer is no delta-rule mixer")
    stale_n = int(conf.get("probe_stale_tokens") or 0) if context_len else 0
    stale = jnp.asarray([context_len - stale_n, context_len], jnp.int32)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray([int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][ids])
        return _first_state(params["layers"], params["gdn_layers"], x, jnp.int32(live), stale,
                            c.freeze(conf, KEYS))


def state_error(system_state, reference_state) -> dict:
    """How far a slot's state lies from the reference's, per HEAD (heads
    differ in how long they remember, and so in what rounding does to them):
    ``|Ss - Sr|_F / |Sr|_F`` of each head, then the median and the max over
    the heads."""
    import numpy as np

    hs, hr = (np.asarray(a, np.float64) for a in (system_state, reference_state))
    num = np.sqrt(np.sum((hs - hr) ** 2, axis=(1, 2)))
    den = np.sqrt(np.sum(hr ** 2, axis=(1, 2)))
    rel = num / np.maximum(den, 1e-30)
    return {"head_median": float(np.median(rel)), "head_max": float(np.max(rel))}


def partial_rope(x, positions, conf):
    """Rotate-half RoPE over the first ``head_dim x partial_rotary_factor``
    dimensions of [t, heads, D]; the rest pass."""
    d = x.shape[-1]
    rot = d if conf.get("probe_full_rotation") else int(d * float(conf.get("partial_rotary_factor") or 1.0))
    turned = c.rope(x[..., :rot], positions, float(conf["rope_theta"]), conf.get("rope_scaling"))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _qkv(lp, ap, l, i, x, conf):
    """(q [t, Nq, D], gate [t, Nq, D], k, v [t, Nk, D]) of attention layer
    ``i`` (layer ``l`` of the shared stack): q and k normed and rotated, k and
    v as the layer caches them."""
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    t, eps = x.shape[0], conf["rms_norm_eps"]
    positions = jnp.arange(t)
    h = zc_norm(x, lp["input_norm"][l], eps)
    qg = (h @ c.f32(ap["wq"][i])).reshape(t, nq, 2 * d)
    k = (h @ c.f32(ap["wk"][i])).reshape(t, nk, d)
    v = (h @ c.f32(ap["wv"][i])).reshape(t, nk, d)
    q = partial_rope(zc_norm(qg[..., :d], ap["attn_q_norm"][i], eps), positions, conf)
    k = partial_rope(zc_norm(k, ap["attn_k_norm"][i], eps), positions, conf)
    return q, qg[..., d:], k, v


@functools.partial(jax.jit, static_argnames=("dims",))
def _keys(lp, ap, l, i, x, dims):
    return _qkv(lp, ap, l, i, x, c.thaw(dims))[2]


@functools.partial(jax.jit, static_argnames=("dims",))
def _attention(lp, ap, l, i, x, dims):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    t = x.shape[0]
    positions = jnp.arange(t)
    q, gate, k, v = _qkv(lp, ap, l, i, x, conf)
    k, v = jnp.repeat(k, nq // nk, axis=1), jnp.repeat(v, nq // nk, axis=1)
    outs = []
    for t0 in range(0, t, Q_BLOCK):
        mask = positions[None, :] <= positions[t0:t0 + Q_BLOCK][:, None]
        s = jnp.einsum("qhd,khd->hqk", q[t0:t0 + Q_BLOCK], k) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, v))
    attn = jnp.concatenate(outs)
    if not conf.get("probe_no_attn_gate"):
        attn = attn * jax.nn.sigmoid(gate)
    return x + attn.reshape(t, nq * d) @ c.f32(ap["wo"][i])


@functools.partial(jax.jit, static_argnames=("dims", "first", "held"))
def _sparse_ffn(lp, i, x, dims, first: int, held: int | None = None):
    """Router over every published expert; the terms of the experts held
    here (ids ``first`` onward, as many as the leaves hold, or the first
    ``held`` of them); the gated shared expert, which every rank computes
    alike."""
    conf = c.thaw(dims)
    h = zc_norm(x, lp["post_norm"][i], conf["rms_norm_eps"])
    scores = jax.nn.softmax(h @ c.f32(lp["router"][i]), axis=-1)  # [T, all experts]
    w, picks = jax.lax.top_k(scores, conf["num_experts_per_tok"])
    if conf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    combine = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], picks].add(w)
    held = held or lp["we_gate"].shape[1]

    def one(e, acc):
        y = c.swiglu(h, lp["we_gate"][i, e], lp["we_up"][i, e], lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    shared = c.swiglu(h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])
    if not conf.get("probe_no_shared_gate"):
        shared = jax.nn.sigmoid(h @ c.f32(lp["ws_sig"][i])) * shared
    return x + y + shared


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, x, tokens, eps):
    return c.logprob_report(zc_norm(x, final_norm, eps) @ c.f32(lm_head), tokens)


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


def _stream(params: dict, full, conf: dict, stale, upto: int | None = None, trace: list | None = None):
    """The residual stream of the token ids ``full`` in front of layer
    ``upto`` (behind the last layer without one). ``stale``: the positions
    [a, b) that the probe's stale snapshot leaves out of the recurrent state.
    ``trace``, a list, receives each layer's input."""
    first = first_held(params, conf)
    dims = c.freeze(conf, KEYS)
    lp = params["layers"]
    x = c.f32(params["embed"][full])
    seen = {"linear_attention": 0, "full_attention": 0}
    for l, kind in enumerate(layer_kinds(conf)[:upto]):
        if trace is not None:
            trace.append(x)
        i = jnp.int32(seen[kind])
        seen[kind] += 1
        if kind == "linear_attention":
            x = _gdn(lp, params["gdn_layers"], jnp.int32(l), i, x, dims, jnp.asarray(stale, jnp.int32))
        else:
            x = _attention(lp, params["attn_layers"], jnp.int32(l), i, x, dims)
        x = _sparse_ffn(lp, jnp.int32(l), x, dims, first, conf.get("experts_used"))
    return x


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob) at positions 0..T-2 of
    ``tokens``, computed behind the context ``params["bound"]`` has for the
    prompt, where it has one. ``trace``, a list, receives each layer's input
    (the CPU tests)."""
    context = bound_context(params, tokens)
    # The probe's stale snapshot: the state misses the context's last tokens.
    stale = (len(context) - int(conf.get("probe_stale_tokens") or 0), len(context)) if context else (0, 0)
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = _stream(params, full, conf, stale, trace=trace)
        # The head over the positions of ``tokens`` only.
        return _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):],
                     conf["rms_norm_eps"])


def first_attention_keys(params: dict, tokens, conf: dict, context_len: int = 0):
    """The keys ``[t, Nk, D]`` that the FIRST attention layer caches for
    ``tokens`` (the whole sequence, a context in front included; padded to
    whatever one shape the caller likes: causal, so padding is inert), normed
    and rotated. What of the attention layers can be read out of the system
    token by token: its pages hold them. Behind the keys lie the first period's
    delta-rule layers and their experts, the zero-centred norm and the
    rotation's width and pairing. ``context_len`` as ``first_mixer_state``'s."""
    at = layer_kinds(conf).index("full_attention")
    stale_n = int(conf.get("probe_stale_tokens") or 0) if context_len else 0
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray([int(t) for t in tokens], jnp.int32)
        x = _stream(params, full, conf, (context_len - stale_n, context_len), upto=at)
        return _keys(params["layers"], params["attn_layers"], jnp.int32(at), jnp.int32(0), x, c.freeze(conf, KEYS))


def key_error(system_keys, reference_keys) -> dict:
    """How far the cached keys lie from the reference's, per TOKEN:
    ``|Ks - Kr|_F / |Kr|_F`` over a token's heads and dimensions, then the
    median over the tokens (what a wrong rotation or norm moves for every
    token) and the share of the tokens, in percent, that lie over
    ``KEY_TOKEN_FAR_RTOL`` (a term that some tokens gain or lose: a held
    expert, a flipped pick)."""
    import numpy as np

    ks, kr = (np.asarray(a, np.float64) for a in (system_keys, reference_keys))
    rel = np.sqrt(np.sum((ks - kr) ** 2, axis=(1, 2)) / np.maximum(np.sum(kr ** 2, axis=(1, 2)), 1e-30))
    return {"token_median": float(np.median(rel)), "token_p99": float(np.quantile(rel, 0.99)),
            "far_share": float(100.0 * np.mean(rel > KEY_TOKEN_FAR_RTOL))}
