"""Plain reference: K-EXAONE-236B-A23B (HF ``LGAI-EXAONE/K-EXAONE-236B-A23B``,
``model_type: exaone_moe``) as ONE RANK of an expert-parallel deployment sees
it: window and full grouped-query attention, a dense first layer, then sparse
layers of which this rank holds a share of the routed experts, and a slice of
the vocabulary. Written from the equations of ISSUE 33 / the published
``config.json``, independent of ``llmd_tpu``. With x^ = RMSNorm(x), eps 1e-5:

  block l:  h = x + Attn_l(x^);  y = h + FFN_l(h^);  final RMSNorm; untied head.
  Attn_l:   q = Wq x^ (64 x 128), k, v = Wk x^, Wv x^ (8 x 128), no bias;
            RMSNorm over each head's 128 of q and of k; RoPE (theta 1e6) where
            layer_types[l] is sliding_attention, none where it is
            full_attention; causal softmax(q k^T / sqrt(128)) v over the
            positions > i - sliding_window on sliding layers (a mask over the
            full causal mask: no ring, no cache), over all on full layers; Wo.
  FFN_0:    Wd(silu(Wg x) * Wu x)   (``first_k_dense_replace`` 1).
  FFN_l>=1: s = sigmoid(x Wr) over ALL published experts (128 logits);
            picks = the ``num_experts_per_tok`` largest of s + b (``n_group`` 1:
            no group limit); w_i = ``routed_scaling_factor`` * s_i / sum_picks s;
            out = sum over the picks i THAT THIS RANK HOLDS of w_i E_i(x) + S(x):
            what the absent ranks' experts would add is left out, here as in
            the program, and the partial sum is what goes on to the next layer.
  logits:   over the held vocabulary slice only (ids 0..vocab_size-1 of the
            file; a sliced vocabulary is a smaller vocabulary).

Which experts are held: as many as the expert leaves hold (``we_gate`` is
``[L, held, H, F]``), ids ``deployment.rank x held`` onward; the router's width
is the router leaf's. So a tree with one expert fewer, or with the router cut
to the held columns, is a different model and reads so (the probes below).

DEPARTURES from the publication, each elementwise or an omission:
  * ``assumed`` (config.json is silent): pre-norm placement; QK-norm; RoPE on
    the sliding layers only (the EXAONE-4.0 family's hybrid-attention
    convention; ``rope_layer_types`` in ``conf`` overrides, for the probe);
    the selection-only bias ``b`` (the ``noaux_tc`` convention whose key names
    the config uses), seeded like the weights.
  * ``omitted``: the one multi-token-prediction module
    (``num_nextn_predict_layers`` 1): it drafts and does not change what the
    model emits.
  * ``reduced``: ``num_hidden_layers`` (the first layers of the 48),
    ``num_experts`` (held of 128), ``vocab_size`` (a slice).

One sequence, float32, ``highest`` matmul precision, one layer at a time, in
BLOCKS so that it fits beside the engine on a 16 GB chip: attention in blocks
of 256 queries, the dense FFN in four slices of its width, one expert at a
time, the head over the compared positions only.

THE COMPARISON (``perfbench/correctness.py`` draws prompts of 64-256 tokens;
``perfbench/topologies/engine_hybrid.py`` says how it is put to work).
``params["bound"]`` maps a prompt to the seeded CONTEXT the system served it
behind; ``forward`` prepends it and reports the positions of ``tokens`` only.

TOLERANCES: beside the constants below, each with the readings it lies
between (``perfbench/tolerance_probe_share.py`` made them on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "sliding_window",
)
ROPE_LAYER_TYPES = ("sliding_attention",)  # assumed: the EXAONE-4.0 convention
Q_BLOCK = 256
DENSE_SLICES = 4

# |system - reference| log-prob of 128 compared tokens, and the reference's
# margin (perfbench/correctness.py). Readings on the chip (PERF.md section 6,
# PR 33): sound over the probe's seeds and every whole run, every control on 3
# seeds, one expert fewer and RoPE on the full layers on every probe seed.
# Median: sound 0.0132-0.0188; RoPE on the full layers 0.071-0.095, the router
# cut to 16 logits 1.37-1.54, the window ignored 1.94-2.49, float8 weights
# 3.63-3.74. One held expert fewer reads 0.017-0.067: over this limit on 10
# seeds of 23 (below).
LOGPROB_MEDIAN_ATOL = 0.035
# 90th percentile: the harness's accepted limit (references/gqa_moe.py). A
# sound run has two kinds of token: nine in ten differ by ~0.015, and one in
# ten by 0.1-0.3, where a top-8-of-128 pick flipped across the EDGE of the
# held range between bfloat16 and float32 (the token gains or loses a whole
# expert term). The 90th percentile sits on the border of the two kinds: 35
# sound readings, 33 of 0.037-0.078, then 0.110 and 0.112 (a whole run, seed
# 3,677,777,789, refused under a first limit of 0.11). Every control but two
# reads over 2.2. RoPE on the full layers (0.185-0.241) fails by the median.
# ONE HELD EXPERT FEWER (0.075-0.334 over 23 seeds, median 0.017-0.067) is
# the same kind of difference as the flips, on 36 % of the tokens where a
# sound run has 10 %: the four numbers of correctness.py tell it from a sound
# run by the median on 10 seeds of 23 and by no number on the rest (PERF.md
# section 7 (ii)).
LOGPROB_P90_ATOL = 0.35
# Max and margin: a flipped top-8-of-128 pick is heavy-tailed (sound 0.13-0.67
# and 0.10-0.85, the other cells' limits); the window ignored, the router cut
# and float8 weights read 3.5-6.8 and 3.1-7.2.
LOGPROB_MAX_ATOL = 2.0
MARGIN_ATOL = 2.0


def rope_theta(conf: dict) -> float:
    return float((conf.get("rope_parameters") or {}).get("rope_theta") or conf.get("rope_theta"))


@functools.partial(jax.jit, static_argnames=("dims", "window", "rotate"))
def _attention(lp, i, x, dims, window: int, rotate: bool, theta: float):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps = conf["rms_norm_eps"]
    t = x.shape[0]
    positions = jnp.arange(t)
    h = c.rms_norm(x, lp["input_norm"][i], eps)
    q = (h @ c.f32(lp["wq"][i])).reshape(t, nq, d)
    k = (h @ c.f32(lp["wk"][i])).reshape(t, nk, d)
    v = (h @ c.f32(lp["wv"][i])).reshape(t, nk, d)
    q = c.rms_norm(q, lp["attn_q_norm"][i], eps)
    k = c.rms_norm(k, lp["attn_k_norm"][i], eps)
    if rotate:
        q, k = c.rope(q, positions, theta, None), c.rope(k, positions, theta, None)
    rep = nq // nk
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    outs = []
    for t0 in range(0, t, Q_BLOCK):
        rows = positions[t0:t0 + Q_BLOCK]
        mask = positions[None, :] <= rows[:, None]
        if window:
            mask &= positions[None, :] > rows[:, None] - window
        s = jnp.einsum("qhd,khd->hqk", q[t0:t0 + Q_BLOCK], k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v).reshape(-1, nq * d))
    return x + jnp.concatenate(outs) @ c.f32(lp["wo"][i])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(lp, i, x, eps):
    h = c.rms_norm(x, lp["post_norm"][i], eps)
    width = lp["w_gate"].shape[-1]
    step = -(-width // DENSE_SLICES)
    y = jnp.zeros_like(x)
    for f0 in range(0, width, step):
        sl = slice(f0, min(f0 + step, width))
        y = y + c.swiglu(h, lp["w_gate"][i][:, sl], lp["w_up"][i][:, sl], lp["w_down"][i][sl, :])
    return x + y


@functools.partial(jax.jit, static_argnames=("dims", "first", "held"))
def _sparse_ffn(lp, i, x, dims, first: int, held: int | None = None):
    """Router over every published expert; the terms of the experts held
    here (ids ``first`` onward, as many as the leaves hold, or the first
    ``held`` of them: the probe's rank with one expert fewer); the shared
    expert, which every rank computes alike."""
    conf = c.thaw(dims)
    h = c.rms_norm(x, lp["post_norm"][i], conf["rms_norm_eps"])
    scores = jax.nn.sigmoid(h @ c.f32(lp["router"][i]))  # [T, all experts]
    _, picks = jax.lax.top_k(scores + c.f32(lp["router_bias"][i]), conf["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if conf.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(conf.get("routed_scaling_factor") or 1.0)
    combine = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], picks].add(w)
    held = held or lp["we_gate"].shape[1]

    def one(e, acc):
        y = c.swiglu(h, lp["we_gate"][i, e], lp["we_up"][i, e], lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    return x + y + c.swiglu(h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, x, tokens, eps):
    logits = c.rms_norm(x, final_norm, eps) @ c.f32(lm_head)
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


def layer_kinds(conf: dict) -> list:
    """Per layer of the cut depth: (window, rotates)."""
    types = list(conf["layer_types"])[: conf["num_hidden_layers"]]
    rot = tuple(conf.get("rope_layer_types") or ROPE_LAYER_TYPES)
    window = int(conf.get("sliding_window") or 0)
    return [(window if t == "sliding_attention" else 0, t in rot) for t in types]


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob) at positions 0..T-2 of
    ``tokens``, computed behind the context ``params["bound"]`` has for the
    prompt, where it has one. ``trace``, a list, receives each layer's input
    (the CPU tests)."""
    dims = c.freeze(conf, KEYS)
    n_dense = int(conf.get("first_k_dense_replace") or 0)
    first = first_held(params, conf)
    context = bound_context(params, tokens)
    theta, eps = rope_theta(conf), conf["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][full])
        for l, (window, rotate) in enumerate(layer_kinds(conf)):
            if trace is not None:
                trace.append(x)
            group, i = ("dense_layers", l) if l < n_dense else ("layers", l - n_dense)
            lp = params[group]
            x = _attention(lp, jnp.int32(i), x, dims, window, rotate, theta)
            if l < n_dense:
                x = _dense_ffn(lp, jnp.int32(i), x, eps)
            else:
                x = _sparse_ffn(lp, jnp.int32(i), x, dims, first, conf.get("experts_used"))
        # The head over the positions of ``tokens`` only.
        return _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):], eps)
