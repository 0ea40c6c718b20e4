"""Plain reference: grouped-query attention over the tokens a learned indexer
selects (DeepSeek-Sparse-Attention style, ``sa_config``) + sparse experts in
every layer: the language model of Keye-VL-2.0-30B-A3B, text only. Written
from the equations of ISSUE 28 / docs/architecture/sparse-attention.md,
independent of ``llmd_tpu``. With x^ = RMSNorm(x):

  main attention (as Qwen3-MoE): q = RoPE(RMSNorm_h(Wq x^)), k likewise, v = Wv x^;
  indexer: qI[t, j] = RoPE(WIq x^_t)_j (J heads of Di), kI[s] = RoPE(LayerNorm(WIk x^_s))
    (ONE key per token), w[t] = WIw x^_t;  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), s <= t;
  S_t = the ``topk`` values of s <= t with the largest I[t, s] (all while t + 1 <= topk; ties: lower s);
  o[t, i] = softmax over s in S_t of (q[t, i] . k[s, g(i)] / sqrt(D)) v[s, g(i)];  x += Wo o; then the
  top-k-of-E expert block of ``gqa_moe`` unchanged.

Rope: ``rope_scaling.mrope_section`` gives each rotary frequency one of three
position rows (time, height, width). ``rope3`` implements the three rows; text
has them equal, which is the ordinary rope (tests/test_sparse_attention.py
shows the identity). The indexer's Di/2 frequencies take the sections in the
same proportion (an assumption; it changes nothing for text).

One sequence, float32, ``highest`` matmul precision, one layer at a time;
scores and attention in blocks of 512 queries so that 4,400 tokens fit beside
the engine.

THE COMPARISON (``perfbench/correctness.py`` draws prompts of 64-256 tokens,
where top-2,048 selects everything; ``perfbench/topologies/engine_longctx.py``
says how it is put to work here). ``params["bound"]`` maps a prompt to a
seeded CONTEXT of 2 x topk tokens that the system served it behind, to the
SYSTEM'S SELECTION (a function) and to the indexer keys the system CACHED
for the sequence. ``forward`` prepends the context and reports the positions
of ``tokens`` only. Every second prompt has an entry, so of the 128 compared
tokens 64 are decoded over 4,160-4,368 cached tokens, of which each may read
2,048, and 64 over 64-272, where nothing is left out. What decides
``correct``:

  * the MEDIAN of |system - reference| rests on the 64 UNBOUND tokens (0.014-
    0.029 alone): what a lower precision or a dropped term moves for every
    token. Behind the context the same median is 0.067-0.114 without any
    fault (a hard threshold at the 2,048th of ~4,300 index scores turns the
    bfloat16 drift of a hidden state into other attended tokens: 3 % of a
    set by the fourth layer, "own" below; on seeded weights attention is
    near-uniform, so they move the output): 128 bound tokens could not tell
    one expert fewer (0.129-0.185) from a sound run (0.078-0.118);
  * the 90TH PERCENTILE rests on the 64 BOUND tokens: what reads the wrong
    tokens, or all of them, is off by several times the noise there;
  * the SELECTED SETS themselves, two numbers of their own per layer. The
    reference's index queries and head weights go to the system's selection
    (the program's own scoring and top-k over the keys the serving path
    cached for this sequence), and the sets that come back are held, every
    bound row (position >= topk) of context, prompt and answer, mean over
    rows of |both| / max(|theirs|, |mine|), against
      "exact": an exact float32 top-k over the SAME cached keys and the same
        queries in the served dtype, so that only the program's arithmetic
        differs: 1.0000 on every layer of every sound run, and what holds
        the selection to being EXACT (log-probs cannot: 0.9 % other tokens
        read as a sound run);
      "own": the sets from this file's own float32 keys, so that what the
        serving path CACHED differs too: 0.999 in the first layer, 0.967-
        0.972 in the fourth (the drift above).
    Under ``SELECTED_EXACT_MIN`` / ``SELECTED_OVERLAP_MIN`` in any layer the
    log-probs of that prompt are returned as NaN, which ``correctness.py``
    (not this PR's to edit) reads as not correct; both go to stderr.

LIMITS, each between two readings (``perfbench/tolerance_probe_dsa.py`` on the
chip, published widths, 6 layers; the sound comparison and every control run
THROUGH ``correctness.reference_check``; my chip runs, PR 28, calls 10-11):
sound, 9 probe seeds and 3 whole runs, all ``ok``: median 0.036-0.053, 90th
percentile 0.145-0.257, max 0.39-0.66, margin max 0.34-0.70, exact 1.0000,
own 0.9672-0.9718. Controls, 5 seeds each (the float8 ones 3, rounded with
``lax.reduce_precision``), every one ``ok: false`` on every seed:
  one expert fewer of eight    median 0.107-0.129; own 0.911-0.914
  selection ignored            90th percentile 0.588-0.773; exact 0.825-0.835
  index keys permuted          90th percentile 0.636-1.007; own 0.624-0.668
  top-k approximate (4 x k/4)  exact 0.9914-0.9916; log-probs as the sound run
  index scores from float8     exact 0.9852-0.9880; log-probs as the sound run
  weights in float8            median 2.03-2.93; own 0.676-0.707
  LOGPROB_MEDIAN_ATOL 0.075: 1.41x the largest sound median, 0.70x the
    smallest with one expert fewer, 0.04x the smallest with float8 weights.
  LOGPROB_P90_ATOL 0.40: 1.56x the largest sound reading, 0.68x the smallest
    of a wrong or ignored selection.
  LOGPROB_MAX_ATOL 2.0: 3.0x the largest sound reading (0.66; the worst
    routing flip is heavy-tailed: 0.93 in 37 earlier comparisons of this
    model), 0.43x the smallest with float8 weights (4.69); a wrong or
    ignored selection reads 1.04-1.60 and is the 90th percentile's to catch.
  MARGIN_ATOL 2.0: as ``gqa_moe`` (sound 0.34-0.70; a wrong mask, position
    or layer reads several units).
  SELECTED_EXACT_MIN 0.999: sound reads 1.0000; the approximate top-k 0.9916
    at most.
  SELECTED_OVERLAP_MIN 0.94: sound 0.9672 at least; one expert fewer 0.914
    at most.
The unbound prompts are served one at a time: a prompt whose prefill is split
over steps that carry other requests reads 0.06-0.33 instead of 0.01-0.05 on
the chip, in Qwen3's configuration as in this one (PERF.md section 6).
"""

from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import _common as c

KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "rope_scaling", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "sa_config",
)
Q_BLOCK = 512
LOGPROB_MEDIAN_ATOL = 0.075
LOGPROB_P90_ATOL = 0.40
LOGPROB_MAX_ATOL = 2.0
MARGIN_ATOL = 2.0
SELECTED_EXACT_MIN = 0.999  # the program's sets against an exact top-k over the SAME cached keys
SELECTED_OVERLAP_MIN = 0.94  # ... against the sets from this file's own keys


def sections_for(half: int, scaling: dict | None) -> tuple:
    """How many of ``half`` rotary frequencies read each position row."""
    sec = (scaling or {}).get("mrope_section")
    if not sec:
        return (half,)
    out = [s * half // sum(sec) for s in sec]
    out[0] += half - sum(out)
    return tuple(out)


def rope3(x, positions3, theta, scaling):
    """Rotate [T, heads, dim] (HF split-half layout) where frequency f reads
    row ``r(f)`` of ``positions3`` [rows, T], rows in blocks of
    ``mrope_section``. Equal rows give ``_common.rope``."""
    dim = x.shape[-1]
    inv, att = c.rope_inv_freq(dim, theta, {"rope_type": "default"})
    secs = sections_for(dim // 2, scaling)
    row_of = np.repeat(np.arange(len(secs)), secs)
    pos = positions3.astype(c.F32)[row_of, :].T  # [T, half]: each frequency's position
    ang = pos * inv
    cos, sin = (jnp.cos(ang) * att)[:, None, :], (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * c.f32(w) + c.f32(b)


def selected(scores, t0: int, topk: int):
    """[q, T] bool: for query row i (token t0 + i) the ``topk`` keys s <= t0 + i
    of largest score, all of them while there are no more than ``topk``
    (``lax.top_k`` puts the lower index first among equals)."""
    q, t = scores.shape
    causal = jnp.arange(t)[None, :] <= (t0 + jnp.arange(q))[:, None]
    if t <= topk:
        return causal
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    return jnp.zeros((q, t), bool).at[jnp.arange(q)[:, None], idx].set(True) & causal


@functools.partial(jax.jit, static_argnames=("topk",))
def plain_selection(qi, wi, keys, topk: int):
    """[n, n] bool: the exact float32 top-k of the index scores of queries
    ``qi`` [n, J, Di] and head weights ``wi`` [n, J] over ``keys`` [n, Di],
    in blocks of queries (what ``_layer`` does with its own keys)."""
    n = keys.shape[0]
    out = []
    for t0 in range(0, n, Q_BLOCK):
        sl = slice(t0, min(t0 + Q_BLOCK, n))
        index = jnp.einsum("qj,qjs->qs", wi[sl], jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi[sl], keys)))
        out.append(selected(index, t0, topk))
    return jnp.concatenate(out)


def indexer(h, lp, i, positions3, conf):
    """(query heads [T, J, Di], keys [T, Di], head weights [T, J]) of layer
    ``i``'s indexer from the layer's normed input ``h``."""
    sa, eps, theta, scaling = conf["sa_config"], conf["rms_norm_eps"], conf["rope_theta"], conf["rope_scaling"]
    nj, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    qi = rope3((h @ c.f32(lp["wi_q"][i])).reshape(-1, nj, di), positions3, theta, scaling)
    ki = layer_norm(h @ c.f32(lp["wi_k"][i]), lp["wi_k_norm"][i], lp["wi_k_norm_b"][i], eps)
    ki = rope3(ki[:, None, :], positions3, theta, scaling)[:, 0]
    return qi, ki, h @ c.f32(lp["wi_w"][i])


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(lp, i, x, positions3, dims):
    conf = c.thaw(dims)
    nq, nk, d = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps, theta, scaling = conf["rms_norm_eps"], conf["rope_theta"], conf["rope_scaling"]
    topk = conf["sa_config"]["topk"]
    t = x.shape[0]
    h = c.rms_norm(x, lp["input_norm"][i], eps)
    q = c.rms_norm((h @ c.f32(lp["wq"][i])).reshape(t, nq, d), lp["attn_q_norm"][i], eps)
    k = c.rms_norm((h @ c.f32(lp["wk"][i])).reshape(t, nk, d), lp["attn_k_norm"][i], eps)
    v = (h @ c.f32(lp["wv"][i])).reshape(t, nk, d)
    q, k = rope3(q, positions3, theta, scaling), rope3(k, positions3, theta, scaling)
    qi, ki, wi = indexer(h, lp, i, positions3, conf)
    rep = nq // nk
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    outs, masks = [], []
    for t0 in range(0, t, Q_BLOCK):
        sl = slice(t0, min(t0 + Q_BLOCK, t))
        index = jnp.einsum("qj,qjs->qs", wi[sl], jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi[sl], ki)))
        mask = selected(index, t0, topk)
        s = jnp.einsum("qhd,khd->hqk", q[sl], k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v).reshape(-1, nq * d))
        masks.append(mask)
    x = x + jnp.concatenate(outs) @ c.f32(lp["wo"][i])
    h = c.rms_norm(x, lp["post_norm"][i], eps)
    return x + c.routed_experts(h, lp, i, conf), jnp.concatenate(masks), qi, wi


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, x, tokens, eps):
    logits = c.rms_norm(x, final_norm, eps) @ c.f32(lm_head)
    return c.logprob_report(logits, tokens)


@jax.jit
def overlap(theirs, mine, lo, n):
    """Of rows ``lo`` <= t < ``n`` of two [R, R] selections, each held to
    the keys s <= t: the mean of |both| / max(|theirs|, |mine|)."""
    t = jnp.arange(theirs.shape[0])
    causal = t[None, :] <= t[:, None]
    a, b = theirs & causal, mine & causal
    share = jnp.sum(a & b, axis=1) / jnp.maximum(jnp.maximum(jnp.sum(a, axis=1), jnp.sum(b, axis=1)), 1)
    rows = (t >= lo) & (t < n)
    return jnp.sum(jnp.where(rows, share, 0.0)) / jnp.sum(rows)


def bound_entry(params: dict, tokens):
    """The entry of ``params["bound"]`` whose prompt ``tokens`` starts with."""
    for prompt, entry in (params.get("bound") or {}).items():
        if tuple(int(t) for t in tokens[: len(prompt)]) == prompt:
            return entry
    return None


def score(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob, overlaps) at positions
    0..T-2 of ``tokens``, computed behind the context ``params["bound"]`` has
    for the prompt, where it has one; ``overlaps``: per layer (exact, own):
    how far the system's selected sets (same entry) agree with an exact top-k
    over the keys the system cached, and with the sets of this file's own
    keys (empty without an entry). ``trace``, a list, receives per layer the
    layer's input and the selected sets ([T, T] bool) of the whole sequence
    (the CPU tests)."""
    scaling = {k: tuple(v) if isinstance(v, list) else v for k, v in (conf.get("rope_scaling") or {}).items()}
    dims = c.freeze(dict(conf, rope_scaling=scaling), KEYS)
    entry = bound_entry(params, tokens) or {}
    context = [int(t) for t in entry.get("context", ())]
    theirs, topk, overlaps = entry.get("selection"), conf["sa_config"]["topk"], []
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        positions3 = jnp.tile(jnp.arange(full.shape[0])[None, :], (3, 1))  # text: three equal rows
        x = c.f32(params["embed"][full])
        for i in range(conf["num_hidden_layers"]):
            x_in = x
            x, mask, qi, wi = _layer(params["layers"], jnp.int32(i), x, positions3, dims)
            if trace is not None:
                trace.append({"input": x_in, "selected": mask})
            if theirs is not None:
                got, n = theirs(i, qi, wi)  # [R, R] bool, of which n rows and columns are cached tokens
                keys, _ = entry["cached"]  # [L, pages, page, Di], the served dtype
                # One shape for every prompt: R rows (pad rows select what they like; none is read).
                fit = lambda a: jnp.pad(a[:n], ((0, got.shape[0] - n),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
                as_served = lambda a: c.f32(fit(a).astype(keys.dtype))  # noqa: E731  (the step's queries are in the served dtype)
                exact = plain_selection(as_served(qi), as_served(wi), c.f32(fit(keys[i].reshape(-1, keys.shape[-1]))),
                                        min(topk, n))
                lo = topk if topk < n else 0  # the rows where the selection binds (all, for a ``topk`` past the sequence)
                overlaps.append((float(overlap(got, exact, lo, n)), float(overlap(got, fit(fit(mask).T).T, lo, n))))
        # The head over the positions of ``tokens`` only: 4,400 rows of
        # 151,936 float32 logits do not fit beside the engine.
        nxt, best = _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):],
                          conf["rms_norm_eps"])
    return nxt, best, overlaps


def held_to_overlap(nxt, overlaps: list):
    """``nxt``, or NaN in its place where a layer's selected sets fall short
    of ``SELECTED_EXACT_MIN`` or ``SELECTED_OVERLAP_MIN`` (the one way a
    reference can make ``correctness.py`` read not correct)."""
    if not overlaps:
        return nxt
    exact, own = min(o[0] for o in overlaps), min(o[1] for o in overlaps)
    print("perfbench gqa_dsa_moe: " + json.dumps({
        "selected_overlap_by_layer": overlaps, "least": [exact, own],
        "least_allowed": [SELECTED_EXACT_MIN, SELECTED_OVERLAP_MIN]}), file=sys.stderr)
    return nxt if exact >= SELECTED_EXACT_MIN and own >= SELECTED_OVERLAP_MIN else nxt * jnp.nan


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """What ``correctness.py`` calls: ``score`` held to the overlaps."""
    nxt, best, overlaps = score(params, tokens, conf, trace)
    return held_to_overlap(nxt, overlaps), best
