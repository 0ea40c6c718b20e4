"""Plain reference: multi-head latent attention over the rows a lightning
indexer selects + sparse experts in groups with a shared one, the first
layers dense, as ONE RANK's share of an expert-parallel deployment
(DeepSeek-V3.2, HF ``deepseek_v32``). Written from the equations of ISSUE 48 /
docs/architecture/sparse-attention.md ("The latent variant"), independent of
``llmd_tpu``. With x^ = RMSNorm(x):

  latent attention: c_q = RMSNorm(Wqa x^); q = Wqb c_q -> heads x (nope + rope); q_pe = RoPE(q[.., nope:]);
    (c, k_r) = Wkva x^; the cached row l = [RMSNorm(c), RoPE(k_r)], one a token, shared by all heads;
    Wkvb: rank -> heads x (nope + v) = (W_uk, W_uv) per head; k[s, i] = [W_uk_i l_c[s], l_r[s]], v[s, i] = W_uv_i l_c[s];
    o[t, i] = softmax over s in S_t of (q[t, i] . k[s, i] * scale) v[s, i], scale = (nope + rope)^-0.5 * m^2,
    m = 0.1 * mscale_all_dim * ln(factor) + 1 (yarn); x += Wo o.  UNABSORBED: keys and values are materialised
    per head (the program folds W_uk into the query and W_uv behind the read).
  lightning indexer: qI = WIq c_q -> J x Di (FROM THE QUERY LATENT); kI = LayerNorm(WIk x^) with weight and bias;
    RoPE over the FIRST ``qk_rope_head_dim`` of the Di dimensions of qI and kI, the rest pass;
    w = WIw x^ * J^-0.5 * Di^-0.5;  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]), s <= t;
    S_t = the ``index_topk`` values of s <= t with the largest I[t, s] (all while t + 1 <= topk; ties: lower s).
  experts: g = sigmoid(Wr x^); selection on g + b: ``n_group`` groups scored by the sum of their two largest, the
    best ``topk_group`` kept (the others' scores set to 0, as published), the top-k of what is left; weights = g at
    the picks / their sum x ``routed_scaling_factor``; the terms of the experts HELD here (``first_held``), and the
    shared expert, which every rank computes alike.

Departures from the published inference code, each also in the configuration
file's ``assumed`` / ``omitted``: bfloat16 weights and cache where it stores
the latent and the indexer key in FP8 with per-block scales; its Hadamard
rotation of qI and kI is orthogonal (qI . kI unchanged in exact arithmetic) and
is not applied; rotate-half pairing of the rotated dimensions in the main
attention as in the indexer (the published main attention interleaves: a
permutation of seeded weight columns, ``llmd_tpu/models/mla_dsa.py::
from_published`` is the loader's map); the multi-token-prediction module is not
served. Nothing else of the mathematics is left out.

One sequence, float32, ``highest`` matmul precision, a layer (an expert) at a
time; index scores in blocks of 512 queries, the main attention's scores in
blocks of 256 queries and 32 heads, so that 4,400 tokens fit beside the engine.

THE COMPARISON is ``references/gqa_dsa_moe.py``'s (its docstring says what the
bound and the unbound half of the prompts are for, and what "exact" and "own"
hold the selected sets to), plus FIRST_LAYER_LATENTS: ``params["bound"]
[prompt]["latents"]`` holds the first layer's cached rows of the sequence as
the serving path left them in the latent pool ([n, Dl], the served dtype), and
every row is held to this file's [RMSNorm(c), RoPE(k_r)] of the same position:
the largest |theirs - mine| / |mine| over the n rows (a row the flat write
lost, or put into another slot, reads ~1; the served dtype's rounding reads
0.004). Over ``LATENT_ROW_RTOL`` the prompt's log-probs are NaN.

LIMITS, each between two readings (``perfbench/tolerance_probe_mla_dsa.py`` on
the chip, published widths, 1 + 4 layers; my chip runs, PR 48: the sound
comparison on 18 seeds, 12 whole runs and 6 probe seeds, and every control
THROUGH ``correctness.reference_check`` on 2 seeds, the two weakest on 6; the
numbers of a control are those BEFORE its checks were held against them):
sound: median 0.043-0.064 (the unbound half alone 0.022-0.032), 90th percentile
0.233-0.435, max 0.50-1.75, margin max 0.50-1.54, exact 0.9999997-1.0000, own
0.9804-0.9833 (fifth layer; 0.999 in the first), latent rows 0.0036-0.0038.
  attend over all cached tokens   90th percentile 3.26-3.31; own 0.915
  indexer's queries from x^       90th percentile 3.78-4.32; own 0.899-0.900
  indexer rotated over all 128    90th percentile 3.90-3.92; own 0.703-0.706
  m^2 dropped from the scale      median 2.10-2.26 (the unbound half 1.75-2.10)
  group limit dropped             median 0.095-0.143 on 6 seeds; own 0.963-0.966
  cached latents in float8        latent rows 0.0307; log-probs as the sound run
  selection over float8 keys      exact 0.9929-0.9930; log-probs as the sound run
  weights in float8               median 3.69-3.73; latent rows 0.78
  ONE HELD EXPERT FEWER           median 0.046-0.104, 90th percentile 0.27-0.44,
    own 0.968-0.982: over the median's limit on 2 seeds of 6 and told from a
    sound run by NO number on the other 4 (a rank's sixteen experts take 6 % of
    the picks and one of them a sixteenth of that: ROADMAP S13 (l), as the
    K-EXAONE comparison).
  LOGPROB_MEDIAN_ATOL 0.08: 1.26x the largest sound median of 18 seeds (0.0636,
    the last whole run; the other 17 read 0.058 at most), 0.84x the smallest
    with the group limit dropped, 0.04x the smallest with m^2 dropped.
  LOGPROB_P90_ATOL 1.0: 2.3x the largest sound reading (0.435, a probe seed
    that Keye's 0.40 would have refused: half the compared tokens sit behind
    4,096 tokens of context, where a hard threshold at the 2,048th score turns
    bfloat16 drift into other attended rows, and this model's sets drift more
    than Keye's by the fifth layer), 0.31x the smallest of a wrong or ignored
    selection.
  LOGPROB_MAX_ATOL 3.0: 1.7x the largest sound reading (1.75, a whole run that
    Keye's 2.0 let pass by a seventh; the worst routing flip is heavy-tailed),
    0.73x the smallest max with m^2 dropped (4.11).
  MARGIN_ATOL 3.0: 1.9x the largest sound reading (1.54, the same run); a wrong
    mask, position or layer reads several units (``gqa_moe``).
  SELECTED_EXACT_MIN 0.999: sound 0.9999997 at least (one slot of one row's set:
    a near-tie at the 2,048th score between the kernel's order of summation and
    this file's); float8 keys 0.9930 at most.
  SELECTED_OVERLAP_MIN 0.95: sound 0.9804 at least; all cached tokens attended
    0.915 at most.
  LATENT_ROW_RTOL 0.012: 3.2x the largest sound reading, 0.39x float8 rows; a
    lost row reads ~1.
"""

from __future__ import annotations

import functools
import json
import sys

import jax
import jax.numpy as jnp

from perfbench.references import _common as c
from perfbench.references.gqa_dsa_moe import bound_entry, layer_norm, overlap, plain_selection, selected
from perfbench.references.gqa_swa_moe_share import _dense_ffn, _head, first_held  # the dense GLU in slices, the head, the rank's first expert

KEYS = (
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "rms_norm_eps", "rope_theta", "rope_scaling", "index_n_heads", "index_head_dim", "index_topk",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "n_group", "topk_group",
    # The tolerance probe's faulty references (never in a configuration file):
    "indexer_from_input", "indexer_rope_all", "no_yarn_temperature", "no_group_limit", "attend_all",
)
Q_BLOCK = 512  # queries a block of index scores
A_BLOCK, H_BLOCK = 256, 32  # queries and heads a block of the main attention's scores

LOGPROB_MEDIAN_ATOL = 0.08
LOGPROB_P90_ATOL = 1.0
LOGPROB_MAX_ATOL = 3.0
MARGIN_ATOL = 3.0
SELECTED_EXACT_MIN = 0.999  # the program's sets against an exact top-k over the SAME cached keys
SELECTED_OVERLAP_MIN = 0.95  # ... against the sets from this file's own keys
LATENT_ROW_RTOL = 0.012  # a first-layer cached row against this file's, |theirs - mine| / |mine|


def rope_first(x, n: int, positions, theta, scaling):
    """RoPE over the first ``n`` dimensions of [T, heads, dim] (rotate-half
    among themselves); the rest pass."""
    return jnp.concatenate([c.rope(x[..., :n], positions, theta, scaling), x[..., n:]], axis=-1)


def latent_rows(lp, i, h, positions, conf):
    """The rows a layer caches: [RMSNorm(c), RoPE(k_r)] [T, rank + rope]."""
    rank = conf["kv_lora_rank"]
    kv_a = h @ c.f32(lp["wkv_a"][i])
    c_kv = c.rms_norm(kv_a[:, :rank], lp["kv_norm"][i], conf["rms_norm_eps"])
    k_pe = c.rope(kv_a[:, None, rank:], positions, conf["rope_theta"], conf.get("rope_scaling"))[:, 0]
    return jnp.concatenate([c_kv, k_pe], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims",))
def _attention(lp, i, x, dims):
    """x + Attn(norm(x)) of one layer; also the selected sets [T, T] bool, the
    indexer's queries and head weights, and the rows it would cache."""
    conf = c.thaw(dims)
    nh, nj, di = conf["num_attention_heads"], conf["index_n_heads"], conf["index_head_dim"]
    nope, rope, vd = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"], conf["v_head_dim"]
    rank, eps, topk = conf["kv_lora_rank"], conf["rms_norm_eps"], conf["index_topk"]
    theta, scaling = conf["rope_theta"], conf.get("rope_scaling")
    t = x.shape[0]
    positions = jnp.arange(t)
    h = c.rms_norm(x, lp["input_norm"][i], eps)
    c_q = c.rms_norm(h @ c.f32(lp["wq_a"][i]), lp["q_norm"][i], eps)
    q = (c_q @ c.f32(lp["wq_b"][i])).reshape(t, nh, nope + rope)
    q_pe = c.rope(q[..., nope:], positions, theta, scaling)
    lat = latent_rows(lp, i, h, positions, conf)
    c_kv, k_pe = lat[:, :rank], lat[:, rank:]
    # The indexer.
    qi_in = h if conf.get("indexer_from_input") else c_q
    qi = (qi_in[:, : lp["wi_q"].shape[1]] @ c.f32(lp["wi_q"][i])).reshape(t, nj, di)
    ki = layer_norm(h @ c.f32(lp["wi_k"][i]), lp["wi_k_norm"][i], lp["wi_k_norm_b"][i], eps)
    rot = di if conf.get("indexer_rope_all") else rope
    qi = rope_first(qi, rot, positions, theta, scaling)
    ki = rope_first(ki[:, None, :], rot, positions, theta, scaling)[:, 0]
    wi = (h @ c.f32(lp["wi_w"][i])) * (nj ** -0.5 * di ** -0.5)
    masks = []
    for t0 in range(0, t, Q_BLOCK):
        sl = slice(t0, min(t0 + Q_BLOCK, t))
        index = jnp.einsum("qj,qjs->qs", wi[sl], jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi[sl], ki)))
        masks.append(selected(index, t0, topk))
    mask = jnp.concatenate(masks)
    attend = (positions[None, :] <= positions[:, None]) if conf.get("attend_all") else mask
    # The main attention, keys and values materialised per head.
    scale = (nope + rope) ** -0.5 * (1.0 if conf.get("no_yarn_temperature") else c.yarn_softmax_mult(scaling))
    wkv_b = c.f32(lp["wkv_b"][i]).reshape(rank, nh, nope + vd)
    outs = []
    for h0 in range(0, nh, H_BLOCK):
        hs = slice(h0, min(h0 + H_BLOCK, nh))
        k_nope = jnp.einsum("sr,rhn->shn", c_kv, wkv_b[:, hs, :nope])
        v = jnp.einsum("sr,rhv->shv", c_kv, wkv_b[:, hs, nope:])
        rows = []
        for t0 in range(0, t, A_BLOCK):
            sl = slice(t0, min(t0 + A_BLOCK, t))
            s = (jnp.einsum("qhd,khd->hqk", q[sl, hs, :nope], k_nope)
                 + jnp.einsum("qhd,kd->hqk", q_pe[sl, hs], k_pe)) * scale
            p = jax.nn.softmax(jnp.where(attend[sl][None], s, -jnp.inf), axis=-1)
            rows.append(jnp.einsum("hqk,khd->qhd", p, v))
        outs.append(jnp.concatenate(rows))
    out = jnp.concatenate(outs, axis=1).reshape(t, nh * vd)
    return x + out @ c.f32(lp["wo"][i]), mask, qi, wi, lat


@functools.partial(jax.jit, static_argnames=("dims", "first", "held"))
def _sparse_ffn(lp, i, x, dims, first: int, held: int | None = None):
    """Router over every published expert, in groups; the terms of the experts
    held here (ids ``first`` onward, as many as the leaves hold, or the first
    ``held`` of them: the probe's rank with one expert fewer); the shared
    expert, which every rank computes alike."""
    conf = c.thaw(dims)
    h = c.rms_norm(x, lp["post_norm"][i], conf["rms_norm_eps"])
    t = x.shape[0]
    scores = jax.nn.sigmoid(h @ c.f32(lp["router"][i]))  # [T, all experts]
    choice = scores + c.f32(lp["router_bias"][i])
    groups = int(conf.get("n_group") or 1)
    if groups > 1 and not conf.get("no_group_limit"):
        grouped = choice.reshape(t, groups, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, conf["topk_group"])
        kept = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(kept, grouped.shape[-1], axis=-1), choice, 0.0)
    _, picks = jax.lax.top_k(choice, conf["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if conf.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(conf.get("routed_scaling_factor") or 1.0)
    combine = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], picks].add(w)
    held = held or lp["we_gate"].shape[1]

    def one(e, acc):
        y = c.swiglu(h, lp["we_gate"][i, e], lp["we_up"][i, e], lp["we_down"][i, e])
        return acc + y * jax.lax.dynamic_index_in_dim(combine, first + e, 1)

    y = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    return x + y + c.swiglu(h, lp["ws_gate"][i], lp["ws_up"][i], lp["ws_down"][i])


@jax.jit
def row_error(theirs, mine):
    """The largest |theirs - mine| / |mine| over the rows of two [n, width]."""
    d = jnp.linalg.norm(c.f32(theirs) - mine, axis=-1)
    return jnp.max(d / jnp.maximum(jnp.linalg.norm(mine, axis=-1), 1e-30))


def score(params: dict, tokens, conf: dict, trace: list | None = None):
    """(log-prob of each next token, best log-prob, checks) at positions
    0..T-2 of ``tokens``, computed behind the context ``params["bound"]`` has
    for the prompt, where it has one. ``checks``: {"overlaps": per layer
    (exact, own) as ``gqa_dsa_moe.score``, "latent": the first layer's cached
    rows' largest error} (empty without an entry). ``trace``, a list, receives
    per layer the layer's input, the selected sets ([T, T] bool) and the rows
    it caches, of the whole sequence (the CPU tests)."""
    scaling = {k: tuple(v) if isinstance(v, list) else v for k, v in (conf.get("rope_scaling") or {}).items()}
    dims = c.freeze(dict(conf, rope_scaling=scaling), KEYS)
    n_dense = int(conf.get("first_k_dense_replace") or 0)
    first = first_held(params, conf)
    entry = bound_entry(params, tokens) or {}
    context = [int(t) for t in entry.get("context", ())]
    theirs, topk, checks = entry.get("selection"), conf["index_topk"], {}
    with jax.default_matmul_precision("highest"):
        full = jnp.asarray(context + [int(t) for t in tokens], jnp.int32)
        x = c.f32(params["embed"][full])
        for l in range(conf["num_hidden_layers"]):
            group, i = ("dense_layers", l) if l < n_dense else ("layers", l - n_dense)
            lp, x_in = params[group], x
            x, mask, qi, wi, lat = _attention(lp, jnp.int32(i), x, dims)
            if trace is not None:
                trace.append({"input": x_in, "selected": mask, "latent": lat})
            if theirs is not None:
                got, n = theirs(l, qi, wi)  # [R, R] bool, of which n rows and columns are cached tokens
                keys, _ = entry["cached"]  # [L, pages, page, Di], the served dtype
                fit = lambda a: jnp.pad(a[:n], ((0, got.shape[0] - n),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
                as_served = lambda a: c.f32(fit(a).astype(keys.dtype))  # noqa: E731
                exact = plain_selection(as_served(qi), as_served(wi), c.f32(fit(keys[l].reshape(-1, keys.shape[-1]))),
                                        min(topk, n))
                lo = topk if topk < n else 0
                checks.setdefault("overlaps", []).append(
                    (float(overlap(got, exact, lo, n)), float(overlap(got, fit(fit(mask).T).T, lo, n))))
                if l == 0 and entry.get("latents") is not None:
                    checks["latent"] = float(row_error(entry["latents"][:n, : lat.shape[1]], lat[:n]))
            if l < n_dense:
                x = _dense_ffn(lp, jnp.int32(i), x, conf["rms_norm_eps"])
            else:
                x = _sparse_ffn(lp, jnp.int32(i), x, dims, first, conf.get("experts_used"))
        nxt, best = _head(params["final_norm"], params["lm_head"], x[len(context):], full[len(context):],
                          conf["rms_norm_eps"])
    return nxt, best, checks


def held_to_checks(nxt, checks: dict):
    """``nxt``, or NaN in its place where a layer's selected sets fall short of
    ``SELECTED_EXACT_MIN`` / ``SELECTED_OVERLAP_MIN`` or a first-layer cached
    row differs by more than ``LATENT_ROW_RTOL`` (the one way a reference can
    make ``correctness.py`` read not correct)."""
    if not checks:
        return nxt
    overlaps = checks.get("overlaps") or [(1.0, 1.0)]
    exact, own = min(o[0] for o in overlaps), min(o[1] for o in overlaps)
    latent = checks.get("latent", 0.0)
    print("perfbench mla_dsa_moe_share: " + json.dumps({
        "selected_overlap_by_layer": overlaps, "least": [exact, own],
        "least_allowed": [SELECTED_EXACT_MIN, SELECTED_OVERLAP_MIN],
        "first_layer_latent_row_error": latent, "most_allowed": LATENT_ROW_RTOL}), file=sys.stderr)
    ok = exact >= SELECTED_EXACT_MIN and own >= SELECTED_OVERLAP_MIN and latent <= LATENT_ROW_RTOL
    return nxt if ok else nxt * jnp.nan


def forward(params: dict, tokens, conf: dict, trace: list | None = None):
    """What ``correctness.py`` calls: ``score`` held to its checks."""
    nxt, best, checks = score(params, tokens, conf, trace)
    return held_to_checks(nxt, checks), best
