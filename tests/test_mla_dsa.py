"""Indexed latent attention (DeepSeek-V3.2: the lightning indexer's top-k read
out of an MLA latent cache, docs/architecture/sparse-attention.md) on the CPU
at a tiny size: the engine — chunked prefill, then decode through the paged
latent pool and its plane of indexer keys — against the plain reference of
``perfbench/references/mla_dsa_moe_share.py`` on seeded float32 weights, at
contexts on both sides of the tiny top-k.
"""

import dataclasses
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.config import (  # noqa: E402
    CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
)
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import llama, mla_dsa, moe  # noqa: E402
from llmd_tpu.models.common import apply_rope, rope_tables  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import sparse_attention as sa  # noqa: E402
from llmd_tpu.ops import sparse_mla  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import mla_dsa_moe_share as ref  # noqa: E402
from perfbench.topologies import engine_longctx, engine_longctx_latent  # noqa: E402
from perfbench.topologies.engine import reference_params  # noqa: E402

TOPK, PAGE = 32, 16
CONF = json.loads((ROOT / "perfbench" / "configs" / "deepseek-v3.2.1chip.json").read_text())
# The reference's view of the tiny preset (``tiny-mla-dsa``), as the
# benchmark's rehearsal states it.
PUBLISHED = CONF["rehearse"]["published"]


def make_engine(num_blocks=128, max_batched=64, max_seqs=8, model=None, **cache) -> LLMEngine:
    return LLMEngine(EngineConfig(
        model=model or get_model_config("tiny-mla-dsa"),
        cache=CacheConfig(page_size=PAGE, num_blocks=num_blocks, dtype="float32", **cache),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
    """[(tokens, log-probs)] per prompt, all in the engine at once."""
    ids = [eng.add_request(list(p), SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                                   ignore_eos=True, logprobs=True)) for p in prompts]
    reqs = list(eng.scheduler.waiting)
    toks = {rid: [] for rid in ids}
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
    return [(toks[rid], np.asarray(r.output_logprobs)) for rid, r in zip(ids, reqs)]


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def reference_logprobs(eng, prompt, toks, published=PUBLISHED, **kw):
    """The reference's log-prob of each emitted token, and its best."""
    params = reference_params(eng.runner.params, eng.config.model)
    nxt, best = ref.forward(params, prompt + toks, published, **kw)
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return np.asarray(nxt[at]), np.asarray(best[at])


@pytest.mark.parametrize("context, budget", [
    (3 * TOPK + 5, 64),   # chunks of whole pages
    (6 * TOPK, 40),       # every chunk starts and ends inside a page
    (TOPK - 9, 64),       # never more than top-k cached: the selection is "all"
    (4 * TOPK + 9, 512),  # one chunk
])
def test_engine_matches_the_reference_on_both_sides_of_topk(context, budget):
    """Prefill in chunks of ``budget``, then decode through the cache: the
    log-softmax of every emitted token equals the reference's full forward
    pass, and the token is the reference's best (float32 both sides)."""
    eng = make_engine(max_batched=budget)
    assert eng.runner.flat_t_buckets and isinstance(eng.runner.kv_cache, sa.IndexedPool)
    prompt = tokens(context, seed=context)
    (toks, lps), = greedy(eng, [prompt], max_tokens=8)
    nxt, best = reference_logprobs(eng, prompt, toks)
    np.testing.assert_allclose(lps, nxt, atol=3e-5)
    np.testing.assert_allclose(nxt, best, atol=3e-5)
    s, n, layers = eng.stats, context + 7, eng.config.model.num_layers
    assert s.indexer_keys_written_total == n
    assert s.sparse_bound_tokens_total == max(0, n - TOPK)
    assert s.latent_rows_written_total == layers * n
    assert s.sparse_rows_selected_total == layers * sum(min(t + 1, TOPK) for t in range(n))


def test_each_departure_changes_the_result_and_the_reference_can_tell():
    """The same engine log-probs against the reference with one term of the
    mathematics changed (the tolerance probe's controls) are far off."""
    eng = make_engine()
    prompt = tokens(5 * TOPK, seed=3)
    (toks, lps), = greedy(eng, [prompt], max_tokens=8)
    sound, _ = reference_logprobs(eng, prompt, toks)
    assert np.max(np.abs(lps - sound)) < 3e-5
    for control in ("attend_all", "indexer_from_input", "indexer_rope_all", "no_yarn_temperature",
                    "no_group_limit"):
        other, _ = reference_logprobs(eng, prompt, toks, dict(PUBLISHED, **{control: True}))
        assert np.median(np.abs(lps - other)) > 1e-3, control
    fewer, _ = reference_logprobs(eng, prompt, toks, dict(PUBLISHED, experts_used=3))
    assert np.max(np.abs(lps - fewer)) > 1e-3


def _served_behind_a_context(eng, context, prompt, max_tokens=5):
    """(tokens, log-probs, pages, cached tokens) of ``context + prompt``."""
    eng.add_request(context + prompt, SamplingParams(max_tokens=max_tokens, temperature=0.0, ignore_eos=True,
                                                     logprobs=True))
    req = eng.scheduler.waiting[0]
    pages = None
    while eng.has_work():
        pages = req.block_ids or pages  # the scheduler extends this list in place; a finish rebinds the field
        eng.step()
    n = len(context) + len(prompt) + max_tokens - 1  # the last token sampled is never fed
    return list(req.output_token_ids), np.asarray(req.output_logprobs), pages[: -(-n // PAGE)], n


def test_the_reference_holds_the_systems_selected_sets_and_cached_latents_to_its_own():
    """What the benchmark's topology hands the reference: a context in front
    of the prompt, the sets the program's own scoring and top-k select over
    the indexer keys the engine CACHED, and the first layer's cached latent
    rows. A sound system agrees wholly; one that picks other keys, or whose
    write lost a row, is answered with NaN."""
    eng = make_engine(max_batched=40)
    context, prompt = tokens(4 * TOPK + 3, seed=41), tokens(21, seed=42)
    toks, lps, pages, n = _served_behind_a_context(eng, context, prompt)
    pool = eng.runner.kv_cache
    ids = jnp.asarray(pages)
    keys, latents = pool.index[:, ids], pool.kv[0, ids].reshape(-1, pool.kv.shape[-1])
    topology = types.SimpleNamespace(model_cfg=eng.config.model)
    params = reference_params(eng.runner.params, eng.config.model)

    def bound(latents=latents, **stand_ins):
        entry = {"context": context, "cached": (keys, n), "latents": latents,
                 "selection": engine_longctx.System.selection(topology, keys, n, **stand_ins)}
        return dict(params, bound={tuple(prompt): entry})

    nxt, _best, checks = ref.score(bound(), prompt + toks, PUBLISHED)
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
    np.testing.assert_allclose(lps, np.asarray(nxt)[at], atol=3e-5)
    assert len(checks["overlaps"]) == PUBLISHED["num_hidden_layers"]
    assert min(min(o) for o in checks["overlaps"]) > 1 - 1e-6 and checks["latent"] < 1e-5
    assert np.all(np.isfinite(np.asarray(ref.forward(bound(), prompt + toks, PUBLISHED)[0])))
    # A system that keeps the LOWEST scores: half of each set, at most, is the reference's.
    wrong = bound(pick=lambda scores, k: sa.select_topk(-scores, k))
    assert max(max(o) for o in ref.score(wrong, prompt + toks, PUBLISHED)[2]["overlaps"]) < 0.6
    assert np.all(np.isnan(np.asarray(ref.forward(wrong, prompt + toks, PUBLISHED)[0])))
    # One row of the pool left as it was made: the latents' check alone sees it.
    lost = bound(latents=latents.at[n // 2].set(0.0))
    assert ref.score(lost, prompt + toks, PUBLISHED)[2]["latent"] > 0.9
    assert np.all(np.isnan(np.asarray(ref.forward(lost, prompt + toks, PUBLISHED)[0])))


def test_the_cached_rows_and_the_selected_sets_are_the_references_in_every_layer():
    """Per layer: the latent rows and indexer keys the engine cached through
    the page table are the reference's, and the reference's index queries
    scored by the engine's ops over the cached plane select exactly the
    reference's sets."""
    eng = make_engine(max_batched=40)
    prompt = tokens(5 * TOPK + 3, seed=11)
    rid = eng.add_request(prompt, SamplingParams(max_tokens=64, temperature=0.0, ignore_eos=True))
    req = eng.scheduler.waiting[0]
    while req.num_computed_tokens < len(prompt) + 4:  # prefilled, four tokens decoded
        eng.step()
    n = req.num_computed_tokens
    seq = (prompt + req.output_token_ids)[:n]
    trace: list = []
    params = reference_params(eng.runner.params, eng.config.model)
    _, _, checks = ref.score(params, seq + [0], PUBLISHED, trace=trace)
    assert not checks and len(trace) == 3
    pool = eng.runner.kv_cache
    table = np.zeros((1, eng.runner.max_pages), np.int32)
    table[0, : len(req.block_ids)] = req.block_ids
    at = (table[0, np.arange(n) // PAGE], np.arange(n) % PAGE)
    rows, kv_lens = jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32) + 1
    dims = rc.freeze(dict(PUBLISHED, rope_scaling=tuple(sorted(PUBLISHED["rope_scaling"].items()))), ref.KEYS)
    for l, layer in enumerate(trace):
        group, i = ("dense_layers", 0) if l == 0 else ("layers", l - 1)
        width = layer["latent"].shape[1]
        np.testing.assert_allclose(np.asarray(pool.kv[l])[at[0], 0, at[1], :width],
                                   np.asarray(layer["latent"])[:n], atol=2e-5)
        assert not np.asarray(pool.kv[l])[at[0], 0, at[1], width:].any()  # the lane pad stays zero
        with jax.default_matmul_precision("highest"):
            _, mask, qi, wi, _ = ref._attention(params[group], jnp.int32(i), layer["input"], dims)
        scores = sa.index_scores(qi[:n], wi[:n], pool.index[l], jnp.asarray(table), rows, kv_lens)
        sel = sa.select_topk(scores, TOPK) & (jnp.arange(scores.shape[1])[None, :] < kv_lens[:, None])
        assert np.array_equal(np.asarray(sel)[:, :n], np.asarray(mask)[:n, :n]), f"layer {l}"
        # ... and as the ascending positions the read gathers
        pos = np.asarray(sparse_mla.selected_positions(sel, TOPK))
        for t in (0, TOPK - 1, TOPK, n - 1):
            want = np.flatnonzero(np.asarray(mask)[t, :n])
            assert pos[t, : len(want)].tolist() == want.tolist() and (pos[t, len(want):] == sel.shape[1]).all()
    eng.abort_request(rid)


@pytest.mark.parametrize("S, topk", [(512, 32), (300, 7), (128, 128)])
def test_selected_positions_lists_a_masks_rows_in_order(S, topk):
    rng = np.random.default_rng(S)
    sel = np.zeros((9, S), bool)
    counts = [0, 1, topk, topk // 2, topk, 3, topk - 1, 2, topk]
    for t, k in enumerate(counts):
        sel[t, rng.permutation(S)[:k]] = True
    sel[2, :] = False
    sel[2, S - topk:] = True  # all in the last blocks
    pos = np.asarray(sparse_mla.selected_positions(jnp.asarray(sel), topk))
    for t, k in enumerate(counts):
        assert pos[t, :k].tolist() == np.flatnonzero(sel[t]).tolist()
        assert (pos[t, k:] == S).all()


def _cold(prompt, max_tokens=6):
    (toks, lps), = greedy(make_engine(), [prompt], max_tokens=max_tokens)
    return toks, lps


def test_a_prefix_cache_hit_carries_the_latent_rows_and_the_indexer_keys():
    """A second request over the same long prefix computes only its tail; its
    log-probs equal a cold run's and the reference's, so the shared pages
    held the prefix's latent rows AND its indexer keys."""
    shared = tokens(4 * TOPK, seed=5)
    a, b = shared + tokens(10, seed=6), shared + tokens(13, seed=7)
    eng = make_engine()
    greedy(eng, [a])
    before = eng.stats.latent_rows_written_total
    (toks, lps), = greedy(eng, [b])
    layers = eng.config.model.num_layers
    assert eng.stats.latent_rows_written_total - before == layers * (len(b) - len(shared) + 5)
    cold_t, cold_l = _cold(b)
    assert toks == cold_t
    np.testing.assert_allclose(lps, cold_l, atol=3e-5)
    np.testing.assert_allclose(lps, reference_logprobs(eng, b, toks)[0], atol=3e-5)


def test_pages_reused_and_preempted_sequences_give_a_cold_runs_logits():
    """A pool too small for three long sequences forces preemption and
    recompute over reused pages; every stream still equals its cold run."""
    prompts = [tokens(3 * TOPK + i, seed=20 + i) for i in range(3)]
    eng = make_engine(num_blocks=22, enable_prefix_caching=False)  # 22 pages of 16 admit the three prompts (21) and not their growth (24)
    outs = greedy(eng, prompts, max_tokens=24)
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    for p, (toks, lps) in zip(prompts, outs):
        cold_t, cold_l = _cold(p, 24)
        assert toks == cold_t
        np.testing.assert_allclose(lps, cold_l, atol=3e-5)


def test_the_interpreted_kernels_write_and_score_what_the_xla_path_does(monkeypatch):
    """The Pallas path in interpret mode (the flat latent write through the
    run plan into both planes, the indexer kernel) against the XLA path:
    the pools' rows after a prompt cut into chunks that start inside a page,
    and the log-probs. The indexer's head is a lane tile wide here, as
    published, so that its plane takes the kernel's write."""
    model = get_model_config("tiny-mla-dsa", indexer_head_dim=128)
    prompt = tokens(200, seed=5)

    def served(eng):
        (toks, lps), = greedy(eng, [prompt], max_tokens=3)
        pages = np.asarray(eng.allocator.lookup_cached_prefix(prompt))
        assert len(pages) == len(prompt) // PAGE
        pool = eng.runner.kv_cache
        return toks, lps, np.asarray(pool.kv[:, pages]), np.asarray(pool.index[:, pages])

    want = served(make_engine(num_blocks=32, max_batched=72, model=model))
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    eng = make_engine(num_blocks=32, max_batched=72, model=model)
    got = served(eng)
    assert eng.runner.kernel_plans["flat_latent_write"] == {"pallas"}  # interpreted
    assert eng.runner.kernel_plans["indexer"] == {"pallas"}
    for a in got[2:]:  # chunks [0, 72), [72, 144), ..: no row left as the pool was made
        assert np.abs(a).sum(axis=-1).min() > 0
    assert got[0] == want[0]
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_the_flat_latent_write_is_a_dense_scatter_of_the_valid_rows(monkeypatch):
    """``write_latent_rows_full_flat`` through the run plan (interpreted)
    against a scatter written here: valid rows only, chunks that start and
    end inside a page, the rest of both planes untouched."""
    def flat_write_runs(rows_w, table, n):
        """(src, off, cnt, phys), ``n`` runs: a run is a span of a row's
        tokens inside one page (``Runner``'s plan, engine/runner.py)."""
        src, off, cnt, phys = (np.zeros(n, np.int32) for _ in range(4))
        i = t0 = 0
        for r, p0, w in rows_w:
            done = 0
            while done < w:
                o = (p0 + done) % PAGE
                take = min(PAGE - o, w - done)
                src[i], off[i], cnt[i], phys[i] = PAGE + t0 + done - o, o, take, table[r, (p0 + done) // PAGE]
                i, done = i + 1, done + take
            t0 += w
        return src, off, cnt, phys

    rng = np.random.default_rng(0)
    L, pages, Dl, Di, T = 2, 12, 128, 128, 48
    cache = sa.IndexedPool(kv=jnp.asarray(rng.normal(size=(L, pages, 1, PAGE, Dl)), jnp.float32),
                           index=jnp.asarray(rng.normal(size=(L, pages, PAGE, Di)), jnp.float32))
    table = np.asarray([[3, 7, 1, 0], [9, 4, 2, 0], [5, 0, 0, 0]], np.int32)
    rows_w = [(0, 10, 23), (1, 30, 18), (2, 7, 1)]  # (row, first position, tokens): 42 live of 48
    tok_rows, pos, valid = np.zeros(T, np.int32), np.zeros(T, np.int32), np.zeros(T, bool)
    t = 0
    for r, p0, w in rows_w:
        tok_rows[t:t + w], pos[t:t + w], valid[t:t + w] = r, p0 + np.arange(w), True
        t += w
    runs = flat_write_runs(rows_w, table, 8)
    latent = jnp.asarray(rng.normal(size=(T, Dl)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(T, Di)), jnp.float32)
    want_kv, want_ix = np.asarray(cache.kv).copy(), np.asarray(cache.index).copy()
    for i in np.flatnonzero(valid):
        page = table[tok_rows[i], pos[i] // PAGE]
        want_kv[1, page, 0, pos[i] % PAGE], want_ix[1, page, pos[i] % PAGE] = latent[i], keys[i]
    args = (jnp.int32(1), latent, keys, jnp.asarray(table), jnp.asarray(tok_rows), jnp.asarray(pos),
            jnp.asarray(valid))
    plain = sparse_mla.write_latent_rows_full_flat(cache, *args, None)
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    planned = sparse_mla.write_latent_rows_full_flat(cache, *args, tuple(jnp.asarray(a) for a in runs))
    for got in (plain, planned):
        np.testing.assert_array_equal(np.asarray(got.kv), want_kv)
        np.testing.assert_array_equal(np.asarray(got.index), want_ix)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test at the deployment's shape in miniature:
    over all ranks the held experts' parts (each rank a part of one routing
    group), with the shared expert counted once, are the uncut layer, which
    is what the uncut reference gives for it."""
    cfg = get_model_config("tiny-mla-dsa")
    whole_cfg = dataclasses.replace(cfg, held_experts=cfg.num_experts, held_experts_first=0)
    full = llama.init_params(whole_cfg, jax.random.key(0))
    lp = jax.tree.map(lambda a: a[1], full["layers"])
    h = jax.random.normal(jax.random.key(1), (3, 7, cfg.hidden_size), jnp.float32)
    whole = moe.moe_block_grouped(h, lp, whole_cfg)
    shared = moe.shared_expert_ffn(h.reshape(-1, cfg.hidden_size), lp).reshape(h.shape)
    ranks, held = cfg.num_experts // cfg.held_experts, cfg.held_experts
    assert ranks == 4 and cfg.num_experts // cfg.n_group == held  # a rank holds one group here, half of one as published
    parts = [
        moe.moe_block_grouped(
            h, {k: (a[r * held:(r + 1) * held] if k.startswith("we_") else a) for k, a in lp.items()},
            dataclasses.replace(cfg, held_experts_first=r * held)) - shared
        for r in range(ranks)
    ]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    assert min(float(jnp.max(jnp.abs(p))) for p in parts) > 1e-4
    stacked = jax.tree.map(lambda a: a[None], dict(lp, post_norm=jnp.ones((cfg.hidden_size,))))
    dims = rc.freeze(dict(PUBLISHED, rope_scaling=None), ref.KEYS)
    x = h.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = ref._sparse_ffn(stacked, jnp.int32(0), x, dims, 0) - x
    normed = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps)).reshape(h.shape)
    np.testing.assert_allclose(moe.moe_block_grouped(normed, lp, whole_cfg).reshape(x.shape), want, atol=5e-5)


def test_the_group_limit_binds_in_the_preset():
    """The router's groups leave picks out that a plain top-k would take
    (else ``no_group_limit`` would control nothing)."""
    cfg = get_model_config("tiny-mla-dsa")
    h = jax.random.normal(jax.random.key(2), (64, cfg.hidden_size), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (cfg.hidden_size, cfg.num_experts), jnp.float32) * 0.5
    b = jax.random.normal(jax.random.key(4), (cfg.num_experts,), jnp.float32) * 0.1
    _, grouped = moe.router_topk(h, w, cfg.num_experts_per_tok, cfg, bias=b)
    _, plain = moe.router_topk(h, w, cfg.num_experts_per_tok, dataclasses.replace(cfg, n_group=1), bias=b)
    assert (np.sort(np.asarray(grouped), 1) != np.sort(np.asarray(plain), 1)).any()
    per_group = cfg.num_experts // cfg.n_group
    assert all(len({e // per_group for e in row}) <= cfg.topk_group for row in np.asarray(grouped).tolist())


def test_a_partial_rotation_of_the_indexer_is_the_first_dimensions_among_themselves():
    cfg = get_model_config("tiny-mla-dsa")
    assert cfg.indexer_rope_dim == cfg.qk_rope_head_dim == 8 < cfg.indexer_head_dim
    assert get_model_config("tiny-dsa").indexer_rope_dim == get_model_config("tiny-dsa").indexer_head_dim
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 1, 2, 16)), jnp.float32)
    pos = jnp.arange(5)[:, None] * 3
    cos, sin = rope_tables(pos, cfg.indexer_rope_dim, cfg.rope_theta, cfg.rope_scaling)
    got = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    want = ref.rope_first(x[:, 0], 8, pos[:, 0], cfg.rope_theta, cfg.rope_scaling)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want), atol=1e-6)


def test_from_published_interleaved_pairs_rotate_as_halves():
    """A published column order (interleaved pairs) mapped by
    ``from_published`` and rotated as halves gives the published rotation's
    dot products."""
    rng = np.random.default_rng(1)
    heads, dim, rope = 2, 12, 8
    w = rng.normal(size=(6, heads * dim))
    x, pos = rng.normal(size=(4, 6)), np.arange(4) * 5
    inv = 1.0 / (10000.0 ** (np.arange(rope // 2) / (rope // 2)))
    ang = pos[:, None] * inv

    def interleaved(v):  # [T, heads, rope] rotated as pairs (x0 x1 | x2 x3 ..)
        a, b = v[..., 0::2], v[..., 1::2]
        c, s = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        return np.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(v.shape)

    pub = (x @ w).reshape(4, heads, dim)
    pub = np.concatenate([pub[..., : dim - rope], interleaved(pub[..., dim - rope:])], axis=-1)
    mine = (x @ mla_dsa.from_published(w, heads, dim, rope)).reshape(4, heads, dim)
    cos, sin = jnp.cos(jnp.asarray(ang))[:, None], jnp.sin(jnp.asarray(ang))[:, None]
    rot = apply_rope(jnp.asarray(mine[:, None, :, dim - rope:]), cos, sin)[:, 0]
    mine = np.concatenate([mine[..., : dim - rope], np.asarray(rot)], axis=-1)
    np.testing.assert_allclose(np.einsum("thd,shd->ths", pub, pub), np.einsum("thd,shd->ths", mine, mine),
                               rtol=1e-5, atol=1e-4)


# --- what such a model refuses, and the configuration ------------------------------

REFUSED = {
    "the bucketed step": dict(scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64,
                                                        ragged_qlens=False)),
    "an int8 KV cache": dict(cache=CacheConfig(page_size=PAGE, num_blocks=64, dtype="int8")),
    "speculative decoding": dict(scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64,
                                                           speculative_ngram=True)),
    "a sharded mesh": dict(parallel=ParallelConfig(tensor_parallel_size=2)),
    "P/D KV transfer": dict(kv_role="kv_producer"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_a_latent_model_with_an_indexer_is_refused_off_the_flat_step(what):
    """``ModelConfig`` accepts MLA with an indexer; every road but the flat
    step of one device is refused at start."""
    kw = dict(model=get_model_config("tiny-mla-dsa"),
              cache=CacheConfig(page_size=PAGE, num_blocks=64, dtype="float32"),
              scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64))
    kw.update(REFUSED[what])
    with pytest.raises(ValueError, match="sparse attention"):
        LLMEngine(EngineConfig(**kw))


@pytest.mark.parametrize("field, value", [("q_lora_rank", 0), ("qk_rope_head_dim", 32), ("sliding_window", 64),
                                          ("attention_sinks", True)])
def test_what_the_indexer_over_a_latent_cache_cannot_be_paired_with(field, value):
    with pytest.raises(ValueError, match="sparse attention|sliding_window"):
        get_model_config("tiny-mla-dsa", **{field: value})


def test_embeddings_and_page_staging_refuse_the_latent_sparse_pool():
    eng = make_engine()
    with pytest.raises(NotImplementedError, match="flat step"):
        eng.runner.run_embed([[1, 2, 3]])
    with pytest.raises(RuntimeError, match="indexer"):
        eng.runner.copy_pages_on_device([0], [1])


def test_the_registry_preset_is_the_published_configuration():
    """``deepseek-v3.2`` holds every published key; the configuration file
    reaches the program through ``engine_longctx_latent`` with the published
    widths, the router's published width and the file's count as the experts
    held."""
    preset = get_model_config("deepseek-v3.2")
    row = next(json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
               if '"name": "DeepSeek-V3.2"' in l)["config"] if pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else dict(
        {k: v for k, v in CONF.items() if k not in CONF["published"]}, **CONF["published"])
    for hf, field in [("hidden_size", "hidden_size"), ("intermediate_size", "intermediate_size"),
                      ("num_hidden_layers", "num_layers"), ("num_attention_heads", "num_heads"),
                      ("vocab_size", "vocab_size"), ("kv_lora_rank", "kv_lora_rank"), ("q_lora_rank", "q_lora_rank"),
                      ("qk_nope_head_dim", "qk_nope_head_dim"), ("qk_rope_head_dim", "qk_rope_head_dim"),
                      ("v_head_dim", "v_head_dim"), ("index_topk", "indexer_topk"),
                      ("index_n_heads", "indexer_num_heads"), ("index_head_dim", "indexer_head_dim"),
                      ("n_routed_experts", "num_experts"), ("num_experts_per_tok", "num_experts_per_tok"),
                      ("moe_intermediate_size", "moe_intermediate_size"), ("n_group", "n_group"),
                      ("topk_group", "topk_group"), ("routed_scaling_factor", "routed_scaling_factor"),
                      ("first_k_dense_replace", "first_dense_layers"), ("rms_norm_eps", "rms_norm_eps"),
                      ("rope_theta", "rope_theta"), ("max_position_embeddings", "max_model_len"),
                      ("norm_topk_prob", "norm_topk_prob"), ("tie_word_embeddings", "tie_word_embeddings"),
                      ("rope_scaling", "rope_scaling")]:
        assert getattr(preset, field) == row[hf], hf
    assert preset.router_scoring == row["scoring_func"] and preset.topk_method == "group_top2"
    assert preset.shared_expert_intermediate_size == row["n_shared_experts"] * row["moe_intermediate_size"]
    assert preset.holds_all_experts and preset.indexer_rope_dim == 64
    assert llama.mixer_kinds(preset) == (mla_dsa.KIND,) * 61
    m = engine_longctx_latent.engine_config(CONF, seed=0, rehearse=False).model
    assert (m.num_experts, m.held_experts, m.held_experts_first) == (256, CONF["n_routed_experts"], 0)
    assert (m.num_layers, m.first_dense_layers, m.vocab_size) == (5, 1, 16160) and m.vocab_size * 8 == preset.vocab_size
    for field in ("hidden_size", "intermediate_size", "num_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "indexer_topk", "indexer_num_heads", "indexer_head_dim",
                  "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok", "n_group",
                  "topk_group", "router_scoring", "topk_method", "routed_scaling_factor", "norm_topk_prob",
                  "rope_theta", "rope_scaling", "rms_norm_eps", "tie_word_embeddings"):
        assert getattr(m, field) == getattr(preset, field), field
    assert m.kv_cache_entry_dim == 640 and m.dtype == "bfloat16"
    tiny = engine_longctx_latent.engine_config(CONF, seed=0, rehearse=True).model
    assert tiny.name == "tiny-mla-dsa" and tiny.held_experts_first == PUBLISHED["deployment"]["rank"] * tiny.held_experts
