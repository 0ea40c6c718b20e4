"""Learned sparse attention (docs/architecture/sparse-attention.md) on the CPU
at a tiny size: the engine — chunked prefill, then decode through the paged
cache — against the plain reference of ``perfbench/references/gqa_dsa_moe.py``
(one copy, where the other references live) on seeded float32 weights, at
contexts of 3-6 x the tiny top-k.
"""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.config import (  # noqa: E402
    CacheConfig, EngineConfig, OffloadConfig, ParallelConfig, SchedulerConfig,
)
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import llama  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import sparse_attention as sa  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import gqa_dsa_moe as ref  # noqa: E402
from perfbench.topologies.engine import reference_params  # noqa: E402
from tests.flat_streams import tiled_stream, token_by_token  # noqa: E402

TOPK = 32
CONF_FILE = ROOT / "perfbench" / "configs" / "keye-vl-2.0-30b-a3b.1chip.json"
# The reference's view of the tiny preset (``tiny-dsa``), as the benchmark's
# rehearsal states it.
PUBLISHED = json.loads(CONF_FILE.read_text())["rehearse"]["published"]


def make_engine(num_blocks=128, max_batched=64, max_seqs=8, page=8, model=None, **kw) -> LLMEngine:
    return LLMEngine(EngineConfig(
        model=model or get_model_config("tiny-dsa"),
        cache=CacheConfig(page_size=page, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
        **kw,
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
    """[(tokens, log-probs)] per prompt, all in the engine at once. Tokens
    from the step outputs: a preemption folds a request's earlier outputs
    into its prompt."""
    ids = [eng.add_request(list(p), SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                                   ignore_eos=True, logprobs=True)) for p in prompts]
    reqs = list(eng.scheduler.waiting)
    toks = {rid: [] for rid in ids}
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
    assert all(len(r.output_logprobs) == len(toks[rid]) for rid, r in zip(ids, reqs))
    return [(toks[rid], np.asarray(r.output_logprobs)) for rid, r in zip(ids, reqs)]


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def reference_logprobs(eng, prompt, toks, **kw):
    """The reference's log-prob of each emitted token, and its best."""
    params = reference_params(eng.runner.params, eng.config.model)
    nxt, best = ref.forward(params, prompt + toks, PUBLISHED, **kw)
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
    return np.asarray(nxt[at]), np.asarray(best[at])


@pytest.mark.parametrize("context, budget", [(3 * TOPK + 5, 64), (6 * TOPK, 48), (4 * TOPK + 9, 512)])
def test_engine_matches_the_reference_where_the_selection_binds(context, budget):
    """Prefill in chunks of ``budget``, then decode through the cache: the
    log-softmax of every emitted token equals the reference's, and the token
    is the reference's best (float32 both sides)."""
    eng = make_engine(max_batched=budget)
    prompt = tokens(context, seed=context)
    (toks, lps), = greedy(eng, [prompt], max_tokens=8)
    nxt, best = reference_logprobs(eng, prompt, toks)
    np.testing.assert_allclose(lps, nxt, atol=2e-5)
    np.testing.assert_allclose(nxt, best, atol=2e-5)
    s = eng.stats
    assert s.indexer_keys_written_total == context + 7
    assert s.sparse_bound_tokens_total == context + 7 - TOPK
    assert s.sparse_unbound_tokens_total == TOPK
    # Bound tokens sit at positions TOPK .. context + 6 and score position + 1 keys.
    assert s.indexer_keys_scored_total == sum(range(TOPK + 1, context + 8))


def test_selection_changes_the_result_and_the_reference_can_tell():
    """The same engine log-probs against the reference with the selection
    ignored, and with the index keys taken from permuted columns of ``wi_k``,
    are far off: the comparison sees the selection."""
    eng = make_engine()
    prompt = tokens(5 * TOPK, seed=3)
    (toks, lps), = greedy(eng, [prompt], max_tokens=8)
    all_tokens = dict(PUBLISHED, sa_config=dict(PUBLISHED["sa_config"], topk=1 << 20))
    params = reference_params(eng.runner.params, eng.config.model)
    layers = dict(params["layers"])
    layers["wi_k"] = layers["wi_k"][..., np.random.default_rng(17).permutation(layers["wi_k"].shape[-1])]
    at = slice(len(prompt) - 1, len(prompt) + 7)
    ignored = np.asarray(ref.forward(params, prompt + toks, all_tokens)[0][at])
    permuted = np.asarray(ref.forward(dict(params, layers=layers), prompt + toks, PUBLISHED)[0][at])
    assert np.median(np.abs(lps - ignored)) > 0.05
    assert np.median(np.abs(lps - permuted)) > 0.05


def test_the_reference_holds_the_systems_selected_sets_to_its_own():
    """What the benchmark's long-context topology hands the reference: a
    context in front of the prompt, and the sets the program's own scoring
    and top-k select over the indexer keys the engine CACHED for the
    sequence. A sound system overlaps the reference's sets wholly; one that
    picks other keys is answered with NaN, which the comparison reads as
    not correct."""
    import types

    from perfbench.topologies import engine_longctx

    eng = make_engine(max_batched=48)
    context, prompt = tokens(4 * TOPK + 3, seed=41), tokens(21, seed=42)
    eng.add_request(context + prompt, SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True, logprobs=True))
    req = eng.scheduler.waiting[0]
    pages = None
    while eng.has_work():
        pages = req.block_ids or pages  # the scheduler extends this list in place; a finish rebinds the field
        eng.step()
    toks, lps = list(req.output_token_ids), np.asarray(req.output_logprobs)
    n = len(context) + len(prompt) + len(toks) - 1  # the last token sampled is never fed
    keys = eng.runner.kv_cache.index[:, jnp.asarray(pages[: -(-n // eng.config.cache.page_size)])]
    topology = types.SimpleNamespace(model_cfg=eng.config.model)
    params = reference_params(eng.runner.params, eng.config.model)

    def bound(**stand_ins):
        entry = {"context": context, "cached": (keys, n),
                 "selection": engine_longctx.System.selection(topology, keys, n, **stand_ins)}
        return dict(params, bound={tuple(prompt): entry})

    nxt, _best, overlaps = ref.score(bound(), prompt + toks, PUBLISHED)
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
    np.testing.assert_allclose(lps, np.asarray(nxt)[at], atol=2e-5)
    # per layer: against an exact top-k over the cached keys, and against the reference's own sets
    assert len(overlaps) == PUBLISHED["num_hidden_layers"] and min(min(o) for o in overlaps) > 1 - 1e-6
    assert ref.bound_entry(bound(), tokens(21, seed=43)) is None  # another prompt: no context
    # A system that keeps the LOWEST scores: half of each set, at most, is the reference's.
    wrong = bound(pick=lambda scores, k: sa.select_topk(-scores, k))
    assert max(max(o) for o in ref.score(wrong, prompt + toks, PUBLISHED)[2]) < 0.6
    assert np.all(np.isnan(np.asarray(ref.forward(wrong, prompt + toks, PUBLISHED)[0])))
    assert np.all(np.isfinite(np.asarray(ref.forward(bound(), prompt + toks, PUBLISHED)[0])))


@pytest.mark.parametrize("scoring", ["the XLA map", "the kernel"])
def test_selected_sets_equal_the_references_exactly(scoring, monkeypatch):
    """Per layer: the reference's indexer queries scored against the ENGINE's
    cached plane of indexer keys (written chunk by chunk through the page
    table) by the engine's ops select exactly the reference's sets, whichever
    of the two scorings ``index_scores`` dispatches to."""
    eng = make_engine(max_batched=48)
    prompt = tokens(5 * TOPK + 3, seed=11)
    rid = eng.add_request(prompt, SamplingParams(max_tokens=64, temperature=0.0, ignore_eos=True))
    req = eng.scheduler.waiting[0]
    while req.num_computed_tokens < len(prompt) + 4:  # prefilled, four tokens decoded
        eng.step()
    n = req.num_computed_tokens
    seq = (prompt + req.output_token_ids)[:n]
    trace: list = []
    params = reference_params(eng.runner.params, eng.config.model)
    ref.forward(params, seq + [0], PUBLISHED, trace=trace)
    conf = dict(PUBLISHED, rope_scaling={k: tuple(v) if isinstance(v, list) else v
                                         for k, v in PUBLISHED["rope_scaling"].items()})
    pool = eng.runner.kv_cache
    assert isinstance(pool, sa.IndexedPool)
    page = eng.config.cache.page_size
    table = np.zeros((1, eng.runner.max_pages), np.int32)
    table[0, : len(req.block_ids)] = req.block_ids
    positions3 = jnp.tile(jnp.arange(n)[None, :], (3, 1))
    rows, kv_lens = jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32) + 1
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(trace):
            h = rc.rms_norm(layer["input"][:n], params["layers"]["input_norm"][i], conf["rms_norm_eps"])
            qi, ki, wi = ref.indexer(h, params["layers"], i, positions3, conf)
            # The engine cached what the reference computes, at the page table's slots.
            cached = np.asarray(pool.index[i])[table[0, np.arange(n) // page], np.arange(n) % page]
            np.testing.assert_allclose(cached, np.asarray(ki), atol=2e-5)
            with monkeypatch.context() as m:
                if scoring == "the kernel":
                    m.setenv("LLMD_PALLAS", "interpret")
                scores = sa.index_scores(qi, wi, pool.index[i], jnp.asarray(table), rows, kv_lens)
            mine = np.asarray(sa.select_topk(scores, TOPK))[:, :n] & np.tril(np.ones((n, n), bool))
            assert np.array_equal(mine, np.asarray(layer["selected"])[:n, :n]), f"layer {i}"
            assert mine.sum(1).tolist() == [min(t + 1, TOPK) for t in range(n)]
    eng.abort_request(rid)


def test_a_shared_tile_keeps_each_tokens_own_selection():
    """The flat kernel under the indexer's mask, on a stream that holds
    every kind of tile: a tile that lies in one row reads the row's pages
    once, and each of its 16 queries still reads ITS selected keys only —
    against the XLA reference under the same mask, and against the same
    stream laid out so that every token goes alone."""
    from llmd_tpu.ops.paged_attention import paged_attention_xla
    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(5)
    L, P, K, page, D, G = 2, 96, 2, 8, 128, 4
    tok_rows, positions, live, pt = tiled_stream(rng, page, P)
    T, S = len(tok_rows), pt.shape[1] * page
    kv_lens = np.where(live, positions + 1, 0).astype(np.int32)
    cache = jnp.asarray(rng.normal(size=(L, P, K, page, 2 * D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(T, 1, K * G, D)).astype(np.float32))
    # every token its own selection: 24 of its cached tokens, all below 24
    scores = np.where(np.arange(S)[None] < kv_lens[:, None], rng.random((T, S)), -np.inf)
    sel = sa.select_topk(jnp.asarray(scores, jnp.float32), 24)
    assert 1 < len({tuple(np.flatnonzero(row)) for row in np.asarray(sel)[16:32]})

    def run(tok_rows, pt):
        return np.asarray(flat_paged_attention_full(
            q, cache, jnp.int32(1), jnp.asarray(tok_rows), jnp.asarray(pt),
            jnp.asarray(kv_lens), interpret=True, pages_per_block=2, sel=sel,
        ))

    out = run(tok_rows, pt)
    oracle = paged_attention_xla(
        q, cache[1], jnp.asarray(pt[tok_rows]), jnp.asarray(kv_lens),
        jnp.asarray(positions[:, None]), sel=sel[:, None, :],
    )
    dense = paged_attention_xla(
        q, cache[1], jnp.asarray(pt[tok_rows]), jnp.asarray(kv_lens),
        jnp.asarray(positions[:, None]),
    )
    np.testing.assert_allclose(out[live], np.asarray(oracle)[live], atol=2e-5)
    np.testing.assert_allclose(out[live], run(*token_by_token(tok_rows, pt))[live], atol=1e-5)
    assert np.abs(out - np.asarray(dense))[live].max() > 1e-2  # the mask binds


def _decode_rows(rng, page, num_pages):
    """Sixteen sequences, one token each: no tile shares a row."""
    lens = rng.integers(1, 16 * page, size=16)
    pt = rng.permutation(num_pages)[: 16 * 16].reshape(16, 16).astype(np.int32)
    return np.arange(16, dtype=np.int32), lens.astype(np.int32), pt


def _edges(rng, page, num_pages):
    """Contexts below one page, at a page's and a block's edge (blocks of two
    pages), one past it and ending mid-block, as decode rows and as a chunk
    whose last token sits on the block's edge; then a tile of pad tokens."""
    S = 2 * page
    lens = [1, page - 1, page, page + 1, S - 1, S, S + 1, 3 * S, 3 * S + page // 2,
            5 * S - 3, 7 * page, 8 * S, 1, 2, 3, 4]
    rows = list(range(16)) + [16] * 16 + [0] * 16
    kv_lens = lens + list(range(4 * S - 15, 4 * S + 1)) + [0] * 16
    pt = rng.permutation(num_pages)[: 17 * 16].reshape(17, 16).astype(np.int32)
    return np.asarray(rows, np.int32), np.asarray(kv_lens, np.int32), pt


def _every_kind_of_tile(rng, page, num_pages):
    """``tests/flat_streams.py``: a chunk whose body fills whole tiles with a
    ragged head and tail, two rows meeting inside a tile, decode rows, a
    verify row and pad tokens."""
    tok_rows, positions, live, pt = tiled_stream(rng, page, num_pages)
    return tok_rows, np.where(live, positions + 1, 0).astype(np.int32), pt


def _the_benchmarks_call(rng, page, num_pages, n=203):
    """``perfbench/topologies/engine_longctx.py::System.selection``: a
    ONE-layer plane, a one-row table over the sequence's pages, T a multiple
    of 256, ``kv_lens`` 1..n and n from there on."""
    T = -(-n // 256) * 256
    pt = np.minimum(np.arange(-(-T // page)), -(-n // page) - 1)[None, :].astype(np.int32)
    return np.zeros(T, np.int32), np.minimum(np.arange(1, T + 1), n).astype(np.int32), pt


INDEXER_STREAMS = {
    "decode rows only": (_decode_rows, 64, np.float32),
    "every kind of tile": (_every_kind_of_tile, 64, np.float32),
    "contexts at the edges of pages and blocks, and pad tiles": (_edges, 64, np.float32),
    "the benchmark's call: a one-row table, T a multiple of 256": (_the_benchmarks_call, 64, np.float32),
    "keys a lane tile wide: the plane goes in as it is": (_every_kind_of_tile, 128, np.float32),
    "the served dtype: bfloat16 queries, weights and keys": (_every_kind_of_tile, 64, jnp.bfloat16),
    "the benchmark's call in bfloat16": (_the_benchmarks_call, 64, jnp.bfloat16),
}


@pytest.mark.parametrize("stream", sorted(INDEXER_STREAMS))
def test_the_indexer_kernel_scores_what_the_xla_map_scores(stream):
    """The Pallas indexer (interpreted) against the XLA map it replaces on the
    chip: scores equal to float32 tolerance, -inf exactly at and past each
    token's ``kv_lens``, and the same exact top-k out of both."""
    make, Di, dtype = INDEXER_STREAMS[stream]
    rng = np.random.default_rng(7)
    P, page, J, topk = 288, 8, 4, 24
    rows, kv_lens, pt = make(rng, page, P)
    T, S = len(rows), pt.shape[1] * page
    plane = jnp.asarray(rng.normal(size=(P, page, Di)), dtype)
    iq = jnp.asarray(rng.normal(size=(T, J, Di)), dtype)
    iw = jnp.asarray(rng.normal(size=(T, J)), dtype)
    args = (jnp.asarray(pt), jnp.asarray(rows), jnp.asarray(kv_lens))
    want = sa._index_scores_xla(iq, iw, plane, *args)
    got = sa.index_scores_pallas(iq, iw, plane, *args, interpret=True, pages_per_block=2)
    want, got = np.asarray(want), np.asarray(got)
    assert got.shape == (T, S) and got.dtype == np.float32
    dead = np.arange(S)[None, :] >= kv_lens[:, None]
    assert np.array_equal(np.isneginf(got), dead) and np.array_equal(np.isneginf(want), dead)
    np.testing.assert_allclose(got[~dead], want[~dead], rtol=1e-5, atol=1e-4)
    assert np.array_equal(np.asarray(sa.select_topk(jnp.asarray(got), topk)),
                          np.asarray(sa.select_topk(jnp.asarray(want), topk)))


def test_the_weights_reach_the_kernel_in_the_dtype_the_caller_rounded_them_to():
    """PR 43's fault on the chip: the benchmark's call rounds float32 weights
    to the served dtype in its own jit (``iw[:rows].astype(bfloat16)``), the
    call widened them again OUTSIDE the kernel, and XLA fused the pair into a
    slice that never rounded: the kernel scored with float32 weights and the
    exact-top-k overlap read 0.9996 for the XLA map's 0.9999997 (PERF.md
    section 6). No compiler on the CPU folds the pair, so the cause is held
    here: every operand of the ``pallas_call`` that carries queries, weights
    or keys has the dtype the caller gave, and nothing float32 goes in."""
    rng = np.random.default_rng(5)
    rows, kv_lens, pt = _the_benchmarks_call(rng, 8, 96)
    T = len(rows)
    shapes = [(T, 4, 64), (T, 4), (96, 8, 64)]
    iq, iw, plane = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    jaxpr = jax.make_jaxpr(
        lambda *a: sa.index_scores_pallas(*a, jnp.asarray(pt), jnp.asarray(rows), jnp.asarray(kv_lens))
    )(iq, iw, plane)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    floating = [v.aval.dtype for v in call.invars if jnp.issubdtype(v.aval.dtype, jnp.floating)]
    assert len(floating) == 5 and all(d == jnp.bfloat16 for d in floating), floating


def test_index_scores_takes_the_kernel_where_the_dispatch_does(monkeypatch):
    """``LLMD_PALLAS=interpret`` stands in for the chip: the call the
    benchmark and the step make takes the kernel (recorded as the plan
    ``indexer``); off the chip it takes the XLA map."""
    from llmd_tpu import ops

    rng = np.random.default_rng(3)
    rows, kv_lens, pt = _every_kind_of_tile(rng, 8, 96)
    plane = jnp.asarray(rng.normal(size=(96, 8, 64)).astype(np.float32))
    iq = jnp.asarray(rng.normal(size=(len(rows), 4, 64)).astype(np.float32))
    iw = jnp.asarray(rng.normal(size=(len(rows), 4)).astype(np.float32))
    args = (jnp.asarray(pt), jnp.asarray(rows), jnp.asarray(kv_lens))

    def scored():
        plans: dict = {}
        with ops.record_plans(plans):
            one = sa.index_scores(iq, iw, plane, *args)
        return plans["indexer"], np.asarray(one)

    plan_x, one_x = scored()
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    plan_k, one_k = scored()
    assert plan_x == {"xla:platform"} and plan_k == {"pallas"}
    finite = np.isfinite(one_x)
    assert np.array_equal(finite, np.isfinite(one_k))
    np.testing.assert_allclose(one_k[finite], one_x[finite], rtol=1e-5, atol=1e-4)


def test_select_topk_is_exact_and_breaks_ties_to_the_lower_position():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(6, 200)).astype(np.float32)
    x[1, 50:] = -np.inf  # fewer finite scores than k: all of them
    x[2, ::3] = 0.0  # a ReLU's exact zeros, some of them -0.0
    x[2, ::6] = -0.0
    x[3] = np.abs(x[3]) * (rng.random(200) < 0.3)  # the threshold falls on a run of zeros
    x[4] = 1.0  # all equal
    got = np.asarray(sa.select_topk(jnp.asarray(x), 40))
    for row, g in zip(x, got):
        want = np.zeros(200, bool)
        want[np.argsort(-row, kind="stable")[:40]] = True  # stable: lower position first
        want |= ~np.isfinite(row) & (np.isfinite(row).sum() <= 40)
        assert np.array_equal(g & np.isfinite(row), want & np.isfinite(row))


def test_below_topk_the_indexer_changes_nothing():
    """While no more than top-k tokens are cached every token is selected:
    the logits are those of the same weights with the indexer removed."""
    prompts = [tokens(20, seed=1), tokens(9, seed=2)]
    sparse = greedy(make_engine(), prompts, max_tokens=8)
    plain_cfg = get_model_config("tiny-dsa", indexer_topk=0, indexer_num_heads=0, indexer_head_dim=0)
    plain = greedy(make_engine(model=plain_cfg), prompts, max_tokens=8)
    for (t_s, l_s), (t_p, l_p) in zip(sparse, plain):
        assert t_s == t_p
        np.testing.assert_allclose(l_s, l_p, atol=1e-6)


def _cold(prompt, n=6):
    (toks, lps), = greedy(make_engine(), [prompt], max_tokens=n)
    return toks, lps


def test_a_prefix_cache_hit_carries_the_indexer_keys():
    """A second request over the same long prefix computes only its tail; its
    log-probs equal a cold run's, so the shared pages held the prefix's
    indexer keys (a hit that dropped them would select from zeros)."""
    shared = tokens(4 * TOPK, seed=5)
    a, b = shared + tokens(10, seed=6), shared + tokens(13, seed=7)
    eng = make_engine()
    greedy(eng, [a])
    (toks, lps), = greedy(eng, [b])
    req_cached = eng.stats.prefix_hit_ratio
    assert req_cached > 0
    cold_t, cold_l = _cold(b)
    assert toks == cold_t
    np.testing.assert_allclose(lps, cold_l, atol=2e-5)


def test_preemption_and_resume_give_a_cold_runs_logits():
    """A pool too small for three long sequences forces preemption and
    recompute; every stream still equals its cold run."""
    prompts = [tokens(3 * TOPK + i, seed=20 + i) for i in range(3)]
    eng = make_engine(num_blocks=40)  # 40 pages of 8 admit the three prompts (36) and not their growth (45)
    outs = greedy(eng, prompts, max_tokens=24)
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    for p, (toks, lps) in zip(prompts, outs):
        cold_t, cold_l = _cold(p, 24)
        assert toks == cold_t
        np.testing.assert_allclose(lps, cold_l, atol=2e-5)


def test_pages_reused_after_a_free_hold_the_new_sequences_keys():
    """With prefix caching off and a pool that fits one sequence, the second
    sequence lands on the first one's pages: stale indexer keys there would
    change its selection."""
    eng = LLMEngine(EngineConfig(
        model=get_model_config("tiny-dsa"),
        cache=CacheConfig(page_size=8, num_blocks=20, dtype="float32", enable_prefix_caching=False),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
    ))
    first, second = tokens(4 * TOPK, seed=30), tokens(4 * TOPK + 5, seed=31)
    greedy(eng, [first])
    (toks, lps), = greedy(eng, [second])
    cold_t, cold_l = _cold(second)
    assert toks == cold_t
    np.testing.assert_allclose(lps, cold_l, atol=2e-5)


def test_mrope_with_equal_rows_is_the_plain_rope():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(40, 3, 16)), jnp.float32)
    pos = jnp.arange(40) * 7
    scaling = {"mrope_section": (2, 3, 3), "rope_type": "default"}
    same = ref.rope3(x, jnp.tile(pos[None, :], (3, 1)), 10000.0, scaling)
    np.testing.assert_allclose(same, rc.rope(x, pos, 10000.0, None), atol=1e-6)
    # ...and rows that differ do matter: frequency f reads its own section's row.
    other = ref.rope3(x, jnp.stack([pos, pos + 3, pos + 5]), 10000.0, scaling)
    assert float(jnp.max(jnp.abs(other - same))) > 1e-2
    assert ref.sections_for(64, {"mrope_section": [16, 24, 24]}) == (16, 24, 24)
    assert ref.sections_for(32, {"mrope_section": [16, 24, 24]}) == (8, 12, 12)
    # The program takes the published scaling (``rope_type: default`` WITH
    # ``mrope_section``) as the ordinary rope.
    from llmd_tpu.models.common import rope_tables

    published = json.loads(CONF_FILE.read_text())["rope_scaling"]
    for got, want in zip(rope_tables(pos, 16, 1e7, published), rope_tables(pos, 16, 1e7, None)):
        np.testing.assert_array_equal(got, want)


def test_the_registry_preset_is_the_published_configuration():
    """``perfbench/topologies/engine.py::HF_TO_MODEL`` has no entry for
    ``sa_config``: those widths reach the program through the preset, so the
    preset is held to the configuration file, key by key."""
    conf = json.loads(CONF_FILE.read_text())
    cfg = get_model_config(conf["registry"])
    sa_conf = conf["sa_config"]
    assert (cfg.indexer_topk, cfg.indexer_num_heads, cfg.indexer_head_dim) == (
        sa_conf["topk"], sa_conf["indexer_num_heads"], sa_conf["indexer_head_dim"])
    assert sa_conf["indexer_num_kv_heads"] == 1  # one shared key per token: the plane's shape
    want = {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rope_scaling": cfg.rope_scaling,
        "rms_norm_eps": cfg.rms_norm_eps, "max_position_embeddings": cfg.max_model_len,
        "num_experts": cfg.num_experts, "num_local_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size, "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_word_embeddings, "attention_bias": cfg.attention_bias,
    }
    assert {k: conf[k] for k in want} == want
    assert cfg.num_layers == 48 and conf["num_hidden_layers"] == 6 and list(conf["reduced"]) == ["num_hidden_layers"]
    assert cfg.qk_norm and cfg.sliding_window == 0 and conf["sliding_window"] is None
    # Every leaf of the indexer is in the parameter tree at the published widths.
    shapes = jax.eval_shape(lambda k: llama.init_params(dataclasses.replace(cfg, num_layers=1), k),
                            jax.random.key(0))["layers"]
    assert shapes["wi_q"].shape == (1, 2048, 16 * 64) and shapes["wi_k"].shape == (1, 2048, 64)
    assert shapes["wi_w"].shape == (1, 2048, 16) and shapes["wi_k_norm"].shape == (1, 64)


REFUSED = {
    "speculation": dict(scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64,
                                                  speculative_ngram=True)),
    "fused decode windows": dict(scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64,
                                                           decode_window=4)),
    "the bucketed step": dict(scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64,
                                                        ragged_qlens=False)),
    "int8 KV": dict(cache=CacheConfig(page_size=8, num_blocks=64, dtype="int8")),
    "offload": dict(offload=OffloadConfig(enabled=True)),
    "P/D producer": dict(kv_role="kv_producer"),
    "P/D consumer": dict(kv_role="kv_consumer"),
    "a sharded mesh": dict(parallel=ParallelConfig(tensor_parallel_size=2)),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_would_drop_the_indexer_plane_is_refused_at_start(what):
    kw = dict(model=get_model_config("tiny-dsa"),
              cache=CacheConfig(page_size=8, num_blocks=64, dtype="float32"),
              scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64))
    kw.update(REFUSED[what])
    with pytest.raises(ValueError, match="sparse attention"):
        LLMEngine(EngineConfig(**kw))


@pytest.mark.parametrize("field, value", [("sliding_window", 64), ("kv_lora_rank", 32),
                                          ("attention_sinks", True), ("num_lora_adapters", 2)])
def test_a_model_cannot_pair_the_indexer_with_another_attention(field, value):
    with pytest.raises(ValueError, match="sparse attention"):
        get_model_config("tiny-dsa", **{field: value})


def test_embeddings_and_page_staging_refuse_the_sparse_pool():
    eng = make_engine()
    with pytest.raises(NotImplementedError, match="flat step"):
        eng.runner.run_embed([[1, 2, 3]])
    with pytest.raises(RuntimeError, match="indexer"):
        eng.runner.copy_pages_on_device([0], [1])
