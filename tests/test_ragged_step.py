"""Flattened-token (`cu_q_lens`) step: parity, padding, and kernels.

The correctness bar (same as the unified step and the split engine
before it): greedy AND seeded streams from the flattened-token program
are byte-identical to the bucketed paths across chunked prefill,
preemption, prefix-cache hits, seeded sampling, speculative verify with
MIXED per-row depths, and async rollback. On top: the padding-waste
ratio must land strictly below the bucketed path's, the step must stay
one-readback, and the window=1 compile surface must SHRINK.
"""

import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams
from tests.flat_streams import tiled_stream, token_by_token


def make_engine(
    ragged,
    unified=True,
    spec=False,
    async_s=False,
    num_blocks=64,
    page=4,
    max_batched=32,
    max_seqs=8,
    seed=0,
    swa=0,
    dtype="float32",
    mla=False,
    **model_kw,
) -> LLMEngine:
    if mla:
        model_kw.update(
            kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16,
        )
    cfg = EngineConfig(
        model=tiny_model_config(**model_kw),
        cache=CacheConfig(
            page_size=page, num_blocks=num_blocks, dtype=dtype,
            swa_ring=bool(swa),
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=max_batched,
            unified_step=unified, ragged_qlens=ragged,
            speculative_ngram=spec,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
    )
    return LLMEngine(cfg, _synchronous_step=not async_s)


PROMPTS = [
    [1, 5, 9, 13, 2, 8],
    [3, 3, 7, 1],
    [1, 5, 9, 13, 2, 8, 4, 4, 4, 4, 6, 6, 6, 6, 11],
]
GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


def _toks(out):
    return list(out.values())


# --------------------------------------------------------------------- #
# byte parity: ragged on vs ragged off vs split


def test_greedy_parity_vs_bucketed_and_split():
    flat = make_engine(True).generate(PROMPTS, GREEDY)
    bucketed = make_engine(False).generate(PROMPTS, GREEDY)
    split = make_engine(False, unified=False).generate(PROMPTS, GREEDY)
    assert _toks(flat) == _toks(bucketed) == _toks(split)


def test_chunked_prefill_parity():
    long_prompt = list(np.random.default_rng(0).integers(0, 256, size=60))
    ref = make_engine(False, max_batched=16).generate([long_prompt], GREEDY)
    flat = make_engine(True, max_batched=16).generate([long_prompt], GREEDY)
    assert _toks(ref) == _toks(flat)


def test_seeded_parity():
    sps = [
        SamplingParams(temperature=0.9, max_tokens=8, seed=41 + i)
        for i in range(len(PROMPTS))
    ]
    ref = make_engine(False, seed=3).generate(PROMPTS, sps)
    flat = make_engine(True, seed=3).generate(PROMPTS, sps)
    assert _toks(ref) == _toks(flat)


def test_preemption_parity():
    """Tight page pool forces recompute-preemption mid-run."""
    prompts = [list(p) for p in PROMPTS] + [[9, 9, 2, 4, 4, 1, 7]]
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    ref = make_engine(False, num_blocks=14)
    flat = make_engine(True, num_blocks=14)
    r, f = ref.generate(prompts, sp), flat.generate(prompts, sp)
    assert _toks(r) == _toks(f)
    assert flat.scheduler.num_preemptions > 0, "pool too big to preempt"


def test_prefix_cache_hit_parity():
    """The second identical prompt hits the prefix cache; the flat step
    must start it from the cached position exactly like the bucketed
    step."""
    p = [5, 5, 1, 2, 3, 4, 8, 8, 6, 6, 2, 2]
    outs = []
    for ragged in (False, True):
        eng = make_engine(ragged)
        a = eng.generate([p], GREEDY)
        b = eng.generate([p], GREEDY)
        assert eng.allocator.hit_ratio() > 0, "no prefix hit exercised"
        outs.append((_toks(a), _toks(b)))
    assert outs[0] == outs[1]


def test_swa_ring_parity():
    """Sliding-window ring engines: the flat run plan carries a second
    phys column for the ring pool."""
    prompts = [list(p) for p in PROMPTS]
    ref = make_engine(False, swa=1, sliding_window=8).generate(prompts, GREEDY)
    flat = make_engine(True, swa=1, sliding_window=8).generate(prompts, GREEDY)
    assert _toks(ref) == _toks(flat)


def test_int8_pool_parity():
    ref = make_engine(False, dtype="int8").generate(PROMPTS, GREEDY)
    flat = make_engine(True, dtype="int8").generate(PROMPTS, GREEDY)
    assert _toks(ref) == _toks(flat)


def test_async_rollback_parity(unforeseen_finishes):
    """max_tokens finishes land late under async stepping; the rows
    dispatched for them meanwhile (wasted, dropped at their commit) must
    leave the stream byte-identical."""
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    ref = make_engine(False, async_s=True)
    flat = make_engine(True, async_s=True)
    sync = make_engine(True)
    r = ref.generate([list(p) for p in PROMPTS], sp)
    f = flat.generate([list(p) for p in PROMPTS], sp)
    s = sync.generate([list(p) for p in PROMPTS], sp)
    assert _toks(r) == _toks(f) == _toks(s)
    assert flat.stats.async_wasted_rows_total > 0, "no late finish exercised"


# --------------------------------------------------------------------- #
# speculative decoding: per-row adaptive verify depth

REPETITIVE = [7, 8, 9] * 10 + [7, 8]
RANDOMISH = [2, 9, 4, 1, 5, 3, 11, 6]


def test_spec_parity_mixed_depths():
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    prompts = [list(REPETITIVE), list(RANDOMISH)]
    ref = make_engine(False, spec=True, seed=1).generate(prompts, sp)
    eng = make_engine(True, spec=True, seed=1)
    flat = eng.generate(prompts, sp)
    assert _toks(ref) == _toks(flat)
    # The repetitive row drafts deep while the other rides shallow: the
    # depth histogram must show MORE than one populated bucket.
    hist = eng.stats.spec_row_depth_hist
    assert sum(1 for c in hist if c) >= 2, hist


def test_spec_two_depths_one_dispatch():
    """THE adaptive-depth pin: a step whose decode rows carry DIFFERENT
    verify depths (one hot-draft row, one shallow row) dispatches as
    ONE device program — which the split engine's verify/decode split
    structurally cannot do."""
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    eng = make_engine(True, spec=True, seed=1)
    seen = []
    orig = eng.runner.dispatch_staged_unified

    def spy(staged):
        if staged.flat and staged.decodes:
            depths = {
                1 + len(s.draft_tokens or []) for s in staged.decodes
            }
            seen.append(depths)
        return orig(staged)

    eng.runner.dispatch_staged_unified = spy
    out = eng.generate([list(REPETITIVE), list(RANDOMISH)], sp)
    ref = make_engine(False, unified=False, spec=True, seed=1).generate(
        [list(REPETITIVE), list(RANDOMISH)], sp
    )
    assert _toks(ref) == _toks(out)
    assert any(len(d) >= 2 for d in seen), (
        f"no single dispatch carried two distinct verify depths: {seen}"
    )


def test_straddle_rows_fit_run_plan():
    """Run-plan width regression: rows whose multi-token spans all start
    at the LAST in-page slot emit one more run than their token count
    alone implies (a 2-token row starting at slot page-1 touches two
    pages), so a batch of them carries 2*rows runs — more than the
    original B + ceil(T/page) bound held. _fill_flat_runs must place
    every run inside the traced width (and the lockstep payload spec
    must agree), not die on the straddle-heavy step."""
    from types import SimpleNamespace

    eng = make_engine(True, spec=True)
    r = eng.runner
    page = r.page
    B = r.flat_rows
    n = 8
    a = {
        "row_start": np.zeros(B, np.int32),
        "pos0": np.zeros(B, np.int32),
        "qlens": np.zeros(B, np.int32),
        "page_table": np.zeros((B, r.max_pages), np.int32),
    }
    for i in range(n):  # every row: 2 tokens starting at slot page-1
        a["row_start"][i] = 2 * i
        a["pos0"][i] = page - 1
        a["qlens"][i] = 2
    a["row_start"][n:] = 2 * n
    T = 2 * n  # == a 16-token flat bucket
    staged = SimpleNamespace(B=B, T=T, row_seqs=[None] * n, arrays=a)
    r._fill_flat_runs(staged, a)  # old bound: IndexError at run n+something
    assert int(a["wcnt"].sum()) == 2 * n
    assert (a["wcnt"] > 0).sum() == 2 * n  # two runs per straddling row
    # the fill width and the lockstep payload spec derive the SAME bound
    spec = {
        name: shp
        for name, shp, _ in r._payload_spec(11, B, T)  # _OP_FLAT
    }
    assert spec["wcnt"] == a["wcnt"].shape


def test_a_chunks_sub_rows_share_the_run_of_the_page_one_ends_in():
    """A chunk that starts inside a page and is split into sub-rows of
    the row cap ends a sub-row inside a page too. Its next sub-row goes on
    in the SAME run: two runs of one launch on one page would lose the
    first one's rows (the write pipeline loads run r+1's page before run
    r's is stored)."""
    from types import SimpleNamespace

    eng = make_engine(True, page=16, num_blocks=32, max_batched=256, max_model_len=512)
    r = eng.runner
    page, cap, B = r.page, r.unified_row_cap, r.flat_rows
    p0, w = 122, 150  # positions [122, 272) as sub-rows of 64, 64 and 22 tokens behind 6 decode tokens
    a = {
        "row_start": np.zeros(B, np.int32), "pos0": np.zeros(B, np.int32),
        "qlens": np.zeros(B, np.int32),
        "page_table": np.tile(np.arange(100, 100 + r.max_pages, dtype=np.int32), (B, 1)),
    }
    widths = [cap, cap, w - 2 * cap]
    for i, wi in enumerate(widths):
        a["row_start"][i], a["pos0"][i], a["qlens"][i] = 6 + i * cap, p0 + i * cap, wi
    a["row_start"][len(widths):] = 6 + w
    staged = SimpleNamespace(B=B, T=160, row_seqs=[None] * len(widths), arrays=a)
    r._fill_flat_runs(staged, a)
    live = a["wcnt"] > 0
    assert int(a["wcnt"].sum()) == w and len(set(a["wphys"][live])) == int(live.sum())  # a page a run
    first, last = p0 // page, (p0 + w - 1) // page
    assert list(a["wphys"][live]) == list(range(100 + first, 100 + last + 1))
    # every token lands where its position says: page row off + j <- stream token src - page + off + j
    for src, off, cnt, phys in zip(a["wsrc"][live], a["woff"][live], a["wcnt"][live], a["wphys"][live]):
        stream0 = src - page + off
        assert (phys - 100) * page + off == p0 + (stream0 - 6) and off + cnt <= page


def test_interpreted_flat_write_keeps_every_row_of_a_split_chunk(monkeypatch):
    """The engine's pages after a prompt that the budget cuts into chunks
    which start inside a page: with the Pallas write interpreted, no
    cached row is left as the pool was made (zeros), and the rows are the
    XLA scatter's."""
    prompt = list(np.random.default_rng(5).integers(0, 256, size=200))
    sp = SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True)

    def cached_rows(eng):
        eng.generate([prompt], sp)
        pages = eng.allocator.lookup_cached_prefix(prompt)
        assert len(pages) == len(prompt) // 16
        return np.asarray(eng.runner.kv_cache[:, np.asarray(pages)])  # [L, pages, K, page, 2D]

    kw = dict(page=16, num_blocks=32, max_batched=72, max_model_len=512, head_dim=128, num_heads=2, num_kv_heads=1)
    want = cached_rows(make_engine(True, **kw))
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    eng = make_engine(True, **kw)
    got = cached_rows(eng)
    assert eng.runner.kernel_plans["flat_kv_write"] == {"pallas"}  # interpreted
    assert np.abs(got).sum(axis=(2, 4)).min() > 0  # chunks [0, 72), [72, 144), ..: sub-rows end at 136, 208
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_spec_seeded_parity():
    sp = [
        SamplingParams(temperature=0.8, max_tokens=10, seed=7),
        SamplingParams(temperature=0.8, max_tokens=10, seed=19),
    ]
    prompts = [list(REPETITIVE), list(RANDOMISH)]
    ref = make_engine(False, spec=True, seed=2).generate(prompts, sp)
    flat = make_engine(True, spec=True, seed=2).generate(prompts, sp)
    assert _toks(ref) == _toks(flat)


# --------------------------------------------------------------------- #
# padding waste, readbacks, compile surface


def _mixed_run(ragged):
    """Staggered arrivals keep prefill chunks and decode rows mixed."""
    eng = make_engine(ragged, max_batched=64, max_seqs=8, num_blocks=128)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, 256, size=n)) for n in
               (40, 9, 22, 5, 31, 14, 7, 18)]
    sp = SamplingParams(temperature=0.0, max_tokens=10, ignore_eos=True)
    for i, p in enumerate(prompts[:4]):
        eng.add_request(p, sp)
    outs = {}
    step = 0
    while eng.has_work() or prompts[4:]:
        if step == 2 and len(prompts) > 4:
            for p in prompts[4:]:
                eng.add_request(p, sp)
            prompts = prompts[:4]
        for o in eng.step():
            outs.setdefault(o.request_id, []).extend(o.new_token_ids)
        step += 1
        assert step < 500
    return eng, outs


def test_padding_waste_drops_and_streams_match():
    """The acceptance bar: the mixed-batch padded/live token ratio of
    the flat path lands STRICTLY below the bucketed path's, with
    byte-identical greedy streams."""
    bucketed, out_b = _mixed_run(False)
    flat, out_f = _mixed_run(True)
    assert list(out_b.values()) == list(out_f.values())
    ratio_b = bucketed.stats.padded_tokens_total / max(
        1, bucketed.stats.live_tokens_total
    )
    ratio_f = flat.stats.padded_tokens_total / max(
        1, flat.stats.live_tokens_total
    )
    assert ratio_f < ratio_b, (ratio_f, ratio_b)
    # The flat stream pads only to the 16-token T granule.
    assert ratio_f < 0.6 < ratio_b, (ratio_f, ratio_b)


def test_one_readback_per_step():
    eng = make_engine(True)
    calls = {"n": 0}
    orig = eng.runner.wait_step

    def counting(prefill, decode, unified=None):
        calls["n"] += 1
        return orig(prefill, decode, unified)

    eng.runner.wait_step = counting
    eng.generate(PROMPTS, GREEDY)
    assert calls["n"] == eng.stats.engine_steps_total
    # and the flat engine dispatches exactly one program per step
    assert eng.stats.step_dispatches_total == eng.stats.engine_steps_total


def test_window1_shape_families_shrink():
    """The compile-surface pin: one flattened T-bucketed family replaces
    the bucketed unified (rows x Q x T) cross-product plus the split
    prefill/verify families — and warmup compiles fewer programs."""
    flat = make_engine(True)
    bucketed = make_engine(False)
    assert (
        flat.runner.window1_shape_families()
        < bucketed.runner.window1_shape_families()
    )
    assert flat.runner.warmup() < bucketed.runner.warmup()
    # spec engines shed the one-shot verify family too
    flat_s = make_engine(True, spec=True)
    buck_s = make_engine(False, spec=True)
    assert (
        flat_s.runner.window1_shape_families()
        < buck_s.runner.window1_shape_families()
    )
    assert flat_s.runner.warmup() < buck_s.runner.warmup()


def test_flat_t_buckets_cover_budget():
    eng = make_engine(True, max_batched=40)
    bks = eng.runner.flat_t_buckets
    assert bks[0] == 16 and all(b % 16 == 0 for b in bks)
    assert bks[-1] >= 40
    assert eng.runner.flat_rows == eng.runner.unified_row_buckets[-1]


def test_mla_keeps_bucketed_layout():
    eng = make_engine(True, mla=True)
    assert eng.runner._flat is None
    out = eng.generate(PROMPTS, GREEDY)
    ref = make_engine(False, mla=True).generate(PROMPTS, GREEDY)
    assert _toks(out) == _toks(ref)


# --------------------------------------------------------------------- #
# kernel parity (interpret mode): the flat write runs + row-lookup
# attention against the XLA oracles


def _flat_layout(rng, page=8, rows=((3, 5), (9, 1), (0, 11))):
    """(rows of (pos0, qlen)) -> packed stream layout + runs."""
    starts, qlens, pos0 = [], [], []
    t = 0
    for p0, w in rows:
        starts.append(t)
        qlens.append(w)
        pos0.append(p0)
        t += w
    T = t + 3  # pad tokens
    tok_rows = np.zeros(T, np.int32)
    positions = np.zeros(T, np.int32)
    live = np.zeros(T, bool)
    t = 0
    for r, (p0, w) in enumerate(rows):
        for j in range(w):
            tok_rows[t] = r
            positions[t] = p0 + j
            live[t] = True
            t += 1
    tok_rows[t:] = len(rows) - 1
    runs = [[], [], [], []]  # src, phys_pageidx, off, cnt (phys filled later)
    for r, (p0, w) in enumerate(rows):
        consumed = 0
        while consumed < w:
            p = p0 + consumed
            pg, o = p // page, p % page
            take = min(page - o, w - consumed)
            runs[0].append(page + starts[r] + consumed - o)
            runs[1].append((r, pg))
            runs[2].append(o)
            runs[3].append(take)
            consumed += take
    return T, tok_rows, positions, live, runs


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_flat_write_kernel_matches_xla_scatter(dtype):
    """Byte-identical to the XLA scatter for every pool dtype: the kernel
    fetches each run's tile-aligned window (8, 16 or 32 rows by dtype) and
    rotates it into place as 32-bit values, which must lose nothing."""
    import jax.numpy as jnp

    from llmd_tpu.ops.kv_write import write_kv_pages_flat_full
    from llmd_tpu.ops.paged_attention import write_kv_pages

    rng = np.random.default_rng(0)
    L, P, K, page, D = 2, 24, 2, 8, 128

    def values(shape):
        if dtype == "int8":
            return jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    cache = values((L, P, K, page, 2 * D))
    # row 1 straddles pages (pos0=3, qlen=11 crosses two page boundaries)
    T, tok_rows, positions, live, runs = _flat_layout(
        rng, page=page, rows=((3, 11), (17, 1), (0, 5))
    )
    pt = rng.permutation(P - 2)[: 3 * 4].reshape(3, 4).astype(np.int32)
    src = np.asarray(runs[0] + [0], np.int32)
    phys = np.asarray(
        [pt[r, pg] for r, pg in runs[1]] + [0], np.int32
    )
    off = np.asarray(runs[2] + [0], np.int32)
    cnt = np.asarray(runs[3] + [0], np.int32)  # trailing pad run
    kv_new = values((T, K, 2 * D))
    out = write_kv_pages_flat_full(
        cache, kv_new, jnp.int32(1), jnp.asarray(src),
        jnp.asarray(phys), jnp.asarray(off), jnp.asarray(cnt),
        interpret=True,
    )
    oracle = write_kv_pages(
        cache[1],
        kv_new[:, None, :, :D],
        kv_new[:, None, :, D:],
        jnp.asarray(pt[tok_rows]),
        jnp.asarray(positions[:, None]),
        jnp.asarray(live[:, None]),
    )

    def bits(a):
        a = np.asarray(a)
        return a.view(f"u{a.dtype.itemsize}")

    np.testing.assert_array_equal(bits(out[1]), bits(oracle))
    np.testing.assert_array_equal(bits(out[0]), bits(cache[0]))
    assert not np.array_equal(bits(oracle), bits(cache[1]))


def test_flat_attention_kernel_matches_xla():
    import jax.numpy as jnp

    from llmd_tpu.ops.paged_attention import paged_attention_xla
    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(1)
    L, P, K, page, D, G = 2, 24, 2, 8, 128, 3
    H = K * G
    cache = jnp.asarray(
        rng.normal(size=(L, P, K, page, 2 * D)).astype(np.float32)
    )
    T, tok_rows, positions, live, _ = _flat_layout(rng, page=page)
    pt = rng.permutation(P)[: 3 * 4].reshape(3, 4).astype(np.int32)
    kv_lens = np.where(live, positions + 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, 1, H, D)).astype(np.float32))
    out = flat_paged_attention_full(
        q, cache, jnp.int32(0), jnp.asarray(tok_rows), jnp.asarray(pt),
        jnp.asarray(kv_lens), interpret=True,
    )
    oracle = paged_attention_xla(
        q, cache[0], jnp.asarray(pt[tok_rows]), jnp.asarray(kv_lens),
        jnp.asarray(positions[:, None]),
    )
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(oracle)[live], atol=2e-5
    )


def test_flat_attention_kernel_int8_scales_match_xla():
    """Int8-pool flat attention: the per-ROW f16 scale plane indexed
    through the scalar-prefetched row map must match the XLA oracle's
    per-token dequant."""
    import jax.numpy as jnp

    from llmd_tpu.ops.paged_attention import paged_attention_xla
    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(2)
    L, P, K, page, D, G = 2, 24, 2, 8, 128, 2
    H = K * G
    cache = jnp.asarray(
        rng.integers(-127, 128, size=(L, P, K, page, 2 * D)).astype(np.int8)
    )
    # Pool-layout scales: f32 values ON the f16 grid (the quant_kv
    # contract the lossless f16 wire cast relies on).
    scales = jnp.asarray(
        rng.uniform(0.01, 0.1, size=(L, P, K, page, 2))
        .astype(np.float16)
        .astype(np.float32)
    )
    T, tok_rows, positions, live, _ = _flat_layout(rng, page=page)
    pt = rng.permutation(P)[: 3 * 4].reshape(3, 4).astype(np.int32)
    kv_lens = np.where(live, positions + 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, 1, H, D)).astype(np.float32))
    out = flat_paged_attention_full(
        q, cache, jnp.int32(1), jnp.asarray(tok_rows), jnp.asarray(pt),
        jnp.asarray(kv_lens), interpret=True, scales=scales,
    )
    oracle = paged_attention_xla(
        q, cache[1], jnp.asarray(pt[tok_rows]), jnp.asarray(kv_lens),
        jnp.asarray(positions[:, None]), scales=scales[1],
    )
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(oracle)[live], atol=2e-2, rtol=1e-2
    )


def flat_attention_grid(**kw):
    """The grid of the flat attention's Pallas call for a 64-token stream."""
    import jax
    import jax.numpy as jnp

    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    T, K, G, D, page = 64, 2, 2, 128, 8
    jaxpr = jax.make_jaxpr(
        lambda q, kv, r, pt, kl: flat_paged_attention_full(
            q, kv, jnp.int32(0), r, pt, kl, interpret=True, **kw
        )
    )(
        jnp.zeros((T, 1, K * G, D)), jnp.zeros((1, 8, K, page, 2 * D)),
        jnp.zeros(T, jnp.int32), jnp.zeros((2, 4), jnp.int32),
        jnp.zeros(T, jnp.int32),
    )
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    return call.params["grid_mapping"].grid


@pytest.mark.parametrize("what", ["plain", "sinks", "int8"])
def test_flat_attention_tiles_match_xla_and_the_token_by_token_pass(what):
    """A tile that lies in one row reads the row's pages once for its 16
    queries; every other tile goes token by token. Both against the XLA
    reference, and the tiled stream against the same stream laid out so
    that no tile is shared (the same keys in the same blocks for each
    query row: the two agree to rounding of the matmuls' row blocking)."""
    import jax.numpy as jnp

    from llmd_tpu.ops.paged_attention import paged_attention_xla
    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(3)
    L, P, K, page, D, G = 2, 96, 2, 8, 128, 4
    H = K * G
    tok_rows, positions, live, pt = tiled_stream(rng, page, P)
    T = len(tok_rows)
    kv_lens = np.where(live, positions + 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, 1, H, D)).astype(np.float32))
    kw, atol = {}, 2e-5
    if what == "int8":
        cache = jnp.asarray(
            rng.integers(-127, 128, size=(L, P, K, page, 2 * D)).astype(np.int8)
        )
        kw["scales"] = jnp.asarray(
            rng.uniform(0.01, 0.1, size=(L, P, K, page, 2))
            .astype(np.float16).astype(np.float32)
        )
        atol = 2e-2
    else:
        cache = jnp.asarray(
            rng.normal(size=(L, P, K, page, 2 * D)).astype(np.float32)
        )
    if what == "sinks":
        kw["sinks"] = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))

    def run(tok_rows, pt):
        return np.asarray(flat_paged_attention_full(
            q, cache, jnp.int32(1), jnp.asarray(tok_rows), jnp.asarray(pt),
            jnp.asarray(kv_lens), interpret=True, pages_per_block=2, **kw,
        ))

    out = run(tok_rows, pt)
    alone = run(*token_by_token(tok_rows, pt))
    oracle = paged_attention_xla(
        q, cache[1], jnp.asarray(pt[tok_rows]), jnp.asarray(kv_lens),
        jnp.asarray(positions[:, None]),
        **{k: (v[1] if k == "scales" else v) for k, v in kw.items()},
    )
    rtol = 1e-2 if what == "int8" else 1e-6
    np.testing.assert_allclose(out[live], np.asarray(oracle)[live], atol=atol, rtol=rtol)
    np.testing.assert_allclose(out[live], alone[live], atol=1e-5, rtol=1e-5)
    assert np.isfinite(out).all() and not out[~live].any()  # pad tokens read nothing


def test_a_token_shard_that_ends_inside_a_tile_goes_token_by_token():
    """Under the ``shard`` plan a device's T need not be a multiple of 16:
    the short last tile is taken token by token (its block's tail is
    nobody's), the whole tiles before it as ever."""
    import jax.numpy as jnp

    from llmd_tpu.ops.paged_attention import paged_attention_xla
    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(4)
    L, P, K, page, D, G = 1, 32, 2, 8, 128, 2
    T = 40  # two whole tiles of one row, and eight tokens
    tok_rows = np.zeros(T, np.int32)
    positions = 9 + np.arange(T, dtype=np.int32)
    pt = rng.permutation(P)[:8].reshape(1, 8).astype(np.int32)
    cache = jnp.asarray(rng.normal(size=(L, P, K, page, 2 * D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(T, 1, K * G, D)).astype(np.float32))
    out = flat_paged_attention_full(
        q, cache, jnp.int32(0), jnp.asarray(tok_rows), jnp.asarray(pt),
        jnp.asarray(positions + 1), interpret=True, pages_per_block=2,
    )
    oracle = paged_attention_xla(
        q, cache[0], jnp.asarray(pt[tok_rows]), jnp.asarray(positions + 1),
        jnp.asarray(positions[:, None]),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=2e-5)


def test_a_window_call_rides_the_grid_of_tiles():
    """ONE grid for the flat stream: a sliding-window call (the argument the
    caller already passes) runs T / 16 programs like the full-attention
    call, so a chunk's window is read once a tile, not once a token."""
    import jax.numpy as jnp

    assert flat_attention_grid() == (4,)
    assert flat_attention_grid(window=jnp.int32(16)) == (4,)


def test_calls_of_one_shape_share_one_trace_of_the_kernel():
    """A step program calls the flat kernel once a layer of its cycle body
    (three sliding layers and a full one, say) and once more in every other
    program of the same T. The call is jitted INLINE: calls that agree in
    shapes and options reuse one traced kernel (and one lowering of it), so
    a T bucket's first call pays for each distinct call once, not once a
    layer: what ``setup_s`` holds of the shape ladder."""
    import jax
    import jax.numpy as jnp

    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    T, K, G, D, page = 32, 2, 2, 128, 8

    def step(q, kv, r, pt, kl):
        for layer in range(3):
            q = q + flat_paged_attention_full(
                q, kv, jnp.int32(layer), r, pt, kl, interpret=True,
                window=jnp.int32(16),
            )
        return q + flat_paged_attention_full(
            q, kv, jnp.int32(3), r, pt, kl, interpret=True
        )

    jaxpr = jax.make_jaxpr(step)(
        jnp.zeros((T, 1, K * G, D)), jnp.zeros((4, 8, K, page, 2 * D)),
        jnp.zeros(T, jnp.int32), jnp.zeros((2, 4), jnp.int32),
        jnp.zeros(T, jnp.int32),
    )
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 4  # inlined: no call boundary in the program
    assert len({id(e.params["jaxpr"]) for e in calls}) == 2


def test_shared_tile_tokens_count_what_the_stream_lays_out():
    """``attn_shared_tile_tokens_total``: of a flat step's live tokens,
    those in 16-token granules of the stream that hold one row only. Two
    prompts prefilled in one step lie at [0, 40) and [40, 70): granules
    0-1 of the first (32 tokens) and granule [48, 64) of the second (16);
    the ragged ends and every decode-only step count nothing."""
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(0, 256, size=n)) for n in (40, 30)]
    eng = make_engine(True, max_batched=96, num_blocks=64)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    for p in prompts:
        eng.add_request(p, sp)
    eng.step()
    assert eng.stats.live_tokens_total == 70
    assert eng.stats.attn_shared_tile_tokens_total == 32 + 16
    while eng.has_work():
        eng.step()
    assert eng.stats.live_tokens_total > 70  # decode-only steps: two rows each
    assert eng.stats.attn_shared_tile_tokens_total == 48
    bucketed = make_engine(False, max_batched=96, num_blocks=64)
    bucketed.generate(prompts, sp)
    assert bucketed.stats.attn_shared_tile_tokens_total == 0  # no flat stream


def test_flat_forward_dispatches_kernels(monkeypatch):
    """Interpret-mode pin: the flat step program actually routes through
    the Pallas flat write + row-lookup attention kernels (not the XLA
    fallback) when the platform allows."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    import llmd_tpu.ops as ops

    calls = {"attn": 0, "write": 0}
    real_attn = ops.flat_paged_attention_full
    real_write = ops.write_kv_pages_flat_full

    def spy_attn(*a, **k):
        calls["attn"] += 1
        return real_attn(*a, **k)

    def spy_write(*a, **k):
        calls["write"] += 1
        return real_write(*a, **k)

    monkeypatch.setattr(ops, "flat_paged_attention_full", spy_attn)
    monkeypatch.setattr(ops, "write_kv_pages_flat_full", spy_write)
    eng = make_engine(True, page=8, head_dim=128)
    out = eng.generate([PROMPTS[0]], GREEDY)
    assert calls["attn"] > 0 and calls["write"] > 0
    # ... and the runner recorded the plan its programs took (what the
    # server reports on /admin/status and chip_smoke.py checks).
    assert eng.runner.kernel_plans == {
        "flat_attention": {"pallas"}, "flat_kv_write": {"pallas"},
    }
    ref_eng = make_engine(False, page=8, head_dim=128)
    ref = ref_eng.generate([PROMPTS[0]], GREEDY)
    assert _toks(out) == _toks(ref)
    # The bucketed engine's prefill rows are wider than the kernels'
    # Q == 1: the record says why those programs took XLA.
    for op in ("attention", "kv_write"):
        assert "xla:geometry" in ref_eng.runner.kernel_plans[op]


def test_page_table_larger_than_smem_is_refused_at_startup(monkeypatch):
    """The attention kernels scalar-prefetch the whole page table into
    SMEM. Where the kernels are active on a device whose SMEM is known, a
    geometry whose table cannot fit is refused when the runner is built,
    naming the flags that size it — not by the chip's compiler at the
    first request (v5e, libtpu 0.0.34: "RESOURCE_EXHAUSTED ... Used 1.04M
    of 1.00M smem"). The bound is the v5e's and only the v5e's."""
    import jax

    from llmd_tpu import ops

    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    kw = dict(page=16, head_dim=128, max_seqs=64, max_batched=2048)
    # 96 flat rows x 8192 pages x 4 B = 3 MiB: the HF default context.
    # Interpreted on this host there is no SMEM to overflow: it starts.
    cfg = make_engine(True, max_model_len=131072, **kw).runner.cfg
    # Lend this host's device kind the v5e's megabyte.
    v5e = ops._SMEM_BYTES["TPU v5 lite"]
    monkeypatch.setitem(ops._SMEM_BYTES, jax.devices()[0].device_kind, v5e)
    with pytest.raises(ValueError, match=r"--max-model-len.*--max-num-seqs"):
        make_engine(True, max_model_len=131072, **kw)

    def smem(max_pages, mesh=None, **rows):
        world = 1 if mesh is None else mesh.devices.size
        return ops.page_table_smem(cfg, 16, max_pages, world, mesh, **rows)

    flat = dict(decode_rows=64, flat_rows=96, flat_tokens=2064)
    need, have = smem(8192, **flat)
    assert have == v5e and need > 3 << 20
    need, have = smem(2048, **flat)  # --max-model-len 32768 fits
    assert 96 * 2048 * 4 < need <= have
    # A decode table splits over dp with the batch, so a device holds its
    # shard's rows; the flat step's compact table stays whole.
    mesh = jax.make_mesh((2, 2), ("dp", "tp"), devices=jax.devices()[:4])
    whole, _ = smem(2048, decode_rows=64)
    shard, _ = smem(2048, mesh, decode_rows=64)
    assert 64 * 2048 * 4 < whole and 32 * 2048 * 4 < shard < 64 * 2048 * 4
    assert 96 * 2048 * 4 < smem(2048, mesh, **flat)[0] <= need
    # With the kernels off nothing is prefetched, so nothing bounds it.
    monkeypatch.setenv("LLMD_PALLAS", "off")
    assert smem(8192, **flat) is None


# --------------------------------------------------------------------- #
# observability surface


def test_metrics_surface():
    from llmd_tpu.serve.metrics import render_metrics

    eng = make_engine(True, spec=True)
    eng.generate(
        [list(REPETITIVE), list(RANDOMISH)],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
    )
    text = render_metrics(eng.stats, "tiny")
    assert "llmd:live_tokens_total" in text
    assert "llmd:padded_tokens_total" in text
    assert "llmd:spec_row_depth_bucket" in text
    assert eng.stats.live_tokens_total > 0
