"""Ring-buffer KV pages for sliding-window layers (CacheConfig.swa_ring).

The TPU-side analogue of the reference's hybrid KV cache manager
(guides/pd-disaggregation/modelserver/gpu/vllm/base/patch-decode.yaml:19
--no-disable-hybrid-kv-cache-manager): sliding layers hold a fixed ring of
pages per sequence instead of full-length pages, roughly halving KV bytes
for gpt-oss-class models (half the layers slide).

Parity tests run generation PAST the ring length so logical pages alias
onto overwritten ring slots — correctness then depends on the window mask
excluding exactly the overwritten positions. Greedy float32 outputs must
match the non-ring engine token for token.
"""

import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
    swa_ring_spec,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams

WINDOW = 8
ALTERNATING = dict(
    num_layers=4, num_heads=4, num_kv_heads=2,
    sliding_window=WINDOW,
    layer_types=(
        "sliding_attention", "full_attention",
        "sliding_attention", "full_attention",
    ),
)


def _make_engine(cfg_over, ring, **kw):
    cache_kw = kw.pop("cache_kw", {})
    sched_kw = kw.pop("sched_kw", {})
    parallel = kw.pop("parallel", None) or ParallelConfig()
    return LLMEngine(EngineConfig(
        model=tiny_model_config(**cfg_over),
        cache=CacheConfig(**{
            "page_size": 4, "num_blocks": 64, "dtype": "float32",
            "swa_ring": ring, **cache_kw,
        }),
        scheduler=SchedulerConfig(
            **{"max_num_seqs": 4, "max_num_batched_tokens": 32, **sched_kw},
        ),
        parallel=parallel,
        offload=None,
    ))


def _generate(eng, prompts, max_tokens=30):
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True)
    return list(eng.generate(prompts, sp).values())


def _parity(cfg_over, prompts, max_tokens=30, **kw):
    """Greedy outputs must match between ring-on and ring-off engines."""
    outs = {}
    for ring in (False, True):
        eng = _make_engine(cfg_over, ring, **kw)
        try:
            outs[ring] = _generate(eng, prompts, max_tokens)
            if ring:
                assert eng.runner.swa is not None, "ring did not resolve"
                assert eng.runner.kv_swa is not None
        finally:
            eng.close()
    assert outs[True] == outs[False]
    return outs[True]


# --------------------------------------------------------------------- #
# spec resolution


def test_ring_spec_geometry():
    model = tiny_model_config(**ALTERNATING, max_model_len=256)
    cache = CacheConfig(page_size=4, swa_ring=True)
    sched = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32)
    spec = swa_ring_spec(model, cache, sched)
    assert spec is not None
    assert spec.full_layers == (1, 3) and spec.swa_layers == (0, 2)
    # R = ceil((window + chunk) / page) + 1 = ceil(40/4) + 1 = 11
    assert spec.ring_pages == 11
    assert spec.num_swa_blocks == 4 * 11

    # flag off / no sliding layers / ring as large as the table -> None
    assert swa_ring_spec(model, CacheConfig(page_size=4), sched) is None
    assert swa_ring_spec(tiny_model_config(), cache, sched) is None
    short = tiny_model_config(**{**ALTERNATING, "max_model_len": 32})
    assert swa_ring_spec(short, cache, sched) is None


# --------------------------------------------------------------------- #
# engine parity (generation wraps the ring)


def test_parity_alternating_wraps_ring():
    """gpt-oss pattern; 30 prompt + 30 decode = 60 tokens > 44-token ring
    (the periodic cycle-scan path, c=2)."""
    prompt = [(7 * i + 3) % 97 for i in range(30)]
    out = _parity(ALTERNATING, [prompt], max_tokens=30)
    assert len(out[0]) == 30


def test_parity_uniform_sliding():
    """Mistral pattern: every layer slides — the full-layer pool is empty
    and the single-group scan runs entirely on the ring pool."""
    over = dict(
        num_layers=3, num_heads=4, num_kv_heads=2, sliding_window=WINDOW,
    )
    prompt = [(5 * i + 11) % 89 for i in range(26)]
    _parity(over, [prompt], max_tokens=28)


def test_parity_upper_layer_sliding():
    """Qwen2 pattern (max_window_layers): aperiodic kinds -> the
    contiguous-runs scan fallback."""
    over = dict(
        num_layers=4, num_heads=4, num_kv_heads=2, sliding_window=WINDOW,
        max_window_layers=2,
    )
    prompt = [(3 * i + 17) % 83 for i in range(24)]
    _parity(over, [prompt], max_tokens=30)


def test_parity_batch_and_chunked_prefill():
    """Several sequences of different lengths; prompts longer than the
    token budget exercise chunked prefill against the ring."""
    prompts = [
        [(11 * i + 1) % 79 for i in range(54)],  # > 32-token budget
        [(13 * i + 5) % 71 for i in range(9)],
        [(17 * i + 7) % 61 for i in range(23)],
    ]
    _parity(ALTERNATING, prompts, max_tokens=20)


def test_parity_fused_decode_window():
    """K-step fused decode interleaves ring writes and windowed reads."""
    prompt = [(19 * i + 2) % 67 for i in range(12)]
    _parity(
        ALTERNATING, [prompt], max_tokens=40,
        sched_kw=dict(decode_window=4, max_num_seqs=1),
    )


def test_parity_sharded_tp2():
    """tp=2 mesh: the ring pool shards its kv-head axis like the main
    pool; sharded write/attention paths stay exact."""
    prompt = [(23 * i + 9) % 59 for i in range(22)]
    _parity(
        ALTERNATING, [prompt], max_tokens=24,
        parallel=ParallelConfig(tensor_parallel_size=2),
    )


def test_parity_with_sinks():
    """gpt-oss proper: sinks + alternating sliding layers + ring."""
    over = dict(**ALTERNATING, attention_sinks=True, attention_out_bias=True)
    prompt = [(29 * i + 4) % 53 for i in range(20)]
    _parity(over, [prompt], max_tokens=24)


# --------------------------------------------------------------------- #
# footprint and lifecycle


def test_footprint_drops_for_long_context():
    """With long max_model_len the ring pool is far smaller than the
    full-length planes it replaces: for the alternating pattern (half the
    layers slide) total KV bytes approach half."""
    over = dict(**ALTERNATING, max_model_len=4096)
    sized = dict(cache_kw=dict(num_blocks=1024))
    off = _make_engine(over, False, **sized)
    try:
        bytes_off = off.runner.kv_bytes()
    finally:
        off.close()
    on = _make_engine(over, True, **sized)
    try:
        bytes_on = on.runner.kv_bytes()
        spec = on.runner.swa
        # full pool keeps 2/4 layers; ring pool is 4 seqs x R pages
        assert bytes_on < 0.6 * bytes_off, (bytes_on, bytes_off)
        assert spec.num_swa_blocks < 1024
    finally:
        on.close()


def test_ring_pages_released_on_finish_and_reuse():
    eng = _make_engine(ALTERNATING, True)
    try:
        R = eng.runner.swa.ring_pages
        for _ in range(3):
            _generate(eng, [[1, 2, 3, 4, 5, 6, 7, 8]], max_tokens=6)
            # Rings release in full; the hybrid-APC section cache keeps
            # its retained pages (one section for the repeated prompt).
            retained = sum(
                e.n_pre - e.s0 for e in eng._swa_sections._entries.values()
            )
            assert retained > 0
            assert (
                eng.swa_allocator.num_free_pages
                == eng.swa_allocator.num_pages - retained
            )
        # mid-flight: exactly one ring held per running sequence (+ the
        # retained sections)
        eng.add_request([9, 8, 7, 6, 5], SamplingParams(max_tokens=50, temperature=0.0, ignore_eos=True))
        eng.step()
        # The step completed this prompt's prefill, so its own section
        # was captured too — recount retention after the step.
        retained = sum(
            e.n_pre - e.s0 for e in eng._swa_sections._entries.values()
        )
        held = eng.swa_allocator.num_pages - eng.swa_allocator.num_free_pages
        assert held == R + retained
    finally:
        eng.close()


def test_hybrid_prefix_cache_hits_under_ring():
    """The reference's hybrid KV-cache manager semantics (pd gpu
    patch-decode.yaml:19): full-attention pages stay reusable while
    sliding layers ride the ring — a repeated prefix seeds a fresh ring
    from the retained section and skips the shared span's prefill, with
    greedy decode parity as the correctness witness."""
    eng = _make_engine(ALTERNATING, True)
    try:
        assert eng.allocator.enable_prefix_caching  # hybrid, not disabled
        prompt = [(31 * i + 6) % 47 for i in range(20)]
        first, f1 = _pd_run(eng, prompt, max_tokens=10)
        assert eng._swa_sections.captures >= 1
        second, f2 = _pd_run(eng, prompt, max_tokens=10)
        assert first == second  # wrong sliding seeds would change logits
        assert eng._swa_sections.hits >= 1
        # n_pre = 19//4 = 4 pages; window 8 -> section covers pages [2,4)
        assert f2.num_cached_tokens == 16
        assert f1.num_cached_tokens == 0
        # A third, LONGER prompt sharing the prefix hits at the retained
        # span (the multi-turn grow case): a section captured at k pages
        # holds the window before continuation k*page, so the extended
        # prompt skips its first k pages and recomputes the rest. Parity
        # against a cold engine is the correctness witness.
        ext = prompt + [1, 2, 3, 4]
        third, f3 = _pd_run(eng, ext, max_tokens=6)
        assert f3.num_cached_tokens == 16
        cold = _make_engine(ALTERNATING, True)
        try:
            ref, _ = _pd_run(cold, ext, max_tokens=6)
        finally:
            cold.close()
        assert third == ref
    finally:
        eng.close()


def test_a_sessions_next_turn_seeds_its_ring_at_the_last_page_its_answer_filled():
    """A sequence leaves TWO sections behind: the window before its prompt's
    last full page and, copied behind the decode step that fills it, the
    window before the last page of its answer (the ring has moved on by the
    time it ends). The session's next turn, prompt + answer + question,
    seeds its ring from the second and skips its own last answer; greedy
    parity with a cold engine is the witness that the window is the right
    one. An answer that fills no page past the prompt's leaves one."""
    eng, cold = _make_engine(ALTERNATING, True), _make_engine(ALTERNATING, True)
    try:
        prompt = [(31 * i + 6) % 47 for i in range(21)]  # last full page: 20
        answer, f1 = _pd_run(eng, prompt, max_tokens=14)  # fed through 33: the last page filled is 32
        assert f1.num_cached_tokens == 0
        # each the window (8) before its boundary, and the page it straddles
        assert sorted((e.s0, e.n_pre) for e in eng._swa_sections._entries.values()) == [(3, 5), (6, 8)]
        nxt = prompt + answer + [5, 4, 3, 2, 1]
        second, f2 = _pd_run(eng, nxt, max_tokens=6)
        assert f2.num_cached_tokens == 32 and eng._swa_sections.hits == 1
        ref, _ = _pd_run(cold, nxt, max_tokens=6)
        assert second == ref
        eng._refresh_gauges()
        assert eng.stats.retained_finish_captures_total == 2
        _pd_run(eng, [7] + prompt, max_tokens=2)  # fed through 22: no page past the prompt's own (20)
        eng._refresh_gauges()
        assert eng.stats.retained_finish_captures_total == 2 and eng._swa_sections.captures == 5
    finally:
        eng.close()
        cold.close()


def test_composition_gates():
    from llmd_tpu.config import OffloadConfig

    base = dict(
        model=tiny_model_config(**ALTERNATING),
        cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32", swa_ring=True),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=32),
    )
    with pytest.raises(ValueError, match="kv_swa_ring"):
        LLMEngine(EngineConfig(**base, offload=OffloadConfig(enabled=True)))
    # P/D transfer DOES compose (ring preload path) — construction works.
    eng = LLMEngine(EngineConfig(
        **base, kv_role="kv_producer", kv_transfer_port=0, offload=None,
    ))
    try:
        assert eng.kv_connector is not None
    finally:
        eng.close()


def test_swa_blocks_smaller_than_one_ring_rejected():
    """An explicit pool smaller than one ring would livelock admission
    silently — it must be a config error instead."""
    model = tiny_model_config(**ALTERNATING, max_model_len=256)
    cache = CacheConfig(page_size=4, swa_ring=True, swa_blocks=8)
    sched = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32)
    with pytest.raises(ValueError, match="swa_blocks"):
        swa_ring_spec(model, cache, sched)  # ring resolves to 11 > 8


def test_failed_admission_returns_ring_pages():
    """When ring allocation succeeds but main-pool pages are exhausted,
    the still-waiting request must NOT keep its ring (a held ring could
    stall a higher-priority arrival's admission)."""
    # Main pool is tiny: the first request consumes nearly all pages.
    eng = _make_engine(
        ALTERNATING, True, cache_kw=dict(num_blocks=8),
        sched_kw=dict(max_num_seqs=4),
    )
    try:
        sp = SamplingParams(temperature=0.0, max_tokens=64, ignore_eos=True)
        eng.add_request([1, 2, 3, 4] * 6, sp)  # 24 toks -> 6 of 8 pages
        first = eng.scheduler.waiting[0]
        while not first.output_token_ids:  # its prompt committed (the
            eng.step()  # pipelined step commits one call after it dispatches)
        free_before = eng.swa_allocator.num_free_pages
        # Second request: ring allocates, pages fail -> ring must return.
        eng.add_request([9, 8, 7, 6] * 5, sp)
        eng.step()
        waiting = list(eng.scheduler.waiting)
        assert waiting and not waiting[0].swa_block_ids
        held = free_before - eng.swa_allocator.num_free_pages
        assert held == 0, f"waiting request still holds {held} ring pages"
    finally:
        eng.close()


# --------------------------------------------------------------------- #
# P/D transfer composition (the reference's gpt-oss P/D decode runs the
# hybrid KV cache manager — ring + transfer together,
# pd-disaggregation/modelserver/gpu/vllm/base/patch-decode.yaml:19)


def _pd_engine(kv_role, local_fastpath=False):
    return LLMEngine(EngineConfig(
        model=tiny_model_config(**ALTERNATING),
        cache=CacheConfig(
            page_size=4, num_blocks=64, dtype="float32", swa_ring=True,
        ),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32),
        parallel=ParallelConfig(),
        kv_role=kv_role,
        kv_transfer_port=0,
        kv_local_fastpath=local_fastpath,
        offload=None,
    ))


def _pd_run(eng, prompt, max_tokens, kv_transfer_params=None):
    rid = eng.add_request(
        list(prompt),
        SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True),
        kv_transfer_params=kv_transfer_params,
    )
    outs, final = [], None
    while eng.has_work():
        for out in eng.step():
            if out.request_id == rid:
                outs.extend(out.new_token_ids)
                if out.finished:
                    final = out
    return outs, final


# 37 tokens: > the 8-token window, crosses page boundaries unaligned.
_PD_PROMPT = [(41 * i + 3) % 61 for i in range(37)]


@pytest.mark.parametrize("fastpath", [False, True])
def test_pd_ring_matches_aggregated(fastpath):
    """Producer ring engine -> consumer ring engine: the sliding-layer
    section travels with the full-group chunks, the consumer preloads
    the request directly (no prefix cache exists), and decode tokens
    match a plain ring engine's — proof the transferred sliding KV is
    read where the window needs it."""
    import time as _time

    ref = _pd_engine(None)
    try:
        ref_tokens, _ = _pd_run(ref, _PD_PROMPT, max_tokens=12)
    finally:
        ref.close()

    producer = _pd_engine("kv_producer", local_fastpath=fastpath)
    consumer = _pd_engine("kv_consumer", local_fastpath=fastpath)
    try:
        _, pre = _pd_run(
            producer, _PD_PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        assert params is not None
        assert params["swa_pages"] > 0
        # preload covers (37-1)//4 = 9 pages; the section spans the
        # window before the continuation point: s0 = (9*4 - 8)//4 = 7.
        assert params["num_full_pages"] == 9
        assert params["swa_start_page"] == 7
        if not fastpath:
            deadline = _time.time() + 5
            while _time.time() < deadline:
                # chunks + the swa section must all register
                if producer.kv_connector.server.registered_count >= 3:
                    break
                _time.sleep(0.02)
        toks, final = _pd_run(
            consumer, _PD_PROMPT, max_tokens=12, kv_transfer_params=params
        )
        assert toks == ref_tokens
        assert final.num_cached_tokens == 36  # 9 preloaded pages
        assert consumer.kv_connector.imported_requests == 1
        assert consumer.kv_connector.import_failures == 0
        if fastpath:
            assert consumer.kv_connector.local_imports == 1
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_ring_producer_down_recompute():
    """Missing sliding section (export expired/unreachable) degrades to
    local recompute under the default policy — never a wrong answer."""
    ref = _pd_engine(None)
    try:
        ref_tokens, _ = _pd_run(ref, _PD_PROMPT, max_tokens=10)
    finally:
        ref.close()
    consumer = _pd_engine("kv_consumer")
    try:
        params = {
            "remote_host": "127.0.0.1", "remote_port": 1,  # nothing there
            "remote_key": "gone", "num_full_pages": 9, "page_size": 4,
            "chunk_pages": 8, "num_chunks": 2,
            "swa_pages": 3, "swa_start_page": 7,
        }
        toks, _ = _pd_run(
            consumer, _PD_PROMPT, max_tokens=10, kv_transfer_params=params
        )
        assert toks == ref_tokens
        assert consumer.kv_connector.import_failures >= 1
    finally:
        consumer.kv_connector.close()


def test_pd_ring_refuses_ringless_producer():
    """A ring consumer handed params WITHOUT a sliding section (ring-off
    producer) must hit the failure policy, not silently decode garbage."""
    consumer = _pd_engine("kv_consumer")
    try:
        params = {
            "remote_host": "127.0.0.1", "remote_port": 1,
            "remote_key": "x", "num_full_pages": 9, "page_size": 4,
            "chunk_pages": 8, "num_chunks": 2,
        }
        ref = _pd_engine(None)
        try:
            ref_tokens, _ = _pd_run(ref, _PD_PROMPT, max_tokens=6)
        finally:
            ref.close()
        toks, _ = _pd_run(
            consumer, _PD_PROMPT, max_tokens=6, kv_transfer_params=params
        )
        assert toks == ref_tokens  # recompute fallback
        assert consumer.kv_connector.import_failures >= 1
    finally:
        consumer.kv_connector.close()


@pytest.mark.parametrize(
    "tamper",
    [
        {"swa_start_page": 8},
        {"swa_pages": 1},
        {"num_full_pages": 8},
        {"num_full_pages": 5},
    ],
    ids=[
        "start-past-s0",
        "count-short-of-n_pre",
        "full-pages-clamps-window",
        "full-pages-empties-section",
    ],
)
def test_pd_ring_rejects_noncovering_section(tamper):
    """A sliding section that merely OVERLAPS [0, n_pre) but does not
    cover the consumer-derived window [s0, n_pre) — stale/hostile
    swa_start_page > s0, or swa_count short of n_pre — must degrade to
    recompute, never leave in-window ring slots zero-initialized while
    num_computed_tokens claims them valid."""
    ref = _pd_engine(None)
    try:
        ref_tokens, _ = _pd_run(ref, _PD_PROMPT, max_tokens=8)
    finally:
        ref.close()
    producer = _pd_engine("kv_producer")
    consumer = _pd_engine("kv_consumer")
    try:
        _, pre = _pd_run(
            producer, _PD_PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = dict(pre.kv_transfer_params)
        assert params["swa_start_page"] == 7  # honest s0 for this prompt
        params.update(tamper)
        toks, final = _pd_run(
            consumer, _PD_PROMPT, max_tokens=8, kv_transfer_params=params
        )
        assert toks == ref_tokens  # recompute fallback, not garbage
        assert consumer.kv_connector.import_failures >= 1
        assert consumer.kv_connector.imported_requests == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_ring_rejects_partial_export():
    """start_page > 0 (stale/hostile skip_pages) must hit the failure
    policy — pages [0, skip) would otherwise decode from uninitialized
    KV with no error."""
    consumer = _pd_engine("kv_consumer")
    try:
        with pytest.raises(ValueError, match="partial export"):
            consumer.kv_connector.fetch_remote(
                _PD_PROMPT,
                {
                    "remote_host": "127.0.0.1", "remote_port": 1,
                    "remote_key": "x", "num_full_pages": 9, "page_size": 4,
                    "chunk_pages": 8, "num_chunks": 2,
                    "swa_pages": 2, "swa_start_page": 7, "start_page": 3,
                },
            )
    finally:
        consumer.kv_connector.close()


def test_preloaded_waiters_cannot_starve_admission():
    """Preloaded arrivals hold rings allocated outside admission; when
    they exhaust the pool behind a ring-less queue head, the scheduler
    reclaims the youngest preload's ring (downgrade to local recompute)
    instead of livelocking."""
    from llmd_tpu.engine.kv_cache import PageAllocator
    from llmd_tpu.engine.request import Request
    from llmd_tpu.engine.scheduler import EngineScheduler

    page, R = 4, 5
    alloc = PageAllocator(64, page, enable_prefix_caching=False)
    swa_alloc = PageAllocator(2 * R, page, enable_prefix_caching=False)
    sched = EngineScheduler(
        SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32),
        CacheConfig(page_size=page, num_blocks=64),
        alloc, max_model_len=128,
        swa_allocator=swa_alloc, swa_ring_pages=R, swa_chunk_tokens=32,
    )
    head = Request(request_id="head", prompt_token_ids=[1] * 9)
    sched.add_request(head)
    # Two preloaded arrivals drain the 2R pool entirely.
    preloaded = []
    for i in range(2):
        r = Request(request_id=f"pre{i}", prompt_token_ids=[2] * 9)
        r.block_ids = alloc.allocate(2)
        r.swa_block_ids = swa_alloc.allocate(R)
        r.num_computed_tokens = 8
        r.num_cached_tokens = 8
        preloaded.append(r)
        sched.add_request(r)
    assert swa_alloc.num_free_pages == 0
    batch = sched.schedule()
    admitted = {s.request.request_id for s in batch.prefills}
    assert "head" in admitted, admitted  # queue head got a reclaimed ring
    # the youngest preload was downgraded to plain recompute
    assert preloaded[1].swa_block_ids == [] or preloaded[0].swa_block_ids == []
    downgraded = [r for r in preloaded if not r.swa_block_ids]
    assert downgraded and all(r.num_computed_tokens == 0 for r in downgraded)


def test_ring_ignored_for_full_attention_models():
    """swa_ring on a model without sliding layers is a no-op, not an
    error (deploy configs can set it unconditionally)."""
    eng = _make_engine(dict(num_layers=2, num_heads=4, num_kv_heads=2), True)
    try:
        assert eng.runner.swa is None and eng.runner.kv_swa is None
        assert eng.allocator.enable_prefix_caching  # untouched
        out = _generate(eng, [[1, 2, 3]], max_tokens=4)
        assert len(out[0]) == 4
    finally:
        eng.close()


def test_ring_pressure_evicts_retained_sections():
    """Live sequences outrank idle hybrid-APC retention: when ring
    allocation fails, LRU retained sections free until admission
    succeeds — retention can never permanently shrink concurrency."""
    eng = _make_engine(ALTERNATING, True, sched_kw={"max_num_seqs": 2})
    try:
        # Distinct prompts: each capture retains a section until the
        # cache (or the pool floor) stops accepting.
        for i in range(4):
            _generate(eng, [[(7 * i + j) % 45 + 1 for j in range(12)]],
                      max_tokens=2)
        retained_before = len(eng._swa_sections._entries)
        assert retained_before > 0
        # Saturate admission: max_num_seqs long-running requests need
        # every ring the (auto-sized 2xR) pool has.
        sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
        for i in range(2):
            eng.add_request([i + 1, i + 2, i + 3], sp)
        while eng.has_work():
            eng.step()
        # Both ran to completion (admission never wedged), shedding
        # retention as needed.
        assert eng.scheduler.num_running == 0 and eng.scheduler.num_waiting == 0
    finally:
        eng.close()
