"""Async (pipelined) stepping tests.

The contract (docs/architecture/async-scheduling.md): the two-slot
pipeline — speculative scheduling against dispatched token counts, one
coalesced readback per step, late-finish rollback — may change WHEN host
work happens, never WHAT the engine emits. Every test here pins async
mode to byte-identical token streams against the synchronous engine.
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


def make_engine(
    async_mode=False, num_blocks=64, page=4, max_batched=64, max_seqs=8,
    seed=0, window=1, **model_kw,
) -> LLMEngine:
    cfg = EngineConfig(
        model=tiny_model_config(**model_kw),
        cache=CacheConfig(page_size=page, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=max_batched,
            decode_window=window,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
    )
    return LLMEngine(cfg, _synchronous_step=not async_mode)


PROMPTS = [
    [1, 5, 9, 13, 2, 8],
    [3, 3, 7, 1],
    [1, 5, 9, 13, 2, 8, 4, 4, 4, 4, 6, 6, 6, 6, 11],
]


def _warm(eng, prompts, max_tokens):
    """Run other prompts of the same lengths through, so that every step
    shape of the test proper is warm."""
    eng.generate(
        [[(t + 1) % 256 for t in p] for p in prompts],
        SamplingParams(temperature=0.0, max_tokens=max_tokens),
    )


def test_async_parity_basic():
    params = SamplingParams(temperature=0.0, max_tokens=8)
    sync = make_engine(False).generate(PROMPTS, params)
    eng = make_engine(True)
    asyn = eng.generate(PROMPTS, params)
    assert list(sync.values()) == list(asyn.values())
    # the pipeline drained: nothing left in flight, gauges populated
    assert eng._inflight is None
    assert eng.stats.engine_steps_total > 0


def test_async_parity_mixed_prefill_decode_preemption():
    """The acceptance workload: chunked prefill (long prompt > chunk),
    interleaved decodes, and page pressure forcing recompute-preemption
    — async must emit byte-identical streams."""
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, 256, size=50)),   # chunked across many steps
        list(range(10)),
        list(range(20, 30)),
        list(range(40, 50)),
    ]
    params = [
        SamplingParams(temperature=0.0, max_tokens=6),
        SamplingParams(temperature=0.0, max_tokens=12),
        SamplingParams(temperature=0.0, max_tokens=9),
        SamplingParams(temperature=0.0, max_tokens=12),
    ]
    kw = dict(num_blocks=14, max_batched=16)  # tight pool -> preemption
    sync = make_engine(False, **kw).generate(prompts, params)
    eng = make_engine(True, **kw)
    asyn = eng.generate(prompts, params)
    assert list(sync.values()) == list(asyn.values())


def test_async_parity_decode_window():
    params = SamplingParams(temperature=0.0, max_tokens=11)
    sync = make_engine(False, window=4).generate(PROMPTS, params)
    asyn = make_engine(True, window=4).generate(PROMPTS, params)
    assert list(sync.values()) == list(asyn.values())


def test_async_parity_seeded_sampling():
    """Seeded non-greedy rows reseed per (request seed, output index) at
    dispatch — staging ahead must not perturb them."""
    p = SamplingParams(temperature=1.0, max_tokens=9, seed=77)
    sync = make_engine(False).generate([PROMPTS[0]], [p])
    asyn = make_engine(True).generate([PROMPTS[0]], [p])
    assert list(sync.values()) == list(asyn.values())


def test_async_parity_unseeded_sampling():
    """Unseeded temperature sampling consumes the engine's stateful rng:
    seeds must be drawn at DISPATCH time in dispatch order (not at
    staging, which runs a step early and re-runs on rollback restages),
    so two same-seed engines agree across modes even with a chunked
    prompt and rollbacks in the mix."""
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, 256, size=50)),
        list(range(10)),
        list(range(20, 30)),
    ]
    p = SamplingParams(temperature=1.0, max_tokens=6)
    sync = make_engine(False, max_batched=16).generate(prompts, [p] * 3)
    asyn = make_engine(True, max_batched=16).generate(prompts, [p] * 3)
    assert list(sync.values()) == list(asyn.values())


def test_async_rollback_on_eos():
    """A speculated sequence that hits a stop token late: its row is in
    the step dispatched before the commit (wasted, counted), the extra
    token is not emitted, its pages return once that step has landed, and
    the stream matches sync exactly."""
    probe = make_engine(False).generate(
        [PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=8)
    )
    tokens = list(probe.values())[0]
    stop = tokens[2]
    expected = tokens[: tokens.index(stop) + 1]
    params = SamplingParams(
        temperature=0.0, max_tokens=8, stop_token_ids=(stop,)
    )
    eng = make_engine(True)
    out = eng.generate([PROMPTS[0]], params)
    assert list(out.values())[0] == expected
    # the EOS landed with the next step already dispatched for this seq
    assert eng.stats.async_wasted_rows_total >= 1
    assert eng.stats.async_rollbacks_total == 0
    # every page came back behind the wasted row: nothing leaked from the pool
    assert eng.allocator.usage() == 0.0


def test_async_max_tokens_finish_is_foreseen_not_rolled_back():
    """A LENGTH finish is the one late finish the speculative schedule
    can be certain of (the step in flight emits the request's last
    token whatever it samples): the row is not staged again, so nothing
    is rolled back, and the streams stay the synchronous engine's. (It
    was rolled back once per request before PR 38.)"""
    params = SamplingParams(temperature=0.0, max_tokens=5)
    eng = make_engine(True)
    sync = make_engine(False).generate(PROMPTS, params)
    asyn = eng.generate(PROMPTS, params)
    assert list(sync.values()) == list(asyn.values())
    assert eng.stats.async_rollbacks_total == 0
    assert eng.stats.async_wasted_rows_total == 0
    assert eng.stats.steps_dispatched_before_readback_total > 0
    assert eng.allocator.usage() == 0.0
    # the model length ends a request the same way
    params = SamplingParams(temperature=0.0, max_tokens=500, ignore_eos=True)
    short = dict(max_model_len=24)
    eng = make_engine(True, **short)
    sync = make_engine(False, **short).generate(PROMPTS[:2], params)
    assert list(sync.values()) == list(eng.generate(PROMPTS[:2], params).values())
    assert eng.stats.async_rollbacks_total == 0 and eng.allocator.usage() == 0.0
    assert eng.stats.async_wasted_rows_total == 0


def test_async_rollback_stop_token_mid_batch():
    """Stop token fires for ONE sequence of a batch while its mates keep
    decoding: only that row is wasted; survivors' streams are
    unperturbed."""
    probe = make_engine(False).generate(
        PROMPTS, SamplingParams(temperature=0.0, max_tokens=10)
    )
    vals = list(probe.values())
    stop = vals[0][3]  # stops seq 0 early; mates may never emit it
    params = SamplingParams(
        temperature=0.0, max_tokens=10, stop_token_ids=(stop,)
    )
    sync = make_engine(False).generate(PROMPTS, params)
    eng = make_engine(True)
    asyn = eng.generate(PROMPTS, params)
    assert list(sync.values()) == list(asyn.values())
    assert eng.stats.async_wasted_rows_total >= 1
    assert eng.allocator.usage() == 0.0


def test_async_host_gap_tracked():
    eng = make_engine(True)
    eng.generate(PROMPTS, SamplingParams(temperature=0.0, max_tokens=6))
    assert eng.stats.engine_steps_total > 0
    assert eng.stats.step_host_gap_ms_total >= 0.0
    # the gauge surfaces through the metrics page
    from llmd_tpu.serve.metrics import parse_prometheus, render_metrics

    page = render_metrics(eng.stats, "tiny")
    parsed = parse_prometheus(page)
    assert "llmd:step_host_gap_ms" in parsed
    assert "llmd:async_rollbacks_total" in parsed
    assert "llmd:async_wasted_rows_total" in parsed
    assert (
        parsed["llmd:steps_dispatched_before_readback_total"]
        == eng.stats.steps_dispatched_before_readback_total > 0
    )
    assert parsed["llmd:engine_steps_total"] == eng.stats.engine_steps_total


def test_async_deferred_abort_of_inflight_request():
    """Aborting a request whose batch is in flight defers to the
    reconcile point (pages freed only after the device stops writing
    them); the other request keeps decoding to completion."""
    eng = make_engine(True)
    _warm(eng, PROMPTS[:2], 6)
    keep = eng.add_request(PROMPTS[0], SamplingParams(temperature=0.0, max_tokens=6))
    victim = eng.add_request(PROMPTS[1], SamplingParams(temperature=0.0, max_tokens=6))
    got: dict[str, list[int]] = {keep: [], victim: []}
    for out in eng.step():  # lands the prompts, primes the pipeline:
        got[out.request_id].extend(out.new_token_ids)
    assert eng._inflight is not None  # both requests' decode rows in flight
    assert eng.abort_request(victim)
    for _ in range(64):
        if not eng.has_work():
            break
        for out in eng.step():
            got[out.request_id].extend(out.new_token_ids)
    ref = make_engine(False).generate(
        [PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=6)
    )
    assert got[keep] == list(ref.values())[0]
    assert len(got[victim]) <= 2  # nothing streamed past the abort window
    assert eng.allocator.usage() == 0.0


def test_async_forced_off_for_producer_role():
    """P/D eager-ACK producers keep the synchronous step shape by what
    the engine observes of its role (response-ordering guarantee)."""
    cfg = EngineConfig(
        model=tiny_model_config(),
        cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        kv_role="kv_producer",
        kv_transfer_port=0,
    )
    eng = LLMEngine(cfg)
    try:
        assert eng._async is False
    finally:
        eng.close()


def test_async_streams_one_step_late_then_drains():
    """A pipeline that starts from empty lands its first step at once
    (nothing to overlap with) and leaves the next in flight; from then on
    a call returns the step before's tokens; every token still arrives,
    and has_work() stays true until the slot drains."""
    eng = make_engine(True)
    _warm(eng, [PROMPTS[1]], 4)
    eng.add_request(PROMPTS[1], SamplingParams(temperature=0.0, max_tokens=4))
    toks: list[int] = [t for out in eng.step() for t in out.new_token_ids]
    assert len(toks) == 1  # the prompt's first token, not a call late
    assert eng._inflight is not None and eng.has_work()  # primed
    steps = eng.stats.engine_steps_total
    for out in eng.step():  # dispatches the step after, returns the one before
        toks.extend(out.new_token_ids)
    assert eng.stats.engine_steps_total == steps + 1 and eng._inflight is not None
    for _ in range(32):
        if not eng.has_work():
            break
        for out in eng.step():
            toks.extend(out.new_token_ids)
    ref = make_engine(False).generate(
        [PROMPTS[1]], SamplingParams(temperature=0.0, max_tokens=4)
    )
    assert toks == list(ref.values())[0]
    assert eng._inflight is None


# --- a finish the schedule cannot foresee, met with the row already dispatched ----


def _step_until(eng, got, done, limit=64):
    """Step until ``done()``; collects the tokens by request."""
    for _ in range(limit):
        eng.step_n = eng._inflight  # (what the call finds in flight)
        for out in eng.step():
            got.setdefault(out.request_id, []).extend(out.new_token_ids)
        if done():
            return
    raise AssertionError("never got there")


@pytest.mark.parametrize("cause", ["stop_token_in_a_decode_row", "stop_token_at_the_first_token", "abort"])
def test_a_late_finish_wastes_the_row_already_dispatched(cause):
    """Step N samples a stop token (or an abort arrives behind N+1's
    dispatch) for a request whose row went out with N+1 before N's readback:
    the request ends with N's token, the token N+1 computes for it is never
    emitted, its pages and its token slot are released when N+1 has LANDED
    and not before (the device may still write them), the row is counted,
    and its batch mate's stream is the synchronous engine's."""
    probe = make_engine(False).generate(
        PROMPTS[:2], SamplingParams(temperature=0.0, max_tokens=8)
    )
    mate_ref, ref = list(probe.values())
    at = {"stop_token_in_a_decode_row": 3, "stop_token_at_the_first_token": 0, "abort": None}[cause]
    sp = SamplingParams(
        temperature=0.0, max_tokens=8,
        stop_token_ids=() if at is None else (ref[at],),
    )
    expected = ref if at is None else ref[: ref.index(ref[at]) + 1]
    eng = make_engine(True)
    _warm(eng, PROMPTS[:2], 8)
    base = eng.stats.async_wasted_rows_total
    mate = eng.add_request(PROMPTS[0], SamplingParams(temperature=0.0, max_tokens=8))
    got: dict = {}
    _step_until(eng, got, lambda: True)  # the pipeline starts: the mate's prompt lands
    rid = eng.add_request(PROMPTS[1], sp)  # its prompt completes in a pipelined step
    req = next(r for r in eng.scheduler.waiting if r.request_id == rid)
    if cause == "abort":
        # brought by the poll behind a readback: the next step is in flight
        def hook() -> int:
            late = len(got.get(rid, ())) >= 2 and eng._inflight is not eng.step_n
            return int(late and not req.is_finished and eng.abort_request(rid))

        eng.intake_hook = hook
    _step_until(eng, got, lambda: req.is_finished)
    assert got[rid] == expected[: len(got[rid])] and (at is None or got[rid] == expected)
    emitted = len(got[rid])
    # ended, and its row is still on the device: nothing is given back yet
    assert eng._inflight is not None and req in [s.request for s in eng._inflight.batch.seqs]
    assert req.block_ids and req.token_slot >= 0 and req.num_pending_tokens == 1
    assert req not in eng.scheduler.running
    assert eng.stats.async_wasted_rows_total == base
    slot, free = req.token_slot, eng.allocator.num_free_pages
    for out in eng.step():  # N+1 lands
        got.setdefault(out.request_id, []).extend(out.new_token_ids)
    assert not req.block_ids and req.token_slot == -1 and req.num_pending_tokens == 0
    assert slot in eng.scheduler._token_slots and eng.allocator.num_free_pages > free
    assert eng.stats.async_wasted_rows_total == base + 1 and eng.stats.async_rollbacks_total == 0
    assert len(got[rid]) == emitted  # the wasted row's token went nowhere
    eng.intake_hook = None
    _step_until(eng, got, lambda: not eng.has_work())
    assert got[mate] == mate_ref and len(got[rid]) == emitted
    assert eng.allocator.usage() == 0.0 and eng._inflight is None
    assert sorted(eng.scheduler._token_slots) == list(range(16))


@pytest.mark.parametrize("keeps", ["drafts", "fused_window"])
def test_a_step_that_needs_the_tokens_on_the_host_waits_for_the_commit(keeps):
    """Read off the batch, never an option: a step with drafts (the proposer
    drafts from committed history) and a fused decode window are dispatched
    behind the commit of the step before them, so a late stop token still
    rolls their staged row back before it is dispatched; the streams are the
    synchronous engine's either way."""
    kw = dict(window=4) if keeps == "fused_window" else {}
    if keeps == "drafts":
        def make(async_mode):
            cfg = EngineConfig(
                model=tiny_model_config(),
                cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
                scheduler=SchedulerConfig(
                    max_num_seqs=8, max_num_batched_tokens=64,
                    speculative_ngram=True, spec_ngram_k=2,
                ),
                parallel=ParallelConfig(tensor_parallel_size=1),
            )
            return LLMEngine(cfg, _synchronous_step=not async_mode)
    else:
        def make(async_mode):
            return make_engine(async_mode, **kw)
    probe = make(False).generate(PROMPTS, SamplingParams(temperature=0.0, max_tokens=12))
    stop = list(probe.values())[0][5]
    sp = SamplingParams(temperature=0.0, max_tokens=12, stop_token_ids=(stop,))
    sync = make(False).generate(PROMPTS, sp)
    eng = make(True)
    early = []
    dispatch = eng._dispatch_async

    def dispatching(batch, staged=None):
        if eng._inflight is not None:  # the step in front is not read back
            early.append(batch)
        return dispatch(batch, staged)

    eng._dispatch_async = dispatching
    asyn = eng.generate(PROMPTS, sp)
    assert list(sync.values()) == list(asyn.values())
    for batch in early:  # (prefill-only steps and one-token decode steps may)
        assert all(s.draft_tokens is None and s.num_tokens == 1 for s in batch.decodes)
    assert eng.stats.steps_dispatched_before_readback_total == len(early)
    if keeps == "drafts":
        assert all(not b.decodes for b in early)
        assert eng.stats.async_rollbacks_total >= 1 and eng.stats.async_wasted_rows_total == 0
    else:
        assert eng.stats.async_rollbacks_total + eng.stats.async_wasted_rows_total >= 1
    assert eng.allocator.usage() == 0.0 and eng._inflight is None


def test_a_shapes_first_call_stays_behind_the_commit():
    """A step program's first call at a shape is seconds of tracing and
    lowering whose time follows the Python path it is reached on (the
    benchmark's set-up is mostly such calls): it keeps the path it had,
    behind the commit, and only a shape that has been called goes out
    before the readback."""
    eng = make_engine(True)
    calls: list = []  # (was the shape warm, was the step in front unread)
    dispatch = eng._dispatch_async

    def dispatching(batch, staged=None):
        warm = staged is not None and eng.runner.shape_is_warm(staged)
        calls.append((warm, eng._inflight is not None))
        return dispatch(batch, staged)

    eng._dispatch_async = dispatching
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    eng.generate(PROMPTS, sp)
    assert all(warm for warm, early in calls if early)
    assert [early for warm, early in calls if not warm] and not any(
        early for warm, early in calls if not warm
    )
    cold = len(calls)
    eng.generate([[(t + 1) % 256 for t in p] for p in PROMPTS], sp)  # the same shapes
    again = calls[cold:]
    assert all(warm for warm, _ in again[1:])  # (the first is the pipeline's entry, unstaged)
    assert sum(early for _, early in again) >= len(again) - 2
