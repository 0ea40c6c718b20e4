"""Speculative decoding (SchedulerConfig.speculative_ngram) tests.

The contract (docs/architecture/speculative-decoding.md): n-gram
prompt-lookup drafting + one-pass verification may change how many
tokens a step emits, never WHICH tokens — greedy and seeded streams are
byte-identical to the non-speculative engine, across chunked prefill,
preemption/recompute, prefix-cache hits, and async stepping. Rejected
draft tokens' provisional KV writes are truncated before any page
commit, so rejected content can never enter the prefix-cache hash chain
(asserted here by walking the allocator's content index).
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
from llmd_tpu.engine.kv_cache import page_hashes_for_tokens
from llmd_tpu.engine.sampler import accept_draft_tokens
from llmd_tpu.engine.spec import NgramProposer


def make_engine(
    spec=False, async_mode=False, num_blocks=64, page=4, max_batched=64,
    max_seqs=8, seed=0, k=4, min_match=2, prefix_caching=True,
    decode_window=1, ragged=True,
    **model_kw,
) -> LLMEngine:
    cfg = EngineConfig(
        model=tiny_model_config(**model_kw),
        cache=CacheConfig(
            page_size=page, num_blocks=num_blocks, dtype="float32",
            enable_prefix_caching=prefix_caching,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=max_batched,
            speculative_ngram=spec,
            spec_ngram_k=k, spec_ngram_min_match=min_match,
            decode_window=decode_window, ragged_qlens=ragged,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
    )
    return LLMEngine(cfg, _synchronous_step=not async_mode)


# Periodic prompts drive the tiny model's greedy output into loops the
# n-gram proposer latches onto — drafts genuinely fire AND genuinely
# reject (the loop onset mispredicts), exercising both acceptance paths.
PROMPTS = [
    [1, 5, 9, 13] * 3,
    [3, 3, 7, 1, 3, 3, 7, 1],
    [1, 5, 9, 13, 2, 8, 4, 4, 4, 4, 6, 6, 6, 6, 11],
]


# --------------------------------------------------------------------- #
# proposer unit behavior


def test_proposer_drafts_periodic_continuation():
    p = NgramProposer(min_match=2)
    #       0  1  2  3  4  5  6  7
    toks = [7, 8, 9, 7, 8, 9, 7, 8]
    # suffix [7, 8] matched; the cycle continues with 9, 7, ...
    assert p.propose(toks, 3) == [9, 7, 8]


def test_proposer_no_match_returns_empty():
    p = NgramProposer(min_match=2)
    assert p.propose([1, 2, 3, 4, 5, 6], 4) == []
    assert p.propose([1, 2], 4) == []  # too short
    assert p.propose([7, 8, 9, 7, 8], 0) == []  # k == 0


def test_proposer_prefers_longer_match_context():
    p = NgramProposer(min_match=2)
    # suffix ...[5, 1, 2]: both [1, 2] sites match at min length, but the
    # site with the longer backward context ([5, 1, 2] at index 6..8)
    # must win over the shorter one ([9, 1, 2] at 0..2).
    toks = [9, 1, 2, 7, 7, 7, 5, 1, 2, 4, 4, 4, 5, 1, 2]
    assert p.propose(toks, 2) == [4, 4]


def test_proposer_incremental_state_matches_stateless():
    p = NgramProposer(min_match=2)
    rng = np.random.default_rng(0)
    toks = list(rng.integers(0, 4, size=40))
    st = p.new_state()
    for n in range(3, len(toks) + 1):
        assert p.propose(toks[:n], 3, st) == p.propose(toks[:n], 3)


def test_accept_draft_tokens_rule():
    # full acceptance: every draft token matched + the bonus sample
    assert accept_draft_tokens([5, 6], [5, 6, 7]) == ([5, 6, 7], 2)
    # first mismatch: the target's correction token ends the window
    assert accept_draft_tokens([5, 6], [5, 9, 7]) == ([5, 9], 1)
    assert accept_draft_tokens([5, 6], [4, 6, 7]) == ([4], 0)
    # no draft: plain single sample
    assert accept_draft_tokens([], [3]) == ([3], 0)


# --------------------------------------------------------------------- #
# parity: spec on == spec off, byte for byte. ``decode_window`` > 1 is
# accepted beside speculation (a Helm value may set both) and inert: a
# speculative engine verifies one-shot every step.


def _assert_decode_window_inert(eng):
    """No step of a speculative engine rides the fused decode program."""
    assert eng.runner.decode_windows == (1,)
    assert not any(
        fam == "decode_window" and shape[1] > 1
        for _, fam, shape in eng.runner.traced_programs
    )


@pytest.mark.parametrize("decode_window", [1, 2, 4])
def test_spec_parity_greedy(decode_window):
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    base = make_engine(False).generate(PROMPTS, sp)
    eng = make_engine(True, decode_window=decode_window)
    spec = eng.generate(PROMPTS, sp)
    assert list(base.values()) == list(spec.values())
    # speculation actually engaged (drafts proposed and some accepted)
    assert eng.scheduler.spec_proposed_tokens > 0
    assert eng.scheduler.spec_accepted_tokens > 0
    assert eng.allocator.usage() == 0.0
    _assert_decode_window_inert(eng)


@pytest.mark.parametrize("decode_window", [1, 2, 4])
def test_spec_parity_seeded_sampling(decode_window):
    """Seeded rows accept via the per-(seed, output-index) PRNG
    derivation. Low temperature keeps the seeded output loop-prone so
    drafts genuinely fire AND at least one accepts (hot sampling over a
    256-vocab is incompressible — the proposer would simply never
    match); the high-temperature case rides test_spec_parity_async's
    seeded leg."""
    sp = SamplingParams(temperature=0.3, max_tokens=16, seed=77, ignore_eos=True)
    base = make_engine(False, seed=3).generate(PROMPTS, sp)
    eng = make_engine(True, seed=3, decode_window=decode_window)
    spec = eng.generate(PROMPTS, sp)
    assert list(base.values()) == list(spec.values())
    assert eng.scheduler.spec_proposed_tokens > 0
    assert eng.scheduler.spec_accepted_tokens > 0
    _assert_decode_window_inert(eng)


@pytest.mark.parametrize("decode_window", [1, 4])
def test_spec_parity_chunked_prefill_and_preemption(decode_window):
    """Tight pool + long periodic prompt: chunked prefill across steps
    and recompute-preemption under page pressure, with drafts in
    flight."""
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, 8, size=6)) * 8,  # 48 tokens, chunked
        [5, 6, 7, 8] * 3,
        [9, 1, 9, 1, 9, 1],
        [2, 4, 2, 4, 2, 4, 2, 4],
    ]
    params = [
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
        SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True),
        SamplingParams(temperature=0.0, max_tokens=9, ignore_eos=True),
        SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True),
    ]
    kw = dict(num_blocks=16, max_batched=16)  # tight pool -> preemption
    base_eng = make_engine(False, **kw)
    base = base_eng.generate([list(p) for p in prompts], params)
    eng = make_engine(True, decode_window=decode_window, **kw)
    spec = eng.generate([list(p) for p in prompts], params)
    assert list(base.values()) == list(spec.values())
    assert eng.scheduler.num_preemptions > 0, (
        "pool was not tight enough to exercise preemption"
    )
    assert eng.allocator.usage() == 0.0


def test_spec_parity_prefix_cache_hit():
    """A repeated prompt admits from the prefix cache (fewer prefill
    steps, decode starts mid-page) and must still stream identically."""
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    base_eng, eng = make_engine(False), make_engine(True)
    first_b = base_eng.generate([PROMPTS[0]], sp)
    first_s = eng.generate([PROMPTS[0]], sp)
    assert list(first_b.values()) == list(first_s.values())
    # second pass: prefix-cache hit on the prompt's full pages
    second_b = base_eng.generate([PROMPTS[0]], sp)
    second_s = eng.generate([PROMPTS[0]], sp)
    assert list(second_b.values()) == list(second_s.values())
    assert eng.allocator.metrics_hits > 0  # the hit actually happened


def test_spec_parity_stop_token_mid_window():
    """A stop token landing inside an accepted window must cut the
    stream exactly where the baseline cuts it (overrun discarded)."""
    probe = make_engine(False).generate(
        [PROMPTS[1]], SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    )
    tokens = list(probe.values())[0]
    stop = tokens[5]
    sp = SamplingParams(temperature=0.0, max_tokens=12, stop_token_ids=(stop,))
    base = make_engine(False).generate([PROMPTS[1]], sp)
    spec = make_engine(True).generate([PROMPTS[1]], sp)
    assert list(base.values()) == list(spec.values())


@pytest.mark.parametrize("decode_window", [1, 4])
@pytest.mark.parametrize("seeded", [False, True])
def test_spec_parity_async_scheduling(seeded, decode_window, unforeseen_finishes):
    """Spec composes with async stepping: the staged next batch is
    planned against max-acceptance counts, and short acceptance lands as
    a partial rollback — streams still byte-identical to the plain sync
    engine, and LENGTH finishes still roll their staged rows back."""
    if seeded:
        sp = SamplingParams(temperature=1.0, max_tokens=14, seed=11, ignore_eos=True)
    else:
        sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    base = make_engine(False).generate(PROMPTS, sp)
    eng = make_engine(True, async_mode=True, decode_window=decode_window)
    out = eng.generate(PROMPTS, sp)
    assert list(base.values()) == list(out.values())
    assert eng._inflight is None
    # every request's LENGTH finish invalidated its staged row
    assert eng.stats.async_rollbacks_total >= len(PROMPTS)
    assert eng.allocator.usage() == 0.0


def test_spec_async_equals_spec_sync():
    """Same spec engine, async on vs off: identical streams AND identical
    acceptance histograms (the pipeline changes when work happens, not
    what is drafted/accepted)."""
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    sync_eng = make_engine(True)
    async_eng = make_engine(True, async_mode=True)
    a = sync_eng.generate(PROMPTS, sp)
    b = async_eng.generate(PROMPTS, sp)
    assert list(a.values()) == list(b.values())
    assert (
        sync_eng.scheduler.spec_accept_len_hist
        == async_eng.scheduler.spec_accept_len_hist
    )


def test_spec_parity_swa_ring():
    """Spec composes with the SWA ring pool: rejected provisional writes
    on sliding layers land in ring slots the real tokens re-write at the
    same position before anything reads them (the ring's write-span
    invariant is sized for 1 + k)."""
    kw = dict(
        num_layers=4, sliding_window=8,
        layer_types=("sliding_attention", "full_attention") * 2,
    )
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)

    def make(spec):
        cfg = EngineConfig(
            model=tiny_model_config(**kw),
            cache=CacheConfig(
                page_size=4, num_blocks=64, dtype="float32", swa_ring=True
            ),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_num_batched_tokens=64,
                speculative_ngram=spec, spec_ngram_k=4,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
        )
        return LLMEngine(cfg)

    base = make(False).generate([list(p) for p in PROMPTS], sp)
    eng = make(True)
    assert eng.runner.swa is not None
    spec = eng.generate([list(p) for p in PROMPTS], sp)
    assert list(base.values()) == list(spec.values())


# --------------------------------------------------------------------- #
# the KV-provisional-write rule


def _committed_hashes_are_subset_of_accepted(eng, streams, prompts):
    """Every hash in the allocator's content index must re-derive from
    some request's ACCEPTED prompt+output tokens — a committed page of
    rejected draft content would fail this set check."""
    page = eng.allocator.page_size
    legit: set[bytes] = set()
    for prompt, out in zip(prompts, streams):
        legit.update(page_hashes_for_tokens(list(prompt) + list(out), page))
    committed = set(eng.allocator._cached.keys())
    assert committed, "no pages were committed: the walk proved nothing"
    assert committed <= legit, (
        f"{len(committed - legit)} committed page(s) hold content no "
        "accepted token stream produced (rejected draft KV leaked into "
        "the prefix-cache index)"
    )


@pytest.mark.parametrize("async_mode", [False, True])
def test_rejected_drafts_never_enter_prefix_index(async_mode):
    """Run a draft-heavy workload with small pages (rejections cross
    page boundaries), then walk the allocator's hash map: every
    committed page must re-derive from accepted tokens only."""
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    eng = make_engine(True, async_mode=async_mode, page=4, num_blocks=96)
    streams = list(eng.generate(PROMPTS, sp).values())
    sch = eng.scheduler
    assert sch.spec_proposed_tokens > sch.spec_accepted_tokens > 0, (
        "workload produced no rejections: the invariant wasn't exercised"
    )
    _committed_hashes_are_subset_of_accepted(eng, streams, PROMPTS)
    assert eng.allocator.usage() == 0.0  # all pages returned


@pytest.mark.parametrize("decode_window", [1, 4])
def test_spec_truncation_returns_pages_sync(decode_window):
    """Sync engines truncate a drafting row's pages back to the computed
    span every step: mid-run, with drafts rejected mid-draft, no running
    request may hold pages past ceil(computed / page) (the
    provisional-write span is transient), and the allocator's content
    index holds accepted content only."""
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    eng = make_engine(
        True, page=4, num_blocks=96, decode_window=decode_window
    )
    for p in PROMPTS:
        eng.add_request(list(p), sp)
    streams: dict[str, list[int]] = {}
    for _ in range(64):
        if not eng.has_work():
            break
        for out in eng.step():
            streams.setdefault(out.request_id, []).extend(out.new_token_ids)
        for req in eng.scheduler.running:
            if req.in_decode:
                max_pages = -(-req.num_computed_tokens // 4)
                assert len(req.block_ids) <= max_pages + 1, (
                    req.request_id, req.num_computed_tokens,
                    len(req.block_ids),
                )
    sch = eng.scheduler
    assert sch.spec_proposed_tokens > sch.spec_accepted_tokens > 0, (
        "workload produced no mid-draft rejections: nothing was proved"
    )
    _committed_hashes_are_subset_of_accepted(
        eng, list(streams.values()), PROMPTS
    )
    assert eng.allocator.usage() == 0.0


# --------------------------------------------------------------------- #
# one-shot verify under async stepping, readbacks and accounting


def test_spec_async_staggered_finishes(unforeseen_finishes):
    """Async rollback under speculation: staggered max_tokens make
    batch-mates finish at reconcile on several different steps; the
    surviving rows keep their planned 1 + k widths through the
    reconciled batch and the streams stay byte-identical to the plain
    sync engine."""
    prompts = [list(p) for p in (PROMPTS * 2)]
    params = [
        SamplingParams(
            temperature=0.0, max_tokens=8 + 3 * i, ignore_eos=True
        )
        for i in range(len(prompts))
    ]
    base = make_engine(False, num_blocks=128, max_seqs=8).generate(
        [list(p) for p in prompts], list(params)
    )
    eng = make_engine(True, async_mode=True, num_blocks=128, max_seqs=8)
    spec_k = eng.scheduler.spec_k
    reconciled: list[int] = []  # widest planned row of a reconciled batch
    seen = {"rollbacks": 0}
    orig = eng._dispatch_async

    def spy(batch, staged_dec=None):
        if (
            eng.stats.async_rollbacks_total > seen["rollbacks"]
            and batch.decodes
        ):
            reconciled.append(max(s.num_tokens for s in batch.decodes))
        seen["rollbacks"] = eng.stats.async_rollbacks_total
        return orig(batch, staged_dec)

    eng._dispatch_async = spy
    out = eng.generate([list(p) for p in prompts], list(params))
    assert list(base.values()) == list(out.values())
    assert len(reconciled) > 1, (
        "rollbacks did not land on several steps", reconciled
    )
    assert max(reconciled) == 1 + spec_k, (
        "no reconciled batch kept a drafting survivor", reconciled
    )
    assert eng.scheduler.spec_accepted_tokens > 0
    assert eng.allocator.usage() == 0.0


def _count_wait_steps(eng) -> dict:
    """Count the engine's blocking readbacks (``runner.wait_step``)."""
    calls = {"n": 0}
    orig = eng.runner.wait_step

    def counting(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    eng.runner.wait_step = counting
    return calls


@pytest.mark.parametrize("async_mode", [False, True])
def test_spec_one_readback_per_step(async_mode):
    """Exactly one blocking host readback per dispatched step, however
    many programs the step's verify/plain split launched, and accepted
    drafts push decode dispatches per emitted token below one."""
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    eng = make_engine(True, async_mode=async_mode)
    calls = _count_wait_steps(eng)
    eng.generate([list(p) for p in PROMPTS], sp)
    assert calls["n"] == eng.stats.engine_steps_total
    assert eng.stats.decode_dispatches_total > 0
    assert 0.0 < eng.stats.dispatches_per_emitted_token < 1.0


@pytest.mark.parametrize("async_mode", [False, True])
def test_spec_accept_len_hist_mean_is_exact(async_mode):
    """The accepted-len histogram keeps (count, sum) exact: sum equals
    the accepted draft tokens and count the (spec row, step) samples,
    so the dashboard's mean-emitted reading 1 + sum / count is the
    decode tokens emitted per row-step."""
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    eng = make_engine(True, async_mode=async_mode)
    rows = {"n": 0}
    orig = eng.scheduler.update_after_step

    def counting(batch, sampled):
        rows["n"] += len(batch.decodes)
        return orig(batch, sampled)

    eng.scheduler.update_after_step = counting
    eng.generate([list(p) for p in PROMPTS], sp)
    sch = eng.scheduler
    hist = sch.spec_accept_len_hist
    accepted = sum(j * c for j, c in enumerate(hist))
    assert accepted == sch.spec_accepted_tokens > 0
    assert sum(hist) == rows["n"]


def test_async_mixed_step_reuses_staged_arrays():
    """Async+spec mixed steps (only SOME rows drafting at dispatch)
    must SLICE the prestaged full-batch verify arrays by the subset
    index sets instead of restaging inside the blocking host region —
    and the sliced dispatch must stay byte-identical to the spec-off
    engine."""
    from llmd_tpu.engine.runner import ModelRunner

    hits = {"verify": 0, "decode": 0}
    orig_v = ModelRunner._subset_staged_verify
    orig_d = ModelRunner._subset_staged_decode

    def count_v(self, *a, **k):
        hits["verify"] += 1
        return orig_v(self, *a, **k)

    def count_d(self, *a, **k):
        hits["decode"] += 1
        return orig_d(self, *a, **k)

    # Mixed drafting needs rows that loop alongside rows that don't.
    prompts = [list(p) for p in PROMPTS] + [[9, 9, 9, 1, 2, 3, 4, 5]]
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    base = make_engine(False, num_blocks=96).generate(
        [list(p) for p in prompts], sp
    )
    # The flattened-token step (ragged_qlens, default) supersedes the
    # verify/decode SPLIT on mixed spec steps — one flat dispatch, no
    # subset slicing. The slicing path this test pins is the bucketed
    # fallback's, so pin it there explicitly.
    eng = make_engine(True, async_mode=True, num_blocks=96, ragged=False)
    try:
        ModelRunner._subset_staged_verify = count_v
        ModelRunner._subset_staged_decode = count_d
        out = eng.generate([list(p) for p in prompts], sp)
    finally:
        ModelRunner._subset_staged_verify = orig_v
        ModelRunner._subset_staged_decode = orig_d
    assert list(base.values()) == list(out.values())
    assert hits["verify"] > 0 and hits["decode"] > 0, (
        "no mixed step reused the prestaged arrays: the slicing path "
        "was never exercised", hits,
    )


# --------------------------------------------------------------------- #
# unified single-dispatch step x speculative decoding: mixed steps pack
# prefill chunks, one-shot [B, 1+k] verify rows and plain decode rows
# into ONE program — acceptance, truncation and byte parity unchanged.

# A long chunked prompt keeps prefill chunks arriving while the periodic
# prompts decode WITH drafts in flight: the three-program split case
# (prefill + verify + decode) the unified step collapses.
UNIFIED_SPEC_PROMPTS = [
    list(np.random.default_rng(3).integers(0, 8, size=6)) * 7,  # 42, chunked
    *PROMPTS,
]


def make_unified_spec(unified, spec=True, async_mode=False, seed=0):
    cfg = EngineConfig(
        model=tiny_model_config(),
        cache=CacheConfig(page_size=4, num_blocks=96, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=16,
            speculative_ngram=spec, spec_ngram_k=4, spec_ngram_min_match=2,
            unified_step=unified,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
    )
    return LLMEngine(cfg, _synchronous_step=not async_mode)


def test_unified_spec_one_shot_parity_greedy():
    """Unified spec steps (verify rows riding the unified program) vs
    the fully split spec-off engine: byte-identical, with speculation
    AND unified steps both actually engaging."""
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    base = make_unified_spec(False, spec=False).generate(
        [list(p) for p in UNIFIED_SPEC_PROMPTS], sp
    )
    eng = make_unified_spec(True)
    out = eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.stats.unified_steps_total > 0
    assert eng.scheduler.spec_proposed_tokens > 0
    assert eng.scheduler.spec_accepted_tokens > 0
    assert eng.allocator.usage() == 0.0


def test_unified_spec_equals_split_spec():
    """Same spec engine, unified on vs off: identical streams AND
    identical acceptance histograms (the unified program changes how
    many dispatches a step pays, not what is drafted/accepted)."""
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    split = make_unified_spec(False)
    uni = make_unified_spec(True)
    a = split.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    b = uni.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    assert list(a.values()) == list(b.values())
    assert (
        split.scheduler.spec_accept_len_hist
        == uni.scheduler.spec_accept_len_hist
    )
    assert uni.stats.unified_steps_total > 0
    assert uni.stats.step_dispatches_total < split.stats.step_dispatches_total


def test_unified_spec_parity_seeded():
    sp = SamplingParams(temperature=0.3, max_tokens=16, seed=77, ignore_eos=True)
    base = make_unified_spec(False, spec=False, seed=3).generate(
        [list(p) for p in UNIFIED_SPEC_PROMPTS], sp
    )
    eng = make_unified_spec(True, seed=3)
    out = eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.stats.unified_steps_total > 0
    assert eng.scheduler.spec_proposed_tokens > 0


def test_unified_spec_rejected_drafts_never_enter_prefix_index():
    """The KV-provisional-write rule survives the unified program:
    rejected draft content verified inside a unified step must never
    reach the allocator's content index."""
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    eng = make_unified_spec(True)
    streams = list(
        eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp).values()
    )
    sch = eng.scheduler
    assert sch.spec_proposed_tokens > sch.spec_accepted_tokens > 0, (
        "workload produced no rejections: the invariant wasn't exercised"
    )
    assert eng.stats.unified_steps_total > 0
    _committed_hashes_are_subset_of_accepted(
        eng, streams, UNIFIED_SPEC_PROMPTS
    )
    assert eng.allocator.usage() == 0.0


def test_unified_spec_async_rollback_parity(unforeseen_finishes):
    """Unified prestaging x spec x async: staged unified batches plan
    verify rows at max acceptance, late finishes roll staged rows back
    (surviving rows sliced from the prestaged arrays), and the stream
    stays byte-identical to the split sync spec-off engine."""
    sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    base = make_unified_spec(False, spec=False).generate(
        [list(p) for p in UNIFIED_SPEC_PROMPTS], sp
    )
    eng = make_unified_spec(True, async_mode=True)
    out = eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng._inflight is None
    assert eng.stats.unified_steps_total > 0
    assert eng.stats.async_rollbacks_total >= 1
    assert eng.allocator.usage() == 0.0


def test_unified_async_rollback_slices_staged_arrays(unforeseen_finishes):
    """A rollback that drops rows from a staged unified batch must
    SLICE the surviving rows' row-independent arrays out of the
    prestaged staging (ModelRunner.restage_unified over
    _slice_staged_rows) instead of restaging in the blocking host
    region — and the sliced dispatch must stay byte-identical."""
    from llmd_tpu.engine.runner import ModelRunner

    hits = {"subset": 0}
    orig = ModelRunner.restage_unified

    def counting(self, *a, **k):
        hits["subset"] += 1
        return orig(self, *a, **k)

    sp = SamplingParams(temperature=0.0, max_tokens=14, ignore_eos=True)
    base = make_unified_spec(False, spec=False).generate(
        [list(p) for p in UNIFIED_SPEC_PROMPTS], sp
    )
    eng = make_unified_spec(True, async_mode=True)
    try:
        ModelRunner.restage_unified = counting
        out = eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
    finally:
        ModelRunner.restage_unified = orig
    assert list(base.values()) == list(out.values())
    assert hits["subset"] > 0, (
        "no rollback reused the staged unified arrays: the slicing "
        "path was never exercised"
    )
    assert eng.stats.async_rollbacks_total > 0


def test_unified_spec_one_readback_per_step():
    """A mixed spec step — prefill chunk + verify rows + plain decode
    rows, up to THREE programs on the split engine — still costs exactly
    one blocking readback, and the unified engine dispatches fewer
    programs for the same byte-identical stream."""
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)

    def run(unified):
        eng = make_unified_spec(unified)
        calls = _count_wait_steps(eng)
        out = eng.generate([list(p) for p in UNIFIED_SPEC_PROMPTS], sp)
        assert calls["n"] == eng.stats.engine_steps_total
        return eng, out

    split_eng, split_out = run(False)
    uni_eng, uni_out = run(True)
    assert list(split_out.values()) == list(uni_out.values())
    assert uni_eng.stats.unified_steps_total > 0
    assert (
        uni_eng.stats.step_dispatches_total
        < split_eng.stats.step_dispatches_total
    )


# --------------------------------------------------------------------- #
# config / observability surfaces


def test_spec_decode_window_config():
    """speculative_ngram beside decode_window > 1 is accepted (and
    inert: see the parity tests); the knob that sized the retired fused
    verify window is gone."""
    cfg = SchedulerConfig(
        speculative_ngram=True, decode_window=4, spec_ngram_k=4,
        max_num_batched_tokens=8,
    )
    assert cfg.decode_window == 4
    retired = "spec_verify" "_window"  # split: the name is gone from the tree
    with pytest.raises(TypeError, match=retired):
        SchedulerConfig(speculative_ngram=True, **{retired: 2})
    with pytest.raises(ValueError, match="spec_ngram_k"):
        SchedulerConfig(speculative_ngram=True, spec_ngram_k=0)


def test_spec_metrics_surface():
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    eng = make_engine(True)
    eng.generate(PROMPTS, sp)
    st = eng.stats
    assert st.spec_proposed_tokens_total > 0
    assert st.spec_accepted_tokens_total > 0
    assert 0.0 < st.spec_acceptance_rate <= 1.0
    assert sum(st.spec_accepted_len_hist) > 0
    from llmd_tpu.serve.metrics import parse_prometheus, render_metrics

    page = render_metrics(st, "tiny")
    parsed = parse_prometheus(page)
    assert parsed["llmd:spec_proposed_tokens_total"] == st.spec_proposed_tokens_total
    assert parsed["llmd:spec_accepted_tokens_total"] == st.spec_accepted_tokens_total
    assert "llmd:spec_acceptance_rate" in parsed
    assert parsed["llmd:decode_dispatches_total"] == st.decode_dispatches_total
    assert "llmd:dispatches_per_emitted_token" in parsed
    assert 'llmd:spec_accepted_len_bucket{le="+Inf"' in page
    # per-request accounting rode along
    assert "llmd:spec_accepted_len_sum" in page


def test_spec_off_emits_no_spec_metrics():
    eng = make_engine(False)
    eng.generate([PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=4))
    from llmd_tpu.serve.metrics import render_metrics

    page = render_metrics(eng.stats, "tiny")
    assert "spec_" not in page


# --------------------------------------------------------------------- #
# resource-lifecycle regression pin (static-analysis.md, LLMD_LEAKSAN):
# the PR 2/4 seam — rejected draft tokens' provisional pages must be
# RETURNED by _truncate_spec_pages, not merely dropped from the request.


# The shared `leaksan` fixture lives in conftest.py.


def _run_spec_workload():
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    eng = make_engine(True, page=4)
    for p in PROMPTS:
        eng.add_request(list(p), sp)
    saw_spec = False
    for _ in range(128):
        if not eng.has_work():
            break
        eng.step()
        if eng.scheduler.spec_proposed_tokens:
            saw_spec = True
    assert not eng.has_work()
    assert saw_spec
    return eng


def test_spec_truncation_leak_free_under_sanitizer(leaksan):
    """Mid-draft rejections truncate provisional pages back through
    allocator.free: a full spec workload ends with ZERO outstanding
    page refs on the engine's allocator."""
    leaksan.leaksan_set_test("pin::spec-truncate")
    _run_spec_workload()
    assert leaksan.leaksan_check_test("pin::spec-truncate") == []


def test_spec_truncation_drop_without_free_caught(leaksan, monkeypatch):
    """Mutation pin: re-introduce the historical rollback bug —
    _truncate_spec_pages dropping the trailing pages from the request
    WITHOUT refunding them — and the sanitizer must name the leaked
    pages (with acquisition backtraces) instead of the pool silently
    shrinking on every rejected draft."""
    from llmd_tpu.engine.scheduler import EngineScheduler

    def leaky_truncate(self, req):
        page = self.allocator.page_size
        slots = req.num_computed_tokens
        if self.pipelined:
            slots = req.num_dispatched_tokens + 1 + self.spec_k
        keep = -(-slots // page)
        if keep < len(req.block_ids):
            del req.block_ids[keep:]  # dropped, never freed: the bug

    monkeypatch.setattr(
        EngineScheduler, "_truncate_spec_pages", leaky_truncate
    )
    leaksan.leaksan_set_test("pin::spec-truncate-mutated")
    eng = _run_spec_workload()
    leaks = leaksan.leaksan_check_test("pin::spec-truncate-mutated")
    assert leaks, "mutated rollback leaked no pages — pin has drifted"
    assert {r["resource"] for r in leaks} == {"pages"}
    assert all(r["stack"] for r in leaks)
    # and the pool really did shrink: the leaked refs are gone from the
    # free list even though every request finished
    assert eng.scheduler.allocator.num_free_pages < 64
