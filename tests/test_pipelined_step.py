"""The pipelined step as the engine's default shape.

``tests/test_async_engine.py`` pins the two-slot pipeline to the
synchronous engine's token streams over the paged pool. Here: the models
whose caches were written for a scheduler whose dispatched and computed
positions are equal (the K-EXAONE ring and its retained sections, the
granite state pool and its snapshots, the sparse-attention indexer plane,
the bucketed latent step), each with a hybrid or prefix hit in the batch and
a stop token that rolls one row back while its mates go on; the top-up
admission (a request that arrives while step N runs rides step N+1); the
host's turn between two programs (its spans tile it, its counters add up to
it); the order in which ``AsyncEngine`` hands a step's outputs on, and the
serving loop's own waits (idle, intake, deliver); and the benchmark's shape
ladder, one dispatch a bucket.
"""

import asyncio
import dataclasses
import pathlib
import statistics
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.config import (  # noqa: E402
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
)
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.engine import runner as runner_mod  # noqa: E402
from llmd_tpu.engine.request import PriorityClass, RequestStatus  # noqa: E402
from llmd_tpu.engine.scheduler import AT_FINISH, AT_PROMPT_END, AT_RUN_END  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.obs import profiling  # noqa: E402
from llmd_tpu.serve.metrics import parse_prometheus, render_metrics  # noqa: E402
from tests.host_trace import host_spans  # noqa: E402
from tests.retained_state import ANSWER, FIRST, MORE  # noqa: E402


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


# model -> (cache keywords, scheduler keywords, shared prefix tokens)
GEOMETRY = {
    "tiny": (dict(page_size=4, num_blocks=128), dict(max_num_batched_tokens=64), 48),
    # 3 window layers to 1 full: two KV pools, retained sliding sections
    "tiny-exaone": (dict(page_size=4, num_blocks=256, swa_ring=True), dict(max_num_batched_tokens=32), 48),
    # Mamba-2 mixers + one attention layer: a state pool with snapshots
    "tiny-granite-hybrid": (dict(page_size=4, num_blocks=256), dict(max_num_batched_tokens=16), 40),
    # learned top-k sparse attention: the indexer plane under the page ids
    "tiny-dsa": (dict(page_size=8, num_blocks=128), dict(max_num_batched_tokens=48), 112),
    # latent attention: the bucketed unified step, not the flat one
    "tiny-mla": (dict(page_size=4, num_blocks=256), dict(max_num_batched_tokens=32), 48),
    # gated delta-rule mixers: the state pool's other kind of slot
    "tiny-qwen3-next": (dict(page_size=4, num_blocks=256), dict(max_num_batched_tokens=16), 40),
    # latent attention with the indexer: the latent plane and its index plane on the flat step
    "tiny-mla-dsa": (dict(page_size=8, num_blocks=128), dict(max_num_batched_tokens=48), 112),
}
# the paged pool, the ring, the state pool, the sparse-attention plane, the
# latent plane and the bucketed latent step
HYBRID = sorted(set(GEOMETRY) - {"tiny-qwen3-next"})


def make_engine(model: str, pipelined: bool, max_seqs=4, num_blocks=None, model_len=None, cache_kw=None, **sched) -> LLMEngine:
    cache, sched_kw, _ = GEOMETRY[model]
    cache = {**cache, **(cache_kw or {})}
    if num_blocks is not None:
        cache = {**cache, "num_blocks": num_blocks}
    model_cfg = get_model_config(model)
    if model_len is not None:
        model_cfg = dataclasses.replace(model_cfg, max_model_len=model_len)
    return LLMEngine(EngineConfig(
        model=model_cfg,
        cache=CacheConfig(dtype="float32", **cache),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, **{**sched_kw, **sched}
        ),
    ), _synchronous_step=not pipelined)


def serve(eng: LLMEngine, prompts, max_tokens=8, stop=()):
    """[(tokens, log-probs, request)] per prompt, all in the engine at once."""
    sp = SamplingParams(max_tokens=max_tokens, temperature=0.0, ignore_eos=not stop,
                        stop_token_ids=tuple(stop), logprobs=True)
    ids = [eng.add_request(list(p), sp) for p in prompts]
    reqs = list(eng.scheduler.waiting)
    toks = {rid: [] for rid in ids}
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
    return [(toks[rid], np.asarray(r.output_logprobs), r) for rid, r in zip(ids, reqs)]


def warm(eng: LLMEngine, prompts, max_tokens) -> None:
    """Other prompts of the same lengths, served first: every step shape of
    the test proper is then warm."""
    serve(eng, [[(t + 1) % 256 for t in p] for p in prompts], max_tokens=max_tokens)


def hit_counters(eng: LLMEngine) -> tuple:
    eng._refresh_gauges()
    s = eng.stats
    return (s.swa_section_hits_total, s.swa_section_misses_total,
            s.state_snapshot_hits_total, s.state_snapshot_misses_total)


def pools_in_use(eng: LLMEngine) -> tuple:
    """Pages that live references hold, main pool and ring / state pool."""
    a, w = eng.allocator, eng.swa_allocator
    return (a.num_pages - a.num_free_pages, None if w is None else w.num_pages - w.num_free_pages)


def session(model: str, pipelined: bool, stop=()):
    """Two requests over a shared prefix one after the other (the second
    leaves the retained section or snapshot at the prefix's end behind, where
    the model has one), then three together: two hits of the prefix and a
    stranger, with ``stop`` to end one of them early."""
    eng = make_engine(model, pipelined)
    shared = tokens(GEOMETRY[model][2], seed=5)
    warm = [shared + tokens(n, seed=s) for n, s in ((7, 6), (13, 7))]
    batch = [shared + tokens(11, seed=8), tokens(23, seed=9), shared + tokens(9, seed=10)]
    for p in warm:
        serve(eng, [p], max_tokens=4)
    got = serve(eng, batch, max_tokens=10, stop=stop)
    return eng, got


@pytest.mark.parametrize("model", HYBRID)
def test_pipelined_equals_synchronous_with_a_hit_and_a_mid_batch_stop(model):
    """The same tokens, request by request, and the same caches afterwards:
    the hits taken, the pages and ring or state slots still held. The stop
    token ends ONE row while its mates decode on, met at the commit a step
    after the pipelined engine has DISPATCHED that row again: the row is
    wasted (its token dropped), and gives back its pages, its ring or slot
    when it has landed. Every step but the ones a pipeline starts with was
    dispatched before the step in front of it was read back, among them the
    decode step behind the chunk that completes a prompt."""
    _, free_run = session(model, pipelined=False)
    stop = free_run[0][0][3]  # ends request 0 early; the others may never emit it
    sync, want = session(model, pipelined=False, stop=(stop,))
    pipe, got = session(model, pipelined=True, stop=(stop,))
    assert pipe._async and not sync._async
    assert [t for t, _, _ in got] == [t for t, _, _ in want]
    assert len(want[0][0]) < 10 and want[0][0][-1] == stop  # it did end early
    for (_, lp, _), (_, ref, _) in zip(got, want):
        np.testing.assert_allclose(lp, ref, atol=2e-5)
    assert [r.num_cached_tokens for _, _, r in got] == [r.num_cached_tokens for _, _, r in want]
    assert got[0][2].num_cached_tokens > 0  # the batch did take a hit
    assert hit_counters(pipe) == hit_counters(sync)
    assert pools_in_use(pipe) == pools_in_use(sync)
    wasted = pipe.stats.async_wasted_rows_total
    assert pipe._inflight is None and wasted >= 1 and pipe.stats.async_rollbacks_total == 0
    assert pipe.stats.steps_prestaged_total > 0 and sync.stats.steps_prestaged_total == 0
    early = pipe.stats.steps_dispatched_before_readback_total
    assert 0 < early <= pipe.stats.steps_prestaged_total and sync.stats.steps_dispatched_before_readback_total == 0
    assert early >= pipe.stats.steps_decode_total - 4  # (four pipelines start here)
    # the host-counted kernel counters count dispatched rows: the synchronous
    # engine's, and one decode token more for each wasted row
    assert pipe.stats.live_tokens_total == sync.stats.live_tokens_total + wasted
    for name in ("ssm_update_rows_total", "ssm_scan_tokens_total", "sparse_bound_tokens_total",
                 "sparse_unbound_tokens_total", "indexer_keys_scored_total", "attn_shared_tile_tokens_total"):
        assert 0 <= getattr(pipe.stats, name) - getattr(sync.stats, name) <= wasted * 65536, name  # (a row scores its whole context)
    # nothing of the wasted row is in the prefix index or a retained entry
    assert set(pipe.allocator._cached) == set(sync.allocator._cached)
    if pipe._swa_sections is not None:
        assert set(pipe._swa_sections._entries) == set(sync._swa_sections._entries)
        assert pipe._swa_sections.stats() == sync._swa_sections.stats()


class Payloads:
    """What ``_put_step`` was handed, dispatch by dispatch: the rows' token
    slots, which of them take their token from the device, the tokens the
    host packed for them, and whether the step in front was still unread."""

    def __init__(self, eng: LLMEngine):
        self.eng, self.steps = eng, []
        put = eng.runner._put_step

        def putting(op, B, QK, arrays):
            host = None  # (a prefill program's rows read no token of the device's)
            if "first" in arrays:
                host = arrays["first"]
            elif "stream" in arrays:  # (pad rows start at the stream's end)
                host = arrays["stream"][np.minimum(arrays["row_start"], len(arrays["stream"]) - 1)]
            dev = arrays.get("tok_dev", np.zeros(B, np.uint8))
            self.steps.append((eng._inflight is not None, arrays["tok_slot"].copy(), dev.copy(),
                               None if host is None else np.asarray(host).copy()))
            return put(op, B, QK, arrays)

        eng.runner._put_step = putting


@pytest.mark.parametrize("model", HYBRID)
def test_the_decode_row_behind_an_unread_step_takes_its_token_from_the_device(model):
    """A prompt of three chunks and a batch mate that decodes meanwhile. The
    chunk that completes the prompt goes out with step N; the sequence's first
    decode row goes out with N+1, before N is read back: the host packs no
    token for it (it has none), flags the row, and names the slot that N's row
    was told to leave its sample in. Every later decode row of a pipelined
    step is fed the same way, the step a pipeline starts with feeds the host's
    tokens, and the streams are the synchronous engine's."""
    budget = min(GEOMETRY[model][1]["max_num_batched_tokens"], 32)
    mate, long = tokens(7, seed=3), tokens(2 * budget + 5, seed=4)
    (want_mate, _, _), (want, _, _) = serve(
        make_engine(model, pipelined=False, max_num_batched_tokens=budget), [mate, long], max_tokens=6)
    eng = make_engine(model, pipelined=True, max_num_batched_tokens=budget)
    warm(eng, [mate, long], 6)
    seen = Payloads(eng)
    (got_mate, _, _), (got, _, r) = serve(eng, [mate, long], max_tokens=6)
    assert (got_mate, got) == (want_mate, want)
    none = eng.runner.token_slots
    fed = [(i, row) for i, (_, slots, dev, _) in enumerate(seen.steps) for row in np.flatnonzero(dev)]
    assert fed and all(seen.steps[i][0] for i, _ in fed)  # only behind a step not read back
    for i, row in fed:
        early, slots, dev, host = seen.steps[i]
        assert slots[row] < none and host[row] == 0  # the device's token: the host packed none
        # the step in front wrote that slot: a decode row, or the chunk that completed the prompt
        assert slots[row] in seen.steps[i - 1][1]
    # the long prompt's first decode row is among them, behind its last chunk
    slot_of_long = [s for _, slots, dev, _ in seen.steps for s in slots[dev != 0]]
    assert len(set(slot_of_long)) == 2  # both sequences were fed from the device
    # every decode row of an early step is device-fed; a step with nothing in flight feeds host tokens
    assert not any(dev.any() for early, _, dev, _ in seen.steps if not early)
    assert eng.stats.steps_dispatched_before_readback_total > 0 and eng.stats.async_wasted_rows_total == 0
    assert r.token_slot == -1 and sorted(eng.scheduler._token_slots) == list(range(none))


# --- a retained-state capture costs the host's turn nothing -------------------------

RETAINING = ["tiny-exaone", "tiny-granite-hybrid"]  # a ring's sections / a state pool's snapshots


class Recorder:
    """Recording fakes around one engine: every hash walk and ``hash_page``
    call, every step dispatch, device copy, capture and commit, in order."""

    def __init__(self, eng: LLMEngine, monkeypatch):
        from llmd_tpu.engine import kv_cache, scheduler as scheduler_mod

        self.eng, self.events, self.walks, self.pages_hashed = eng, [], [], 0
        self.captured, self.hashed_in_a_capture = [], 0
        walk, hash_page = kv_cache.page_hashes_for_tokens, kv_cache.hash_page

        def walking(token_ids, page_size, extra=b""):
            self.walks.append(len(token_ids))
            return walk(token_ids, page_size, extra)

        def hashing(*a, **kw):
            self.pages_hashed += 1
            return hash_page(*a, **kw)

        monkeypatch.setattr(kv_cache, "page_hashes_for_tokens", walking)
        monkeypatch.setattr(kv_cache, "hash_page", hashing)  # (the walk's own calls)
        monkeypatch.setattr(scheduler_mod, "hash_page", hashing)  # (the commit chain's)
        depth = [0]

        def dispatching(fn):
            def call(*a, **kw):
                if not depth[0]:
                    self.events.append("dispatch")
                depth[0] += 1
                try:
                    return fn(*a, **kw)
                finally:
                    depth[0] -= 1
            return call

        r = eng.runner
        for name in dir(r):
            if name.startswith("dispatch_"):
                setattr(r, name, dispatching(getattr(r, name)))
        copy, commit = r.copy_pages_on_device, eng.scheduler.update_after_step
        note, self.noted = eng.scheduler.note_dispatch, None  # the batch dispatched last
        # per hook call: is the request a row of the batch dispatched last?
        self.behind_own_dispatch = []

        def noting(batch):
            self.noted = batch
            return note(batch)
        capture, hook = eng._swa_sections.capture, eng.scheduler.capture_hook
        at = [None]  # the capture point of the hook that is running
        # per hook call: (point, request, its dispatched position, walks, pages hashed)
        self.hooked = []

        def copying(src, dst, swa=False):
            self.events.append("capture-copy" if at[0] else "seed-copy")
            return copy(src, dst, swa=swa)

        def committing(batch, sampled):
            self.events.append("commit")
            return commit(batch, sampled)

        def capturing(key, ring_ids, s0, n_pre, shared=False):
            self.captured.append((key, n_pre, shared))
            self.captured_at.append(at[0])
            return capture(key, ring_ids, s0, n_pre, shared=shared)

        def hooking(req, point):
            self.behind_own_dispatch.append(any(s.request is req for s in self.noted.seqs))
            before, at[0] = (len(self.walks), self.pages_hashed), point
            try:
                return hook(req, point)
            finally:
                at[0] = None
                cost = (len(self.walks) - before[0], self.pages_hashed - before[1])
                self.hooked.append((point, req, req.num_dispatched_tokens, *cost))
                if point != AT_FINISH:
                    self.hashed_in_a_capture += sum(cost)

        self.captured_at = []
        r.copy_pages_on_device = copying
        eng.scheduler.update_after_step = committing
        eng.scheduler.note_dispatch = noting
        eng._swa_sections.capture = capturing
        eng.scheduler.capture_hook = hooking

    def own(self, point=AT_PROMPT_END):
        """The (key, n_pre, shared) captured at ``point``."""
        return [c for c, p in zip(self.captured, self.captured_at) if p == point]

    def copies_lie_between_their_step_and_the_next(self) -> int:
        """Every capture's copy was enqueued with its own step the one
        dispatched last (the device runs it behind the step that wrote the
        state and in front of the next, which overwrites it) and not yet
        committed, and every step before it committed (a finish boundary's
        key hashes the token the step before sampled): dispatch, copy, commit
        behind a synchronous step; dispatch N+1, commit N, copy, dispatch N+2
        where N+1 went out before N's readback. Returns the copies."""
        assert all(self.behind_own_dispatch) and self.behind_own_dispatch
        ev = self.events
        copies = [i for i, e in enumerate(ev) if e == "capture-copy"]
        for i in copies:
            assert ev[:i].count("commit") == ev[:i].count("dispatch") - 1, ev[max(0, i - 4): i + 3]
        return len(copies)


def turns(eng: LLMEngine, n_turns=3, first=37, more=9, max_tokens=6, seed=40):
    """A session of ``n_turns``: each prompt is the last one, its answer and
    ``more`` new tokens. [(prompt, tokens, request)]."""
    prompt, out = tokens(first, seed=seed), []
    for i in range(n_turns):
        (toks, _lp, req), = serve(eng, [prompt], max_tokens=max_tokens)
        out.append((prompt, toks, req))
        prompt = prompt + toks + tokens(more, seed=seed + 1 + i)
    return out


def forget_the_admissions_key(eng: LLMEngine) -> None:
    """The parent's path: the capture finds no key on the request and walks
    the prompt (``_section_key``)."""
    hook = eng.scheduler.hybrid_hit_hook

    def hit(req):
        hook(req)
        req.capture_key = None

    eng.scheduler.hybrid_hit_hook = hit


@pytest.mark.parametrize("model", RETAINING)
@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "synchronous"])
def test_a_capture_walks_no_prompt_and_hashes_no_page(model, pipelined, monkeypatch):
    """Each turn's prompt is walked ONCE, at its admission; the capture at
    its end takes its key from that walk: no walk, no ``hash_page`` call of
    its own, and nothing counted as hashed again. What is hashed in all is
    the admission's walk and the commit chain's new pages."""
    eng = make_engine(model, pipelined)
    rec = Recorder(eng, monkeypatch)
    page = eng.config.cache.page_size
    session = turns(eng)
    assert len(rec.own()) == len(rec.own(AT_FINISH)) == len(session) == 3 and rec.hashed_in_a_capture == 0
    # (the capture at a sequence's last page hashes that ONE page onto the commit chain's tail)
    assert [(w, h) for point, _, _, w, h in rec.hooked if point == AT_FINISH] == [(0, 1)] * 3
    assert rec.walks == [(len(p) - 1) // page * page for p, _, _ in session]
    chain = sum((len(p) + len(t) - 1) // page - r.num_cached_tokens // page for p, t, r in session)
    assert rec.pages_hashed == sum(w // page for w in rec.walks) + chain + 3
    eng._refresh_gauges()
    assert eng.stats.retained_capture_rehashed_total == 0
    assert eng.stats.retained_capture_host_ms_total > 0
    page_of_metrics = parse_prometheus(render_metrics(eng.stats, model))
    for family in ("vllm", "llmd"):
        assert page_of_metrics[f"{family}:retained_capture_rehashed_total"] == 0
        assert page_of_metrics[f"{family}:retained_capture_host_ms_total"] > 0
    assert [r.num_cached_tokens for _, _, r in session[1:]] == [(len(p) + len(t) - 1) // page * page for p, t, _ in session[:-1]]


@pytest.mark.parametrize("model", RETAINING)
@pytest.mark.parametrize("extra", [b"", b"lora:tenant-a", b"salt:7"], ids=["plain", "lora", "cache_salt"])
def test_a_captured_key_is_the_section_key_of_its_prompt(model, extra, monkeypatch):
    """Byte for byte ``_section_key``'s (the fallback and the oracle), with
    the identity that the page hashes fold folded in; a hit is found by it:
    the next turn skips the whole of the last one's full pages, as it does
    when the capture walks the prompt (the parent's path)."""
    page, cached = None, {}
    for parents_path in (False, True):
        eng = make_engine(model, pipelined=True)
        eng.scheduler._hash_extra = lambda req: extra  # (a LoRA's name / a cache salt: what `hash_extra` folds)
        rec = Recorder(eng, monkeypatch)
        if parents_path:
            forget_the_admissions_key(eng)
        page = eng.config.cache.page_size
        session = turns(eng)
        own = rec.own()
        assert [k for k, _, _ in own] == [eng._section_key(p, extra)[0] for p, _, _ in session]
        # (and the one at a sequence's last page that of a prompt that goes on there)
        assert [k for k, _, _ in rec.own(AT_FINISH)] == [eng._section_key(p + t, extra)[0] for p, t, _ in session]
        assert [n for _, n, _ in own] == [(len(p) - 1) // page for p, _, _ in session]
        if extra:
            assert own[0][0] != eng._section_key(session[0][0], b"")[0]
        assert eng._swa_sections.rehashed == (3 if parents_path else 0)
        cached[parents_path] = [r.num_cached_tokens for _, _, r in session]
    assert cached[False] == cached[True] == [0] + [(len(p) + len(t) - 1) // page * page for p, t, _ in session[:-1]]


@pytest.mark.parametrize("model", RETAINING)
@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "synchronous"])
def test_a_captures_copy_is_dispatched_behind_its_step_and_outside_the_commit(model, pipelined, monkeypatch, tmp_path):
    """Dispatch N, the copy, dispatch N+1, on both steps and at every
    capture point: the device runs the copy behind the step that wrote the
    state and in front of the one that overwrites it. The step's own commit
    comes after the copy, and the ``llmd.state.capture`` span lies in no
    ``llmd.step.commit`` span (in the pipelined step: inside
    ``llmd.step.finish``, under the step just dispatched, behind the commit
    of the step before it)."""
    eng = make_engine(model, pipelined)
    shared = tokens(GEOMETRY[model][2], seed=5)
    serve(eng, [shared + tokens(7, seed=6)], max_tokens=4)  # (warm; leaves the shared pages behind)
    rec = Recorder(eng, monkeypatch)
    profiling.start(tmp_path)
    try:
        # a miss at the shared run's end (its section is captured as the
        # prefill passes it), a stranger, and each prompt's own end
        serve(eng, [shared + tokens(13, seed=7), tokens(23, seed=9)], max_tokens=5)
        turns(eng, n_turns=2)
    finally:
        profiling.stop()
    copies = rec.copies_lie_between_their_step_and_the_next()
    assert copies == len(rec.captured) >= 5 and {AT_RUN_END, AT_PROMPT_END, AT_FINISH} == set(rec.captured_at)
    assert "seed-copy" in rec.events  # (the session's second turn took its hit)
    if pipelined:
        assert eng.stats.steps_dispatched_before_readback_total > 0
    spans = host_spans(tmp_path)
    captures, commits = ([(b, e) for name, b, e, _ in spans if name == want]
                         for want in ("llmd.state.capture", "llmd.step.commit"))
    assert len(captures) == copies
    assert not any(b <= cb < e for cb, _ in captures for b, e in commits)
    if pipelined:
        # in a step with a commit (not the one a pipeline starts with): behind
        # the re-dispatch, inside the finish span, under the step just launched
        behind = 0
        for _, s0, s1, _ in (e for e in spans if e[0] == "llmd.step"):
            names = {n: (b, e) for n, b, e, _ in spans if s0 <= b and e <= s1}
            mine = [c for c in captures if s0 <= c[0] and c[1] <= s1]
            if "llmd.step.commit" in names and mine:
                launch, finish = names["llmd.runner.launch"], names["llmd.step.finish"]
                assert all(launch[1] <= cb and finish[0] <= cb and ce <= finish[1] for cb, ce in mine)
                behind += len(mine)
        assert behind >= 1


@pytest.mark.parametrize("model", RETAINING)
@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "synchronous"])
def test_a_session_reads_the_same_with_the_key_kept_as_with_the_prompt_walked(model, pipelined):
    """Greedy tokens and log-probs of a multi-turn session, the hits taken
    and the pools afterwards, against the parent's path (the key forgotten
    at admission, so that every capture falls back to ``_section_key``)."""
    got = {}
    for parents_path in (False, True):
        eng = make_engine(model, pipelined)
        if parents_path:
            forget_the_admissions_key(eng)
        session = turns(eng, n_turns=3, max_tokens=8)
        got[parents_path] = (
            [t for _, t, _ in session], [np.asarray(r.output_logprobs) for _, _, r in session],
            [r.num_cached_tokens for _, _, r in session], hit_counters(eng), pools_in_use(eng),
        )
        assert eng._swa_sections.rehashed == (3 if parents_path else 0)
    new, parent = got[False], got[True]
    assert new[0] == parent[0] and new[2:] == parent[2:]
    for a, b in zip(new[1], parent[1]):
        np.testing.assert_array_equal(a, b)
    assert all(c > 0 for c in new[2][1:])


# --- a sequence leaves its retained state behind at its last page before a foreseen finish


KINDS = RETAINING + ["tiny-qwen3-next"]  # a ring's sections, a Mamba-2 slot, a delta-rule slot
BOTH_STEPS = pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "synchronous"])


def uncached(model: str, pipelined: bool, prompts, max_tokens: int):
    """The same prompts one after the other, with prefix caching off."""
    eng = make_engine(model, pipelined, cache_kw=dict(enable_prefix_caching=False))
    assert eng._swa_sections is None
    return [serve(eng, [p], max_tokens=max_tokens)[0] for p in prompts]


def finish_captures(eng: LLMEngine) -> int:
    eng._refresh_gauges()
    return eng.stats.retained_finish_captures_total


@pytest.mark.parametrize("model", KINDS)
@BOTH_STEPS
def test_a_sessions_next_turn_starts_behind_its_own_last_answer(model, pipelined, monkeypatch):
    """Turn 2 is turn 1's prompt + answer + a question. It starts at the last
    page turn 1's answer filled, not at turn 1's prompt's end, and reads,
    tokens and log-probs, as a run with prefix caching off. The capture fired
    once a turn, behind the dispatch of the decode step that left the state
    AT that page and in front of the next, under the key the next admission's
    walk computes for it, for one page hashed and no walk."""
    from llmd_tpu.engine.kv_cache import page_hashes_for_tokens

    eng = make_engine(model, pipelined)
    rec = Recorder(eng, monkeypatch)
    page = eng.config.cache.page_size
    (first, answer, r1), (second, answer2, r2) = turns(eng, n_turns=2, first=FIRST, more=MORE, max_tokens=ANSWER)
    at = (FIRST + ANSWER - 1) // page * page
    assert r2.num_cached_tokens == at == 44 > (FIRST - 1) // page * page
    fired = [(req, pos, cost) for point, req, pos, *cost in rec.hooked if point == AT_FINISH]
    assert fired == [(r1, at, [0, 1]), (r2, (len(second) + ANSWER - 1) // page * page, [0, 1])]
    key, n_pre, shared = rec.own(AT_FINISH)[0]
    assert (key, n_pre, shared) == (page_hashes_for_tokens(second, page)[at // page - 1], at // page, False)
    assert rec.copies_lie_between_their_step_and_the_next() >= 2
    for (toks, lps, _), got, req in zip(uncached(model, pipelined, [first, second], ANSWER), (answer, answer2), (r1, r2)):
        assert got == toks
        np.testing.assert_allclose(np.asarray(req.output_logprobs), lps, atol=2e-5)
    assert finish_captures(eng) == 2 and eng.stats.retained_capture_rehashed_total == 0
    page_of_metrics = parse_prometheus(render_metrics(eng.stats, model))
    assert page_of_metrics["vllm:retained_finish_captures_total"] == page_of_metrics["llmd:retained_finish_captures_total"] == 2


NO_FORESEEN_FINISH = {
    # fed through 38: no page past the prompt's own (36)
    "an_answer_under_a_page": dict(max_tokens=3),
    # 37 + 26 tokens and one more are no prompt of a model of 64 (a resident-decode sequence stops there)
    "a_sequence_that_fills_the_model": dict(max_tokens=26, model_len=64),
    # ends at its fourth token, before the page its length would have filled
    "a_stop_token": dict(max_tokens=ANSWER, stop_at=3),
}


@pytest.mark.parametrize("model", KINDS)
@pytest.mark.parametrize("case", NO_FORESEEN_FINISH)
def test_no_capture_where_no_finish_is_foreseen_that_a_next_turn_could_use(model, case, monkeypatch):
    """Nothing fires; the sequence that comes back (the turn's continuation,
    or the same prompt again where the model is full) hits the entry at the
    prompt's end, as before, and reads as a run with prefix caching off."""
    kw = dict(NO_FORESEEN_FINISH[case])
    stop_at, max_tokens = kw.pop("stop_at", None), kw.pop("max_tokens")
    first = tokens(FIRST, seed=40)
    stop = ()
    if stop_at is not None:
        (free, _, _), = serve(make_engine(model, True, **kw), [first], max_tokens=max_tokens)
        stop = (free[stop_at],)
        assert stop[0] not in free[:stop_at]
    eng = make_engine(model, True, **kw)
    rec = Recorder(eng, monkeypatch)
    page = eng.config.cache.page_size
    (answer, _, r1), = serve(eng, [first], max_tokens=max_tokens, stop=stop)
    assert len(answer) == (max_tokens if stop_at is None else stop_at + 1)
    assert AT_FINISH not in [point for point, *_ in rec.hooked] and finish_captures(eng) == 0
    again = first if "model_len" in kw else first + answer + tokens(MORE, seed=41)
    (toks, lps, r2), = serve(eng, [again], max_tokens=3)
    assert r2.num_cached_tokens == (FIRST - 1) // page * page and hit_counters(eng) in ((1, 0, 0, 0), (0, 0, 1, 0))
    ref_eng = make_engine(model, True, cache_kw=dict(enable_prefix_caching=False), **kw)
    (want, want_lps, _), = serve(ref_eng, [again], max_tokens=3)
    assert toks == want
    np.testing.assert_allclose(lps, want_lps, atol=2e-5)


@pytest.mark.parametrize("model", KINDS)
def test_resident_sequences_that_restart_hit_their_prompts_end_with_the_cache_at_capacity(model):
    """As many retained entries as sequences, each run to the model length
    and sent again with its own prompt (a resident-decode cell): a capture at
    the last page could serve nobody and would evict another sequence's
    prompt's-end entry, a whole prefill a miss. None is taken, every restart
    hits, nothing is evicted."""
    eng = make_engine(model, True, max_seqs=2, model_len=64, cache_kw=dict(swa_sections=2))
    page = eng.config.cache.page_size
    prompts = [tokens(37, seed=50), tokens(45, seed=51)]
    # (the resident-decode generator's rule: up to one token short of the model length)
    sp = [SamplingParams(max_tokens=64 - len(p) - 1, temperature=0.0, ignore_eos=True) for p in prompts]
    for _ in range(3):
        reqs = []
        for p, s in zip(prompts, sp):
            eng.add_request(list(p), s)
            reqs.append(eng.scheduler.waiting[-1])
        while eng.has_work():
            eng.step()
        assert [r.num_tokens for r in reqs] == [63, 63]
    assert [r.num_cached_tokens for r in reqs] == [(len(p) - 1) // page * page for p in prompts]
    kept = eng._swa_sections
    assert finish_captures(eng) == 0 and (kept.hits, kept.misses, kept.evictions, kept.captures) == (4, 0, 0, 2)
    assert len(kept._entries) == kept.capacity == 2


@pytest.mark.parametrize("model", KINDS)
@BOTH_STEPS
def test_an_aborted_row_leaves_a_whole_entry_or_none(model, pipelined, monkeypatch):
    """Aborted while the step that fills its last page is in flight: the
    abort waits for that step's commit, which registers the page the entry
    is keyed by, so the entry is whole (a prompt that goes on there hits it
    and reads as uncached) and the cache's pages are the pool's. A copy that
    fails makes no entry and gives its pages back."""
    eng = make_engine(model, pipelined)
    rec = Recorder(eng, monkeypatch)
    kept, page = eng._swa_sections, eng.config.cache.page_size
    first = tokens(FIRST, seed=60)
    sp = SamplingParams(max_tokens=ANSWER, temperature=0.0, ignore_eos=True)
    rid, out = eng.add_request(list(first), sp), []
    while not any(point == AT_FINISH for point, *_ in rec.hooked):
        out += [t for o in eng.step() for t in o.new_token_ids]
    req = rec.hooked[-1][1]
    assert eng.abort_request(rid)
    while eng.has_work():
        out += [t for o in eng.step() for t in o.new_token_ids]
    at = (FIRST + ANSWER - 1) // page * page
    assert req.finish_reason.name == "ABORT" and req.num_computed_tokens <= at + 1
    assert kept.retained_pages == sum(len(e.pages) for e in kept._entries.values()) == pools_in_use(eng)[1]
    assert finish_captures(eng) == 1 and all(eng.allocator.has_cached(k) for k in kept._entries)
    goes_on = first + list(req.output_token_ids)[: at - FIRST] + tokens(MORE, seed=61)
    (toks, lps, r2), = serve(eng, [goes_on], max_tokens=4)
    assert r2.num_cached_tokens == at
    (want, want_lps, _), = uncached(model, pipelined, [goes_on], 4)
    assert toks == want
    np.testing.assert_allclose(lps, want_lps, atol=2e-5)
    # a copy that fails: no entry, its pages refunded, serving unaffected
    copy, before = eng.runner.copy_pages_on_device, (kept.retained_pages, len(kept._entries), kept.captures)

    def failing(src, dst, swa=False):
        raise RuntimeError("the device refused the copy")

    eng.runner.copy_pages_on_device = failing
    (toks, _, _), = serve(eng, [tokens(FIRST, seed=62)], max_tokens=ANSWER)
    eng.runner.copy_pages_on_device = copy
    assert len(toks) == ANSWER and (kept.retained_pages, len(kept._entries), kept.captures) == before
    assert kept.retained_pages == pools_in_use(eng)[1]


# --- the top-up admission ----------------------------------------------------------


class Arrivals:
    """An ``intake_hook`` that hands the engine one request: at a poll while
    the step in flight still runs, or at the one after its readback."""

    def __init__(self, eng: LLMEngine, while_running: bool, prompt, sp):
        self.eng, self.while_running, self.prompt, self.sp = eng, while_running, prompt, sp
        self.polls_running, self.polls_read, self.rid = 0, 0, None
        self.step_n = eng._inflight  # (behind its readback another step is in flight, or none)

    def __call__(self) -> int:
        running = self.step_n is not None and self.eng._inflight is self.step_n
        self.polls_running += running
        self.polls_read += not running
        if self.rid is not None or running != self.while_running:
            return 0
        self.rid = self.eng.add_request(list(self.prompt), self.sp)
        return 1


@pytest.mark.parametrize("model", ["tiny", "tiny-granite-hybrid"])
@pytest.mark.parametrize("while_running", [True, False], ids=["while_the_device_runs", "after_the_readback"])
def test_a_request_that_arrives_while_a_step_runs_rides_the_next(model, while_running):
    """Step N is in flight and step N+1 staged (the hook is polled while the
    device runs): the arrival is admitted into the staged batch and
    dispatched with it, as the synchronous engine, whose intake runs between
    two steps, would have it; the step is counted. One that arrives when N
    has been read back finds N+1 on the device already (it was dispatched the
    moment N was seen ready, before the readback): it rides N+2, as one that
    arrives while a synchronous engine's step N+1 runs."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    first, late = tokens(9, seed=1), tokens(13, seed=2)

    eng = make_engine(model, pipelined=True)
    warm(eng, [first, late], 6)
    topped = eng.stats.steps_topped_up_total
    a = eng.add_request(first, sp)
    got: dict = {a: []}
    for out in eng.step():  # the pipeline starts: a's prompt lands, step N is in flight behind it
        got[out.request_id].extend(out.new_token_ids)
    assert eng._inflight is not None
    hook = eng.intake_hook = Arrivals(eng, while_running, late, sp)
    for out in eng.step():  # N+1 staged, N read back, N+1 dispatched
        got[out.request_id].extend(out.new_token_ids)
    assert hook.rid is not None and hook.polls_running >= 1 and hook.polls_read == 1
    assert eng.stats.steps_dispatched_before_readback_total >= 1
    got[hook.rid] = []
    if not while_running:  # N+1 was on the device before the arrival
        assert [s.request.request_id for s in eng._inflight.batch.seqs] == [a]
        assert [r.request_id for r in eng.scheduler.waiting] == [hook.rid]
        for out in eng.step():  # N+2: scheduled with the arrival waiting, no top-up
            got[out.request_id].extend(out.new_token_ids)
    batch = eng._inflight.batch  # (a state-space prompt ends in a chunk of its own: a may still prefill)
    assert batch.prefills[-1].request.request_id == hook.rid
    assert eng.stats.steps_topped_up_total == topped + while_running
    assert [s.request.request_id for s in batch.seqs if s.request.request_id != hook.rid] == [a]
    while eng.has_work():
        for out in eng.step():
            got[out.request_id].extend(out.new_token_ids)
    assert eng.stats.steps_topped_up_total == topped + while_running

    sync = make_engine(model, pipelined=False)
    warm(sync, [first, late], 6)  # (the same prefix cache and state pool as the pipelined engine's)
    want: dict = {sync.add_request(first, sp): []}
    for _ in range(2 if while_running else 3):  # the step that landed, step N (and N+1)
        for out in sync.step():
            want[out.request_id].extend(out.new_token_ids)
    want[sync.add_request(late, sp)] = []  # between two steps
    while sync.has_work():
        for out in sync.step():
            want[out.request_id].extend(out.new_token_ids)
    assert list(got.values()) == list(want.values())
    assert pools_in_use(eng) == pools_in_use(sync)


def test_an_arrival_during_the_step_a_pipeline_starts_with_rides_the_step_behind_it():
    """A pipeline that starts from empty lands its first step synchronously
    (no poll in that wait) and dispatches the next behind it: what arrived
    meanwhile is taken in before that dispatch, not a step later."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    first, late = tokens(9, seed=1), tokens(13, seed=2)
    eng = make_engine("tiny", pipelined=True)
    warm(eng, [first, late], 6)
    a = eng.add_request(first, sp)
    hook = eng.intake_hook = Arrivals(eng, False, late, sp)
    got: dict = {a: [t for out in eng.step() for t in out.new_token_ids]}
    assert len(got[a]) == 1 and hook.polls_read == 1 and hook.polls_running == 0
    assert {s.request.request_id for s in eng._inflight.batch.seqs} == {a, hook.rid}
    got[hook.rid] = []
    while eng.has_work():
        for out in eng.step():
            got[out.request_id].extend(out.new_token_ids)

    sync = make_engine("tiny", pipelined=False)
    warm(sync, [first, late], 6)
    want: dict = {sync.add_request(first, sp): []}
    for out in sync.step():
        want[out.request_id].extend(out.new_token_ids)
    want[sync.add_request(late, sp)] = []  # between the first step and the second
    while sync.has_work():
        for out in sync.step():
            want[out.request_id].extend(out.new_token_ids)
    assert list(got.values()) == list(want.values())


def test_an_abort_of_a_staged_row_that_was_never_dispatched_is_dropped_before_the_wait():
    """The hook's first poll brings an abort of a request that the
    speculative schedule has just admitted: its pages are free at once
    (nothing of it is in flight), and the row must not be dispatched."""
    sp = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
    eng = make_engine("tiny", pipelined=True)
    warm(eng, [tokens(9, seed=1), tokens(7, seed=2)], 5)
    keep = eng.add_request(tokens(9, seed=1), sp)
    got = [t for out in eng.step() for t in out.new_token_ids]  # lands, and primes the pipeline
    gone = eng.add_request(tokens(7, seed=2), sp)  # waiting: the next schedule admits it

    def hook() -> int:
        hook.calls += 1
        return int(hook.calls == 1 and eng.abort_request(gone))

    hook.calls = 0
    eng.intake_hook = hook
    got += [t for out in eng.step() for t in out.new_token_ids]
    assert [s.request.request_id for s in eng._inflight.batch.seqs] == [keep]
    assert eng.stats.async_rollbacks_total == 1
    while eng.has_work():
        got += [t for out in eng.step() for t in out.new_token_ids]
    (want, _, _), = serve(make_engine("tiny", pipelined=False), [tokens(9, seed=1)], max_tokens=5)
    assert got == want and eng.allocator.usage() == 0.0


def test_a_staged_batch_row_reclaimed_by_a_head_that_then_fails_admission_is_not_dispatched():
    """After the readback an interactive arrival takes the staged batch-band
    row's pages (recompute-preemption) and still does not fit: nothing was
    added to the staged batch, and the preempted row must leave it all the
    same, or the device would write its KV into pages that are free. (On an
    engine whose staged step waits for the commit, here one that drafts:
    where the step is dispatched before the readback, the row is in flight
    by then and protected.)"""
    page = GEOMETRY["tiny"][0]["page_size"]
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    held, batch_row, head = tokens(30, seed=1), tokens(6, seed=2), tokens(34, seed=3)
    alone = [serve(make_engine("tiny", pipelined=False), [p], max_tokens=12)[0][0]
             for p in (held, batch_row, head)]

    eng = make_engine("tiny", pipelined=True, num_blocks=16, speculative_ngram=True, spec_ngram_k=1)
    warm(eng, [held, batch_row], 12)
    dispatch = eng._dispatch_async

    def only_running_rows(batch, staged=None):
        assert all(s.request.status is RequestStatus.RUNNING for s in batch.seqs)
        assert all(len(s.request.block_ids) * page >= s.request.num_dispatched_tokens + s.num_tokens
                   for s in batch.seqs)
        return dispatch(batch, staged)

    eng._dispatch_async = only_running_rows
    r = eng.add_request(held, sp)
    b = eng.add_request(batch_row, sp, priority=int(PriorityClass.BATCH))
    got: dict = {r: [], b: []}
    for out in eng.step():  # both prompts land, the first decode step is in flight
        got[out.request_id].extend(out.new_token_ids)
    assert [s.request.request_id for s in eng._inflight.batch.seqs] == [r, b]
    hook = eng.intake_hook = Arrivals(eng, False, head, sp)
    for out in eng.step():  # (r, b) staged again; the head arrives behind the readback
        got[out.request_id].extend(out.new_token_ids)
    waiting = {q.request_id: q for q in eng.scheduler.waiting}
    assert set(waiting) == {hook.rid, b} and not waiting[b].block_ids  # reclaimed, and still short
    assert eng.stats.batch_preemptions == 1 and eng.stats.steps_topped_up_total == 0
    assert [s.request.request_id for s in eng._inflight.batch.seqs] == [r]
    got[hook.rid] = []
    eng.intake_hook = None
    while eng.has_work():
        for out in eng.step():
            got[out.request_id].extend(out.new_token_ids)
    assert [got[r], got[b], got[hook.rid]] == alone  # (the preempted row recomputed its own)
    assert eng.allocator.usage() == 0.0 and eng._inflight is None


# --- the host's turn between two programs ----------------------------------------------

TURN = ("llmd.runner.readback", "llmd.step.commit", "llmd.sched.schedule", "llmd.runner.launch")
# a step dispatched the moment the one before it is seen ready: the launch first
EARLY_TURN = ("llmd.runner.launch", "llmd.runner.readback", "llmd.step.commit")
# an engine whose staged step wants the tokens on the host (its proposer drafts
# from them) keeps the order readback, commit, dispatch
DRAFTS = dict(speculative_ngram=True, spec_ngram_k=1)


class LateArrivals:
    """An ``intake_hook`` that brings one new request at every poll AFTER a
    readback (nothing in flight), so that every turn has a top-up in it."""

    def __init__(self, eng: LLMEngine, sp):
        self.eng, self.sp, self.n, self.on = eng, sp, 0, True

    def __call__(self) -> int:
        if not self.on or self.eng._inflight is not None:
            return 0
        self.n += 1
        self.eng.add_request(tokens(5, seed=100 + self.n), self.sp)
        return 1


@pytest.mark.parametrize("case", ["dispatched_at_ready", "plain_turn", "top_up_after_the_readback"])
def test_the_spans_of_a_pipelined_step_tile_the_turn(tmp_path, case):
    """From the end of ``llmd.runner.wait`` on, every instant lies in exactly
    one span, each one's end the next one's start. A step dispatched the
    moment the one before it was seen ready: launch (all the device waits
    for), then readback and commit, under the device. A step that waits for
    the commit: readback, commit, schedule (the top-up WITH its restage) and
    launch, to the dispatch's return. The readback lies behind the wait, not
    inside it. (A junction is a few microseconds of Python; the median over
    the steps is held to 50 us, so that one preempted step of a busy machine
    does not fail what every step would show were there code between two
    spans.) What a step dispatched at ready is filled with, and the put of
    its payload, were done ahead, while the step before it ran."""
    early, top_up_in_the_gap = case == "dispatched_at_ready", case == "top_up_after_the_readback"
    eng = make_engine("tiny", pipelined=True, max_seqs=8, **({} if early else DRAFTS))
    warm(eng, [tokens(9, seed=1), tokens(5, seed=2), tokens(5, seed=3)], 10)
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    eng.add_request(tokens(9, seed=1), sp)
    eng.step()  # lands, and primes the pipeline
    hook = eng.intake_hook = LateArrivals(eng, SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True))
    hook.on = top_up_in_the_gap
    admit = eng.stats.step_gap_admit_ms_total
    profiling.start(tmp_path)
    try:
        for _ in range(6):
            eng.step()
    finally:
        profiling.stop()
    hook.on = False
    while eng.has_work():
        eng.step()
    spans = host_spans(tmp_path)
    steps = [e for e in spans if e[0] == "llmd.step"]
    assert len(steps) == 6
    worst = []
    for _, s0, s1, _ in steps:
        inside = [e for e in spans if s0 <= e[1] and e[2] <= s1]
        (wait,) = [e for e in inside if e[0] == "llmd.runner.wait"]
        turn = [e for e in inside if e[0] in TURN and e[1] >= wait[2]]  # (the speculative schedule lies before the wait)
        want = [n for n in (EARLY_TURN if early else TURN) if top_up_in_the_gap or n != "llmd.sched.schedule"]
        assert [e[0] for e in turn] == want, [e[0] for e in inside]
        if early:  # filled and put ahead, under the device: the launch is the call alone
            assert not [e for e in inside if e[0] == "llmd.runner.build" and e[1] >= wait[2]]
            assert [e for e in inside if e[0] == "llmd.runner.build" and e[2] <= wait[2]]
        chain = [wait, *turn]
        junctions = [b[1] - a[2] for a, b in zip(chain, chain[1:])]
        assert all(j >= 0 for j in junctions)  # siblings: none starts inside the one before
        worst.append(max(junctions))
    assert statistics.median(worst) < 50_000, worst
    assert (eng.stats.step_gap_admit_ms_total > admit) == top_up_in_the_gap
    assert (eng.stats.steps_dispatched_before_readback_total > 0) == early


@pytest.mark.parametrize("early", [True, False], ids=["dispatched_at_ready", "behind_the_commit"])
def test_the_turns_counters_add_up_to_first_ready_to_dispatch_return(early):
    """What the device waits for host code, as counted, is what a clock
    around both reads from the instant the host knew the outputs were ready
    to the return of the next dispatch. A step dispatched at that instant:
    the redispatch alone, which is the whole host gap (readback and commit
    are counted too, and are under the device). A step that waits for the
    commit: readback + commit + redispatch, and the host gap is commit +
    redispatch and starts at the readback's END."""
    eng = make_engine("tiny", pipelined=True, **({} if early else DRAFTS))
    warm(eng, [tokens(9, seed=1), tokens(7, seed=2)], 12)
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    returned: list = []
    counted: list = []
    dispatch, finish = eng._dispatch_async, eng._finish_step

    def dispatching(*a, **kw):
        out = dispatch(*a, **kw)
        returned.append(time.monotonic())
        return out

    def finishing(batch, host_gap_s, **kw):
        if kw.get("commit_s"):  # a pipelined step
            counted.append((eng.runner.last_wait.ready_at, returned[-1], host_gap_s, kw))
        return finish(batch, host_gap_s, **kw)

    eng._dispatch_async, eng._finish_step = dispatching, finishing
    eng.intake_hook = lambda: 0  # a serving loop's poll: the wait polls is_ready()
    for p in (tokens(9, seed=1), tokens(7, seed=2)):
        eng.add_request(p, sp)
    s0 = {k: getattr(eng.stats, k) for k in ("step_readback_ms_total", "step_commit_ms_total",
                                             "step_redispatch_ms_total", "step_host_gap_ms_total",
                                             "steps_dispatched_before_readback_total")}
    while eng.has_work():
        eng.step()
    dispatched = [c for c in counted if c[1] > c[0]]  # (the last step has nothing to dispatch)
    assert len(dispatched) >= 8
    clock = sum(ret - ready for ready, ret, _, _ in dispatched)
    if early:
        parts = sum(kw["redispatch_s"] for _, _, _, kw in dispatched)
    else:
        parts = sum(kw["readback_s"] + kw["commit_s"] + kw["redispatch_s"] for _, _, _, kw in dispatched)
    assert parts == pytest.approx(clock, rel=0.05)
    for c in counted:
        _, _, gap, kw = c
        if early and c in dispatched:
            assert gap == kw["redispatch_s"]
        else:
            assert gap == pytest.approx(kw["commit_s"] + kw["redispatch_s"], rel=1e-9)
        assert kw["readback_s"] > 0 and kw["commit_s"] > 0 and 0 <= kw["gap_admit_s"] <= kw["redispatch_s"]
    st = eng.stats
    assert st.steps_dispatched_before_readback_total - s0["steps_dispatched_before_readback_total"] == (
        len(dispatched) if early else 0)
    assert st.step_readback_ms_total - s0["step_readback_ms_total"] >= 1e3 * sum(c[3]["readback_s"] for c in counted)
    assert st.step_gap_admit_ms_total <= st.step_redispatch_ms_total


@pytest.mark.parametrize("while_running", [True, False], ids=["under_the_device", "in_the_gap"])
def test_gap_admit_counts_only_the_admission_behind_the_readback(while_running):
    """A top-up that runs inside the wait, while the device executes, costs
    the device nothing and is no part of ``step_gap_admit_ms_total``; one
    behind the readback is, and is a part of the redispatch."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    first, late = tokens(9, seed=1), tokens(13, seed=2)
    # (behind the readback there is an admission only where the staged step
    # waits for the commit: an engine that drafts)
    eng = make_engine("tiny", pipelined=True, **({} if while_running else DRAFTS))
    warm(eng, [first, late], 6)
    eng.add_request(first, sp)
    eng.step()
    st = eng.stats
    before = (st.step_gap_admit_ms_total, st.step_redispatch_ms_total, st.steps_topped_up_total)
    hook = eng.intake_hook = Arrivals(eng, while_running, late, sp)
    eng.step()
    assert hook.rid is not None and st.steps_topped_up_total == before[2] + 1
    admit, redispatch = st.step_gap_admit_ms_total - before[0], st.step_redispatch_ms_total - before[1]
    if while_running:
        assert admit == 0.0
    else:
        assert 0.0 < admit <= redispatch
    while eng.has_work():
        eng.step()


class FakePack:
    """A step's packed output whose ``is_ready()`` turns true at its n-th call."""

    def __init__(self, rows: int, ready_at_call: int):
        self.arr, self.calls, self.ready_at_call = np.zeros((rows, 2), np.float32), 0, ready_at_call

    def is_ready(self) -> bool:
        self.calls += 1
        return self.calls >= self.ready_at_call

    def __array__(self, dtype=None, copy=None):
        return self.arr


@pytest.mark.parametrize("case", ["blocking", "ready_during_a_pause", "ready_at_the_first_look"])
def test_the_ready_lag_bound(case):
    """0 for a blocking wait; where the pack turns ready during a pause of
    the poll, at least that pause (the last look that found it running to
    the first that found it ready); where the first look finds it ready, what
    the poll did in front of that look."""
    runner = make_engine("tiny", pipelined=True).runner
    pack = FakePack(3, {"blocking": 1, "ready_during_a_pause": 4, "ready_at_the_first_look": 1}[case])
    pending = runner_mod.PendingUnified(pack, S=1, prefill_rows=[], decode_rows=[0, 1], n_prefills=0, n_decodes=2)
    polls: list = []

    def poll():
        polls.append(time.monotonic())
        if case == "ready_at_the_first_look":
            time.sleep(2e-3)  # an intake with a top-up in it

    t0 = time.monotonic()
    pres, dres = runner.wait_step(None, None, pending, poll=None if case == "blocking" else poll)
    t1 = time.monotonic()
    w = runner.last_wait
    assert pres is None and dres.tokens.shape == (2, 1)
    assert t0 <= w.ready_at <= w.read_at <= t1 and w.readback_s > 0
    if case == "blocking":
        assert w.ready_lag_bound_s == 0.0 and pack.calls == 0
    elif case == "ready_during_a_pause":
        assert len(polls) == 4 and pack.calls == 4
        assert runner_mod._POLL_S <= w.ready_lag_bound_s <= w.ready_at - polls[-2]
    else:
        assert len(polls) == 1 and 2e-3 <= w.ready_lag_bound_s <= w.ready_at - t0


# --- the serving loop ---------------------------------------------------------------


def test_a_steps_outputs_are_delivered_after_the_next_steps_dispatch():
    """``AsyncEngine._run``: step N's outputs reach their queues while step
    N+1 runs, so between two deliveries there is a dispatch; the stream a
    client sees is the synchronous engine's: the same tokens in order, one
    terminal item, the last."""
    from llmd_tpu.serve.async_engine import AsyncEngine

    sp = SamplingParams(max_tokens=7, temperature=0.0, ignore_eos=True)
    prompts = [tokens(9, seed=1), tokens(5, seed=2)]
    eng = make_engine("tiny", pipelined=True)
    events: list = []
    dispatch, assemble = eng._dispatch_async, eng._assemble_outputs

    def dispatching(*a, **kw):
        events.append("dispatch")
        return dispatch(*a, **kw)

    def assembling(*a, **kw):
        outs = assemble(*a, **kw)
        events.append(("outputs", len(outs)))
        return outs

    eng._dispatch_async, eng._assemble_outputs = dispatching, assembling

    async def run():
        served = AsyncEngine(eng, watchdog_s=0)
        served.start(asyncio.get_running_loop())
        deliver = served._deliver

        def delivering(rid, item):
            events.append("deliver")
            return deliver(rid, item)

        served._deliver = delivering

        async def one(i, p):
            return [out async for out in served.generate(f"r{i}", p, sp)]

        try:
            return await asyncio.wait_for(asyncio.gather(*(one(i, p) for i, p in enumerate(prompts))), 120)
        finally:
            served.stop()

    streams = asyncio.run(run())
    assert eng.intake_hook is None  # the hook goes with the serving thread
    want = serve(make_engine("tiny", pipelined=False), prompts, max_tokens=7)
    for items, (toks, _, _) in zip(streams, want):
        assert [t for it in items for t in it.new_token_ids] == toks
        assert [it.finished for it in items] == [False] * (len(items) - 1) + [True]
        assert [it.num_output_tokens for it in items] == sorted(it.num_output_tokens for it in items)
    # Every step's outputs were assembled after the next step's dispatch and
    # delivered after it too (but the step the pipeline started with, which
    # lands at once and is followed by the first pipelined dispatch, and the
    # last: nothing is left to dispatch).
    groups = [i for i, e in enumerate(events) if isinstance(e, tuple)]
    assert len(groups) >= 7
    assert events[groups[0] + 1] == "dispatch"
    for i in groups[1:-1]:
        assert events[i - 1] == "dispatch", events[max(0, i - 3): i + 2]
    first_deliver = events.index("deliver")
    assert events[:first_deliver].count("dispatch") >= 1  # the next step is on the device before any output goes out
    assert eng.stats.steps_prestaged_total >= 5
    # every output handed over is counted with its way from the readback's end
    assert eng.stats.outputs_delivered_total == events.count("deliver") == sum(len(items) for items in streams)
    assert eng.stats.deliver_lag_ms_total > 0 and eng.stats.intake_requests_total == len(prompts)


class SpanLog:
    """Stands in for ``profiling.span`` in the serving loop: every span's
    name, length and what ``check`` said when it opened."""

    def __init__(self, check):
        self.check, self.seen = check, []

    def __call__(self, name, **attrs):
        log = self

        class _Span:
            def __enter__(self):
                self.t, self.state = time.monotonic(), log.check(name)
                return self

            def __exit__(self, *exc):
                log.seen.append((name, time.monotonic() - self.t, self.state))

            def set_metadata(self, **kw):
                pass

        return _Span()


def test_the_idle_span_opens_only_with_nothing_to_run_and_is_counted(monkeypatch):
    """``llmd.serve.idle``: no inbox, no aborts, no work, not paused, and
    ``engine_idle_ms_total`` grows by its length; a paused engine waits under
    ``llmd.serve.paused`` and counts nothing, whatever it holds."""
    from llmd_tpu.serve import async_engine

    eng = make_engine("tiny", pipelined=True)
    warm(eng, [tokens(9, seed=1)], 4)
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)

    async def run():
        served = async_engine.AsyncEngine(eng, watchdog_s=0)
        log = SpanLog(lambda name: (served._paused, bool(served._inbox), bool(served._aborts), eng.has_work()))
        monkeypatch.setattr(async_engine.profiling, "span", log)
        served.start(asyncio.get_running_loop())
        try:
            await asyncio.sleep(0.05)  # nothing to run
            served.pause()
            queue = served.submit("held", tokens(9, seed=1), sp)  # a paused engine with an inbox
            await asyncio.sleep(0.05)
            idle_when_paused = eng.stats.engine_idle_ms_total
            await asyncio.sleep(0.03)
            assert eng.stats.engine_idle_ms_total == idle_when_paused
            served.resume()
            while not (await asyncio.wait_for(queue.get(), 60)).finished:
                pass
            await asyncio.sleep(0.03)  # nothing to run again
        finally:
            served.stop()
        return log.seen

    seen = asyncio.run(run())
    idle = [(length, state) for name, length, state in seen if name == "llmd.serve.idle"]
    paused = [(length, state) for name, length, state in seen if name == "llmd.serve.paused"]
    assert idle and all(state == (False, False, False, False) for _, state in idle)
    assert paused and all(state[0] for _, state in paused) and any(state[1] for _, state in paused)
    assert sum(length for length, _ in idle) >= 0.07
    # (the counter's clock is read just outside the span's)
    assert eng.stats.engine_idle_ms_total == pytest.approx(1e3 * sum(length for length, _ in idle), rel=0.02, abs=0.5)


def test_intake_wait_is_the_time_a_request_sat_in_the_inbox():
    """``submit`` stamps the request, ``_intake`` counts now - that: the wait
    that lies in front of ``arrival_time``, and so of queue wait and TTFT."""
    from llmd_tpu.serve.async_engine import AsyncEngine

    eng = make_engine("tiny", pipelined=True)
    served = AsyncEngine(eng, watchdog_s=0)  # no serving thread: the intake is called by hand
    sp = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
    t0 = time.monotonic()
    served.submit("a", tokens(5, seed=1), sp)
    time.sleep(0.02)
    served.submit("b", tokens(5, seed=2), sp)
    time.sleep(0.01)
    assert eng.stats.intake_requests_total == 0
    assert served._intake() == 2
    held = time.monotonic() - t0
    st = eng.stats
    assert st.intake_requests_total == 2 and len(eng.scheduler.waiting) == 2
    assert 1e3 * (0.03 + 0.01) <= st.intake_wait_ms_total <= 1e3 * 2 * held
    assert min(r.arrival_time for r in eng.scheduler.waiting) >= t0 + 0.03  # stamped at the intake
    assert served._intake() == 0 and st.intake_requests_total == 2
    for rid in ("a", "b"):
        eng.abort_request(rid)


# --- the benchmark's shape ladder ------------------------------------------------------


def test_the_shape_ladder_reaches_each_bucket_once():
    """``perfbench/topologies/engine.py::_run_shape`` steps once, aborts and
    drains. The pipeline starts from empty at every bucket, so its one step
    lands at once on the synchronous step's path (a shape's first call is
    seconds of tracing and lowering whose time follows the path it is
    called on: PERF.md section 6, PR 38) and nothing is left in flight.
    Every T bucket of the flat step is traced and dispatched exactly once,
    and traffic afterwards traces nothing."""
    from perfbench.topologies import engine as topology

    eng = make_engine("tiny", pipelined=True, max_seqs=8)
    runner = eng.runner
    assert runner.flat_t_buckets and eng._async

    class Harness:  # what _run_shape and _ladder read of a System
        engine, config = eng, eng.config
        geo: dict = {}
        vocab_size, max_model_len = eng.config.model.vocab_size, eng.config.model.max_model_len
        _SamplingParams = SamplingParams
        _ladder, _run_shape = topology.System._ladder, topology.System._run_shape
        _tokens, _sampling = topology.System._tokens, topology.System._sampling

    dispatched: list = []
    exec_flat = runner._exec_flat

    def counting(arrays, all_greedy, *put_ahead):
        dispatched.append(arrays["stream"].shape[0])
        return exec_flat(arrays, all_greedy, *put_ahead)

    runner._exec_flat = counting
    h, rng = Harness(), np.random.default_rng(0)
    ladder = h._ladder()
    buckets = [T for _, T in ladder]
    assert buckets == [T for T in runner.flat_t_buckets if T <= 64] and len(buckets) >= 4
    for shape in ladder:
        h._run_shape(rng, *shape)
        assert not eng.has_work() and eng._inflight is None
    assert dispatched == buckets  # one dispatch a bucket, in the ladder's order
    assert sorted(shape[0] for _, fam, shape in runner.traced_programs if fam == "flat") == buckets
    assert eng.allocator.usage() == 0.0
    traced = runner.programs_traced
    serve(eng, [tokens(n, seed=n) for n in (5, 17, 40)], max_tokens=6)
    assert runner.programs_traced == traced  # every shape the traffic reaches was warmed
