"""The profiler control and span helper (obs/profiling.py), the step-phase
counters of EngineStats, the queue-wait counter, and their serving surfaces
(/metrics, the engine.generate span, POST /start_profile, /stop_profile).
CPU, tiny engine: what is counted and what is written, never a time."""

import dataclasses

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams
from llmd_tpu.engine.engine import EngineStats
from llmd_tpu.obs import profiling
from llmd_tpu.obs.tracing import InMemoryExporter, configure_tracing, reset_tracing
from llmd_tpu.serve.api import build_app
from llmd_tpu.serve.async_engine import AsyncEngine
from llmd_tpu.serve.metrics import parse_prometheus, render_metrics
from llmd_tpu.serve.tokenizer import ByteTokenizer
from tests.host_trace import host_spans

NEW_COUNTERS = (
    "step_admit_ms_total", "step_schedule_ms_total", "step_launch_ms_total",
    "step_wait_ms_total", "step_finish_ms_total", "step_ms_total",
    "steps_prefill_total", "steps_decode_total", "steps_mixed_total",
    "step_ms_decode_total", "step_ms_prefill_total",
    "queue_wait_ms_total", "queue_admitted_total", "programs_traced_total",
    # the host's turn between two programs, and the serving loop's waits
    "step_ready_lag_bound_ms_total", "step_readback_ms_total", "step_gap_admit_ms_total",
    "engine_idle_ms_total", "intake_wait_ms_total", "intake_requests_total",
    "deliver_lag_ms_total", "outputs_delivered_total",
    # the host's tail (tests/test_host_tail.py; a step's hold is a histogram there)
    "step_ready_interval_ms_decode_total", "step_ready_intervals_decode_total",
    "step_ready_interval_ms_prefill_total", "step_ready_intervals_prefill_total",
    "gc_pause_ms_total", "gc_collections_total", "gc_full_pause_ms_total", "gc_full_collections_total",
    "engine_thread_cpu_ms_total", "engine_thread_preemptions_total",
)


@pytest.fixture
def anyio_backend():
    return "asyncio"


def make_engine(num_blocks=128, max_batched=64, pipelined=True, **sched) -> LLMEngine:
    cfg = EngineConfig(
        model=tiny_model_config(vocab_size=512, max_model_len=128),
        cache=CacheConfig(page_size=4, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=max_batched, **sched
        ),
    )
    return LLMEngine(cfg, _synchronous_step=not pipelined)


def host_events(trace_dir) -> dict:
    """{span name: stats dict} of the trace's ``llmd.*`` host events (the
    last written of each name)."""
    return {name: stats for name, _, _, stats in host_spans(trace_dir)}


def test_start_stop_refuse_a_second_session(tmp_path):
    assert not profiling.active()
    with pytest.raises(profiling.ProfilerBusy):
        profiling.stop()
    profiling.start(tmp_path / "a")
    try:
        assert profiling.active()
        with pytest.raises(profiling.ProfilerBusy):
            profiling.start(tmp_path / "b")
        assert profiling.active()  # the refused start left the session alone
    finally:
        assert profiling.stop() == str(tmp_path / "a")
    assert not profiling.active()
    assert not (tmp_path / "b").exists()


def test_a_span_lands_in_the_profilers_own_trace(tmp_path):
    with profiling.span("llmd.test.before"):  # no session: recorded nowhere
        pass
    profiling.start(tmp_path)
    try:
        with profiling.span("llmd.test.outer", rows=3) as outer:
            with profiling.span("llmd.test.inner"):
                pass
            outer.set_metadata(kind="mixed")

        @profiling.spanned("llmd.test.decorated")
        def work(x):
            return x + 1

        assert work(1) == 2
    finally:
        profiling.stop()
    events = host_events(tmp_path)
    assert set(events) == {"llmd.test.outer", "llmd.test.inner", "llmd.test.decorated"}
    assert events["llmd.test.outer"] == {"rows": 3, "kind": "mixed"}


def test_engine_steps_write_their_phase_spans(tmp_path):
    eng = make_engine()
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0, max_tokens=2))  # compiled
    profiling.start(tmp_path)
    try:
        eng.generate([[9, 8, 7, 6, 5]], SamplingParams(temperature=0.0, max_tokens=3))
    finally:
        profiling.stop()
    events = host_events(tmp_path)
    assert {
        "llmd.step", "llmd.step.admit", "llmd.sched.schedule", "llmd.runner.launch",
        "llmd.runner.build", "llmd.runner.dispatch", "llmd.runner.wait", "llmd.step.finish",
    } <= set(events)
    assert "llmd.runner.trace" not in events  # every shape was warm
    step = events["llmd.step"]  # the last one written: a decode step
    assert step["kind"] == "decode" and step["rows"] == 1 and step["tokens"] == 1
    assert step["program"] == eng.runner.last_program != ""
    assert events["llmd.runner.dispatch"]["program"] == eng.runner.last_program
    # (the last one written: scheduled under the request's last step, whose
    # end by max_tokens the pipelined step foresees: nothing is staged)
    assert events["llmd.sched.schedule"] == {"prefills": 0, "decodes": 0}
    assert events["llmd.step.commit"] == {"rolled": 0, "early": 0}
    assert events["llmd.runner.readback"]["bytes"] > 0  # the one packed output of the step


@pytest.mark.parametrize("pipelined", [False, True])
def test_wait_and_readback_are_two_spans_and_the_readback_is_counted(tmp_path, pipelined, monkeypatch):
    """Both kinds of step go through ``wait_step``: ``llmd.runner.wait`` ends
    where the host knows the outputs are ready, ``llmd.runner.readback``
    follows it (a sibling, not a child) and ``step_readback_ms_total`` sums
    its length; a blocking wait (no serving loop polls) has no ready lag.
    A pipelined step launches the next program BETWEEN the two (the moment
    the outputs are seen ready, before they are read back), and counts it.

    The counter's clock and the span's are read some microseconds apart, and
    under six test workers a thread may lose the processor for milliseconds
    between the two. So the wait and the readback are each given a BODY of
    a known least length (a pause before the outputs are ready, one in the
    parsing), and the counter is held to what it shares with the spans
    whatever the scheduler does: it holds every readback's body, and lies
    inside wait + readback less every wait's."""
    import time

    import jax

    body_ms = 2.0
    eng = make_engine(pipelined=pipelined)
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0, max_tokens=3))  # compiled
    block, split = jax.block_until_ready, eng.runner._split_results

    def blocking(x):  # (the wait's body: before the host knows the outputs are ready)
        time.sleep(body_ms / 1e3)
        return block(x)

    def splitting(*a):  # (the readback's body: the parsing, behind the transfer)
        time.sleep(body_ms / 1e3)
        return split(*a)

    monkeypatch.setattr(jax, "block_until_ready", blocking)
    eng.runner._split_results = splitting
    s = eng.stats
    before = (s.engine_steps_total, s.step_readback_ms_total, s.step_wait_ms_total,
              s.steps_dispatched_before_readback_total)
    profiling.start(tmp_path)
    try:
        eng.generate([[9, 8, 7, 6, 5]], SamplingParams(temperature=0.0, max_tokens=5))
    finally:
        profiling.stop()
    steps = s.engine_steps_total - before[0]
    spans = host_spans(tmp_path)
    waits, reads = ([(b, e) for name, b, e, _ in spans if name == want]
                    for want in ("llmd.runner.wait", "llmd.runner.readback"))
    assert len(waits) == len(reads) == steps >= 5
    for (wait_start, wait_end), (read_start, read_end) in zip(waits, reads):
        assert wait_start < wait_end <= read_start < read_end
        assert wait_end - wait_start >= body_ms * 1e6 and read_end - read_start >= body_ms * 1e6
    counted = s.step_readback_ms_total - before[1]
    both = sum(r[1] - w[0] for w, r in zip(waits, reads)) / 1e6
    assert body_ms * steps <= counted <= both - body_ms * steps
    assert counted < s.step_wait_ms_total - before[2] + 1e-6  # a part of the wait as counted
    assert s.step_ready_lag_bound_ms_total == 0.0  # no poll: nothing was looked at twice
    assert (s.step_commit_ms_total > 0) == pipelined and s.step_gap_admit_ms_total == 0.0
    # the launch of the next step lies between a step's wait and its readback
    launches = [(b, e) for name, b, e, _ in spans if name == "llmd.runner.launch"]
    between = [1 for w, r in zip(waits, reads) for lb, le in launches if w[1] <= lb and le <= r[0]]
    assert len(between) == s.steps_dispatched_before_readback_total - before[3]
    assert (len(between) >= 3) == pipelined
    from llmd_tpu.serve.metrics import parse_prometheus, render_metrics

    page = parse_prometheus(render_metrics(s, "tiny"))
    assert page["llmd:steps_dispatched_before_readback_total"] == s.steps_dispatched_before_readback_total
    assert page["llmd:async_wasted_rows_total"] == s.async_wasted_rows_total == 0


def test_async_first_step_lands_at_once_and_is_named_by_its_batch(tmp_path):
    eng = make_engine(pipelined=True)
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0, max_tokens=2))  # compiled
    eng.add_request([9, 8, 7, 6, 5], SamplingParams(temperature=0.0, max_tokens=2))
    steps = eng.stats.engine_steps_total
    profiling.start(tmp_path)
    try:
        outs = eng.step()  # a pipeline starts with a step that lands, and the next in flight
    finally:
        profiling.stop()
    assert [len(o.new_token_ids) for o in outs] == [1]
    assert eng.stats.engine_steps_total == steps + 1 and eng._inflight is not None
    events = host_events(tmp_path)
    step = events["llmd.step"]
    assert step["kind"] == "prefill" and step["rows"] == 1 and step["tokens"] == 5
    assert step["program"] == eng.runner.last_program
    assert "llmd.step.commit" not in events  # the synchronous step's phases
    while eng.has_work():
        eng.step()


@pytest.mark.parametrize("pipelined", [False, True])
def test_phase_counters_add_up(pipelined):
    eng = make_engine(max_batched=16, pipelined=pipelined)
    prompts = [list(range(1, 41)), [5, 6, 7], list(range(50, 75))]
    eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=6))
    s = eng.stats
    assert s.engine_steps_total > 6
    assert s.steps_prefill_total + s.steps_decode_total + s.steps_mixed_total == s.engine_steps_total
    assert s.steps_decode_total > 0 and s.steps_prefill_total + s.steps_mixed_total > 0
    phases = (
        s.step_admit_ms_total + s.step_schedule_ms_total + s.step_launch_ms_total
        + s.step_wait_ms_total + s.step_finish_ms_total
    )
    assert 0 < phases <= s.step_ms_total + 1e-6
    assert s.step_ms_decode_total + s.step_ms_prefill_total == pytest.approx(s.step_ms_total)
    if not pipelined:  # the host gap IS these three phases, unrounded
        assert (
            s.step_schedule_ms_total + s.step_launch_ms_total + s.step_finish_ms_total
            == pytest.approx(s.step_host_gap_ms_total, rel=1e-9)
        )
        assert s.step_commit_ms_total == s.step_redispatch_ms_total == 0.0
        assert s.steps_prestaged_total == s.steps_topped_up_total == 0
    else:  # readback to the next dispatch's return, in its two parts
        # (+ the whole host side of the step that started the pipeline)
        assert 0 < s.step_commit_ms_total + s.step_redispatch_ms_total < s.step_host_gap_ms_total
        assert s.step_commit_ms_total > 0 and s.step_redispatch_ms_total > 0
        assert 0 < s.steps_prestaged_total <= s.engine_steps_total
    assert s.queue_admitted_total == len(prompts) and s.queue_wait_ms_total >= 0
    assert s.programs_traced_total == eng.runner.programs_traced > 0
    assert len(eng.runner.traced_programs) == min(eng.runner.programs_traced, 256)
    _when, family, shape = eng.runner.traced_programs[-1]
    assert family in ("flat", "unified", "prefill", "decode_window") and len(shape) == 2
    # one entry per jitted program: all greedy here, so no (family, shape) twice
    programs = [(f, sh) for _when, f, sh in eng.runner.traced_programs]
    assert len(set(programs)) == len(programs)
    traced = eng.runner.programs_traced
    eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=6))
    assert eng.runner.programs_traced == traced  # the same shapes again: nothing traced


def test_queue_wait_counts_a_request_once_across_preemption():
    # 12 pages of 4 tokens for 3 x (10 prompt + 12 output) tokens: page
    # pressure preempts and re-admits (tests/test_engine.py).
    eng = make_engine(num_blocks=12)
    prompts = [list(r) for r in (range(10), range(20, 30), range(40, 50))]
    for p in prompts:
        eng.add_request(p, SamplingParams(temperature=0.0, max_tokens=12))
    reqs = list(eng.scheduler.waiting)
    outs = []
    while eng.has_work():
        outs.extend(eng.step())
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    assert eng.stats.queue_admitted_total == 3
    assert eng.stats.queue_wait_ms_total == pytest.approx(sum(r.queue_wait_ms for r in reqs))
    # every output carries the request's own wait and time to first token
    assert all(o.queue_wait_ms is not None and o.ttft_ms >= o.queue_wait_ms for o in outs)
    by_req = {r.request_id: r for r in reqs}
    assert all(o.queue_wait_ms == by_req[o.request_id].queue_wait_ms for o in outs)


def test_metrics_page_exposes_every_new_counter():
    stats = EngineStats()
    kinds = {f.name: f.type for f in dataclasses.fields(EngineStats)}
    for i, name in enumerate(NEW_COUNTERS):
        # ms sums are kept unrounded and rounded to 3 places where exported
        setattr(stats, name, i + 1 if kinds[name] == "int" else i + 1.00049)
    parsed = parse_prometheus(render_metrics(stats, "tiny"))
    for i, name in enumerate(NEW_COUNTERS):
        for family in ("vllm", "llmd"):
            assert parsed[f"{family}:{name}"] == pytest.approx(i + 1.0, abs=1e-9), name


def test_host_gap_sum_is_not_rounded_on_every_add():
    eng = make_engine()
    eng.stats.step_host_gap_ms_total = 0.0
    eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=8))
    total = eng.stats.step_host_gap_ms_total
    assert total != round(total, 3)  # accumulated as measured


@pytest.mark.anyio
async def test_generate_span_carries_queue_wait_and_ttft():
    exporter = InMemoryExporter()
    configure_tracing("test", exporter=exporter, sample_ratio=1.0)
    try:
        app = build_app(AsyncEngine(make_engine()), ByteTokenizer(), "tiny", 128)
        async with TestClient(TestServer(app)) as c:
            for stream in (False, True):
                r = await c.post("/v1/completions", json={
                    "prompt": "hello", "max_tokens": 4, "temperature": 0.0, "stream": stream})
                assert r.status == 200
                await r.read()
        spans = [s for s in exporter.spans if s.name == "engine.generate"]
        assert len(spans) == 2
        for s in spans:
            assert 0 <= s.attributes["llm_d.queue_wait_ms"] <= s.attributes["llm_d.ttft_ms"]
    finally:
        reset_tracing()


@pytest.mark.anyio
async def test_profile_endpoints(tmp_path):
    engine = make_engine()
    off = build_app(AsyncEngine(engine), ByteTokenizer(), "tiny", 128)
    async with TestClient(TestServer(off)) as c:
        assert (await c.post("/start_profile")).status == 409  # no --profile-dir
        assert (await c.post("/stop_profile")).status == 409  # nothing open
    on = build_app(AsyncEngine(engine), ByteTokenizer(), "tiny", 128, profile_dir=str(tmp_path))
    async with TestClient(TestServer(on)) as c:
        r = await c.post("/start_profile")
        assert r.status == 200 and (await r.json())["trace_dir"] == str(tmp_path)
        try:
            assert (await c.post("/start_profile")).status == 409  # one session at a time
            r = await c.post("/v1/completions", json={"prompt": "hi", "max_tokens": 3, "temperature": 0.0})
            assert r.status == 200
        finally:
            r = await c.post("/stop_profile")
        assert r.status == 200 and (await r.json()) == {"profiling": False, "trace_dir": str(tmp_path)}
        assert (await c.post("/stop_profile")).status == 409
    events = host_events(tmp_path)
    assert {"llmd.serve.intake", "llmd.step", "llmd.serve.deliver"} <= set(events)
    assert not profiling.active()
