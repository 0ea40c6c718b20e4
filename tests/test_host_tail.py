"""The host's tail (EngineStats, obs/profiling.py::gc_watch): the collector's
pauses, a step's hold on the device by size, the engine thread's CPU time
and preemptions, the pace of a step ready to ready; their ``/metrics`` form
and the nine benchmark metrics that read them (perfbench/layer_metrics).
CPU, tiny engine: what is counted and where it lands, never how long."""

import gc
import os
import re
import subprocess
import sys
import threading
import time

import jax
import pytest

from llmd_tpu.engine import SamplingParams
from llmd_tpu.engine.engine import _HOLD_BUCKETS, _RUSAGE_THREAD, EngineStats
from llmd_tpu.obs import profiling
from llmd_tpu.serve.metrics import render_metrics
from perfbench import reducers
from tests.host_trace import host_spans
from tests.test_profiling import make_engine

GREEDY = dict(temperature=0.0, ignore_eos=True)


@pytest.fixture
def own_collector():
    """The module state of ``gc_watch`` as a fresh process has it (other
    tests of this worker leave engines open, and their watches with them),
    and no collection but those the test asks for."""
    watchers, hooked = profiling._gc_watchers, profiling._on_gc in gc.callbacks
    profiling._gc_watchers = 0
    if hooked:
        gc.callbacks.remove(profiling._on_gc)
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()
        profiling._gc_watchers = watchers
        if hooked and profiling._on_gc not in gc.callbacks:
            gc.callbacks.append(profiling._on_gc)


def collector(eng) -> tuple:
    s = eng.stats
    return (s.gc_pause_ms_total, s.gc_collections_total,
            s.gc_full_pause_ms_total, s.gc_full_collections_total)


def run(eng, prompts, max_tokens=6) -> None:
    for p in prompts:
        eng.add_request(list(p), SamplingParams(max_tokens=max_tokens, **GREEDY))
    while eng.has_work():
        eng.step()


def test_a_forced_full_collection_is_counted_once_and_written_as_one_span(tmp_path, own_collector):
    eng = make_engine()
    run(eng, [[1, 2, 3, 4, 5]])  # compiled
    before = collector(eng)
    profiling.start(tmp_path)
    try:
        gc.collect(2)
        gc.collect(0)  # a young one: counted, not full
        run(eng, [[9, 8, 7, 6, 5]], max_tokens=3)  # a step folds the totals' growth in
    finally:
        profiling.stop()
    pause, n, full_pause, full_n = (b - a for a, b in zip(before, collector(eng)))
    assert (n, full_n) == (2, 1)
    assert 0 <= full_pause <= pause
    spans = [(b, e, st) for name, b, e, st in host_spans(tmp_path) if name == "llmd.runner.gc"]
    assert [st["generation"] for _, _, st in spans] == [2, 0]
    assert all(e >= b for b, e, _ in spans)
    # the span and the counter time the same pass, on two clocks
    assert (spans[0][1] - spans[0][0]) / 1e6 == pytest.approx(full_pause, rel=0.5, abs=1.0)
    eng.close()


def test_two_engines_share_one_callback_and_the_last_to_close_removes_it(own_collector):
    a, b = make_engine(), make_engine()
    assert gc.callbacks.count(profiling._on_gc) == 1
    for eng in (a, b):
        run(eng, [[1, 2, 3]], max_tokens=2)
    before = collector(a), collector(b)
    gc.collect(2)
    for eng in (a, b):
        run(eng, [[4, 5, 6]], max_tokens=2)
    # one collection of the process: each engine saw it, once
    assert [after[3] - was[3] for was, after in zip(before, (collector(a), collector(b)))] == [1, 1]
    a.close()
    a.close()  # idempotent: b's watch stands
    assert gc.callbacks.count(profiling._on_gc) == 1
    seen = collector(b)[1]
    gc.collect(0)
    run(b, [[7, 8, 9]], max_tokens=2)
    assert collector(b)[1] == seen + 1
    b.close()
    assert profiling._on_gc not in gc.callbacks
    totals = profiling.gc_totals()
    gc.collect(0)  # timed by nobody now
    assert profiling.gc_totals() == totals


def holds(eng) -> list:
    return [getattr(eng.stats, name) for name in _HOLD_BUCKETS]


def test_a_poll_that_sleeps_past_a_ready_output_lands_in_its_bucket():
    """The intake hook of ONE step waits until the step in flight is done on
    the device and sleeps 30 ms more: the device stood finished for that
    long with nothing queued, and the hold says so, in ``16to64``."""
    eng = make_engine()
    sp = dict(max_tokens=10, **GREEDY)
    eng.intake_hook = lambda: 0
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(**sp))  # compiled
    armed = []

    def late_poll():
        if armed and eng._inflight is not None:
            armed.pop()
            jax.block_until_ready(eng.runner.last_tokens)  # the in-flight program's own output
            time.sleep(0.03)
        return 0

    eng.intake_hook = late_poll
    eng.add_request([9, 8, 7, 6, 5], SamplingParams(**sp))
    eng.step()  # lands the prompt, enters the pipeline
    before, s = holds(eng), eng.stats
    held_ms, held_n = s.step_host_hold_ms_total, s.step_host_holds_total
    armed.append(1)
    while eng.has_work():
        eng.step()
    grown = [b - a for a, b in zip(before, holds(eng))]
    assert grown[_HOLD_BUCKETS.index("step_host_hold_16to64ms_total")] >= 1
    assert sum(grown[3:]) < sum(grown[:3])  # the other steps' holds are small: a stall is an event
    assert sum(holds(eng)) == s.step_host_holds_total
    assert s.step_host_holds_total - held_n == sum(grown) >= 8
    # (the last step has nothing to dispatch behind it: no hold)
    assert s.step_host_holds_total < s.engine_steps_total
    assert s.step_host_hold_ms_total - held_ms >= 30.0
    # the hold holds the ready-lag bound, which is where the sleep fell
    assert s.step_ready_lag_bound_ms_total >= 30.0


def test_the_synchronous_step_holds_nothing_and_paces_nothing():
    eng = make_engine(pipelined=False)
    run(eng, [[1, 2, 3, 4, 5], [3, 4, 5]])
    s = eng.stats
    assert s.engine_steps_total >= 6
    assert s.step_host_holds_total == 0 and sum(holds(eng)) == 0
    assert s.step_ready_intervals_decode_total == s.step_ready_intervals_prefill_total == 0


def test_no_ready_interval_is_counted_across_a_pipeline_that_ran_empty():
    """Two bursts with the engine idle between them: every step but a
    burst's first is paced from the step in front of it, and the sums are
    the bursts' own lengths, first ready to last: the idle time is in none."""
    eng = make_engine(max_batched=16)
    readies: list = []
    finish = eng._finish_step

    def finishing(*a, **kw):
        readies.append(kw["ready_at"])
        return finish(*a, **kw)

    eng._finish_step = finishing
    bursts = []
    for prompts in ([list(range(1, 41)), [5, 6, 7]], [list(range(50, 75))]):
        first = len(readies)
        run(eng, prompts)
        bursts.append(readies[first:])
        time.sleep(0.05)
    s = eng.stats
    assert all(len(b) >= 6 for b in bursts)
    assert (s.step_ready_intervals_decode_total + s.step_ready_intervals_prefill_total
            == sum(len(b) - 1 for b in bursts))
    # (a 40-token prompt under a 16-token budget: mixed steps are paced too)
    assert s.step_ready_intervals_prefill_total >= 2 and s.step_ready_intervals_decode_total >= 6
    paced_ms = s.step_ready_interval_ms_decode_total + s.step_ready_interval_ms_prefill_total
    assert paced_ms == pytest.approx(sum(b[-1] - b[0] for b in bursts) * 1e3, rel=1e-9)
    assert paced_ms < (readies[-1] - readies[0]) * 1e3 - 50.0


def test_the_engine_threads_cpu_time_is_its_own_and_a_new_thread_starts_anew():
    eng = make_engine()
    run(eng, [[1, 2, 3, 4, 5]])  # compiled; the first step set the baseline
    s = eng.stats
    t0, c0 = time.monotonic(), s.engine_thread_cpu_ms_total
    run(eng, [[9, 8, 7, 6, 5]])
    spent = s.engine_thread_cpu_ms_total - c0
    assert 0 < spent <= (time.monotonic() - t0) * 1e3 + 1.0  # CPU time of ONE thread: under the wall clock
    assert s.engine_thread_preemptions_total >= 0
    # Another thread steps: its clock has nothing to do with this one's.
    c1, p1 = s.engine_thread_cpu_ms_total, s.engine_thread_preemptions_total
    eng._thread_seen = (eng._thread_seen[0], 1e9, 10**9)  # (a baseline no growth can follow)
    worker = threading.Thread(target=run, args=(eng, [[2, 4, 6]], 1))
    worker.start()
    worker.join()
    assert s.engine_thread_cpu_ms_total >= c1 and s.engine_thread_preemptions_total >= p1
    assert eng._thread_seen[0] == worker.ident != threading.get_ident()


@pytest.mark.skipif(_RUSAGE_THREAD is None or not hasattr(os, "sched_setaffinity"),
                    reason="needs RUSAGE_THREAD and sched_setaffinity (Linux)")
def test_an_engine_thread_that_shares_its_core_is_counted_preempted():
    """The thread that steps and a process that never sleeps are held to ONE
    core: the kernel takes the core from the thread again and again while
    its wait polls, and the counter says so. The first step on the thread
    only sets the baseline; what follows is counted, and the thread's CPU
    time stays under the wall clock it shared."""
    eng = make_engine()

    def busy_poll():  # a serving loop's poll with 2 ms of work in it: the thread WANTS the core
        until = time.monotonic() + 0.002
        while time.monotonic() < until:
            pass
        return 0

    eng.intake_hook = busy_poll
    run(eng, [[1, 2, 3, 4, 5]])  # compiled
    s = eng.stats
    core = max(os.sched_getaffinity(0))
    seen: dict = {}

    def stepping():
        try:
            os.sched_setaffinity(0, {core})  # (0: the calling thread alone)
        except OSError as e:
            seen["refused"] = e
            return
        run(eng, [[2, 4, 6]], max_tokens=1)  # this thread's baseline
        seen["before"] = (s.engine_thread_preemptions_total, s.engine_thread_cpu_ms_total, time.monotonic())
        until = time.monotonic() + 20.0
        while s.engine_thread_preemptions_total - seen["before"][0] < 3 and time.monotonic() < until:
            run(eng, [[9, 8, 7, 6, 5]], max_tokens=16)
        seen["after"] = (s.engine_thread_preemptions_total, s.engine_thread_cpu_ms_total, time.monotonic())

    spinner = subprocess.Popen([sys.executable, "-c", "while True: pass"])
    try:
        try:
            os.sched_setaffinity(spinner.pid, {core})
        except OSError as e:
            pytest.skip(f"this host refuses sched_setaffinity: {e}")
        worker = threading.Thread(target=stepping)
        worker.start()
        worker.join()
    finally:
        spinner.kill()
        spinner.wait()
    if "refused" in seen:
        pytest.skip(f"this host refuses sched_setaffinity: {seen['refused']}")
    (p0, c0, t0), (p1, c1, t1) = seen["before"], seen["after"]
    assert p1 - p0 >= 3, (p1 - p0, t1 - t0)
    assert 0 < c1 - c0 < (t1 - t0) * 1e3


def test_metrics_page_shows_the_holds_as_one_cumulative_histogram():
    stats = EngineStats()
    for i, name in enumerate(_HOLD_BUCKETS):
        setattr(stats, name, i + 1)  # 1, 2, .. 6 holds a bucket
    stats.step_host_holds_total, stats.step_host_hold_ms_total = 21, 1234.56789
    page = render_metrics(stats, "tiny")
    assert "# TYPE llmd:step_host_hold_ms histogram" in page
    buckets = re.findall(r'^llmd:step_host_hold_ms_bucket\{le="([^"]+)",model_name="tiny"\} (\d+)$', page, re.M)
    assert buckets == [("1", "1"), ("4", "3"), ("16", "6"), ("64", "10"), ("256", "15"), ("+Inf", "21")]
    assert 'llmd:step_host_hold_ms_count{model_name="tiny"} 21\n' in page
    assert 'llmd:step_host_hold_ms_sum{model_name="tiny"} 1234.568\n' in page
    assert "vllm:step_host_hold" not in page  # this engine's name, not vLLM's
    # an engine that has not stepped yet: a histogram of nothing, not a missing one
    assert 'llmd:step_host_hold_ms_bucket{le="+Inf",model_name="tiny"} 0\n' in render_metrics(EngineStats(), "tiny")


# --------------------------------------------------------------------------- #
# The nine benchmark metrics (perfbench/layer_metrics/*.json, BENCHMARK.json).

CHANGE = {  # a window's counter deltas under this program
    "engine_steps_total": 2000,
    "step_host_hold_ms_total": 900.0, "step_host_holds_total": 1800,
    "step_host_hold_le1ms_total": 1700, "step_host_hold_1to4ms_total": 80,
    "step_host_hold_4to16ms_total": 11, "step_host_hold_16to64ms_total": 5,
    "step_host_hold_64to256ms_total": 3, "step_host_hold_over256ms_total": 1,
    "gc_pause_ms_total": 500.0, "gc_collections_total": 40,
    "gc_full_pause_ms_total": 0.0, "gc_full_collections_total": 0,
    "engine_thread_cpu_ms_total": 3000.0, "engine_thread_preemptions_total": 14,
    "step_ready_interval_ms_decode_total": 30000.0, "step_ready_intervals_decode_total": 1500,
    "step_ready_interval_ms_prefill_total": 15000.0, "step_ready_intervals_prefill_total": 500,
}
PARENT = {"engine_steps_total": 2000, "step_readback_ms_total": 1.0, "engine_idle_ms_total": 0.0}
COUNTER_METRICS = {
    "runner.host_hold_ms": 0.5,
    "runner.host_stalls_per_kstep": 5.0,
    "runner.gc_pause_ms_per_step": 0.25,
    "runner.gc_full_pause_ms_per_step": 0.0,  # no full collection in the window: a value
    "runner.preemptions_per_kstep": 7.0,
    "runner.host_cpu_ms_per_step": 1.5,
    "runner.decode_pace_ms": 20.0,
    "runner.mixed_pace_ms": 30.0,
}


def ctx(delta: dict, trace=None) -> dict:
    return {"series": {}, "counter_delta": delta, "counter_delta_traced": None, "trace": trace,
            "device": {}, "config": {}, "cell": {}}


@pytest.mark.parametrize("name", sorted(COUNTER_METRICS))
def test_a_counter_metric_reads_the_change_and_is_silent_on_a_parent(name):
    value = reducers.reduce("per_layer", name, ctx(CHANGE))
    assert value == pytest.approx(COUNTER_METRICS[name], rel=1e-12) and isinstance(value, float)
    assert reducers.reduce("per_layer", name, ctx(PARENT)) is None


def test_mixed_pace_is_left_out_of_a_window_without_a_mixed_step():
    none = {**CHANGE, "step_ready_interval_ms_prefill_total": 0.0, "step_ready_intervals_prefill_total": 0}
    assert reducers.reduce("per_layer", "runner.mixed_pace_ms", ctx(none)) is None
    assert reducers.reduce("per_layer", "runner.decode_pace_ms", ctx(none)) == 20.0


def test_idle_gc_share_reads_the_gaps_named_after_the_collector():
    trace = {"window_s": 2.0, "idle_by_host_s": {"llmd.runner.wait": 0.2, "llmd.runner.gc": 0.05}}
    name = "device.idle_gc_share"
    assert reducers.reduce("per_layer", name, ctx(CHANGE, trace)) == pytest.approx(2.5)
    quiet = {"window_s": 2.0, "idle_by_host_s": {"llmd.runner.wait": 0.2}}
    assert reducers.reduce("per_layer", name, ctx(CHANGE, quiet)) == 0.0  # no such gap: a value
    assert reducers.reduce("per_layer", name, ctx(CHANGE)) is None  # no trace
    assert reducers.reduce("per_layer", name, ctx(PARENT, trace)) is None  # a program that times no collector
    assert reducers.reduce("per_layer", name, ctx(CHANGE, {"window_s": 0.0, "idle_by_host_s": {}})) is None
    # a part of the two rests, as their files cut the idle time today
    both = ctx({**CHANGE, **PARENT}, trace)
    assert reducers.reduce("per_layer", "device.idle_unnamed_share", both) == pytest.approx(2.5)
    assert reducers.reduce("per_layer", "device.idle_unattributed_share", both) == pytest.approx(12.5)
