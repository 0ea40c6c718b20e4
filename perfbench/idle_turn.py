"""``device.idle_share`` by the parts of the host's turn between two step
programs, and the idle time a step: the arithmetic behind
``device.idle_{no_work,wait,readback,commit,unnamed}_share`` and
``device.idle_ms_per_step``, for their readers.

``perfbench/idle_split.py`` cut the idle time for the synchronous step:
``schedule``, ``launch``, ``finish`` and the rest. Since the step is
pipelined the rest IS the idle time. A program that times the turn
(llmd_tpu/engine/runner.py::wait_step, engine.py::_step_async,
serve/async_engine.py::_run) writes the spans that split it; ``share`` sums
``trace_reduce``'s ``idle_by_host_s`` by their names, in % of the traced
window:

  no_work   ``llmd.serve.idle``, ``llmd.serve.paused``: the serving loop
            had nothing to run. The only idle time that is the load's.
  wait      ``llmd.runner.wait``: the device had finished, or had not
            started, and the host did not know (the poll's pause, the
            notification, the launch latency at the head of the next wait)
  readback  ``llmd.runner.readback``: the transfer and the parsing
  commit    ``llmd.step.commit``
  unnamed   none of these and none of idle_split's three phases:
            ``llmd.step.admit``, ``llmd.serve.intake``, ``llmd.serve.deliver``,
            ``pb.*`` and "outside any step" (no ONE span covers half of
            the gap: a gap that runs through readback, commit and launch
            lands here, until the idle time is laid over the spans exactly)

With idle_split's ``schedule``, ``launch`` and ``finish`` the five add up
to the idle seconds of the first chip, which on one chip is
``device.idle_share``; the five alone to ``device.idle_unattributed_share``.

A program that does not time the turn (a parent of PR 39) has no
``llmd.runner.readback`` and its ``llmd.runner.wait`` holds the readback
too: its shares would read under the same names and mean something else, so
``times_the_turn`` looks for the turn's counters and every reader here
returns None without them (the line then leaves the metric out).
"""

from __future__ import annotations

from perfbench import idle_split

PARTS = {
    "no_work": ("llmd.serve.idle", "llmd.serve.paused"),
    "wait": ("llmd.runner.wait",),
    "readback": ("llmd.runner.readback",),
    "commit": ("llmd.step.commit",),
}
_NAMED = {span for spans in (*PARTS.values(), *idle_split.PHASES.values()) for span in spans}
# What a program that times the turn counts (EngineStats).
TURN_COUNTERS = ("step_readback_ms_total", "engine_idle_ms_total")


def times_the_turn(ctx: dict) -> bool:
    delta = ctx.get("counter_delta") or {}
    return all(name in delta for name in TURN_COUNTERS)


def seconds(trace: dict, part: str) -> float:
    """Idle seconds of the traced window that ``part`` names."""
    by = trace["idle_by_host_s"]
    if part == "unnamed":
        return sum(s for span, s in by.items() if span not in _NAMED)
    return sum(by.get(span, 0.0) for span in PARTS[part])


def share(ctx: dict, part: str) -> float | None:
    """``part``'s share of the traced window in %; None without a trace or
    under a program that does not time the turn."""
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s") or not times_the_turn(ctx):
        return None
    return 100.0 * seconds(trace, part) / trace["window_s"]


def idle_ms_per_step(ctx: dict) -> float | None:
    """Idle ms of the traced slice's device (first chip) a step: every idle
    gap but those the serving loop spent with nothing to run, over the
    steps the program counted while the profiler was on."""
    trace = ctx.get("trace")
    steps = (ctx.get("counter_delta_traced") or {}).get("engine_steps_total")
    if not trace or not trace.get("window_s") or not steps or not times_the_turn(ctx):
        return None
    idle_s = sum(trace["idle_by_host_s"].values()) - seconds(trace, "no_work")
    return 1e3 * idle_s / steps
