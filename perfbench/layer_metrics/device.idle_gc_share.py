"""The part of the device's idle time that falls under a collection of Python's
cyclic collector (span ``llmd.runner.gc``), in % of the traced window: a part of
``device.idle_unnamed_share`` (perfbench/idle_turn.py), read the same way."""

SPAN = "llmd.runner.gc"
COUNTER = "gc_pause_ms_total"  # what a program that times the collector counts


def read(ctx, definition):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s") or COUNTER not in (ctx.get("counter_delta") or {}):
        return None
    return 100.0 * trace["idle_by_host_s"].get(SPAN, 0.0) / trace["window_s"]
