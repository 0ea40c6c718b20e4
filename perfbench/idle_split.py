"""``device.idle_share`` split by what the host was doing: the arithmetic
behind the four ``device.idle_*_share`` metrics, for their readers.

``trace_reduce.reduce`` names every idle gap of the first chip by the ONE host
span that covers most of it (at least half, else "outside any step") and sums
the gaps by that name into ``idle_by_host_s``. Under ``--trace 2`` the host
spans are the program's own phase spans (llmd_tpu/obs/profiling.py;
``trace_reduce.SPAN_PREFIX`` says which are read). ``share`` sums a phase's
names over the traced window, in %:

  schedule      ``llmd.sched.schedule``
  launch        ``llmd.runner.launch`` and the spans inside it
  finish        ``llmd.step.finish``
  unattributed  everything else: ``llmd.step.admit``, ``llmd.runner.wait``
                (the device went idle while the host still waited for it: the
                readback), ``llmd.serve.*``, the ``--trace 1`` wrappers
                ``pb.*``, "outside any step"

The four add up to the idle seconds of the first chip over the window, which
on one chip is ``device.idle_share``. A whole gap goes to one name: a gap that
runs from the readback through finish, deliver, intake and schedule into the
launch, with no phase covering half of it, is "outside any step" and so
unattributed. Laying the idle time over the spans exactly needs the spans in
the readers' context, which is the harness's to give (PERF.md, section 7).
"""

from __future__ import annotations

PHASES = {
    "schedule": ("llmd.sched.schedule",),
    "launch": ("llmd.runner.launch", "llmd.runner.build", "llmd.runner.dispatch", "llmd.runner.trace"),
    "finish": ("llmd.step.finish",),
}
_NAMED = {span for spans in PHASES.values() for span in spans}


def share(trace: dict | None, phase: str) -> float | None:
    """``phase``'s share of the traced window in %, or None without a trace."""
    if not trace or not trace.get("window_s"):
        return None
    by = trace["idle_by_host_s"]
    if phase == "unattributed":
        seconds = sum(s for span, s in by.items() if span not in _NAMED)
    else:
        seconds = sum(by.get(span, 0.0) for span in PHASES[phase])
    return 100.0 * seconds / trace["window_s"]
