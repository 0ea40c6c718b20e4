"""The program's own spans, read back out of a profiler trace (shared by
test_profiling.py and test_pipelined_step.py)."""

import glob
import os


def host_spans(trace_dir) -> list:
    """[(name, start ns, end ns, stats)] of the trace's ``llmd.*`` events on
    the ``/host:CPU`` plane, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    for line in plane.lines for ev in line.events if ev.name.startswith("llmd.")]
    return sorted(out, key=lambda e: e[1])
