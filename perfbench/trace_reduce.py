"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time per operation, and the longest idle gaps with what the host was
doing in them. Read with ``jax.profiler.ProfileData`` and nothing else.

What the planes of a TPU trace look like (looked at by hand, PR 24): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO operation (fusions, custom calls — the Pallas kernels
among them — copies, while-loop bodies' contents) and whose line ``XLA
Modules`` holds one event per executed program; one plane ``/host:CPU`` with
a line per thread, which is where ``jax.profiler.TraceAnnotation`` spans of
the benchmark's wrappers (``pb.*``, ``--trace 1``) and of the program itself
(``llmd.*``, llmd_tpu/obs/profiling.py) appear, on the same clock.

Busy time is the UNION of the op intervals of a chip (ops can nest or
overlap), averaged over the chips that ran anything.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# An event of ``XLA Ops`` is named by its whole HLO instruction: "%gmm.13 =
# f32[512,768]{...} custom-call(...)". short_name() is the part before " = ".
# Containers span their children, which are events of their own: they count
# for busy time and are left out of per-operation time.
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]*$")


def short_name(name: str) -> str:
    return name.split(" = ", 1)[0]

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# The benchmark's wrappers, and the program's PHASE spans. The program's
# whole-step span (the bare "llmd.step") is left out on purpose: a gap goes to
# the span that covers most of it, a whole step covers at least what any phase
# inside it covers, and "inside step()" is what the phases are there to split.
SPAN_PREFIX = ("pb.", "llmd.sched.", "llmd.runner.", "llmd.step.", "llmd.serve.")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str, device_plane=DEVICE_PLANE, ops_line: str = OPS_LINE) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "spans": [(name,
    start_ns, end_ns)], "lines": {plane: [line names]}} from one file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, lines = {}, [], {}
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if device_plane.match(plane.name):
            evs = []
            for ln in plane.lines:
                if ln.name != ops_line:
                    continue
                for e in ln.events:
                    evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            devices[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1]), "lines": lines}


def _host_doing(spans: list, s: float, e: float) -> str:
    """The innermost benchmark span that covers most of [s, e]."""
    best, best_cover, best_len = "outside any step", 0.0, float("inf")
    for name, a, b in spans:
        cover = min(e, b) - max(s, a)
        if cover <= 0:
            continue
        length = b - a
        if cover > best_cover * 1.001 or (cover >= best_cover * 0.999 and length < best_len):
            best, best_cover, best_len = name, cover, length
    if best_cover < 0.5 * (e - s):
        return "outside any step"
    return best


def reduce(loaded: dict, window_ns: tuple | None = None, top: int = 10) -> dict:
    """busy_s and window_s (averaged over the chips that ran anything),
    op_seconds {name: s} summed over chips / chips, and the ``top`` longest
    idle gaps of the first chip as [(what the host was doing, seconds)].

    ``window_ns`` clips to (start, end); default: first op start to last op
    end over all chips."""
    devs = {p: evs for p, evs in loaded["devices"].items() if evs}
    if not devs:
        return {"busy_s": 0.0, "window_s": 0.0, "op_seconds": {}, "op_calls": {}, "idle_gaps": [],
                "idle_by_host_s": {}, "chips": 0}
    if window_ns is None:
        window_ns = (
            min(s for evs in devs.values() for _, s, _ in evs),
            max(e for evs in devs.values() for _, _, e in evs),
        )
    w0, w1 = window_ns
    busy, ops, calls = 0.0, {}, {}
    gaps: list = []
    for i, (_plane, evs) in enumerate(sorted(devs.items())):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged)
        for n, s, e in clipped:
            if CONTAINER.match(short_name(n)):
                continue
            ops[n] = ops.get(n, 0.0) + (e - s)
            calls[n] = calls.get(n, 0) + 1
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b))
    n = len(devs)
    by_host: dict = {}
    named = [(_host_doing(loaded["spans"], a, b), b - a) for a, b in gaps]
    for what, length in named:
        by_host[what] = by_host.get(what, 0.0) + length
    longest = sorted(named, key=lambda g: -g[1])[:top]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": n,
        "op_seconds": {k: v / n / 1e9 for k, v in ops.items()},
        "op_calls": {k: v / n for k, v in calls.items()},
        "idle_by_host_s": {k: v / 1e9 for k, v in sorted(by_host.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[what, length / 1e9] for what, length in longest],
    }


def by_short_name(op_seconds: dict) -> dict:
    """Seconds per operation kind: short names with their numbering dropped
    ("%gmm.13" and "%gmm.12" are both "%gmm")."""
    out: dict = {}
    for name, v in op_seconds.items():
        k = re.sub(r"[.\d]+$", "", short_name(name))
        out[k] = out.get(k, 0.0) + v
    return out


def top_ops(op_seconds: dict, top: int = 10) -> list:
    kinds = by_short_name(op_seconds)
    return [[k, v] for k, v in sorted(kinds.items(), key=lambda kv: -kv[1])[:top]]


def share(op_seconds: dict, patterns: list, busy_s: float) -> float | None:
    """Device time of the ops whose name matches any pattern, over busy time."""
    if busy_s <= 0:
        return None
    rx = [re.compile(p) for p in patterns]
    t = sum(v for k, v in op_seconds.items() if any(r.search(short_name(k)) for r in rx))
    return t / busy_s
