"""One process, one cell, one run:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Finds everything by the names in ``BENCHMARK.json``: the cell's parameters in
``perfbench/cells/<workload>.json``, its configuration's file, its traffic mix
in ``perfbench/traffic/<traffic>.json``, the mix's generator in
``perfbench/generators/``, the cell's topology in ``perfbench/topologies/``,
each metric's definition in ``perfbench/end_to_end/`` or
``perfbench/layer_metrics/``. It names none of them itself.

Prints one JSON object as the last line of its output. ``--trace 0`` reports
the cell's end-to-end metrics with the profiler off; ``--trace 1`` reports its
per-layer metrics, with host steps recorded and a profiler trace over the
last seconds of the window. ``--trace 2`` is a ``--trace 0`` run followed by a
short traced tail in the same process: up to the moment the window is closed
it does what ``--trace 0`` does and takes the end-to-end numbers from there;
then the same traffic goes on, the profiler is started and stopped once for
nothing (its first start costs more), and TRACE_SECONDS are traced through the
program's own control (``llmd_tpu/obs/profiling.py``). Its line holds both
kinds of metric: counters are deltas over the MEASURED window, trace metrics,
the step time and the breakdown come from the tail (a reader that lays a
counter beside the trace takes ``counter_delta_traced``, the counters over
the traced slice). Off the chip a run fails,
unless ``--rehearse`` (the builder's CPU rehearsal at a tiny size: exit code
3, no device metric).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
OUT_DIR = ROOT / "chiprun_out" / "perfbench"
TRACE_SECONDS = 2.5
# --trace 2: the traced slice starts this long after the window was closed,
# under the same traffic (an open-loop cell has just waited for its last first
# tokens with no new arrivals; the profiler's first start and stop, 0.3-1.4 s,
# fall at the head of it), and this much more open-loop traffic is drawn (the
# tail ends when the trace is written, well before it runs out).
TAIL_SETTLE_SECONDS = 3.5
TAIL_OFFER_SECONDS = 40.0


def load(workload: str, root: pathlib.Path = ROOT) -> types.SimpleNamespace:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r}; known: {sorted(cells)}")
    entry = cells[workload]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[entry["config"]]
    bench = root / manifest["paths"][0]

    def reported(section: str) -> list:
        return [
            m["name"] for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]
        ]

    return types.SimpleNamespace(
        manifest=manifest, entry=entry, bench_dir=bench,
        cell=json.loads((bench / "cells" / f"{workload}.json").read_text()),
        config=json.loads((root / conf_entry["file"]).read_text()),
        mix=json.loads((bench / "traffic" / f"{entry['traffic']}.json").read_text()),
        end_to_end=reported("end_to_end"), per_layer=reported("per_layer"),
        units={m["name"]: m["unit"] for s in ("end_to_end", "per_layer") for m in manifest[s]},
    )


def published(conf: dict) -> dict:
    """The published keys of a configuration file (what a reference reads)."""
    own = {"registry", "dtype", "engine", "assumed", "stands_for", "reference", "rehearse",
           "source", "reduced", "note"}
    return {k: v for k, v in conf.items() if k not in own}


def window_numbers(rec) -> dict:
    """What a run reports of the closed window, from the recorder."""
    attempted = rec.attempted()
    return {
        "series": rec.series(), "attempted": len(attempted),
        "failed": sum(1 for r in attempted if rec.failed(r)),
    }


async def _measure(system, gen, rec, mode: int, trace_dir: pathlib.Path) -> dict:
    """Serve, run the generator, and mark the window: counters at its start
    and end; the profiler over its last TRACE_SECONDS (``mode`` 1) or over
    TRACE_SECONDS of a tail that follows the closed window (``mode`` 2)."""
    import jax

    marks: dict = {}
    traced = mode == 1

    async def tail(offer) -> None:
        """Called by the generator when the window is closed and complete."""
        marks["closed"] = window_numbers(rec)
        t_closed = time.monotonic()
        system.time_steps()
        more = asyncio.create_task(offer(TAIL_OFFER_SECONDS)) if offer else None
        discard = trace_dir.with_name(trace_dir.name + ".discard")
        try:
            # The first start costs more, and not the same in every run: it
            # is paid at once and thrown away, and the traced slice starts at
            # a fixed offset into the tail's traffic whatever it cost.
            await asyncio.to_thread(system.trace_start, str(discard))
            await asyncio.to_thread(system.trace_stop)
            marks["first_start_stop_s"] = time.monotonic() - t_closed
            await asyncio.sleep(max(0.0, t_closed + TAIL_SETTLE_SECONDS - time.monotonic()))
            await asyncio.to_thread(system.trace_start, str(trace_dir))
            marks["trace_on"], marks["ct0"] = time.monotonic(), system.counters()
            await asyncio.sleep(TRACE_SECONDS)
            marks["trace_off"], marks["ct1"] = time.monotonic(), system.counters()
            await asyncio.to_thread(system.trace_stop)
            marks["trace_stop_s"] = time.monotonic() - marks["trace_off"]
        finally:
            shutil.rmtree(discard, ignore_errors=True)
            if more is not None:
                marks["tail_offer_ran_out"] = more.done()
                more.cancel()
                await asyncio.gather(more, return_exceptions=True)

    async def watch() -> None:
        while rec.t0 is None or time.monotonic() < rec.t0:
            await asyncio.sleep(0.002)
        marks["c0"] = system.counters()
        if traced:
            lead = rec.t1 - TRACE_SECONDS - time.monotonic()
            if lead > 0:
                await asyncio.sleep(lead)
            jax.profiler.start_trace(str(trace_dir))
            marks["trace_on"], marks["ct0"] = time.monotonic(), system.counters()
        await asyncio.sleep(max(0.0, rec.t1 - time.monotonic()))
        marks["c1"] = system.counters()
        marks["steps_at_t1"] = len(system.steps) if system.steps is not None else 0
        if traced:
            marks["ct1"] = marks["c1"]
            await asyncio.to_thread(jax.profiler.stop_trace)

    system.start(record_steps=traced)
    try:
        watcher = asyncio.create_task(watch())
        await gen.run(system, rec, tail if mode == 2 else None)
        await watcher
    finally:
        system.pause()
    return marks


def prepare(args):
    """(spec, mix, system): the cell's files read, the system built."""
    root = pathlib.Path(args.root).resolve()
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))  # a copy's new modules join the namespace packages
    spec = load(args.workload, root)
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, chips=spec.entry["chips"],
        rehearse=args.rehearse, config=spec.config, cell=spec.cell, mix=spec.mix,
    )
    mix = dict(spec.mix)
    if args.rehearse:
        mix.update(spec.mix.get("rehearse", {}))
    topology = importlib.import_module(f"perfbench.topologies.{spec.cell['topology']}")
    return spec, mix, topology.start(ctx)


def set_up(system, spec, args) -> dict:
    """Warm-up and the reference comparison; returns the comparison."""
    from perfbench import correctness

    system.warm_up()
    t = time.monotonic()
    pub = published(spec.config["rehearse"]["published"] if args.rehearse else spec.config)
    check = correctness.reference_check(system, pub, spec.config["reference"], args.seed)
    system.setup_log.append(("reference_check", round(time.monotonic() - t, 3)))
    return check


def run_window(system, spec, mix, cell, seed, seconds, mode, trace_dir):
    from perfbench import recorder

    generator = importlib.import_module(f"perfbench.generators.{mix['generator']}")
    rec = recorder.Recorder(system.vocab_size)
    gen = generator.Generator(mix, cell, seed, seconds, system)
    marks = asyncio.run(_measure(system, gen, rec, mode, trace_dir))
    return rec, marks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=str(ROOT), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    spec, mix, system = prepare(args)
    traced = bool(args.trace)
    from perfbench import reducers, trace_reduce

    try:
        check = set_up(system, spec, args)
        trace_dir = OUT_DIR / "trace" / args.workload
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
        rec, marks = run_window(system, spec, mix, spec.cell, args.seed, args.seconds, args.trace, trace_dir)
        peak = system.peak_bytes()
        plans = system.kernel_plans()
        programs = system.traced_programs() if args.trace == 2 else None
    finally:
        system.stop()

    closed = marks.get("closed") or window_numbers(rec)
    series = closed["series"]
    series["setup_s"] = [rec.t0 - T_PROCESS]

    def counter_delta(a: str, b: str) -> dict:
        return {k: marks[b][k] - v for k, v in marks[a].items() if isinstance(v, (int, float))}

    delta = counter_delta("c0", "c1")
    # What the program counted while the profiler was on: a number that is laid
    # beside the trace comes from the steps that were traced, and a --trace 2
    # tail does not look like its window (an open-loop cell's starts drained).
    delta_traced = counter_delta("ct0", "ct1") if "ct1" in marks else None
    device = dict(system.device)
    trace = None
    if traced:
        xplane = trace_reduce.find_xplane(str(trace_dir))
        if xplane:
            loaded = trace_reduce.load(xplane)
            trace = trace_reduce.reduce(loaded)
            trace["lines"] = loaded["lines"]
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            if trace["window_s"] > 0:
                device["idle_share"] = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
        if args.trace == 2:  # the steps of the traced tail; the trace is reduced, nothing else reads it
            shutil.rmtree(trace_dir, ignore_errors=True)
            series["step_ms"] = [
                (e - s) * 1e3 for s, e, _n in system.steps
                if s >= marks.get("trace_on", 0.0) and e <= marks.get("trace_off", 0.0)
            ]
            if series["step_ms"]:  # against the window's step_ms_total / engine_steps_total: what tracing costs
                marks["traced_step_ms_mean"] = sum(series["step_ms"]) / len(series["step_ms"])
        if args.trace == 1:
            steps = system.steps[: marks["steps_at_t1"]]
            series["step_ms"] = [
                (e - s) * 1e3 for s, e, _n in steps if s >= rec.t0 and e <= marks.get("trace_on", rec.t1)
            ]
    if peak is not None:
        device["memory_peak_bytes"] = peak
        device["peak_hbm_gb"] = peak / 1e9

    rctx = {
        "series": series, "counter_delta": delta, "counter_delta_traced": delta_traced,
        "trace": trace, "device": device, "config": spec.config, "cell": spec.cell, "bench_dir": str(spec.bench_dir),
    }
    sections = {0: ("end_to_end",), 1: ("per_layer",), 2: ("end_to_end", "per_layer")}[args.trace]
    metrics = {}
    for section in sections:
        for name in getattr(spec, section):
            v = reducers.reduce(section, name, rctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": spec.units[name]}

    attempted, failed = closed["attempted"], closed["failed"]
    result = {
        "correct": bool(check["ok"] and failed == 0 and attempted > 0),
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": {k: device[k] for k in
                   ("platform", "kind", "count", "memory_peak_bytes", "busy_s", "window_s")
                   if k in device},
    }
    if trace:
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace["op_seconds"]),
            "idle_gaps": trace["idle_gaps"],
        }
    detail = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds, "traced": traced,
        "tail": {k: marks[k] for k in
                 ("first_start_stop_s", "trace_stop_s", "tail_offer_ran_out", "traced_step_ms_mean") if k in marks},
        "traced_programs": programs,
        "setup_log": system.setup_log, "reference_check": check, "kernel_plans": plans,
        "counter_delta": delta, "counter_delta_traced": delta_traced,
        "compile": {k: marks["c1"][k] for k in marks["c1"] if k.startswith("compile_")},
        "samples": {k: len(v) for k, v in series.items()},
        "idle_by_host_s": trace and trace["idle_by_host_s"],
        "trace_lines": trace and trace["lines"],
        "result": result,
    }
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{args.workload}.seed{args.seed}.trace{args.trace}.json").write_text(
        json.dumps(detail, indent=1, default=str))
    print(json.dumps({k: detail[k] for k in ("setup_log", "reference_check", "samples", "compile")},
                     default=str), file=sys.stderr)
    if args.rehearse:
        # A rehearsal is not a measurement: nothing timed on a CPU leaves here.
        result = {"rehearsal": True, "correct": result["correct"], "attempted": result["attempted"],
                  "failed": result["failed"], "metrics_reported": sorted(metrics),
                  "device": system.device}
        print(json.dumps(result))
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
