"""One metric = one definition file (``perfbench/end_to_end/<name>.json`` or
``perfbench/layer_metrics/<name>.json``), reduced here by its ``kind``. A
definition whose inputs are missing gives None and the metric is left out.

kinds:
  percentile     {"series", "q"}           nearest-rank percentile of a series
  rate           {"series"}                sum of a series over the window's seconds
  value          {"series"}                the one value of a series
  ratio_of_sums  {"num", "den", "scale"}   sum(series num) / sum(series den) x scale
  counter_delta  {"counter"}               a program counter, end minus start of the window
  counter_ratio  {"num": [..], "den": [..], "scale"}  sums of counter deltas; with "over": "traced" the
                 deltas over the slice the profiler was on (None without one), and with "den_config": [keys]
                 the denominator times the configuration's first such key (a count per expert, say)
  trace_share    {"patterns": [..]}        device time of matching ops / device busy time, in %
  device         {"field"}                 a field of the device summary
  reader         {}                        perfbench/layer_metrics/<name>.py::read(ctx)
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

from perfbench import stats, trace_reduce

HERE = pathlib.Path(__file__).resolve().parent


FOLDER = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def definition(section: str, name: str, bench_dir=HERE) -> dict:
    return json.loads((pathlib.Path(bench_dir) / FOLDER[section] / f"{name}.json").read_text())


def _sum_counters(delta: dict, names: list):
    if any(n not in delta for n in names):
        return None
    return sum(delta[n] for n in names)


def reduce(section: str, name: str, ctx: dict):
    """The metric's value, or None. ``ctx``: series, counter_delta, trace
    (trace_reduce.reduce output or None), device, config, cell, bench_dir."""
    bench_dir = pathlib.Path(ctx.get("bench_dir") or HERE)
    d = definition(section, name, bench_dir)
    kind = d["kind"]
    series = ctx["series"]
    if kind == "percentile":
        return stats.percentile(series.get(d["series"], []), d["q"])
    if kind == "rate":
        vals, window = series.get(d["series"]), series.get("window_s")
        return sum(vals) / window[0] if vals and window else None
    if kind == "value":
        vals = series.get(d["series"])
        return float(vals[0]) if vals else None
    if kind == "ratio_of_sums":
        num, den = series.get(d["num"]), series.get(d["den"])
        if not den or sum(den) == 0:
            return None
        return d.get("scale", 1.0) * sum(num) / sum(den)
    if kind == "counter_delta":
        v = ctx["counter_delta"].get(d["counter"])
        return None if v is None else float(v)
    if kind == "counter_ratio":
        delta = (ctx.get("counter_delta_traced") or {}) if d.get("over") == "traced" else ctx["counter_delta"]
        num, den = _sum_counters(delta, d["num"]), _sum_counters(delta, d["den"])
        if den and "den_config" in d:
            den *= next((ctx["config"][k] for k in d["den_config"] if ctx["config"].get(k)), 0)
        return None if num is None or not den else d.get("scale", 1.0) * num / den
    if kind == "trace_share":
        tr = ctx.get("trace")
        if not tr:
            return None
        s = trace_reduce.share(tr["op_seconds"], d["patterns"], tr["busy_s"])
        return None if s is None else 100.0 * s
    if kind == "device":
        v = ctx["device"].get(d["field"])
        return None if v is None else float(v)
    if kind == "reader":
        path = bench_dir / FOLDER[section] / f"{name}.py"
        spec = importlib.util.spec_from_file_location(f"perfbench_reader_{abs(hash(name))}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx, d)
    raise ValueError(f"metric {name}: unknown kind {kind!r}")
