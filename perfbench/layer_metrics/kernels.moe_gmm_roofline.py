"""Roofline share of the grouped expert matmul (megablox ``gmm``).

Each ``%gmm`` event of the trace is named by its HLO instruction, which holds
the call's shapes: output ``f32[M,N]``, activations ``bf16[M,K]``, weights
``bf16[E,K,N]``. From them, per call:
  FLOPs = 2 M K N
  bytes = E K N w   (every expert's weights once: with M >= E rows spread
                     over E experts nearly all are touched; counts too many
                     where some are not, never too few per byte moved)
        + M K a + M N 4   (activations in, f32 out)
The least time is the larger of FLOPs / peak FLOP/s and bytes / peak HBM
bytes/s (perfbench/peaks.json, by device kind; a kind that is missing is an
error). The metric is sum(least) / sum(measured device time), in %. It says
in ``bound`` which of the two bounds it.
"""

import json
import pathlib
import re

SHAPE = re.compile(r"(bf16|f32|s8)\[([\d,]+)\]")
WIDTH = {"bf16": 2, "f32": 4, "s8": 1}


def call_cost(name: str):
    """(flops, bytes) of one gmm call from its instruction text, or None."""
    head, _, rest = name.partition(" custom-call(")
    out = SHAPE.search(head)
    ops = [(d, [int(x) for x in dims.split(",")]) for d, dims in SHAPE.findall(rest)]
    w = next((o for o in ops if len(o[1]) == 3), None)
    if not out or not w:
        return None
    m, n = (int(x) for x in out.group(2).split(","))
    e, k, n2 = w[1]
    if n2 != n:
        return None
    act = next((WIDTH[d] for d, dims in ops if dims == [m, k]), 2)
    return 2.0 * m * k * n, e * k * n * WIDTH[w[0]] + m * k * act + m * n * WIDTH[out.group(1)]


def read(ctx, definition):
    trace = ctx.get("trace")
    if not trace or not trace.get("op_seconds"):
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    least = measured = 0.0
    for name, seconds in trace["op_seconds"].items():
        if not rx.search(name.split(" = ", 1)[0]):
            continue
        cost = call_cost(name)
        if cost is None:
            continue
        flops, nbytes = cost
        per_call = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
        least += per_call * trace["op_calls"][name]
        measured += seconds
    return 100.0 * least / measured if measured > 0 else None
