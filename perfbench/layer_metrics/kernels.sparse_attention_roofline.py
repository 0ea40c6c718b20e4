"""Roofline share of the sparse attention kernel (``%llmd.sparse_attention``:
the Pallas flat attention under the indexer's mask).

Counted as what the call MUST move and compute, not what this version reads:
each computed token attends ``topk`` selected tokens (``sa_config.topk`` of
the configuration), so per call of T stream tokens, H query heads of width D,
K cached heads (shapes from the event's HLO text: output ``bf16[T,K,G,D]``,
the pool ``bf16[L,P,K,page,2D]``):
  tokens = T x live share  (the flat stream pads to a multiple of 16 and a
           pad token attends nothing: the share of LIVE tokens among the
           computed ones comes from the program's counters over the
           TRACED slice (``counter_delta_traced``, never the window's: a
           tail does not look like its window), ``live_tokens_total`` /
           (live + ``padded_tokens_total``); nothing caps the result, so a
           count that is too high shows as a share over 100 %)
  FLOPs  = tokens x topk x H x D x 4        (q.k and p.v)
  bytes  = tokens x topk x K x 2D x width   (the selected rows of K and V)
         + 2 x tokens x H x D x width       (q in, o out)
Valid where every computed token has more than ``topk`` cached tokens, which
``sched.sparse_bound_token_share`` (~100 % in the cell this metric lists)
shows; a token with fewer attends fewer, and the count would be too high.
A dense pass under the mask reads a token's whole context, 8-12x the selected
rows at 16-24k tokens, so this version reads a single-digit share; a gather of
the selected rows can approach the bound. HBM-bound: 8 query heads share a
cached head, so ~8 FLOP a byte against the chip's 240.
"""

import json
import pathlib
import re

SHAPE = re.compile(r"(bf16|f32|f16|s8)\[([\d,]+)\]")
WIDTH = {"bf16": 2, "f32": 4, "f16": 2, "s8": 1}


def call_cost(name: str, topk: int, live_share: float = 1.0):
    """(flops, bytes) of one call from its instruction text, or None;
    ``live_share`` of its T stream tokens are live."""
    head, _, rest = name.partition(" custom-call(")
    out = SHAPE.search(head)
    pool = next(((d, [int(x) for x in dims.split(",")]) for d, dims in SHAPE.findall(rest)
                 if dims.count(",") == 4), None)
    if not out or not pool or out.group(2).count(",") != 3:
        return None
    t, k, g, d = (int(x) for x in out.group(2).split(","))
    width = WIDTH[pool[0]]
    if pool[1][2] != k or pool[1][4] != 2 * d:
        return None
    tokens = t * live_share
    h = k * g
    return (4.0 * tokens * topk * h * d,
            tokens * topk * k * 2 * d * width + 2 * tokens * h * d * WIDTH[out.group(1)])


def read(ctx, definition):
    trace = ctx.get("trace")
    topk = (ctx["config"].get("sa_config") or {}).get("topk")
    counters = ctx.get("counter_delta_traced") or {}
    live, padded = counters.get("live_tokens_total", 0), counters.get("padded_tokens_total", 0)
    if not trace or not trace.get("op_seconds") or not topk or live <= 0:
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    least = measured = 0.0
    for name, seconds in trace["op_seconds"].items():
        if not rx.search(name.split(" = ", 1)[0]):
            continue
        cost = call_cost(name, int(topk), live / (live + padded))
        if cost is None:
            continue
        flops, nbytes = cost
        least += max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) * trace["op_calls"][name]
        measured += seconds
    return 100.0 * least / measured if measured > 0 else None
