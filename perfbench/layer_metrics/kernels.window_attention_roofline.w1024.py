"""Roofline share of the flat attention of the sliding-window layers
(``%llmd.attn.window``), counted PER TILE as PERF.md section 7 (o) sets out,
for a cell whose window (1,024) exceeds its chunk budget (128).

``kernels.window_attention_roofline`` charges ``sliding_window + page`` rows of
K and V to every live token. That is what one program a token must read, and
more than a kernel needs that serves a 16-token granule of ONE row (the body
of a prefill chunk) from one pass over ``window + page + 16`` rows: such a
kernel would read over 100 % there. Here the rows a call MUST read are

  tile tokens  = tokens x attn_shared_tile_tokens_total / live_tokens_total
                 (over the TRACED slice: the tokens the host laid out in
                 granules that hold one row only; ``runner._fill_unified``)
  rows         = tile tokens / tile x (sliding_window + page + tile)
               + (tokens - tile tokens) x (sliding_window + page)

with ``tokens`` = T x live / (live + padded) of a call's T stream tokens
(shapes from the event's HLO text: output ``bf16[T,K,G,D]``, the pool
``bf16[L,P,K,page,2D]``), and per call

  FLOPs = tokens x sliding_window x H x D x 4     (q.k and p.v; a token
          attends its window whichever way the rows arrive)
  bytes = rows x K x 2D x width + 2 x tokens x H x D x width   (q in, o out)

Valid where a computed token has at least ``sliding_window + page`` cached
tokens: this cell's contexts are 16-24k. A decode row, a verify row and a seam
token are in no tile and count per token. Nothing caps it. HBM-bound by the
count: 8 query heads share a cached head, ~8 FLOP a byte per token and ~60 a
tile against the chip's 240.
"""

import json
import pathlib
import re

SHAPE = re.compile(r"(bf16|f32|f16|s8)\[([\d,]+)\]")
WIDTH = {"bf16": 2, "f32": 4, "f16": 2, "s8": 1}


def rows_read(tokens: float, tile_tokens: float, window: int, page: int, tile: int) -> float:
    """Rows of K and V (a cached head) the call must read for ``tokens`` live
    tokens of which ``tile_tokens`` lie in one-row granules of ``tile``."""
    return tile_tokens / tile * (window + page + tile) + (tokens - tile_tokens) * (window + page)


def call_cost(name: str, window: int, live_share: float, tile_share: float, tile: int):
    """(flops, bytes) of one call from its instruction text, or None."""
    head, _, rest = name.partition(" custom-call(")
    out = SHAPE.search(head)
    pool = next(((d, [int(x) for x in dims.split(",")]) for d, dims in SHAPE.findall(rest)
                 if dims.count(",") == 4), None)
    if not out or not pool or out.group(2).count(",") != 3:
        return None
    t, k, g, d = (int(x) for x in out.group(2).split(","))
    if pool[1][2] != k or pool[1][4] != 2 * d:
        return None
    tokens, h = t * live_share, k * g
    rows = rows_read(tokens, tokens * tile_share, window, pool[1][3], tile)
    return (4.0 * tokens * window * h * d,
            rows * k * 2 * d * WIDTH[pool[0]] + 2 * tokens * h * d * WIDTH[out.group(1)])


def read(ctx, definition):
    trace = ctx.get("trace")
    window = ctx["config"].get("sliding_window")
    counters = ctx.get("counter_delta_traced") or {}
    live, padded = counters.get("live_tokens_total", 0), counters.get("padded_tokens_total", 0)
    if not trace or not trace.get("op_seconds") or not window or live <= 0:
        return None
    tile_share = min(1.0, counters.get("attn_shared_tile_tokens_total", 0) / live)
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    least = measured = 0.0
    for name, seconds in trace["op_seconds"].items():
        if not rx.search(name.split(" = ", 1)[0]):
            continue
        cost = call_cost(name, int(window), live / (live + padded), tile_share, int(definition["tile"]))
        if cost is None:
            continue
        flops, nbytes = cost
        least += max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) * trace["op_calls"][name]
        measured += seconds
    return 100.0 * least / measured if measured > 0 else None
