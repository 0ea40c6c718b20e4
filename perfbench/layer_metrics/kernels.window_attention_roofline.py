"""Roofline share of the flat attention of the sliding-window layers
(``%llmd.attn.window``: the Pallas flat attention under ``jax.named_scope
llmd.attn.window``, which a model that mixes window and full layers gives the
call of its sliding layers).

Counted as what the call MUST move and compute, not what a version reads: a
token on a sliding layer attends the ``sliding_window`` positions before it,
which lie in at most window / page + 1 pages of the paged pool (the page is
the granule a window straddles and the smallest thing a kernel can skip). Per
call of T stream tokens, H query heads of width D, K cached heads (shapes from
the event's HLO text: output ``bf16[T,K,G,D]``, the pool
``bf16[L,P,K,page,2D]``):
  tokens = T x live share  (the flat stream pads to a multiple of 16 and a pad
           token attends nothing: live / (live + padded) of the program's
           counters over the TRACED slice, ``counter_delta_traced``)
  keys   = sliding_window + page
  FLOPs  = tokens x keys x H x D x 4        (q.k and p.v)
  bytes  = tokens x keys x K x 2D x width   (the in-window rows of K and V)
         + 2 x tokens x H x D x width       (q in, o out)
Valid where a computed token has at least ``keys`` cached tokens (a token with
fewer attends fewer, and the count would be too high): the cell this metric
lists serves 4-8k-token contexts, where only the first 144 tokens of a
session's first prompt have fewer. Nothing caps it: a count that is too high
would show over 100 %. A kernel that fetched every block up to the query's
position would read 30-60x these bytes at 4-8k tokens and a few % here; one
program per token (8 KV heads x 144 rows x 512 B = 0.6 MB, 0.7 us at the
chip's bandwidth) is bound by its own start-up long before HBM. HBM-bound by
the count: 8 query heads share a cached head, ~8 FLOP a byte against the
chip's 240.
"""

import json
import pathlib
import re

SHAPE = re.compile(r"(bf16|f32|f16|s8)\[([\d,]+)\]")
WIDTH = {"bf16": 2, "f32": 4, "f16": 2, "s8": 1}


def call_cost(name: str, window: int, live_share: float = 1.0):
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
    tokens, keys, h = t * live_share, window + pool[1][3], k * g
    return (4.0 * tokens * keys * h * d,
            tokens * keys * k * 2 * d * width + 2 * tokens * h * d * WIDTH[out.group(1)])


def read(ctx, definition):
    trace = ctx.get("trace")
    window = ctx["config"].get("sliding_window")
    counters = ctx.get("counter_delta_traced") or {}
    live, padded = counters.get("live_tokens_total", 0), counters.get("padded_tokens_total", 0)
    if not trace or not trace.get("op_seconds") or not window or live <= 0:
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    least = measured = 0.0
    for name, seconds in trace["op_seconds"].items():
        if not rx.search(name.split(" = ", 1)[0]):
            continue
        cost = call_cost(name, int(window), live / (live + padded))
        if cost is None:
            continue
        flops, nbytes = cost
        least += max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) * trace["op_calls"][name]
        measured += seconds
    return 100.0 * least / measured if measured > 0 else None
