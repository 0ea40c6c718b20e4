"""Device time and least time of the sparse latent read
(``llmd_tpu/ops/sparse_mla.py``, scope ``llmd.sparse_mla``) in a trace.

This version of the read is XLA operations (the mask's ascending positions by
counting, a row gather out of the latent pool, two einsums and a softmax), and
the device events of an ``.xplane.pb`` carry no scope (``perfbench/
sparse_trace.py`` says what they do carry: each event's whole HLO instruction).
The read's shapes are the configuration's own and no other part of the step has
them: ``[T * topk, Dl]`` gathered out of the pool ``[L, pages, 1, page, Dl]``
(the row gather, most of the read's time), ``[T, topk, Dl]`` (the same rows at
the einsums), ``[T, H, topk]`` (scores and probabilities), ``[T, topk,
blocks]`` / ``[T, topk, 128]`` / ``[T, blocks, 128]`` (the counting), ``[T,
topk, 24]`` / ``[T, blocks, 8]`` (a block's page ids), ``s32[T, topk]``
(positions and pages), with topk =
``index_topk``, Dl the latent row padded to the lane tile, H the heads, blocks
= ``max_model_len`` / 128. A kernel under the scope's own name
(``%llmd.sparse_mla``) is counted with them. A program without the mechanism
has no such event, and the readers return None.

Least time (the roofline's numerator), from the program's counters over the
TRACED slice, whatever implements the read:
  rows   = ``sparse_rows_selected_total``: per computed token and layer
           min(cached tokens, index_topk), exactly what it must fetch
  tokens = ``latent_rows_written_total``: computed tokens x layers
  bytes  = rows x Dl x width + tokens x H x (Dl + rank) x width   (q_eff in, o out)
  FLOPs  = rows x H x ((rank + rope) + rank) x 2                   (q . l and p . l_c)
  least  = max(FLOPs / peak FLOP/s, bytes / peak HBM bytes/s); nothing caps it.
The two terms are about equal here (a row serves every head), and the FLOP term
is exact, so no sharing between tokens can push a sound reading over 100 %.
"""

from __future__ import annotations

import json
import pathlib
import re

WIDTH = 2  # bytes of the served dtype (bfloat16: the configuration's ``dtype``)


def sizes(config: dict):
    """(topk, Dl, heads, rank, rope, blocks, pages, page) of a configuration,
    or None where it has no indexer over a latent cache."""
    try:
        topk, rank, rope = int(config["index_topk"]), int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
        heads, geo = int(config["num_attention_heads"]), config["engine"]
        s, pages, page = int(geo["max_model_len"]), int(geo["num_pages"]), int(geo["page_size"])
    except (KeyError, TypeError, ValueError):
        return None
    return topk, -(-(rank + rope) // 128) * 128, heads, rank, rope, -(-s // 128), pages, page


def pattern(config: dict):
    got = sizes(config)
    if got is None:
        return None
    topk, dl, heads, rank, _rope, blocks, pages, page = got
    parts = 3 * max(128 // page, 1)  # a block's page ids as three bytes each
    return re.compile(
        rf"^%llmd\.sparse_mla"
        rf"|\[\d+,{dl}\][^ ]* fusion\((?:bf16|f32|f16)\[\d+,{pages},1,{page},{dl}\]"  # the row gather out of the pool
        rf"|\[\d+,{topk},(?:{dl}|{rank}|{blocks}|128|{parts})\]"  # gathered rows, their value part, the counting
        rf"|\[\d+,{heads},{topk}\]|\[\d+,{topk},{heads}\]"     # scores and probabilities
        rf"|\[\d+,{blocks},(?:128|{parts // 3})\]"               # the mask in blocks, its running counts, the pages
        rf"|(?:s32|u32|pred)\[\d+,{topk}\]"                     # positions, pages, live
    )


def seconds(ctx: dict):
    """Device seconds of the read's events in the trace, or None."""
    trace, rx = ctx.get("trace"), pattern(ctx.get("config") or {})
    if not trace or not trace.get("op_seconds") or rx is None:
        return None
    own = "%llmd.sparse_mla"
    # Another scope's kernel has its own metric (the indexer's operands hold
    # the page table, [rows, topk] wide here); a conditional or a loop spans
    # its body's operations, which are events of their own.
    t = sum(s for name, s in trace["op_seconds"].items()
            if name.startswith(own)
            or not name.startswith(("%llmd.", "%cond", "%while", "%call")) and rx.search(name))
    return t if t > 0.0 else None


def share(ctx: dict):
    t, trace = seconds(ctx), ctx.get("trace") or {}
    return 100.0 * t / trace["busy_s"] if t and trace.get("busy_s") else None


def least_seconds(ctx: dict):
    """The least time the traced slice's selected rows can take, or None."""
    got = sizes(ctx.get("config") or {})
    counters = ctx.get("counter_delta_traced") or {}
    rows, tokens = counters.get("sparse_rows_selected_total", 0), counters.get("latent_rows_written_total", 0)
    if got is None or rows <= 0 or tokens <= 0:
        return None
    _topk, dl, heads, rank, rope = got[:5]
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    flops = 2.0 * rows * heads * (rank + rope + rank)
    nbytes = WIDTH * (rows * dl + tokens * heads * (dl + rank))
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def roofline(ctx: dict):
    t = seconds(ctx)
    least = least_seconds(ctx) if t else None
    return 100.0 * least / t if least else None
