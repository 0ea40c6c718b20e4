"""Device time of the sparse-attention parts that run as XLA operations.

The indexer's scores and the selection (``llmd_tpu/ops/sparse_attention.py``,
scopes ``llmd.indexer`` and ``llmd.sparse_select``) are XLA fusions, and the
device events of an ``.xplane.pb`` carry no scope: their names are
``%fusion.416``, ``%convert_reduce_fusion.3``, numbered anew by every compile.
What the trace DOES name is each event's whole HLO instruction, with the
shapes of its result and operands. Those shapes are the configuration's own
(PERF.md section 3): the indexer-key plane is ``bf16[pages,page,Di]`` (a
layer) or ``bf16[L,pages,page,Di]``, the gathered keys of a 16-token tile
``bf16[16*max_pages,page,Di]`` and their scores ``f32[16,S]``; the selection
works on ``[T,S]`` planes of ``u32``, ``s32``, ``pred`` and ``f32`` (S =
``max_model_len``, which no other operation of the step has as its minor
dimension). A program without the mechanism has no such event: the readers
then return None. So does a reader whose OWN part matched no event (the
compiler fused differently, or a Pallas indexer landed): the run's line then
lacks that metric, which the check of a traced run refuses, rather than
carrying a 0 % that nobody asked about.
"""

from __future__ import annotations

import re


def shapes(config: dict):
    """(indexer patterns, selection pattern) for a configuration, or None
    where it has no indexer."""
    sa, geo = config.get("sa_config"), config.get("engine", {})
    if not sa or not geo:
        return None
    di, page, pages, s = sa["indexer_head_dim"], geo["page_size"], geo["num_pages"], geo["max_model_len"]
    dt = r"(?:bf16|f32|f16)"
    indexer = re.compile(
        rf"{dt}\[(?:\d+,)?{pages},{page},{di}\]"      # the plane, one layer or all
        rf"|{dt}\[\d+,{page},{di}\][^ ]* fusion\("   # a tile's gathered key pages
        rf"|{dt}\[\d+,{s},{di}\]"                    # the same as [tile, S, Di]: the scoring fusion's operand
        rf"|= f32\[\d+,\d+,{s}\]"                # the tiles' scores, stacked
    )
    select = re.compile(rf"(?:u32|s32|pred|f32|bf16)\[\d+(?:,1)?,{s}\]")
    return indexer, select


def share(ctx: dict, part: str):
    """Device time of ``part`` ("indexer" or "select") over busy time, in %."""
    trace = ctx.get("trace")
    rx = shapes(ctx.get("config") or {})
    if not trace or not trace.get("op_seconds") or not rx or not trace.get("busy_s"):
        return None
    indexer, select = rx
    t = {"indexer": 0.0, "select": 0.0}
    for name, seconds in trace["op_seconds"].items():
        # The Pallas kernel has its own metric; a conditional spans its
        # branch's operations, which are events of their own.
        if name.startswith(("%llmd.", "%cond", "%while", "%call")):
            continue
        if indexer.search(name):
            t["indexer"] += seconds
        elif select.search(name):
            t["select"] += seconds
    return 100.0 * t[part] / trace["busy_s"] if t[part] > 0.0 else None
