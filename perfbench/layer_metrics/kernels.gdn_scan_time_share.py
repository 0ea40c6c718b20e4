"""Device time of the gated delta rule's prefill scan over device busy time,
in %.

Two kinds of device event make the scan up (``llmd_tpu/ops/gdn.py::gdn_scan``):
  * its Pallas calls, which take their scope's name, ``%llmd.gdn.scan`` (a
    row's read and write of its slot's state): ``definition["pattern"]``;
  * XLA fusions, anonymous in the trace (``%fusion.812``, numbered anew by
    every compile). What the trace does carry is each event's whole HLO
    instruction with the shapes of its result and operands
    (``perfbench/sparse_trace.py`` says the same of the indexer), and the
    scan's shapes are the configuration's own and no other operation's: the
    carried state ``f32[Hv,Dk,Dv]``, the row's ``f32[Hv,64,64]`` decay, system,
    powers and inverse, its ``f32[Hv,64,Dk]`` / ``f32[Hv,64,Dv]`` operands, and
    the head-major padded stream ``f32[Hv,T+64,D]`` the rows are sliced from.
    (The decode update is one Pallas call and has a metric of its own; the
    projections, the conv, the gate and the norm work on ``[T, ..]`` planes
    and are not the scan's.)
A configuration without the ``linear_*`` keys, a run without a trace, or a
slice in which nothing matched gives None (the line then lacks the metric).
"""

import re

ROW = 64  # the flat step's row, the scan's chunk (llmd_tpu/models/mamba.py::ROW_TOKENS)
# Spans of their children, or kernels with a metric of their own.
OTHERS = ("%llmd.", "%gmm", "%closed_call", "%cond", "%while", "%call")


def shapes(config: dict):
    try:
        hv, dk, dv = (int(config[k]) for k in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    except (KeyError, TypeError, ValueError):
        return None
    wide = "|".join(sorted({str(dk), str(dv)}))
    return re.compile(
        rf"f32\[{hv},{dk},{dv}\]"              # the carried state
        rf"|f32\[{hv},{ROW},{ROW}\]"           # decay, the system and its inverse
        rf"|f32\[{hv},{ROW},(?:{wide})\]"      # a row's q, k, v, deltas, outputs
        rf"|f32\[{hv},(?:[89]\d|\d{{3,}}),(?:{wide})\]"  # the head-major padded stream, T + 64 >= 80
        rf"|f32\[{hv},{ROW}\]"                 # a row's decay and beta
    )


def read(ctx, definition):
    trace = ctx.get("trace")
    own = shapes(ctx.get("config") or {})
    if not trace or not trace.get("op_seconds") or not trace.get("busy_s") or own is None:
        return None
    named = re.compile(definition["pattern"])
    total = 0.0
    for name, seconds in trace["op_seconds"].items():
        short = name.split(" = ", 1)[0]
        if named.search(short) or (not short.startswith(OTHERS) and own.search(name)):
            total += seconds
    return 100.0 * total / trace["busy_s"] if total > 0.0 else None
