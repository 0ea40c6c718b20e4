"""Device time of the state-space mixers over device busy time, in %: the
WHOLE mixer, not its Pallas calls alone.

Two kinds of device event make a mixer up (``llmd_tpu/models/mamba.py``,
``llmd_tpu/ops/ssm.py``):
  * the Pallas calls, which take their scope's name: ``%llmd.ssm.update`` (the
    decode rows' state update) and ``%llmd.ssm.scan`` (the prefill scan's
    reads and writes of a slot's state): ``definition["pattern"]``;
  * XLA fusions and copies: the in- and out-projections, the causal conv, the
    gate and norm, the scan's per-row einsums. The trace does not name them
    (``%fusion.1019``, numbered anew by every compile); what it does carry is
    each event's whole HLO instruction with the shapes of its result and
    operands (``perfbench/sparse_trace.py`` says the same of the indexer), and
    the mixer's shapes are the configuration's own and no other layer's: the
    in-projection's width ``2 d_in + 2 G N + heads`` (16,768), the conv's
    channels ``d_in + 2 G N`` (8,448), ``[tokens, d_in]`` and the
    out-projection ``[d_in, hidden]``, ``[tokens, heads, d_head]``, the state
    ``[heads, d_head, d_state]`` and the scan's ``[row, row, heads]`` decay.
    Not matched, and small: the index arithmetic of the ragged conv
    (``s32[rows]``), the decode rows' gathers of B, C and dt (``[rows, N]``)
    and the ``[row, row]`` product C . B^T of a scan row.
A configuration without ``mamba_*`` keys, a run without a trace, or one in
which nothing matched gives None (the line then lacks the metric).
"""

import re

# Spans of their children, or kernels with a metric of their own.
OTHERS = ("%llmd.", "%gmm", "%closed_call", "%cond", "%while", "%call")


def shapes(config: dict):
    """The pattern of the mixer's own shapes, or None where the configuration
    has no state-space mixer."""
    try:
        nh, p, n = (int(config[f"mamba_{k}"]) for k in ("n_heads", "d_head", "d_state"))
        g, hidden = int(config.get("mamba_n_groups", 1)), int(config["hidden_size"])
    except (KeyError, TypeError):
        return None
    d_in = nh * p
    conv, width = d_in + 2 * g * n, 2 * d_in + 2 * g * n + nh
    return re.compile(
        rf"\[(?:\d+,)*(?:{width}|{conv})\]"      # the in-projection's output, the conv's channels
        rf"|\[\d+,{d_in}\]"                      # [tokens, d_in]: gate, norm
        rf"|\[(?:\d+,)?{d_in},{hidden}\]"        # the out-projection's weights
        rf"|\[\d+,{nh},{p}\]"                    # [tokens or rows, heads, d_head]
        rf"|\[(?:\d+,)*{nh},{p},{n}\]"           # a state, the pool
        rf"|\[(\d+),\1,{nh}\]"                   # the scan's [row, row, heads]
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
