"""Roofline share of the gated delta rule's decode update
(``%llmd.gdn.update``: the call of ``llmd_tpu/ops/gdn.py`` under
``jax.named_scope llmd.gdn.update``, one a delta-rule layer a step).

Counted as what the update MUST move and compute, whatever implements it: a
decode row reads its slot's state ``[Hv, Dk, Dv]`` in float32 (it needs the
state's product with k BEFORE it can write), updates it with one token and
writes it back; nothing of it can be skipped or shared between rows. The sizes
are the CONFIGURATION's (``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``), not an operand's of the kernel that happens to run;
the rows are the program's counter over the TRACED slice
(``counter_delta_traced["gdn_update_rows_total"]``: live decode rows x
delta-rule layers):
  bytes = rows x (2 x Hv x Dk x Dv x 4      the state, read and written
               + 2 x Hv x Dk x 4            q and k
               + 2 x Hv x Dv x 4            v in, the output out
               + 2 x Hv x 4)                the decay and beta
  FLOPs = rows x Hv x Dk x Dv x 7          the decay (1), S^T k (2), the rank-1
                                           update (2), S^T q (2)
HBM-bound by the count: 7 FLOP to 8 bytes. Nothing caps it: a count that is
too high would show over 100 %. A program without the counter or the call, a
configuration without the keys, or a run without a trace gives None.
"""

import json
import pathlib
import re


def row_cost(config: dict):
    """(flops, bytes) of ONE live row of ONE layer, or None."""
    try:
        hv, dk, dv = (int(config[k]) for k in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    except (KeyError, TypeError, ValueError):
        return None
    state = hv * dk * dv
    return 7.0 * state, 4.0 * (2 * state + 2 * hv * dk + 2 * hv * dv + 2 * hv)


def read(ctx, definition):
    trace = ctx.get("trace")
    rows = (ctx.get("counter_delta_traced") or {}).get("gdn_update_rows_total", 0)
    cost = row_cost(ctx.get("config") or {})
    if not trace or not trace.get("op_seconds") or rows <= 0 or cost is None:
        return None
    rx = re.compile(definition["pattern"])
    measured = sum(s for name, s in trace["op_seconds"].items() if rx.search(name.split(" = ", 1)[0]))
    if measured <= 0:
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    flops, nbytes = cost
    least = rows * max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / measured
