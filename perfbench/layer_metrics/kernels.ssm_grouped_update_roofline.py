"""Roofline share of the state-space layers' decode update
(``%llmd.ssm.update``) where B and C come in G GROUPS (``n_groups``), counted
as ``kernels.ssm_update_roofline`` counts the one-group kernel: what the call
MUST move and compute for its LIVE rows, from the event's HLO text (the pool
is its ``f32[Lm,slots,H,P,N]`` operand), the configuration's ``n_groups`` and
the program's counter over the TRACED slice:
  rows  = ssm_update_rows_total            (live decode rows x mixer layers)
  bytes = rows x (2 x H x P x N x 4        the state, read and written
               + 3 x H x P x 4             decay and dt*x in (pre-broadcast over P), y out
               + 2 x G x N x 4)            the row's G groups' B and C
  FLOPs = rows x H x P x N x 5             a*H + dtx*B (3), H*C and its sum (2)
HBM-bound by the count: 5 FLOP to 8 bytes. Nothing caps it. A program without
the counter (or without the kernel), or a configuration without ``n_groups``,
gives None.
"""

import json
import pathlib
import re

POOL = re.compile(r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def row_cost(name: str, groups: int):
    """(flops, bytes) of ONE live row from the call's instruction text."""
    m = POOL.search(name.partition(" custom-call(")[2])
    if not m:
        return None
    _lm, _slots, h, p, n = (int(x) for x in m.groups())
    return 5.0 * h * p * n, 2.0 * h * p * n * 4 + 3.0 * h * p * 4 + 2.0 * groups * n * 4


def read(ctx, definition):
    trace = ctx.get("trace")
    groups = (ctx.get("config") or {}).get("n_groups")
    rows = (ctx.get("counter_delta_traced") or {}).get("ssm_update_rows_total", 0)
    if not trace or not trace.get("op_seconds") or rows <= 0 or not groups:
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    cost, measured = None, 0.0
    for name, seconds in trace["op_seconds"].items():
        if rx.search(name.split(" = ", 1)[0]):
            cost = cost or row_cost(name, int(groups))
            measured += seconds
    if cost is None or measured <= 0:
        return None
    flops, nbytes = cost
    least = rows * max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / measured
