"""Roofline share of the state-space layers' decode update
(``%llmd.ssm.update``: the Pallas kernel of ``llmd_tpu/ops/ssm.py`` under
``jax.named_scope llmd.ssm.update``, one call a mixer layer a step).

Counted as what the call MUST move and compute: a decode row reads its
slot's state ``[H, P, N]`` in float32, updates it with one token and writes
it back; nothing of it can be skipped or shared between rows. Per call, from
the event's HLO text (the pool is its ``f32[Lm,slots,H,P,N]`` operand) and the
program's counter over the TRACED slice (``counter_delta_traced``):
  rows  = ssm_update_rows_total / calls   (live decode rows x mixer layers, over
          one call a layer: the live rows of a call; a call's trailing entries
          name the last live row's blocks again and move nothing)
  bytes = rows x (2 x H x P x N x 4        the state, read and written
               + 3 x H x P x 4             decay and dt*x in (pre-broadcast over P), y out
               + 2 x N x 4)                B and C
  FLOPs = rows x H x P x N x 5             a*H + dtx*B (3), H*C and its sum (2)
HBM-bound by the count: 5 FLOP to 8 bytes. Nothing caps it: a count that is
too high would show over 100 %. A program without the counter (or without the
kernel) gives None.
"""

import json
import pathlib
import re

POOL = re.compile(r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def row_cost(name: str):
    """(flops, bytes) of ONE live row from the call's instruction text."""
    m = POOL.search(name.partition(" custom-call(")[2])
    if not m:
        return None
    _lm, _slots, h, p, n = (int(x) for x in m.groups())
    return 5.0 * h * p * n, 2.0 * h * p * n * 4 + 3.0 * h * p * 4 + 2.0 * n * 4


def read(ctx, definition):
    trace = ctx.get("trace")
    rows = (ctx.get("counter_delta_traced") or {}).get("ssm_update_rows_total", 0)
    if not trace or not trace.get("op_seconds") or rows <= 0:
        return None
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    rx = re.compile(definition["pattern"])
    cost, measured = None, 0.0
    for name, seconds in trace["op_seconds"].items():
        if rx.search(name.split(" = ", 1)[0]):
            cost = cost or row_cost(name)
            measured += seconds
    if cost is None or measured <= 0:
        return None
    flops, nbytes = cost
    least = rows * max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / measured
