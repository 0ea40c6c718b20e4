"""Roofline share of the grouped expert matmul (megablox ``gmm``, or a kernel
that replaces it and keeps an event name that starts with ``%gmm``).

Each ``%gmm`` event of the trace is named by its HLO instruction, which holds
the call's shapes: output ``f32[M,N]``, activations ``bf16[M,K]`` and ONE weight
operand, in one of these forms:
  ``[E,K,N]``    one layer's experts (today; gate and up fused to ``[E,K,2N']``
                 against an output ``[M,2N']`` reads the same way);
  ``[L,E,K,N]``  the stacked leaf of all layers with a layer index: the call
                 multiplies ONE layer's ``E x K x N``, never ``L x``.
Any other form raises and names the instruction. From the shapes, per call:
  FLOPs = 2 M K N        (over the padded rows: the kernel multiplies them)
  bytes = touched x E K N w   (the weights of the experts that HAVE rows: the
                               kernel skips the tiles of an empty group)
        + M K a + M N o       (activations in, output out)
``touched`` is the metric that the definition names under ``touched_metric``,
in %: the share of experts with at least one row in a grouped call over the
traced steps, which only the program can count (group sizes are run-time
values; no trace holds them). Where ``perfbench/layer_metrics/`` has no
definition of that name, no program counts yet and every expert is charged,
``touched`` = 1: an UPPER bound on the bytes, which read PR 27's kernel at
140 % of the chip's memory (ledger) and is exact only where every expert has
rows. The PR that makes the program count adds that definition (data: a
``counter_ratio`` over the traced slice) and from then on a trace with
``%gmm`` calls whose share reads nothing, 0 or over 100 raises: nothing falls
back to every expert. One mean share is exact for the sum: gate, up and down of
a layer have the same ``E x K x N`` bytes and the same group sizes.
The least time is the larger of FLOPs / peak FLOP/s and bytes / peak HBM
bytes/s (perfbench/peaks.json, by device kind; a kind that is missing is an
error). The metric is sum(least) / sum(measured device time), in %; HBM-bound
in every cell so far. Nothing caps it: bytes counted too high show over 100 %.
"""

import json
import pathlib
import re

from perfbench import reducers

SHAPE = re.compile(r"(bf16|f32|f16|s8)\[([\d,]+)\]")
WIDTH = {"bf16": 2, "f32": 4, "f16": 2, "s8": 1}


def _operands(rest: str) -> str:
    """The operand list of a call: ``rest`` up to the parenthesis that closes
    it (layouts such as ``T(8,128)(2,1)`` nest; the attributes that follow, an
    ``operand_layout_constraints`` among them, repeat the shapes)."""
    depth = 0
    for i, c in enumerate(rest):
        if c == "(":
            depth += 1
        elif c == ")":
            if depth == 0:
                return rest[:i]
            depth -= 1
    return rest


def call_cost(name: str, touched: float = 1.0):
    """(flops, bytes) of one gmm call from its instruction text, ``touched``
    of its experts having rows; ValueError on a form it does not know."""
    short = name.split(" = ", 1)[0]
    head, call, rest = name.partition(" custom-call(")
    out = SHAPE.search(head.partition(" = ")[2])
    ops = [(d, [int(x) for x in dims.split(",")]) for d, dims in SHAPE.findall(_operands(rest))]
    weights = [o for o in ops if len(o[1]) >= 3]
    if not call or not out or out.group(2).count(",") != 1 or len(weights) != 1 or len(weights[0][1]) > 4:
        raise ValueError(f"kernels.moe_gmm_roofline: {short}: no output [M,N] with one weight operand "
                         f"[E,K,N] or [L,E,K,N] in {name[:2000]!r}")
    m, n = (int(x) for x in out.group(2).split(","))
    e, k, n2 = weights[0][1][-3:]
    if n2 != n:
        raise ValueError(f"kernels.moe_gmm_roofline: {short}: weights [..,{k},{n2}] against an output [{m},{n}]")
    act = next((WIDTH[d] for d, dims in ops if dims == [m, k]), 2)
    nbytes = touched * e * k * n * WIDTH[weights[0][0]] + m * k * act + m * n * WIDTH[out.group(1)]
    return 2.0 * m * k * n, nbytes


def touched_share(ctx, definition) -> float:
    """The share in (0, 1] of a grouped call's experts that had rows over the
    traced steps; 1.0 while no program counts (see the module's docstring)."""
    name = definition.get("touched_metric")
    if not name or not (pathlib.Path(ctx["bench_dir"]) / "layer_metrics" / f"{name}.json").exists():
        return 1.0
    share = reducers.reduce("per_layer", name, ctx)
    if share is None or not 0.0 < share <= 100.0:
        raise RuntimeError(f"kernels.moe_gmm_roofline: the trace has grouped matmul calls and {name} over the "
                           f"traced slice reads {share!r}: it has to say how many experts had rows")
    return share / 100.0


def read(ctx, definition):
    trace = ctx.get("trace")
    if not trace or not trace.get("op_seconds"):
        return None
    rx = re.compile(definition["pattern"])
    calls = {n: s for n, s in trace["op_seconds"].items() if rx.search(n.split(" = ", 1)[0])}
    measured = sum(calls.values())
    if measured <= 0:
        return None
    touched = touched_share(ctx, definition)
    peaks = json.loads((pathlib.Path(ctx["bench_dir"]) / "peaks.json").read_text())
    peak = peaks[ctx["device"]["kind"]]  # KeyError: a chip without peaks is an error
    least = 0.0
    for name in calls:
        flops, nbytes = call_cost(name, touched)
        least += max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) * trace["op_calls"][name]
    return 100.0 * least / measured
