"""Find the knee of an open-loop cell once, on the chip: one set-up, then the
cell's traffic at each of a few rates for ``--seconds`` each. A builder's
tool, not part of a run; its points go into PERF.md and the chosen rate into
the cell's file. The knee is the highest rate at which the backlog does not
grow: completed tokens keep up with offered tokens and the time to first
token does not climb through the window.

    python3 perfbench/sweep.py --workload <cell> --rates 4,6,8,10,12 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import run, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args()
    spec, mix, system = run.prepare(args)
    try:
        check = run.set_up(system, spec, args)
        print(json.dumps({"setup_log": system.setup_log, "reference_check": check}), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell = dict(spec.cell, rate=rate)
            rec, marks = run.run_window(system, spec, mix, cell, args.seed + i, args.seconds, False, None)
            s = rec.series()
            reqs = rec.measured()
            half = [r for r in reqs if r.due >= (rec.t0 + rec.t1) / 2]
            ttft_late = [(r.token_times[0] - r.due) * 1e3 for r in half if r.token_times]
            offered = sum(r.want_tokens for r in reqs) / args.seconds
            print(json.dumps({
                "rate": rate, "requests": len(reqs),
                "failed": sum(1 for r in reqs if rec.failed(r)),
                "offered_tok_s": offered,
                "output_tok_s": s["output_tokens"][0] / args.seconds,
                "ttft_p50_ms": stats.percentile(s["ttft_ms"], 50),
                "ttft_p90_ms": stats.percentile(s["ttft_ms"], 90),
                "ttft_p50_ms_second_half": stats.percentile(ttft_late, 50),
                "itl_p50_ms": stats.percentile(s["itl_ms"], 50),
                "itl_p95_ms": stats.percentile(s["itl_ms"], 95),
                "late_p95_ms": stats.percentile(s["late_ms"], 95),
                "preemptions": marks["c1"]["preemptions"] - marks["c0"]["preemptions"],
                "compiles": marks["c1"]["compile_programs"] - marks["c0"]["compile_programs"],
            }), flush=True)
    finally:
        system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
