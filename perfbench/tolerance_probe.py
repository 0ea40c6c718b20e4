"""Where the tolerances of a reference come from: the comparison that decides
``correct`` (perfbench/correctness.py), made for MANY seeds in one process
on the chip, with every compared token written down. A builder's tool, not
part of a run; what it found is in the reference's file and in PERF.md.

Per seed: weights from the seed, the engine built (no warm-up unless
``--warm``: the few step shapes the sample needs compile or load on demand;
a whole run compares after its warm-up, and eight DeepSeek seeds read the
same to the last digit either way), ``--prompts`` prompts
through the engine and the reference, and the SKIPPED-TERM probe: the
reference again with one expert fewer of the top-k in every layer, scored
against the system's log-probs, which is what the comparison would read if
either side dropped a term. The tolerances have to sit between the two.

    python3 perfbench/tolerance_probe.py --workload <cell> --seeds 7,2147483999 --prompts 8
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import correctness, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=1.0, help=argparse.SUPPRESS)
    ap.add_argument("--warm", action="store_true", help="warm up first, as a whole run does")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        args.seed = seed
        spec, _mix, system = run.prepare(args)
        held = system.peak_bytes()  # would grow from seed to seed if the last system were not freed
        try:
            conf = spec.config["rehearse"]["published"] if args.rehearse else spec.config
            pub = run.published(conf)
            ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
            if args.warm:
                system.warm_up()
            got = correctness.sample(system, pub, spec.config["reference"], seed, args.prompts)
            less = dict(pub, num_experts_per_tok=pub["num_experts_per_tok"] - 1)
            params = system.reference_params()
            skipped = []
            for padded, at in got["scored"]:
                skipped.extend(np.asarray(ref.forward(params, padded, less)[0], np.float64)[at].tolist())
            del params
            print(json.dumps({
                "seed": seed, "complete": got["complete"], "prompt_lens": got["prompt_lens"],
                "system": got["system"], "reference": got["reference"], "margin": got["margin"],
                "reference_one_expert_fewer": skipped, "setup_log": system.setup_log, "peak_bytes_at_start": held,
            }), flush=True)
        finally:
            system.stop()
            del system
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
