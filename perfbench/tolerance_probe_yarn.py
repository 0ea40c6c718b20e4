"""Where the tolerances of ``references/gqa_swa_yarn_moe_share.py`` come from,
and the controls that have to fail them: the comparison that decides
``correct`` (``perfbench/correctness.py`` through
``topologies/engine_hybrid_yarn.py``: a 4,096-token context through both
pools, a section miss and two hits, 64 decoded tokens a prompt among the cell's
other running rows, layer 3's cached keys) made for MANY seeds in one process
on the chip, then the SAME system log-probs and keys scored against wrong
references. A builder's tool, not part of a run; what it found is in the
reference's file and in PERF.md.

    python3 perfbench/tolerance_probe_yarn.py --workload <cell> --seeds 7,2147483999

Per seed one JSON line for the sound comparison and one a control, each with
``first16`` (``correctness.py``'s 128 tokens), ``decode`` (the topology's
longer decode, pooled over the eight prompts) and ``keys`` (layer 3's cached
keys of the four bound prompts); a control FAILS when any of the three is not
``ok``. Controls (each a reference that differs from the model in ONE way):
  plain_on_full      the full layers rotate under the sliding layers' table
  yarn_on_sliding    the sliding layers rotate under the full layers' table
  attention_factor_1 YaRN's frequencies with cos and sin x 1
  window_x2          a window of twice the published 1,024
  one_expert_fewer   the rank holds 15 of its 16 experts
  bfloat16_reference the reference's products in bfloat16 (it does NOT fail:
                     the system computes in bfloat16 itself; the reference's
                     file says so beside its limits)
  float8_weights     every weight rounded to float8 (e4m3) before use: the
                     nearest precision below the configuration's bfloat16
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
from perfbench.tolerance_probe_share import rescore, stats  # noqa: E402
from perfbench.topologies.engine_mixer import decode_stats  # noqa: E402


def controls(params, conf) -> dict:
    """name -> conf of each wrong reference (the parameter tree is the model's)."""
    tables = conf["rope_parameters"]
    held = params["layers"]["we_gate"].shape[1]
    return {
        "plain_on_full": dict(conf, rope_parameters=dict(tables, full_attention=tables["sliding_attention"])),
        "yarn_on_sliding": dict(conf, rope_parameters=dict(tables, sliding_attention=tables["full_attention"])),
        "attention_factor_1": dict(conf, rope_parameters=dict(
            tables, full_attention=dict(tables["full_attention"], attention_factor=1.0))),
        "window_x2": dict(conf, sliding_window=2 * conf["sliding_window"]),
        "one_expert_fewer": dict(conf, experts_used=held - 1),
        "bfloat16_reference": dict(conf, compute_dtype="bfloat16"),
    }


def with_float8(ref, fn):
    """``fn()`` with every value the reference reads from the parameter tree
    rounded to float8 e4m3 first (its jitted layers traced anew)."""
    import jax
    import jax.numpy as jnp

    from perfbench.references import _common

    jitted = [ref._attention, ref._keys, ref._sparse_ffn, ref._head]
    plain = _common.f32
    _common.f32 = lambda x: jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=4, mantissa_bits=3)
    try:
        for f in jitted:
            f.clear_cache()
        return fn()
    finally:
        _common.f32 = plain
        for f in jitted:
            f.clear_cache()


def probe(args, seed: int, wanted) -> None:
    """One seed: the sound comparison, then every control's."""
    spec, _mix, system = run.prepare(args)
    try:
        conf = run.published(spec.config["rehearse"]["published"] if args.rehearse else spec.config)
        ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
        if args.warm:
            system.warm_up()
        system.withhold = False
        got = correctness.sample(system, conf, spec.config["reference"], seed)
        params = system.reference_params()
        decoded = [(padded, at) for padded, at, _ in system.decoded]
        lps = np.concatenate([np.asarray(lp) for _, _, lp in system.decoded])

        def scores(c) -> dict:
            out = {"first16": stats(ref, got["system"], *rescore(ref, params, c, got["scored"]))}
            nxt, margin = rescore(ref, params, c, decoded)
            out["decode"] = decode_stats(ref, np.abs(lps - np.asarray(nxt)), np.asarray(margin))
            out["keys"] = system.key_errors(conf=c, params=params)
            out["fails"] = not (out["first16"]["ok"] and out["decode"]["ok"] and all(e["ok"] for e in out["keys"]))
            return out

        say = lambda **kw: print(json.dumps({"seed": seed, **kw}), flush=True)  # noqa: E731
        say(complete=got["complete"], prompt_lens=got["prompt_lens"], check_log=system.check_log,
            live_rows=system.live_rows, setup_log=system.setup_log[:4], sound=scores(conf))
        for name, c in controls(params, conf).items():
            if wanted(name):
                say(**{name: scores(c)})
        if wanted("float8_weights"):
            say(float8_weights=with_float8(ref, lambda: scores(conf)))
    finally:
        system.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--warm", action="store_true", help="warm up first, as a whole run does")
    ap.add_argument("--controls", type=int, default=None, help="run every control for the first N seeds only")
    ap.add_argument("--always", default="", help="controls to run for every seed, comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.seconds, args.root = 1.0, str(ROOT)
    always = set(filter(None, args.always.split(",")))
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        args.seed = seed
        every = args.controls is None or n < args.controls
        probe(args, seed, lambda name: every or name in always)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
