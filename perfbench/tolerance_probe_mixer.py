"""``tolerance_probe_state.py`` for ``references/mamba2_gqa_relu2_moe_share.py``:
where its tolerances come from, and the controls that have to fail them. The
comparison that decides ``correct`` (``perfbench/correctness.py`` through
``topologies/engine_mixer.py``: a state carried over a 1.5k-token context, a
snapshot miss and two hits, 64 decoded tokens a prompt among other running
rows) made for MANY seeds in one process on the chip, then the SAME system
log-probs and states scored against wrong references. A builder's tool, not
part of a run; what it found is in the reference's file and in PERF.md.

    python3 perfbench/tolerance_probe_mixer.py --workload <cell> --seeds 7,2147483999

The probe's command line, ``stats`` and ``rescore`` are
``tolerance_probe_state``'s (imported; the seeds' loop is re-stated here, a
dozen lines, so that nothing of that module is replaced). The SYSTEM is
sampled with ``system.withhold`` off, so that the topology withholds nothing
and a seed outside a limit still gives its readings; ``ok`` is then judged by
the limits as the file has them.
Per seed one line for the sound comparison and one a control, each with
``first16`` (``correctness.py``'s 128 tokens), ``decode`` (the topology's
longer decode, pooled over the eight prompts: what decides) and ``states``.
Controls (each a reference that differs from the model in ONE way):
  state_bf16       the SSM state rounded to bfloat16 after every token
  one_group        ONE B and C (group 0's) for all heads: G = 1 arithmetic on
                   G = 8 weights
  norm_whole       the gated norm over all d_in channels at once
  silu_for_relu2   silu in place of relu^2 in the routed and the shared experts
  no_scaling       routed_scaling_factor 1 for 2.5
  router_held      the router scores the 16 held experts only, not all 128
  float8_weights   every weight rounded to float8 (e4m3) before use: the
                   nearest precision below the configuration's bfloat16
"""

from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import tolerance_probe_state as base  # noqa: E402
from perfbench.references import _common  # noqa: E402


def controls(ref, params, conf) -> dict:
    """name -> (params, conf) of each wrong reference."""
    layers = params["layers"]
    held = layers["we_down"].shape[1]
    first = ref.first_held(params, conf)
    cut = {k: layers[k][..., first:first + held] for k in ("router", "router_bias")}
    return {
        "state_bf16": (params, dict(conf, probe_state_dtype="bfloat16")),
        "one_group": (params, dict(conf, probe_one_group=True)),
        "norm_whole": (params, dict(conf, probe_norm_whole=True)),
        "silu_for_relu2": (params, dict(conf, probe_silu=True)),
        "no_scaling": (params, dict(conf, routed_scaling_factor=1.0)),
        "router_held": (dict(params, layers=dict(layers, **cut)), conf),
    }


def with_float8(ref, fn):
    """``fn()`` with every value the reference reads from the parameter tree
    rounded to float8 e4m3 first (its jitted blocks traced anew)."""
    import jax
    import jax.numpy as jnp

    jitted = [ref._mamba, ref._first_state, ref._attention, ref._experts, ref._head]
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
    import importlib
    import json

    import numpy as np

    from perfbench import correctness, run
    from perfbench.topologies.engine_mixer import decode_stats

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

        def scores(p, c) -> dict:
            out = {"first16": base.stats(ref, got["system"], *base.rescore(ref, p, c, got["scored"]))}
            nxt, margin = base.rescore(ref, p, c, decoded)
            out["decode"] = decode_stats(ref, np.abs(lps - np.asarray(nxt)), np.asarray(margin))
            out["states"] = system.state_errors(conf=c, params=p)
            out["fails"] = not (out["first16"]["ok"] and out["decode"]["ok"] and all(e["ok"] for e in out["states"]))
            return out

        say = lambda **kw: print(json.dumps({"seed": seed, **kw}), flush=True)  # noqa: E731
        say(complete=got["complete"], prompt_lens=got["prompt_lens"], check_log=system.check_log,
            live_rows=system.live_rows, setup_log=system.setup_log[:3], sound=scores(params, conf))
        for name, (p, c) in controls(ref, params, conf).items():
            if wanted(name):
                say(**{name: scores(p, c)})
        if wanted("float8_weights"):
            say(float8_weights=with_float8(ref, lambda: scores(params, conf)))
    finally:
        system.stop()


def main() -> int:
    import argparse
    import gc

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
