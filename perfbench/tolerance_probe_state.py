"""Where the tolerances of ``references/mamba2_gqa_moe_share.py`` come from,
and the controls that have to fail them: the comparison that decides
``correct`` (``perfbench/correctness.py`` through ``topologies/engine_state.py``:
a state carried over 1.5k-token contexts, a snapshot miss and two hits,
prefills split over shared steps), made for MANY seeds in one process on the
chip, then the SAME system log-probs scored against wrong references. A
builder's tool, not part of a run; what it found is in the reference's file
and in PERF.md. ``tolerance_probe_share.py`` for this architecture's controls.

    python3 perfbench/tolerance_probe_state.py --workload <cell> --seeds 7,2147483999

Per seed one JSON line for the sound comparison and one for each control: the
median, 90th percentile and max of |system - reference| over the 128 compared
tokens, the reference's largest margin, ``ok`` by the reference's limits, and
under ``states`` the first mixer's SSM states the topology read out of the
pool (four slots and a snapshot) against the same reference's
``first_mixer_state``, each with its own ``ok``. A control FAILS when its
log-probs or any of its states is not ``ok`` (``fails``).
Controls (each a reference that differs from the model in ONE way):
  state_bf16          the SSM state rounded to bfloat16 after every token
  conv_state_zeroed   the conv forgets its inputs at every 512th position and
                      at every decoded token (a conv state zeroed between
                      steps: prefill chunks of 512, a decode token a step)
  snapshot_stale      behind a context, the recurrent state misses the
                      context's last page (a snapshot taken one page early;
                      the attention layer sees every token)
  residual_1          residual_multiplier 1 for 0.22
  attention_scale     the softmax scale head_dim^-0.5 for attention_multiplier
  router_held         the router scores the 36 held experts only, not all 72
  float8_weights      every weight rounded to float8 (e4m3) before use: the
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
from perfbench.references import _common  # noqa: E402


def stats(ref, system, reference, margin) -> dict:
    d = np.abs(np.asarray(system) - np.asarray(reference))
    m = np.asarray(margin)
    out = {"median": float(np.median(d)), "p90": float(np.quantile(d, 0.9)), "max": float(np.max(d)),
           "margin_max": float(np.max(m))}
    out["ok"] = bool(
        np.all(np.isfinite(d)) and out["median"] <= ref.LOGPROB_MEDIAN_ATOL and out["p90"] <= ref.LOGPROB_P90_ATOL
        and out["max"] <= ref.LOGPROB_MAX_ATOL and out["margin_max"] <= ref.MARGIN_ATOL)
    return out


def rescore(ref, params, conf, scored) -> tuple[list, list]:
    nxts, margins = [], []
    for padded, at in scored:
        if conf.get("probe_conv_reset"):
            conf = dict(conf, probe_conv_from=at.start + 1)  # the prompt's length: decoding starts there
        nxt, best = (np.asarray(a, np.float64) for a in ref.forward(params, padded, conf))
        nxts.extend(nxt[at].tolist())
        margins.extend((best[at] - nxt[at]).tolist())
    return nxts, margins


def with_float8(ref, fn):
    """``fn()`` with every value the reference reads from the parameter tree
    rounded to float8 e4m3 first (its jitted layers traced anew)."""
    import jax
    import jax.numpy as jnp

    jitted = [ref._mamba, ref._first_state, ref._attention, ref._sparse_ffn, ref._head]
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


def controls(ref, params, conf) -> dict:
    """name -> (params, conf) of each wrong reference."""
    layers = params["layers"]
    held = layers["we_gate"].shape[1]
    first = ref.first_held(params, conf)
    head_dim = conf["hidden_size"] // conf["num_attention_heads"]
    return {
        "state_bf16": (params, dict(conf, probe_state_dtype="bfloat16")),
        "conv_state_zeroed": (params, dict(conf, probe_conv_reset=512)),
        "snapshot_stale": (params, dict(conf, probe_stale_tokens=16)),
        "residual_1": (params, dict(conf, residual_multiplier=1.0)),
        "attention_scale": (params, dict(conf, attention_multiplier=head_dim ** -0.5)),
        "router_held": (dict(params, layers=dict(layers, router=layers["router"][..., first:first + held])), conf),
    }


def probe(args, seed: int, wanted) -> None:
    """One seed: its own function, so that nothing of one system (a control's
    parameter tree among it) outlives the call into the next seed's."""
    spec, _mix, system = run.prepare(args)
    try:
        conf = run.published(spec.config["rehearse"]["published"] if args.rehearse else spec.config)
        ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
        if args.warm:
            system.warm_up()
        got = correctness.sample(system, conf, spec.config["reference"], seed)
        params = system.reference_params()
        say = lambda **kw: print(json.dumps({"seed": seed, **kw}), flush=True)  # noqa: E731
        say(complete=got["complete"], prompt_lens=got["prompt_lens"], check_log=system.check_log,
            setup_log=system.setup_log, sound=stats(ref, got["system"], got["reference"], got["margin"]),
            states=system.state_log)
        if not got["complete"]:
            # (the states need none of the outputs that the comparison withheld)
            for name, (p, c) in controls(ref, params, conf).items():
                say(**{name: {"states": system.state_errors(conf=c, params=p)}})
            return

        def control(p, c) -> dict:
            out = stats(ref, got["system"], *rescore(ref, p, c, got["scored"]))
            out["states"] = system.state_errors(conf=c, params=p)
            out["fails"] = not (out["ok"] and all(e["ok"] for e in out["states"]))
            return out

        for name, (p, c) in controls(ref, params, conf).items():
            if wanted(name):
                say(**{name: control(p, c)})
        if wanted("float8_weights"):
            say(float8_weights=with_float8(ref, lambda: control(params, conf)))
    finally:
        system.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0, help=argparse.SUPPRESS)
    ap.add_argument("--warm", action="store_true", help="warm up first, as a whole run does")
    ap.add_argument("--controls", type=int, default=None, help="run every control for the first N seeds only")
    ap.add_argument("--always", default="", help="controls to run for every seed, comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args()
    always = set(filter(None, args.always.split(",")))
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        args.seed = seed
        every = args.controls is None or n < args.controls
        probe(args, seed, lambda name: every or name in always)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
