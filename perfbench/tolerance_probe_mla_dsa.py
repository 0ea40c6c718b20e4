"""Where the limits of ``references/mla_dsa_moe_share.py`` come from: the
comparison that decides ``correct``, for MANY seeds in one process on the chip,
sound and under its CONTROLS, every one of them THROUGH
``correctness.reference_check`` (the limits it reads are the reference's own),
so that what it prints is what a whole run would have read. A control must
come out ``ok: false``; one that a sound run cannot be told from on some seed
is written down as such in the reference's file (ROADMAP S13 (l)).

Wrong references (the system is sound, the reference leaves a term out or
changes it; ``tolerance_probe_dsa.py``'s ``Replay`` runs the system once a
seed and replays its answers):
  * ``one_held_expert_fewer``: 15 of the 16 held experts' terms, every layer;
  * ``attend_all``: attention over ALL cached tokens (the selection ignored);
  * ``indexer_from_input``: the indexer's queries from x^ (its first
    ``q_lora_rank`` dimensions) instead of the query latent c_q;
  * ``indexer_rope_all``: the indexer rotated over all 128 dimensions;
  * ``no_yarn_temperature``: m^2 dropped from the softmax scale;
  * ``no_group_limit``: the router's top-8 over all 256 scores;
  * ``weights_float8``: every weight rounded to float8_e4m3, the nearest
    precision below the served bfloat16 (in place, last).
A faulty system (its log-probs are sound; what it left in the cache is not):
  * ``latents_float8``: the first layer's cached latent rows rounded to
    float8_e4m3, as a cache narrower than bfloat16 would hold them;
  * ``index_keys_float8``: the selection made over cached indexer keys
    rounded to float8_e4m3 (scores from a narrower key plane).

One line a check: ``reference_check``'s verdict and numbers and, per bound
prompt, the overlaps and the first layer's latent row error. A builder's tool,
not part of a run.

    python3 perfbench/tolerance_probe_mla_dsa.py --workload <cell> --seeds 7,2147483999
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import pathlib
import sys
import types

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from perfbench import correctness, run, tolerance_probe  # noqa: E402
from perfbench.tolerance_probe_dsa import RECORDER, Replay, faulty_selection  # noqa: E402


def recorder(ref, rows: list):
    """The reference as a module of its own name: ``forward`` writes down what
    ``score`` gave before holding it to its checks."""
    def forward(params, tokens, conf):
        nxt, best, checks = ref.score(params, tokens, conf)
        rows.append(dict(checks, nxt=np.asarray(nxt, np.float64)))
        return ref.held_to_checks(nxt, checks), best

    mod = types.ModuleType(RECORDER)
    mod.forward = forward
    for name in ("LOGPROB_MEDIAN_ATOL", "LOGPROB_P90_ATOL", "LOGPROB_MAX_ATOL", "MARGIN_ATOL"):
        setattr(mod, name, getattr(ref, name))
    return mod


def controls(system, pub: dict) -> dict:
    """name -> (published keys the reference reads, what is done to the tree)."""
    import jax
    import jax.numpy as jnp

    from llmd_tpu.ops import sparse_attention as sa

    # float8_e4m3's bits by ``reduce_precision``: XLA may drop a cast there and back (tolerance_probe_dsa.py).
    f8 = lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)  # noqa: E731

    def keys_float8(iq, iw, plane, *rest):
        return sa.index_scores(iq, iw, f8(plane), *rest)

    def latents_float8(params):
        return dict(params, bound={p: dict(e, latents=f8(e["latents"])) for p, e in params["bound"].items()})

    def float8(params):  # leaf by leaf, each into its own buffer: a second copy of the experts does not fit
        rounded = jax.jit(f8, donate_argnums=0)
        arrays = {k: v for k, v in params.items() if k != "bound"}
        return dict(jax.tree.map(lambda a: rounded(a) if jnp.issubdtype(a.dtype, jnp.floating) else a, arrays),
                    bound=params["bound"])

    same = lambda params: params  # noqa: E731
    held = system.model_cfg.held_experts
    return {
        "sound": (pub, same),
        "one_held_expert_fewer": (dict(pub, experts_used=held - 1), same),
        "attend_all": (dict(pub, attend_all=True), same),
        "indexer_from_input": (dict(pub, indexer_from_input=True), same),
        "indexer_rope_all": (dict(pub, indexer_rope_all=True), same),
        "no_yarn_temperature": (dict(pub, no_yarn_temperature=True), same),
        "no_group_limit": (dict(pub, no_group_limit=True), same),
        "latents_float8": (pub, latents_float8),
        "index_keys_float8": (pub, lambda params: faulty_selection(system, params, score=keys_float8)),
        "weights_float8": (pub, float8),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--only", default="", help="comma-separated controls (default: all)")
    ap.add_argument("--seconds", type=float, default=1.0, help=argparse.SUPPRESS)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=str(tolerance_probe.ROOT))
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        args.seed = seed
        spec, _mix, system = run.prepare(args)
        try:
            conf = spec.config["rehearse"]["published"] if args.rehearse else spec.config
            ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
            replay, rows = Replay(system), []
            sys.modules[RECORDER] = recorder(ref, rows)
            for name, (pub, alter) in controls(system, run.published(conf)).items():
                if args.only and name not in args.only.split(","):
                    continue
                replay.alter = alter
                del rows[:]
                try:
                    check = correctness.reference_check(replay, pub, RECORDER.rsplit(".", 1)[1], seed)
                except Exception as e:  # noqa: BLE001  (a control that cannot run is a finding, not the end of the seeds)
                    print(json.dumps({"seed": seed, "check": name, "error": repr(e)[:500]}), flush=True)
                    continue
                # The log-prob differences BEFORE the checks were held against them (a failed check reads NaN).
                lps = [x for answers in replay.answers.values() for _toks, lp in answers for x in lp]
                raw = np.abs(np.asarray(lps) - np.concatenate(
                    [r["nxt"][n - 1:n - 1 + correctness.DECODE_TOKENS] for r, n in zip(rows, check["prompt_lens"])]))
                least = lambda k: min((min(o[k] for o in r["overlaps"]) for r in rows if r.get("overlaps")), default=None)  # noqa: E731
                print(json.dumps({
                    "seed": seed, "check": name, "ok": check["ok"], "logprob_diff": check["logprob_diff"],
                    "reference_margin": check["reference_margin"],
                    "unheld": {"p50": float(np.median(raw)), "p90": float(np.quantile(raw, 0.9)), "max": float(raw.max()),
                               "p50_unbound": float(np.median(raw.reshape(-1, 2, correctness.DECODE_TOKENS)[:, 1]))},
                    "least_exact": least(0), "least_own": least(1),
                    "own_by_layer": [[round(o[1], 4) for o in r["overlaps"]] for r in rows if r.get("overlaps")][:1],
                    "latent_row_error": max((r["latent"] for r in rows if "latent" in r), default=None),
                }), flush=True)
        finally:
            system.stop()
            del system, replay
            sys.modules.pop(RECORDER, None)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
