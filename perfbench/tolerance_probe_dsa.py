"""Where the limits of ``references/gqa_dsa_moe.py`` come from: the comparison
that decides ``correct``, for MANY seeds in one process on the chip, sound and
under SEVEN CONTROLS, every one of them THROUGH ``correctness.reference_check``
(the limits it reads are the reference's own), so that what it prints is what
a whole run would have read. A control must come out ``ok: false``.

Wrong references (the system is sound, the reference is not):
  * ``one_expert_fewer``: top-k of the experts less one, every layer (either
    side dropped a term);
  * ``selection_ignored``: ``sa_config.topk`` past the context (attention over
    ALL cached tokens);
  * ``index_keys_permuted``: the 64 columns of every layer's ``wi_k`` in
    another order (a selection is made, from the wrong keys);
  * ``weights_float8``: every weight rounded to float8_e4m3, the nearest
    precision below the served bfloat16 (rounded IN PLACE, last: the engine
    is not used again).
Faulty systems (the serving path's log-probs are sound; the selection handed
to the reference is made as a faulty program would make it, over the same
cached keys):
  * ``approx_topk``: the top quarter-k of each of four interleaved quarters
    of the keys in place of the exact top-k (a blocked top-k without its
    merge pass: ~1.5 % of a set differs; ``jax.lax.approx_max_k`` was tried
    first and is exact at 2,048 of 4,352 on the v5e);
  * ``scores_float8``: the index scores from operands rounded to float8_e4m3.

The system is run once a seed; the controls replay its answers. Per check the
line holds ``reference_check``'s verdict and numbers, every compared token's
two log-probs (before the overlaps are held against them) and, per bound
prompt and layer, the two overlaps.
A builder's tool, not part of a run; it stands beside ``tolerance_probe.py``
and edits nothing of it.

    python3 perfbench/tolerance_probe_dsa.py --workload <cell> --seeds 7,2147483999
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

RECORDER = "perfbench.references._probe_recorder"


class Replay:
    """The system as ``correctness.py`` sees it: run once, its answers
    replayed to every later check, its parameter tree passed through
    ``alter``."""

    def __init__(self, system) -> None:
        self.system, self.answers, self.alter = system, {}, lambda params: params
        self.vocab_size, self.max_model_len = system.vocab_size, system.max_model_len
        self._params = None

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        key = tuple(tuple(p) for p in prompts)
        if key not in self.answers:
            self.answers[key] = self.system.greedy_with_logprobs(prompts, max_tokens)
        return self.answers[key]

    def reference_params(self) -> dict:
        if self._params is None:
            self._params = self.system.reference_params()
        return self.alter(self._params)


def recorder(ref, rows: list):
    """The reference as a module of its own name: ``forward`` writes down what
    ``score`` gave before holding it to the overlap."""
    def forward(params, tokens, conf):
        nxt, best, overlaps = ref.score(params, tokens, conf)
        rows.append({"nxt": np.asarray(nxt, np.float64), "overlap": overlaps})
        return ref.held_to_overlap(nxt, overlaps), best

    mod = types.ModuleType(RECORDER)
    mod.forward = forward
    for name in ("LOGPROB_MEDIAN_ATOL", "LOGPROB_P90_ATOL", "LOGPROB_MAX_ATOL", "MARGIN_ATOL"):
        setattr(mod, name, getattr(ref, name))
    return mod


def faulty_selection(system, params: dict, **stand_ins) -> dict:
    """``params`` with every bound prompt's selection made by ``stand_ins``
    (``score`` / ``pick``) over the keys the system cached."""
    bound = {p: dict(e, selection=system.selection(*e["cached"], **stand_ins))
             for p, e in params["bound"].items()}
    return dict(params, bound=bound)


def controls(system, pub: dict) -> dict:
    """name -> (published keys the reference reads, what is done to the tree)."""
    import jax
    import jax.numpy as jnp

    from llmd_tpu.ops import sparse_attention as sa

    # float8_e4m3's 4 exponent and 3 mantissa bits. Not a cast there and back: XLA may drop such a pair as excess
    # precision, and on the v5e it did (my chip run, PR 28: both float8 controls read as the sound run).
    f8 = lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)  # noqa: E731

    def approx_topk(scores, topk, parts=4):
        t, s = scores.shape
        _, idx = jax.lax.top_k(scores.reshape(t, s // parts, parts).swapaxes(1, 2), topk // parts)  # [t, parts, k/parts]
        idx = idx * parts + jnp.arange(parts)[None, :, None]
        return jnp.zeros(scores.shape, bool).at[jnp.arange(t)[:, None], idx.reshape(t, -1)].set(True)

    def scores_float8(iq, iw, plane, *rest):
        return sa.index_scores(f8(iq), f8(iw), f8(plane), *rest)

    def permuted(params):
        layers = dict(params["layers"])
        perm = np.random.default_rng(17).permutation(layers["wi_k"].shape[-1])
        layers["wi_k"] = layers["wi_k"][..., perm]
        return dict(params, layers=layers)

    def float8(params):  # leaf by leaf, each into its own buffer: a second copy of the experts does not fit
        rounded = jax.jit(f8, donate_argnums=0)
        arrays = {k: v for k, v in params.items() if k != "bound"}
        return dict(jax.tree.map(lambda a: rounded(a) if jnp.issubdtype(a.dtype, jnp.floating) else a, arrays),
                    bound=params["bound"])

    same = lambda params: params  # noqa: E731
    return {
        "sound": (pub, same),
        "one_expert_fewer": (dict(pub, num_experts_per_tok=pub["num_experts_per_tok"] - 1), same),
        "selection_ignored": (dict(pub, sa_config=dict(pub["sa_config"], topk=1 << 30)), same),
        "index_keys_permuted": (pub, permuted),
        "approx_topk": (pub, lambda params: faulty_selection(system, params, pick=approx_topk)),
        "scores_float8": (pub, lambda params: faulty_selection(system, params, score=scores_float8)),
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
                lps = [x for answers in replay.answers.values() for _toks, lp in answers for x in lp]
                print(json.dumps({
                    "seed": seed, "check": name, "ok": check["ok"], "logprob_diff": check["logprob_diff"],
                    "reference_margin": check["reference_margin"], "prompt_lens": check["prompt_lens"],
                    "system": [float(x) for x in lps],
                    "reference": [r["nxt"].tolist() for r in rows],  # per prompt, every position of the padded prompt
                    "overlap": [r["overlap"] for r in rows],
                }), flush=True)
        finally:
            system.stop()
            del system, replay
            sys.modules.pop(RECORDER, None)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
