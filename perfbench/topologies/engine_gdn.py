"""Topology ``engine_gdn``: ``engine_mixer`` for a configuration whose published
file is a ``qwen3_next`` one: Gated DeltaNet layers through the state pool with
one gated full-attention layer in four (head size 256, a quarter-width
rotation, zero-centred norms), 512 experts top-10 with a sigmoid-gated shared
one, ONE RANK's share of an 8-way expert-parallel deployment, served under
long-document sessions.

Why this file exists. ``topologies/engine_mixer.py`` (not this PR's to edit)
reads ``nemotron_h``'s keys (``hybrid_override_pattern``, ``mamba_*``,
``n_routed_experts``). This file builds the ``EngineConfig`` from THIS
configuration's published keys: ``engine.model_overrides``' mapping, then
``layer_types`` from ``full_attention_interval`` cut to ``num_hidden_layers``,
the ``linear_*`` sizes, ``partial_rotary_factor``, the shared expert's width,
the router's published width (``published.num_experts``) with the file's
``num_experts`` as the experts HELD from id ``deployment.rank`` x held, and the
state pool's ``engine.state_snapshots`` retained snapshots. What the published
file has no key for (the attention's output gate, the zero-centred norms, the
shared expert's gate, QK-norm) is ``model_type: qwen3_next`` itself and comes
with the registry's preset.

The comparison that decides ``correct`` is ``engine_mixer``'s, unchanged in
code and one step further in size: (i) every second of the eight prompts is
served behind a seeded context of ``engine.check_context_tokens`` = 4,096
tokens, 64 rows of the scan a layer, so that the state CARRIED from row to row
decides what is compared and not the first row's; (ii) the second such is a
snapshot MISS that leaves the snapshot, the third and fourth HITS; (iii)
chunks share their steps with decode rows; (iv) the FIRST delta-rule layer's
state of each bound prompt's slot and of the snapshot is read out of the pool
and held per head to the reference's ``first_mixer_state``; (v) each compared
prompt decodes ``engine.check_decode_tokens`` = 64 tokens among
``engine.check_background_rows`` = ``max_num_seqs`` less the pair other rows
over SHORT contexts, admitted once and kept through the last pair, so that
the compared steps are the window's own step program; the fewest live rows of
a compared step is logged (``decode_check.live_rows``).

One comparison is this file's own, because the log-probs cannot tell a wrong
rotation here (three attention layers of twelve, whose scores over seeded
weights are near-uniform: PERF.md section 6, PR 44): (vi) the FIRST attention
layer's cached KEYS of each bound prompt (context, prompt and decoded tokens,
the full pages of them) are read out of the paged pool, as (iv) reads the state
out of the state pool, and held per token to the reference's
``first_attention_keys`` (``key_check.*`` in the set-up log). ``System.
state_errors`` makes it where ``engine_mixer``'s loop asks for (iv), and a
failure withholds the prompt's outputs as a wrong state does.

Everything else is ``topologies/engine_mixer.py``.
"""

from __future__ import annotations

import numpy as np

from perfbench.topologies import engine, engine_mixer

LINEAR_KEYS = ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
               "linear_value_head_dim", "linear_conv_kernel_dim")


def layer_types(conf: dict) -> tuple:
    every = int(conf["full_attention_interval"])
    return tuple("full_attention" if (i + 1) % every == 0 else "linear_attention"
                 for i in range(conf["num_hidden_layers"]))


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, and what this architecture adds to or corrects in it."""
    out = engine.model_overrides(conf)
    held = conf["num_experts"]
    out.update(
        layer_types=layer_types(conf), partial_rotary_factor=conf["partial_rotary_factor"],
        num_experts=conf["published"]["num_experts"], held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
        shared_expert_intermediate_size=conf["shared_expert_intermediate_size"],
        **{k: conf[k] for k in LINEAR_KEYS},
    )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """The EngineConfig the file describes: ``engine_mixer``'s geometry with
    this file's model."""
    engine_mixer.model_overrides, stock = model_overrides, engine_mixer.model_overrides
    try:
        return _mixer_engine_config(conf, seed, rehearse)
    finally:
        engine_mixer.model_overrides = stock


_mixer_engine_config = engine_mixer.engine_config


class System(engine_mixer.System):
    def __init__(self, ctx) -> None:
        # engine_mixer.System builds its EngineConfig through its module's function.
        engine_mixer.engine_config = engine_config
        try:
            super().__init__(ctx)
        finally:
            engine_mixer.engine_config = _mixer_engine_config
        self.keys_seen: list = []  # (vi), per bound prompt: (its sequence, the keys its full pages hold)
        self.key_log: list = []  # (vi), the sound comparison's entries

    def _cached_keys(self, toks: list):
        """(vi): the first attention layer's keys ``[tokens, Nk, D]`` in the
        full pages the main pool has cached for ``toks`` (a finished
        request's pages keep their rows until they are allocated again, and
        nothing has run since)."""
        eng = self.engine
        pages = eng.allocator.lookup_cached_prefix(toks)
        if not pages:
            return None
        d = self.model_cfg.head_dim  # a row is K | V
        # (gather the pages, THEN cut the rows: one indexing expression makes the chip's compiler re-lay the pool)
        rows = np.asarray(eng.runner.kv_cache[0, np.asarray(pages)][..., :d], np.float32)  # [pages, Nk, page, D]
        return rows.transpose(0, 2, 1, 3).reshape(-1, rows.shape[1], d)

    def key_errors(self, seen: list | None = None, conf: dict | None = None, params: dict | None = None) -> list:
        """(vi): ``keys_seen`` (or the ones given) against the reference's
        ``first_attention_keys``, under its limits; ``conf`` / ``params``: a
        wrong reference (``perfbench/tolerance_probe_gdn.py``)."""
        ref = self._ref
        own = self.ctx.config["rehearse"]["published"] if self.ctx.rehearse else self.ctx.config
        conf, params = conf or own, params or self.reference_params()
        n_ctx = int(self.geo["check_context_tokens"])
        # the one shape ``state_errors`` pads to: the reference's layers are compiled for it already
        total = n_ctx + engine_mixer.correctness.PROMPT_MAX + max(
            engine_mixer.correctness.DECODE_TOKENS, int(self.geo["check_decode_tokens"]))
        out = []
        for toks, keys in self.keys_seen if seen is None else seen:
            want = ref.first_attention_keys(params, toks + [0] * (total - len(toks)), conf, context_len=n_ctx)
            err = ref.key_error(keys, np.asarray(want)[: len(keys)])
            err.update(what="keys", tokens=len(keys), ok=bool(
                err["token_median"] <= ref.KEY_TOKEN_MEDIAN_RTOL and err["far_share"] <= ref.KEY_FAR_SHARE_MAX))
            out.append(err)
        return out

    def state_errors(self, seen: list | None = None, conf: dict | None = None, params: dict | None = None) -> list:
        """``engine_mixer``'s (iv), and (vi) beside it. With ``seen`` (the
        loop's call behind a pair) the bound prompt's keys are read out of the
        pool first; without, everything seen so far is scored again (the
        probe's wrong references)."""
        out = super().state_errors(seen, conf, params)
        new = None
        if seen is not None:
            bound = [toks for what, toks, _state in seen if what == "slot"]
            new = [(toks, keys) for toks in bound if (keys := self._cached_keys(toks)) is not None]
            self.keys_seen += new
        errs = self.key_errors(new, conf, params)
        if seen is not None and len(new) < len(bound):
            errs.append({"what": "keys", "tokens": 0, "ok": False})  # a bound prompt whose pages are gone
        if conf is None and params is None:
            self.key_log += errs
            self.setup_log += [(f"key_check.{k}", round(e[k], 6)) for e in errs if e["tokens"]
                               for k in ("token_median", "far_share")]
        return out + errs


def start(ctx) -> System:
    return System(ctx)
