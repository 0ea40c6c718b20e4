"""Topology ``engine_mixer``: ``engine_state`` for a configuration whose
published file is a ``nemotron_h`` one: BLOCKS of one mixer each named by
``hybrid_override_pattern`` (Mamba-2 with B and C in groups, non-gated relu^2
experts + a shared one, NoPE attention), ONE RANK's share of an expert-parallel
deployment, served decode-heavy at many resident sequences.

Why this file exists. ``topologies/engine_state.py`` (not this PR's to edit)
reads granite's keys (``layer_types``, ``mamba_n_heads``, the multipliers,
``num_local_experts``), and its comparison decodes 16 tokens a prompt with two
sequences in the system: a cell that is decode only at 128 resident rows wants
the decode through both pools held to the reference for longer, among other
running rows. This file

* builds the ``EngineConfig`` from the configuration file: the published keys
  through ``engine.model_overrides``, then what this architecture adds: the
  pattern cut to ``num_hidden_layers`` BLOCKS and read as layers of mixer (+
  FFN) (``llmd_tpu.models.registry.nemotron_h_layers``), the mixer's sizes
  under their ``nemotron_h`` names, ``norm_eps``, ``mlp_hidden_act``, the
  shared expert's own width, the router's published width
  (``published.n_routed_experts``) with the file's ``n_routed_experts`` as the
  experts HELD from id ``deployment.rank`` x held, and the state pool's
  ``engine.state_snapshots`` retained snapshots;
* serves the comparison that decides ``correct`` as ``engine_state`` does
  ((i) a state carried through several chunks behind a seeded context, (ii) a
  snapshot MISS then HITS, (iii) chunks that share their steps with decode
  rows, (iv) the first mixer's state per head against the reference's), and
    (v)  each compared prompt DECODES ``engine.check_decode_tokens`` tokens
         (>= 64) through the state pool and the paged pool AT THE CELL'S OWN
         LOAD: ``engine.check_background_rows`` further sequences
         (``max_num_seqs`` less the pair) decode beside it over contexts drawn
         from ``engine.check_background_context``, so every compared decode
         step is the step program the window times (the
         T bucket of ``max_num_seqs`` rows, the update kernel over that many
         slots, flat attention over that many rows' pages). The rows are
         admitted in the first of ``correctness.py``'s calls, the shortest
         context first (it decodes longest), and STAY through the last: they
         are aborted when the system starts to serve. (Aborted after a call
         and sent again, their 126 snapshots and the pairs' nine are more than
         the 128 retained: each row that misses captures anew and evicts the
         snapshot the next row came for, and the compared context's pages and
         snapshot go with them: my chip run, PR 42, call 9.) Their contexts are
         SHORT (``check_background_context``, 128-384 tokens): which step
         program runs, its T bucket and the update kernel's slot count follow
         from HOW MANY rows run, and a compared row reads no other row's pages
         or state, so the other rows' lengths bear on nothing that is
         compared; contexts of the traffic's 2,048-3,328 were 340k tokens of
         prefill, 45 s of set-up (my chip run, PR 42, call 10). A row decodes
         from its own admission through all four pairs (~550 steps) and ends
         far below the model length.
         The fewest sequences running in a compared
         decode step is logged (``decode_check.live_rows``), and a call in
         which it is not every background row beside what is left of the pair
         withholds its outputs.
         EVERY decoded token's log-prob is held here to the reference's full
         forward pass under the reference's four limits, POOLED over the
         prompts compared so far (256 tokens after the first four prompts,
         512 after all eight: 64 tokens' median swings by a factor of three
         from prompt to prompt, my chip runs, PR 42). Outside them the call's
         outputs are withheld, so the comparison is incomplete and the run not
         ``correct``. ``correctness.py`` (unedited) then compares the first 16
         tokens of each prompt as it does in every cell.

Everything else is ``topologies/engine_state.py``.
"""

from __future__ import annotations

import importlib

import numpy as np

from perfbench import correctness
from perfbench.topologies import engine, engine_state


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, and what this architecture adds to or corrects in it."""
    from llmd_tpu.models.registry import nemotron_h_layers

    out = engine.model_overrides(conf)
    types, ffn = nemotron_h_layers(conf["hybrid_override_pattern"][: conf["num_hidden_layers"]])
    held = conf["n_routed_experts"]
    out.update(
        num_layers=len(types), layer_types=types, layer_ffn=ffn, rope_layer_types=(),
        rms_norm_eps=conf["norm_eps"],
        mamba_n_heads=conf["mamba_num_heads"], mamba_d_head=conf["mamba_head_dim"],
        mamba_d_state=conf["ssm_state_size"], mamba_n_groups=conf["n_groups"], mamba_d_conv=conf["conv_kernel"],
        num_experts=conf["published"]["n_routed_experts"], held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
        moe_activation=conf["mlp_hidden_act"],
        shared_expert_intermediate_size=conf["n_shared_experts"] * conf["moe_shared_expert_intermediate_size"],
    )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """The EngineConfig the file describes."""
    from llmd_tpu.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
    from llmd_tpu.models.registry import get_model_config

    geo = dict(conf["engine"])
    if rehearse:
        geo.update(conf["rehearse"]["engine"])
        model = get_model_config(
            conf["rehearse"]["registry"], max_model_len=geo["max_model_len"],
            **conf["rehearse"].get("overrides", {}),
        )
    else:
        model = get_model_config(
            conf["registry"], max_model_len=geo["max_model_len"], dtype=conf["dtype"],
            **model_overrides(conf),
        )
    return EngineConfig(
        model=model,
        cache=CacheConfig(page_size=geo["page_size"], num_blocks=geo["num_pages"], dtype=geo["kv_dtype"],
                          swa_sections=geo["state_snapshots"]),
        scheduler=SchedulerConfig(**{k: geo[k] for k in ("max_num_seqs", "max_num_batched_tokens")}),
        parallel=ParallelConfig(),
        seed=seed % (2**31 - 1),
    )


def decode_stats(ref, diffs, margins) -> dict:
    """The four numbers of (v) over ``diffs`` and ``margins``, and ``ok`` by ``ref``'s limits."""
    out = {"tokens": int(len(diffs)), "median": float(np.median(diffs)), "p90": float(np.quantile(diffs, 0.9)),
           "max": float(np.max(diffs)), "margin": float(np.max(margins))}
    out["ok"] = bool(
        np.all(np.isfinite(diffs)) and out["median"] <= ref.LOGPROB_MEDIAN_ATOL and out["p90"] <= ref.LOGPROB_P90_ATOL
        and out["max"] <= ref.LOGPROB_MAX_ATOL and out["margin"] <= ref.MARGIN_ATOL)
    return out


class System(engine_state.System):
    def __init__(self, ctx) -> None:
        engine_state.engine_config, stock = engine_config, engine_state.engine_config
        try:  # engine_state.System builds its EngineConfig through its module's function
            super().__init__(ctx)
        finally:
            engine_state.engine_config = stock
        self.decoded: list = []  # (v), per compared prompt: (padded sequence, positions, system log-probs)
        self._decode_scores: list = []  # (v), per compared prompt: (|system - reference|, the reference's margins)
        self.decode_log: list = []  # (v), per call: the four numbers over the prompts so far, and "ok"
        self.live_rows: list = []  # (v), per call: the fewest sequences running in a compared decode step
        self._back: list = []  # (v): the background rows' request ids, from the first call until the system serves
        # A probe that wants a seed's readings whatever they are sets this False
        # (perfbench/tolerance_probe_mixer.py); a run never does.
        self.withhold = True

    @property
    def _ref(self):
        return importlib.import_module(f"perfbench.references.{self.ctx.config['reference']}")

    def _decode_score(self, prompt: list, toks: list, lps: list) -> None:
        """(v): a prompt's decoded tokens against the reference's full forward pass."""
        ref = self._ref
        own = self.ctx.config["rehearse"]["published"] if self.ctx.rehearse else self.ctx.config
        hi = min(correctness.PROMPT_MAX, self.max_model_len - correctness.DECODE_TOKENS - 1)
        total = hi + int(self.geo["check_decode_tokens"])  # one shape for every prompt
        seq = list(prompt) + list(toks)
        padded = seq + [0] * (total - len(seq))
        nxt, best = (np.asarray(a, np.float64) for a in ref.forward(self.reference_params(), padded, own))
        at = slice(len(prompt) - 1, len(prompt) - 1 + len(toks))
        self.decoded.append((padded, at, [float(x) for x in lps]))
        self._decode_scores.append((np.abs(np.asarray(lps, np.float64) - nxt[at]), best[at] - nxt[at]))

    def _decode_check(self) -> dict:
        """(v): the prompts scored so far, pooled, under the reference's limits."""
        out = decode_stats(self._ref, *(np.concatenate(a) for a in zip(*self._decode_scores)))
        self.decode_log.append(out)
        self.setup_log += [(f"decode_check.{k}", round(out[k], 6)) for k in ("median", "p90", "max", "margin")]
        return out

    def _background(self) -> list:
        """(v): the background rows' prompts, shortest first."""
        rng = np.random.default_rng(self.ctx.seed ^ 0xBAC6)
        lo, hi = (int(x) for x in self.geo["check_background_context"])
        lens = np.sort(rng.integers(lo, hi + 1, size=int(self.geo["check_background_rows"])))
        return [self._tokens(rng, int(n)) for n in lens]

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """``engine_state``'s pairs (bound, unbound), each prompt decoding
        ``check_decode_tokens`` tokens among ``check_background_rows`` other
        running rows; see the module's docstring."""
        eng, context = self.engine, self._check_context()
        page = self.geo["page_size"]
        want = len(context) // page * page
        n_dec = max(max_tokens, int(self.geo["check_decode_tokens"]))
        names = ("state_snapshot_hits_total", "state_snapshot_misses_total", "state_snapshot_captures_total")
        if not self._back:
            self._back = [eng.add_request(p, self._sampling(self.max_model_len)) for p in self._background()]
            started: set = set()
            while len(started) < len(self._back):  # until the background rows decode
                started.update(o.request_id for o in eng.step())
        back = self._back
        outs: list = [None] * len(prompts)
        live = len(back) + 2  # the fewest sequences running in a compared decode step
        for i in range(0, len(prompts), 2):
            pair = [context + list(prompts[i])] + [list(p) for p in prompts[i + 1:i + 2]]
            eng._refresh_gauges()
            before = [getattr(eng.stats, n) for n in names]
            for p in pair:
                eng.add_request(p, self._sampling(n_dec, logprobs=True))
            reqs = list(eng.scheduler.waiting)
            retained, slot = set(eng._swa_sections._entries), None
            while not all(len(r.output_token_ids) >= n_dec for r in reqs) and eng.has_work():
                if all(r.output_token_ids for r in reqs):  # the step to come decodes what is left of the pair
                    live = min(live, len(eng.scheduler.running))
                eng.step()
                slot = reqs[0].swa_block_ids[0] if reqs[0].swa_block_ids else slot
            while any(r.swa_block_ids for r in reqs) and eng.has_work():  # until their last step is committed
                eng.step()
            eng._refresh_gauges()
            hits, misses, captures = (getattr(eng.stats, n) - b for n, b in zip(names, before))
            cached = reqs[0].num_cached_tokens
            self.check_log.append((cached, hits, misses, captures))
            self.bound[tuple(int(t) for t in prompts[i])] = {"context": context}
            self._bound_served += 1
            for j, r in enumerate(reqs):
                toks, lps = list(r.output_token_ids), list(r.output_logprobs)
                if len(toks) == n_dec == len(lps):
                    self._decode_score(prompts[i + j], toks, lps)
                    outs[i + j] = (toks[:max_tokens], lps[:max_tokens])
                else:
                    outs[i + j] = ([], [])
            if self._bound_served == 2 and not (misses == 1 and hits == 0 and captures >= 1):
                outs[i] = ([], [])
            if self._bound_served >= 3 and not (hits == 1 and cached >= want):
                outs[i] = ([], [])
            seen = [("slot", pair[0] + list(reqs[0].output_token_ids)[:-1], slot)]
            seen += [("snapshot", context[:want], e.pages[0]) for k, e in eng._swa_sections._entries.items()
                     if k not in retained and e.shared and e.n_pre * page == want]
            new = [(what, toks, np.asarray(eng.runner.kv_swa.ssm[0, at])) for what, toks, at in seen if at is not None]
            self.states_seen += new
            if not all(e["ok"] for e in self.state_errors(new)) or (self._bound_served == 2 and len(new) < 2):
                outs[i] = ([], [])
        self.live_rows.append(live)
        self.setup_log.append(("decode_check.live_rows", live))
        # (the pair's first may finish a few steps before its second: one row fewer, the same step program)
        held = bool(self._decode_scores) and self._decode_check()["ok"] and live > len(back)
        return outs if held or not self.withhold else [([], [])] * len(outs)

    def release_background(self) -> None:
        """(v): the comparison's background rows aborted, the engine drained."""
        eng = self.engine
        for rid in self._back:
            eng.abort_request(rid)
        self._back = []
        while eng.has_work():
            eng.step()

    def start(self, record_steps: bool) -> None:
        self.release_background()
        super().start(record_steps)

    def state_errors(self, seen: list | None = None, conf: dict | None = None, params: dict | None = None) -> list:
        """``engine_state``'s, its lines copied for ONE of them: the shape every
        state's tokens are padded to has room for this topology's longer
        decode (there it is ``correctness.DECODE_TOKENS``, a constant of the
        harness that a topology does not rewrite)."""
        ref = self._ref
        sound = conf is None and params is None
        own = self.ctx.config["rehearse"]["published"] if self.ctx.rehearse else self.ctx.config
        conf, params = conf or own, params or self.reference_params()
        n_ctx = int(self.geo["check_context_tokens"])
        total = n_ctx + correctness.PROMPT_MAX + max(correctness.DECODE_TOKENS, int(self.geo["check_decode_tokens"]))
        out = []
        for what, toks, state in self.states_seen if seen is None else seen:
            want = ref.first_mixer_state(params, toks + [0] * (total - len(toks)), len(toks), conf, context_len=n_ctx)
            err = ref.state_error(state, want)
            err.update(what=what, tokens=len(toks), ok=bool(
                err["head_median"] <= ref.STATE_HEAD_MEDIAN_RTOL and err["head_max"] <= ref.STATE_HEAD_MAX_RTOL))
            out.append(err)
        if sound:
            self.state_log += out
            self.setup_log += [(f"state_check.{e['what']}.{k}", round(e[k], 6)) for e in out
                               for k in ("head_median", "head_max")]
        return out


def start(ctx) -> System:
    return System(ctx)
