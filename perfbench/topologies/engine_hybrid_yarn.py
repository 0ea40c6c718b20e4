"""Topology ``engine_hybrid_yarn``: ``engine_hybrid`` for a configuration whose
published file is a ``mellum`` one (Mellum2): window and full attention three
to one over two KV pools with a RoPE table PER LAYER TYPE
(``rope_parameters`` keyed by ``layer_types``' values: YaRN on the full
layers, the plain table on the sliding ones), every layer's FFN the held share
of a softmax router's experts WITHOUT a shared expert, ONE RANK of a four-chip
host, served under long-document sessions.

Why this file exists. ``topologies/engine_hybrid.py`` (not this PR's to edit)
reads K-EXAONE's keys (a flat ``rope_parameters.rope_theta``,
``num_shared_experts``, ``scoring_func``), sizes the retained sections itself
and compares 16 tokens a prompt with two sequences in the system behind a
context of 12 windows of 128. This file

* builds the ``EngineConfig`` from THIS configuration's published keys:
  ``engine.model_overrides``' mapping, then ``layer_types`` cut to the depth,
  ``sliding_window``, the nested ``rope_parameters`` as they stand (the
  model's own table is the sliding layers'), the router's published width
  (``published.num_experts``) with the file's ``num_experts`` as the experts
  HELD from id ``deployment.rank`` x held, the ring, and
  ``engine.swa_sections`` retained sections (a section is 65 pages x 21 layers
  here: the file counts them against memory);
* serves the comparison that decides ``correct`` (``correctness.py``,
  unedited) by ``engine_hybrid``'s protocol one step further in size:
    (i)   every second of the eight prompts is served BEHIND one seeded
          context of ``engine.check_context_tokens`` = 4,096 tokens (4 windows
          of 1,024, 3.5 rings of 73 pages, 32 chunks of 128; half of YaRN's
          original 8,192, so the blended frequencies have turned), told to
          the reference in ``params["bound"]``;
    (ii)  the second bound prompt is a section MISS that leaves the context's
          section behind (65 pages x 21 layers), the third and fourth are HITS
          (full pages + the section seeding a fresh ring); a run in which they
          are not withholds their outputs;
    (iii) chunks of at most 128 tokens, SMALLER than the window, share their
          steps with decode rows;
    (iv)  each compared prompt decodes ``engine.check_decode_tokens`` = 64
          tokens among ``engine.check_background_rows`` other running rows
          (``max_num_seqs`` less the pair; short contexts, admitted once and
          kept through the last pair: ``engine_mixer``'s (v), whose words on
          why the rows are short and stay hold here), so every compared decode
          step is the window's own step program; every decoded token's
          log-prob is held to the reference's full forward pass under its four
          limits, POOLED over the prompts so far, and the fewest sequences
          running in a compared decode step is logged
          (``decode_check.live_rows``);
    (v)   LAYER 3's cached KEYS of each bound prompt (the first full layer:
          context, prompt and decoded tokens, the full pages of them) are read
          out of the main pool and held per token to the reference's
          ``first_full_layer_keys`` (``key_check.*`` in the set-up log): the
          one place where the full layers' table is compared without a
          softmax in between, behind three sliding layers whose rings have
          wrapped 3.5 times. A failure withholds the prompt's outputs.

Everything else is ``topologies/engine.py``.
"""

from __future__ import annotations

import numpy as np

from perfbench import correctness
from perfbench.topologies import engine, engine_hybrid, engine_mixer


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, and what this architecture adds to it."""
    out = engine.model_overrides(conf)
    depth, held = conf["num_hidden_layers"], conf["num_experts"]
    out.update(
        layer_types=tuple(conf["layer_types"][:depth]),
        sliding_window=conf["sliding_window"],
        rope_parameters=conf["rope_parameters"],
        rope_theta=float(conf["rope_parameters"]["sliding_attention"]["rope_theta"]),
        num_experts=conf["published"]["num_experts"],
        held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
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
                          swa_ring=bool(geo["swa_ring"]), swa_sections=geo["swa_sections"]),
        scheduler=SchedulerConfig(**{k: geo[k] for k in ("max_num_seqs", "max_num_batched_tokens")}),
        parallel=ParallelConfig(),
        seed=seed % (2**31 - 1),
    )


class System(engine_hybrid.System):
    def __init__(self, ctx) -> None:
        # engine_hybrid.System builds its EngineConfig through its module's function.
        engine_hybrid.engine_config, stock = engine_config, engine_hybrid.engine_config
        try:
            super().__init__(ctx)
        finally:
            engine_hybrid.engine_config = stock
        self.decoded: list = []  # (iv), per compared prompt: (padded sequence, positions, system log-probs)
        self._decode_scores: list = []  # (iv), per compared prompt: (|system - reference|, the reference's margins)
        self.decode_log: list = []  # (iv), per call: the four numbers over the prompts so far, and "ok"
        self.live_rows: list = []  # (iv), per call: the fewest sequences running in a compared decode step
        self._back: list = []  # (iv): the background rows' request ids, until the system serves
        self.keys_seen: list = []  # (v), per bound prompt: (its sequence, the keys its full pages hold)
        self.key_log: list = []  # (v), the sound comparison's entries
        # A probe that wants a seed's readings whatever they are sets this False
        # (perfbench/tolerance_probe_yarn.py); a run never does.
        self.withhold = True

    # (iv) is ``engine_mixer``'s (v), method for method: they read ``ctx``,
    # ``geo``, the engine and the lists above, which this class has too.
    _ref = engine_mixer.System._ref
    _decode_score = engine_mixer.System._decode_score
    _decode_check = engine_mixer.System._decode_check
    _background = engine_mixer.System._background
    release_background = engine_mixer.System.release_background

    @property
    def _published(self) -> dict:
        return self.ctx.config["rehearse"]["published"] if self.ctx.rehearse else self.ctx.config

    def start(self, record_steps: bool) -> None:
        self.release_background()
        super().start(record_steps)

    # -- (v): layer 3's keys out of the main pool -------------------------- #

    def _cached_keys(self, toks: list):
        """The first full layer's keys ``[tokens, Nk, D]`` in the full pages
        the main pool has cached for ``toks`` (a finished request's pages keep
        their rows until they are allocated again, and nothing has run since).
        The main pool's plane 0 IS layer 3: the sliding layers lie in the ring
        pool."""
        eng = self.engine
        pages = eng.allocator.lookup_cached_prefix(toks)
        if not pages:
            return None
        d = self.model_cfg.head_dim  # a row is K | V
        # (gather the pages, THEN cut the rows: one indexing expression makes the chip's compiler re-lay the pool)
        rows = np.asarray(eng.runner.kv_cache[0, np.asarray(pages)][..., :d], np.float32)  # [pages, Nk, page, D]
        return rows.transpose(0, 2, 1, 3).reshape(-1, rows.shape[1], d)

    def key_errors(self, seen: list | None = None, conf: dict | None = None, params: dict | None = None) -> list:
        """``keys_seen`` (or the ones given) against the reference's
        ``first_full_layer_keys``, under its limits; ``conf`` / ``params``: a
        wrong reference (``perfbench/tolerance_probe_yarn.py``)."""
        ref = self._ref
        sound = conf is None and params is None
        conf, params = conf or self._published, params or self.reference_params()
        # one shape for every sequence: the reference's layers are compiled once
        total = int(self.geo["check_context_tokens"]) + correctness.PROMPT_MAX + max(
            correctness.DECODE_TOKENS, int(self.geo["check_decode_tokens"]))
        out = []
        for toks, keys in self.keys_seen if seen is None else seen:
            want = ref.first_full_layer_keys(params, toks + [0] * (total - len(toks)), conf)
            err = ref.key_error(keys, np.asarray(want)[: len(keys)])
            err.update(tokens=len(keys), ok=bool(
                err["token_median"] <= ref.KEY_TOKEN_MEDIAN_RTOL and err["far_share"] <= ref.KEY_FAR_SHARE_MAX))
            out.append(err)
        if sound:
            self.key_log += out
            self.setup_log += [(f"key_check.{k}", round(e[k], 6)) for e in out for k in ("token_median", "far_share")]
        return out

    # -- the comparison ---------------------------------------------------- #

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """Pairs (bound, unbound) in the system together, one pair after the
        other, each prompt decoding ``check_decode_tokens`` tokens among the
        background rows; see the module's docstring."""
        eng, context = self.engine, self._check_context()
        page = self.geo["page_size"]
        want = len(context) // page * page
        n_dec = max(max_tokens, int(self.geo["check_decode_tokens"]))
        names = ("swa_section_hits_total", "swa_section_misses_total", "swa_section_captures")
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
            while not all(r.is_finished for r in reqs) and eng.has_work():
                if all(r.output_token_ids for r in reqs):  # the step to come decodes what is left of the pair
                    live = min(live, len(eng.scheduler.running))
                eng.step()
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
            seq = pair[0] + list(reqs[0].output_token_ids)[:-1]  # the last token sampled is never fed
            keys = self._cached_keys(seq)
            new = [(seq, keys)] if keys is not None else []
            self.keys_seen += new
            sound = bool(new) and all(e["ok"] for e in self.key_errors(new))
            if self._bound_served == 2:
                sound &= misses == 1 and hits == 0 and captures >= 1
            if self._bound_served >= 3:
                sound &= hits == 1 and cached >= want
            if not sound and self.withhold:
                outs[i] = ([], [])
        self.live_rows.append(live)
        self.setup_log.append(("decode_check.live_rows", live))
        # (the pair's first may finish a few steps before its second: one row fewer, the same step program)
        held = bool(self._decode_scores) and self._decode_check()["ok"] and live > len(back)
        return outs if held or not self.withhold else [([], [])] * len(outs)


def start(ctx) -> System:
    return System(ctx)
