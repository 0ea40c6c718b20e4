"""Topology ``engine_hybrid``: ``engine`` for a configuration that is ONE
RANK's share of an expert-parallel deployment, with window and full attention
over two KV pools.

Why this file exists. ``topologies/engine.py`` (not this PR's to edit) maps
neither ``layer_types``, ``sliding_window``, ``num_shared_experts``, the held
share of the experts nor the sliding-window ring, and its comparison sees
prompts of 64-256 tokens served alone: under two windows of context, no ring
ever wraps, and no prefix hit. This file

* builds the ``EngineConfig`` from the configuration file: the published keys
  through ``engine.model_overrides``, plus ``layer_types`` cut to the depth,
  ``sliding_window``, the shared expert, the router's published width
  (``published.num_experts``) with the file's ``num_experts`` as the experts
  HELD, from id ``deployment.rank`` x held, and ``swa_ring`` from the file's
  ``engine`` block;
* serves the comparison that decides ``correct`` (``correctness.py``,
  unedited) so that it sees, in every run,
    (i)   rows whose context is several windows long and longer than one ring:
          every second prompt is served BEHIND one seeded context of
          ``engine.check_context_tokens`` tokens (told to the reference in
          ``params["bound"]``, which prepends it on its side);
    (ii)  a HYBRID PREFIX HIT: the second bound prompt finds the context's full
          pages in the main pool and no sliding section (a miss: it prefills
          the span and leaves the section behind), the third and fourth take
          the hit: full pages + the retained section seeding a fresh ring.
          A run in which they do not is not ``correct`` (their outputs are
          withheld, so the comparison is incomplete);
    (iii) prompts whose prefill is split over steps that carry other requests:
          each bound prompt is in the system together with the unbound prompt
          drawn after it, under the cell's chunk budget.
  So of the 128 compared tokens 64 are decoded over ``check_context_tokens`` +
  64..272 cached tokens (32 of them behind a hybrid hit) and 64 over 64..272,
  all through both pools.

Everything else is ``topologies/engine.py``.
"""

from __future__ import annotations

import numpy as np

from perfbench.topologies import engine


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, and what this architecture adds to it."""
    out = engine.model_overrides(conf)
    depth = conf["num_hidden_layers"]
    held = conf["num_experts"]
    out.update(
        rope_theta=float(conf["rope_parameters"]["rope_theta"]),
        layer_types=tuple(conf["layer_types"][:depth]),
        sliding_window=conf["sliding_window"],
        num_experts=conf["published"]["num_experts"],
        held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
        shared_expert_intermediate_size=conf["num_shared_experts"] * conf["moe_intermediate_size"],
        router_scoring=conf["scoring_func"],
    )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """The EngineConfig the file describes (``engine.engine_config`` with
    this file's model overrides and the ring)."""
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
                          swa_ring=bool(geo["swa_ring"])),
        scheduler=SchedulerConfig(**{k: geo[k] for k in ("max_num_seqs", "max_num_batched_tokens")}),
        parallel=ParallelConfig(),
        seed=seed % (2**31 - 1),
    )


def reference_params(runner_params: dict, model_cfg) -> dict:
    """``engine.reference_params`` plus the dense layers' fused gate|up."""
    out = engine.reference_params(runner_params, model_cfg)
    for group in ("layers", "dense_layers"):
        d = dict(out.get(group) or {})
        if "w_gu" in d:
            w = d.pop("w_gu")
            f = w.shape[-1] // 2
            d["w_gate"], d["w_up"] = w[..., :f], w[..., f:]
            out[group] = d
    return out


class System(engine.System):
    def __init__(self, ctx) -> None:
        engine.engine_config, stock = engine_config, engine.engine_config
        try:  # engine.System builds its EngineConfig through the module's function
            super().__init__(ctx)
        finally:
            engine.engine_config = stock
        self.bound: dict = {}  # prompt -> {"context"}
        self.check_log: list = []  # per bound prompt: (cached tokens, hits before, hits after)
        self._bound_served = 0

    def _check_context(self) -> list:
        n = int(self.geo["check_context_tokens"])
        rng = np.random.default_rng(self.ctx.seed ^ 0xC0DE)
        return rng.integers(0, self.vocab_size, size=n).tolist()

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """Pairs (bound, unbound) in the system together, one pair after the
        other; see the module's docstring for what each pair shows."""
        eng, context = self.engine, self._check_context()
        page = self.geo["page_size"]
        outs: list = [None] * len(prompts)
        for i in range(0, len(prompts), 2):
            pair = [context + list(prompts[i])] + [list(p) for p in prompts[i + 1:i + 2]]
            before = eng.stats.swa_section_hits_total
            for p in pair:
                eng.add_request(p, self._sampling(max_tokens, logprobs=True))
            reqs = list(eng.scheduler.waiting)
            while eng.has_work():
                eng.step()
            for j, r in enumerate(reqs):
                outs[i + j] = (list(r.output_token_ids), list(r.output_logprobs))
            self.bound[tuple(int(t) for t in prompts[i])] = {"context": context}
            hit = eng.stats.swa_section_hits_total - before
            self.check_log.append((reqs[0].num_cached_tokens, hit))
            self._bound_served += 1
            # The third bound prompt onward has to be served from a hybrid hit
            # of the whole context's full pages (the ring on; without it the
            # plain prefix cache has to give them).
            want = len(context) // page * page
            if self._bound_served >= 3 and reqs[0].num_cached_tokens < want:
                outs[i] = ([], [])
        return outs

    def reference_params(self) -> dict:
        return dict(reference_params(self.engine.runner.params, self.model_cfg), bound=self.bound)


def start(ctx) -> System:
    return System(ctx)
