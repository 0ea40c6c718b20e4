"""Topology ``engine_longctx_latent``: ``engine_longctx`` for a configuration
whose cache is a LATENT pool beside its plane of indexer keys, served as ONE
RANK's share of an expert-parallel deployment (DeepSeek-V3.2).

What ``engine_longctx`` does, this does: every second prompt of the
comparison behind one seeded context of ``engine.check_context_tokens``
tokens, the system's selected sets and the indexer keys it cached handed to
the reference in ``params["bound"]``; its ``selection`` and ``_check_context``
serve unchanged. Two things are this file's:

* the ``EngineConfig``: ``topologies/engine.py`` (not this PR's to edit) maps
  neither the ``index_*`` keys nor the held share of the experts. The
  published keys go through ``engine.model_overrides``; the indexer comes from
  ``index_topk`` / ``index_n_heads`` / ``index_head_dim``, the router's width
  from ``published.n_routed_experts`` with the file's ``n_routed_experts`` as
  the experts HELD, from id ``deployment.rank`` x held;
* the LATENT READOUT: when a bound request finishes, its pages of the first
  layer's latent pool (``runner.kv_cache.kv[0][ids]``) are copied aside
  beside the indexer keys, and ``references/mla_dsa_moe_share.py`` holds
  every cached row to its own [RMSNorm(c), RoPE(k_r)] of that position. The
  flat latent write is new with this configuration, and a row that a write
  plan loses moves no log-prob enough to be seen (PR 44's key readout found
  twenty PRs of such rows in the flat KV write). For the readout the loop of
  ``engine_longctx.System.greedy_with_logprobs`` is repeated here with one
  more line: the page ids it needs live in that loop alone.

Everything else is ``topologies/engine.py``.
"""

from __future__ import annotations

from perfbench.topologies import engine, engine_longctx


stock_model_overrides, stock_engine_config = engine.model_overrides, engine.engine_config


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, the indexer, and the held share of the experts."""
    out = stock_model_overrides(conf)
    held = conf["n_routed_experts"]
    out.update(
        indexer_topk=conf["index_topk"], indexer_num_heads=conf["index_n_heads"],
        indexer_head_dim=conf["index_head_dim"],
        num_experts=conf["published"]["n_routed_experts"], held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
    )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """``engine.engine_config`` with this file's model overrides."""
    engine.model_overrides = model_overrides
    try:
        return stock_engine_config(conf, seed, rehearse)
    finally:
        engine.model_overrides = stock_model_overrides


class System(engine_longctx.System):
    def __init__(self, ctx) -> None:
        engine.engine_config = engine_config
        try:  # engine.System builds its EngineConfig through the module's function
            super().__init__(ctx)
        finally:
            engine.engine_config = stock_engine_config

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """``engine_longctx``'s: the even-numbered prompts behind the context,
        in the system together, then the others one at a time; a bound
        request's entry also holds the first layer's cached latent rows."""
        import jax.numpy as jnp

        eng, context, page = self.engine, self._check_context(), self.geo["page_size"]
        reqs: list = [None] * len(prompts)
        cached = {}  # request id -> (prompt, tokens whose rows the run caches)
        for i in range(0, len(prompts), 2):
            full = context + list(prompts[i])
            rid = eng.add_request(full, self._sampling(max_tokens, logprobs=True))  # the last token sampled is never fed
            cached[rid] = (tuple(int(t) for t in prompts[i]), len(full) + max_tokens - 1)
        reqs[0::2] = list(eng.scheduler.waiting)
        held: dict = {}  # request id -> its list of page ids (the scheduler extends it in place)
        while eng.has_work():
            for r in reqs[0::2]:
                if r.block_ids:
                    held[r.request_id] = r.block_ids
            eng.step()
            for r in reqs[0::2]:
                # Finished in this step: its pages are free but nothing has been
                # scheduled since, so they still hold the sequence.
                if r.request_id in held and r.is_finished:
                    prompt, n = cached[r.request_id]
                    ids = jnp.asarray(held.pop(r.request_id)[: -(-n // page)], jnp.int32)
                    pool = eng.runner.kv_cache
                    keys = pool.index[:, ids]  # [L, pages of the sequence, page, Di]
                    latents = pool.kv[0, ids].reshape(-1, pool.kv.shape[-1])  # [tokens, Dl]
                    self.bound[prompt] = {"context": context, "cached": (keys, n), "latents": latents,
                                          "selection": self.selection(keys, n)}
        for i in range(1, len(prompts), 2):
            eng.add_request(list(prompts[i]), self._sampling(max_tokens, logprobs=True))
            reqs[i] = eng.scheduler.waiting[0]
            while eng.has_work():
                eng.step()
        return [(list(r.output_token_ids), list(r.output_logprobs)) for r in reqs]


def start(ctx) -> System:
    return System(ctx)
