"""Topology ``engine_state``: ``engine`` for a configuration that is ONE
RANK's share of an expert-parallel deployment of a HYBRID STATE-SPACE model:
Mamba-2 mixers whose per-sequence state lives in a state pool beside the
paged KV pool of the attention layers.

Why this file exists. ``topologies/engine.py`` (not this PR's to edit) maps
neither the ``mamba_*`` keys, the multipliers, ``shared_intermediate_size``
nor ``num_local_experts`` and the held share; its shape ladder sends prompts
of exactly T tokens, which this engine cuts at their last full page (a
recurrent state can be retained only AT a boundary: docs/architecture/
kv-cache.md), so the T buckets it means to warm would be missed; and its
comparison sees prompts of 64-256 tokens served alone: one prefill chunk, no
state carried from a chunk to the next, no snapshot taken or used. This file

* builds the ``EngineConfig`` from the configuration file: the published keys
  through ``engine.model_overrides``, plus ``layer_types`` cut to the depth,
  the mixer's sizes, the three multipliers, the attention scale, no rope, the
  shared GLU, the router's published width (``published.num_local_experts``)
  with the file's ``num_local_experts`` as the experts HELD, from id
  ``deployment.rank`` x held;
* walks the shape ladder with prompts of T + 1 tokens, whose first chunk is
  the T tokens up to their last full page;
* serves the comparison that decides ``correct`` (``correctness.py``,
  unedited) so that it sees, in every run,
    (i)   a state carried through several prefill chunks and many scan rows:
          every second prompt is served BEHIND one seeded context of
          ``engine.check_context_tokens`` tokens (told to the reference in
          ``params["bound"]``, which prepends it on its side);
    (ii)  a SNAPSHOT MISS and HITS: the second bound prompt finds the
          context's full pages in the main pool and no snapshot at their end
          (a miss: it prefills the span, its chunk ENDS at the boundary and
          the engine copies its slot into a snapshot there), the third and
          fourth take the hit: the attention layer's full pages + the state
          AT THE SAME BOUNDARY seeding a fresh slot. A run in which the
          second is no miss that leaves a snapshot, or the third or fourth no
          hit, is not ``correct`` (their outputs are withheld, so the
          comparison is incomplete);
    (iii) prefill chunks that share their steps with other sequences' decode
          rows: each bound prompt is in the system together with the unbound
          prompt drawn after it, under the cell's chunk budget.
  So of the 128 compared tokens 64 are decoded from a state that ran over
  ``check_context_tokens`` + 64..272 tokens (32 of them from a snapshot) and
  64 over 64..272.
    (iv)  THE STATE ITSELF, held to the reference's. Log-probs of seeded
          weights cannot tell a bfloat16 SSM state or a snapshot one page
          stale from a sound run (PERF.md section 6, PR 37): ten layers of
          bfloat16 arithmetic lie between a state and a logit. So the
          FIRST mixer's SSM state is read out of the state pool and held to
          ``reference.first_mixer_state`` by ``reference.state_error`` (per
          head, relative) under the reference's ``STATE_HEAD_*`` limits:
          each bound prompt's slot after its last computed token (the
          prefill scan in chunks, 15 decode updates by the Pallas kernel,
          and for the third and fourth a slot SEEDED from the snapshot),
          and the snapshot the second bound prompt left behind (the state
          AT the boundary the pages end at). A state outside the limits
          withholds that prompt's outputs: the comparison is incomplete.

Everything else is ``topologies/engine.py``.
"""

from __future__ import annotations

import importlib

import numpy as np

from perfbench import correctness
from perfbench.topologies import engine


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the file: what ``engine.model_overrides``
    maps, and what this architecture adds to it."""
    out = engine.model_overrides(conf)
    depth, held = conf["num_hidden_layers"], conf["num_local_experts"]
    out.update(
        layer_types=tuple(conf["layer_types"][:depth]),
        rope_layer_types=() if conf["position_embedding_type"] == "nope" else None,
        attention_multiplier=conf["attention_multiplier"],
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=conf["residual_multiplier"],
        logits_scaling=float(conf["logits_scaling"]),
        num_experts=conf["published"]["num_local_experts"],
        held_experts=held,
        held_experts_first=conf["deployment"]["rank"] * held,
        moe_intermediate_size=conf["intermediate_size"],
        shared_expert_intermediate_size=conf["shared_intermediate_size"],
        **{k: conf[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv")},
    )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """The EngineConfig the file describes (``engine.engine_config`` with
    this file's model overrides)."""
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
        cache=CacheConfig(page_size=geo["page_size"], num_blocks=geo["num_pages"], dtype=geo["kv_dtype"]),
        scheduler=SchedulerConfig(**{k: geo[k] for k in ("max_num_seqs", "max_num_batched_tokens")}),
        parallel=ParallelConfig(),
        seed=seed % (2**31 - 1),
    )


class System(engine.System):
    def __init__(self, ctx) -> None:
        engine.engine_config, stock = engine_config, engine.engine_config
        try:  # engine.System builds its EngineConfig through the module's function
            super().__init__(ctx)
        finally:
            engine.engine_config = stock
        self.bound: dict = {}  # prompt -> {"context"}
        self.check_log: list = []  # per bound prompt: (cached tokens, hits, misses, captures)
        self._bound_served = 0
        # The first mixer's SSM states read out of the pool, each with the
        # tokens it ran over: (what, tokens, state [heads, d_head, d_state]).
        self.states_seen: list = []
        self.state_log: list = []  # per state: {"what", "head_median", "head_max", "ok"}

    def _ladder(self) -> list:
        """A prompt of T + 1 tokens: its first chunk ends at its last full
        page, T tokens in (T a multiple of the page), the bucket meant."""
        return [(rows, n + 1 if n else 0) for rows, n in super()._ladder()]

    def _check_context(self) -> list:
        n = int(self.geo["check_context_tokens"])
        rng = np.random.default_rng(self.ctx.seed ^ 0xC0DE)
        return rng.integers(0, self.vocab_size, size=n).tolist()

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """Pairs (bound, unbound) in the system together, one pair after the
        other; see the module's docstring for what each pair shows."""
        eng, context = self.engine, self._check_context()
        page = self.geo["page_size"]
        want = len(context) // page * page
        names = ("state_snapshot_hits_total", "state_snapshot_misses_total", "state_snapshot_captures_total")
        outs: list = [None] * len(prompts)
        for i in range(0, len(prompts), 2):
            pair = [context + list(prompts[i])] + [list(p) for p in prompts[i + 1:i + 2]]
            eng._refresh_gauges()
            before = [getattr(eng.stats, n) for n in names]
            for p in pair:
                eng.add_request(p, self._sampling(max_tokens, logprobs=True))
            reqs = list(eng.scheduler.waiting)
            retained, slot = set(eng._swa_sections._entries), None
            while eng.has_work():
                eng.step()
                slot = reqs[0].swa_block_ids[0] if reqs[0].swa_block_ids else slot
            for j, r in enumerate(reqs):
                outs[i + j] = (list(r.output_token_ids), list(r.output_logprobs))
            self.bound[tuple(int(t) for t in prompts[i])] = {"context": context}
            hits, misses, captures = (getattr(eng.stats, n) - b for n, b in zip(names, before))
            cached = reqs[0].num_cached_tokens
            self.check_log.append((cached, hits, misses, captures))
            self._bound_served += 1
            # The second bound prompt has to be a snapshot MISS that leaves the
            # snapshot behind, the third onward HITS of the whole context.
            if self._bound_served == 2 and not (misses == 1 and hits == 0 and captures >= 1):
                outs[i] = ([], [])
            if self._bound_served >= 3 and not (hits == 1 and cached >= want):
                outs[i] = ([], [])
            # (iv): the slot as the last computed token left it (the last
            # emitted token is never fed), and what this pair retained AT the
            # context's end (``shared``: the end of a run the main pool offered,
            # not a prompt's own end). A freed slot keeps its state until it is
            # reused, and nothing else runs here.
            seen = [("slot", pair[0] + list(reqs[0].output_token_ids)[:-1], slot)]
            seen += [("snapshot", context[:want], e.pages[0]) for k, e in eng._swa_sections._entries.items()
                     if k not in retained and e.shared and e.n_pre * page == want]
            new = [(what, toks, np.asarray(eng.runner.kv_swa.ssm[0, at])) for what, toks, at in seen if at is not None]
            self.states_seen += new
            if not all(e["ok"] for e in self.state_errors(new)) or (self._bound_served == 2 and len(new) < 2):
                outs[i] = ([], [])
        return outs

    def state_errors(self, seen: list | None = None, conf: dict | None = None, params: dict | None = None) -> list:
        """``states_seen`` (or the states given) against the reference's
        ``first_mixer_state``, under its limits; the sound comparison's
        entries go to ``state_log``. ``conf`` / ``params``: a wrong reference
        (``perfbench/tolerance_probe_state.py``)."""
        ref = importlib.import_module(f"perfbench.references.{self.ctx.config['reference']}")
        sound = conf is None and params is None
        own = self.ctx.config["rehearse"]["published"] if self.ctx.rehearse else self.ctx.config
        conf, params = conf or own, params or self.reference_params()
        n_ctx = int(self.geo["check_context_tokens"])
        total = n_ctx + correctness.PROMPT_MAX + correctness.DECODE_TOKENS  # one shape for every state
        out = []
        for what, toks, state in self.states_seen if seen is None else seen:
            want = ref.first_mixer_state(params, toks + [0] * (total - len(toks)), len(toks), conf, context_len=n_ctx)
            err = ref.state_error(state, want)
            err.update(what=what, tokens=len(toks), ok=bool(
                err["head_median"] <= ref.STATE_HEAD_MEDIAN_RTOL and err["head_max"] <= ref.STATE_HEAD_MAX_RTOL))
            out.append(err)
        if sound:
            self.state_log += out
            # The one list of a topology's that the harness prints and keeps
            # (the detail file): the comparison's numbers beside its seconds.
            self.setup_log += [(f"state_check.{e['what']}.{k}", round(e[k], 6)) for e in out
                               for k in ("head_median", "head_max")]
        return out

    def reference_params(self) -> dict:
        return dict(engine.reference_params(self.engine.runner.params, self.model_cfg), bound=self.bound)


def start(ctx) -> System:
    return System(ctx)
