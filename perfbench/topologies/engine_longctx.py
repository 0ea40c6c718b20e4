"""Topology ``engine_longctx``: ``engine`` with the comparison that decides
``correct`` made partly BEHIND a long seeded context, and with the system's
selected sets handed to the reference.

Why this file exists. ``perfbench/correctness.py`` draws its eight prompts at
64-256 tokens. A model with learned sparse attention (``sa_config.topk``
2,048) selects every cached token at those lengths, so the comparison would
never see a selection: an indexer that chose wrongly, or was ignored, would
read ``correct``. This topology serves EVERY SECOND prompt of a call (the
first and third of each batch of four, in the system together) behind ONE
seeded context of
``engine.check_context_tokens`` tokens (from the run's seed), and tells the
reference so in the parameter tree: ``params["bound"][prompt]`` holds the
context, which ``references/gqa_dsa_moe.py`` prepends on its side, and the
system's ``selection`` and the keys it ``cached``. ``correctness.py``,
unedited, then compares 64 tokens decoded over ``check_context_tokens`` +
64..272 cached tokens and 64 decoded over 64..272 (served one request at a
time); the reference's file says what each half is for. The second
batch finds the context in the prefix cache, so cached indexer keys are
compared too.

The system's selection. The serving path does not hand out the sets a step
selected (that would be a new output of every step program). What it leaves
behind is the plane of indexer keys it cached for the sequence: when a bound
request finishes, its pages of the plane are copied aside, and ``selection``
runs the program's OWN scoring and top-k (``ops/sparse_attention.py::
index_scores`` and ``select_topk``, what the step calls) over them for
whatever queries it is given. The reference gives its own and holds the
result against two sets of its making: an exact float32 top-k over the SAME
cached keys (what differs is the program's arithmetic alone: an approximate
top-k, scores in a lower precision), and the sets from its own keys (what
differs is also what the serving path cached: the served dtype's rounding,
or keys written to the wrong slot).

Everything else is ``topologies/engine.py``. A later ``benchmark`` PR should
let a configuration file state the comparison's lengths and give
``correctness.py`` a number for the selected sets (PERF.md section 7); this
file then goes.
"""

from __future__ import annotations

import numpy as np

from perfbench.topologies import engine

ROW_GRANULE = 256  # selections are computed for a multiple of this many rows: two shapes, not eight


class System(engine.System):
    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        self.bound: dict = {}  # prompt -> {"context", "selection", "cached": (keys, tokens)}

    def _check_context(self) -> list:
        n = int(self.geo["check_context_tokens"])
        rng = np.random.default_rng(self.ctx.seed ^ 0xC0DE)
        return rng.integers(0, self.vocab_size, size=n).tolist()

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """The even-numbered prompts behind the context, in the system
        together; then the others as drawn, ONE AT A TIME (a prompt whose
        prefill shares a step with another request's decode row reads 3-5x
        the log-prob noise on the chip, in every configuration: PERF.md
        section 6; the unbound half is the comparison's quiet yardstick, the
        bound half runs as the traffic does)."""
        import jax.numpy as jnp

        eng, context, page = self.engine, self._check_context(), self.geo["page_size"]
        reqs: list = [None] * len(prompts)
        cached = {}  # request id -> (prompt, tokens whose keys the run caches)
        for i in range(0, len(prompts), 2):
            full = context + list(prompts[i])
            rid = eng.add_request(full, self._sampling(max_tokens, logprobs=True))  # the last token sampled is never fed: its key is never written
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
                    keys = eng.runner.kv_cache.index[:, ids]  # [L, pages of the sequence, page, Di]
                    self.bound[prompt] = {"context": context, "cached": (keys, n),
                                          "selection": self.selection(keys, n)}
        for i in range(1, len(prompts), 2):
            eng.add_request(list(prompts[i]), self._sampling(max_tokens, logprobs=True))
            reqs[i] = eng.scheduler.waiting[0]
            while eng.has_work():
                eng.step()
        return [(list(r.output_token_ids), list(r.output_logprobs)) for r in reqs]

    def selection(self, keys, n: int, score=None, pick=None):
        """``f(layer, iq, iw) -> ([rows, rows] bool, n)``: the sets the
        program's scoring and top-k select for index queries ``iq`` [T, J, Di]
        and head weights ``iw`` [T, J] (cast to the served dtype, as the step
        has them) over the ``n`` cached keys ``keys`` [L, pages, page, Di].
        ``score`` and ``pick`` stand in for the program's two functions (the
        tolerance probe's faulty systems)."""
        import jax
        import jax.numpy as jnp

        from llmd_tpu.ops import sparse_attention as sa

        score, pick = score or sa.index_scores, pick or sa.select_topk
        topk, page = self.model_cfg.indexer_topk, keys.shape[2]
        rows = -(-n // ROW_GRANULE) * ROW_GRANULE
        table = jnp.minimum(jnp.arange(-(-rows // page), dtype=jnp.int32), keys.shape[1] - 1)[None, :]
        kv_lens = jnp.minimum(jnp.arange(1, rows + 1, dtype=jnp.int32), n)

        @jax.jit
        def select(plane, iq, iw):
            fit = lambda a: jnp.pad(a[:rows], ((0, max(0, rows - a.shape[0])),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
            scores = score(fit(iq).astype(plane.dtype), fit(iw).astype(plane.dtype), plane, table,
                           jnp.zeros(rows, jnp.int32), kv_lens)
            return pick(scores, topk)[:, :rows]

        return lambda layer, iq, iw: (select(keys[layer], iq, iw), n)

    def reference_params(self) -> dict:
        return dict(super().reference_params(), bound=self.bound)


def start(ctx) -> System:
    return System(ctx)
