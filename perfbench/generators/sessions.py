"""Closed loop of multi-turn sessions over shared prefixes: ``clients``
sessions are in flight; each picks a prefix group, runs its turns with the
history carried, and is replaced when it ends. A client sends its next turn
only when the last one has finished.

The scripts (group, turns, question and answer lengths) are a FIXED list, the
same for every seed and in the same order (the mix's ``base_seed``): the
first ``clients`` of them are the sessions in flight at the start, the rest
replace sessions that end. The seed draws only the token ids (the shared
prefixes among them) and the weights, so every seed's window holds the same
work (open_loop.py says what another order costs).

Mix parameters: ``groups``, ``prefix_tokens``, ``group_zipf_s``, ``turns``,
``question``, ``answer`` (distributions), ``context_cap``, ``scripts``,
``warm_seconds``. Cell parameter: ``clients``.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from perfbench import stats
from perfbench.generators._drive import cancel_all, drive


def scripts(mix: dict) -> list:
    """[(group, [(question tokens, answer tokens), ...])]: the fixed list."""
    n = int(mix["scripts"])
    rng = np.random.default_rng(int(mix.get("base_seed", 0)))
    counts = stats.zipf_counts(n, int(mix["groups"]), float(mix.get("group_zipf_s", 1.0)))
    groups = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    turns = rng.permutation(stats.size_grid(mix["turns"], n))
    total = int(turns.sum())
    q = rng.permutation(stats.size_grid(mix["question"], total))
    a = rng.permutation(stats.size_grid(mix["answer"], total))
    out, k = [], 0
    for i in range(n):
        t = int(turns[i])
        out.append((int(groups[i]), [(int(q[k + j]), int(a[k + j])) for j in range(t)]))
        k += t
    return out


class Generator:
    def __init__(self, mix: dict, cell: dict, seed: int, seconds: float, system) -> None:
        self.mix, self.clients, self.seconds = mix, int(cell["clients"]), seconds
        self.warm_seconds = float(mix.get("warm_seconds", 0))
        self.rng = np.random.default_rng(seed ^ 0x70CE25)
        self.vocab = system.vocab_size
        self.cap = min(int(mix["context_cap"]), system.max_model_len - 1)
        self.prefixes = [
            self.rng.integers(0, self.vocab, size=int(mix["prefix_tokens"])).tolist()
            for _ in range(int(mix["groups"]))
        ]
        self.scripts = scripts(mix)
        self.next_script = 0

    def _take(self):
        s = self.scripts[self.next_script % len(self.scripts)]
        self.next_script += 1
        return s

    async def _client(self, system, rec) -> None:
        while True:
            group, turns = self._take()
            context = list(self.prefixes[group])
            for q_len, a_len in turns:
                if len(context) + q_len + a_len > self.cap:
                    break
                context += self.rng.integers(0, self.vocab, size=q_len).tolist()
                r = rec.new(time.monotonic(), len(context), a_len, True)
                answer: list = []
                await drive(system, rec, r, context, a_len, sink=answer)
                if r.error:
                    break
                context += answer

    async def run(self, system, rec, tail=None) -> None:
        start = time.monotonic()
        rec.t0 = start + self.warm_seconds
        rec.t1 = rec.t0 + self.seconds
        tasks = [asyncio.create_task(self._client(system, rec)) for _ in range(self.clients)]
        await asyncio.sleep(max(0.0, rec.t1 - time.monotonic()))
        if tail is not None:  # --trace 2: the clients simply keep going
            await tail(None)
        await cancel_all(tasks)
