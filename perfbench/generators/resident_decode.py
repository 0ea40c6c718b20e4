"""Resident decode: ``clients`` sequences whose contexts are prefilled during
set-up and which then all decode through the whole window; they are cut at
its end. Measures the decode step over long caches and nothing else. A
sequence that reaches the model's length is sent again at once: its context
is still in the prefix cache, so it is decoding again after one short step.

Mix parameters: ``context`` (distribution), ``warm_seconds``.
Cell parameter: ``clients``.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from perfbench import stats
from perfbench.generators._drive import cancel_all, drive


class Generator:
    def __init__(self, mix: dict, cell: dict, seed: int, seconds: float, system) -> None:
        self.mix, self.seconds = mix, seconds
        self.warm_seconds = float(mix.get("warm_seconds", 0))
        rng = np.random.default_rng(seed)
        n = int(cell["clients"])
        # the same contexts for every seed; the seed draws the token ids
        self.prompts = [
            rng.integers(0, system.vocab_size, size=int(c)).tolist()
            for c in stats.size_grid(mix["context"], n)
        ]
        self.max_model_len = system.max_model_len

    async def _client(self, system, rec, prompt: list, first: list) -> None:
        room = self.max_model_len - len(prompt) - 1
        while True:
            r = rec.new(time.monotonic(), len(prompt), room, True)
            first.append(r)
            await drive(system, rec, r, prompt, room)
            if r.error:
                return

    async def run(self, system, rec, tail=None) -> None:
        tasks, records = [], []
        for p in self.prompts:
            first: list = []
            tasks.append(asyncio.create_task(self._client(system, rec, p, first)))
            await asyncio.sleep(0)
            records.append(first[0])
        # Set-up: every context prefilled, then a few seconds of decoding.
        while not all(r.token_times or r.error for r in records):
            await asyncio.sleep(0.05)
        await asyncio.sleep(self.warm_seconds)
        rec.t0 = time.monotonic()
        rec.t1 = rec.t0 + self.seconds
        await asyncio.sleep(self.seconds)
        if tail is not None:  # --trace 2: the sequences simply keep decoding
            await tail(None)
        await cancel_all(tasks)
