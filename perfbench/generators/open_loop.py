"""Open loop: requests are sent on a schedule fixed before the run, whether
or not earlier ones have finished. The schedule is ``rate x seconds`` requests
whose prompt lengths, output lengths and inter-arrival gaps are fixed sets
(mid-quantiles of the mix's distributions) in a FIXED order (the mix's
``base_seed``): every seed offers the same arrivals of the same sizes, and
draws only the token ids (and the weights). Measured on the chip (PR 24): the
same schedule reproduces within ~1 %, another ORDER of the same sizes moves
the tokens completed by 14 % and the 90th percentile of the time to first
token by 40 %; so the order is part of the cell, not of the seed. A request
is timed from when it was DUE.

``run(system, rec, tail)``: with a ``tail`` (``--trace 2``) the generator,
once the window is closed and its numbers are complete, calls ``await
tail(offer)``, where ``offer(seconds)`` sends that much more of the same mix
in yet another order (unmeasured records), and cuts everything when it returns.

Mix parameters: ``prompt``, ``output`` (distributions, perfbench/stats.py),
``arrival_cv`` (1 = Poisson), ``warm_seconds``, ``drain_seconds``.
Cell parameters: ``rate`` (requests a second); ``above_knee`` (true for a cell
offered more than the system sustains: it is judged on tokens completed, the
queue grows through the run by design, and a request still unanswered at the
window's end is cut, not waited for and not a failure).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from perfbench import stats
from perfbench.generators._drive import cancel_all, drive, wait_first_tokens


def schedule(mix: dict, rate: float, seconds: float, seed: int, vocab: int, order: int = 0) -> list:
    """[(due offset s, prompt token ids, output tokens)], in order of time.
    ``order`` (with the mix's ``base_seed``) fixes sizes and arrivals; ``seed``
    draws the token ids."""
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng([int(mix.get("base_seed", 0)), order])
    prompts = fixed.permutation(stats.size_grid(mix["prompt"], n))
    outputs = fixed.permutation(stats.size_grid(mix["output"], n))
    gaps = fixed.permutation(stats.gap_grid(n, seconds, mix.get("arrival_cv", 1.0)))
    due = np.cumsum(gaps) - gaps[0] * fixed.random()  # first arrival inside its gap
    rng = np.random.default_rng(seed)
    return [
        (float(due[i]), rng.integers(0, vocab, size=int(prompts[i])).tolist(), int(outputs[i]))
        for i in range(n)
    ]


class Generator:
    def __init__(self, mix: dict, cell: dict, seed: int, seconds: float, system) -> None:
        self.mix = mix
        warm = float(mix.get("warm_seconds", 0))
        vocab = system.vocab_size
        # The warm replay is the same traffic in another order and from another
        # seed; the window's schedule follows it without a break, so the
        # window starts in a steady state and not on an empty queue.
        self.warm = schedule(mix, cell["rate"], warm, seed ^ 0x5EED5EED, vocab, order=1) if warm else []
        self.main = schedule(mix, cell["rate"], seconds, seed, vocab)
        self.warm_seconds, self.seconds = warm, seconds
        self.above_knee = bool(cell.get("above_knee", False))
        self.rate, self.seed, self.vocab = cell["rate"], seed, vocab

    async def run(self, system, rec, tail=None) -> None:
        tasks: list = []

        async def send(plan) -> None:
            for due, prompt, out_tokens, measured in plan:
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                r = rec.new(due, len(prompt), out_tokens, measured)
                tasks.append(asyncio.create_task(drive(system, rec, r, prompt, out_tokens)))

        async def offer(seconds: float) -> None:
            more = schedule(self.mix, self.rate, seconds, self.seed ^ 0x7A117A11, self.vocab, order=2)
            start = time.monotonic()
            await send([(start + d, p, o, False) for d, p, o in more])

        start = time.monotonic()
        rec.t0 = start + self.warm_seconds
        rec.t1 = rec.t0 + self.seconds
        plan = [(start + d, p, o, False) for d, p, o in self.warm]
        plan += [(rec.t0 + d, p, o, True) for d, p, o in self.main]
        await send(plan)
        delay = rec.t1 - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        if not self.above_knee:
            await wait_first_tokens(rec, float(self.mix.get("drain_seconds", 10)))
        if tail is not None:
            await tail(offer)
        await cancel_all(tasks)
