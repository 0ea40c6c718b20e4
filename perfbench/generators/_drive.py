"""Shared by the generators: send one request and write down what comes back."""

from __future__ import annotations

import asyncio
import time


async def drive(system, rec, record, prompt: list, max_tokens: int, sink: list | None = None) -> None:
    record.sent = time.monotonic()
    try:
        async for out in system.stream(prompt, max_tokens):
            rec.on_output(record, out.new_token_ids, out.finished, out.num_cached_tokens)
            if sink is not None:
                sink.extend(out.new_token_ids)
    except Exception as e:  # counted in `failed`, never swallowed silently
        record.error = repr(e)


async def cancel_all(tasks) -> None:
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def wait_first_tokens(rec, timeout_s: float) -> None:
    """After the window: until every request that was due in it has its first
    token (or failed), so that the tail of the time to first token is the
    tail of ALL requests. Bounded; what is still unanswered counts as failed."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(r.token_times or r.error for r in rec.measured() if r.sent is not None):
            return
        await asyncio.sleep(0.01)
    for r in rec.measured():
        if r.sent is not None and not r.token_times and not r.error:
            r.error = f"no first token {timeout_s} s after the window"
