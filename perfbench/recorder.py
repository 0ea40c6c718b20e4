"""What the load generators write down, and the series the metrics are
reduced from. One process, one event loop: no locks."""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class RequestRecord:
    due: float  # when the request was due (open loop) or sent (closed loop)
    want_tokens: int
    n_prompt: int
    sent: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    n_cached: int = 0
    finished: bool = False
    error: str | None = None
    bad_token: bool = False
    measured: bool = True  # False for warm-up traffic


class Recorder:
    def __init__(self, vocab_size: int) -> None:
        self.vocab_size = vocab_size
        self.records: list[RequestRecord] = []
        self.t0: float | None = None  # window start
        self.t1: float | None = None  # window end

    def new(self, due: float, n_prompt: int, want_tokens: int, measured: bool) -> RequestRecord:
        r = RequestRecord(due=due, want_tokens=want_tokens, n_prompt=n_prompt, measured=measured)
        self.records.append(r)
        return r

    def on_output(self, r: RequestRecord, new_token_ids, finished: bool, n_cached: int) -> None:
        now = time.monotonic()
        for tok in new_token_ids:
            r.token_times.append(now)
            if not 0 <= int(tok) < self.vocab_size:
                r.bad_token = True
        r.n_cached = n_cached
        r.finished = r.finished or finished

    # -- reductions ----------------------------------------------------- #

    def _in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def measured(self) -> list[RequestRecord]:
        """Requests that were due inside the window."""
        return [r for r in self.records if r.measured and self._in_window(r.due)]

    def attempted(self) -> list[RequestRecord]:
        """Measured requests that were alive in the window: due before its
        end, and not finished before its start."""
        return [
            r for r in self.records
            if r.measured and r.due < self.t1
            and not (r.finished and r.token_times and r.token_times[-1] < self.t0)
        ]

    def failed(self, r: RequestRecord) -> bool:
        if r.error or r.bad_token:
            return True
        if r.finished and len(r.token_times) != r.want_tokens:
            return True
        return False

    def series(self) -> dict:
        """Named sample lists over the window. Times in ms."""
        reqs = self.measured()
        ttft, late, prompt, cached = [], [], [], []
        for r in reqs:
            if r.sent is not None:
                late.append((r.sent - r.due) * 1e3)
            if r.token_times and not self.failed(r):
                ttft.append((r.token_times[0] - r.due) * 1e3)
            if r.finished and not self.failed(r):
                prompt.append(r.n_prompt)
                cached.append(r.n_cached)
        gaps, tokens = [], 0
        for r in self.records:
            if r.error or r.bad_token:
                continue
            tt = r.token_times
            tokens += sum(1 for t in tt if self._in_window(t))
            gaps.extend(
                (b - a) * 1e3 for a, b in zip(tt, tt[1:]) if self._in_window(b)
            )
        return {
            "ttft_ms": ttft,
            "itl_ms": gaps,
            "late_ms": late,
            "output_tokens": [tokens],
            "prompt_tokens_finished": prompt,
            "cached_tokens_finished": cached,
            "window_s": [self.t1 - self.t0],
        }
