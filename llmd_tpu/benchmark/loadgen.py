"""Async load generator: open-loop (Poisson) and closed-loop stages.

Per-request lifecycle recording matches the reference report fields
(report.request_lifecycle per_request: start, TTFT, TPOT, E2E, token
counts, status). Streamed completions count SSE frames for TTFT/ITL the
same way the router does.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import random
import time

import aiohttp

from llmd_tpu.benchmark.workload import PromptSource, Stage, WorkloadSpec


@dataclasses.dataclass
class RequestRecord:
    stage: int
    start_s: float
    ttft_s: float | None = None
    e2e_s: float | None = None
    prompt_tokens: int = 0
    output_tokens: int = 0
    status: int = 0
    error: str = ""

    @property
    def ok(self) -> bool:
        return 200 <= self.status < 300 and not self.error

    @property
    def tpot_s(self) -> float | None:
        if (
            self.ttft_s is None
            or self.e2e_s is None
            or self.output_tokens <= 1
        ):
            return None
        return (self.e2e_s - self.ttft_s) / (self.output_tokens - 1)


class LoadGenerator:
    def __init__(
        self,
        base_url: str,
        model: str,
        spec: WorkloadSpec,
        request_timeout_s: float = 600.0,
    ) -> None:
        self.base_url = base_url.rstrip("/")
        self.model = model
        self.spec = spec
        self.timeout_s = request_timeout_s
        self.records: list[RequestRecord] = []
        self._prompts = PromptSource(spec)
        self._rng = random.Random(spec.seed ^ 0x5EED)

    # ------------------------------------------------------------ request

    async def _one(
        self, session: aiohttp.ClientSession, stage_idx: int
    ) -> RequestRecord:
        prompt, max_tokens = self._prompts.next_request()
        rec = RequestRecord(
            stage=stage_idx,
            start_s=time.monotonic(),
            prompt_tokens=max(1, len(prompt) // 4),
        )
        if self.spec.api == "chat":
            path = "/v1/chat/completions"
            body = {
                "model": self.model,
                "messages": [{"role": "user", "content": prompt}],
                "max_tokens": max_tokens,
                "stream": self.spec.streaming,
                "ignore_eos": self.spec.ignore_eos,
            }
        else:
            path = "/v1/completions"
            body = {
                "model": self.model,
                "prompt": prompt,
                "max_tokens": max_tokens,
                "stream": self.spec.streaming,
                "ignore_eos": self.spec.ignore_eos,
            }
        t0 = rec.start_s
        try:
            async with session.post(self.base_url + path, json=body) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:200]
                    rec.e2e_s = time.monotonic() - t0
                    return rec
                if self.spec.streaming:
                    # Chat streams open with a role-priming frame emitted
                    # before any token is generated; TTFT must anchor on
                    # the first CONTENT frame, and the role frame must not
                    # count as an output token.
                    n_frames = 0
                    usage_tokens = None
                    carry = b""
                    async for chunk in resp.content.iter_any():
                        lines = (carry + chunk).split(b"\n")
                        carry = lines.pop()
                        for ln in lines:
                            if not ln.startswith(b"data:") or b"[DONE]" in ln:
                                continue
                            # The engine fuses multiple tokens per SSE
                            # frame — up to decode_window for fused
                            # decode windows, and up to 1 + k when a
                            # speculative verify step accepts a full
                            # draft — so frames undercount
                            # tokens: trust the stream's usage frame and
                            # fall back to frame counting only when
                            # usage is absent.
                            if b'"usage"' in ln:
                                try:
                                    u = json.loads(ln[5:]).get("usage") or {}
                                    if "completion_tokens" in u:
                                        usage_tokens = u["completion_tokens"]
                                except (json.JSONDecodeError, AttributeError):
                                    pass
                            if (
                                self.spec.api == "chat"
                                and b'"content"' not in ln
                                and n_frames == 0
                            ):
                                continue  # role-priming frame
                            n_frames += 1
                            if rec.ttft_s is None:
                                rec.ttft_s = time.monotonic() - t0
                    rec.output_tokens = (
                        usage_tokens
                        if usage_tokens is not None
                        else max(0, n_frames - 1)  # final frame = usage
                    )
                else:
                    data = await resp.json()
                    rec.ttft_s = time.monotonic() - t0
                    rec.output_tokens = (
                        data.get("usage", {}).get("completion_tokens", 0)
                    )
                rec.e2e_s = time.monotonic() - t0
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            rec.error = type(e).__name__
            rec.e2e_s = time.monotonic() - t0
        return rec

    # ------------------------------------------------------------ stages

    async def _run_closed_loop(
        self, session: aiohttp.ClientSession, stage: Stage, stage_idx: int
    ) -> None:
        assert stage.num_requests is not None
        sem = asyncio.Semaphore(stage.concurrency or 1)
        remaining = stage.num_requests

        async def worker():
            async with sem:
                rec = await self._one(session, stage_idx)
                self.records.append(rec)

        await asyncio.gather(*(worker() for _ in range(remaining)))

    async def run(self) -> list[RequestRecord]:
        timeout = aiohttp.ClientTimeout(total=self.timeout_s, sock_connect=10)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            for i, stage in enumerate(self.spec.stages):
                if stage.open_loop:
                    await self._run_open_loop(session, stage, i)
                else:
                    await self._run_closed_loop(session, stage, i)
        return self.records

    async def _run_open_loop(
        self, session: aiohttp.ClientSession, stage: Stage, stage_idx: int
    ) -> None:
        """Poisson arrivals at `rate` for `duration_s`, no concurrency cap
        (open loop measures the system, not the client); optional
        num_requests cap ends the stage early."""
        assert stage.rate is not None and stage.duration_s is not None
        end = time.monotonic() + stage.duration_s
        tasks: list[asyncio.Task] = []
        while time.monotonic() < end:
            if stage.num_requests is not None and len(tasks) >= stage.num_requests:
                break
            tasks.append(asyncio.ensure_future(self._one(session, stage_idx)))
            await asyncio.sleep(self._rng.expovariate(stage.rate))
        for rec in await asyncio.gather(*tasks):
            self.records.append(rec)
