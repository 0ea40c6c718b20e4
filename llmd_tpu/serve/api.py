"""aiohttp OpenAI-compatible API server over AsyncEngine.

Surface (the model-server contract of the reference,
docs/architecture/core/model-servers.md:38-100):
  POST /v1/completions, /v1/chat/completions   (stream + non-stream)
  GET  /v1/models, /health
  GET  /metrics                                 (EPP scrape protocol)
  POST /v1/completions/render, /v1/chat/completions/render, /tokenize
       (the tokenizer surface the router's token-producer calls,
        kv-indexer.md:104-113)
"""

from __future__ import annotations

import asyncio
import dataclasses
import hmac
import json
import logging
import os
import re
import time

import aiohttp
from typing import Any

import pydantic
from aiohttp import web

from llmd_tpu import faults
from llmd_tpu.engine.request import PriorityClass, RequestOutput, SamplingParams
from llmd_tpu.epp.types import (
    HDR_EC_HOST,
    HDR_PRIORITY,
    HDR_RESUME,
    HDR_STREAM_TOKENS,
)
from llmd_tpu.obs import profiling
from llmd_tpu.obs.tracing import get_tracer
from llmd_tpu.serve import protocol as P
from llmd_tpu.serve.async_engine import (
    AsyncEngine,
    DeadlineExceeded,
    EngineError,
    RequestFailed,
)
from llmd_tpu.serve.metrics import render_metrics

log = logging.getLogger(__name__)

ENGINE_KEY = web.AppKey("llmd_engine", AsyncEngine)
TOK_KEY = web.AppKey("llmd_tokenizer", object)
MODEL_KEY = web.AppKey("llmd_model_name", str)
MAXLEN_KEY = web.AppKey("llmd_max_model_len", int)
MM_SESSION_KEY = web.AppKey("llmd_mm_session", object)
# adapter name -> slot id (1-based; the base model is slot 0)
LORA_KEY = web.AppKey("llmd_lora_adapters", dict)
# () -> dict of what the process runs on (device, kernel plans, compile
# counters); the entry point supplies it, /admin/status reports it.
RUNTIME_KEY = web.AppKey("llmd_runtime_report", object)
# --profile-dir: where POST /start_profile writes; None = profiling off.
PROFILE_DIR_KEY = web.AppKey("llmd_profile_dir", object)

_EC_HOST_RE = re.compile(r"[A-Za-z0-9_.\-]{1,253}:\d{1,5}")
_EC_DIGEST_RE = re.compile(r"[0-9a-f]{16,64}")


async def _resolve_ec_parts(request: web.Request, messages: list) -> int:
    """E-disaggregation consumer side: pull EC embedding handles placed by
    the sidecar (parts of type `ec_embedding`), free-notify the encode
    worker, and substitute a digest-stable placeholder marker.

    The pull + free exercises the full EC-connector lease lifecycle
    (multimodal-serving/README.md:44-46). The pulled embeddings are the
    injection point for a trained VLM checkpoint (soft tokens at the
    placeholder positions); with random-init weights the engine consumes
    the stable `<|image:digest|>` marker, which keeps prefix caching
    content-correct across identical images.
    """
    pulled = 0
    session = request.app.get(MM_SESSION_KEY)
    # SSRF guard. When LLMD_EC_ALLOWED_HOSTS is set it is authoritative:
    # only those encoder hosts are ever pulled from, even with a vouching
    # header (a direct-to-engine client can forge headers). Without the
    # allowlist, trust the sidecar's x-llm-d-ec-host (the sidecar strips
    # the client's copy) — this stops clients routed through the sidecar
    # but NOT a caller with direct engine-port access; deployments where
    # that matters must set the allowlist (and front encoders with a
    # stable Service name) or network-police the engine port.
    env_allowed = {
        h.strip()
        for h in os.environ.get("LLMD_EC_ALLOWED_HOSTS", "").split(",")
        if h.strip()
    }
    if env_allowed:
        allowed = env_allowed
    else:
        vouched = request.headers.get(HDR_EC_HOST, "")
        allowed = {vouched} if vouched else set()
    for m in messages:
        content = m.get("content") if isinstance(m, dict) else None
        if not isinstance(content, list):
            continue
        for part in content:
            if not (isinstance(part, dict) and part.get("type") == "ec_embedding"):
                continue
            ec = part.get("ec_embedding") or {}
            host, digest = str(ec.get("host") or ""), str(ec.get("digest") or "")
            if (
                host not in allowed
                or not _EC_HOST_RE.fullmatch(host)
                or not _EC_DIGEST_RE.fullmatch(digest)
            ):
                host = ""
            if session is not None and host and digest:
                try:
                    async with session.get(
                        f"http://{host}/v1/ec/{digest}"
                    ) as resp:
                        if resp.status == 200:
                            await resp.read()
                            pulled += 1
                except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                    log.warning("EC pull %s/%s failed: %s", host, digest, e)
            # No free-notify: EC entries are content-addressed and may be
            # shared by concurrent requests and by the P and D engines of
            # one request; the producer's lease (+ LRU) reclaims them.
            # POST /v1/ec/{digest}/free remains for explicit invalidation.
            part.clear()
            part["type"] = "text"
            part["text"] = f"<|image:{digest}|>"
    return pulled


class Detokenizer:
    """Incremental detokenization with stop-string scanning.

    Decodes the full output each call and diffs against the previously
    emitted text so multi-token/multi-byte characters stream correctly.
    While stop strings are configured, the longest possible stop-string
    prefix (max stop length - 1 chars) is held back from emission so a stop
    match never requires retracting text already sent to the client; the
    held-back tail is flushed with ``feed([], final=True)``.
    """

    def __init__(self, tokenizer, stops: list[str]) -> None:
        self.tok = tokenizer
        self.stops = stops
        self._holdback = max((len(s) for s in stops), default=1) - 1
        self.ids: list[int] = []
        self.emitted = ""
        self.stopped = False

    def feed(self, new_ids: list[int], final: bool = False) -> str:
        """Returns the text delta to emit; sets .stopped on a stop match."""
        self.ids.extend(new_ids)
        text = self.tok.decode(self.ids)
        if text.endswith("�"):
            # Incomplete UTF-8 sequence: hold back until it completes.
            text = text[: text.rfind("�")]
        if len(text) < len(self.emitted):
            return ""
        # Earliest occurrence across ALL stop strings wins.
        idx = min(
            (i for i in (text.find(s) for s in self.stops) if i != -1), default=-1
        )
        if idx != -1:
            self.stopped = True
            text = text[:idx]
            final = True
        if final or not self.stops:
            limit = len(text)
        else:
            limit = max(len(self.emitted), len(text) - self._holdback)
        delta = text[len(self.emitted) : limit]
        self.emitted = text[:limit]
        return delta


def _tokenize_prompt(tokenizer, prompt) -> list[int]:
    if isinstance(prompt, str):
        return tokenizer.encode(prompt)
    if isinstance(prompt, list):
        if not prompt:
            raise ValueError("empty prompt")
        if isinstance(prompt[0], int):
            return list(prompt)
        if isinstance(prompt[0], str):
            if len(prompt) != 1:
                raise ValueError("batched prompts unsupported; send one request per prompt")
            return tokenizer.encode(prompt[0])
        if isinstance(prompt[0], list):
            if len(prompt) != 1:
                raise ValueError("batched prompts unsupported; send one request per prompt")
            return list(prompt[0])
    raise ValueError("invalid prompt type")


def _chat_prompt_ids(tokenizer, messages: list) -> list[int]:
    """messages: ChatMessage models or plain dicts."""
    msgs = [
        m.model_dump() if isinstance(m, P.ChatMessage) else m for m in messages
    ]
    ids = tokenizer.apply_chat_template(msgs, add_generation_prompt=True, tokenize=True)
    return list(ids)


def _error(status: int, message: str) -> web.Response:
    return web.json_response(P.error_body(message, code=status), status=status)


def _error_status(e: BaseException) -> int:
    """Engine-exception -> HTTP status, shared by every generate surface
    (streamed terminal frames and non-streaming bodies alike)."""
    if isinstance(e, RequestFailed):
        return 400
    if isinstance(e, DeadlineExceeded):
        return 504
    return 500


async def _collect(
    engine: AsyncEngine,
    rid: str,
    prompt_ids: list[int],
    sampling: SamplingParams,
    detok: Detokenizer,
    priority: int,
    kv_transfer_params: dict | None,
    lora_id: int = 0,
    lora_name: str = "",
    deadline_s: float | None = None,
    resume_output_tokens: int = 0,
):
    """Run to completion; returns (text, finish_reason, final RequestOutput)."""
    finish = None
    final: RequestOutput | None = None
    async for out in engine.generate(rid, prompt_ids, sampling, priority,
                                     kv_transfer_params, lora_id, lora_name,
                                     deadline_s, resume_output_tokens):
        detok.feed(out.new_token_ids, final=out.finished)
        final = out
        if detok.stopped:
            engine.abort(rid)
            finish = "stop"
            break
        if out.finished:
            finish = out.finish_reason.value if out.finish_reason else None
    return detok.emitted, finish, final


# --------------------------------------------------------------------- #
# handlers


def _request_deadline_s(request: web.Request) -> float | None:
    """Per-request deadline: `x-request-deadline-s` header, falling back
    to LLMD_REQUEST_DEADLINE_S. Malformed values degrade to no deadline
    (a bad header must not reject a request the engine could serve)."""
    raw = request.headers.get("x-request-deadline-s") or os.environ.get(
        "LLMD_REQUEST_DEADLINE_S", ""
    )
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def _effective_priority(request: web.Request, body_priority: int) -> int:
    """Fold the batch-band header into the request's priority.

    `x-llmd-priority: batch` (sent by the batch processor,
    docs/architecture/batch-processing.md) clamps the request to the
    offline backfill band (PriorityClass.BATCH) regardless of what the
    body claimed — a batch job must never smuggle itself into the
    interactive band by omitting the body field. Other header values
    are ignored (the body integer stands)."""
    if request.headers.get(HDR_PRIORITY, "").strip().lower() == "batch":
        return min(int(body_priority), int(PriorityClass.BATCH))
    return int(body_priority)


async def handle_health(request: web.Request) -> web.Response:
    # Liveness stays cheap — but a watchdog-stalled engine IS dead for
    # serving purposes: flip 503 so the platform restarts/ejects us
    # instead of routing into a wedge.
    engine = request.app[ENGINE_KEY]
    if engine.stalled:
        return web.json_response(
            {"status": "stalled", "watchdog_s": engine.watchdog_s},
            status=503,
        )
    return web.json_response({"status": "ok"})


async def handle_ready(request: web.Request) -> web.Response:
    """Readiness (engine warmed + watchdog fresh + not draining/paused):
    the gateway's routing gate, distinct from /health liveness."""
    engine = request.app[ENGINE_KEY]
    if engine.ready:
        return web.json_response({"status": "ready"})
    return web.json_response(
        {
            "status": "not-ready",
            "draining": engine.draining,
            "paused": engine.paused,
            "stalled": engine.stalled,
        },
        status=503,
    )


async def handle_models(request: web.Request) -> web.Response:
    model = request.app[MODEL_KEY]
    entries = [
        {
            "id": model,
            "object": "model",
            "created": int(time.time()),
            "owned_by": "llmd-tpu",
            "max_model_len": request.app[MAXLEN_KEY],
        }
    ]
    # LoRA adapters serve under their own model ids (vLLM convention).
    # Dynamic-pool engines list the runtime registry (load/unload moves
    # this set); static engines the build-time map.
    registry = _adapter_registry(request)
    names = (
        registry.names()
        if registry is not None
        else list(request.app.get(LORA_KEY) or {})
    )
    for name in names:
        entries.append(
            {
                "id": name,
                "object": "model",
                "created": int(time.time()),
                "owned_by": "llmd-tpu",
                "parent": model,
                "max_model_len": request.app[MAXLEN_KEY],
            }
        )
    return web.json_response(
        {
            "object": "list",
            "data": entries,
        }
    )


async def handle_metrics(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    return web.Response(
        text=render_metrics(
            engine.stats, request.app[MODEL_KEY],
            request.app.get(LORA_KEY) or None,
        ),
        content_type="text/plain",
    )


async def handle_tokenize(request: web.Request) -> web.Response:
    tokenizer = request.app[TOK_KEY]
    try:
        body = await request.json()
        if "messages" in body:
            ids = _chat_prompt_ids(
                tokenizer, [P.ChatMessage(**m) for m in body["messages"]]
            )
        else:
            ids = _tokenize_prompt(tokenizer, body.get("prompt", ""))
    except (json.JSONDecodeError, ValueError, TypeError, AttributeError,
            pydantic.ValidationError) as e:
        return _error(400, str(e))
    return web.json_response({"tokens": ids, "count": len(ids)})


async def handle_embeddings(request: web.Request) -> web.Response:
    """OpenAI /v1/embeddings: mean-pooled L2-normalized hidden states.

    `input` accepts a string, a list of strings, a token array, or a list
    of token arrays (the OpenAI surface; reference request-handling.md:
    50-51 routes /embeddings, and the vllmgrpc parser's Embed verb is the
    token-in form)."""
    tokenizer = request.app[TOK_KEY]
    engine: AsyncEngine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        if not isinstance(body, dict):
            return _error(400, "request body must be a JSON object")
        # Same model-id discipline as the generate endpoints: adapter ids
        # embed through their slot; unknown ids 404 rather than silently
        # embedding with the base model.
        try:
            lora_id, lora_name = _resolve_lora(
                request, body.get("model") or ""
            )
        except UnknownModelError as e:
            return _error(404, f"unknown model {e}")
        raw = body.get("input")
        if isinstance(raw, str):
            items = [raw]
        elif isinstance(raw, list) and raw and isinstance(raw[0], int):
            items = [raw]
        elif isinstance(raw, list):
            items = raw
        else:
            return _error(400, "input must be a string, list of strings, "
                               "or token array(s)")
        prompts = []
        for item in items:
            if isinstance(item, str):
                prompts.append(_tokenize_prompt(tokenizer, item))
            elif isinstance(item, list) and all(isinstance(t, int) for t in item):
                prompts.append(item)
            else:
                return _error(400, "mixed or invalid input items")
        if not prompts or any(not p for p in prompts):
            return _error(400, "empty input")
    except (json.JSONDecodeError, ValueError, TypeError) as e:
        return _error(400, str(e))
    try:
        vectors = await engine.embed(prompts, lora_id, lora_name)
    except ValueError as e:  # over max_model_len
        return _error(400, str(e))
    total_tokens = sum(len(p) for p in prompts)
    return web.json_response({
        "object": "list",
        "model": body.get("model") or request.app[MODEL_KEY],
        "data": [
            {"object": "embedding", "index": i, "embedding": row}
            for i, row in enumerate(vectors.tolist())
        ],
        "usage": {"prompt_tokens": total_tokens, "total_tokens": total_tokens},
    })


async def handle_cache_probe(request: web.Request) -> web.Response:
    """POST /v1/cache/probe — the P/D byte-diet question: how many
    leading FULL pages of this request's prompt are already cached here?

    Accepts the same body shape as /v1/completions ("prompt") or
    /v1/chat/completions ("messages"); the sidecar calls it on the local
    decode engine before phase 1 so the prefiller can skip staging pages
    the decode side already holds (reference disagg decider,
    scheduling.md:113)."""
    engine: AsyncEngine = request.app[ENGINE_KEY]
    tokenizer = request.app[TOK_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    try:
        if body.get("messages") is not None:
            ids = _chat_prompt_ids(tokenizer, body["messages"])
        elif body.get("prompt") is not None:
            ids = _tokenize_prompt(tokenizer, body["prompt"])
        else:
            return _error(400, "prompt or messages is required")
    except (ValueError, TypeError) as e:
        return _error(400, str(e))
    eng = engine.engine
    return web.json_response({
        "cached_full_pages": eng.cached_prefix_pages(ids),
        "page_size": eng.allocator.page_size,
        "num_full_pages": len(ids) // eng.allocator.page_size,
    })


async def handle_completions_render(request: web.Request) -> web.Response:
    """vLLM-style render: return the token ids the engine would see."""
    tokenizer = request.app[TOK_KEY]
    try:
        req = P.CompletionRequest(**await request.json())
        ids = _tokenize_prompt(tokenizer, req.prompt)
    except (ValueError, TypeError) as e:
        return _error(400, str(e))
    return web.json_response({"prompt_token_ids": ids, "model": req.model})


async def handle_chat_render(request: web.Request) -> web.Response:
    tokenizer = request.app[TOK_KEY]
    try:
        req = P.ChatCompletionRequest(**await request.json())
        ids = _chat_prompt_ids(tokenizer, req.messages)
    except (ValueError, TypeError) as e:
        return _error(400, str(e))
    return web.json_response({"prompt_token_ids": ids, "model": req.model})


def _sse(data: dict) -> bytes:
    return b"data: " + json.dumps(data, separators=(",", ":")).encode() + b"\n\n"


async def _stream_response(
    request: web.Request,
    engine: AsyncEngine,
    rid: str,
    model: str,
    prompt_ids: list[int],
    sampling: SamplingParams,
    detok: Detokenizer,
    priority: int,
    kv_transfer_params: dict | None,
    chat: bool,
    span=None,
    lora_id: int = 0,
    lora_name: str = "",
    deadline_s: float | None = None,
    resume_output_tokens: int = 0,
    stream_token_ids: bool = False,
    resume_leg: bool = False,
) -> web.StreamResponse:
    resp = web.StreamResponse(
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "x-request-id": rid,
        }
    )
    await resp.prepare(request)
    if chat and not resume_leg:
        # A resume leg continues an already-opened client stream: the
        # role preamble went out with the first leg. `resume_leg` covers
        # the empty-history replay too (HDR_RESUME: the upstream died
        # after the preamble but before the first token frame).
        await resp.write(_sse(P.chat_chunk(rid, model, {"role": "assistant"}, None)))
    finish = None
    n_out = resume_output_tokens
    cached = 0
    out = None
    try:
        async for out in engine.generate(rid, prompt_ids, sampling, priority,
                                         kv_transfer_params, lora_id, lora_name,
                                         deadline_s, resume_output_tokens):
            delta = detok.feed(out.new_token_ids, final=out.finished)
            n_out = out.num_output_tokens
            cached = out.num_cached_tokens
            if detok.stopped:
                engine.abort(rid)
                finish = "stop"
            elif out.finished:
                finish = out.finish_reason.value if out.finish_reason else None
            # Emit a chunk per engine output even when the detokenizer
            # holds text back (incomplete UTF-8 / stop-string holdback):
            # the empty delta is what tells a streaming client the first
            # token EXISTS — without it, TTFT degrades to time-to-full-
            # response whenever the text buffer never flushes early.
            if delta or out.new_token_ids:
                chunk = (
                    P.chat_chunk(rid, model, {"content": delta}, None)
                    if chat
                    else P.completion_chunk(rid, model, delta, None)
                )
                if stream_token_ids:
                    # Raw token ids ride the frame for the router's
                    # resume history (HDR_STREAM_TOKENS contract); the
                    # router strips them before the client sees bytes.
                    chunk["token_ids"] = list(out.new_token_ids)
                await resp.write(_sse(chunk))
                # Injection site: the replica "dies" mid-stream — the
                # transport is severed without an SSE terminator, which
                # is exactly what a crashed engine looks like to the
                # router's upstream read loop.
                if faults.fires("serve.stream.cut", rid):
                    engine.abort(rid)
                    if request.transport is not None:
                        request.transport.close()
                    return resp
            if finish is not None:
                break
    except (RequestFailed, EngineError) as e:
        # The stream is already committed: a terminal error frame (504
        # for deadline, 500 engine, 400 client) instead of a hang.
        await resp.write(_sse(P.error_body(str(e), code=_error_status(e))))
        await resp.write(b"data: [DONE]\n\n")
        return resp
    except (asyncio.CancelledError, ConnectionResetError):
        engine.abort(rid)
        raise
    if span is not None:
        span.set("gen_ai.usage.completion_tokens", n_out)
        span.set("llm_d.cache.hit_tokens", cached)
        _set_engine_timing(span, out)
    final = (
        P.chat_chunk(rid, model, {}, finish)
        if chat
        else P.completion_chunk(rid, model, "", finish)
    )
    final["usage"] = P.usage_dict(
        len(prompt_ids) - resume_output_tokens, n_out, cached
    )
    await resp.write(_sse(final))
    await resp.write(b"data: [DONE]\n\n")
    await resp.write_eof()
    return resp


def _set_engine_timing(span, out: RequestOutput | None) -> None:
    """The request's own timestamps, as the engine reports them on its
    outputs: arrival to first admission, arrival to first token."""
    if out is not None and out.queue_wait_ms is not None:
        span.set("llm_d.queue_wait_ms", round(out.queue_wait_ms, 3))
        span.set("llm_d.ttft_ms", round(out.ttft_ms, 3))


async def _stream_response_multi(
    request: web.Request,
    engine: AsyncEngine,
    rid: str,
    model: str,
    prompt_ids: list[int],
    sampling: SamplingParams,
    tokenizer,
    stops: list[str],
    n: int,
    priority: int,
    kv_transfer_params: dict | None,
    chat: bool,
    span=None,
    lora_id: int = 0,
    lora_name: str = "",
    deadline_s: float | None = None,
) -> web.StreamResponse:
    """SSE with n>1: one engine stream per choice, chunks multiplexed onto
    the response with their choice index (OpenAI interleave semantics).
    Choice i derives seed+i when seeded; only choice 0 carries the remote
    KV pull — mirroring the non-streaming n>1 path."""
    resp = web.StreamResponse(
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "x-request-id": rid,
        }
    )
    await resp.prepare(request)
    if chat:
        for i in range(n):
            await resp.write(_sse(
                P.chat_chunk(rid, model, {"role": "assistant"}, None, index=i)
            ))
    queue: asyncio.Queue = asyncio.Queue()
    totals = {"out": 0, "cached": 0}

    async def pump(i: int) -> None:
        sp = (
            dataclasses.replace(sampling, seed=sampling.seed + i)
            if sampling.seed is not None
            else sampling
        )
        crid = f"{rid}-{i}"
        detok = Detokenizer(tokenizer, stops)
        terminal = False
        try:
            async for out in engine.generate(
                crid, prompt_ids, sp, priority,
                kv_transfer_params if i == 0 else None, lora_id, lora_name,
                deadline_s,
            ):
                delta = detok.feed(out.new_token_ids, final=out.finished)
                finish = None
                if detok.stopped:
                    engine.abort(crid)
                    finish = "stop"
                elif out.finished:
                    finish = (
                        out.finish_reason.value if out.finish_reason else None
                    )
                # Empty deltas still signal token arrival (UTF-8 / stop
                # holdback) — same TTFT honesty as the single-stream path.
                if delta or out.new_token_ids:
                    await queue.put(("delta", i, delta))
                if finish is not None or out.finished:
                    totals["out"] += out.num_output_tokens
                    totals["cached"] = max(
                        totals["cached"], out.num_cached_tokens
                    )
                    terminal = True
                    await queue.put(("finish", i, finish))
                    return
            # Generator exhausted without a finished output (defensive):
            # still emit a terminal item or the consumer loop waits forever.
            terminal = True
            await queue.put(("finish", i, None))
        except asyncio.CancelledError:
            raise
        # llmd: allow(broad-except) -- the failure IS surfaced: forwarded to the consumer loop as a terminal error item
        except Exception as e:
            # ANY pump failure must surface as a terminal item — a silent
            # exit deadlocks the `while done < n` consumer.
            if not terminal:
                await queue.put(("error", i, e))

    tasks = [asyncio.ensure_future(pump(i)) for i in range(n)]
    done = 0
    try:
        while done < n:
            kind, i, payload = await queue.get()
            if kind == "error":
                await resp.write(_sse(P.error_body(
                    str(payload), code=_error_status(payload),
                )))
                await resp.write(b"data: [DONE]\n\n")
                for t in tasks:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
                return resp
            if kind == "delta":
                chunk = (
                    P.chat_chunk(rid, model, {"content": payload}, None, index=i)
                    if chat
                    else P.completion_chunk(rid, model, payload, None, index=i)
                )
            else:
                done += 1
                chunk = (
                    P.chat_chunk(rid, model, {}, payload, index=i)
                    if chat
                    else P.completion_chunk(rid, model, "", payload, index=i)
                )
            await resp.write(_sse(chunk))
    except (asyncio.CancelledError, ConnectionResetError):
        for i in range(n):
            engine.abort(f"{rid}-{i}")
        for t in tasks:
            t.cancel()
        raise
    if span is not None:
        span.set("gen_ai.usage.completion_tokens", totals["out"])
        span.set("llm_d.cache.hit_tokens", totals["cached"])
    usage_chunk = {
        "id": rid,
        "object": "chat.completion.chunk" if chat else "text_completion",
        "model": model,
        "choices": [],
        "usage": P.usage_dict(len(prompt_ids), totals["out"], totals["cached"]),
    }
    await resp.write(_sse(usage_chunk))
    await resp.write(b"data: [DONE]\n\n")
    await resp.write_eof()
    return resp


def _validate_resume(resume_ids, max_tokens: int, n: int = 1) -> str | None:
    """Shared resume-admission validation for every generate surface
    (OpenAI + vllmgrpc): None = admissible, else the 400 message. The
    caller counts `stream_resume_failures_total` on rejection."""
    if n != 1:
        return "resume_token_ids requires n == 1"
    if not (
        isinstance(resume_ids, list)
        and all(isinstance(t, int) and 0 <= t for t in resume_ids)
    ):
        return "resume_token_ids must be non-negative token ids"
    if len(resume_ids) > max_tokens:
        return (
            f"resume history of {len(resume_ids)} tokens exceeds the "
            f"request's max_tokens {max_tokens}"
        )
    return None


def _resume_finished(
    prompt_len: int,
    resume_ids: list[int],
    sampling: SamplingParams,
    max_len: int,
) -> str | None:
    """Finish reason already reached by the DELIVERED history — the dead
    replica emitted the terminal token but its finish frame was lost.
    Mirrors the engine's stop-check order (stop token, then length)."""
    if (
        not sampling.ignore_eos
        and resume_ids
        and resume_ids[-1] in sampling.stop_token_ids
    ):
        return "stop"
    if len(resume_ids) >= sampling.max_tokens:
        return "length"
    if prompt_len + len(resume_ids) >= max_len:
        return "length"
    return None


async def _finish_only_stream(
    request: web.Request,
    rid: str,
    model: str,
    chat: bool,
    finish: str,
    usage: dict,
) -> web.StreamResponse:
    """Resume leg with nothing left to generate: only the terminal frame
    (+ usage + [DONE]) was lost with the dead replica — emit exactly
    that, so the stitched client stream matches an uninterrupted one."""
    resp = web.StreamResponse(
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "x-request-id": rid,
        }
    )
    await resp.prepare(request)
    final = (
        P.chat_chunk(rid, model, {}, finish)
        if chat
        else P.completion_chunk(rid, model, "", finish)
    )
    final["usage"] = usage
    await resp.write(_sse(final))
    await resp.write(b"data: [DONE]\n\n")
    await resp.write_eof()
    return resp


class UnknownModelError(Exception):
    pass


def _adapter_registry(request: web.Request):
    """The engine's DYNAMIC adapter registry (paged-pool engines,
    docs/architecture/multi-tenant-lora.md), or None on static/no-LoRA
    engines."""
    engine = request.app.get(ENGINE_KEY)
    return getattr(getattr(engine, "engine", None), "adapter_registry", None)


def _resolve_lora(request: web.Request, model: str) -> tuple[int, str]:
    """Model id -> (lora slot, adapter name). With adapters configured,
    an id that is neither the base model nor an adapter is a client error
    (adapters are advertised as distinct model ids; silently serving the
    base for a typo'd name masks misconfiguration).

    Dynamic-pool engines resolve by NAME: the engine owns the name->slot
    map (residency moves at runtime), so the returned slot is 0 and the
    name alone rides to add_request."""
    adapters = request.app.get(LORA_KEY) or {}
    if model in adapters:
        return adapters[model], model
    registry = _adapter_registry(request)
    if registry is not None and model and registry.has(model):
        return 0, model
    known = bool(adapters) or (registry is not None and len(registry))
    if known and model and model != request.app[MODEL_KEY]:
        raise UnknownModelError(model)
    return 0, ""


async def _handle_generate(request: web.Request, chat: bool) -> web.StreamResponse:
    engine = request.app[ENGINE_KEY]
    tokenizer = request.app[TOK_KEY]
    model = request.app[MODEL_KEY]
    max_len = request.app[MAXLEN_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    try:
        if chat:
            req = P.ChatCompletionRequest(**body)
            msgs = [m.model_dump() for m in req.messages]
            await _resolve_ec_parts(request, msgs)
            prompt_ids = _chat_prompt_ids(tokenizer, msgs)
            req_max = req.max_completion_tokens or req.max_tokens
        else:
            req = P.CompletionRequest(**body)
            prompt_ids = _tokenize_prompt(tokenizer, req.prompt)
            req_max = req.max_tokens
    except (ValueError, TypeError, pydantic.ValidationError) as e:
        return _error(400, str(e))
    if req.n < 1 or req.n > 16:
        return _error(400, "n must be in [1, 16]")
    if len(prompt_ids) >= max_len:
        return _error(400, f"prompt length {len(prompt_ids)} >= max_model_len {max_len}")
    budget = max_len - len(prompt_ids)
    max_tokens = min(req_max if req_max is not None else budget, budget)
    eos = getattr(tokenizer, "eos_token_id", None)
    sampling = P.to_sampling(req, eos, max_tokens)
    rid = request.headers.get("x-request-id") or P.request_id(
        "chatcmpl" if chat else "cmpl"
    )
    # Mid-stream failover resume (docs/architecture/fault-tolerance.md):
    # the delivered history becomes committed prefix; the response
    # carries ONLY the continuation, starting at the exact next output
    # position (byte-identical for greedy and seeded streams).
    resume_ids = list(req.resume_token_ids or [])
    if resume_ids:
        reject = _validate_resume(resume_ids, max_tokens, req.n)
        if reject is not None:
            engine.stats.stream_resume_failures_total += 1
            return _error(400, reject)
    try:
        lora_id, lora_name = _resolve_lora(request, req.model)
    except UnknownModelError:
        return _error(404, f"model {req.model!r} not found")
    if lora_name:
        model = lora_name  # responses echo the requested adapter id
    detok = Detokenizer(tokenizer, P.stop_strings(req.stop))
    # Engine-side span continues the router's traceparent (reference
    # tracing.md: per-hop spans; cache-hit attribution via cached tokens).
    span = get_tracer().start_span(
        "engine.generate",
        traceparent=request.headers.get("traceparent"),
        kind="SPAN_KIND_SERVER",
    )
    span.set("gen_ai.request.model", model)
    span.set("gen_ai.usage.prompt_tokens", len(prompt_ids))
    span.set("llm_d.request.streaming", bool(req.stream))
    deadline_s = _request_deadline_s(request)
    priority = _effective_priority(request, req.priority)
    stream_token_ids = request.headers.get(HDR_STREAM_TOKENS, "") == "1"
    resume_leg = bool(resume_ids) or (
        request.headers.get(HDR_RESUME, "") == "1"
    )

    engine_prompt_ids = prompt_ids
    resume_text_base = 0
    if resume_ids:
        span.set("llm_d.resume.tokens", len(resume_ids))
        fin = _resume_finished(len(prompt_ids), resume_ids, sampling, max_len)
        # Replaying the history through a fresh detokenizer reproduces
        # the exact text the first leg emitted (decode-then-diff is
        # deterministic), so deltas continue mid-UTF-8 and mid-holdback.
        detok.feed(resume_ids, final=fin is not None)
        if fin is None and detok.stopped:
            fin = "stop"  # history ends exactly on a stop string
        resume_text_base = len(detok.emitted)
        if fin is not None:
            span.end()
            usage = P.usage_dict(len(prompt_ids), len(resume_ids))
            if req.stream:
                return await _finish_only_stream(
                    request, rid, model, chat, fin, usage
                )
            builder = P.chat_response if chat else P.completion_response
            return web.json_response(
                builder(rid, model, "", fin, usage),
                headers={"x-request-id": rid},
            )
        engine_prompt_ids = prompt_ids + resume_ids

    if req.stream:
        try:
            if req.n > 1:
                return await _stream_response_multi(
                    request, engine, rid, model, prompt_ids, sampling,
                    tokenizer, P.stop_strings(req.stop), req.n,
                    priority, req.kv_transfer_params, chat, span,
                    lora_id, lora_name, deadline_s,
                )
            return await _stream_response(
                request, engine, rid, model, engine_prompt_ids, sampling,
                detok, priority, req.kv_transfer_params, chat, span,
                lora_id, lora_name, deadline_s,
                resume_output_tokens=len(resume_ids),
                stream_token_ids=stream_token_ids,
                resume_leg=resume_leg,
            )
        except BaseException as e:
            span.error(str(e))
            raise
        finally:
            span.end()
    try:
        if req.n == 1:
            choices = [await _collect(
                engine, rid, engine_prompt_ids, sampling, detok, priority,
                req.kv_transfer_params, lora_id, lora_name, deadline_s,
                resume_output_tokens=len(resume_ids),
            )]
        else:
            # n parallel samples share the prompt (and its cached prefix).
            # With a seed set, choice i derives seed+i so the batch is
            # reproducible; unseeded choices draw independent randomness.
            # Greedy (temperature=0) necessarily yields identical choices,
            # matching OpenAI semantics. Only choice 0 carries the remote
            # KV pull (one transfer; siblings reuse the cached prefix or
            # recompute locally).
            async def one(i: int):
                sp = (
                    dataclasses.replace(sampling, seed=sampling.seed + i)
                    if sampling.seed is not None
                    else sampling
                )
                return await _collect(
                    engine, f"{rid}-{i}", prompt_ids, sp,
                    Detokenizer(tokenizer, P.stop_strings(req.stop)),
                    priority,
                    req.kv_transfer_params if i == 0 else None,
                    lora_id, lora_name, deadline_s,
                )

            tasks = [asyncio.ensure_future(one(i)) for i in range(req.n)]
            try:
                choices = list(await asyncio.gather(*tasks))
            except BaseException:
                # First failure: stop the siblings (cancellation aborts
                # their engine requests) and drain their exceptions.
                for t in tasks:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
                raise
        text, finish, final = choices[0]
        if resume_text_base:
            # The response body carries ONLY the continuation; the
            # client already holds the replayed history's text.
            text = text[resume_text_base:]
    except RequestFailed as e:
        span.error(str(e))
        span.end()
        return _error(400, str(e))
    except DeadlineExceeded as e:
        span.error(str(e))
        span.end()
        return web.json_response(
            P.error_body(str(e), etype="timeout_error", code=504), status=504
        )
    except EngineError as e:
        span.error(str(e))
        span.end()
        return web.json_response(
            P.error_body(str(e), etype="internal_error", code=500), status=500
        )
    except BaseException as e:
        # CancelledError on client disconnect etc.: the span for the
        # anomalous request must still export.
        span.error(str(e) or type(e).__name__)
        span.end()
        raise
    completion_tokens = sum(f.num_output_tokens for _, _, f in choices if f)
    span.set("gen_ai.usage.completion_tokens", completion_tokens)
    span.set("llm_d.cache.hit_tokens", final.num_cached_tokens if final else 0)
    _set_engine_timing(span, final)
    span.end()
    usage = P.usage_dict(
        len(prompt_ids),
        completion_tokens,
        final.num_cached_tokens if final else 0,
    )
    kvp = final.kv_transfer_params if final else None
    builder = P.chat_response if chat else P.completion_response
    resp = builder(rid, model, text, finish, usage, kvp)
    if req.n > 1:
        tmpl = resp["choices"][0]
        resp["choices"] = [
            {
                **tmpl,
                "index": i,
                **(
                    {"message": {"role": "assistant", "content": txt}}
                    if chat
                    else {"text": txt}
                ),
                "finish_reason": fin,
            }
            for i, (txt, fin, _) in enumerate(choices)
        ]
    return web.json_response(resp, headers={"x-request-id": rid})


async def handle_grpc_embed(request: web.Request) -> web.Response:
    """vLLM gRPC Embed, JSON-transcoded: token-in / vector-out."""
    engine = request.app[ENGINE_KEY]
    max_len = request.app[MAXLEN_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    if not isinstance(body, dict):
        return _error(400, "request body must be a JSON object")
    try:
        lora_id, lora_name = _resolve_lora(
            request, str(body.get("model") or "")
        )
    except UnknownModelError as e:
        return _error(404, f"unknown model {e}")
    ids = body.get("prompt_token_ids") or body.get("token_ids") or []
    if not (isinstance(ids, list) and ids):
        return _error(400, "prompt_token_ids must be a non-empty list")
    # single token array or batch of arrays
    prompts = ids if isinstance(ids[0], list) else [ids]
    for p in prompts:
        if not (isinstance(p, list) and p and all(isinstance(t, int) for t in p)):
            return _error(400, "prompt_token_ids must be int token array(s)")
        if len(p) > max_len:
            return _error(400, f"prompt length {len(p)} > max_model_len {max_len}")
    try:
        vectors = await engine.embed(prompts, lora_id, lora_name)
    except ValueError as e:  # over the embed batch-token limit
        return _error(400, str(e))
    return web.json_response({"embeddings": vectors.tolist()})


async def handle_grpc_generate(request: web.Request) -> web.StreamResponse:
    """vLLM gRPC Generate, JSON-transcoded: token-in / token-out.

    The EPP's `vllmgrpc-parser` routes these (reference
    request-handling.md:50-86); the engine surface never detokenizes —
    clients own the tokenizer. Streamed form emits SSE frames of
    {"token_ids": [...]}, final frame carries finish_reason + usage.
    """
    engine = request.app[ENGINE_KEY]
    max_len = request.app[MAXLEN_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    if not isinstance(body, dict):
        return _error(400, "request body must be a JSON object")
    ids = body.get("prompt_token_ids") or body.get("token_ids") or []
    if not isinstance(ids, list) or not all(isinstance(t, int) for t in ids):
        return _error(400, "prompt_token_ids must be a list of ints")
    if not ids:
        return _error(400, "empty prompt_token_ids")
    if len(ids) >= max_len:
        return _error(400, f"prompt length {len(ids)} >= max_model_len {max_len}")
    sp = body.get("sampling_params") or {}
    if not isinstance(sp, dict):
        return _error(400, "sampling_params must be an object")
    budget = max_len - len(ids)
    eos = getattr(request.app[TOK_KEY], "eos_token_id", None)
    try:
        stops = [int(t) for t in (sp.get("stop_token_ids") or [])]
        if eos is not None and not sp.get("ignore_eos", False):
            stops.append(int(eos))
        req_max = sp.get("max_tokens")
        max_tokens = budget if req_max is None else min(int(req_max), budget)
        if max_tokens < 0:
            return _error(400, "max_tokens must be >= 0")
        seed = sp.get("seed")
        sampling = SamplingParams(
            max_tokens=max_tokens,
            temperature=float(sp.get("temperature", 1.0)),
            top_k=int(sp.get("top_k", 0) or 0),
            top_p=float(sp.get("top_p", 1.0)),
            stop_token_ids=tuple(stops),
            ignore_eos=bool(sp.get("ignore_eos", False)),
            seed=None if seed is None else int(seed),
        )
        priority = int(sp.get("priority", 0) or 0)
    except (TypeError, ValueError) as e:
        return _error(400, f"invalid sampling_params: {e}")
    rid = request.headers.get("x-request-id") or P.request_id("grpcgen")
    kvp = body.get("kv_transfer_params")
    deadline_s = _request_deadline_s(request)
    try:
        lora_id, lora_name = _resolve_lora(request, str(body.get("model") or ""))
    except UnknownModelError as e:
        return _error(404, f"model {e.args[0]!r} not found")
    resume_ids = body.get("resume_token_ids") or []
    if resume_ids:
        reject = _validate_resume(resume_ids, sampling.max_tokens)
        if reject is not None:
            engine.stats.stream_resume_failures_total += 1
            return _error(400, reject)
        fin = _resume_finished(len(ids), resume_ids, sampling, max_len)
        if fin is not None:
            usage = P.usage_dict(len(ids), len(resume_ids))
            if body.get("stream", False):
                resp = web.StreamResponse(
                    headers={
                        "Content-Type": "text/event-stream",
                        "Cache-Control": "no-cache",
                        "x-request-id": rid,
                    }
                )
                await resp.prepare(request)
                await resp.write(_sse({"finish_reason": fin, "usage": usage}))
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
                return resp
            return web.json_response(
                {"id": rid, "model": model, "token_ids": [],
                 "finish_reason": fin, "usage": usage,
                 "kv_transfer_params": None},
                headers={"x-request-id": rid},
            )
        ids = ids + resume_ids
    n_resume = len(resume_ids)

    if body.get("stream", False):
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "x-request-id": rid,
            }
        )
        await resp.prepare(request)
        final = None
        try:
            async for out in engine.generate(rid, ids, sampling, priority, kvp,
                                             lora_id, lora_name, deadline_s,
                                             n_resume):
                final = out
                if out.new_token_ids:
                    await resp.write(_sse({"token_ids": list(out.new_token_ids)}))
                    # Same mid-stream kill site as the OpenAI surface.
                    if faults.fires("serve.stream.cut", rid):
                        engine.abort(rid)
                        if request.transport is not None:
                            request.transport.close()
                        return resp
        except (RequestFailed, EngineError) as e:
            await resp.write(_sse(P.error_body(str(e), code=_error_status(e))))
            await resp.write(b"data: [DONE]\n\n")
            return resp
        except (asyncio.CancelledError, ConnectionResetError):
            engine.abort(rid)
            raise
        await resp.write(
            _sse(
                {
                    "finish_reason": (
                        final.finish_reason.value
                        if final is not None and final.finish_reason
                        else None
                    ),
                    "usage": P.usage_dict(
                        len(ids) - n_resume,
                        final.num_output_tokens if final else n_resume,
                        final.num_cached_tokens if final else 0,
                    ),
                }
            )
        )
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    out_ids: list[int] = []
    final = None
    try:
        async for out in engine.generate(rid, ids, sampling, priority, kvp,
                                         lora_id, lora_name, deadline_s,
                                         n_resume):
            final = out
            out_ids.extend(out.new_token_ids)
    except RequestFailed as e:
        return _error(400, str(e))
    except DeadlineExceeded as e:
        return web.json_response(
            P.error_body(str(e), etype="timeout_error", code=504), status=504
        )
    except EngineError as e:
        return web.json_response(
            P.error_body(str(e), etype="internal_error", code=500), status=500
        )
    return web.json_response(
        {
            "id": rid,
            "model": model,
            "token_ids": out_ids,
            "finish_reason": (
                final.finish_reason.value
                if final is not None and final.finish_reason
                else None
            ),
            "usage": P.usage_dict(
                len(ids) - n_resume,
                final.num_output_tokens if final else n_resume,
                final.num_cached_tokens if final else 0,
            ),
            "kv_transfer_params": final.kv_transfer_params if final else None,
        },
        headers={"x-request-id": rid},
    )


# --------------------------------------------------------------------- #
# IRO engine-coordination surface (proposals/inference-resilience-operator.md:
# pause/resume/drain called by the resilience operator's EngineAdapter
# around infrastructure recovery actions).
#
# Auth: pause halts serving, so these must not be client-callable. With
# LLMD_ADMIN_TOKEN set, requests need `x-admin-token` (or Bearer) to
# match; without it, only loopback peers are accepted (the IRO runs on
# the same host in no-K8s mode; on K8s, mount a token).


def _admin_denied(request: web.Request) -> web.Response | None:
    token = os.environ.get("LLMD_ADMIN_TOKEN", "")
    if token.startswith("REPLACE-ME"):
        # The committed recipe placeholder is public knowledge — treating
        # it as a valid credential would be worse than no token at all.
        return _error(403, "placeholder admin token; set a real secret")
    if token:
        given = request.headers.get("x-admin-token", "")
        auth = request.headers.get("authorization", "")
        if auth.lower().startswith("bearer "):
            given = given or auth[7:]
        if hmac.compare_digest(given, token):
            return None
        return _error(403, "admin token required")
    peer = request.transport.get_extra_info("peername") if request.transport else None
    host = peer[0] if isinstance(peer, (tuple, list)) and peer else ""
    if host in ("127.0.0.1", "::1", "::ffff:127.0.0.1"):
        return None
    return _error(403, "admin surface is loopback-only without LLMD_ADMIN_TOKEN")


async def handle_admin_pause(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    request.app[ENGINE_KEY].pause()
    return web.json_response({"paused": True})


async def handle_admin_resume(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    request.app[ENGINE_KEY].resume()
    return web.json_response({"paused": False})


async def handle_admin_drain(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    try:
        timeout_s = float(request.query.get("timeout", 60.0))
    except ValueError:
        return _error(400, "timeout must be a number")
    drained = await request.app[ENGINE_KEY].drain(timeout_s)
    return web.json_response({"drained": drained}, status=200 if drained else 504)


async def handle_admin_status(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    engine = request.app[ENGINE_KEY]
    stats = engine.stats
    runtime = request.app.get(RUNTIME_KEY)
    return web.json_response(
        {
            "paused": engine.paused,
            "running": stats.num_running,
            "waiting": stats.num_waiting,
            **(runtime() if runtime is not None else {}),
        }
    )


# --------------------------------------------------------------------- #
# Profiler control (vLLM's endpoint names): the same obs.profiling calls
# the benchmark makes, in the process that holds the chip. Admin surface:
# a trace slows the host and writes to the server's disk.


async def handle_start_profile(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    trace_dir = request.app.get(PROFILE_DIR_KEY)
    if not trace_dir:
        return _error(409, "profiling is off: start the server with --profile-dir")
    try:
        await asyncio.to_thread(profiling.start, trace_dir)
    except profiling.ProfilerBusy as e:
        return _error(409, str(e))
    return web.json_response({"profiling": True, "trace_dir": trace_dir})


async def handle_stop_profile(request: web.Request) -> web.Response:
    denied = _admin_denied(request)
    if denied is not None:
        return denied
    try:
        trace_dir = await asyncio.to_thread(profiling.stop)
    except profiling.ProfilerBusy as e:
        return _error(409, str(e))
    return web.json_response({"profiling": False, "trace_dir": trace_dir})


# --------------------------------------------------------------------- #
# Runtime adapter load/unload (the vLLM dynamic-LoRA contract;
# docs/architecture/multi-tenant-lora.md). Registration is unbounded —
# the paged pool bounds HBM residency, not the servable set. Loads are
# lockstep-broadcast slot installs, so multi-host replicas flip
# atomically; a failed fetch degrades to a counted 4xx
# (lora_load_failures_total), never a wedged batch.

_LORA_NAME_RE = re.compile(r"[A-Za-z0-9._:/-]+")


async def handle_load_lora_adapter(request: web.Request) -> web.Response:
    engine: AsyncEngine = request.app[ENGINE_KEY]
    if _adapter_registry(request) is None:
        return _error(
            400,
            "dynamic adapter serving is disabled (start the server with "
            "--lora-pool-slots)",
        )
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    if not isinstance(body, dict):
        return _error(400, "request body must be a JSON object")
    name = str(body.get("lora_name") or "")
    source = str(
        body.get("lora_path") or body.get("lora_url") or body.get("source")
        or ""
    )
    if not name or not _LORA_NAME_RE.fullmatch(name):
        # Names interpolate into Prometheus label values and model ids.
        return _error(
            400, f"invalid lora_name {name!r}: use letters, digits, ._:/-"
        )
    if name == request.app[MODEL_KEY]:
        return _error(400, f"lora_name {name!r} shadows the base model id")
    if not source:
        return _error(
            400, "lora_path (or lora_url / source) is required"
        )
    from llmd_tpu.lora import AdapterFetchError

    try:
        await engine.load_adapter(name, source)
    except (AdapterFetchError, ValueError) as e:
        # Fetch/decode/duplicate failures are CLIENT errors: counted
        # (lora_load_failures_total covers the fetch leg) and surfaced;
        # base-model rows and resident adapters are untouched.
        return _error(400, str(e))
    except RuntimeError as e:  # dynamic serving disabled
        return _error(400, str(e))
    return web.json_response(
        {
            "status": "ok",
            "message": f"Success: LoRA adapter '{name}' added successfully",
            "lora_name": name,
        }
    )


async def handle_unload_lora_adapter(request: web.Request) -> web.Response:
    engine: AsyncEngine = request.app[ENGINE_KEY]
    if _adapter_registry(request) is None:
        return _error(400, "dynamic adapter serving is disabled")
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error(400, f"invalid JSON: {e}")
    if not isinstance(body, dict):
        return _error(400, "request body must be a JSON object")
    name = str(body.get("lora_name") or "")
    if not name:
        return _error(400, "lora_name is required")
    try:
        await engine.unload_adapter(name)
    except KeyError as e:
        return _error(404, str(e.args[0]) if e.args else name)
    except RuntimeError as e:
        # In-flight rows reference the adapter: conflict, retry later.
        return _error(409, str(e))
    return web.json_response(
        {
            "status": "ok",
            "message": f"Success: LoRA adapter '{name}' removed successfully",
            "lora_name": name,
        }
    )


async def handle_completions(request: web.Request) -> web.StreamResponse:
    return await _handle_generate(request, chat=False)


async def handle_chat(request: web.Request) -> web.StreamResponse:
    return await _handle_generate(request, chat=True)


# --------------------------------------------------------------------- #


def _responses_routes() -> list:
    from llmd_tpu.serve.responses import make_handlers

    return make_handlers(ENGINE_KEY, TOK_KEY, MODEL_KEY, MAXLEN_KEY)


def build_app(
    engine: AsyncEngine,
    tokenizer,
    model_name: str,
    max_model_len: int,
    extra_routes: list | None = None,
    lora_adapters: dict[str, int] | None = None,
    runtime_report=None,
    profile_dir: str | None = None,
) -> web.Application:
    app = web.Application()
    app[ENGINE_KEY] = engine
    app[PROFILE_DIR_KEY] = profile_dir
    if runtime_report is not None:
        app[RUNTIME_KEY] = runtime_report
    app[TOK_KEY] = tokenizer
    app[MODEL_KEY] = model_name
    app[MAXLEN_KEY] = max_model_len
    app[LORA_KEY] = dict(lora_adapters or {})
    from llmd_tpu.serve.responses import STORE_KEY, ResponsesStore

    app[STORE_KEY] = ResponsesStore()
    app.add_routes(
        [
            web.get("/health", handle_health),
            web.get("/ready", handle_ready),
            web.get("/v1/models", handle_models),
            web.get("/metrics", handle_metrics),
            web.post("/tokenize", handle_tokenize),
            web.post("/v1/completions", handle_completions),
            web.post("/v1/embeddings", handle_embeddings),
            web.post("/vllm.Generation/Generate", handle_grpc_generate),
            web.post("/vllm.Generation/Embed", handle_grpc_embed),
            web.post("/v1/chat/completions", handle_chat),
            web.post("/v1/completions/render", handle_completions_render),
            web.post("/v1/chat/completions/render", handle_chat_render),
            web.post("/v1/cache/probe", handle_cache_probe),
            web.post("/v1/load_lora_adapter", handle_load_lora_adapter),
            web.post("/v1/unload_lora_adapter", handle_unload_lora_adapter),
            *_responses_routes(),
            web.post("/start_profile", handle_start_profile),
            web.post("/stop_profile", handle_stop_profile),
            web.post("/admin/pause", handle_admin_pause),
            web.post("/admin/resume", handle_admin_resume),
            web.post("/admin/drain", handle_admin_drain),
            web.get("/admin/status", handle_admin_status),
        ]
    )
    if extra_routes:
        app.add_routes(extra_routes)

    async def _start_engine(app: web.Application):
        engine.start(asyncio.get_event_loop())
        # EC-connector pulls (E-disaggregation consumer side).
        app[MM_SESSION_KEY] = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=60, sock_connect=5)
        )
        yield
        await app[MM_SESSION_KEY].close()
        engine.stop()

    app.cleanup_ctx.append(_start_engine)
    return app
