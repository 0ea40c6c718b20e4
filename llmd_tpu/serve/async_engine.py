"""Async bridge between the HTTP front end and the blocking engine loop.

The engine (like vLLM's EngineCore in the reference's model-server layer,
docs/architecture/core/model-servers.md:5-7) steps on a dedicated thread;
request submission and incremental outputs cross the thread boundary through
a lock-guarded inbox and per-request asyncio queues. The asyncio side never
blocks on device work.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator

from llmd_tpu.engine.engine import LLMEngine
from llmd_tpu.engine.request import RequestOutput, SamplingParams
from llmd_tpu.obs import profiling

log = logging.getLogger(__name__)


@dataclass
class _Pending:
    request_id: str
    prompt_token_ids: list[int]
    sampling: SamplingParams
    priority: int = 0
    kv_transfer_params: dict[str, Any] | None = None
    lora_id: int = 0
    lora_name: str = ""
    # Mid-stream failover: the prompt's last N tokens are output already
    # delivered to the client by a dead replica; generation continues at
    # output position N (docs/architecture/fault-tolerance.md).
    resume_output_tokens: int = 0
    # When submit() queued it: the intake counts the wait from here
    # (EngineStats.intake_wait_ms_total).
    submitted_at: float = field(default_factory=time.monotonic)


def _release_pulled(engine, kv_transfer_params) -> None:
    """Release a fetched-but-never-applied bundle riding in
    ``kv_transfer_params["__pulled__"]`` (or abandon an in-flight
    group-stream handle in ``"__stream__"``): a streamed fetch
    pre-allocates pool pages that leak permanently unless every path
    that drops the bundle before apply funnels through here."""
    conn = getattr(engine, "kv_connector", None)
    if conn is None or not kv_transfer_params:
        return
    b = kv_transfer_params.get("__pulled__")
    if b is not None:
        conn.release_bundle(b)
    handle = kv_transfer_params.get("__stream__")
    if handle is not None:
        handle.abandon()


class RequestFailed(Exception):
    """Client-side error (invalid request); maps to HTTP 400."""


class EngineError(Exception):
    """Internal engine failure (device fault, compile error); maps to 500."""


class DeadlineExceeded(EngineError):
    """Per-request deadline elapsed before the stream finished; maps to
    504 (non-streaming) or a terminal error frame (streaming)."""


class WatchdogStalled(EngineError):
    """The engine step loop blew past the watchdog budget: the device
    program (or a collective peer) is wedged. In-flight streams get this
    as a terminal frame instead of hanging forever."""


class AsyncEngine:
    """Runs an LLMEngine on a background thread with an asyncio surface."""

    def __init__(
        self, engine: LLMEngine, watchdog_s: float | None = None
    ) -> None:
        self.engine = engine
        # Step watchdog: last-step-heartbeat liveness for the engine
        # thread. A step outliving the budget means the device program
        # (or a lockstep peer) is wedged — /health flips 503 and every
        # in-flight stream gets a terminal WatchdogStalled frame instead
        # of hanging until the client gives up. 0/None disables.
        if watchdog_s is None:
            try:
                watchdog_s = float(
                    os.environ.get("LLMD_STEP_WATCHDOG_S", "0") or 0
                )
            except ValueError:
                watchdog_s = 0.0
        self.watchdog_s = watchdog_s or 0.0
        self._step_started: float | None = None
        self.last_step_done = time.monotonic()
        # The FIRST step carries jit compilation (seconds to minutes on a
        # cold cache) — that's the startup probe's domain, not a wedge.
        # The watchdog arms once one step has completed.
        self._steps_done = 0
        self._stall_flagged = False
        self._watchdog_task: asyncio.Task | None = None
        # Graceful-shutdown readiness: flipped by drain() so /ready goes
        # 503 before the gateway sees connection errors.
        self.draining = False
        self._lock = threading.Condition()
        self._inbox: list[_Pending] = []  # llmd: guarded_by(_lock)
        self._aborts: list[str] = []  # llmd: guarded_by(_lock)
        self._stop = False  # llmd: guarded_by(_lock)
        # IRO pause gate (proposals/inference-resilience-operator.md): a
        # paused engine stops stepping entirely — in-flight sequences stay
        # scheduled with their KV intact and continue on resume. Used to
        # quiesce the device before a RESET_DEVICE / REBOOT_NODE action.
        self._paused = False  # llmd: guarded_by(_lock)
        self._loop: asyncio.AbstractEventLoop | None = None
        # request_id -> asyncio.Queue of RequestOutput | Exception
        self._subs: dict[str, asyncio.Queue] = {}  # llmd: guarded_by(_lock)
        self._thread: threading.Thread | None = None
        # P/D fetch pool (see generate): owning the concurrent futures is
        # what makes abandoned-fetch cleanup possible. Sized like the
        # default loop executor — fetches block in pull_wait for long
        # stretches, so a small cap would head-of-line-block TTFT under
        # concurrent prefill handoffs.
        import concurrent.futures

        self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(32, (os.cpu_count() or 1) + 4),
            thread_name_prefix="llmd-kv-fetch",
        )

    # ------------------------------------------------------------------ #

    def start(self, loop: asyncio.AbstractEventLoop | None = None) -> None:
        self._loop = loop or asyncio.get_event_loop()
        self._thread = threading.Thread(
            target=self._run, name="llmd-engine", daemon=True
        )
        self._thread.start()
        if self.watchdog_s and self._loop.is_running():
            self._watchdog_task = self._loop.create_task(
                self._watchdog_loop()
            )

    def stop(self) -> None:
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            self._watchdog_task = None
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._fetch_pool.shutdown(wait=False, cancel_futures=True)

    # ------------------------------------------------------------------ #
    # step watchdog (liveness for the engine thread)

    @property
    def stalled(self) -> bool:
        """True while the current step has outlived the watchdog budget
        (warmed engines only: the first step's jit compile is startup-
        probe territory)."""
        if not self.watchdog_s or not self._steps_done:
            return False
        t0 = self._step_started
        return t0 is not None and time.monotonic() - t0 > self.watchdog_s

    @property
    def ready(self) -> bool:
        """Readiness (vs /health liveness): engine thread up, stepping
        within budget, not paused, not draining."""
        return (
            self._thread is not None
            and self._thread.is_alive()
            # llmd: allow(concurrency) -- single atomic bool read for a health probe; a probe racing pause() legitimately reports either state
            and not self._paused
            and not self.draining
            and not self.stalled
        )

    async def _watchdog_loop(self) -> None:
        period = max(self.watchdog_s / 4.0, 0.05)
        while True:
            await asyncio.sleep(period)
            if not self.stalled:
                continue
            if not self._stall_flagged:
                self._stall_flagged = True
                self.engine.stats.engine_watchdog_stalls_total += 1
                log.error(
                    "engine step watchdog: step running > %.1fs; failing "
                    "in-flight streams and turning /health 503",
                    self.watchdog_s,
                )
            # Terminal frames for every in-flight stream; their engine-
            # side sequences are queued for abort so a recovering thread
            # doesn't keep burning device time on abandoned requests.
            with self._lock:
                subs, self._subs = dict(self._subs), {}
                self._aborts.extend(subs)
                self._lock.notify_all()
            err = WatchdogStalled(
                f"engine step exceeded the {self.watchdog_s}s watchdog "
                "budget; the engine is wedged"
            )
            for q in subs.values():
                q.put_nowait(err)

    @property
    def stats(self):
        return self.engine.stats

    # ------------------------------------------------------------------ #
    # IRO engine-coordination surface

    @property
    def paused(self) -> bool:
        # llmd: allow(concurrency) -- single atomic bool read; IRO polls this, and racing a concurrent pause() legitimately returns either side
        return self._paused

    def pause(self) -> None:
        with self._lock:
            self._paused = True
            self._lock.notify_all()

    def resume(self) -> None:
        with self._lock:
            self._paused = False
            self.draining = False  # a resumed engine serves again
            self._lock.notify_all()

    async def drain(self, timeout_s: float = 60.0) -> bool:
        """Wait until no requests are in flight (queued or running).
        New submissions keep being accepted; callers gate those upstream
        — /ready flips 503 HERE so the gateway stops routing before the
        engine goes away (resume() re-readies after maintenance)."""
        self.draining = True
        deadline = asyncio.get_running_loop().time() + timeout_s
        while asyncio.get_running_loop().time() < deadline:
            with self._lock:
                idle = not self._inbox and not self.engine.has_work()
            if idle:
                return True
            await asyncio.sleep(0.05)
        return False

    # ------------------------------------------------------------------ #

    def submit(
        self,
        request_id: str,
        prompt_token_ids: list[int],
        sampling: SamplingParams,
        priority: int = 0,
        kv_transfer_params: dict[str, Any] | None = None,
        lora_id: int = 0,
        lora_name: str = "",
        resume_output_tokens: int = 0,
    ) -> asyncio.Queue:
        """Queue a request for the engine thread; returns its output queue."""
        q: asyncio.Queue = asyncio.Queue()
        with self._lock:
            if request_id in self._subs:
                raise RequestFailed(f"duplicate request id {request_id}")
            self._subs[request_id] = q
            self._inbox.append(
                _Pending(request_id, prompt_token_ids, sampling, priority,
                         kv_transfer_params, lora_id, lora_name,
                         resume_output_tokens)
            )
            self._lock.notify_all()
        return q

    async def embed(
        self,
        prompts: list[list[int]],
        lora_id: int = 0,
        lora_name: str = "",
    ):
        """Pooled embeddings off the event loop (the forward runs on an
        executor thread; params are read-only so it coexists with the
        step thread)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None,
            functools.partial(self.engine.embed, prompts, lora_id, lora_name),
        )

    async def load_adapter(self, name: str, source: str = "") -> None:
        """Runtime adapter registration (/v1/load_lora_adapter): the
        fetch + lockstep slot install run on an executor thread — the
        event loop and the step thread never block on the weight
        transfer (docs/architecture/multi-tenant-lora.md)."""
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, functools.partial(self.engine.load_adapter, name, source)
        )

    async def unload_adapter(self, name: str) -> None:
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, functools.partial(self.engine.unload_adapter, name)
        )

    def abort(self, request_id: str) -> None:
        with self._lock:
            self._subs.pop(request_id, None)
            self._aborts.append(request_id)
            self._lock.notify_all()

    async def generate(
        self,
        request_id: str,
        prompt_token_ids: list[int],
        sampling: SamplingParams,
        priority: int = 0,
        kv_transfer_params: dict[str, Any] | None = None,
        lora_id: int = 0,
        lora_name: str = "",
        deadline_s: float | None = None,
        resume_output_tokens: int = 0,
    ) -> AsyncIterator[RequestOutput]:
        """Async stream of incremental outputs until the request finishes.

        ``deadline_s`` bounds the WHOLE request (fetch included): when it
        elapses the stream raises :class:`DeadlineExceeded` and the
        engine-side sequence is aborted — a wedged or starved engine can
        slow requests down, but never hold a caller hostage."""
        deadline = (
            None if deadline_s is None else time.monotonic() + deadline_s
        )
        # P/D consumer: run the (potentially slow) remote-KV pull on an
        # executor so it never blocks the engine step thread or the event
        # loop; the engine thread only applies the pre-fetched bundle.
        conn = getattr(self.engine, "kv_connector", None)
        if conn is not None and conn.streaming_import(kv_transfer_params):
            # Group-streamed import (v3 wire): the fetch thread scatters
            # each layer group into batch-allocated pool pages as it
            # lands; submit the request the moment the FIRST group is
            # resident, so engine admission, scheduling, and host
            # staging overlap the rest of the wire transfer. The engine
            # parks the request and finalizes when the stream resolves
            # (apply on success, local recompute on failure).
            handle = conn.make_stream_handle(kv_transfer_params)
            loop = asyncio.get_running_loop()
            admittable = asyncio.Event()
            # Signal the loop directly from the fetch thread: no thread
            # is parked for the wait, so a burst of concurrent streamed
            # imports cannot exhaust the default executor.
            handle.on_first_group = functools.partial(
                loop.call_soon_threadsafe, admittable.set
            )

            def _fetch_streamed() -> None:
                try:
                    conn.fetch_remote_policy(
                        list(prompt_token_ids), kv_transfer_params, handle
                    )
                finally:
                    # Policy='recompute' never raises, but an unexpected
                    # failure mode must not leave the parked request
                    # waiting forever — fail() degrades it to recompute.
                    if not handle.done.is_set():
                        handle.fail("streamed fetch died unresolved")

            self._fetch_pool.submit(_fetch_streamed)
            try:
                if deadline is None:
                    await admittable.wait()
                else:
                    try:
                        await asyncio.wait_for(
                            admittable.wait(),
                            max(deadline - time.monotonic(), 0.001),
                        )
                    except asyncio.TimeoutError:
                        pass  # surfaced via the is_set() check below
            except asyncio.CancelledError:
                handle.abandon()
                raise
            if not handle.first_group.is_set():
                # Deadline elapsed before the first group landed; the
                # fetch keeps running and the abandon hook frees its
                # stream-reserved pages whenever it resolves.
                handle.abandon()
                raise DeadlineExceeded(
                    f"request deadline of {deadline_s}s exceeded during "
                    "remote KV stream"
                )
            kv_transfer_params = {**kv_transfer_params, "__stream__": handle}
        elif conn is not None and conn.wants_import(kv_transfer_params):
            # Submitted on OUR executor so the CONCURRENT future is in
            # hand: cancelling the awaiting task cancels only the
            # asyncio wrapper (which then DISCARDS the executor's real
            # result), so cleanup must attach to the concurrent future —
            # it alone still observes the fetched bundle whose streamed
            # multi-host fetch pre-allocated pool pages.
            cfut = self._fetch_pool.submit(
                conn.fetch_remote_policy,
                list(prompt_token_ids), kv_transfer_params,
            )
            def _release(f):
                try:
                    b = f.result()
                # llmd: allow(broad-except) -- done-callback probe: a failed fetch has no bundle to release
                except BaseException:
                    return  # fetch failed/cancelled: nothing to free
                _release_pulled(self.engine, {"__pulled__": b})

            try:
                if deadline is None:
                    bundle = await asyncio.wrap_future(cfut)
                else:
                    # The deadline bounds the FETCH too: a slow/absent
                    # producer must not hold the caller past it. The
                    # executor's real fetch keeps running after the
                    # timeout; the release callback frees its stream-
                    # reserved pages when it eventually lands.
                    try:
                        bundle = await asyncio.wait_for(
                            asyncio.wrap_future(cfut),
                            max(deadline - time.monotonic(), 0.001),
                        )
                    except asyncio.TimeoutError:
                        cfut.add_done_callback(_release)
                        raise DeadlineExceeded(
                            f"request deadline of {deadline_s}s exceeded "
                            "during remote KV fetch"
                        ) from None
            except asyncio.CancelledError:
                cfut.add_done_callback(_release)
                raise
            except DeadlineExceeded:
                raise
            except Exception as e:  # KVLoadError under policy='fail'
                raise EngineError(f"remote KV load failed: {e}") from e
            kv_transfer_params = {**kv_transfer_params, "__pulled__": bundle}
        try:
            q = self.submit(request_id, prompt_token_ids, sampling, priority,
                            kv_transfer_params, lora_id, lora_name,
                            resume_output_tokens)
        except Exception:
            # A bundle that never reaches apply must release its pages.
            _release_pulled(self.engine, kv_transfer_params)
            raise
        try:
            while True:
                if deadline is None:
                    item = await q.get()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise DeadlineExceeded(
                            f"request deadline of {deadline_s}s exceeded"
                        )
                    try:
                        item = await asyncio.wait_for(q.get(), remaining)
                    except asyncio.TimeoutError:
                        raise DeadlineExceeded(
                            f"request deadline of {deadline_s}s exceeded"
                        ) from None
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.finished:
                    return
        finally:
            with self._lock:
                # Identity check: only abort OUR registration — the id may
                # have finished and been reused by a newer request.
                if self._subs.get(request_id) is q:
                    # Consumer bailed early (client disconnect): abort.
                    self._subs.pop(request_id, None)
                    self._aborts.append(request_id)
                    self._lock.notify_all()

    # ------------------------------------------------------------------ #

    def _deliver(self, request_id: str, item) -> None:
        # Engine-thread side of the _subs registry. The get/pop pair
        # must hold the lock: the loop thread concurrently registers
        # (submit), deregisters-and-aborts (generate's finally, with an
        # identity check this pop must be ordered against), and swaps
        # the whole dict (watchdog) — an unlocked pop here could race a
        # same-id resubmit and silently drop the NEW stream's queue.
        with self._lock:
            q = self._subs.get(request_id)
            if q is None:
                return
            if isinstance(item, RequestOutput) and item.finished:
                self._subs.pop(request_id, None)
        assert self._loop is not None
        self._loop.call_soon_threadsafe(q.put_nowait, item)

    def _intake(self) -> int:
        """Hand the engine what arrived since the last call: aborts, then
        new requests. Engine-thread only: between two steps (``_run``) and,
        as ``LLMEngine.intake_hook``, from inside the pipelined step while
        the device runs, so that an arrival during step N rides step N+1.
        Returns how many of either there were."""
        with self._lock:
            if not self._inbox and not self._aborts:
                return 0
            pending, self._inbox = self._inbox, []
            aborts, self._aborts = self._aborts, []
        if pending:
            now, stats = time.monotonic(), self.engine.stats
            stats.intake_wait_ms_total += sum(
                now - p.submitted_at for p in pending
            ) * 1e3
            stats.intake_requests_total += len(pending)
        with profiling.span("llmd.serve.intake", added=len(pending)):
            for rid in aborts:
                self.engine.abort_request(rid)
            for p in pending:
                try:
                    self.engine.add_request(
                        p.prompt_token_ids,
                        p.sampling,
                        request_id=p.request_id,
                        priority=p.priority,
                        kv_transfer_params=p.kv_transfer_params,
                        lora_id=p.lora_id,
                        lora_name=p.lora_name,
                        resume_output_tokens=p.resume_output_tokens,
                    )
                # llmd: allow(broad-except) -- surfaced: the caller receives it as a RequestFailed terminal item
                except Exception as e:  # validation errors -> caller
                    _release_pulled(self.engine, p.kv_transfer_params)
                    self._deliver(p.request_id, RequestFailed(str(e)))
        return len(pending) + len(aborts)

    def _wait_for_work_locked(self) -> None:
        """Sleep on the lock while the engine is paused or has nothing to
        run, each sleep under the span that says which: ``llmd.serve.idle``
        (no inbox, no aborts, no work; summed into ``engine_idle_ms_total``)
        and ``llmd.serve.paused`` are the only spans under which the chip
        is idle for want of load."""
        stats = self.engine.stats
        while not self._stop:
            if self._paused:
                with profiling.span("llmd.serve.paused"):
                    self._lock.wait()
            elif self._inbox or self._aborts or self.engine.has_work():
                return
            else:
                t = time.monotonic()
                with profiling.span("llmd.serve.idle"):
                    self._lock.wait()
                stats.engine_idle_ms_total += (time.monotonic() - t) * 1e3

    def _run(self) -> None:
        self.engine.intake_hook = self._intake
        while True:
            with self._lock:
                self._wait_for_work_locked()
                if self._stop:
                    # Queued entries die with the loop — their fetched
                    # bundles (stream-reserved pool pages) must not.
                    for p in self._inbox:
                        _release_pulled(self.engine, p.kv_transfer_params)
                    self._inbox = []
                    self.engine.intake_hook = None
                    return
            self._intake()
            if not self.engine.has_work():
                continue
            try:
                # Watchdog heartbeat brackets the one blocking call.
                self._step_started = time.monotonic()
                outputs = self.engine.step()
            # llmd: allow(broad-except) -- surfaced: every subscriber receives the EngineError as a terminal item (HTTP 500)
            except Exception:
                log.exception("engine step failed")
                with self._lock:
                    subs = list(self._subs)
                for rid in subs:
                    self._deliver(rid, EngineError("engine step failed"))
                continue
            finally:
                self._step_started = None
                self.last_step_done = time.monotonic()
                self._steps_done += 1
                self._stall_flagged = False
            with profiling.span("llmd.serve.deliver", outputs=len(outputs)):
                stats, t_read = self.engine.stats, self.engine.last_readback_at
                for out in outputs:
                    self._deliver(out.request_id, out)
                    stats.deliver_lag_ms_total += (time.monotonic() - t_read) * 1e3
                stats.outputs_delivered_total += len(outputs)
