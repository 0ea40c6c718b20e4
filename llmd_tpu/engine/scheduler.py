"""Continuous-batching scheduler with chunked prefill and prefix caching.

This is the engine-side scheduler (the reference delegates it to vLLM's
continuous batching; docs/architecture/core/model-servers.md:5-7), distinct
from the EPP *request* scheduler in ``llmd_tpu.epp``. Every engine step it
selects a token budget's worth of work: one token per running decode
sequence, plus prompt chunks for waiting/prefilling sequences (chunked
prefill so long prompts never starve decodes -- the reference's
--max-num-batched-tokens / --long-prefill-token-threshold pattern,
guides/agentic-serving/modelserver/tpu/vllm/patch-vllm.yaml:39).

Preemption is recompute-style: when KV pages run out, the youngest running
sequence is evicted, its pages freed, and it restarts from the waiting queue
(its generated tokens are folded into the prompt).
"""

from __future__ import annotations

import bisect
import dataclasses
import time

from llmd_tpu.config import CacheConfig, SchedulerConfig
from llmd_tpu.engine.kv_cache import (
    NoFreePagesError,
    PageAllocator,
    _ROOT_HASH,
    hash_page,
    page_hashes_for_tokens,
)
from llmd_tpu.engine.request import FinishReason, Request, RequestStatus
from llmd_tpu.engine.sampler import accept_draft_tokens

# The points a retained-state capture is taken at (``capture_dispatched``).
AT_PROMPT_END, AT_RUN_END, AT_FINISH = "prompt_end", "run_end", "finish"


def token_slot_count(max_num_seqs: int) -> int:
    """Entries of the runner's ``last_tokens``: one a running request, and
    one a request that ended under a step still in flight (it keeps its
    entry, with its pages, until that step has landed, while its row
    already runs another request)."""
    return 2 * max_num_seqs


@dataclasses.dataclass
class ScheduledSeq:
    request: Request
    num_tokens: int  # tokens to compute for this seq in this step
    # Speculative decoding: None for non-speculative rows; a (possibly
    # empty) draft for spec decode rows. The scheduler PLANS with the
    # max-acceptance count (num_tokens = 1 + spec_ngram_k, pages
    # included) and the engine fills the actual draft — at most
    # num_tokens - 1 tokens — at dispatch time from committed history,
    # which is what lets async staging reuse its existing
    # speculate/rollback machinery unchanged.
    draft_tokens: list[int] | None = None

    @property
    def start_pos(self) -> int:
        """Where the row starts, asked before its own dispatch is noted:
        behind what is committed and what is still in flight."""
        return self.request.num_dispatched_tokens


@dataclasses.dataclass
class ScheduledBatch:
    prefills: list[ScheduledSeq]
    decodes: list[ScheduledSeq]

    @property
    def seqs(self) -> list[ScheduledSeq]:
        return self.prefills + self.decodes

    @property
    def total_tokens(self) -> int:
        return sum(s.num_tokens for s in self.seqs)

    @property
    def is_empty(self) -> bool:
        return not self.prefills and not self.decodes


class EngineScheduler:
    def __init__(
        self,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        allocator: PageAllocator,
        max_model_len: int,
        swa_allocator: PageAllocator | None = None,
        swa_ring_pages: int = 0,
        swa_chunk_tokens: int = 0,
        state_aligned: bool = False,
    ) -> None:
        self.config = scheduler_config
        self.cache_config = cache_config
        self.allocator = allocator
        # Ring pool for sliding-window layers (CacheConfig.swa_ring): each
        # admitted sequence holds a fixed ring of ``swa_ring_pages`` pages
        # reused circularly, independent of sequence length. Per-seq
        # prefill chunks are capped at ``swa_chunk_tokens`` (the span R is
        # sized for); the BATCH budget may be larger.
        self.swa_allocator = swa_allocator
        self.swa_ring_pages = swa_ring_pages
        self.swa_chunk_tokens = swa_chunk_tokens
        # A model with state-space layers: the "ring" is ONE slot of the
        # state pool (config.StateSlotSpec), allocated, reclaimed and freed
        # as a ring is. A recurrent state can be retained only AT the token
        # it stands at, so a prefill chunk ENDS at the page boundaries a
        # snapshot is wanted at (_chunk_for) and the capture hooks fire when
        # the computed count stands there, not when it has passed.
        self.state_aligned = state_aligned
        self.max_model_len = max_model_len
        # Does the engine step pipelined (a staged batch may be planned
        # against dispatched positions)? The engine says, from its role.
        self.pipelined = True
        # Ordered by (-priority, arrival_time): higher priority first, FCFS
        # within a priority class (the InferenceObjective priority semantics,
        # reference docs/api-reference/*.md).
        self.waiting: list[Request] = []
        self.running: list[Request] = []
        self.num_preemptions = 0
        # Queue wait, taken at a request's FIRST admission (one that
        # comes back from preemption is not counted again): the sums
        # behind EngineStats.queue_wait_ms_total / queue_admitted_total.
        self.queue_wait_ms = 0.0
        self.queue_admitted = 0
        # request_id -> committed page hash chain tail + count
        self._chain: dict[str, tuple[bytes, int]] = {}
        # Called with the finished Request before its pages are released
        # (P/D producer KV export point).
        self.finish_hook = None
        # Ring engines: ``capture_hook(req, point)``, called behind the
        # DISPATCH of the step that leaves ``req``'s per-sequence state at
        # a capture point (``capture_dispatched``), where hybrid APC
        # retains a copy of it: AT_PROMPT_END (the chunk that completes
        # the prompt: behind it the ring holds the prompt's trailing
        # window), AT_RUN_END (the chunk that carries the request past
        # the span it noted in ``Request.swa_capture``; the ring then holds
        # the window before any page boundary of the chunk) and AT_FINISH
        # (the decode step that fills the last page before a finish by
        # length: ``_finish_boundary``).
        self.capture_hook = None
        # Ring engines: the hybrid prefix hit, taken at admission
        # (_apply_prefix_cache); fills the request's pages, ring and
        # computed count like a locally-sourced preload, or notes the miss.
        self.hybrid_hit_hook = None
        # Called when a ring allocation fails: frees idle retained
        # sections (hybrid APC) so live sequences outrank retention.
        # Returns True if anything was freed (retry the allocation).
        self.ring_pressure_hook = None
        # Decode-time KV pager (engine/pager.py): called with a
        # preemption victim before the recompute release. Returns the
        # number of tokens preserved in the host tier (the victim
        # resumes from there instead of recomputing from zero), or 0
        # when the victim was not parked (fall through to recompute).
        self.park_hook = None
        # Async stepping: request ids whose pages the in-flight device
        # programs still read/write — preemption must never evict them
        # (their pages would be freed under the device's feet). Sync
        # engines leave this empty.
        self.protected: set[str] = set()
        # ``Request.token_slot``s not handed out (lowest first).
        self._token_slots = list(
            reversed(range(token_slot_count(scheduler_config.max_num_seqs)))
        )
        # Rows that landed for a request which had ended meanwhile (a stop
        # token or an abort the schedule could not foresee, met at the
        # commit of the step before, with the row already dispatched):
        # EngineStats.async_wasted_rows_total.
        self.wasted_rows = 0
        # Speculative decoding (SchedulerConfig.speculative_ngram):
        # decode rows are planned at the max-acceptance token count
        # (1 + spec_k) and the accepted prefix is resolved per row at
        # update_after_step; the counters feed EngineStats / the bench.
        self.spec_k = (
            scheduler_config.spec_ngram_k
            if scheduler_config.speculative_ngram else 0
        )
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        # Accepted-draft-length histogram over spec decode rows: index j
        # counts (row, step) pairs that accepted exactly j draft tokens.
        self.spec_accept_len_hist = [0] * (self.spec_k + 1)
        # Global draft-backoff clock: rows whose last drafts were fully
        # rejected retry only on steps aligned to a power-of-two of this
        # counter, so retries CLUSTER on the same steps (one mixed
        # verify+decode step per retry wave) instead of every step
        # paying the mixed-dispatch cost for one stray drafting row.
        self.spec_step = 0
        # Batch serving tier (SchedulerConfig.batch_backfill;
        # docs/architecture/batch-processing.md): rows at or below
        # PriorityClass.BATCH backfill headroom only. Counters feed
        # EngineStats (batch_tokens_total / batch_preemptions_total /
        # batch_backfill_utilization).
        self.batch_tokens = 0
        self.num_batch_preemptions = 0
        # Batch tokens the LAST schedule() planned (the per-step
        # backfill-utilization gauge's numerator).
        self.last_batch_backfill_tokens = 0

    # ------------------------------------------------------------------ #
    # queue management

    def add_request(self, request: Request) -> None:
        request.status = RequestStatus.WAITING
        bisect.insort(
            self.waiting, request, key=lambda r: (-r.priority, r.arrival_time)
        )

    def abort_request(self, request_id: str) -> Request | None:
        for req in self.running:
            if req.request_id == request_id:
                self._retire(req)
                req.finish(FinishReason.ABORT)
                return req
        for req in list(self.waiting):
            if req.request_id == request_id:
                self.waiting.remove(req)
                self._release(req)
                req.finish(FinishReason.ABORT)
                return req
        return None

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ------------------------------------------------------------------ #
    # scheduling

    def schedule(self) -> ScheduledBatch:
        """Select the next batch.

        All position math uses ``num_dispatched_tokens`` (committed +
        in-flight), so the same code path serves both modes: in sync
        engines nothing is ever pending and dispatched == computed; in
        async engines this IS the speculative schedule — the next batch
        is planned assuming every in-flight row lands its tokens, and a
        late finish (EOS/max-tokens at reconcile) invalidates the
        affected staged rows (engine-side rollback).
        """
        budget = self.config.max_num_batched_tokens
        decodes: list[ScheduledSeq] = []
        prefills: list[ScheduledSeq] = []
        scheduled: set[str] = set()

        decoding = [r for r in self.running if r.in_decode_dispatched]
        mid_prefill = [r for r in self.running if not r.in_decode_dispatched]
        # Batch band (PriorityClass.BATCH, SchedulerConfig.batch_backfill):
        # batch rows are split OUT of the interactive phases and only
        # backfill whatever budget/pages those phases leave — the
        # interactive half of this method never sees them, which is what
        # makes interactive streams byte-identical batch-on vs batch-off.
        batch_decoding: list[Request] = []
        batch_prefill: list[Request] = []
        if self._batch_band:
            batch_decoding = [r for r in decoding if r.is_batch]
            batch_prefill = [r for r in mid_prefill if r.is_batch]
            if batch_decoding:
                decoding = [r for r in decoding if not r.is_batch]
            if batch_prefill:
                mid_prefill = [r for r in mid_prefill if not r.is_batch]
        in_backfill = bool(batch_decoding or batch_prefill)

        # Fused K-step decode windows apply whenever this step cannot make
        # admission progress anyway (no admissible waiting request, no
        # in-flight prompt chunks) -- in particular in the saturated regime
        # (running == max_num_seqs with a backlog), which is exactly where
        # the dispatch amortization pays off. Otherwise K=1 keeps admission
        # latency at one step. K is uniform across the batch (one compiled
        # program) and capped so no seq can run past max_model_len.
        # Batch-backfill steps pin K=1: batch rows ride the same program
        # at one-token width (a K-token fused commitment would have to be
        # unwound the moment interactive load preempts them), and a
        # uniform-K dispatch cannot mix widths. Speculative engines pin
        # K=1 too: every step is a one-shot verify.
        window = self.config.decode_window
        can_admit = bool(self.waiting) and len(self.running) < self.config.max_num_seqs
        k = 1
        if (
            not self.spec_k and window > 1 and decoding and not mid_prefill
            and not can_admit and not in_backfill
        ):
            k = max(
                1,
                min(
                    window,
                    min(
                        self.max_model_len - r.num_dispatched_tokens
                        for r in decoding
                    ),
                ),
            )

        if self.spec_k and decoding:
            self.spec_step += 1

        # 1. Decodes claim pages FIRST: a running decode must never be
        #    starved by prefill admission taking the last free pages.
        for req in decoding:
            if (
                req.status is not RequestStatus.RUNNING
                or not req.in_decode_dispatched
            ):
                continue  # reset by a preemption earlier in this loop
            if self._ends_in_flight(req):
                continue  # its last tokens are on the device already
            if budget <= 0:
                break
            if self.spec_k:
                # Speculative rows plan (budget, pages, pending counts)
                # at the MAX-acceptance count; the actual draft — capped
                # at num_tokens - 1 — is proposed at dispatch, so the
                # planned slots always cover its provisional KV writes.
                # Backed-off rows (consecutive full rejections) plan as
                # plain rows until their aligned retry step.
                cap = self.max_model_len - req.num_dispatched_tokens
                k_row = 1
                if self._spec_eligible(req):
                    k_row += max(0, min(self.spec_k, cap - 1))
            else:
                k_row = k
            if not self._ensure_pages(req, k_row):
                # Never evict a sequence already placed in this step's batch:
                # its pages would be freed while the runner still writes them.
                if not self._preempt_for(req, exclude=scheduled):
                    continue
                if not self._ensure_pages(req, k_row):
                    continue
            decodes.append(
                ScheduledSeq(
                    req, k_row,
                    draft_tokens=[] if self.spec_k else None,
                )
            )
            scheduled.add(req.request_id)
            # Drafted positions are real batch compute (the verify step
            # scores 1 + draft tokens for the row), so speculative rows
            # charge their planned width; plain decodes stay at 1.
            budget -= k_row if self.spec_k else 1

        # 2. Continue chunked prefills of already-running sequences.
        for req in mid_prefill:
            if req.status is not RequestStatus.RUNNING or budget <= 0:
                continue
            chunk = self._chunk_for(
                req, req.num_prompt_tokens - req.num_dispatched_tokens, budget
            )
            if chunk <= 0:
                continue
            if not self._ensure_pages(req, chunk):
                continue
            prefills.append(ScheduledSeq(req, chunk))
            scheduled.add(req.request_id)
            budget -= chunk

        # 3. Admit waiting sequences (priority order, FCFS within class).
        budget = self._admit_waiting(prefills, scheduled, budget)

        # 4. Batch backfill: rows at or below PriorityClass.BATCH harvest
        #    whatever token budget and pages the interactive phases left.
        if self._batch_band and budget > 0:
            budget = self._schedule_batch_backfill(
                batch_decoding, batch_prefill, decodes, prefills,
                scheduled, budget,
            )
        self.last_batch_backfill_tokens = sum(
            s.num_tokens
            for s in (*prefills, *decodes)
            if s.request.is_batch
        )

        return ScheduledBatch(prefills=prefills, decodes=decodes)

    def top_up(
        self, batch: ScheduledBatch, in_flight: bool
    ) -> list[ScheduledSeq]:
        """Admission into a batch that is staged and not yet dispatched
        (the pipelined step): what ``schedule()`` would have admitted from
        ``waiting`` had it been there, under the token budget and the rows
        ``batch`` left. Returns the prefill rows added; ``batch`` itself is
        not changed. A staged fused decode window is left alone (its K was
        chosen because nothing could be admitted), and new batch-band rows
        wait for the next schedule: they are no one's latency. With nothing
        ``in_flight`` (after the readback) a staged batch-band row is still
        what an interactive head reclaims slots and pages from, as it would
        be behind a synchronous step: the caller drops the rows that are no
        longer running."""
        if not self.waiting or any(
            s.draft_tokens is None and s.num_tokens != 1
            for s in batch.decodes
        ):
            return []
        added: list[ScheduledSeq] = []
        self._admit_waiting(
            added,
            {
                s.request.request_id for s in batch.seqs
                if in_flight or not s.request.is_batch
            },
            self.config.max_num_batched_tokens - batch.total_tokens,
        )
        return added

    def _admit_waiting(
        self, prefills: list[ScheduledSeq], scheduled: set[str], budget: int
    ) -> int:
        """Admit waiting sequences into ``prefills`` under ``budget``
        (priority order, FCFS within class); returns the budget left.
        Interactive only: batch-band heads defer to the backfill
        phase, and an interactive head blocked on slots or
        pages reclaims them from RUNNING batch rows first (the
        "preempted the moment interactive load returns" half of the
        backfill contract — recompute-preemption frees the victims'
        provisional pages immediately). ``scheduled``: the rows already
        placed in this step's batch, never victims."""
        while self.waiting and budget > 0:
            req = self.waiting[0]
            if req.kv_fetch_pending:
                # Parked by the pager and its attention window is not
                # yet resident again — a wait state, not a fault. The
                # pager's pump retries the restore each step; admission
                # stays FCFS behind it.
                break
            if self._batch_band and req.is_batch:
                break  # backfill phase owns batch admission
            if self._rows_taken() >= self.config.max_num_seqs:
                # A running batch row's slot yields to an interactive
                # admission; without batch victims the step is full.
                if not (
                    self._batch_band
                    and self._preempt_for(req, exclude=scheduled,
                                          batch_only=True)
                ):
                    break
                continue
            chain = None
            if req.num_computed_tokens == 0:
                chain = self._apply_prefix_cache(req)
            remaining = req.num_prompt_tokens - req.num_dispatched_tokens
            chunk = self._chunk_for(req, remaining, budget)
            if chunk <= 0:
                break
            if not self.config.enable_chunked_prefill and chunk < remaining:
                break  # whole-prompt admission only
            if not self._ensure_ring(req) and not (
                self._reclaim_waiting_ring(req) and self._ensure_ring(req)
            ):
                break  # out of ring pages; retry next step
            if not self._ensure_pages_reclaiming_batch(req, chunk, scheduled):
                if chain is not None:
                    # What the prefix cache lent this attempt goes back
                    # (the pages stay cached): held by a request that
                    # still waits, they are what the running rows the
                    # pool is short for cannot get, and no preemption
                    # reaches a waiting request. The next attempt walks
                    # the same chain: not hashed and not counted again.
                    if req.block_ids:
                        self._release(req)
                        req.num_computed_tokens = req.num_cached_tokens = 0
                    req.prefix_hashes = chain
                # Return the ring: a still-waiting request holding R ring
                # pages would break the pool's sizing guarantee and could
                # stall a higher-priority arrival's admission. Safe only
                # while nothing has been computed into it — a PRELOADED
                # ring (P/D import, num_computed > 0) holds transferred
                # sliding-layer KV and must be kept.
                if req.swa_block_ids and req.num_computed_tokens == 0:
                    self.swa_allocator.free(req.swa_block_ids)
                    req.swa_block_ids = []
                    req.swa_table_row = None
                break  # out of pages; retry next step
            self.waiting.pop(0)
            self._note_admitted(req)
            self.running.append(req)
            prefills.append(ScheduledSeq(req, chunk))
            scheduled.add(req.request_id)
            budget -= chunk
        return budget

    def _rows_taken(self) -> int:
        """The rows of ``max_num_seqs`` that are not free for an admission:
        the running requests less those that the step in flight ends for
        certain. The staged step is dispatched before that step's commit
        (``LLMEngine._dispatch_early``), so a row its FORESEEN finish
        frees is given to the speculative schedule, or a waiting request
        would ride one step later than behind a synchronous step. (The
        row: the pages, the ring or state slot of the request that ends
        are free only once the step has landed, and an admission that
        needs them waits for that as before.)"""
        n = len(self.running)
        if n < self.config.max_num_seqs:
            return n  # (the one test of most calls)
        return n - sum(1 for r in self.running if self._ends_in_flight(r))

    def _ends_in_flight(self, req: Request) -> bool:
        """The speculative schedule's one certainty: the step in flight
        ends ``req`` by LENGTH whatever it samples (``max_tokens`` or the
        model length reached by the LEAST it can emit: one token for a
        prompt-completing chunk or a speculative row, the whole window
        for a plain decode row). Such a row is not staged again, so it is
        not rolled back either; a stop token stays a late finish."""
        if not req.num_pending_tokens:
            return False
        least = 1
        if req.in_decode and not self.spec_k:
            least = req.num_pending_tokens
        return (
            req.total_output_tokens + least >= req.sampling.max_tokens
            or req.num_tokens + least >= self.max_model_len
        )

    def _prompt_boundary(self, req: Request) -> int:
        """The prompt's last full page, in tokens (the last token is always
        computed, for its logits)."""
        page = self.cache_config.page_size
        return (req.num_prompt_tokens - 1) // page * page

    def _finish_boundary(self, req: Request) -> int:
        """The last page boundary ``req`` fills before the finish that its
        admission foresees, in tokens, or 0 where a state retained there
        could serve nobody. A request ends by LENGTH at the latest: its
        state then covers ``end`` tokens (the last sampled token is never
        fed). The boundary is worth a capture where it lies behind the
        prompt's own (which is captured anyway) and a continuation still
        fits the model: the ``end + 1`` tokens and one more are a prompt,
        which has to be shorter than the model length. A sequence that has
        filled the model cannot grow, so whoever sends it again sends a
        prompt this boundary is not in (a resident sequence comes back with
        its own prompt), and the entry would only push another's out. A
        stop token may end the request sooner; that is not foreseen, and
        the prompt's-end entry serves the next turn."""
        page = self.cache_config.page_size
        left = req.sampling.max_tokens - req.num_prior_output_tokens
        end = min(req.num_prompt_tokens + left, self.max_model_len) - 1
        at = end // page * page
        if at > self._prompt_boundary(req) and end + 2 < self.max_model_len:
            return at
        return 0

    def _snapshot_boundaries(self, req: Request) -> tuple[int, ...]:
        """The token counts a state-space sequence's prefill must STAND at
        for a snapshot to be taken: its prompt's last full page, and the end
        of the run of full pages it was refused at admission."""
        ends = (self._prompt_boundary(req),)
        if req.swa_capture is not None:
            ends += (req.swa_capture[0] * self.cache_config.page_size,)
        return ends

    def _chunk_for(self, req: Request, remaining: int, budget: int) -> int:
        """The next prefill chunk of ``req``: what is left of its prompt,
        within the step's budget and the ring's chunk cap, and, where the
        per-sequence state is recurrent, not past the next snapshot
        boundary (the <= page - 1 tokens left behind a prompt's last full
        page are one more chunk)."""
        chunk = min(remaining, budget)
        if self.swa_chunk_tokens:
            chunk = min(chunk, self.swa_chunk_tokens)
        if self.state_aligned and self.allocator.enable_prefix_caching:
            at = req.num_dispatched_tokens
            for end in self._snapshot_boundaries(req):
                if at < end:
                    chunk = min(chunk, end - at)
        return chunk

    def _note_admitted(self, req: Request) -> None:
        req.status = RequestStatus.RUNNING
        if req.token_slot < 0:
            req.token_slot = self._token_slots.pop()
        if self.capture_hook is not None:
            req.finish_capture_at = self._finish_boundary(req)
        if req.queue_wait_ms is None:
            req.queue_wait_ms = (time.monotonic() - req.arrival_time) * 1e3
            self.queue_wait_ms += req.queue_wait_ms
            self.queue_admitted += 1

    @property
    def _batch_band(self) -> bool:
        return self.config.batch_backfill

    def _ensure_pages_reclaiming_batch(
        self, req: Request, new_tokens: int, exclude: set[str]
    ) -> bool:
        """_ensure_pages for an INTERACTIVE request, reclaiming pages
        from running batch rows (recompute-preemption, youngest first)
        until the allocation fits or no batch victim remains. With no
        batch rows running this is exactly _ensure_pages."""
        while not self._ensure_pages(req, new_tokens):
            if not (
                self._batch_band
                and self._preempt_for(req, exclude=exclude, batch_only=True)
            ):
                return False
        return True

    def _schedule_batch_backfill(
        self,
        batch_decoding: list[Request],
        batch_prefill: list[Request],
        decodes: list[ScheduledSeq],
        prefills: list[ScheduledSeq],
        scheduled: set[str],
        budget: int,
    ) -> int:
        """The batch band's whole step, run strictly AFTER the
        interactive phases (docs/architecture/batch-processing.md):

        - running batch decodes ride the same dispatch at one-token
          width (never drafting, never windowed — a wider commitment
          would have to be unwound at the next interactive preemption);
        - batch prefill chunks continue with leftover budget;
        - NEW batch rows are admitted only while no interactive request
          is blocked at the queue head, main-pool utilization is at or
          below batch_kv_watermark, and the batch_max_seqs cap (if any)
          has headroom.

        Page pressure inside the band preempts OTHER batch rows only —
        an interactive row is never a victim of batch work."""
        for req in batch_decoding:
            if (
                req.status is not RequestStatus.RUNNING
                or not req.in_decode_dispatched
            ):
                continue  # reset by a preemption earlier in this pass
            if self._ends_in_flight(req):
                continue  # (as an interactive row: its row is given away)
            if budget <= 0:
                break
            if not self._ensure_pages(req, 1):
                if not self._preempt_for(req, exclude=scheduled,
                                         batch_only=True):
                    continue
                if not self._ensure_pages(req, 1):
                    continue
            decodes.append(
                ScheduledSeq(
                    req, 1,
                    # Spec engines: batch rows stay draft-less (their
                    # one-token width leaves no room for a draft), so
                    # acceptance accounting runs but no provisional
                    # verify columns are ever planned for them.
                    draft_tokens=[] if self.spec_k else None,
                )
            )
            scheduled.add(req.request_id)
            budget -= 1
        for req in batch_prefill:
            if req.status is not RequestStatus.RUNNING or budget <= 0:
                continue
            chunk = self._chunk_for(
                req, req.num_prompt_tokens - req.num_dispatched_tokens, budget
            )
            if chunk <= 0:
                continue
            if not self._ensure_pages(req, chunk):
                continue  # wait for headroom; batch never preempts upward
            prefills.append(ScheduledSeq(req, chunk))
            scheduled.add(req.request_id)
            budget -= chunk
        while (
            self.waiting
            and budget > 0
            and len(self.running) < self.config.max_num_seqs
            and self.waiting[0].is_batch
        ):
            if (
                self.config.batch_max_seqs
                and sum(1 for r in self.running if r.is_batch)
                >= self.config.batch_max_seqs
            ):
                break
            if self.allocator.usage() > self.config.batch_kv_watermark:
                break  # pool too hot: admitting would enter the
                # preemption regime interactive rows pay for
            req = self.waiting[0]
            if req.num_computed_tokens == 0:
                self._apply_prefix_cache(req)
            remaining = req.num_prompt_tokens - req.num_dispatched_tokens
            chunk = self._chunk_for(req, remaining, budget)
            if chunk <= 0:
                break
            if not self.config.enable_chunked_prefill and chunk < remaining:
                break  # whole-prompt admission only
            if not self._ensure_ring(req):
                break  # rings are interactive capacity: never reclaimed
            if not self._ensure_pages(req, chunk):
                if req.swa_block_ids and req.num_computed_tokens == 0:
                    self.swa_allocator.free(req.swa_block_ids)
                    req.swa_block_ids = []
                    req.swa_table_row = None
                break  # out of pages; retry next step
            self.waiting.pop(0)
            self._note_admitted(req)
            self.running.append(req)
            prefills.append(ScheduledSeq(req, chunk))
            scheduled.add(req.request_id)
            budget -= chunk
        return budget

    @staticmethod
    def _hash_extra(req: Request) -> bytes:
        """Cache-identity discriminator: LoRA-adapted KV (v is adapted)
        must never be shared across adapters or with the base model
        (reference kv-indexer.md:145-151 key folding)."""
        if not req.lora_id:
            return b""
        # Salt by NAME (stable across engine processes and the router's
        # token-producer, which folds `lora:<model>`). Unnamed requests
        # salt in a DISTINCT namespace: a digit-only adapter name must
        # never collide with a raw slot id.
        if req.lora_name:
            return f"lora:{req.lora_name}".encode()
        return f"lora-slot:{req.lora_id}".encode()

    def _apply_prefix_cache(self, req: Request) -> list[bytes] | None:
        """Reuse cached full pages covering the prompt prefix. Returns the
        hash chain it looked up in the main pool (an admission that then
        fails for want of pages keeps it for the next attempt)."""
        if req.block_ids:
            return None
        if self.swa_ring_pages:
            # Ring engines do HYBRID hits only: a full-pool hit is usable
            # solely when a retained sliding section seeds the fresh ring
            # (engine RetainedStateCache) — a bare full-pool shortcut here
            # would skip sliding-layer KV the ring never got and silently
            # decode garbage. A noted miss is not probed again.
            if self.hybrid_hit_hook is not None and req.swa_capture is None:
                self.hybrid_hit_hook(req)
            return None
        # Never satisfy the *entire* prompt from cache: the last token must be
        # computed so the step emits logits for sampling. Lookup + touch
        # are one atomic allocator call: a concurrent allocate() (the
        # multi-host streamed-import fetch thread) must not steal a
        # ref-0 hit between the two.
        hashes, req.prefix_hashes = req.prefix_hashes, None
        again = hashes is not None  # (a waiting request's prompt does not change)
        if not again:
            max_cached = (req.num_prompt_tokens - 1) // self.allocator.page_size
            hashes = page_hashes_for_tokens(
                req.prompt_token_ids, self.allocator.page_size,
                self._hash_extra(req),
            )[:max_cached]
        cached = self.allocator.lookup_and_touch_hashes(hashes, count=not again)
        if not cached:
            return hashes
        req.block_ids.extend(cached)
        n = len(cached)
        req.num_cached_tokens = n * self.allocator.page_size
        req.num_computed_tokens = req.num_cached_tokens
        self._chain[req.request_id] = (hashes[n - 1], n)
        return hashes

    def _ensure_ring(self, req: Request) -> bool:
        """Allocate the sequence's sliding-window ring (once, at admission).

        The auto-sized ring pool (max_num_seqs x R) covers every RUNNING
        sequence; P/D preloads additionally allocate rings at add_request
        time (outside admission), so a burst of preloaded arrivals can
        transiently exhaust the pool — _reclaim_waiting_ring keeps the
        queue head admissible then. An explicit smaller swa_blocks turns
        shortage into a wait-for-next-step, like the main pool.
        """
        if self.swa_allocator is None or req.swa_block_ids:
            return True
        while True:
            try:
                req.swa_block_ids = self.swa_allocator.allocate(
                    self.swa_ring_pages
                )
                return True
            except NoFreePagesError:
                # Idle retained sections (hybrid APC) yield to live
                # sequences before admission gives up for this step.
                if self.ring_pressure_hook is None or not self.ring_pressure_hook():
                    return False

    def _reclaim_waiting_ring(self, req: Request) -> bool:
        """Downgrade the youngest preloaded WAITING request: free its ring
        (and preloaded pages), resetting it to plain local recompute.

        Without this, preloaded arrivals holding rings behind a ring-less
        queue head would starve admission forever (nothing running, so no
        ring would ever free) — correctness over the transfer savings.
        """
        for victim in reversed(self.waiting):
            if victim is req or not victim.swa_block_ids:
                continue
            if victim.status is not RequestStatus.WAITING:
                continue
            self._release(victim)  # frees pages + ring
            victim.num_computed_tokens = 0
            victim.num_cached_tokens = 0
            return True
        return False

    def _ensure_pages(self, req: Request, new_tokens: int) -> bool:
        # Dispatched position: in-flight tokens already own their slots.
        need_slots = req.num_dispatched_tokens + new_tokens
        need_pages = -(-need_slots // self.allocator.page_size)
        missing = need_pages - len(req.block_ids)
        if missing <= 0:
            return True
        try:
            req.block_ids.extend(self.allocator.allocate(missing))
            return True
        except NoFreePagesError:
            return False

    def _preempt_for(
        self,
        req: Request,
        exclude: set[str] = frozenset(),
        batch_only: bool = False,
    ) -> bool:
        """Evict the youngest other running sequence to recompute later.

        Victim order is (lowest priority, youngest) — batch-band rows
        are therefore always reclaimed before any interactive row.
        ``batch_only`` restricts the victim set to the batch band (the
        interactive-pressure reclaim path: interactive admission must
        never evict interactive work just to make room for itself).

        In-flight sequences (``protected``, async stepping) are never
        victims: the dispatched device programs still read/write their
        pages, and recompute-preemption frees those pages immediately.
        """
        victims = [
            r for r in self.running
            if r is not req
            and r.request_id not in exclude
            and r.request_id not in self.protected
            and (not batch_only or r.is_batch)
        ]
        if not victims:
            return False
        victim = max(victims, key=lambda r: (r.priority * -1, r.arrival_time))
        if victim.is_batch:
            self.num_batch_preemptions += 1
        kept = self.park_hook(victim) if self.park_hook is not None else 0
        if kept:
            # Parked: the pager already hosted the committed KV and
            # released the HBM pages; only queue bookkeeping remains.
            # Resume streams the attention window back instead of
            # recomputing the whole prefix.
            victim.num_pending_tokens = 0
            self.protected.discard(victim.request_id)
            self._return_token_slot(victim)
        else:
            self._release(victim)
        self.running.remove(victim)
        # Fold generated tokens into the prompt and restart from scratch
        # (or, when parked, from the pager's preserved prefix).
        victim.num_prior_output_tokens += len(victim.output_token_ids)
        victim.prompt_token_ids = victim.all_token_ids
        victim.output_token_ids = []
        self.num_preemptions += 1
        victim.num_computed_tokens = kept
        victim.num_cached_tokens = kept
        victim.status = RequestStatus.PREEMPTED
        # insort keeps the victim FCFS-ordered by its original arrival time
        # within its priority class, so it resumes ahead of newer arrivals.
        bisect.insort(
            self.waiting, victim, key=lambda r: (-r.priority, r.arrival_time)
        )
        return True

    def _return_token_slot(self, req: Request) -> None:
        if req.token_slot >= 0:
            self._token_slots.append(req.token_slot)
            req.token_slot = -1

    def _release(self, req: Request) -> None:
        req.num_pending_tokens = 0
        self.protected.discard(req.request_id)
        self._return_token_slot(req)
        if req.block_ids:
            # Paged-out indexes hold stale ids — the pager freed (and the
            # allocator may have recycled) those pages when it spilled
            # them to the host tier; freeing again would corrupt another
            # sequence's pages.
            ids = [
                b for i, b in enumerate(req.block_ids)
                if i not in req.paged_out
            ]
            if ids:
                self.allocator.free(ids)
            req.block_ids = []
        req.paged_out.clear()
        req.kv_fetch_pending = False
        if req.swa_block_ids:
            self.swa_allocator.free(req.swa_block_ids)
            req.swa_block_ids = []
            req.swa_table_row = None
        self._chain.pop(req.request_id, None)

    # ------------------------------------------------------------------ #
    # post-step bookkeeping

    def note_dispatch(self, batch: ScheduledBatch) -> None:
        """Mark a dispatched batch's tokens as in flight (async stepping).

        Until the readback commits them, scheduling proceeds against the
        dispatched positions and the sequences are protected from
        preemption. ``update_after_step`` is the matching commit (it
        drains the pending counts); sync engines call both back to back,
        so the window is empty there.
        """
        for seq in batch.seqs:
            seq.request.num_pending_tokens += seq.num_tokens
            self.protected.add(seq.request.request_id)

    def capture_dispatched(self, batch: ScheduledBatch) -> None:
        """Call the capture hook for the rows of ``batch``, which has JUST
        been dispatched, that the step leaves with their per-sequence state
        at a capture point: a prefill row at its prompt's end or at the end
        of the run it was refused at, a decode row at the last page it
        fills before its foreseen finish. The device runs programs in
        dispatch order and a dispatched step is never rolled back
        (in-flight rows are protected, their aborts deferred), so the copy
        the hook dispatches now runs behind the step that writes the state
        and in front of the next one, which overwrites it: the order it had
        when the hooks fired at the step's commit, at no cost to the host's
        turn."""
        if self.capture_hook is None:
            return
        page = self.cache_config.page_size
        for seq in batch.prefills:
            req = seq.request
            if req.is_finished:
                continue  # a wasted row leaves nothing behind
            at = req.num_dispatched_tokens
            run_end = req.swa_capture[0] * page if req.swa_capture else None
            if run_end is not None and at >= run_end:
                # A recurrent state is the run's only AT its end (the chunk
                # was cut there); one that has passed it is dropped.
                if not self.state_aligned or at == run_end:
                    self.capture_hook(req, AT_RUN_END)
                req.swa_capture = None
            if self.state_aligned:
                # The state stands at the prompt's last full page.
                if at == self._prompt_boundary(req):
                    self.capture_hook(req, AT_PROMPT_END)
            elif req.in_decode_dispatched:
                # The chunk completes the prompt: behind it the ring holds
                # the prompt's trailing window.
                self.capture_hook(req, AT_PROMPT_END)
        for seq in batch.decodes:
            # One token a step: the row lands ON the boundary with its
            # input in the host's hands (a fused window's tokens are not,
            # a speculative row's may be taken back), ring and slot alike.
            req = seq.request
            at = req.finish_capture_at  # (0: none, the one test of most rows)
            if (
                at and seq.num_tokens == 1 and req.num_dispatched_tokens == at
                and not req.is_finished
            ):
                self.capture_hook(req, AT_FINISH)

    def _commit_pending(self, seq: ScheduledSeq) -> None:
        req = seq.request
        req.num_pending_tokens = max(0, req.num_pending_tokens - seq.num_tokens)
        if not req.num_pending_tokens:  # (else: a row of the next step)
            self.protected.discard(req.request_id)

    def _land_wasted(self, seq: ScheduledSeq) -> None:
        """A row that has landed for a request which ended at the commit
        of the step before (``_retire`` found the row in flight): nothing
        of it is kept, and what the request held goes back now."""
        self._commit_pending(seq)
        self.wasted_rows += 1
        if not seq.request.num_pending_tokens:
            self._release(seq.request)

    def update_after_step(
        self, batch: ScheduledBatch, sampled: dict[str, list[int]]
    ) -> dict[str, list[int]]:
        """Advance state after the device step.

        ``sampled`` maps request id -> the window of sampled tokens (length 1
        for prefill/single-step rows, K for fused decode windows). Tokens
        past a stop condition are discarded (their speculative KV writes sit
        in pages that are freed with the request and never committed).
        Returns the tokens actually accepted per request.
        """
        accepted: dict[str, list[int]] = {}
        for seq in batch.prefills:
            req = seq.request
            if req.is_finished:
                self._land_wasted(seq)
                continue
            self._commit_pending(seq)
            req.num_computed_tokens += seq.num_tokens
            if req.is_batch:
                self.batch_tokens += seq.num_tokens
            if req.in_decode:  # this chunk completed the prompt -> 1st token
                token = sampled[req.request_id][0]
                req.output_token_ids.append(token)
                accepted[req.request_id] = [token]
                reason = self._check_stop(req, token)
                if reason is not None:
                    self._finish(req, reason)
                    continue
            self._commit_full_pages(req)
        for seq in batch.decodes:
            req = seq.request
            if req.is_finished:
                self._land_wasted(seq)
                continue
            self._commit_pending(seq)
            window = sampled[req.request_id]
            if seq.draft_tokens:
                # Speculative row: resolve the accepted prefix first
                # (sampler.accept_draft_tokens), then run the emitted
                # window through the SAME stop-check loop as a fused
                # decode window — tokens past a stop (or past the first
                # draft mismatch) are discarded and their provisional KV
                # never counts as computed.
                window, n_acc = accept_draft_tokens(seq.draft_tokens, window)
                self.spec_proposed_tokens += len(seq.draft_tokens)
                self.spec_accepted_tokens += n_acc
                self.spec_accept_len_hist[n_acc] += 1
                req.spec_drafted_tokens += len(seq.draft_tokens)
                req.spec_accepted_tokens += n_acc
                # Draft backoff: a fully-rejected draft suggests the
                # n-gram matches are spurious (low-repetition output) —
                # exponentially sparser aligned retries (_spec_eligible)
                # cap the wasted verify columns.
                if n_acc == 0:
                    req.spec_consec_rejected += 1
                else:
                    req.spec_consec_rejected = 0
            elif seq.draft_tokens is not None:
                # Spec row that drafted nothing: one plain committed
                # sample, no provisional writes.
                self.spec_accept_len_hist[0] += 1
            acc: list[int] = []
            reason = None
            for token in window:
                req.num_computed_tokens += 1
                req.output_token_ids.append(token)
                acc.append(token)
                reason = self._check_stop(req, token)
                if reason is not None:
                    break
            accepted[req.request_id] = acc
            if req.is_batch:
                self.batch_tokens += len(acc)
            if reason is not None:
                self._finish(req, reason)
            else:
                self._commit_full_pages(req)
                if seq.draft_tokens:
                    # Drafting rows made provisional KV writes. Draft-
                    # less rows hold at most one page of planned
                    # headroom, which the next step reuses — no
                    # truncation walk for them.
                    self._truncate_spec_pages(req)
        return accepted

    def _spec_eligible(self, req: Request) -> bool:
        """Draft-backoff gate: after c consecutive fully-rejected drafts
        a row retries only on steps where the global clock is a multiple
        of 2^min(c+1, 8). The shared clock ALIGNS retries across rows —
        low-repetition traffic converges to plain decode steps with one
        clustered retry wave every few hundred steps, instead of every
        step paying a mixed verify dispatch for one stray row. A single
        accepted token resets the row to drafting every step."""
        c = req.spec_consec_rejected
        return c == 0 or self.spec_step % (1 << min(c + 1, 8)) == 0

    def _truncate_spec_pages(self, req: Request) -> None:
        """Return the pages a speculative row claimed past its accepted
        prefix (the partial-rollback half of the propose/verify/accept
        contract): rejected draft tokens' provisional KV writes sit in
        slots >= num_computed_tokens, which by construction are never
        committed (``_commit_full_pages`` stops at the computed-token
        page floor) — freeing the trailing pages BEFORE any commit_page
        call makes it structurally impossible for rejected content to
        enter the prefix-cache hash chain.

        Async engines keep the slots a staged-but-undispatched next
        batch may already be planned against (its verify writes reach at
        most num_dispatched + the planned width, 1 + spec_k); sync
        engines have nothing in flight here and keep exactly
        the computed span — the next schedule's _ensure_pages re-extends
        as needed."""
        page = self.allocator.page_size
        slots = req.num_computed_tokens
        if self.pipelined:
            slots = req.num_dispatched_tokens + 1 + self.spec_k
        keep = -(-slots // page)
        if keep < len(req.block_ids):
            self.allocator.free(req.block_ids[keep:])
            del req.block_ids[keep:]

    def _finish(self, req: Request, reason: FinishReason) -> None:
        # Commit computed full pages before release: the KV is valid, so
        # future identical prompts (and P/D exports) can reuse it.
        self._commit_full_pages(req)
        if self.finish_hook is not None:
            # P/D producer export runs here, while block_ids are live.
            self.finish_hook(req)
        self._retire(req)
        req.finish(reason)

    def _retire(self, req: Request) -> None:
        """``req`` stops running. What it holds (pages, ring or state slot,
        token slot) goes back at once, or, where a step still in flight
        carries a row of it (dispatched before the commit that ended it),
        when that step has landed (``_land_wasted``): a dispatched program
        may still write there."""
        self.running.remove(req)
        if not req.num_pending_tokens:
            self._release(req)

    def _check_stop(self, req: Request, token: int) -> FinishReason | None:
        s = req.sampling
        if not s.ignore_eos and token in s.stop_token_ids:
            return FinishReason.STOP
        if req.total_output_tokens >= s.max_tokens:
            return FinishReason.LENGTH
        if req.num_tokens >= self.max_model_len:
            return FinishReason.LENGTH
        return None

    def seed_commit_chain(self, req: Request, parent: bytes, committed: int) -> None:
        """Mark the request's first ``committed`` pages as already in the
        prefix index with ``parent`` as the chain head — the one
        sanctioned way for admission-side hit paths (hybrid SWA-ring) to
        keep _commit_full_pages from re-hashing and re-committing a
        cached prefix."""
        self._chain[req.request_id] = (parent, committed)

    def hash_extra(self, req: Request) -> bytes:
        """Public cache-identity discriminator (see _hash_extra)."""
        return self._hash_extra(req)

    def commit_chain_state(self, req: Request) -> tuple[bytes, int]:
        """(chain tail hash, committed page count) — the pager consults
        this before seeding past a spilled range so it never regresses a
        prefix-cache-seeded chain."""
        return self._chain.get(req.request_id, (_ROOT_HASH, 0))

    def _commit_full_pages(self, req: Request) -> None:
        """Register newly-completed full pages in the prefix index."""
        if not self.allocator.enable_prefix_caching:
            return  # commit_page would no-op; skip the hashing walk too
        page = self.allocator.page_size
        parent, committed = self._chain.get(req.request_id, (_ROOT_HASH, 0))
        # Only KV already computed counts; the just-sampled token's KV is not
        # yet written (it is written when fed as input next step).
        full = req.num_computed_tokens // page
        while committed < full:  # (a page's tokens, never the whole history)
            chunk = req.tokens_between(committed * page, (committed + 1) * page)
            h = hash_page(parent, chunk, extra=self._hash_extra(req))
            self.allocator.commit_page(req.block_ids[committed], h, chunk, parent)
            parent = h
            committed += 1
        self._chain[req.request_id] = (parent, committed)
