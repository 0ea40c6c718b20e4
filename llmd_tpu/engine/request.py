"""Request and sequence state for the continuous-batching engine.

Mirrors the request lifecycle of the reference's model server layer
(docs/architecture/core/model-servers.md:3-25): a request arrives with a
prompt and sampling parameters, is queued, scheduled incrementally
(chunked prefill), then decoded one token per engine step until a stop
condition, streaming tokens out as they are produced.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 16
    temperature: float = 1.0
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False
    seed: int | None = None
    logprobs: bool = False

    @property
    def greedy(self) -> bool:
        return self.temperature <= 1e-5


class FinishReason(str, enum.Enum):
    STOP = "stop"          # hit EOS / stop token
    LENGTH = "length"      # hit max_tokens or max_model_len
    ABORT = "abort"        # client disconnect / cancelled


class RequestStatus(enum.Enum):
    WAITING = enum.auto()
    RUNNING = enum.auto()
    PREEMPTED = enum.auto()
    FINISHED = enum.auto()


class PriorityClass(enum.IntEnum):
    """Serving bands on the ONE continuous batch.

    ``priority`` stays a free integer (higher schedules first, FCFS
    within a value); the class boundary is the contract: any request at
    or below ``BATCH`` rides the offline backfill band — it only
    consumes token-budget/page headroom interactive rows left unused
    this step, never displaces an interactive admission, and is the
    first recompute-preemption victim the moment interactive load
    returns (docs/architecture/batch-processing.md). The serving layer
    maps the ``x-llmd-priority: batch`` header here; the EPP's
    batch-saturation-filter keys on the same boundary
    (llmd_tpu.epp.types.BATCH_PRIORITY — kept numerically identical,
    pinned by test)."""

    INTERACTIVE = 0
    BATCH = -100


@dataclasses.dataclass
class Request:
    """One inflight sequence.

    ``num_computed_tokens`` tracks how much of the prompt has been prefilled
    (chunked prefill advances it in steps); once it reaches
    ``len(prompt_token_ids)`` the sequence enters decode.
    """

    request_id: str
    prompt_token_ids: list[int]
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    arrival_time: float = dataclasses.field(default_factory=time.monotonic)
    priority: int = 0
    # Opaque KV-transfer params injected by the P/D routing sidecar
    # (reference disaggregation/README.md:104-131); interpreted by the
    # kvtransfer connector, not the engine core.
    kv_transfer_params: dict[str, Any] | None = None
    # LoRA adapter slot (0 = base model); set by the serving layer from
    # the requested model name. The adapter NAME rides lora_name for the
    # lora_requests_info metric.
    lora_id: int = 0
    lora_name: str = ""

    # --- mutable state ---
    status: RequestStatus = RequestStatus.WAITING
    output_token_ids: list[int] = dataclasses.field(default_factory=list)
    num_computed_tokens: int = 0
    # Physical page ids allocated to this sequence, in order. The
    # request is an ownership root for its pages: the scheduler's
    # _release/_truncate paths free from here (static-analysis.md).
    block_ids: list[int] = dataclasses.field(default_factory=list)  # llmd: owns(pages)
    # Ring pages for sliding-window layers (CacheConfig.swa_ring): a fixed
    # list of R pages from the ring pool, reused circularly — logical page
    # l of this sequence lives at swa_block_ids[l % R] on sliding layers.
    swa_block_ids: list[int] = dataclasses.field(default_factory=list)  # llmd: owns(pages)
    # Memoized [max_pages] ring-view table row (immutable once the ring is
    # allocated; invalidated whenever swa_block_ids is freed).
    swa_table_row: Any = None
    # (pages, chain hash) of a full-page prefix run the main pool offered
    # at admission and the hybrid cache had to refuse for want of a
    # sliding section: this request prefills that span anyway, so the
    # section is captured as its prefill passes the run's end (scheduler
    # ``capture_hook`` at AT_RUN_END) and the next request takes the hit.
    swa_capture: tuple | None = None
    # (n_pre, chain hash of page n_pre - 1) of the prompt's own retained
    # section, kept from the admission's hash walk
    # (``LLMEngine._try_hybrid_ring_hit``) so that the capture at the
    # prompt's end hashes nothing. A prompt only ever grows (a preemption
    # folds outputs in), so the hash stands while ``n_pre`` does.
    capture_key: tuple | None = None
    # The last page boundary the sequence fills before the finish its
    # admission foresees, in tokens (``EngineScheduler._finish_boundary``;
    # 0: none worth a capture): the decode step that leaves the state
    # there leaves a copy behind for the session's next turn.
    finish_capture_at: int = 0
    # The prompt's page-hash chain, left by an admission that looked the
    # prefix cache up and then failed for want of fresh pages (what the
    # cache lent went back): the next attempt walks it again instead of
    # hashing the prompt, and is not counted as another query
    # (scheduler._apply_prefix_cache).
    prefix_hashes: list | None = None
    # Tokens dispatched to the device but not yet committed by a step
    # readback (the pipelined step): the scheduler speculates the next
    # batch against dispatched positions while the in-flight step
    # executes. Always 0 behind a synchronous step and between reconcile
    # and the next dispatch.
    num_pending_tokens: int = 0
    # The request's entry of the runner's ``last_tokens`` (the token the
    # device sampled last for it, which the next step's decode row reads
    # there and not from the host): handed out by the scheduler when the
    # request starts to run, taken back with its pages. -1: none.
    token_slot: int = -1
    # Number of prompt tokens satisfied from the prefix cache (skipped compute).
    num_cached_tokens: int = 0
    # Decode-time KV paging (OffloadConfig.decode_paging): logical page
    # index -> content hash of pages whose HBM copy was released to the
    # host tier. A stale physical id may linger in block_ids at these
    # indexes — every attention read below the sliding window is masked,
    # and _release skips them when freeing.
    paged_out: dict[int, bytes] = dataclasses.field(default_factory=dict)
    # Parked by the pager: committed KV lives in the host tier and the
    # scheduler must not re-admit this request until the pager has
    # streamed the attention window back into freshly allocated pages
    # (fetch-pending is a wait state, not a fault).
    kv_fetch_pending: bool = False
    # Outputs generated before a recompute-preemption folded them into the
    # prompt; counts toward max_tokens and reported output length.
    num_prior_output_tokens: int = 0
    # Speculative decoding accounting (SchedulerConfig.speculative_ngram):
    # draft tokens proposed for / accepted by this request across its
    # verify steps. Purely observational — acceptance itself lives in the
    # scheduler's update loop.
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0
    # Draft backoff state: consecutive fully-rejected drafts. The
    # scheduler gates drafting eligibility on this against a GLOBAL
    # step clock (scheduler.spec_step), so backed-off rows retry on the
    # same aligned steps instead of smearing one drafting row across
    # every step — low-repetition traffic then runs almost every step as
    # a plain decode. Never affects WHAT is emitted (acceptance is exact
    # either way), only whether a draft is attempted — parity untouched.
    spec_consec_rejected: int = 0
    # Incremental n-gram index over all_token_ids (NgramProposer state;
    # valid across preemption because recompute folds output into the
    # prompt without changing the token sequence).
    spec_gram_state: Any = None
    finish_reason: FinishReason | None = None
    # ms between arrival and the FIRST admission by the scheduler; kept
    # across preemption (EngineStats.queue_wait_ms_total, and the
    # llm_d.queue_wait_ms attribute of the request's OTLP span).
    queue_wait_ms: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None
    # Per-step sampled logprob of each output token (if requested).
    output_logprobs: list[float] = dataclasses.field(default_factory=list)
    # KV-transfer params produced at finish by a kv_producer engine
    # (set by the connector's finish hook; echoed in RequestOutput).
    export_params: dict[str, Any] | None = None

    @property
    def is_batch(self) -> bool:
        """True when this request rides the offline backfill band."""
        return self.priority <= PriorityClass.BATCH

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def total_output_tokens(self) -> int:
        return self.num_prior_output_tokens + len(self.output_token_ids)

    @property
    def all_token_ids(self) -> list[int]:
        return self.prompt_token_ids + self.output_token_ids

    def token_at(self, pos: int) -> int:
        """``all_token_ids[pos]`` without building the list (a step's fill
        reads one token of each decode row: a copy of a 4-24k-token
        history a row a step is milliseconds the device waits for)."""
        n = len(self.prompt_token_ids)
        return self.prompt_token_ids[pos] if pos < n else self.output_token_ids[pos - n]

    def tokens_between(self, start: int, stop: int) -> list[int]:
        """``all_token_ids[start:stop]``, copying only what is asked for."""
        n = len(self.prompt_token_ids)
        if stop <= n:
            return self.prompt_token_ids[start:stop]
        if start >= n:
            return self.output_token_ids[start - n:stop - n]
        return self.prompt_token_ids[start:] + self.output_token_ids[:stop - n]

    @property
    def in_decode(self) -> bool:
        return self.num_computed_tokens >= self.num_prompt_tokens

    @property
    def num_dispatched_tokens(self) -> int:
        """Committed + in-flight position: what the KV/pages will hold
        once the dispatched step lands. The scheduler plans against THIS
        (== num_computed_tokens whenever nothing is in flight)."""
        return self.num_computed_tokens + self.num_pending_tokens

    @property
    def in_decode_dispatched(self) -> bool:
        """in_decode once the in-flight step lands (async speculation:
        a prompt-completing chunk in flight makes the seq decode-ready
        for the next staged batch)."""
        return self.num_dispatched_tokens >= self.num_prompt_tokens

    @property
    def num_dispatched_outputs(self) -> int:
        """``total_output_tokens`` once the steps in flight land: one more
        for every pending decode position, one for a pending chunk that
        completes the prompt (a speculative row's pending count is its
        widest acceptance, and is never asked while it pends)."""
        if not self.num_pending_tokens:
            return self.total_output_tokens
        if self.in_decode:
            return self.total_output_tokens + self.num_pending_tokens
        return self.total_output_tokens + int(self.in_decode_dispatched)

    @property
    def is_finished(self) -> bool:
        return self.status is RequestStatus.FINISHED

    def finish(self, reason: FinishReason) -> None:
        self.status = RequestStatus.FINISHED
        self.finish_reason = reason
        self.finish_time = time.monotonic()


@dataclasses.dataclass
class RequestOutput:
    """Incremental output for one request after an engine step."""

    request_id: str
    new_token_ids: list[int]
    finished: bool
    finish_reason: FinishReason | None
    num_prompt_tokens: int
    num_output_tokens: int
    num_cached_tokens: int = 0
    kv_transfer_params: dict[str, Any] | None = None
    # From the request's own timestamps, once known: arrival to first
    # admission, and arrival to the step that produced the first token.
    queue_wait_ms: float | None = None
    ttft_ms: float | None = None
