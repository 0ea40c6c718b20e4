"""LLMEngine: the continuous-batching serving engine.

Plays the role vLLM plays in the reference stack (L3 of SURVEY.md's layer
map): accepts requests, schedules them with chunked prefill + paged KV +
automatic prefix caching, steps the jitted model, streams outputs, and
exposes the queue/KV metrics the EPP scrapes
(docs/architecture/core/model-servers.md:38-52).
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import itertools
import logging
import threading
import time
import uuid
from dataclasses import dataclass, field

import jax
import numpy as np

from llmd_tpu import faults
from llmd_tpu.config import (
    EngineConfig, state_slot_spec, swa_ring_spec, swa_section_count,
)
from llmd_tpu.engine.kv_cache import KVEventSink, PageAllocator
from llmd_tpu.engine.request import (
    FinishReason,
    Request,
    RequestOutput,
    RequestStatus,
    SamplingParams,
)
from llmd_tpu.engine.runner import (
    ModelRunner,
    PendingDecode,
    PendingPrefill,
    PendingUnified,
    StagedDecode,
    StagedUnified,
    StagedVerify,
    StepResult,
)
from llmd_tpu.engine.scheduler import (
    AT_FINISH,
    AT_RUN_END,
    EngineScheduler,
    ScheduledBatch,
)
from llmd_tpu.obs import profiling
from llmd_tpu.parallel.mesh import MeshContext, build_mesh

try:  # the engine thread's involuntary context switches (Linux)
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    _RUSAGE_THREAD = None

# EngineStats' partition of the holds: upper edges in ms, and the counters.
_HOLD_EDGES_MS = (1.0, 4.0, 16.0, 64.0, 256.0)
_HOLD_BUCKETS = tuple(
    f"step_host_hold_{name}ms_total"
    for name in ("le1", "1to4", "4to16", "16to64", "64to256", "over256")
)


@dataclass
class _Section:
    """One retained sliding section: ring slots [s0, n_pre) copied into
    ``pages``. ``shared``: captured at the end of a full-page run that the
    main pool offered to ANOTHER request than the one that wrote it: a
    prefix several sessions share. The others were captured at a prompt's
    end and serve that session's next turn, once."""

    s0: int
    n_pre: int
    # llmd: owns(pages)
    pages: list[int]
    shared: bool = False
    hits: int = 0


class RetainedStateCache:
    """Retained per-sequence state for HYBRID prefix caching: ONE policy
    for both kinds of state that live outside the paged pool. A sliding-
    window ring's SECTION (below), and a state-space model's SNAPSHOT: the
    sequence's one slot of the state pool AT page boundary ``n_pre``, a
    section of one "page" (``s0 = n_pre - 1``, a ring of one:
    ``config.StateSlotSpec``). Keys, budget, eviction order, capture, seed
    and counters are the same; the device copy is the same program over
    the other pool's leaves (``runner.copy_pages_on_device(swa=True)``).

    Retained sliding-window sections for HYBRID prefix caching under
    the SWA ring (the reference's hybrid KV-cache manager role, pd gpu
    patch-decode.yaml:19).

    Ring pages are transient per sequence, so a bare full-pool prefix
    hit would skip sliding-layer KV that no longer exists. This cache
    keeps, per recently-prefilled prefix, a COPY of the ring's
    in-window section (the same [s0, n_pre) geometry the P/D transfer
    ships — SwaRingSpec.section) in ref-held SWA-pool pages. On a
    repeated prefix, a fresh ring is seeded from the section on device
    and the request starts at num_computed = n_pre * page: exactly the
    P/D preload path, sourced locally. LRU-capped; entries own their
    pages and free them on eviction."""

    def __init__(
        self, swa_allocator, runner, capacity: int, page_budget: int,
        is_live=None,
    ) -> None:
        import collections

        self._alloc = swa_allocator
        self._runner = runner
        # ``is_live(key)``: whether the main pool still caches the full
        # page the section is keyed by. A hit needs that page, so a
        # section without it is dead and goes first.
        self._is_live = is_live
        self.capacity = capacity
        # Retention pages are PROVISIONED on top of the ring pool
        # (engine sizing); this budget keeps retention from ever eating
        # ring capacity even transiently.
        self.page_budget = page_budget
        self.retained_pages = 0
        # llmd: owns(pages)
        self._entries: "collections.OrderedDict[bytes, _Section]" = (
            collections.OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        self.captures = 0
        self.evictions = 0
        # Captures whose key had to be hashed from the prompt again (the
        # request came without its admission's walk), and the host time of
        # all captures, wherever in the step it is spent.
        self.rehashed = 0
        self.capture_host_ms = 0.0
        # Entries made at a sequence's finish boundary (the engine counts:
        # to the cache they are entries like a prompt's end's).
        self.finish_captures = 0
        # Keys captured behind a step that has not been committed yet: the
        # main pool registers their full page at that commit (``settle``),
        # so until then ``is_live`` says nothing about them.
        self._unsettled: set[bytes] = set()

    def capture(
        self, key: bytes, ring_ids: list[int], s0: int, n_pre: int,
        shared: bool = False,
    ) -> bool:
        """Copy ring slots [s0, n_pre) into retained pages (device op,
        no host bytes). No-op if the key is already retained or the SWA
        pool lacks headroom (a ring allocation must never fail because
        retention hoarded pages). Returns whether an entry was made."""
        from llmd_tpu.engine.kv_cache import NoFreePagesError

        if key in self._entries:
            self._entries[key].shared |= shared
            return False
        if self.capacity <= 0 or n_pre <= s0:
            return False
        cnt = n_pre - s0
        R = len(ring_ids)
        # Entry-count LRU + page budget, evicted BEFORE allocating so
        # the budget invariant holds at the allocate call.
        evicted = False
        while self._entries and (
            len(self._entries) >= self.capacity
            or self.retained_pages + cnt > self.page_budget
        ):
            evicted = self.evict_one()
        if self.retained_pages + cnt > self.page_budget:
            return False  # a single oversized section cannot fit the budget
        try:
            # The pages its eviction has just given back (the free list's
            # head), else the pages free longest: never what a SEQUENCE has
            # just released. A ring or slot stays as its sequence left it
            # until an admission takes it, and a capture may run steps
            # after a batch mate's finish, where there was none before a
            # decode row took one (a finished sequence's state can still
            # be read out: the benchmark's state comparison does).
            dst = self._alloc.allocate(cnt, longest_free=not evicted)
        except NoFreePagesError:
            # Pool transiently drained past the provisioned budget
            # (preload bursts hold extra rings): skip this capture.
            return False
        self.retained_pages += cnt
        src = [ring_ids[l % R] for l in range(s0, n_pre)]
        try:
            with profiling.span("llmd.state.capture", pages=cnt):
                self._runner.copy_pages_on_device(src, dst, swa=True)
        except BaseException:
            # A failed device copy must refund the retained pages, or
            # the ring pool permanently shrinks by `cnt` on every retry.
            self.retained_pages -= cnt
            self._alloc.free(dst)
            raise
        self._entries[key] = _Section(s0, n_pre, pages=dst, shared=shared)
        self._unsettled.add(key)
        self.captures += 1
        return True

    def settle(self) -> None:
        """The step the last captures were dispatched behind is committed."""
        self._unsettled.clear()

    def evict_one(self) -> bool:
        """Free the retained section that is worth least (ring-pressure
        relief: a live sequence's ring allocation outranks idle
        retention). Returns True if an entry was freed. In this order,
        the least recently used of each kind: an entry whose full page the
        main pool no longer caches (it can serve no hit); a prompt's-end
        section that has served its hit (the session has moved on to a
        longer one); any prompt's-end section; a shared prefix's. Plain
        LRU let 32 sessions' prompt ends, two rounds of turns, push the
        eight shared prefixes out before the next new session came, and
        each such miss is a 4,096-token prefill."""
        if not self._entries:
            return False
        ranked = (
            lambda k, e: self._is_live is not None
            and k not in self._unsettled and not self._is_live(k),
            lambda k, e: not e.shared and e.hits > 0,
            lambda k, e: not e.shared,
            lambda k, e: True,
        )
        victim = next(
            k for worth_less in ranked
            for k, e in self._entries.items() if worth_less(k, e)
        )
        ids = self._entries.pop(victim).pages
        self._alloc.free(ids)
        self.retained_pages -= len(ids)
        self.evictions += 1
        return True

    def has(self, key: bytes) -> bool:
        return key in self._entries

    def candidate_lengths(self, n_pre_max: int) -> list[int]:
        """Retained entry lengths usable for a prompt whose own
        preloadable span is ``n_pre_max`` pages, longest first: a
        section captured at k <= n_pre_max pages holds the window before
        continuation k*page, so an EXTENDED prompt sharing that prefix
        can still skip its first k pages (the multi-turn grow case)."""
        return sorted(
            {
                e.n_pre for e in self._entries.values()
                if e.n_pre <= n_pre_max
            },
            reverse=True,
        )

    def seed(self, key: bytes, ring_ids: list[int]) -> tuple[int, int] | None:
        """Seed a freshly allocated ring from the retained section.
        Returns (s0, n_pre) on success; None on miss."""
        entry = self._entries.get(key)
        if entry is None:
            return None
        self._entries.move_to_end(key)
        entry.hits += 1
        s0, n_pre = entry.s0, entry.n_pre
        R = len(ring_ids)
        dst = [ring_ids[(s0 + i) % R] for i in range(n_pre - s0)]
        with profiling.span("llmd.state.seed", pages=len(dst)):
            self._runner.copy_pages_on_device(entry.pages, dst, swa=True)
        self.hits += 1
        return s0, n_pre

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "captures": self.captures,
            "evictions": self.evictions,
        }


@dataclass
class EngineStats:
    """The EPP metrics contract (model-servers.md:38-52)."""

    num_waiting: int = 0
    num_running: int = 0
    kv_usage: float = 0.0
    prefix_hit_ratio: float = 0.0
    num_pages: int = 0
    page_size: int = 0
    # SWA ring pool (kv_swa_ring): under P/D preload bursts the ring pool
    # is the binding admission constraint, so it must be visible to
    # utilization-based routing, not just the main pool.
    swa_ring_usage: float = 0.0
    swa_ring_pages: int = 0
    # Hybrid-APC section retention (RetainedStateCache)
    swa_sections: int = 0
    # Hybrid prefix hits taken (a fresh ring seeded from a retained
    # section), and full-page runs the main pool offered at admission that
    # were refused for want of a section (the request prefills the span and
    # leaves the section behind for the next one).
    swa_section_hits_total: int = 0
    swa_section_misses_total: int = 0
    swa_section_captures: int = 0
    # Rings seeded from a retained section (at an admission: in the schedule
    # or in a top-up), the pages copied, and the host time the copies'
    # dispatch took (span ``llmd.ring.seed``): a section of a 1,024-token
    # window is 65 pages x the sliding layers.
    swa_ring_seeds_total: int = 0
    swa_ring_seed_pages_total: int = 0
    swa_ring_seed_host_ms_total: float = 0.0
    # The state pool of a model with state-space layers (0 elsewhere): slots
    # that running sequences hold, snapshots retained, and the retained-
    # state cache's counters under the meanings above (a hit seeds a fresh
    # slot from a snapshot; a miss is a run of full pages the main pool
    # offered at admission, refused for want of a snapshot at its end).
    state_slots_in_use: int = 0
    # The slots the pool provisions for RUNNING sequences (max_num_seqs: a
    # slot a sequence), which ``state_slots_in_use`` is read against.
    state_slots: int = 0
    state_snapshots: int = 0
    state_snapshot_hits_total: int = 0
    state_snapshot_misses_total: int = 0
    state_snapshot_captures_total: int = 0
    state_snapshot_evictions_total: int = 0
    # Retained-state captures (sections and snapshots alike) that had to
    # hash their prompt again because the request carried no key from its
    # admission (a P/D preload, a request resumed from the pager), and the
    # host time of all captures: spent behind the dispatch of the step that
    # writes the state, under the device, and no part of the host's turn.
    retained_capture_rehashed_total: int = 0
    retained_capture_host_ms_total: float = 0.0
    # Of those captures, the ones taken where a sequence fills its last
    # page before a finish by length that its admission foresaw (the next
    # turn of its session then hits behind its own last answer); over
    # requests_finished, how often that mechanism engages.
    retained_finish_captures_total: int = 0
    # Bytes of state-pool slots held (running + retained), summed over
    # steps: beside kv_bytes_in_use_total (which counts pages only there)
    # over cached_tokens_total, what a cached token costs in both pools.
    state_bytes_in_use_total: int = 0
    # Decode rows the state-space layers updated and prefill tokens they
    # scanned, each x the mixer layers (what the rooflines divide by).
    ssm_update_rows_total: int = 0
    ssm_scan_tokens_total: int = 0
    # The same where the recurrent mixers are gated delta-rule ones: decode
    # rows, the scan's rows (chunks) and tokens, each x the mixer layers,
    # and the bytes of state those rows read and wrote.
    gdn_update_rows_total: int = 0
    gdn_scan_rows_total: int = 0
    gdn_scan_tokens_total: int = 0
    gdn_state_bytes_moved_total: int = 0
    # counters
    prompt_tokens: int = 0
    generation_tokens: int = 0
    requests_finished: int = 0
    preemptions: int = 0
    # tiered offload (kv-offloader metrics)
    offload_pages: int = 0
    offload_fs_pages: int = 0
    offload_saves: int = 0
    offload_restores: int = 0
    # Cross-replica KV federation (docs/architecture/kv-federation.md):
    # the store client's read path (peer pulls / failed pulls / locate
    # misses), master-accepted publications from this replica, pages
    # this replica fetched from the store, and the prompt tokens whose
    # re-prefill those committed pages avoided.
    kvstore_pulls: int = 0
    kvstore_pull_failures: int = 0
    kvstore_misses: int = 0
    kv_federation_published: int = 0
    kv_federation_hits: int = 0
    recompute_avoided_tokens: int = 0
    # P/D KV transfer (reference operations-vllm.md transfer accounting)
    kv_exported_requests: int = 0
    kv_exported_bytes: int = 0
    kv_imported_requests: int = 0
    kv_imported_bytes: int = 0
    kv_import_failures: int = 0
    # Layer-streamed transfer (the v3 group-framed wire): (layer-group x
    # chunk) cells landed by streamed imports, and the last import's
    # first-group latency — the admission-gate leg of the pipeline
    # waterfall (kv-cache.md "layer-streamed import").
    kv_stream_groups_total: int = 0
    kv_stream_first_group_ms: float = 0.0
    # Publish-budget pacing (LLMD_KV_PUBLISH_BYTES_PER_S): bytes the
    # federation publisher delayed to keep publish-on-evict bursts off
    # the transfer NIC (kv-federation.md).
    kv_publish_paced_bytes_total: int = 0
    # LoRA (reference model-servers.md:78-89 lora_requests_info)
    max_lora: int = 0
    running_lora_adapters: tuple = ()
    waiting_lora_adapters: tuple = ()
    # Multi-tenant paged adapter pool (multi-tenant-lora.md): adapters
    # resident in HBM slots right now, idle residents LRU-evicted for
    # incoming tenants, requests that had to wait for a cold weight
    # install, and /v1/load_lora_adapter fetches that failed (surfaced
    # as 4xx). resident/available ride lora_requests_info labels so the
    # EPP's tri-state LoraAffinityScorer can route on residency.
    lora_pool_resident_adapters: int = 0
    lora_pool_evictions_total: int = 0
    lora_cold_loads_total: int = 0
    lora_load_failures_total: int = 0
    resident_lora_adapters: tuple = ()
    available_lora_adapters: tuple = ()
    # Step pipeline observability (serve/metrics.py): the host gap is
    # the per-step host time the device sits idle for. In the pipelined
    # step (the default shape) it is the time from a readback's end to
    # the next dispatch's return: commit + reconcile, a last top-up, the
    # fill and the one put + call; everything else overlaps device
    # execution (the readback itself, which the device also waits for,
    # is step_readback_ms_total: the host's whole turn between two
    # programs is readback + commit + redispatch). In the synchronous
    # step (lockstep followers, P/D producers) it is schedule + launch +
    # finish. Last value + running sum + step count so a scrape (or the
    # bench) can read both a gauge and a mean.
    engine_steps_total: int = 0
    step_host_gap_ms: float = 0.0
    step_host_gap_ms_total: float = 0.0
    # Where a step's time goes, by phase (running sums over the steps
    # counted in engine_steps_total; unrounded, rounded where exported).
    # The phases are the spans of obs/profiling.py by the same names:
    # admit (parked KV streams, cold adapter loads, the pager's pump),
    # schedule, launch (host arrays built and the program handed to the
    # device), wait (the device and the one readback), finish (collect,
    # scheduler update, outputs, offload flush). Each is host time
    # SPENT; in the synchronous step schedule + launch + finish IS
    # step_host_gap_ms_total, in the pipelined step schedule, the staging
    # half of launch and the assembly half of finish overlap the device
    # and the gap is step_commit_ms_total (collect, scheduler update,
    # late intake, reconcile) + step_redispatch_ms_total (last top-up,
    # fill, put + call). step_ms_total is the whole of step().
    step_admit_ms_total: float = 0.0
    step_schedule_ms_total: float = 0.0
    step_launch_ms_total: float = 0.0
    step_wait_ms_total: float = 0.0
    step_finish_ms_total: float = 0.0
    step_commit_ms_total: float = 0.0
    step_redispatch_ms_total: float = 0.0
    step_ms_total: float = 0.0
    # The host's turn between two step programs, timed where it happens
    # (runner.wait_step, _step_async; docs/architecture/async-scheduling.md
    # "The turn"). ready_lag_bound: per step the time from the last
    # is_ready() that was false (the wait's entry where the first was
    # true) to the first that was true; the device finished somewhere in
    # it, so it bounds from above how late the host noticed, and holds
    # what the poll did meanwhile (the pause's oversleep, an intake, a
    # top-up); 0 for a blocking wait. readback: from that notice to the
    # parsed results (span llmd.runner.readback; both kinds of step).
    # gap_admit: the part of step_redispatch_ms_total that is admission,
    # a top-up or the whole schedule of an empty slot, run AFTER the
    # readback with the device empty (steps_topped_up_total does not say
    # where a top-up ran).
    step_ready_lag_bound_ms_total: float = 0.0
    step_readback_ms_total: float = 0.0
    step_gap_admit_ms_total: float = 0.0
    # The host's worst moments, which a mean over engine_steps_total hides
    # (docs/architecture/observability.md's metric reference). HOLD: for a
    # step behind which another was dispatched in the same turn, from the
    # last is_ready() that was false (WaitTiming.ready_at less its lag
    # bound) to the NEXT dispatch's return: an upper bound, from host
    # clocks alone, on how long the device stood finished with nothing
    # queued (the ready-lag bound + redispatch on a step dispatched early;
    # + readback and commit on one that waits for the commit). A step with
    # nothing to dispatch behind it holds nothing (that idle time is the
    # load's), nor does the synchronous step. A bound only where a look
    # found the device RUNNING: a step the host comes late to (a pause that
    # fell under the device, in readback, commit, finish or staging, and
    # outlasted the program) is held from the wait's entry, and how long
    # the device had stood before that no host clock says; the pace's sum
    # and gc_full_pause_ms_total hold such a pause. The six bucket counts
    # partition the holds (ms, upper edge included); /metrics renders them
    # as the one histogram llmd:step_host_hold_ms.
    step_host_hold_ms_total: float = 0.0
    step_host_holds_total: int = 0
    step_host_hold_le1ms_total: int = 0
    step_host_hold_1to4ms_total: int = 0
    step_host_hold_4to16ms_total: int = 0
    step_host_hold_16to64ms_total: int = 0
    step_host_hold_64to256ms_total: int = 0
    step_host_hold_over256ms_total: int = 0
    # PACE: WaitTiming.ready_at of step N less that of N-1, under N's kind
    # (prefill: prefill or mixed), counted only where N was dispatched while
    # N-1 was in flight or in N-1's own turn, never across a pipeline that
    # ran empty: what a step costs every stream, ready to ready. While the
    # pipeline stays full the two sums add up to the wall clock.
    step_ready_interval_ms_decode_total: float = 0.0
    step_ready_intervals_decode_total: int = 0
    step_ready_interval_ms_prefill_total: float = 0.0
    step_ready_intervals_prefill_total: int = 0
    # The cyclic collector (obs/profiling.py::gc_watch; span llmd.runner.gc):
    # the process's collections and their pauses since this engine began to
    # watch, folded in once a step; a pause on ANY thread counts, it holds
    # the interpreter lock. full: generation 2, a pass over the whole heap.
    gc_pause_ms_total: float = 0.0
    gc_collections_total: int = 0
    gc_full_pause_ms_total: float = 0.0
    gc_full_collections_total: int = 0
    # The engine thread against the machine, once a step on the thread
    # that steps: the growth of time.thread_time() and of the thread's
    # involuntary context switches (getrusage(RUSAGE_THREAD).ru_nivcsw; 0
    # on a platform without it) since the step before. A host phase that
    # reads longer with the same CPU ms was preempted or waited; with more
    # CPU ms it ran slower (a starved core, a colder cache). A changed
    # thread id restarts the baseline.
    engine_thread_cpu_ms_total: float = 0.0
    engine_thread_preemptions_total: int = 0
    # The serving loop (serve/async_engine.py; 0 for an engine stepped
    # directly). engine_idle: time the loop waited with nothing to run
    # (span llmd.serve.idle; not paused): 1 - idle / wall is the
    # replica's duty cycle, which tells "no load" from "slow host".
    # intake_wait: submit() to the engine thread's intake, a request (it
    # ends where queue_wait_ms and ttft_ms start). deliver_lag: a step's
    # readback's end to each of its outputs handed to its stream (it
    # starts where ttft_ms ends).
    engine_idle_ms_total: float = 0.0
    intake_wait_ms_total: float = 0.0
    intake_requests_total: int = 0
    deliver_lag_ms_total: float = 0.0
    outputs_delivered_total: int = 0
    # Steps by what they carried (prefill chunks only, decode rows only,
    # both) and their whole-step time: a mean step time hides that a
    # step with a long prefill chunk is another thing than a decode step.
    steps_prefill_total: int = 0
    steps_decode_total: int = 0
    steps_mixed_total: int = 0
    # (since the step dispatches early, PR 49, step() holds the wait of the
    # step in FRONT: these two time a call's entry to its exit under the
    # kind of the step it landed, not what that step cost. The pace of a
    # step, ready to ready, is step_ready_interval_ms_*_total above.)
    step_ms_decode_total: float = 0.0
    step_ms_prefill_total: float = 0.0  # prefill or mixed
    # Queue wait: at a request's FIRST scheduling, now - arrival_time
    # (a re-admission after preemption is not counted again).
    queue_wait_ms_total: float = 0.0
    queue_admitted_total: int = 0
    # Step programs traced (runner.traced_programs names them): a trace
    # after warm-up is a shape nobody warmed, seconds inside a step.
    programs_traced_total: int = 0
    # Step payload buffers put on the device and their bytes (runner.
    # _put_step): a step program's host inputs travel as ONE buffer, so
    # step_h2d_transfers_total / step_dispatches_total reads 1.
    step_h2d_transfers_total: int = 0
    step_h2d_bytes_total: int = 0
    # Speculative rows invalidated by a late finish/abort at reconcile
    # (EOS / stop token / max-tokens landed after the next batch was
    # staged against the optimistic one-token-per-decode assumption).
    async_rollbacks_total: int = 0
    # How often the pipeline engages: steps dispatched from a slot that
    # was scheduled and staged under the step before (over
    # engine_steps_total: near 1 while there is work; a pipeline that
    # restarts from empty, or a slot rolled back whole, dispatches
    # unstaged), and of those the steps whose staged batch was topped up
    # with requests admitted after the speculative schedule.
    steps_prestaged_total: int = 0
    steps_topped_up_total: int = 0
    # Steps dispatched BEFORE the step in front of them was read back (the
    # moment it was seen ready: a decode row takes its token from the
    # device, so the staged step needs nothing of the host's copy). On
    # such a step step_host_gap_ms and step_redispatch_ms run from the
    # first ready to the dispatch's return (what the device still waits
    # for host code), and the readback and the commit are under the
    # device. Rows that landed for a request which had ended meanwhile (a
    # stop token or an abort met at the commit, one step after the row was
    # dispatched): computed, dropped, and what the request held released
    # only then. A finish by length is foreseen and wastes none.
    steps_dispatched_before_readback_total: int = 0
    async_wasted_rows_total: int = 0
    # Speculative decoding (SchedulerConfig.speculative_ngram; the
    # propose/verify/accept contract in
    # docs/architecture/speculative-decoding.md): draft tokens proposed
    # and accepted across all verify steps, their ratio, and the
    # accepted-draft-length histogram — index j counts (spec row, step)
    # pairs that accepted exactly j draft tokens, so mean emitted
    # tokens/row/step reads as 1 + sum(j * hist[j]) / sum(hist).
    spec_proposed_tokens_total: int = 0
    spec_accepted_tokens_total: int = 0
    spec_acceptance_rate: float = 0.0
    spec_accepted_len_hist: tuple = ()
    # Decode-side device programs dispatched, and the ratio that is the
    # fused-window headline: decode dispatches per generated token —
    # fused decode windows and accepted drafts both push it down by
    # spreading one dispatch over more emitted tokens.
    decode_dispatches_total: int = 0
    dispatches_per_emitted_token: float = 0.0
    # Unified single-dispatch steps (SchedulerConfig.unified_step): engine
    # steps whose entire window=1 batch — prefill chunks + decode rows +
    # one-shot verify rows — rode ONE ragged program. The family split of
    # decode_dispatches_total: unified_steps_total of those dispatches
    # came from the unified family, the rest from the split families.
    unified_steps_total: int = 0
    # EVERY device program engine steps dispatched (prefill bucket
    # groups + decode-side programs + unified programs): the unified
    # step's headline is step_dispatches_total / engine_steps_total
    # falling toward 1.0 on mixed workloads.
    step_dispatches_total: int = 0
    # Padding efficiency (the flattened-token step's headline,
    # SchedulerConfig.ragged_qlens): tokens the dispatched programs
    # computed for real vs the pad lanes their traced shapes paid on
    # top — the bucketed [B, Q] unified step pads every decode row to
    # the sub-row Q bucket; the flat stream pads only to the 16-token
    # T granule. padded / live is the padding-waste gauge.
    live_tokens_total: int = 0
    padded_tokens_total: int = 0
    # Of the flat stream's live tokens, those that lie in a 16-token
    # granule of the stream holding tokens of ONE row only (the body of a
    # prefill chunk's sub-row): the flat attention kernel reads such a
    # tile's context once for its 16 queries, every other token's once
    # per token. Over live_tokens_total: the share of computed tokens
    # whose attention bytes are shared (0 for decode-only steps).
    attn_shared_tile_tokens_total: int = 0
    # Shared-prefix runs (engine/prefix_runs.py; flat step, calls without a
    # window): over the flat steps' plain decode tokens, the keys under
    # their horizons, and of those the keys in a run's blocks, which the
    # attention kernel reads once a tile for all the run's members and not
    # once a row. Host-counted where the runs are planned; keys / keys is
    # the share of the decode rows' context that crosses HBM shared.
    attn_prefix_run_keys_total: int = 0
    attn_decode_keys_total: int = 0
    # The grouped expert matmul (one-device "grouped" MoE backend; 0 for a
    # dense model and under wide-EP, which has its own census): grouped
    # MoE layer calls of the step programs (one a layer and step; gate, up
    # and down share it), and over those calls the groups (experts) with
    # at least one row AS THE KERNEL SEES THEM: the zero rows
    # ops/grouped_gemm.py pads into the last group make it non-empty, and
    # a pad token is a token. Counted on the device, read with each step's
    # one readback and added at that step's finish. groups / (calls x
    # experts) is the share of expert weights a call reads: what
    # kernels.moe_gmm_roofline charges.
    moe_grouped_calls_total: int = 0
    moe_groups_with_rows_total: int = 0
    # The router's picks over those calls (tokens x top-k, pad tokens
    # included) and the picks whose expert this rank holds = the rows the
    # grouped matmuls multiplied (ModelConfig.held_experts; equal where the
    # model is served whole). Same count, same readback.
    moe_picks_total: int = 0
    moe_picks_held_total: int = 0
    # KV bytes held by live references, summed over steps: the main pool's
    # referenced pages x its layers' page bytes plus the ring pool's (rings
    # and retained sections) x its layers'; and the tokens whose KV the
    # scheduled sequences hold, summed over the same steps. bytes / tokens
    # is what a cached token costs: every layer's page share without the
    # ring, the full layers' plus the rings with it.
    kv_bytes_in_use_total: int = 0
    cached_tokens_total: int = 0
    # Learned sparse attention (models with an indexer; 0 elsewhere),
    # counted on the host from each flat step's positions: computed query
    # tokens that had more than indexer_topk cached tokens (the selection
    # binds) and those that had not; the cached keys the indexer scored
    # for the former (sum of their context lengths; every layer scores
    # them again); indexer keys written (one a computed token and layer's
    # plane, counted once a token).
    sparse_bound_tokens_total: int = 0
    sparse_unbound_tokens_total: int = 0
    indexer_keys_scored_total: int = 0
    indexer_keys_written_total: int = 0
    # The same over a latent cache (models/mla_dsa.py; 0 elsewhere), each
    # x the model's layers: the rows the sparse latent read selected (per
    # computed token min(cached tokens, indexer_topk): what it must fetch
    # and multiply) and the latent rows written (one a computed token).
    sparse_rows_selected_total: int = 0
    latent_rows_written_total: int = 0
    # Per-row verify depth histogram (speculative engines): index d
    # counts decode rows dispatched with a 1 + draft width of exactly d
    # tokens (backed-off rows: 1; hot-draft rows: up to 1 + spec_k).
    # Two rows in DIFFERENT buckets on one step is the per-row adaptive
    # depth the flattened step dispatches in one program.
    spec_row_depth_hist: tuple = ()
    # Batch serving tier (docs/architecture/batch-processing.md): the
    # backfill band's observability contract — waiting batch-band rows
    # (the engine-side backlog the WVA counts as deferrable demand),
    # tokens computed for batch rows, batch rows recompute-preempted
    # when interactive load returned, and the fraction of the LAST
    # step's token budget the band backfilled.
    batch_backlog_jobs: int = 0
    batch_tokens: int = 0
    batch_preemptions: int = 0
    batch_backfill_utilization: float = 0.0
    # Robustness trail (docs/architecture/fault-tolerance.md): watchdog
    # trips on the step loop, CRC-rejected KV bundles, transfers that
    # degraded to local recompute, and the per-(stage, policy)
    # transfer-failure breakdown — a failure that leaves no metric
    # trail is invisible to the SLO layer.
    engine_watchdog_stalls_total: int = 0
    kv_bundle_crc_failures_total: int = 0
    kv_recompute_fallbacks_total: int = 0
    # ((stage, policy), count) pairs; rendered as labeled series.
    kv_transfer_failures: tuple = ()
    # Mid-stream failover (docs/architecture/fault-tolerance.md, stream
    # continuation contract): requests admitted as RESUMES (prefill of
    # an already-delivered prefix continuing at the exact next output
    # position), the delivered tokens those admissions replayed as
    # committed prefix, and resume requests the serving layer REJECTED
    # (invalid history / unsupported shape) — a rejected resume is a
    # client-visible stream failure upstream, so it must leave a trail.
    stream_resumes_total: int = 0
    resume_replayed_tokens_total: int = 0
    stream_resume_failures_total: int = 0
    # Wide-EP MoE (docs/architecture/wide-ep.md): the per-expert load
    # census drained from the runner each step. moe_expert_tokens is the
    # cumulative routed-token count per LOGICAL expert (rendered as the
    # moe_expert_tokens_total labeled series — the EPLB control loop's
    # input); dropped slots are valid (token, expert) assignments that
    # lost the capacity race; peak demand is the largest observed
    # per-destination dispatch demand as a capacity-factor multiple (the
    # adaptive controller's input: >1.0 means the static factor would
    # have dropped); capacity_factor is the LIVE factor the compiled
    # programs were traced at; rebalances counts EPLB placements applied.
    moe_expert_tokens: tuple = ()
    moe_dropped_slots_total: int = 0
    moe_peak_demand: float = 0.0
    moe_capacity_factor: float = 0.0
    moe_rebalances_total: int = 0
    # Million-token context tier (docs/architecture/long-context.md):
    # bytes of live-sequence KV spilled to the host tier by the decode
    # pager, restores that were NOT fully pre-staged when the sequence
    # needed them (the pager's miss signal — late prefetches serialize a
    # host->HBM wait onto the decode path), and ring collective steps
    # the context-parallel prefill dispatched (cp per cp-prefill call).
    kv_paged_out_bytes: int = 0
    kv_pager_prefetch_late_total: int = 0
    cp_ring_steps_total: int = 0


@dataclass
class _InflightStep:
    """One dispatched-but-unread engine step (async stepping slot)."""

    batch: ScheduledBatch
    pending_prefill: PendingPrefill | None
    pending_decode: PendingDecode | None
    pending_unified: PendingUnified | None = None


@dataclass
class _StagedStep:
    """The pipeline's other slot: the batch scheduled (speculatively)
    and staged on the host while ``_InflightStep`` runs on the device."""

    batch: ScheduledBatch
    # What of its dispatch is built already (``LLMEngine._stage``).
    staging: StagedDecode | StagedVerify | StagedUnified | None = None
    topped_up: bool = False  # took in rows admitted after the schedule
    admit_s: float = 0.0  # host seconds spent scheduling and topping up
    in_wait_s: float = 0.0  # ... of them inside the wait for the readback
    in_gap_s: float = 0.0  # ... and behind it, with the device empty
    # Dispatched the moment the step in flight was seen ready, before its
    # readback (``LLMEngine._dispatch_early``): when the dispatch returned.
    early_at: float | None = None


class LLMEngine:
    def __init__(
        self,
        config: EngineConfig,
        mesh_ctx: MeshContext | None = None,
        params: dict | None = None,
        event_sink: KVEventSink | None = None,
        _synchronous_step: bool = False,
    ) -> None:
        self.config = config
        import jax

        # Multi-host: staging programs (page gather/scatter) are lockstep-
        # broadcast to every process by the runner, so P/D transfer and
        # tiered offload compose with a multi-process mesh — the
        # reference's flagship 16P+16D wide-EP topology does exactly this
        # (wide-ep-lws/README.md + multi-node.md). The network-facing
        # halves (shipper server, host cache, store client) live on the
        # LEADER only; followers just mirror device programs.
        follower = jax.process_count() > 1 and jax.process_index() != 0
        self.ctx = mesh_ctx or build_mesh(config.parallel)
        # SWA ring (CacheConfig.swa_ring): sliding-window layers move to a
        # fixed per-sequence page ring in their own pool. Ring content is
        # transient per sequence; prefix caching stays ON for the main
        # (full-attention) pool and becomes HYBRID: hits are taken only
        # when a retained sliding section can seed the fresh ring
        # (RetainedStateCache — the reference's hybrid KV-cache manager
        # role). Tiered offload still refuses (host-cached pages would
        # lack sliding-layer KV).
        config.check_state_space()
        # A model with state-space layers: the same machinery over the state
        # pool, a "ring" of one slot a sequence (config.StateSlotSpec).
        self._swa = swa_ring_spec(
            config.model, config.cache, config.scheduler
        ) or state_slot_spec(config.model, config.scheduler)
        self._state_pool = self._swa is not None and self._swa.recurrent
        if self._swa is not None:
            if not config.scheduler.enable_chunked_prefill:
                raise ValueError(
                    "kv_swa_ring requires chunked prefill: a whole-prompt "
                    "chunk can exceed the ring span the step-write/read "
                    "invariant is sized for (SwaRingSpec.chunk_tokens)"
                )
            if config.offload is not None and config.offload.enabled:
                raise ValueError(
                    "kv_swa_ring does not compose with tiered KV offload: "
                    "host-cached pages would lack the sliding layers' KV "
                    "— disable one of the two"
                )
        # HYBRID prefix caching under the ring: the main pool (full-
        # attention layers) stays hashed/reusable; a hit is USABLE only
        # when the retained sliding section (RetainedStateCache) can seed
        # the fresh ring, so the scheduler's bare shortcut is disabled
        # (scheduler._apply_prefix_cache) and hits happen at admission.
        # With section retention off, hits are structurally impossible —
        # downgrade APC entirely so the engine doesn't hash and
        # advertise blocks (BlockStored events) a router would route to
        # in vain.
        prefix_caching = config.cache.enable_prefix_caching
        if (
            self._swa is not None
            and prefix_caching
            and config.cache.swa_section_cache <= 0
        ):
            logging.getLogger(__name__).info(
                "kv_swa_ring with swa_section_cache=0: disabling prefix "
                "caching (no retained sliding sections -> no usable hits)"
            )
            prefix_caching = False
        # Tiered offload wraps the event sink (device evictions of host-held
        # pages downgrade to cpu-tier stores instead of removals).
        self._host_cache = None
        self._kvstore_client = None
        self._federation = None
        if config.offload is not None and config.offload.enabled and not follower:
            from llmd_tpu.kvtransfer.offload import HostKVCache, TieredEventSink

            if config.offload.store_master_url:
                from llmd_tpu.federation import KVFederation
                from llmd_tpu.kvstore import CrossSliceStoreClient

                self._kvstore_client = CrossSliceStoreClient(
                    master_url=config.offload.store_master_url,
                    advertised_host=config.kv_host,
                    data_port=config.offload.store_data_port,
                    segment_bytes=config.offload.store_segment_bytes,
                )
                self._federation = KVFederation(
                    self._kvstore_client,
                    publish_policy=config.offload.publish_policy,
                    publish_min_hits=config.offload.publish_min_hits,
                )
            self._host_cache = HostKVCache(
                max_pages=config.offload.cpu_chunks,
                fs_dir=config.offload.fs_dir,
                fs_max_pages=config.offload.fs_max_pages,
                federation=self._federation,
            )
            event_sink = TieredEventSink(event_sink or KVEventSink(), self._host_cache)
            if self._federation is not None:
                # Accepted publications advertise the store tier
                # (BlockStored medium="store") through the same sink.
                self._federation.event_sink = event_sink
        self.allocator = PageAllocator(
            num_pages=config.cache.num_blocks,
            page_size=config.cache.page_size,
            enable_prefix_caching=prefix_caching,
            event_sink=event_sink,
        )
        # Hybrid-APC retention rides a PROVISIONED budget on top of the
        # ring pool (the auto-sized pool is exactly max_num_seqs rings —
        # retention must never eat ring capacity).
        self._swa_retention_budget = 0
        swa_sections_cap = swa_section_count(config.cache, config.scheduler)
        if (
            self._swa is not None
            and prefix_caching
            and config.cache.swa_section_cache > 0
        ):
            self._swa_retention_budget = (
                swa_sections_cap
                * self._swa.max_section_pages(config.cache.page_size)
            )
        if self._swa_retention_budget:
            # The ring pool ON THE DEVICE holds the retained sections too:
            # the runner sizes it from this spec, the allocator hands out
            # its page ids.
            self._swa = dataclasses.replace(
                self._swa,
                num_swa_blocks=self._swa.num_swa_blocks
                + self._swa_retention_budget,
            )
        self.swa_allocator = (
            PageAllocator(
                num_pages=self._swa.num_swa_blocks,
                page_size=config.cache.page_size,
                enable_prefix_caching=False,
            )
            if self._swa is not None
            else None
        )
        self.scheduler = EngineScheduler(
            config.scheduler, config.cache, self.allocator,
            config.model.max_model_len,
            swa_allocator=self.swa_allocator,
            swa_ring_pages=self._swa.ring_pages if self._swa else 0,
            swa_chunk_tokens=self._swa.chunk_tokens if self._swa else 0,
            state_aligned=self._state_pool,
        )
        self.runner = ModelRunner(
            config, self.ctx, params=params, swa_spec=self._swa
        )
        # Hybrid-APC section retention (ring engines with APC on).
        self._swa_sections = None
        if (
            self._swa is not None
            and prefix_caching
            and config.cache.swa_section_cache > 0
        ):
            self._swa_sections = RetainedStateCache(
                self.swa_allocator, self.runner, swa_sections_cap,
                self._swa_retention_budget,
                is_live=self.allocator.has_cached,
            )
            self.scheduler.capture_hook = self._capture_section
            self.scheduler.hybrid_hit_hook = self._try_hybrid_ring_hit
            self.scheduler.ring_pressure_hook = self._swa_sections.evict_one
        self.stats = EngineStats(
            num_pages=config.cache.num_blocks, page_size=config.cache.page_size
        )
        # Static surface of the adapter contract: present from the first
        # scrape, not the first step (load failures can precede steps).
        self.stats.max_lora = config.model.num_lora_adapters
        self._counter = itertools.count()
        self._embed_lock = threading.Lock()

        # Multi-tenant LoRA (docs/architecture/multi-tenant-lora.md): a
        # paged adapter pool — num_lora_adapters HBM slots over an
        # unbounded host-RAM registry. Requests naming a non-resident
        # adapter PARK in _lora_parked (the loading queue) and are
        # admitted at a step boundary once their weights install; the
        # batch never stalls on a tenant miss. Slot installs ride the
        # runner's _OP_LORA lockstep broadcast, so multi-host replicas
        # flip residency atomically.
        self.adapter_registry = None
        self.adapter_pool = None
        self._lora_parked: list = []
        # Terminal ABORT outputs for parked rows whose adapter vanished
        # (defensive; drained into the next step's return).
        self._lora_failed_outputs: list[RequestOutput] = []
        # Group-streamed KV imports (docs/architecture/kv-cache.md
        # "layer-streamed import"): requests whose transferred KV is
        # still on the wire park here — admitted by _admit_kv_streams at
        # a step boundary once the stream resolves (apply on success,
        # plain recompute on failure). The serving layer submits them as
        # soon as the FIRST layer group is resident, so admission,
        # scheduling, and host staging all overlap the remaining wire
        # transfer. Entries: (Request, KVStreamHandle).
        self._kv_parked: list = []
        if config.model.lora_dynamic and not follower:
            from llmd_tpu.lora import AdapterPool, AdapterRegistry

            self.adapter_registry = AdapterRegistry()
            self.adapter_pool = AdapterPool(
                self.adapter_registry,
                install=self.runner.set_lora_weights,
                num_slots=config.model.num_lora_adapters,
                pinned=self._adapter_pinned,
            )

        # Tiered offload pump (save-on-commit / restore-on-prefill).
        self.offloader = None
        if self._host_cache is not None:
            from llmd_tpu.kvtransfer.offload import OffloadConnector

            self.offloader = OffloadConnector(
                self.runner, self.allocator, self._host_cache
            )
            self.allocator.commit_hook = self.offloader.on_commit

        # Decode-time KV pager (OffloadConfig.decode_paging): spills cold
        # page ranges of live long-context sequences through the offload
        # tier and streams the attention window back ahead of resume, so
        # resident HBM per sequence is bounded by window + horizon, not
        # context length (docs/architecture/long-context.md).
        self.pager = None
        if (
            self.offloader is not None
            and config.offload.decode_paging
            and not follower
        ):
            windows = config.model.layer_windows
            if not windows or min(windows) <= 0:
                raise ValueError(
                    "offload.decode_paging requires every layer to be "
                    "sliding-window: a full-attention layer reads "
                    "arbitrarily far back, so no page is ever cold"
                )
            self.runner._require_single_host("decode-time KV paging")
            from llmd_tpu.engine.pager import KVPager

            self.pager = KVPager(
                self.runner,
                self.scheduler,
                self.allocator,
                self._host_cache,
                window=max(windows),
                horizon=config.offload.pager_horizon_tokens,
                stream_groups=config.kv_stream_groups,
            )
            self.scheduler.park_hook = self.pager.park

        # P/D disaggregation: optional KV-transfer connector (reference
        # TPUConnector roles, pd tpu patch-decode.yaml:17-20).
        self.kv_connector = None
        if config.kv_role and not follower:
            from llmd_tpu.kvtransfer.connector import KVTransferConfig, TPUConnector

            kv_cfg = KVTransferConfig(
                role=config.kv_role,
                host=config.kv_host,
                port=config.kv_transfer_port,
                lease_ms=config.kv_lease_ms,
                load_failure_policy=config.kv_load_failure_policy,
                transfer_dtype=config.kv_transfer_dtype,
                local_fastpath=config.kv_local_fastpath,
                stream_groups=config.kv_stream_groups,
            )
            self.kv_connector = TPUConnector(kv_cfg, self.runner, self.allocator)
            self.scheduler.finish_hook = self._on_finish

        # The two-slot pipeline is how the engine steps: one batch
        # executing on device while the next is speculatively scheduled,
        # admitted and staged on host (_step_async). The synchronous step
        # stays where its shape is itself a correctness contract, which
        # the code can see: multi-host lockstep followers mirror a totally
        # ordered op stream whose cadence the leader's sync step defines,
        # and P/D eager-ACK producers answer before the readback on the
        # promise that nothing was reordered around the enqueued KV
        # snapshots. No configuration key selects the step:
        # ``_synchronous_step`` is the seam through which the two-way
        # parity tests and ``bench.py --parts async_step`` reach the
        # synchronous shape on a single host.
        self._async = not _synchronous_step
        if self._async and jax.process_count() > 1:
            logging.getLogger(__name__).info(
                "synchronous step: multi-host lockstep engines keep the "
                "synchronous step shape"
            )
            self._async = False
        if self._async and config.kv_role in ("kv_producer", "kv_both"):
            logging.getLogger(__name__).info(
                "synchronous step: P/D eager-ACK producers rely on "
                "synchronous step ordering"
            )
            self._async = False
        self.scheduler.pipelined = self._async
        # Installed by the serving layer (AsyncEngine), as finish_hook is
        # by the connector: drains the requests and aborts that arrived
        # since the last call into add_request / abort_request. The
        # pipelined step polls it while the device runs, so that an
        # arrival during step N rides step N+1 (_top_up).
        self.intake_hook = None
        self._inflight: _InflightStep | None = None
        # The host's tail (EngineStats): WaitTiming.ready_at of the step
        # before while the pipeline has not run empty since; the collector's
        # totals and the stepping thread's (id, CPU s, involuntary switches)
        # as the last step found them.
        self._paced_from: float | None = None
        self._gc_seen: tuple | None = profiling.gc_watch()
        self._thread_seen: tuple | None = None
        # time.monotonic() at the end of the newest step readback: the
        # serving loop counts an output's deliver lag from it.
        self.last_readback_at = time.monotonic()
        # (kind, rows, tokens) of the batch this call of step() finished
        # (async, first call of a pipeline: dispatched), for the llmd.step
        # span and the by-kind sums.
        self._step_carried: tuple[str, int, int] = ("empty", 0, 0)
        # Aborts that arrived while their request was in flight: freeing
        # the pages immediately would hand them to another sequence while
        # the device still writes them — applied at the reconcile point.
        self._deferred_aborts: set[str] = set()

        # Speculative decoding (SchedulerConfig.speculative_ngram):
        # model-free n-gram drafting + one-pass verification. The
        # proposer is host-only; drafts are proposed at DISPATCH time
        # from committed history (async staging runs a step early), and
        # acceptance/rollback live in the scheduler's update loop.
        self._spec_proposer = None
        # Per-row verify depth histogram (index = 1 + draft width; see
        # EngineStats.spec_row_depth_hist).
        self._spec_row_depth = [0] * (2 + config.scheduler.spec_ngram_k)
        if config.scheduler.speculative_ngram:
            from llmd_tpu.engine.spec import NgramProposer

            self._spec_proposer = NgramProposer(
                min_match=config.scheduler.spec_ngram_min_match
            )

        # Wide-EP MoE control loops (docs/architecture/wide-ep.md): the
        # runner accumulates a device-side census ([E] routed tokens per
        # logical expert, dropped slots, peak dispatch demand); the engine
        # drains it at step boundaries and feeds two slow controllers —
        # adaptive capacity (ep_capacity_adaptive) and EPLB placement
        # (eplb_interval_steps). Both act through runner methods that
        # rebuild the compiled programs, so they only ever fire between
        # steps. EPLB is leader-only single-host (the remap gather is a
        # host-driven reshard).
        pc = config.parallel
        self._moe_active = self.runner._ep_active
        self._moe_expert_tokens = (
            np.zeros(config.model.num_experts, np.int64)
            if self._moe_active else None
        )
        self._adaptive_cap = None
        if self._moe_active and pc.ep_capacity_adaptive:
            from llmd_tpu.parallel.eplb import AdaptiveCapacity

            self._adaptive_cap = AdaptiveCapacity(base=pc.ep_capacity_factor)
        self._eplb_interval = (
            int(pc.eplb_interval_steps)
            if self._moe_active and jax.process_count() == 1 else 0
        )
        self._eplb_redundancy = int(pc.eplb_redundancy)
        self._eplb_next = self._eplb_interval
        self._eplb_window_base = (
            np.zeros(config.model.num_experts, np.int64)
            if self._eplb_interval else None
        )
        if self._moe_active:
            self.stats.moe_expert_tokens = (0,) * config.model.num_experts
            self.stats.moe_capacity_factor = self.runner.ep_capacity

    def _on_finish(self, req) -> None:
        if self.kv_connector is not None and self.kv_connector.wants_export(req):
            req.export_params = self.kv_connector.export_finished(req)

    def _section_key(self, prompt_token_ids: list[int], extra: bytes):
        """(chain-hash key, n_pre, s0) of a prompt's retained section —
        identical derivation on capture and seed, folding the same extra
        (LoRA/multimodal) the full-pool page hashes fold."""
        from llmd_tpu.engine.kv_cache import page_hashes_for_tokens

        page = self.config.cache.page_size
        n_pre, s0, _cnt = self._swa.section(len(prompt_token_ids), page)
        if n_pre <= s0:
            return None, 0, 0
        hashes = page_hashes_for_tokens(
            list(prompt_token_ids[: n_pre * page]), page, extra=extra
        )
        if len(hashes) < n_pre:
            return None, 0, 0
        return hashes[n_pre - 1], n_pre, s0

    def _capture_key(self, req):
        """``_section_key`` of ``req``'s prompt, from the hash the admission's
        walk left on the request where it is there for this ``n_pre``; a
        request that came another way (a P/D preload, one the pager
        resumed) is hashed again, and counted."""
        page = self.config.cache.page_size
        n_pre, s0, _cnt = self._swa.section(len(req.prompt_token_ids), page)
        if n_pre <= s0:
            return None, 0, 0
        if req.capture_key is not None and req.capture_key[0] == n_pre:
            return req.capture_key[1], n_pre, s0
        self._swa_sections.rehashed += 1
        return self._section_key(
            req.prompt_token_ids, self.scheduler.hash_extra(req)
        )

    def _finish_key(self, req):
        """(key, n_pre, s0) of the section at ``req``'s finish boundary, the
        value the next turn's admission walk computes for that page: the
        commit chain holds the hash of the page before it (every commit
        registers the pages it filled, and the step in flight computes the
        boundary's last position, whose token is its input), so ONE page is
        hashed and no prompt walked. No key where the chain stands
        elsewhere: an entry under another key could never be hit."""
        from llmd_tpu.engine.kv_cache import hash_page

        page = self.config.cache.page_size
        at = req.finish_capture_at
        n_pre, s0, _cnt = self._swa.section(at + 1, page)
        tail, committed = self.scheduler.commit_chain_state(req)
        if committed != n_pre - 1:
            return None, 0, 0
        key = hash_page(
            tail, req.tokens_between(at - page, at),
            self.scheduler.hash_extra(req),
        )
        return key, n_pre, s0

    def _capture_section(self, req, point: str) -> None:
        """Scheduler hook (``capture_hook``), the one capture behind all
        three capture points, best effort: a failure costs a future hit
        and nothing else. It runs behind the DISPATCH of the step that
        leaves the state at the point: the chunk that completes the prompt
        (for a recurrent state: that leaves it at the prompt's last full
        page; behind it the ring holds the prompt's trailing window), the
        chunk that passes the full-page run the request was refused at
        admission (``Request.swa_capture``; the ring then holds the window
        before every page boundary of the chunk), and the decode step that
        fills the last page before a finish by length. At FINISH time
        itself the ring has advanced past every boundary and the slot is
        released, which is why a capture is taken on the way, mirroring
        the P/D export's staleness rule."""
        t0 = time.monotonic()
        kept = self._swa_sections
        try:
            if point == AT_RUN_END:
                pages, key = req.swa_capture
                # The one geometry (SwaRingSpec.section) of a prompt that
                # continues right after the run.
                page = self.config.cache.page_size
                n_pre, s0, _cnt = self._swa.section(pages * page + 1, page)
            elif point == AT_FINISH:
                key, n_pre, s0 = self._finish_key(req)
            else:
                key, n_pre, s0 = self._capture_key(req)
            if key is not None and req.swa_block_ids:
                made = kept.capture(
                    key, req.swa_block_ids, s0, n_pre,
                    shared=point == AT_RUN_END,
                )
                if made and point == AT_FINISH:
                    kept.finish_captures += 1
        # llmd: allow(broad-except) -- best-effort section retention; a capture failure only costs a future cache hit
        except Exception:
            logging.getLogger(__name__).exception(
                "swa section capture failed (serving unaffected)"
            )
        finally:
            kept.capture_host_ms += (time.monotonic() - t0) * 1e3

    def _commit(self, batch: ScheduledBatch, sampled) -> dict:
        """The scheduler's commit of a step that has been read back."""
        accepted = self.scheduler.update_after_step(batch, sampled)
        if self._swa_sections is not None:
            # The full pages behind the step's captures are registered now.
            self._swa_sections.settle()
        return accepted

    def _capture_behind(self, batch: ScheduledBatch) -> None:
        """Behind ``batch``'s dispatch: the retained-state captures of the
        rows it leaves at a capture point
        (``EngineScheduler.capture_dispatched``). The copy is
        a program of its own that reads the pool the step has just been
        handed, so the device runs it behind the step and in front of the
        next one; in the multi-host leg its ``_OP_KV_COPY`` goes out behind
        the step's opcode, from the one thread that sends both."""
        if self._swa_sections is not None:
            self.scheduler.capture_dispatched(batch)

    # ------------------------------------------------------------------ #

    def add_request(
        self,
        prompt_token_ids: list[int],
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        priority: int = 0,
        kv_transfer_params: dict | None = None,
        lora_id: int = 0,
        lora_name: str = "",
        resume_output_tokens: int = 0,
    ) -> str:
        if not prompt_token_ids:
            raise ValueError("empty prompt")
        if resume_output_tokens and not (
            0 < resume_output_tokens < len(prompt_token_ids)
        ):
            raise ValueError(
                f"resume_output_tokens {resume_output_tokens} must leave a "
                f"non-empty prompt head (prompt carries "
                f"{len(prompt_token_ids)} tokens)"
            )
        park_adapter = False
        lora_lease = ""
        if lora_name and self.adapter_pool is not None:
            # Dynamic pool path: names resolve to slots HERE (the serving
            # layer no longer owns a fixed name->slot map). Resident
            # adapters ride their slot; registered-but-cold adapters park
            # in the loading queue; unknown names are a client error.
            # acquire() holds an admission lease so a concurrent install
            # (load API prefetch / embed cold load) cannot evict the slot
            # before this row is visible to the pinned scan.
            slot = self.adapter_pool.acquire(lora_name)
            if slot is not None:
                lora_id = slot
                lora_lease = lora_name
            elif self.adapter_registry.has(lora_name):
                lora_id = 0  # assigned when the cold load installs
                park_adapter = True
            else:
                raise ValueError(
                    f"unknown lora_name {lora_name!r} (loaded adapters: "
                    f"{self.adapter_registry.names()})"
                )
        elif lora_name and not lora_id:
            # Static path: the serving layer maps names to slots before
            # add_request — a name arriving WITHOUT a slot is exactly the
            # silent-base-model bug this guard exists for.
            raise ValueError(
                f"unknown lora_name {lora_name!r} (this engine serves "
                f"{self.config.model.num_lora_adapters} fixed adapter "
                "slot(s); map the name to its slot id, or enable the "
                "dynamic pool with lora_dynamic)"
            )
        try:
            return self._admit_request(
                prompt_token_ids, sampling, request_id, priority,
                kv_transfer_params, lora_id, lora_name,
                resume_output_tokens, park_adapter,
            )
        finally:
            # The admission lease only bridges the resolve->admitted
            # window; from here the scheduler-list pinned scan (or the
            # parked queue) carries the pin.
            if lora_lease:
                self.adapter_pool.release_acquire(lora_lease)

    def _admit_request(
        self,
        prompt_token_ids: list[int],
        sampling: SamplingParams | None,
        request_id: str | None,
        priority: int,
        kv_transfer_params: dict | None,
        lora_id: int,
        lora_name: str,
        resume_output_tokens: int,
        park_adapter: bool,
    ) -> str:
        if lora_id and not (
            0 < lora_id <= self.config.model.num_lora_adapters
        ):
            raise ValueError(
                f"lora_id {lora_id} out of range "
                f"(model has {self.config.model.num_lora_adapters} adapters)"
            )
        if len(prompt_token_ids) >= self.config.model.max_model_len:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} >= max_model_len "
                f"{self.config.model.max_model_len}"
            )
        sched = self.config.scheduler
        if (
            not sched.enable_chunked_prefill
            and len(prompt_token_ids) > sched.max_num_batched_tokens
        ):
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} > max_num_batched_tokens "
                f"{sched.max_num_batched_tokens} and chunked prefill is disabled"
            )
        rid = request_id or f"req-{next(self._counter)}-{uuid.uuid4().hex[:8]}"
        # P/D consumer: pull remote KV and seed the local prefix cache before
        # the request is ever scheduled, so prefill becomes a cache hit. The
        # async serving layer pre-fetches off-thread and hands the bundle in
        # via "__pulled__"; the sync path fetches inline. Ring engines
        # (kv_swa_ring) have no prefix cache — their transfers land via
        # the PRELOAD path: pages (full-group + a fresh ring holding the
        # sliding-layer section) handed straight to the Request below.
        preload = None
        kv_stream = None
        if self.kv_connector is not None and self.kv_connector.wants_import(
            kv_transfer_params
        ):
            kv_transfer_params = dict(kv_transfer_params)
            # Group-streamed import (v3 wire): the serving layer submits
            # at first-group-resident with the in-flight handle; the
            # request PARKS below and _admit_kv_streams finalizes at a
            # step boundary — admission/scheduling overlap the rest of
            # the wire transfer.
            kv_stream = kv_transfer_params.pop("__stream__", None)
            if kv_stream is None:
                if "__pulled__" in kv_transfer_params:
                    bundle = kv_transfer_params.pop("__pulled__")
                else:
                    bundle = self.kv_connector.fetch_remote_policy(
                        list(prompt_token_ids), kv_transfer_params
                    )
                if bundle is not None:
                    if self._swa is not None:
                        preload = self.kv_connector.apply_preload(
                            list(prompt_token_ids), bundle,
                            self.swa_allocator, self._swa.ring_pages,
                        )
                    else:
                        self.kv_connector.apply_bundle(
                            list(prompt_token_ids), bundle
                        )
        # Tiered offload: pull host-cached pages extending the device prefix
        # run back into HBM before scheduling (restore-on-prefill).
        # (Streamed imports defer this to finalize: the transferred pages
        # land first, then the host tiers only fill what is left.)
        if self.offloader is not None and kv_stream is None:
            self.offloader.restore_for_prompt(list(prompt_token_ids))
        req = Request(
            request_id=rid,
            prompt_token_ids=list(prompt_token_ids),
            sampling=sampling or SamplingParams(),
            priority=priority,
            kv_transfer_params=kv_transfer_params,
            lora_id=lora_id,
            lora_name=lora_name,
        )
        if resume_output_tokens:
            # Mid-stream failover resume: the prompt's TAIL is output the
            # client already received from a dead replica. Admitting it
            # through the recompute-preemption seam (delivered history
            # folded into the prompt, num_prior_output_tokens carrying
            # the output position) makes the continuation byte-identical
            # by construction: the seeded sampler derives per-(seed,
            # total_output_tokens) and the LENGTH check counts prior
            # output toward max_tokens.
            req.num_prior_output_tokens = resume_output_tokens
            self.stats.stream_resumes_total += 1
            self.stats.resume_replayed_tokens_total += resume_output_tokens
        if preload is not None:
            # Transferred KV handed straight to the request (ring mode):
            # admission skips the preloaded prefix; only the recompute
            # tail (at least the last token) is prefilled locally.
            req.block_ids = list(preload["block_ids"])
            req.swa_block_ids = list(preload["swa_block_ids"])
            req.num_computed_tokens = preload["tokens"]
            req.num_cached_tokens = preload["tokens"]
        if kv_stream is not None:
            # Waiting on the group stream: schedulable the moment the
            # import resolves (apply on success, recompute on failure).
            self._kv_parked.append((req, kv_stream, park_adapter))
            return rid
        if park_adapter:
            # Loading queue (multi-tenant-lora.md): the request waits for
            # its adapter's cold load — admitted by _admit_cold_loads at
            # a step boundary with its assigned slot. The batch keeps
            # serving resident tenants meanwhile.
            self._lora_parked.append(req)
            return rid
        self.scheduler.add_request(req)
        return rid

    def _try_hybrid_ring_hit(self, req) -> None:
        """Scheduler hook, at a request's admission (not at its arrival:
        what the requests queued in front of it have cached and captured
        meanwhile counts; of four sessions that open on one document
        together, two prefill it and two take the hit). Hybrid prefix hit
        under the ring: usable only when BOTH a
        full-pool prefix run AND a retained sliding section exist for
        the SAME span — then a fresh ring is seeded from the section
        (device copy) and the request starts past that span, like a
        locally-sourced P/D preload. Sections retained at SHORTER spans
        serve extended prompts too (the multi-turn grow case): the
        longest retained span covered by this prompt wins. Any miss,
        allocation failure, or device error degrades to a normal full
        prefill (resources released)."""
        from llmd_tpu.engine.kv_cache import (
            NoFreePagesError, page_hashes_for_tokens,
        )

        page = self.config.cache.page_size
        n_pre, _s0, _cnt = self._swa.section(len(req.prompt_token_ids), page)
        if n_pre <= 0:
            return
        lengths = self._swa_sections.candidate_lengths(n_pre)
        extra = self.scheduler.hash_extra(req)
        # ONE hash walk serves the section probes, the full-pool lookup and
        # the miss's note (the prompt is hashed nowhere else on this path).
        hashes = page_hashes_for_tokens(
            list(req.prompt_token_ids[: n_pre * page]), page, extra=extra
        )
        # ... and the capture at this prompt's end (``_capture_key``).
        req.capture_key = (n_pre, hashes[n_pre - 1])
        for k in lengths:
            key = hashes[k - 1]
            if not self._swa_sections.has(key):
                continue
            # Probe without touching: failed candidates must not inflate
            # hit metrics or refresh LRU recency of pages left unused.
            if self.allocator.peek_hash_run(hashes[:k]) < k:
                continue
            cached = self.allocator.lookup_and_touch_hashes(hashes[:k])
            if len(cached) < k:
                # Raced an eviction between peek and touch.
                if cached:
                    self.allocator.free(cached)
                continue
            ring_ids: list[int] = []
            try:
                ring_ids = self.swa_allocator.allocate(self._swa.ring_pages)
                if self._seed(key, ring_ids) is None:
                    raise KeyError("section evicted between has() and seed()")
            # llmd: allow(broad-except) -- a retained-section hit must never fail the request; degrades to a plain prefill
            except Exception as e:
                # Includes device/lockstep errors from the seed copy: a
                # hit must never fail the request — release and prefill.
                self.allocator.free(cached)
                if ring_ids:
                    self.swa_allocator.free(ring_ids)
                if not isinstance(e, (NoFreePagesError, KeyError)):
                    logging.getLogger(__name__).exception(
                        "hybrid ring seed failed; recomputing locally"
                    )
                return
            req.block_ids = cached
            req.swa_block_ids = ring_ids
            req.num_computed_tokens = k * page
            req.num_cached_tokens = k * page
            # Seed the commit chain past the hit (key IS hashes[k-1]) so
            # finish does not re-hash and re-commit the cached prefix —
            # duplicate BlockStored events would reach the router's
            # indexer.
            self.scheduler.seed_commit_chain(req, key, k)
            return
        # No section served. Where the main pool offers a run of full pages
        # all the same, that is a miss: the request prefills the span, and
        # leaves the section at the run's end behind as it passes it.
        run = self.allocator.peek_hash_run(hashes)
        if run > 0:
            self._swa_sections.misses += 1
            req.swa_capture = (run, hashes[run - 1])

    def _seed(self, key: bytes, ring_ids: list[int]):
        """``RetainedStateCache.seed``; a RING's seed under its own span and
        counters (a state pool's slot is one page, under ``llmd.state.seed``
        alone)."""
        if self._state_pool:
            return self._swa_sections.seed(key, ring_ids)
        t0 = time.monotonic()
        with profiling.span("llmd.ring.seed") as span:
            seeded = self._swa_sections.seed(key, ring_ids)
            if seeded is not None:
                s0, n_pre = seeded
                span.set_metadata(pages=n_pre - s0)
                self.stats.swa_ring_seeds_total += 1
                self.stats.swa_ring_seed_pages_total += n_pre - s0
        self.stats.swa_ring_seed_host_ms_total += (time.monotonic() - t0) * 1e3
        return seeded

    def abort_request(self, request_id: str) -> bool:
        for i, r in enumerate(self._lora_parked):
            if r.request_id == request_id:
                # Parked in the adapter loading queue: never scheduled,
                # nothing on device to reconcile.
                del self._lora_parked[i]
                return True
        for i, (r, handle, _pa) in enumerate(self._kv_parked):
            if r.request_id == request_id:
                # Parked on a group stream: abandon() releases the
                # fetched bundle (stream-reserved pages included) from
                # whichever side of the fetch-thread race holds it.
                del self._kv_parked[i]
                handle.abandon()
                return True
        if self._inflight is not None and any(
            s.request.request_id == request_id
            for s in self._inflight.batch.seqs
        ):
            # In-flight sequence (async stepping): the dispatched device
            # programs still write its pages — defer the abort to the
            # reconcile point instead of freeing pages mid-write.
            self._deferred_aborts.add(request_id)
            return True
        return self.scheduler.abort_request(request_id) is not None

    def cached_prefix_pages(self, prompt_token_ids: list[int]) -> int:
        """Leading FULL pages of this prompt already held locally (device
        prefix cache or tiered host/FS cache — restore-on-prefill pulls
        the latter in without a transfer). The P/D byte-diet probe: the
        sidecar asks before phase 1 so the producer skips staging pages
        the decode side already has (the reference's disagg decider asks
        the same question, scheduling.md:113)."""
        from llmd_tpu.engine.kv_cache import page_hashes_for_tokens

        hashes = page_hashes_for_tokens(
            list(prompt_token_ids), self.allocator.page_size
        )
        n = 0
        for h in hashes:
            if self.allocator.has_cached(h) or (
                self._host_cache is not None and self._host_cache.has(h)
            ):
                n += 1
            else:
                break
        return n

    def embed(
        self, prompts: list[list[int]], lora_id: int = 0, lora_name: str = ""
    ):
        """[n, H] mean-pooled L2-normalized embeddings (OpenAI
        /v1/embeddings surface); independent of the serving KV cache.

        Serialized: each call allocates a scratch KV pool, so unbounded
        concurrency (N executor threads x multi-GB scratch) would OOM the
        device under an embedding burst."""
        lease = ""
        if lora_name and self.adapter_pool is not None:
            # Embeddings have no loading queue (one-shot forward): make
            # the adapter resident now — the same install path a cold
            # generate pays at its step boundary — and hold the
            # admission lease across the WHOLE forward: embeds create
            # no scheduler row, so without the lease a concurrent cold
            # load could evict the slot and swap in another tenant's
            # weights mid-embed.
            for _ in range(3):
                slot = self.adapter_pool.acquire(lora_name)
                if slot is not None:
                    break
                if not self.adapter_registry.has(lora_name):
                    raise ValueError(
                        f"unknown lora_name {lora_name!r} (loaded "
                        f"adapters: {self.adapter_registry.names()})"
                    )
                self.adapter_pool.install_cold(lora_name)
            else:
                raise ValueError(
                    f"adapter {lora_name!r} cannot become resident: "
                    "every pool slot is pinned by in-flight requests"
                )
            lora_id = slot
            lease = lora_name
        try:
            if lora_id and not (
                0 < lora_id <= self.config.model.num_lora_adapters
            ):
                raise ValueError(f"lora_id {lora_id} out of range")
            with self._embed_lock:
                return self.runner.run_embed(prompts, lora_id=lora_id)
        finally:
            if lease:
                self.adapter_pool.release_acquire(lease)

    def close(self) -> None:
        """Release network-facing resources (KV connector, store client)
        and, in a multi-host world, release the follower processes."""
        self.runner.stop_followers()
        if self._gc_seen is not None:  # (idempotent)
            self._gc_seen = None
            profiling.gc_unwatch()
        if self.kv_connector is not None:
            self.kv_connector.close()
        if self._kvstore_client is not None:
            self._kvstore_client.close()

    def set_lora_weights(self, lora_id: int, weights: dict) -> None:
        """Install trained adapter weights into slot ``lora_id``; until
        then every slot serves exactly the base model (B init == 0).

        Refuses while requests on that slot are in flight (their KV was
        computed under the old weights — swap mid-decode would mix weight
        versions silently). Device AND host/FS cached pages are cleared:
        the reference's weight-rollout analog is the AllBlocksCleared KV
        event (kv-indexer.md:63)."""
        in_flight = [
            r.request_id
            for r in (*self.scheduler.running, *self.scheduler.waiting)
            if r.lora_id == lora_id
        ]
        if in_flight:
            raise RuntimeError(
                f"cannot swap lora slot {lora_id} weights with "
                f"{len(in_flight)} request(s) in flight (pause/drain first)"
            )
        self.runner.set_lora_weights(lora_id, weights)
        self.allocator.clear()
        if self._host_cache is not None:
            self._host_cache.clear()

    # ------------------------------------------------------------------ #
    # multi-tenant adapter pool (docs/architecture/multi-tenant-lora.md)

    def _adapter_pinned(self, name: str) -> bool:
        """Pin-while-referenced: an adapter named by any running or
        queued row must keep its slot — the forward reads slot weights
        every step, and displacing a referenced tenant would silently
        mix weight versions mid-stream. (The same scheduler-list scan
        set_lora_weights uses for its in-flight refusal.)"""
        return any(
            r.lora_name == name
            for r in (*self.scheduler.running, *self.scheduler.waiting)
        )

    def _lora_rows_inflight(self, name: str) -> int:
        return sum(
            1
            for r in (
                *self.scheduler.running,
                *self.scheduler.waiting,
                *self._lora_parked,
            )
            if r.lora_name == name
        )

    def _normalize_adapter_weights(self, weights: dict) -> dict:
        """Slot-form factor tensors with ABSENT pairs zero-filled: a
        pool install must fully overwrite the evicted tenant's slot, or
        a q-only adapter would silently compose with the previous
        resident's v factors."""
        import numpy as np

        from llmd_tpu.lora.source import FACTOR_KEYS

        layers = self.runner.params["layers"]
        out = {}
        for k in FACTOR_KEYS:
            shape = (layers[k].shape[0], *layers[k].shape[2:])
            if k in weights:
                out[k] = np.ascontiguousarray(
                    np.asarray(weights[k], np.float32)
                ).reshape(shape)
            else:
                out[k] = np.zeros(shape, np.float32)
        return out

    def load_adapter(
        self, name: str, source: str = "", weights: dict | None = None
    ) -> None:
        """Register ``name`` in the serving registry (the
        ``/v1/load_lora_adapter`` contract): fetch + decode its weights
        (CRC-framed for URL/kvstore sources), then eagerly install into
        a FREE pool slot when one exists — otherwise the adapter stays
        one cold load away. Any failure raises without touching the
        registry; the caller surfaces a counted 4xx."""
        if self.adapter_pool is None:
            raise RuntimeError(
                "dynamic adapter serving is disabled "
                "(ModelConfig.lora_dynamic / --lora-pool-slots)"
            )
        if weights is None:
            from llmd_tpu.lora import AdapterFetchError, fetch_adapter

            try:
                weights = fetch_adapter(
                    source,
                    name=name,
                    model_cfg=self.config.model,
                    kvstore_get=(
                        self._kvstore_client.get
                        if self._kvstore_client is not None
                        else None
                    ),
                )
            except (AdapterFetchError, ValueError):
                self.stats.lora_load_failures_total += 1
                raise
        weights = self._normalize_adapter_weights(weights)
        _, stale_cache = self.adapter_registry.register(name, weights, source)
        if stale_cache:
            # The name was previously served with DIFFERENT weights:
            # its name-salted prefix pages are stale. Same blast radius
            # as a static weight swap (AllBlocksCleared analog).
            self.allocator.clear()
            if self._host_cache is not None:
                self._host_cache.clear()
        self.adapter_pool.install_prefetch(name)
        self._refresh_lora_stats()

    def _refresh_lora_stats(self) -> None:
        """Registry/residency stats refresh OUTSIDE the step loop too:
        an idle engine that just loaded adapters must advertise them on
        the next scrape (the tri-state scorer routes on these labels),
        not after its first generate request."""
        if self.adapter_pool is None:
            return
        pc = self.adapter_pool.counters()
        self.stats.lora_pool_resident_adapters = pc["resident"]
        self.stats.lora_pool_evictions_total = pc["evictions"]
        self.stats.lora_cold_loads_total = pc["cold_loads"]
        self.stats.resident_lora_adapters = tuple(
            self.adapter_pool.resident_names()
        )
        self.stats.available_lora_adapters = tuple(
            self.adapter_registry.names()
        )

    def unload_adapter(self, name: str) -> None:
        """Unregister ``name`` and release its slot
        (``/v1/unload_lora_adapter``). Refuses while any row references
        the adapter — mirroring set_lora_weights' in-flight refusal."""
        if self.adapter_pool is None:
            raise RuntimeError("dynamic adapter serving is disabled")
        if not self.adapter_registry.has(name):
            raise KeyError(
                f"adapter {name!r} is not loaded "
                f"(loaded: {self.adapter_registry.names()})"
            )
        n = self._lora_rows_inflight(name)
        if n:
            raise RuntimeError(
                f"cannot unload adapter {name!r} with {n} request(s) in "
                "flight (drain first)"
            )
        # remove() re-checks references UNDER the pool lock (admission
        # leases + the pinned scan), so a request admitted between the
        # friendly count above and here still refuses — a freed slot is
        # never reused under a live row.
        self.adapter_pool.remove(name)
        self.adapter_registry.unregister(name)
        self._refresh_lora_stats()

    def _admit_cold_loads(self) -> None:
        """Drain the adapter loading queue at a step boundary: install
        the head request's adapter (evicting an idle LRU resident when
        no slot is free) and admit every parked row for it. Stops when
        every slot is pinned by in-flight rows — backpressure, the
        parked rows wait for capacity."""
        while self._lora_parked:
            name = self._lora_parked[0].lora_name
            slot = self.adapter_pool.slot_of(name)
            if slot is None:
                rec = self.adapter_registry.get(name)
                if rec is None:
                    # Unloaded while parked (unload refuses this; purely
                    # defensive): fail the rows rather than hang them —
                    # a terminal ABORT output rides the step's return so
                    # subscribers see a finished stream, never silence.
                    failed = [
                        r for r in self._lora_parked if r.lora_name == name
                    ]
                    self._lora_parked = [
                        r for r in self._lora_parked if r.lora_name != name
                    ]
                    for r in failed:
                        self._lora_failed_outputs.append(RequestOutput(
                            request_id=r.request_id,
                            new_token_ids=[],
                            finished=True,
                            finish_reason=FinishReason.ABORT,
                            num_prompt_tokens=len(r.prompt_token_ids),
                            num_output_tokens=0,
                        ))
                    logging.getLogger(__name__).error(
                        "adapter %r vanished with %d parked request(s); "
                        "aborted", name, len(failed),
                    )
                    continue
                slot = self.adapter_pool.install_cold(name)
                if slot is None:
                    return  # every slot pinned; keep waiting
            still = []
            for req in self._lora_parked:
                if req.lora_name == name:
                    req.lora_id = slot
                    self.scheduler.add_request(req)
                else:
                    still.append(req)
            self._lora_parked = still

    def _admit_kv_streams(self) -> None:
        """Drain resolved group-streamed imports at a step boundary.

        A landed bundle applies (hash-chain commit only — the fetch
        thread already scattered every group into pool pages) and the
        request goes to the scheduler, where the prefill is now a
        prefix-cache hit; a failed stream admits as a plain local
        recompute (the PR 7 degradation contract, byte-identical
        either way). When streams are the ONLY pending work, block
        briefly on the oldest handle so the step loop wakes the instant
        it resolves instead of busy-spinning."""
        while True:
            still: list = []
            admitted = False
            for req, handle, park_adapter in self._kv_parked:
                if not handle.done.is_set():
                    still.append((req, handle, park_adapter))
                    continue
                bundle = handle.take()
                if bundle is not None:
                    self.kv_connector.apply_bundle(
                        list(req.prompt_token_ids), bundle
                    )
                elif self.offloader is not None:
                    # Stream failed: give the host tiers their usual
                    # restore-on-prefill shot before the recompute.
                    self.offloader.restore_for_prompt(
                        list(req.prompt_token_ids)
                    )
                if park_adapter:
                    self._lora_parked.append(req)
                else:
                    self.scheduler.add_request(req)
                admitted = True
            self._kv_parked = still
            if admitted or not still:
                return
            if (
                self._inflight is not None
                or self.scheduler.has_work()
                or self._lora_parked
            ):
                return  # other work to run; re-check next step
            # Idle except for in-flight streams: wait on the oldest —
            # bounded so the serving loop still sees aborts promptly.
            if not still[0][1].done.wait(0.05):
                return

    def has_work(self) -> bool:
        return (
            self.scheduler.has_work()
            or self._inflight is not None
            or bool(self._lora_parked)
            or bool(self._kv_parked)
        )

    # ------------------------------------------------------------------ #

    def step(self) -> list[RequestOutput]:
        # Injection site: a wedged device program (engine.step.stall)
        # stalls the whole step — the AsyncEngine watchdog's job is to
        # notice, 503 /health and terminate in-flight streams. Unarmed
        # this is one module-global None check.
        faults.delay("engine.step.stall")
        t_in = time.monotonic()
        counted = self.stats.engine_steps_total
        self._step_carried = ("empty", 0, 0)
        if self._inflight is None:
            self._paced_from = None  # the pipeline ran empty: no pace across it
        with profiling.span("llmd.step") as step_span:
            with profiling.span("llmd.step.admit"):
                if self._kv_parked:
                    self._admit_kv_streams()
                if self._lora_parked:
                    self._admit_cold_loads()
                if self.pager is not None:
                    # Restore parked attention windows before scheduling —
                    # a still-pending fetch leaves the request fetch-pending
                    # (a wait state the scheduler skips, not a fault).
                    self.pager.pump(self.scheduler.waiting)
            t_admitted = time.monotonic()
            if self._inflight is not None:
                outputs = self._step_async()
            else:
                # Nothing in flight (the synchronous roles always; the
                # pipeline when it starts from empty): the step is landed
                # here and now, and the pipeline entered behind it.
                outputs = self._step_sync()
                if self._async:
                    self._prime()
            kind, rows, tokens = self._step_carried
            if rows:
                step_span.set_metadata(
                    kind=kind, rows=rows, tokens=tokens,
                    program=self.runner.last_program,
                )
            else:
                step_span.set_metadata(kind=kind)
            if self.stats.engine_steps_total != counted:
                self._count_step(kind, t_in, t_admitted)
        if self._lora_failed_outputs:
            outputs = [*self._lora_failed_outputs, *outputs]
            self._lora_failed_outputs = []
        return outputs

    def _count_step(self, kind: str, t_in: float, t_admitted: float) -> None:
        """The whole-step sums of a step that ran a batch (the phase sums
        were added by ``_finish_step``)."""
        st = self.stats
        step_ms = (time.monotonic() - t_in) * 1e3
        st.step_admit_ms_total += (t_admitted - t_in) * 1e3
        st.step_ms_total += step_ms
        if kind == "decode":
            st.step_ms_decode_total += step_ms
        else:
            st.step_ms_prefill_total += step_ms

    def _step_sync(self) -> list[RequestOutput]:
        t0 = time.monotonic()
        batch = self._schedule_spanned()
        if batch.is_empty:
            return []
        now = time.monotonic()
        # Eager-ACK: an export-only prefill's sampled token is thrown
        # away by the routing sidecar (the two-phase protocol only
        # consumes kv_transfer_params), so the producer's response
        # does not wait for prefill compute or the token readback —
        # device program order alone guarantees the KV snapshots the
        # consumer pulls are valid. Cuts compute + one host RTT off
        # the P/D TTFT critical path.
        eager_ack = bool(batch.prefills) and (
            self.kv_connector is not None
            and self.kv_connector.cfg.is_producer
            and all(
                s.request.kv_transfer_params is not None
                and s.request.kv_transfer_params.get("do_remote_decode")
                and s.request.sampling.max_tokens == 1
                for s in batch.prefills
            )
        )
        pend_p = pend_d = pend_u = None
        with profiling.span("llmd.runner.launch"):
            if not eager_ack and self._unified_eligible(batch):
                pend_u = self._dispatch_unified(batch, None)
            else:
                if batch.prefills:
                    pend_p = self.runner.dispatch_prefill(batch.prefills)
                    self.stats.step_dispatches_total += len(pend_p.entries)
                    for seq in batch.prefills:
                        self.stats.prompt_tokens += seq.num_tokens
                if batch.decodes:
                    pend_d = self._dispatch_decodes(batch.decodes)
            self.scheduler.note_dispatch(batch)
        t_dispatched = time.monotonic()
        self._capture_behind(batch)  # under the device, like the wait
        # One coalesced readback for the whole step (prefill bucket
        # groups + the decode window — or the one unified program —
        # come back in a single transfer).
        pres, dres = self.runner.wait_step(
            None if eager_ack else pend_p, pend_d, pend_u
        )
        waited = self.runner.last_wait
        t_read = self.last_readback_at = waited.read_at
        with profiling.span("llmd.step.finish") as finish_span:
            sampled, logprobs = self._collect(batch, pres, dres)
            accepted = self._commit(batch, sampled)
            outputs = self._assemble_outputs(batch, accepted, logprobs)
            if self.offloader is not None:
                # One bucketed HBM->host gather for the step's committed
                # pages.
                self.offloader.flush()
            if self.pager is not None:
                # Spill pages that fell below the window + prefetch
                # horizon.
                self.pager.tick(self.scheduler.running)
            finish_span.set_metadata(outputs=len(outputs))
        finish_s = time.monotonic() - t_read
        self._finish_step(
            batch, (t_dispatched - t0) + finish_s,
            schedule_s=now - t0, launch_s=t_dispatched - now,
            wait_s=t_read - t_dispatched, finish_s=finish_s,
            readback_s=waited.readback_s, ready_at=waited.ready_at,
        )
        return outputs

    def _step_async(self) -> list[RequestOutput]:
        """The pipelined step, two slots deep: while the in-flight batch
        N executes on device, the host does everything that does not need
        N's tokens — schedule N+1 speculatively (each in-flight decode
        assumed to land its tokens), prestage its host arrays, and, all
        through the wait for N's outputs, take in the requests that
        arrive and top the staged batch up with them. The moment N is
        seen ready N+1 is DISPATCHED (``_dispatch_early``): its decode
        rows take their input tokens from the device, where N left them
        (``ModelRunner.last_tokens``), so nothing of it waits for the
        host's copy. Then, under N+1: the readback, the commit (a late
        EOS/stop finish or an abort finds its row already in N+1: that
        row is wasted, dropped at N+1's commit, and what the request
        holds is released only then), N+1's retained-state captures,
        output assembly and the offloader's flush. Between two programs
        stand the ready lag and the jitted call (llmd.runner.launch: the
        fill and the put of N+1's payload were done ahead, ``_prepare``).

        A staged step that needs N's tokens ON THE HOST before it can be
        dispatched keeps the order the step had before (``_dispatches_early``
        reads it off the batch and the engine): readback, commit,
        reconcile (late finishes and aborts invalidate their staged rows —
        the released pages follow the recompute-preemption path), a last
        top-up and the fill-and-dispatch of N+1, the host's TURN, tiled
        without a hole by the spans llmd.runner.readback,
        llmd.step.commit, llmd.sched.schedule and llmd.runner.launch
        (docs/architecture/observability.md). Outputs arrive one call
        late; the pipeline is entered by ``_prime`` behind a step that
        landed synchronously (docs/architecture/async-scheduling.md)."""
        inflight = self._inflight
        # ---- overlapped host region: the device is executing N ----
        t0 = time.monotonic()
        slot = _StagedStep(self._schedule_spanned())  # speculative: pending counts
        slot.admit_s = time.monotonic() - t0
        slot.staging = self._stage(slot.batch)
        self._prepare(slot)
        # ---- wait for step N; dispatch N+1; N's one coalesced readback ----
        t_staged = time.monotonic()
        pres, dres = self.runner.wait_step(
            inflight.pending_prefill, inflight.pending_decode,
            inflight.pending_unified,
            # Serving: what arrives while N runs rides N+1, as it would
            # behind a synchronous step, and is admitted under the device.
            poll=None if self.intake_hook is None
            else functools.partial(self._admit_arrivals, slot),
            at_ready=functools.partial(self._dispatch_early, slot),
        )
        waited = self.runner.last_wait
        early = slot.early_at is not None
        t_read = self.last_readback_at = waited.read_at
        with profiling.span("llmd.step.commit") as commit_span:
            sampled, logprobs = self._collect(inflight.batch, pres, dres)
            accepted = self._commit(inflight.batch, sampled)
            if not early:
                self._inflight = None
            if self.intake_hook is not None:
                # The last instants' arrivals and aborts. With nothing in
                # flight an abort releases its row at once and the
                # reconcile below drops it with the late finishes; an
                # abort of a row of N+1 in flight is deferred to the
                # lines below.
                self.intake_hook()
            # (a request with a row still in flight keeps what it holds
            # until that row has landed: EngineScheduler._retire)
            for rid in sorted(self._deferred_aborts):
                self.scheduler.abort_request(rid)
            self._deferred_aborts.clear()
            if self.pager is not None:
                # Spill pages that fell below the window + prefetch
                # horizon HERE, the one point of the pipelined step where
                # nothing is in flight (an engine with the pager never
                # dispatches early; behind the re-dispatch every running
                # row is protected and the tick would spill nothing,
                # ever). The staged tables keep the spilled pages' stale
                # ids, as Request.block_ids does: every read of those
                # positions is window-masked.
                self.pager.tick(self.scheduler.running)
            # ---- reconcile the speculative slot against late finishes ----
            rolled = 0 if early else self._reconcile(slot)
            commit_span.set_metadata(rolled=rolled, early=early)
        t_reconciled = time.monotonic()
        prestaged = not slot.batch.is_empty
        if early:
            # N+1 has been on the device since N was seen ready: the
            # device waited for the ready lag and the dispatch alone.
            t_redispatched = slot.early_at
            redispatch_s = t_redispatched - waited.ready_at
            host_gap_s = redispatch_s
            self.stats.steps_dispatched_before_readback_total += 1
        else:
            if not prestaged:
                if self.scheduler.has_work():
                    # The slot is empty (every staged row rolled back, or
                    # all of N's rows foreseen to end): nothing is
                    # pending, so the freed rows, pages and budget are
                    # scheduled whole.
                    slot.batch = self._schedule_spanned()
                    slot.in_gap_s = time.monotonic() - t_reconciled
                    slot.admit_s += slot.in_gap_s
            elif self.scheduler.waiting:
                # Rows and budget that N's finishes gave back, and whatever
                # arrived in the last instants: admitted now, one step
                # sooner than the next speculative schedule would.
                self._top_up(slot)
            if not slot.batch.is_empty:
                self._dispatch_async(slot.batch, slot.staging)
            # The host's turn ends at the re-dispatch's return above (the
            # device's idle time a little later: the program's launch
            # latency is no host code's to hold).
            t_redispatched = time.monotonic()
            redispatch_s = t_redispatched - t_reconciled
            host_gap_s = t_redispatched - t_read
        if not slot.batch.is_empty:
            self.stats.steps_prestaged_total += prestaged
            self.stats.steps_topped_up_total += slot.topped_up
        # Output assembly and gauge refresh below overlap step N+1's
        # execution; so do N+1's captures, which want N's commit (a row
        # of a request that has just ended leaves nothing behind, and a
        # finish boundary's key hashes the token N sampled) and are on
        # the device's queue before the next dispatch either way.
        t_finish = time.monotonic()
        with profiling.span("llmd.step.finish") as finish_span:
            self._capture_behind(slot.batch)
            outputs = self._assemble_outputs(
                inflight.batch, accepted, logprobs
            )
            if self.offloader is not None:
                self.offloader.flush()
            finish_span.set_metadata(outputs=len(outputs))
        # The phases of THIS call, by the sync step's names, as host time
        # spent: schedule (with the top-ups, which are taken out of the
        # wait they ran in) and prestaging ran while the device executed
        # step N; commit/reconcile count as finish with the assembly. The
        # host gap is what the device waited for host code: from N's
        # readback's END to N+1's dispatch (commit + redispatch; the turn
        # is the readback more), or, on a step dispatched early, from the
        # first ready to the dispatch's return.
        early_s = redispatch_s if early else 0.0
        self._finish_step(
            inflight.batch, host_gap_s,
            schedule_s=slot.admit_s,
            launch_s=(t_staged - t0) + redispatch_s
            - (slot.admit_s - slot.in_wait_s),
            wait_s=t_read - t_staged - slot.in_wait_s - early_s,
            finish_s=(t_reconciled - t_read)
            + (time.monotonic() - t_finish),
            readback_s=waited.readback_s,
            ready_lag_bound_s=waited.ready_lag_bound_s,
            commit_s=t_reconciled - t_read,
            redispatch_s=redispatch_s,
            gap_admit_s=slot.in_gap_s,
            ready_at=waited.ready_at,
            # (an empty slot dispatched nothing: that idle time is the load's)
            dispatched_at=None if slot.batch.is_empty else t_redispatched,
        )
        return outputs

    def _dispatches_early(self, slot: _StagedStep) -> bool:
        """May ``slot``'s batch, staged under the step in flight, go to the
        device before that step is read back? Read off the batch and the
        engine, never an option. It may where every row's input is the
        host's already or the device's own: a prefill chunk's tokens, and
        a one-token decode row, whose token the step in flight leaves in
        ``ModelRunner.last_tokens``. Not a step with drafts (the proposer
        wants the token on the host), not a fused decode window (its K
        was chosen because nothing could be admitted; it amortises the
        turn already), not an engine with the decode pager (``pager.tick``
        wants the one instant with nothing in flight, which this order
        does not have), not a batch-band row while an interactive request
        waits (the head reclaims such a row's slot and pages at the last
        top-up, which wants the row out of flight: dispatched early step
        after step it would be protected until it ends), and not an empty
        slot (nothing to dispatch). Nor a step whose program has not been
        called at its shape yet, or is not staged whole (the split prefill
        programs, which are built at their dispatch): a shape's FIRST call
        is seconds of tracing and lowering whose time follows the Python
        path it is reached on (PERF.md section 7 (k); reached from
        ``wait_step``'s hook it read +0.2 s a bucket on the v5e host, 40 %
        of ``long-decode``'s warm ladder), so it stays on the path the
        set-up bound was set with, and loses nothing: the device waits
        seconds either way. The two roles whose order is a protocol
        (multi-host lockstep, the P/D producer) never come here: they step
        synchronously."""
        batch, staged = slot.batch, slot.staging
        waiting = self.scheduler.waiting
        if (
            batch.is_empty
            or self.pager is not None
            or any(
                s.draft_tokens is not None or s.num_tokens != 1
                for s in batch.decodes
            )
            or (
                waiting and not waiting[0].is_batch
                and any(s.request.is_batch for s in batch.seqs)
            )
        ):
            return False
        if self._unified_eligible(batch):
            whole = isinstance(staged, StagedUnified)
        else:
            # The decode program alone (a prefill program beside it is
            # filled at the dispatch, and draws its seeds first).
            whole = isinstance(staged, StagedDecode) and not batch.prefills
        return whole and self.runner.shape_is_warm(staged)

    def _dispatch_early(self, slot: _StagedStep) -> None:
        """``wait_step``'s ``at_ready``: step N has just been seen ready
        and is not read back yet. Dispatch the staged N+1 now where it
        needs nothing of N's host copy."""
        if self._deferred_aborts:
            # An abort the poll brought for a row of N: its staged row is
            # not dispatched (the abort itself waits for N's commit).
            batch = self._rows_where(
                slot.batch,
                lambda s: s.request.request_id not in self._deferred_aborts,
            )
            if batch is not slot.batch:
                self._restaged(slot, batch)
        if self._dispatches_early(slot):
            self._dispatch_async(slot.batch, slot.staging)
            slot.early_at = time.monotonic()

    def _prime(self) -> None:
        """Enter the pipeline behind a step that has just landed: what
        arrived while it ran is taken in, what is left to run is scheduled
        (nothing is pending) and dispatched, and the next call of
        ``step()`` finds it in flight.

        This entry is a SET-UP WORKAROUND, not a design: a pipeline could
        as well start by dispatching from empty inside ``_step_async``,
        but a shape's first call (trace + lowering) reached on that path
        read +0.24 s a bucket on the v5e host, cause not found (PERF.md
        section 6, PR 38; section 7 (k)). Landing the first step on the
        synchronous path keeps a warm-up that starts from empty at every
        shape on the code the set-up bound was set with; a shape first
        reached INSIDE the pipeline (a top-up's, a lazily compiling
        server's) still pays the slower lowering."""
        if self.intake_hook is not None:
            self.intake_hook()
        if self.scheduler.has_work():
            batch = self._schedule_spanned()
            if not batch.is_empty:
                self._dispatch_async(batch)
                self._capture_behind(batch)

    def _stage(
        self, batch: ScheduledBatch
    ) -> StagedDecode | StagedVerify | StagedUnified | None:
        """Prestage ``batch``: everything of its dispatch that does not
        depend on the in-flight step's tokens."""
        if batch.is_empty:
            return None
        if self._unified_eligible(batch):
            # Unified single-dispatch step: the row structure and the
            # row-independent arrays (page/ring tables, knobs) prestage
            # here; the packed stream, (start, qlen, kind) metadata,
            # drafts and seeds fill at dispatch, after step N's
            # readback commits.
            return self.runner.stage_unified(batch.prefills, batch.decodes)
        if not batch.decodes:
            return None
        if self._spec_proposer is not None:
            # Spec mode stages the verify shape; tokens, drafts and
            # seeds fill at dispatch, after step N's readback commits.
            return self.runner.stage_spec_verify(batch.decodes)
        return self.runner.stage_decode(
            batch.decodes, k_steps=batch.decodes[0].num_tokens
        )

    def _restage(
        self,
        staged_dec: StagedDecode | StagedVerify | StagedUnified | None,
        was: ScheduledBatch,
        batch: ScheduledBatch,
    ) -> StagedDecode | StagedVerify | StagedUnified | None:
        """The staging of ``batch``, which is ``was`` (what ``staged_dec``
        was built for) less the rows a rollback dropped or plus the rows a
        top-up admitted. A unified prestage survives by SLICING the rows
        both share out of the full staging (runner.restage_unified) and
        building the added ones alone; a decode or verify prestage stands
        while its decode rows do (the split path builds prefill rows at
        dispatch); anything else is staged anew."""
        if isinstance(staged_dec, StagedUnified):
            if not batch.is_empty and self._unified_eligible(batch):
                return self.runner.restage_unified(
                    staged_dec, batch.prefills, batch.decodes
                )
        elif (
            staged_dec is not None
            and len(batch.decodes) == len(was.decodes)
            and not self._unified_eligible(batch)
        ):
            return staged_dec
        return self._stage(batch)

    def _restaged(self, slot: _StagedStep, batch: ScheduledBatch) -> None:
        """``slot`` becomes ``batch``: what it had staged less the rows a
        rollback dropped, plus the rows a top-up admitted."""
        self.runner.unprepare(slot.staging)
        slot.staging = self._restage(slot.staging, slot.batch, batch)
        slot.batch = batch
        self._prepare(slot)

    def _prepare(self, slot: _StagedStep) -> None:
        """Under the step in flight: where the staged step will go out
        the moment that step is seen ready (``_dispatches_early``), its
        fill and the put of its payload are done NOW
        (``ModelRunner.prepare_staged``), so that the device then waits for
        the jitted call alone. Nothing of the fill reads what the step in
        flight will commit: positions and seeds follow the dispatched
        position, and a decode row's un-read token is the device's."""
        if self._inflight is not None and self._dispatches_early(slot):
            self.runner.prepare_staged(slot.staging)

    @staticmethod
    def _rows_where(batch: ScheduledBatch, keep) -> ScheduledBatch:
        """``batch`` less the rows ``keep`` refuses (``batch`` itself where
        it refuses none)."""
        live_p = [s for s in batch.prefills if keep(s)]
        live_d = [s for s in batch.decodes if keep(s)]
        if len(live_p) + len(live_d) == len(batch.prefills) + len(batch.decodes):
            return batch
        return ScheduledBatch(prefills=live_p, decodes=live_d)

    @classmethod
    def _running(cls, batch: ScheduledBatch) -> ScheduledBatch:
        """``batch`` less the rows whose request is no longer running."""
        return cls._rows_where(
            batch, lambda s: s.request.status is RequestStatus.RUNNING
        )

    def _reconcile(self, slot: _StagedStep) -> int:
        """Drop the staged rows whose request is no longer running (a
        late finish of the in-flight step, or an abort); returns how
        many. Rolled-back rows already returned every page (speculative
        allocations included), their ring or state slot and what they
        held of a retained section via _finish/_release — the same
        release the recompute-preemption path uses."""
        batch = self._running(slot.batch)
        rolled = len(slot.batch.seqs) - len(batch.seqs)
        if rolled:
            self.stats.async_rollbacks_total += rolled
            self._restaged(slot, batch)
        return rolled

    def _top_up(self, slot: _StagedStep) -> None:
        """Admit into the staged batch what the scheduler would have
        admitted from ``waiting`` had it been there at the speculative
        schedule, under the budget and the rows the staged batch left
        (prefix lookup, page allocation, ring section or state snapshot
        of a hybrid hit included), and stage the added rows alone."""
        t0 = time.monotonic()
        in_flight = self._inflight is not None
        # (the restage is under the span too: after the readback the
        # turn's spans leave no hole)
        with profiling.span("llmd.sched.schedule", top_up=True) as span:
            added = self.scheduler.top_up(slot.batch, in_flight=in_flight)
            span.set_metadata(prefills=len(added), decodes=0)
            # An interactive head may have reclaimed a staged batch-band
            # row's slot and pages, whether or not it was then admitted: a
            # row that no longer runs leaves the staged batch either way.
            kept = self._running(slot.batch)
            if added or kept is not slot.batch:
                self._restaged(slot, ScheduledBatch(
                    prefills=kept.prefills + added, decodes=kept.decodes
                ))
                slot.topped_up |= bool(added)
        spent = time.monotonic() - t0
        slot.admit_s += spent
        if in_flight:
            slot.in_wait_s += spent
        else:
            slot.in_gap_s += spent

    def _admit_arrivals(self, slot: _StagedStep) -> None:
        """``wait_step``'s poll while step N runs: take in what arrived
        and top the staged batch up with it (an abort among the arrivals
        may have released a staged row that was never dispatched: it is
        dropped here, before the wait ends)."""
        if self.intake_hook():
            self._reconcile(slot)
            self._top_up(slot)

    def _schedule_spanned(self) -> ScheduledBatch:
        with profiling.span("llmd.sched.schedule") as sched_span:
            batch = self.scheduler.schedule()
            sched_span.set_metadata(
                prefills=len(batch.prefills), decodes=len(batch.decodes)
            )
        return batch

    def _dispatch_async(
        self,
        batch: ScheduledBatch,
        staged_dec: (
            StagedDecode | StagedVerify | StagedUnified | None
        ) = None,
    ) -> None:
        pend_p = pend_d = pend_u = None
        with profiling.span("llmd.runner.launch"):
            if self._unified_eligible(batch):
                pend_u = self._dispatch_unified(
                    batch,
                    staged_dec if isinstance(staged_dec, StagedUnified)
                    else None,
                )
            else:
                if batch.prefills:
                    pend_p = self.runner.dispatch_prefill(batch.prefills)
                    self.stats.step_dispatches_total += len(pend_p.entries)
                    for seq in batch.prefills:
                        self.stats.prompt_tokens += seq.num_tokens
                if batch.decodes:
                    pend_d = self._dispatch_decodes(
                        batch.decodes,
                        None if isinstance(staged_dec, StagedUnified)
                        else staged_dec,
                    )
            self.scheduler.note_dispatch(batch)
        self._inflight = _InflightStep(batch, pend_p, pend_d, pend_u)

    def _unified_eligible(self, batch: ScheduledBatch) -> bool:
        """Does this batch ride the unified single-dispatch program?
        Window=1 steps only (fused decode windows keep their own
        dispatch — they already amortize the round-trip).

        Flattened-token engines (`--ragged-qlens`): EVERY window=1 step
        kind rides the ONE flat program — prefill-only, pure-decode,
        mixed, and one-shot verify mixes (a mixed drafted/plain spec
        step becomes one dispatch where the split path launched two,
        with per-row adaptive verify depth via each row's own qlen).

        Bucketed engines: only where the split engine would launch MORE
        than one program — mixed prefill+decode steps, or prefill-only
        steps spanning several Q buckets. Pure-decode window=1 steps
        are already one dispatch (mixed drafted/plain spec splits keep
        the two-program path — their staging shape depends on drafts
        only known at dispatch)."""
        if (
            self.runner.cp_prefill
            and batch.prefills
            and any(
                s.num_tokens >= max(self.runner.cp_min_tokens,
                                    self.runner.cp_prefill)
                for s in batch.prefills
            )
        ):
            # Context-parallel ring prefill lives in the split _forward
            # family only; long chunks divert so they ride it.
            return False
        if self.runner._flat is not None:
            if batch.is_empty:
                return False
            # Fused decode windows (non-spec K>1 rows) keep their own
            # dispatch — they already amortize the round-trip.
            if self._spec_proposer is None and any(
                s.num_tokens != 1 for s in batch.decodes
            ):
                return False
            return True
        if self.runner._unified is None:
            return False
        if not batch.prefills:
            return False
        if batch.decodes:
            # A window=1 mixed step always has one-token decode rows in
            # spec-off engines (the fused window only engages on pure-
            # decode steps); guard anyway so an unexpected fused batch
            # keeps its own program.
            if self._spec_proposer is None and any(
                s.num_tokens != 1 for s in batch.decodes
            ):
                return False
            return True
        return self.runner.prefill_group_count(batch.prefills) > 1

    def _dispatch_unified(
        self, batch: ScheduledBatch, staged: StagedUnified | None
    ) -> PendingUnified:
        """Dispatch the whole window=1 step as ONE ragged program (drafts
        proposed first, exactly like the split paths). ``staged`` reuses
        the async pipeline's prestaged arrays when the row set still
        matches."""
        if self._spec_proposer is not None and batch.decodes:
            self._propose_drafts(batch.decodes)
        reuse = (
            staged is not None
            and len(staged.prefills) == len(batch.prefills)
            and len(staged.decodes) == len(batch.decodes)
            and all(a is b for a, b in zip(staged.prefills, batch.prefills))
            and all(a is b for a, b in zip(staged.decodes, batch.decodes))
        )
        if reuse:
            pend_u = self.runner.dispatch_staged_unified(staged)
        else:
            self.runner.unprepare(staged)
            pend_u = self.runner.dispatch_unified(
                batch.prefills, batch.decodes
            )
        for seq in batch.prefills:
            self.stats.prompt_tokens += seq.num_tokens
        self.stats.unified_steps_total += 1
        self.stats.step_dispatches_total += 1
        if batch.decodes:
            self.stats.decode_dispatches_total += 1
        return pend_u

    def _dispatch_decodes(
        self,
        decodes: list,
        staged: StagedDecode | StagedVerify | None = None,
    ) -> PendingDecode:
        """Dispatch the step's decode rows: the one-shot speculative
        verify path when drafting is on and any row drafted, the plain
        decode program otherwise. ``staged`` reuses host arrays prebuilt
        by the async pipeline when they still match the dispatch shape —
        including SLICING the row-independent page-table/knob rows for
        mixed-step subsets instead of restaging them in the blocking
        host region."""
        pend = self._dispatch_decode_programs(decodes, staged)
        self.stats.decode_dispatches_total += len(pend.entries)
        self.stats.step_dispatches_total += len(pend.entries)
        return pend

    def _dispatch_decode_programs(
        self,
        decodes: list,
        staged: StagedDecode | StagedVerify | None,
    ) -> PendingDecode:
        if self._spec_proposer is not None:
            self._propose_drafts(decodes)
            drafted = sum(1 for s in decodes if s.draft_tokens)
            if drafted == len(decodes):
                if not isinstance(staged, StagedVerify):
                    staged = self.runner.stage_spec_verify(decodes)
                return self.runner.dispatch_staged_verify(staged)
            if drafted == 0:
                # No row drafted anything this step: the plain one-token
                # decode program (no wasted verify columns — the
                # adversarial-traffic guard). The rows stay speculative
                # (draft_tokens == []), so acceptance accounting and
                # page truncation still run.
                return self.runner.dispatch_decode(decodes, k_steps=1)
            # Mixed step: drafting rows verify, the rest decode plainly
            # (two enqueues, one coalesced readback). The prestaged
            # full-batch verify arrays are reused by slicing their
            # row-independent rows per subset.
            return self.runner.dispatch_spec_split(
                decodes,
                staged if isinstance(staged, StagedVerify) else None,
            )
        if not isinstance(staged, StagedDecode):
            staged = self.runner.stage_decode(
                decodes, k_steps=decodes[0].num_tokens
            )
        return self.runner.dispatch_staged_decode(staged)

    def _propose_drafts(self, decodes: list) -> None:
        """Fill each speculative decode row's draft from COMMITTED
        history, at dispatch time — async staging runs a step early,
        where the history is stale and the tail token unknown. The cap
        — num_tokens - 1, the planned width — guarantees the draft never
        writes a slot that wasn't allocated, even when a short
        acceptance left the row behind its planned position."""
        max_len = self.config.model.max_model_len
        for seq in decodes:
            req = seq.request
            # Rows planned draft-less (max_model_len cap, draft backoff
            # — scheduler._spec_eligible — or the batch band) get no
            # proposer call and no verify columns.
            cap = min(
                seq.num_tokens - 1, max_len - req.num_computed_tokens - 1
            )
            if cap <= 0:
                seq.draft_tokens = []
                continue
            if req.spec_gram_state is None:
                req.spec_gram_state = self._spec_proposer.new_state()
            seq.draft_tokens = self._spec_proposer.propose(
                req.all_token_ids, cap, req.spec_gram_state
            )
        for seq in decodes:
            self._spec_row_depth[1 + len(seq.draft_tokens or [])] += 1

    def _collect(
        self,
        batch: ScheduledBatch,
        pres: StepResult | None,
        dres: StepResult | None,
    ) -> tuple[dict[str, list[int]], dict[str, list[float]]]:
        sampled: dict[str, list[int]] = {}
        logprobs: dict[str, list[float]] = {}
        if batch.prefills:
            if pres is None:
                # Eager-ACK: tokens were never fetched (the consumer
                # discards them); zeros keep the bookkeeping uniform.
                pres = StepResult(
                    np.zeros((len(batch.prefills), 1), np.int32),
                    np.zeros((len(batch.prefills), 1), np.float32),
                )
            for i, seq in enumerate(batch.prefills):
                sampled[seq.request.request_id] = pres.tokens[i].tolist()
                logprobs[seq.request.request_id] = pres.logprobs[i].tolist()
        if batch.decodes and dres is not None:
            for i, seq in enumerate(batch.decodes):
                toks, lps = dres.tokens[i], dres.logprobs[i]
                if seq.draft_tokens is not None:
                    # One-shot speculative row: only 1 + draft_len
                    # columns are real; the rest are the verify shape's
                    # padding.
                    m = 1 + len(seq.draft_tokens)
                    toks, lps = toks[:m], lps[:m]
                sampled[seq.request.request_id] = toks.tolist()
                logprobs[seq.request.request_id] = lps.tolist()
        return sampled, logprobs

    def _assemble_outputs(
        self,
        batch: ScheduledBatch,
        accepted: dict[str, list[int]],
        logprobs: dict[str, list[float]],
    ) -> list[RequestOutput]:
        outputs: list[RequestOutput] = []
        finished = 0
        now = time.monotonic()  # the step's tokens are on the host
        for seq in batch.seqs:
            req = seq.request
            new_tokens = accepted.get(req.request_id)
            if not new_tokens:
                continue
            if req.first_token_time is None:
                req.first_token_time = now
            if req.sampling.logprobs:
                req.output_logprobs.extend(
                    logprobs[req.request_id][: len(new_tokens)]
                )
            self.stats.generation_tokens += len(new_tokens)
            finished += int(req.is_finished)
            outputs.append(
                RequestOutput(
                    request_id=req.request_id,
                    new_token_ids=new_tokens,
                    finished=req.is_finished,
                    finish_reason=req.finish_reason,
                    num_prompt_tokens=req.num_prompt_tokens - req.num_prior_output_tokens,
                    num_output_tokens=req.total_output_tokens,
                    num_cached_tokens=req.num_cached_tokens,
                    kv_transfer_params=req.export_params,
                    queue_wait_ms=req.queue_wait_ms,
                    ttft_ms=(req.first_token_time - req.arrival_time) * 1e3,
                )
            )
        self.stats.requests_finished += finished
        return outputs

    @staticmethod
    def _carried(batch: ScheduledBatch) -> tuple[str, int, int]:
        """(kind, rows, tokens) of a batch that is not empty."""
        if batch.prefills and batch.decodes:
            kind = "mixed"
        else:
            kind = "prefill" if batch.prefills else "decode"
        return kind, len(batch.seqs), batch.total_tokens

    def _finish_step(
        self,
        batch: ScheduledBatch,
        host_gap_s: float,
        schedule_s: float,
        launch_s: float,
        wait_s: float,
        finish_s: float,
        readback_s: float,
        ready_lag_bound_s: float = 0.0,
        commit_s: float = 0.0,
        redispatch_s: float = 0.0,
        gap_admit_s: float = 0.0,
        ready_at: float = 0.0,
        dispatched_at: float | None = None,
    ) -> None:
        """Count one step that ran ``batch``: the host gap, the phase
        sums and the step kind (``step()`` adds the whole-step sums).
        ``host_gap_s`` of the pipelined step starts at the readback's END
        and ends at the next dispatch's return: ``commit_s`` +
        ``redispatch_s``, its two parts (``gap_admit_s``: the admission
        inside the second). ``readback_s``, which precedes it (first
        ready to parsed results, inside ``wait_s``), makes it the host's
        whole turn between two programs; ``ready_lag_bound_s`` is the
        most the host can have noticed the device's end late. The
        synchronous step's gap is its phases. ``ready_at`` is the wait's
        (``WaitTiming``); ``dispatched_at`` the return of the dispatch that
        followed in the same turn, where one did: the end of the step's
        hold on the device."""
        st = self.stats
        gap_ms = host_gap_s * 1e3
        st.engine_steps_total += 1
        st.step_host_gap_ms = round(gap_ms, 3)
        st.step_host_gap_ms_total += gap_ms
        st.step_schedule_ms_total += schedule_s * 1e3
        st.step_launch_ms_total += launch_s * 1e3
        st.step_wait_ms_total += wait_s * 1e3
        st.step_finish_ms_total += finish_s * 1e3
        st.step_commit_ms_total += commit_s * 1e3
        st.step_redispatch_ms_total += redispatch_s * 1e3
        st.step_readback_ms_total += readback_s * 1e3
        st.step_ready_lag_bound_ms_total += ready_lag_bound_s * 1e3
        st.step_gap_admit_ms_total += gap_admit_s * 1e3
        self._step_carried = self._carried(batch)
        by_kind = f"steps_{self._step_carried[0]}_total"
        setattr(st, by_kind, getattr(st, by_kind) + 1)
        if dispatched_at is not None:
            hold_ms = (dispatched_at - ready_at + ready_lag_bound_s) * 1e3
            st.step_host_hold_ms_total += hold_ms
            st.step_host_holds_total += 1
            bucket = _HOLD_BUCKETS[bisect.bisect_left(_HOLD_EDGES_MS, hold_ms)]
            setattr(st, bucket, getattr(st, bucket) + 1)
        if self._paced_from is not None:
            pace_ms = (ready_at - self._paced_from) * 1e3
            if self._step_carried[0] == "decode":
                st.step_ready_interval_ms_decode_total += pace_ms
                st.step_ready_intervals_decode_total += 1
            else:
                st.step_ready_interval_ms_prefill_total += pace_ms
                st.step_ready_intervals_prefill_total += 1
        self._paced_from = ready_at
        self._count_host_tail()
        st.kv_bytes_in_use_total += self._kv_bytes_in_use()
        if self._state_pool:
            w = self.swa_allocator
            st.state_bytes_in_use_total += (
                (w.num_pages - w.num_free_pages) * self.runner.kv_swa_page_bytes
            )
        st.cached_tokens_total += sum(
            s.request.num_computed_tokens for s in batch.seqs
        )
        self._moe_tick()
        self._refresh_gauges()

    def _count_host_tail(self) -> None:
        """Once a step, on the thread that steps: what the collector paused
        the process for since the step before, and what the machine gave
        this thread (EngineStats' gc_* and engine_thread_* counters)."""
        st = self.stats
        if self._gc_seen is not None:
            was, now = self._gc_seen, profiling.gc_totals()
            self._gc_seen = now
            st.gc_pause_ms_total += now[0] - was[0]
            st.gc_collections_total += now[1] - was[1]
            st.gc_full_pause_ms_total += now[2] - was[2]
            st.gc_full_collections_total += now[3] - was[3]
        switches = (
            0 if _RUSAGE_THREAD is None
            else resource.getrusage(_RUSAGE_THREAD).ru_nivcsw
        )
        was = self._thread_seen
        self._thread_seen = now = (
            threading.get_ident(), time.thread_time(), switches
        )
        if was is not None and was[0] == now[0]:
            st.engine_thread_cpu_ms_total += (now[1] - was[1]) * 1e3
            st.engine_thread_preemptions_total += now[2] - was[2]

    def _kv_bytes_in_use(self) -> int:
        """Bytes of KV pages that live references hold, over both pools."""
        r = self.runner
        a = self.allocator
        n = (a.num_pages - a.num_free_pages) * r.kv_page_bytes
        if self.swa_allocator is not None and not self._state_pool:
            w = self.swa_allocator
            n += (w.num_pages - w.num_free_pages) * r.kv_swa_page_bytes
        return n

    def _moe_tick(self) -> None:
        """Drain the wide-EP census and run the slow control loops.

        Per step: fold routed-token counts / dropped slots / peak demand
        into EngineStats, and let the adaptive-capacity controller move
        the live factor (hysteresis lives in AdaptiveCapacity, so
        retrace-causing moves are rare and deliberate). Every
        eplb_interval_steps: compute a fresh expert->shard placement from
        the loads observed SINCE the last rebalance (not all-time — the
        balancer must track drift, not history) and apply it at this
        step boundary."""
        if not self._moe_active:
            return
        census = self.runner.drain_moe_census()
        if census is None:
            return
        E = self.config.model.num_experts
        self._moe_expert_tokens += census[:E].astype(np.int64)
        self.stats.moe_expert_tokens = tuple(
            int(v) for v in self._moe_expert_tokens
        )
        self.stats.moe_dropped_slots_total += int(census[E])
        need = float(census[E + 1])
        if need > self.stats.moe_peak_demand:
            self.stats.moe_peak_demand = round(need, 4)
        if self._adaptive_cap is not None:
            factor = self._adaptive_cap.observe(need)
            if factor is not None:
                self.runner.set_ep_capacity(factor)
        self.stats.moe_capacity_factor = self.runner.ep_capacity
        steps = self.stats.engine_steps_total
        if self._eplb_interval and steps >= self._eplb_next:
            self._eplb_next = steps + self._eplb_interval
            window = self._moe_expert_tokens - self._eplb_window_base
            if window.sum() > 0:
                from llmd_tpu.parallel.eplb import compute_placement

                placement = compute_placement(
                    window,
                    world=self.ctx.world,
                    redundancy=self._eplb_redundancy,
                )
                self.runner.apply_expert_placement(placement)
                self.stats.moe_rebalances_total += 1
                self._eplb_window_base = self._moe_expert_tokens.copy()

    def _refresh_gauges(self) -> None:
        self.stats.num_waiting = self.scheduler.num_waiting
        self.stats.num_running = self.scheduler.num_running
        self.stats.kv_usage = self.allocator.usage()
        if self._state_pool:
            st, w = self.stats, self.swa_allocator
            s = self._swa_sections.stats() if self._swa_sections else {}
            st.state_snapshots = s.get("entries", 0)
            st.state_slots = self.config.scheduler.max_num_seqs
            st.state_slots_in_use = (
                w.num_pages - w.num_free_pages - st.state_snapshots
            )
            st.state_snapshot_hits_total = s.get("hits", 0)
            st.state_snapshot_misses_total = s.get("misses", 0)
            st.state_snapshot_captures_total = s.get("captures", 0)
            st.state_snapshot_evictions_total = s.get("evictions", 0)
            st.ssm_update_rows_total = self.runner.ssm_update_rows_total
            st.ssm_scan_tokens_total = self.runner.ssm_scan_tokens_total
            r = self.runner
            st.gdn_update_rows_total = r.gdn_update_rows_total
            st.gdn_scan_rows_total = r.gdn_scan_rows_total
            st.gdn_scan_tokens_total = r.gdn_scan_tokens_total
            # A row moves its slot's state of a layer once in and once out.
            plane = r.kv_swa.ssm
            st.gdn_state_bytes_moved_total = (
                2 * (plane.nbytes // (plane.shape[0] * plane.shape[1]))
                * (r.gdn_update_rows_total + r.gdn_scan_rows_total)
            )
        elif self.swa_allocator is not None:
            self.stats.swa_ring_usage = self.swa_allocator.usage()
            self.stats.swa_ring_pages = self.swa_allocator.num_pages
            if self._swa_sections is not None:
                s = self._swa_sections.stats()
                self.stats.swa_sections = s["entries"]
                self.stats.swa_section_hits_total = s["hits"]
                self.stats.swa_section_misses_total = s["misses"]
                self.stats.swa_section_captures = s["captures"]
        if self._swa_sections is not None:
            kept = self._swa_sections
            self.stats.retained_capture_rehashed_total = kept.rehashed
            self.stats.retained_capture_host_ms_total = kept.capture_host_ms
            self.stats.retained_finish_captures_total = kept.finish_captures
        self.stats.prefix_hit_ratio = self.allocator.hit_ratio()
        self.stats.preemptions = self.scheduler.num_preemptions
        self.stats.async_wasted_rows_total = self.scheduler.wasted_rows
        self.stats.queue_wait_ms_total = self.scheduler.queue_wait_ms
        self.stats.queue_admitted_total = self.scheduler.queue_admitted
        self.stats.programs_traced_total = self.runner.programs_traced
        self.stats.step_h2d_transfers_total = (
            self.runner.step_h2d_transfers_total
        )
        self.stats.step_h2d_bytes_total = self.runner.step_h2d_bytes_total
        self.stats.batch_backlog_jobs = sum(
            1 for r in self.scheduler.waiting if r.is_batch
        )
        self.stats.batch_tokens = self.scheduler.batch_tokens
        self.stats.batch_preemptions = self.scheduler.num_batch_preemptions
        self.stats.batch_backfill_utilization = round(
            self.scheduler.last_batch_backfill_tokens
            / max(1, self.config.scheduler.max_num_batched_tokens),
            6,
        )
        if self.scheduler.spec_k:
            sch = self.scheduler
            self.stats.spec_proposed_tokens_total = sch.spec_proposed_tokens
            self.stats.spec_accepted_tokens_total = sch.spec_accepted_tokens
            self.stats.spec_acceptance_rate = round(
                sch.spec_accepted_tokens / max(1, sch.spec_proposed_tokens), 6
            )
            self.stats.spec_accepted_len_hist = tuple(sch.spec_accept_len_hist)
            self.stats.spec_row_depth_hist = tuple(self._spec_row_depth)
        self.stats.live_tokens_total = self.runner.live_tokens_total
        self.stats.padded_tokens_total = self.runner.padded_tokens_total
        self.stats.attn_shared_tile_tokens_total = (
            self.runner.attn_shared_tile_tokens_total
        )
        r = self.runner
        self.stats.attn_prefix_run_keys_total = r.attn_prefix_run_keys_total
        self.stats.attn_decode_keys_total = r.attn_decode_keys_total
        self.stats.moe_grouped_calls_total = r.moe_grouped_calls_total
        self.stats.moe_groups_with_rows_total = r.moe_groups_with_rows_total
        self.stats.moe_picks_total = r.moe_picks_total
        self.stats.moe_picks_held_total = r.moe_picks_held_total
        self.stats.sparse_bound_tokens_total = r.sparse_bound_tokens_total
        self.stats.sparse_unbound_tokens_total = r.sparse_unbound_tokens_total
        self.stats.indexer_keys_scored_total = r.indexer_keys_scored_total
        self.stats.indexer_keys_written_total = r.indexer_keys_written_total
        self.stats.sparse_rows_selected_total = r.sparse_rows_selected_total
        self.stats.latent_rows_written_total = r.latent_rows_written_total
        self.stats.dispatches_per_emitted_token = round(
            self.stats.decode_dispatches_total
            / max(1, self.stats.generation_tokens),
            6,
        )
        if self.config.model.num_lora_adapters:
            self.stats.max_lora = self.config.model.num_lora_adapters
            self.stats.running_lora_adapters = tuple(
                sorted({r.lora_name for r in self.scheduler.running if r.lora_name})
            )
            self.stats.waiting_lora_adapters = tuple(
                sorted({r.lora_name for r in self.scheduler.waiting if r.lora_name})
            )
            if self.adapter_pool is not None:
                # Paged pool observability (multi-tenant-lora.md): the
                # waiting list also counts rows PARKED on cold loads —
                # they are queued demand the routing layer must see.
                if self._lora_parked:
                    self.stats.waiting_lora_adapters = tuple(sorted(
                        set(self.stats.waiting_lora_adapters)
                        | {r.lora_name for r in self._lora_parked}
                    ))
                self._refresh_lora_stats()
        if self._host_cache is not None:
            hs = self._host_cache.stats()
            self.stats.offload_pages = hs["pages"]
            self.stats.offload_fs_pages = hs["fs_pages"]
            self.stats.offload_saves = hs["saves"]
            self.stats.offload_restores = hs["restores"]
        if self._kvstore_client is not None:
            ks = self._kvstore_client.stats()
            self.stats.kvstore_pulls = ks["pulls"]
            self.stats.kvstore_pull_failures = ks["pull_failures"]
            self.stats.kvstore_misses = ks["misses"]
            self.stats.kv_publish_paced_bytes_total = ks.get(
                "paced_publish_bytes", 0
            )
        if self._federation is not None:
            fs = self._federation.stats()
            self.stats.kv_federation_published = fs["published"]
            self.stats.kv_federation_hits = fs["hits"]
        if self.offloader is not None:
            self.stats.recompute_avoided_tokens = (
                self.offloader.recompute_avoided_tokens
            )
        if self.pager is not None:
            self.stats.kv_paged_out_bytes = self.pager.paged_out_bytes
            self.stats.kv_pager_prefetch_late_total = (
                self.pager.prefetch_late_total
            )
        self.stats.cp_ring_steps_total = self.runner.cp_ring_steps_total
        if self.kv_connector is not None:
            cs = self.kv_connector.stats()
            self.stats.kv_exported_requests = cs["exported_requests"]
            self.stats.kv_exported_bytes = cs["exported_bytes"]
            self.stats.kv_imported_requests = cs["imported_requests"]
            self.stats.kv_imported_bytes = cs["imported_bytes"]
            self.stats.kv_import_failures = cs["import_failures"]
            self.stats.kv_stream_groups_total = cs["stream_groups_total"]
            self.stats.kv_stream_first_group_ms = cs["last_first_group_ms"]
            self.stats.kv_bundle_crc_failures_total = cs["crc_failures"]
            self.stats.kv_recompute_fallbacks_total = cs[
                "recompute_fallbacks"
            ]
            self.stats.kv_transfer_failures = tuple(
                sorted(cs["transfer_failures"].items())
            )

    # ------------------------------------------------------------------ #

    def generate(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | list[SamplingParams] | None = None,
        max_steps: int = 100_000,
    ) -> dict[str, list[int]]:
        """Offline batch API: run all prompts to completion."""
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling or SamplingParams()] * len(prompts)
        if len(sampling) != len(prompts):
            raise ValueError(
                f"{len(prompts)} prompts but {len(sampling)} sampling params"
            )
        order: list[str] = []
        for p, s in zip(prompts, sampling):
            order.append(self.add_request(p, s))
        done: dict[str, list[int]] = {rid: [] for rid in order}
        for _ in range(max_steps):
            if not self.has_work():
                break
            for out in self.step():
                done[out.request_id].extend(out.new_token_ids)
        return {rid: done[rid] for rid in order}
