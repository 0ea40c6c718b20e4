"""Prometheus metrics in the model-server protocol the router scrapes.

The EPP↔engine metrics contract (reference
docs/architecture/core/model-servers.md:38-52): TotalQueuedRequests,
TotalRunningRequests, KVCacheUtilization (+ optional BlockSize /
NumGPUBlocks), resolved through a per-engine metric-name mapping. We emit
BOTH the vLLM names (so a stock llm-d EPP scrapes us unchanged with the
vllm mapping) and `llmd:` canonical names.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from llmd_tpu import faults

if TYPE_CHECKING:
    # Annotation-only: importing EngineStats at runtime drags the whole
    # jax engine in, and this module's scrape-side half
    # (parse_prometheus) serves accelerator-free consumers — the EPP
    # data layer and the fleet simulator's control-plane imports.
    from llmd_tpu.engine.engine import EngineStats


def render_metrics(
    stats: EngineStats, model_name: str, lora_adapters: dict | None = None
) -> str:
    label = f'{{model_name="{model_name}"}}'
    gauges = {
        "num_requests_waiting": stats.num_waiting,
        "num_requests_running": stats.num_running,
        # Routing-visible utilization is the BINDING pool: with a SWA ring
        # pool the ring (not the main table) is often the admission
        # constraint under P/D preload bursts, and a scorer reading only
        # main-pool usage would keep sending work to an exhausted engine.
        "gpu_cache_usage_perc": round(
            max(stats.kv_usage, stats.swa_ring_usage), 6
        ),
        "prefix_cache_hit_rate": round(stats.prefix_hit_ratio, 6),
        # Step-pipeline observability (async stepping): the per-step
        # host time the device idles for. Async mode shrinks it to the
        # reconcile/patch sliver; the *_total counters let a scraper (or
        # bench.py --parts async_step) compute a mean over any interval.
        "step_host_gap_ms": round(stats.step_host_gap_ms, 3),
        # Decode dispatches per generated token: the fused-window
        # headline ratio — fused decode windows and accepted drafts
        # both push it down by spreading one dispatch over more emitted
        # tokens per device program.
        "dispatches_per_emitted_token": round(
            stats.dispatches_per_emitted_token, 6
        ),
    }
    # Batch serving tier (docs/architecture/batch-processing.md): the
    # backfill band's scrape surface — backlog is what the WVA counts as
    # deferrable demand (floor-not-scale-up), utilization is the LAST
    # step's budget fraction the band harvested.
    gauges["batch_backlog_jobs"] = stats.batch_backlog_jobs
    gauges["batch_backfill_utilization"] = round(
        stats.batch_backfill_utilization, 6
    )
    if stats.swa_ring_pages:
        gauges["swa_ring_usage_perc"] = round(stats.swa_ring_usage, 6)
        gauges["swa_ring_pages"] = stats.swa_ring_pages
        # Raw main-pool usage stays observable when the ring is busier
        # (gpu_cache_usage_perc above collapses to the max of the two).
        gauges["kv_main_usage_perc"] = round(stats.kv_usage, 6)
        # Hybrid-APC section retention
        gauges["swa_sections"] = stats.swa_sections
    if stats.state_slots_in_use or stats.state_snapshots:
        # The state pool: slots that running sequences hold, snapshots kept.
        gauges["state_slots_in_use"] = stats.state_slots_in_use
        gauges["state_slots"] = stats.state_slots
        gauges["state_snapshots"] = stats.state_snapshots
    gauges["kv_offload_cpu_pages"] = stats.offload_pages
    gauges["kv_offload_fs_pages"] = stats.offload_fs_pages
    # Decode-pager residency (long-context.md): LIVE-sequence bytes in
    # the offload tier — falls as windows stream back, so a gauge.
    gauges["kv_paged_out_bytes"] = stats.kv_paged_out_bytes
    # Last streamed import's first-group latency: the admission-gate
    # leg of the layer-streamed transfer waterfall (kv-cache.md).
    gauges["kv_stream_first_group_ms"] = round(
        stats.kv_stream_first_group_ms, 2
    )
    counters = {
        "prompt_tokens_total": stats.prompt_tokens,
        "generation_tokens_total": stats.generation_tokens,
        "request_success_total": stats.requests_finished,
        "num_preemptions_total": stats.preemptions,
        "kv_offload_saves_total": stats.offload_saves,
        "kv_offload_restores_total": stats.offload_restores,
        # Batch tier counters: tokens the band backfilled and batch rows
        # recompute-preempted when interactive load returned.
        "batch_tokens_total": stats.batch_tokens,
        "batch_preemptions_total": stats.batch_preemptions,
        # Cross-replica KV federation (kv-federation.md): store-client
        # reads (peer pulls / failures / locate misses), publications
        # the master accepted, pages fetched from the store, and the
        # prompt tokens whose fleet-wide re-prefill those pages avoided
        # — the federation's headline counter.
        "kvstore_pulls_total": stats.kvstore_pulls,
        "kvstore_pull_failures_total": stats.kvstore_pull_failures,
        "kvstore_misses_total": stats.kvstore_misses,
        "kv_federation_published_total": stats.kv_federation_published,
        "kv_federation_hits_total": stats.kv_federation_hits,
        "recompute_avoided_tokens_total": stats.recompute_avoided_tokens,
        # P/D transfer accounting (producer exports / consumer pulls)
        "kv_transfer_exported_requests_total": stats.kv_exported_requests,
        "kv_transfer_exported_bytes_total": stats.kv_exported_bytes,
        "kv_transfer_imported_requests_total": stats.kv_imported_requests,
        "kv_transfer_imported_bytes_total": stats.kv_imported_bytes,
        "kv_transfer_import_failures_total": stats.kv_import_failures,
        # Layer-streamed transfer (the v3 group-framed wire): streamed
        # (layer-group x chunk) cells landed on this consumer.
        "kv_stream_groups_total": stats.kv_stream_groups_total,
        # Publish-budget pacing (LLMD_KV_PUBLISH_BYTES_PER_S): bytes the
        # federation publisher delayed to protect the transfer NIC.
        "kv_publish_paced_bytes_total": stats.kv_publish_paced_bytes_total,
        # Million-token context tier (docs/architecture/long-context.md):
        # late pager window fetches and ring collective steps from
        # context-parallel prefill (paged-out residency is a gauge above).
        "kv_pager_prefetch_late_total": stats.kv_pager_prefetch_late_total,
        "cp_ring_steps_total": stats.cp_ring_steps_total,
        # The step pipeline (speculate/rollback contract)
        "engine_steps_total": stats.engine_steps_total,
        "step_host_gap_ms_total": round(stats.step_host_gap_ms_total, 3),
        # Where a step's time goes (EngineStats says what each phase
        # holds; obs/profiling.py's spans carry the same names): running
        # sums in ms over engine_steps_total, the steps by what they
        # carried, queue wait at first admission, step programs traced.
        "step_admit_ms_total": round(stats.step_admit_ms_total, 3),
        "step_schedule_ms_total": round(stats.step_schedule_ms_total, 3),
        "step_launch_ms_total": round(stats.step_launch_ms_total, 3),
        "step_wait_ms_total": round(stats.step_wait_ms_total, 3),
        "step_finish_ms_total": round(stats.step_finish_ms_total, 3),
        # The pipelined step's host gap in its two parts: readback to
        # reconciled, reconciled to the next dispatch's return.
        "step_commit_ms_total": round(stats.step_commit_ms_total, 3),
        "step_redispatch_ms_total": round(stats.step_redispatch_ms_total, 3),
        # The host's turn between two programs, timed where it happens:
        # the most the notice of the device's end can have lagged, the
        # readback (first ready to parsed results; readback + commit +
        # redispatch is the turn), and the admission inside redispatch.
        "step_ready_lag_bound_ms_total": round(
            stats.step_ready_lag_bound_ms_total, 3
        ),
        "step_readback_ms_total": round(stats.step_readback_ms_total, 3),
        "step_gap_admit_ms_total": round(stats.step_gap_admit_ms_total, 3),
        # The host's tail: the pace of a step, ready to ready, by kind;
        # the cyclic collector's pauses (any thread: it holds the
        # interpreter lock), those of a full pass apart; the engine
        # thread's CPU time and involuntary context switches. (A step's
        # hold on the device is the histogram llmd:step_host_hold_ms.)
        "step_ready_interval_ms_decode_total": round(
            stats.step_ready_interval_ms_decode_total, 3
        ),
        "step_ready_intervals_decode_total": (
            stats.step_ready_intervals_decode_total
        ),
        "step_ready_interval_ms_prefill_total": round(
            stats.step_ready_interval_ms_prefill_total, 3
        ),
        "step_ready_intervals_prefill_total": (
            stats.step_ready_intervals_prefill_total
        ),
        "gc_pause_ms_total": round(stats.gc_pause_ms_total, 3),
        "gc_collections_total": stats.gc_collections_total,
        "gc_full_pause_ms_total": round(stats.gc_full_pause_ms_total, 3),
        "gc_full_collections_total": stats.gc_full_collections_total,
        "engine_thread_cpu_ms_total": round(
            stats.engine_thread_cpu_ms_total, 3
        ),
        "engine_thread_preemptions_total": (
            stats.engine_thread_preemptions_total
        ),
        # The serving loop: time waited with nothing to run (1 - idle /
        # wall is the replica's duty cycle), submit() to intake, and a
        # step's readback's end to its outputs handed to their streams.
        "engine_idle_ms_total": round(stats.engine_idle_ms_total, 3),
        "intake_wait_ms_total": round(stats.intake_wait_ms_total, 3),
        "intake_requests_total": stats.intake_requests_total,
        "deliver_lag_ms_total": round(stats.deliver_lag_ms_total, 3),
        "outputs_delivered_total": stats.outputs_delivered_total,
        "step_ms_total": round(stats.step_ms_total, 3),
        "steps_prefill_total": stats.steps_prefill_total,
        "steps_decode_total": stats.steps_decode_total,
        "steps_mixed_total": stats.steps_mixed_total,
        "step_ms_decode_total": round(stats.step_ms_decode_total, 3),
        "step_ms_prefill_total": round(stats.step_ms_prefill_total, 3),
        "queue_wait_ms_total": round(stats.queue_wait_ms_total, 3),
        "queue_admitted_total": stats.queue_admitted_total,
        "programs_traced_total": stats.programs_traced_total,
        # A step's host inputs as one packed buffer: transfers a
        # dispatched step program reads 1.
        "step_h2d_transfers_total": stats.step_h2d_transfers_total,
        "step_h2d_bytes_total": stats.step_h2d_bytes_total,
        "async_rollbacks_total": stats.async_rollbacks_total,
        # How often the pipeline engages: steps dispatched from a slot
        # staged under the step before, and those topped up with
        # requests admitted after the speculative schedule.
        "steps_prestaged_total": stats.steps_prestaged_total,
        "steps_topped_up_total": stats.steps_topped_up_total,
        # Steps dispatched before the step in front of them was read
        # back, and rows computed for a request that had ended meanwhile.
        "steps_dispatched_before_readback_total": (
            stats.steps_dispatched_before_readback_total
        ),
        "async_wasted_rows_total": stats.async_wasted_rows_total,
        "decode_dispatches_total": stats.decode_dispatches_total,
        # Unified single-dispatch steps (the family split of
        # decode_dispatches_total) and EVERY program engine steps
        # dispatched — step_dispatches_total / engine_steps_total is the
        # unified step's dispatches-per-step headline.
        "unified_steps_total": stats.unified_steps_total,
        "step_dispatches_total": stats.step_dispatches_total,
        # Padding efficiency (flattened-token step, --ragged-qlens):
        # tokens the dispatched programs computed for real vs the pad
        # lanes the traced shapes paid on top; padded/live is the
        # padding-waste gauge the ragged_step bench part bounds.
        "live_tokens_total": stats.live_tokens_total,
        "padded_tokens_total": stats.padded_tokens_total,
        # Live tokens whose 16-token stream granule holds one row only:
        # the flat attention reads their context once per tile.
        "attn_shared_tile_tokens_total": stats.attn_shared_tile_tokens_total,
        # Keys under the flat steps' decode tokens' horizons, and those of
        # them in a shared-prefix run: read once a tile for its members.
        "attn_prefix_run_keys_total": stats.attn_prefix_run_keys_total,
        "attn_decode_keys_total": stats.attn_decode_keys_total,
        # What a cached token costs, over both KV pools: bytes held by
        # live references and the scheduled sequences' tokens, each summed
        # over steps (the ratio of two rates).
        "kv_bytes_in_use_total": stats.kv_bytes_in_use_total,
        "cached_tokens_total": stats.cached_tokens_total,
        # Robustness trail (docs/architecture/fault-tolerance.md):
        # watchdog trips on the step loop, CRC-rejected bundles, and
        # transfers that degraded to local recompute.
        "engine_watchdog_stalls_total": stats.engine_watchdog_stalls_total,
        "kv_bundle_crc_failures_total": stats.kv_bundle_crc_failures_total,
        "kv_recompute_fallbacks_total": stats.kv_recompute_fallbacks_total,
        # Mid-stream failover (the stream-continuation contract,
        # fault-tolerance.md): resume admissions, the delivered tokens
        # they replayed as committed prefix, and resume requests the
        # serving layer rejected.
        "stream_resumes_total": stats.stream_resumes_total,
        "resume_replayed_tokens_total": stats.resume_replayed_tokens_total,
        "stream_resume_failures_total": stats.stream_resume_failures_total,
    }
    if stats.moe_grouped_calls_total:
        # The grouped expert matmul's count (one-device grouped MoE
        # backend only): groups / (calls x experts) is the share of the
        # expert weights a call reads.
        counters["moe_grouped_calls_total"] = stats.moe_grouped_calls_total
        counters["moe_groups_with_rows_total"] = stats.moe_groups_with_rows_total
        # held / picks is the share of the router's picks this rank's
        # experts serve (100 % where the model is served whole).
        counters["moe_picks_total"] = stats.moe_picks_total
        counters["moe_picks_held_total"] = stats.moe_picks_held_total
    if stats.indexer_keys_written_total:
        # Learned sparse attention (models with an indexer only).
        counters["sparse_bound_tokens_total"] = stats.sparse_bound_tokens_total
        counters["sparse_unbound_tokens_total"] = stats.sparse_unbound_tokens_total
        counters["indexer_keys_scored_total"] = stats.indexer_keys_scored_total
        counters["indexer_keys_written_total"] = stats.indexer_keys_written_total
    if stats.latent_rows_written_total:
        # ... over a latent cache (models/mla_dsa.py), x the layers.
        counters["sparse_rows_selected_total"] = stats.sparse_rows_selected_total
        counters["latent_rows_written_total"] = stats.latent_rows_written_total
    if stats.swa_ring_pages:
        # Hybrid-APC section retention activity
        counters["swa_section_hits_total"] = stats.swa_section_hits_total
        counters["swa_section_misses_total"] = stats.swa_section_misses_total
        counters["swa_section_captures_total"] = stats.swa_section_captures
        counters["swa_ring_seeds_total"] = stats.swa_ring_seeds_total
        counters["swa_ring_seed_pages_total"] = stats.swa_ring_seed_pages_total
        counters["swa_ring_seed_host_ms_total"] = stats.swa_ring_seed_host_ms_total
    if stats.state_bytes_in_use_total:
        # The state pool of a model with state-space layers: the retained-
        # state cache's activity, the slots' bytes beside the pages'
        # (kv_bytes_in_use_total counts pages only there), and what the
        # mixers computed (rows and tokens x mixer layers).
        counters["state_snapshot_hits_total"] = stats.state_snapshot_hits_total
        counters["state_snapshot_misses_total"] = stats.state_snapshot_misses_total
        counters["state_snapshot_captures_total"] = stats.state_snapshot_captures_total
        counters["state_snapshot_evictions_total"] = stats.state_snapshot_evictions_total
        counters["state_bytes_in_use_total"] = stats.state_bytes_in_use_total
        counters["ssm_update_rows_total"] = stats.ssm_update_rows_total
        counters["ssm_scan_tokens_total"] = stats.ssm_scan_tokens_total
        counters["gdn_update_rows_total"] = stats.gdn_update_rows_total
        counters["gdn_scan_rows_total"] = stats.gdn_scan_rows_total
        counters["gdn_scan_tokens_total"] = stats.gdn_scan_tokens_total
        counters["gdn_state_bytes_moved_total"] = stats.gdn_state_bytes_moved_total
    if stats.swa_ring_pages or stats.state_bytes_in_use_total:
        # Retained-state captures of either kind: those that hashed their
        # prompt again (0 where every request was hashed at its admission),
        # their host time, spent behind a step's dispatch, and those taken
        # at a sequence's last page before its foreseen finish.
        counters["retained_capture_rehashed_total"] = stats.retained_capture_rehashed_total
        counters["retained_capture_host_ms_total"] = round(stats.retained_capture_host_ms_total, 3)
        counters["retained_finish_captures_total"] = stats.retained_finish_captures_total
    lines: list[str] = []
    if stats.kv_transfer_failures:
        # Per-(stage, policy) transfer-failure breakdown (llmd-family
        # extension): which leg swallowed the failure and what
        # degradation was applied — the detail behind
        # kv_transfer_import_failures_total's flat count.
        lines.append("# TYPE llmd:kv_transfer_failures_total counter")
        for (stage, policy), n in stats.kv_transfer_failures:
            lines.append(
                f'llmd:kv_transfer_failures_total{{stage="{stage}",'
                f'policy="{policy}",model_name="{model_name}"}} {n}'
            )
    if stats.moe_expert_tokens:
        # Wide-EP MoE (docs/architecture/wide-ep.md): per-logical-expert
        # routed-token counts — the EPLB control loop's input, and the
        # skew panel's series. llmd-family only (vLLM has no per-expert
        # load contract). Dropped slots and the live/peak capacity
        # numbers ride the flat namespaces below.
        lines.append("# TYPE llmd:moe_expert_tokens_total counter")
        for e, n in enumerate(stats.moe_expert_tokens):
            lines.append(
                f'llmd:moe_expert_tokens_total{{expert="{e}",'
                f'model_name="{model_name}"}} {n}'
            )
        gauges["moe_capacity_factor"] = round(stats.moe_capacity_factor, 4)
        gauges["moe_peak_demand"] = round(stats.moe_peak_demand, 4)
        counters["moe_dropped_slots_total"] = stats.moe_dropped_slots_total
        counters["moe_rebalances_total"] = stats.moe_rebalances_total
    injected = faults.injected_counts()
    if injected:
        # Only present while a fault plan is armed (chaos runs): how many
        # injections each site actually delivered, so a matrix leg can
        # assert its fault fired from the same surface it asserts the
        # degradation on.
        lines.append("# TYPE llmd:faults_injected_total counter")
        for site, n in sorted(injected.items()):
            lines.append(
                f'llmd:faults_injected_total{{site="{site}",'
                f'model_name="{model_name}"}} {n}'
            )
    if stats.max_lora:
        # reference model-servers.md:78-89: adapter state rides labels on
        # a gauge named vllm:lora_requests_info. available_lora_adapters
        # is this framework's extension: the FULL registered set — the
        # DYNAMIC registry on paged-pool engines (runtime load/unload),
        # falling back to the build-time static map — so the router can
        # fold adapter identity into prefix hashes even for adapters
        # with nothing in flight. resident_lora_adapters is the HBM
        # working set the tri-state LoraAffinityScorer routes on
        # (docs/architecture/multi-tenant-lora.md).
        running = ",".join(stats.running_lora_adapters)
        waiting = ",".join(stats.waiting_lora_adapters)
        available = ",".join(
            stats.available_lora_adapters or sorted(lora_adapters or ())
        )
        resident = ",".join(stats.resident_lora_adapters) or available
        lines.append("# TYPE vllm:lora_requests_info gauge")
        lines.append(
            f'vllm:lora_requests_info{{max_lora="{stats.max_lora}",'
            f'running_lora_adapters="{running}",'
            f'waiting_lora_adapters="{waiting}",'
            f'available_lora_adapters="{available}",'
            f'resident_lora_adapters="{resident}",'
            f'model_name="{model_name}"}} 1'
        )
        # Paged adapter pool (multi-tenant-lora.md): HBM residency vs
        # the unbounded registry — evictions, cold-load waits, and load
        # API failures are the thrash/degradation trail.
        gauges["lora_pool_resident_adapters"] = (
            stats.lora_pool_resident_adapters
        )
        counters["lora_pool_evictions_total"] = stats.lora_pool_evictions_total
        counters["lora_cold_loads_total"] = stats.lora_cold_loads_total
        counters["lora_load_failures_total"] = stats.lora_load_failures_total
    if stats.spec_accepted_len_hist:
        # Speculative decoding (propose/verify/accept contract,
        # docs/architecture/speculative-decoding.md + observability.md).
        # llmd-family ONLY: these names are this engine's, not vLLM's
        # (vLLM's spec-decode metrics are named differently), so they
        # must not masquerade in the vllm: namespace a stock dashboard
        # keys on.
        lines.append("# TYPE llmd:spec_acceptance_rate gauge")
        lines.append(
            f"llmd:spec_acceptance_rate{label} "
            f"{round(stats.spec_acceptance_rate, 6)}"
        )
        for name, v in (
            ("spec_proposed_tokens_total", stats.spec_proposed_tokens_total),
            ("spec_accepted_tokens_total", stats.spec_accepted_tokens_total),
        ):
            lines.append(f"# TYPE llmd:{name} counter")
            lines.append(f"llmd:{name}{label} {v}")
        # Per-step accepted-draft-length histogram (one bucket per
        # accepted length 0..k).
        hist = stats.spec_accepted_len_hist
        lines += _histogram(
            "llmd:spec_accepted_len", model_name,
            [*enumerate(hist), ("+Inf", 0)],
            sum(j * c for j, c in enumerate(hist)), sum(hist),
        )
    if stats.spec_row_depth_hist:
        # Per-row verify depth histogram (--ragged-qlens adaptive depth:
        # bucket d counts decode rows dispatched at a 1 + draft width of
        # exactly d tokens; two buckets populated on one step means two
        # rows ran DIFFERENT verify depths in the same program).
        hist = stats.spec_row_depth_hist
        lines += _histogram(
            "llmd:spec_row_depth", model_name,
            [*enumerate(hist), ("+Inf", 0)],
            sum(d * c for d, c in enumerate(hist)), sum(hist),
        )
    # A step's hold on the device (EngineStats.step_host_hold_*): how long,
    # at most, the device stood finished with nothing queued before the
    # next step was dispatched, by size in ms. A hold over 16 ms is a host
    # stall: every stream's next token waits for it.
    lines += _histogram(
        "llmd:step_host_hold_ms", model_name,
        [
            (1, stats.step_host_hold_le1ms_total),
            (4, stats.step_host_hold_1to4ms_total),
            (16, stats.step_host_hold_4to16ms_total),
            (64, stats.step_host_hold_16to64ms_total),
            (256, stats.step_host_hold_64to256ms_total),
            ("+Inf", stats.step_host_hold_over256ms_total),
        ],
        round(stats.step_host_hold_ms_total, 3), stats.step_host_holds_total,
    )
    for family in ("vllm", "llmd"):
        for name, v in gauges.items():
            lines.append(f"# TYPE {family}:{name} gauge")
            lines.append(f"{family}:{name}{label} {v}")
        for name, v in counters.items():
            lines.append(f"# TYPE {family}:{name} counter")
            lines.append(f"{family}:{name}{label} {v}")
        lines.append(f"# TYPE {family}:cache_config_info gauge")
        lines.append(
            f'{family}:cache_config_info{{block_size="{stats.page_size}",'
            f'num_gpu_blocks="{stats.num_pages}",model_name="{model_name}"}} 1'
        )
    return "\n".join(lines) + "\n"


def _histogram(
    name: str, model_name: str, buckets: list, total, count: int
) -> list[str]:
    """The Prometheus text form of one histogram. ``buckets``: [(upper
    edge, observations in that bucket alone)], the last edge ``+Inf``; they
    are made cumulative here. ``count``: every observation."""
    out = [f"# TYPE {name} histogram"]
    cum = 0
    for le, n in buckets:
        cum += n
        out.append(f'{name}_bucket{{le="{le}",model_name="{model_name}"}} {cum}')
    out.append(f'{name}_sum{{model_name="{model_name}"}} {total}')
    out.append(f'{name}_count{{model_name="{model_name}"}} {count}')
    return out


def parse_prometheus(text: str) -> dict[str, float]:
    """Parse a Prometheus text page into {metric_name: value}.

    Labels are dropped; repeated names keep the first sample (single-model
    engines emit one series per name). This is the scrape-side half of the
    metrics contract used by the EPP data layer.
    """
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            name_part, value = line.rsplit(None, 1)
        except ValueError:
            continue
        name = name_part.split("{", 1)[0]
        if name not in out:
            try:
                out[name] = float(value)
            except ValueError:
                continue
    return out
