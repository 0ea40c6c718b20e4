#!/usr/bin/env python3
"""Generate the Grafana dashboard bundle.

One source of truth for panel layout/units/thresholds so the six
dashboards stay consistent (the reference ships 28-39KB hand-built
dashboards; here they are generated — edit THIS file, then run it:

    python observability/grafana/generate.py

Metric names come from the live exporters: llmd_tpu/serve/metrics.py
(engine, vllm:/llmd: families), epp/server.py + epp/precise_prefix.py
(llm_d_epp_*), autoscale/engine.py (wva_*), batch/asyncproc.py
(llmd_async_*), kvstore/master.py (store stats).
"""

from __future__ import annotations

import json
import os

OUT = os.path.dirname(os.path.abspath(__file__)) + "/dashboards"

_next_id = [0]


def _id() -> int:
    _next_id[0] += 1
    return _next_id[0]


def panel(title, exprs, *, kind="timeseries", w=8, h=7, unit=None,
          desc=None, thresholds=None, legends=None, max1=False):
    targets = []
    for i, e in enumerate(exprs):
        t = {"expr": e, "refId": chr(65 + i)}
        if legends and i < len(legends):
            t["legendFormat"] = legends[i]
        targets.append(t)
    p = {"type": kind, "title": title, "id": _id(), "targets": targets}
    fc = {}
    if unit:
        fc["unit"] = unit
    if max1:
        fc["min"] = 0
        fc["max"] = 1
    if thresholds:
        fc["thresholds"] = {
            "mode": "absolute",
            "steps": [{"color": c, "value": v} for v, c in thresholds],
        }
    if fc:
        p["fieldConfig"] = {"defaults": fc}
    if desc:
        p["description"] = desc
    p["_w"], p["_h"] = w, h
    return p


def heatmap(title, expr, *, desc=None, w=8):
    """A Prometheus histogram's buckets over time (one target, by ``le``)."""
    p = panel(title, [expr], kind="heatmap", w=w, desc=desc,
              legends=["{{le}}"])
    p["targets"][0]["format"] = "heatmap"
    return p


def row(title):
    return {"type": "row", "title": title, "id": _id(), "_w": 24, "_h": 1}


def dashboard(uid, title, comment, panels, links=()):
    # flow layout: rows reset x; panels wrap at 24 cols
    x = y = 0
    row_h = 0
    placed = []
    for p in panels:
        w, h = p.pop("_w"), p.pop("_h")
        if p["type"] == "row" or x + w > 24:
            x, y = 0, y + (row_h if row_h else 0)
            row_h = 0
        p["gridPos"] = {"x": x, "y": y, "w": w, "h": h}
        x += w
        row_h = max(row_h, h)
        if p["type"] == "row":
            x, y = 0, y + 1
            row_h = 0
        placed.append(p)
    return {
        "__comment": comment,
        "title": f"llmd-tpu / {title}",
        "uid": uid,
        "schemaVersion": 39,
        "editable": True,
        "timezone": "browser",
        "time": {"from": "now-1h", "to": "now"},
        "refresh": "30s",
        "tags": ["llmd-tpu"],
        "links": [
            {"type": "dashboards", "tags": ["llmd-tpu"], "title": "llmd-tpu",
             "asDropdown": True, "includeVars": True}
        ],
        "templating": {"list": [{
            "name": "model",
            "label": "model",
            "type": "query",
            "datasource": None,
            "query": "label_values(vllm:num_requests_running, model_name)",
            "refresh": 2,
            "includeAll": True,
            "current": {"text": "All", "value": "$__all"},
        }]},
        "panels": placed,
    }


M = '{model_name=~"$model"}'

DASHBOARDS = {}

# ---------------------------------------------------------------- router
DASHBOARDS["llmd-router-overview"] = dashboard(
    "llmd-router-overview", "Router Overview",
    "Router (EPP) overview — request flow, scheduling, flow control, "
    "latency. Counterpart of the reference llm-d-vllm-overview dashboard "
    "on this framework's llm_d_epp_* names (epp/server.py).",
    [
        panel("Ready endpoints", ["llm_d_epp_ready_endpoints"], kind="stat",
              w=4, h=4, thresholds=[(None, "red"), (1, "green")],
              desc="Pods passing discovery + scrape. 0 = the pool is dark."),
        panel("Flow-control queue", ["llm_d_epp_flow_control_queue_size"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (64, "yellow"), (256, "red")],
              desc="Requests parked by flow control. Sustained growth = "
                   "saturated pool or too-strict bands; KEDA scales on this."),
        panel("Request rate", ["rate(llm_d_epp_requests_total[5m])"],
              kind="stat", w=4, h=4, unit="reqps"),
        panel("Proxy errors /s", ["rate(llm_d_epp_proxy_errors_total[5m])"],
              kind="stat", w=4, h=4, unit="reqps",
              thresholds=[(None, "green"), (0.1, "red")]),
        panel("Scheduling errors /s",
              ["rate(llm_d_epp_scheduling_errors_total[5m])"],
              kind="stat", w=4, h=4, unit="reqps",
              thresholds=[(None, "green"), (0.01, "red")]),
        panel("Mean TTFT (router-observed)",
              ["llm_d_epp_ttft_seconds_mean"], kind="stat", w=4, h=4,
              unit="s", thresholds=[(None, "green"), (0.2, "yellow"), (1, "red")]),
        row("Pool state"),
        panel("Pool avg KV utilization",
              ["llm_d_epp_pool_avg_kv_cache_utilization"], unit="percentunit",
              max1=True,
              desc="Average of the pods' routing-visible utilization "
                   "(binding pool: main KV table or SWA ring)."),
        panel("Pool avg queue depth", ["llm_d_epp_pool_avg_queue_size"],
              desc="Mean vllm:num_requests_waiting across pods; compare "
                   "with per-pod drilldown to spot skew the scorers miss."),
        panel("Scheduling throughput",
              ["rate(llm_d_epp_scheduling_attempts_total[5m])",
               "rate(llm_d_epp_requests_total[5m])"],
              legends=["attempts/s", "requests/s"], unit="reqps",
              desc="attempts > requests means retries after failed picks."),
        row("Prefix index (precise routing)"),
        panel("Index size", ["llm_d_epp_prefix_index_blocks"],
              desc="Block-hash entries held; tracks the fleet's live KV."),
        panel("Index hit ratio",
              ["rate(llm_d_epp_prefix_index_hits_total[5m]) / "
               "rate(llm_d_epp_prefix_index_lookups_total[5m])"],
              unit="percentunit", max1=True,
              desc="Lookups that found a longest-prefix owner. Low + "
                   "repetitive workload = events not flowing (check ZMQ)."),
        panel("KV events ingested /s",
              ["rate(llm_d_epp_prefix_index_events_total[5m])"],
              desc="BlockStored/Removed/Cleared stream rate from engines."),
        panel("Store-fetchable blocks",
              ["llm_d_epp_prefix_index_store_blocks"],
              desc="Blocks the index knows to be one fetch away in the "
                   "fleet-wide store — the tri-state scoring tier "
                   "(docs/architecture/kv-federation.md). Zero with "
                   "federation on = publications not reaching the index."),
    ],
)

# ---------------------------------------------------------------- engine
DASHBOARDS["llmd-engine-kv-cache"] = dashboard(
    "llmd-engine-kv-cache", "Engine & KV Cache",
    "Per-engine serving + KV state in the EPP metrics protocol "
    "(serve/metrics.py; reference model-servers.md:38-52).",
    [
        panel("Requests running", [f"vllm:num_requests_running{M}"],
              kind="stat", w=4, h=4),
        panel("Requests waiting", [f"vllm:num_requests_waiting{M}"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (8, "yellow"), (32, "red")]),
        panel("KV utilization (binding)", [f"vllm:gpu_cache_usage_perc{M}"],
              kind="stat", w=4, h=4, unit="percentunit",
              thresholds=[(None, "green"), (0.8, "yellow"), (0.95, "red")],
              desc="max(main pool, SWA ring) — what routing sees."),
        panel("Prefix hit rate", [f"vllm:prefix_cache_hit_rate{M}"],
              kind="stat", w=4, h=4, unit="percentunit"),
        panel("Token throughput",
              [f"rate(vllm:generation_tokens_total{M}[5m])",
               f"rate(vllm:prompt_tokens_total{M}[5m])"],
              legends=["generation tok/s", "prompt tok/s"], w=8, h=4),
        row("KV pools"),
        panel("Pool usage by tier",
              [f"vllm:kv_main_usage_perc{M}", f"vllm:swa_ring_usage_perc{M}"],
              legends=["main table", "SWA ring"], unit="percentunit", max1=True,
              desc="Ring pool saturating first under P/D preload bursts is "
                   "expected (it is the admission constraint)."),
        panel("Offload tiers (pages)",
              [f"vllm:kv_offload_cpu_pages{M}", f"vllm:kv_offload_fs_pages{M}"],
              legends=["host DRAM", "filesystem"],
              desc="Tiered offload residency; flat at max = tier full, "
                   "oldest prefixes now evict for real."),
        panel("Offload traffic /s",
              [f"rate(vllm:kv_offload_saves_total{M}[5m])",
               f"rate(vllm:kv_offload_restores_total{M}[5m])"],
              legends=["saves/s", "restores/s"],
              desc="restores ≫ saves = HBM too small for the working set; "
                   "saves with zero restores = offload not earning its copies."),
        panel("SWA ring sections",
              [f"vllm:swa_ring_pages{M}", f"llmd:swa_sections{M}"],
              legends=["ring pool pages", "retained sections"],
              desc="Ring-pool size and hybrid-APC sections retained "
                   "(CacheConfig.swa_section_cache); sections pinned at "
                   "the cap = retention budget is the prefix-reuse limit."),
        panel("SWA section activity /s",
              [f"rate(llmd:swa_section_hits_total{M}[5m])",
               f"rate(llmd:swa_section_misses_total{M}[5m])",
               f"rate(llmd:swa_section_captures_total{M}[5m])"],
              legends=["hits/s", "misses/s", "captures/s"],
              desc="captures with zero hits = retention is paying copy "
                   "cost for prefixes that never repeat. A miss is a run "
                   "of full pages the main pool offered and the hybrid "
                   "cache refused for want of a section (the request "
                   "prefills the span and leaves the section behind): "
                   "misses that do not turn into hits = sections evicted "
                   "before their prefix comes back."),
        panel("KV bytes per cached token",
              [f"rate(llmd:kv_bytes_in_use_total{M}[5m]) / "
               f"rate(llmd:cached_tokens_total{M}[5m])"],
              legends=["bytes a cached token"], unit="bytes",
              desc="Bytes of KV pages held by live references over both "
                   "pools (main pool + SWA ring pool with its retained "
                   "sections), per token the scheduled sequences hold, "
                   "each summed per step. Every layer's share without "
                   "the ring; the full-attention layers' share plus the "
                   "rings with it; pages shared through the prefix cache "
                   "lower it."),
        panel("State snapshot hit share",
              [f"rate(llmd:state_snapshot_hits_total{M}[5m]) / "
               f"(rate(llmd:state_snapshot_hits_total{M}[5m]) + "
               f"rate(llmd:state_snapshot_misses_total{M}[5m]))",
               f"llmd:state_snapshots{M}", f"llmd:state_slots_in_use{M}"],
              legends=["hits / (hits + misses)", "snapshots retained",
                       "running slots"],
              desc="Models with state-space layers: a hit seeds a fresh "
                   "slot of the state pool from a snapshot at the end of "
                   "a run of full pages; a miss is a run the main pool "
                   "offered and the engine refused for want of a snapshot "
                   "there (the request prefills the span, 4-8k tokens in "
                   "a session, and leaves the snapshot behind). A share "
                   "that stays low = snapshots evicted before their "
                   "prefix comes back: more of them (swa_section_cache)."),
        panel("State slots: running against provisioned",
              [f"llmd:state_slots_in_use{M}", f"llmd:state_slots{M}",
               f"llmd:state_slots_in_use{M} / llmd:state_slots{M}"],
              legends=["running slots", "max_num_seqs (a slot a sequence)",
                       "share in use"],
              desc="Models with state-space layers: a running sequence "
                   "holds ONE slot of the state pool whatever its length "
                   "(megabytes a slot), so the pool is sized by the "
                   "sequences the scheduler may run. In use = provisioned "
                   "for long = the state pool, not the pages, bounds the "
                   "batch: more slots if memory allows (a decode-heavy, "
                   "high-concurrency deployment runs 128 and more)."),
        panel("State snapshot activity /s",
              [f"rate(llmd:state_snapshot_hits_total{M}[5m])",
               f"rate(llmd:state_snapshot_misses_total{M}[5m])",
               f"rate(llmd:state_snapshot_captures_total{M}[5m])",
               f"rate(llmd:state_snapshot_evictions_total{M}[5m])"],
              legends=["hits/s", "misses/s", "captures/s", "evictions/s"],
              desc="captures with zero hits = the device copies buy "
                   "nothing; evictions at the rate of captures = the "
                   "retained-state cache is too small for the sessions "
                   "it serves (every turn leaves one snapshot behind)."),
        panel("Ring seeds: host ms and pages a seed, seeds /s",
              [f"rate(llmd:swa_ring_seed_host_ms_total{M}[5m]) / "
               f"rate(llmd:swa_ring_seeds_total{M}[5m])",
               f"rate(llmd:swa_ring_seed_pages_total{M}[5m]) / "
               f"rate(llmd:swa_ring_seeds_total{M}[5m])",
               f"rate(llmd:swa_ring_seeds_total{M}[5m])"],
              legends=["host ms a seed", "pages a seed", "seeds/s"],
              desc="A hybrid prefix hit copies a retained section into a "
                   "fresh ring at the admission that takes it (span "
                   "llmd.ring.seed, in the schedule or in a top-up): "
                   "window/page pages x the sliding layers, 64 x 21 at a "
                   "1,024-token window. The step that carries the "
                   "admission is later by the host ms; the copy itself "
                   "runs on the device in front of that step."),
        panel("Retained-state capture: host ms, prompts hashed again, "
              "captures at a foreseen finish",
              [f"rate(llmd:retained_capture_host_ms_total{M}[5m]) / "
               f"(rate(llmd:state_snapshot_captures_total{M}[5m]) + "
               f"rate(llmd:swa_section_captures_total{M}[5m]))",
               f"rate(llmd:retained_capture_rehashed_total{M}[5m])",
               f"rate(llmd:retained_finish_captures_total{M}[5m]) / "
               f"rate(llmd:request_success_total{M}[5m])"],
              legends=["host ms a capture", "captures that hashed their "
                       "prompt again /s", "captures at a sequence's last "
                       "page, a finished request"],
              desc="A ring's sections and a state pool's snapshots alike. "
                   "A capture's host work (key, eviction, allocation, two "
                   "index puts, the copy's dispatch) runs behind the "
                   "dispatch of the step that writes the state, under the "
                   "device: no part of the host gap. Its key comes from "
                   "the admission's hash walk; a capture that hashed its "
                   "prompt again (a P/D preload, a request the pager "
                   "resumed) costs ~3 us a page of context on the host: "
                   "0 for session traffic. A request whose end by length "
                   "is foreseen leaves its state behind at the last page "
                   "it fills, so its session's next turn does not prefill "
                   "its own last answer: ~1 a finished request for "
                   "sessions that end by max_tokens, 0 for sequences that "
                   "fill the model and restart, low where answers end on "
                   "a stop token (then each turn prefills the last answer "
                   "again from its prompt's end)."),
        panel("State-space work /s",
              [f"rate(llmd:ssm_update_rows_total{M}[5m]) + "
               f"rate(llmd:gdn_update_rows_total{M}[5m])",
               f"rate(llmd:ssm_scan_tokens_total{M}[5m]) + "
               f"rate(llmd:gdn_scan_tokens_total{M}[5m])",
               f"rate(llmd:gdn_scan_rows_total{M}[5m])",
               f"rate(llmd:gdn_state_bytes_moved_total{M}[5m])"],
              legends=["decode rows x mixer layers /s",
                       "prefill tokens x mixer layers /s",
                       "delta-rule scan rows x layers /s",
                       "delta-rule state bytes moved /s"],
              desc="What the state-space layers computed: a decode row "
                   "reads and writes its whole slot state a layer "
                   "(bandwidth), a prefill token goes through the "
                   "chunked scan (compute). Scan tokens that stay high "
                   "under a high snapshot hit share = turns prefill "
                   "their own last answers again (ROADMAP M4 (a))."),
        panel("State bytes held",
              [f"rate(llmd:state_bytes_in_use_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])",
               f"(rate(llmd:kv_bytes_in_use_total{M}[5m]) + "
               f"rate(llmd:state_bytes_in_use_total{M}[5m])) / "
               f"rate(llmd:cached_tokens_total{M}[5m])"],
              legends=["state-pool bytes held a step",
                       "bytes a cached token, both pools"], unit="bytes",
              desc="Bytes of state-pool slots held (running sequences' "
                   "and retained snapshots' alike: a fixed size a slot, "
                   "whatever the context), and with the attention "
                   "layers' pages what a cached token costs. The state's "
                   "share falls as contexts grow."),
        row("Million-token context tier (long-context.md)"),
        panel("Ring prefill steps /s",
              [f"rate(llmd:cp_ring_steps_total{M}[5m])"],
              legends=["ring steps/s"],
              desc="Context-parallel prefill collective steps "
                   "(ops/ring_attention.py). Zero with cp_prefill > 1 "
                   "configured = prompts never clear "
                   "cp_prefill_min_tokens, the ring is not engaging."),
        panel("Pager residency (spilled bytes)",
              [f"llmd:kv_paged_out_bytes{M}"],
              legends=["paged-out bytes"], unit="bytes",
              desc="Decode-time pager: live-sequence KV resident in the "
                   "offload tier instead of HBM. Growing with flat pool "
                   "usage is the tier working; zero under long-context "
                   "load = decode_paging off or windows too wide."),
        panel("Late window fetches /s",
              [f"rate(llmd:kv_pager_prefetch_late_total{M}[5m])"],
              legends=["late fetches/s"],
              desc="Window restores that finished after the request "
                   "could have run — sustained rate means "
                   "pager_horizon_tokens is too small for the wire."),
        row("KV federation (fleet-wide store)"),
        panel("Recompute avoided tok/s",
              [f"rate(llmd:recompute_avoided_tokens_total{M}[5m])",
               f"rate(vllm:prompt_tokens_total{M}[5m])"],
              legends=["avoided tok/s", "prompt tok/s"],
              desc="Prompt tokens served by store-fetched pages instead "
                   "of fleet-wide re-prefill — the federation headline "
                   "(docs/architecture/kv-federation.md); read against "
                   "total prompt throughput."),
        panel("Federation flow /s",
              [f"rate(llmd:kv_federation_published_total{M}[5m])",
               f"rate(llmd:kv_federation_hits_total{M}[5m])"],
              legends=["published/s", "store hits/s"],
              desc="Publications the master accepted vs pages pulled "
                   "back. Publishes with zero hits fleet-wide = the "
                   "store is not earning its copies (raise the hotness "
                   "gate); hits on this replica come from peers."),
        panel("Store client reads /s",
              [f"rate(llmd:kvstore_pulls_total{M}[5m])",
               f"rate(llmd:kvstore_pull_failures_total{M}[5m])",
               f"rate(llmd:kvstore_misses_total{M}[5m])"],
              legends=["pulls/s", "pull failures/s", "misses/s"],
              desc="Peer-to-peer read path. Failures degrade to "
                   "recompute (never an error upstream); a miss burst "
                   "with the master down rides the read breaker's "
                   "cooldown."),
        row("Step pipeline (the pipelined step)"),
        panel("Host gap per step",
              [f"llmd:step_host_gap_ms{M}",
               f"rate(llmd:step_host_gap_ms_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])"],
              legends=["last step (ms)", "mean (5m)"], unit="ms",
              desc="Per-step host time the device idles for: in the "
                   "pipelined step the time from a readback's end to the "
                   "next dispatch's return (commit, reconcile, a last "
                   "top-up, fill, put + call); a regression here "
                   "re-serializes the pipeline "
                   "(docs/architecture/async-scheduling.md)."),
        panel("Host gap by part",
              [f"rate(llmd:step_{part}_ms_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])"
               for part in ("readback", "commit", "redispatch",
                            "gap_admit", "ready_lag_bound")],
              legends=["readback (before the gap)", "commit + reconcile",
                       "top-up + fill + put + call",
                       "of it: admission in the gap",
                       "ready lag, upper bound"],
              unit="ms",
              desc="The host's turn between two step programs, part by "
                   "part: the readback (first ready to parsed results; "
                   "the host gap starts at its end), the gap's two parts "
                   "(readback to reconciled: collect, scheduler update, "
                   "late intake, rollbacks; reconciled to the next "
                   "dispatch's return), the admission that ran inside "
                   "the second with the device empty, and the most the "
                   "host can have noticed the device's end late (last "
                   "poll that found it running to the first that found "
                   "it ready). Commit and redispatch are 0 on an engine "
                   "that keeps the synchronous step (lockstep, P/D "
                   "producer)."),
        panel("Engine duty cycle",
              [f"1 - rate(llmd:engine_idle_ms_total{M}[5m]) / 1000"],
              unit="percentunit", max1=True,
              desc="The share of wall time the serving loop had "
                   "something to run (1 - time waited with no inbox, no "
                   "aborts and no work; a paused engine is not counted "
                   "idle). Low duty with a high time to first token is "
                   "a slow host or device, not load: the saturation "
                   "signal that tells the two apart."),
        panel("Steps dispatched from a prestaged slot",
              [f"rate(llmd:steps_prestaged_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])",
               f"rate(llmd:steps_topped_up_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])",
               f"rate(llmd:steps_dispatched_before_readback_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])"],
              legends=["prestaged share", "topped-up share",
                       "dispatched before the readback"],
              unit="percentunit",
              desc="How often the pipeline engages: the share of steps "
                   "whose batch was scheduled and staged while the step "
                   "before ran (near 1 under load; 0 on an engine that "
                   "keeps the synchronous step), of steps whose "
                   "staged batch took in requests that arrived after the "
                   "speculative schedule, and of steps dispatched the "
                   "moment the step before was seen ready, before its "
                   "readback (decode rows take their token from the "
                   "device; a step with drafts or a fused window waits "
                   "for the commit)."),
        panel("Step time by phase",
              [f"rate(llmd:step_{ph}_ms_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])"
               for ph in ("admit", "schedule", "launch", "wait", "finish")]
              + [f"rate(llmd:step_ms_total{M}[5m]) / "
                 f"rate(llmd:engine_steps_total{M}[5m])"],
              legends=["admit", "schedule", "launch", "wait (device + "
                       "readback)", "finish", "whole step"], unit="ms",
              desc="Mean ms a step spends in each phase (the spans of "
                   "llmd_tpu/obs/profiling.py by the same names), as host "
                   "time spent: in the pipelined step schedule and most "
                   "of launch run under the device; in the synchronous "
                   "step schedule + launch + finish is the host gap; "
                   "whatever of the whole step the five do not cover is "
                   "the engine's own bookkeeping after the step."),
        panel("Step time by kind",
              [f"rate(llmd:step_ms_decode_total{M}[5m]) / "
               f"rate(llmd:steps_decode_total{M}[5m])",
               f"rate(llmd:step_ms_prefill_total{M}[5m]) / "
               f"(rate(llmd:steps_prefill_total{M}[5m]) + "
               f"rate(llmd:steps_mixed_total{M}[5m]))",
               f"(rate(llmd:steps_prefill_total{M}[5m]) + "
               f"rate(llmd:steps_mixed_total{M}[5m])) / "
               f"rate(llmd:engine_steps_total{M}[5m])"],
              legends=["decode-only step (ms)", "step with prefill (ms)",
                       "share of steps with prefill"],
              desc="A decode row's gap between tokens is the decode step "
                   "where no prompt is in the batch and the prefill step "
                   "where one is: the share says how often."),
        panel("Queue wait before first scheduling",
              [f"rate(llmd:queue_wait_ms_total{M}[5m]) / "
               f"rate(llmd:queue_admitted_total{M}[5m])",
               f"rate(llmd:intake_wait_ms_total{M}[5m]) / "
               f"rate(llmd:intake_requests_total{M}[5m])",
               f"rate(llmd:deliver_lag_ms_total{M}[5m]) / "
               f"rate(llmd:outputs_delivered_total{M}[5m])"],
              legends=["arrival to first admission",
                       "submit to intake (before arrival)",
                       "readback to delivery (per output)"],
              unit="ms",
              desc="Mean ms between a request's arrival at the engine and "
                   "its first admission by the scheduler: the part of the "
                   "time to first token that is waiting, not computing. "
                   "Beside it the two waits outside the engine's own "
                   "clock: a submitted request in the serving loop's "
                   "inbox until the engine thread takes it in, and a "
                   "step's outputs from its readback's end to their "
                   "streams (commit, re-dispatch and assembly come "
                   "first in the pipelined step)."),
        panel("Step programs traced /s",
              [f"rate(llmd:programs_traced_total{M}[5m])"],
              thresholds=[(None, "green"), (0.01, "red")],
              desc="A step program traced after warm-up is a shape nobody "
                   "warmed: seconds of trace, lowering and (on a cache "
                   "miss) compile inside a serving step. /admin/status "
                   "names the shapes."),
        panel("Host-to-device transfers per step program",
              [f"rate(llmd:step_h2d_transfers_total{M}[5m]) / "
               f"rate(llmd:step_dispatches_total{M}[5m])"],
              thresholds=[(None, "green"), (1.01, "red")],
              desc="A step program's host inputs travel as ONE packed "
                   "buffer (llmd:step_h2d_bytes_total has its bytes): 1 "
                   "means the packed payload engages. A transfer costs "
                   "the host about the same whatever its size, so more "
                   "than 1 is launch time the chip idles through."),
        panel("Engine steps /s", [f"rate(llmd:engine_steps_total{M}[5m])"],
              desc="Step cadence; flat at 0 while requests run = the "
                   "step loop is wedged."),
        panel("Async rollbacks /s",
              [f"rate(llmd:async_rollbacks_total{M}[5m])",
               f"rate(llmd:async_wasted_rows_total{M}[5m])"],
              legends=["staged rows rolled back", "dispatched rows wasted"],
              thresholds=[(None, "green"), (5, "yellow")],
              desc="Rows a late EOS / stop-token finish or an abort "
                   "found staged (dropped before their dispatch) or "
                   "already dispatched (computed, their token dropped, "
                   "one row-step each). A few per second is the async "
                   "contract working; a surge means the speculate-ahead "
                   "window mismatches the workload's stop behavior."),
        panel("Dispatches per emitted token",
              [f"llmd:dispatches_per_emitted_token{M}",
               f"rate(llmd:decode_dispatches_total{M}[5m])"],
              legends=["dispatches/token (lifetime)", "decode dispatches/s"],
              desc="Decode device programs per generated token — the "
                   "fused-window headline: fused decode windows and "
                   "accepted drafts (speculative-decoding.md) both "
                   "spread one dispatch over more tokens, pushing the "
                   "ratio toward 1/window or 1/mean emitted per "
                   "verify step."),
        panel("Dispatches per step (unified step)",
              [f"rate(llmd:step_dispatches_total{M}[5m]) / "
               f"rate(llmd:engine_steps_total{M}[5m])",
               f"rate(llmd:unified_steps_total{M}[5m])"],
              legends=["device programs/step", "unified steps/s"],
              desc="Device programs dispatched per engine step. The "
                   "unified single-dispatch step (--unified-step) packs "
                   "mixed prefill+decode+verify steps into ONE ragged "
                   "program, pulling this toward 1.0; a rise with "
                   "unified steps/s at zero means mixed traffic is "
                   "paying the split engine's two-to-three dispatches "
                   "(plus one lockstep broadcast each on multi-host)."),
        panel("Attention tokens in shared tiles (flat step)",
              [f"rate(llmd:attn_shared_tile_tokens_total{M}[5m]) / "
               f"rate(llmd:live_tokens_total{M}[5m])"],
              unit="percentunit", legends=["shared-tile / live tokens"],
              desc="Of the tokens the flat steps computed, the share in "
                   "16-token granules of the stream that hold one row "
                   "only (a prefill chunk's body): the flat attention "
                   "kernel reads such a tile's context once for its 16 "
                   "queries, every other token's once a token. High "
                   "under prefill-heavy traffic; 0 while only decode "
                   "rows run. A prefill-heavy engine that reads low has "
                   "chunks cut smaller than the 16-token granule or "
                   "laid off it."),
        panel("Decode keys read through shared-prefix runs (flat step)",
              [f"rate(llmd:attn_prefix_run_keys_total{M}[5m]) / "
               f"rate(llmd:attn_decode_keys_total{M}[5m])"],
              unit="percentunit", legends=["run keys / decode keys"],
              desc="Of the keys under the horizons of the flat steps' "
                   "decode rows, the share in a shared-prefix run: "
                   "leading blocks of the SAME physical pages (a shared "
                   "document or system prompt, handed out by the prefix "
                   "cache) that the attention kernel reads once a "
                   "16-token tile for all the rows of the run, not once "
                   "a row. High where the router brings sessions of one "
                   "prefix to one replica; 0 where nothing is shared. "
                   "Low beside a high prefix-cache hit rate: the shared "
                   "part is under one 256-key block, or fewer than "
                   "three rows of a prefix decode in the same tile."),
        panel("Padding efficiency (ragged qlens)",
              [f"rate(llmd:padded_tokens_total{M}[5m]) / "
               f"rate(llmd:live_tokens_total{M}[5m])",
               f"rate(llmd:live_tokens_total{M}[5m])"],
              legends=["padded/live token ratio", "live tokens/s"],
              desc="Pad lanes the traced shapes paid per live token. "
                   "The flattened-token step (--ragged-qlens) charges a "
                   "decode row ONE stream token instead of a bucketed "
                   "[B, Q] sub-row, bounding per-step waste at the "
                   "16-token T-granule; a high ratio with ragged on "
                   "means steps are too small for their granule, with "
                   "ragged off it is the bucketed sub-row padding."),
        panel("Sparse attention (learned indexer)",
              [f"rate(llmd:sparse_bound_tokens_total{M}[5m]) / "
               f"(rate(llmd:sparse_bound_tokens_total{M}[5m]) + "
               f"rate(llmd:sparse_unbound_tokens_total{M}[5m]))",
               f"rate(llmd:indexer_keys_scored_total{M}[5m]) / "
               f"rate(llmd:sparse_bound_tokens_total{M}[5m])",
               f"rate(llmd:indexer_keys_written_total{M}[5m])",
               f"rate(llmd:sparse_rows_selected_total{M}[5m]) / "
               f"rate(llmd:latent_rows_written_total{M}[5m])"],
              legends=["bound token share", "indexer keys scored/bound token",
                       "indexer keys written/s",
                       "latent rows selected/computed token (latent cache)"],
              desc="Models with learned sparse attention only "
                   "(docs/architecture/sparse-attention.md). Bound token "
                   "share: computed query tokens that had more cached "
                   "tokens than the indexer's top-k, so the selection "
                   "binds. Keys scored per bound token is their context "
                   "length: the indexer's scores and the dense pass "
                   "under the mask grow with it. Over a latent cache "
                   "(DeepSeek-V3.2) the read gathers the selected rows: "
                   "rows selected per computed token and layer is "
                   "min(cached tokens, top-k), what it must fetch."),
        panel("Grouped expert matmul: experts with rows",
              [f"rate(llmd:moe_groups_with_rows_total{M}[5m]) / "
               f"rate(llmd:moe_grouped_calls_total{M}[5m])",
               f"rate(llmd:moe_grouped_calls_total{M}[5m])"],
              legends=["experts with rows per grouped layer call",
                       "grouped layer calls/s"],
              desc="One-device grouped MoE backend only "
                   "(docs/architecture/observability.md). Experts that "
                   "had at least one row in a grouped MoE layer call, as "
                   "the kernel sees them: over the experts held it is the "
                   "share of expert weights a call streams from HBM, "
                   "which bounds the kernel's time from below. Few rows "
                   "a step or tokens that share a context touch fewer."),
        panel("Router picks served by the experts held here",
              [f"rate(llmd:moe_picks_held_total{M}[5m]) / "
               f"rate(llmd:moe_picks_total{M}[5m])"],
              legends=["held / picks"], unit="percentunit", max1=True,
              desc="Of the router's picks (tokens x top-k per grouped MoE "
                   "layer call) the share whose expert this rank holds = "
                   "the rows its grouped matmuls multiply "
                   "(ModelConfig.held_experts, docs/architecture/"
                   "wide-ep.md). 1 where the model is served whole; "
                   "held / routed experts under balanced routing; off "
                   "that = this rank's experts run hot or cold."),
        row("The host's tail (what a mean over steps hides)"),
        heatmap("A step's hold on the device, by size",
                f"sum by (le) (rate(llmd:step_host_hold_ms_bucket{M}[5m]))",
                desc="llmd:step_host_hold_ms: for every step behind which "
                     "another was dispatched, from the last look that found "
                     "it running to the NEXT dispatch's return: an upper "
                     "bound on how long the chip stood finished with "
                     "nothing queued. Nearly every hold is under 1 ms; a "
                     "cell lighting up above 16 ms is a host stall, several "
                     "step times in which every stream's next token waits."),
        panel("Hold p99.9, and stalls a second",
              ["histogram_quantile(0.999, sum by (le) "
               f"(rate(llmd:step_host_hold_ms_bucket{M}[5m])))",
               f"rate(llmd:step_host_hold_ms_sum{M}[5m]) / "
               f"rate(llmd:step_host_hold_ms_count{M}[5m])",
               f"sum(rate(llmd:step_host_hold_ms_count{M}[5m])) - "
               "sum(rate(llmd:step_host_hold_ms_bucket"
               f'{{le="16",{M[1:]}[5m]))'],
              legends=["p99.9 (ms, to the bucket's edge)", "mean (ms)",
                       "holds over 16 ms a second"],
              desc="The tail of the histogram beside its mean: a stall "
                   "every ten seconds moves no mean and no 95th percentile "
                   "of the gap between tokens, and is all a user of that "
                   "second sees."),
        panel("Collector pauses, ms a second by generation",
              [f"rate(llmd:gc_full_pause_ms_total{M}[5m])",
               f"rate(llmd:gc_pause_ms_total{M}[5m]) - "
               f"rate(llmd:gc_full_pause_ms_total{M}[5m])",
               f"rate(llmd:gc_full_collections_total{M}[5m])",
               f"rate(llmd:gc_collections_total{M}[5m])"],
              legends=["generation 2 (a full pass), ms/s",
                       "generations 0 and 1, ms/s",
                       "full collections a second", "collections a second"],
              desc="Python's cyclic collector, timed where it runs (any "
                   "thread: a collection holds the interpreter lock). A "
                   "full pass over an engine's heap is tens of ms; beside "
                   "the hold histogram it says whether a stall was the "
                   "collector's."),
        panel("The engine thread against the machine",
              [f"rate(llmd:engine_thread_preemptions_total{M}[5m])",
               f"rate(llmd:engine_thread_cpu_ms_total{M}[5m]) / 1000"],
              legends=["preemptions a second (involuntary context "
                       "switches)", "CPU share of one core"],
              desc="How often the kernel took the processor from the "
                   "thread that steps the engine while it wanted to run, "
                   "and how much of a core that thread used. Host phases "
                   "that read longer at the same CPU time: the thread was "
                   "preempted or waited (a crowded host); at more CPU "
                   "time: the same work ran slower. A sandboxed kernel "
                   "that reports no context switches to getrusage reads "
                   "0 preemptions whatever happens: trust a 0 only on a "
                   "host where the series has been seen to move."),
        panel("The pace of a step, ready to ready",
              [f"rate(llmd:step_ready_interval_ms_decode_total{M}[5m]) / "
               f"rate(llmd:step_ready_intervals_decode_total{M}[5m])",
               f"rate(llmd:step_ready_interval_ms_prefill_total{M}[5m]) / "
               f"rate(llmd:step_ready_intervals_prefill_total{M}[5m])"],
              legends=["decode step", "step with a prefill chunk (prefill "
                       "or mixed)"], unit="ms",
              desc="From one step's outputs seen ready to the next's, "
                   "under the kind of the later step, while the pipeline "
                   "stays full: what a step of each kind puts between two "
                   "tokens of every running stream, the host's turn "
                   "included. (step_ms_decode_total times a call of "
                   "step(), which holds the wait of the step in front.)"),
        row("Speculative decoding"),
        panel("Draft acceptance", [f"llmd:spec_acceptance_rate{M}"],
              unit="percentunit", max1=True,
              desc="accepted/proposed draft tokens. Near 0 with drafting "
                   "on = proposer overhead for nothing; raise "
                   "--spec-ngram-min-match or turn speculation off."),
        panel("Draft tokens /s",
              [f"rate(llmd:spec_proposed_tokens_total{M}[5m])",
               f"rate(llmd:spec_accepted_tokens_total{M}[5m])"],
              legends=["proposed/s", "accepted/s"]),
        panel("Mean emitted tokens per row-step",
              [f"1 + rate(llmd:spec_accepted_len_sum{M}[5m]) / "
               f"rate(llmd:spec_accepted_len_count{M}[5m])"],
              desc="From the llmd:spec_accepted_len histogram; this IS "
                   "the decode speedup on a weight-read-bound engine "
                   "(observability.md)."),
        panel("Mean per-row verify depth",
              [f"rate(llmd:spec_row_depth_sum{M}[5m]) / "
               f"rate(llmd:spec_row_depth_count{M}[5m])"],
              desc="Mean 1 + draft width rows were dispatched at (from "
                   "the llmd:spec_row_depth histogram). With "
                   "--ragged-qlens each row pays exactly its own depth "
                   "in the flattened stream — hot-draft rows run deep "
                   "while backed-off rows run depth 1 in the SAME "
                   "program; stuck at 1 = drafting never engages."),
        row("Batch tier (offline backfill)"),
        panel("Batch backlog (jobs)",
              [f"llmd:batch_backlog_jobs{M}"],
              thresholds=[(None, "green"), (1000, "yellow")],
              desc="Waiting batch-band rows — the deferrable demand the "
                   "WVA floors the fleet on instead of scaling up for "
                   "(docs/architecture/batch-processing.md). Growing "
                   "through troughs = backfill is not draining (check "
                   "the EPP batch-saturation-filter watermark)."),
        panel("Batch harvest tok/s",
              [f"rate(llmd:batch_tokens_total{M}[5m])",
               f"rate(vllm:generation_tokens_total{M}[5m])"],
              legends=["batch tok/s", "all gen tok/s"],
              desc="Tokens the backfill band computed vs total "
                   "generation — the utilization the batch tier "
                   "harvests from idle decode capacity at zero "
                   "interactive cost."),
        panel("Backfill utilization (last step)",
              [f"llmd:batch_backfill_utilization{M}"],
              unit="percentunit", max1=True,
              desc="Fraction of the last step's token budget backfilled "
                   "by batch rows. High through interactive peaks means "
                   "the watermark is too loose; zero with a backlog "
                   "means interactive traffic leaves no headroom (as "
                   "designed) or admission is wedged."),
        panel("Batch preemptions /s",
              [f"rate(llmd:batch_preemptions_total{M}[5m])"],
              thresholds=[(None, "green"), (5, "yellow")],
              desc="Batch rows recompute-preempted the moment "
                   "interactive load returned — the contract working. "
                   "A sustained surge means batch admission is fighting "
                   "interactive arrivals (lower --batch-kv-watermark or "
                   "--batch-max-seqs)."),
        row("Health"),
        panel("Preemptions /s", [f"rate(vllm:num_preemptions_total{M}[5m])"],
              thresholds=[(None, "green"), (0.5, "yellow"), (2, "red")],
              desc="Scheduler evictions under pressure; sustained rate = "
                   "raise blocks or lower max_num_seqs."),
        panel("Requests finished /s",
              [f"rate(vllm:request_success_total{M}[5m])"], unit="reqps"),
        row("Adapter pool"),
        panel("LoRA adapters (running/waiting/resident ride labels)",
              [f"vllm:lora_requests_info{M}"], kind="table", h=6,
              desc="Adapter state gauge; available_lora_adapters lists the "
                   "DYNAMIC registry (runtime load/unload), "
                   "resident_lora_adapters the HBM working set the "
                   "tri-state lora-affinity scorer routes on "
                   "(docs/architecture/multi-tenant-lora.md)."),
        panel("Resident adapters",
              [f"llmd:lora_pool_resident_adapters{M}"], kind="stat",
              w=4, h=6,
              desc="Adapters holding an HBM pool slot right now "
                   "(bounded by --lora-pool-slots; the registry is "
                   "unbounded)."),
        panel("Adapter pool churn /s",
              [f"rate(llmd:lora_cold_loads_total{M}[5m])",
               f"rate(llmd:lora_pool_evictions_total{M}[5m])"],
              legends=["cold loads/s", "evictions/s"],
              thresholds=[(None, "green"), (5, "yellow")],
              desc="Cold loads (requests parked for a slot install) and "
                   "LRU evictions of idle residents. Sustained high "
                   "churn = the tenant working set exceeds pool "
                   "capacity — raise --lora-pool-slots or tighten "
                   "router adapter affinity (LLMD_LORA_TIER_WEIGHTS)."),
        panel("Adapter load failures /s",
              [f"rate(llmd:lora_load_failures_total{M}[5m])"],
              kind="stat", w=4, h=6,
              thresholds=[(None, "green"), (0.01, "red")],
              desc="/v1/load_lora_adapter fetches that failed after "
                   "retry (surfaced 4xx): the adapter store is "
                   "unreachable or serving corrupt blobs — base-model "
                   "and resident-adapter serving is unaffected."),
        panel("Cache geometry (block_size / num_gpu_blocks ride labels)",
              [f"vllm:cache_config_info{M}"], kind="table", h=6,
              desc="The BlockSize/NumGPUBlocks half of the EPP metrics "
                   "contract (model-servers.md:38-52)."),
    ],
)

# ---------------------------------------------------------------- pd
DASHBOARDS["llmd-pd-coordinator"] = dashboard(
    "llmd-pd-coordinator", "P/D Transfer",
    "Prefill/decode disaggregation: export/import flow, failure modes, "
    "byte economics (kvtransfer/connector.py stats).",
    [
        panel("Exports /s",
              [f"rate(vllm:kv_transfer_exported_requests_total{M}[5m])"],
              kind="stat", w=4, h=4),
        panel("Imports /s",
              [f"rate(vllm:kv_transfer_imported_requests_total{M}[5m])"],
              kind="stat", w=4, h=4),
        panel("Import failures /s",
              [f"rate(vllm:kv_transfer_import_failures_total{M}[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.01, "yellow"), (0.1, "red")],
              desc="Failures degrade to local recompute (policy=recompute) "
                   "— correct but slow; nonzero here is capacity silently "
                   "moving back onto decode pods."),
        panel("Export bandwidth",
              [f"rate(vllm:kv_transfer_exported_bytes_total{M}[5m])"],
              kind="stat", w=6, h=4, unit="Bps"),
        panel("Import bandwidth",
              [f"rate(vllm:kv_transfer_imported_bytes_total{M}[5m])"],
              kind="stat", w=6, h=4, unit="Bps"),
        row("Layer-streamed import (v3 group wire)"),
        panel("Streamed cells /s",
              [f"rate(vllm:kv_stream_groups_total{M}[5m])"],
              kind="stat", w=6, h=4,
              desc="(layer-group × chunk) cells landed by group-streamed "
                   "imports; zero with P/D traffic flowing means "
                   "transfers fell back to the monolithic v2 wire "
                   "(compat pin, multi-host, or ring consumers)."),
        panel("First-group latency (ms)",
              [f"vllm:kv_stream_first_group_ms{M}"],
              kind="stat", w=6, h=4,
              desc="Last streamed import's admission-gate wait: the "
                   "decode request is schedulable once group 0 is "
                   "resident, so this — not the full transfer — is the "
                   "serial TTFT leg."),
        panel("Publish pacing (B/s delayed)",
              [f"rate(vllm:kv_publish_paced_bytes_total{M}[5m])"],
              kind="stat", w=6, h=4,
              desc="Bytes the federation publisher held back under the "
                   "LLMD_KV_PUBLISH_BYTES_PER_S budget. Persistently "
                   "high = publish demand exceeds the NIC share; raise "
                   "the hotness gate or the budget."),
        row("Flow"),
        panel("Transfer requests",
              [f"rate(vllm:kv_transfer_exported_requests_total{M}[5m])",
               f"rate(vllm:kv_transfer_imported_requests_total{M}[5m])",
               f"rate(vllm:kv_transfer_import_failures_total{M}[5m])"],
              legends=["exported/s", "imported/s", "failed/s"], w=12,
              desc="exported ≈ imported in steady state; a widening gap = "
                   "consumers falling back (check failures + lease expiry)."),
        panel("Transfer bytes",
              [f"rate(vllm:kv_transfer_exported_bytes_total{M}[5m])",
               f"rate(vllm:kv_transfer_imported_bytes_total{M}[5m])"],
              legends=["staged out B/s", "pulled in B/s"], unit="Bps", w=12,
              desc="bytes/request far below (layers × tokens × entry bytes) "
                   "= the probe byte-diet is working (cached prefixes skipped)."),
        row("Decode-side effects"),
        panel("Decode KV pressure",
              [f"vllm:gpu_cache_usage_perc{M}", f"vllm:swa_ring_usage_perc{M}"],
              legends=["binding pool", "SWA ring"], unit="percentunit",
              max1=True, w=12,
              desc="Preload bursts land pages ref-held before scheduling; "
                   "ring exhaustion here throttles admission first."),
        panel("Decode queue",
              [f"vllm:num_requests_waiting{M}", f"vllm:num_requests_running{M}"],
              legends=["waiting", "running"], w=12),
    ],
)

# ---------------------------------------------------------------- wide-EP
DASHBOARDS["llmd-wide-ep"] = dashboard(
    "llmd-wide-ep", "Wide-EP MoE",
    "Wide expert parallelism (docs/architecture/wide-ep.md): per-expert "
    "routed-token flow, EP dispatch balance, capacity drops, and the "
    "EPLB/adaptive-capacity control loops (engine census -> "
    "serve/metrics.py).",
    [
        panel("Capacity factor",
              [f"vllm:moe_capacity_factor{M}"],
              kind="stat", w=4, h=4,
              desc="Live GShard capacity_factor (the AdaptiveCapacity "
                   "ladder rung when ep_capacity_adaptive is on, the "
                   "static config otherwise). Every change recompiles "
                   "the forward programs — it should move rarely."),
        panel("Peak required factor",
              [f"vllm:moe_peak_demand{M}"],
              kind="stat", w=4, h=4,
              desc="High-water per-destination dispatch demand, in "
                   "capacity_factor units (census element E+1). "
                   "Persistently above the live capacity factor means "
                   "tokens are overflowing C — check dropped slots."),
        panel("Dropped slots /s",
              [f"rate(llmd:moe_dropped_slots_total{M}[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.1, "yellow"), (10, "red")],
              desc="Valid routed tokens that overflowed the capacity "
                   "bound and were dropped (residual-only via the MoE "
                   "block's skip connection — degraded quality, not an "
                   "error). Nonzero steady-state = raise capacity or "
                   "fix placement."),
        panel("EPLB rebalances",
              [f"increase(llmd:moe_rebalances_total{M}[1h])"],
              kind="stat", w=4, h=4,
              desc="Expert-placement recomputations applied at step "
                   "boundaries over the last hour. Zero with visible "
                   "skew below = the control loop is disarmed "
                   "(eplb_interval_steps=0) or multi-host."),
        panel("Expert load skew (max/mean)",
              [f"max(rate(llmd:moe_expert_tokens_total{M}[5m])) / "
               f"avg(rate(llmd:moe_expert_tokens_total{M}[5m]))"],
              kind="stat", w=8, h=4,
              thresholds=[(None, "green"), (2.0, "yellow"), (4.0, "red")],
              desc="Hot-expert ratio over the logical experts. The EP "
                   "step is gated by the hottest shard, so sustained "
                   "skew here is the direct tax EPLB placement exists "
                   "to remove (DeepSeek-V3-style replicate + repack)."),
        row("Per-expert routed flow"),
        panel("Routed tokens /s by expert",
              [f"rate(llmd:moe_expert_tokens_total{M}[5m])"],
              legends=["expert {{expert}}"], w=24, h=8,
              desc="Census counts per LOGICAL expert (valid routed "
                   "token slots, k slots per token). The Zipf shape of "
                   "this fan is the input the EPLB control loop "
                   "balances; after a rebalance the per-SHARD flow "
                   "evens out while this per-expert fan keeps its "
                   "popularity curve."),
        row("Dispatch economics"),
        panel("Drops vs rebalances",
              [f"rate(llmd:moe_dropped_slots_total{M}[5m])",
               f"rate(llmd:moe_rebalances_total{M}[5m])"],
              legends=["dropped slots/s", "rebalances/s"], w=12,
              desc="Drops spiking between rebalances = the placement "
                   "is going stale faster than eplb_interval_steps; "
                   "drops surviving rebalances = capacity_factor too "
                   "tight for the residual skew."),
        panel("Required vs provisioned capacity",
              [f"vllm:moe_peak_demand{M}",
               f"vllm:moe_capacity_factor{M}"],
              legends=["peak required", "provisioned"], w=12,
              desc="Padded a2a payload scales with the provisioned "
                   "factor (2 x W x C x H bytes per microbatch): the "
                   "gap between these lines is pure padding — the "
                   "adaptive ladder closes it from above at zero "
                   "drops (wide-ep-perf-model.md)."),
    ],
)

# ---------------------------------------------------------------- autoscaler
DASHBOARDS["llmd-autoscaler"] = dashboard(
    "llmd-autoscaler", "Autoscaling (WVA + KEDA)",
    "WVA decisions vs the signals driving them (autoscale/engine.py; "
    "reference hpa-wva.md).",
    [
        panel("Desired replicas", ["wva_desired_replicas"], kind="stat",
              w=6, h=4),
        panel("WVA cycles /min", ["rate(wva_cycles_total[5m]) * 60"],
              kind="stat", w=6, h=4,
              desc="Collect→Analyze→Optimize→Enforce loop rate (2/min at "
                   "the default 30 s interval). 0 = the loop is stuck."),
        panel("Scale signal: queue", ["llm_d_epp_flow_control_queue_size",
                                      "llm_d_epp_pool_avg_queue_size"],
              legends=["flow-control queue", "pool avg engine queue"],
              w=6, h=4),
        panel("Scale signal: KV", ["llm_d_epp_pool_avg_kv_cache_utilization"],
              unit="percentunit", max1=True, w=6, h=4),
        row("Decisions vs load"),
        panel("Replicas vs desired", ["wva_desired_replicas"],
              w=12, desc="Overlay actual replica count from your K8s "
                         "datasource (kube_deployment_status_replicas) to "
                         "see enforcement lag."),
        panel("Demand",
              ["rate(llm_d_epp_requests_total[5m])",
               "sum(rate(vllm:generation_tokens_total[5m]))"],
              legends=["req/s", "gen tok/s"], w=12,
              desc="V2 (token-based) analyzer follows the second series; "
                   "V1 follows utilization; SLO follows observed TTFT."),
    ],
)

# ---------------------------------------------------------------- failure
DASHBOARDS["llmd-failure-saturation"] = dashboard(
    "llmd-failure-saturation", "Failure & Saturation",
    "Every 'is it broken or just busy' signal on one screen "
    "(reference alerting.md roles).",
    [
        panel("Ready endpoints", ["llm_d_epp_ready_endpoints"], kind="stat",
              w=4, h=4, thresholds=[(None, "red"), (1, "green")]),
        panel("Proxy 5xx /s", ["rate(llm_d_epp_proxy_errors_total[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.1, "red")]),
        panel("Scheduling errors /s",
              ["rate(llm_d_epp_scheduling_errors_total[5m])"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (0.01, "red")]),
        panel("KV import failures /s",
              ["sum(rate(vllm:kv_transfer_import_failures_total[5m]))"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.01, "yellow"), (0.1, "red")]),
        panel("Preemptions /s",
              ["sum(rate(vllm:num_preemptions_total[5m]))"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (0.5, "yellow"), (2, "red")]),
        panel("Async backoffs /s", ["rate(llmd_async_backoffs_total[5m])"],
              kind="stat", w=4, h=4,
              desc="Async-processor dispatch failures being retried "
                   "(2s→60s exp backoff)."),
        row("Saturation ladder"),
        panel("Queue depths",
              ["llm_d_epp_flow_control_queue_size",
               "llm_d_epp_pool_avg_queue_size"],
              legends=["router (flow control)", "engines (avg)"], w=12,
              desc="Router queue grows only after engines saturate — if it "
                   "grows while engine queues are empty, a band/limit is "
                   "misconfigured, not capacity."),
        panel("KV utilization",
              ["llm_d_epp_pool_avg_kv_cache_utilization"], w=12,
              unit="percentunit", max1=True,
              thresholds=[(None, "green"), (0.85, "yellow"), (0.95, "red")]),
        row("Capacity escape valves"),
        panel("Offload restores /s (HBM relief)",
              ["sum(rate(vllm:kv_offload_restores_total[5m]))"], w=8),
        panel("Transfer fallbacks /s (recompute on decode)",
              ["sum(rate(vllm:kv_transfer_import_failures_total[5m]))"], w=8),
        panel("Throughput sanity",
              ["sum(rate(vllm:generation_tokens_total[5m]))"], w=8,
              desc="If this falls while queues grow, the fleet is losing "
                   "capacity (failures), not gaining load."),
        row("Degradation trails (fault-tolerance.md)"),
        panel("Engine watchdog stalls",
              [f"llmd:engine_watchdog_stalls_total{M}"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (1, "red")],
              desc="Step loop blew the watchdog budget: /health went 503 "
                   "and in-flight streams were terminated. Any nonzero "
                   "value is a wedged-device incident."),
        panel("KV bundle CRC rejects /s",
              [f"rate(llmd:kv_bundle_crc_failures_total{M}[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.001, "red")],
              desc="Corrupt transfer payloads caught by the v2 header "
                   "CRC32 and degraded to recompute instead of poisoning "
                   "the pool. Nonzero = investigate the transfer plane."),
        panel("Recompute fallbacks /s",
              [f"rate(llmd:kv_recompute_fallbacks_total{M}[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.01, "yellow"), (0.1, "red")],
              desc="Transfers that degraded to local prefill — correct "
                   "but slow; sustained rate = P/D capacity silently "
                   "shifting onto decode pods."),
        panel("EPP request retries /s",
              ["rate(llm_d_epp_request_retries_total[5m])"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (0.1, "yellow"),
                                    (1, "red")],
              desc="Re-picks after connect-refused/5xx from the picked "
                   "endpoint (capped exponential backoff)."),
        panel("EPP circuit trips /s",
              ["rate(llm_d_epp_circuit_trips_total[5m])"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (0.01, "red")],
              desc="Per-endpoint request-failure breakers opening (faster "
                   "than the 3-scrape health window)."),
        panel("EPP fail-open events /s",
              ["rate(llm_d_epp_fail_open_total[5m])"], kind="stat",
              w=4, h=4, thresholds=[(None, "green"), (0.001, "red")],
              desc="healthy-filter saw a wholly-unhealthy pool and passed "
                   "it through — usually a telemetry outage, not a fleet "
                   "outage."),
        row("Stream continuation (fault-tolerance.md)"),
        panel("Mid-stream upstream failures /s",
              ["rate(llm_d_epp_mid_stream_failures_total[5m])"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (0.01, "yellow"), (0.1, "red")],
              desc="Upstream streams cut after first byte (replica death "
                   "mid-decode). Each one either resumes transparently or "
                   "surfaces a terminal error frame."),
        panel("Stream resumes /s",
              ["rate(llm_d_epp_stream_resumes_total[5m])",
               f"rate(llmd:stream_resumes_total{M}[5m])"],
              legends=["router re-picks", "engine resume admissions"],
              w=8, h=4,
              desc="Cut streams continued on a fresh replica: the router "
                   "replays the delivered history; the engine admits it "
                   "as prefill of committed prefix and continues at the "
                   "exact next output position."),
        panel("Resume replayed tokens /s",
              ["rate(llm_d_epp_resume_replayed_tokens_total[5m])",
               f"rate(llmd:resume_replayed_tokens_total{M}[5m])"],
              legends=["router", "engine"], w=8, h=4,
              desc="Delivered-history tokens re-admitted as committed "
                   "prefix. Store/prefix-cache hits keep this cheap — "
                   "resume TTFT should be store-fetch-bound, not "
                   "recompute-bound (kv-federation.md)."),
        panel("Stream resume failures",
              ["llm_d_epp_stream_resume_failures_total",
               f"llmd:stream_resume_failures_total{M}"],
              legends=["router (budget/deadline exhausted)",
                       "engine (rejected resume)"],
              kind="stat", w=4, h=4,
              thresholds=[(None, "green"), (1, "red")],
              desc="Client-visible stream failures: the resume budget or "
                   "deadline ran out (router) or the replay was rejected "
                   "(engine). The fleet target is zero."),
        panel("Transfer failures by stage/policy",
              ["sum by (stage, policy) "
               "(rate(llmd:kv_transfer_failures_total[5m]))"], w=8,
              desc="Which transfer leg swallowed the failure (fetch / "
                   "apply / preload / export-staging) and the degradation "
                   "applied — the detail behind the flat import-failures "
                   "count."),
        panel("Open circuits", ["llm_d_epp_circuit_open"], kind="table",
              h=6, w=8,
              desc="Endpoints currently excluded by the request-failure "
                   "breaker (endpoint label carries the address)."),
        panel("Faults injected by site",
              ["sum by (site) (llmd:faults_injected_total)"], kind="table",
              h=6, w=8,
              desc="Chaos-only series: present while an LLMD_FAULT_PLAN "
                   "is armed (tests/test_faults.py, bench fault_degrade). "
                   "Nonzero in production means a fault plan leaked into "
                   "a serving process — page someone."),
    ],
)

# ---------------------------------------------------------------- drilldown
DASHBOARDS["llmd-diagnostic-drilldown"] = dashboard(
    "llmd-diagnostic-drilldown", "Diagnostic Drilldown",
    "Per-pod skew hunting: every panel intentionally NOT aggregated "
    "(reference diagnostic-drilldown role). Pair with the overview; "
    "here series fan out per scraped instance.",
    [
        panel("Running per pod", [f"vllm:num_requests_running{M}"], w=12,
              desc="One series per pod. Persistent skew with balanced "
                   "scores = an affinity plugin pinning traffic."),
        panel("Waiting per pod", [f"vllm:num_requests_waiting{M}"], w=12),
        panel("KV per pod", [f"vllm:gpu_cache_usage_perc{M}"], w=12,
              unit="percentunit", max1=True,
              desc="One hot pod at 0.95 while others idle = prefix/session "
                   "affinity outweighing load — expected for agentic "
                   "workloads, a bug for uniform ones."),
        panel("Prefix hit per pod", [f"vllm:prefix_cache_hit_rate{M}"], w=12,
              unit="percentunit", max1=True),
        panel("Gen tok/s per pod",
              [f"rate(vllm:generation_tokens_total{M}[5m])"], w=12),
        panel("Preemptions per pod",
              [f"rate(vllm:num_preemptions_total{M}[5m])"], w=12),
        panel("Transfer imports per pod",
              [f"rate(vllm:kv_transfer_imported_requests_total{M}[5m])"],
              w=12, desc="Decode pods only; a silent pod here while peers "
                         "import = its sidecar or connector is down."),
        panel("Offload restores per pod",
              [f"rate(vllm:kv_offload_restores_total{M}[5m])"], w=12),
    ],
)


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    for uid, d in DASHBOARDS.items():
        path = os.path.join(OUT, f"{uid}.json")
        with open(path, "w") as f:
            json.dump(d, f, indent=2, sort_keys=False)
            f.write("\n")
        print(f"{path}: {len(d['panels'])} panels")


if __name__ == "__main__":
    main()
