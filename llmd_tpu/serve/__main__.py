"""`python -m llmd_tpu.serve` — the model-server entry point.

Flag names mirror the vLLM flags the reference's deployment patches set
(e.g. guides/pd-disaggregation/modelserver/tpu/v6/vllm/patch-decode.yaml:
--tensor-parallel-size, --max-model-len, --block-size,
--max-num-batched-tokens, --kv-transfer-config).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time


def parse_lora_adapters(spec: str | None) -> dict[str, tuple[int, str | None]]:
    """'a,b=/path' -> {'a': (1, None), 'b': (2, '/path')}.

    Deduplicated, order-preserving. A bare name reserves an empty slot
    (identity adapter until weights install); `name=dir` loads an HF PEFT
    adapter directory into the slot at startup. Names are restricted to
    Prometheus-label-safe characters: they are interpolated into the
    lora_requests_info label values, and a quote or backslash would
    corrupt the exposition page."""
    if not spec:
        return {}
    import re

    entries: dict[str, str | None] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, path = part.partition("=")
        name = name.strip()
        if not re.fullmatch(r"[A-Za-z0-9._:/-]+", name):
            raise ValueError(
                f"invalid adapter name {name!r}: use letters, digits, ._:/-"
            )
        path = path.strip() or None
        if name in entries:
            if entries[name] != path:
                raise ValueError(
                    f"adapter {name!r} listed twice with conflicting paths "
                    f"({entries[name]!r} vs {path!r})"
                )
            continue
        entries[name] = path
    return {
        name: (i + 1, path) for i, (name, path) in enumerate(entries.items())
    }


def make_engine_config(args, lora_adapters=None):
    from llmd_tpu.config import (
        CacheConfig,
        EngineConfig,
        OffloadConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from llmd_tpu.models.loader import config_from_hf, is_model_dir
    from llmd_tpu.models.registry import get_model_config

    def _multihost_world() -> bool:
        import jax

        return jax.process_count() > 1

    overrides = {}
    if args.max_model_len is not None:
        overrides["max_model_len"] = args.max_model_len
    if args.quantization:
        overrides["quantization"] = args.quantization
    if getattr(args, "lora_pool_slots", 0):
        # Paged adapter pool (docs/architecture/multi-tenant-lora.md):
        # the slot count bounds HBM residency only; the servable set is
        # the runtime registry (/v1/load_lora_adapter), seeded from any
        # --lora-adapters entries at startup.
        overrides["num_lora_adapters"] = args.lora_pool_slots
        overrides["lora_rank"] = args.lora_rank
        overrides["lora_dynamic"] = True
    elif lora_adapters:
        overrides["num_lora_adapters"] = len(lora_adapters)
        overrides["lora_rank"] = args.lora_rank
    weights_path = args.weights_path
    tokenizer_path = args.tokenizer
    if is_model_dir(args.model):
        # --model <hf-dir>: architecture, weights, and tokenizer all come
        # from the checkpoint directory (vLLM-style); max_model_len
        # defaults to the checkpoint's max_position_embeddings.
        model = config_from_hf(args.model, **overrides)
        weights_path = weights_path or args.model
        tokenizer_path = tokenizer_path or args.model
    else:
        overrides.setdefault("max_model_len", 8192)
        model = get_model_config(args.model, **overrides)
    kv_cfg = json.loads(args.kv_transfer_config) if args.kv_transfer_config else {}
    return EngineConfig(
        model=model,
        cache=CacheConfig(
            page_size=args.block_size,
            num_blocks=args.num_gpu_blocks_override or 2048,
            dtype=args.kv_cache_dtype,
            enable_prefix_caching=not args.no_enable_prefix_caching,
            swa_ring=args.kv_swa_ring,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=args.max_num_seqs,
            max_num_batched_tokens=args.max_num_batched_tokens,
            decode_window=args.decode_window,
            speculative_ngram=args.speculative_ngram,
            spec_ngram_k=args.spec_ngram_k,
            spec_ngram_min_match=args.spec_ngram_min_match,
            unified_step=args.unified_step,
            ragged_qlens=args.ragged_qlens,
            batch_backfill=args.batch_backfill,
            batch_max_seqs=args.batch_max_seqs,
            batch_kv_watermark=args.batch_kv_watermark,
        ),
        parallel=ParallelConfig(
            tensor_parallel_size=args.tensor_parallel_size,
            # Single-process: DP across processes is the supervisor's job,
            # so the in-process mesh is TP-only. In a jax.distributed
            # world (mode B) ONE engine owns the global (dp, tp) mesh and
            # --data-parallel-size is a real mesh axis.
            data_parallel_size=(
                args.data_parallel_size if _multihost_world() else 1
            ),
            moe_backend=args.moe_backend,
            enable_dbo=args.enable_dbo,
            cp_prefill=(
                args.cp_prefill if _multihost_world() else 1
            ),
            cp_prefill_min_tokens=args.cp_prefill_min_tokens,
        ),
        seed=args.seed,
        weights_path=weights_path,
        tokenizer_path=tokenizer_path,
        kv_role=kv_cfg.get("kv_role"),
        kv_side_channel_port=int(kv_cfg.get("side_channel_port", 9600)),
        kv_transfer_port=int(kv_cfg.get("transfer_port", 9100)),
        kv_transfer_dtype=str(kv_cfg.get("transfer_dtype", "auto")),
        kv_stream_groups=int(kv_cfg.get("stream_groups", 4)),
        kv_events_endpoint=args.kv_events_endpoint,
        offload=(
            OffloadConfig(
                cpu_chunks=args.kv_offload_chunks,
                fs_dir=args.kv_offload_fs_dir,
                store_master_url=args.kv_store_master_url,
                store_segment_bytes=args.kv_store_segment_bytes,
                store_data_port=args.kv_store_data_port,
                publish_policy=args.kv_publish_policy,
                publish_min_hits=args.kv_publish_min_hits,
                decode_paging=args.kv_decode_paging,
                pager_horizon_tokens=args.kv_pager_horizon_tokens,
            )
            if args.kv_offload_chunks
            else None
        ),
    )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("llmd-tpu serve")
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--weights-path", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument(
        "--max-model-len", type=int, default=None,
        help="default: checkpoint max_position_embeddings (dir models) or 8192",
    )
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-gpu-blocks-override", type=int, default=None)
    p.add_argument("--kv-cache-dtype", default="bfloat16")
    p.add_argument(
        "--enable-dbo", action="store_true",
        help="dual-batch overlap: overlap the EP all-to-all of one half-"
        "batch with the other half's attention (wide-EP decode; the vLLM "
        "--enable-dbo role)",
    )
    p.add_argument(
        "--quantization", default=None, choices=["int8"],
        help="weight quantization (int8 W8A8; the vLLM --quantization "
        "role — the reference serves its headline path FP8)",
    )
    p.add_argument("--no-enable-prefix-caching", action="store_true")
    p.add_argument(
        "--kv-swa-ring", action="store_true",
        help="ring-buffer KV pages for sliding-window layers (the "
        "reference's hybrid KV cache manager role, pd patch-decode.yaml "
        "--no-disable-hybrid-kv-cache-manager): sliding layers hold a "
        "fixed per-sequence page ring instead of full-length pages — "
        "~2x KV capacity on gpt-oss-class models. Prefix caching "
        "becomes HYBRID: full-attention pages stay reusable, and a "
        "repeated prefix hits when its retained sliding-window section "
        "(CacheConfig.swa_section_cache) can seed the fresh ring",
    )
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument("--max-num-batched-tokens", type=int, default=2048)
    p.add_argument("--decode-window", type=int, default=1)
    p.add_argument(
        "--speculative-ngram", action="store_true",
        help="model-free speculative decoding: n-gram prompt-lookup "
             "drafting verified in one [B, 1+k] pass. Token streams stay "
             "byte-identical to the non-speculative engine for greedy "
             "and seeded sampling "
             "(docs/architecture/speculative-decoding.md)",
    )
    p.add_argument(
        "--spec-ngram-k", type=int, default=4,
        help="max draft tokens per sequence per step (the k in the "
             "[B, 1+k] verify shape family)",
    )
    p.add_argument(
        "--spec-ngram-min-match", type=int, default=2,
        help="minimum trailing n-gram length that must recur in the "
             "sequence's own history before a draft is proposed",
    )
    p.add_argument(
        "--unified-step", action=argparse.BooleanOptionalAction, default=True,
        help="pack each window=1 engine step (prefill chunks + decode "
             "rows + one-shot verify rows) into ONE ragged device "
             "program with one coalesced readback; --no-unified-step "
             "restores the split per-family dispatch paths. Streams are "
             "byte-identical either way for greedy and seeded sampling "
             "(docs/architecture/async-scheduling.md)",
    )
    p.add_argument(
        "--ragged-qlens", action=argparse.BooleanOptionalAction, default=True,
        help="genuinely ragged flattened-token unified step (cu_q_lens): "
             "the window=1 step runs over the packed token stream — a "
             "decode row costs 1 token, a verify row 1 + its own draft "
             "length (per-row adaptive verify depth) — instead of "
             "padding every row to the bucketed [B, Q] sub-row width; "
             "--no-ragged-qlens restores the bucketed unified program. "
             "Greedy and seeded streams are byte-identical either way "
             "(docs/architecture/async-scheduling.md)",
    )
    p.add_argument(
        "--batch-backfill", action=argparse.BooleanOptionalAction,
        default=True,
        help="batch serving tier: requests at or below "
             "PriorityClass.BATCH (the x-llmd-priority: batch header) "
             "ride the SAME continuous batch but only backfill "
             "token-budget/page headroom interactive rows left unused, "
             "never displace an interactive admission, and are "
             "recompute-preempted the moment interactive load returns; "
             "interactive streams stay byte-identical batch-on vs "
             "batch-off. --no-batch-backfill degrades batch-priority "
             "rows to plain low-priority rows "
             "(docs/architecture/batch-processing.md)",
    )
    p.add_argument(
        "--batch-max-seqs", type=int, default=0,
        help="cap on concurrently RUNNING batch-band rows (0 = no "
             "dedicated cap: batch may fill whatever --max-num-seqs "
             "slots interactive left idle)",
    )
    p.add_argument(
        "--batch-kv-watermark", type=float, default=0.85,
        help="admit new batch-band rows only while main-pool KV "
             "utilization is at or below this fraction, so backfill "
             "never pushes the pool into the preemption regime "
             "interactive rows pay for",
    )
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument(
        "--data-parallel-rank", type=int, default=0,
        help="this process's global DP rank (set by the DP supervisor)",
    )
    p.add_argument(
        "--moe-backend", default="grouped", choices=["grouped", "dense", "ep"],
        help="MoE path: grouped GEMM (DeepGEMM role, default), dense "
             "combine (oracle), or shard_map all-to-all (wide-EP)",
    )
    p.add_argument(
        "--cp-prefill", type=int, default=1,
        help="context-parallel ring prefill degree (long-context.md): "
        "shard long prompts' chunks over the dp mesh axis and compute "
        "attention as a ppermute ring; must equal --data-parallel-size "
        "(1 disables; forced to 1 outside a jax.distributed world, "
        "like DP itself)",
    )
    p.add_argument(
        "--cp-prefill-min-tokens", type=int, default=512,
        help="smallest chunk that rides the ring — shorter chunks are "
        "dispatch-bound and take the monolithic arm",
    )
    p.add_argument(
        "--kv-decode-paging", action="store_true",
        help="decode-time KV pager (long-context.md): spill live-"
        "sequence pages below the attention window to the offload tier "
        "and stream them back ahead of the window; requires "
        "--kv-offload-chunks and a sliding-window model",
    )
    p.add_argument(
        "--kv-pager-horizon-tokens", type=int, default=256,
        help="prefetch horizon the pager keeps resident beyond the "
        "attention window",
    )
    p.add_argument(
        "--platform", default=None,
        help="JAX platform to serve from. Default: the TPU, and the server "
        "refuses to start when JAX finds none; cpu asks for the CPU on "
        "purpose (tests, the sim backend)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kv-transfer-config", default=None, help="JSON, vLLM-style")
    p.add_argument("--kv-events-endpoint", default=None, help="ZMQ pub endpoint")
    p.add_argument(
        "--advertised-address", default=None,
        help="host:port this pod is reachable at (pod IP in-cluster); used "
        "to attribute KV events and kv-transfer params. Defaults to "
        "host:port, which is wrong when binding 0.0.0.0.",
    )
    p.add_argument(
        "--kv-offload-chunks", type=int, default=0,
        help="host-DRAM KV page budget (0 disables tiered offload; the "
        "reference TPU recipe uses 25000, tiered-prefix-cache/README.md:41-48)",
    )
    p.add_argument("--kv-offload-fs-dir", default=None, help="FS spill tier dir")
    p.add_argument(
        "--kv-store-master-url", default=None,
        help="cross-slice KV store master URL (Mooncake-Store role); "
        "enables the shared tier behind host-DRAM/FS",
    )
    p.add_argument(
        "--kv-store-segment-bytes", type=int, default=8 << 30,
        help="DRAM this host contributes to the shared pool",
    )
    p.add_argument("--kv-store-data-port", type=int, default=9200)
    p.add_argument(
        "--kv-publish-policy", default="save",
        choices=["save", "evict-hot", "off"],
        help="federation publish policy (kv-federation.md): save = "
        "publish every host-tier save (eager); evict-hot = publish only "
        "device-evicted pages used >= --kv-publish-min-hits times; off = "
        "read-only store participation",
    )
    p.add_argument(
        "--kv-publish-min-hits", type=int, default=2,
        help="hotness gate for --kv-publish-policy evict-hot: distinct "
        "uses of a page's hash chain before eviction earns a store copy",
    )
    p.add_argument("--skip-warmup", action="store_true")
    p.add_argument(
        "--lora-adapters", default=None,
        help="comma-separated adapter names to serve (each becomes a model "
        "id; random-init weights of --lora-rank until checkpoint loading)",
    )
    p.add_argument("--lora-rank", type=int, default=16)
    p.add_argument(
        "--lora-pool-slots", type=int, default=0,
        help="paged adapter pool: N HBM rank-(--lora-rank) slots over an "
        "UNBOUNDED runtime adapter registry "
        "(/v1/load_lora_adapter + /v1/unload_lora_adapter, the vLLM "
        "dynamic-LoRA contract) — idle residents are LRU-evicted for "
        "incoming tenants, slots referenced by in-flight rows are "
        "pinned, and a request naming a cold adapter parks in a "
        "loading queue instead of stalling the batch. 0 (default) "
        "keeps the fixed build-time --lora-adapters slot mapping "
        "(docs/architecture/multi-tenant-lora.md)",
    )
    p.add_argument(
        "--otlp-traces-endpoint", default=None,
        help="OTLP/HTTP collector base URL (e.g. http://otel:4318)",
    )
    p.add_argument("--trace-file", default=None, help="JSONL span log path")
    p.add_argument("--trace-sample-ratio", type=float, default=0.1)
    p.add_argument(
        "--profile-dir", default=None,
        help="directory POST /start_profile writes JAX profiler traces "
        "under (the device's operations and the engine's phase spans on "
        "one clock); unset, the two profile endpoints answer 409",
    )
    # Multi-host: join a jax.distributed world (reference LWS leader/worker
    # shape, --data-parallel-address $LWS_LEADER_ADDRESS; here the env
    # contract LLMD_COORDINATOR/LWS_LEADER_ADDRESS + LWS_GROUP_SIZE +
    # LWS_WORKER_INDEX also works without flags).
    p.add_argument(
        "--distributed-coordinator", default=None,
        help="host:port of the jax.distributed coordinator (LWS leader)",
    )
    p.add_argument("--distributed-num-processes", type=int, default=None)
    p.add_argument("--distributed-process-id", type=int, default=None)
    return p


def main(argv=None) -> None:
    logging.basicConfig(level=logging.INFO)
    args = build_parser().parse_args(argv)

    from llmd_tpu import jaxrt

    jaxrt.pin_platform(args.platform)
    cache_dir = jaxrt.enable_compile_cache()
    compiles = jaxrt.CompileCounters().install()

    from aiohttp import web

    from llmd_tpu.engine import LLMEngine
    from llmd_tpu.parallel import distributed as dist
    from llmd_tpu.serve.api import build_app
    from llmd_tpu.serve.async_engine import AsyncEngine
    from llmd_tpu.serve.tokenizer import load_tokenizer

    multihost = dist.maybe_initialize(
        coordinator=args.distributed_coordinator,
        num_processes=args.distributed_num_processes,
        process_id=args.distributed_process_id,
    )
    device = jaxrt.serving_device(args.platform)
    logging.info(
        "serving from platform=%s device_kind=%s count=%d; compile cache %s",
        device["platform"], device["kind"], device["count"], cache_dir,
    )

    adapter_specs = parse_lora_adapters(args.lora_adapters) or None
    lora_adapters = (
        {name: slot for name, (slot, _) in adapter_specs.items()}
        if adapter_specs
        else None
    )
    config = make_engine_config(args, lora_adapters)
    advertised = args.advertised_address or f"{args.host}:{args.port}"
    if advertised.startswith("0.0.0.0"):
        logging.warning(
            "advertised address %s binds all interfaces; set "
            "--advertised-address to the pod IP or KV-event attribution "
            "and P/D transfers will not resolve", advertised,
        )
    config.kv_host = advertised.rsplit(":", 1)[0]
    event_sink = None
    if config.kv_events_endpoint:
        from llmd_tpu.events.publisher import ZMQEventSink

        event_sink = ZMQEventSink(
            endpoint=config.kv_events_endpoint,
            pod=advertised,
        )
    if args.otlp_traces_endpoint or args.trace_file:
        from llmd_tpu.obs.tracing import configure_tracing

        configure_tracing(
            "llmd-engine",
            otlp_endpoint=args.otlp_traces_endpoint,
            trace_file=args.trace_file,
            sample_ratio=args.trace_sample_ratio,
        )
    t0 = time.monotonic()
    engine = LLMEngine(config, event_sink=event_sink)
    startup = {"model_load_s": round(time.monotonic() - t0, 3)}
    if multihost and not dist.is_leader():
        # Worker rank of a multi-host deployment: no HTTP frontend — mirror
        # the leader's device dispatches until it broadcasts shutdown (the
        # LWS worker role; the leader serves the API for the whole group).
        import jax

        logging.info(
            "multi-host worker %d/%d: entering follower loop",
            jax.process_index(), jax.process_count(),
        )
        engine.runner.follower_loop()
        return
    if args.lora_pool_slots:
        # Dynamic pool: --lora-adapters entries seed the runtime
        # registry (bare names register identity adapters until weights
        # load through the API); names resolve engine-side thereafter.
        for name, (_slot, path) in (adapter_specs or {}).items():
            if path:
                engine.load_adapter(name, path)
            else:
                engine.load_adapter(name, weights={})
            logging.info("registered LoRA adapter %r (source=%s)",
                         name, path or "<identity>")
        lora_adapters = None
    else:
        for name, (slot, path) in (adapter_specs or {}).items():
            if path:
                from llmd_tpu.models.loader import load_lora_adapter

                engine.set_lora_weights(
                    slot, load_lora_adapter(config.model, path)
                )
                logging.info("loaded LoRA adapter %r from %s into slot %d",
                             name, path, slot)
    if not args.skip_warmup:
        t0 = time.monotonic()
        n = engine.runner.warmup()
        startup["warmup_programs"] = n
        startup["warmup_s"] = round(time.monotonic() - t0, 3)
        logging.info(
            "warmup compiled %d programs in %.1f s", n, startup["warmup_s"]
        )
    startup["compile"] = compiles.snapshot()

    def runtime_report() -> dict:
        return {
            "device": device,
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "device_files": jaxrt.held_device_files(),
            "pallas_mode": os.environ.get("LLMD_PALLAS", "auto"),
            "kernel_plans": {
                op: sorted(plans)
                for op, plans in list(engine.runner.kernel_plans.items())
            },
            "traced_programs": [
                {"time": t, "family": family, "shape": list(shape)}
                for t, family, shape in list(engine.runner.traced_programs)
            ],
            "startup": startup,
            "compile": compiles.snapshot(),
            "compile_cache_dir": cache_dir,
            "peak_bytes_in_use": jaxrt.peak_bytes_in_use(),
        }

    tokenizer = load_tokenizer(config.tokenizer_path)
    app = build_app(
        AsyncEngine(engine),
        tokenizer,
        args.served_model_name or args.model,
        config.model.max_model_len,
        lora_adapters=lora_adapters,
        runtime_report=runtime_report,
        profile_dir=args.profile_dir,
    )

    async def _close_engine(app):
        # Unregisters the KV-store segment (peers stop being routed to a
        # dead address) and closes the transfer connector.
        engine.close()

    app.on_cleanup.append(_close_engine)
    web.run_app(app, host=args.host, port=args.port)


if __name__ == "__main__":
    main()
