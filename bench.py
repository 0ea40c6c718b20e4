"""Single-chip serving benchmark (driver contract).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.

Headline: offline continuous-batching decode of a Llama-3.2-3B-class model
(W8A8 INT8 weights — the TPU counterpart of the serving precision the
reference's headline path uses, FP8 DeepGEMM, docker/Dockerfile.cuda:69-70)
— batch 256, 128-token prompts, 64 output tokens, greedy, end-to-end
through LLMEngine (scheduler + paged KV + sampling), so host overhead
counts. (B rose 128 -> 256 in r4: int8's halved weight bytes leave
bandwidth headroom a larger batch converts to throughput; measured
ladder in bench_dense.) vs_baseline: ratio against the reference's
closest per-chip decode figure, ~1,600 output tok/s per decode GPU
(DeepSeek-R1 wide-EP on 32xH200, reference guides/wide-ep-lws/
README.md:271; see BASELINE.md). Different model/chip class — a
tracking ratio, not a like-for-like claim.

extras (north-star shapes, BASELINE.json):
  dense_bf16_tok_s — same workload, bf16 weights + bf16 KV (r01/r02
                    headline basis; keeps the precision trade visible).
  weight_stream_gbps — effective weight-stream bandwidth of the bf16 run
                    (iterations/s x weight bytes): the roofline context
                    for a flat bf16 number.
  kv_int8_tok_s_isl384_b128 / kv_bf16_tok_s_isl384_b96max — int8 KV
                    pool at long context: 2x pages per HBM byte serves
                    B=128 at ISL 384 where bf16 OOMs at compile; on this
                    KV-read-bound chip that capacity does NOT raise
                    tok/s (see bench_kv_int8_long_context for the
                    honest framing; the pool's throughput win is
                    pd_kvint8's wire TTFT).
  mla_moe_tok_s   — decode tok/s on a DeepSeek-V2-Lite-geometry MLA+MoE
                    model (depth cut to 8 to fit one chip's HBM), INT8
                    grouped-GEMM expert backend (the reference's FP8
                    DeepGEMM role). The architecture the 2.2k tok/s/chip
                    north star names.
  pd_ttft_p50_ms  — p50 time-to-first-token through the FULL P/D path
                    (client -> sidecar -> prefill engine -> kvship KV
                    transfer -> decode engine first token) on localhost,
                    against the < 200 ms north-star target.
  dispatch_rtt_ms — measured host->device dispatch round-trip. The P/D
                    path pays several dispatches plus two ~25 MB
                    HBM<->host stagings, so read pd_ttft_p50_ms relative
                    to this RTT (BENCH_r04 recorded 107.3 ms; on the
                    current machine chip_smoke.py measures it).
  roofline_int8 / roofline_bf16 — MFU and HBM-BW utilization context for
                    the raw tok/s headlines: config-derived FLOPs/token
                    and bytes/token against the chip's peak specs
                    (_roofline_extras; estimates, labeled as such).
  ragged_step     — flattened-token step (--ragged-qlens) CPU-sim part:
                    mixed-batch padded/live token ratio ragged vs
                    bucketed (target <= 0.15 vs multiples of it), with
                    byte-identical greedy AND seeded streams and the
                    window=1 shape-family counts.
  fault_degrade   — graceful-degradation CPU-sim part (fault-
                    tolerance.md): P/D throughput under a seeded 1%
                    kv.pull.drop FaultPlan vs the clean run (target
                    ratio >= 0.9, recorded), with the recompute
                    fallback proven engaged and streams byte-identical.
  fleet_soak      — fleet-scale chaos-soak CPU-sim part (fleet-soak.md):
                    the replica-kill + steady scenarios over the REAL
                    EPP/flow-control/breaker/autoscale stack on a
                    virtual-time loop at reduced scale — zero requests
                    lost to mid-stream crashes, bounded time-to-reroute,
                    breaker-open visible, byte-identical scoreboards
                    across two runs (the full >=10^4-QPS matrix runs in
                    the CI `soak` job).
  kv_federation   — cross-replica KV-federation CPU-sim part
                    (kv-federation.md): the kv_federation fleetsim
                    scenario federated vs cold (store tier disabled) on
                    the same trace — recompute_avoided_ratio (> 0, the
                    fleet-wide reuse headline), exact virtual-time
                    federated-vs-cold p50 TTFT ratio, byte-identical
                    scoreboards across two federated runs.
  stream_resume   — mid-stream failover CPU-sim part (fault-
                    tolerance.md stream continuation contract): the
                    replica_kill fleetsim scenario (store tier armed)
                    — kill-at-p50 resume TTFT vs the deterministic
                    cold-recompute cost, zero client-visible stream
                    failures, stitched streams byte-identical, plus
                    the router_soak leg driving the REAL aiohttp
                    router's resume path over loopback sockets.
  batch_backfill  — batch serving tier CPU-sim part
                    (batch-processing.md): the batch_backfill fleetsim
                    scenario batch-on vs no-batch on the same diurnal
                    interactive trace — batch tok/s harvested from
                    trough capacity, trough-utilization lift, backlog
                    drained, and the interactive p99 TTFT on/off ratio
                    (the zero-regression headline), byte-identical
                    scoreboards across two batch-on runs.
  lora_pool       — multi-tenant LoRA CPU-sim part
                    (multi-tenant-lora.md): a real-engine 2-slot paged
                    adapter pool under mixed-tenant churn vs a
                    single-adapter baseline (cold-load TTFT ratio,
                    eviction counts, resident-vs-cold byte parity
                    greedy+seeded), plus the lora_tenant fleetsim
                    scenario affinity-routed vs adapter-blind — the
                    exact virtual-time resident-hit-ratio lift.
  moe_ep          — wide-EP dispatch-path CPU-sim part (wide-ep.md):
                    the real moe_block_ep census on the 8-device
                    virtual mesh — hot-expert required capacity and
                    drops before vs after the real EPLB placement,
                    AdaptiveCapacity converging below static 2.0 at
                    zero drops (fewer padded slots, smaller a2a
                    payload), and the expert_skew fleetsim scenario's
                    EPLB-on-vs-identity comparison at reduced scale.
  moe_overlap     — microbatched overlapped expert dispatch on/off
                    step time on the virtual CPU mesh; byte-identity
                    gated in tests, flag default off, graduates on a
                    real-slice win (same contract as dbo).
  pd_stream       — layer-streamed disaggregated TTFT CPU-sim part
                    (kv-cache.md "layer-streamed import"): the full
                    sidecar two-phase P->D stack at a CPU-compilable
                    size — streamed local/cached p50 TTFT vs the
                    < 200 ms acceptance target, the v3 group-framed
                    wire's fetch->CRC->scatter pipeline with the
                    first-group admission seam (overlap ratio), a
                    monolithic (v2) wire comparison, and a per-stage
                    waterfall that provably sums to the measured TTFT.
"""

from __future__ import annotations

import asyncio
import json
import time

REFERENCE_PER_CHIP_TOKS = 1600.0  # wide-ep-lws/README.md:271


# Peak per-chip specs for the roofline context (dense matmul peak at
# the compute dtype, HBM bandwidth), keyed by a device_kind substring.
# Sources: public TPU spec sheets; the bench only needs the right order
# of magnitude to turn raw tok/s into MFU / BW-utilization context.
_CHIP_PEAKS = {
    # kind-substring: (bf16 FLOP/s, int8 OP/s, HBM bytes/s)
    "v5 lite": (197e12, 394e12, 819e9),
    "v5e": (197e12, 394e12, 819e9),
    "v5p": (459e12, 918e12, 2765e9),
    "v4": (275e12, 275e12, 1228e9),
    "v6e": (918e12, 1836e12, 1640e9),
    "v6 lite": (918e12, 1836e12, 1640e9),
}


def _roofline_extras(model, engine, tok_s, B, ISL, OSL, quantization):
    """MFU / HBM-BW context next to the raw tok/s headline (ROADMAP
    "Recent" debt): model FLOPs/token and bytes/token DERIVED FROM
    CONFIG — 2 x matmul params per token plus the attention score/value
    matmuls at the workload's mean context — against the chip's peak
    specs. Estimates, labeled as such: the point is knowing whether a
    headline sits at 2% or 40% of the chip, not a third decimal."""
    import jax

    matmul_params = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        engine.runner.params
    )[0]:
        name = str(path[-1])
        if "embed" in name or "_scale" in name or "norm" in name:
            continue
        matmul_params += leaf.size
    mean_ctx = ISL + OSL / 2
    cfg = model
    attn_flops = 4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim * mean_ctx
    flops_per_token = 2.0 * matmul_params + attn_flops
    wbytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(engine.runner.params)
    )
    kv_elt = 1 if engine.runner.kv_quantized else jax.numpy.dtype(
        engine.config.cache.dtype
    ).itemsize
    kv_read = (
        2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim
        * mean_ctx * kv_elt
    )
    # Decode streams the full weight set once per ITERATION (whole
    # batch), so per token it is wbytes / B; each token also reads its
    # own KV context.
    bytes_per_token = wbytes / B + kv_read
    kind = jax.devices()[0].device_kind.lower()
    peak = next(
        (v for sub, v in _CHIP_PEAKS.items() if sub in kind), None
    )
    out = {
        "flops_per_token": round(flops_per_token),
        "bytes_per_token": round(bytes_per_token),
        "device_kind": jax.devices()[0].device_kind,
        "note": (
            "config-derived estimates (2 x matmul params + attention at "
            "mean context); mfu against the dense matmul peak at the "
            "compute dtype, hbm_bw_util against the HBM spec ceiling"
        ),
    }
    if peak is not None:
        bf16_peak, int8_peak, hbm = peak
        compute_peak = int8_peak if quantization == "int8" else bf16_peak
        out["mfu"] = round(tok_s * flops_per_token / compute_peak, 4)
        out["hbm_bw_util"] = round(tok_s * bytes_per_token / hbm, 4)
    else:
        out["mfu"] = out["hbm_bw_util"] = None
    return out


def bench_dense(quantization: str | None = "int8", kv_dtype: str = "bfloat16"):
    import numpy as np

    import jax

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config

    # INT8 runs at B=256: halved weight bytes leave bandwidth headroom
    # that a LARGER batch converts to throughput (measured ladder r4,
    # same workload/chip: B=128 4,224 -> 192 4,626 -> 256 4,680-4,830
    # across runs -> 320 OOM). bf16 keeps the r1-r3 shape (B=128; its
    # weight stream already saturates, and B=256 bf16 KV+weights exceed
    # HBM).
    B = 256 if quantization == "int8" else 128
    ISL, OSL = 128, 64
    model = get_model_config(
        "llama-3.2-3b", max_model_len=512, quantization=quantization
    )
    # Tuned when the host-dispatch RTT was ~100 ms (BENCH_r04) and
    # dominated small steps, so the whole prefill rides ONE batched
    # dispatch (B*ISL tokens) and the whole decode ONE fused 64-step
    # window. Earlier ladder (B=128): dw=16/mbt=2048 997 tok/s ->
    # dw=32/4096 1209 -> dw=64/8192 1468 -> dw=64/16384 1777; page=32
    # measured worse (3,244) than page=16.
    # kv_dtype="int8": same HBM budget holds 2x the pages.
    cfg = EngineConfig(
        model=model,
        cache=CacheConfig(
            page_size=16,
            num_blocks=4096 if (kv_dtype == "int8" or B > 128) else 2048,
            dtype=kv_dtype,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=B, max_num_batched_tokens=B * ISL, decode_window=64
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=0,
    )
    engine = LLMEngine(cfg)
    rng = np.random.default_rng(0)
    sampling = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
    warm = [list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)]
    engine.generate(warm, sampling)

    prompts = [list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)]
    t0 = time.monotonic()
    out = engine.generate(prompts, sampling)
    dt = time.monotonic() - t0
    total_out = sum(len(v) for v in out.values())
    assert total_out == B * OSL, (total_out, B * OSL)
    # Roofline note: each decode iteration streams the full weight set
    # once for the whole batch, so effective weight-stream bandwidth
    # = iterations/s x weight bytes = (tok_s / B) x sum(param bytes).
    # Compare against the chip's effective HBM ceiling to see whether
    # the dense number is bandwidth-bound (BENCH_r04: 176.5 GB/s
    # effective through this path; not measured on the current machine).
    wbytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(engine.runner.params)
    )
    tok_s = total_out / dt
    stream_gbps = tok_s / B * wbytes / 1e9
    roofline = _roofline_extras(model, engine, tok_s, B, ISL, OSL, quantization)
    del engine
    return tok_s, stream_gbps, roofline


def bench_mla_moe():
    """DeepSeek-family decode: MLA latent KV + grouped-GEMM MoE experts."""
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config

    B, ISL, OSL = 128, 128, 64
    # V2-Lite geometry (MLA rank 512+64, 64 experts top-6, shared expert,
    # dense first layer) at depth 8: ~4B params fit one chip. INT8 experts
    # stream half the bytes through the grouped GEMM — the quantized-
    # serving shape the reference runs this architecture in (FP8 DeepGEMM).
    model = get_model_config(
        "deepseek-v2-lite", num_layers=8, max_model_len=512,
        quantization="int8",
    )
    cfg = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_blocks=2048, dtype="bfloat16"),
        scheduler=SchedulerConfig(
            max_num_seqs=B, max_num_batched_tokens=16384, decode_window=64
        ),
        parallel=ParallelConfig(tensor_parallel_size=1, moe_backend="grouped"),
        seed=0,
    )
    engine = LLMEngine(cfg)
    rng = np.random.default_rng(1)
    sampling = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
    warm = [list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)]
    engine.generate(warm, sampling)

    prompts = [list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)]
    t0 = time.monotonic()
    out = engine.generate(prompts, sampling)
    dt = time.monotonic() - t0
    total_out = sum(len(v) for v in out.values())
    assert total_out == B * OSL, (total_out, B * OSL)
    del engine
    return total_out / dt


def bench_kv_int8_long_context():
    """The int8 KV pool at long context (ISL 384 of a 512 window),
    honestly framed. CAPACITY: B=128 needs 3,584 pages — the bf16 pool
    cannot fit that next to the weights on this chip (compile-time OOM);
    the int8 pool serves it. THROUGHPUT (r5 rework, measured stage by
    stage): the r4 deficit was the SCALE WRITE path, not the kernel or
    the scale gather — the per-(token,head) scale scatter enumerated
    T*K eight-byte updates (scatter cost is per-update, and a
    const-scales probe showed kernel + gather are within noise of the
    bf16 path). Prefill now scatters [K,2] windows per token and decode
    rewrites whole [K,page,2] slabs; with that, decode at capacity
    B=128 runs 0.192 ms/seq/tok vs bf16's 0.196 at its feasible B=96.
    Residual at EQUAL B=96: ~10% — the quantize/dequant work an int8
    pool inherently pays, which short-ISL prefill can't amortize. The
    pool's wins: capacity (B=128 serves at all), long-OSL decode, and
    the wire (pd_kvint8 ships pool bytes directly — half bytes, zero
    quantize work). Reference precedent: FP8 KV on the flagship path
    (Dockerfile.cuda:69-70)."""
    return {
        "kv_int8_tok_s_isl384_b128": _bench_long_ctx("int8", 128, 4096),
        # xfail-style regression note (r6 hunt over the captured r04
        # deficit, 1,518 vs bf16's 1,845 on its home turf): the r5 scale-
        # WRITE fix above addressed the largest stage, but the captured
        # record predates it (BENCH_r05 died rc=124) so the deficit
        # stands un-requalified. Remaining ranked suspects, from reading
        # the decode attention's int8-only work: (1) the per-layer scale
        # GATHER+RELAYOUT plane ([B, K, 2, max_pages*page]) scales with
        # the TABLE width, not the live context — r6 halves it by
        # shipping f16 scales (lossless: pool scales live on the f16
        # grid; ragged_paged_attention.py) — and (2) the inherent
        # per-block dequant multiplies on the [K, G, S] score plane,
        # which equal-B parity (~10%) already prices. Requalify on the
        # next captured chip run; if the f16-plane halving doesn't close
        # it, the residual is (2) and the pool's honest wins stay
        # capacity + wire bytes, not same-B throughput.
        "kv_int8_note": (
            "captured 0.82x vs bf16 predates the r5 scale-write fix and "
            "the r6 f16 scale-plane halving; expected to close or "
            "attribute to inherent dequant cost on requalification"
        ),
    }


def bench_kv_bf16_long_context():
    return {
        "kv_bf16_tok_s_isl384_b96max": _bench_long_ctx("bfloat16", 96, 2816)
    }


def _bench_long_ctx(kv_dtype: str, B: int, blocks: int) -> float:
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config

    ISL, OSL = 384, 64
    model = get_model_config(
        "llama-3.2-3b", max_model_len=512, quantization="int8"
    )
    cfg = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_blocks=blocks, dtype=kv_dtype),
        scheduler=SchedulerConfig(
            # One-shot prefill (B x ISL in a single dispatch) — the same
            # dispatch-RTT-amortizing philosophy as the headline config.
            max_num_seqs=B, max_num_batched_tokens=B * ISL, decode_window=64
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=0,
    )
    engine = LLMEngine(cfg)
    rng = np.random.default_rng(0)
    sp = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
    engine.generate(
        [list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)],
        sp,
    )
    prompts = [
        list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)
    ]
    t0 = time.monotonic()
    res = engine.generate(prompts, sp)
    dt = time.monotonic() - t0
    assert sum(len(v) for v in res.values()) == B * OSL
    del engine
    return round(B * OSL / dt, 1)


def bench_swa_ring(ring: bool):
    """SWA ring pool (--kv-swa-ring; the reference's hybrid KV cache
    manager role, pd patch-decode.yaml:19) on a gpt-oss-geometry proxy.

    Two claims, measured separately because they have different honest
    substrates: (1) tok/s ring-on vs ring-off on the SAME e2e workload —
    the ring changes memory layout, not attention work (the window-skip
    already avoids out-of-window reads either way). Measured on this
    proxy: ~203-207 off vs ~185 on (reproducible ~10% overhead: two-pool
    scan carries + the per-dispatch ring-view table). (2) per-sequence
    KV bytes AT max_model_len — exact geometry math, where the ring's
    win lives (sliding layers hold R pages instead of ctx/page): at the
    real gpt-oss-20b shape (24 layers alternating at window 128, ctx
    131072) the ratio is 0.508 — 6.0 -> 3.05 GB/seq. Like the int8
    pool, the flag buys CAPACITY (2x the concurrent long sequences per
    HBM byte), not single-batch speed.

    The on/off runs live in SEPARATE bench parts (subprocesses): two
    engines in one process RESOURCE_EXHAUST the chip (lagging
    arena reclaim between engine lifetimes — same reason main() runs
    every part in a subprocess)."""
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        swa_ring_spec,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config

    B, ISL, OSL = 32, 1024, 64
    # Depth 4 + vocab 32768 + 8-row prefill dispatches: the 32-expert
    # layers cost ~0.8G/layer int8 and the MoE prefill temps ~0.25M/token,
    # so deeper/wider proxies RESOURCE_EXHAUST this 16G chip.
    proxy = get_model_config(
        "gpt-oss-20b", num_layers=4,
        layer_types=tuple(
            "sliding_attention" if i % 2 == 0 else "full_attention"
            for i in range(4)
        ),
        max_model_len=8192, quantization="int8", vocab_size=32768,
    )

    def run(ring: bool):
        cfg = EngineConfig(
            model=proxy,
            cache=CacheConfig(
                page_size=16, num_blocks=2304, dtype="bfloat16",
                swa_ring=ring,
                # Ring-on force-disables prefix caching; the off run must
                # match or its per-page hashing slows it and the A/B
                # conflates two effects.
                enable_prefix_caching=False,
            ),
            scheduler=SchedulerConfig(
                max_num_seqs=B, max_num_batched_tokens=8 * ISL,
                decode_window=64,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
        )
        engine = LLMEngine(cfg)
        rng = np.random.default_rng(2)
        sp = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
        mk = lambda: [  # noqa: E731
            list(rng.integers(1, proxy.vocab_size, size=ISL)) for _ in range(B)
        ]
        engine.generate(mk(), sp)
        t0 = time.monotonic()
        out = engine.generate(mk(), sp)
        dt = time.monotonic() - t0
        assert sum(len(v) for v in out.values()) == B * OSL
        del engine
        return round(B * OSL / dt, 1)

    if not ring:
        return {"swa_off_tok_s": run(False)}

    # Exact per-seq KV bytes at max context, real gpt-oss-20b geometry.
    model = get_model_config("gpt-oss-20b")
    cache = CacheConfig(page_size=16, swa_ring=True)
    sched = SchedulerConfig(max_num_seqs=1, max_num_batched_tokens=2048)
    spec = swa_ring_spec(model, cache, sched)
    page_bytes = (
        model.kv_cache_heads * cache.page_size * model.kv_cache_entry_dim * 2
    )
    pages_full_len = model.max_model_len // cache.page_size
    per_seq_off = pages_full_len * model.num_layers * page_bytes
    per_seq_on = (
        pages_full_len * len(spec.full_layers)
        + spec.ring_pages * len(spec.swa_layers)
    ) * page_bytes
    return {
        "swa_on_tok_s": run(True),
        "gpt_oss_20b_kv_per_seq_at_131k_gb": round(per_seq_off / 2**30, 2),
        "gpt_oss_20b_kv_per_seq_ring_gb": round(per_seq_on / 2**30, 2),
        "kv_per_seq_ratio": round(per_seq_on / per_seq_off, 3),
    }


async def _bench_pd_ttft(
    transfer_dtype: str = "auto",
    kv_dtype: str = "bfloat16",
    local_fastpath: bool = False,
    cached_repeat: bool = False,
    stream_groups: int | None = None,
    model_cfg=None,
    isl: int = 512,
    n_requests: int = 12,
    page_size: int = 16,
    num_blocks: int = 512,
):
    """p50 TTFT through sidecar two-phase P->D with a real KV transfer.

    transfer_dtype="int8" measures the opt-in quantized transfer encoding
    (half the staging bytes — the dominant cost in BENCH_r04's stages).
    kv_dtype="int8" runs int8 POOLS on both sides: the q8 wire form ships
    the pool bytes directly (half bytes AND no quantize work).
    local_fastpath=False keeps the WIRE path honest even though both
    bench engines share this process (the default-on fast path would
    claim device snapshots directly); the pd_local part measures it on.
    cached_repeat=True measures the byte-diet warm case: every request
    repeats ONE prompt, so from request 2 on the decode cache holds the
    full prefix and the probe makes the producer stage nothing.
    stream_groups pins the v3 layer-group stream width (None = engine
    default, 1 = the monolithic v2 wire — the streamed-vs-monolithic
    comparison leg); model_cfg/isl/... let the CPU-sim pd_stream part
    reuse this harness at a CPU-compilable size.

    Returns (p50_ms, stages) where ``stages`` includes the per-stage
    WATERFALL of the last measured request: consecutive monotonic
    milestone differences (request start -> fetch start -> first group
    -> fetch done -> apply done -> first token) that telescope, so they
    provably sum to that request's measured TTFT within clock epsilon.
    """
    import numpy as np
    from aiohttp import ClientSession
    from aiohttp.test_utils import TestServer

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config
    from llmd_tpu.serve.api import build_app
    from llmd_tpu.serve.async_engine import AsyncEngine
    from llmd_tpu.serve.tokenizer import ByteTokenizer
    from llmd_tpu.sidecar.proxy import SidecarConfig, build_sidecar_app

    ISL, N = isl, n_requests
    model = model_cfg or get_model_config(
        "llama-3.2-3b", num_layers=12, max_model_len=1024
    )

    def make_engine(role):
        return LLMEngine(EngineConfig(
            model=model,
            cache=CacheConfig(
                page_size=page_size, num_blocks=num_blocks, dtype=kv_dtype
            ),
            scheduler=SchedulerConfig(
                max_num_seqs=8, max_num_batched_tokens=1024, decode_window=1
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            kv_role=role,
            kv_transfer_port=0,
            kv_transfer_dtype=transfer_dtype,
            kv_local_fastpath=local_fastpath,
            **(
                {} if stream_groups is None
                else {"kv_stream_groups": stream_groups}
            ),
        ))

    prefill = make_engine("kv_producer")
    decode = make_engine("kv_consumer")
    rng = np.random.default_rng(2)
    # Warm every program shape each side needs (prefill bucket + 1-token
    # decode + the P side's 1-token generation) so TTFT measures serving,
    # not compilation.
    warm_sp = SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True)
    for eng in (prefill, decode):
        eng.generate(
            [list(rng.integers(1, 255, size=ISL)) for _ in range(2)], warm_sp
        )

    prefill_srv = TestServer(
        build_app(AsyncEngine(prefill), ByteTokenizer(), "bench", 1024)
    )
    decode_srv = TestServer(
        build_app(AsyncEngine(decode), ByteTokenizer(), "bench", 1024)
    )
    await prefill_srv.start_server()
    await decode_srv.start_server()
    sidecar_srv = TestServer(
        build_sidecar_app(SidecarConfig(vllm_port=decode_srv.port), rank=0)
    )
    await sidecar_srv.start_server()

    ttfts = []
    last_t0 = last_first = None
    try:
        async with ClientSession() as session:
            fixed = "".join(chr(c) for c in rng.integers(97, 122, size=ISL))
            for i in range(N + 2):  # first two are HTTP/connection warmup
                prompt = fixed if cached_repeat else "".join(
                    chr(c) for c in rng.integers(97, 122, size=ISL)
                )
                t0 = time.monotonic()
                async with session.post(
                    f"http://{sidecar_srv.host}:{sidecar_srv.port}/v1/completions",
                    json={
                        "prompt": prompt, "max_tokens": 4,
                        "temperature": 0.0, "stream": True,
                    },
                    headers={
                        "x-prefiller-host-port":
                            f"{prefill_srv.host}:{prefill_srv.port}"
                    },
                ) as resp:
                    assert resp.status == 200, await resp.text()
                    async for line in resp.content:
                        if line.startswith(b"data:") and b"[DONE]" not in line:
                            if i >= 2:
                                ttfts.append(time.monotonic() - t0)
                                last_t0, last_first = (
                                    t0, time.monotonic()
                                )
                            break
                    async for _ in resp.content:
                        pass
    finally:
        for srv in (sidecar_srv, decode_srv, prefill_srv):
            await srv.close()
        for eng in (prefill, decode):
            if eng.kv_connector:
                eng.kv_connector.close()
    assert prefill.kv_connector.exported_requests >= N
    ttfts.sort()
    p_stats = prefill.kv_connector.stats()
    d_stats = decode.kv_connector.stats()
    if transfer_dtype == "adaptive":
        # The decision inputs + outcome: measured staging throughput per
        # ORIGINAL byte for each encoding on THIS link, and which one
        # the producer converged to.
        stages = {
            "enc_rate_exact_mbps": p_stats["enc_rate_exact_mbps"],
            "enc_rate_q8_mbps": p_stats["enc_rate_q8_mbps"],
            "picked": (
                "q8"
                if p_stats["enc_rate_q8_mbps"] > p_stats["enc_rate_exact_mbps"]
                else "exact"
            ),
        }
        return ttfts[len(ttfts) // 2] * 1e3, stages
    # Per-stage budget of the last transfer (the pipelined path: the
    # producer responds after prefill compute; its HBM->host staging
    # overlaps the consumer's pull-wait + device uploads, so fetch_ms
    # ~= the one staging leg that remains on the critical path).
    stages = {
        "producer_stage_ms": p_stats["last_stage_ms"],
        "consumer_fetch_ms": d_stats["last_fetch_ms"],
        "consumer_apply_ms": d_stats["last_apply_ms"],
        # Layer-streamed import: how long the decode side waited before
        # becoming schedulable (group 0 resident) on each side's clock.
        "producer_first_group_ms": p_stats["last_first_group_ms"],
        "consumer_first_group_ms": d_stats["last_first_group_ms"],
        "stream_groups_cells": d_stats["stream_groups_total"],
    }
    # The WATERFALL of the last measured request: consecutive segments
    # of one monotonic timeline (request start -> fetch start -> first
    # group -> fetch done -> apply done -> first token). Telescoping
    # differences, so sum(waterfall) == measured TTFT up to the two
    # clock reads bracketing the HTTP write (epsilon, asserted by the
    # CI summary check on the CPU-sim part).
    tl = dict(decode.kv_connector.last_timeline)
    if last_t0 is not None and tl.get("fetch_start"):
        fs = tl["fetch_start"]
        fg = tl.get("first_group", tl.get("fetch_done", fs))
        fd = tl.get("fetch_done", fg)
        ad = tl.get("apply_done", fd)
        ttft_ms = (last_first - last_t0) * 1e3
        waterfall = {
            # sidecar probe + phase-1 prefill + HTTP until the consumer
            # fetch starts
            "phase1_ms": round((fs - last_t0) * 1e3, 3),
            # admission gate: wire/claim until group 0 resident
            "first_group_ms": round((fg - fs) * 1e3, 3),
            # remaining groups streaming while the request is parked/
            # scheduled — the OVERLAPPED leg
            "stream_rest_ms": round((fd - fg) * 1e3, 3),
            # stream resolution -> hash-chain commit at a step boundary
            "apply_ms": round((ad - fd) * 1e3, 3),
            # tail prefill + first decode token
            "decode_ms": round((last_first - ad) * 1e3, 3),
        }
        stages["waterfall"] = waterfall
        stages["waterfall_total_ms"] = round(
            sum(waterfall.values()), 3
        )
        stages["last_ttft_ms"] = round(ttft_ms, 3)
        span = fd - fs
        stages["overlap_ratio"] = round(
            (fd - fg) / span, 3
        ) if span > 0 else 0.0
    return ttfts[len(ttfts) // 2] * 1e3, stages


def bench_env_probes() -> dict:
    """Environment controls for the P/D wire numbers.

    The wire TTFT rides three links whose run-to-run variance is
    otherwise indistinguishable from a code regression: raw TCP
    loopback (the shipper's socket path), device->host staging (the
    producer's download leg), and host->device staging (the consumer's
    upload leg). Recording all three lets round-over-round wire numbers
    be normalized against the substrate they ran on."""
    import socket
    import threading

    import numpy as np

    out = {}
    # --- raw TCP loopback ---
    total = 256 << 20
    srv = socket.create_server(("127.0.0.1", 0))
    got = threading.Event()

    def sink():
        conn, _ = srv.accept()
        n = 0
        while n < total:
            b = conn.recv(1 << 20)
            if not b:
                break
            n += len(b)
        conn.close()
        got.set()

    threading.Thread(target=sink, daemon=True).start()
    c = socket.create_connection(("127.0.0.1", srv.getsockname()[1]))
    buf = b"\0" * (8 << 20)
    t0 = time.monotonic()
    for _ in range(total // len(buf)):
        c.sendall(buf)
    if got.wait(timeout=60):
        out["loopback_gbps"] = round(
            total / (time.monotonic() - t0) / 2**30, 2
        )
    else:
        # A wedged sink must not record a plausible-but-wrong number —
        # the probe exists to DISAMBIGUATE environment vs regression.
        out["loopback_error"] = "sink did not drain within 60s"
    c.close()
    srv.close()

    # --- device<->host staging ---
    import jax
    import jax.numpy as jnp

    x = np.zeros((16 << 20) // 4, np.float32)  # 16 MB
    # The download probe must fetch DEVICE-COMPUTED data: a device_put
    # array keeps a host mirror and device_get short-circuits to memcpy
    # speed, reporting fantasy bandwidth.
    make = jax.jit(lambda s: jnp.full(x.shape, 1.0, jnp.float32) * s)
    h2d, d2h = [], []
    for i in range(3):
        t0 = time.monotonic()
        jax.device_put(x).block_until_ready()
        h2d.append(time.monotonic() - t0)
        d = make(float(i))
        d.block_until_ready()
        t0 = time.monotonic()
        np.asarray(jax.device_get(d))
        d2h.append(time.monotonic() - t0)
    out["host_to_device_gbps"] = round(x.nbytes / sorted(h2d)[1] / 2**30, 3)
    out["device_to_host_gbps"] = round(x.nbytes / sorted(d2h)[1] / 2**30, 3)
    return out


def bench_predictor_real() -> dict:
    """Latency-predictor accuracy against MEASURED engine timings.

    The r4 number was circular: trained and evaluated on the synthetic
    generator whose functional form the features share (VERDICT r4 weak
    7). Here a real engine serves a mixed trace (bursty arrivals, varied
    ISL, some repeated prompts for prefix hits) on this chip; each
    request's submission-time stats snapshot is the feature vector and
    its measured first-token latency the label; evaluation is
    prequential (predict-then-observe). Reference bar: ~5% MAPE against
    real served traffic (latency-predictor.md:58); with a long dispatch
    round trip the floor is far higher — first tokens land on step
    boundaries and a burst completes in one batched prefill, so
    feature-identical requests get different TTFTs (and vice versa).
    The mean is outlier-skewed; the median is the stabler read. The
    point of this part is that the number is no longer circular."""
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config
    from llmd_tpu.predictor.model import LatencyPredictor, ttft_features

    model = get_model_config("llama-3.2-3b", num_layers=4, max_model_len=512)
    engine = LLMEngine(EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_blocks=1024, dtype="bfloat16"),
        scheduler=SchedulerConfig(
            max_num_seqs=16, max_num_batched_tokens=2048, decode_window=4
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=0,
    ))
    rng = np.random.default_rng(7)
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    # Warm the step shapes so compiles don't pollute the labels.
    engine.generate(
        [list(rng.integers(1, 255, size=s)) for s in (64, 384)], sp
    )

    N = 480
    repeat_pool = [
        list(rng.integers(1, 255, size=int(s)))
        for s in rng.integers(64, 384, size=8)
    ]
    submitted = 0
    inflight_tokens = 0
    pending: dict[str, tuple[float, list, int]] = {}
    samples: list[tuple[list, float]] = []
    while submitted < N or engine.has_work():
        # Bursty arrivals up to 1.5x the batch width: real queueing
        # delays (multiple scheduler rounds) so TTFT's dynamic range is
        # feature-driven, not dominated by one-step dispatch noise.
        if submitted < N and engine.scheduler.num_waiting < 8:
            for _ in range(int(rng.integers(1, 25))):
                if submitted >= N:
                    break
                if rng.random() < 0.25:
                    prompt = repeat_pool[int(rng.integers(len(repeat_pool)))]
                    prefix = 1.0
                else:
                    prompt = list(
                        rng.integers(1, 255, size=int(rng.integers(32, 500)))
                    )
                    prefix = 0.0
                # LIVE scheduler/allocator state, not engine.stats: the
                # stats gauges refresh at step end, so every request in
                # a burst would see identical stale queue features.
                feats = ttft_features(
                    engine.allocator.usage(),
                    engine.scheduler.num_waiting,
                    engine.scheduler.num_running,
                    len(prompt), prefix, inflight_tokens,
                )
                rid = engine.add_request(prompt, sp)
                pending[rid] = (time.monotonic(), feats, len(prompt) + 8)
                inflight_tokens += len(prompt) + 8
                submitted += 1
        for out in engine.step():
            entry = pending.get(out.request_id)
            if entry is None:
                continue
            t0, feats, toks = entry
            if feats is not None and out.new_token_ids:
                samples.append((feats, (time.monotonic() - t0) * 1e3))
                # Sampled, but the request stays pending until finished
                # so inflight_tokens bookkeeping balances.
                pending[out.request_id] = (t0, None, toks)
            if out.finished:
                del pending[out.request_id]
                inflight_tokens -= toks
    del engine
    # Prequential (predict-THEN-observe) evaluation after a warmup: the
    # honest analog of the reference's continuously retraining sidecar
    # (latency-predictor.md:20-41) — every prediction uses only the
    # past, and the trainer has seen recent traffic, exactly as in
    # deployment. A frozen 70/30 temporal split was tried first and
    # measures mostly bucket-coverage drift (most predictions fall to
    # the heuristic), which is not how the sidecar runs.
    pred = LatencyPredictor()
    warm = len(samples) // 4
    errs = []
    sources: dict[str, int] = {}
    for i, (feats, ttft) in enumerate(samples):
        if i >= warm:
            p, src = pred.predict_ttft(feats)
            sources[src] = sources.get(src, 0) + 1
            errs.append(abs(p - ttft) / max(ttft, 1e-6))
        pred.observe_ttft(feats, ttft)
    return {
        "predictor_ttft_mape": round(float(np.mean(errs)), 4),
        "predictor_ttft_median_ape": round(float(np.median(errs)), 4),
        "n_warmup": warm,
        "n_eval": len(errs),
        "pred_sources": sources,
        "substrate": (
            "real engine trace, prequential eval "
            "(bursty, mixed ISL, prefix hits)"
        ),
    }


def measure_dispatch_rtt_ms() -> float:
    """Median round-trip of a trivial compiled dispatch + host fetch.

    The fetch is a real device_get, so the result crosses to the host
    inside the timed region."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    np.asarray(jax.device_get(f(x)))
    samples = []
    for _ in range(5):
        t0 = time.monotonic()
        np.asarray(jax.device_get(f(x)))
        samples.append(time.monotonic() - t0)
    samples.sort()
    return samples[len(samples) // 2] * 1e3


def _run_part(part: str):
    """One sub-benchmark (dispatched in a SUBPROCESS by main: engines do
    not share a device arena — a fragmented/lagging reclaim from one
    bench must not RESOURCE_EXHAUST the next on the chip)."""
    if part == "dense_int8":
        tok_s, _, roofline = bench_dense("int8", kv_dtype="bfloat16")
        return {"tok_s": round(tok_s, 1), "roofline": roofline}
    if part == "kv_int8_long":
        return bench_kv_int8_long_context()
    if part == "kv_bf16_long":
        return bench_kv_bf16_long_context()
    if part == "dense_bf16":
        tok_s, stream, roofline = bench_dense(None, kv_dtype="bfloat16")
        return {
            "dense_bf16_tok_s": round(tok_s, 1),
            "weight_stream_gbps": round(stream, 1),
            "roofline_bf16": roofline,
        }
    if part == "mla_moe":
        return round(bench_mla_moe(), 1)
    if part == "pd":
        p50, stages = asyncio.run(_bench_pd_ttft())
        return {"pd_ttft_p50_ms": round(p50, 1), "pd_stages": stages}
    if part == "pd_int8":
        # Same configuration as the r03 number under this key: bf16
        # pools + the opt-in int8 TRANSFER encoding (comparable
        # round-over-round; also keeps the float-pool q8 wire measured).
        p50, stages = asyncio.run(_bench_pd_ttft(transfer_dtype="int8"))
        return {"pd_ttft_p50_int8_ms": round(p50, 1), "pd_int8_stages": stages}
    if part == "pd_kvint8":
        # Int8 POOLS both sides: q8 wire ships pool bytes directly.
        p50, stages = asyncio.run(_bench_pd_ttft(kv_dtype="int8"))
        return {
            "pd_ttft_p50_kvint8_ms": round(p50, 1),
            "pd_kvint8_stages": stages,
        }
    if part == "pd_local":
        # Single-host xPyD device fast path (reference single-host/pd
        # shape): consumer claims the producer's device snapshots — no
        # host staging, no wire.
        p50, stages = asyncio.run(_bench_pd_ttft(local_fastpath=True))
        return {
            "pd_ttft_p50_local_ms": round(p50, 1),
            "pd_local_stages": stages,
        }
    if part == "pd_cached":
        # Byte-diet warm case: repeated prompt -> probe makes the
        # producer stage nothing; near-zero transfer.
        p50, stages = asyncio.run(_bench_pd_ttft(cached_repeat=True))
        return {
            "pd_ttft_p50_cached_ms": round(p50, 1),
            "pd_cached_stages": stages,
        }
    if part == "pd_adaptive":
        # transfer_dtype="adaptive": the producer measures both wire
        # encodings on this link and converges to the faster (VERDICT r4
        # item 8 — r3 and r4 measured OPPOSITE winners on one link,
        # so the right encoding is a link property, not a config).
        p50, stages = asyncio.run(_bench_pd_ttft(transfer_dtype="adaptive"))
        return {
            "pd_ttft_p50_adaptive_ms": round(p50, 1),
            "pd_adaptive": stages,
        }
    if part == "env":
        return bench_env_probes()
    if part == "swa_ring_off":
        return bench_swa_ring(False)
    if part == "swa_ring_on":
        return bench_swa_ring(True)
    if part == "rtt":
        return round(measure_dispatch_rtt_ms(), 1)
    if part == "predictor":
        # Real-engine trace (the honest number); the synthetic eval
        # stays as a generator-consistency check in the extras.
        from llmd_tpu.predictor.synth import run_accuracy_eval

        out = bench_predictor_real()
        out["predictor_synth_mape"] = round(
            run_accuracy_eval()["ttft_mape"], 4
        )
        return out
    if part == "dbo":
        return _bench_dbo_delta()
    if part == "moe_ep":
        return _bench_moe_ep()
    if part == "moe_overlap":
        return _bench_moe_overlap()
    if part == "async_step":
        return bench_async_step()
    if part == "spec_decode":
        return bench_spec_decode()
    if part == "unified_step":
        return bench_unified_step()
    if part == "ragged_step":
        return bench_ragged_step()
    if part == "fault_degrade":
        return bench_fault_degrade()
    if part == "fleet_soak":
        return bench_fleet_soak()
    if part == "kv_federation":
        return bench_kv_federation()
    if part == "stream_resume":
        return bench_stream_resume()
    if part == "batch_backfill":
        return bench_batch_backfill()
    if part == "lora_pool":
        return bench_lora_pool()
    if part == "pd_stream":
        return bench_pd_stream()
    if part == "long_context":
        return bench_long_context()
    raise KeyError(part)


def bench_pd_stream():
    """Sub-200 ms disaggregated TTFT, CPU-sim part (kv-cache.md
    "layer-streamed import"): the FULL sidecar two-phase P->D stack —
    HTTP proxy, two engines, kvship wire, prefix-cache probe — at a
    CPU-compilable model size, measuring the v3 group-streamed import
    end to end.

    Four legs: streamed local-fastpath (the single-host xPyD shape),
    streamed byte-diet cached repeat, streamed WIRE (group cells over
    TCP loopback with the fetch->CRC->scatter pipeline + first-group
    admission), and the monolithic (stream_groups=1, v2 wire)
    local-fastpath comparison. The local/cached p50s are the < 200 ms
    acceptance record; the waterfall is consecutive monotonic segments
    of the last wire request's timeline, so it provably sums to that
    request's TTFT within clock epsilon — both asserted by the CI
    summary check."""
    import asyncio

    import jax

    jax.config.update("jax_platforms", "cpu")

    from llmd_tpu.config import tiny_model_config

    model = tiny_model_config(num_layers=8, max_model_len=128)
    kw = dict(
        model_cfg=model, isl=96, n_requests=8, page_size=8,
        num_blocks=256,
    )
    local_p50, local_stages = asyncio.run(
        _bench_pd_ttft(local_fastpath=True, **kw)
    )
    cached_p50, cached_stages = asyncio.run(
        _bench_pd_ttft(cached_repeat=True, **kw)
    )
    wire_p50, wire_stages = asyncio.run(_bench_pd_ttft(**kw))
    mono_p50, _mono_stages = asyncio.run(
        _bench_pd_ttft(stream_groups=1, **kw)
    )
    waterfall = wire_stages.get("waterfall", {})
    total = wire_stages.get("waterfall_total_ms", 0.0)
    last = wire_stages.get("last_ttft_ms", 0.0)
    return {
        "substrate": (
            "cpu-sim (tiny geometry; the pd_local/pd_cached chip parts "
            "carry the device-staging numbers)"
        ),
        # The acceptance record: streamed local-fastpath and byte-diet
        # cached p50 TTFT through the full sidecar path.
        "pd_ttft_p50_local_ms": round(local_p50, 1),
        "pd_ttft_p50_cached_ms": round(cached_p50, 1),
        "target_200ms_met": bool(local_p50 < 200 and cached_p50 < 200),
        # The wire pipeline: group cells streamed over TCP loopback.
        "pd_ttft_p50_wire_ms": round(wire_p50, 1),
        "streamed_cells": wire_stages.get("stream_groups_cells", 0),
        "first_group_ms": wire_stages.get("consumer_first_group_ms", 0.0),
        # Fraction of the wire-import window the request was already
        # admitted/schedulable for (first-group admission seam).
        "overlap_ratio": wire_stages.get("overlap_ratio", 0.0),
        # Monolithic (v2, stream_groups=1) WIRE comparison — the leg the
        # stage/ship/fetch pipeline is built for (the local fast path is
        # already device-copy-bound either way).
        "pd_ttft_p50_wire_mono_ms": round(mono_p50, 1),
        "stream_vs_mono_ratio": round(wire_p50 / max(mono_p50, 1e-9), 3),
        # The per-stage waterfall: telescoping segments of ONE request's
        # monotonic timeline — sums to its TTFT within epsilon.
        "waterfall": waterfall,
        "waterfall_total_ms": total,
        "waterfall_ttft_ms": last,
        "waterfall_sums_to_ttft": bool(
            last > 0 and abs(total - last) <= max(5.0, 0.05 * last)
        ),
        "cached_stages": {
            k: v for k, v in cached_stages.items()
            if not isinstance(v, dict)
        },
    }


def bench_fleet_soak():
    """Fleet-scale chaos-soak CPU-sim part (fleet-soak.md): the
    replica-kill and steady scenarios from the seeded matrix at reduced
    scale (~2k QPS, the full >=10^4-QPS matrix runs in the CI `soak`
    job), recording the fleet-level recovery scoreboard headline: zero
    requests lost to the mid-stream crashes, bounded time-to-reroute,
    breaker-open visible, p99 TTFT/TPOT bands — and the determinism
    contract, proven by running the chaos scenario TWICE and comparing
    scoreboard bytes. No chip, no jax: the simulator drives the real
    EPP/flow-control/breaker/predictor/autoscale code on a virtual-time
    event loop, so ~2 s of fleet time costs ~1 s of wall clock."""
    from llmd_tpu.fleetsim.scenarios import SCENARIOS
    from llmd_tpu.fleetsim.scoreboard import to_canonical_json

    scale = 0.2
    t0 = time.monotonic()
    kill_a = SCENARIOS["replica_kill"].build(0, scale).run()
    kill_wall_s = time.monotonic() - t0
    kill_b = SCENARIOS["replica_kill"].build(0, scale).run()
    steady = SCENARIOS["steady"].build(0, scale).run()
    return {
        "qps_scale": scale,
        "deterministic": (
            to_canonical_json(kill_a) == to_canonical_json(kill_b)
        ),
        "zero_lost": (
            kill_a["requests"]["lost"] == 0
            and kill_a["requests"]["hung"] == 0
        ),
        "invariants_ok": bool(kill_a["ok"] and steady["ok"]),
        "replica_kill": {
            "requests": kill_a["trace"]["requests"],
            "offered_qps": round(kill_a["trace"]["offered_qps"], 1),
            "kills": len(kill_a["reroute"]["kills"]),
            "breaker_trips": kill_a["breaker"]["trips_total"],
            "time_to_reroute_s": round(
                kill_a["reroute"]["time_to_reroute_s"], 4
            ),
            "p99_ttft_ms": round(kill_a["latency_ms"]["ttft"]["p99"], 2),
            "stream_interrupted": kill_a["requests"]["outcomes"].get(
                "stream-interrupted", 0
            ),
            "wall_s": round(kill_wall_s, 2),
        },
        "steady": {
            "requests": steady["trace"]["requests"],
            "offered_qps": round(steady["trace"]["offered_qps"], 1),
            "p99_ttft_ms": round(steady["latency_ms"]["ttft"]["p99"], 2),
            "p99_tpot_ms": round(steady["latency_ms"]["tpot"]["p99"], 2),
            "jain_fairness": round(
                steady["fairness"]["jain_completed"], 4
            ),
        },
    }


def bench_kv_federation():
    """Cross-replica KV-federation CPU-sim part (kv-federation.md): the
    kv_federation fleetsim scenario — overlapping-tenant shared
    prefixes, tight per-replica caches, seeded store-leg pull drops —
    run FEDERATED (simulated store tier armed) and COLD (store
    disabled, every shared prefix re-prefills), on the same trace and
    seed. Virtual time is deterministic, so the TTFT comparison is
    exact, not wall-clock noise: the headline is the fraction of
    offered shared-prefix tokens the store erased
    (recompute_avoided_ratio) and the federated-vs-cold p50 TTFT
    ratio. Determinism is proven by running the federated leg twice
    and comparing scoreboard bytes."""
    from llmd_tpu.fleetsim.scenarios import build_kv_federation
    from llmd_tpu.fleetsim.scoreboard import to_canonical_json

    scale = 0.5
    seed = 0
    fed_sim = build_kv_federation(seed, scale, store=True)
    offered_prefix_tokens = sum(r.prefix_tokens for r in fed_sim.trace)
    fed = fed_sim.run()
    fed_b = build_kv_federation(seed, scale, store=True).run()
    cold = build_kv_federation(seed, scale, store=False).run()
    kf = fed["kv_federation"]
    avoided = kf["recompute_avoided_tokens"]
    return {
        "qps_scale": scale,
        "deterministic": (
            to_canonical_json(fed) == to_canonical_json(fed_b)
        ),
        "invariants_ok": bool(fed["ok"] and cold["ok"]),
        "zero_lost": (
            fed["requests"]["lost"] == 0 and cold["requests"]["lost"] == 0
        ),
        "offered_prefix_tokens": offered_prefix_tokens,
        "recompute_avoided_tokens": avoided,
        # the summary-check headline: > 0 means fleet-wide reuse is real
        "recompute_avoided_ratio": round(
            avoided / max(1, offered_prefix_tokens), 4
        ),
        "store": kf["store"],
        "store_published": kf["store_published"],
        "store_hits": kf["store_hits"],
        "local_prefix_hits": kf["local_prefix_hits"],
        "dropped_pulls": kf["store"]["dropped_pulls"],
        "p50_ttft_ms": {
            "federated": round(fed["latency_ms"]["ttft"]["p50"], 2),
            "cold": round(cold["latency_ms"]["ttft"]["p50"], 2),
        },
        # deterministic virtual time: federated prefill must be cheaper
        "ttft_ratio_fed_vs_cold": round(
            fed["latency_ms"]["ttft"]["p50"]
            / max(1e-9, cold["latency_ms"]["ttft"]["p50"]), 4
        ),
    }


def bench_stream_resume():
    """Mid-stream failover CPU-sim part (fault-tolerance.md, stream
    continuation contract): the replica_kill fleetsim scenario — two
    replicas crashed mid-stream with the federation store tier armed —
    at reduced scale. Virtual time is deterministic, so the headline
    comparison is exact: p50 TTFT of resumed legs (store fetch of the
    replayed prefix + tail prefill) vs the deterministic cost of
    recomputing prompt + delivered history cold. Gates: resumes > 0,
    ZERO client-visible stream failures, stitched streams byte-identical
    to the uninterrupted expectation (parity), determinism across two
    runs — plus a router_soak leg driving the REAL epp/server.py aiohttp
    router's proxy/resume path over loopback sockets on the virtual
    loop (content gates only; real I/O is not byte-compared)."""
    from llmd_tpu.fleetsim.scenarios import SCENARIOS
    from llmd_tpu.fleetsim.scoreboard import to_canonical_json

    scale = 0.25
    t0 = time.monotonic()
    a = SCENARIOS["replica_kill"].build(0, scale).run()
    kill_wall_s = time.monotonic() - t0
    b = SCENARIOS["replica_kill"].build(0, scale).run()
    sc = a["stream_continuation"]
    router = SCENARIOS["router_soak"].build(0, 1.0).run()
    rsc = router["stream_continuation"]
    return {
        "qps_scale": scale,
        "deterministic": to_canonical_json(a) == to_canonical_json(b),
        "invariants_ok": bool(a["ok"] and router["ok"]),
        "zero_lost": (
            a["requests"]["lost"] == 0 and a["requests"]["hung"] == 0
        ),
        "kills": len(a["reroute"]["kills"]),
        "mid_stream_failures": sc["mid_stream_failures"],
        "resumes": sc["resumes"],
        "resume_replayed_tokens": sc["resume_replayed_tokens"],
        # THE acceptance gates: nothing client-visible, streams whole.
        "client_visible_stream_failures": (
            sc["interrupted"]
            + a["requests"]["outcomes"].get("stream-corrupt", 0)
        ),
        "parity_failures": sc["parity_failures"],
        # kill-at-p50 headline: resume TTFT must be store-fetch-bound,
        # not recompute-bound.
        "resume_ttft_p50_ms": round(sc["resume_ttft_p50_ms"], 3),
        "cold_recompute_ttft_p50_ms": round(
            sc["cold_recompute_ttft_p50_ms"], 3
        ),
        "resume_vs_cold_ratio": round(
            sc["resume_ttft_p50_ms"]
            / max(1e-9, sc["cold_recompute_ttft_p50_ms"]), 4
        ),
        "wall_s": round(kill_wall_s, 2),
        # The REAL router leg: the production proxy detected the cuts,
        # fed the breaker, and replayed the history end to end.
        "router_soak": {
            "requests": router["trace"]["requests"],
            "kills": len(router["reroute"]["kills"]),
            "mid_stream_failures": rsc["mid_stream_failures"],
            "resumes": rsc["resumes"],
            "parity_failures": rsc["parity_failures"],
            "client_visible_stream_failures": rsc["interrupted"],
            "invariants_ok": bool(router["ok"]),
        },
    }


def bench_long_context():
    """Million-token context tier CPU-sim part (long-context.md).

    ENGINE leg — a real LLMEngine on the 8-device virtual CPU mesh:
    TTFT at growing context lengths for cp=1 vs cp=2 ring prefill (a
    warm-up prompt per bucket excludes compile; CPU wall-clock is
    recorded as context, the GATES are structural — ring steps > 0 and
    greedy-token parity), plus resident-KV-bytes-per-seq with the
    decode-time pager on vs off over the same long decode (the pager
    leg must spill and stay bounded near window + horizon while the
    off leg's residency tracks full context).

    FLEET leg — the long_context fleetsim scenario at reduced scale,
    cp on vs off on the same seeded trace: virtual time is
    deterministic, so the document-TTFT compression is exact (~the cp
    degree), with the chat-p99-through-the-wave and kv-peak-bounded
    gates riding along."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8".strip()
        )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig,
        EngineConfig,
        OffloadConfig,
        ParallelConfig,
        SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine.engine import LLMEngine
    from llmd_tpu.engine.request import SamplingParams

    rng = np.random.default_rng(0)

    def make(cp=0, window=0, paging=False):
        dp = cp if cp else 1
        return LLMEngine(EngineConfig(
            model=tiny_model_config(max_model_len=512, sliding_window=window),
            cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=8, max_num_batched_tokens=256,
            ),
            parallel=ParallelConfig(
                tensor_parallel_size=1, data_parallel_size=dp,
                cp_prefill=cp if cp else 1, cp_prefill_min_tokens=16,
            ),
            offload=OffloadConfig(
                enabled=True, cpu_chunks=512, decode_paging=True,
                pager_horizon_tokens=8,
            ) if paging else None,
            seed=0,
        ))

    # --- TTFT vs context length, cp=1 vs cp=2 (ring prefill) ---------- #
    one_tok = SamplingParams(temperature=0.0, max_tokens=1)
    ctx_lengths = (128, 256)
    ttft_ms: dict = {}
    tokens: dict = {}
    ring_steps = 0
    for cp in (0, 2):
        eng = make(cp=cp)
        rows = {}
        for ctx in ctx_lengths:
            # Warm-up compiles this Q bucket; the timed prompt differs
            # in content so the prefix cache cannot skip the prefill.
            warm = list(rng.integers(0, 256, size=ctx))
            eng.generate([warm], one_tok)
            timed = list(
                np.random.default_rng(ctx).integers(0, 256, size=ctx)
            )
            t0 = time.monotonic()
            out = eng.generate([timed], one_tok)
            rows[str(ctx)] = round((time.monotonic() - t0) * 1e3, 2)
            tokens.setdefault(str(ctx), {})[f"cp{cp or 1}"] = (
                list(out.values())[0]
            )
        ttft_ms[f"cp{cp or 1}"] = rows
        if cp:
            ring_steps = eng.runner.cp_ring_steps_total
    parity = all(
        tokens[str(ctx)]["cp1"] == tokens[str(ctx)]["cp2"]
        for ctx in ctx_lengths
    )

    # --- resident KV bytes per sequence, pager on vs off -------------- #
    prompt = list(rng.integers(0, 256, size=48))
    decode = SamplingParams(temperature=0.0, max_tokens=40)
    page_bytes = None
    resident: dict = {}
    for paging in (False, True):
        eng = make(window=8, paging=paging)
        if page_bytes is None:
            page_bytes = int(eng.runner.gather_pages([0]).nbytes)
        rid = eng.add_request(prompt, decode)
        peak_pages = 0
        for _ in range(200):
            if not eng.has_work():
                break
            eng.step()
            for req in eng.scheduler.running:
                if req.request_id == rid:
                    peak_pages = max(
                        peak_pages,
                        len(req.block_ids) - len(getattr(
                            req, "paged_out", {},
                        )),
                    )
        key = "pager_on" if paging else "pager_off"
        resident[key] = {
            "peak_resident_pages": peak_pages,
            "peak_resident_kv_bytes": peak_pages * page_bytes,
        }
        if paging:
            resident[key]["kv_paged_out_bytes"] = int(
                eng.pager.paged_out_bytes
            )

    # --- the fleet leg: exact virtual-time document-TTFT scaling ------ #
    from llmd_tpu.fleetsim.scenarios import build_long_context

    scale = 0.25
    on = build_long_context(0, scale).run()
    off = build_long_context(0, scale, cp=False).run()
    doc_on = on["per_tenant"]["docs"]["p99_ttft_ms"]
    doc_off = off["per_tenant"]["docs"]["p99_ttft_ms"]
    return {
        "engine": {
            "ttft_ms": ttft_ms,
            "cp_ring_steps": ring_steps,
            "cp_token_parity": parity,
            "page_bytes": page_bytes,
            "resident_kv": resident,
        },
        "fleet": {
            "qps_scale": scale,
            "cp_degree": on["long_context"]["cp_degree"],
            "doc_ttft_p99_ms_cp": round(doc_on, 1),
            "doc_ttft_p99_ms_mono": round(doc_off, 1),
            # THE headline: ring prefill compresses document TTFT by
            # ~the cp degree, exactly, in virtual time.
            "doc_ttft_speedup": round(doc_off / max(doc_on, 1e-9), 2),
            "chat_p99_ttft_ms": round(max(
                v["p99_ttft_ms"]
                for t, v in on["per_tenant"].items() if t != "docs"
            ), 2),
            "kv_paged_out_tokens": on["long_context"]["kv_paged_out_tokens"],
            "peak_kv_tokens": on["long_context"]["peak_kv_tokens"],
            "kv_capacity_tokens": on["long_context"]["kv_capacity_tokens"],
            "invariants_ok": bool(on["ok"] and off["ok"]),
        },
    }


def bench_batch_backfill():
    """Batch serving tier CPU-sim part (batch-processing.md): the
    batch_backfill fleetsim scenario run BATCH-ON (standing offline
    queue at BATCH_PRIORITY riding the real flow-control band, the
    production chain's batch-saturation-filter, and the replicas'
    backfill path, with the WVA flooring the fleet on the backlog) and
    NO-BATCH (same diurnal interactive trace, utilization sampler
    armed) — virtual time, so the comparison is exact. Headlines: batch
    tok/s harvested from trough capacity, the trough-utilization lift
    over the no-batch baseline, backlog drained to zero, and the
    interactive p99 TTFT on/off ratio — the zero-interactive-regression
    bar the CI summary asserts. Determinism proven by running the
    batch-on leg twice and comparing scoreboard bytes."""
    from llmd_tpu.fleetsim.scenarios import build_batch_backfill
    from llmd_tpu.fleetsim.scoreboard import to_canonical_json

    scale = 0.5
    seed = 0
    t0 = time.monotonic()
    on = build_batch_backfill(seed, scale, batch=True).run()
    wall_s = time.monotonic() - t0
    on_b = build_batch_backfill(seed, scale, batch=True).run()
    off = build_batch_backfill(seed, scale, batch=False).run()
    bt = on["batch"]
    # Harvested-token rate over the window the jobs actually drained in
    # (virtual seconds — the deterministic "batch tok/s" headline).
    drain_span = max(bt["last_drain_t"], 1e-9)
    p99_on = on["latency_ms"]["ttft"]["p99"]
    p99_off = off["latency_ms"]["ttft"]["p99"]
    return {
        "qps_scale": scale,
        "deterministic": (
            to_canonical_json(on) == to_canonical_json(on_b)
        ),
        "invariants_ok": bool(on["ok"] and off["ok"]),
        "zero_lost": (
            on["requests"]["lost"] == 0 and on["requests"]["hung"] == 0
        ),
        "jobs": bt["enqueued"],
        "backlog_drained": bt["outstanding"] == 0 and bt["hung"] == 0,
        "backlog_monotone": bt["backlog_monotone_after_peak"],
        "watermark_retries": bt["retries"],
        "harvested_tokens": bt["harvested_tokens"],
        "batch_tok_s_harvested": round(
            bt["harvested_tokens"] / drain_span, 1
        ),
        "trough_utilization": {
            "batch_on": round(
                on["utilization"]["trough_utilization"], 4
            ),
            "no_batch": round(
                off["utilization"]["trough_utilization"], 4
            ),
        },
        "interactive_p99_ttft_ms": {
            "batch_on": round(p99_on, 2),
            "no_batch": round(p99_off, 2),
        },
        # the summary-check headline: backfill must cost interactive
        # latency nothing (ratio ~1.0 in exact virtual time)
        "p99_ratio_on_vs_off": round(p99_on / max(1e-9, p99_off), 4),
        "wall_s": round(wall_s, 2),
    }


def bench_lora_pool():
    """Multi-tenant LoRA CPU-sim part (multi-tenant-lora.md): two legs.

    ENGINE leg — a real engine with a 2-slot paged adapter pool over a
    6-tenant registry serves a mixed-tenant round-robin workload (every
    request a different tenant: worst-case churn) vs the same request
    count on ONE adapter (all-resident baseline); headline is the
    throughput ratio and the cold-vs-resident first-request latency
    ratio (both recorded, not asserted — CPU wall clock is noisy),
    plus the cold-load/eviction counts and resident-vs-cold byte
    parity (greedy + seeded) — the CI summary check asserts those.

    FLEET leg — the lora_tenant fleetsim scenario (192 Zipf tenants,
    32-slot pools) run affinity-routed vs adapter-blind on the same
    trace; virtual time, so the resident-hit-ratio lift and cold-stall
    comparison are exact. Determinism proven by running the affinity
    leg twice and comparing scoreboard bytes."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    N_REQ, ISL, OSL, TENANTS = 18, 16, 8, 6

    def make_engine():
        return LLMEngine(EngineConfig(
            model=tiny_model_config(
                name="tiny-lora", num_lora_adapters=2, lora_rank=4,
                lora_dynamic=True,
            ),
            cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=8, max_num_batched_tokens=64
            ),
            seed=0,
        ))

    def adapter_weights(engine, seed):
        layers = engine.runner.params["layers"]
        rng = np.random.default_rng(seed)
        return {
            k: rng.normal(
                0.0, 0.5, (layers[k].shape[0], *layers[k].shape[2:])
            ).astype(np.float32)
            for k in ("la_q", "lb_q", "la_v", "lb_v")
        }

    names = [f"tenant-{i}" for i in range(TENANTS)]

    def run_one(eng, name, seed=None, prompt=None):
        rid = eng.add_request(
            prompt or list(range(2, 2 + ISL)),
            SamplingParams(
                temperature=0.0 if seed is None else 0.8,
                max_tokens=OSL, ignore_eos=True, seed=seed,
            ),
            lora_name=name,
        )
        outs = []
        while eng.has_work():
            for out in eng.step():
                if out.request_id == rid:
                    outs.extend(out.new_token_ids)
        return outs

    def leg(mixed: bool) -> dict:
        eng = make_engine()
        for i, n in enumerate(names):
            eng.load_adapter(n, weights=adapter_weights(eng, 100 + i))
        run_one(eng, names[0])  # warm the step shapes off the clock
        t0 = time.monotonic()
        for i in range(N_REQ):
            run_one(eng, names[i % TENANTS] if mixed else names[0])
        dt = time.monotonic() - t0
        pc = eng.adapter_pool.counters()
        return {"tok_s": N_REQ * OSL / dt, **pc}

    single = leg(mixed=False)
    mixed = leg(mixed=True)

    # Cold-vs-resident TTFT ratio + byte parity: engine A serves the
    # adapter resident; engine B must first evict it, then cold-load it
    # back for the timed request. Same weights, byte-identical streams.
    streams = {}
    lat = {}
    for mode in ("resident", "cold"):
        eng = make_engine()
        eng.load_adapter("x", weights=adapter_weights(eng, 7))
        run_one(eng, "x")  # warm shapes + make x resident
        if mode == "cold":
            eng.load_adapter("y", weights=adapter_weights(eng, 8))
            eng.load_adapter("z", weights=adapter_weights(eng, 9))
            run_one(eng, "y")
            run_one(eng, "z")
            assert eng.adapter_pool.slot_of("x") is None
        t0 = time.monotonic()
        greedy = run_one(eng, "x", prompt=list(range(3, 3 + ISL)))
        lat[mode] = time.monotonic() - t0
        seeded = run_one(eng, "x", seed=1234, prompt=list(range(3, 3 + ISL)))
        streams[mode] = (greedy, seeded)

    from llmd_tpu.fleetsim.scenarios import build_lora_tenant
    from llmd_tpu.fleetsim.scoreboard import to_canonical_json

    scale = 0.5
    aff = build_lora_tenant(0, scale, affinity=True).run()
    aff_b = build_lora_tenant(0, scale, affinity=True).run()
    blind = build_lora_tenant(0, scale, affinity=False).run()
    return {
        "engine": {
            "tenants": TENANTS,
            "pool_slots": 2,
            "single_adapter_tok_s": round(single["tok_s"], 1),
            "mixed_tenant_tok_s": round(mixed["tok_s"], 1),
            # worst-case churn cost (recorded; CPU wall clock is noisy)
            "mixed_vs_single_ratio": round(
                mixed["tok_s"] / max(single["tok_s"], 1e-9), 3
            ),
            "cold_loads": mixed["cold_loads"],
            "evictions": mixed["evictions"],
            "cold_ttft_ms": round(lat["cold"] * 1e3, 1),
            "resident_ttft_ms": round(lat["resident"] * 1e3, 1),
            "cold_ttft_ratio": round(
                lat["cold"] / max(lat["resident"], 1e-9), 3
            ),
            # THE parity bar: resident and cold-loaded streams are
            # byte-identical, greedy and seeded.
            "outputs_identical": streams["resident"] == streams["cold"],
        },
        "fleet": {
            "qps_scale": scale,
            "deterministic": (
                to_canonical_json(aff) == to_canonical_json(aff_b)
            ),
            "invariants_ok": bool(aff["ok"] and blind["ok"]),
            "zero_lost": (
                aff["requests"]["lost"] == 0
                and aff["requests"]["hung"] == 0
            ),
            "adapters": aff["lora"]["adapters"],
            "affinity_hit_ratio": round(aff["lora"]["hit_ratio"], 4),
            "blind_hit_ratio": round(blind["lora"]["hit_ratio"], 4),
            # exact virtual-time lift of residency-aware routing
            "hit_ratio_lift": round(
                aff["lora"]["hit_ratio"]
                / max(blind["lora"]["hit_ratio"], 1e-9), 4
            ),
            "cold_loads": aff["lora"]["cold_loads"],
            "evictions": aff["lora"]["evictions"],
            "pinned_evictions": aff["lora"]["pinned_evictions"],
            "cold_stall_p50_ms": round(
                aff["lora"]["cold_stall_p50_ms"], 2
            ),
        },
    }


def bench_fault_degrade():
    """Graceful-degradation CPU-sim part (fault-tolerance.md): P/D
    engine pair serving a stream of unique prompts, once clean and once
    under a seeded 1%-kv.pull.drop FaultPlan (plus one guaranteed drop,
    so the recompute path provably engages even at small N). Dropped
    pulls degrade to local recompute — correct but slower — and the
    headline is the throughput RATIO under faults vs clean: the
    target is >= 0.9 (degradation must cost single-digit percent at a
    1% drop rate, not collapse the consumer). Streams are asserted
    byte-identical per prompt across the two legs: degradation is
    TRANSPARENT, not just survivable."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from llmd_tpu import faults
    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    N, ISL, OSL = 24, 18, 8
    model = tiny_model_config()

    def make_engine(kv_role):
        return LLMEngine(EngineConfig(
            model=model,
            cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=8, max_num_batched_tokens=64
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
            kv_role=kv_role,
            kv_transfer_port=0,
            kv_local_fastpath=False,  # the faults live on the wire path
        ))

    # Unique prompts so every request really pulls (a shared prefix
    # would let the consumer's cache absorb the drops for free).
    prompts = [
        [((i * 7 + j) % (model.vocab_size - 2)) + 2 for j in range(ISL)]
        for i in range(N)
    ]

    def run_one(eng, prompt, max_tokens, kv_params=None):
        rid = eng.add_request(
            list(prompt),
            SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
            kv_transfer_params=kv_params,
        )
        outs, final = [], None
        while eng.has_work():
            for out in eng.step():
                if out.request_id == rid:
                    outs.extend(out.new_token_ids)
                    if out.finished:
                        final = out
        return outs, final

    def leg(armed: bool) -> dict:
        producer = make_engine("kv_producer")
        consumer = make_engine("kv_consumer")
        try:
            if armed:
                faults.arm(faults.FaultPlan([
                    faults.FaultSpec(
                        site="kv.pull.drop", p=0.01, times=None
                    ),
                    faults.FaultSpec(site="kv.pull.drop", times=1),
                ], seed=7))
            else:
                faults.disarm()
            # warm both engines' step shapes off the clock
            run_one(producer, prompts[0], 1)
            run_one(consumer, prompts[0], 2)
            toks = 0
            streams = []
            t0 = time.monotonic()
            for prompt in prompts:
                _, pre = run_one(
                    producer, prompt, 1,
                    kv_params={"do_remote_decode": True},
                )
                outs, _ = run_one(
                    consumer, prompt, OSL, kv_params=pre.kv_transfer_params
                )
                toks += len(outs)
                streams.append(outs)
            dt = time.monotonic() - t0
            return {
                "tok_s": toks / dt,
                "streams": streams,
                "recompute_fallbacks":
                    consumer.kv_connector.recompute_fallbacks,
                "drops": faults.injected_counts().get("kv.pull.drop", 0),
            }
        finally:
            faults.disarm()
            producer.kv_connector.close()
            consumer.kv_connector.close()

    clean = leg(False)
    faulty = leg(True)
    ratio = faulty["tok_s"] / max(clean["tok_s"], 1e-9)
    return {
        "clean_tok_s": round(clean["tok_s"], 1),
        "faulty_tok_s": round(faulty["tok_s"], 1),
        # The headline: throughput under a 1% pull-drop plan relative
        # to the clean run (target >= 0.9; CPU-sim wall clock is noisy,
        # so the target is recorded, not hard-asserted here).
        "degrade_ratio": round(ratio, 3),
        "target_met": ratio >= 0.9,
        "drops_injected": faulty["drops"],
        "recompute_fallbacks": faulty["recompute_fallbacks"],
        # Degradation transparency: byte-identical greedy streams.
        "outputs_identical": clean["streams"] == faulty["streams"],
        "requests": N,
    }


def bench_ragged_step():
    """Flattened-token step (SchedulerConfig.ragged_qlens) CPU-sim
    microbench: the same rolling mixed prefill+decode workload as
    bench_unified_step, ragged on vs off in LOCKSTEP — same arrivals,
    same scheduler decisions, byte-identical greedy AND seeded streams
    asserted. The headline is the MIXED-BATCH PADDED/LIVE TOKEN RATIO:
    the bucketed unified program pads every decode row to the chunk
    sub-row Q bucket (so a mixed step pays rows x Q_bucket compute for
    sum-of-real-tokens work), while the flat stream pads only to the
    16-token T granule — expect <= 0.15 for the flat path against
    multiples of it for the bucketed one. Wall-clock on the CPU sim is
    NOT the transferable number (the tiny model is compute-bound either
    way); the padding ratio is, because pad lanes ride through every
    layer of the real model too."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    SEQS, BUDGET, ISL, OSL, N = 8, 96, 64, 24, 20
    model = tiny_model_config(max_model_len=256)

    def make_engine(ragged: bool) -> LLMEngine:
        cfg = EngineConfig(
            model=model,
            cache=CacheConfig(page_size=4, num_blocks=512, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=SEQS, max_num_batched_tokens=BUDGET,
                unified_step=True, ragged_qlens=ragged,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
        )
        return LLMEngine(cfg)

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(N)
    ]
    # Half greedy, half seeded: BOTH stream classes must be
    # byte-identical across the ragged switch (unseeded hot sampling is
    # reproducible within a mode only, the standing contract).
    sps = [
        SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
        if i % 2 == 0 else
        SamplingParams(
            temperature=0.8, max_tokens=OSL, seed=100 + i, ignore_eos=True
        )
        for i in range(N)
    ]
    engines = {False: make_engine(False), True: make_engine(True)}
    for eng in engines.values():  # warm the step shapes
        eng.generate(
            [list(p) for p in prompts[:SEQS]], [sps[i] for i in range(SEQS)]
        )
    for eng in engines.values():
        st = eng.stats
        st.live_tokens_total = eng.runner.live_tokens_total = 0
        st.padded_tokens_total = eng.runner.padded_tokens_total = 0
        st.step_dispatches_total = 0
        st.engine_steps_total = 0
        st.generation_tokens = 0
    outs: dict[bool, dict[str, list[int]]] = {False: {}, True: {}}
    # Per-step (live, padded) deltas, lockstep across engines: step t of
    # one IS step t of the other, so the mixed-step filter below selects
    # the same steps on both sides.
    deltas: dict[bool, list[tuple[int, int]]] = {False: [], True: []}
    submitted = SEQS
    for eng in engines.values():
        for i in range(SEQS):
            eng.add_request(list(prompts[i]), sps[i])
    while any(eng.has_work() for eng in engines.values()):
        finished = 0
        for ragged, eng in engines.items():
            r = eng.runner
            before = (r.live_tokens_total, r.padded_tokens_total)
            for out in eng.step():
                outs[ragged].setdefault(out.request_id, []).extend(
                    out.new_token_ids
                )
                finished += int(out.finished)
            deltas[ragged].append((
                r.live_tokens_total - before[0],
                r.padded_tokens_total - before[1],
            ))
        for _ in range(min(finished // 2, N - submitted)):
            for eng in engines.values():
                eng.add_request(list(prompts[submitted]), sps[submitted])
            submitted += 1
    streams = {
        u: [outs[u][k] for k in sorted(outs[u])] for u in (False, True)
    }
    identical = streams[False] == streams[True]

    def ratio(ragged: bool, steps) -> float:
        live = sum(deltas[ragged][i][0] for i in steps)
        padded = sum(deltas[ragged][i][1] for i in steps)
        return round(padded / max(live, 1), 4)

    # Mixed steps: more live tokens than a pure-decode step could carry
    # (every decode row contributes at most 1 + spec_k; spec is off
    # here, so > SEQS live tokens means prefill chunks were aboard).
    n = min(len(deltas[False]), len(deltas[True]))
    mixed = [i for i in range(n) if deltas[False][i][0] > SEQS]
    mixed_ratio = {
        "bucketed": ratio(False, mixed), "ragged": ratio(True, mixed)
    }
    overall_ratio = {
        "bucketed": ratio(False, range(n)), "ragged": ratio(True, range(n))
    }
    return {
        "mixed_steps": len(mixed),
        "steps": n,
        # THE acceptance numbers: flat strictly below bucketed, and at
        # or under the 0.15 waste target on mixed batches.
        "mixed_padding_ratio": mixed_ratio,
        "overall_padding_ratio": overall_ratio,
        "padding_bound_ok": bool(
            mixed_ratio["ragged"] < mixed_ratio["bucketed"]
            and mixed_ratio["ragged"] <= 0.15
        ),
        "outputs_identical": identical,
        "dispatches_per_step": {
            ragged: round(
                engines[ragged].stats.step_dispatches_total
                / max(engines[ragged].stats.engine_steps_total, 1), 4
            )
            for ragged in (False, True)
        },
        "window1_shape_families": {
            ragged: engines[ragged].runner.window1_shape_families()
            for ragged in (False, True)
        },
        "substrate": (
            "tiny model on CPU (compute-bound): padding ratios, "
            "outputs_identical and the shape-family counts are the "
            "transferable numbers — pad lanes ride through every layer "
            "of the real model too"
        ),
    }


def bench_unified_step():
    """Unified single-dispatch engine step (SchedulerConfig.unified_step)
    CPU-sim microbench: a rolling mixed prefill+decode workload (chunked
    prompts arriving while a decode pool runs, so nearly every step
    carries both prefill chunks and decode rows), unified on vs off in
    LOCKSTEP — same arrivals, same scheduler decisions, byte-identical
    outputs asserted. The headline is the MIXED-STEP DISPATCH RATIO:
    device programs dispatched on mixed steps, unified / split (expect
    <= 0.6 — the split engine launches a prefill program AND a decode
    program, plus one lockstep opcode broadcast each on multi-host,
    where the unified engine launches one). Also records overall
    dispatches/step and the mean per-step host gap. On a remote-dispatch
    TPU runtime each saved dispatch is a saved host round-trip; the CPU
    sim is compute-bound, so wall-clock here understates the win."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    SEQS, BUDGET, ISL, OSL, N = 6, 24, 48, 24, 18
    model = tiny_model_config(max_model_len=128)

    def make_engine(unified: bool) -> LLMEngine:
        cfg = EngineConfig(
            model=model,
            cache=CacheConfig(page_size=4, num_blocks=256, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=SEQS, max_num_batched_tokens=BUDGET,
                unified_step=unified,
                # Pin the BUCKETED unified program: ragged_qlens defaults
                # on and would silently swap _OP_FLAT in — that family
                # has its own part (bench_ragged_step); this one must
                # keep covering _OP_UNIFIED, still the live path for MLA
                # models and --no-ragged-qlens.
                ragged_qlens=False,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
        )
        return LLMEngine(cfg)

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(N)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
    engines = {False: make_engine(False), True: make_engine(True)}
    for eng in engines.values():  # warm the step shapes (incl. unified)
        eng.generate([list(p) for p in prompts[:SEQS]], sp)
    for eng in engines.values():
        st = eng.stats
        st.step_dispatches_total = 0
        st.engine_steps_total = 0
        st.unified_steps_total = 0
        st.step_host_gap_ms_total = 0.0
        st.generation_tokens = 0
    # LOCKSTEP drive: both engines see the identical arrival schedule
    # (initial pool + one fresh prompt per finish), so step t of one IS
    # step t of the other and per-step dispatch deltas compare directly.
    outs: dict[bool, dict[str, list[int]]] = {False: {}, True: {}}
    deltas: dict[bool, list[int]] = {False: [], True: []}
    submitted = SEQS
    for eng in engines.values():
        for p in prompts[:SEQS]:
            eng.add_request(list(p), sp)
    wall: dict[bool, float] = {False: 0.0, True: 0.0}
    while any(eng.has_work() for eng in engines.values()):
        finished = 0
        for unified, eng in engines.items():
            before = eng.stats.step_dispatches_total
            t = time.monotonic()
            for out in eng.step():
                outs[unified].setdefault(out.request_id, []).extend(
                    out.new_token_ids
                )
                finished += int(out.finished)
            wall[unified] += time.monotonic() - t
            deltas[unified].append(eng.stats.step_dispatches_total - before)
        # One fresh arrival per finished request (arrivals mirrored to
        # both engines keep the drive lockstep); /2 because both engines
        # finish the same request on the same step.
        for _ in range(min(finished // 2, N - submitted)):
            for eng in engines.values():
                eng.add_request(list(prompts[submitted]), sp)
            submitted += 1
    streams = {
        u: [outs[u][k] for k in sorted(outs[u])] for u in (False, True)
    }
    identical = streams[False] == streams[True]
    # Mixed steps: the steps where the SPLIT engine needed >1 program.
    mixed = [i for i, d in enumerate(deltas[False]) if d > 1]
    mixed_split = sum(deltas[False][i] for i in mixed)
    mixed_uni = sum(deltas[True][i] for i in mixed if i < len(deltas[True]))

    def summarize(unified: bool) -> dict:
        st = engines[unified].stats
        return {
            "dispatches_per_step": round(
                st.step_dispatches_total / max(st.engine_steps_total, 1), 4
            ),
            "host_gap_ms_mean": round(
                st.step_host_gap_ms_total / max(st.engine_steps_total, 1), 3
            ),
            "steps": st.engine_steps_total,
            "tok_s": round(st.generation_tokens / max(wall[unified], 1e-9), 1),
            **(
                {"unified_steps": st.unified_steps_total} if unified else {}
            ),
        }

    return {
        "split": summarize(False),
        "unified": summarize(True),
        "mixed_steps": len(mixed),
        # THE acceptance number: device programs on mixed steps,
        # unified / split (expect <= 0.6).
        "mixed_dispatch_ratio": round(mixed_uni / max(mixed_split, 1), 3),
        "outputs_identical": identical,
        "substrate": (
            "tiny model on CPU (compute-bound): mixed_dispatch_ratio and "
            "outputs_identical are the transferable numbers — on an "
            "RTT-dominated TPU runtime each saved dispatch is a saved "
            "host round-trip"
        ),
    }


def bench_async_step():
    """The pipelined step against the synchronous one (reached through
    LLMEngine's private ``_synchronous_step``, the parity tests' seam): host-gap
    microbench on the CPU substrate (chip-free: the host gap is a HOST
    property — schedule + page-table build + array prep + assembly — so
    the hidden-vs-exposed comparison carries; absolute tok/s here is a
    tiny-model artifact). Same decode-heavy workload, async off vs on:
    records tok/s, the mean per-step host gap (step_host_gap_ms_total /
    engine_steps_total — un-overlapped host time, exposed every step in
    sync mode, shrunk to the reconcile/patch sliver in async mode), and
    the late-finish rollback count (docs/architecture/
    async-scheduling.md)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    B, ISL, OSL = 16, 64, 48
    model = tiny_model_config(max_model_len=256)

    def run(async_mode: bool) -> dict:
        cfg = EngineConfig(
            model=model,
            cache=CacheConfig(page_size=16, num_blocks=512, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=B, max_num_batched_tokens=B * ISL,
                decode_window=1,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
        )
        engine = LLMEngine(cfg, _synchronous_step=not async_mode)
        rng = np.random.default_rng(0)
        sp = SamplingParams(temperature=0.0, max_tokens=OSL, ignore_eos=True)
        mk = lambda: [  # noqa: E731
            list(rng.integers(1, model.vocab_size, size=ISL)) for _ in range(B)
        ]
        engine.generate(mk(), sp)  # warm the step shapes
        engine.stats.step_host_gap_ms_total = 0.0
        engine.stats.engine_steps_total = 0
        engine.stats.async_rollbacks_total = 0
        t0 = time.monotonic()
        out = engine.generate(mk(), sp)
        dt = time.monotonic() - t0
        total = sum(len(v) for v in out.values())
        assert total == B * OSL, (total, B * OSL)
        st = engine.stats
        res = {
            "tok_s": round(total / dt, 1),
            "host_gap_ms_mean": round(
                st.step_host_gap_ms_total / max(st.engine_steps_total, 1), 3
            ),
            "steps": st.engine_steps_total,
        }
        if async_mode:
            res["rollbacks"] = st.async_rollbacks_total
        return res

    off, on = run(False), run(True)
    return {
        "async_off": off,
        "async_on": on,
        "host_gap_hidden_ratio": round(
            1.0 - on["host_gap_ms_mean"] / max(off["host_gap_ms_mean"], 1e-9),
            3,
        ),
        "substrate": (
            "tiny model on CPU; the gap ratio (not tok/s) is the "
            "transferable number"
        ),
    }


def bench_spec_decode():
    """Speculative decoding (SchedulerConfig.speculative_ngram) CPU-sim
    microbench: n-gram prompt-lookup drafting + one-pass verification,
    spec on/off over two workloads. ``repetitive`` (periodic prompts,
    greedy decode — greedy tiny-model outputs loop, the prompt-lookup
    sweet spot) records MEAN EMITTED TOKENS PER ROW-STEP (the
    transferable number: on a memory-bound TPU decode, tokens/step IS
    the speedup; the CPU sim is compute-bound, so wall-clock here
    UNDERSTATES the win) and the draft acceptance rate. ``adversarial``
    (random prompts, temperature sampling — incompressible output, no
    n-gram ever accepted) pins the overhead of speculation that never
    fires: proposer scans + draft-backoff bookkeeping, which must stay
    within noise of the spec-off engine
    (docs/architecture/speculative-decoding.md)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import statistics

    import numpy as np

    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    B, ISL, OSL, K = 16, 64, 64, 4
    model = tiny_model_config(max_model_len=256)

    def make_engine(spec: bool) -> LLMEngine:
        cfg = EngineConfig(
            model=model,
            cache=CacheConfig(page_size=16, num_blocks=512, dtype="float32"),
            scheduler=SchedulerConfig(
                max_num_seqs=B, max_num_batched_tokens=B * ISL,
                speculative_ngram=spec, spec_ngram_k=K,
                spec_ngram_min_match=2,
            ),
            parallel=ParallelConfig(tensor_parallel_size=1),
            seed=0,
        )
        return LLMEngine(cfg)

    def run(workload: str) -> dict:
        rng = np.random.default_rng(0)
        if workload == "repetitive":
            sp = SamplingParams(
                temperature=0.0, max_tokens=OSL, ignore_eos=True
            )
            mk = lambda: [  # noqa: E731
                list(rng.integers(1, model.vocab_size, size=8)) * (ISL // 8)
                for _ in range(B)
            ]
        else:
            sp = SamplingParams(
                temperature=1.0, max_tokens=OSL, ignore_eos=True
            )
            mk = lambda: [  # noqa: E731
                list(rng.integers(1, model.vocab_size, size=ISL))
                for _ in range(B)
            ]
        engines = {False: make_engine(False), True: make_engine(True)}
        for eng in engines.values():  # warm, incl. mixed-split buckets
            eng.generate(mk(), sp)
            eng.generate(mk(), sp)
        sch = engines[True].scheduler
        sch.spec_accept_len_hist = [0] * (K + 1)
        sch.spec_proposed_tokens = 0
        sch.spec_accepted_tokens = 0
        # PAIRED runs: each round feeds the same fresh prompt set to
        # both engines back to back, so host drift (CI neighbors,
        # thermal) cancels in the ratio instead of dominating it.
        rates: dict[bool, list[float]] = {False: [], True: []}
        steps: dict[bool, int] = {}
        for _ in range(5):
            prompts = mk()  # fresh: no prefix-cache pollution
            for spec, eng in engines.items():
                eng.stats.engine_steps_total = 0
                t0 = time.monotonic()
                out = eng.generate([list(p) for p in prompts], sp)
                dt = time.monotonic() - t0
                total = sum(len(v) for v in out.values())
                assert total == B * OSL, (total, B * OSL)
                rates[spec].append(total / dt)
                steps[spec] = eng.stats.engine_steps_total
        res = {
            "spec_off": {
                "tok_s": round(statistics.median(rates[False]), 1),
                "steps": steps[False],
            },
            "spec_on": {
                "tok_s": round(statistics.median(rates[True]), 1),
                "steps": steps[True],
            },
            "tok_s_ratio": round(
                statistics.median(
                    on / off
                    for off, on in zip(rates[False], rates[True])
                ),
                3,
            ),
        }
        hist = sch.spec_accept_len_hist
        rows = max(sum(hist), 1)
        res["spec_on"]["accepted_len_hist"] = list(hist)
        # Mean tokens emitted per (spec row, step): 1 committed sample +
        # the accepted draft prefix. >1 means the weight read amortized
        # over more than one token.
        res["spec_on"]["mean_accepted_len"] = round(
            1 + sum(j * c for j, c in enumerate(hist)) / rows, 3
        )
        res["spec_on"]["acceptance_rate"] = round(
            sch.spec_accepted_tokens / max(sch.spec_proposed_tokens, 1), 3
        )
        return res

    out: dict = {}
    for workload in ("repetitive", "adversarial"):
        out[workload] = run(workload)
    out["substrate"] = (
        "tiny model on CPU (compute-bound): mean_accepted_len and the "
        "adversarial tok_s_ratio are the transferable numbers — "
        "repetitive wall-clock UNDERSTATES the TPU win, where decode "
        "steps are weight-read-bound and tokens/step is the speedup"
    )
    return out


def _bench_dbo_delta():
    """Dual-batch-overlap on/off wall-clock on the virtual 8-device CPU
    mesh (the only multi-device substrate here; real-slice numbers come
    from the same knob on hardware). Exactness is gated in
    tests/test_wide_ep.py; this records the measured step-time ratio."""
    import os

    # Must precede the first jax import (fresh subprocess via --only dbo).
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8".strip()
        )
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from llmd_tpu.config import ParallelConfig, tiny_model_config
    from llmd_tpu.models import llama
    from llmd_tpu.models.common import StepInput
    from llmd_tpu.parallel.mesh import build_mesh

    cfg = tiny_model_config(
        num_experts=8, num_experts_per_tok=2, hidden_size=128,
        moe_intermediate_size=128, num_layers=4, num_heads=8, num_kv_heads=4,
    )
    ctx = build_mesh(ParallelConfig(tensor_parallel_size=4, data_parallel_size=2))
    params = llama.init_params(cfg, jax.random.key(0))
    B, page, max_pages = 8, 4, 8
    kv = jnp.zeros(
        (cfg.num_layers, B * max_pages, cfg.kv_cache_heads, page,
         cfg.kv_cache_entry_dim), jnp.float32,
    )
    rng = np.random.default_rng(0)
    inp = StepInput(
        token_ids=jnp.asarray(rng.integers(1, 200, (B, 1)), jnp.int32),
        positions=jnp.full((B, 1), 5, jnp.int32),
        query_lens=jnp.ones(B, jnp.int32),
        kv_lens=jnp.full(B, 6, jnp.int32),
        page_table=jnp.arange(B * max_pages, dtype=jnp.int32).reshape(B, -1),
    )

    def step_time(dbo):
        with ctx.mesh:
            f = jax.jit(lambda p, kv: llama.forward_hidden(
                p, kv, inp, cfg, ctx.world, mesh=ctx.mesh,
                moe_backend="ep", ep_capacity_factor=8.0, dbo=dbo,
            )[0])
            f(params, kv).block_until_ready()
            samples = []
            for _ in range(10):
                t0 = time.monotonic()
                f(params, kv).block_until_ready()
                samples.append(time.monotonic() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    off, on = step_time(False), step_time(True)
    return {
        "dbo_off_ms": round(off * 1e3, 2),
        "dbo_on_ms": round(on * 1e3, 2),
        "substrate": "8-dev virtual CPU mesh (dp2 x tp4, ep8)",
        # on > off here is EXPECTED, not a defect — the canonical
        # explanation lives on ParallelConfig.enable_dbo (config.py);
        # exactness is gated in tests/test_wide_ep.py.
        "note": (
            "profiled (docs/architecture/dbo.md): the split multiplies "
            "a2a ops ~3.8x on the CPU mesh with nothing to hide behind; "
            "flag is experimental, default off, gated on a real-slice win"
        ),
    }


def _moe_ep_mesh():
    """8-device virtual CPU mesh + tiny EP-MoE geometry shared by the
    moe_ep / moe_overlap parts (fresh subprocess via --only, so the
    device-count flag can still land before the first jax import)."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8".strip()
        )
    import jax

    jax.config.update("jax_platforms", "cpu")

    from llmd_tpu.config import ParallelConfig, tiny_model_config
    from llmd_tpu.models import llama
    from llmd_tpu.parallel.mesh import build_mesh

    cfg = tiny_model_config(
        num_experts=8, num_experts_per_tok=2, hidden_size=128,
        moe_intermediate_size=64, num_layers=1, num_heads=8, num_kv_heads=4,
    )
    ctx = build_mesh(ParallelConfig(data_parallel_size=8))
    lp = {
        k: v[0]
        for k, v in llama.init_params(cfg, jax.random.key(0))["layers"].items()
        if k.startswith(("router", "we_", "ws_"))
    }
    return cfg, ctx, lp


def _bench_moe_ep():
    """Wide-EP dispatch-path CPU-sim part (wide-ep.md /
    wide-ep-perf-model.md): the three legs the perf model prices, all
    measured through the REAL ``moe_block_ep`` census on the 8-device
    virtual mesh (numerics/byte-identity are gated in
    tests/test_wide_ep.py; this records the payload/skew/drop counts
    the model predicts).

    HOT-EXPERT leg — a worst-case router (every token to experts 0+1)
    vs the same batch after the real EPLB placement
    (``compute_placement`` on the measured census, redundancy 1):
    per-destination required capacity_factor and dropped slots at
    static C=2.0, before vs after balancing — the factor-of-W/k skew
    EPLB erases.

    ADAPTIVE leg — a naturally-imbalanced router: the AdaptiveCapacity
    ladder converges on the observed demand and ships strictly fewer
    padded slots (and a2a payload bytes, 2 x W x C x H x 4 per
    microbatch both directions) than static 2.0 — both legs at ZERO
    dropped slots (the CI summary asserts this).

    FLEET leg — the expert_skew fleetsim scenario EPLB-on vs
    identity-layout on the same seeded Zipf trace: exact virtual-time
    dropped-slot and mean-shard-skew comparison plus the tail-TPOT
    ratio."""
    cfg, ctx, lp = _moe_ep_mesh()
    import numpy as np

    import jax
    import jax.numpy as jnp

    from llmd_tpu.parallel.eplb import AdaptiveCapacity, compute_placement
    from llmd_tpu.parallel.moe_ep import _capacity, moe_block_ep

    E, H, k = cfg.num_experts, cfg.hidden_size, cfg.num_experts_per_tok
    W = ctx.world
    B, T = 8, 64  # 512 tokens -> t*k/W = 128 per destination at balance
    h = jax.random.normal(jax.random.key(1), (B, T, H), jnp.float32)

    def census_of(lp, factor, placement=None, hh=None):
        with ctx.mesh:
            _, census = jax.jit(lambda h, lp: moe_block_ep(
                h, lp, cfg, ctx.mesh, capacity_factor=factor,
                placement=placement, emit_census=True,
            ))(h if hh is None else hh, lp)
        return np.asarray(census)

    # HOT-EXPERT leg: zeroed router logits tie every score, so top-k
    # routes every token to logical experts 0 and 1 — the two hottest
    # destinations take W/k = 4x the balanced flow.
    lp_hot = dict(lp)
    lp_hot["router"] = jnp.zeros_like(lp["router"])
    hot = census_of(lp_hot, 2.0)
    counts = hot[:E]
    pl = compute_placement(counts, world=W, redundancy=1)
    tables = {
        "phys_to_logical": jnp.asarray(pl.phys_to_logical),
        "replicas": jnp.asarray(pl.replicas),
        "n_replicas": jnp.asarray(pl.n_replicas),
    }
    # Physical expert weights = logical gathered through the placement
    # (the runner's we_* leaf remap at the step boundary).
    lp_bal = {
        k2: (jnp.take(v, tables["phys_to_logical"], axis=0)
             if k2.startswith("we_") else v)
        for k2, v in lp_hot.items()
    }
    balanced = census_of(lp_bal, 2.0, placement=tables)

    # ADAPTIVE leg: the natural (mildly imbalanced) router, balanced by
    # its own EPLB placement — the deployment shape. Feed the measured
    # required factor to the ladder until the down-hysteresis clears,
    # then price the padded slots / a2a bytes each factor ships.
    # Serving-sized batch: per-destination demand noise shrinks with
    # sample count, which is what lets the ladder settle under 2.0.
    Tb = 256
    h_big = jax.random.normal(jax.random.key(2), (B, Tb, H), jnp.float32)
    nat = census_of(lp, 8.0, hh=h_big)  # lossless probe: read true demand
    pl_nat = compute_placement(nat[:E], world=W, redundancy=1)
    tables_nat = {
        "phys_to_logical": jnp.asarray(pl_nat.phys_to_logical),
        "replicas": jnp.asarray(pl_nat.replicas),
        "n_replicas": jnp.asarray(pl_nat.n_replicas),
    }
    lp_nat = {
        k2: (jnp.take(v, tables_nat["phys_to_logical"], axis=0)
             if k2.startswith("we_") else v)
        for k2, v in lp.items()
    }
    need = float(census_of(lp_nat, 8.0, placement=tables_nat, hh=h_big)[E + 1])
    ladder = AdaptiveCapacity(base=2.0)
    factor = 2.0
    for _ in range(3 * ladder.hold_steps):
        nxt = ladder.observe(need)
        if nxt is not None:
            factor = nxt
    t_loc = B * Tb // W
    c_static, c_adapt = _capacity(t_loc, k, W, 2.0), _capacity(t_loc, k, W, factor)
    a2a_bytes = lambda c: 2 * W * c * H * 4  # noqa: E731  dispatch + combine
    drops_static = float(
        census_of(lp_nat, 2.0, placement=tables_nat, hh=h_big)[E]
    )
    drops_adapt = float(
        census_of(lp_nat, factor, placement=tables_nat, hh=h_big)[E]
    )

    # FLEET leg at reduced scale (the full-scale matrix runs in CI).
    from llmd_tpu.fleetsim.scenarios import build_expert_skew

    on = build_expert_skew(0, 0.25, eplb=True).run()
    off = build_expert_skew(0, 0.25, eplb=False).run()

    return {
        "geometry": f"E{E} k{k} over {W} EP shards, {B * T} tokens/step",
        "hot_required_factor": round(float(hot[E + 1]), 3),
        "hot_dropped_slots_static2": int(hot[E]),
        "eplb_required_factor": round(float(balanced[E + 1]), 3),
        "eplb_dropped_slots_static2": int(balanced[E]),
        "expert_counts_skew": round(
            float(counts.max() / max(counts.mean(), 1e-9)), 3
        ),
        "adaptive_factor": factor,
        "adaptive_required": round(need, 3),
        "padded_slots_static2": W * c_static,
        "padded_slots_adaptive": W * c_adapt,
        "a2a_mb_static2": round(a2a_bytes(c_static) / 2**20, 3),
        "a2a_mb_adaptive": round(a2a_bytes(c_adapt) / 2**20, 3),
        "dropped_slots_static2": drops_static,
        "dropped_slots_adaptive": drops_adapt,
        "fleet_dropped_on_vs_off": [
            on["expert_skew"]["dropped_slots"],
            off["expert_skew"]["dropped_slots"],
        ],
        "fleet_mean_skew_on_vs_off": [
            on["expert_skew"]["mean_shard_skew"],
            off["expert_skew"]["mean_shard_skew"],
        ],
        "fleet_tpot_p99_ratio": round(
            on["latency_ms"]["tpot"]["p99"] / off["latency_ms"]["tpot"]["p99"],
            3,
        ),
    }


def _bench_moe_overlap():
    """Microbatched overlapped expert dispatch on/off step time on the
    8-device virtual CPU mesh (wide-ep.md "overlapped dispatch").
    Byte-identity of the microbatched path is gated in
    tests/test_wide_ep.py; this records the measured ratio. Same
    graduation contract as DBO: the flag is experimental and default
    OFF until a real TPU slice shows overlap >= 2 step time strictly
    below overlap = 0 at serving batch — the falsifiable gate; on the
    CPU mesh the extra a2a dispatches have nothing to hide behind, so
    on > off here is EXPECTED, not a defect."""
    cfg, ctx, lp = _moe_ep_mesh()
    import jax
    import jax.numpy as jnp

    from llmd_tpu.parallel.moe_ep import moe_block_ep

    h = jax.random.normal(
        jax.random.key(1), (8, 64, cfg.hidden_size), jnp.float32
    )

    def step_time(overlap):
        with ctx.mesh:
            f = jax.jit(lambda h, lp: moe_block_ep(
                h, lp, cfg, ctx.mesh, capacity_factor=2.0, overlap=overlap,
            ))
            f(h, lp).block_until_ready()
            samples = []
            for _ in range(10):
                t0 = time.monotonic()
                f(h, lp).block_until_ready()
                samples.append(time.monotonic() - t0)
        samples.sort()
        return samples[len(samples) // 2]

    off, on = step_time(0), step_time(2)
    return {
        "overlap_off_ms": round(off * 1e3, 2),
        "overlap2_ms": round(on * 1e3, 2),
        "substrate": "8-dev virtual CPU mesh (dp8, ep8)",
        "note": (
            "byte-identical microbatched dispatch "
            "(tests/test_wide_ep.py); experimental, default off, "
            "graduates on a real-slice overlap-on win at serving batch"
        ),
    }


def _atomic_write_json(path: str, obj) -> None:
    """Write JSON via tmp + rename: a SIGKILL mid-write must never leave
    a torn/unparseable file (the partial stream IS the crash record)."""
    import os

    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _part_in_subprocess(part: str, retries: int = 0, timeout: float = 1800):
    import os
    import subprocess
    import sys

    last = None
    for attempt in range(retries + 1):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only", part],
            capture_output=True, text=True, timeout=timeout,
        )
        if proc.returncode == 0:
            return json.loads(proc.stdout.strip().splitlines()[-1])
        # The headline part gets one retry to separate a transient
        # device/fetch error from a real break (a blanket retry would
        # double the worst-case wall clock — the r5 failure mode).
        last = RuntimeError(
            f"bench part {part} failed rc={proc.returncode}: "
            + proc.stderr[-300:]
        )
    raise last


# Parts whose substrate is the CPU sim (forced inside the part itself):
# runnable in CI / under --skip-chip without a device.
_CPU_PARTS = frozenset({
    "dbo", "async_step", "spec_decode", "unified_step",
    "ragged_step", "fault_degrade", "fleet_soak", "kv_federation",
    "stream_resume", "batch_backfill", "lora_pool", "pd_stream",
    "moe_ep", "moe_overlap", "long_context",
})

# Every part main() can dispatch, in run order (also the validation set
# for --parts: a typo'd name must fail fast, not silently run nothing).
# CHEAPEST-FIRST (VERDICT r5 job #1): the chip-free CPU-sim parts are
# guaranteed-capturable even with a wedged chip, the cheap chip probes
# come next, the headline leads the engine parts, and the most expensive
# multi-minute parts run last — so whenever the deadline (or the
# driver's kill) lands, the summary already holds everything cheaper.
_ALL_PARTS = (
    "ragged_step", "unified_step", "async_step", "spec_decode",
    "dbo", "moe_ep", "moe_overlap", "fault_degrade",
    "fleet_soak", "kv_federation",
    "stream_resume", "batch_backfill", "lora_pool", "pd_stream",
    "long_context",
    "rtt", "env", "dense_int8", "dense_bf16", "mla_moe",
    "kv_int8_long", "kv_bf16_long", "swa_ring_off", "swa_ring_on",
    "pd", "pd_int8", "pd_kvint8", "pd_local", "pd_cached", "pd_adaptive",
    "predictor",
)

# Below this much remaining deadline a part is skipped outright (and
# recorded): starting a part that cannot finish only risks dying mid-
# measurement with nothing to show for the time.
_PART_FLOOR_S = 45.0


def main() -> None:
    import os
    import signal
    import sys

    if "--only" in sys.argv:
        part = sys.argv[sys.argv.index("--only") + 1]
        # Every part is a process of its own: one persistent compile cache
        # between them (JAX_COMPILATION_CACHE_DIR, or the checkout's).
        from llmd_tpu import jaxrt

        jaxrt.enable_compile_cache()
        print(json.dumps(_run_part(part)))
        return

    # Part selection (VERDICT r5): --parts a,b,c runs only those parts;
    # --skip-chip runs only the CPU-sim parts (CI-friendly: no chip,
    # no 17 sequential chip subprocesses).
    argv = sys.argv[1:]
    selected: set[str] | None = None
    if "--parts" in argv:
        selected = set(argv[argv.index("--parts") + 1].split(","))
        unknown = selected - set(_ALL_PARTS)
        if unknown:
            sys.exit(
                f"unknown bench parts {sorted(unknown)}; "
                f"known: {', '.join(_ALL_PARTS)}"
            )
    skip_chip = "--skip-chip" in argv
    # Global wall-clock deadline (VERDICT r6 job #1: the bench must be
    # un-killable). Default sits well inside the driver's kill timeout;
    # parts that cannot fit the remaining budget are skipped AND
    # recorded, so an externally killed run still leaves the last
    # complete summary line on stdout and on disk.
    deadline_s = float(os.environ.get("LLMD_BENCH_DEADLINE", 2400))
    if "--deadline" in argv:
        deadline_s = float(argv[argv.index("--deadline") + 1])
    t_start = time.monotonic()
    deadline_at = t_start + deadline_s

    state: dict = {"value": None, "extras": {}}
    extras: dict = state["extras"]

    # Parts that produced a value this run, in completion order: the
    # machine-readable line between "this part's number is from THIS
    # run" and "the run died before reaching it" — automation gates on
    # it instead of inferring from which extras keys happen to exist.
    completed: list[str] = []

    def summary() -> dict:
        v = state["value"]
        return {
            "metric": "output tokens/s/chip (llama-3.2-3b-class int8 "
            "W8A8, B=256 128in/64out, single chip, e2e engine)",
            "value": v,
            "unit": "tok/s/chip",
            "vs_baseline": (
                round(v / REFERENCE_PER_CHIP_TOKS, 3) if v else None
            ),
            "parts_completed": list(completed),
            "extras": extras,
        }

    def flush_partial() -> None:
        # Stream the evolving summary after every part, on BOTH
        # channels: an atomic tmp+rename file write (a SIGKILL mid-write
        # can never tear it) and a flushed stdout line (the driver
        # parses the LAST line of stdout, so however the run dies the
        # tail is the furthest-complete parseable summary — the fix for
        # r5's rc=124/tail:"" empty record).
        s = summary()
        try:
            _atomic_write_json("bench_partial.json", s)
        except OSError:  # pragma: no cover
            pass
        print(json.dumps(s), flush=True)

    def on_signal(signum, frame):  # pragma: no cover - timeout path
        # An hour-capped run (timeout(1) -> SIGTERM -> rc=124) must
        # still deliver every finished part on stdout, not tail: ""
        # (VERDICT r5) — AND on disk: the stdout line can be lost to a
        # closed pipe, so the signal path writes the same atomic partial
        # file the per-part flush maintains.
        extras["interrupted"] = (
            f"signal {signum}: emitting partial results"
        )
        s = summary()
        try:
            _atomic_write_json("bench_partial.json", s)
        except OSError:
            pass
        print(json.dumps(s), flush=True)
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    # EVERY chip touch (including the RTT probe) lives in a subprocess:
    # a chip admits one process at a time, and a parent that ever
    # initialized the TPU client would starve every child part.
    attempted: set[str] = set()

    def run(part: str, apply, group: dict | None = None) -> None:
        if selected is not None and part not in selected:
            return
        if skip_chip and part not in _CPU_PARTS:
            return
        target = extras if group is None else group
        remaining = deadline_at - time.monotonic()
        if remaining < _PART_FLOOR_S:
            # Out of budget: record the skip instead of starting a part
            # that would die mid-measurement (rc=124 with data lost).
            extras.setdefault("skipped_deadline", []).append(part)
            flush_partial()
            return
        attempted.add(part)
        try:
            apply(target, _part_in_subprocess(
                part,
                # Only the headline separates transient device faults
                # from real breaks with a retry; a blanket retry doubles
                # the worst-case clock (the r5 failure mode).
                retries=1 if part == "dense_int8" else 0,
                # Per-part timeout derives from the remaining deadline:
                # no single part may eat the whole budget.
                timeout=max(min(1800.0, remaining - 15.0), 30.0),
            ))
            completed.append(part)
        except Exception as e:
            target[f"{part}_error"] = f"{type(e).__name__}: {e}"[:200]
        flush_partial()

    set_key = lambda key: lambda t, v: t.__setitem__(key, v)  # noqa: E731
    merge = lambda t, v: t.update(v)  # noqa: E731
    swa: dict = {}
    extras_key_of = {
        # part -> (apply, group target)
        "ragged_step": (set_key("ragged_step"), None),
        "unified_step": (set_key("unified_step"), None),
        "async_step": (set_key("async_step"), None),
        "spec_decode": (set_key("spec_decode"), None),
        "dbo": (set_key("dbo"), None),
        "moe_ep": (set_key("moe_ep"), None),
        "moe_overlap": (set_key("moe_overlap"), None),
        "fault_degrade": (set_key("fault_degrade"), None),
        "fleet_soak": (set_key("fleet_soak"), None),
        "kv_federation": (set_key("kv_federation"), None),
        "stream_resume": (set_key("stream_resume"), None),
        "batch_backfill": (set_key("batch_backfill"), None),
        "lora_pool": (set_key("lora_pool"), None),
        "pd_stream": (set_key("pd_stream"), None),
        "long_context": (set_key("long_context"), None),
        "rtt": (set_key("dispatch_rtt_ms"), None),
        "env": (set_key("env"), None),
        # The headline part now also carries the MFU/roofline context:
        # the scalar stays the summary's `value`, the roofline dict
        # lands in extras next to it (and in bench_partial.json).
        "dense_int8": (
            lambda t, v: (
                state.__setitem__("value", v["tok_s"]),
                t.__setitem__("roofline_int8", v["roofline"]),
            ),
            None,
        ),
        "dense_bf16": (merge, None),
        "mla_moe": (set_key("mla_moe_tok_s"), None),
        "kv_int8_long": (merge, None),
        "kv_bf16_long": (merge, None),
        "swa_ring_off": (merge, swa),
        "swa_ring_on": (merge, swa),
        "pd": (merge, None),
        "pd_int8": (merge, None),
        "pd_kvint8": (merge, None),
        "pd_local": (merge, None),
        "pd_cached": (merge, None),
        "pd_adaptive": (merge, None),
        # Latency-predictor accuracy vs the reference's ~5% MAPE bar
        # (latency-predictor.md:58), measured on a REAL engine trace;
        # the synthetic eval rides along inside.
        "predictor": (set_key("predictor"), None),
    }
    # _ALL_PARTS is the cheapest-first run order (see its comment).
    for part in _ALL_PARTS:
        apply, group = extras_key_of[part]
        run(part, apply, group)
        if group is swa and swa and "swa_ring" not in extras:
            # Fold the group in and re-flush IMMEDIATELY: a kill during
            # the next (long) part must not lose a finished group part.
            extras["swa_ring"] = swa
            flush_partial()

    print(json.dumps(summary()))
    if "dense_int8" in attempted and state["value"] is None:
        # The headline part ran and produced nothing: the summary above
        # still carries every other part, but automation gating on the
        # exit code must not record this as a clean bench run.
        sys.exit(1)
    if not completed:
        # ZERO parts completed (every attempt failed or the deadline
        # skipped them all): the summary is hollow, and rc=0 on a hollow
        # summary is exactly how an empty bench record once passed
        # gating. Exit nonzero so automation sees a failed run.
        sys.exit(1)


if __name__ == "__main__":
    main()
