"""End-to-end engine tests on the CPU mesh (tiny model).

The key invariance test: chunked prefill + paged KV + prefix caching +
preemption must all produce exactly the same greedy tokens as a
one-shot whole-prompt run -- the paged machinery may never change numerics.
"""

import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams


def make_engine(
    tp=1, num_blocks=64, page=4, max_batched=64, max_seqs=8, seed=0, window=1,
    **model_kw,
) -> LLMEngine:
    cfg = EngineConfig(
        model=tiny_model_config(**model_kw),
        cache=CacheConfig(page_size=page, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=max_batched,
            decode_window=window,
        ),
        parallel=ParallelConfig(tensor_parallel_size=tp),
        seed=seed,
    )
    return LLMEngine(cfg)


PROMPTS = [
    [1, 5, 9, 13, 2, 8],
    [3, 3, 7, 1],
    [1, 5, 9, 13, 2, 8, 4, 4, 4, 4, 6, 6, 6, 6, 11],
]


def test_greedy_generation_basic():
    eng = make_engine()
    out = eng.generate(PROMPTS, SamplingParams(temperature=0.0, max_tokens=8))
    assert len(out) == 3
    for toks in out.values():
        assert len(toks) == 8
        assert all(0 <= t < 256 for t in toks)


def test_chunked_prefill_matches_oneshot():
    long_prompt = list(np.random.default_rng(0).integers(0, 256, size=50))
    ref = make_engine(max_batched=128).generate(
        [long_prompt], SamplingParams(temperature=0.0, max_tokens=6)
    )
    # chunk size 16 forces multi-step prefill
    chunked = make_engine(max_batched=16).generate(
        [long_prompt], SamplingParams(temperature=0.0, max_tokens=6)
    )
    assert list(ref.values())[0] == list(chunked.values())[0]


def test_batched_matches_single():
    params = SamplingParams(temperature=0.0, max_tokens=6)
    together = make_engine().generate(PROMPTS, params)
    for i, p in enumerate(PROMPTS):
        alone = make_engine().generate([p], params)
        assert list(alone.values())[0] == list(together.values())[i], f"prompt {i}"


def test_prefix_cache_reuse_preserves_output():
    eng = make_engine()
    prompt = list(range(1, 41))  # 40 tokens = 10 full pages
    params = SamplingParams(temperature=0.0, max_tokens=5)
    first = eng.generate([prompt], params)
    hits_before = eng.allocator.metrics_hits
    second = eng.generate([prompt], params)
    assert list(first.values())[0] == list(second.values())[0]
    assert eng.allocator.metrics_hits > hits_before  # cache actually used
    # a fresh engine (cold cache) agrees too
    cold = make_engine().generate([prompt], params)
    assert list(cold.values())[0] == list(second.values())[0]


def test_preemption_under_page_pressure():
    # 12 pages of 4 tokens = 48 slots for 3 seqs x (10 prompt + 12 out) = 66:
    # forces preemption + recompute; outputs must still match the
    # unconstrained engine.
    params = SamplingParams(temperature=0.0, max_tokens=12)
    prompts = [list(rng) for rng in (range(10), range(20, 30), range(40, 50))]
    small = make_engine(num_blocks=12).generate(prompts, params)
    big = make_engine(num_blocks=64).generate(prompts, params)
    assert small == {k: v for k, v in zip(small.keys(), big.values())}


def test_decode_window_matches_single_step():
    params = SamplingParams(temperature=0.0, max_tokens=11)
    single = make_engine(window=1).generate(PROMPTS, params)
    fused = make_engine(window=4).generate(PROMPTS, params)
    assert list(single.values()) == list(fused.values())


def test_decode_window_respects_stop_token():
    probe = make_engine().generate(
        [PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=8)
    )
    tokens = list(probe.values())[0]
    stop = tokens[2]
    expected = tokens[: tokens.index(stop) + 1]  # first occurrence wins
    out = make_engine(window=4).generate(
        [PROMPTS[0]],
        SamplingParams(temperature=0.0, max_tokens=8, stop_token_ids=(stop,)),
    )
    assert list(out.values())[0] == expected


def test_decode_window_seeded_reproducible():
    p = SamplingParams(temperature=1.0, max_tokens=9, seed=77)
    a = make_engine(window=1).generate([PROMPTS[0]], [p])
    b = make_engine(window=3).generate([PROMPTS[0]], [p])
    assert list(a.values())[0] == list(b.values())[0]


def test_stop_token():
    eng = make_engine()
    probe = eng.generate(
        [PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=4)
    )
    tokens = list(probe.values())[0]
    stop = tokens[1]
    eng2 = make_engine()
    out = eng2.generate(
        [PROMPTS[0]],
        SamplingParams(temperature=0.0, max_tokens=4, stop_token_ids=(stop,)),
    )
    # First occurrence wins: if the greedy stream repeats the chosen
    # token earlier than index 1 (numerics vary by backend), the engine
    # rightly stops there.
    assert list(out.values())[0] == tokens[: tokens.index(stop) + 1]


def test_sampling_with_seed_changes_tokens():
    params = SamplingParams(temperature=1.0, top_k=50, max_tokens=16)
    a = make_engine(seed=0).generate([PROMPTS[0]], params)
    b = make_engine(seed=1).generate([PROMPTS[0]], params)
    # different engine seeds should (overwhelmingly) differ
    assert list(a.values())[0] != list(b.values())[0]


def test_per_request_seed_reproducible():
    params = SamplingParams(temperature=1.0, max_tokens=12, seed=1234)
    # different engine seeds + different batch-mates: seeded request must
    # still reproduce exactly
    # same weights (engine seed) but different batch-mates / row position:
    # the seeded request must still reproduce exactly
    a = make_engine(seed=0).generate([PROMPTS[0]], [params])
    b = make_engine(seed=0).generate(
        [PROMPTS[1], PROMPTS[0]], [SamplingParams(max_tokens=12), params]
    )
    assert list(a.values())[0] == list(b.values())[1]


def test_priority_admission_order():
    eng = make_engine(max_seqs=8)
    low = eng.add_request(PROMPTS[0], SamplingParams(max_tokens=2), priority=0)
    high = eng.add_request(PROMPTS[1], SamplingParams(max_tokens=2), priority=5)
    assert eng.scheduler.waiting[0].request_id == high
    assert eng.scheduler.waiting[1].request_id == low


def test_unchunkable_prompt_rejected():
    import pytest as _pytest

    eng = make_engine(max_batched=16)
    eng.config.scheduler.enable_chunked_prefill = False
    with _pytest.raises(ValueError):
        eng.add_request(list(range(1, 30)))


def test_tp2_matches_tp1(devices):
    params = SamplingParams(temperature=0.0, max_tokens=6)
    tp1 = make_engine(tp=1).generate(PROMPTS, params)
    tp2 = make_engine(tp=2).generate(PROMPTS, params)
    assert list(tp1.values()) == list(tp2.values())


def test_moe_engine_runs():
    eng = make_engine(
        name="tiny-moe", num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=32,
    )
    out = eng.generate(PROMPTS[:2], SamplingParams(temperature=0.0, max_tokens=4))
    assert all(len(v) == 4 for v in out.values())


def test_max_model_len_rejected():
    eng = make_engine()
    with pytest.raises(ValueError):
        eng.add_request(list(range(200)))  # max_model_len=128


def test_engine_qk_norm_generates():
    """Qwen3-style QK-norm path: engine generates deterministically."""
    from llmd_tpu.config import CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config
    from llmd_tpu.engine import LLMEngine, SamplingParams

    model = tiny_model_config(name="tiny-qkn", qk_norm=True)
    cfg = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
    )
    engine = LLMEngine(cfg)
    out = engine.generate(
        [[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    )
    toks = list(out.values())[0]
    assert len(toks) == 6
    # qk-norm changes the function: outputs differ from the no-norm model
    engine2 = LLMEngine(EngineConfig(
        model=tiny_model_config(name="tiny-qkn"),
        cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
    ))
    out2 = engine2.generate(
        [[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    )
    assert list(out2.values())[0] != toks


def test_tp2_decode_runs_pallas_kernels_sharded(devices, monkeypatch):
    """tp>1 engine drives the Pallas decode kernels (interpret mode) under
    shard_map and matches the pure-XLA engine token for token. Geometry
    chosen so the kernel gates pass: head_dim 128, page 8."""
    monkeypatch.setenv("LLMD_PALLAS", "off")
    kw = dict(
        num_blocks=32, page=8, hidden_size=256, num_heads=2, num_kv_heads=2,
        head_dim=128, intermediate_size=128,
    )
    ref = make_engine(tp=1, **kw).generate(
        PROMPTS, SamplingParams(temperature=0.0, max_tokens=6)
    )
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    from llmd_tpu import ops

    plans = []
    real_plan = ops._plan

    def spy(*a, **k):
        plans.append(real_plan(*a, **k))
        return plans[-1]

    monkeypatch.setattr(ops, "_plan", spy)
    got = make_engine(tp=2, **kw).generate(
        PROMPTS, SamplingParams(temperature=0.0, max_tokens=6)
    )
    assert list(ref.values()) == list(got.values())
    assert "shard" in plans  # the sharded kernel path actually ran


def test_tp_exceeding_kv_heads_shards_via_replication(devices):
    """tp > num_kv_heads: the pool stores each kv head tp/K times so the
    head axis shards over tp (per-chip KV = pool/K, not a full replica),
    and outputs match the unsharded engine exactly."""
    kw = dict(num_heads=8, num_kv_heads=2, hidden_size=64,
              intermediate_size=128)
    params = SamplingParams(temperature=0.0, max_tokens=6)
    ref = make_engine(tp=1, **kw).generate(PROMPTS, params)
    eng = make_engine(tp=8, **kw)
    assert eng.runner.kv_rep == 4
    assert eng.runner.kv_cache.shape[2] == 8  # 2 kv heads x 4 copies
    got = eng.generate(PROMPTS, params)
    assert list(ref.values()) == list(got.values())


def test_kv_rep_pd_transfer_interops_with_unsharded_producer(devices):
    """P/D across different tp layouts: bundles travel in the canonical
    original-head format, so a tp=1 producer feeds a kv-replicated
    consumer byte-exact."""
    kw = dict(num_heads=8, num_kv_heads=2, hidden_size=64,
              intermediate_size=128)

    def engine_with(tp, role):
        cfg = EngineConfig(
            model=tiny_model_config(**kw),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64),
            parallel=ParallelConfig(tensor_parallel_size=tp),
            kv_role=role,
            kv_transfer_port=0,
        )
        return LLMEngine(cfg)

    prompt = list(range(1, 18))
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    ref = make_engine(tp=1, **kw).generate([prompt], sp)

    producer = engine_with(1, "kv_producer")
    consumer = engine_with(8, "kv_consumer")
    try:
        assert consumer.runner.kv_rep == 4
        rid = producer.add_request(
            list(prompt), SamplingParams(temperature=0.0, max_tokens=1),
            kv_transfer_params={"do_remote_decode": True},
        )
        pre = None
        while producer.has_work():
            for out in producer.step():
                if out.request_id == rid and out.finished:
                    pre = out
        rid = consumer.add_request(
            list(prompt), sp, kv_transfer_params=pre.kv_transfer_params
        )
        toks = []
        while consumer.has_work():
            for out in consumer.step():
                if out.request_id == rid:
                    toks.extend(out.new_token_ids)
        assert toks == list(ref.values())[0]
        assert consumer.kv_connector.imported_requests == 1
        assert consumer.kv_connector.import_failures == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


# --------------------------------------------------------------------- #
# unified single-dispatch step (SchedulerConfig.unified_step): one ragged
# program per window=1 step must change how many device programs a step
# launches, never WHICH tokens it emits.


def make_unified(unified, max_batched=16, num_blocks=64, seed=0, **kw):
    cfg = EngineConfig(
        model=tiny_model_config(),
        cache=CacheConfig(page_size=4, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=max_batched,
            unified_step=unified, **kw,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
    )
    return LLMEngine(cfg)


# A long prompt (chunked across steps under the small budget) next to
# short ones: once the short prompts decode, every remaining chunk step
# is MIXED (prefill chunk + decode rows) — the unified program's case.
MIXED_PROMPTS = [
    list(np.random.default_rng(7).integers(0, 256, size=40)),
    [3, 3, 7, 1],
    [1, 5, 9, 13, 2, 8],
    [9, 1, 9, 1, 9, 1, 2, 2],
]


def test_unified_vs_split_parity_mixed_chunked():
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    base = make_unified(False).generate([list(p) for p in MIXED_PROMPTS], sp)
    eng = make_unified(True)
    out = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.stats.unified_steps_total > 0  # mixed steps actually fused


def test_unified_fewer_dispatches_same_stream():
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    split = make_unified(False)
    base = split.generate([list(p) for p in MIXED_PROMPTS], sp)
    eng = make_unified(True)
    out = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.stats.engine_steps_total == split.stats.engine_steps_total
    assert eng.stats.step_dispatches_total < split.stats.step_dispatches_total
    assert eng.stats.unified_steps_total > 0


def test_unified_vs_split_parity_preemption():
    """Page pressure forces recompute-preemption mid-run; streams must
    still match the split engine under the SAME tight pool."""
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    kw = dict(num_blocks=14, max_batched=16)
    base_eng = make_unified(False, **kw)
    base = base_eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    eng = make_unified(True, **kw)
    out = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    assert eng.stats.unified_steps_total > 0
    assert eng.allocator.usage() == 0.0


def test_unified_vs_split_parity_prefix_cache_hit():
    """A repeated prompt admits from the prefix cache (decode starts
    mid-page) and must still stream identically through unified steps."""
    sp = SamplingParams(temperature=0.0, max_tokens=10, ignore_eos=True)
    base_eng, eng = make_unified(False), make_unified(True)
    first_b = base_eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    first_u = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(first_b.values()) == list(first_u.values())
    second_b = base_eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    second_u = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(second_b.values()) == list(second_u.values())
    assert eng.allocator.metrics_hits > 0  # the hit actually happened


def test_unified_vs_split_parity_seeded_sampling():
    """Seeded rows must reproduce byte-for-byte through the unified
    sample plane (column 0 of a non-verify row carries exactly the seed
    the split engine's one-sample dispatch would use)."""
    sp = SamplingParams(temperature=1.0, max_tokens=12, seed=77, ignore_eos=True)
    base = make_unified(False, seed=3).generate(
        [list(p) for p in MIXED_PROMPTS], sp
    )
    eng = make_unified(True, seed=3)
    out = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng.stats.unified_steps_total > 0


def test_unified_vs_split_parity_async_rollback(unforeseen_finishes):
    """Unified prestaging composes with async stepping: a late finish
    finds its row in the step dispatched before the commit (wasted, its
    token dropped) and streams stay byte-identical to the split sync
    engine."""
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    base = make_unified(False).generate([list(p) for p in MIXED_PROMPTS], sp)
    eng = make_unified(True)  # (pipelined, as every engine is)
    out = eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert list(base.values()) == list(out.values())
    assert eng._inflight is None
    assert eng.stats.unified_steps_total > 0
    assert eng.stats.async_wasted_rows_total >= 1  # LENGTH finishes land late
    assert eng.stats.async_rollbacks_total == 0
    assert eng.allocator.usage() == 0.0


def test_unified_one_readback_per_step():
    """One blocking host readback per engine step, however many prefill
    chunks, decode rows (and on spec engines, verify rows) the unified
    program packed."""
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    eng = make_unified(True)
    calls = {"n": 0}
    orig = eng.runner.wait_step

    def counting(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    eng.runner.wait_step = counting
    eng.generate([list(p) for p in MIXED_PROMPTS], sp)
    assert eng.stats.unified_steps_total > 0
    assert calls["n"] == eng.stats.engine_steps_total


def test_unified_multi_group_prefill_collapses_to_one_dispatch():
    """A prefill-only step whose chunks span several Q buckets (one
    long + several short prompts under a large budget) rides ONE
    unified program instead of one program per bucket group."""
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    prompts = [
        list(np.random.default_rng(5).integers(0, 256, size=40)),
        [3, 3, 7, 1],
        [1, 5, 9, 13],
    ]
    split = make_unified(False, max_batched=64)
    base = split.generate([list(p) for p in prompts], sp)
    eng = make_unified(True, max_batched=64)
    out = eng.generate([list(p) for p in prompts], sp)
    assert list(base.values()) == list(out.values())
    # step 1 (whole-batch prefill): split pays one program per Q bucket
    # group, unified pays one.
    assert eng.stats.unified_steps_total > 0
    assert eng.stats.step_dispatches_total < split.stats.step_dispatches_total


import pytest as _pytest


@_pytest.mark.parametrize("over", [
    {},  # plain GQA
    {"attention_bias": True, "qk_norm": True},  # Qwen-style extras
    {"quantization": "int8"},  # int8 scales must concatenate losslessly
])
def test_fused_projections_match_unfused(over):
    """fuse_projections is claimed lossless: greedy tokens with fusion on
    must equal fusion off exactly, across bias/qk_norm/int8 variants; the
    fused params must actually be fused (and only then)."""
    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine, SamplingParams

    def gen(fuse):
        eng = LLMEngine(EngineConfig(
            model=tiny_model_config(num_heads=4, num_kv_heads=2, **over),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
            parallel=ParallelConfig(tensor_parallel_size=1, fuse_projections=fuse),
            offload=None,
        ))
        try:
            fused_keys = "wqkv" in eng.runner.params["layers"]
            assert fused_keys == fuse
            sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
            return list(eng.generate([[1, 2, 3, 4, 5, 6]], sp).values())[0]
        finally:
            eng.close()

    assert gen(True) == gen(False)


def test_fused_projections_skip_guards(devices):
    """tp > 1 / LoRA / MLA layouts must NOT fuse (the fused axis cannot
    ride the per-projection TP shard; adapters and MLA keep their own
    projection structure)."""
    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
        tiny_model_config,
    )
    from llmd_tpu.engine import LLMEngine

    cases = [
        (dict(num_heads=4, num_kv_heads=2), dict(tensor_parallel_size=2)),
        (dict(num_heads=4, num_kv_heads=2, num_lora_adapters=1),
         dict(tensor_parallel_size=1)),
        (dict(kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16),
         dict(tensor_parallel_size=1)),
    ]
    for model_over, par_over in cases:
        eng = LLMEngine(EngineConfig(
            model=tiny_model_config(**model_over),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
            parallel=ParallelConfig(fuse_projections=True, **par_over),
            offload=None,
        ))
        try:
            assert "wqkv" not in eng.runner.params["layers"], (model_over, par_over)
        finally:
            eng.close()
