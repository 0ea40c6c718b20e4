"""Decode-time KV paging (engine/pager.py).

Byte-parity discipline: with the pager armed, every output token must be
identical to an untouched run — spills only ever free pages no kernel
reads (window-masked), restores bring back the exact bytes, and a host-
tier miss refunds the sequence to plain recompute-preemption (itself
parity-safe).
"""

import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig, EngineConfig, OffloadConfig, ParallelConfig,
    SchedulerConfig, tiny_model_config,
)
from llmd_tpu.engine.engine import LLMEngine
from llmd_tpu.engine.request import SamplingParams

rng = np.random.default_rng(0)
PROMPT = list(rng.integers(0, 256, size=48))


def make_engine(
    decode_paging, num_blocks=128, horizon=8, window=8, cpu_chunks=512,
    **sched_kw,
):
    cfg = EngineConfig(
        model=tiny_model_config(max_model_len=256, sliding_window=window),
        cache=CacheConfig(page_size=4, num_blocks=num_blocks, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=64, **sched_kw
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        offload=OffloadConfig(
            enabled=True, cpu_chunks=cpu_chunks, decode_paging=decode_paging,
            pager_horizon_tokens=horizon,
        ),
        seed=0,
    )
    return LLMEngine(cfg)


def test_spill_tick_byte_parity():
    """Cold pages spill while the sequence decodes; tokens unchanged and
    resident pages bounded by window + horizon, not context length."""
    params = SamplingParams(temperature=0.0, max_tokens=24)
    ref = make_engine(False).generate([PROMPT], params)
    eng = make_engine(True)
    got = eng.generate([PROMPT], params)
    assert eng.pager is not None
    assert eng.pager.pages_spilled_total > 0
    assert list(ref.values())[0] == list(got.values())[0]
    eng._refresh_gauges()
    assert eng.stats.kv_paged_out_bytes > 0


def test_resident_pages_bounded_by_window():
    """Directly observe the HBM bound: during a long decode, the live
    page count of the sequence stays near window + horizon while its
    logical context keeps growing."""
    eng = make_engine(True, num_blocks=64, window=8, horizon=8)
    rid = eng.add_request(PROMPT, SamplingParams(temperature=0.0, max_tokens=40))
    peak_resident = 0
    for _ in range(200):
        if not eng.has_work():
            break
        eng.step()
        for req in eng.scheduler.running:
            # (once the prompt has committed: the pipelined step returns
            # from its first call with the whole prefill still in flight)
            if req.request_id == rid and req.in_decode:
                resident = len(req.block_ids) - len(req.paged_out)
                peak_resident = max(peak_resident, resident)
    page = 4
    keep_pages = (8 + 8) // page  # window + horizon
    # bound: kept window + the partial frontier + one chunk of slack
    assert peak_resident <= keep_pages + 3, peak_resident
    # ... while the context grew far past it
    assert (len(PROMPT) + 40) // page > keep_pages + 3


def test_park_restore_byte_parity():
    """Page pressure preempts a decoding victim; with the pager armed it
    parks (KV hosted, pages freed) and restores the attention window on
    resume instead of recomputing — tokens identical to a clean run."""
    prompts = [list(rng.integers(0, 256, size=24)) for _ in range(2)]
    params = SamplingParams(temperature=0.0, max_tokens=40)
    ref = make_engine(False, num_blocks=256, window=32, horizon=4).generate(
        prompts, params
    )
    eng = make_engine(True, num_blocks=14, window=32, horizon=4)
    got = eng.generate(prompts, params)
    assert eng.pager.parks_total > 0, "pressure never parked a victim"
    assert eng.pager.pages_restored_total > 0
    assert eng.pager.refunds_total == 0
    for i in range(len(prompts)):
        assert list(ref.values())[i] == list(got.values())[i], f"seq {i}"


def test_refund_to_recompute_byte_parity():
    """A host-tier miss at restore refunds the victim to plain
    recompute-from-zero — the wire failed, compute did not, and the
    output bytes must not change."""
    prompts = [list(rng.integers(0, 256, size=24)) for _ in range(2)]
    params = SamplingParams(temperature=0.0, max_tokens=40)
    ref = make_engine(False, num_blocks=256, window=32, horizon=4).generate(
        prompts, params
    )
    eng = make_engine(True, num_blocks=14, window=32, horizon=4)
    rids = [eng.add_request(p, params) for p in prompts]
    out = {rid: [] for rid in rids}
    dropped = False
    for _ in range(400):
        if not eng.has_work():
            break
        if not dropped and eng.pager.parks_total > 0:
            # Sabotage the host tier: every parked page vanishes, as if
            # evicted under memory pressure before the restore.
            for req in eng.scheduler.waiting:
                if req.kv_fetch_pending:
                    for h in req.paged_out.values():
                        eng._host_cache.drop(h)
                    dropped = True
        for o in eng.step():
            out[o.request_id].extend(o.new_token_ids)
    assert dropped, "pressure never parked a victim"
    assert eng.pager.refunds_total > 0, "host miss never refunded"
    for i, rid in enumerate(rids):
        assert out[rid] == list(ref.values())[i], f"seq {i}"


def test_fetch_pending_is_not_a_fault():
    """While a parked request's window is non-resident, schedule() simply
    skips it (and everything behind it, FCFS); nothing raises."""
    eng = make_engine(True, num_blocks=14, window=32, horizon=4)
    params = SamplingParams(temperature=0.0, max_tokens=40)
    prompts = [list(rng.integers(0, 256, size=24)) for _ in range(2)]
    rids = [eng.add_request(p, params) for p in prompts]
    saw_pending = False
    for _ in range(400):
        if not eng.has_work():
            break
        eng.step()
        saw_pending = saw_pending or any(
            r.kv_fetch_pending for r in eng.scheduler.waiting
        )
    # The run completed (no stall, no fault); whether a pending state was
    # observable depends on pump timing, but a park must have happened.
    assert eng.pager.parks_total > 0
    assert not eng.has_work()
    del rids, saw_pending


def test_decode_paging_requires_sliding_window():
    with pytest.raises(ValueError, match="sliding-window"):
        make_engine(True, window=0)
