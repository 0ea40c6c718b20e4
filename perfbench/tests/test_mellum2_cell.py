"""The Mellum2 cell's own tests: CPU only, the tiny preset. Not collected by
the repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_mellum2_cell.py -q
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run  # noqa: E402

CELL = "mellum2-12b-a2.5b.long-doc-sessions"
BENCH = ROOT / "perfbench"
CONF = json.loads((BENCH / "configs" / "mellum2-12b-a2.5b.1chip.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {
    "kernels.window_attn_time_share.w1024", "kernels.full_attn_time_share.w1024",
    "kernels.window_attention_roofline.w1024", "sched.swa_section_hit_share.w1024",
    "sched.kv_bytes_per_cached_token.w1024", "kernels.moe_held_pick_share.ep4", "sched.ring_seed_ms.w1024",
}


def test_the_configuration_keeps_the_catalogs_keys_but_those_in_reduced():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == "mellum2-12b-a2.5b.1chip")
    reduced = set(entry["reduced"])
    assert reduced == set(CONF["reduced"]) and {"num_experts", "vocab_size"} <= reduced <= {
        "num_experts", "vocab_size", "num_hidden_layers"}
    if CATALOG.exists():
        row = next(json.loads(l) for l in CATALOG.open() if '"name": "Mellum2-12B-A2.5B-Instruct"' in l)
        assert entry["source"] == CONF["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (CONF[k] == v) != (k in reduced), k
        assert {k: row["config"][k] for k in reduced} == {k: CONF["published"][k] for k in reduced}
    # the cut: whole periods of S S S F, a quarter of the experts and of the vocabulary
    assert CONF["num_hidden_layers"] % 4 == 0 and CONF["num_hidden_layers"] in (28, 24, 20)
    assert CONF["num_experts"] * CONF["deployment"]["chips_per_layer"] == CONF["published"]["num_experts"] == 64
    assert CONF["vocab_size"] * 4 == CONF["published"]["vocab_size"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mellum2-12b-a2.5b.1chip", "long-doc-sessions", 1)
    listed = {m["name"] for m in manifest["per_layer"] if m.get("workloads") == [CELL]}
    assert listed == NEW_METRICS
    assert all(m["layer"] in ("kernels", "scheduler") for m in manifest["per_layer"] if m["name"] in NEW_METRICS)


def test_the_pools_arithmetic():
    """What PERF.md section 4 and the file's ``stands_for`` reckon with."""
    geo, mix = CONF["engine"], json.loads((BENCH / "traffic" / "long-doc-sessions.json").read_text())
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    depth, page, window = CONF["num_hidden_layers"], geo["page_size"], CONF["sliding_window"]
    n_full = CONF["layer_types"][:depth].count("full_attention")
    n_swa = depth - n_full
    assert (n_full, n_swa) == (depth // 4, 3 * depth // 4)
    token = CONF["num_key_value_heads"] * 2 * CONF["head_dim"] * 2  # K | V of a layer, bfloat16
    assert token == 2048
    # the main pool holds what the traffic can hold: the documents once, every session's own tail
    own = mix["context_cap"] - mix["prefix_tokens"]
    need = mix["groups"] * mix["prefix_tokens"] + cell["clients"] * own
    assert need == 262144 <= geo["num_pages"] * page == 327680
    assert mix["context_cap"] < geo["max_model_len"] and cell["clients"] == geo["max_num_seqs"] == 24
    # a ring: window + chunk, in pages, + 1; a section: the window + a page of straddle
    from llmd_tpu.config import swa_ring_spec

    sys.path.insert(0, str(ROOT))
    from perfbench.topologies import engine_hybrid_yarn

    config = engine_hybrid_yarn.engine_config(CONF, seed=0, rehearse=False)
    swa = swa_ring_spec(config.model, config.cache, config.scheduler)
    assert window > geo["max_num_batched_tokens"] == swa.chunk_tokens == 128
    assert swa.ring_pages == (window + 128) // page + 1 == 73 and swa.max_section_pages(page) == 65
    assert (len(swa.full_layers), len(swa.swa_layers)) == (n_full, n_swa)
    ring_bytes = swa.ring_pages * n_swa * page * token
    section_bytes = 65 * n_swa * page * token
    if depth == 28:
        assert round(ring_bytes / 2**20, 1) == 47.9 and round(section_bytes / 2**20, 1) == 42.7
        total = (geo["num_pages"] * page * token * n_full + geo["max_num_seqs"] * ring_bytes
                 + geo["swa_sections"] * section_bytes)
        assert round(total / 2**30, 2) == 7.16  # 4.375 + 1.12 + 1.67 GiB of pools beside 6.49 GiB of weights
    # the comparison's context: several windows, several rings, many chunks, under YaRN's original length
    ctx = geo["check_context_tokens"]
    assert ctx == 4 * window and ctx / (swa.ring_pages * page) > 3.5 and ctx // 128 == 32
    assert ctx < CONF["rope_parameters"]["full_attention"]["original_max_position_embeddings"] < mix["prefix_tokens"]
    assert geo["check_background_rows"] == geo["max_num_seqs"] - 2 and geo["check_decode_tokens"] == 64


def test_the_cell_rehearses_through_both_pools_with_a_miss_two_hits_and_the_keys_held(capsys):
    seed = 2**31 + 51
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", "2", "--rehearse"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    reported = set(line["metrics_reported"])
    assert {"itl_p95_ms", "output_tok_s", "setup_s", "sched.swa_section_hit_share.w1024",
            "sched.kv_bytes_per_cached_token.w1024", "kernels.moe_held_pick_share.ep4",
            "sched.ring_seed_ms.w1024"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{seed}.trace2.json").read_text())
    check = detail["reference_check"]
    assert check["ok"] and check["tokens_compared"] == 128 and check["logprob_diff"]["max"] < 1e-4
    log = [tuple(e) for e in detail["setup_log"]]
    keys = [v for k, v in log if k == "key_check.token_median"]
    assert len(keys) == 4 and max(keys) < 1e-5  # layer 3's keys of the four bound prompts
    assert [v for k, v in log if k == "decode_check.live_rows"] == [7, 7]  # 6 background rows + what is left of the pair
    delta = detail["counter_delta"]
    # (the hits are a gauge's copy of the cache's count, refreshed a step; the seeds count as they happen)
    assert delta["swa_ring_seeds_total"] > 0 and abs(delta["swa_ring_seeds_total"] - delta["swa_section_hits_total"]) <= 4
    assert delta["swa_ring_seed_pages_total"] > 0 and delta["moe_picks_held_total"] < delta["moe_picks_total"]


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _window_call(t: int) -> str:
    return (f"%llmd.attn.window.3 = (bf16[{t},4,8,128]{{3,2,1,0}}, f32[8]{{0}}) custom-call(s32[{t}]{{0}} %rows, "
            f"bf16[{t},4,8,128]{{3,2,1,0}} %q, bf16[21,4352,4,16,256]{{4,3,2,1,0}} %pool), custom_call_target=\"tpu_custom_call\"")


def test_the_window_roofline_counts_rows_per_tile():
    name = "kernels.window_attention_roofline.w1024"
    mod, d = _reader(name), reducers.definition("per_layer", name)
    assert mod.rows_read(128, 0, 1024, 16, 16) == 128 * 1040  # per token: what one program a token must read
    assert mod.rows_read(128, 128, 1024, 16, 16) == 8 * 1056  # per tile: ~15.8x fewer rows
    assert mod.rows_read(24 + 96, 96, 1024, 16, 16) == 6 * 1056 + 24 * 1040  # a mixed step: 24 decode rows + a chunk
    op = _window_call(128)
    ctx = {"trace": {"op_seconds": {op: 0.001, "%gmm.1 = f32[8,8]": 1.0}, "op_calls": {op: 21, "%gmm.1 = f32[8,8]": 1},
                     "busy_s": 1.1},
           "config": CONF, "bench_dir": str(BENCH), "device": {"kind": "TPU v5 lite"}, "series": {}, "counter_delta": {},
           "counter_delta_traced": {"live_tokens_total": 1200, "padded_tokens_total": 80,
                                    "attn_shared_tile_tokens_total": 960}}
    tokens = 128 * 1200 / 1280
    rows = mod.rows_read(tokens, tokens * 0.8, 1024, 16, 16)
    want_bytes = rows * 4 * 256 * 2 + 2 * tokens * 32 * 128 * 2
    flops, nbytes = mod.call_cost(op, 1024, 1200 / 1280, 0.8, 16)
    assert nbytes == want_bytes and flops == 4.0 * tokens * 1024 * 32 * 128
    share = mod.read(ctx, d)
    assert abs(share - 100 * 21 * max(flops / 197e12, nbytes / 819e9) / 0.001) < 1e-6 and 0 < share
    # per token it would be charged ~3.9x the bytes: the accepted reader's count, which a tiled kernel could beat
    per_token = mod.call_cost(op, 1024, 1200 / 1280, 0.0, 16)[1]
    assert 3.5 < per_token / nbytes < 4.5
    # a program without the tile counter counts per token; nothing caps a reading
    assert mod.read(dict(ctx, counter_delta_traced={"live_tokens_total": 1200, "padded_tokens_total": 80}), d) > share
    assert mod.read(dict(ctx, trace={"op_seconds": {op: 1e-6}, "op_calls": {op: 21}, "busy_s": 1.0}), d) > 100
    # nothing to read: no trace, no counters, no window, no such event -> nothing, and nothing raised
    assert mod.read(dict(ctx, trace=None), d) is None
    assert mod.read(dict(ctx, counter_delta_traced=None), d) is None
    assert mod.read(dict(ctx, config={}), d) is None
    assert mod.read(dict(ctx, trace={"op_seconds": {"%gmm.1 = f32[8,8]": 1.0}, "op_calls": {}, "busy_s": 1.0}), d) is None


def test_the_data_metrics_read_their_counters_and_leave_a_parents_line_alone():
    delta = {"swa_section_hits_total": 19, "swa_section_misses_total": 1, "kv_bytes_in_use_total": 3_000_000,
             "cached_tokens_total": 100, "moe_picks_held_total": 25, "moe_picks_total": 100,
             "swa_ring_seed_host_ms_total": 30.0, "swa_ring_seeds_total": 20}
    ctx = {"series": {}, "counter_delta": delta, "counter_delta_traced": None, "trace": None, "device": {},
           "config": CONF, "bench_dir": str(BENCH)}
    want = {"sched.swa_section_hit_share.w1024": 95.0, "sched.kv_bytes_per_cached_token.w1024": 30000.0,
            "kernels.moe_held_pick_share.ep4": 25.0, "sched.ring_seed_ms.w1024": 1.5}
    for name, v in want.items():
        assert abs(reducers.reduce("per_layer", name, ctx) - v) < 1e-9, name
    # a program without the seed's counters (the parent of PR 51), or a window without a seed: left out
    old = {k: v for k, v in delta.items() if not k.startswith("swa_ring_seed")}
    assert reducers.reduce("per_layer", "sched.ring_seed_ms.w1024", dict(ctx, counter_delta=old)) is None
    none = dict(delta, swa_ring_seeds_total=0)
    assert reducers.reduce("per_layer", "sched.ring_seed_ms.w1024", dict(ctx, counter_delta=none)) is None
    for name in ("kernels.window_attn_time_share.w1024", "kernels.full_attn_time_share.w1024"):
        assert reducers.reduce("per_layer", name, ctx) is None
        tr = {"op_seconds": {_window_call(64): 0.2, "%llmd.attn.full.1 = bf16[64,4,8,128] custom-call()": 0.3}, "busy_s": 1.0}
        got = reducers.reduce("per_layer", name, dict(ctx, trace=tr))
        assert abs(got - (20.0 if "window" in name else 30.0)) < 1e-9
