"""The DeepSeek-V3.2 cell's own tests: CPU only, the tiny preset. Not
collected by the repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_deepseek_v32_cell.py -q
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run, sparse_mla_trace  # noqa: E402

CELL = "deepseek-v3.2.long-doc-sessions"
BENCH = ROOT / "perfbench"
CONF = json.loads((BENCH / "configs" / "deepseek-v3.2.1chip.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_configuration_keeps_the_catalogs_keys_but_those_in_reduced():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == "deepseek-v3.2.1chip")
    reduced = set(entry["reduced"])
    assert reduced == set(CONF["reduced"]) == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                                                "vocab_size"}
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in reduced)  # no width
    if CATALOG.exists():
        row = next(json.loads(l) for l in CATALOG.open() if '"name": "DeepSeek-V3.2"' in l)
        assert entry["source"] == CONF["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert (CONF[k] == v) != (k in reduced), k
        assert {k: row["config"][k] for k in reduced} == CONF["published"]
    # the cut: 1 dense + 4 expert layers, a sixteenth of the experts, an eighth of the vocabulary
    assert (CONF["num_hidden_layers"], CONF["first_k_dense_replace"]) == (5, 1)
    assert CONF["n_routed_experts"] * CONF["deployment"]["ranks"] == CONF["published"]["n_routed_experts"]
    assert CONF["vocab_size"] * 8 == CONF["published"]["vocab_size"]


def test_the_pools_fit_the_traffic_and_the_comparison_binds():
    geo, mix = CONF["engine"], json.loads((BENCH / "traffic" / "long-doc-sessions.json").read_text())
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    own = mix["context_cap"] - mix["prefix_tokens"]
    need = mix["groups"] * mix["prefix_tokens"] + cell["clients"] * own
    assert need == 262144 <= geo["num_pages"] * geo["page_size"] == 393216
    assert geo["check_context_tokens"] == 2 * CONF["index_topk"] and mix["prefix_tokens"] >= 8 * CONF["index_topk"]
    assert mix["context_cap"] < geo["max_model_len"]
    # a token's bytes over both planes and the five layers
    lanes = -(-(CONF["kv_lora_rank"] + CONF["qk_rope_head_dim"]) // 128) * 128 + CONF["index_head_dim"]
    assert lanes * 2 * CONF["num_hidden_layers"] == 7680


def test_the_cell_rehearses_behind_more_than_topk_tokens_with_latents_and_keys_held(capsys):
    seed = 2**31 + 29
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", "2", "--rehearse"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    reported = set(line["metrics_reported"])
    assert {"itl_p95_ms", "output_tok_s", "setup_s", "sched.sparse_bound_token_share.mla",
            "sched.cache_bytes_per_cached_token.mla", "kernels.moe_held_pick_share.ep16"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{seed}.trace2.json").read_text())
    check = detail["reference_check"]
    assert check["ok"] and check["tokens_compared"] == 128 and check["logprob_diff"]["max"] < 1e-4
    tiny = CONF["rehearse"]
    assert tiny["engine"]["check_context_tokens"] > 2 * tiny["published"]["index_topk"]
    delta = detail["counter_delta"]
    assert delta["sparse_bound_tokens_total"] > 0 and delta["latent_rows_written_total"] > 0
    assert delta["sparse_rows_selected_total"] <= delta["latent_rows_written_total"] * tiny["published"]["index_topk"]
    # the reference said what it held the bound prompts to
    held = [json.loads(l.split(": ", 1)[1]) for l in out.err.splitlines() if l.startswith("perfbench mla_dsa_moe_share: ")]
    assert len(held) == 4 and all(h["least"] == [1.0, 1.0] and h["first_layer_latent_row_error"] < 1e-5 for h in held)


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Instruction texts as a v5e trace of this cell gave them (my chip run, PR 48).
OPS = {
    "%fusion.708 = bf16[65536,640]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[5,24576,1,16,640]{4,3,2,1,0:T(8,128)(2,1)} %llmd.latent_write.30, s32[65536]{0:T(1024)S(1)} %bitcast.685), kind=kCustom": 0.020,
    "%fusion.713 = f32[32,128,2048]{2,1,0:T(8,128)S(1)} fusion(bf16[32,2048,640]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.686, pred[32,2048]{1,0:T(8,128)(4,1)} %live, bf16[32,128,640]{2,1,0} %q), kind=kOutput": 0.010,
    "%select_reduce_fusion.2 = (s32[32,2048]{1,0:T(8,128)S(1)}, s32[32,2048]{1,0:T(8,128)S(1)}) fusion(s32[32,256]{1,0:T(8,128)S(1)} %ends, s32[32,256]{1,0} %count), kind=kLoop": 0.004,
    "%fusion.705 = s32[32,2048,24]{1,2,0:T(8,128)S(1)} fusion(s32[32,2048]{1,0:T(8,128)S(1)} %b, bf16[32,256,8]{1,2,0:T(8,128)(2,1)S(1)} %hi), kind=kOutput": 0.002,
    "%fusion.701 = s32[32,256,128]{1,0,2:T(8,128)S(1)} fusion(s32[32,256,128]{1,0,2:T(8,128)S(1)} %copy.408), kind=kOutput": 0.004,
    "%convert_reduce_fusion.3 = s32[32]{0:T(512)S(1)} fusion(u32[32,32768]{1,0} %fusion.9, u32[32]{0} %x)": 0.004,
    "%broadcast_compare_fusion = pred[32,32768]{1,0:T(8,128)(4,1)} fusion(u32[32,32768]{1,0} %fusion.9)": 0.001,
    "%llmd.indexer.15 = f32[32,32768]{1,0:T(8,128)S(1)} custom-call(s32[32]{0:T(128)S(1)} %r, s32[34,2048]{1,0:T(8,128)S(1)} %table, bf16[2,16,64,128]{3,2,1,0} %q)": 0.030,
    "%llmd.latent_write.5 = bf16[5,24576,1,16,640]{4,3,2,1,0} custom-call(s32[1]{0} %l, bf16[1,96,640]{2,1,0} %n)": 0.002,
    "%cond.1.clone = (pred[32,32768]{1,0}) conditional(pred[] %p)": 0.5,  # a container: left out
    "%gmm.13 = f32[32,2048]{1,0} custom-call(bf16[32,7168]{1,0} %x, bf16[4,16,7168,2048]{3,2,1,0} %w)": 0.4,
    "%fusion.9 = bf16[32,2048]{1,0} fusion(bf16[32,7168]{1,0} %x, bf16[7168,2048]{1,0} %ws_up), kind=kOutput": 0.1,
}


def test_the_sparse_latent_read_is_found_by_its_shapes_and_held_to_its_selected_rows():
    ctx = {"trace": {"op_seconds": OPS, "busy_s": 1.0}, "config": CONF, "bench_dir": str(BENCH), "series": {},
           "device": {"kind": "TPU v5 lite"},
           "counter_delta": {"sparse_rows_selected_total": 10**9, "latent_rows_written_total": 10**6},  # the window's: never read
           "counter_delta_traced": {"sparse_rows_selected_total": 5 * 24 * 2048 * 40, "latent_rows_written_total": 5 * 24 * 40}}
    d = reducers.definition("per_layer", "kernels.sparse_mla_time_share")
    assert abs(_reader("kernels.sparse_mla_time_share").read(ctx, d) - 4.0) < 1e-9  # not the experts' [T, 2048]
    assert abs(_reader("kernels.sparse_select_time_share.mla").read(
        ctx, reducers.definition("per_layer", "kernels.sparse_select_time_share.mla")) - 0.5) < 1e-9
    for name, want in (("kernels.indexer_time_share.mla", 3.0), ("kernels.latent_write_time_share", 0.2)):
        assert abs(reducers.reduce("per_layer", name, ctx) - want) < 1e-9
    rows, tokens = 5 * 24 * 2048 * 40, 5 * 24 * 40
    flops, nbytes = 2.0 * rows * 128 * (576 + 512), 2 * (rows * 640 + tokens * 128 * (640 + 512))
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == sparse_mla_trace.least_seconds(ctx) and 0.8 < (flops / 197e12) / (nbytes / 819e9) < 1.25  # balanced
    d = reducers.definition("per_layer", "kernels.sparse_mla_roofline")
    mod = _reader("kernels.sparse_mla_roofline")
    share = mod.read(ctx, d)
    assert 0 < share < 100 and abs(share - 100 * least / 0.040) < 1e-9
    # Nothing caps it: events timed faster than the selected rows can move read over 100 %.
    fast = {k: v / 100 for k, v in OPS.items()}
    assert mod.read(dict(ctx, trace={"op_seconds": fast, "busy_s": 0.01}), d) > 100
    # A program without the counters or the events (the parent), a configuration
    # without an indexer over a latent cache, a run without a trace: nothing, and nothing raised.
    for name in ("kernels.sparse_mla_roofline", "kernels.sparse_mla_time_share", "kernels.sparse_select_time_share.mla"):
        dd, m = reducers.definition("per_layer", name), _reader(name)
        assert m.read(dict(ctx, trace={"op_seconds": {"%gmm.1 = f32[8,8]": 1.0}, "busy_s": 1.0}), dd) is None
        assert m.read(dict(ctx, config={}), dd) is None
        assert m.read(dict(ctx, trace=None), dd) is None
    assert mod.read(dict(ctx, counter_delta_traced={}), d) is None
    assert mod.read(dict(ctx, counter_delta_traced=None), d) is None
