"""The qwen3-next-80b-a3b cell's own tests: CPU only, the tiny preset. Not
collected by the repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_qwen3_next_cell.py -q
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run  # noqa: E402

CELL = "qwen3-next-80b-a3b.long-doc-sessions"
CONFIG = "qwen3-next-80b-a3b.1chip"
BENCH = ROOT / "perfbench"
NEW = {"kernels.gdn_update_time_share", "kernels.gdn_scan_time_share", "kernels.gdn_update_roofline",
       "kernels.gated_attn_time_share", "sched.state_snapshot_hit_share.gdn",
       "sched.cache_bytes_per_cached_token.gdn", "kernels.moe_held_pick_share.ep8x512"}


def test_the_cell_rehearses_and_its_comparison_carries_a_state_through_many_rows(capsys):
    seed = 2**31 + 44
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "4", "--trace", "2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    spec = run.load(CELL)
    reported = set(line["metrics_reported"])
    assert set(spec.end_to_end) == {"itl_p95_ms", "output_tok_s", "setup_s"} <= reported
    assert {"kernels.moe_held_pick_share.ep8x512", "sched.state_snapshot_hit_share.gdn",
            "sched.cache_bytes_per_cached_token.gdn", "sched.live_tokens_per_step", "runner.decode_step_ms"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{seed}.trace2.json").read_text())
    check = detail["reference_check"]
    assert check["ok"] and check["complete"] and check["tokens_compared"] == 128
    assert check["logprob_diff"]["max"] < 1e-3
    log = dict()
    for k, v in detail["setup_log"]:
        log.setdefault(k, []).append(v)
    assert len(log["decode_check.max"]) == 2 and max(log["decode_check.max"]) < 1e-3
    assert len(log["state_check.slot.head_max"]) == 4 and len(log["state_check.snapshot.head_max"]) == 1
    assert max(log["state_check.slot.head_max"] + log["state_check.snapshot.head_max"]) < 1e-4
    # (vi): the first attention layer's cached keys of each of the four bound prompts
    assert len(log["key_check.token_median"]) == 4 and max(log["key_check.token_median"]) < 1e-4
    assert log["key_check.far_share"] == [0.0] * 4
    geo = dict(spec.config["engine"], **spec.config["rehearse"]["engine"])
    assert geo["check_background_rows"] == geo["max_num_seqs"] - 2
    assert len(log["decode_check.live_rows"]) == 2 and min(log["decode_check.live_rows"]) > geo["check_background_rows"]
    delta = detail["counter_delta"]
    assert delta["gdn_update_rows_total"] > 0 and delta["gdn_scan_rows_total"] > 0 and delta["gdn_state_bytes_moved_total"] > 0
    assert delta["ssm_update_rows_total"] == 0 and delta["moe_picks_held_total"] > 0
    assert delta["state_snapshot_hits_total"] > 0  # a session's next turn hits what its last one left
    assert detail["kernel_plans"]["gdn_update"] == ["xla:platform"]


def test_the_comparison_sees_a_miss_then_hits_and_the_controls_are_told():
    import argparse

    from perfbench import correctness, tolerance_probe_gdn as probe

    args = argparse.Namespace(workload=CELL, seed=2**31 + 45, seconds=1.0, trace=0, rehearse=True, root=str(ROOT))
    spec, _mix, system = run.prepare(args)
    try:
        conf = run.published(spec.config["rehearse"]["published"])
        got = correctness.sample(system, conf, spec.config["reference"], args.seed)
        assert got["complete"] and [c[1:3] for c in system.check_log] == [(0, 0), (0, 1), (1, 0), (1, 0)]
        assert system.withhold and min(system.live_rows) > system.geo["check_background_rows"]
        assert [e["what"] for e in system.state_log] == ["slot", "slot", "snapshot", "slot", "slot"]
        assert all(e["ok"] and e["head_max"] < 1e-4 for e in system.state_log)
        assert [d["tokens"] for d in system.decode_log] == [80, 160] and all(d["ok"] for d in system.decode_log)
        # (vi): the full pages of context + prompt + decoded tokens of each bound prompt, a key a token
        assert len(system.key_log) == len(system.keys_seen) == 4 and all(e["ok"] for e in system.key_log)
        geo = system.geo
        assert all(geo["check_context_tokens"] + 64 <= e["tokens"] <= geo["check_context_tokens"] + 256 + 20
                   and e["token_p99"] < 1e-4 for e in system.key_log)
        ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
        wrong = probe.controls(ref, system.reference_params(), conf)
        assert set(wrong) == {"state_bf16", "beta_raw", "decay_after", "no_attn_gate", "full_rotation",
                              "shared_ungated", "one_expert_fewer", "snapshot_stale"}
        # what only the pooled state tells: each of these moves it far outside a sound run's reading
        sound = max(e["head_max"] for e in system.state_log)
        for name in ("state_bf16", "beta_raw", "decay_after"):
            errs = [e for e in system.state_errors(conf=wrong[name][1]) if e["what"] != "keys"]
            assert len(errs) == 5 and min(e["head_max"] for e in errs) > 30 * max(sound, 1e-6), name
        stale = system.state_errors(conf=wrong["snapshot_stale"][1])
        assert [e["head_max"] > 0.1 for e in stale if e["what"] == "snapshot"] == [True]
        # what the cached keys tell and the log-probs of the real model cannot: a full-width rotation; and they
        # tell every fault upstream of the first attention layer a second time (a state's errors come back with
        # the keys' behind them)
        for name in ("full_rotation", "one_expert_fewer", "shared_ungated", "beta_raw", "snapshot_stale"):
            errs = [e for e in system.state_errors(conf=wrong[name][1]) if e["what"] == "keys"]
            assert len(errs) == 4 and min(e["token_p99"] for e in errs) > 0.05, name
        turned = system.key_errors(conf=wrong["full_rotation"][1])
        assert min(e["token_median"] for e in turned) > 0.5 and not any(e["ok"] for e in turned)
        assert all(e["ok"] for e in system.key_errors(conf=wrong["no_attn_gate"][1]))  # nothing upstream has a gate
        # what the log-probs tell: the four that lie behind the first layer's state
        lps = [lp for _p, _at, lp in system.decoded]
        for name in ("no_attn_gate", "full_rotation", "shared_ungated", "one_expert_fewer"):
            p, c = wrong[name]
            worst = 0.0
            for (padded, at, _lp), lp in zip(system.decoded[:2], lps):
                nxt, _b = ref.forward(p, padded, c)
                worst = max(worst, float(abs(__import__("numpy").asarray(nxt)[at] - lp).max()))
            assert worst > 1e-2, name
        system.release_background()
        assert not system._back and not system.engine.has_work()
    finally:
        system.stop()


def test_the_configuration_keeps_the_catalogs_keys_and_the_pools_fit_the_traffic():
    conf = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    manifest = run.load(CELL).manifest
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (12, 64, 18992)
    assert conf["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert conf["published"]["vocab_size"] == 8 * conf["vocab_size"] and conf["num_hidden_layers"] % conf["full_attention_interval"] == 0
    catalog = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():  # every number of the catalog row under the same key, but the three in `reduced`
        row = next(json.loads(ln) for ln in catalog.read_text().splitlines() if '"Qwen3-Next-80B-A3B-Instruct"' in ln)
        assert entry["source"] == conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])
    # the published widths
    assert (conf["hidden_size"], conf["head_dim"], conf["moe_intermediate_size"], conf["shared_expert_intermediate_size"]) == (
        2048, 256, 512, 512)
    assert [conf[f"linear_{k}"] for k in ("num_key_heads", "num_value_heads", "key_head_dim", "value_head_dim", "conv_kernel_dim")] == [
        16, 32, 128, 128, 4]
    assert (conf["num_attention_heads"], conf["num_key_value_heads"], conf["num_experts_per_tok"], conf["partial_rotary_factor"]) == (
        16, 2, 10, 0.25)
    # kernels.moe_gmm_roofline's share of experts with rows finds the experts HELD under the published name
    d = reducers.definition("per_layer", "kernels.moe_experts_touched_share")
    assert next(conf[k] for k in d["den_config"] if conf.get(k)) == 64
    cell, geo = json.loads((BENCH / "cells" / f"{CELL}.json").read_text()), conf["engine"]
    keye = json.loads((BENCH / "cells" / "keye-vl-2.0-30b-a3b.long-doc-sessions.json").read_text())
    assert cell["clients"] == geo["max_num_seqs"] == keye["clients"] == 24 and cell["topology"] == "engine_gdn"
    assert run.load(CELL).entry["traffic"] == run.load("keye-vl-2.0-30b-a3b.long-doc-sessions").entry["traffic"]
    assert geo["state_snapshots"] == 4 * geo["max_num_seqs"] and geo["check_background_rows"] == geo["max_num_seqs"] - 2
    assert geo["check_context_tokens"] == 64 * 64  # 64 rows of the scan a layer
    mix = run.load(CELL).mix
    assert mix["context_cap"] + mix["answer"]["max"] <= geo["max_model_len"]
    # the documents and every session's history at the cap fit the main pool
    assert mix["groups"] * mix["prefix_tokens"] + cell["clients"] * (mix["context_cap"] - mix["prefix_tokens"]) <= (
        geo["num_pages"] * geo["page_size"])
    # a decode step of every running row leaves a question's chunk room in the token budget
    assert geo["max_num_seqs"] + 64 <= geo["max_num_batched_tokens"]
    # the new metrics are this cell's alone, and no accepted list grew
    for m in manifest["per_layer"]:
        assert (m["name"] in NEW) == (CELL in m.get("workloads", [])), m["name"]
    assert manifest["workloads"][-1]["name"] == CELL and manifest["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in manifest["per_layer"][-len(NEW):]] and {m["name"] for m in manifest["per_layer"][-len(NEW):]} == NEW


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Event names as the compiler gives them (the step compiled for a described
# v5e, perfbench/rehearse_compile_gdn.py), with the operands' shapes a trace
# event carries.
UPDATE = ("%llmd.gdn.update.12 = (f32[9,121,32,128,128]{4,3,2,1,0:T(8,128)}, f32[32,2,16,128]{3,2,1,0:T(8,128)}) "
          "custom-call(s32[32]{0} %slots, s32[1]{0} %cnt, s32[1]{0} %layer, f32[9,121,32,128,128]{4,3,2,1,0} %pool, "
          "f32[32,2,2,128,16]{4,3,2,1,0} %qk, f32[32,2,3,16,128]{4,3,2,1,0} %vab), custom_call_target=\"tpu_custom_call\"")
SLOT = ("%llmd.gdn.scan.25 = f32[9,121,32,128,128]{4,3,2,1,0} custom-call(s32[1]{0} %s, s32[1]{0} %l, "
        "f32[9,121,32,128,128]{4,3,2,1,0} %p, f32[32,128,128]{2,1,0} %v)")
SCAN_OPS = {
    "%fusion.401 = f32[32,64,64]{2,1,0} fusion(f32[32,64]{1,0} %cum, pred[64,64]{1,0} %tril), kind=kLoop": 0.02,
    "%fusion.402 = f32[32,64,128]{2,1,0} fusion(f32[32,64,64]{2,1,0} %inv, f32[32,64,128]{2,1,0} %rhs), kind=kOutput": 0.03,
    "%fusion.403 = f32[32,128,128]{2,1,0} fusion(f32[32,64,128]{2,1,0} %k, f32[32,64,128]{2,1,0} %d), kind=kOutput": 0.04,
    "%copy.9 = f32[32,208,128]{2,1,0} copy(f32[208,32,128]{2,1,0} %q)": 0.01,
}
NOT_SCAN = {  # the projections, the gate and norm, the conv, the update, the experts, the attention
    "%fusion.7 = bf16[144,12288]{1,0} fusion(bf16[144,2048]{1,0} %h, bf16[9,2048,12288]{2,1,0} %w, s32[] %l)": 1.0,
    "%fusion.8 = f32[144,32,128]{2,1,0} fusion(f32[144,32,128]{2,1,0} %y, bf16[144,32,128]{2,1,0} %z)": 1.0,
    "%fusion.9 = f32[32,32,128]{2,1,0} fusion(f32[32,32,128]{2,1,0} %y)": 1.0,  # [T = 32, heads, dim] of a decode step
    "%add_add_fusion.6 = f32[144,8192]{0,1} fusion(f32[141,8192]{1,0} %a, f32[8192]{0} %w)": 1.0,
    UPDATE: 0.5,
    "%gmm.1 = f32[256,512]{1,0} custom-call(s32[] %l, s32[65]{0} %g, bf16[256,2048]{1,0} %x, bf16[12,64,2048,512]{3,2,1,0} %w)": 1.0,
    "%llmd.block.attn.25 = bf16[144,2,8,256]{3,2,1,0} custom-call(bf16[144,2,8,256]{3,2,1,0} %q)": 0.7,
    "%while.2 = (f32[32,128,128]{2,1,0}) while((f32[32,128,128]{2,1,0}) %t)": 9.0,  # spans its body's events
}


def _ctx(ops, conf, traced):
    return {"trace": {"op_seconds": ops, "op_calls": {}, "busy_s": 10.0}, "config": conf, "bench_dir": str(BENCH),
            "device": {"kind": "TPU v5 lite"}, "series": {}, "cell": {}, "counter_delta": {"gdn_update_rows_total": 10**9},
            "counter_delta_traced": traced}


def test_the_update_roofline_charges_a_live_row_its_state_twice_and_nothing_else_of_size():
    mod = _reader("kernels.gdn_update_roofline")
    conf = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    flops, nbytes = mod.row_cost(conf)
    state = 32 * 128 * 128
    assert flops == 7.0 * state and nbytes == 4 * (2 * state + 2 * 32 * 128 + 2 * 32 * 128 + 2 * 32)
    ops = {UPDATE.replace(".12 ", f".{i} "): 2e-3 for i in range(9)}  # nine layers' calls, 24 live rows each
    d = reducers.definition("per_layer", "kernels.gdn_update_roofline")
    ctx = _ctx({**ops, SLOT: 1e-3, **SCAN_OPS}, conf, {"gdn_update_rows_total": 9 * 24})
    share = mod.read(ctx, d)
    assert abs(share - 100 * (9 * 24 * nbytes / 819e9) / (9 * 2e-3)) < 1e-9 and 0 < share < 100
    assert abs(reducers.reduce("per_layer", "kernels.gdn_update_roofline", ctx) - share) < 1e-9
    # nothing caps it; a program without the counter or the kernel, another
    # configuration, or no trace says nothing and raises nothing
    assert mod.read(_ctx({UPDATE: 1e-6}, conf, {"gdn_update_rows_total": 24}), d) > 100
    assert mod.read(_ctx(ops, conf, {}), d) is None and mod.read(_ctx(ops, conf, None), d) is None
    assert mod.read(_ctx(SCAN_OPS, conf, {"gdn_update_rows_total": 24}), d) is None
    granite = json.loads((BENCH / "configs" / "granite-4.0-h-small.1chip.json").read_text())
    assert mod.read(_ctx(ops, granite, {"gdn_update_rows_total": 24}), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


def test_the_time_shares_read_the_scan_by_its_shapes_and_the_calls_by_their_names():
    mod = _reader("kernels.gdn_scan_time_share")
    conf = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    d = reducers.definition("per_layer", "kernels.gdn_scan_time_share")
    ctx = _ctx({**SCAN_OPS, SLOT: 0.05, **NOT_SCAN}, conf, {})
    want = 100 * (sum(SCAN_OPS.values()) + 0.05) / 10.0
    assert abs(reducers.reduce("per_layer", "kernels.gdn_scan_time_share", ctx) - want) < 1e-9
    assert abs(reducers.reduce("per_layer", "kernels.gdn_update_time_share", ctx) - 5.0) < 1e-9
    assert abs(reducers.reduce("per_layer", "kernels.gated_attn_time_share", ctx) - 7.0) < 1e-9
    # a program without the mixer (the parent's), another configuration, no trace: nothing, and nothing raised
    assert mod.read(_ctx(NOT_SCAN | {UPDATE: 0.0}, conf, {}), d) is None
    granite = json.loads((BENCH / "configs" / "granite-4.0-h-small.1chip.json").read_text())
    assert mod.read(_ctx(SCAN_OPS, granite, {}), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None
    for name in ("kernels.gdn_update_time_share", "kernels.gated_attn_time_share"):
        assert reducers.reduce("per_layer", name, dict(ctx, trace=None)) is None


def test_the_counter_metrics_of_the_cell():
    delta = {"state_snapshot_hits_total": 99, "state_snapshot_misses_total": 1, "moe_picks_held_total": 125,
             "moe_picks_total": 1000, "kv_bytes_in_use_total": 6144 * 1000, "state_bytes_in_use_total": 4856 * 1000,
             "cached_tokens_total": 1000}
    ctx = {"series": {}, "counter_delta": delta, "counter_delta_traced": None, "trace": None, "device": {},
           "config": {}, "cell": {}, "bench_dir": str(BENCH)}
    assert reducers.reduce("per_layer", "sched.state_snapshot_hit_share.gdn", ctx) == 99.0
    assert reducers.reduce("per_layer", "kernels.moe_held_pick_share.ep8x512", ctx) == 12.5
    assert reducers.reduce("per_layer", "sched.cache_bytes_per_cached_token.gdn", ctx) == 11000.0
    bare = dict(ctx, counter_delta={"engine_steps_total": 3})  # a program without the counters
    for name in ("sched.state_snapshot_hit_share.gdn", "kernels.moe_held_pick_share.ep8x512",
                 "sched.cache_bytes_per_cached_token.gdn"):
        assert reducers.reduce("per_layer", name, bare) is None
