"""The nemotron-3-nano-30b-a3b cell's own tests: CPU only, the tiny preset. Not
collected by the repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_nemotron_cell.py -q
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run  # noqa: E402

CELL = "nemotron-3-nano-30b-a3b.long-decode"
CONFIG = "nemotron-3-nano-30b-a3b.1chip"
BENCH = ROOT / "perfbench"


def test_the_cell_rehearses_and_its_comparison_decodes_long_among_other_rows(capsys):
    seed = 2**31 + 42
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "4", "--trace", "2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    spec = run.load(CELL)
    reported = set(line["metrics_reported"])
    assert set(spec.end_to_end) == {"itl_p95_ms", "output_tok_s", "setup_s"} <= reported
    assert {"kernels.moe_held_pick_share.ep8", "sched.live_tokens_per_step", "runner.decode_step_ms",
            "runner.compiles_in_window.resident"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{seed}.trace2.json").read_text())
    check = detail["reference_check"]
    assert check["ok"] and check["complete"] and check["tokens_compared"] == 128
    assert check["logprob_diff"]["max"] < 1e-4
    log = dict()
    for k, v in detail["setup_log"]:
        log.setdefault(k, []).append(v)
    # (v): eight prompts' longer decode held to the reference, pooled after each four; (iv): four slots and the snapshot
    assert len(log["decode_check.max"]) == 2 and max(log["decode_check.max"]) < 1e-4
    assert len(log["state_check.slot.head_max"]) == 4 and len(log["state_check.snapshot.head_max"]) == 1
    # (v) at the cell's own load: max_num_seqs less the pair decode beside it, none of them gone in a compared step
    geo = dict(spec.config["engine"], **spec.config["rehearse"]["engine"])
    assert geo["check_background_rows"] == geo["max_num_seqs"] - 2
    assert len(log["decode_check.live_rows"]) == 2 and min(log["decode_check.live_rows"]) > geo["check_background_rows"]
    delta = detail["counter_delta"]
    assert detail["result"]["metrics"]["runner.compiles_in_window.resident"]["value"] == 0
    assert delta["ssm_update_rows_total"] > 0 and delta["moe_picks_held_total"] > 0
    # (the rehearsal's 8 slots serve 128 clients in turn, so no sequence comes
    # back to its own snapshot inside a window of seconds: the restart is the
    # next test's)
    assert detail["kernel_plans"]["ssm_update"] == ["xla:platform"]


def test_the_comparison_sees_a_miss_then_hits_a_restart_is_a_hit_and_the_controls_are_told():
    import argparse

    from perfbench import correctness, tolerance_probe_mixer as probe

    args = argparse.Namespace(workload=CELL, seed=2**31 + 43, seconds=1.0, trace=0, rehearse=True, root=str(ROOT))
    spec, _mix, system = run.prepare(args)
    try:
        conf = run.published(spec.config["rehearse"]["published"])
        got = correctness.sample(system, conf, spec.config["reference"], args.seed)
        assert got["complete"] and [c[1:3] for c in system.check_log] == [(0, 0), (0, 1), (1, 0), (1, 0)]
        assert system.withhold and min(system.live_rows) > system.geo["check_background_rows"]
        assert [e["what"] for e in system.state_log] == ["slot", "slot", "snapshot", "slot", "slot"]
        assert all(e["ok"] and e["head_max"] < 1e-4 for e in system.state_log)
        assert [d["tokens"] for d in system.decode_log] == [80, 160] and all(d["ok"] for d in system.decode_log)
        assert len(system.decoded) == 8
        import importlib

        ref = importlib.import_module(f"perfbench.references.{spec.config['reference']}")
        wrong = probe.controls(ref, system.reference_params(), conf)
        assert set(wrong) == {"state_bf16", "one_group", "norm_whole", "silu_for_relu2", "no_scaling", "router_held"}
        one = system.state_errors(conf=wrong["one_group"][1])
        assert min(e["head_median"] for e in one) > 0.1 and not any(e["ok"] for e in one)
        rounded = system.state_errors(conf=wrong["state_bf16"][1])
        assert min(e["head_max"] for e in rounded) > 30 * max(e["head_max"] for e in system.state_log)
        assert len(system._back) == system.geo["check_background_rows"]  # kept through both calls ...
        system.release_background()  # ... and gone before the system serves
        assert not system._back and not system.engine.has_work()
        # the cell's traffic: a resident sequence runs to the model length and
        # comes back with its context: a snapshot hit at its last full page
        eng, page = system.engine, system.geo["page_size"]
        context = system._tokens(__import__("numpy").random.default_rng(5), 430)
        room = system.max_model_len - len(context) - 1
        seen = []
        for _ in range(2):
            eng._refresh_gauges()
            before = (eng.stats.state_snapshot_hits_total, eng.stats.state_snapshot_misses_total)
            eng.add_request(list(context), system._sampling(room))
            req = eng.scheduler.waiting[-1]
            while eng.has_work():
                eng.step()
            eng._refresh_gauges()
            seen.append((len(req.output_token_ids), req.num_cached_tokens,
                         eng.stats.state_snapshot_hits_total - before[0], eng.stats.state_snapshot_misses_total - before[1]))
        assert seen == [(room, 0, 0, 0), (room, (len(context) - 1) // page * page, 1, 0)]
    finally:
        system.stop()


def test_the_configuration_keeps_the_catalogs_keys_and_the_pools_fit_the_traffic():
    conf = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    manifest = run.load(CELL).manifest
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"], conf["vocab_size"]) == (28, 16, 16384)
    assert conf["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert len(conf["hybrid_override_pattern"]) == 52 and conf["hybrid_override_pattern"][:28] == "MEMEM*E" * 4
    # the published widths, as the catalog row has them
    assert (conf["hidden_size"], conf["moe_intermediate_size"], conf["moe_shared_expert_intermediate_size"]) == (2688, 1856, 3712)
    assert (conf["mamba_num_heads"], conf["mamba_head_dim"], conf["ssm_state_size"], conf["n_groups"]) == (64, 64, 128, 8)
    assert (conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"], conf["num_experts_per_tok"]) == (32, 2, 128, 6)
    # kernels.moe_experts_touched_share finds the experts HELD under the published name
    d = reducers.definition("per_layer", "kernels.moe_experts_touched_share")
    assert next(conf[k] for k in d["den_config"] if conf.get(k)) == 16
    cell, geo = json.loads((BENCH / "cells" / f"{CELL}.json").read_text()), conf["engine"]
    assert cell["clients"] == geo["max_num_seqs"] == geo["state_snapshots"] == 128  # a slot and a snapshot a sequence
    # the comparison runs at the window's load: every running slot taken (short rows: only their number bears on the program)
    assert geo["check_background_rows"] == geo["max_num_seqs"] - 2
    lo, hi = geo["check_background_context"]  # a row decodes from its admission through all four pairs: room to the model length
    assert geo["page_size"] < lo <= hi <= geo["max_model_len"] - 768
    # a decode step of every resident row leaves a restart's chunk room in the token budget
    assert geo["max_num_seqs"] + geo["page_size"] <= geo["max_num_batched_tokens"]
    assert cell["clients"] * geo["max_model_len"] <= geo["num_pages"] * geo["page_size"]
    # the new metrics are this cell's alone, and no accepted list grew
    new = {"kernels.ssm_grouped_time_share", "kernels.ssm_grouped_update_roofline", "sched.state_restart_hit_share",
           "kernels.moe_held_pick_share.ep8", "kernels.ssm_grouped_xla_time_share", "kernels.block_attn_time_share",
           "runner.compiles_in_window.resident"}
    for m in manifest["per_layer"]:
        assert (m["name"] in new) == (CELL in m.get("workloads", [])), m["name"]


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Event names as the compiler gives them (the step compiled for a described
# v5e, perfbench/rehearse_compile_mixer.py), with the operands' shapes a trace
# event carries.
UPDATE = ("%llmd.ssm.update.36 = (f32[12,257,64,64,128]{4,3,2,1,0:T(8,128)}, f32[136,2,64,32]{3,2,1,0:T(8,128)}) "
          "custom-call(s32[136]{0} %slots, s32[1]{0} %cnt, s32[1]{0} %layer, f32[12,257,64,64,128]{4,3,2,1,0} %ssm.1, "
          "f32[136,2,2,64,32]{4,3,2,1,0} %ax, f32[136,2,4,128]{3,2,1,0} %b, f32[136,2,4,128]{3,2,1,0} %c), "
          "custom_call_target=\"tpu_custom_call\"")
SCAN = ("%llmd.ssm.scan.25 = f32[12,257,64,64,128]{4,3,2,1,0} custom-call(s32[1]{0} %s, s32[1]{0} %l, "
        "f32[12,257,64,64,128]{4,3,2,1,0} %p, f32[64,64,128]{2,1,0} %v)")


def test_the_grouped_update_roofline_charges_a_row_its_groups_b_and_c():
    mod = _reader("kernels.ssm_grouped_update_roofline")
    flops, nbytes = mod.row_cost(UPDATE, 8)
    state = 64 * 64 * 128
    assert flops == 5.0 * state and nbytes == 2 * state * 4 + 3 * 64 * 64 * 4 + 2 * 8 * 128 * 4
    ops = {UPDATE.replace(".36 ", f".{i} "): 8e-4 for i in range(12)}  # 12 calls of 128 live rows
    ctx = {"trace": {"op_seconds": {**ops, SCAN: 1e-3, "%gmm.1 = f32[8,8]": 1.0}, "op_calls": {}, "busy_s": 2.0},
           "bench_dir": str(BENCH), "device": {"kind": "TPU v5 lite"}, "config": {"n_groups": 8},
           "counter_delta": {"ssm_update_rows_total": 10**6},  # the window's: never read
           "counter_delta_traced": {"ssm_update_rows_total": 12 * 128}}
    d = reducers.definition("per_layer", "kernels.ssm_grouped_update_roofline")
    share = mod.read(ctx, d)
    assert abs(share - 100 * (12 * 128 * nbytes / 819e9) / (12 * 8e-4)) < 1e-9 and 0 < share < 100
    # Nothing caps it; a program without the counter or the kernel, or the
    # parent's configuration (no n_groups), says nothing and raises nothing.
    assert mod.read(dict(ctx, trace=dict(ctx["trace"], op_seconds={UPDATE: 1e-6})), d) > 100
    assert mod.read(dict(ctx, counter_delta_traced={}), d) is None
    assert mod.read(dict(ctx, counter_delta_traced=None), d) is None
    assert mod.read(dict(ctx, config={}), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


IN_PROJ = ("%fusion.1120 = bf16[128,10304]{1,0:T(8,128)(2,1)} fusion(bf16[12,2688,10304]{2,1,0} %p, s32[] %l, "
           "bf16[128,1,2688]{2,1,0} %h, f32[128]{0} %r, bf16[2688]{0} %w), kind=kOutput, calls=%fused_computation.250")
OUT_PROJ = ("%fusion.1130 = bf16[128,1,2688]{2,1,0} fusion(bf16[128,1,2688]{2,1,0} %x, bf16[12,4096,2688]{2,1,0} %w, "
            "s32[] %l, f32[128,4096]{1,0} %y), kind=kOutput")
CONV = "%add_add_fusion.6 = f32[128,6144]{0,1} fusion(f32[125,6144]{1,0} %a, f32[126,6144]{1,0} %b, f32[6144]{0} %w)"
GATHER = "%fusion.1196 = f32[8,136,128]{2,1,0} fusion(f32[128,8,128]{2,1,0} %b, s32[136]{0} %tok), kind=kCustom"
SCAN_ROW = "%fusion.1338 = f32[64,64,8,8]{1,0,3,2} fusion(f32[64,64]{1,0} %cum, pred[64,64]{1,0} %tril), kind=kLoop"
NOT_MIXER = {  # the shared expert, the router, the head, the attention blocks' q- and out-projection, the kernels
    "%fusion.7 = bf16[128,3712]{1,0} fusion(bf16[128,2688]{1,0} %h, bf16[12,2688,3712]{2,1,0} %w, s32[] %l)": 1.0,
    "%fusion.8 = (f32[128]{0}, f32[128,128]{1,0}) fusion(bf16[128,2688]{1,0} %h, bf16[12,2688,128]{2,1,0} %r)": 1.0,
    "%fusion.10 = bf16[128,16384]{1,0} fusion(bf16[128,2688]{1,0} %h, bf16[2688,16384]{1,0} %e)": 1.0,
    "%fusion.11 = bf16[128,4096]{1,0} fusion(bf16[128,2688]{1,0} %h, bf16[4,2688,4096]{2,1,0} %wq, s32[] %l)": 1.0,
    "%fusion.12 = bf16[128,1,2688]{2,1,0} fusion(bf16[128,4096]{1,0} %a, bf16[4,4096,2688]{2,1,0} %wo, s32[] %l)": 1.0,
    "%gmm.1 = f32[768,1920]{1,0} custom-call(s32[] %l, s32[17]{0} %g, bf16[768,2688]{1,0} %x, bf16[12,16,2688,1920]{3,2,1,0} %w)": 1.0,
    "%llmd.block.attn.25 = bf16[128,2,16,128]{3,2,1,0} custom-call(bf16[128,2,16,128]{3,2,1,0} %q)": 1.0,
    "%while.2 = (f32[64,64,128]{2,1,0}) while((f32[64,64,128]{2,1,0}) %t)": 9.0,  # spans its body's events
}


def test_the_grouped_time_share_reads_the_whole_mixer_and_not_the_attention_of_the_same_width():
    mod = _reader("kernels.ssm_grouped_time_share")
    conf = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    d = reducers.definition("per_layer", "kernels.ssm_grouped_time_share")
    mixer = {UPDATE: 0.6, SCAN: 0.011, IN_PROJ: 0.1, OUT_PROJ: 0.05, CONV: 0.02, GATHER: 0.01, SCAN_ROW: 0.03}
    ctx = {"trace": {"op_seconds": {**mixer, **NOT_MIXER}, "op_calls": {}, "busy_s": 2.0}, "config": conf,
           "bench_dir": str(BENCH), "device": {"kind": "TPU v5 lite"}, "series": {}, "cell": {},
           "counter_delta": {}, "counter_delta_traced": {}}
    assert abs(reducers.reduce("per_layer", "kernels.ssm_grouped_time_share", ctx) - 100 * sum(mixer.values()) / 2.0) < 1e-9
    named = dict(ctx, trace=dict(ctx["trace"], op_seconds={UPDATE: 0.6, SCAN: 0.011, **NOT_MIXER}))
    assert abs(mod.read(named, d) - 100 * 0.611 / 2.0) < 1e-9
    # the XLA part alone (the Pallas calls left out), and the attention blocks under their own name
    xla = reducers.reduce("per_layer", "kernels.ssm_grouped_xla_time_share", ctx)
    assert abs(xla - 100 * (sum(mixer.values()) - 0.611) / 2.0) < 1e-9
    assert reducers.reduce("per_layer", "kernels.ssm_grouped_xla_time_share", named) is None
    assert abs(reducers.reduce("per_layer", "kernels.block_attn_time_share", ctx) - 100 * 1.0 / 2.0) < 1e-9
    assert reducers.reduce("per_layer", "kernels.block_attn_time_share", dict(ctx, trace=None)) is None
    # the update's operands by head block: the kernel's own rule, and no other [a, b, 32, 64]
    from llmd_tpu.ops.ssm import _head_block
    for heads, groups in ((64, 8), (128, 8), (64, 2), (48, 3), (24, 8), (4, 2)):
        assert mod.head_block(heads, heads // groups) == _head_block(heads, heads // groups), (heads, groups)
    other = "%fusion.9 = f32[136,5,32,64]{3,2,1,0} fusion(f32[136,5,32,64]{3,2,1,0} %x), kind=kLoop"
    y_out = "%fusion.13 = f32[136,2,64,32]{3,2,1,0} fusion(f32[136,2,64,32]{3,2,1,0} %y), kind=kLoop"
    only = lambda ops: mod.read(dict(ctx, trace=dict(ctx["trace"], op_seconds=ops)), d)  # noqa: E731
    assert only({other: 1.0}) is None and abs(only({y_out: 1.0}) - 50.0) < 1e-9
    # another configuration (the parent's files know no such keys), a program
    # without the mixers, no trace: nothing, and nothing raised
    granite = json.loads((BENCH / "configs" / "granite-4.0-h-small.1chip.json").read_text())
    assert mod.read(dict(ctx, config=granite), d) is None
    assert mod.read(dict(ctx, trace=dict(ctx["trace"], op_seconds=NOT_MIXER)), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


def test_the_counter_metrics_of_the_cell():
    delta = {"state_snapshot_hits_total": 99, "state_snapshot_misses_total": 1, "moe_picks_held_total": 125, "moe_picks_total": 1000}
    ctx = {"series": {}, "counter_delta": delta, "counter_delta_traced": None, "trace": None, "device": {},
           "config": {}, "cell": {}, "bench_dir": str(BENCH)}
    assert reducers.reduce("per_layer", "sched.state_restart_hit_share", ctx) == 99.0
    assert reducers.reduce("per_layer", "kernels.moe_held_pick_share.ep8", ctx) == 12.5
    bare = dict(ctx, counter_delta={"engine_steps_total": 3})  # a program without the counters
    assert reducers.reduce("per_layer", "sched.state_restart_hit_share", bare) is None
    assert reducers.reduce("per_layer", "kernels.moe_held_pick_share.ep8", bare) is None
