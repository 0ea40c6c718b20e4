"""The granite-4.0-h-small cell's own tests: CPU only, the tiny preset. Not
collected by the repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run  # noqa: E402

CELL = "granite-4.0-h-small.prefix-sessions"
BENCH = ROOT / "perfbench"


def test_the_cell_rehearses_and_its_comparison_sees_a_miss_and_two_hits(capsys):
    seed = 2**31 + 37
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", "2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    spec = run.load(CELL)
    reported = set(line["metrics_reported"])
    assert set(spec.end_to_end) == {"itl_p95_ms", "output_tok_s", "setup_s"} <= reported
    assert {"sched.state_snapshot_hit_share", "sched.cache_bytes_per_cached_token"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{seed}.trace2.json").read_text())
    check = detail["reference_check"]
    # float32 on both sides at the tiny size: exact to rounding, behind a
    # context of several chunks, through a snapshot miss and two hits (an
    # incomplete comparison is not ``ok``).
    assert check["ok"] and check["complete"] and check["tokens_compared"] == 128
    assert check["logprob_diff"]["max"] < 1e-4
    delta = detail["counter_delta"]
    assert delta["ssm_update_rows_total"] > 0 and delta["ssm_scan_tokens_total"] > 0
    assert delta["state_bytes_in_use_total"] > 0 and delta["state_snapshot_hits_total"] > 0
    assert detail["kernel_plans"]["ssm_update"] == ["xla:platform"]


def test_the_comparison_holds_the_first_mixers_state_to_the_references():
    """Four slots and the snapshot, read out of the pool: float32 on both
    sides at the tiny size, so exact to rounding; a reference whose snapshot
    is a page stale, or whose state is bfloat16, is told from it by the state
    where the log-probs of the tiny model cannot be trusted to."""
    import argparse

    from perfbench import correctness

    args = argparse.Namespace(workload=CELL, seed=2**31 + 41, seconds=1.0, trace=0, rehearse=True, root=str(ROOT))
    spec, _mix, system = run.prepare(args)
    try:
        conf = run.published(spec.config["rehearse"]["published"])
        got = correctness.sample(system, conf, spec.config["reference"], args.seed)
        assert got["complete"] and [c[1:3] for c in system.check_log] == [(0, 0), (0, 1), (1, 0), (1, 0)]
        assert [e["what"] for e in system.state_log] == ["slot", "slot", "snapshot", "slot", "slot"]
        assert all(e["ok"] and e["head_max"] < 1e-4 for e in system.state_log)
        assert {k for k, _ in system.setup_log if k.startswith("state_check.")} == {
            f"state_check.{w}.{k}" for w in ("slot", "snapshot") for k in ("head_median", "head_max")}
        stale = system.state_errors(conf=dict(conf, probe_stale_tokens=16))
        # (the tiny model's heads forget a page within the 100-250 tokens that a
        # slot runs on behind the context; the snapshot stands AT its end)
        assert [e["head_median"] > 0.05 and not e["ok"] for e in stale if e["what"] == "snapshot"] == [True]
        rounded = system.state_errors(conf=dict(conf, probe_state_dtype="bfloat16"))
        assert min(e["head_max"] for e in rounded) > 30 * max(e["head_max"] for e in system.state_log)
        assert len(system.state_log) == 5  # a control's errors are not the run's
    finally:
        system.stop()


def test_the_configuration_keeps_the_catalogs_keys_and_names_the_readers_count():
    conf = json.loads((BENCH / "configs" / "granite-4.0-h-small.1chip.json").read_text())
    entry = next(c for c in run.load(CELL).manifest["configs"] if c["name"] == "granite-4.0-h-small.1chip")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_local_experts"], conf["vocab_size"]) == (10, 36, 50176)
    assert conf["published"] == {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert conf["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and len(conf["layer_types"]) == 40
    # kernels.moe_experts_touched_share finds the experts held under its own
    # names; the published key is another (PERF.md section 7 (i))
    d = reducers.definition("per_layer", "kernels.moe_experts_touched_share")
    assert next(conf[k] for k in d["den_config"] if conf.get(k)) == conf["num_local_experts"]
    # the pools the engine builds from this geometry fit the traffic
    mix = json.loads((BENCH / "traffic" / "prefix-sessions.json").read_text())
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    own = mix["context_cap"] - mix["prefix_tokens"]
    assert mix["groups"] * mix["prefix_tokens"] + cell["clients"] * own <= conf["engine"]["num_pages"] * conf["engine"]["page_size"]
    assert conf["engine"]["max_num_seqs"] == cell["clients"]  # a slot a session; twice as many snapshots


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


UPDATE = ("%llmd.ssm.update.7 = (f32[9,97,128,64,128]{4,3,2,1,0:T(8,128)}, f32[40,8,64,16]{3,2,1,0:T(8,128)S(1)}) "
          "custom-call(s32[40]{0} %slots, s32[1]{0} %cnt, s32[1]{0} %layer, f32[9,97,128,64,128]{4,3,2,1,0} %ssm.1, "
          "f32[40,8,2,64,16]{4,3,2,1,0} %ax, f32[40,1,128]{2,1,0} %b, f32[40,1,128]{2,1,0} %c), "
          "custom_call_target=\"tpu_custom_call\"")
SCAN = "%llmd.ssm.scan.9 = f32[9,97,128,64,128]{4,3,2,1,0} custom-call(s32[1]{0} %s, s32[1]{0} %l, f32[9,97,128,64,128]{4,3,2,1,0} %p, f32[128,64,128]{2,1,0} %v)"


def test_the_update_roofline_charges_the_live_rows_their_state_twice():
    mod = _reader("kernels.ssm_update_roofline")
    flops, nbytes = mod.row_cost(UPDATE)
    state = 128 * 64 * 128
    assert flops == 5.0 * state and nbytes == 2 * state * 4 + 3 * 128 * 64 * 4 + 2 * 128 * 4
    # 9 calls (a mixer layer each) of 31 live rows, 0.4 ms each
    ops = {UPDATE.replace(".7 ", f".{i} "): 4e-4 for i in range(9)}
    ctx = {"trace": {"op_seconds": {**ops, SCAN: 1e-3, "%gmm.1 = f32[8,8]": 1.0}, "op_calls": {}, "busy_s": 2.0},
           "bench_dir": str(BENCH), "device": {"kind": "TPU v5 lite"}, "config": {},
           "counter_delta": {"ssm_update_rows_total": 10**6},  # the window's: never read
           "counter_delta_traced": {"ssm_update_rows_total": 9 * 31}}
    d = reducers.definition("per_layer", "kernels.ssm_update_roofline")
    share = mod.read(ctx, d)
    assert abs(share - 100 * (9 * 31 * nbytes / 819e9) / (9 * 4e-4)) < 1e-9 and 0 < share < 100
    # Nothing caps it; a program without the counter or the kernel says nothing.
    assert mod.read(dict(ctx, trace=dict(ctx["trace"], op_seconds={UPDATE: 1e-6})), d) > 100
    assert mod.read(dict(ctx, counter_delta_traced={}), d) is None
    assert mod.read(dict(ctx, counter_delta_traced=None), d) is None
    assert mod.read(dict(ctx, trace={"op_seconds": {"%gmm.1 = f32[8,8]": 1.0}, "op_calls": {}, "busy_s": 1.0}), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


IN_PROJ = ("%fusion.1019 = bf16[528,16768]{1,0:T(8,128)(2,1)} fusion(bf16[9,4096,16768]{2,1,0} %p, s32[] %l, "
           "bf16[528,1,4096]{2,1,0} %h, f32[528]{0} %r, bf16[4096]{0} %w), kind=kOutput, calls=%fused_computation.250")
OUT_PROJ = ("%fusion.1030 = (f32[528]{0}, bf16[528,1,4096]{2,1,0}) fusion(bf16[528,1,4096]{2,1,0} %x, "
            "bf16[9,8192,4096]{2,1,0} %w, s32[] %l, f32[528,8192]{1,0} %y, f32[8192]{0} %n), kind=kOutput")
CONV = "%add_add_fusion.3 = f32[528,8448]{1,0} fusion(f32[525,8448]{1,0} %a, f32[526,8448]{1,0} %b, f32[8448]{0} %w)"
SCAN_ROW = "%fusion.1185 = f32[64,64,128]{2,1,0} fusion(f32[64,128]{1,0} %cum, pred[64,64]{1,0} %tril), kind=kLoop"
SCAN_Y = "%fusion.1190 = f32[64,128,64]{2,1,0} fusion(f32[64,64,128]{2,1,0} %w, f32[592,128,64]{2,1,0} %x), kind=kOutput"
NOT_MIXER = {  # the shared GLU, the router, the expert rows, the head, the attention layer's q|k|v
    "%fusion.7 = bf16[528,1536]{1,0} fusion(bf16[528,4096]{1,0} %h, bf16[10,4096,1536]{2,1,0} %w, s32[] %l)": 1.0,
    "%fusion.8 = (f32[528]{0}, f32[528,72]{1,0}) fusion(bf16[528,4096]{1,0} %h, bf16[10,4096,72]{2,1,0} %r)": 1.0,
    "%fusion.9 = bf16[5280,4096]{1,0} fusion(bf16[528,4096]{1,0} %h, s32[6144]{0} %i)": 1.0,
    "%fusion.10 = bf16[40,50176]{1,0} fusion(bf16[40,4096]{1,0} %h, bf16[50176,4096]{1,0} %e)": 1.0,
    "%fusion.11 = bf16[528,6144]{1,0} fusion(bf16[528,4096]{1,0} %h, bf16[1,4096,6144]{2,1,0} %wqkv)": 1.0,
    "%gmm.1 = f32[5376,768]{1,0} custom-call(s32[] %l, s32[37]{0} %g, bf16[5376,4096]{1,0} %x)": 1.0,
    "%closed_call.3 = bf16[528,32,128]{2,1,0} custom-call(bf16[528,32,128]{2,1,0} %q)": 1.0,
    "%while.2 = (f32[128,64,128]{2,1,0}) while((f32[128,64,128]{2,1,0}) %t)": 9.0,  # spans its body's events
}


def test_the_time_share_reads_the_whole_mixer_by_name_and_by_shape():
    mod = _reader("kernels.ssm_time_share")
    conf = json.loads((BENCH / "configs" / "granite-4.0-h-small.1chip.json").read_text())
    d = reducers.definition("per_layer", "kernels.ssm_time_share")
    mixer = {UPDATE: 0.36, SCAN: 0.011, IN_PROJ: 0.1, OUT_PROJ: 0.05, CONV: 0.02, SCAN_ROW: 0.03, SCAN_Y: 0.04}
    ctx = {"trace": {"op_seconds": {**mixer, **NOT_MIXER}, "op_calls": {}, "busy_s": 2.0}, "config": conf,
           "bench_dir": str(BENCH), "device": {"kind": "TPU v5 lite"}, "series": {}, "cell": {},
           "counter_delta": {}, "counter_delta_traced": {}}
    assert abs(reducers.reduce("per_layer", "kernels.ssm_time_share", ctx) - 100 * sum(mixer.values()) / 2.0) < 1e-9
    # the Pallas calls alone where the compiler fused otherwise; nothing for
    # another configuration's trace, a program without the mixers, no trace
    named = dict(ctx, trace=dict(ctx["trace"], op_seconds={UPDATE: 0.36, SCAN: 0.011, **NOT_MIXER}))
    assert abs(mod.read(named, d) - 100 * 0.371 / 2.0) < 1e-9
    assert mod.read(dict(ctx, config={"hidden_size": 2048}), d) is None
    assert mod.read(dict(ctx, trace=dict(ctx["trace"], op_seconds=NOT_MIXER)), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


def test_the_counter_metrics_read_both_pools():
    delta = {"kv_bytes_in_use_total": 4096 * 1000, "state_bytes_in_use_total": 38_200_000 * 2, "cached_tokens_total": 8000,
             "state_snapshot_hits_total": 97, "state_snapshot_misses_total": 3}
    ctx = {"series": {}, "counter_delta": delta, "counter_delta_traced": None, "trace": None, "device": {},
           "config": {}, "cell": {}, "bench_dir": str(BENCH)}
    assert reducers.reduce("per_layer", "sched.cache_bytes_per_cached_token", ctx) == (4096 * 1000 + 76_400_000) / 8000
    assert reducers.reduce("per_layer", "sched.state_snapshot_hit_share", ctx) == 97.0
    # the parent's program has no such counters: nothing to read, nothing raised
    bare = dict(ctx, counter_delta={"kv_bytes_in_use_total": 1, "cached_tokens_total": 1})
    assert reducers.reduce("per_layer", "sched.cache_bytes_per_cached_token", bare) is None
    assert reducers.reduce("per_layer", "sched.state_snapshot_hit_share", bare) is None
