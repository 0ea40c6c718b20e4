"""The benchmark's own tests: small, pure, CPU only. Not collected by the
repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import asyncio
import importlib
import importlib.util
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import idle_split, recorder, reducers, run, stats, trace_reduce  # noqa: E402
from perfbench.generators import open_loop, sessions  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCH = ROOT / "perfbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_percentile_is_nearest_rank():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile(list(range(101)), 90) == 90
    assert stats.percentile([5], 95) == 5


def test_quartile_spread_matches_statistics_quantiles():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    assert stats.quartile_spread(vals) == pytest.approx((10.25 - 9.875) / 10.05)


def test_size_grid_respects_clip_and_mean():
    g = stats.size_grid({"type": "lognormal", "mean": 512, "sd": 400, "min": 32, "max": 3072}, 400)
    assert g.min() >= 32 and g.max() <= 3072
    assert 440 < g.mean() < 540
    assert stats.gap_grid(50, 10.0).sum() == pytest.approx(10.0)
    assert stats.zipf_counts(512, 8).sum() == 512


def test_open_loop_schedule_reproduces_and_the_seed_draws_only_tokens():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    a = open_loop.schedule(mix, 8.0, 10.0, 7, 1000)
    b = open_loop.schedule(mix, 8.0, 10.0, 7, 1000)
    c = open_loop.schedule(mix, 8.0, 10.0, 2**31 + 5, 1000)
    assert a == b and a != c
    # another seed: the same arrivals of the same sizes, other token ids
    assert [(d, len(p), o) for d, p, o in a] == [(d, len(p), o) for d, p, o in c]
    assert all(0 <= d <= 10.0 for d, _, _ in a)
    # the warm replay: the same multiset of sizes in another order
    w = open_loop.schedule(mix, 8.0, 10.0, 7, 1000, order=1)
    assert [len(p) for _, p, _ in w] != [len(p) for _, p, _ in a]
    assert sorted(len(p) for _, p, _ in w) == sorted(len(p) for _, p, _ in a)


def test_session_scripts_are_one_fixed_list():
    mix = json.loads((BENCH / "traffic" / "prefix-sessions.json").read_text())
    a = sessions.scripts(mix)
    assert a == sessions.scripts(mix) and len(a) == mix["scripts"]
    assert all(1 <= len(turns) <= 16 and 0 <= g < mix["groups"] for g, turns in a)


def test_names_and_units_are_legal():
    for section in ("end_to_end", "per_layer"):
        for m in MANIFEST[section]:
            assert NAME.match(m["name"]), m["name"]
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_resolves(cell):
    spec = run.load(cell)
    importlib.import_module(f"perfbench.topologies.{spec.cell['topology']}")
    importlib.import_module(f"perfbench.generators.{spec.mix['generator']}")
    importlib.import_module(f"perfbench.references.{spec.config['reference']}")
    assert spec.end_to_end and "setup_s" in spec.end_to_end and len(spec.end_to_end) >= 2
    assert spec.per_layer
    for name in spec.end_to_end:
        reducers.definition("end_to_end", name)
    for name in spec.per_layer:
        d = reducers.definition("per_layer", name)
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert (d["layer"], d["moves"], d["source"]) == (entry["layer"], entry["moves"], entry["source"])
        assert entry["moves"] in spec.end_to_end  # the moved metric is reported in this cell


def test_catalog_keys_of_a_configuration_are_published_values():
    conf = json.loads((BENCH / "configs" / "deepseek-v2-lite.1chip.json").read_text())
    assert conf["kv_lora_rank"] == 512 and conf["moe_intermediate_size"] == 1408
    assert conf["num_experts_per_tok"] == 6 and conf["hidden_size"] == 2048
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_reducers_by_kind():
    ctx = {
        "series": {"ttft_ms": [1.0, 2.0, 3.0], "output_tokens": [300], "window_s": [10.0],
                   "cached_tokens_finished": [90, 0], "prompt_tokens_finished": [100, 100]},
        "counter_delta": {"live_tokens_total": 1000, "engine_steps_total": 10,
                          "padded_tokens_total": 250, "compile_programs": 0},
        "trace": {"op_seconds": {"gmm.1": 0.5, "fusion.2": 0.5}, "busy_s": 1.0},
        "device": {"idle_share": 25.0},
    }
    assert reducers.reduce("end_to_end", "ttft_p50_ms", ctx) == 2.0
    assert reducers.reduce("per_layer", "sched.ttft_p90_ms", ctx) == 3.0
    assert reducers.reduce("end_to_end", "output_tok_s", ctx) == 30.0
    assert reducers.reduce("per_layer", "sched.prefix_hit_share", ctx) == 45.0
    assert reducers.reduce("per_layer", "sched.live_tokens_per_step", ctx) == 100.0
    assert reducers.reduce("per_layer", "runner.padded_share", ctx) == 20.0
    assert reducers.reduce("per_layer", "runner.compiles_in_window", ctx) == 0.0
    assert reducers.reduce("per_layer", "device.idle_share", ctx) == 25.0
    # nothing to read: nothing returned
    assert reducers.reduce("per_layer", "runner.step_ms_p50", ctx) is None
    assert reducers.reduce("per_layer", "device.peak_hbm_gb", ctx) is None


def test_trace_reduction_arithmetic():
    loaded = {
        "devices": {"/device:TPU:0": [("a", 0, 100), ("b", 50, 150), ("a", 300, 400)]},
        "spans": [("pb.step", 140, 310), ("pb.wait_step", 150, 300)],
    }
    r = trace_reduce.reduce(loaded)
    assert r["busy_s"] == pytest.approx(250e-9) and r["window_s"] == pytest.approx(400e-9)
    assert r["op_seconds"]["a"] == pytest.approx(200e-9)
    assert r["idle_gaps"] == [["pb.wait_step", pytest.approx(150e-9)]]
    assert trace_reduce.share(r["op_seconds"], ["^a$"], r["busy_s"]) == pytest.approx(0.8)


def test_trace_reduction_on_the_recorded_fixture():
    fixture = BENCH / "tests" / "fixture_v5e.xplane.pb"
    want = json.loads((BENCH / "tests" / "fixture_v5e.expected.json").read_text())
    loaded = trace_reduce.load(str(fixture))
    r = trace_reduce.reduce(loaded)
    assert r["chips"] == want["chips"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert trace_reduce.top_ops(r["op_seconds"], 3)[0][0] == want["top_op"]


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# %gmm instruction texts: the first as a v5e trace gives it (my chip run, PR 29), the others as the
# kernels that ROADMAP S9/S10 ask for would name theirs; (text, M, K, N, E, weight width).
GMM_TEXTS = {
    "one_layer": ("%gmm.13 = f32[256,768]{1,0:T(8,128)} custom-call(s32[130]{0} %a, s32[130]{0} %b, s32[130]{0} %c, "
                  "s32[1]{0} %d, bf16[256,2048]{1,0:T(8,128)(2,1)} %x, bf16[128,2048,768]{2,1,0:T(8,128)(2,1)} %w), "
                  "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={s32[130]{0}, s32[130]{0}, "
                  "s32[130]{0}, s32[1]{0}, bf16[256,2048]{1,0}, bf16[128,2048,768]{2,1,0}}", 256, 2048, 768, 128, 2),
    "gate_up_fused": ("%gmm.2 = f32[256,1536]{1,0} custom-call(s32[130]{0} %a, bf16[256,2048]{1,0} %x, "
                      "bf16[128,2048,1536]{2,1,0} %w)", 256, 2048, 1536, 128, 2),
    "stacked_with_layer_index": ("%gmm.5 = f32[192,1408]{1,0} custom-call(s32[1]{0} %layer, s32[66]{0} %a, "
                                 "bf16[192,2048]{1,0} %x, bf16[8,64,2048,1408]{3,2,1,0} %w)", 192, 2048, 1408, 64, 2),
    "int8_weights": ("%gmm.7 = f32[256,768]{1,0} custom-call(s32[130]{0} %a, bf16[256,2048]{1,0} %x, "
                     "s8[128,2048,768]{2,1,0} %w)", 256, 2048, 768, 128, 1),
}


@pytest.mark.parametrize("form", sorted(GMM_TEXTS))
def test_gmm_call_cost_charges_the_experts_that_have_rows(form):
    """Weight bytes = touched x ONE layer's E x K x N x width, whatever the
    operand's form; activations, output and FLOPs do not depend on it."""
    text, m, k, n, e, width = GMM_TEXTS[form]
    cost = _reader("kernels.moe_gmm_roofline").call_cost
    flops, all_bytes = cost(text, 1.0)
    _, half_bytes = cost(text, 0.5)
    rows = m * k * 2 + m * n * 4
    assert flops == 2.0 * m * k * n
    assert all_bytes == e * k * n * width + rows
    assert half_bytes == 0.5 * e * k * n * width + rows


@pytest.mark.parametrize("text", [
    "%gmm.9 = f32[256,768]{1,0} custom-call(bf16[256,2048]{1,0} %x, bf16[1024,2048,768]{2,1,0} %w, bf16[128,2048,768]{2,1,0} %v)",
    "%gmm.9 = f32[256,768]{1,0} custom-call(bf16[256,2048]{1,0} %x, bf16[2,8,128,2048,768]{4,3,2,1,0} %w)",
    "%gmm.9 = f32[256,768]{1,0} custom-call(bf16[256,2048]{1,0} %x, bf16[128,2048,1536]{2,1,0} %w)",
    "%gmm.9 = f32[256,768]{1,0} custom-call(bf16[256,2048]{1,0} %x)",
    "%gmm.9 = f32[256,768]{1,0} fusion(bf16[256,2048]{1,0} %x, bf16[128,2048,768]{2,1,0} %w)",
], ids=["two_weight_operands", "five_dims", "n_differs_from_output", "no_weights", "not_a_custom_call"])
def test_gmm_call_cost_raises_on_a_form_it_does_not_know(text):
    with pytest.raises(ValueError, match=r"%gmm\.9"):
        _reader("kernels.moe_gmm_roofline").call_cost(text, 1.0)


def _gmm_ctx(bench=BENCH, **over):
    text = GMM_TEXTS["one_layer"][0]
    ctx = {"trace": {"op_seconds": {text: 2e-3, "%fusion.1 = f32[8]{0} fusion()": 1.0}, "op_calls": {text: 2}, "busy_s": 1.1},
           "bench_dir": str(bench), "device": {"kind": "TPU v5 lite"}, "series": {}, "counter_delta": {},
           "config": {"num_experts": 128, "num_experts_per_tok": 8},
           "counter_delta_traced": {"calls_total": 48, "groups_with_rows_total": 48 * 96, "live_tokens_total": 640}}
    ctx.update(over)
    return ctx


def test_the_gmm_roofline_charges_every_expert_while_no_program_counts():
    """This tree: ``layer_metrics/`` has no definition of the reader's
    ``touched_metric``, so the reading is the every-expert upper bound, whatever
    a program's counters are called; nothing caps it."""
    d = reducers.definition("per_layer", "kernels.moe_gmm_roofline")
    assert not (BENCH / "layer_metrics" / f"{d['touched_metric']}.json").exists()
    text = GMM_TEXTS["one_layer"][0]
    _, every = _reader("kernels.moe_gmm_roofline").call_cost(text)
    assert reducers.reduce("per_layer", "kernels.moe_gmm_roofline", _gmm_ctx()) == pytest.approx(100 * 2 * (every / 819e9) / 2e-3)
    fast = _gmm_ctx(trace={"op_seconds": {text: 0.8 * every / 819e9}, "op_calls": {text: 1}})
    assert reducers.reduce("per_layer", "kernels.moe_gmm_roofline", fast) == pytest.approx(125.0)


@pytest.fixture
def counting_bench(tmp_path):
    """What the PR that makes a program count adds, as DATA: the touched share
    as a ``counter_ratio`` over the traced slice, under the name the roofline's
    definition gives; no file of the benchmark is edited."""
    lm = tmp_path / "layer_metrics"
    lm.mkdir()
    shutil.copy(BENCH / "peaks.json", tmp_path)
    for f in ("kernels.moe_gmm_roofline.json", "kernels.moe_gmm_roofline.py"):
        shutil.copy(BENCH / "layer_metrics" / f, lm)
    (lm / "kernels.moe_experts_touched_share.json").write_text(json.dumps({
        "kind": "counter_ratio", "over": "traced", "num": ["groups_with_rows_total"], "den": ["calls_total"],
        "den_config": ["num_experts", "n_routed_experts"], "scale": 100.0,
        "layer": "kernels", "source": "program_counter", "moves": "output_tok_s"}))
    return tmp_path


def test_the_gmm_roofline_takes_the_share_of_a_program_that_counts(counting_bench):
    ctx = _gmm_ctx(counting_bench)
    text = GMM_TEXTS["one_layer"][0]
    _, nbytes = _reader("kernels.moe_gmm_roofline").call_cost(text, 0.75)
    assert reducers.reduce("per_layer", "kernels.moe_experts_touched_share", ctx) == pytest.approx(75.0)
    assert reducers.reduce("per_layer", "kernels.moe_gmm_roofline", ctx) == pytest.approx(100 * 2 * (nbytes / 819e9) / 2e-3)
    # a kernel at 80 % of the weight stream with every expert charged reads 125 %; with its half, under 100
    _, every = _reader("kernels.moe_gmm_roofline").call_cost(text)
    fast = _gmm_ctx(counting_bench, trace={"op_seconds": {text: 0.8 * every / 819e9}, "op_calls": {text: 1}},
                    counter_delta_traced={"calls_total": 2, "groups_with_rows_total": 128})
    assert 60 < reducers.reduce("per_layer", "kernels.moe_gmm_roofline", fast) < 70
    # DeepSeek's key for the routed experts; the window's counters are not the slice's
    ds = _gmm_ctx(counting_bench, config={"n_routed_experts": 64}, counter_delta={"calls_total": 1, "groups_with_rows_total": 1})
    assert reducers.reduce("per_layer", "kernels.moe_experts_touched_share", ds) == pytest.approx(150.0)


@pytest.mark.parametrize("over", [
    {"counter_delta_traced": {"engine_steps_total": 20, "live_tokens_total": 640}},
    {"counter_delta_traced": {"calls_total": 0, "groups_with_rows_total": 0}},
    {"counter_delta_traced": {"calls_total": 48, "groups_with_rows_total": 0}},
    {"counter_delta_traced": None},
    {"config": {"hidden_size": 2048}},
    {"config": {"n_routed_experts": 64}},
], ids=["counters_missing", "both_deltas_zero", "no_group_with_rows", "no_traced_slice", "no_routed_experts", "over_100"])
def test_once_a_program_counts_gmm_calls_without_a_share_raise(counting_bench, over):
    """Never a fall back to every expert once the share has a definition, and
    none estimated from the steps' tokens (tokens that share a context share
    experts: 32 rows touched 59-66 % of 128 experts where independence says 87)."""
    with pytest.raises(RuntimeError, match="kernels.moe_experts_touched_share"):
        reducers.reduce("per_layer", "kernels.moe_gmm_roofline", _gmm_ctx(counting_bench, **over))


def test_a_trace_without_gmm_calls_gives_no_roofline(counting_bench):
    for bench in (BENCH, counting_bench):
        no_gmm = _gmm_ctx(bench, trace={"op_seconds": {"%fusion.1 = f32[8]{0} fusion()": 1.0}, "op_calls": {}},
                          counter_delta_traced=None)
        assert reducers.reduce("per_layer", "kernels.moe_gmm_roofline", no_gmm) is None
        assert reducers.reduce("per_layer", "kernels.moe_gmm_roofline", _gmm_ctx(bench, trace=None)) is None
    untraced = _gmm_ctx(counting_bench, trace=None, counter_delta_traced=None)  # --trace 0: no slice
    assert reducers.reduce("per_layer", "kernels.moe_experts_touched_share", untraced) is None


IDLE_PHASES = ("schedule", "launch", "finish", "unattributed")


def test_idle_split_names_the_phase_of_each_gap():
    """Device busy 0-100, 300-400, 700-800, 950-1000 ns; the first gap lies
    in the program's schedule span, the second mostly in launch (its build
    covers less of it), the third in nothing. The whole-step span is not
    among the candidates: it would take every gap inside a step."""
    assert not "llmd.step".startswith(trace_reduce.SPAN_PREFIX)
    assert all(n.startswith(trace_reduce.SPAN_PREFIX) for n in
               ("pb.step", "llmd.step.finish", "llmd.step.admit", "llmd.sched.schedule", "llmd.runner.wait",
                "llmd.serve.intake"))
    loaded = {
        "devices": {"/device:TPU:0": [("a", 0, 100), ("a", 300, 400), ("a", 700, 800), ("a", 950, 1000)]},
        "spans": [("llmd.sched.schedule", 100, 290), ("llmd.runner.wait", 295, 410),
                  ("llmd.runner.launch", 440, 700), ("llmd.runner.build", 450, 690),
                  ("llmd.runner.wait", 700, 810)],
    }
    r = trace_reduce.reduce(loaded)
    # a whole gap goes to the one span that covers most of it
    assert r["idle_by_host_s"] == {
        "llmd.runner.launch": pytest.approx(300e-9), "llmd.sched.schedule": pytest.approx(200e-9),
        "outside any step": pytest.approx(150e-9)}
    shares = {p: idle_split.share(r, p) for p in IDLE_PHASES}
    assert shares == {"schedule": pytest.approx(20.0), "launch": pytest.approx(30.0), "finish": 0.0,
                      "unattributed": pytest.approx(15.0)}
    assert sum(shares.values()) == pytest.approx(100.0 * (1 - r["busy_s"] / r["window_s"]))
    # a span inside launch counts as launch; the --trace 1 wrappers as unattributed
    inner = dict(r, idle_by_host_s={"llmd.runner.dispatch": 100e-9, "llmd.runner.build": 50e-9, "pb.step": 25e-9})
    assert idle_split.share(inner, "launch") == pytest.approx(15.0)
    assert idle_split.share(inner, "unattributed") == pytest.approx(2.5)
    assert idle_split.share(None, "launch") is None  # no trace: nothing to read


def test_idle_split_on_the_recorded_fixture_with_the_programs_spans():
    """The second recorded trace (tests/make_fixture_llmd.py, a v5e): steps
    under the program's own ``llmd.*`` spans with a long schedule in one step
    and a long finish in another, read through the four metric files; each
    phase gets its gaps and they add up to ``device.idle_share``."""
    fixture = BENCH / "tests" / "fixture_llmd_v5e.xplane.pb"
    want = json.loads((BENCH / "tests" / "fixture_llmd_v5e.expected.json").read_text())
    loaded = trace_reduce.load(str(fixture))
    assert {n for n, _, _ in loaded["spans"]} == {
        "llmd.serve.intake", "llmd.step.admit", "llmd.sched.schedule", "llmd.runner.launch", "llmd.runner.build",
        "llmd.runner.dispatch", "llmd.runner.wait", "llmd.step.finish", "llmd.serve.deliver"}
    r = trace_reduce.reduce(loaded)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["idle_by_host_s"] == {k: pytest.approx(v, rel=1e-9) for k, v in want["idle_by_host_s"].items()}
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    ctx = {"series": {}, "counter_delta": {}, "trace": r, "device": {"idle_share": idle}, "bench_dir": str(BENCH)}
    shares = {p: reducers.reduce("per_layer", f"device.idle_{p}_share", ctx) for p in IDLE_PHASES}
    assert shares == {p: pytest.approx(v, rel=1e-9) for p, v in want["shares"].items()}
    assert all(shares[p] > 5.0 for p in ("schedule", "launch", "finish"))  # each phase has its gap
    assert sum(shares.values()) == pytest.approx(reducers.reduce("per_layer", "device.idle_share", ctx), rel=1e-9)
    # the recorder slept 3 ms in one schedule, 2 ms in every build, 4 ms in one finish
    assert all(name.startswith(("llmd.sched.", "llmd.runner.", "llmd.step.", "outside")) for name, _ in r["idle_gaps"])


class _FakeSystem:
    """Answers every request with its tokens at once: enough for a generator."""

    vocab_size, max_model_len = 1000, 4096

    async def stream(self, prompt, max_tokens):
        for i in range(max_tokens):
            await asyncio.sleep(0)
            yield type("Out", (), {"new_token_ids": [1], "finished": i == max_tokens - 1, "num_cached_tokens": 0})


def test_the_window_of_a_trace2_run_is_the_window_of_a_trace0_run():
    """Open loop: with a tail the generator sends the window's schedule
    element for element as without one, and only then more of the mix, in
    another order, as unmeasured records."""
    mix = dict(json.loads((BENCH / "traffic" / "chat.json").read_text()), warm_seconds=0.2, drain_seconds=2)
    mix["output"] = {"type": "lognormal", "mean": 8, "sd": 4, "min": 2, "max": 16}
    system = _FakeSystem()

    def once(with_tail: bool):
        rec = recorder.Recorder(system.vocab_size)
        gen = open_loop.Generator(mix, {"rate": 40.0}, 2**31 + 9, 1.0, system)
        seen = {}

        async def tail(offer):
            seen["closed"] = run.window_numbers(rec)
            await offer(0.5)

        asyncio.run(gen.run(system, rec, tail) if with_tail else gen.run(system, rec))
        return gen, rec, seen

    g0, r0, _ = once(False)
    g2, r2, seen = once(True)
    assert g0.main == g2.main and g0.warm == g2.warm

    def sent(rec, measured):
        return [(round(r.due - rec.t0, 6), r.n_prompt, r.want_tokens) for r in rec.records if r.measured == measured]

    assert sent(r0, True) == sent(r2, True) == [(round(d, 6), len(p), o) for d, p, o in g0.main]
    extra = [r for r in r2.records[len(r0.records):]]
    assert len(extra) == 20 and all(not r.measured and r.due >= r2.t1 for r in extra)
    assert sorted(r.n_prompt for r in extra) != sorted(r.n_prompt for r in r2.records if r.measured)[:20]
    # the numbers taken when the window closed are the numbers of the whole run's window
    assert seen["closed"]["attempted"] == len(r2.attempted()) == len(r0.attempted()) == 40
    assert seen["closed"]["failed"] == 0 and seen["closed"]["series"]["output_tokens"] == r2.series()["output_tokens"]


@pytest.mark.parametrize("cell", ["qwen3-30b-a3b.prefix-sessions", "deepseek-v2-lite.chat-overload"])
def test_trace2_rehearsal_reports_both_sections(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "2", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    spec = run.load(cell)
    reported = set(line["metrics_reported"])
    assert set(spec.end_to_end) <= reported  # what --trace 0 reports
    counters = {n for n in spec.per_layer if reducers.definition("per_layer", n)["source"] != "device_trace"}
    assert counters - {"device.peak_hbm_gb"} <= reported  # and what a CPU can of --trace 1's
    assert {"runner.step_ms_p50", "runner.launch_ms", "runner.retraces_in_window"} <= reported
    assert not (run.OUT_DIR / "trace" / cell).exists()  # the trace is deleted once reduced
    # the readers were given what the program counted while the profiler was on
    detail = json.loads((run.OUT_DIR / f"{cell}.seed{2**31 + 11}.trace2.json").read_text())
    assert 0 < detail["counter_delta_traced"]["live_tokens_total"] != detail["counter_delta"]["live_tokens_total"]


@pytest.mark.parametrize("cell", ["qwen3-30b-a3b.chat", "deepseek-v2-lite.long-decode"])
def test_rehearsal_runs_in_process(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "0", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert "metrics" not in line  # nothing timed on a CPU leaves a rehearsal


def test_off_the_chip_a_run_fails_without_rehearse():
    with pytest.raises(SystemExit):
        run.main(["--workload", "qwen3-30b-a3b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"])


def test_a_new_cell_arrives_as_new_files_only(tmp_path, capsys):
    """One new configuration, mix, cell, generator, topology, reference and
    per-layer metric (with a reader), added to a copy as files and manifest
    entries: run.py is not edited and runs the new cell."""
    shutil.copytree(BENCH, tmp_path / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "perfbench"
    conf = json.loads((b / "configs" / "qwen3-30b-a3b.1chip.json").read_text())
    conf["reference"] = "plain2"
    (b / "configs" / "other.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["generator"] = "open_loop2"
    (b / "traffic" / "chat2.json").write_text(json.dumps(mix))
    (b / "cells" / "other.chat2.json").write_text(json.dumps({"topology": "engine2", "rate": 5.0}))
    (b / "generators" / "open_loop2.py").write_text("from perfbench.generators.open_loop import Generator  # noqa\n")
    (b / "topologies" / "engine2.py").write_text("from perfbench.topologies.engine import start  # noqa\n")
    (b / "references" / "plain2.py").write_text("from perfbench.references.gqa_moe import *  # noqa\n")
    (b / "layer_metrics" / "new.steps.json").write_text(json.dumps(
        {"kind": "reader", "layer": "runner", "source": "program_counter", "moves": "output_tok_s"}))
    (b / "layer_metrics" / "new.steps.py").write_text(
        "def read(ctx, d):\n    return float(ctx['counter_delta']['engine_steps_total'])\n")
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "other", "source": "x", "file": "perfbench/configs/other.json", "reduced": [], "why": "t"})
    m["workloads"].append({"name": "other.chat2", "config": "other", "traffic": "chat2", "chips": 1, "why": "t"})
    m["per_layer"].append({"name": "new.steps", "unit": "count", "better": "higher", "source": "program_counter",
                           "layer": "runner", "moves": "output_tok_s", "workloads": ["other.chat2"]})
    for e in m["end_to_end"]:
        if "workloads" in e:
            e["workloads"].append("other.chat2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    rc = run.main(["--workload", "other.chat2", "--seed", "3", "--seconds", "3", "--trace", "1",
                   "--rehearse", "--root", str(tmp_path)])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True
    assert "new.steps" in line["metrics_reported"]
    sys.path.remove(str(tmp_path))
