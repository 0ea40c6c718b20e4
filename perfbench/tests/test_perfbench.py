"""The benchmark's own tests: small, pure, CPU only. Not collected by the
repo's tier-1 command (``pytest tests/``); run with

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import importlib
import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import reducers, run, stats, trace_reduce  # noqa: E402
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
