"""The readers of the host's turn (PR 39): ``perfbench/idle_turn.py`` and the
``counter_ratio`` files over the turn's counters, against a hand-made trace
and hand-made counter deltas. Pure, CPU only; run with the benchmark's own tests:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import idle_turn, reducers, run, trace_reduce  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
BENCH = ROOT / "perfbench"
CELLS = [w["name"] for w in MANIFEST["workloads"]]
OLD = ("schedule", "launch", "finish")
NEW = ("no_work", "wait", "readback", "commit", "unnamed")
RATIOS = ("runner.ready_lag_bound_ms", "runner.readback_ms", "runner.commit_ms", "runner.redispatch_ms",
          "runner.gap_admit_ms", "runner.turn_ms", "sched.intake_wait_ms", "runner.deliver_lag_ms")
READERS = ("device.idle_ms_per_step", *(f"device.idle_{p}_share" for p in NEW))

# What a program of PR 39 counts over a window and over the traced slice, and
# a parent's counters (PR 38: commit and redispatch, none of the turn's).
PARENT = {"engine_steps_total": 100, "step_host_gap_ms_total": 150.0, "step_commit_ms_total": 30.0,
          "step_redispatch_ms_total": 120.0, "queue_wait_ms_total": 8.0, "queue_admitted_total": 4}
CHANGE = {**PARENT, "step_readback_ms_total": 70.0, "step_ready_lag_bound_ms_total": 25.0,
          "step_gap_admit_ms_total": 40.0, "engine_idle_ms_total": 500.0, "intake_wait_ms_total": 2.0,
          "intake_requests_total": 4, "deliver_lag_ms_total": 330.0, "outputs_delivered_total": 110}


def traced():
    """Busy 0-1, 2-3, 4-5, 6-7, 8-9, 10-11, 12-13, 14-15, 16-17 us; eight
    gaps of 1 us, one under each kind of span and one under none."""
    us = 1000
    spans = ["llmd.sched.schedule", "llmd.runner.launch", "llmd.step.finish", "llmd.serve.idle",
             "llmd.runner.wait", "llmd.runner.readback", "llmd.step.commit"]
    loaded = {
        "devices": {"/device:TPU:0": [("op", 2 * i * us, (2 * i + 1) * us) for i in range(9)]},
        "spans": [(name, (2 * i + 1) * us, (2 * i + 2) * us) for i, name in enumerate(spans)],
    }
    return trace_reduce.reduce(loaded)


def ctx_of(counters: dict, trace=None, steps_traced: int = 4) -> dict:
    tr = trace or traced()
    return {
        "series": {}, "counter_delta": counters, "trace": tr, "bench_dir": str(BENCH),
        "counter_delta_traced": {"engine_steps_total": steps_traced} if "engine_steps_total" in counters else {},
        "device": {"idle_share": 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])},
    }


def test_the_eight_idle_shares_add_up_to_the_idle_share():
    ctx = ctx_of(CHANGE)
    shares = {p: reducers.reduce("per_layer", f"device.idle_{p}_share", ctx) for p in OLD + NEW}
    each = pytest.approx(100.0 / 17)  # one gap of 1 us each, of a window of 17
    assert shares == dict.fromkeys(OLD + NEW, each)
    assert sum(shares.values()) == pytest.approx(reducers.reduce("per_layer", "device.idle_share", ctx))
    # the old rest is the five new shares together
    assert reducers.reduce("per_layer", "device.idle_unattributed_share", ctx) == pytest.approx(sum(shares[p] for p in NEW))


def test_unnamed_holds_what_no_part_and_no_phase_names():
    tr = dict(traced(), idle_by_host_s={"llmd.serve.deliver": 1e-6, "llmd.serve.intake": 2e-6, "llmd.step.admit": 3e-6,
                                        "pb.step": 4e-6, "outside any step": 5e-6, "llmd.serve.paused": 6e-6,
                                        "llmd.runner.dispatch": 7e-6})
    assert idle_turn.seconds(tr, "unnamed") == pytest.approx(15e-6)
    assert idle_turn.seconds(tr, "no_work") == pytest.approx(6e-6)  # a paused engine has nothing to run either
    assert idle_turn.seconds(tr, "wait") == 0.0


def test_idle_ms_per_step_leaves_the_time_with_nothing_to_run_out():
    ctx = ctx_of(CHANGE, steps_traced=4)
    # eight gaps of 1 us, one of them under llmd.serve.idle, over four steps
    assert reducers.reduce("per_layer", "device.idle_ms_per_step", ctx) == pytest.approx(7e-3 / 4)
    loaded_tr = dict(ctx["trace"], idle_by_host_s={**ctx["trace"]["idle_by_host_s"], "llmd.serve.idle": 1.0})
    assert idle_turn.idle_ms_per_step(dict(ctx, trace=loaded_tr)) == pytest.approx(7e-3 / 4)  # however long it idled
    assert idle_turn.idle_ms_per_step(dict(ctx, counter_delta_traced={"engine_steps_total": 0})) is None
    assert idle_turn.idle_ms_per_step(dict(ctx, counter_delta_traced=None)) is None
    assert idle_turn.idle_ms_per_step(dict(ctx, trace=None)) is None


def test_the_turns_ratios_read_the_counters():
    ctx = ctx_of(CHANGE)
    got = {name: reducers.reduce("per_layer", name, ctx) for name in RATIOS}
    assert got == {
        "runner.ready_lag_bound_ms": pytest.approx(0.25), "runner.readback_ms": pytest.approx(0.70),
        "runner.commit_ms": pytest.approx(0.30), "runner.redispatch_ms": pytest.approx(1.20),
        "runner.gap_admit_ms": pytest.approx(0.40), "runner.turn_ms": pytest.approx(2.20),
        "sched.intake_wait_ms": pytest.approx(0.5), "runner.deliver_lag_ms": pytest.approx(3.0),
    }
    # the turn is the old gap with the readback in front of it
    assert got["runner.turn_ms"] == pytest.approx(reducers.reduce("per_layer", "runner.host_gap_ms", ctx) + 0.70)
    assert got["runner.gap_admit_ms"] <= got["runner.redispatch_ms"]
    # a blocking wait counts a lag of 0, and 0 is a reading
    assert reducers.reduce("per_layer", "runner.ready_lag_bound_ms",
                           ctx_of({**CHANGE, "step_ready_lag_bound_ms_total": 0.0})) == 0.0


def test_a_parent_without_the_counters_reports_nothing_and_raises_nothing():
    """The driver lays these files over the parent's checkout: its program
    has PR 38's counters and spans, none of PR 39's."""
    ctx = ctx_of(PARENT)
    new = [n for n in RATIOS if n not in ("runner.commit_ms", "runner.redispatch_ms")]
    assert {n: reducers.reduce("per_layer", n, ctx) for n in new} == dict.fromkeys(new)
    assert {n: reducers.reduce("per_layer", n, ctx) for n in READERS} == dict.fromkeys(READERS)
    # PR 38's two counters are there, read by no metric until now
    assert reducers.reduce("per_layer", "runner.commit_ms", ctx) == pytest.approx(0.30)
    assert reducers.reduce("per_layer", "runner.redispatch_ms", ctx) == pytest.approx(1.20)
    # a parent of PR 38, --trace 0 (no trace, no traced slice): nothing anywhere
    bare = {"series": {}, "counter_delta": {"engine_steps_total": 10}, "counter_delta_traced": None, "trace": None,
            "device": {}, "bench_dir": str(BENCH)}
    assert {n: reducers.reduce("per_layer", n, bare) for n in RATIOS + READERS} == dict.fromkeys(RATIOS + READERS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_lists_the_new_metrics_it_can_read(cell):
    spec = run.load(cell)
    qwen = cell.startswith("qwen3-30b-a3b.")
    for name in RATIOS + READERS:
        only_ttft_cells = name in ("sched.intake_wait_ms", "runner.deliver_lag_ms")
        assert (name in spec.per_layer) == (qwen or not only_ttft_cells), name
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        d = reducers.definition("per_layer", name)
        assert (d["layer"], d["moves"], d["source"]) == (entry["layer"], entry["moves"], entry["source"])
        if name in spec.per_layer:
            assert entry["moves"] in spec.end_to_end
