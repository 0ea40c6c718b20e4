"""The Keye-VL-2.0-30B-A3B cell's own tests: CPU only, the tiny preset. Not
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

CELL = "keye-vl-2.0-30b-a3b.long-doc-sessions"
BENCH = ROOT / "perfbench"


def test_the_cell_rehearses_and_its_comparison_runs_behind_more_than_topk_tokens(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 23), "--seconds", "2", "--trace", "2", "--rehearse"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 3 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    spec = run.load(CELL)
    reported = set(line["metrics_reported"])
    assert set(spec.end_to_end) == {"itl_p95_ms", "output_tok_s", "setup_s"} <= reported
    assert {"sched.sparse_bound_token_share", "sched.indexer_keys_per_token"} <= reported
    detail = json.loads((run.OUT_DIR / f"{CELL}.seed{2**31 + 23}.trace2.json").read_text())
    check = detail["reference_check"]
    assert check["ok"] and check["tokens_compared"] == 128
    # float32 on both sides at the tiny size: the comparison is exact to rounding,
    # over a context past the tiny top-k, so a wrong selection would show.
    assert check["logprob_diff"]["max"] < 1e-4
    tiny = spec.config["rehearse"]
    assert tiny["engine"]["check_context_tokens"] > 2 * tiny["published"]["sa_config"]["topk"]
    # Every computed token of the tiny traffic past the context is bound.
    assert detail["counter_delta"]["sparse_bound_tokens_total"] > 0


def test_the_comparisons_context_exceeds_twice_topk_at_the_real_size():
    conf = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b.1chip.json").read_text())
    topk = conf["sa_config"]["topk"]
    assert conf["engine"]["check_context_tokens"] >= 2 * topk == 4096
    mix = json.loads((BENCH / "traffic" / "long-doc-sessions.json").read_text())
    assert mix["prefix_tokens"] >= 8 * topk and mix["context_cap"] < conf["engine"]["max_model_len"]
    # The pool holds the shared documents and every client's own tokens.
    own = mix["context_cap"] - mix["prefix_tokens"]
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    need = mix["groups"] * mix["prefix_tokens"] + cell["clients"] * own
    assert need <= conf["engine"]["num_pages"] * conf["engine"]["page_size"]


def test_the_reference_prepends_the_context_and_reports_the_prompt_only():
    import jax
    import numpy as np

    from llmd_tpu.models import llama
    from llmd_tpu.models.registry import get_model_config
    from perfbench.references import gqa_dsa_moe as ref

    pub = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b.1chip.json").read_text())["rehearse"]["published"]
    params = llama.init_params(get_model_config("tiny-dsa"), jax.random.key(0))
    rng = np.random.default_rng(0)
    context, tokens = rng.integers(0, 256, 70).tolist(), rng.integers(0, 256, 30).tolist()
    whole = ref.forward(params, context + tokens, pub)
    bound = {tuple(tokens[:12]): {"context": context}}  # the prompt's first tokens name its entry
    behind = ref.forward(dict(params, bound=bound), tokens, pub)
    for a, b in zip(whole, behind):
        assert b.shape == (29,)
        np.testing.assert_allclose(np.asarray(a)[70:], np.asarray(b), atol=1e-6)
    # another prompt has no entry: scored as it stands
    other = rng.integers(0, 256, 30).tolist()
    for a, b in zip(ref.forward(params, other, pub), ref.forward(dict(params, bound=bound), other, pub)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _reader(name):
    spec = importlib.util.spec_from_file_location("r", BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_sparse_attention_roofline_counts_the_selected_rows_of_live_tokens():
    mod = _reader("kernels.sparse_attention_roofline")
    name = ("%llmd.sparse_attention.3 = bf16[48,4,8,128]{3,2,1,0} custom-call(s32[1]{0} %a, s32[48]{0} %b, "
            "s32[40,2048]{1,0} %c, s32[48]{0} %d, s32[48]{0} %e, bf16[48,4,8,128]{3,2,1,0} %q, f32[4,8]{1,0} %s, "
            "bf16[6,24576,4,16,256]{4,3,2,1,0} %pool, f32[48,1,32768]{2,1,0} %sel), custom_call_target=\"tpu_custom_call\"")
    flops, nbytes = mod.call_cost(name, 2048, 0.75)
    tokens = 48 * 0.75  # the traced slice's counters say which share of the computed tokens was live
    assert flops == 4.0 * tokens * 2048 * 32 * 128
    assert nbytes == tokens * 2048 * 4 * 256 * 2 + 2 * tokens * 32 * 128 * 2
    ctx = {"trace": {"op_seconds": {name: 1e-3}, "op_calls": {name: 1}}, "bench_dir": str(BENCH),
           "device": {"kind": "TPU v5 lite"}, "config": {"sa_config": {"topk": 2048}},
           "counter_delta": {"live_tokens_total": 1000, "padded_tokens_total": 0},  # the window's: never read
           "counter_delta_traced": {"live_tokens_total": 360, "padded_tokens_total": 120}}
    d = reducers.definition("per_layer", "kernels.sparse_attention_roofline")
    share = mod.read(ctx, d)
    assert 0 < share < 100 and abs(share - 100 * (nbytes / 819e9) / 1e-3) < 1e-9
    # Nothing caps it: a call timed faster than its selected rows can move reads over 100 %.
    assert mod.read(dict(ctx, trace={"op_seconds": {name: 1e-5}, "op_calls": {name: 1}}), d) > 100
    assert mod.read(dict(ctx, counter_delta_traced={}), d) is None  # a program without the counters
    assert mod.read(dict(ctx, counter_delta_traced=None), d) is None  # a trace and no counters over it
    # A program without the kernel (the parent), or a configuration without
    # an indexer: nothing to read, nothing raised.
    assert mod.read(dict(ctx, trace={"op_seconds": {"%gmm.1 = f32[8,8]": 1.0}, "op_calls": {}}), d) is None
    assert mod.read(dict(ctx, config={}), d) is None
    assert mod.read(dict(ctx, trace=None), d) is None


def test_the_indexer_and_the_selection_are_found_by_their_shapes():
    """Names as a v5e trace gave them (my chip run, PR 28): anonymous fusions,
    told apart by the configuration's shapes in the HLO text."""
    conf = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b.1chip.json").read_text())
    ops = {
        "%fusion.416 = bf16[32768,16,64]{2,1,0:T(8,128)(2,1)} fusion(bf16[24576,16,64]{2,1,0} %gte.2497, s32[32768]{0} %c.6), kind=kCustom": 0.05,
        "%fusion.417 = f32[16,32768]{1,0:T(8,128)S(1)} fusion(bf16[16,32768,64]{2,1,0} %bitcast.578, f32[16,16]{1,0} %c.5), kind=kOutput": 0.03,
        "%copy.47 = bf16[6,24576,16,64]{3,2,1,0} copy(bf16[6,24576,16,64]{1,3,2,0} %kv_cache_index.1)": 0.02,
        "%convert_reduce_fusion.3 = s32[512]{0:T(512)S(1)} fusion(u32[512,32768]{1,0} %fusion.9, u32[512]{0} %x)": 0.004,
        "%broadcast_compare_fusion = pred[512,32768]{1,0:T(8,128)(4,1)} fusion(u32[512,32768]{1,0} %fusion.9)": 0.001,
        "%cond.1.clone = (f32[208,1,32768]{2,1,0}) conditional(pred[] %p)": 0.5,  # a container: left out
        "%llmd.sparse_attention.14 = bf16[512,4,8,128]{3,2,1,0} custom-call(f32[512,1,32768]{2,1,0} %sel)": 0.2,
        "%gmm.13 = f32[512,768]{1,0} custom-call(bf16[512,2048]{1,0} %x, bf16[128,2048,768]{2,1,0} %w)": 0.4,
    }
    ctx = {"trace": {"op_seconds": ops, "busy_s": 1.0}, "config": conf}
    for name, want in (("kernels.indexer_time_share", 10.0), ("kernels.sparse_select_time_share", 0.5)):
        d = reducers.definition("per_layer", name)
        assert abs(_reader(name).read(ctx, d) - want) < 1e-9
        # the parent's program (no such event) and a configuration without an indexer
        assert _reader(name).read({"trace": {"op_seconds": {"%gmm.1 = f32[8,8]": 1.0}, "busy_s": 1.0}, "config": conf}, d) is None
        assert _reader(name).read(dict(ctx, config={}), d) is None
        assert _reader(name).read(dict(ctx, trace=None), d) is None
    # A reader whose own part matched nothing says nothing (the other's events do not speak for it).
    only_indexer = {k: v for k, v in ops.items() if k.startswith(("%fusion.41", "%copy", "%gmm"))}
    ctx = {"trace": {"op_seconds": only_indexer, "busy_s": 1.0}, "config": conf}
    assert _reader("kernels.indexer_time_share").read(ctx, d) == 10.0
    assert _reader("kernels.sparse_select_time_share").read(ctx, d) is None
