"""nemotron-3-nano's architecture in miniature (``tiny-nemotron-h``): BLOCKS of
one mixer each (Mamba-2 with B and C in groups, non-gated relu^2 experts + a
shared one, NoPE attention), served as layers of mixer (+ FFN), scanned in
cycles, over the state pool and the paged pool on the flat step — against the
plain reference of ``perfbench/references/mamba2_gqa_relu2_moe_share.py``
(float32, the recurrence a scan over tokens, no kernel, no cache). The layers
one at a time (the grouped state kernels, the gated norm, the non-gated experts,
the share test) are in ``tests/test_mixer_only_hybrid_ops.py``.
"""

import json
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.config import CacheConfig, EngineConfig, ModelConfig, SchedulerConfig  # noqa: E402
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import llama  # noqa: E402
from llmd_tpu.models.registry import get_model_config, nemotron_h_layers  # noqa: E402
from llmd_tpu.ops import ssm  # noqa: E402
from perfbench.references import mamba2_gqa_relu2_moe_share as ref  # noqa: E402
from perfbench.topologies import engine_mixer  # noqa: E402

CONF = json.loads((ROOT / "perfbench" / "configs" / "nemotron-3-nano-30b-a3b.1chip.json").read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference
PAGE = 4
MODEL = get_model_config("tiny-nemotron-h")
LM, LE, LA = len(MODEL.mamba_layers), len(MODEL.ffn_layers), len(MODEL.attention_layers)


def make_engine(model=MODEL, num_blocks=512, max_batched=32, max_seqs=4, max_len=None, **cache) -> LLMEngine:
    if max_len:
        model = get_model_config(model.name, max_model_len=max_len)
    return LLMEngine(EngineConfig(
        model=model,
        cache=CacheConfig(page_size=PAGE, num_blocks=num_blocks, dtype="float32", **cache),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
    """[(tokens, log-probs, request)] per prompt, all in the engine at once."""
    ids = [eng.add_request(list(p), SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                                   ignore_eos=True, logprobs=True)) for p in prompts]
    reqs = list(eng.scheduler.waiting)
    toks = {rid: [] for rid in ids}
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
    return [(toks[rid], np.asarray(r.output_logprobs), r) for rid, r in zip(ids, reqs)]


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def assert_matches_reference(eng, prompt, toks, lps, published=PUBLISHED, atol=5e-5, params=None):
    assert len(toks) == len(lps) > 0
    nxt, _best = ref.forward(params or eng.runner.params, prompt + toks, published)
    np.testing.assert_allclose(lps, np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)]), atol=atol)


def snapshots(eng):
    eng._refresh_gauges()
    s = eng.stats
    return (s.state_snapshot_hits_total, s.state_snapshot_misses_total, s.state_snapshot_captures_total)


# --- the configuration ----------------------------------------------------------


def test_blocks_of_one_mixer_are_layers_of_mixer_and_ffn():
    types, ffn = nemotron_h_layers("MEMEM*EME")
    assert types == ("mamba", "mamba", "mamba", "attention", "mamba")
    assert ffn == (True, True, False, True, True)
    full = get_model_config("nemotron-3-nano-30b-a3b")
    assert (full.num_layers, len(full.mamba_layers), len(full.attention_layers), len(full.ffn_layers)) == (29, 23, 6, 23)
    assert full.mamba_conv_dim == 6144 and full.mamba_d_inner == 4096 and full.moe_storage_width == 1920
    with pytest.raises(ValueError, match="no\\s+mixer in front"):
        nemotron_h_layers("EM")


def test_groups_of_b_and_c_are_accepted_where_they_divide_the_heads():
    assert get_model_config("tiny-nemotron-h", mamba_n_heads=8, mamba_n_groups=8).mamba_n_groups == 8
    with pytest.raises(ValueError, match="does not divide"):
        get_model_config("tiny-nemotron-h", mamba_n_heads=4, mamba_n_groups=3)


def test_layers_without_ffn_need_a_model_of_mixer_kinds():
    with pytest.raises(ValueError, match="layer_ffn"):
        ModelConfig(name="x", vocab_size=8, hidden_size=8, intermediate_size=8, num_layers=2, num_heads=2,
                    num_kv_heads=2, layer_ffn=(True, False))


def test_the_configuration_file_reaches_the_program_as_published():
    over = engine_mixer.model_overrides(CONF)
    cfg = get_model_config(CONF["registry"], **over)
    assert cfg.num_layers == 16 and cfg.layer_types == ("mamba", "mamba", "mamba", "attention") * 4
    assert cfg.layer_ffn == (True, True, False, True) * 4
    assert (len(cfg.mamba_layers), len(cfg.ffn_layers), len(cfg.attention_layers)) == (12, 12, 4)
    assert (cfg.num_experts, cfg.held_experts, cfg.held_experts_first, cfg.num_experts_per_tok) == (128, 16, 0, 6)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size, cfg.moe_activation) == (1856, 3712, "relu2")
    assert (cfg.router_scoring, cfg.routed_scaling_factor, cfg.norm_topk_prob) == ("sigmoid", 2.5, True)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups) == (64, 64, 128, 8)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == (32, 2, 128, 16384)
    assert set(cfg.layer_rope) == {None} and not cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-5
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.key(0))
    lp = shapes["layers"]
    assert lp["we_up"].shape == (12, 16, 2688, 1920) and lp["we_down"].shape == (12, 16, 1920, 2688)
    assert "we_gate" not in lp and "ws_gate" not in lp and lp["ws_up"].shape == (12, 2688, 3712)
    assert lp["input_norm"].shape == (16, 2688) and lp["post_norm"].shape == (12, 2688)
    assert shapes["mamba_layers"]["m_in"].shape == (12, 2688, 10304)
    # the catalog row's numbers, unchanged but for the three in `reduced`
    row = CONF["published"]
    assert set(CONF["reduced"]) == set(row) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_expert_leaves_are_stored_padded_with_zeros_where_the_kernel_needs_lanes():
    cfg = get_model_config("tiny-nemotron-h", hidden_size=128, head_dim=32, moe_intermediate_size=72)
    assert cfg.moe_storage_width == 128
    lp = llama.init_params(cfg, jax.random.key(1))["layers"]
    assert lp["we_up"].shape[-1] == 128 and lp["we_down"].shape[-2] == 128
    assert not np.any(np.asarray(lp["we_up"][..., 72:])) and not np.any(np.asarray(lp["we_down"][..., 72:, :]))
    assert np.all(np.any(np.asarray(lp["we_up"][..., :72]) != 0, axis=-2))


# --- the cycle scan -------------------------------------------------------------------


def test_the_pattern_cycles_and_what_is_no_whole_cycle_is_peeled():
    pat = lambda cfg: tuple(zip(cfg.layer_types, cfg.layer_ffn or (True,) * cfg.num_layers))  # noqa: E731
    assert llama._kind_cycles(pat(MODEL)) == (4, 2)  # + a tail of three layers
    assert llama._kind_cycles(pat(get_model_config("nemotron-3-nano-30b-a3b"))) == (4, 5)  # + a tail of nine
    assert llama._kind_cycles(pat(get_model_config("granite-4.0-h-small"))) is None
    assert llama._kind_cycles(pat(get_model_config("tiny-granite-hybrid"))) is None
    assert llama._kind_cycles((("a", True),) * 8) is None  # a homogeneous run has period 1
    assert llama._kind_cycles((1, 2, 1, 2, 1)) == (2, 2)


@pytest.mark.parametrize("blocks", ["MEMEM*E" * 2 + "MEM*E", "MEMEM*E" * 2, "MEMEM*E" * 3 + "ME"])
def test_the_cycle_scan_equals_the_same_layers_scanned_run_by_run(blocks, monkeypatch):
    """Bit for bit in float32: whole cycles as one scan body (+ the peeled
    tail) against one scan a homogeneous run, the branch every other model
    of mixer kinds takes. Run operation by operation (``disable_jit``): a
    compiled program may fuse the two shapes differently and round the last
    bit another way (1e-6 here), which says nothing about the layers run."""
    types, ffn = nemotron_h_layers(blocks)
    model = get_model_config("tiny-nemotron-h", num_layers=len(types), layer_types=types, layer_ffn=ffn)
    prompts = [tokens(5, seed=1)]  # one prefill step, then a decode step
    engines = [make_engine(model, max_batched=16) for _ in range(2)]
    with jax.disable_jit():
        cyc = greedy(engines[0], prompts, max_tokens=2)
        monkeypatch.setattr(llama, "_kind_cycles", lambda kinds: None)
        runs = greedy(engines[1], prompts, max_tokens=2)
    for (t1, l1, _a), (t2, l2, _b) in zip(cyc, runs):
        assert t1 == t2 and len(l1) == 2
        np.testing.assert_array_equal(l1, l2)
    assert_matches_reference(engines[0], prompts[0], cyc[0][0], cyc[0][1],
                             dict(PUBLISHED, hybrid_override_pattern=blocks, num_hidden_layers=len(blocks)))


def test_the_ffn_leaves_are_told_from_the_layers_own():
    assert llama.is_ffn_leaf("post_norm") and llama.is_ffn_leaf("ws_up") and llama.is_ffn_leaf("router_bias")
    assert not llama.is_ffn_leaf("input_norm") and not llama.is_ffn_leaf("wo") and not llama.is_ffn_leaf("wq")


# --- the engine against the reference ---------------------------------------------------


def test_the_preset_runs_the_flat_step_over_both_pools():
    eng = make_engine()
    r, spec = eng.runner, eng._swa
    assert r._flat is not None and isinstance(r.kv_swa, ssm.StatePool)
    assert r.kv_cache.shape[0] == LA == 3 and r.kv_swa.ssm.shape[0] == r.kv_swa.conv.shape[0] == LM == 8
    assert (len(spec.kv_layers), len(spec.state_layers)) == (3, 8)
    assert r.params["layers"]["post_norm"].shape[0] == LE == 8 and r.params["layers"]["input_norm"].shape[0] == 11


@pytest.mark.parametrize("case", ["chunked_prefill", "eight_rows", "interpret"])
def test_prefill_then_decode_match_the_reference(case, monkeypatch):
    """Prefill in chunks, then decode through the state pool and the paged
    pool: the reference's full forward, log-probs compared."""
    if case == "interpret":
        monkeypatch.setenv("LLMD_PALLAS", "interpret")
    if case == "eight_rows":
        eng = make_engine(max_seqs=10, max_batched=48)
        prompts = [tokens(5 + 7 * i, seed=40 + i) for i in range(9)]
    else:
        eng = make_engine(max_batched=16 if case == "interpret" else 32)
        prompts = [tokens(75, seed=1), tokens(33, seed=2), tokens(7, seed=3)][: 2 if case == "interpret" else 3]
    outs = greedy(eng, prompts, max_tokens=3 if case == "interpret" else 8)
    for p, (toks, lps, _r) in zip(prompts, outs):
        assert_matches_reference(eng, p, toks, lps)
    if case == "eight_rows":
        assert eng.stats.steps_decode_total > 0 and max(eng.stats.live_tokens_total, 0) > 0
    if case == "interpret":
        assert eng.runner.kernel_plans["ssm_update"] == {"pallas"}


def test_a_snapshot_miss_then_hits_then_a_restart_at_the_model_length():
    """The cell's traffic in miniature: a context served cold leaves its
    snapshot; the same context again is a HIT at its last full page; a
    sequence that ran to the model length comes back with its context and is
    a hit again: every answer the reference's."""
    eng = make_engine(max_len=96, max_batched=16)
    shared = tokens(40, seed=5)
    a, b, c = (shared + tokens(n, seed=s) for n, s in ((7, 6), (13, 7), (11, 8)))
    (_t, _l, req), = greedy(eng, [a])
    assert snapshots(eng) == (0, 0, 2) and req.num_cached_tokens == 0  # a's prompt end, and the last page its answer fills
    (toks, lps, req), = greedy(eng, [b])
    assert snapshots(eng)[:2] == (0, 1) and req.num_cached_tokens == 0  # a MISS that leaves the snapshot
    assert_matches_reference(eng, b, toks, lps)
    (toks, lps, req), = greedy(eng, [c])
    assert snapshots(eng)[:2] == (1, 1) and req.num_cached_tokens == len(shared)
    assert_matches_reference(eng, c, toks, lps)
    # resident decode: the context, decoded up to the model length, then again
    ctx = tokens(61, seed=9)
    room = 96 - len(ctx) - 1
    (toks, lps, req), = greedy(eng, [ctx], max_tokens=room)
    assert len(toks) == room and req.num_cached_tokens == 0
    assert_matches_reference(eng, ctx, toks, lps)
    hits, _, captures = snapshots(eng)
    (toks2, lps2, req), = greedy(eng, [ctx], max_tokens=room)
    assert snapshots(eng)[0] == hits + 1 and req.num_cached_tokens == (len(ctx) - 1) // PAGE * PAGE
    # (a sequence that fills the model leaves nothing at its last page: no prompt goes on from there)
    assert snapshots(eng)[2] == captures and eng.stats.retained_finish_captures_total == 3
    assert toks2 == toks
    assert_matches_reference(eng, ctx, toks2, lps2)


# --- the counters, one each ---------------------------------------------------------------


@pytest.fixture(scope="module")
def counted():
    """Three prompts (75, 33, 7 tokens) decoded 8 tokens each, then the first
    again (a hit), on one engine: (stats, steps)."""
    eng = make_engine(max_seqs=4)
    prompts = [tokens(75, seed=1), tokens(33, seed=2), tokens(7, seed=3)]
    greedy(eng, prompts, max_tokens=8)
    eng._refresh_gauges()
    first = {f: getattr(eng.stats, f) for f in vars(eng.stats) if isinstance(getattr(eng.stats, f), (int, float))}
    greedy(eng, [prompts[0]], max_tokens=2)
    eng._refresh_gauges()
    return first, eng.stats, eng


COUNTERS = {
    # the mixers' rows and tokens: 8 mixer layers x (decode rows; prefill tokens)
    "ssm_update_rows_total": lambda s, st: s["ssm_update_rows_total"] == LM * 3 * 7,
    "ssm_scan_tokens_total": lambda s, st: s["ssm_scan_tokens_total"] == LM * (75 + 33 + 7),
    "state_bytes_in_use_total": lambda s, st: s["state_bytes_in_use_total"] > 0,
    "state_snapshot_hits_total": lambda s, st: (s["state_snapshot_hits_total"], st.state_snapshot_hits_total) == (0, 1),
    "state_snapshot_misses_total": lambda s, st: st.state_snapshot_misses_total == 0,
    "state_snapshot_captures_total": lambda s, st: s["state_snapshot_captures_total"] >= 3,
    "state_snapshot_evictions_total": lambda s, st: st.state_snapshot_evictions_total == 0,
    # every capture took its key from its admission's walk, and was timed
    "retained_capture_rehashed_total": lambda s, st: st.retained_capture_rehashed_total == 0,
    "retained_capture_host_ms_total": lambda s, st: 0 < s["retained_capture_host_ms_total"] <= st.retained_capture_host_ms_total,
    # one count a grouped expert layer for its TWO matmuls: 8 expert layers a step, none for the 3 layers without FFN
    "moe_grouped_calls_total": lambda s, st: s["moe_grouped_calls_total"] == LE * s["engine_steps_total"],
    "moe_picks_total": lambda s, st: s["moe_picks_total"] % (LE * 2 * 16) == 0 and s["moe_picks_total"] > 0,
    "moe_picks_held_total": lambda s, st: 0 < s["moe_picks_held_total"] < s["moe_picks_total"],
    "moe_groups_with_rows_total": lambda s, st: 0 < s["moe_groups_with_rows_total"] <= 4 * s["moe_grouped_calls_total"],
}


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_the_counter_counts_this_models_layers(counted, name):
    first, stats, _eng = counted
    assert COUNTERS[name](first, stats), (name, first.get(name), getattr(stats, name))


def test_the_slots_in_use_gauge_reaches_the_running_sequences():
    eng = make_engine(max_seqs=8, max_batched=64)
    for i in range(8):
        eng.add_request(tokens(5 + i, seed=70 + i), SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True))
    peak = 0
    while eng.has_work():
        eng.step()
        eng._refresh_gauges()
        peak = max(peak, eng.stats.state_slots_in_use)
    assert peak == 8
    eng._refresh_gauges()
    assert eng.stats.state_slots_in_use == 0
