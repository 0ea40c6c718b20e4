"""Qwen3-Next's architecture in miniature (``tiny-qwen3-next``): Gated DeltaNet
layers over the STATE POOL with one gated full-attention layer in four over
the paged pool (a quarter-width rotation, an output gate, zero-centred norms),
an expert layer that holds a share with a sigmoid-gated shared expert, on the
flat step with snapshots for prefix hits — against the plain reference of
``perfbench/references/gdn_gqa_gated_moe_share.py`` (float32, the delta rule
token by token, no kernel, no cache, no chunking).
"""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.config import (  # noqa: E402
    CacheConfig, EngineConfig, ModelConfig, OffloadConfig, ParallelConfig, SchedulerConfig, state_slot_spec,
)
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import gdn, llama, mamba, moe  # noqa: E402
from llmd_tpu.models.common import apply_rope, rms_norm, rope_tables  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import ssm  # noqa: E402
from llmd_tpu.serve.metrics import render_metrics  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import gdn_gqa_gated_moe_share as ref  # noqa: E402
from perfbench.topologies import engine_gdn  # noqa: E402
from tests import retained_state  # noqa: E402

CONF = json.loads((ROOT / "perfbench" / "configs" / "qwen3-next-80b-a3b.1chip.json").read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference
PAGE = 4
MODEL = get_model_config("tiny-qwen3-next")
GRANITE = get_model_config("tiny-granite-hybrid")


def make_engine(num_blocks=256, max_batched=32, max_seqs=4, **cache) -> LLMEngine:
    return LLMEngine(EngineConfig(
        model=MODEL,
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


def assert_matches_reference(eng, prompt, toks, lps, atol=2e-4):
    assert len(toks) == len(lps) > 0
    nxt, _best = ref.forward(eng.runner.params, prompt + toks, PUBLISHED)
    np.testing.assert_allclose(lps, np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)]), atol=atol)


def snapshots(eng):
    eng._refresh_gauges()
    s = eng.stats
    return (s.state_snapshot_hits_total, s.state_snapshot_misses_total, s.state_snapshot_captures_total)


# --- the engine against the reference -----------------------------------------


def test_the_preset_runs_the_flat_step_over_both_pools_in_one_cycle_body():
    eng = make_engine()
    r, spec = eng.runner, eng._swa
    assert r._flat is not None and isinstance(r.kv_swa, ssm.StatePool)
    assert (spec.kv_layers, spec.state_layers) == ((3, 7), (0, 1, 2, 4, 5, 6))
    assert r.kv_cache.shape[0] == 2 and r.kv_swa.ssm.shape[0] == r.kv_swa.conv.shape[0] == 6
    # a slot: the delta-rule state [value heads, key dim, value dim] in float32
    # and the conv's last three inputs over q, k and v
    assert r.kv_swa.ssm.shape[2:] == (4, 8, 8) == MODEL.state_shapes[0] and r.kv_swa.ssm.dtype == jnp.float32
    assert r.kv_swa.conv.shape[2:] == (3, 2 * 2 * 8 + 4 * 8) == MODEL.state_shapes[1]
    assert eng.swa_allocator.num_pages == 4 + 2 * 4 == r.kv_swa.ssm.shape[1] - 1
    # ``L L L F`` x 2 is ONE scanned body of four layers, both pools carried
    kinds = llama.mixer_kinds(MODEL)
    assert llama._kind_cycles(tuple(zip(kinds, (True,) * 8))) == (4, 2)
    assert [k is gdn.KIND for k in kinds] == [t == "linear_attention" for t in MODEL.layer_types]
    assert (gdn.KIND.stack, gdn.KIND.pool, gdn.KIND.init) == ("gdn_layers", 1, gdn.init_layers)
    assert {k for k in kinds if k is not gdn.KIND} == {llama.ATTENTION}
    # the attention layers: a q projection of twice the width, a quarter rotated
    assert r.params["attn_layers"]["wq"].shape == (2, 64, 4 * 2 * 16) and MODEL.rotary_dim == 4
    assert "wq" not in r.params["layers"] and r.params["layers"]["ws_sig"].shape == (8, 64, 1)
    assert set(MODEL.layer_rope) == {0}
    # granite's slot, through the same property
    assert GRANITE.state_shapes == ((4, 8, 16), (3, 4 * 8 + 2 * 16)) and not GRANITE.delta_rule


def test_prefill_then_decode_match_the_reference_and_count():
    """Prefill in chunks (a budget of 32: the 75-token prompt takes three, cut
    again at its last full page), then decode through the state pool and the
    cache: the reference's full forward pass, log-probs compared."""
    eng = make_engine()
    prompts = [tokens(75, seed=1), tokens(33, seed=2), tokens(7, seed=3)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=8)):
        assert_matches_reference(eng, p, toks, lps)
    assert 0 < eng.stats.moe_picks_held_total < eng.stats.moe_picks_total
    eng._refresh_gauges()
    st = eng.stats
    # 6 delta-rule layers x (decode rows; prefill tokens); the Mamba-2 mixers' counters stand still
    assert st.gdn_update_rows_total == 6 * 3 * 7 and st.gdn_scan_tokens_total == 6 * (75 + 33 + 7)
    assert st.gdn_scan_rows_total >= 6 * 5 and (st.ssm_update_rows_total, st.ssm_scan_tokens_total) == (0, 0)
    slot = 6 * 4 * 8 * 8 * 4  # a slot's recurrent state over the six layers
    assert st.gdn_state_bytes_moved_total == 2 * slot * (st.gdn_update_rows_total + st.gdn_scan_rows_total) // 6
    assert st.state_bytes_in_use_total > 0 and st.kv_bytes_in_use_total > 0
    text = render_metrics(st, "tiny-qwen3-next")
    for name in ("gdn_update_rows_total", "gdn_scan_rows_total", "gdn_scan_tokens_total", "gdn_state_bytes_moved_total"):
        assert f"llmd:{name}" in text or name in text


def test_the_flat_step_in_interpret_mode_matches_the_reference(monkeypatch):
    """The engine with every Pallas kernel interpreted: the delta-rule update
    and the scan's slot reads and writes as the chip runs them."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    eng = make_engine(max_batched=16)
    prompts = [tokens(21, seed=61), tokens(6, seed=62)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=3)):
        assert_matches_reference(eng, p, toks, lps)
    assert eng.runner.kernel_plans["gdn_update"] == {"pallas"}  # interpreted


def test_the_first_layers_pooled_state_is_the_references():
    """What the benchmark's comparison reads out of the pool: the FIRST
    delta-rule layer's state of a sequence's slot after its last computed
    token, per head, against ``first_mixer_state``."""
    eng = make_engine(max_batched=16)
    prompt = tokens(45, seed=21)
    eng.add_request(list(prompt), SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True))
    req, slot = eng.scheduler.waiting[0], None
    while eng.has_work():
        eng.step()
        slot = req.swa_block_ids[0] if req.swa_block_ids else slot
    seen = prompt + list(req.output_token_ids)[:-1]  # the last emitted token is never fed
    want = ref.first_mixer_state(eng.runner.params, seen + [0] * 7, len(seen), PUBLISHED)
    err = ref.state_error(np.asarray(eng.runner.kv_swa.ssm[0, slot]), want)
    assert err["head_max"] < 1e-4, err
    # the probe's controls are not the model: a state rounded to bfloat16, a raw beta, the decay after the update
    for wrong in ("probe_state_dtype", "probe_beta_raw", "probe_decay_after"):
        bad = ref.first_mixer_state(eng.runner.params, seen, len(seen), dict(PUBLISHED, **{wrong: "bfloat16"}))
        assert ref.state_error(np.asarray(eng.runner.kv_swa.ssm[0, slot]), bad)["head_max"] > 1e-3, wrong


def test_the_first_attention_layers_cached_keys_are_the_references():
    """What the benchmark's comparison reads out of the PAGES: the first
    attention layer's keys of a finished sequence's full pages, per token,
    against ``first_attention_keys``: behind them lie the first period's
    delta-rule layers and experts, the zero-centred norm and the rotation."""
    eng = make_engine(max_batched=16)
    prompt = tokens(45, seed=22)
    [(toks, _lps, _req)] = greedy(eng, [prompt], max_tokens=5)
    seen = prompt + toks[:-1]  # the last emitted token is never fed
    pages = eng.allocator.lookup_cached_prefix(seen)
    assert len(pages) == len(seen) // PAGE
    rows = np.asarray(eng.runner.kv_cache[0, np.asarray(pages)][..., : MODEL.head_dim])  # [pages, Nk, page, D]
    keys = rows.transpose(0, 2, 1, 3).reshape(-1, MODEL.num_kv_heads, MODEL.head_dim)
    want = np.asarray(ref.first_attention_keys(eng.runner.params, seen + [0] * 3, PUBLISHED))[: len(keys)]
    err = ref.key_error(keys, want)
    assert err["token_p99"] < 1e-4 and err["far_share"] == 0.0, err
    # the probe's controls are not the model: every dimension rotated, one held expert fewer (which some tokens
    # pick in the first three layers and the others do not), a raw beta upstream
    turned = ref.first_attention_keys(eng.runner.params, seen, dict(PUBLISHED, probe_full_rotation=True))
    assert ref.key_error(keys, np.asarray(turned)[: len(keys)])["token_median"] > 0.5
    fewer = ref.key_error(keys, np.asarray(
        ref.first_attention_keys(eng.runner.params, seen, dict(PUBLISHED, experts_used=3)))[: len(keys)])
    assert fewer["token_p99"] > 0.05 and fewer["token_p99"] > 100 * err["token_p99"]
    raw = ref.first_attention_keys(eng.runner.params, seen, dict(PUBLISHED, probe_beta_raw=True))
    assert ref.key_error(keys, np.asarray(raw)[: len(keys)])["token_median"] > 0.05


def test_a_snapshot_hit_equals_cold_and_chunks_share_their_steps():
    """Pages and state at the SAME boundary: the first request leaves its own
    prompt end behind; the second finds the shared pages and no snapshot at
    their end (a MISS whose chunk ends there and leaves the snapshot); the
    third is a HIT of the whole shared prefix, served while another sequence
    decodes."""
    eng = make_engine(max_batched=16)
    shared = tokens(40, seed=5)
    a, b, c = (shared + tokens(n, seed=s) for n, s in ((7, 6), (13, 7), (11, 8)))
    (_t, _l, req), = greedy(eng, [a])
    assert snapshots(eng) == (0, 0, 2) and req.num_cached_tokens == 0  # a's prompt end, and the last page its answer fills
    (toks, lps, req), = greedy(eng, [b])
    assert snapshots(eng) == (0, 1, 5) and req.num_cached_tokens == 0  # the run's end, b's own end, its answer's last page
    assert_matches_reference(eng, b, toks, lps)
    eng.add_request(tokens(9, seed=9), SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True))
    for _ in range(3):  # the other decodes while c's chunk comes
        eng.step()
    mixed = eng.stats.steps_mixed_total
    rid = eng.add_request(list(c), SamplingParams(max_tokens=7, temperature=0.0, ignore_eos=True, logprobs=True))
    req, toks = eng.scheduler.waiting[-1], []
    while eng.has_work():
        toks += [t for out in eng.step() if out.request_id == rid for t in out.new_token_ids]
    assert snapshots(eng)[:2] == (1, 1) and req.num_cached_tokens == len(shared)
    assert eng.stats.steps_mixed_total > mixed
    # == the same request served cold and alone: the reference has no cache
    assert_matches_reference(eng, c, toks, np.asarray(req.output_logprobs))


def test_the_snapshot_at_a_sequences_last_page_is_the_references_state_there():
    """The delta-rule slot: ``tests/retained_state.py``."""
    retained_state.check_the_snapshot_at_a_sequences_last_page(
        make_engine(max_batched=16), greedy, ref, PUBLISHED, assert_matches_reference)


# --- the layers, each against a hand-written case ------------------------------


def test_the_rotation_turns_the_first_quarter_of_a_head_and_passes_the_rest():
    d, rot = 16, 4
    x = jax.random.normal(jax.random.key(0), (3, 1, 2, d), jnp.float32)
    pos = jnp.asarray([[0], [5], [1234]])
    cos, sin = rope_tables(pos, rot, 1e4)
    got = np.asarray(apply_rope(x, cos, sin))
    np.testing.assert_array_equal(got[..., rot:], np.asarray(x)[..., rot:])
    np.testing.assert_array_equal(got[0], np.asarray(x)[0])  # position 0 turns nothing
    # by hand: dimension i pairs with i + rot / 2, at frequency theta^(-2 i / rot)
    for t, p in enumerate((0, 5, 1234)):
        for i in range(rot // 2):
            ang = p * 1e4 ** (-2 * i / rot)
            a, b = np.asarray(x)[t, 0, :, i], np.asarray(x)[t, 0, :, i + rot // 2]
            np.testing.assert_allclose(got[t, 0, :, i], a * np.cos(ang) - b * np.sin(ang), atol=1e-5)
            np.testing.assert_allclose(got[t, 0, :, i + rot // 2], b * np.cos(ang) + a * np.sin(ang), atol=1e-5)
    # a table of half the head's width is the whole rotation every other model has
    cos, sin = rope_tables(pos, d, 1e4)
    assert np.abs(np.asarray(apply_rope(x, cos, sin))[1:, ..., rot:] - np.asarray(x)[1:, ..., rot:]).max() > 1e-2


def test_the_zero_centred_norm_applies_one_plus_its_weight():
    x = jax.random.normal(jax.random.key(1), (5, 8), jnp.float32)
    w = 0.1 * jax.random.normal(jax.random.key(2), (8,), jnp.float32)
    plain = np.asarray(x) / np.sqrt(np.mean(np.asarray(x) ** 2, axis=-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(rms_norm(x, w, 1e-6, zero_centered=True), plain * (1 + np.asarray(w)), atol=1e-6)
    np.testing.assert_allclose(rms_norm(x, w, 1e-6), plain * np.asarray(w), atol=1e-6)


@pytest.fixture(scope="module")
def layers():
    params = llama.init_params(MODEL, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (19, MODEL.hidden_size), jnp.float32)
    return params, x, rc.freeze(PUBLISHED, ref.KEYS)


def test_the_attention_gate_scales_each_heads_output_by_the_sigmoid_of_its_half(layers):
    """One gated attention layer by hand from the program's weights: the q
    projection's second half a head is the gate; without it, or with the whole
    head rotated, the result is another (the probe's controls)."""
    params, x, dims = layers
    ap, lp = params["attn_layers"], params["layers"]
    nq, nk, d, rot, t = 4, 2, 16, 4, x.shape[0]
    h = np.asarray(rms_norm(x, lp["input_norm"][3], 1e-6, zero_centered=True), np.float64)
    qg = (h @ np.asarray(ap["wq"][0], np.float64)).reshape(t, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ np.asarray(ap["wk"][0], np.float64)).reshape(t, nk, d)
    v = (h @ np.asarray(ap["wv"][0], np.float64)).reshape(t, nk, d)

    def zc(a, w):
        return a / np.sqrt(np.mean(a * a, axis=-1, keepdims=True) + 1e-6) * (1 + np.asarray(w, np.float64))

    def turn(a):
        out = a.copy()
        for i in range(rot // 2):
            ang = np.arange(t) * 1e4 ** (-2 * i / rot)
            c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
            out[..., i] = a[..., i] * c - a[..., i + rot // 2] * s
            out[..., i + rot // 2] = a[..., i + rot // 2] * c + a[..., i] * s
        return out

    q, k = turn(zc(q, ap["attn_q_norm"][0])), turn(zc(k, ap["attn_k_norm"][0]))
    k, v = np.repeat(k, nq // nk, axis=1), np.repeat(v, nq // nk, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    attn = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
    want = np.asarray(x) + (attn / (1 + np.exp(-gate))).reshape(t, nq * d) @ np.asarray(ap["wo"][0], np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref._attention(lp, ap, jnp.int32(3), jnp.int32(0), x, dims))
        for wrong in ("probe_no_attn_gate", "probe_full_rotation"):
            bad = ref._attention(lp, ap, jnp.int32(3), jnp.int32(0), x, rc.freeze(dict(PUBLISHED, **{wrong: True}), ref.KEYS))
            assert np.abs(np.asarray(bad) - want).max() > 1e-2, wrong
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_ranks_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_layer(layers):
    """THE SHARE TEST: one expert layer over all 8 experts (the uncut
    reference) = the sum of what each of 8 ranks' program computes of it from
    the one expert it holds, with the sigmoid-gated shared expert, which every
    rank computes alike, counted once."""
    whole = get_model_config("tiny-qwen3-next", held_experts=8)
    params = llama.init_params(whole, jax.random.key(7))
    lp_all, (_p, x, dims), i = params["layers"], layers, 5
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref._sparse_ffn(lp_all, jnp.int32(i), x, dims, 0)) - np.asarray(x)
        h = ref.zc_norm(x, lp_all["post_norm"][i], 1e-6)
        shared = np.asarray(jax.nn.sigmoid(h @ lp_all["ws_sig"][i]) * rc.swiglu(
            h, lp_all["ws_gate"][i], lp_all["ws_up"][i], lp_all["ws_down"][i]))
        ungated = np.asarray(ref._sparse_ffn(lp_all, jnp.int32(i), x, rc.freeze(
            dict(PUBLISHED, probe_no_shared_gate=True), ref.KEYS), 0)) - np.asarray(x)
    hn = rms_norm(x, lp_all["post_norm"][i], whole.rms_norm_eps, zero_centered=True)[None]
    total = np.zeros_like(uncut)
    for rank in range(8):
        cfg = get_model_config("tiny-qwen3-next", held_experts=1, held_experts_first=rank)
        lp = {k: a[i] for k, a in lp_all.items()}
        lp.update({k: lp[k][rank: rank + 1] for k in ("we_gate", "we_up", "we_down")})
        part = np.asarray(moe.moe_block_grouped(hn, lp, cfg)[0])
        with jax.default_matmul_precision("highest"):  # the rank's own reference agrees with its program
            rlp = {k: (a[:, rank: rank + 1] if k.startswith("we_") else a) for k, a in lp_all.items()}
            rpart = np.asarray(ref._sparse_ffn(rlp, jnp.int32(i), x, dims, rank)) - np.asarray(x)
        np.testing.assert_allclose(part, rpart, atol=3e-5)
        total += part - shared
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
    assert np.abs(shared).max() > 1e-2 and np.abs(uncut - shared).max() > 1e-2
    assert np.abs(ungated - uncut).max() > 1e-2  # the gate is no identity


# --- what is refused at start, for both kinds of recurrent state -----------------


REFUSED = {
    "speculative decoding": dict(scheduler=SchedulerConfig(speculative_ngram=True)),
    "fused decode windows": dict(scheduler=SchedulerConfig(decode_window=4)),
    "the bucketed or split step": dict(scheduler=SchedulerConfig(ragged_qlens=False)),
    "whole-prompt prefill": dict(scheduler=SchedulerConfig(enable_chunked_prefill=False)),
    "an int8 KV cache": dict(cache=CacheConfig(dtype="int8")),
    "the sliding-window ring": dict(cache=CacheConfig(swa_ring=True)),
    "prefix caching without retained snapshots": dict(cache=CacheConfig(swa_section_cache=0)),
    "tiered KV offload": dict(offload=OffloadConfig(enabled=True)),
    "P/D KV transfer": dict(kv_role="kv_producer"),
    "a sharded mesh": dict(parallel=ParallelConfig(tensor_parallel_size=2)),
    "ring prefill or dual-batch overlap": dict(parallel=ParallelConfig(enable_dbo=True)),
    "int8 weights": None,
}


@pytest.mark.parametrize("model", [MODEL, GRANITE], ids=lambda m: m.name)
@pytest.mark.parametrize("what", REFUSED)
def test_every_road_that_knows_pages_only_is_refused_for_either_recurrent_kind(what, model):
    over = REFUSED[what] or dict(model=dataclasses.replace(model, quantization="int8"))
    cfg = EngineConfig(**{"model": model, **over})
    with pytest.raises(ValueError, match="state-space layers do not run with") as e:
        cfg.check_state_space()
    assert what in str(e.value) and model.name in str(e.value)


def test_a_model_has_one_kind_of_recurrent_state_and_whole_sizes():
    with pytest.raises(ValueError, match="one kind of recurrent state"):
        dataclasses.replace(MODEL, layer_types=("linear_attention", "mamba") * 4)
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        dataclasses.replace(MODEL, linear_num_value_heads=3)
    with pytest.raises(ValueError, match="no even number of rotated"):
        dataclasses.replace(MODEL, partial_rotary_factor=0.2)
    with pytest.raises(ValueError, match="shared_expert_gate needs"):
        ModelConfig(shared_expert_gate=True)
    with pytest.raises(NotImplementedError, match="flat step only"):
        make_engine().runner.run_embed([[1, 2, 3]])
    spec = state_slot_spec(MODEL, SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32))
    assert spec.recurrent and spec.state_layers == MODEL.mamba_layers == (0, 1, 2, 4, 5, 6)


# --- the configuration -----------------------------------------------------------


def test_the_configuration_file_reaches_the_program_as_published():
    """``topologies/engine_gdn.py`` builds the model from the file: the
    published widths, the router's published width with the file's count as
    the experts held, ``layer_types`` from the interval, cut to the depth."""
    cfg = engine_gdn.engine_config(CONF, seed=0, rehearse=False)
    m, preset = cfg.model, get_model_config("qwen3-next-80b-a3b")
    assert (m.num_experts, m.held_experts, m.held_experts_first) == (512, CONF["num_experts"], 0) == (512, 64, 0)
    assert m.num_experts == CONF["published"]["num_experts"] == preset.num_experts
    assert m.vocab_size == CONF["vocab_size"] == preset.vocab_size // 8 == CONF["published"]["vocab_size"] // 8
    assert m.num_layers == CONF["num_hidden_layers"] == 12 and m.layer_types == preset.layer_types[:12]
    assert m.layer_types[:4] == ("linear_attention",) * 3 + ("full_attention",)
    assert preset.num_layers == CONF["published"]["num_hidden_layers"] == 48
    assert preset.layer_types == engine_gdn.layer_types(dict(CONF, num_hidden_layers=48))
    for field in ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "moe_intermediate_size",
                  "shared_expert_intermediate_size", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                  "rope_theta", "tie_word_embeddings", "partial_rotary_factor", "attn_output_gate",
                  "norm_zero_centered", "shared_expert_gate", "qk_norm", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
                  "linear_conv_kernel_dim", "router_scoring"):
        assert getattr(m, field) == getattr(preset, field), field
    assert (m.head_dim, m.rotary_dim, m.linear_conv_dim, m.state_shapes) == (
        256, 64, 8192, ((32, 128, 128), (3, 8192)))
    assert sorted(CONF["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg.cache.swa_sections == CONF["engine"]["state_snapshots"] == 96
    tiny = engine_gdn.engine_config(CONF, seed=0, rehearse=True).model
    assert tiny.name == "tiny-qwen3-next"
    for k in ("linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "partial_rotary_factor", "rms_norm_eps", "num_experts_per_tok"):
        assert PUBLISHED[k] == getattr(tiny, k), k
    assert ref.layer_kinds(PUBLISHED) == list(tiny.layer_types)
    # the engine_mixer module is left as it was found
    from perfbench.topologies import engine_mixer
    assert engine_mixer.engine_config is engine_gdn._mixer_engine_config
    assert engine_mixer.model_overrides is not engine_gdn.model_overrides
    assert mamba.KIND.stack == "mamba_layers"
