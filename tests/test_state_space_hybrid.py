"""granite-4.0-h-small's architecture in miniature (``tiny-granite-hybrid``):
Mamba-2 mixers over a STATE POOL (a slot a sequence) with one NoPE attention
layer in ten over the paged pool, on the flat step, snapshots for prefix hits,
and an expert layer that holds a share — against the plain reference of
``perfbench/references/mamba2_gqa_moe_share.py`` (float32, the recurrence as a
scan over tokens, no kernel, no cache, no chunking).
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
    CacheConfig, EngineConfig, OffloadConfig, ParallelConfig, SchedulerConfig,
)
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import attention, llama, mamba  # noqa: E402
from llmd_tpu.models.common import rms_norm  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import ssm  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import mamba2_gqa_moe_share as ref  # noqa: E402
from perfbench.topologies import engine_state  # noqa: E402
from tests import retained_state  # noqa: E402

CONF_FILE = ROOT / "perfbench" / "configs" / "granite-4.0-h-small.1chip.json"
CONF = json.loads(CONF_FILE.read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference
PAGE = 4
MODEL = get_model_config("tiny-granite-hybrid")


def make_engine(num_blocks=256, max_batched=32, max_seqs=4, **cache) -> LLMEngine:
    return LLMEngine(EngineConfig(
        model=MODEL,
        cache=CacheConfig(page_size=PAGE, num_blocks=num_blocks, dtype="float32", **cache),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
    """[(tokens, log-probs, request)] per prompt, all in the engine at once;
    tokens from the step outputs (a preemption folds earlier outputs into the
    prompt)."""
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


def reference_logprobs(eng, prompt, toks):
    nxt, _best = ref.forward(eng.runner.params, prompt + toks, PUBLISHED)
    return np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)])


def assert_matches_reference(eng, prompt, toks, lps):
    assert len(toks) == len(lps) > 0
    np.testing.assert_allclose(lps, reference_logprobs(eng, prompt, toks), atol=5e-5)


def snapshots(eng):
    eng._refresh_gauges()
    s = eng.stats
    return (s.state_snapshot_hits_total, s.state_snapshot_misses_total, s.state_snapshot_captures_total)


# --- (a) the scan over a ragged flat batch -------------------------------------

T_BUCKET, ROWS, CAP = 48, 8, 16  # rows of at most CAP tokens: a 40-token chunk is three


def _flat_step(segments, n_slots):
    """The flat step's per-row arrays for ``segments`` [(slot, pos0, n tokens,
    kind)], chunks cut into rows of CAP, as the runner cuts them at 64."""
    slot, start, qlen, pos0, kind, t = [], [], [], [], [], 0
    for s, p0, n, k in segments:
        for off in range(0, n, CAP):
            w = min(CAP, n - off)
            slot.append(s), start.append(t), qlen.append(w), pos0.append(p0 + off), kind.append(k)
            t += w
    pad = ROWS - len(slot)
    i32 = lambda a, fill: jnp.asarray(a + [fill] * pad, jnp.int32)  # noqa: E731
    return i32(slot, 0), i32(start, t), i32(qlen, 0), i32(pos0, 0), i32(kind, 0), t


@jax.jit
def _mix_step(lp, pool, x, slot, start, qlen, pos0, kind):
    """One mixer (plane 0) over a flat stream ``x`` [T, H]: what
    ``llama.layer_body`` does with a mamba layer."""
    t = jnp.arange(T_BUCKET)
    ends = start + qlen
    row_of = jnp.clip(jnp.searchsorted(ends, t, side="right"), 0, ROWS - 1).astype(jnp.int32)
    rows = ssm.state_rows(slot, start, qlen, pos0, kind, row_of, t < ends[-1])
    h = rms_norm(x, lp["input_norm"], MODEL.rms_norm_eps)[:, None, :]
    out, pool = mamba.mix(h, lp, pool, jnp.int32(0), rows, MODEL, None, row_cap=CAP)
    return x + MODEL.residual_multiplier * out[:, 0], pool


@pytest.fixture(scope="module")
def mixer():
    params = llama.init_params(MODEL, jax.random.key(3))
    lp = {**jax.tree.map(lambda a: a[0], params["mamba_layers"]),
          "input_norm": params["layers"]["input_norm"][0]}
    x = jax.random.normal(jax.random.key(4), (40, MODEL.hidden_size), jnp.float32)
    other = jax.random.normal(jax.random.key(5), (41, MODEL.hidden_size), jnp.float32)
    dims = rc.freeze(PUBLISHED, ref.KEYS)
    with jax.default_matmul_precision("highest"):
        want = ref._mamba(params["layers"], params["mamba_layers"], jnp.int32(0), jnp.int32(0), x, dims)
        want_other = ref._mamba(params["layers"], params["mamba_layers"], jnp.int32(0), jnp.int32(0), other, dims)
    return lp, x, other, np.asarray(want), np.asarray(want_other)


def _fresh_pool(n_slots=5):
    m = MODEL
    return ssm.StatePool(
        # Whatever a slot held before is not the new owner's: start from noise.
        ssm=jax.random.normal(jax.random.key(9), (1, n_slots, m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state)),
        conv=jax.random.normal(jax.random.key(8), (1, n_slots, m.mamba_d_conv - 1, m.mamba_conv_dim)),
    )


@pytest.mark.parametrize("split", range(1, 40))
def test_the_chunked_scan_equals_the_recurrence_at_every_split(mixer, split):
    """A 40-token prompt prefilled as [0, split) and [split, 40), each chunk
    in a step it shares with another sequence's decode row (and, in the first
    step, with that sequence's own short prefill), its state carried through
    its slot: the mixer's output is the token-by-token recurrence's."""
    lp, x, other, want, want_other = mixer
    pool = _fresh_pool()

    def stream(parts):
        flat = jnp.concatenate(parts)
        return jnp.concatenate([flat, jnp.zeros((T_BUCKET - flat.shape[0], flat.shape[1]))])

    # step 1: the other sequence's first 2 tokens (slot 3), then our first chunk (slot 1)
    meta = _flat_step([(3, 0, 2, ssm.KIND_PREFILL), (1, 0, split, ssm.KIND_PREFILL)], 5)
    y1, pool = _mix_step(lp, pool, stream([other[:2], x[:split]]), *meta[:5])
    # step 2: our second chunk, then the other sequence's decode row at position 2
    meta = _flat_step([(1, split, 40 - split, ssm.KIND_PREFILL), (3, 2, 1, ssm.KIND_DECODE)], 5)
    y2, pool = _mix_step(lp, pool, stream([x[split:], other[2:3]]), *meta[:5])
    got = np.concatenate([y1[2:2 + split], y2[:40 - split]])
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.concatenate([y1[:2], y2[40 - split:41 - split]]), want_other[:3], atol=2e-5)
    # a slot no row touched is as it was
    np.testing.assert_array_equal(pool.ssm[0, 2], _fresh_pool().ssm[0, 2])


@pytest.mark.parametrize("count", [0, 1, 3, 5])
def test_the_update_kernel_equals_its_xla_form(count):
    """The Pallas decode update in interpret mode: live entries updated in
    place, the entries behind them and every other slot untouched."""
    ks = jax.random.split(jax.random.key(count), 6)
    L, S, H, P, N, U = 2, 7, 4, 8, 16, 5
    pool = jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32)
    slots = jax.random.permutation(ks[1], S)[:U].astype(jnp.int32)
    args = (pool, jnp.int32(1), slots, jnp.int32(count), jax.random.uniform(ks[2], (U, H)),
            jax.random.normal(ks[3], (U, H, P)), jax.random.normal(ks[4], (U, N)), jax.random.normal(ks[5], (U, N)))
    want_pool, want_y = ssm.ssm_update_xla(*args)
    got_pool, got_y = ssm.ssm_update_pallas(*args, interpret=True)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-5)
    np.testing.assert_allclose(got_y[:count], want_y[:count], atol=1e-5)
    untouched = [s for s in range(S) if s not in set(np.asarray(slots[:count]).tolist())]
    np.testing.assert_array_equal(got_pool[:, untouched], pool[:, untouched])


def test_the_flat_step_in_interpret_mode_matches_the_reference(monkeypatch):
    """The engine with every Pallas kernel interpreted: the state update and
    the scan's slot reads and writes as the chip runs them."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    eng = make_engine(max_batched=16)
    prompts = [tokens(21, seed=61), tokens(6, seed=62)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=3)):
        assert_matches_reference(eng, p, toks, lps)
    assert eng.runner.kernel_plans["ssm_update"] == {"pallas"}  # interpreted


# --- (b)-(f) the engine against the reference ----------------------------------


def test_the_preset_runs_the_flat_step_over_a_state_pool():
    eng = make_engine()
    r, spec = eng.runner, eng._swa
    assert r._flat is not None and isinstance(r.kv_swa, ssm.StatePool)
    assert (spec.kv_layers, spec.state_layers) == ((5,), (0, 1, 2, 3, 4, 6, 7, 8, 9))
    assert r.kv_cache.shape[0] == 1 and r.kv_swa.ssm.shape[0] == r.kv_swa.conv.shape[0] == 9
    # the pool on the device holds what its allocator hands out (4 running
    # slots + 8 snapshots) and the scan's scratch slot
    assert eng.swa_allocator.num_pages == 4 + 2 * 4 == r.kv_swa.ssm.shape[1] - 1
    assert r.kv_swa.ssm.dtype == jnp.float32
    assert "state_slots" in {f.name for f in r._layout(11, r.flat_rows, 16).fields}
    # no layer rotates, and the scale is the configuration's
    assert set(MODEL.layer_rope) == {None} and MODEL.sm_scale == 1 / 16 != MODEL.head_dim ** -0.5
    assert llama._scan_period(tuple(int(t == "mamba") for t in MODEL.layer_types)) is None


def test_prefill_then_decode_match_the_reference():
    """Prefill in chunks (a budget of 32: the 75-token prompt takes three,
    cut again at its last full page), then decode through the state pool and
    the cache: the reference's full forward, logits compared."""
    eng = make_engine()
    prompts = [tokens(75, seed=1), tokens(33, seed=2), tokens(7, seed=3)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=8)):
        assert_matches_reference(eng, p, toks, lps)
    assert 0 < eng.stats.moe_picks_held_total < eng.stats.moe_picks_total
    eng._refresh_gauges()
    # 9 mixer layers x (decode rows; prefill tokens)
    assert eng.stats.ssm_update_rows_total == 9 * 3 * 7
    assert eng.stats.ssm_scan_tokens_total == 9 * (75 + 33 + 7)
    assert eng.stats.state_bytes_in_use_total > 0 and eng.stats.kv_bytes_in_use_total > 0


def test_chunks_that_share_their_steps_equal_the_prompt_served_alone():
    long = tokens(70, seed=11)
    (alone_t, alone_lp, _r), = greedy(make_engine(max_batched=16), [long])
    eng = make_engine(max_batched=16)
    others = [tokens(5, seed=12), tokens(9, seed=13)]
    for p in others:
        eng.add_request(list(p), SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True))
    for _ in range(3):  # the others decode while the long prompt's chunks come
        eng.step()
    rid = eng.add_request(list(long), SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=True))
    req = eng.scheduler.waiting[-1]
    toks = []
    while eng.has_work():
        toks += [t for out in eng.step() if out.request_id == rid for t in out.new_token_ids]
    lps = np.asarray(req.output_logprobs)
    assert eng.stats.steps_mixed_total >= 4
    assert toks == alone_t
    np.testing.assert_allclose(lps, alone_lp, atol=2e-5)
    assert_matches_reference(eng, long, toks, lps)


def test_a_snapshot_hit_equals_cold_and_an_evicted_snapshot_is_a_plain_prefill():
    """Pages and state at the SAME boundary. The first request leaves its own
    prompt end behind; the second finds the shared pages and no snapshot at
    their end: a MISS, whose chunk ends there and leaves the snapshot; the
    third is a HIT of the whole shared prefix."""
    eng = make_engine(max_batched=16)
    shared = tokens(40, seed=5)
    a, b, c, d = (shared + tokens(n, seed=s) for n, s in ((7, 6), (13, 7), (11, 8), (9, 9)))
    (_t, _l, req), = greedy(eng, [a])
    assert snapshots(eng) == (0, 0, 2) and req.num_cached_tokens == 0  # a's prompt end, and the last page its answer fills
    (toks, lps, req), = greedy(eng, [b])
    assert snapshots(eng) == (0, 1, 5) and req.num_cached_tokens == 0  # the run's end, b's own end, its answer's last page
    assert_matches_reference(eng, b, toks, lps)
    (toks, lps, req), = greedy(eng, [c])
    assert snapshots(eng)[:2] == (1, 1) and req.num_cached_tokens == len(shared)
    assert_matches_reference(eng, c, toks, lps)  # == the same request served cold: the reference has no cache
    # a session's next turn hits the snapshot its own answer left behind at the last page it filled
    nxt = c + toks + tokens(5, seed=10)
    (toks2, lps2, req), = greedy(eng, [nxt])
    assert snapshots(eng)[0] == 2 and req.num_cached_tokens == (len(c) + len(toks) - 1) // PAGE * PAGE
    assert_matches_reference(eng, nxt, toks2, lps2)
    # every snapshot evicted: the pages alone serve no hit
    while eng._swa_sections.evict_one():
        pass
    eng._refresh_gauges()
    assert eng.stats.state_snapshots == 0 and eng.stats.state_snapshot_evictions_total >= 4
    (toks, lps, req), = greedy(eng, [d])
    assert req.num_cached_tokens == 0 and snapshots(eng)[:2] == (2, 2)
    assert_matches_reference(eng, d, toks, lps)


def test_the_snapshot_at_a_sequences_last_page_is_the_references_state_there():
    """The Mamba-2 slot: ``tests/retained_state.py``."""
    retained_state.check_the_snapshot_at_a_sequences_last_page(
        make_engine(max_batched=16), greedy, ref, PUBLISHED, assert_matches_reference)


def test_preemption_and_resume_match_the_reference():
    prompts = [tokens(48 + i, seed=20 + i) for i in range(3)]
    eng = make_engine(num_blocks=44)  # admits the three prompts and not their growth
    outs = greedy(eng, prompts, max_tokens=24)
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    for p, (toks, lps, _r) in zip(prompts, outs):
        assert_matches_reference(eng, p, toks, lps)
    # every slot came back: the running ones at finish and at preemption
    eng._refresh_gauges()
    assert eng.stats.state_slots_in_use == 0


def test_a_reused_slot_starts_from_zeros():
    """One running slot: every request takes the slot the last one left full."""
    eng = make_engine(max_seqs=1, enable_prefix_caching=False)
    assert eng._swa_sections is None and eng.swa_allocator.num_pages == 1
    for seed in (31, 32, 33):
        p = tokens(19, seed=seed)
        (toks, lps, req), = greedy(eng, [p])
        assert_matches_reference(eng, p, toks, lps)
    assert float(jnp.max(jnp.abs(eng.runner.kv_swa.ssm[:, 0]))) > 0


# --- (h) what is refused at start ------------------------------------------------


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
    "int8 weights": dict(model=dataclasses.replace(MODEL, quantization="int8")),
}


@pytest.mark.parametrize("what", REFUSED)
def test_every_other_road_is_refused_at_start(what):
    cfg = EngineConfig(**{"model": MODEL, **REFUSED[what]})
    with pytest.raises(ValueError, match="state-space layers do not run with") as e:
        cfg.check_state_space()
    assert what in str(e.value)
    if what not in ("a sharded mesh",):  # (the mesh itself needs devices)
        with pytest.raises(ValueError):
            LLMEngine(cfg)


def test_a_model_without_state_space_layers_is_not_checked():
    EngineConfig(model=get_model_config("tiny"), scheduler=SchedulerConfig(decode_window=4)).check_state_space()
    with pytest.raises(NotImplementedError, match="flat step only"):
        make_engine().runner.run_embed([[1, 2, 3]])


# --- the configuration -----------------------------------------------------------


def test_the_configuration_file_reaches_the_program_as_published():
    """``topologies/engine_state.py`` builds the model from the file: the
    published widths, the router's published width with the file's count as
    the experts held, ``layer_types`` cut to the depth."""
    cfg = engine_state.engine_config(CONF, seed=0, rehearse=False)
    m, preset = cfg.model, get_model_config("granite-4.0-h-small")
    assert (m.num_experts, m.held_experts, m.held_experts_first) == (72, CONF["num_local_experts"], 0)
    assert m.num_experts == CONF["published"]["num_local_experts"] == preset.num_experts
    assert CONF["num_experts"] == CONF["num_local_experts"] == 36  # the readers' name for the same count
    assert m.vocab_size == CONF["vocab_size"] == preset.vocab_size // 2 == CONF["published"]["vocab_size"] // 2
    assert m.layer_types == tuple(CONF["layer_types"][: m.num_layers]) and m.num_layers == CONF["num_hidden_layers"] == 10
    assert preset.layer_types == tuple(CONF["layer_types"]) and preset.num_layers == CONF["published"]["num_hidden_layers"]
    for field in ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "moe_intermediate_size",
                  "shared_expert_intermediate_size", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                  "rope_parameters", "tie_word_embeddings", "attention_multiplier", "embedding_multiplier",
                  "residual_multiplier", "logits_scaling", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                  "mamba_n_groups", "mamba_d_conv", "router_scoring"):
        assert getattr(m, field) == getattr(preset, field), field
    assert (m.hidden_size, m.mamba_d_inner, m.mamba_conv_dim, m.sm_scale) == (4096, 8192, 8448, 1 / 128)
    assert m.mamba_d_inner == CONF["mamba_expand"] * CONF["hidden_size"]
    assert sorted(CONF["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    tiny = engine_state.engine_config(CONF, seed=0, rehearse=True).model
    assert tiny.name == "tiny-granite-hybrid"
    assert {k: PUBLISHED[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state")} == {
        "mamba_n_heads": tiny.mamba_n_heads, "mamba_d_head": tiny.mamba_d_head, "mamba_d_state": tiny.mamba_d_state}


def test_the_mixer_is_a_kind_and_the_second_pools_spec_says_what_it_holds():
    """One dispatch point: a layer's kind names its weight stack, its pool
    and its ``mix``; a model of one kind has it L times, over the shared
    stack. The two specs of the second pool declare ``recurrent`` (no type
    test, no flag beside them)."""
    from llmd_tpu.config import state_slot_spec, swa_ring_spec

    kinds = llama.mixer_kinds(MODEL)
    assert [k is mamba.KIND for k in kinds] == [t == "mamba" for t in MODEL.layer_types]
    assert {k for k in kinds if k is not mamba.KIND} == {llama.ATTENTION}
    assert (mamba.KIND.stack, mamba.KIND.pool, llama.ATTENTION.stack, llama.ATTENTION.pool) == (
        "mamba_layers", 1, "attn_layers", 0)
    assert mamba.KIND.init is mamba.init_layers and llama.ATTENTION.mix is attention.mix
    assert llama.mixer_kinds(get_model_config("tiny-moe")) == (attention.KIND,) * get_model_config("tiny-moe").num_layers
    assert attention.KIND.stack == "layers" and llama.ATTENTION == attention.KIND._replace(stack="attn_layers")
    params = jax.eval_shape(lambda k: llama.init_params(MODEL, k), jax.random.key(0))
    assert params["mamba_layers"]["m_in"].shape[0] == 9 and params["attn_layers"]["wq"].shape[0] == 1
    assert "wq" not in params["layers"]
    sched = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32)
    spec = state_slot_spec(MODEL, sched)
    assert spec.recurrent and spec.num_swa_blocks == 4 and spec.ring_pages == 1
    exa = get_model_config("tiny-exaone")
    assert swa_ring_spec(exa, CacheConfig(page_size=PAGE, num_blocks=64, swa_ring=True), sched).recurrent is False
    eng = make_engine()
    assert eng.runner.state_pool and eng._state_pool and eng.runner.kv_swa.ssm.dtype == jnp.float32
    # the knobs nothing read are gone
    assert not {"mamba_chunk_size", "mamba_state_dtype"} & {f.name for f in dataclasses.fields(MODEL)}
    assert "align_chunks" not in {f.name for f in dataclasses.fields(spec)}
