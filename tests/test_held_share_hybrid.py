"""K-EXAONE's architecture in miniature (``tiny-exaone``): window and full
attention over two KV pools on the flat step, RoPE on the sliding layers only,
a dense prefix before an aperiodic scan, and an expert layer that holds a
SHARE of the experts its router scores — against the plain reference of
``perfbench/references/gqa_swa_moe_share.py`` (float32, no kernel, no cache).
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

from llmd_tpu.config import CacheConfig, EngineConfig, SchedulerConfig  # noqa: E402
from llmd_tpu.engine import LLMEngine, SamplingParams  # noqa: E402
from llmd_tpu.models import llama, moe  # noqa: E402
from llmd_tpu.models.common import StepInput  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import grouped_gemm  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import gqa_swa_moe_share as ref  # noqa: E402
from perfbench.references import mamba2_gqa_moe_share as ref_granite  # noqa: E402
from perfbench.topologies import engine_hybrid  # noqa: E402

CONF_FILE = ROOT / "perfbench" / "configs" / "k-exaone-236b-a23b.1chip.json"
CONF = json.loads(CONF_FILE.read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference
WINDOW, PAGE = 16, 4


def make_engine(num_blocks=256, max_batched=32, max_seqs=4, ring=True, model=None, **cache) -> LLMEngine:
    return LLMEngine(EngineConfig(
        model=model or get_model_config("tiny-exaone"),
        cache=CacheConfig(page_size=PAGE, num_blocks=num_blocks, dtype="float32", swa_ring=ring, **cache),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
    """[(tokens, log-probs)] per prompt, all in the engine at once; tokens from
    the step outputs (a preemption folds earlier outputs into the prompt)."""
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


def reference_logprobs(eng, prompt, toks, conf=PUBLISHED, params=None):
    params = params or engine_hybrid.reference_params(eng.runner.params, eng.config.model)
    nxt, _best = ref.forward(params, prompt + toks, conf)
    return np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)])


def assert_matches_reference(eng, prompt, toks, lps):
    assert len(toks) == len(lps) > 0
    np.testing.assert_allclose(lps, reference_logprobs(eng, prompt, toks), atol=5e-5)


# --- the engine against the reference ---------------------------------------


def test_the_preset_runs_the_flat_step_over_two_pools():
    eng = make_engine()
    assert eng.runner._flat is not None and eng.runner.kv_swa is not None
    swa = eng._swa
    assert (swa.full_layers, swa.swa_layers) == ((3, 7), (0, 1, 2, 4, 5, 6))
    assert eng.runner.kv_cache.shape[0] == 2 and eng.runner.kv_swa.shape[0] == 6
    # the ring pool on the device holds what its allocator hands out: the
    # rings and the retained sections
    assert eng.runner.kv_swa.shape[1] == eng.swa_allocator.num_pages
    assert eng.swa_allocator.num_pages == 4 * swa.ring_pages + 2 * 4 * swa.max_section_pages(PAGE)


@pytest.mark.parametrize("ring", [True, False], ids=["ring", "one-pool"])
def test_prefill_then_decode_match_the_reference(ring):
    """Contexts of several windows, longer than one ring (13 pages of 4), with
    prefills split over steps that carry the other requests."""
    eng = make_engine(ring=ring)
    prompts = [tokens(9 * WINDOW + 3, seed=1), tokens(5 * WINDOW, seed=2), tokens(7, seed=3)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=8)):
        assert_matches_reference(eng, p, toks, lps)
    assert eng.stats.moe_picks_held_total < eng.stats.moe_picks_total


def test_a_hybrid_prefix_hit_matches_the_reference():
    """A miss leaves the section at the offered run's end behind, the next
    request over the shared prefix takes full pages + the section seeding a
    fresh ring, and computes only its tail."""
    eng = make_engine()
    shared = tokens(6 * WINDOW, seed=5)
    a, b, c = (shared + tokens(n, seed=s) for n, s in ((9, 6), (13, 7), (11, 8)))
    greedy(eng, [a])
    (toks, lps, req), = greedy(eng, [b])  # the main pool offers the shared pages: no section at their end
    assert (eng.stats.swa_section_hits_total, eng.stats.swa_section_misses_total) == (0, 1)
    assert req.num_cached_tokens == 0
    assert_matches_reference(eng, b, toks, lps)
    (toks, lps, req), = greedy(eng, [c])
    assert (eng.stats.swa_section_hits_total, eng.stats.swa_section_misses_total) == (1, 1)
    assert req.num_cached_tokens == len(shared)
    assert_matches_reference(eng, c, toks, lps)
    # a session's next turn hits the section its own prompt left behind
    nxt = c + toks + tokens(5, seed=9)
    (toks2, lps2, req), = greedy(eng, [nxt])
    assert eng.stats.swa_section_hits_total == 2 and req.num_cached_tokens >= len(c) // PAGE * PAGE - PAGE
    assert_matches_reference(eng, nxt, toks2, lps2)


def test_preemption_and_resume_match_the_reference():
    prompts = [tokens(3 * WINDOW + i, seed=20 + i) for i in range(3)]
    eng = make_engine(num_blocks=44)  # admits the three prompts and not their growth
    outs = greedy(eng, prompts, max_tokens=24)
    assert eng.scheduler.num_preemptions > 0, "pool not tight enough"
    for p, (toks, lps, _r) in zip(prompts, outs):
        assert_matches_reference(eng, p, toks, lps)


def test_sections_are_sized_from_the_sequences_and_the_dead_go_first():
    """2 x max_num_seqs sections (32 sessions thrash 8); an entry whose full
    page the main pool no longer caches can serve no hit and is evicted
    before a live one."""
    eng = make_engine(max_seqs=4)
    cache = eng._swa_sections
    assert cache.capacity == 8
    a, b = tokens(3 * WINDOW, seed=31), tokens(3 * WINDOW, seed=32)
    greedy(eng, [a])
    greedy(eng, [b])
    (key_a, _, _), (key_b, _, _) = (eng._section_key(p, b"") for p in (a, b))
    assert cache.has(key_a) and cache.has(key_b)
    eng.allocator._cached.pop(key_b)  # as an eviction in the main pool leaves it
    assert cache.evict_one() and cache.has(key_a) and not cache.has(key_b)


def test_a_spent_prompt_end_goes_before_a_shared_prefix():
    """Eviction order among live sections: a session's own section (at a
    prompt's end, or at the last page an answer filled) that has served its
    hit, then any of a session's own, then a shared prefix's (captured on
    demand after a miss), however recently each was used."""
    eng = make_engine(max_seqs=6)  # (room for the nine sections the four sequences leave)
    cache = eng._swa_sections
    shared = tokens(4 * WINDOW, seed=41)
    a, b, c = (shared + tokens(n, seed=s) for n, s in ((9, 42), (13, 43), (11, 44)))
    greedy(eng, [a])
    greedy(eng, [b])  # a miss: the shared prefix's section is captured on demand
    (toks, _lps, _r), = greedy(eng, [c])  # hits it
    nxt = c + toks + tokens(5, seed=45)
    greedy(eng, [nxt])  # hits the section c's answer left at the last page it filled, which is spent now
    key_shared = eng._section_key(shared + [0], b"")[0]  # the chain hash of the shared pages
    key_c = eng._section_key(c + toks, b"")[0]
    kinds = {k: (e.shared, e.hits) for k, e in cache._entries.items()}
    assert kinds[key_shared] == (True, 1) and kinds[key_c] == (False, 1)
    assert cache.evict_one() and not cache.has(key_c)  # spent: first
    n = len(cache._entries)
    for _ in range(n - 1):
        assert cache.evict_one()
    assert list(cache._entries) == [key_shared]  # the shared prefix: last


# --- the share ----------------------------------------------------------------


def _moe_layer(cfg, key=0):
    """One sparse layer's parameters of ``cfg`` and a batch of hidden states."""
    full = llama.init_params(dataclasses.replace(cfg, held_experts=cfg.num_experts, held_experts_first=0),
                             jax.random.key(key))
    lp = jax.tree.map(lambda a: a[2], full["layers"])
    h = jax.random.normal(jax.random.key(key + 1), (3, 7, cfg.hidden_size), jnp.float32)
    return lp, h


def _held(lp, first, n):
    return {k: (a[first:first + n] if k.startswith("we_") else a) for k, a in lp.items()}


# (preset, its reference, what the benchmark's rehearsal hands that reference)
SHARED_MODELS = {
    "tiny-exaone": (ref, PUBLISHED),
    "tiny-granite-hybrid": (
        ref_granite,
        json.loads((ROOT / "perfbench" / "configs" / "granite-4.0-h-small.1chip.json").read_text())["rehearse"]["published"],
    ),
}


@pytest.mark.parametrize("model", SHARED_MODELS)
@pytest.mark.parametrize("backend", ["grouped", "dense", "kernel"])
def test_the_ranks_shares_add_up_to_the_uncut_layer(backend, model, monkeypatch):
    """Over all ranks the held experts' parts, with the shared expert (which
    every rank computes alike) counted once, are the uncut layer — which is
    what the uncut reference gives for it (under the model's residual
    multiplier, where it has one)."""
    if backend == "kernel":
        monkeypatch.setenv("LLMD_PALLAS", "interpret")
    over = dict(hidden_size=128, moe_intermediate_size=128, num_heads=4) if backend == "kernel" else {}
    cfg = get_model_config(model, **over)
    ref, PUBLISHED = SHARED_MODELS[model]
    block = moe.moe_block if backend == "dense" else moe.moe_block_grouped
    lp, h = _moe_layer(cfg)
    whole_cfg = dataclasses.replace(cfg, held_experts=cfg.num_experts, held_experts_first=0)
    whole = block(h, lp, whole_cfg)
    shared = moe.shared_expert_ffn(h.reshape(-1, cfg.hidden_size), lp).reshape(h.shape)
    ranks = cfg.num_experts // cfg.held_experts
    parts = [
        block(h, _held(lp, r * cfg.held_experts, cfg.held_experts),
              dataclasses.replace(cfg, held_experts_first=r * cfg.held_experts)) - shared
        for r in range(ranks)
    ]
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    assert max(float(jnp.max(jnp.abs(p))) for p in parts) > 1e-3

    # the uncut reference layer: x + FFN(RMSNorm(x)) with every expert held
    stacked = jax.tree.map(lambda a: a[None], dict(lp, post_norm=jnp.ones((cfg.hidden_size,))))
    dims = rc.freeze(PUBLISHED, ref.KEYS)
    x = h.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = (ref._sparse_ffn(stacked, jnp.int32(0), x, dims, 0) - x) / cfg.residual_multiplier
    normed = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps)).reshape(h.shape)
    np.testing.assert_allclose(block(normed, lp, whole_cfg).reshape(x.shape), want, atol=5e-5)


def test_a_pick_outside_the_held_range_gives_no_row():
    """Group sizes and the census run over the held experts: a pick of an
    expert held elsewhere is in no group, so the matmuls never see it."""
    cfg = get_model_config("tiny-exaone")  # holds 4-7 of 16
    T, k, H = 5, cfg.num_experts_per_tok, cfg.hidden_size
    ids = jnp.asarray([[4, 0], [7, 15], [3, 8], [5, 5 + 1], [12, 4]], jnp.int32)
    slots = grouped_gemm.held_slots(ids, cfg, 4)  # slot 4: held on another rank
    np.testing.assert_array_equal(np.asarray(slots), [[0, 4], [3, 4], [4, 4], [1, 2], [4, 0]])
    lp, _ = _moe_layer(cfg)
    lp = _held(lp, 4, 4)
    ht = jax.random.normal(jax.random.key(3), (T, H), jnp.float32)
    weights = jnp.full((T, k), 0.5, jnp.float32)
    seen = []
    real = grouped_gemm.expert_mlp_grouped

    def spy(xs, group_sizes, *a, **kw):
        seen.append(np.asarray(group_sizes))
        return real(xs, group_sizes, *a, **kw)

    grouped_gemm.expert_mlp_grouped = spy
    try:
        y, census = grouped_gemm.moe_apply_grouped(
            ht, weights, ids, lp["we_gate"], lp["we_up"], lp["we_down"], cfg=cfg, emit_census=True)
    finally:
        grouped_gemm.expert_mlp_grouped = real
    np.testing.assert_array_equal(seen[0], [2, 1, 1, 1])  # experts 4, 5, 6, 7
    np.testing.assert_array_equal(np.asarray(census), [1, 4, T * k, 5])
    assert not np.any(np.asarray(y[2]))  # token 2 picked nothing held here: no term
    # every row's result is its held picks' weighted sum
    want = np.zeros((T, H), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(ids[t, j]) - 4
            if 0 <= e < 4:
                want[t] += 0.5 * np.asarray(rc.swiglu(ht[t][None], lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e]))[0]
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_no_held_pick_at_all_is_an_empty_call(monkeypatch):
    """A decode step may route nothing to this rank: the kernel runs no tile."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    cfg = get_model_config("tiny-exaone", hidden_size=128, moe_intermediate_size=128, num_heads=4)
    lp, _ = _moe_layer(cfg)
    lp = _held(lp, 4, 4)
    ht = jax.random.normal(jax.random.key(3), (3, 128), jnp.float32)
    ids = jnp.asarray([[0, 1], [2, 3], [8, 9]], jnp.int32)
    y, census = grouped_gemm.moe_apply_grouped(
        ht, jnp.ones((3, 2), jnp.float32), ids, lp["we_gate"], lp["we_up"], lp["we_down"], cfg=cfg,
        emit_census=True)
    assert not np.any(np.asarray(y)) and np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_array_equal(np.asarray(census), [1, 0, 6, 0])


# --- the scan -------------------------------------------------------------------


def _forward(cfg, params, toks, ring: bool):
    """One prefill of ``toks`` through ``forward_hidden`` (bucketed layout,
    XLA ops), over one pool or two."""
    n, page = len(toks), PAGE
    pages = -(-n // page)
    windows = cfg.layer_windows
    n_swa = sum(1 for w in windows if w > 0) if ring else 0
    shape = lambda layers: (layers, pages + 1, cfg.num_kv_heads, page, 2 * cfg.head_dim)  # noqa: E731
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    inp = StepInput(
        token_ids=jnp.asarray(toks, jnp.int32)[None], positions=jnp.arange(n, dtype=jnp.int32)[None],
        query_lens=jnp.asarray([n], jnp.int32), kv_lens=jnp.asarray([n], jnp.int32), page_table=table,
        swa_page_table=table if ring else None,
    )
    out = llama.forward_hidden(
        params, jnp.zeros(shape(cfg.num_layers - n_swa), jnp.float32), inp, cfg, moe_backend="grouped",
        kv_swa=jnp.zeros(shape(n_swa), jnp.float32) if ring else None,
    )
    return out[0][0]


@pytest.mark.parametrize("ring", [True, False], ids=["four-scans", "one-scan"])
def test_dense_prefix_and_aperiodic_scan_equal_layer_by_layer(ring):
    """The dense layer, then ``S S F S S S F``: no period, so with two pools the
    layers run as four scans (S S | F | S S S | F); with one pool as one scan
    whose window and rotation are per-layer values. Either is the reference's
    plain loop over the layers."""
    cfg = get_model_config("tiny-exaone")
    kinds = tuple(1 if w else 0 for w in cfg.layer_windows[1:])
    assert kinds == (1, 1, 0, 1, 1, 1, 0) and llama._scan_period(kinds) is None
    params = llama.init_params(cfg, jax.random.key(4))
    toks = tokens(3 * WINDOW + 5, seed=11)
    got = _forward(cfg, params, toks, ring)
    trace: list = []
    ref.forward(params, toks, PUBLISHED, trace=trace)
    assert len(trace) == cfg.num_layers
    # the reference's last layer input is the program's hidden state before
    # the last layer; compare the end: final norm of the last layer's output
    with jax.default_matmul_precision("highest"):
        dims = rc.freeze(PUBLISHED, ref.KEYS)
        (window, rotate), lp = ref.layer_kinds(PUBLISHED)[-1], params["layers"]
        x = ref._attention(lp, jnp.int32(6), trace[-1], dims, window, rotate, ref.rope_theta(PUBLISHED))
        x = ref._sparse_ffn(lp, jnp.int32(6), x, dims, ref.first_held(params, PUBLISHED))
        want = rc.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_rope_only_where_the_layer_type_says():
    cfg = get_model_config("tiny-exaone")
    assert cfg.layer_rope == (0, 0, 0, None) * 2 and len(cfg.rope_specs) == 1
    assert get_model_config("tiny").layer_rope == (0, 0)
    params = llama.init_params(cfg, jax.random.key(4))
    toks = tokens(2 * WINDOW, seed=12)
    everywhere = dataclasses.replace(cfg, rope_parameters=None)
    assert float(jnp.max(jnp.abs(_forward(cfg, params, toks, True) - _forward(everywhere, params, toks, True)))) > 1e-3


# --- the configuration ------------------------------------------------------------


def test_the_configuration_file_reaches_the_program_as_published():
    """``topologies/engine_hybrid.py`` builds the model from the file: the
    published widths, the router's published width with the file's count as
    the experts held, ``layer_types`` cut to the depth."""
    cfg = engine_hybrid.engine_config(CONF, seed=0, rehearse=False)
    m, preset = cfg.model, get_model_config("k-exaone-236b-a23b")
    assert (m.num_experts, m.held_experts, m.held_experts_first) == (128, CONF["num_experts"], 0)
    assert m.num_experts == CONF["published"]["num_experts"] == preset.num_experts
    assert m.vocab_size == CONF["vocab_size"] == preset.vocab_size // 8 == CONF["published"]["vocab_size"] // 8
    assert m.layer_types == tuple(CONF["layer_types"][: m.num_layers]) and m.num_layers == CONF["num_hidden_layers"]
    assert preset.layer_types == tuple(CONF["layer_types"]) and preset.num_layers == CONF["published"]["num_hidden_layers"]
    for field in ("hidden_size", "intermediate_size", "num_heads", "num_kv_heads", "head_dim", "sliding_window",
                  "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok",
                  "first_dense_layers", "router_scoring", "routed_scaling_factor", "norm_topk_prob", "rope_theta",
                  "rms_norm_eps", "qk_norm", "rope_parameters", "tie_word_embeddings"):
        assert getattr(m, field) == getattr(preset, field), field
    assert cfg.cache.swa_ring and (m.hidden_size, m.num_heads, m.num_kv_heads) == (6144, 64, 8)
    assert sorted(CONF["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    tiny = engine_hybrid.engine_config(CONF, seed=0, rehearse=True).model
    assert tiny.name == "tiny-exaone" and tiny.held_experts_first == PUBLISHED["deployment"]["rank"] * tiny.held_experts


def test_the_loader_maps_the_exaone_moe_config(tmp_path):
    from llmd_tpu.models.loader import config_from_hf

    hf = {k: v for k, v in CONF.items() if k in json.loads(CONF_FILE.read_text()) and not isinstance(v, dict)}
    hf.update(architectures=["ExaoneMoEForCausalLM"], rope_parameters=CONF["rope_parameters"],
              num_hidden_layers=48, num_experts=128, vocab_size=153600)
    for own in ("source", "note", "stands_for", "registry", "dtype", "reference"):
        hf.pop(own)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    got = config_from_hf(str(tmp_path), name="k-exaone-236b-a23b", dtype="bfloat16")
    want = get_model_config("k-exaone-236b-a23b")
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
