"""Mellum2's architecture in miniature (``tiny-mellum2``): window and full
attention three to one over two KV pools on the flat step, a RoPE table PER
LAYER TYPE (YaRN on the full layers, the plain table on the sliding ones), the
periodic cycle scan as ONE body, and an expert layer that holds a SHARE of a
softmax router's experts with no shared expert — against the plain reference
of ``perfbench/references/gqa_swa_yarn_moe_share.py`` (float32, no kernel, no
cache).
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
from llmd_tpu.models import llama, loader, moe  # noqa: E402
from llmd_tpu.models.common import StepInput, rope_tables  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import gqa_swa_yarn_moe_share as ref  # noqa: E402
from perfbench.topologies import engine_hybrid_yarn  # noqa: E402

CONF_FILE = ROOT / "perfbench" / "configs" / "mellum2-12b-a2.5b.1chip.json"
CONF = json.loads(CONF_FILE.read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference
WINDOW, PAGE = 16, 4


def make_engine(num_blocks=256, max_batched=8, max_seqs=4, ring=True, model=None, **cache) -> LLMEngine:
    """Chunks of at most ``max_batched`` = 8 tokens: SMALLER than the window."""
    return LLMEngine(EngineConfig(
        model=model or get_model_config("tiny-mellum2"),
        cache=CacheConfig(page_size=PAGE, num_blocks=num_blocks, dtype="float32", swa_ring=ring, **cache),
        scheduler=SchedulerConfig(max_num_seqs=max_seqs, max_num_batched_tokens=max_batched),
    ))


def greedy(eng: LLMEngine, prompts, max_tokens=6):
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


def reference_params(eng):
    return engine_hybrid_yarn.engine_hybrid.reference_params(eng.runner.params, eng.config.model)


def assert_matches_reference(eng, prompt, toks, lps, conf=PUBLISHED):
    assert len(toks) == len(lps) > 0
    nxt, _best = ref.forward(reference_params(eng), prompt + toks, conf)
    np.testing.assert_allclose(lps, np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)]), atol=5e-5)


# --- the engine against the reference ---------------------------------------


def test_the_preset_runs_the_flat_step_over_two_pools():
    eng = make_engine()
    assert eng.runner._flat is not None and eng.runner.kv_swa is not None
    swa = eng._swa
    assert (swa.full_layers, swa.swa_layers) == ((3, 7), (0, 1, 2, 4, 5, 6))
    assert eng.runner.kv_cache.shape[0] == 2 and eng.runner.kv_swa.shape[0] == 6
    # the window EXCEEDS the chunk: a ring is window + chunk, a section the window + a page
    assert swa.chunk_tokens == 8 < WINDOW and swa.ring_pages == (WINDOW + 8) // PAGE + 1
    assert swa.max_section_pages(PAGE) == WINDOW // PAGE + 1
    assert eng.swa_allocator.num_pages == 4 * swa.ring_pages + 2 * 4 * swa.max_section_pages(PAGE)


@pytest.mark.parametrize("ring", [True, False], ids=["ring", "one-pool"])
def test_prefill_then_decode_match_the_reference(ring):
    """Contexts of several windows, longer than one ring, prefilled in chunks
    smaller than the window over steps that carry the other requests."""
    eng = make_engine(ring=ring)
    prompts = [tokens(9 * WINDOW + 3, seed=1), tokens(5 * WINDOW, seed=2), tokens(7, seed=3)]
    for p, (toks, lps, _r) in zip(prompts, greedy(eng, prompts, max_tokens=8)):
        assert_matches_reference(eng, p, toks, lps)
    assert eng.stats.moe_picks_held_total < eng.stats.moe_picks_total


def test_a_section_miss_then_hits_match_the_reference():
    """A miss leaves the section at the offered run's end behind; the next
    requests over the shared prefix take full pages + the section seeding a
    fresh ring, under the ring seed's span and counters."""
    eng = make_engine()
    shared = tokens(6 * WINDOW, seed=5)
    a, b, c, d = (shared + tokens(n, seed=s) for n, s in ((9, 6), (13, 7), (11, 8), (10, 9)))
    greedy(eng, [a])
    (toks, lps, req), = greedy(eng, [b])
    assert (eng.stats.swa_section_hits_total, eng.stats.swa_section_misses_total) == (0, 1)
    assert req.num_cached_tokens == 0 and eng.stats.swa_ring_seeds_total == 0
    assert_matches_reference(eng, b, toks, lps)
    for n, prompt in enumerate((c, d), start=1):
        (toks, lps, req), = greedy(eng, [prompt])
        assert (eng.stats.swa_section_hits_total, eng.stats.swa_section_misses_total) == (n, 1)
        assert req.num_cached_tokens == len(shared)
        assert_matches_reference(eng, prompt, toks, lps)
    st = eng.stats
    assert st.swa_ring_seeds_total == 2 and st.swa_ring_seed_host_ms_total > 0
    # (a section that ends on a page boundary is the window's pages; the budget has one more for a straddle)
    assert st.swa_ring_seed_pages_total == 2 * (WINDOW // PAGE)
    from llmd_tpu.serve.metrics import render_metrics

    text = render_metrics(st, "tiny-mellum2")
    for name in ("swa_ring_seeds_total", "swa_ring_seed_pages_total", "swa_ring_seed_host_ms_total"):
        assert f"llmd:{name}" in text, name


def test_a_wrong_table_on_either_kind_is_told_from_the_model():
    """The reference with the plain table on the full layers, with the YaRN
    table on the sliding ones, or with ``attention_factor`` 1 is another
    model: the engine's log-probs lie far from it."""
    eng = make_engine()
    prompt = tokens(7 * WINDOW, seed=21)
    (toks, lps, _r), = greedy(eng, [prompt], max_tokens=8)
    assert_matches_reference(eng, prompt, toks, lps)
    tables = PUBLISHED["rope_parameters"]
    wrong = {
        "plain on full": dict(tables, full_attention=tables["sliding_attention"]),
        "yarn on sliding": dict(tables, sliding_attention=tables["full_attention"]),
        "factor 1": dict(tables, full_attention=dict(tables["full_attention"], attention_factor=1.0)),
    }
    for name, rp in wrong.items():
        nxt, _ = ref.forward(reference_params(eng), prompt + toks, dict(PUBLISHED, rope_parameters=rp))
        far = np.abs(lps - np.asarray(nxt[len(prompt) - 1: len(prompt) - 1 + len(toks)]))
        assert float(np.max(far)) > 1e-3, name


# --- a table per kind ---------------------------------------------------------


def test_a_table_per_kind_of_layer():
    cfg = get_model_config("tiny-mellum2")
    plain, yarn = cfg.rope_specs
    assert cfg.layer_rope == (0, 0, 0, 1) * 2 and plain == (10000.0, None)
    assert yarn[1]["rope_type"] == "yarn" and "rope_theta" not in yarn[1]
    # the full layers' table is the sliding layers' but for YaRN's blend and factor
    pos = jnp.arange(200)
    want_inv, want_att = ref._inv_freq(cfg.head_dim, PUBLISHED["rope_parameters"]["full_attention"])
    cos, sin = rope_tables(pos, cfg.head_dim, *yarn)
    np.testing.assert_allclose(cos, jnp.cos(pos[:, None] * want_inv) * want_att, atol=1e-5)
    np.testing.assert_allclose(sin, jnp.sin(pos[:, None] * want_inv) * want_att, atol=1e-5)
    assert want_att == pytest.approx(1.1386294361119891)
    inv0, att0 = ref._inv_freq(cfg.head_dim, PUBLISHED["rope_parameters"]["sliding_attention"])
    assert att0 == 1.0 and float(jnp.max(jnp.abs(want_inv - inv0))) > 1e-3
    assert float(want_inv[0]) == pytest.approx(float(inv0[0]))  # the fastest frequency is not scaled
    assert float(want_inv[-1]) == pytest.approx(float(inv0[-1]) / 4)  # the slowest by the whole factor
    # the published model: the same two kinds at its widths
    big = get_model_config("mellum2-12b-a2.5b")
    assert big.layer_rope == (0, 0, 0, 1) * 7 and big.layer_windows == (1024, 1024, 1024, 0) * 7
    assert big.rope_specs[1][1]["attention_factor"] == 1.2772588722239782
    assert big.rope_specs[1][1] == {k: v for k, v in CONF["rope_parameters"]["full_attention"].items()
                                    if k != "rope_theta"}


@pytest.mark.parametrize("model, tables, rotating", [
    ("tiny-mellum2", 2, 8), ("tiny-exaone", 1, 6), ("tiny-granite-hybrid", 1, 0), ("tiny", 1, 2),
])
def test_no_table_is_a_case_of_the_same_choice(model, tables, rotating):
    """``rope_layer_types`` (the older spelling) folds into ``rope_parameters``:
    K-EXAONE's full layers and granite's attention have no table, every other
    layer the model's own."""
    cfg = get_model_config(model)
    assert cfg.rope_layer_types is None and len(cfg.rope_specs) == tables
    attn = [r for r, t in zip(cfg.layer_rope, cfg.layer_types or ("full_attention",) * cfg.num_layers)
            if t != "mamba"]
    assert sum(r is not None for r in attn) == rotating
    again = get_model_config(model, max_model_len=64)  # rebuilt through the overrides
    assert again.layer_rope == cfg.layer_rope and again.rope_parameters == cfg.rope_parameters


def test_the_older_spelling_still_decides():
    cfg = get_model_config("tiny-exaone")
    assert cfg.rope_parameters == {"full_attention": None}
    assert get_model_config("tiny-exaone", rope_layer_types=()).layer_rope == (None,) * 8
    both = get_model_config("tiny-exaone", rope_layer_types=("sliding_attention", "full_attention"))
    assert both.layer_rope == (0,) * 8 and not both.rope_parameters
    with pytest.raises(ValueError, match="layer_types"):
        get_model_config("tiny", rope_parameters={"full_attention": None})


def test_the_loader_reads_rope_parameters_keyed_by_layer_type(tmp_path):
    hf = {k: v for k, v in CONF.items() if k in (
        "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "layer_types", "sliding_window", "use_sliding_window", "max_window_layers", "rope_parameters",
        "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings", "moe_intermediate_size",
        "num_experts_per_tok", "norm_topk_prob", "attention_bias")}
    hf.update(architectures=["MellumForCausalLM"], model_type="mellum", num_hidden_layers=28,
              num_experts=64, vocab_size=98304)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    got, preset = loader.config_from_hf(str(tmp_path)), get_model_config("mellum2-12b-a2.5b")
    assert got.layer_rope == preset.layer_rope and got.rope_specs == preset.rope_specs
    for field in ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "sliding_window", "layer_types",
                  "rope_theta", "rope_scaling", "qk_norm", "num_experts", "num_experts_per_tok",
                  "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps", "max_model_len", "vocab_size"):
        assert getattr(got, field) == getattr(preset, field), field
    # a flat rope_parameters is the model's theta, as before
    hf["rope_parameters"] = {"rope_theta": 1e6, "rope_type": "default"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    flat = loader.config_from_hf(str(tmp_path))
    assert flat.rope_theta == 1e6 and flat.rope_parameters is None and flat.layer_rope == (0,) * 28


# --- the share ----------------------------------------------------------------


def _moe_layer(cfg, key=0):
    full = llama.init_params(dataclasses.replace(cfg, held_experts=cfg.num_experts, held_experts_first=0),
                             jax.random.key(key))
    lp = jax.tree.map(lambda a: a[2], full["layers"])
    h = jax.random.normal(jax.random.key(key + 1), (3, 7, cfg.hidden_size), jnp.float32)
    return lp, h


def _held(lp, first, n):
    return {k: (a[first:first + n] if k.startswith("we_") else a) for k, a in lp.items()}


@pytest.mark.parametrize("backend", ["grouped", "dense", "kernel"])
def test_the_four_ranks_partial_sums_add_up_to_the_uncut_reference_layer(backend, monkeypatch):
    """No shared expert: the four ranks' parts ARE the layer, and a token none
    of whose picks a rank holds gets exactly nothing from it."""
    if backend == "kernel":
        monkeypatch.setenv("LLMD_PALLAS", "interpret")
    over = dict(hidden_size=128, moe_intermediate_size=128, num_heads=4) if backend == "kernel" else {}
    cfg = get_model_config("tiny-mellum2", **over)
    assert not cfg.shared_expert_intermediate_size and cfg.num_experts // cfg.held_experts == 4
    block = moe.moe_block if backend == "dense" else moe.moe_block_grouped
    lp, h = _moe_layer(cfg)
    assert not any(k.startswith("ws_") for k in lp)
    whole_cfg = dataclasses.replace(cfg, held_experts=cfg.num_experts, held_experts_first=0)
    parts = [
        block(h, _held(lp, r * cfg.held_experts, cfg.held_experts),
              dataclasses.replace(cfg, held_experts_first=r * cfg.held_experts))
        for r in range(4)
    ]
    np.testing.assert_allclose(sum(parts), block(h, lp, whole_cfg), atol=2e-5)
    # some token gets NO term from some rank: none of its two picks is held there
    empty = [np.all(np.asarray(p).reshape(-1, cfg.hidden_size) == 0, axis=-1) for p in parts]
    assert any(e.any() for e in empty) and not np.all(np.stack(empty), axis=0).any()
    # the uncut reference layer: x + MoE(RMSNorm(x)) with every expert held
    stacked = jax.tree.map(lambda a: a[None], dict(lp, post_norm=jnp.ones((cfg.hidden_size,))))
    dims = rc.freeze(PUBLISHED, ref.KEYS)
    x = h.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = ref._sparse_ffn(stacked, jnp.int32(0), x, dims, 0) - x
        # ... and rank by rank, the reference's own shares add up to it
        shares = [
            ref._sparse_ffn(jax.tree.map(lambda a: a[None], dict(
                _held(lp, r * cfg.held_experts, cfg.held_experts), post_norm=jnp.ones((cfg.hidden_size,)))),
                jnp.int32(0), x, dims, r * cfg.held_experts) - x
            for r in range(4)
        ]
    np.testing.assert_allclose(sum(shares), want, atol=2e-5)
    normed = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_norm_eps)).reshape(h.shape)
    np.testing.assert_allclose(sum(block(normed, _held(lp, r * cfg.held_experts, cfg.held_experts),
                                         dataclasses.replace(cfg, held_experts_first=r * cfg.held_experts))
                                   for r in range(4)).reshape(x.shape), want, atol=5e-5)


# --- the scan -------------------------------------------------------------------


def _inputs(cfg, toks, ring: bool):
    n, page = len(toks), PAGE
    pages = -(-n // page)
    n_swa = sum(1 for w in cfg.layer_windows if w > 0) if ring else 0
    shape = lambda layers: (layers, pages + 1, cfg.num_kv_heads, page, 2 * cfg.head_dim)  # noqa: E731
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    inp = StepInput(
        token_ids=jnp.asarray(toks, jnp.int32)[None], positions=jnp.arange(n, dtype=jnp.int32)[None],
        query_lens=jnp.asarray([n], jnp.int32), kv_lens=jnp.asarray([n], jnp.int32), page_table=table,
        swa_page_table=table if ring else None,
    )
    return inp, jnp.zeros(shape(cfg.num_layers - n_swa), jnp.float32), (
        jnp.zeros(shape(n_swa), jnp.float32) if ring else None)


def _forward(cfg, params, toks, ring: bool):
    inp, kv, kv_swa = _inputs(cfg, toks, ring)
    return llama.forward_hidden(params, kv, inp, cfg, moe_backend="grouped", kv_swa=kv_swa)[0][0]


def _eqns(jaxpr, name):
    """The equations called ``name`` anywhere under ``jaxpr``."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _eqns(inner, name)
    return out


@pytest.mark.parametrize("model, ring, scans, lengths", [
    ("tiny-mellum2", True, 1, [2]),        # S S S F x 2: ONE body, two cycles
    ("tiny-mellum2", False, 1, [8]),       # one pool: one scan, the table a row a layer
    ("tiny-exaone", True, 4, [2, 1, 3, 1]),  # the dense layer first: no period, four runs
])
def test_sssf_cycles_lower_to_one_scan_body(model, ring, scans, lengths):
    cfg = get_model_config(model)
    params = llama.init_params(cfg, jax.random.key(4))
    inp, kv, kv_swa = _inputs(cfg, tokens(3 * WINDOW + 5, seed=11), ring)
    jaxpr = jax.make_jaxpr(
        lambda p, kv, kv_swa: llama.forward_hidden(p, kv, inp, cfg, moe_backend="grouped", kv_swa=kv_swa)
    )(params, kv, kv_swa).jaxpr
    top = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in top] == lengths and len(top) == scans
    # a table is traced once a kind, whatever the scans
    assert len(_eqns(jaxpr, "cos")) == len(cfg.rope_specs)
    if model == "tiny-mellum2" and ring:
        # both pools ride the one body's carry, and no leaf rides it as ``xs``:
        # the scanned operands are the cycle's plane and layer ids alone
        body = top[0]
        n_xs = len(body.invars) - body.params["num_consts"] - body.params["num_carry"]
        assert n_xs == 2 and all(v.aval.shape == (2, 4) for v in body.invars[-2:])
        carried = [v.aval.shape for v in body.invars[body.params["num_consts"]:][: body.params["num_carry"]]]
        assert kv.shape in carried and kv_swa.shape in carried


@pytest.mark.parametrize("ring", [True, False], ids=["one-cycle-body", "one-scan"])
def test_the_cycle_scan_equals_the_reference_layer_by_layer(ring):
    cfg = get_model_config("tiny-mellum2")
    assert llama._scan_period(tuple(zip((1, 1, 1, 0) * 2, cfg.layer_rope))) == 4
    params = llama.init_params(cfg, jax.random.key(4))
    toks = tokens(3 * WINDOW + 5, seed=11)
    got = _forward(cfg, params, toks, ring)
    with jax.default_matmul_precision("highest"):
        x = ref._stream(params, jnp.asarray(toks, jnp.int32), PUBLISHED)
        want = rc.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    np.testing.assert_allclose(got, want, atol=5e-5)
    # layer 3's keys, as the benchmark reads them out of the main pool
    inp, kv, kv_swa = _inputs(cfg, toks, True)
    _h, kv, _swa = llama.forward_hidden(params, kv, inp, cfg, moe_backend="grouped", kv_swa=kv_swa)
    pages = -(-len(toks) // PAGE)
    keys = np.asarray(kv[0, :pages, :, :, : cfg.head_dim]).transpose(0, 2, 1, 3).reshape(-1, cfg.num_kv_heads, cfg.head_dim)
    want_keys = ref.first_full_layer_keys(params, toks, PUBLISHED)
    err = ref.key_error(keys[: len(toks)], want_keys)
    assert err["token_median"] < 1e-5 and err["far_share"] == 0.0
    wrong = dict(PUBLISHED, rope_parameters=dict(
        PUBLISHED["rope_parameters"], full_attention=PUBLISHED["rope_parameters"]["sliding_attention"]))
    assert ref.key_error(keys[: len(toks)], ref.first_full_layer_keys(params, toks, wrong))["token_median"] > 0.05


# --- the configuration ------------------------------------------------------------


def test_the_configuration_file_reaches_the_program_as_published():
    cfg = engine_hybrid_yarn.engine_config(CONF, seed=0, rehearse=False)
    m, preset = cfg.model, get_model_config("mellum2-12b-a2.5b")
    assert (m.num_experts, m.held_experts, m.held_experts_first) == (64, CONF["num_experts"], 0)
    assert m.num_experts == CONF["published"]["num_experts"] == preset.num_experts
    assert m.vocab_size == CONF["vocab_size"] == preset.vocab_size // 4 == CONF["published"]["vocab_size"] // 4
    assert m.layer_types == tuple(CONF["layer_types"][: m.num_layers]) and m.num_layers == CONF["num_hidden_layers"]
    assert preset.layer_types == tuple(CONF["layer_types"]) and preset.num_layers == CONF["published"]["num_hidden_layers"]
    for field in ("hidden_size", "num_heads", "num_kv_heads", "head_dim", "sliding_window", "moe_intermediate_size",
                  "shared_expert_intermediate_size", "num_experts_per_tok", "first_dense_layers", "router_scoring",
                  "norm_topk_prob", "rope_theta", "rope_scaling", "rope_specs", "rms_norm_eps", "qk_norm",
                  "tie_word_embeddings"):
        assert getattr(m, field) == getattr(preset, field), field
    assert m.layer_rope == preset.layer_rope[: m.num_layers]
    assert cfg.cache.swa_ring and cfg.cache.swa_sections == CONF["engine"]["swa_sections"]
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.moe_intermediate_size) == (2304, 32, 4, 896)
    assert set(CONF["reduced"]) >= {"num_experts", "vocab_size"}
    tiny = engine_hybrid_yarn.engine_config(CONF, seed=0, rehearse=True).model
    assert tiny.name == "tiny-mellum2" and tiny.held_experts_first == PUBLISHED["deployment"]["rank"] * tiny.held_experts
    assert PUBLISHED["layer_types"] == list(tiny.layer_types) and PUBLISHED["sliding_window"] == tiny.sliding_window
