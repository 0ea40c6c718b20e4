"""Sliding-window attention (gpt-oss / Mistral / Qwen2 class — the
reference's flagship P/D benchmark model family, reference
guides/pd-disaggregation/README.md:600-615).

Covers: XLA mask parity vs a dense windowed-softmax oracle, the Pallas
decode kernel's windowed DMA/masking path (interpret mode), mixed
full/sliding layer stacks through the engine, and HF config mapping."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llmd_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
)
from llmd_tpu.ops.paged_attention import paged_attention_xla, write_kv_pages
from llmd_tpu.ops.ragged_paged_attention import (
    decode_paged_attention, flat_paged_attention_full,
)

from flat_streams import (
    tiled_stream, window_attention_on_the_grid_of_tokens,
)


def _dense_windowed_oracle(q, k, v, positions, kv_lens, window):
    """Straightforward masked softmax over the raw context."""
    B, Q, H, D = q.shape
    S = k.shape[1]
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Q, K, G, D)
    scores = jnp.einsum("bqkgd,bskd->bqkgs", qg, k) * (D ** -0.5)
    key_pos = jnp.arange(S)[None, None, :]
    mask = (
        (key_pos <= positions[:, :, None])
        & (key_pos < kv_lens[:, None, None])
        & (key_pos > positions[:, :, None] - window)
    )[:, :, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", probs, v)
    return out.reshape(B, Q, H, D)


def _build_cache(k, v, page):
    B, S, K, D = k.shape
    pages_per_seq = S // page
    cache = jnp.zeros((B * pages_per_seq, K, page, 2 * D), jnp.float32)
    page_table = jnp.arange(B * pages_per_seq, dtype=jnp.int32).reshape(B, -1)
    positions = jnp.tile(jnp.arange(S), (B, 1))
    valid = jnp.ones((B, S), bool)
    cache = write_kv_pages(cache, k, v, page_table, positions, valid)
    return cache, page_table


def test_xla_prefill_window_matches_oracle():
    B, S, K, G, D, page, window = 2, 32, 2, 2, 16, 4, 10
    rng = jax.random.key(0)
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, K * G, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, K, D), jnp.float32)
    cache, pt = _build_cache(k, v, page)
    positions = jnp.tile(jnp.arange(S), (B, 1))
    kv_lens = jnp.full(B, S, jnp.int32)
    out = paged_attention_xla(
        q, cache, pt, kv_lens, positions, window=jnp.int32(window)
    )
    ref = _dense_windowed_oracle(q, k, v, positions, kv_lens, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # window=0 means full attention (identical to omitting it)
    full = paged_attention_xla(q, cache, pt, kv_lens, positions)
    full0 = paged_attention_xla(
        q, cache, pt, kv_lens, positions, window=jnp.int32(0)
    )
    np.testing.assert_allclose(np.asarray(full), np.asarray(full0), atol=1e-6)


def test_pallas_decode_window_matches_oracle(monkeypatch):
    """The kernel's windowed path: leading pages are skipped (never
    DMA'd), in-window positions mask exactly. head_dim 128 + page 8 to
    satisfy the kernel gates; interpret mode on CPU."""
    B, S, K, G, D, page, window = 2, 64, 2, 2, 128, 8, 20
    rng = jax.random.key(1)
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, 1, K * G, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, K, D), jnp.float32)
    cache, pt = _build_cache(k, v, page)
    kv_lens = jnp.asarray([S, S - 9], jnp.int32)
    positions = (kv_lens - 1)[:, None]
    out = decode_paged_attention(
        q, cache, pt, kv_lens, interpret=True, pages_per_block=2,
        window=jnp.int32(window),
    )
    ref = _dense_windowed_oracle(q, k, v, positions, kv_lens, window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_engine_mixed_layer_types_match_reference_masking():
    """A 4-layer model alternating sliding/full (the gpt-oss pattern)
    through the full engine: greedy tokens must match a step-by-step
    jitted forward using the same per-layer windows (exactness), and must
    DIFFER from the all-full-attention model once the context passes the
    window (the mask is actually live)."""
    from llmd_tpu.engine import LLMEngine, SamplingParams

    window = 8
    over = dict(
        num_layers=4, num_heads=4, num_kv_heads=2,
        sliding_window=window,
        layer_types=(
            "sliding_attention", "full_attention",
            "sliding_attention", "full_attention",
        ),
    )

    def gen(cfg_over):
        eng = LLMEngine(EngineConfig(
            model=tiny_model_config(**cfg_over),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
            offload=None,
        ))
        try:
            prompt = list(range(1, 30))  # 29 tokens > window
            sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
            return list(eng.generate([prompt], sp).values())[0]
        finally:
            eng.close()

    windowed = gen(over)
    full = gen({**over, "sliding_window": 0, "layer_types": None})
    assert len(windowed) == 8
    assert windowed != full, (
        "sliding window produced identical tokens to full attention on a "
        "context 3.6x the window — the mask is not being applied"
    )
    # determinism across engines
    assert gen(over) == windowed


def test_config_window_patterns():
    cfg = tiny_model_config(
        num_layers=4, sliding_window=16,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention", "full_attention"),
    )
    assert cfg.layer_windows == (16, 0, 16, 0)
    cfg = tiny_model_config(num_layers=4, sliding_window=16, max_window_layers=2)
    assert cfg.layer_windows == (0, 0, 16, 16)
    cfg = tiny_model_config(num_layers=4, sliding_window=16)
    assert cfg.layer_windows == (16, 16, 16, 16)
    with pytest.raises(ValueError):
        tiny_model_config(num_layers=4, sliding_window=8, layer_types=("full_attention",))


def test_loader_accepts_sliding_window_configs(tmp_path):
    import json

    from llmd_tpu.models.loader import config_from_hf

    hf = {
        "architectures": ["Qwen2ForCausalLM"],
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "max_position_embeddings": 4096,
        "sliding_window": 1024, "use_sliding_window": True,
        "max_window_layers": 2,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = config_from_hf(str(tmp_path))
    assert cfg.sliding_window == 1024
    assert cfg.layer_windows == (0, 0, 1024, 1024)
    # per-layer layer_types (gpt-oss shape) wins over max_window_layers
    hf["layer_types"] = [
        "sliding_attention", "full_attention",
        "sliding_attention", "full_attention",
    ]
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = config_from_hf(str(tmp_path))
    assert cfg.layer_windows == (1024, 0, 1024, 0)


def test_pallas_decode_sinks_matches_oracle():
    """The decode kernel's sink epilogue (gpt-oss): exp(sink) folded into
    the denominator must match the dense concat-then-drop oracle, alone
    and combined with a sliding window."""
    B, S, K, G, D, page = 2, 64, 2, 2, 128, 8
    rng = jax.random.key(3)
    kq, kk, kv_, ks = jax.random.split(rng, 4)
    q = jax.random.normal(kq, (B, 1, K * G, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, K, D), jnp.float32)
    sinks = jax.random.normal(ks, (K * G,), jnp.float32) * 2.0
    cache, pt = _build_cache(k, v, page)
    kv_lens = jnp.asarray([S, S - 5], jnp.int32)
    positions = (kv_lens - 1)[:, None]

    def oracle(window):
        qg = q.reshape(B, 1, K, G, D)
        scores = jnp.einsum("bqkgd,bskd->bqkgs", qg, k) * (D ** -0.5)
        key_pos = jnp.arange(S)[None, None, :]
        mask = (key_pos <= positions[:, :, None]) & (
            key_pos < kv_lens[:, None, None]
        )
        if window:
            mask = mask & (key_pos > positions[:, :, None] - window)
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
        sk = jnp.broadcast_to(
            sinks.reshape(K, G)[None, None, :, :, None], (B, 1, K, G, 1)
        )
        probs = jax.nn.softmax(
            jnp.concatenate([scores, sk], axis=-1), axis=-1
        )[..., :-1]
        out = jnp.einsum("bqkgs,bskd->bqkgd", probs, v)
        return out.reshape(B, 1, K * G, D)

    for window in (None, 20):
        out = decode_paged_attention(
            q, cache, pt, kv_lens, interpret=True, pages_per_block=2,
            window=None if window is None else jnp.int32(window),
            sinks=sinks,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(oracle(window)),
            atol=2e-4, rtol=2e-4,
        )


def _one_row_stream(first, tokens, T):
    """One sequence's chunk from position ``first``, pads behind it."""
    tok_rows = np.zeros(T, np.int32)
    positions = np.zeros(T, np.int32)
    live = np.arange(T) < tokens
    positions[:tokens] = first + np.arange(tokens)
    return tok_rows, positions, live


# window, and what differs from the mixed stream of ``tiled_stream`` (a
# chunk with ragged ends, sub-rows and their seam, decode rows, a verify
# row, pads) over an f32 pool, page 8, blocks of 32 keys.
WINDOWED_TILES = {
    "window_5_smaller_than_a_tile": (5, {}),
    "window_16_a_tile": (16, {}),
    "window_21_starts_mid_page_and_mid_block": (21, {}),
    "window_32_a_block": (32, {}),
    "window_50_larger_than_a_block": (50, {}),
    "a_tile_at_a_rows_start_has_fewer_than_a_window_cached": (
        24, {"stream": (0, 40, 48)}
    ),
    "a_traced_window_of_0_is_full_attention": (0, {"full": True}),
    "a_traced_negative_window_is_full_attention": (-1, {"full": True}),
    "a_ring_view_table_repeats_modulo_the_ring": (21, {"ring": 9}),
    "sinks": (21, {"sinks": True}),
    "int8_pool": (21, {"int8": True}),
    "a_shards_short_last_tile": (21, {"stream": (9, 40, 40)}),
}


@pytest.mark.parametrize("case", list(WINDOWED_TILES))
def test_windowed_tiles_match_xla_and_the_grid_of_tokens(case):
    """The flat stream's sliding-window call on the 16-token tile: a tile
    inside one row reads the row's pages ONCE, from its first token's
    window start to its last token's horizon, each query row under its own
    window and horizon; every other tile goes token by token. Against the
    XLA oracle, and BIT FOR BIT against the grid of one program a token
    that the call rode before (kept in ``flat_streams``): both visit the
    same blocks in the same order for every token."""
    window, what = WINDOWED_TILES[case]
    rng = np.random.default_rng(11)
    L, P, K, page, D, G, ppb = 2, 96, 2, 8, 128, 4, 4
    H = K * G
    if "stream" in what:
        tok_rows, positions, live = _one_row_stream(*what["stream"])
        pt = rng.permutation(P)[:16].reshape(1, 16).astype(np.int32)
    else:
        tok_rows, positions, live, pt = tiled_stream(rng, page, P)
    if "ring" in what:  # logical page p -> the ring's page p % ring
        pt = np.take_along_axis(
            pt, np.arange(pt.shape[1])[None] % what["ring"], axis=1
        )
    T = len(tok_rows)
    kv_lens = jnp.asarray(np.where(live, positions + 1, 0).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(T, 1, H, D)).astype(np.float32))
    kw, atol, rtol = {}, 2e-5, 1e-6
    if "int8" in what:
        cache = jnp.asarray(
            rng.integers(-127, 128, size=(L, P, K, page, 2 * D)).astype(np.int8)
        )
        kw["scales"] = jnp.asarray(
            rng.uniform(0.01, 0.1, size=(L, P, K, page, 2))
            .astype(np.float16).astype(np.float32)
        )
        atol, rtol = 2e-2, 1e-2
    else:
        cache = jnp.asarray(
            rng.normal(size=(L, P, K, page, 2 * D)).astype(np.float32)
        )
    if "sinks" in what:
        kw["sinks"] = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))
    rows, table = jnp.asarray(tok_rows), jnp.asarray(pt)

    tiled = jax.jit(lambda w: flat_paged_attention_full(
        q, cache, jnp.int32(1), rows, table, kv_lens, interpret=True,
        pages_per_block=ppb, window=w, **kw,
    ))  # the window a traced scalar, as a scan over layers hands it in
    out = np.asarray(tiled(jnp.int32(window)))
    oracle = np.asarray(paged_attention_xla(
        q, cache[1], jnp.asarray(pt[tok_rows]), kv_lens,
        jnp.asarray(positions[:, None]), window=jnp.int32(window),
        **{k: (v[1] if k == "scales" else v) for k, v in kw.items()},
    ))
    np.testing.assert_allclose(out[live], oracle[live], atol=atol, rtol=rtol)
    assert np.isfinite(out).all() and not out[~live].any()  # pads read nothing
    by_token = np.asarray(window_attention_on_the_grid_of_tokens(
        q, cache, jnp.int32(1), rows, table, kv_lens, window,
        pages_per_block=ppb, **kw,
    ))
    np.testing.assert_array_equal(out, by_token)
    if what.get("full"):
        np.testing.assert_array_equal(out, np.asarray(flat_paged_attention_full(
            q, cache, jnp.int32(1), rows, table, kv_lens, interpret=True,
            pages_per_block=ppb, **kw,
        )))
