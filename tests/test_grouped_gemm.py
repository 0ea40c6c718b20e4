"""The grouped expert matmul (ops/grouped_gemm.py): the tile rule as a pure
function, the layer-indexed kernel in interpret mode against ``lax.ragged_dot``
on the layer's slice at shapes with more than one tile on every axis (and
against megablox, bit for bit), and the step programs' count of grouped calls
and of groups with rows against a ``numpy`` count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams
from llmd_tpu.models import moe
from llmd_tpu.models.registry import get_model_config, list_models
from llmd_tpu.ops import grouped_gemm
from llmd_tpu.ops.grouped_gemm import gmm_tiles, grouped_matmul


def _blocks(tm, tk, tn, w_bytes, x_bytes):
    """VMEM of one grid step: double-buffered activation, weight and f32
    output blocks, and the f32 accumulator."""
    return 2 * (tm * tk * x_bytes + tk * tn * w_bytes + tm * tn * 4) + tm * tn * 4


# Every expert projection of the registry that takes the kernel's path (both
# dims lane-tiled; gpt-oss's 2,880 and the tiny models take ragged_dot).
EXPERT_SHAPES = sorted({
    (K, N)
    for cfg in map(get_model_config, list_models()) if cfg.is_moe
    for K, N in [(cfg.hidden_size, cfg.moe_intermediate_size),
                 (cfg.moe_intermediate_size, cfg.hidden_size)]
    if K % 128 == 0 and N % 128 == 0
})


def test_the_registry_has_the_expert_shapes_the_rule_is_held_to():
    assert {(2048, 768), (768, 2048), (2048, 1408), (1408, 2048),
            (4096, 14336), (14336, 4096), (6144, 16384), (7168, 2048),
            } <= set(EXPERT_SHAPES)


def _tiles(total, tile):
    return -(-total // tile)


@pytest.mark.parametrize("w_bytes", [2, 4], ids=["bf16", "f32"])
@pytest.mark.parametrize("K,N", EXPERT_SHAPES)
def test_tile_rule(K, N, w_bytes):
    budget = grouped_gemm._VMEM_BUDGET

    def fits(tk, tn):
        return _blocks(128, tk, tn, w_bytes, w_bytes) <= budget

    tk, tn = gmm_tiles(K, N, w_bytes, w_bytes, budget)
    assert tk % 128 == 0 and tn % 128 == 0
    assert 128 <= tk <= K and 128 <= tn <= N  # never under 128 x 128
    assert fits(tk, tn)  # never over the budget
    assert (tk == K) == fits(K, 128)  # whole K where it fits
    lanes = range(128, N + 1, 128)
    if tk == K:
        # the fewest n tiles that fit beside the whole K, of even length
        fewest = min(_tiles(N, t) for t in lanes if fits(K, t))
    else:
        fewest = _tiles(N, 1024)
        # and the fewest even K tiles that fit beside that
        fewest_k = min(_tiles(K, t) for t in range(128, K + 1, 128) if fits(t, tn))
        assert _tiles(K, tk) == fewest_k and tk - 128 < K / fewest_k <= tk
    assert _tiles(N, tn) == fewest and tn - 128 < N / fewest <= tn


def test_tile_rule_at_the_cells_shapes():
    """The four shapes the benchmark's cells run, in bf16, as the sweep on
    the chip kept them: whole K; N whole, or in two even tiles."""
    assert gmm_tiles(2048, 768, 2) == (2048, 768)
    assert gmm_tiles(768, 2048, 2) == (768, 2048)
    assert gmm_tiles(2048, 1408, 2) == (2048, 768)
    assert gmm_tiles(1408, 2048, 2) == (1408, 1024)


def _sizes(rng, rows, groups, empty=()):
    live = [g for g in range(groups) if g not in empty]
    sizes = np.zeros(groups, np.int64)
    for g in rng.choice(live, rows):
        sizes[g] += 1
    return sizes


# shape -> (K, N, groups without rows, VMEM budget, the f32 tiles it gives)
KERNEL_SHAPES = {
    # Under 2 MiB an f32 K of 1,024 does not fit whole: eight k tiles of 128
    # beside two n tiles, 640 + 512 (N irregular against tn). Groups 1 and 5
    # are empty, so the pad rows land in a last group that had none.
    "split-k": (1024, 1152, (1, 5), 2 << 20, (128, 640)),
    # DeepSeek-V2-Lite's N beside a whole K, as the cells run it: 768 + 640.
    # The first group, the last and one in the middle are empty.
    "n1408": (512, 1408, (0, 3, 5), 6 << 20, (512, 768)),
}
LAYERS = 3


@pytest.mark.parametrize("layer", [None, 0, 1, LAYERS - 1],
                         ids=["3d", "first", "middle", "last"])
@pytest.mark.parametrize("rows", [4, 30, 48, 192])
@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_grouped_matmul_megablox_matches_ragged_dot(monkeypatch, shape, rows, layer):
    """tests/test_wide_ep.py::test_grouped_matmul_megablox_parity's row counts
    at more than one tile on every axis (that test only sees K = N = 128).
    The kernel takes the stacked ``[L, G, K, N]`` and reads layer ``layer`` of
    it alone: every other layer is NaN. A 3-D weight is its one-layer case."""
    K, N, empty, budget, tiles = KERNEL_SHAPES[shape]
    groups = 6
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    monkeypatch.setattr(grouped_gemm, "_VMEM_BUDGET", budget)
    assert gmm_tiles(K, N, 4, 4, budget) == tiles
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.standard_normal((rows, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((groups, K, N)) / np.sqrt(K), jnp.float32)
    gs = jnp.asarray(_sizes(rng, rows, groups, empty=empty), jnp.int32)
    assert gs[-1] == 0  # pad rows make an empty last group live
    ref = jax.lax.ragged_dot(x, w, gs)
    if layer is None:
        got = grouped_matmul(x, w, gs)
    else:
        stacked = jnp.full((LAYERS, *w.shape), jnp.nan, w.dtype).at[layer].set(w)
        got = grouped_matmul(x, stacked, gs, layer=jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_kernel_is_megablox_on_the_layer_bit_for_bit(dtype):
    """The same metadata, grid, accumulation order and masks as megablox's
    ``gmm``: on ``w[layer]`` with the same tiles the results are EQUAL, a short
    last k tile (1,024 in tiles of 384) and an uneven last n tile included."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm

    rows, K, N, groups, tiling = 64, 1024, 1152, 6, (32, 384, 640)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((rows, K)), dtype)
    w = jnp.asarray(rng.standard_normal((LAYERS, groups, K, N)) / np.sqrt(K), dtype)
    gs = jnp.asarray(_sizes(rng, rows, groups, empty=(2,)), jnp.int32)
    for layer in range(LAYERS):
        want = megablox_gmm(x, w[layer], gs, tiling=tiling, interpret=True)
        got = grouped_gemm.gmm(
            x, w, gs, jnp.full((1,), layer, jnp.int32), tiling=tiling, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- the step programs' count ------------------------------------------------

PROMPTS = [list(range(1, 16)), [3, 3, 7, 1, 9, 9, 2], list(range(30, 41))]


def _engine(flat: bool, **model):
    # Lane-tiled expert dims take the kernel's path under LLMD_PALLAS=
    # interpret, whose row tile pads the sorted rows with rows of no group.
    model = {"hidden_size": 128, "num_heads": 4, "num_kv_heads": 2,
             "intermediate_size": 128, "num_experts": 8,
             "num_experts_per_tok": 3, "moe_intermediate_size": 128, **model}
    return LLMEngine(EngineConfig(
        model=tiny_model_config(**model),
        cache=CacheConfig(page_size=4, num_blocks=128, dtype="float32"),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=64, ragged_qlens=flat),
        seed=0,
    ))


def _generate(engine):
    sp = SamplingParams(temperature=0.0, max_tokens=5)
    return list(engine.generate([list(p) for p in PROMPTS], sp).values())


@pytest.fixture
def routed(monkeypatch):
    """Every executed router call's expert ids, as the host saw them."""
    seen = []
    real = moe.router_topk

    def spy(ht, router, k, cfg, bias=None):
        weights, ids = real(ht, router, k, cfg, bias)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), ids)
        return weights, ids

    monkeypatch.setattr(moe, "router_topk", spy)
    return seen


@pytest.mark.parametrize("flat", [True, False], ids=["flat", "unified"])
def test_step_programs_count_groups_with_rows(monkeypatch, routed, flat):
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    engine = _engine(flat)
    assert (engine.runner._flat is not None) == flat
    outs = _generate(engine)
    jax.effects_barrier()
    E = engine.config.model.num_experts
    calls = groups = padded_calls = picks = 0
    for ids in routed:  # one [T, k] per executed grouped MoE layer
        has_rows = np.bincount(ids.ravel(), minlength=E) > 0
        rows = ids.size
        tm = min(128, -(-rows // 8) * 8)
        if rows % tm:  # zero rows pad the row tile; they belong to no group
            padded_calls += 1
        picks += rows
        calls += 1
        groups += int(has_rows.sum())
    st = engine.stats
    assert calls == st.engine_steps_total * engine.config.model.num_layers
    assert padded_calls  # the case has steps whose rows do not fill a tile
    assert (st.moe_grouped_calls_total, st.moe_groups_with_rows_total) == (calls, groups)
    assert 0 < groups <= calls * E
    assert st.moe_picks_total == st.moe_picks_held_total == picks  # every expert is held here

    # the same engine with the count never armed: the same greedy tokens
    plain = _engine(flat)
    plain.runner._moe_census = None
    assert _generate(plain) == outs
    assert plain.stats.moe_grouped_calls_total == 0


def test_a_dense_model_counts_nothing():
    engine = _engine(True, num_experts=0, num_experts_per_tok=0)
    _generate(engine)
    assert engine.stats.engine_steps_total > 0
    assert (engine.stats.moe_grouped_calls_total,
            engine.stats.moe_groups_with_rows_total) == (0, 0)


def test_the_count_without_the_kernel_has_no_pad_rows(routed):
    """ragged_dot (the non-lane-tiled fallback: tiny models, gpt-oss) pads
    nothing, so its count is the plain count of groups with rows."""
    engine = _engine(True, hidden_size=64, moe_intermediate_size=64)
    _generate(engine)
    jax.effects_barrier()
    E = engine.config.model.num_experts
    groups = sum(int((np.bincount(i.ravel(), minlength=E) > 0).sum()) for i in routed)
    assert engine.stats.moe_grouped_calls_total == len(routed) > 0
    assert engine.stats.moe_groups_with_rows_total == groups
