"""The seam between ``llama.forward_hidden`` and a layer's mixer
(``common.MixerKind``): every layer of every model has a kind, a kind's
``init`` makes exactly its stack's mixer leaves, and a kind's ``mix`` hands
back the cache it was given. Shapes only (``jax.eval_shape``): no engine, no
weights, the benchmark's registries at their published widths.
"""

import jax
import jax.numpy as jnp
import pytest

from llmd_tpu import ops
from llmd_tpu.models import llama
from llmd_tpu.models.common import StepInput, param_dtype
from llmd_tpu.models.registry import get_model_config
from llmd_tpu.ops import ssm

TINY = ("tiny-moe", "tiny-swa", "tiny-mla", "tiny-dsa", "tiny-exaone",
        "tiny-granite-hybrid", "tiny-nemotron-h", "tiny-qwen3-next", "tiny-mla-dsa")
# The benchmark's registries, cut to two periods of their layer pattern.
BENCH = {"qwen3-30b-a3b": 2, "deepseek-v2-lite": 3, "keye-vl-2.0-30b-a3b": 2,
         "k-exaone-236b-a23b": 8, "granite-4.0-h-small": 20,
         "nemotron-3-nano-30b-a3b": 8, "qwen3-next-80b-a3b": 8, "deepseek-v3.2": 4}
PAGE, PAGES, SLOTS = 8, 16, 5


def model(name: str):
    if name in TINY:
        return get_model_config(name)
    full, n = get_model_config(name), BENCH[name]
    cut = {k: getattr(full, k)[:n] for k in ("layer_types", "layer_ffn")
           if getattr(full, k) is not None}
    return get_model_config(name, num_layers=n, max_model_len=512, **cut)


def param_shapes(cfg):
    return jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.key(0))


@pytest.mark.parametrize("name", [*TINY, *BENCH])
def test_every_layer_has_a_kind_with_an_init_a_mix_and_a_stack(name):
    cfg = model(name)
    kinds, params = llama.mixer_kinds(cfg), param_shapes(cfg)
    assert len(kinds) == cfg.num_layers
    for kind in kinds:
        assert callable(kind.init) and callable(kind.mix) and callable(kind.halves)
        assert kind.stack in params and kind.pool in (0, 1)
    # one kind: the shared stack; several: a stack each, none of them shared
    stacks = {k.stack for k in kinds}
    assert stacks == {"layers"} or "layers" not in stacks and len(stacks) == len(set(kinds))
    assert llama.ATTENTION.stack == "attn_layers" and llama.ATTENTION.mix is llama.attention.mix


@pytest.mark.parametrize("name", [*TINY, *BENCH])
def test_a_kinds_init_makes_exactly_its_stacks_mixer_leaves(name):
    """Name for name and shape for shape: a leaf can neither be lost nor made
    twice when a kind moves. (The shared stack holds the layers' input norm
    beside its one kind's leaves.)"""
    cfg = model(name)
    kinds, params = llama.mixer_kinds(cfg), param_shapes(cfg)
    dt = param_dtype(cfg)

    def mk(name, shape, scale=None):
        return jnp.zeros(shape, dt)

    n_dense = cfg.first_dense_layers if cfg.is_moe else 0
    for kind in dict.fromkeys(kinds):
        n = kinds.count(kind) - (n_dense if kind.stack == "layers" else 0)
        made = jax.eval_shape(lambda: kind.init(cfg, n, mk, dt))
        held = {k: a for k, a in params[kind.stack].items()
                if not llama.is_ffn_leaf(k) and k != "input_norm"}
        assert {k: (a.shape, a.dtype) for k, a in made.items()} == \
            {k: (a.shape, a.dtype) for k, a in held.items()}
        assert kind.stack == "layers" or "input_norm" not in params[kind.stack]
    if n_dense:  # the dense prefix takes its attention leaves from the same init
        made = jax.eval_shape(lambda: kinds[0].init(cfg, n_dense, mk, dt))
        assert set(made) == {k for k in params["dense_layers"]
                             if not llama.is_ffn_leaf(k) and k != "input_norm"}


def pools(cfg, ring: bool):
    """(kv_cache, kv_swa) shapes as the runner allocates them."""
    dt = param_dtype(cfg)
    kinds = llama.mixer_kinds(cfg)
    second = [k.pool == 1 for k in kinds] if cfg.state_space else \
        [ring and w > 0 for w in cfg.layer_windows]
    page = (PAGES, cfg.kv_cache_heads, PAGE, cfg.kv_cache_entry_dim)
    kv = jax.ShapeDtypeStruct((second.count(False), *page), dt)
    if cfg.sparse_attention:
        kv = ops.IndexedPool(kv=kv, index=jax.ShapeDtypeStruct(
            (cfg.num_layers, PAGES, PAGE, cfg.indexer_head_dim), dt))
    if cfg.state_space:
        state, conv = cfg.state_shapes
        return kv, ssm.StatePool(
            ssm=jax.ShapeDtypeStruct((sum(second), SLOTS, *state), jnp.float32),
            conv=jax.ShapeDtypeStruct((sum(second), SLOTS, *conv), dt))
    return kv, jax.ShapeDtypeStruct((sum(second), *page), dt) if ring else None


def step_input(cfg, flat: bool, ring: bool, B: int = 4, Q: int = 8) -> StepInput:
    """Zeros in the form the runner's step programs build: bucketed [B, Q],
    or the flat stream of B * Q tokens over B rows."""
    table = jnp.zeros((B, 4), jnp.int32)
    swa_table = table if ring else None
    if not flat:
        return StepInput(
            token_ids=jnp.zeros((B, Q), jnp.int32), positions=jnp.zeros((B, Q), jnp.int32),
            query_lens=jnp.ones(B, jnp.int32), kv_lens=jnp.ones(B, jnp.int32),
            page_table=table, swa_page_table=swa_table)
    T, rows = B * Q, jnp.zeros(B, jnp.int32)
    tok = jnp.zeros(T, jnp.int32)
    return StepInput(
        token_ids=tok[:, None], positions=tok[:, None], query_lens=tok + 1, kv_lens=tok + 1,
        page_table=table, swa_page_table=swa_table, token_rows=tok,
        flat_runs=((rows, rows, rows), rows, rows if ring else None),
        state_rows=ssm.state_rows(rows, rows, rows + Q, rows, rows, tok, tok == 0)
        if cfg.state_space else None)


@pytest.mark.parametrize("flat", [False, True], ids=["bucketed", "flat"])
@pytest.mark.parametrize("name", [*TINY, *BENCH])
def test_forward_hidden_hands_back_the_hidden_and_the_caches_it_was_given(name, flat):
    cfg = model(name)
    ring = cfg.sliding_window > 0 and not cfg.is_mla and len(set(cfg.layer_windows)) > 1
    kv, swa = pools(cfg, ring)
    second = {} if swa is None else {"kv_swa": swa}

    def forward(params, kv, second):
        return llama.forward_hidden(params, kv, step_input(cfg, flat, ring), cfg, **second)

    # a layout a kind does not serve is refused, not misread
    # (latent attention WITH an indexer exists on the flat stream only)
    if (cfg.is_mla and not cfg.sparse_attention if flat else cfg.state_space or cfg.sparse_attention):
        with pytest.raises(NotImplementedError, match="bucketed step only" if flat else "flat step only"):
            jax.eval_shape(forward, param_shapes(cfg), kv, second)
        return
    hidden, *caches = jax.eval_shape(forward, param_shapes(cfg), kv, second)
    rows = (32, 1) if flat else (4, 8)
    assert hidden.shape == (*rows, cfg.hidden_size) and hidden.dtype == param_dtype(cfg)
    given = jax.tree.leaves((kv, *second.values()))
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(caches)] == [(a.shape, a.dtype) for a in given]
    assert jax.tree.structure(tuple(caches)) == jax.tree.structure((kv, *second.values()))
