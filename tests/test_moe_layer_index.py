"""The layer scans of ``forward_hidden`` hand the grouped expert kernel the
STACKED expert leaves and the layer's index into ``params["layers"]``
(ops/grouped_gemm.py reads that layer in place): under every scan shape the
hidden states equal those of ``lax.ragged_dot`` on expert leaves that ride the
scan as ``xs``, where the scan itself slices the layer and no index is passed.
The index is not the layer's plane of the KV pool: a dense prefix and the ring
pool's own plane count both shift one against the other."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu import ops
from llmd_tpu.models import llama
from llmd_tpu.models.common import StepInput
from llmd_tpu.models.registry import get_model_config
from llmd_tpu.ops import grouped_gemm

B, Q, PAGE, MAX_PAGES = 2, 8, 4, 4
SLIDING, FULL = "sliding_attention", "full_attention"


def _cfg(preset, **over):
    # Lane-tiled expert dims, or the decision ladder keeps ragged_dot.
    return dataclasses.replace(
        get_model_config(preset), hidden_size=128, moe_intermediate_size=128,
        **over,
    )


# name -> (config, ring pool for the sliding layers)
CASES = {
    # one scan over every layer: what the benchmark's Qwen3 and Keye cells run
    "homogeneous": (_cfg("tiny-moe", num_layers=3), False),
    # cycles of (sliding, full): planes 0, 0, 1, 1 against layers 0, 1, 2, 3
    "periodic": (_cfg(
        "tiny-moe", num_layers=4, sliding_window=8,
        layer_types=(SLIDING, FULL, SLIDING, FULL),
    ), True),
    # runs (full), (sliding, sliding): the second run's layers are 1 and 2,
    # its planes 0 and 1
    "aperiodic": (_cfg(
        "tiny-moe", num_layers=3, sliding_window=8,
        layer_types=(FULL, SLIDING, SLIDING),
    ), True),
    # one dense layer first: planes 1, 2 against layers 0, 1 (DeepSeek's cells)
    "dense-prefix": (_cfg("tiny-mla"), False),
}


def _pool(cfg, layers):
    return jnp.zeros(
        (layers, B * MAX_PAGES, cfg.kv_cache_heads, PAGE,
         cfg.kv_cache_entry_dim), jnp.float32,
    )


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_reads_the_layer_the_scan_is_at(monkeypatch, case):
    cfg, ring = CASES[case]
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    params = llama.init_params(cfg, jax.random.key(7))
    assert params["layers"]["we_gate"].ndim == 4
    sliding = sum(w > 0 for w in cfg.layer_windows) if ring else 0
    table = jnp.arange(B * MAX_PAGES, dtype=jnp.int32).reshape(B, -1)
    rng = np.random.default_rng(0)
    inp = StepInput(
        token_ids=jnp.asarray(rng.integers(1, 200, (B, Q)), jnp.int32),
        positions=jnp.tile(jnp.arange(Q, dtype=jnp.int32), (B, 1)),
        query_lens=jnp.full(B, Q, jnp.int32),
        kv_lens=jnp.full(B, Q, jnp.int32),
        page_table=table,
        swa_page_table=table if ring else None,
    )
    kv = _pool(cfg, cfg.num_layers - sliding)
    kv_swa = _pool(cfg, sliding) if ring else None

    def hidden():
        plans = {}
        with ops.record_plans(plans):
            out = jax.jit(lambda p: llama.forward_hidden(
                p, kv, inp, cfg, moe_backend="grouped", kv_swa=kv_swa,
            )[0])(params)
        return np.asarray(out), plans.get("grouped_gemm")

    got, plan = hidden()
    assert plan == {"pallas"}
    # The reference takes no index from anyone: every leaf is scanned.
    monkeypatch.setattr(llama, "STACKED_EXPERT_LEAVES", ())
    monkeypatch.setattr(grouped_gemm, "_use_kernel", lambda *a: False)
    want, _ = hidden()
    assert np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
