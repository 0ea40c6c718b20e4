"""Indexed sparse attention (HF ``sa_config``: Keye-VL-2.0, the DeepSeek-V3.2
indexer over grouped-query attention), on the flat step.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches a
layer of such a model on. It is grouped-query attention (``attention.project``,
``write_kv``, ``output``) with an indexer between the projection and the
write: J query heads and per-head score weights from the layer's normed
input and ONE shared key a token, kept in a plane of its own under the
pool's page ids (``ops.IndexedPool``); a query token attends the
``indexer_topk`` cached tokens its indexer scores highest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig
from llmd_tpu.models import attention
from llmd_tpu.models.common import (
    LayerCtx, MixerKind, StepCtx, apply_rope, layer_norm, rope_tables,
)
from llmd_tpu.ops import sparse_attention_full_flat, write_index_keys_full_flat


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights: the attention's and the indexer's."""
    H, J, Di = cfg.hidden_size, cfg.indexer_num_heads, cfg.indexer_head_dim
    return {
        **attention.init_layers(cfg, n, mk, dt),
        "wi_q": mk("wi_q", (n, H, J * Di)),
        "wi_k": mk("wi_k", (n, H, Di)),
        "wi_w": mk("wi_w", (n, H, J)),
        "wi_k_norm": jnp.ones((n, Di), dt),
        "wi_k_norm_b": jnp.zeros((n, Di), dt),
    }


def hoist(cfg: ModelConfig, inp):
    """The indexer's rope tables: it rotates all Di dimensions of its heads
    and its key."""
    if inp.token_rows is None:
        raise NotImplementedError(
            f"{cfg.name}: learned sparse attention runs on the flat step "
            "only; this program would attend past the indexer's selection"
        )
    return rope_tables(
        inp.positions, cfg.indexer_head_dim, cfg.rope_theta, cfg.rope_scaling
    )


def mix(h, lp, cache, step: StepCtx, layer: LayerCtx):
    """One layer over the flat stream; returns (out [T, 1, H], cache)."""
    cfg, inp = step.cfg, step.inp
    B, Q, _ = h.shape
    J, Di = cfg.indexer_num_heads, cfg.indexer_head_dim
    icos, isin = step.hoisted[KIND]
    q, k, v, out_gate = attention.project(h, lp, step, layer)
    # Indexer: per-token query heads, head weights and the one
    # shared key (LayerNorm, then rope), in one matmul.
    iqkw = h @ jnp.concatenate([lp["wi_q"], lp["wi_k"], lp["wi_w"]], axis=-1)
    iq = apply_rope(iqkw[..., : J * Di].reshape(B, Q, J, Di), icos, isin)
    ik = layer_norm(
        iqkw[..., J * Di : (J + 1) * Di], lp["wi_k_norm"],
        lp["wi_k_norm_b"], cfg.rms_norm_eps,
    )
    ik = apply_rope(ik[:, :, None, :], icos, isin)[:, :, 0]
    iw = iqkw[..., (J + 1) * Di :]
    cache = write_index_keys_full_flat(
        cache, layer.plane, ik[:, 0], layer.table, inp.token_rows,
        inp.positions[:, 0], step.valid[:, 0],
    )
    cache = attention.write_kv(cache, k, v, step, layer)
    attn = sparse_attention_full_flat(
        q, iq[:, 0], iw[:, 0], cache, layer.plane, inp.token_rows,
        layer.table, inp.kv_lens, inp.positions, cfg.indexer_topk,
        cfg.sm_scale, world_size=step.world_size, mesh=step.mesh,
        runs=inp.attn_runs,
    )
    return attention.output(attn, out_gate, lp, cfg), cache


KIND = MixerKind(
    stack="layers", pool=0, init=init_layers, mix=mix, hoist=hoist
)
