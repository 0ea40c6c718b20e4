"""Indexed latent attention (HF ``deepseek_v32``: DeepSeek-V3.2's sparse
attention), on the flat step.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches a
layer of such a model on. It is multi-head latent attention (``mla``'s
weights, its projections and its absorption: one cached row
``[RMSNorm(c), RoPE(k_r)]`` a token) with the lightning indexer of ``dsa``
between the projection and the read: J query heads from the NORMED QUERY
LATENT ``c_q`` (not from the layer's input, which the grouped-query variant
reads for want of such a latent), head weights and ONE shared key a token
from the layer's normed input, the key under LayerNorm, kept in the plane
beside the latent pool under its page ids (``ops.IndexedPool``). The indexer
rotates the FIRST ``qk_rope_head_dim`` of its ``indexer_head_dim``
dimensions (``ModelConfig.indexer_rope_dim``), rotate-half among
themselves; the rest pass. A query token attends the ``indexer_topk`` cached
rows its indexer scores highest (``ops/sparse_mla.py``).

Published weights. The published inference code rotates the main
attention's rope dimensions as interleaved pairs and the indexer's as
halves; the program rotates halves in both (``common.apply_rope``). A dot
product of two vectors rotated alike does not care how the pairs are laid
out, so this is a permutation of the columns that PRODUCE the rotated
dimensions: ``from_published`` is that map, a loader's step. Its Hadamard
rotation of the indexer's queries and keys is orthogonal (the scores are
unchanged in exact arithmetic) and is not applied; its FP8 storage of the
latent and the key is not served (``EngineConfig.check_sparse_attention``
refuses a narrower cache).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from llmd_tpu.config import ModelConfig
from llmd_tpu.models import mla
from llmd_tpu.models.common import (
    LayerCtx, MixerKind, StepCtx, apply_rope, layer_norm, pdot, rms_norm,
    rope_tables, yarn_sm_scale_mult,
)
from llmd_tpu.ops.sparse_mla import (
    sparse_mla_attention_full_flat,
    write_latent_rows_full_flat,
)


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights: latent attention's and the
    indexer's (its queries from the query latent)."""
    H, J, Di = cfg.hidden_size, cfg.indexer_num_heads, cfg.indexer_head_dim
    return {
        **mla.init_layers(cfg, n, mk, dt),
        "wi_q": mk("wi_q", (n, cfg.q_lora_rank, J * Di)),
        "wi_k": mk("wi_k", (n, H, Di)),
        "wi_w": mk("wi_w", (n, H, J)),
        "wi_k_norm": jnp.ones((n, Di), dt),
        "wi_k_norm_b": jnp.zeros((n, Di), dt),
    }


def from_published(w: np.ndarray, heads: int, head_dim: int, rope_dim: int):
    """Columns of a published projection ``w`` [in, heads * head_dim] whose
    LAST ``rope_dim`` dimensions a head are rotated as interleaved pairs
    (x0 x1 | x2 x3 | ...: ``wq_b``'s rope part with heads = num_heads,
    ``wkv_a``'s ``k_pe`` with heads = 1 over its last ``rope_dim`` columns),
    reordered for the rotate-half layout the program rotates (x0 x2 .. |
    x1 x3 ..). The indexer's projections are published in that layout and
    pass as they are."""
    w = np.asarray(w).reshape(w.shape[0], heads, head_dim)
    keep, rot = w[..., : head_dim - rope_dim], w[..., head_dim - rope_dim :]
    rot = np.concatenate([rot[..., 0::2], rot[..., 1::2]], axis=-1)
    return np.concatenate([keep, rot], axis=-1).reshape(w.shape[0], -1)


def hoist(cfg: ModelConfig, inp):
    """The indexer's rope tables: ``indexer_rope_dim`` of its dimensions
    rotate (the main attention's are ``step.ropes[0]``)."""
    if inp.token_rows is None:
        raise NotImplementedError(
            f"{cfg.name}: learned sparse attention runs on the flat step "
            "only; this program would attend past the indexer's selection"
        )
    return rope_tables(
        inp.positions, cfg.indexer_rope_dim, cfg.rope_theta, cfg.rope_scaling
    )


def mix(h, lp, cache, step: StepCtx, layer: LayerCtx):
    """One layer over the flat stream; returns (out [T, 1, H], cache)."""
    cfg, inp = step.cfg, step.inp
    T = h.shape[0]
    nh, J, Di = cfg.num_heads, cfg.indexer_num_heads, cfg.indexer_head_dim
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, Dl = cfg.kv_lora_rank, cfg.kv_cache_entry_dim
    eps = cfg.rms_norm_eps
    icos, isin = step.hoisted[KIND]
    cos, sin = step.ropes[0]
    # ---- queries, from the normed query latent
    c_q = rms_norm(pdot(h, lp, "wq_a"), lp["q_norm"], eps)
    # The two products of c_q come out FLAT before their heads are told
    # apart: left free, the compiler lays their results out by head for the
    # batched products behind them and pays with a transposed copy of each
    # weight (75 and 25 MB a layer and step at the published widths).
    q, iq = jax.lax.optimization_barrier(
        (pdot(c_q, lp, "wq_b"), c_q @ lp["wi_q"])
    )
    q = q.reshape(T, 1, nh, nope + rope)
    q_pe = apply_rope(q[..., nope:], cos, sin)
    # ---- the cached row: [RMSNorm(c), RoPE(k_r)], padded to the lane tile
    kv_a = pdot(h, lp, "wkv_a")
    c_kv = rms_norm(kv_a[..., :rank], lp["kv_norm"], eps)
    k_pe = apply_rope(kv_a[..., None, rank:], cos, sin)[:, :, 0]
    latent = jnp.pad(
        jnp.concatenate([c_kv, k_pe], axis=-1),
        ((0, 0), (0, 0), (0, Dl - rank - rope)),
    )
    # ---- the indexer: heads from c_q; key and head weights from h
    iq = apply_rope(iq.reshape(T, 1, J, Di), icos, isin)
    ikw = h @ jnp.concatenate([lp["wi_k"], lp["wi_w"]], axis=-1)
    ik = layer_norm(ikw[..., :Di], lp["wi_k_norm"], lp["wi_k_norm_b"], eps)
    ik = apply_rope(ik[:, :, None, :], icos, isin)[:, :, 0]
    iw = ikw[..., Di:] * (J**-0.5 * Di**-0.5)
    cache = write_latent_rows_full_flat(
        cache, layer.plane, latent[:, 0], ik[:, 0], layer.table,
        inp.token_rows, inp.positions[:, 0], step.valid[:, 0],
        (*inp.flat_runs[0], layer.run_phys)
        if inp.flat_runs is not None and layer.run_phys is not None
        else None,
        mesh=step.mesh,
    )
    # ---- absorption: W_uk into the query, W_uv behind the read
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
    q_eff = jnp.concatenate([
        jnp.einsum("tqhn,rhn->tqhr", q[..., :nope], wkv_b[..., :nope]), q_pe,
    ], axis=-1)
    q_eff = jnp.pad(q_eff, ((0, 0),) * 3 + ((0, Dl - rank - rope),))
    out_lat = sparse_mla_attention_full_flat(
        q_eff[:, 0], iq[:, 0], iw[:, 0], cache, layer.plane, inp.token_rows,
        layer.table, inp.kv_lens, cfg.indexer_topk, rank,
        (nope + rope) ** -0.5 * yarn_sm_scale_mult(cfg.rope_scaling),
        world_size=step.world_size, mesh=step.mesh,
    )  # [T, nh, rank]
    out = jnp.einsum("thr,rhv->thv", out_lat, wkv_b[..., nope:])
    return pdot(out.reshape(T, 1, nh * vd), lp, "wo"), cache


KIND = MixerKind(
    stack="layers", pool=0, init=init_layers, mix=mix, hoist=hoist
)
