"""Multi-head latent attention (DeepSeek V2/V3/R1 family).

The attention half of the DeepSeek architecture the reference's wide-EP
guides deploy (SURVEY.md §2.4: DeepSeek-R1 on 16P+16D; wide-EP MoE
lives in llmd_tpu/parallel/moe_ep.py — MLA is what makes its decode
batches fit by caching one compressed latent per token).

Projections (HF naming in comments):
  q:  x -> [q_lora_rank] -> norm -> heads x (nope + rope)   (q_a/q_b)
      or dense x -> heads x (nope + rope) when q_lora_rank == 0
  kv: x -> [kv_lora_rank + rope]                            (kv_a)
      latent = [rmsnorm(c_kv), rope(k_pe)]   <- THE CACHED ROW
      kv_b: [kv_lora_rank] -> heads x (nope + v)
Decode uses weight absorption: fold kv_b's key half into the query
(q_eff = [q_nope @ W_uk, q_pe]) and its value half into the output
(out = attn_latent @ W_uv), so attention itself never materializes
per-head K/V — it runs against the latent cache directly.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches a
layer of such a model on: ``mla_attention``, and ``mla_write`` / ``mla_read``
apart for dual-batch overlap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig
from llmd_tpu.models import attention
from llmd_tpu.models.common import (
    LayerCtx, MixerKind, StepCtx, StepInput, apply_rope, pdot, rms_norm,
    rope_tables, yarn_sm_scale_mult,
)
from llmd_tpu.ops import mla_paged_attention_full, write_kv_pages_full


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights (``mk(name, shape, scale=None)``
    draws a seeded leaf)."""
    H, Nq = cfg.hidden_size, cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    layers = {
        "wkv_a": mk("wkv_a", (n, H, rank + rope)),
        "kv_norm": jnp.ones((n, rank), dt),
        "wkv_b": mk("wkv_b", (n, rank, Nq * (nope + vd))),
        "wo": mk("wo", (n, Nq * vd, H)),
    }
    if cfg.q_lora_rank > 0:
        layers["wq_a"] = mk("wq_a", (n, H, cfg.q_lora_rank))
        layers["q_norm"] = jnp.ones((n, cfg.q_lora_rank), dt)
        layers["wq_b"] = mk("wq_b", (n, cfg.q_lora_rank, Nq * (nope + rope)))
    else:
        layers["wq"] = mk("wq", (n, H, Nq * (nope + rope)))
    layers.update(attention.extras(cfg, n, mk, dt))
    return layers


def mla_write(
    h: jax.Array,          # [B, Q, H] (already input-normed)
    lp: dict,              # this layer's params
    cache: jax.Array,      # FULL [L, pages, 1, page, Dl]
    layer_idx: jax.Array,  # scalar i32
    inp: StepInput,
    cfg: ModelConfig,
    cos: jax.Array,
    sin: jax.Array,
    world_size: int = 1,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Write phase: project + cache this step's latents; returns
    (updated cache, absorbed effective queries q_eff [B, Q, nh, Dl]).

    Split from the read phase so dual-batch-overlap can write the FULL
    batch once and then run read-only attention per microbatch."""
    B, Q, _ = h.shape
    nh = cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rank = cfg.kv_lora_rank
    Dl = cfg.kv_cache_entry_dim

    # ---- queries
    if cfg.q_lora_rank > 0:
        q = pdot(
            rms_norm(pdot(h, lp, "wq_a"), lp["q_norm"], cfg.rms_norm_eps),
            lp, "wq_b",
        )
    else:
        q = pdot(h, lp, "wq")
    q = q.reshape(B, Q, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, cos, sin)

    # ---- latent (the cached row)
    kv_a = pdot(h, lp, "wkv_a")  # [B, Q, rank + rope]
    c_kv = rms_norm(kv_a[..., :rank], lp["kv_norm"], cfg.rms_norm_eps)
    k_pe = apply_rope(kv_a[..., None, rank:], cos, sin)[:, :, 0]  # shared head
    latent = jnp.concatenate([c_kv, k_pe], axis=-1)
    if Dl > rank + rope:
        latent = jnp.pad(latent, ((0, 0), (0, 0), (0, Dl - rank - rope)))
    # Write through the generic page writer: split the row into two
    # halves posing as K/V — the writer just concatenates them back.
    half = Dl // 2
    lat4 = latent[:, :, None, :]  # [B, Q, 1, Dl]
    cache = write_kv_pages_full(
        cache, layer_idx, lat4[..., :half], lat4[..., half:],
        inp.page_table, inp.positions, inp.valid, world_size=world_size,
        mesh=mesh,
    )

    # ---- absorption (query half): W_uk [nh, rank, nope]
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + cfg.v_head_dim)
    w_uk = wkv_b[..., :nope].transpose(1, 0, 2)  # [nh, rank, nope]
    q_eff_nope = jnp.einsum("bqhn,hrn->bqhr", q_nope, w_uk)
    q_eff = jnp.concatenate([q_eff_nope, q_pe], axis=-1)  # [B, Q, nh, rank+rope]
    if Dl > rank + rope:
        q_eff = jnp.pad(q_eff, ((0, 0), (0, 0), (0, 0), (0, Dl - rank - rope)))
    return cache, q_eff


def mla_read(
    q_eff: jax.Array,      # [B, Q, nh, Dl]
    lp: dict,
    cache: jax.Array,
    layer_idx: jax.Array,
    page_table: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,     # [B]
    positions: jax.Array,   # [B, Q]
    cfg: ModelConfig,
    world_size: int = 1,
    mesh=None,
) -> jax.Array:
    """Read phase: latent attention against cache[layer] + value
    absorption + output projection. Read-only on the cache — microbatches
    of the same step run independently (the DBO property)."""
    B, Q = q_eff.shape[:2]
    nh = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    # MLA scales by the FULL qk head dim (nope + rope), not the latent;
    # DeepSeek yarn folds its mscale^2 temperature correction in here.
    sm_scale = (nope + rope) ** -0.5 * yarn_sm_scale_mult(cfg.rope_scaling)
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
    w_uv = wkv_b[..., nope:].transpose(1, 0, 2)  # [nh, rank, vd]
    # ---- latent attention (Pallas on TPU decode: streams live pages;
    # never slices the pool)
    out_lat = mla_paged_attention_full(
        q_eff, cache, layer_idx, page_table, kv_lens, positions,
        rank=rank, sm_scale=sm_scale, world_size=world_size, mesh=mesh,
    )  # [B, Q, nh, rank]
    out = jnp.einsum("bqhr,hrv->bqhv", out_lat, w_uv)  # [B, Q, nh, vd]
    return pdot(out.reshape(B, Q, nh * vd), lp, "wo")


def mla_attention(
    h: jax.Array,          # [B, Q, H] (already input-normed)
    lp: dict,              # this layer's params
    cache: jax.Array,      # FULL [L, pages, 1, page, Dl]
    layer_idx: jax.Array,  # scalar i32
    inp: StepInput,
    cfg: ModelConfig,
    cos: jax.Array | None = None,  # rope tables for qk_rope_head_dim,
    sin: jax.Array | None = None,  # hoisted out of the layer scan
    world_size: int = 1,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (attn output [B, Q, H_hidden], updated cache)."""
    if cos is None or sin is None:
        cos, sin = rope_tables(
            inp.positions, cfg.qk_rope_head_dim, cfg.rope_theta,
            cfg.rope_scaling,
        )
    cache, q_eff = mla_write(
        h, lp, cache, layer_idx, inp, cfg, cos, sin,
        world_size=world_size, mesh=mesh,
    )
    out = mla_read(
        q_eff, lp, cache, layer_idx, inp.page_table, inp.kv_lens,
        inp.positions, cfg, world_size=world_size, mesh=mesh,
    )
    return out, cache


def _mix(h, lp, cache, step: StepCtx, layer: LayerCtx):
    if step.inp.token_rows is not None:
        raise NotImplementedError(
            f"{step.cfg.name}: latent attention runs on the bucketed step "
            "only; the flat stream's write plan addresses K and V"
        )
    return mla_attention(
        h, lp, cache, layer.plane, step.inp, step.cfg, *step.ropes[0],
        world_size=step.world_size, mesh=step.mesh,
    )


def _halves(h, lp, cache, step: StepCtx, layer: LayerCtx):
    cfg, inp = step.cfg, step.inp
    cache, q_eff = mla_write(
        h, lp, cache, layer.plane, inp, cfg, *step.ropes[0],
        world_size=step.world_size, mesh=step.mesh,
    )

    def read(sl):
        return mla_read(
            q_eff[sl], lp, cache, layer.plane, inp.page_table[sl],
            inp.kv_lens[sl], inp.positions[sl], cfg,
            world_size=step.world_size, mesh=step.mesh,
        )

    return cache, read, lambda out: out  # ``mla_read`` projects


KIND = MixerKind(
    stack="layers", pool=0, init=init_layers, mix=_mix, halves=_halves
)


def mla_reference_attention(
    h: jax.Array,
    lp: dict,
    inp: StepInput,
    cfg: ModelConfig,
    context_latent: jax.Array,  # [B, S, rank+rope] unnormalized? no: cached latents
) -> jax.Array:
    """Numerical oracle WITHOUT absorption: materialize per-head K/V from
    the context latents and run standard masked attention. Used by tests
    to validate the absorbed/paged path."""
    B, Q, _ = h.shape
    nh = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    sm_scale = (nope + rope) ** -0.5 * yarn_sm_scale_mult(cfg.rope_scaling)
    cos, sin = rope_tables(inp.positions, rope, cfg.rope_theta, cfg.rope_scaling)

    if cfg.q_lora_rank > 0:
        q = rms_norm(h @ lp["wq_a"], lp["q_norm"], cfg.rms_norm_eps) @ lp["wq_b"]
    else:
        q = h @ lp["wq"]
    q = q.reshape(B, Q, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, cos, sin)

    S = context_latent.shape[1]
    c_kv = context_latent[..., :rank]          # already normed when cached
    k_pe = context_latent[..., rank : rank + rope]
    wkv_b = lp["wkv_b"].reshape(rank, nh, nope + vd)
    k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, wkv_b[..., :nope])
    v = jnp.einsum("bsr,rhv->bshv", c_kv, wkv_b[..., nope:])
    scores = (
        jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe, preferred_element_type=jnp.float32)
    ) * sm_scale
    key_pos = jnp.arange(S)[None, None, :]
    mask = (key_pos <= inp.positions[:, :, None])[:, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshv->bqhv", probs, v)
    return out.reshape(B, Q, nh * vd) @ lp["wo"]
