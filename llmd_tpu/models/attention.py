"""Grouped-query attention over the paged KV pool: the mixer of Llama-2/3,
Qwen2/3, Mixtral, gpt-oss and of the attention layers of the hybrids.

``KIND`` (``common.MixerKind``) is what ``llama.forward_hidden`` dispatches
such a layer on; ``llama.ATTENTION`` is the same kind over a hybrid's
``attn_layers`` stack. ``project`` (q | k | v fused or not, bias, per-row
LoRA, the output gate's split, q/k norm, rotation, ``kv_rep``), ``write_kv``
and ``output`` are also what indexed sparse attention (``models/dsa.py``)
builds its own ``mix`` from.

``step.kv_rep`` > 1 stores each KV head ``kv_rep`` times consecutively so the
pool's head axis divides tp when num_kv_heads alone does not (tp > K):
per-chip KV is then pool/K instead of a full replicated pool. Attention
grouping stays exact — q head h reads expanded head h // (Nq / (K*kv_rep)),
which holds h's original kv head.

``step.cp`` > 1 (ParallelConfig.cp_prefill) runs the layer's attention as a
context-parallel ring over the mesh "dp" axis (ops/ring_attention.py): the
chunk's query rows and fresh K/V shard contiguously across dp, K/V blocks
rotate via ppermute while every shard folds online-softmax partials, and the
committed prefix is read from the post-write pool — tolerance-equal to the
monolithic path. Only engaged for the bucketed non-DBO layout with Q
divisible by cp (the runner compiles a dedicated prefill program for it).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig
from llmd_tpu.models.common import (
    LayerCtx, MixerKind, StepCtx, apply_rope, norm_weight, pdot, rms_norm,
)
from llmd_tpu.ops import (
    paged_attention_full,
    paged_attention_full_flat,
    write_kv_pages_full,
    write_kv_pages_full_flat,
)
from llmd_tpu.ops.ring_attention import ring_prefill_attention_full


def init_layers(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The ``n`` stacked mixers' weights (``mk(name, shape, scale=None)``
    draws a seeded leaf)."""
    H, D, Nq, K = cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    # ``attn_output_gate``: per head (q[D], gate[D]), as published.
    qw = 2 * D if cfg.attn_output_gate else D
    layers = {
        "wq": mk("wq", (n, H, Nq * qw)),
        "wk": mk("wk", (n, H, K * D)),
        "wv": mk("wv", (n, H, K * D)),
        "wo": mk("wo", (n, Nq * D, H)),
    }
    layers.update(extras(cfg, n, mk, dt))
    if cfg.num_lora_adapters:
        # Adapter slot 0 = base model (zeros); slots 1..A are live
        # adapters on the q and v projections (the classic target set).
        A1, r = cfg.num_lora_adapters + 1, cfg.lora_rank
        mask = (jnp.arange(A1) > 0).astype(dt)[None, :, None, None]
        layers["la_q"] = mk("la_q", (n, A1, H, r)) * mask
        layers["la_v"] = mk("la_v", (n, A1, H, r)) * mask
        # Standard LoRA init: B starts at zero so every adapter slot is
        # exactly the base model until real adapter weights are loaded
        # (random B would perturb outputs for adapter-named requests).
        layers["lb_q"] = jnp.zeros((n, A1, r, Nq * D), dt)
        layers["lb_v"] = jnp.zeros((n, A1, r, K * D), dt)
    return layers


def extras(cfg: ModelConfig, n: int, mk, dt) -> dict[str, jax.Array]:
    """The leaves any attention kind carries where the model has them:
    biases, sinks, the per-head q/k norms."""
    D, Nq, K = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    layers = {}
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((n, Nq * D), dt)
        layers["bk"] = jnp.zeros((n, K * D), dt)
        layers["bv"] = jnp.zeros((n, K * D), dt)
    if cfg.attention_out_bias:
        layers["bo"] = jnp.zeros((n, cfg.hidden_size), dt)
    if cfg.attention_sinks:
        layers["sinks"] = mk("sinks", (n, Nq), scale=1.0)
    if cfg.qk_norm:
        layers["attn_q_norm"] = norm_weight(cfg, mk, dt, "attn_q_norm", (n, D))
        layers["attn_k_norm"] = norm_weight(cfg, mk, dt, "attn_k_norm", (n, D))
    return layers


def project(h, lp, step: StepCtx, layer: LayerCtx):
    """(q [B, Q, Nq, D], k, v [B, Q, K * kv_rep, D], the output gate or
    None) of the normed input ``h`` [B, Q, H]: projected, rotated, and the
    K/V heads repeated as the pool stores them."""
    cfg, inp = step.cfg, step.inp
    B, Q, _ = h.shape
    D, Nq, K = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    if "wqkv" in lp:  # fused q|k|v (runner._maybe_fuse; lossless)
        qkv = pdot(h, lp, "wqkv")
        q = qkv[..., : Nq * D]
        k = qkv[..., Nq * D : (Nq + K) * D]
        v = qkv[..., (Nq + K) * D :]
    else:
        q = pdot(h, lp, "wq")
        k = pdot(h, lp, "wk")
        v = pdot(h, lp, "wv")
    if cfg.attention_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if cfg.num_lora_adapters and inp.lora_ids is not None:
        # Per-sequence adapters: gather each row's A/B and apply
        # x@A@B on q and v (batched einsum; slot 0 is zeros).
        la_q = lp["la_q"][inp.lora_ids]  # [B, H, r]
        lb_q = lp["lb_q"][inp.lora_ids]  # [B, r, Nq*D]
        la_v = lp["la_v"][inp.lora_ids]
        lb_v = lp["lb_v"][inp.lora_ids]
        q = q + jnp.einsum(
            "bqr,brd->bqd", jnp.einsum("bqh,bhr->bqr", h, la_q), lb_q
        )
        v = v + jnp.einsum(
            "bqr,brd->bqd", jnp.einsum("bqh,bhr->bqr", h, la_v), lb_v
        )
    out_gate = None
    if cfg.attn_output_gate:
        # A head's projection is (q[D], gate[D]); the gate meets the
        # attention's output in ``output``.
        q = q.reshape(B, Q, Nq, 2 * D)
        q, out_gate = q[..., :D], q[..., D:]
    q = q.reshape(B, Q, Nq, D)
    k = k.reshape(B, Q, K, D)
    if cfg.qk_norm:  # Qwen3: per-head RMS norm before RoPE
        q = rms_norm(q, lp["attn_q_norm"], cfg.rms_norm_eps, cfg.norm_zero_centered)
        k = rms_norm(k, lp["attn_k_norm"], cfg.rms_norm_eps, cfg.norm_zero_centered)
    if layer.rope is not None:  # None: the layer's kind has no table
        if isinstance(layer.rope, int):
            cos_l, sin_l = step.ropes[layer.rope]
        else:  # a scan over layers of several kinds: the layer's row
            cos_l, sin_l = (t[layer.rope] for t in step.rope_stack)
        q = apply_rope(q, cos_l, sin_l)
        k = apply_rope(k, cos_l, sin_l)
    v = v.reshape(B, Q, K, D)
    if step.kv_rep > 1:
        k = jnp.repeat(k, step.kv_rep, axis=2)
        v = jnp.repeat(v, step.kv_rep, axis=2)
    return q, k, v, out_gate


def write_kv(cache, k, v, step: StepCtx, layer: LayerCtx):
    """This step's K/V into the layer's plane of ``cache``: the flat stream
    by its run plan, else the bucketed rows."""
    inp = step.inp
    if inp.token_rows is not None:
        return write_kv_pages_full_flat(
            cache, layer.plane, k, v, layer.table, inp.token_rows,
            inp.positions, step.valid,
            (*inp.flat_runs[0], layer.run_phys)
            if inp.flat_runs is not None and layer.run_phys is not None
            else None,
            world_size=step.world_size, mesh=step.mesh,
        )
    return write_kv_pages_full(
        cache, layer.plane, k, v, layer.table, inp.positions, step.valid,
        world_size=step.world_size, mesh=step.mesh,
    )


def output(attn, out_gate, lp, cfg: ModelConfig):
    """The attention's rows through the gate and the output projection."""
    B, Q = attn.shape[:2]
    D, Nq = cfg.head_dim, cfg.num_heads
    if out_gate is not None:
        attn = attn.reshape(B, Q, Nq, D) * jax.nn.sigmoid(out_gate)
    out = pdot(attn.reshape(B, Q, Nq * D), lp, "wo")
    if "bo" in lp:
        out = out + lp["bo"]
    return out


def mix(h, lp, cache, step: StepCtx, layer: LayerCtx):
    """One attention layer; returns (out [B, Q, H], cache)."""
    cfg, inp = step.cfg, step.inp
    q, k, v, out_gate = project(h, lp, step, layer)
    cache = write_kv(cache, k, v, step, layer)
    sinks = lp.get("sinks")
    if inp.token_rows is not None:
        # A Pallas call takes its scope's name in the device trace:
        # a model that mixes the two kinds reads them apart.
        scope = (
            jax.named_scope(f"llmd.attn.{layer.attn_kind}") if layer.attn_kind
            else contextlib.nullcontext()
        )
        with scope:
            attn = paged_attention_full_flat(
                q, cache, layer.plane, inp.token_rows, layer.table,
                inp.kv_lens, inp.positions, cfg.sm_scale,
                world_size=step.world_size, mesh=step.mesh,
                window=layer.window, sinks=sinks,
                runs=inp.attn_runs if layer.window is None else None,
            )
    elif step.cp:
        attn = ring_prefill_attention_full(
            q, cache, layer.plane, k, v, layer.table, inp.kv_lens,
            inp.positions, step.valid, cfg.sm_scale, mesh=step.mesh,
            cp=step.cp, window=layer.window, sinks=sinks,
        )
    else:
        attn = paged_attention_full(
            q, cache, layer.plane, layer.table, inp.kv_lens, inp.positions,
            cfg.sm_scale, world_size=step.world_size, mesh=step.mesh,
            window=layer.window, sinks=sinks,
        )
    return output(attn, out_gate, lp, cfg), cache


def halves(h, lp, cache, step: StepCtx, layer: LayerCtx):
    """The whole batch's write, then the read-only attention and projection
    of a half (dual-batch overlap; the bucketed layout)."""
    cfg, inp = step.cfg, step.inp
    if cfg.attn_output_gate:
        raise NotImplementedError(
            f"{cfg.name}: dual-batch overlap would drop the attention's "
            "output gate"
        )
    q, k, v, _ = project(h, lp, step, layer)
    cache = write_kv(cache, k, v, step, layer)

    def read(sl):
        return paged_attention_full(
            q[sl], cache, layer.plane, layer.table[sl], inp.kv_lens[sl],
            inp.positions[sl], cfg.sm_scale, world_size=step.world_size,
            mesh=step.mesh, window=layer.window, sinks=lp.get("sinks"),
        )

    return cache, read, lambda attn: output(attn, None, lp, cfg)


KIND = MixerKind(
    stack="layers", pool=0, init=init_layers, mix=mix, halves=halves
)
