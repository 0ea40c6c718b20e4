"""Paged attention over a block (page) table.

TPU equivalent of the reference's FlashInfer / ragged-paged-attention path
(SURVEY.md N8: reference ships FlashInfer CUDA kernels, and the TPU images
use Pallas ragged paged attention). Two implementations behind one
interface:

- ``paged_attention_xla``: pure-XLA reference implementation (gather pages,
  masked softmax). Correct everywhere (CPU test mesh included); used as the
  numerical oracle for the Pallas kernel and as the fallback path.
- ``paged_attention`` in ``llmd_tpu.ops.ragged_paged_attention``:
  the Pallas TPU kernel (flash-style online softmax over pages).

Layout conventions (TPU-first):
  kv_cache (one layer): [num_pages, num_kv_heads, page_size, 2*head_dim]
      (K in [..., :head_dim], V in [..., head_dim:]; head-major within a
      page so one (page, head) slab is a contiguous DMA)
  q:          [B, Q, num_q_heads, head_dim]
  page_table: [B, max_pages] int32
  kv_lens:    [B] int32, total valid kv tokens per seq AFTER this step's
              writes (so causality is enforced via per-token positions).
  positions:  [B, Q] int32 absolute position of each query token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def write_kv_pages(
    kv_cache: jax.Array,  # [num_pages, K, page, 2D]
    k: jax.Array,  # [B, Q, K, D]
    v: jax.Array,  # [B, Q, K, D]
    page_table: jax.Array,  # [B, max_pages]
    positions: jax.Array,  # [B, Q]
    valid: jax.Array,  # [B, Q] bool
) -> jax.Array:
    """Scatter this step's K/V into their cache slots.

    Token (b, i) lands at [page_table[b, pos // page], :, pos % page, :].
    Invalid (padding) tokens scatter out-of-bounds and are dropped.
    """
    num_pages, K, page, D2 = kv_cache.shape
    kv = jnp.concatenate([k, v], axis=-1)  # [B, Q, K, 2D]
    page_idx = positions // page
    offset = positions % page
    phys = jnp.take_along_axis(page_table, page_idx, axis=1)  # [B, Q]
    phys = jnp.where(valid, phys, num_pages)  # OOB => dropped
    T = phys.size
    kv_flat = kv.reshape(T, K, D2).astype(kv_cache.dtype)
    return kv_cache.at[
        phys.reshape(T, 1), jnp.arange(K)[None, :], offset.reshape(T, 1), :
    ].set(kv_flat, mode="drop")


def scatter_kv_scales(
    scales: jax.Array,  # [num_pages, K, page, 2] f32 (one layer)
    srow: jax.Array,  # [B, Q, K, 2] per-row K/V-half scales
    page_table: jax.Array,  # [B, max_pages]
    positions: jax.Array,  # [B, Q]
    valid: jax.Array,  # [B, Q] bool
) -> jax.Array:
    """Scatter this step's per-row scales into one layer's scale pool
    (the tiny sibling of write_kv_pages; ~1/32 of the data bytes, so the
    plain XLA scatter is fine even on the Pallas write path). The
    half-pair is the trailing contiguous dim — one 8-byte write per
    (token, head)."""
    num_pages, K, page, two = scales.shape
    B, Q = positions.shape
    page_idx = positions // page
    offset = positions % page
    phys = jnp.take_along_axis(page_table, page_idx, axis=1)
    phys = jnp.where(valid, phys, num_pages)  # OOB => dropped
    T = phys.size
    if Q > 1:
        # Prefill: K stays a SLICE, not an enumerated index — T scatter
        # updates with a [K, 2] window each instead of T*K eight-byte
        # updates. Scatter cost is per-update; the enumerated form was
        # measured at ~1/5 of the whole int8 prefill step (B=128,
        # Q=384: 3.68s -> 3.16s, vs 3.07s with the write deleted).
        return scales.at[
            phys.reshape(T), :, offset.reshape(T), :
        ].set(srow.reshape(T, K, 2).astype(scales.dtype), mode="drop")
    # Decode (T = B rows): gather each row's page slab, update its
    # column densely, write back WHOLE [K, page, 2] slabs — contiguous
    # 1KB updates instead of T*K strided 8-byte ones. Safe: a writable
    # page belongs to exactly one sequence (prefix-shared pages are
    # read-only), so slab writes cannot race. (Measured per 64-step
    # window: enumerated scatter 5.5ms/step; [K,2] strided windows
    # worse; this form ~zero.)
    phys_f = phys.reshape(T)
    slabs = scales[jnp.minimum(phys_f, num_pages - 1)]  # [T, K, page, 2]
    col = (
        jax.lax.broadcasted_iota(jnp.int32, (T, 1, page, 1), 2)
        == offset.reshape(T, 1, 1, 1)
    )
    slabs = jnp.where(
        col, srow.reshape(T, K, 1, 2).astype(scales.dtype), slabs
    )
    return scales.at[phys_f].set(slabs, mode="drop")


def scatter_kv_scales_flat(
    scales: jax.Array,  # [num_pages, K, page, 2] f32 (one layer)
    srow: jax.Array,  # [T, 1, K, 2] per-token K/V-half scales
    page_table: jax.Array,  # [R, max_pages] COMPACT per-row table
    rows: jax.Array,  # [T] i32 token -> row
    positions: jax.Array,  # [T, 1]
    valid: jax.Array,  # [T, 1] bool
) -> jax.Array:
    """Flattened-token scale scatter: one enumerated (page, slot) write
    per live token. The decode path's dense-slab form is WRONG here —
    it assumes one token per page, and a gathered-slab update with
    duplicate page indices drops all but one of a prefill chunk's
    same-page tokens — while the enumerated targets are distinct by
    construction (distinct (page, slot) per live token)."""
    num_pages, K, page, two = scales.shape
    T = rows.shape[0]
    pos = positions[:, 0]
    phys = page_table[rows, pos // page]
    phys = jnp.where(valid[:, 0], phys, num_pages)  # OOB => dropped
    return scales.at[phys, :, pos % page, :].set(
        srow.reshape(T, K, 2).astype(scales.dtype), mode="drop"
    )


def _dequant_gathered(kv, scales, page_idx, D, dtype=jnp.bfloat16):
    """Gathered int8 pages [B, n, K, page, 2D] + one layer's scale pool
    [P, K, page, 2] with the same page indices [B, n] -> k, v
    [B, S, K, D] in ``dtype`` (S = n * page).

    ``dtype`` defaults to bf16, NOT f32: these feed the attention
    matmuls, and f32 operands push them onto the MXU's 1/8-rate f32
    path with 2x the VMEM bytes — measured as the entire int8-pool
    prefill regression vs bf16 pools (the decode kernel was within 5%
    all along). int8 values are exact in bf16; only the scale multiply
    rounds, bounded by the quantization error already accepted."""
    B, n, K, page, D2 = kv.shape
    S = n * page
    kv = kv.transpose(0, 1, 3, 2, 4).reshape(B, S, K, D2).astype(jnp.float32)
    g = scales[page_idx]  # [B, n, K, page, 2]
    s = g.transpose(0, 1, 3, 2, 4).reshape(B, S, K, 2).astype(jnp.float32)
    k = (kv[..., :D] * s[..., 0:1]).astype(dtype)
    v = (kv[..., D:] * s[..., 1:2]).astype(dtype)
    return k, v


def _window_mask(key_pos, positions, window):
    """Sliding-window lower bound: key_pos > q_pos - window (no-op when
    window <= 0). ``window`` may be a traced i32 scalar (per-layer value
    inside the layer scan)."""
    if window is None:
        return jnp.bool_(True)
    window = jnp.asarray(window, jnp.int32)
    return jnp.where(
        window > 0, key_pos > positions[:, :, None] - window, True
    )


def paged_attention_xla_blocked(
    q: jax.Array,  # [B, Q, H, D]
    kv_cache: jax.Array,  # [num_pages, K, page, 2D]
    page_table: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B]
    positions: jax.Array,  # [B, Q]
    sm_scale: float | None = None,
    block_pages: int = 32,
    window=None,  # i32 scalar (0/None = full attention)
    sinks=None,   # [H] per-q-head virtual-key logits (gpt-oss)
    scales=None,  # [num_pages, K, page, 2] f32: int8-pool row scales
    sel=None,     # [B, Q, S] bool: keys each query may read (sparse attention)
) -> jax.Array:
    """Flash-style blocked paged attention in plain XLA.

    The dense path materializes [B, Q, K, G, S] scores — at 16k context
    with an 8k prefill chunk that is a ~100GB tensor. This version scans
    page blocks with an online-softmax carry (m, l, acc), so peak memory
    is O(B * Q * block) regardless of context length. Used for long
    contexts; the dense path remains the small-shape oracle.
    """
    B, Q, H, D = q.shape
    num_pages, K, page, D2 = kv_cache.shape
    max_pages = page_table.shape[1]
    if sm_scale is None:
        sm_scale = D**-0.5
    if max_pages % block_pages:
        pad = block_pages - max_pages % block_pages
        # repeat last page id: masked out by kv_lens anyway
        page_table = jnp.concatenate(
            [page_table, jnp.repeat(page_table[:, -1:], pad, axis=1)], axis=1
        )
        max_pages += pad
        if sel is not None:
            sel = jnp.pad(sel, ((0, 0), (0, 0), (0, pad * page)))
    n_blocks = max_pages // block_pages
    Sb = block_pages * page
    G = H // K
    qg = q.reshape(B, Q, K, G, D)

    def body(carry, blk):
        m, l, acc = carry
        pt_blk = jax.lax.dynamic_slice_in_dim(
            page_table, blk * block_pages, block_pages, axis=1
        )  # [B, bp]
        kv = kv_cache[pt_blk]  # [B, bp, K, page, 2D]
        if scales is not None:
            k, v = _dequant_gathered(kv, scales, pt_blk, D, q.dtype)
        else:
            kv = kv.transpose(0, 1, 3, 2, 4).reshape(B, Sb, K, D2)
            k = kv[..., :D]
            v = kv[..., D:]
        s = (
            jnp.einsum(
                "bqkgd,bskd->bqkgs", qg, k, preferred_element_type=jnp.float32
            )
            * sm_scale
        )  # [B, Q, K, G, Sb]
        key_pos = blk * Sb + jnp.arange(Sb)[None, None, :]
        causal = key_pos <= positions[:, :, None]
        in_ctx = key_pos < kv_lens[:, None, None]
        mask = causal & in_ctx & _window_mask(key_pos, positions, window)
        if sel is not None:
            mask &= jax.lax.dynamic_slice_in_dim(sel, blk * Sb, Sb, axis=2)
        mask = mask[:, :, None, None, :]
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # [B, Q, K, G]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        # fully-masked rows: m_new stays -1e30, p rows ~e^0=1 — zero them
        p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bqkgs,bskd->bqkgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Q, K, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Q, K, G), jnp.float32)
    acc0 = jnp.zeros((B, Q, K, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), jnp.arange(n_blocks, dtype=jnp.int32)
    )
    if sinks is not None:
        # The sink is one more (value-less) key: fold exp(sink) into the
        # softmax denominator, rescaled into the online-softmax's running
        # max frame (exactly HF's concat-then-drop formulation).
        sk = sinks.astype(jnp.float32).reshape(K, G)[None, None, :, :]
        m2 = jnp.maximum(m, sk)
        l = l * jnp.exp(m - m2) + jnp.exp(sk - m2)
        acc = acc * jnp.exp(m - m2)[..., None]
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]
    return out.reshape(B, Q, H, D).astype(q.dtype)


def paged_attention_xla(
    q: jax.Array,  # [B, Q, H, D]
    kv_cache: jax.Array,  # [num_pages, K, page, 2D]
    page_table: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B]
    positions: jax.Array,  # [B, Q]
    sm_scale: float | None = None,
    window=None,  # i32 scalar (0/None = full attention)
    sinks=None,   # [H] per-q-head virtual-key logits (gpt-oss)
    scales=None,  # [num_pages, K, page, 2] f32: int8-pool row scales
    sel=None,     # [B, Q, S] bool: keys each query may read (sparse attention)
) -> jax.Array:
    """Reference paged attention: gather the whole context, masked softmax."""
    B, Q, H, D = q.shape
    num_pages, K, page, D2 = kv_cache.shape
    max_pages = page_table.shape[1]
    S = max_pages * page
    if sm_scale is None:
        sm_scale = D ** -0.5

    kv = kv_cache[page_table]  # [B, max_pages, K, page, 2D]
    if scales is not None:
        k, v = _dequant_gathered(kv, scales, page_table, D, q.dtype)
    else:
        kv = kv.transpose(0, 1, 3, 2, 4).reshape(B, S, K, D2)
        k = kv[..., :D]
        v = kv[..., D:]

    group = H // K
    qg = q.reshape(B, Q, K, group, D)
    # Accumulate scores in f32 on the MXU while streaming bf16 operands.
    scores = (
        jnp.einsum("bqkgd,bskd->bqkgs", qg, k, preferred_element_type=jnp.float32)
        * sm_scale
    )

    key_pos = jnp.arange(S)[None, None, :]  # [1,1,S]
    causal = key_pos <= positions[:, :, None]  # [B,Q,S]
    in_ctx = key_pos < kv_lens[:, None, None]  # [B,1,S]
    mask = causal & in_ctx & _window_mask(key_pos, positions, window)
    if sel is not None:
        mask &= sel
    mask = mask[:, :, None, None, :]  # [B,Q,1,1,S]
    scores = jnp.where(mask, scores, -1e30)
    if sinks is not None:
        # gpt-oss attention sinks: append the per-head sink logit as an
        # extra (always-unmasked) column, softmax, then drop it — the
        # sink only absorbs probability mass (HF eager_attention_forward).
        sk = jnp.broadcast_to(
            sinks.astype(scores.dtype).reshape(K, group)[None, None, :, :, None],
            (B, Q, K, group, 1),
        )
        probs = jax.nn.softmax(
            jnp.concatenate([scores, sk], axis=-1), axis=-1
        )[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bqkgs,bskd->bqkgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Q, H, D).astype(q.dtype)
