"""Context-parallel ring attention for chunked prefill.

The monolithic chunked-prefill attention computes every query row of a
chunk on every device (the batch axis is 1 for a long prompt, so the dp
axis idles). This op shards the CHUNK's query axis across the mesh "dp"
axis instead — each shard holds Q/cp query rows plus the matching slice
of the chunk's fresh K/V — and computes attention as a ring (Liu et al.,
Ring Attention):

  * every shard first accumulates online-softmax partials (flash-style
    m/l/acc) of its queries against the COMMITTED prefix in the paged
    pool (keys strictly below the chunk start — earlier chunks' pages),
    reading the same post-write cache the monolithic path reads so no
    pool copy materializes;
  * the chunk's fresh K/V blocks then rotate around the ring via
    ``jax.lax.ppermute`` (CollectivePermute over ICI) while each shard
    folds the visiting block into its partials;
  * blocks that originate on a HIGHER shard than the queries hold only
    future positions (the query axis is split contiguously), so the
    fold is skipped entirely — causal block skipping, ~half the ring
    work. The ppermute stays OUTSIDE the skip so every shard runs the
    identical collective sequence.

Numerics match the monolithic path to floating-point tolerance (the same
online-softmax recurrence over a different key partition); routing and
sampling downstream are byte-identical in practice. The fresh K/V
operands still CONTAIN pad rows (the pool write drops them via its OOB
scatter; here they are masked explicitly via ``valid``), and int8 pools
dequantize gathered prefix pages exactly like the blocked XLA fallback.

Geometry contract (validated by ParallelConfig): cp == mesh dp size,
Q % cp == 0, q heads divide tp, kv heads divide tp (or K == 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llmd_tpu.ops.paged_attention import _dequant_gathered, _window_mask

_NEG_INF = -1e30


def _online_update(m, l, acc, s, mask, v):
    """One flash-style block fold: s [B, Qs, K, G, S] masked scores,
    v [B, S, K, D] values; carry shapes match paged_attention_xla_blocked."""
    s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bqkgs,bskd->bqkgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    acc_new = acc * alpha[..., None] + pv
    return m_new, l_new, acc_new


def _prefix_partials(
    qg, kv_slice, scales, page_table, kv_lens, positions, chunk_start,
    sm_scale, window, block_pages,
):
    """Online-softmax partials of the local queries against the COMMITTED
    prefix (pool keys strictly below the chunk start). Blocked scan over
    page blocks — the same recurrence as ``paged_attention_xla_blocked``
    but returning the raw (m, l, acc) carry for the ring to extend."""
    B, Qs, K, G, D = qg.shape
    num_pages, Kc, page, D2 = kv_slice.shape
    max_pages = page_table.shape[1]
    if max_pages % block_pages:
        pad = block_pages - max_pages % block_pages
        page_table = jnp.concatenate(
            [page_table, jnp.repeat(page_table[:, -1:], pad, axis=1)], axis=1
        )
        max_pages += pad
    n_blocks = max_pages // block_pages
    Sb = block_pages * page

    def body(carry, blk):
        m, l, acc = carry
        pt_blk = jax.lax.dynamic_slice_in_dim(
            page_table, blk * block_pages, block_pages, axis=1
        )
        kv = kv_slice[pt_blk]  # [B, bp, K, page, 2D]
        if scales is not None:
            k, v = _dequant_gathered(kv, scales, pt_blk, D, qg.dtype)
        else:
            kv = kv.transpose(0, 1, 3, 2, 4).reshape(B, Sb, K, D2)
            k = kv[..., :D]
            v = kv[..., D:]
        s = (
            jnp.einsum(
                "bqkgd,bskd->bqkgs", qg, k,
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )
        key_pos = blk * Sb + jnp.arange(Sb)[None, None, :]
        # Prefix keys only: strictly below the chunk start (this step's
        # fresh writes live at key_pos >= chunk_start and arrive via the
        # ring instead — reading them here would double-count).
        prefix = key_pos < chunk_start[:, None, None]
        in_ctx = key_pos < kv_lens[:, None, None]
        mask = (
            prefix & in_ctx & _window_mask(key_pos, positions, window)
        )[:, :, None, None, :]
        return _online_update(m, l, acc, s, mask, v), None

    m0 = jnp.full((B, Qs, K, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Qs, K, G), jnp.float32)
    acc0 = jnp.zeros((B, Qs, K, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), jnp.arange(n_blocks, dtype=jnp.int32)
    )
    return m, l, acc


def ring_prefill_attention_full(
    q: jax.Array,        # [B, Q, H, D] post-RoPE queries
    kv_cache_full,       # [L, P, K, page, 2D] POST-write pool (or int8 tuple)
    layer,               # i32 scalar layer index
    k: jax.Array,        # [B, Q, K, D] this chunk's fresh keys (post-RoPE/rep)
    v: jax.Array,        # [B, Q, K, D] this chunk's fresh values
    page_table: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B] context end AFTER this chunk's writes
    positions: jax.Array,  # [B, Q]
    valid: jax.Array,    # [B, Q] bool — fresh rows include pad tokens
    sm_scale: float | None = None,
    mesh=None,
    cp: int = 1,
    window=None,         # i32 scalar (0/None = full attention)
    sinks=None,          # [H] per-q-head virtual-key logits
    block_pages: int = 32,
) -> jax.Array:
    """Ring-parallel chunked-prefill attention on the FULL [L, ...] cache.

    Reads the post-write pool for the committed prefix (masked to keys
    below the chunk start) and the rotating fresh K/V blocks for the
    chunk itself; the union covers exactly the monolithic path's
    ``key_pos <= position`` read set.
    """
    B, Q, H, D = q.shape
    if sm_scale is None:
        sm_scale = D**-0.5
    if cp <= 1 or mesh is None or Q % cp:
        # Degenerate geometry: the monolithic path IS the reference.
        from llmd_tpu.ops import paged_attention_full

        return paged_attention_full(
            q, kv_cache_full, layer, page_table, kv_lens, positions,
            sm_scale, world_size=1, mesh=None, window=window, sinks=sinks,
        )
    if isinstance(kv_cache_full, tuple):
        kv_cache_full, kv_scales = kv_cache_full
    else:
        kv_scales = None
    Kc = kv_cache_full.shape[2]
    sl = jax.lax.dynamic_index_in_dim(kv_cache_full, layer, 0, keepdims=False)
    ssl = (
        None if kv_scales is None
        else jax.lax.dynamic_index_in_dim(kv_scales, layer, 0, keepdims=False)
    )
    # Chunk start per row: the first query position. Computed on the
    # unsharded array — shard s > 0 never holds column 0.
    chunk_start = positions[:, 0]

    tp = mesh.shape["tp"]
    tp_k = "tp" if tp > 1 and Kc > 1 and Kc % tp == 0 else None
    win = jnp.zeros((), jnp.int32) if window is None else jnp.asarray(window, jnp.int32)
    use_win = window is not None
    sk = jnp.zeros((H,), jnp.float32) if sinks is None else sinks
    use_sinks = sinks is not None
    scale_spec = (P(None, tp_k, None, None),) if ssl is not None else ()
    scale_arg = (ssl,) if ssl is not None else ()
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def local(q, k, v, pos, val, sl, pt, kl, cs, win, sk, *sc):
        Bq, Qs, Hl, _ = q.shape
        Kl = k.shape[2]
        G = Hl // Kl
        qg = q.reshape(Bq, Qs, Kl, G, D)
        scales = sc[0] if sc else None
        my = jax.lax.axis_index("dp")

        # Prefix partials against the committed pool pages (overlappable
        # with the ring steps: no data dependency between the two).
        m, l, acc = _prefix_partials(
            qg, sl, scales, pt, kl, pos, cs, sm_scale,
            win if use_win else None, block_pages,
        )

        kb, vb, pb, ab = k, v, pos, val
        for t in range(cp):
            src = (my - t) % cp

            def attend(carry, kb=kb, vb=vb, pb=pb, ab=ab):
                m, l, acc = carry
                s = (
                    jnp.einsum(
                        "bqkgd,bskd->bqkgs", qg, kb,
                        preferred_element_type=jnp.float32,
                    )
                    * sm_scale
                )
                key_pos = pb[:, None, :]  # [B, 1, Qs]
                mask = (
                    (key_pos <= pos[:, :, None])
                    & ab[:, None, :]
                    & _window_mask(key_pos, pos, win if use_win else None)
                )[:, :, None, None, :]
                return _online_update(m, l, acc, s, mask, vb)

            # Causal block skipping: blocks from a higher-origin shard
            # hold only future positions (contiguous query split) — the
            # whole fold is skipped, ~halving the ring's work. The
            # rotation below stays OUTSIDE the cond: every shard must
            # run the identical collective sequence.
            m, l, acc = jax.lax.cond(
                src <= my, attend, lambda c: c, (m, l, acc)
            )
            if t < cp - 1:
                kb = jax.lax.ppermute(kb, "dp", perm)
                vb = jax.lax.ppermute(vb, "dp", perm)
                pb = jax.lax.ppermute(pb, "dp", perm)
                ab = jax.lax.ppermute(ab, "dp", perm)

        if use_sinks:
            skg = sk.astype(jnp.float32).reshape(Kl, G)[None, None, :, :]
            m2 = jnp.maximum(m, skg)
            l = l * jnp.exp(m - m2) + jnp.exp(skg - m2)
            acc = acc * jnp.exp(m - m2)[..., None]
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l[..., None]
        return out.reshape(Bq, Qs, Hl, D).astype(q.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(
            P(None, "dp", "tp", None),   # q: chunk rows over dp, heads over tp
            P(None, "dp", tp_k, None),   # fresh k
            P(None, "dp", tp_k, None),   # fresh v
            P(None, "dp"),               # positions
            P(None, "dp"),               # valid
            P(None, tp_k, None, None),   # pool layer slice (dp-replicated)
            P(None, None),               # page table
            P(None),                     # kv_lens
            P(None),                     # chunk_start
            P(),                         # window
            P("tp"),                     # sinks (per-q-head)
            *scale_spec,
        ),
        out_specs=P(None, "dp", "tp", None),
        check_vma=False,
    )(q, k, v, positions, valid, sl, page_table, kv_lens, chunk_start,
      win, sk, *scale_arg)
