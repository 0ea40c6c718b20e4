"""Pallas TPU paged attention (decode path).

TPU-native replacement for the reference's FlashInfer decode kernels
(SURVEY.md N8; reference docker/Dockerfile.cuda:71-72). The XLA fallback in
``paged_attention.py`` materializes the full padded context per layer; this
kernel streams only the LIVE context pages HBM->VMEM (double-buffered manual
DMAs, dynamic trip count = cdiv(kv_len, page)) and keeps a flash-style
online-softmax accumulator in VMEM. pages_per_block=16 measured ~2% faster
than 8 at short contexts (fewer loop trips) and keeps the per-slot VMEM
buffer around 1MB for GQA geometries.

Layout: kv_cache [num_pages, K, page, 2D] -- one page is a contiguous
[K, page, 2D] slab, fetched in a single DMA per loop iteration. Grid is
(B,): each program handles one sequence, looping its pages while the next
page's DMA is in flight; all KV heads are processed per iteration as a
K-batched MXU matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] i32 layer index (full-cache variant; [0] otherwise)
    # [rows_ref [T] i32 when row_lookup: the flattened-token layout's
    # token -> page-table-row map — the row-lookup prologue that lets
    # the grid iterate TOKENS against a compact [R, max_pages] table]
    *refs,
    page_size: int,
    head_dim: int,
    sm_scale: float,
    pages_per_block: int,
    has_sinks: bool,
    quant: bool,
    row_lookup: bool = False,
    select: bool = False,
):
    # remaining scalar prefetch:
    #   page_table_ref  [B|R, max_pages] i32
    #   kv_lens_ref     [B] i32 (per token when row_lookup: position + 1,
    #                   the causal mask derived from cu_q_lens)
    #   win_starts_ref  [B] i32 first attended position (sliding; 0=full)
    # blocks: q_ref, sinks_ref, kv_hbm_full_ref, [ks_ref, vs_ref when
    # quant: [1, K, S_max] f32 per-row scales, gathered into lane-aligned
    # form by XLA in _decode_call — Mosaic manual DMA requires a
    # 128-aligned minor dim, which a page's [K, page, 2] scale slab (2
    # lanes) can never satisfy, so the scales cannot ride per-page DMAs
    # like the data], out_ref — see _decode_call
    if row_lookup:
        rows_ref, *refs = refs
    page_table_ref, kv_lens_ref, win_starts_ref, *refs = refs
    q_ref, sinks_ref, kv_hbm_full_ref, *refs = refs
    if quant:
        ks_ref, vs_ref, *refs = refs
    if select:
        # sel_ref [1, 1, S_max] f32: 1.0 where this token may read the key
        # (learned sparse attention's selection), lane-aligned like the
        # scale planes above.
        sel_ref, *refs = refs
    out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    # Row-lookup prologue: program b handles TOKEN b; its pages live in
    # the compact table's row rows_ref[b]. kv_lens/win_starts stay
    # per-program (per token).
    tr = rows_ref[b] if row_lookup else b
    kv_hbm_ref = (
        kv_hbm_full_ref.at[layer_ref[0]]
        if len(kv_hbm_full_ref.shape) == 5
        else kv_hbm_full_ref
    )
    D = head_dim
    K = q_ref.shape[1]
    ppb = pages_per_block
    S = ppb * page_size  # tokens per compute block
    kv_len = kv_lens_ref[b]
    win_start = win_starts_ref[b]  # first position this query may attend
    n_blocks = (kv_len + S - 1) // S
    blk_lo = win_start // S  # blocks fully before the window are skipped

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    n_live_pages = (kv_len + page_size - 1) // page_size
    first_live_page = win_start // page_size

    def body(buf, sem):
        # buf: [2, K, S, 2D]; one DMA per page, ppb in flight per block.
        # Pages past the live context (tail block) — or wholly before the
        # sliding window — are never fetched.
        def _dma(slot, i, j):
            return pltpu.make_async_copy(
                kv_hbm_ref.at[page_table_ref[tr, i * ppb + j]],
                buf.at[slot, :, pl.ds(j * page_size, page_size), :],
                sem.at[slot, j],
            )

        def _page_live(i, j):
            p = i * ppb + j
            return jnp.logical_and(p < n_live_pages, p >= first_live_page)

        def start_block(slot, i):
            for j in range(ppb):  # static unroll

                @pl.when(_page_live(i, j))
                def _start():
                    _dma(slot, i, j).start()

        def wait_block(slot, i):
            for j in range(ppb):

                @pl.when(_page_live(i, j))
                def _wait():
                    _dma(slot, i, j).wait()

        @pl.when(n_blocks > blk_lo)
        def _warmup():
            start_block(jax.lax.rem(blk_lo, 2), blk_lo)

        def loop(i, _):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_block(jax.lax.rem(i + 1, 2), i + 1)

            wait_block(slot, i)
            kv = buf[slot]  # [K, S, 2D]
            k = kv[:, :, :D]
            v = kv[:, :, D:].astype(jnp.float32)
            q = q_ref[0]  # [K, G, D]
            ks = vs = None
            if quant:
                # Scales ride as f32, the pool's own dtype: Mosaic has
                # no f16 vector type on TPU, so an f16 plane (half the
                # bytes) is refused by the chip's compiler.
                ks = ks_ref[0, :, pl.ds(i * S, S)]
                vs = vs_ref[0, :, pl.ds(i * S, S)]
                k = k.astype(q.dtype)  # i8 -> exact in bf16/f32
            # Unfetched positions (tail past kv_len, or pages before the
            # window) hold uninitialized VMEM; zero them so a stray NaN
            # can't poison the (0-prob x v) accumulation.
            pos_v = i * S + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            live_v = jnp.logical_and(pos_v < kv_len, pos_v >= win_start)
            v = jnp.where(live_v, v, 0.0)
            # K-batched (G, D) x (D, S) -> [K, G, S], f32 accumulate.
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if quant:
                # Row dequantization, factored around the matmuls on the
                # small [K, G, S] plane: (q . k_i8) * ks == q . (k_i8 *
                # ks); (probs * vs) . v_i8 == probs . (v_i8 * vs) — the
                # [K, S, D] value plane is never touched by scales.
                # Dead-column scale values die in the live mask below
                # (jnp.where does not propagate the unselected arm).
                s = s * ks[:, None, :]
            pos = i * S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            live = jnp.logical_and(pos < kv_len, pos >= win_start)
            if select:
                chosen = sel_ref[0, :, pl.ds(i * S, S)] > 0.5  # [1, S]
                live = jnp.logical_and(live, chosen[:, None, :])
            s = jnp.where(live, s, NEG_INF)

            m_prev = m_ref[:, :, :1]  # [K, G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s - m_new)  # [K, G, S]
            probs = jnp.where(live, probs, 0.0)
            l_ref[:, :, :1] = l_ref[:, :, :1] * alpha + jnp.sum(
                probs, axis=2, keepdims=True
            )
            m_ref[:, :, :1] = m_new
            # Dead-column vs values are DEFINED (the scale operand is a
            # fully-copied XLA gather, not a manual DMA) but may be a
            # pathological inf — 0-prob x inf = NaN, so re-mask after
            # the multiply.
            pv_probs = (
                probs if not quant
                else jnp.where(live, probs * vs[:, None, :], 0.0)
            )
            pv = jax.lax.dot_general(
                pv_probs, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [K, G, D]
            acc_ref[:] = acc_ref[:] * alpha + pv
            return 0

        jax.lax.fori_loop(blk_lo, n_blocks, loop, 0)

    pl.run_scoped(
        body,
        buf=pltpu.VMEM(
            (2, K, ppb * page_size, kv_hbm_ref.shape[-1]), kv_hbm_ref.dtype
        ),
        sem=pltpu.SemaphoreType.DMA((2, ppb)),
    )

    l = l_ref[:, :, :1]
    if has_sinks:
        # gpt-oss attention sink: one extra value-less key — fold
        # exp(sink) into the denominator, rescaled into the running-max
        # frame (exact concat-then-drop semantics).
        m = m_ref[:, :, :1]
        sk = sinks_ref[...][:, :, None]  # read the block, then broadcast
        m2 = jnp.maximum(m, sk)
        l = l * jnp.exp(m - m2) + jnp.exp(sk - m2)
        acc_ref[:] = acc_ref[:] * jnp.exp(m - m2)
    l = jnp.where(l == 0.0, 1.0, l)
    out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)


def _decode_call(
    q, kv_cache, layer, page_table, kv_lens, sm_scale, interpret,
    pages_per_block, window=None, sinks=None, scales=None,
):
    B, Q, H, D = q.shape
    assert Q == 1, "decode kernel handles Q=1"
    K, page, D2 = kv_cache.shape[-3], kv_cache.shape[-2], kv_cache.shape[-1]
    assert D2 == 2 * D
    G = H // K
    if sm_scale is None:
        sm_scale = D**-0.5
    max_pages = page_table.shape[1]
    if max_pages % pages_per_block:
        # pad the table so block index arithmetic never reads out of bounds
        pad = pages_per_block - max_pages % pages_per_block
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))

    qk = q.reshape(B, K, G, D)
    # Sliding window: the decode query sits at kv_len-1, so the first
    # attended position is max(0, kv_len - window). window may be a traced
    # per-layer scalar; window<=0 (or None) degrades to full attention.
    if window is None:
        win_starts = jnp.zeros_like(kv_lens)
    else:
        window = jnp.asarray(window, jnp.int32)
        win_starts = jnp.where(
            window > 0, jnp.maximum(kv_lens - window, 0), 0
        ).astype(jnp.int32)

    if sinks is None:
        sinks2d = jnp.zeros((K, G), jnp.float32)
    else:
        # q head h maps to (h // G, h % G) — same grouping as qk above.
        sinks2d = sinks.astype(jnp.float32).reshape(K, G)

    in_specs = [
        pl.BlockSpec((1, K, G, D), lambda b, l, pt, kl, ws: (b, 0, 0, 0)),
        pl.BlockSpec((K, G), lambda b, l, pt, kl, ws: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM; manual DMA
    ]
    operands = [qk, sinks2d, kv_cache]
    if scales is not None:
        # Per-row scales, gathered + relayouted to lane-aligned
        # [B, K, S_max] by XLA. A per-page scale DMA inside the kernel
        # (like the data pages) is structurally impossible: Mosaic
        # requires a 128-aligned minor dim on manual copies and a page's
        # scale slab is 2 lanes wide in every scatter-friendly layout —
        # measured anyway via a const-scales probe: this gather is NOT
        # the int8 decode cost (within noise of zero).
        lidx = jnp.asarray(layer, jnp.int32).reshape(-1)[0]
        sl = (
            jax.lax.dynamic_index_in_dim(scales, lidx, 0, keepdims=False)
            if scales.ndim == 5 else scales
        )  # [P, K, page, 2]
        mp = page_table.shape[1]
        # This plane scales with max_pages, not the live context: the
        # widest int8-only HBM stream in the decode step.
        g = sl[page_table]  # [B, mp, K, page, 2]
        ksvs = g.transpose(0, 2, 4, 1, 3).reshape(B, K, 2, mp * page)
        sspec = pl.BlockSpec(
            (1, K, mp * page), lambda b, l, pt, kl, ws: (b, 0, 0)
        )
        in_specs.extend([sspec, sspec])
        operands.extend([ksvs[:, :, 0], ksvs[:, :, 1]])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, K, G, D), lambda b, l, pt, kl, ws: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=page,
            head_dim=D,
            sm_scale=sm_scale,
            pages_per_block=pages_per_block,
            has_sinks=sinks is not None,
            quant=scales is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    out = kernel(
        layer.astype(jnp.int32).reshape(1), page_table, kv_lens, win_starts,
        *operands,
    )
    return out.reshape(B, 1, H, D)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret", "pages_per_block")
)
def decode_paged_attention(
    q: jax.Array,  # [B, 1, H, D]
    kv_cache: jax.Array,  # [num_pages, K, page, 2D]
    page_table: jax.Array,  # [B, max_pages] i32
    kv_lens: jax.Array,  # [B] i32
    sm_scale: float | None = None,
    interpret: bool = False,
    pages_per_block: int = 16,
    window: jax.Array | None = None,
    sinks: jax.Array | None = None,
    scales: jax.Array | None = None,  # [num_pages, K, page, 2]
) -> jax.Array:
    return _decode_call(
        q, kv_cache, jnp.zeros((1,), jnp.int32), page_table, kv_lens,
        sm_scale, interpret, pages_per_block, window=window, sinks=sinks,
        scales=scales,
    )


def flat_paged_attention_full(
    q: jax.Array,  # [T, 1, H, D] packed token-query stream
    kv_cache: jax.Array,  # [L, num_pages, K, page, 2D] (whole model)
    layer: jax.Array,  # scalar i32
    rows: jax.Array,  # [T] i32 token -> page-table row (cu_q_lens lookup)
    page_table: jax.Array,  # [R, max_pages] COMPACT per-row table
    kv_lens: jax.Array,  # [T] i32 per-token: position + 1 (causal-in-row)
    sm_scale: float | None = None,
    interpret: bool = False,
    pages_per_block: int = 16,
    window: jax.Array | None = None,
    sinks: jax.Array | None = None,
    scales: jax.Array | None = None,  # [L, num_pages, K, page, 2]
    sel: jax.Array | None = None,  # [T, S] bool: keys each token may read
) -> jax.Array:
    """Flattened-token (``cu_q_lens``) attention: the grid iterates the
    packed TOKEN stream — program t streams exactly the pages token t's
    row holds up to its own position (kv_len = pos + 1 IS the causal
    mask within the row) — against the compact per-row table through a
    scalar-prefetched row-lookup prologue, so no [T, max_pages]
    per-token table is ever materialized for the data DMAs. Pure decode
    rows cost ONE program; prefill-chunk tokens each stream their live
    prefix (write-before-read per layer makes same-step earlier tokens'
    fresh KV visible). ``sel`` (learned sparse attention) masks every
    key a token's indexer did not select, on top of the causal mask: the
    pass stays dense over the live pages, the result reads selected
    tokens only."""
    T, Q, H, D = q.shape
    assert Q == 1, "flat attention takes the packed [T, 1, H, D] stream"
    K, page, D2 = kv_cache.shape[-3], kv_cache.shape[-2], kv_cache.shape[-1]
    assert D2 == 2 * D
    G = H // K
    if sm_scale is None:
        sm_scale = D**-0.5
    max_pages = page_table.shape[1]
    if max_pages % pages_per_block:
        pad = pages_per_block - max_pages % pages_per_block
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))

    qk = q.reshape(T, K, G, D)
    if window is None:
        win_starts = jnp.zeros_like(kv_lens)
    else:
        window = jnp.asarray(window, jnp.int32)
        win_starts = jnp.where(
            window > 0, jnp.maximum(kv_lens - window, 0), 0
        ).astype(jnp.int32)
    if sinks is None:
        sinks2d = jnp.zeros((K, G), jnp.float32)
    else:
        sinks2d = sinks.astype(jnp.float32).reshape(K, G)

    # 5 scalar prefetch args: layer, rows, page_table, kv_lens, win_starts.
    in_specs = [
        pl.BlockSpec((1, K, G, D), lambda b, l, r, pt, kl, ws: (b, 0, 0, 0)),
        pl.BlockSpec((K, G), lambda b, l, r, pt, kl, ws: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM; manual DMA
    ]
    operands = [qk, sinks2d, kv_cache]
    if scales is not None:
        # Per-ROW scale plane (scales cannot ride the page DMAs — see
        # _decode_call): gathered ONCE per row ([R, K, mp*page] f32) and
        # indexed through the scalar-prefetched row map in the
        # BlockSpec, so a prefill chunk's tokens share one plane
        # instead of duplicating it chunk-length times into a
        # [T, max_pages, ...] intermediate.
        lidx = jnp.asarray(layer, jnp.int32).reshape(-1)[0]
        sl = (
            jax.lax.dynamic_index_in_dim(scales, lidx, 0, keepdims=False)
            if scales.ndim == 5 else scales
        )
        mp = page_table.shape[1]
        R = page_table.shape[0]
        g = sl[page_table]  # [R, mp, K, page, 2]
        ksvs = g.transpose(0, 2, 4, 1, 3).reshape(R, K, 2, mp * page)
        sspec = pl.BlockSpec(
            (1, K, mp * page), lambda b, l, r, pt, kl, ws: (r[b], 0, 0)
        )
        in_specs.extend([sspec, sspec])
        operands.extend([ksvs[:, :, 0], ksvs[:, :, 1]])
    if sel is not None:
        S_max = page_table.shape[1] * page
        selp = jnp.pad(
            sel.astype(jnp.float32), ((0, 0), (0, S_max - sel.shape[1]))
        )
        in_specs.append(pl.BlockSpec(
            (1, 1, S_max), lambda b, l, r, pt, kl, ws: (b, 0, 0)
        ))
        operands.append(selp[:, None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, K, G, D), lambda b, l, r, pt, kl, ws: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=page,
            head_dim=D,
            sm_scale=sm_scale,
            pages_per_block=pages_per_block,
            has_sinks=sinks is not None,
            quant=scales is not None,
            row_lookup=True,
            select=sel is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    out = kernel(
        jnp.asarray(layer, jnp.int32).reshape(1),
        rows.astype(jnp.int32),
        page_table,
        kv_lens,
        win_starts,
        *operands,
    )
    return out.reshape(T, 1, H, D)


def decode_paged_attention_full(
    q: jax.Array,  # [B, 1, H, D]
    kv_cache: jax.Array,  # [L, num_pages, K, page, 2D] (whole model)
    layer: jax.Array,  # scalar i32
    page_table: jax.Array,
    kv_lens: jax.Array,
    sm_scale: float | None = None,
    interpret: bool = False,
    pages_per_block: int = 16,
    window: jax.Array | None = None,
    sinks: jax.Array | None = None,
    scales: jax.Array | None = None,  # [L, num_pages, K, page, 2]
) -> jax.Array:
    """Layer-indexed variant: reads cache[layer] pages directly from the
    full-cache HBM ref — a scan over layers never materializes a
    pool-sized slice."""
    return _decode_call(
        q, kv_cache, layer, page_table, kv_lens, sm_scale, interpret,
        pages_per_block, window=window, sinks=sinks, scales=scales,
    )
