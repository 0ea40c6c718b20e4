"""Pallas TPU paged attention (decode path and the flat token stream).

TPU-native replacement for the reference's FlashInfer decode kernels
(SURVEY.md N8; reference docker/Dockerfile.cuda:71-72). The XLA fallback in
``paged_attention.py`` materializes the full padded context per layer; these
kernels stream only the LIVE context pages HBM->VMEM (double-buffered manual
DMAs, dynamic trip count = cdiv(kv_len, page)) and keep a flash-style
online-softmax accumulator in VMEM. pages_per_block=16 measured ~2% faster
than 8 at short contexts (fewer loop trips) and keeps the per-slot VMEM
buffer around 1MB for GQA geometries.

Layout: kv_cache [num_pages, K, page, 2D] -- one page is a contiguous
[K, page, 2D] slab, fetched in a single DMA per page; all KV heads are
processed per compute block as a K-batched MXU matmul while the next
block's pages are in flight.

Two grids over the same block stream (``_stream_blocks``) and the same
online-softmax step (``_attend_block``):
- ``_decode_kernel``, grid (B,): one program a sequence (the bucketed
  [B, 1] decode), each streaming its own live pages;
- ``_flat_tile_kernel``, grid (T / 16,): one program per 16-token tile of
  the flat stream, with or without a sliding window: the blocks some of
  its queries share, read once for all 16 as one operand, then each
  token's rest. A tile inside ONE page-table row (a prefill chunk's body)
  is all shared: the row's pages once, from its first token's window start
  to its last token's horizon. Decode rows of DIFFERENT rows that hold the
  same physical pages at their heads (sessions over one cached document)
  share those: a SHARED-PREFIX RUN, planned by the host from the step's
  page table (``engine/prefix_runs.py``) for calls without a window, read
  once a tile off its leader's row; each member then streams only what is
  its own. Any other token goes alone, as a grid of tokens would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30
TILE = 16  # the flat stream's granule: ``flat_t_buckets`` pads T to it
PAGES_PER_BLOCK = 16  # pages a compute block, where the caller names none


def _stream_blocks(
    kv_hbm_ref, page_table_ref, buf, sem, tr, blk_lo, n_blocks,
    first_live_page, n_live_pages, ppb, page_size, compute, planes=(),
):
    """Stream compute blocks ``[blk_lo, n_blocks)`` of page-table row
    ``tr`` through the double buffer ``buf`` [2, K, S, 2D] and call
    ``compute(slot, i)`` on each while the next one's pages are in
    flight: one DMA a page, ppb in flight a block. Pages past the live
    context (tail block) — or wholly before the sliding window — are
    never fetched. ``planes`` are (hbm [R, K, S_max], vmem [2, K, S],
    sem [2]) triples whose block-wide slab rides along (int8 scales)."""
    S = ppb * page_size

    def block(slot, i, start):
        def page(p, j):  # page p of the row -> page slot j of the block
            off = j * page_size
            if not isinstance(j, int):
                off = pl.multiple_of(off, page_size)
            dma = pltpu.make_async_copy(
                kv_hbm_ref.at[page_table_ref[tr, p]],
                buf.at[slot, :, pl.ds(off, page_size), :],
                sem.at[slot],
            )
            dma.start() if start else dma.wait()

        lo = jnp.maximum(first_live_page, i * ppb)
        hi = jnp.minimum(n_live_pages, (i + 1) * ppb)
        whole = hi - lo == ppb

        # A block that is live throughout (all but a context's last one
        # and a window's first) issues its ppb copies unrolled and
        # unguarded and waits for them at once: the slot's semaphore
        # counts bytes, and a (never started) copy the size of the slot
        # waits for all of them. A partial block loops over its live
        # pages on the scalar core. (A guard on every page costs a branch
        # a page to run, in a pass that is bound by what the scalar core
        # issues, and a traced conditional a page: most of what a warm
        # bucket's first call spends on this kernel.)
        @pl.when(whole)
        def _whole():
            if start:
                for j in range(ppb):
                    page(i * ppb + j, j)
            else:
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sem.at[slot]
                ).wait()

        @pl.when(jnp.logical_not(whole))
        def _partial():
            def one(p, _):
                page(p, p - i * ppb)
                return 0

            jax.lax.fori_loop(lo, hi, one, 0)

        for hbm, vmem, psem in planes:
            dma = pltpu.make_async_copy(
                hbm.at[tr, :, pl.ds(i * S, S)], vmem.at[slot], psem.at[slot]
            )
            dma.start() if start else dma.wait()

    def loop(i, _):
        # From blk_lo - 1: the first trip only starts the first block.
        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            block(jax.lax.rem(i + 1, 2), i + 1, start=True)

        @pl.when(i >= blk_lo)
        def _block():
            slot = jax.lax.rem(i, 2)
            block(slot, i, start=False)
            compute(slot, i)

        return 0

    jax.lax.fori_loop(blk_lo - 1, n_blocks, loop, 0)


def _reset(m_ref, l_ref, acc_ref, M):
    m_ref[:, :M] = jnp.full((m_ref.shape[0], M, m_ref.shape[2]), NEG_INF)
    l_ref[:, :M] = jnp.zeros((l_ref.shape[0], M, l_ref.shape[2]))
    acc_ref[:, :M] = jnp.zeros((acc_ref.shape[0], M, acc_ref.shape[2]))


def _attend_block(
    q, kv, i, m_ref, l_ref, acc_ref, *, head_dim, sm_scale, key_end,
    kv_len, key_start=0, win_start=0, ks=None, vs=None, chosen=None,
):
    """One online-softmax step: the M query rows ``q`` [K, M, D] against
    the S keys of compute block ``i`` in ``kv`` [K, S, 2D]; running max,
    sum and f32 accumulator in the first M rows of the scratch refs.
    ``kv_len`` bounds each query row above and ``win_start`` (a sliding
    window's first position) below: scalars, or [1, M, 1] where the rows
    are a tile's tokens, each with its own. Keys before ``key_start``'s
    page or past ``key_end`` (the nearest and furthest) were never fetched.
    ``ks``/``vs`` [K, S] are int8 row scales, ``chosen`` (broadcastable
    to [K, M, S]) the learned-sparse selection."""
    D = head_dim
    M, S = q.shape[1], kv.shape[1]
    k = kv[:, :, :D]
    v = kv[:, :, D:].astype(jnp.float32)
    if ks is not None:
        k = k.astype(q.dtype)  # i8 -> exact in bf16/f32
    # Unfetched positions (tail past the context, or pages before the
    # window) hold uninitialized VMEM; zero them so a stray NaN
    # can't poison the (0-prob x v) accumulation.
    pos_v = i * S + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    live_v = jnp.logical_and(pos_v < key_end, pos_v >= key_start)
    v = jnp.where(live_v, v, 0.0)
    # K-batched (M, D) x (D, S) -> [K, M, S], f32 accumulate.
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    if ks is not None:
        # Row dequantization, factored around the matmuls on the
        # small [K, M, S] plane: (q . k_i8) * ks == q . (k_i8 *
        # ks); (probs * vs) . v_i8 == probs . (v_i8 * vs) — the
        # [K, S, D] value plane is never touched by scales.
        # Dead-column scale values die in the live mask below
        # (jnp.where does not propagate the unselected arm).
        s = s * ks[:, None, :]
    pos = i * S + jax.lax.broadcasted_iota(jnp.int32, (1, M, S), 2)
    live = jnp.logical_and(pos < kv_len, pos >= win_start)
    if chosen is not None:
        live = jnp.logical_and(live, chosen)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[:, :M, :1]  # [K, M, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(s - m_new)  # [K, M, S]
    probs = jnp.where(live, probs, 0.0)
    l_ref[:, :M, :1] = l_ref[:, :M, :1] * alpha + jnp.sum(
        probs, axis=2, keepdims=True
    )
    m_ref[:, :M, :1] = m_new
    if vs is not None:
        # Dead-column vs values are DEFINED (the scale operand is a
        # whole copy, never a partial one) but may be a pathological
        # inf — 0-prob x inf = NaN, so re-mask after the multiply.
        probs = jnp.where(live, probs * vs[:, None, :], 0.0)
    pv = jax.lax.dot_general(
        probs, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [K, M, D]
    acc_ref[:, :M] = acc_ref[:, :M] * alpha + pv


def _normalized(m_ref, l_ref, acc_ref, M, sinks=None):
    """The accumulated [K, M, D] over its softmax denominator; ``sinks``
    [K, M] folds one extra value-less key per head into it."""
    l = l_ref[:, :M, :1]
    acc = acc_ref[:, :M]
    if sinks is not None:
        # gpt-oss attention sink: fold exp(sink) into the denominator,
        # rescaled into the running-max frame (exact concat-then-drop
        # semantics).
        m = m_ref[:, :M, :1]
        sk = sinks[:, :, None]
        m2 = jnp.maximum(m, sk)
        l = l * jnp.exp(m - m2) + jnp.exp(sk - m2)
        acc = acc * jnp.exp(m - m2)
    return acc / jnp.where(l == 0.0, 1.0, l)


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] i32 layer index (full-cache variant; [0] otherwise)
    *refs,
    page_size: int,
    head_dim: int,
    sm_scale: float,
    pages_per_block: int,
    has_sinks: bool,
    quant: bool,
):
    # remaining scalar prefetch:
    #   page_table_ref  [B, max_pages] i32
    #   kv_lens_ref     [B] i32
    #   win_starts_ref  [B] i32 first attended position (sliding; 0=full)
    # blocks: q_ref, sinks_ref, kv_hbm_full_ref, [ks_ref, vs_ref when
    # quant: [1, K, S_max] f32 per-row scales, gathered into lane-aligned
    # form by XLA in _decode_call — Mosaic manual DMA requires a
    # 128-aligned minor dim, which a page's [K, page, 2] scale slab (2
    # lanes) can never satisfy, so the scales cannot ride per-page DMAs
    # like the data], out_ref — see _decode_call
    page_table_ref, kv_lens_ref, win_starts_ref, *refs = refs
    q_ref, sinks_ref, kv_hbm_full_ref, *refs = refs
    if quant:
        ks_ref, vs_ref, *refs = refs
    out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    kv_hbm_ref = (
        kv_hbm_full_ref.at[layer_ref[0]]
        if len(kv_hbm_full_ref.shape) == 5
        else kv_hbm_full_ref
    )
    K, G = q_ref.shape[1], q_ref.shape[2]
    ppb = pages_per_block
    S = ppb * page_size  # tokens per compute block
    kv_len = kv_lens_ref[b]
    win_start = win_starts_ref[b]  # first position this query may attend
    _reset(m_ref, l_ref, acc_ref, G)

    def body(buf, sem):
        def compute(slot, i):
            # Scales ride as f32, the pool's own dtype: Mosaic has no f16
            # vector type on TPU, so an f16 plane (half the bytes) is
            # refused by the chip's compiler.
            _attend_block(
                q_ref[0], buf[slot], i, m_ref, l_ref, acc_ref,
                head_dim=head_dim, sm_scale=sm_scale, key_end=kv_len,
                kv_len=kv_len, key_start=win_start, win_start=win_start,
                ks=ks_ref[0, :, pl.ds(i * S, S)] if quant else None,
                vs=vs_ref[0, :, pl.ds(i * S, S)] if quant else None,
            )

        _stream_blocks(
            kv_hbm_ref, page_table_ref, buf, sem, b,
            win_start // S,  # blocks fully before the window are skipped
            (kv_len + S - 1) // S, win_start // page_size,
            (kv_len + page_size - 1) // page_size, ppb, page_size, compute,
        )

    pl.run_scoped(
        body,
        buf=pltpu.VMEM((2, K, S, kv_hbm_ref.shape[-1]), kv_hbm_ref.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)),
    )
    out_ref[0] = _normalized(
        m_ref, l_ref, acc_ref, G, sinks_ref[...] if has_sinks else None
    ).astype(out_ref.dtype)


def _flat_tile_kernel(
    # scalar prefetch
    layer_ref,  # [1] i32 layer index
    rows_ref,  # [T] i32 token -> page-table row
    page_table_ref,  # [R, max_pages] i32
    kv_lens_ref,  # [T] i32 per token: position + 1
    # [win_starts_ref [T] i32 when windowed: per token its window's first
    # position, 0 where the layer's window is <= 0]
    # [run_lead_ref, run_blocks_ref [T] i32 when runs: a token of a
    # shared-prefix run carries the run's leading compute blocks and the
    # place in its tile of the run's leader; any other token 0 blocks]
    *refs,
    page_size: int,
    head_dim: int,
    sm_scale: float,
    pages_per_block: int,
    has_sinks: bool,
    quant: bool,
    select: bool,
    windowed: bool,
    runs: bool,
    num_tokens: int,
):
    """One program per TILE consecutive stream tokens: the blocks some of
    its queries share, then each token's rest. Both ride the same block
    stream and the same online-softmax step, and a query row meets its
    keys in the order a pass of its own would bring them.

    A SHARED pass streams one page-table row's blocks once for all of the
    tile's queries as one [K, TILE*G, D] operand, each query row under its
    own bounds. A tile whose tokens all sit in one row at consecutive
    positions (the body of a prefill chunk's sub-row) is one such pass
    and nothing else: from its first token's window start (0 without a
    window) to its last token's horizon. A tile of a call with ``runs``
    takes one pass per shared-prefix run (decode rows of DIFFERENT
    page-table rows whose leading pages are the same physical ids, found
    by the host): the run's leading blocks off its leader's row, its
    members' query rows live and every other row dead (a masked block
    leaves a dead row's running max, sum and accumulator exactly as they
    were). Then every token streams what is left of its own row, from the
    state the shared passes left it. A tile with neither (decode rows that
    share nothing, a chunk's ragged head or tail, a sub-row's seam, verify
    rows, pad tokens, a shard's short last tile) goes token by token from
    nothing. One-row tiles are read off ``rows``/``kv_lens``."""
    # blocks: q_ref [TILE, K, G, D], sinks_ref [K, TILE*G] (the heads'
    # sinks, once per token of a tile), kv_hbm_full_ref, [ks_hbm_ref,
    # vs_hbm_ref when quant: [R, K, S_max] f32 per-ROW scale planes left
    # in HBM: a block's [K, S] slab rides beside its pages], [sel_ref
    # when select: [TILE, S_max] f32, 1.0 where the token may read the
    # key], out_ref [TILE, K, G, D]; scratch m/l [K, TILE*G, 128], acc
    # [K, TILE*G, D], q16_ref [K, TILE*G, D], [when runs, a token's state
    # by its place in the tile: tm/tl [TILE, K, G, 128], tacc [TILE, K,
    # G, D]].
    win_starts_ref = run_lead_ref = run_blocks_ref = None
    if windowed:
        win_starts_ref, *refs = refs
    if runs:
        run_lead_ref, run_blocks_ref, *refs = refs
    q_ref, sinks_ref, kv_hbm_full_ref, *refs = refs
    ks_hbm_ref = vs_hbm_ref = sel_ref = None
    if quant:
        ks_hbm_ref, vs_hbm_ref, *refs = refs
    if select:
        sel_ref, *refs = refs
    out_ref, m_ref, l_ref, acc_ref, q16_ref, *tok_state = refs
    kv_hbm_ref = kv_hbm_full_ref.at[layer_ref[0]]
    K, G = q_ref.shape[1], q_ref.shape[2]
    M = TILE * G
    ppb = pages_per_block
    S = ppb * page_size
    t0 = pl.program_id(0) * TILE
    whole = num_tokens % TILE == 0  # static: only a dp shard's T is not
    n_tok = TILE if whole else jnp.minimum(TILE, num_tokens - t0)
    row0, kvl0 = rows_ref[t0], kv_lens_ref[t0]

    def at(j):
        return t0 + j if whole else jnp.minimum(t0 + j, num_tokens - 1)

    def same_row_next_position(j, carry):
        one_row, any_run = carry
        t = at(j)
        one_row = jnp.logical_and(one_row, jnp.logical_and(
            rows_ref[t] == row0, kv_lens_ref[t] == kvl0 + j
        ))
        if runs:
            any_run = jnp.logical_or(any_run, run_blocks_ref[t] > 0)
        return one_row, any_run

    one_row, any_run = jax.lax.fori_loop(
        1, TILE, same_row_next_position, (
            jnp.asarray(True) if whole else n_tok == TILE,
            run_blocks_ref[t0] > 0 if runs else jnp.asarray(False),
        ),
    )

    def body(buf, sem, *scale_bufs):
        planes = ()
        if quant:
            ks_buf, vs_buf, ssem = scale_bufs
            planes = (
                (ks_hbm_ref, ks_buf, ssem.at[0]),
                (vs_hbm_ref, vs_buf, ssem.at[1]),
            )

        def attend(state, tr, q, key_start, key_end, kv_len, win_start, chosen):
            """Row ``tr``'s keys [key_start, key_end) against the query rows
            that ``q()`` loads ([K, M, D]), each in [win_start, kv_len)."""
            def compute(slot, i):
                _attend_block(
                    q(), buf[slot], i, *state,
                    head_dim=head_dim, sm_scale=sm_scale, key_end=key_end,
                    kv_len=kv_len, key_start=key_start, win_start=win_start,
                    ks=ks_buf[slot] if quant else None,
                    vs=vs_buf[slot] if quant else None,
                    chosen=chosen(i) if select else None,
                )

            _stream_blocks(
                kv_hbm_ref, page_table_ref, buf, sem, tr, key_start // S,
                (key_end + S - 1) // S, key_start // page_size,
                (key_end + page_size - 1) // page_size, ppb, page_size,
                compute, planes,
            )

        sinks = sinks_ref[...] if has_sinks else None
        tile_state = (m_ref, l_ref, acc_ref)

        @pl.when(jnp.logical_or(one_row, any_run))
        def _shared_passes():
            # Query row r is head r % G of token r // G.
            q16_ref[...] = jnp.concatenate(
                [q_ref[j] for j in range(TILE)], axis=1
            )
            tok_of = jax.lax.broadcasted_iota(jnp.int32, (1, M, 1), 1) // G
            ws0 = win = 0
            if windowed:
                # Consecutive positions under one window: each start is
                # the last token's less their distance, floored at 0.
                ws0 = win_starts_ref[t0]
                win = jnp.maximum(
                    win_starts_ref[t0 + (TILE - 1)] - (TILE - 1) + tok_of, 0
                )
            lead_of = None
            if runs:
                # Each query row's run, by its leader's place in the tile
                # (-1: the row's token is in none).
                lead_of = jnp.full((1, M, 1), -1, jnp.int32)
                for j in range(TILE):
                    t = at(j)
                    lead = jnp.where(
                        run_blocks_ref[t] > 0, run_lead_ref[t], -1
                    )
                    if not whole:
                        lead = jnp.where(j < n_tok, lead, -1)
                    lead_of = jnp.where(tok_of == j, lead, lead_of)
            _reset(*tile_state, M)

            def shared_pass(u, _):
                """The blocks the tile's queries share with token ``u``,
                off its row: all of a one-row tile's, a run's if ``u``
                leads one."""
                key_end, leads = kvl0 + (TILE - 1), one_row
                if runs:
                    t = at(u)
                    run_end = run_blocks_ref[t] * S
                    leads = jnp.logical_or(one_row, jnp.logical_and(
                        run_end > 0, run_lead_ref[t] == u
                    ))
                    key_end = jnp.where(one_row, key_end, run_end)

                @pl.when(leads)
                def _():
                    kv_len = kvl0 + tok_of  # a one-row tile's horizons
                    if runs:  # a run's: its end for its members, 0 for the dead
                        kv_len = jnp.where(
                            one_row, kv_len, jnp.where(lead_of == u, run_end, 0)
                        )
                    attend(
                        tile_state, rows_ref[at(u)], lambda: q16_ref[...],
                        ws0, key_end, kv_len, win,
                        lambda i: jnp.concatenate([
                            jnp.broadcast_to(
                                sel_ref[j:j + 1, pl.ds(i * S, S)], (G, S)
                            )
                            for j in range(TILE)
                        ])[None] > 0.5,  # [1, M, S]
                    )

                return 0

            if runs:
                jax.lax.fori_loop(
                    0, jnp.where(one_row, 1, n_tok), shared_pass, 0
                )
            else:
                shared_pass(0, 0)

            @pl.when(one_row)
            def _out():
                o = _normalized(*tile_state, M, sinks)
                for j in range(TILE):
                    out_ref[j] = o[:, j * G:(j + 1) * G].astype(out_ref.dtype)

            if runs:
                @pl.when(jnp.logical_not(one_row))
                def _to_tokens():
                    for ref, by_token in zip(tile_state, tok_state):
                        for j in range(TILE):
                            by_token[j] = ref[:, j * G:(j + 1) * G]

        @pl.when(jnp.logical_not(one_row))
        def _each_tokens_rest():
            def token(u, _):
                t = t0 + u
                kv_len = kv_lens_ref[t]
                ws = win_starts_ref[t] if windowed else 0
                if runs:
                    state = tuple(ref.at[u] for ref in tok_state)
                    key_start = run_blocks_ref[t] * S

                    @pl.when(jnp.logical_not(any_run))
                    def _from_nothing():
                        _reset(*state, G)
                else:
                    state, key_start = tile_state, ws
                    _reset(*state, G)
                attend(
                    state, rows_ref[t], lambda: q_ref[u], key_start, kv_len,
                    kv_len, ws,
                    lambda i: (
                        sel_ref[pl.ds(u, 1), pl.ds(i * S, S)] > 0.5
                    )[None],
                )
                out_ref[u] = _normalized(
                    *state, G, None if sinks is None else sinks[:, :G],
                ).astype(out_ref.dtype)
                return 0

            jax.lax.fori_loop(0, n_tok, token, 0)

    scoped = [
        pltpu.VMEM((2, K, S, kv_hbm_ref.shape[-1]), kv_hbm_ref.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if quant:
        scoped += [
            pltpu.VMEM((2, K, S), jnp.float32),
            pltpu.VMEM((2, K, S), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ]
    pl.run_scoped(body, *scoped)


def _row_scale_planes(scales, layer, page_table):
    """The int8 pool's per-row K and V scales of ``layer``, gathered per
    page-table row and relayouted to two lane-aligned [R, K, S_max] f32
    planes by XLA. A per-page scale DMA inside the kernel (like the data
    pages) is structurally impossible: Mosaic requires a 128-aligned
    minor dim on manual copies and a page's scale slab is 2 lanes wide
    in every scatter-friendly layout — measured anyway via a
    const-scales probe: this gather is NOT the int8 decode cost (within
    noise of zero). The planes scale with max_pages, not the live
    context: the widest int8-only HBM stream in the decode step."""
    lidx = jnp.asarray(layer, jnp.int32).reshape(-1)[0]
    sl = (
        jax.lax.dynamic_index_in_dim(scales, lidx, 0, keepdims=False)
        if scales.ndim == 5 else scales
    )  # [P, K, page, 2]
    R, mp = page_table.shape
    _, K, page, _ = sl.shape
    g = sl[page_table]  # [R, mp, K, page, 2]
    ksvs = g.transpose(0, 2, 4, 1, 3).reshape(R, K, 2, mp * page)
    return ksvs[:, :, 0], ksvs[:, :, 1]


def _win_starts(kv_lens, window):
    """Per query the first position a sliding window lets it attend: the
    query sits at kv_len - 1. ``window`` may be a traced per-layer scalar;
    <= 0 degrades to full attention."""
    window = jnp.asarray(window, jnp.int32)
    return jnp.where(
        window > 0, jnp.maximum(kv_lens - window, 0), 0
    ).astype(jnp.int32)


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
    win_starts = (
        jnp.zeros_like(kv_lens) if window is None
        else _win_starts(kv_lens, window)
    )

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
        mp = page_table.shape[1]
        sspec = pl.BlockSpec(
            (1, K, mp * page), lambda b, l, pt, kl, ws: (b, 0, 0)
        )
        in_specs.extend([sspec, sspec])
        operands.extend(_row_scale_planes(scales, layer, page_table))
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
    pages_per_block: int = PAGES_PER_BLOCK,
    window: jax.Array | None = None,
    sinks: jax.Array | None = None,
    scales: jax.Array | None = None,  # [num_pages, K, page, 2]
) -> jax.Array:
    return _decode_call(
        q, kv_cache, jnp.zeros((1,), jnp.int32), page_table, kv_lens,
        sm_scale, interpret, pages_per_block, window=window, sinks=sinks,
        scales=scales,
    )


# Jitted INLINE: no call boundary in the step program, but calls that agree
# in shapes and options (a cycle body's sliding layers; one T's greedy and
# sampled programs) share one trace and one lowering of the kernel.
@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret", "pages_per_block"),
    inline=True,
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
    pages_per_block: int = PAGES_PER_BLOCK,
    window: jax.Array | None = None,
    sinks: jax.Array | None = None,
    scales: jax.Array | None = None,  # [L, num_pages, K, page, 2]
    sel: jax.Array | None = None,  # [T, S] bool: keys each token may read
    runs: tuple[jax.Array, jax.Array] | None = None,  # ([T], [T]) i32
) -> jax.Array:
    """Flattened-token (``cu_q_lens``) attention over the packed stream,
    against the compact per-row table through the scalar-prefetched
    token -> row map, so no [T, max_pages] per-token table is ever
    materialized for the data DMAs. kv_len = pos + 1 IS the causal mask
    within a row; write-before-read per layer makes same-step earlier
    tokens' fresh KV visible.

    The grid iterates the stream in tiles of ``TILE`` tokens (the granule
    T is padded to). A tile that lies inside one row — the body of a
    prefill chunk — streams the row's live pages ONCE for its TILE
    queries, each under its own horizon; in every other tile (decode and
    verify rows, a chunk's ragged ends, pad tokens) a token streams its
    own live pages, but for what ``runs`` let it share. The kernel finds
    a one-row tile from ``rows`` and ``kv_lens``. ``window`` (a sliding
    layer's; a traced per-layer scalar may be <= 0: full attention) bounds
    each token below too, at ``kv_len - window``: a shared tile then reads
    from its first token's start. A call without one carries no such
    operand: its program knows no window.

    ``runs`` = (run_lead, run_blocks), the host's plan of SHARED-PREFIX
    RUNS over ``page_table`` (``engine/prefix_runs.py``): tokens of one
    tile, of different rows, whose leading ``run_blocks[t]`` compute blocks
    (``pages_per_block`` pages each) are the same physical pages in every
    member's row and lie wholly under every member's horizon. A member
    carries the run's blocks and ``run_lead[t]``, its leader's place in the
    tile (a member too); every other token carries 0 blocks. The tile reads
    a run's blocks once, off the leader's row, for all its members, and
    each member then streams only the rest of its own row. Only a call
    without a window may carry them.

    ``sel`` (learned sparse attention) masks every key a token's indexer
    did not select, on top of the causal mask: the pass stays dense over
    the live pages (of the tile, or of the token), the result reads
    selected tokens only."""
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
    assert window is None or sel is None, "no windowed sparse layer"
    assert window is None or runs is None, "no shared-prefix run under a window"

    qk = q.reshape(T, K, G, D)
    if sinks is None:
        sinks2d = jnp.zeros((K, TILE * G), jnp.float32)
    else:
        sinks2d = jnp.tile(sinks.astype(jnp.float32).reshape(K, G), (1, TILE))
    prefetch = [
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
        page_table, kv_lens,
    ]
    if window is not None:
        prefetch.append(_win_starts(kv_lens, window))
    if runs is not None:
        prefetch.extend(r.astype(jnp.int32) for r in runs)

    def at(*index):
        """An index map over (program, *scalar prefetch refs)."""
        return lambda b, *_: tuple(b if i == "b" else 0 for i in index)

    in_specs = [
        pl.BlockSpec((TILE, K, G, D), at("b", 0, 0, 0)),
        pl.BlockSpec((K, TILE * G), at(0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM; manual DMA
    ]
    operands = [qk, sinks2d, kv_cache]
    if scales is not None:
        # Per-ROW scale planes (_row_scale_planes): gathered ONCE per row,
        # so a prefill chunk's tokens share one plane instead of
        # duplicating it chunk-length times into a [T, max_pages, ...]
        # intermediate. They stay in HBM; a block's slab is copied beside
        # its pages.
        sspec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs.extend([sspec, sspec])
        operands.extend(_row_scale_planes(scales, layer, page_table))
    if sel is not None:
        S_max = page_table.shape[1] * page
        in_specs.append(pl.BlockSpec((TILE, S_max), at("b", 0)))
        operands.append(jnp.pad(
            sel.astype(jnp.float32), ((0, 0), (0, S_max - sel.shape[1]))
        ))
    kernel = pl.pallas_call(
        functools.partial(
            _flat_tile_kernel, page_size=page, head_dim=D, sm_scale=sm_scale,
            pages_per_block=pages_per_block, has_sinks=sinks is not None,
            quant=scales is not None, select=sel is not None,
            windowed=window is not None, runs=runs is not None,
            num_tokens=T,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(pl.cdiv(T, TILE),),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((TILE, K, G, D), at("b", 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, TILE * G, 128), jnp.float32),
                pltpu.VMEM((K, TILE * G, 128), jnp.float32),
                pltpu.VMEM((K, TILE * G, D), jnp.float32),
                pltpu.VMEM((K, TILE * G, D), q.dtype),
            ] + ([
                pltpu.VMEM((TILE, K, G, 128), jnp.float32),
                pltpu.VMEM((TILE, K, G, 128), jnp.float32),
                pltpu.VMEM((TILE, K, G, D), jnp.float32),
            ] if runs is not None else []),
        ),
        out_shape=jax.ShapeDtypeStruct((T, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    return kernel(*prefetch, *operands).reshape(T, 1, H, D)


def decode_paged_attention_full(
    q: jax.Array,  # [B, 1, H, D]
    kv_cache: jax.Array,  # [L, num_pages, K, page, 2D] (whole model)
    layer: jax.Array,  # scalar i32
    page_table: jax.Array,
    kv_lens: jax.Array,
    sm_scale: float | None = None,
    interpret: bool = False,
    pages_per_block: int = PAGES_PER_BLOCK,
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
