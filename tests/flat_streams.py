"""Flat token streams for the attention kernel's tests: one that holds every
kind of 16-token tile, and its token-by-token twin."""

import numpy as np


def tiled_stream(rng, page, num_pages, T=160):
    """A flat stream that holds every kind of 16-token tile: a chunk from
    t=0 with a ragged tail (two one-row tiles), a 64-token sub-row that
    starts OFF the granule, the seam to its sequence's next sub-row inside
    a tile, decode rows, a verify row (four consecutive positions) and a pad
    tail with one whole pad tile. Rows of one sequence share its pages.
    -> (token rows [T], positions [T], live [T], page table [R, max_pages])."""
    rows = [  # (first position, tokens, sequence)
        (37, 40, 0), (5, 64, 1), (69, 27, 1), (100, 1, 2), (33, 1, 3),
        (12, 4, 4), (7, 1, 5),
    ]
    max_pages = 16
    pages = rng.permutation(num_pages)[: 6 * max_pages].reshape(6, max_pages)
    tok_rows = np.full(T, len(rows) - 1, np.int32)
    positions = np.zeros(T, np.int32)
    live = np.zeros(T, bool)
    t = 0
    for r, (p0, w, _) in enumerate(rows):
        tok_rows[t:t + w], positions[t:t + w] = r, p0 + np.arange(w)
        live[t:t + w] = True
        t += w
    assert t < T - 16
    pt = np.stack([pages[seq] for _, _, seq in rows]).astype(np.int32)
    return tok_rows, positions, live, pt


def token_by_token(tok_rows, pt):
    """The same stream with every page-table row held twice and the tokens
    of a row alternating between its two copies: no tile lies in ONE row
    any more, so the kernel takes every token alone, over the same pages."""
    R = pt.shape[0]
    return tok_rows + R * (np.arange(len(tok_rows)) % 2), np.concatenate([pt, pt])


def window_attention_on_the_grid_of_tokens(
    q, kv_cache, layer, rows, page_table, kv_lens, window, *,
    pages_per_block, sinks=None, scales=None,
):
    """The flat stream's sliding-window call as it ran until the window
    joined the 16-token tile: one program a stream TOKEN, each streaming
    its own window's pages. Kept here as the reference the tile is held to
    bit for bit: the same block stream and the same online-softmax step
    (the module's own), on a grid of T programs. Interpret mode."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from llmd_tpu.ops import ragged_paged_attention as rpa

    T, _, H, D = q.shape
    K, page = kv_cache.shape[-3], kv_cache.shape[-2]
    G = H // K
    ppb = pages_per_block
    S = ppb * page
    if page_table.shape[1] % ppb:
        page_table = jnp.pad(
            page_table, ((0, 0), (0, ppb - page_table.shape[1] % ppb))
        )
    quant = scales is not None

    def kernel(layer_ref, rows_ref, pt_ref, kl_ref, ws_ref, *refs):
        q_ref, sinks_ref, kv_full_ref, *refs = refs
        if quant:
            ks_ref, vs_ref, *refs = refs
        out_ref, m_ref, l_ref, acc_ref = refs
        t = pl.program_id(0)
        kv_ref = kv_full_ref.at[layer_ref[0]]
        kv_len, ws = kl_ref[t], ws_ref[t]
        rpa._reset(m_ref, l_ref, acc_ref, G)

        def body(buf, sem):
            def compute(slot, i):
                rpa._attend_block(
                    q_ref[0], buf[slot], i, m_ref, l_ref, acc_ref,
                    head_dim=D, sm_scale=D**-0.5, key_end=kv_len,
                    kv_len=kv_len, key_start=ws, win_start=ws,
                    ks=ks_ref[0, :, pl.ds(i * S, S)] if quant else None,
                    vs=vs_ref[0, :, pl.ds(i * S, S)] if quant else None,
                )

            rpa._stream_blocks(
                kv_ref, pt_ref, buf, sem, rows_ref[t], ws // S,
                (kv_len + S - 1) // S, ws // page,
                (kv_len + page - 1) // page, ppb, page, compute,
            )

        pl.run_scoped(
            body, buf=pltpu.VMEM((2, K, S, 2 * D), kv_ref.dtype),
            sem=pltpu.SemaphoreType.DMA((2,)),
        )
        out_ref[0] = rpa._normalized(
            m_ref, l_ref, acc_ref, G,
            sinks_ref[...] if sinks is not None else None,
        ).astype(out_ref.dtype)

    win_starts = rpa._win_starts(kv_lens, window)
    token = lambda t, *_: (t, 0, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, K, G, D), token),
        pl.BlockSpec((K, G), lambda t, *_: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        q.reshape(T, K, G, D),
        jnp.zeros((K, G), jnp.float32) if sinks is None
        else sinks.astype(jnp.float32).reshape(K, G),
        kv_cache,
    ]
    if quant:
        plane = pl.BlockSpec(
            (1, K, page_table.shape[1] * page), lambda t, l, r, *_: (r[t], 0, 0)
        )
        in_specs += [plane, plane]
        operands += rpa._row_scale_planes(scales, layer, page_table)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(T,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, K, G, D), token),
            scratch_shapes=[
                pltpu.VMEM((K, G, 128), jnp.float32),
                pltpu.VMEM((K, G, 128), jnp.float32),
                pltpu.VMEM((K, G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, K, G, D), q.dtype),
        interpret=True,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
        page_table, kv_lens, win_starts, *operands,
    )
    return out.reshape(T, 1, H, D)
