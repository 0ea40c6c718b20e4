"""Pallas TPU latent paged attention (MLA decode path).

The MQA-shaped sibling of ragged_paged_attention._decode_kernel: one
latent "head" of width Dl serves every query head, scores contract over
the full latent row, values are its first `rank` components. Streams
only the LIVE context pages HBM->VMEM (double-buffered DMAs) with a
flash-style online-softmax accumulator — the XLA fallback gathers the
whole padded context per layer per step, which is exactly what makes
naive MLA decode slow at 160k context.

Layer-indexed like the other decode kernels: the FULL [L, pages, 1,
page, Dl] cache stays in HBM and the kernel reads cache[layer], so the
scan over layers never slices the pool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _mla_decode_kernel(
    # scalar prefetch
    layer_ref,       # [1] i32
    page_table_ref,  # [B, max_pages] i32
    kv_lens_ref,     # [B] i32
    # blocks
    q_ref,       # [1, H, Dl] VMEM
    lat_hbm_ref,  # [(L,) num_pages, 1, page, Dl] HBM (unblocked)
    out_ref,     # [1, H, rank] VMEM
    # scratch
    m_ref,    # [H, 128] f32
    l_ref,    # [H, 128] f32
    acc_ref,  # [H, rank] f32
    *,
    page_size: int,
    rank: int,
    sm_scale: float,
    pages_per_block: int,
):
    b = pl.program_id(0)
    hbm = (
        lat_hbm_ref.at[layer_ref[0]]
        if len(lat_hbm_ref.shape) == 5
        else lat_hbm_ref
    )
    ppb = pages_per_block
    S = ppb * page_size
    kv_len = kv_lens_ref[b]
    n_blocks = (kv_len + S - 1) // S
    n_live_pages = (kv_len + page_size - 1) // page_size

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(buf, sem):
        # buf: [2, 1, S, Dl]; one DMA per page.
        def _dma(slot, i, j):
            return pltpu.make_async_copy(
                hbm.at[page_table_ref[b, i * ppb + j]],
                buf.at[slot, :, pl.ds(j * page_size, page_size), :],
                sem.at[slot, j],
            )

        def start_block(slot, i):
            for j in range(ppb):

                @pl.when(i * ppb + j < n_live_pages)
                def _start():
                    _dma(slot, i, j).start()

        def wait_block(slot, i):
            for j in range(ppb):

                @pl.when(i * ppb + j < n_live_pages)
                def _wait():
                    _dma(slot, i, j).wait()

        @pl.when(n_blocks > 0)
        def _warmup():
            start_block(0, 0)

        def loop(i, _):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_block(jax.lax.rem(i + 1, 2), i + 1)

            wait_block(slot, i)
            lat = buf[slot, 0]  # [S, Dl]
            # zero unfetched tail rows so stray VMEM can't poison (0 x v)
            pos_l = i * S + jax.lax.broadcasted_iota(jnp.int32, lat.shape, 0)
            lat = jnp.where(pos_l < kv_len, lat, 0.0)
            q = q_ref[0]  # [H, Dl]
            s = jax.lax.dot_general(
                q, lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [H, S]
            pos = i * S + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < kv_len, s, NEG_INF)

            m_prev = m_ref[:, :1]  # [H, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(s - m_new)  # [H, S]
            l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(
                probs, axis=1, keepdims=True
            )
            m_ref[:, :1] = m_new
            pv = jax.lax.dot_general(
                probs.astype(lat.dtype), lat[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, rank]
            acc_ref[:] = acc_ref[:] * alpha + pv
            return 0

        jax.lax.fori_loop(0, n_blocks, loop, 0)

    pl.run_scoped(
        body,
        buf=pltpu.VMEM(
            (2, 1, ppb * page_size, lat_hbm_ref.shape[-1]), lat_hbm_ref.dtype
        ),
        sem=pltpu.SemaphoreType.DMA((2, ppb)),
    )

    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)


def mla_decode_paged_attention_full(
    q_eff: jax.Array,        # [B, 1, H, Dl]
    latent_cache: jax.Array,  # [L, num_pages, 1, page, Dl]
    layer: jax.Array,        # scalar i32
    page_table: jax.Array,   # [B, max_pages]
    kv_lens: jax.Array,      # [B]
    rank: int,
    sm_scale: float,
    interpret: bool = False,
    pages_per_block: int = 8,
) -> jax.Array:
    """Returns [B, 1, H, rank]."""
    B, Q, H, Dl = q_eff.shape
    assert Q == 1, "MLA decode kernel handles Q=1"
    page = latent_cache.shape[-2]
    max_pages = page_table.shape[1]
    if max_pages % pages_per_block:
        pad = pages_per_block - max_pages % pages_per_block
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
    qh = q_eff.reshape(B, H, Dl)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, Dl), lambda b, l, pt, kl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, l, pt, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _mla_decode_kernel,
            page_size=page,
            rank=rank,
            sm_scale=sm_scale,
            pages_per_block=pages_per_block,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_eff.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    out = kernel(
        layer.astype(jnp.int32).reshape(1), page_table, kv_lens, qh, latent_cache
    )
    return out.reshape(B, 1, H, rank)
