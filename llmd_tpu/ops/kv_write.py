"""Pallas in-place KV cache write (decode path).

The XLA scatter in ``write_kv_pages`` is not in-place under ``lax.scan``:
every decode step copies the ENTIRE per-layer KV pool (read+write), which
measured ~12ms/step for a 2048-page llama-3B pool on v5e — about 40% of
the decode step. This kernel aliases the cache HBM buffer into the
output (``input_output_aliases``) and issues one small DMA per token
(the [K, 1, 2D] slab at its page/offset), so per-step traffic is the
actual KV bytes (~1MB) instead of the pool size (GBs).

Used for Q==1 (decode); prefill keeps the XLA scatter, whose pool copy
amortizes over thousands of tokens per dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _write_kernel(
    # scalar prefetch
    layer_ref,   # [1] i32 layer index (full-cache variant; [0] otherwise)
    phys_ref,    # [T] i32 physical page per token
    offset_ref,  # [T] i32 in-page slot per token
    valid_ref,   # [T] i32 (0/1)
    # blocks
    kv_new_ref,  # [1, K, 1, 2D] VMEM (this token's K/V slab)
    kv_hbm_ref,  # [(L,) num_pages, K, page, 2D] ANY (aliased into out)
    out_ref,     # same buffer as kv_hbm_ref
    # scratch (scratch_shapes buffers persist across grid steps — the
    # documented substrate for cross-step software pipelines)
    page_buf,    # [2, K, page, 2D] VMEM double buffer
    sem_in,      # [2] DMA
    sem_out,     # scalar DMA (stores complete in-step; no second slot)
):
    """Read-modify-write of the token's page: a direct single-row DMA into
    HBM violates the (8,128) sublane tiling, so the whole [K, page, 2D]
    slab (~64KB) rides through VMEM. Precondition: tokens in one grid
    launch target distinct pages (decode: one token per sequence, and the
    allocator never shares a page across sequences)."""
    t = pl.program_id(0)
    T = pl.num_programs(0)
    is_full = len(kv_hbm_ref.shape) == 5
    src = kv_hbm_ref.at[layer_ref[0]] if is_full else kv_hbm_ref
    dst = out_ref.at[layer_ref[0]] if is_full else out_ref

    # Software pipeline across grid steps (TPU grids run sequentially and
    # scratch persists): step t waits on the load it started at t-1,
    # modifies, stores, while t+1's load is already in flight. Each
    # index's start and wait are gated on the SAME predicate
    # (valid_ref[i]), so the semaphore protocol stays balanced while pad
    # rows skip their page DMA entirely (a 64-row bucket with 2 live
    # sequences would otherwise stream ~4MB/layer/step of discarded
    # pages).
    def load(i):
        slot_i = jax.lax.rem(i, 2)
        return pltpu.make_async_copy(
            src.at[phys_ref[i]], page_buf.at[slot_i], sem_in.at[slot_i]
        )

    @pl.when((t == 0) & (valid_ref[0] != 0))
    def _warmup():
        load(0).start()

    @pl.when((t + 1 < T) & (valid_ref[jnp.minimum(t + 1, T - 1)] != 0))
    def _prefetch():
        load(t + 1).start()

    slot = jax.lax.rem(t, 2)

    @pl.when(valid_ref[t] != 0)
    def _write():
        load(t).wait()
        # Masked select instead of a dynamic-index store: Mosaic cannot
        # prove sublane alignment for a runtime page offset.
        buf = page_buf.at[slot]
        rows = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
        buf[:] = jnp.where(rows == offset_ref[t], kv_new_ref[0], buf[:])
        store = pltpu.make_async_copy(buf, dst.at[phys_ref[t]], sem_out)
        store.start()
        # The slot's next LOAD starts at t+1 (other slot) and t+2 (this
        # slot); waiting here still overlaps this store with t+1's
        # in-flight load.
        store.wait()


def _slab_align(dtype) -> int:
    """Row alignment a manual DMA may start at on the sublane-tiled token
    axis of the HBM slab: one (8, 128) tile of 32-bit words, which packs
    8 rows of a 4-byte, 16 of a 2-byte and 32 of a 1-byte dtype."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _flat_write_kernel(
    # scalar prefetch
    layer_ref,  # [1] i32 layer index (full-cache variant; [0] otherwise)
    src_ref,    # [R] i32 slab row of the run's first token (pre-shifted:
                #     src = page + t0 - off, so slab row off+j = token t0+j)
    phys_ref,   # [R] i32 physical page per run
    off_ref,    # [R] i32 first in-page slot per run
    cnt_ref,    # [R] i32 token count per run (0 = pad run, fully skipped)
    # blocks
    kv_new_ref,  # [K, Tp, 2D] ANY (whole step's token slab, page-padded)
    kv_hbm_ref,  # [(L,) num_pages, K, page, 2D] ANY (aliased into out)
    out_ref,     # same buffer as kv_hbm_ref
    # scratch
    page_buf,   # [2, K, page, 2D] VMEM double buffer (the target pages)
    slab_buf,   # [2, K, page + align, 2D] VMEM double buffer (token windows)
    sem_page,   # [2] DMA
    sem_slab,   # [2] DMA
    sem_out,    # scalar DMA
):
    """Flattened-token KV write, one RUN per grid step: a run is a
    maximal span of consecutive stream tokens landing in one physical
    page, so runs target DISTINCT pages by construction (the allocator
    never shares a page across sequences, and within a row the run
    covers every token the page receives) — which is what keeps the
    cross-step software pipeline's prefetch safe where the per-token
    decode kernel's same-page read-modify-writes would race it. The
    token slab arrives page-padded and pre-shifted ([K, Tp, 2D], run
    slab start = page + t0 - off), so slab row src+j is what page row j
    receives. The token axis is sublane-tiled in HBM and Mosaic refuses
    a DMA that starts off a tile boundary, so each run fetches the
    ALIGNED window holding its slab (start rounded down to the tiling,
    one tile longer than a page) and rotates it into place in VMEM —
    as 32-bit values, the only width the sublane rotate takes at an
    arbitrary shift; widening and narrowing back is exact for every
    pool dtype."""
    r = pl.program_id(0)
    R = pl.num_programs(0)
    page = page_buf.shape[2]
    win = slab_buf.shape[2]
    align = win - page
    is_full = len(kv_hbm_ref.shape) == 5
    src = kv_hbm_ref.at[layer_ref[0]] if is_full else kv_hbm_ref
    dst = out_ref.at[layer_ref[0]] if is_full else out_ref

    def load(i):
        slot_i = jax.lax.rem(i, 2)
        start = pl.multiple_of((src_ref[i] // align) * align, align)
        return (
            pltpu.make_async_copy(
                src.at[phys_ref[i]], page_buf.at[slot_i], sem_page.at[slot_i]
            ),
            pltpu.make_async_copy(
                kv_new_ref.at[:, pl.ds(start, win), :],
                slab_buf.at[slot_i],
                sem_slab.at[slot_i],
            ),
        )

    @pl.when((r == 0) & (cnt_ref[0] != 0))
    def _warmup():
        for c in load(0):
            c.start()

    @pl.when((r + 1 < R) & (cnt_ref[jnp.minimum(r + 1, R - 1)] != 0))
    def _prefetch():
        for c in load(r + 1):
            c.start()

    slot = jax.lax.rem(r, 2)

    @pl.when(cnt_ref[r] != 0)
    def _write():
        for c in load(r):
            c.wait()
        buf = page_buf.at[slot]
        wide = (
            jnp.float32 if jnp.issubdtype(buf.dtype, jnp.floating)
            else jnp.int32
        )
        # Window row shift+j -> page row j: rotate up by the run's offset
        # into its window (roll takes a non-negative amount).
        shift = jax.lax.rem(src_ref[r], align)
        slab = pltpu.roll(slab_buf[slot].astype(wide), win - shift, 1)
        slab = slab[:, :page, :].astype(buf.dtype)
        rows = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
        hit = (rows >= off_ref[r]) & (rows < off_ref[r] + cnt_ref[r])
        buf[:] = jnp.where(hit, slab, buf[:])
        store = pltpu.make_async_copy(buf, dst.at[phys_ref[r]], sem_out)
        store.start()
        store.wait()


def _flat_write_call(kv_cache, kv_new_t, layer, src, phys, offset, cnt, interpret):
    K = kv_new_t.shape[0]
    page, D2 = kv_cache.shape[-2], kv_cache.shape[-1]
    R = src.shape[0]
    win = page + _slab_align(kv_cache.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, K, page, D2), kv_cache.dtype),
            pltpu.VMEM((2, K, win, D2), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = pl.pallas_call(
        _flat_write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype),
        # operand index counts scalar-prefetch args first: 5 scalars,
        # kv_new_t, then kv_cache at index 6 -> aliased to output 0.
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    return kernel(
        layer.astype(jnp.int32).reshape(1),
        src.astype(jnp.int32),
        phys.astype(jnp.int32),
        offset.astype(jnp.int32),
        cnt.astype(jnp.int32),
        kv_new_t,
        kv_cache,
    )


def write_kv_pages_flat_full(
    kv_cache: jax.Array,  # [L, num_pages, K, page, 2D] (whole model)
    kv_new: jax.Array,    # [T, K, 2D] packed token stream (K|V halves)
    layer: jax.Array,     # scalar i32
    src: jax.Array,       # [R] i32 slab start row (page + t0 - off)
    phys: jax.Array,      # [R] i32 physical page per run
    offset: jax.Array,    # [R] i32 first in-page slot per run
    cnt: jax.Array,       # [R] i32 token count per run (0 = pad)
    interpret: bool = False,
) -> jax.Array:
    """Layer-indexed flattened-token write: the whole step's packed token
    stream lands through run-addressed page read-modify-writes (see
    ``_flat_write_kernel``). The caller owns donation of the full cache
    (called under the engine's jitted flat step program)."""
    T, K, D2 = kv_new.shape
    L, num_pages, Kc, page, D2c = kv_cache.shape
    assert (K, D2) == (Kc, D2c), (kv_new.shape, kv_cache.shape)
    # Head-major slab, padded one page in front so every pre-shifted run
    # start (src in [1, page + T)) is in range, and behind so the aligned
    # window of the last run is too (it ends before src + page + align),
    # rounded up to whole tiles.
    align = _slab_align(kv_cache.dtype)
    tail = page + align + (-(T + 2 * page + align)) % align
    kv_new_t = jnp.pad(
        kv_new.transpose(1, 0, 2).astype(kv_cache.dtype),
        ((0, 0), (page, tail), (0, 0)),
    )
    return _flat_write_call(
        kv_cache, kv_new_t, layer, src, phys, offset, cnt, interpret
    )


def _write_call(kv_cache, kv_new4, layer, phys, offset, valid, interpret):
    T, K = kv_new4.shape[0], kv_new4.shape[1]
    page, D2 = kv_cache.shape[-2], kv_cache.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, K, 1, D2), lambda t, l, p, o, v: (t, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, K, page, D2), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype),
        # operand index counts scalar-prefetch args first: 4 scalars,
        # kv_new, then kv_cache at index 5 -> aliased to output 0.
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )
    return kernel(
        layer.astype(jnp.int32).reshape(1),
        phys.astype(jnp.int32),
        offset.astype(jnp.int32),
        valid.astype(jnp.int32),
        kv_new4,
        kv_cache,
    )


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def write_kv_pages_decode(
    kv_cache: jax.Array,  # [num_pages, K, page, 2D]
    kv_new: jax.Array,    # [T, K, 2D] (K then V halves on the last axis)
    phys: jax.Array,      # [T] i32
    offset: jax.Array,    # [T] i32
    valid: jax.Array,     # [T] bool/i32
    interpret: bool = False,
) -> jax.Array:
    T, K, D2 = kv_new.shape
    num_pages, Kc, page, D2c = kv_cache.shape
    assert (K, D2) == (Kc, D2c), (kv_new.shape, kv_cache.shape)
    kv_new4 = kv_new.reshape(T, K, 1, D2).astype(kv_cache.dtype)
    return _write_call(
        kv_cache, kv_new4, jnp.zeros((1,), jnp.int32), phys, offset, valid,
        interpret,
    )


def write_kv_pages_decode_full(
    kv_cache: jax.Array,  # [L, num_pages, K, page, 2D] (whole model)
    kv_new: jax.Array,    # [T, K, 2D]
    layer: jax.Array,     # scalar i32
    phys: jax.Array,      # [T] i32
    offset: jax.Array,    # [T] i32
    valid: jax.Array,     # [T] bool/i32
    interpret: bool = False,
) -> jax.Array:
    """Layer-indexed variant: writes into cache[layer] with the FULL cache
    aliased in place, so a scan over layers never slices (and never
    copies) the pool. Called under an enclosing jit (the engine's step
    programs); the caller owns donation of the full cache."""
    T, K, D2 = kv_new.shape
    L, num_pages, Kc, page, D2c = kv_cache.shape
    assert (K, D2) == (Kc, D2c), (kv_new.shape, kv_cache.shape)
    kv_new4 = kv_new.reshape(T, K, 1, D2).astype(kv_cache.dtype)
    return _write_call(kv_cache, kv_new4, layer, phys, offset, valid, interpret)
