"""Learned sparse attention on the flat step (DeepSeek-Sparse-Attention-
style indexer over GQA; docs/architecture/sparse-attention.md).

Per layer and query token t, with one cached indexer key per token:

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])        s <= t
    S_t     = the ``topk`` values of s with the largest I[t, s]
              (every s <= t while no more than ``topk`` are cached)
    o[t, i] = softmax over s in S_t of (q[t, i] . k[s, g(i)] / sqrt(D)) v

Three parts, each under its own ``jax.named_scope``:

* ``llmd.indexer``: the index scores of the step's T tokens against each
  token's own row of the page table, [T, S_max] float32. On the chip a
  Pallas kernel (``_indexer_kernel``), one program per 16-token tile of the
  stream with the attention kernel's decision read off ``rows``/``kv_lens``:
  a tile inside ONE page-table row (a prefill chunk's body) streams the
  row's live key pages once, up to its last token's horizon, and scores its
  16 x J heads against each block as one [16 J, Di] x [Di, block] product;
  any other tile (decode rows, seams, pad tokens) goes token by token over
  the token's own live pages. No page past a token's ``kv_lens`` is
  fetched; nothing is gathered. The kernel takes ONE layer's plane (the
  step slices it, as it does for the fallback). Its pages are [page, Di]
  slabs and the chip's compiler copies no slab narrower than a lane tile,
  so for Di < 128 the layer goes in zero-padded to 128 lanes (an XLA pad;
  zeros add nothing to a dot product). Queries, weights and keys go in in
  the dtype the caller gave and widen inside the kernel. Elsewhere (``LLMD_PALLAS=off``, off the chip) the XLA map
  ``_index_scores_xla``: every ``_SCORE_TILE`` tokens gather their rows'
  key pages and an einsum scores the copy; ``_SCORE_TILE`` bounds that
  fallback's gathered keys (16 tokens x S_max x Di) and nothing else.
* ``llmd.sparse_select``: the ``topk``-th largest score of each token, found
  EXACTLY by a 32-step bisection on the scores' bit patterns (32 counting
  passes over [T, S_max]; no sort, no approximate top-k), and the mask
  I[t, s] >= that threshold; equal scores AT the threshold (a ReLU makes
  exact zeros) go to the lower s, in a branch that runs only when some
  token has such a tie.
* ``llmd.sparse_attention``: flat paged attention under that mask. The pass
  is dense over a token's live pages; no key outside S_t reaches the
  softmax. (On random weights the selected tokens lie in every page, so a
  gather of the 2,048 rows reads less but in 512-byte pieces; which is
  faster on the chip is later work, PERF.md.)

The indexer keys live in ``IndexedPool.index`` ([L, pages, page, Di], the
served dtype) under the KV pool's page ids: whatever shares, frees or
reuses a page carries its indexer keys with it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmd_tpu.ops.ragged_paged_attention import (
    TILE,
    _stream_blocks,
    flat_paged_attention_full,
)

# Tokens the XLA fallback scores at once: bounds ITS gathered keys to
# _SCORE_TILE x S_max x Di. The kernel's tile is the flat stream's TILE.
_SCORE_TILE = 16
# Pages a compute block of the kernel streams (PERF.md section 6, PR 43).
_PAGES_PER_BLOCK = 64


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexedPool:
    """The paged pool of a sparse-attention model: K and V, and the plane of
    indexer keys that rides the same page ids."""

    kv: jax.Array  # [L, pages, K, page, 2D]
    index: jax.Array  # [L, pages, page, Di]


def write_index_keys_full_flat(
    cache: IndexedPool, layer, keys, page_table, rows, positions, valid
) -> IndexedPool:
    """This step's indexer keys ([T, Di], packed token stream) into layer
    ``layer``'s plane, at the slots the flat KV write puts K and V: page
    ``page_table[rows[t], pos // page]``, slot ``pos % page``."""
    plane = cache.index
    _, num_pages, page, _ = plane.shape
    phys = page_table[rows, positions // page]
    phys = jnp.where(valid, phys, num_pages)  # out of bounds: dropped
    sl = jax.lax.dynamic_index_in_dim(plane, layer, 0, keepdims=False)
    sl = sl.at[phys, positions % page, :].set(
        keys.astype(plane.dtype), mode="drop"
    )
    return dataclasses.replace(
        cache, index=jax.lax.dynamic_update_index_in_dim(plane, sl, layer, 0)
    )


def _tiles(a, tile: int):
    """[T, ...] -> [tiles, tile, ...], the stream zero-padded to whole tiles
    (a pad token's ``kv_lens`` is 0: it scores nothing)."""
    a = jnp.pad(a, ((0, -a.shape[0] % tile),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape(-1, tile, *a.shape[1:])


def _index_scores_xla(iq, iw, plane, page_table, rows, kv_lens) -> jax.Array:
    """The fallback: every ``_SCORE_TILE`` tokens gather each token's whole
    page-table row of one layer's ``plane`` and an einsum scores the copy."""
    T, J, Di = iq.shape
    S = page_table.shape[1] * plane.shape[1]
    tile = min(_SCORE_TILE, T)

    def one_tile(args):
        q, w, r, kl = args
        keys = plane[page_table[r]].reshape(tile, S, Di)
        s = jnp.einsum(
            "tjd,tsd->tjs", q, keys, preferred_element_type=jnp.float32
        )
        s = jnp.sum(
            jax.nn.relu(s) * w.astype(jnp.float32)[:, :, None], axis=1
        )
        return jnp.where(jnp.arange(S)[None, :] < kl[:, None], s, -jnp.inf)

    out = jax.lax.map(
        one_tile, tuple(_tiles(a, tile) for a in (iq, iw, rows, kv_lens))
    )
    return out.reshape(-1, S)[:T]


def _indexer_kernel(
    # scalar prefetch
    rows_ref,  # [T] i32 token -> page-table row
    page_table_ref,  # [R, max_pages] i32
    kv_lens_ref,  # [T] i32 per token: position + 1
    # blocks
    q_ref,  # [TILE, J, Di]: a token's heads, for the token-by-token pass
    w_ref,  # [TILE, J, 1], the weights' own dtype
    qj_ref,  # [J * TILE, Di]: the same, head-major (row j * TILE + token)
    wj_ref,  # [J * TILE, 1]
    plane_ref,  # [pages, 1, page, Di]: one layer's plane, in HBM
    out_ref,  # [TILE, S_max] f32
    *,
    page_size: int,
    pages_per_block: int,
):
    """One program per TILE consecutive stream tokens, the decision of
    ``_flat_tile_kernel`` read off the same ``rows``/``kv_lens``: a tile
    whose tokens sit in one page-table row at consecutive positions streams
    that row's live indexer-key pages ONCE, up to its last token's horizon,
    and scores its TILE x J heads against each block as one [J*TILE, Di] x
    [Di, block] product; any other tile goes token by token, each over its
    own live pages. Pages past a token's ``kv_lens`` are never fetched."""
    J = q_ref.shape[1]
    ppb = pages_per_block
    S = ppb * page_size
    t0 = pl.program_id(0) * TILE
    row0, kvl0 = rows_ref[t0], kv_lens_ref[t0]

    def same_row_next_position(j, ok):
        return jnp.logical_and(ok, jnp.logical_and(
            rows_ref[t0 + j] == row0, kv_lens_ref[t0 + j] == kvl0 + j
        ))

    shared = jax.lax.fori_loop(
        1, TILE, same_row_next_position, jnp.asarray(True)
    )
    # What no block below reaches (at and past a token's horizon's block).
    out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, jnp.float32)

    def body(buf, sem):
        def score(tr, key_end, block):
            """Row ``tr``'s key blocks over [0, key_end), each handed to
            ``block(i, keys [S, Di])``. A block's unfetched tail holds
            whatever the buffer held: a key's column is its own, and the
            causal bound drops it."""
            _stream_blocks(
                plane_ref, page_table_ref, buf, sem, tr, 0,
                (key_end + S - 1) // S, 0,
                (key_end + page_size - 1) // page_size, ppb, page_size,
                lambda slot, i: block(i, buf[slot, 0]),
            )

        def weighted(q, keys, w):
            """relu(q . keys) * w: [M, Di] x [S, Di] -> [M, S] f32. The
            precision is stated: a caller's ``default_matmul_precision``
            (the benchmark's reference sets "highest" around its call)
            reaches a kernel's dot as well, and the chip's compiler has no
            float32-precision product of bfloat16 operands, whose products
            are exact in the float32 accumulator as they are."""
            s = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                precision=(
                    jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                    else jax.lax.Precision.DEFAULT
                ),
                preferred_element_type=jnp.float32,
            )
            return jnp.maximum(s, 0.0) * w.astype(jnp.float32)

        def put(rows, i, s, horizon):
            """Block ``i``'s scores into ``out_ref[rows]``, -inf at and past
            each row's ``horizon``."""
            lo = pl.multiple_of(i * S, S)
            pos = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            out_ref[rows, pl.ds(lo, S)] = jnp.where(
                pos < horizon, s, -jnp.inf
            )

        @pl.when(shared)
        def _one_row():
            horizon = kvl0 + jax.lax.broadcasted_iota(
                jnp.int32, (TILE, 1), 0
            )

            def block(i, keys):
                s = weighted(qj_ref[...], keys, wj_ref[...])
                acc = s[:TILE]
                for j in range(1, J):  # the sum over heads: whole-slab adds
                    acc = acc + s[j * TILE:(j + 1) * TILE]
                put(slice(None), i, acc, horizon)

            score(row0, kvl0 + (TILE - 1), block)

        @pl.when(jnp.logical_not(shared))
        def _token_by_token():
            def token(u, _):
                kv_len = kv_lens_ref[t0 + u]

                def block(i, keys):
                    s = jnp.sum(
                        weighted(q_ref[u], keys, w_ref[u]), axis=0,
                        keepdims=True,
                    )
                    put(pl.ds(u, 1), i, s, kv_len)

                score(rows_ref[t0 + u], kv_len, block)
                return 0

            jax.lax.fori_loop(0, TILE, token, 0)

    pl.run_scoped(
        body,
        buf=pltpu.VMEM((2, 1, S, plane_ref.shape[-1]), plane_ref.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)),
    )


def index_scores_pallas(
    iq, iw, plane, page_table, rows, kv_lens, *,
    interpret: bool = False, pages_per_block: int = _PAGES_PER_BLOCK,
) -> jax.Array:
    """``index_scores`` as one Pallas call over one layer's paged ``plane``
    [pages, page, Di], which stays in HBM: nothing is gathered.
    ``pages_per_block`` is the tests' hook (blocks of two pages put a small
    stream's contexts on both sides of a block's edge)."""
    T, J, Di = iq.shape
    page = plane.shape[-2]
    S = page_table.shape[1] * page
    ppb = pages_per_block
    if Di % 128:
        # The chip's compiler copies no page slab narrower than a lane tile
        # (Di 64: "slice shape must be aligned to tiling (128)"), and XLA
        # holds such a plane row-major with its lanes padded anyway: the
        # layer goes in zero-padded to the tile (zeros add nothing to a dot
        # product). A copy of the layer, not of gathered keys.
        lanes = ((0, -Di % 128),)
        plane = jnp.pad(plane, ((0, 0), (0, 0)) + lanes)
        iq = jnp.pad(iq, ((0, 0), (0, 0)) + lanes)
        Di = plane.shape[-1]
    page_table = jnp.pad(page_table, ((0, 0), (0, -page_table.shape[1] % ppb)))
    S_pad = page_table.shape[1] * page
    q = _tiles(iq.astype(plane.dtype), TILE)
    # The weights go in as they are and widen IN the kernel. Widened here,
    # the convert would sit beside the caller's rounding to the served dtype,
    # and XLA folds such a pair into one fusion that never rounds (excess
    # precision): the scores would be those of float32 weights.
    w = _tiles(iw, TILE)
    n = q.shape[0]
    prefetch = [
        _tiles(rows.astype(jnp.int32), TILE).reshape(-1), page_table,
        _tiles(kv_lens.astype(jnp.int32), TILE).reshape(-1),
    ]

    def at(*index):
        return lambda b, *_: tuple(b if i == "b" else 0 for i in index)

    # A Pallas call in a loop body is named after the body unless a scope is
    # open AT the call: the device event is ``%llmd.indexer``.
    with jax.named_scope("llmd.indexer"):
        out = pl.pallas_call(
            functools.partial(
                _indexer_kernel, page_size=page, pages_per_block=ppb
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(n,),
                in_specs=[
                    pl.BlockSpec((None, TILE, J, Di), at("b", 0, 0, 0)),
                    pl.BlockSpec((None, TILE, J, 1), at("b", 0, 0, 0)),
                    pl.BlockSpec((None, J * TILE, Di), at("b", 0, 0)),
                    pl.BlockSpec((None, J * TILE, 1), at("b", 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM
                ],
                out_specs=pl.BlockSpec((TILE, S_pad), at("b", 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((n * TILE, S_pad), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(
            # The queries twice, a token's heads together for the pass that
            # goes token by token and head-major for the shared one, whose
            # sum over heads is then J whole-slab adds (8 KiB a tile).
            *prefetch, q, w[..., None],
            q.transpose(0, 2, 1, 3).reshape(n, J * TILE, Di),
            w.transpose(0, 2, 1).reshape(n, J * TILE, 1),
            # A unit head axis: ``_stream_blocks`` copies [K, page, width]
            # page slabs. A bitcast, not a copy.
            jnp.expand_dims(plane, -3),
        )
    return out[:T, :S]


def index_scores(
    iq, iw, plane, page_table, rows, kv_lens, mesh=None
) -> jax.Array:
    """I[t, s] for the packed stream: ``iq`` [T, J, Di], ``iw`` [T, J], one
    layer's ``plane`` [pages, page, Di]; -inf where s >= kv_lens[t].
    Operands in the served dtype, float32 accumulation. The Pallas kernel
    where the dispatch of the attention beside it takes one
    (``ops._decide``), the XLA map elsewhere."""
    from llmd_tpu import ops

    sublanes = 8 * 4 // plane.dtype.itemsize  # rows of one tile of the dtype
    plan = ops._decide("indexer", plane.shape[-2] % sublanes == 0, 1, mesh)
    if plan == "direct":
        return index_scores_pallas(
            iq, iw, plane, page_table, rows, kv_lens,
            interpret=ops._interpret(),
        )
    with jax.named_scope("llmd.indexer"):
        return _index_scores_xla(iq, iw, plane, page_table, rows, kv_lens)


@jax.named_scope("llmd.sparse_select")
def select_topk(scores, topk: int) -> jax.Array:
    """[T, S] bool: the ``topk`` largest scores of each row, ties to the
    lower s. Exact: the ``topk``-th largest value is built bit by bit, from
    the top, as the largest value that at least ``topk`` scores reach
    (float order is integer order once the sign is folded); where more than
    ``topk`` scores reach it (equal scores at the threshold: a ReLU makes
    exact zeros) the equal ones are taken in order of s. A row with no more
    than ``topk`` finite scores selects them all (and -inf entries, which
    the attention's causal mask drops)."""
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 is 0.0
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    u = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)

    def count(mask):
        return jnp.sum(mask, axis=1, dtype=jnp.int32)

    def step(i, prefix):
        cand = prefix | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(u >= cand[:, None]) >= topk, cand, prefix)

    thr = jax.lax.fori_loop(
        0, 32, step, jnp.zeros(scores.shape[0], jnp.uint32)
    )[:, None]
    reach = u >= thr
    finite = thr > jnp.uint32(0x007FFFFF)  # the threshold is not -inf
    tied = finite[:, 0] & (count(reach) > topk)

    def lower_s_first(_):
        above, equal = u > thr, u == thr
        need = topk - count(above)
        first = jnp.cumsum(equal, axis=1, dtype=jnp.int32) <= need[:, None]
        return jnp.where(tied[:, None], above | (equal & first), reach)

    return jax.lax.cond(jnp.any(tied), lower_s_first, lambda _: reach, None)


def sparse_attention_full_flat(
    q, iq, iw, cache: IndexedPool, layer, rows, page_table, kv_lens,
    positions, topk: int, sm_scale=None, world_size=1, mesh=None, runs=None,
):
    """Attention of the packed ``[T, 1, H, D]`` stream over each token's
    selected keys only (see the module docstring). ``iq`` [T, J, Di] and
    ``iw`` [T, J] are the indexer's rotated query heads and head weights;
    ``kv_lens`` is per token (position + 1); ``runs`` the step's
    shared-prefix runs (the dense pass under the mask reads a run's blocks
    once a tile)."""
    from llmd_tpu import ops

    if world_size != 1:
        raise NotImplementedError(
            "sparse attention runs on one device (EngineConfig."
            "check_sparse_attention refuses a sharded mesh at start)"
        )
    kv = cache.kv
    _, _, K, page, D2 = kv.shape
    T, Q, H, D = q.shape
    plane = jax.lax.dynamic_index_in_dim(cache.index, layer, 0, keepdims=False)
    sel = select_topk(
        index_scores(iq, iw, plane, page_table, rows, kv_lens, mesh), topk
    )
    plan = ops._plan(
        "sparse_attention", Q, page, D, D2, world_size, True, mesh, T, H, K
    )
    with jax.named_scope("llmd.sparse_attention"):
        if plan == "direct":
            return flat_paged_attention_full(
                q, kv, layer, rows, page_table, kv_lens, sm_scale=sm_scale,
                interpret=ops._interpret(), sel=sel, runs=runs,
            )
        sl = jax.lax.dynamic_index_in_dim(kv, layer, 0, keepdims=False)
        return ops._attention_xla(
            q, sl, page_table[rows], kv_lens, positions, sm_scale,
            sel=sel[:, None, :],
        )
