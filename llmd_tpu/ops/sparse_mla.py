"""Latent attention over a token's selected rows, on the flat step
(DeepSeek-V3.2: the lightning indexer's top-k read out of an MLA latent
cache; docs/architecture/sparse-attention.md, "The latent variant").

The pool is an ``IndexedPool`` whose ``kv`` is the latent pool
``[L, pages, 1, page, Dl]`` (one row ``[RMSNorm(c), RoPE(k_r)]`` a token,
padded to the lane tile) and whose ``index`` is the indexer's key plane
``[L, pages, page, Di]`` under the same page ids. Two entry points, each
under its own ``jax.named_scope``:

* ``llmd.latent_write`` (``write_latent_rows_full_flat``): the stream's
  latent rows AND its indexer keys through the flat step's run plan, the
  page read-modify-writes of ``ops/kv_write.py::write_kv_pages_flat_full``.
  That kernel moves ``[K, page, width]`` slabs and never looks inside a
  row, so a latent row goes through it whole (K = 1, width Dl) and the
  key plane as a pool of one head (a unit axis: a bitcast). Both address
  ``[L, ...]`` by the layer's index, in place on the donated pool: no
  layer's plane is sliced out and written back. Off the chip an XLA scatter
  of the valid rows.
* ``llmd.sparse_mla`` (``sparse_mla_attention_full_flat``): for token t the
  softmax over S_t only. The indexer's scores and the exact top-k are
  ``ops/sparse_attention.py``'s, unchanged (the key plane goes to the
  scoring whole, as one layer of L x pages pages with the page ids offset
  by the layer: nothing is sliced out). The selection's mask becomes each
  token's ascending list of selected rows as (page, slot)
  (``selected_slots``: counting and one one-hot product; no sort, no
  scatter, no gather of single ids), the rows are GATHERED out of the pool
  by ``(layer, page, slot)`` and the 128-head product runs over
  ``[topk, Dl]`` a token. Why gathered, where the grouped-query variant
  passes densely under the mask: a latent row serves every head, so a row
  read costs 2 x H x (Dl + rank) FLOPs, and a dense pass over a 16-24k
  context would multiply the FLOPs by the same 8-12 it multiplies the
  bytes. XLA operations (a row gather and two einsums); the gather is what
  the read's time is (PERF.md section 5, ROADMAP S19).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from llmd_tpu.ops.kv_write import write_kv_pages_flat_full
from llmd_tpu.ops.sparse_attention import (
    IndexedPool,
    index_scores,
    select_topk,
)

# Positions a counting block of ``_slot_blocks`` covers: one lane tile.
_COUNT_BLOCK = 128


def write_latent_rows_full_flat(
    cache: IndexedPool, layer, latent, keys, page_table, rows, positions,
    valid, runs, mesh=None,
) -> IndexedPool:
    """This step's latent rows ``latent`` [T, Dl] and indexer keys ``keys``
    [T, Di] (packed token stream) into layer ``layer`` of both planes, at
    page ``page_table[rows[t], pos // page]``, slot ``pos % page``.
    ``runs`` = (src, off, cnt, phys) is the flat write plan
    (``StepInput.flat_runs``), or None where the step has none."""
    from llmd_tpu import ops

    kv, plane = cache.kv, cache.index
    _, num_pages, _, page, Dl = kv.shape
    plan = ops._plan_write(
        "flat_latent_write", 1, page, Dl // 2, Dl, 1, mesh,
        have_plan=runs is not None and plane.shape[-1] % 128 == 0,
    )
    with jax.named_scope("llmd.latent_write"):
        if plan == "direct":
            src, off, cnt, phys = runs
            interpret = ops._interpret()
            kv = write_kv_pages_flat_full(
                kv, latent[:, None, :], layer, src, phys, off, cnt,
                interpret=interpret,
            )
            plane = write_kv_pages_flat_full(
                jnp.expand_dims(plane, 2), keys[:, None, :], layer, src,
                phys, off, cnt, interpret=interpret,
            )[:, :, 0]
            return IndexedPool(kv=kv, index=plane)
        phys = page_table[rows, positions // page]
        phys = jnp.where(valid, phys, num_pages)  # out of bounds: dropped
        slot = positions % page
        return IndexedPool(
            kv=kv.at[layer, phys, 0, slot, :].set(
                latent.astype(kv.dtype), mode="drop"
            ),
            index=plane.at[layer, phys, slot, :].set(
                keys.astype(plane.dtype), mode="drop"
            ),
        )


def _slot_blocks(sel, topk: int):
    """What slot j of each row's ascending list of selected positions lies
    in, by counting over blocks of ``_COUNT_BLOCK`` positions: (``onehot``
    [T, topk, blocks] in bfloat16: the slot's block, all zero past the row's
    count; ``b`` [T, topk] its number, ``blocks`` past the count; ``at``
    [T, topk]: the slot's place in the block). Slot j lies in the block
    whose running count first passes j, at the place whose running count
    in the block first passes what is left: comparisons, sums and ONE
    one-hot product ([topk, blocks] x [blocks, block] a row: the MXU fetches
    each slot's block of running counts; integers up to 128 are exact in
    bfloat16) — no sort, no scatter, no gather."""
    T, S = sel.shape
    blk = _COUNT_BLOCK
    m = jnp.pad(sel, ((0, 0), (0, -S % blk))).reshape(T, -1, blk)
    within = jnp.cumsum(m, axis=2, dtype=jnp.int32)  # [T, nb, blk] inclusive
    count = within[:, :, -1]
    ends = jnp.cumsum(count, axis=1)  # [T, nb] inclusive
    j = jnp.arange(topk, dtype=jnp.int32)[None, :, None]
    before = ends[:, None, :] <= j  # [T, topk, nb]: blocks wholly before slot j
    b = jnp.sum(before, axis=2, dtype=jnp.int32)  # [T, topk]
    start = jnp.sum(
        jnp.where(before, count[:, None, :], 0), axis=2, dtype=jnp.int32
    )
    r = j[..., 0] - start  # the slot's place among its block's selected
    onehot = (
        b[:, :, None] == jnp.arange(within.shape[1], dtype=jnp.int32)
    ).astype(jnp.bfloat16)
    counts = jnp.einsum(
        "tkb,tbl->tkl", onehot, within.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )  # [T, topk, blk]: the running counts of each slot's block
    at = jnp.sum(
        counts <= r[:, :, None].astype(jnp.float32), axis=2, dtype=jnp.int32
    )
    return onehot, b, at


def selected_positions(sel, topk: int) -> jax.Array:
    """[T, topk] i32: the positions a row of ``sel`` [T, S] bool selects, in
    ascending order, then S for every slot past the row's count (a row
    selects at most ``topk``)."""
    _, b, at = _slot_blocks(sel, topk)
    return jnp.minimum(b * _COUNT_BLOCK + at, sel.shape[1])


def selected_slots(sel, table, topk: int, page: int):
    """(page ids, in-page slots, live), [T, topk] each: where the rows a row
    of ``sel`` [T, S] selects lie in the pool, through the token's own row of
    the page table ``table`` [T, S / page]; ``live`` False for every slot
    past the row's count. A slot's page comes out of the same one-hot
    product that finds its position (the block's pages, each id as three
    bytes: exact in bfloat16): a gather of single ids by 2,048 positions a
    token costs as much as the gather of the rows themselves."""
    T, S = sel.shape
    blk = _COUNT_BLOCK
    if blk % page or table.shape[1] * page != S:
        pos = selected_positions(sel, topk)
        live = pos < S
        pos = jnp.where(live, pos, 0)
        return (
            jnp.take_along_axis(table, pos // page, axis=1), pos % page, live
        )
    onehot, b, at = _slot_blocks(sel, topk)
    ppb = blk // page
    ids = jnp.pad(table, ((0, 0), (0, -table.shape[1] % ppb)))
    ids = ids.reshape(T, -1, ppb)  # a block's pages
    parts = jnp.concatenate(
        [(ids >> s) & 0xFF for s in (16, 8, 0)], axis=2
    ).astype(jnp.bfloat16)
    parts = jnp.einsum(
        "tkb,tbp->tkp", onehot, parts, preferred_element_type=jnp.float32
    ).astype(jnp.int32)  # [T, topk, 3 * ppb]
    pages = (
        (parts[..., :ppb] << 16) | (parts[..., ppb : 2 * ppb] << 8)
        | parts[..., 2 * ppb :]
    )
    live = (b * blk + at) < S
    at = jnp.where(live, at, 0)
    pick = (at // page)[:, :, None] == jnp.arange(ppb, dtype=jnp.int32)
    return jnp.sum(jnp.where(pick, pages, 0), axis=2), at % page, live


def sparse_mla_attention_full_flat(
    q_eff, iq, iw, cache: IndexedPool, layer, rows, page_table, kv_lens,
    topk: int, rank: int, sm_scale: float, world_size=1, mesh=None,
):
    """Latent attention of the packed stream over each token's selected rows
    only: ``q_eff`` [T, H, Dl] (absorbed queries, zero past the latent's
    width) against the rows S_t of layer ``layer``; returns [T, H, rank]
    (the value is a row's first ``rank`` lanes). ``iq`` [T, J, Di] and
    ``iw`` [T, J] are the indexer's rotated query heads and head weights;
    ``kv_lens`` is per token (position + 1)."""
    if world_size != 1:
        raise NotImplementedError(
            "sparse attention runs on one device (EngineConfig."
            "check_sparse_attention refuses a sharded mesh at start)"
        )
    kv = cache.kv
    page = kv.shape[3]
    # The whole key plane as ONE layer of L x pages pages, the layer chosen
    # by the page ids: no layer's plane is sliced out for the scoring.
    L, num_pages = cache.index.shape[:2]
    plane = cache.index.reshape(L * num_pages, *cache.index.shape[2:])
    sel = select_topk(index_scores(
        iq, iw, plane, page_table + layer * num_pages, rows, kv_lens, mesh
    ), topk)
    S = sel.shape[1]
    with jax.named_scope("llmd.sparse_mla"):
        # A row past kv_lens scored -inf: where no more than ``topk`` are
        # cached the selection takes such rows too, and the causal bound
        # drops them here.
        sel = sel & (jnp.arange(S)[None, :] < kv_lens[:, None])
        phys, slot, live = selected_slots(sel, page_table[rows], topk, page)
        lat = kv[layer, phys, 0, slot]  # [T, topk, Dl]
        s = jnp.einsum(
            "thd,tkd->thk", q_eff, lat, preferred_element_type=jnp.float32
        ) * sm_scale
        s = jnp.where(live[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "thk,tkr->thr", p.astype(lat.dtype), lat[..., :rank],
            preferred_element_type=jnp.float32,
        )
        return out.astype(q_eff.dtype)
