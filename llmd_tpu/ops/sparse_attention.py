"""Learned sparse attention on the flat step (DeepSeek-Sparse-Attention-
style indexer over GQA; docs/architecture/sparse-attention.md).

Per layer and query token t, with one cached indexer key per token:

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])        s <= t
    S_t     = the ``topk`` values of s with the largest I[t, s]
              (every s <= t while no more than ``topk`` are cached)
    o[t, i] = softmax over s in S_t of (q[t, i] . k[s, g(i)] / sqrt(D)) v

Three parts, each under its own ``jax.named_scope``:

* ``llmd.indexer``: the index scores of the step's T tokens against each
  token's own row of the page table, [T, S_max] float32, in tiles of
  tokens so that the gathered keys stay bounded (16 tokens x S_max x Di).
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

import jax
import jax.numpy as jnp

from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

# Tokens scored at once: bounds the gathered keys to TILE x S_max x Di.
_SCORE_TILE = 16


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


@jax.named_scope("llmd.indexer")
def index_scores(iq, iw, plane, page_table, rows, kv_lens) -> jax.Array:
    """I[t, s] for the packed stream: ``iq`` [T, J, Di], ``iw`` [T, J], one
    layer's ``plane`` [pages, page, Di]; -inf where s >= kv_lens[t].
    Operands in the served dtype, float32 accumulation."""
    T, J, Di = iq.shape
    S = page_table.shape[1] * plane.shape[1]
    tile = min(_SCORE_TILE, T)
    pad = -T % tile

    def padded(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((T + pad) // tile, tile, *a.shape[1:])

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
        one_tile, (padded(iq), padded(iw), padded(rows), padded(kv_lens))
    )
    return out.reshape(T + pad, S)[:T]


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
    positions, topk: int, sm_scale=None, world_size=1, mesh=None,
):
    """Attention of the packed ``[T, 1, H, D]`` stream over each token's
    selected keys only (see the module docstring). ``iq`` [T, J, Di] and
    ``iw`` [T, J] are the indexer's rotated query heads and head weights;
    ``kv_lens`` is per token (position + 1)."""
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
        index_scores(iq, iw, plane, page_table, rows, kv_lens), topk
    )
    plan = ops._plan(
        "sparse_attention", Q, page, D, D2, world_size, True, mesh, T, H, K
    )
    with jax.named_scope("llmd.sparse_attention"):
        if plan == "direct":
            return flat_paged_attention_full(
                q, kv, layer, rows, page_table, kv_lens, sm_scale=sm_scale,
                interpret=ops._interpret(), sel=sel,
            )
        sl = jax.lax.dynamic_index_in_dim(kv, layer, 0, keepdims=False)
        return ops._attention_xla(
            q, sl, page_table[rows], kv_lens, positions, sm_scale,
            sel=sel[:, None, :],
        )
