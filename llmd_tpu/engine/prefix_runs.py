"""The host half of the flat attention kernel's SHARED-PREFIX RUNS.

Decode rows that came in through the prefix cache hold the SAME physical
pages at the head of their page-table rows (a shared document, a system
prompt). Token by token the kernel reads those pages once a row; told which
tokens of a 16-token tile share how many leading blocks, it reads them once
a tile (``ops/ragged_paged_attention.py::_flat_tile_kernel``). Nothing here
looks at requests or at the cache: the plan is read off the step's
``page_table`` and the stream's layout, so it holds whatever put equal ids
there.

- ``group_order``: where the decode rows of a step go in its stream, so
  that rows which start on the same page lie side by side;
- ``plan_runs``: per token of the stream the run it belongs to.
"""

from __future__ import annotations

import numpy as np

from llmd_tpu.ops.ragged_paged_attention import TILE

# A run's least number of members: one pass over a block for a tile's 16
# query rows at once costs what about two tokens' own passes over it do
# (PERF.md section 6, PR 54: the kernel alone on a v5e).
RUN_MIN_MEMBERS = 3


def group_order(first_pages) -> np.ndarray:
    """The order to lay rows in so that rows with the same first page are
    neighbours: groups by their first member, members as they came. Rows
    that share nothing keep their order."""
    first_pages = np.asarray(first_pages)
    if first_pages.size < 2:
        return np.arange(first_pages.size)
    _, first, inverse = np.unique(
        first_pages, return_index=True, return_inverse=True
    )
    return np.argsort(first[inverse.reshape(-1)], kind="stable")


def plan_runs(
    page_table: np.ndarray,  # [R, max_pages] i32
    rows: np.ndarray,  # [n] page-table row of each candidate token
    at: np.ndarray,  # [n] its place in the stream, increasing
    kv_lens: np.ndarray,  # [n] its horizon (position + 1)
    num_tokens: int,  # T, the stream's bucket
    block_keys: int,  # keys a compute block (pages_per_block x page)
    page: int,
    shards: int = 1,  # the stream splits over dp: tiles are a shard's
) -> tuple[np.ndarray, np.ndarray, int]:
    """-> (run_lead [T], run_blocks [T], keys read through a run).

    The candidates are the stream's plain decode tokens. Inside a tile,
    neighbours in the stream whose rows begin with the same pages can form
    a run: its blocks are the leading WHOLE compute blocks whose page ids
    are equal in every member's row and that lie wholly under every
    member's horizon. From each leader the run takes the neighbours that
    make ``blocks x (members - RUN_MIN_MEMBERS + 1)`` largest (a member
    that shares little would cut the blocks of all), and has at least
    RUN_MIN_MEMBERS of them. A member carries the run's blocks and its
    leader's place in the tile; every other token carries 0 blocks."""
    run_lead = np.zeros(num_tokens, np.int32)
    run_blocks = np.zeros(num_tokens, np.int32)
    n = len(rows)
    if n < RUN_MIN_MEMBERS or num_tokens % shards:
        return run_lead, run_blocks, 0
    per_shard = num_tokens // shards
    ppb = block_keys // page
    # Leading blocks a token shares with the candidate before it: equal
    # page ids, and both in the same tile as stream neighbours.
    shard, in_shard = np.divmod(at, per_shard)
    tile = shard * (per_shard // TILE + 1) + in_shard // TILE
    beside = (at[1:] == at[:-1] + 1) & (tile[1:] == tile[:-1])
    if not beside.any():
        return run_lead, run_blocks, 0
    differ = page_table[rows[1:]] != page_table[rows[:-1]]
    same_pages = np.where(
        differ.any(axis=1), differ.argmax(axis=1), page_table.shape[1]
    )
    with_prev = np.where(beside, same_pages // ppb, 0)
    if not with_prev.any():
        return run_lead, run_blocks, 0
    under = kv_lens // block_keys  # whole blocks under a token's horizon
    keys = 0
    i = 0
    while i < n - 1:
        if not with_prev[i]:  # i + 1 shares nothing with i
            i += 1
            continue
        # Candidates i .. end: the stretch that shares something pairwise.
        stop = i + 1
        while stop < n - 1 and with_prev[stop]:
            stop += 1
        blocks = np.minimum(
            np.minimum.accumulate(with_prev[i:stop]),
            np.minimum.accumulate(under[i:stop + 1])[1:],
        )  # of a run i .. i + 1 + k, which has k + 2 members
        members = np.arange(len(blocks)) + 2
        worth = blocks * (members - (RUN_MIN_MEMBERS - 1))
        k = int(worth.argmax())
        if worth[k] <= 0:  # fewer than RUN_MIN_MEMBERS, or no whole block
            i += 1
            continue
        last = i + 1 + k
        run_blocks[at[i]:at[last] + 1] = blocks[k]
        run_lead[at[i]:at[last] + 1] = in_shard[i] % TILE
        keys += int(blocks[k]) * block_keys * int(members[k])
        i = last + 1
    return run_lead, run_blocks, keys
