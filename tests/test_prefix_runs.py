"""Shared-prefix runs: decode rows of one tile whose page-table rows begin
with the same physical pages read those pages once (the host's plan in
``engine/prefix_runs.py``, the kernel's shared pass in
``ops/ragged_paged_attention.py``)."""

import numpy as np
import pytest

from llmd_tpu.engine import prefix_runs
from llmd_tpu.engine.prefix_runs import group_order, plan_runs

PAGE, PPB = 8, 2
S = PAGE * PPB  # keys a compute block in these tests


# --------------------------------------------------------------------- #
# the kernel, interpreted: a plan changes what crosses HBM, not the result


def run_stream(rng, num_pages, max_pages=24):
    """A stream with every kind of tile a plan meets: a 20-token chunk (a
    one-row tile, then a ragged tail that shares its tile with decode rows),
    a group of 5 (one member's horizon ends exactly where the shared blocks
    do), a group of 3, a straggler, six more rows of the first group laid
    ACROSS a tile boundary, a verify row over the second group's pages and
    pad tokens. -> (rows [48], kv_lens [48], decode places, page table)."""
    R = 17
    pt = rng.permutation(num_pages)[: R * max_pages].reshape(R, max_pages).astype(np.int32)
    doc_a, doc_b = pt[0, :12].copy(), pt[5, :8].copy()
    for r in (0, 1, 2, 3, 4, 9, 10, 11, 12, 13, 14):
        pt[r, :12] = doc_a  # 6 blocks
    for r in (5, 6, 7, 15):
        pt[r, :8] = doc_b  # 4 blocks
    T = 48
    rows, kvl = np.full(T, 16, np.int32), np.zeros(T, np.int32)
    rows[:20], kvl[:20] = 16, 31 + np.arange(20)
    horizons = {
        0: 6 * S + 5, 1: 6 * S, 2: 6 * S + 17, 3: 150, 4: 190,  # group a
        5: 4 * S, 6: 4 * S + 3, 7: 100,  # group b
        8: 77,  # shares nothing
        9: 97, 10: 120, 11: 6 * S + 1, 12: 111, 13: 6 * S + 30, 14: 99,  # group a again
    }
    t, places = 20, []
    for r, n in horizons.items():
        rows[t], kvl[t] = r, n
        places.append(t)
        t += 1
    rows[t:t + 4], kvl[t:t + 4] = 15, 70 + np.arange(4)  # a verify row
    return rows, kvl, np.asarray(places), pt


def planned(rows, kvl, places, pt, T, shards=1):
    return plan_runs(pt, rows[places], places, kvl[places], T, S, PAGE, shards=shards)


def test_the_streams_plan_has_the_runs_the_kernel_tests_are_about():
    rows, kvl, places, pt = run_stream(np.random.default_rng(0), 600)
    lead, blocks, keys = planned(rows, kvl, places, pt, 48)
    # tile 1 = tokens 16..31: a run of 5 led from place 4 over 6 blocks, a
    # run of 3 led from place 9 over 4, the straggler, then group a's next
    # three (97 // 16 = 6 whole blocks under the shortest horizon).
    assert blocks[20:32].tolist() == [6] * 5 + [4] * 3 + [0] + [6] * 3
    assert lead[20:32].tolist() == [4] * 5 + [9] * 3 + [0] + [13] * 3
    # tile 2: the same group's other three, a run of their own; the verify
    # row's tokens and the pads are in none.
    assert blocks[32:].tolist() == [6] * 3 + [0] * 13 and lead[32:35].tolist() == [0] * 3
    assert not blocks[:20].any()  # a chunk's tokens never
    assert keys == S * (5 * 6 + 3 * 4 + 3 * 6 + 3 * 6)


@pytest.mark.parametrize("G,D,what,T", [
    (4, 128, "plain", 48), (8, 128, "plain", 48), (16, 128, "plain", 48),
    (8, 256, "plain", 48), (4, 128, "sel", 48), (8, 128, "int8", 48),
    (4, 128, "sinks", 48), (8, 128, "plain", 40), (16, 128, "sel", 40),
])
def test_a_plan_of_runs_leaves_the_result_bit_for_bit(G, D, what, T):
    """Runs on against off, in interpret mode: a member's query row meets
    the same keys in the same blocks in the same order, so the result is
    the run-less pass's to the bit, whatever rides along (a selection mask,
    int8 row planes, sinks) and wherever a tile ends (T = 40: the last tile
    of a dp shard's stream is short, and a run lies in it)."""
    import jax.numpy as jnp

    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(3)
    L, P, K = 2, 600, 2
    rows, kvl, places, pt = run_stream(rng, P)
    rows, kvl, places = rows[:T], kvl[:T], places[places < T]
    lead, blocks, keys = planned(rows, kvl, places, pt, T)
    assert keys > 0 and blocks[32:35].all()
    q = jnp.asarray(rng.normal(size=(T, 1, K * G, D)).astype(np.float32))
    kw = {}
    if what == "int8":
        cache = jnp.asarray(rng.integers(-127, 128, size=(L, P, K, PAGE, 2 * D)).astype(np.int8))
        kw["scales"] = jnp.asarray(
            rng.uniform(0.01, 0.1, size=(L, P, K, PAGE, 2)).astype(np.float16).astype(np.float32)
        )
    else:
        cache = jnp.asarray(rng.normal(size=(L, P, K, PAGE, 2 * D)).astype(np.float32))
    if what == "sinks":
        kw["sinks"] = jnp.asarray(rng.normal(size=(K * G,)).astype(np.float32))
    if what == "sel":
        kw["sel"] = jnp.asarray(rng.random((T, pt.shape[1] * PAGE)) < 0.4)

    def run(runs):
        return np.asarray(flat_paged_attention_full(
            q, cache, jnp.int32(1), jnp.asarray(rows), jnp.asarray(pt), jnp.asarray(kvl),
            interpret=True, pages_per_block=PPB, runs=runs, **kw,
        ))

    off = run(None)
    on = run((jnp.asarray(lead), jnp.asarray(blocks)))
    assert np.isfinite(off).all() and np.abs(off[places]).sum() > 0
    np.testing.assert_array_equal(on, off)
    if (G, what, T) == (8, "plain", 48):
        # ... and a call that carries the operands with no run in them (a
        # step whose decode rows share nothing) is the run-less pass too.
        zeros = jnp.zeros(T, jnp.int32)
        np.testing.assert_array_equal(run((zeros, zeros)), off)


def test_a_runs_members_read_the_leaders_pages():
    """What the shared pass reads is the LEADER's row: with the members' own
    rows pointing at other pages over the run's blocks, the result is as if
    they pointed at the leader's."""
    import jax.numpy as jnp

    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    rng = np.random.default_rng(4)
    L, P, K, G, D = 1, 600, 1, 8, 128
    rows, kvl, places, pt = run_stream(rng, P)
    lead, blocks, _ = planned(rows, kvl, places, pt, 48)
    scrambled = pt.copy()
    for r in (1, 2, 3, 4):  # the first run's members, not its leader
        scrambled[r, :12] = rng.integers(0, P, size=12)
    q = jnp.asarray(rng.normal(size=(48, 1, K * G, D)).astype(np.float32))
    cache = jnp.asarray(rng.normal(size=(L, P, K, PAGE, 2 * D)).astype(np.float32))

    def run(table, runs):
        return np.asarray(flat_paged_attention_full(
            q, cache, jnp.int32(0), jnp.asarray(rows), jnp.asarray(table), jnp.asarray(kvl),
            interpret=True, pages_per_block=PPB, runs=runs,
        ))

    plan = (jnp.asarray(lead), jnp.asarray(blocks))
    np.testing.assert_array_equal(run(scrambled, plan), run(pt, None))
    assert np.abs(run(scrambled, None) - run(pt, None))[21:25].max() > 1e-3


def test_a_stream_split_over_dp_reads_its_runs_inside_a_shards_tiles(monkeypatch):
    """The ``shard`` plan (tokens over dp, heads over tp): the runs split
    with the tokens, planned for a shard's own tiles (here 24 tokens a shard:
    a whole tile and a short one), and the result is the run-less one."""
    import jax
    import jax.numpy as jnp

    from llmd_tpu import ops

    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    mesh = jax.make_mesh((2, 2), ("dp", "tp"), devices=jax.devices()[:4])
    rng = np.random.default_rng(6)
    L, P, K, G, D = 1, 600, 2, 4, 128
    rows, kvl, places, pt = run_stream(rng, P)
    lead, blocks, keys = planned(rows, kvl, places, pt, 48, shards=2)
    # shard 1 opens at token 24, the last of the first group's five: the
    # four before it are a run of shard 0's short tile, it is in none
    assert blocks[20:35].tolist() == [6] * 4 + [0] + [4] * 3 + [0] + [6] * 6
    assert lead[20:35].tolist() == [4] * 4 + [0] + [1] * 3 + [0] + [5] * 6
    q = jnp.asarray(rng.normal(size=(48, 1, K * G, D)).astype(np.float32))
    cache = jnp.asarray(rng.normal(size=(L, P, K, PAGE, 2 * D)).astype(np.float32))
    monkeypatch.setattr(
        ops, "flat_paged_attention_full",
        lambda *a, **kw: ops.ragged_paged_attention.flat_paged_attention_full(
            *a, **{**kw, "pages_per_block": PPB}),
    )

    def run(runs):
        plans = {}
        with ops.record_plans(plans):
            out = jax.jit(lambda q, c, r, t, kl, rn: ops.paged_attention_full_flat(
                q, c, jnp.int32(0), r, t, kl, (kl - 1)[:, None], world_size=4,
                mesh=mesh, runs=rn,
            ))(q, cache, jnp.asarray(rows), jnp.asarray(pt), jnp.asarray(kvl), runs)
        assert plans["flat_attention"] == {"pallas_shard"}
        return np.asarray(out)

    np.testing.assert_array_equal(
        run((jnp.asarray(lead), jnp.asarray(blocks))), run(None)
    )


def test_a_call_with_a_window_takes_no_runs():
    import jax.numpy as jnp

    from llmd_tpu.ops.ragged_paged_attention import flat_paged_attention_full

    z = jnp.zeros(16, jnp.int32)
    with pytest.raises(AssertionError, match="window"):
        flat_paged_attention_full(
            jnp.zeros((16, 1, 4, 128)), jnp.zeros((1, 8, 1, PAGE, 256)), jnp.int32(0), z,
            jnp.zeros((2, 4), jnp.int32), z, interpret=True, pages_per_block=PPB,
            window=jnp.int32(8), runs=(z, z),
        )


# --------------------------------------------------------------------- #
# the host's plan alone


def table(n, max_pages=16):
    return (1000 + np.arange(n * max_pages)).reshape(n, max_pages).astype(np.int32)


def plan(pt, horizons, first=0, T=32, rows=None, **kw):
    n = len(horizons)
    rows = np.arange(n) if rows is None else np.asarray(rows)
    at = first + np.arange(n)
    return plan_runs(pt, rows, at, np.asarray(horizons), T, S, PAGE, **kw)


def test_rows_that_start_on_the_same_page_are_laid_side_by_side():
    assert group_order([7, 3, 7, 9, 3, 7]).tolist() == [0, 2, 5, 1, 4, 3]
    assert group_order([4, 5, 6]).tolist() == [0, 1, 2]  # nothing shared: as they came
    assert group_order([]).tolist() == [] and group_order([3]).tolist() == [0]


def test_a_run_needs_three_members_and_counts_whole_blocks_only():
    pt = table(6)
    pt[1:4, :5] = pt[0, :5]  # rows 0-3 share 5 pages = 2 blocks and a page
    lead, blocks, keys = plan(pt, [100, 90, 80, 70, 60, 50])
    assert blocks[:6].tolist() == [2, 2, 2, 2, 0, 0] and lead[:4].tolist() == [0] * 4
    assert keys == 4 * 2 * S
    # two rows that share are no run (a shared pass costs two tokens' passes)
    pt = table(4)
    pt[1, :8] = pt[0, :8]
    assert plan(pt, [100] * 4)[2] == 0
    assert prefix_runs.RUN_MIN_MEMBERS == 3
    # one shared page is no block
    pt = table(4)
    pt[1:, :1] = pt[0, :1]
    assert plan(pt, [100] * 4)[2] == 0


def test_a_runs_blocks_lie_under_every_members_horizon():
    pt = table(4)
    pt[1:, :12] = pt[0, :12]  # 6 blocks shared
    lead, blocks, keys = plan(pt, [6 * S + 3, 6 * S, 4 * S + 15, 6 * S + 9])
    assert blocks[:4].tolist() == [4] * 4  # the third row's horizon holds 4 whole blocks
    # a member that shares or sees little would cut the blocks of all: the
    # run ends in front of it where that reads more keys once
    lead, blocks, keys = plan(pt, [6 * S + 3, 6 * S, 6 * S + 15, S + 1])
    assert blocks[:4].tolist() == [6, 6, 6, 0] and keys == 3 * 6 * S


def test_a_member_that_shares_less_ends_the_run_where_more_is_read_once():
    pt = table(7)
    pt[1:3, :12] = pt[0, :12]   # rows 0-2 share 6 blocks
    pt[3:6, :12] = pt[3, :12]   # rows 3-5 share 6 blocks of another document
    pt[3:6, :2] = pt[0, :2]     # and every row the same first block (a system prompt)
    lead, blocks, _ = plan(pt, [200] * 7, first=2)
    assert blocks[2:9].tolist() == [6, 6, 6, 6, 6, 6, 0]
    assert lead[2:9].tolist() == [2, 2, 2, 5, 5, 5, 0]


def test_runs_stay_inside_a_tile_and_a_shards_tiles_are_its_own():
    pt = table(8)
    pt[1:, :8] = pt[0, :8]
    # places 12..19: tile 0 takes four, tile 1 four, led from their own places
    lead, blocks, _ = plan(pt, [100] * 8, first=12)
    assert blocks[12:20].tolist() == [4] * 8
    assert lead[12:20].tolist() == [12] * 4 + [0] * 4
    # two shards of 24 tokens: tiles [0, 16) [16, 24) | [24, 40) [40, 48)
    lead, blocks, _ = plan(pt, [100] * 8, first=20, T=48, shards=2)
    assert blocks[20:28].tolist() == [4] * 4 + [4] * 4
    assert lead[20:28].tolist() == [4] * 4 + [0] * 4  # place 20 is the 4th of the short tile; 24 opens shard 1
    # a stream that does not split evenly is not sharded: no plan
    assert plan(pt, [100] * 8, T=40, shards=3)[2] == 0


def test_tokens_apart_in_the_stream_form_no_run():
    pt = table(6)
    pt[1:, :8] = pt[0, :8]
    at = np.asarray([0, 1, 3, 4, 5, 7])  # 2 and 6 are another kind of token
    lead, blocks, _ = plan_runs(pt, np.arange(6), at, np.full(6, 100), 16, S, PAGE)
    assert blocks[:8].tolist() == [0, 0, 0, 4, 4, 4, 0, 0]  # 0 and 1 are two: no run
    assert lead[3:6].tolist() == [3, 3, 3]


# --------------------------------------------------------------------- #
# the engine: sessions over shared documents


def _sessions(eng, seed=5):
    """A closed loop of the benchmark's ``sessions`` rehearsal scripts (the
    groups, turns and lengths of ``perfbench/traffic/long-doc-sessions.json``
    at rehearsal size) over documents long enough to hold whole blocks at
    the tests' page: six clients, each sends its next turn with the history
    carried when the last one has finished. -> {(script, turn): (token ids,
    log-probs)}."""
    import json
    import pathlib

    from llmd_tpu.engine.request import SamplingParams
    from perfbench.generators.sessions import scripts

    root = pathlib.Path(__file__).resolve().parent.parent
    mix = json.loads((root / "perfbench/traffic/long-doc-sessions.json").read_text())["rehearse"]
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, 256, size=300).tolist() for _ in range(mix["groups"])]
    todo = list(enumerate(scripts(mix)[:9]))
    live, out = {}, {}

    def send(k, group, turns, t, context):
        q_len, a_len = turns[t]
        context = context + rng.integers(0, 256, size=q_len).tolist()
        rid = eng.add_request(context, SamplingParams(
            temperature=0.0, max_tokens=a_len, ignore_eos=True, logprobs=True))
        live[rid] = (k, group, turns, t, context, eng.scheduler.waiting[-1], [])

    def next_session():
        if todo:
            k, (group, turns) = todo.pop(0)
            send(k, group, turns, 0, docs[group])

    for _ in range(6):
        next_session()
    while live:
        for o in eng.step():
            live[o.request_id][-1].extend(o.new_token_ids)
            if not o.finished:
                continue
            k, group, turns, t, context, req, answer = live.pop(o.request_id)
            out[k, t] = (answer, list(req.output_logprobs))
            if t + 1 < len(turns):
                send(k, group, turns, t + 1, context + answer)
            else:
                next_session()
    return out


def test_sessions_over_shared_documents_decode_as_without_runs(monkeypatch):
    """Greedy token ids and log-probs of a sessions mix, with the Pallas
    kernels interpreted: the tree (decode rows laid group by group, runs
    planned) against the same engine with no run planned, rows reordered or
    not. The counters say that the first did read through runs."""
    from test_ragged_step import make_engine

    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    kw = dict(page=8, num_blocks=400, max_batched=48, max_seqs=8, max_model_len=640,
              head_dim=128, num_heads=2, num_kv_heads=1, async_s=True)
    eng = make_engine(True, **kw)
    assert eng.runner._plans_runs
    with_runs = _sessions(eng)
    stats = eng.stats
    assert stats.attn_decode_keys_total > 0
    assert stats.attn_prefix_run_keys_total > 0.3 * stats.attn_decode_keys_total
    assert eng.runner.kernel_plans["flat_attention"] == {"pallas"}

    def no_runs(pt, rows, at, kv_lens, T, *a, **k):
        return np.zeros(T, np.int32), np.zeros(T, np.int32), 0

    monkeypatch.setattr(prefix_runs, "plan_runs", no_runs)
    grouped = make_engine(True, **kw)
    without = _sessions(grouped)
    assert grouped.stats.attn_prefix_run_keys_total == 0
    monkeypatch.setattr(prefix_runs, "group_order", lambda first: np.arange(len(first)))
    as_they_came = _sessions(make_engine(True, **kw))
    assert with_runs.keys() == without.keys() == as_they_came.keys() and len(with_runs) > 12
    for key, (toks, logps) in with_runs.items():
        for other in (without, as_they_came):
            assert toks == other[key][0], key
            np.testing.assert_allclose(logps, other[key][1], atol=1e-5, rtol=1e-5)
