"""The gated delta rule over the state pool (``llmd_tpu/ops/gdn.py``): the
decode rows' update (XLA, and the Pallas kernel in interpret mode) and the
prefill rows' chunked scan, each against the recurrence itself, token by token:

    S' = exp(g_t) S;  d = beta_t (v_t - S'^T k_t);  S = S' + k_t d^T;  o_t = S^T q_t

over RAGGED steps: a segment split over steps and over rows, a fresh segment in
a slot that held another sequence's state, a restart from a snapshot (a copy of
a slot), rows of 1-64 tokens, pad rows behind them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu.models import gdn as gdn_model
from llmd_tpu.models.registry import get_model_config
from llmd_tpu.ops import gdn, ssm

H, DK, DV = 4, 8, 8
CAP = 64  # the flat step's row
SLOTS = 6  # the last one is the scan's scratch


def draw(seed, t):
    """(q, k, v, g, beta) of ``t`` tokens as the mixer hands them over."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = gdn_model.l2norm(jax.random.normal(ks[0], (t, H, DK))) * DK ** -0.5
    k = gdn_model.l2norm(jax.random.normal(ks[1], (t, H, DK)))
    v = jax.random.normal(ks[2], (t, H, DV))
    # Heads that forget in a few tokens and heads that hardly forget.
    g = -jnp.exp(jnp.linspace(-6.0, 1.0, H))[None, :] * jax.nn.softplus(jax.random.normal(ks[3], (t, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))
    return tuple(np.asarray(a, np.float32) for a in (q, k, v, g, beta))


def recurrence(s, q, k, v, g, beta):
    """(state after the tokens, outputs [t, H, DV]) from state ``s``, in float64."""
    s = np.asarray(s, np.float64)
    out = []
    for q_t, k_t, v_t, g_t, b_t in zip(*(np.asarray(a, np.float64) for a in (q, k, v, g, beta))):
        s = np.exp(g_t)[:, None, None] * s
        d = b_t[:, None] * (v_t - np.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        out.append(np.einsum("hkv,hk->hv", s, q_t))
    return s, np.asarray(out)


def pack(rows_spec, t_bucket, n_rows):
    """``StateRows`` of a step: ``rows_spec`` = [(slot, tokens, first position,
    kind)], in stream order, pad rows behind."""
    slot = np.zeros(n_rows, np.int32)
    start = np.full(n_rows, sum(r[1] for r in rows_spec), np.int32)
    qlen, pos0, kind = (np.zeros(n_rows, np.int32) for _ in range(3))
    t = 0
    for i, (s, n, p, kd) in enumerate(rows_spec):
        slot[i], start[i], qlen[i], pos0[i], kind[i] = s, t, n, p, kd
        t += n
    ends = jnp.asarray(start + qlen)
    tok = jnp.arange(t_bucket)
    row_of = jnp.clip(jnp.searchsorted(ends, tok, side="right"), 0, n_rows - 1).astype(jnp.int32)
    return ssm.state_rows(*(jnp.asarray(a) for a in (slot, start, qlen, pos0, kind)), row_of, tok < t)


def noise_pool(seed=9):
    # Whatever a slot held before is not the new owner's: start from noise.
    return jax.random.normal(jax.random.key(seed), (2, SLOTS, H, DK, DV), jnp.float32)


def padded(arrays, t_bucket):
    return tuple(jnp.asarray(np.concatenate([a, np.zeros((t_bucket - len(a), *a.shape[1:]), a.dtype)])) for a in arrays)


def step(pool, layer, rows_spec, inputs, plan, t_bucket=None, n_rows=None):
    """One flat step of one layer: update, then scan. ``inputs``: the stream's
    (q, k, v, g, beta). Returns (pool, y [tokens, H, DV])."""
    n = len(inputs[0])
    t_bucket = t_bucket or -(-n // 16) * 16
    rows = pack(rows_spec, t_bucket, n_rows or len(rows_spec) + 2)
    q, k, v, g, beta = padded(inputs, t_bucket)
    live = rows.live[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    pool, y = gdn.gdn_update(pool, jnp.int32(layer), rows, q, k, v, g, beta, plan)
    pool, y = gdn.gdn_scan(pool, jnp.int32(layer), rows, q, k, v, g, beta, y, CAP, plan)
    return pool, np.asarray(y)[:n]


PLANS = ("xla", "interpret")


@pytest.mark.parametrize("plan", PLANS)
def test_decode_rows_update_their_slots_and_no_other(plan):
    """Five decode rows of five slots in one step (one of them at position 0: a
    fresh start in a slot full of noise), pad rows behind: each slot's new
    state and output are the recurrence's one step; the untouched slot, the
    scratch slot and the other layer's plane keep every bit."""
    pool = noise_pool()
    inputs = draw(1, 5)
    spec = [(3, 1, 17, 1), (0, 1, 0, 1), (4, 1, 5, 1), (1, 1, 900, 1), (2, 1, 1, 1)]
    new, y = step(pool, 1, spec, inputs, plan, n_rows=8)
    for i, (slot, _n, pos, _k) in enumerate(spec):
        s0 = np.zeros((H, DK, DV)) if pos == 0 else pool[1, slot]
        want_s, want_y = recurrence(s0, *(a[i:i + 1] for a in inputs))
        np.testing.assert_allclose(new[1, slot], want_s, atol=2e-5)
        np.testing.assert_allclose(y[i], want_y[0], atol=2e-5)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, SLOTS - 1], pool[1, SLOTS - 1])


@pytest.mark.parametrize("plan", PLANS)
def test_a_step_without_decode_rows_leaves_the_pool_as_it_was(plan):
    pool = noise_pool()
    rows = pack([], 16, 4)
    z = jnp.zeros((16, H, DK))
    new, y = gdn.gdn_update(pool, jnp.int32(0), rows, z, z, z, jnp.zeros((16, H)), jnp.zeros((16, H)), plan)
    np.testing.assert_array_equal(new, pool)
    assert not np.any(np.asarray(y))


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("lens", [(1,), (64,), (63, 1), (64, 64, 7), (5, 64, 2, 33)], ids=str)
def test_a_fresh_segment_of_rows_equals_the_recurrence(plan, lens):
    """One prefill chunk from position 0, cut into rows of 1-64 tokens, in a
    slot that held noise: outputs of every token and the state the slot is
    left with."""
    n = sum(lens)
    inputs = draw(2, n)
    pos, spec = 0, []
    for ln in lens:
        spec.append((2, ln, pos, 0))
        pos += ln
    new, y = step(noise_pool(), 0, spec, inputs, plan)
    want_s, want_y = recurrence(np.zeros((H, DK, DV)), *inputs)
    np.testing.assert_allclose(y, want_y, atol=5e-5)
    np.testing.assert_allclose(new[0, 2], want_s, atol=5e-5)


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("split", [1, 16, 63, 64, 65, 100])
def test_a_segment_split_over_steps_carries_its_state_through_the_slot(plan, split):
    """A prompt of 130 tokens prefilled in two steps, cut at ``split``: the
    second step enters with what the first left in the slot. A decode row of
    another slot rides in the second step."""
    n = 130
    inputs = draw(3, n)
    other = draw(4, 1)
    pool = noise_pool()

    def rows_of(first, count):
        out, pos = [], first
        while count:
            ln = min(CAP, count)
            out.append((1, ln, pos, 0))
            pos, count = pos + ln, count - ln
        return out

    pool1, y1 = step(pool, 0, rows_of(0, split), tuple(a[:split] for a in inputs), plan)
    second = tuple(np.concatenate([o, a[split:]]) for o, a in zip(other, inputs))
    pool2, y2 = step(pool1, 0, [(4, 1, 33, 1)] + rows_of(split, n - split), second, plan)
    want_s, want_y = recurrence(np.zeros((H, DK, DV)), *inputs)
    np.testing.assert_allclose(np.concatenate([y1, y2[1:]]), want_y, atol=1e-4)
    np.testing.assert_allclose(pool2[0, 1], want_s, atol=1e-4)
    other_s, other_y = recurrence(pool[0, 4], *other)
    np.testing.assert_allclose(pool2[0, 4], other_s, atol=2e-5)
    np.testing.assert_allclose(y2[0], other_y[0], atol=2e-5)


@pytest.mark.parametrize("plan", PLANS)
def test_a_restart_from_a_snapshot_continues_where_the_snapshot_was_taken(plan):
    """Slot 0 runs 48 tokens; its state is copied into slot 3 (a snapshot, as
    the engine copies ``[:, src] -> [:, dst]``); slot 0 goes on with its own
    tokens; later a NEW sequence is seeded from the snapshot into slot 2 and
    scans 20 other tokens from position 48: it equals the recurrence over the
    48 + 20, and the snapshot keeps every bit."""
    head, tail, mine = draw(5, 48), draw(6, 20), draw(7, 9)
    pool, _ = step(noise_pool(), 0, [(0, 48, 0, 0)], head, plan)
    pool = pool.at[:, 3].set(pool[:, 0])
    snap = np.asarray(pool[0, 3])
    pool, _ = step(pool, 0, [(0, 9, 48, 0)], mine, plan)
    pool = pool.at[:, 2].set(pool[:, 3])
    pool, y = step(pool, 0, [(2, 20, 48, 0)], tail, plan)
    want_s, want_y = recurrence(np.zeros((H, DK, DV)), *(np.concatenate([a, b]) for a, b in zip(head, tail)))
    np.testing.assert_allclose(y, want_y[48:], atol=1e-4)
    np.testing.assert_allclose(pool[0, 2], want_s, atol=1e-4)
    np.testing.assert_array_equal(pool[0, 3], snap)


@pytest.mark.parametrize("n", [2, 3, 64])
def test_the_series_inverts_a_unit_lower_triangular_matrix(n):
    # Entries as beta (k_l . k_s) has them: a tenth of a unit.
    a = np.tril(0.1 * np.random.default_rng(n).normal(size=(3, n, n)), -1).astype(np.float32)
    inv = np.asarray(gdn.unit_lower_inverse(jnp.asarray(-a)))  # (I + a)^-1
    np.testing.assert_allclose(inv @ (np.eye(n) + a), np.broadcast_to(np.eye(n), a.shape), atol=1e-5)


def test_the_published_projection_order_maps_onto_the_stored_blocks():
    """``in_proj_qkvz`` per KEY head is (q[Dk], k[Dk], v[R Dv], z[R Dv]) and
    ``in_proj_ba`` (b[R], a[R]); HF splits them so, head by head. The stored
    leaves hold the same columns as blocks."""
    cfg = get_model_config("tiny-qwen3-next")
    hk, hv, dk, dv = cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r = hv // hk
    rng = np.random.default_rng(0)
    w_qkvz = rng.normal(size=(cfg.hidden_size, hk * (2 * dk + 2 * r * dv))).astype(np.float32)
    w_ba = rng.normal(size=(cfg.hidden_size, hk * 2 * r)).astype(np.float32)
    x = rng.normal(size=(3, cfg.hidden_size)).astype(np.float32)
    # HF's fix_query_key_value_ordering on the projection's output.
    mixed = (x @ w_qkvz).reshape(3, hk, 2 * dk + 2 * r * dv)
    q, k, v, z = np.split(mixed, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (x @ w_ba).reshape(3, hk, 2 * r)
    b, a = ba[..., :r].reshape(3, hv), ba[..., r:].reshape(3, hv)
    g_in, g_ba = gdn_model.from_published(jnp.asarray(w_qkvz), jnp.asarray(w_ba), cfg)
    got, got_ba = x @ np.asarray(g_in), x @ np.asarray(g_ba)
    want = np.concatenate([q.reshape(3, -1), k.reshape(3, -1), v.reshape(3, -1), z.reshape(3, -1)], axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got_ba, np.concatenate([b, a], axis=-1), atol=1e-5)


def test_a_head_block_of_the_pool_fits_the_kernels_memory():
    assert gdn.head_block(32, 128, 128) == 16  # 1 MiB a block: four of them in VMEM
    assert gdn.head_block(4, 8, 8) == 4
    assert gdn.head_block(3, 8, 8) == 1
