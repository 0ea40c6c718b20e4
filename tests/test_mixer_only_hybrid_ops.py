"""The layers of ``tiny-nemotron-h`` one at a time (``tests/test_mixer_only_hybrid.py``
holds the model and the engine; two files, so that the test run's workers share
them): the state update kernel and the prefill scan with B and C in groups, the
gated norm over each group's channels and the non-gated relu^2 experts, against
the plain reference of ``perfbench/references/mamba2_gqa_relu2_moe_share.py``
and the token-by-token recurrence.
"""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from llmd_tpu.models import llama, moe  # noqa: E402
from llmd_tpu.models.common import rms_norm  # noqa: E402
from llmd_tpu.models.registry import get_model_config  # noqa: E402
from llmd_tpu.ops import ssm  # noqa: E402
from perfbench.references import _common as rc  # noqa: E402
from perfbench.references import mamba2_gqa_relu2_moe_share as ref  # noqa: E402

CONF = json.loads((ROOT / "perfbench" / "configs" / "nemotron-3-nano-30b-a3b.1chip.json").read_text())
PUBLISHED = CONF["rehearse"]["published"]  # what the benchmark's rehearsal hands the reference


# --- the grouped state kernels against the token-by-token recurrence --------------


def _recurrence(h0, a, dtx, b, c):
    """One token: H = a H + dtx (x) b_group, y = H . c_group. ``b``, ``c`` [G, N]."""
    H = h0.shape[0]
    bh, ch = (np.repeat(v, H // v.shape[0], axis=0) for v in (b, c))
    h = h0 * a[:, None, None] + dtx[:, :, None] * bh[:, None, :]
    return h, np.sum(h * ch[:, None, :], axis=-1)


@pytest.mark.parametrize("H,G", [(4, 1), (4, 2), (8, 8), (64, 8), (16, 2), (64, 4)])
def test_the_grouped_update_kernel_equals_the_recurrence(H, G):
    """The Pallas decode update in interpret mode with B and C in G groups:
    H 64 / G 8 is a head block of 32 that spans four groups; H 16 / G 2 a
    block inside one group; G 1 the one-group operand."""
    ks = jax.random.split(jax.random.key(H * 10 + G), 6)
    L, S, P, N, U, count = 2, 6, 8, 16, 4, 3
    pool = jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32)
    slots = jax.random.permutation(ks[1], S)[:U].astype(jnp.int32)
    a, dtx = jax.random.uniform(ks[2], (U, H)), jax.random.normal(ks[3], (U, H, P))
    b, c = jax.random.normal(ks[4], (U, G, N)), jax.random.normal(ks[5], (U, G, N))
    bc = (b[:, 0], c[:, 0]) if G == 1 else (b, c)
    got_pool, got_y = ssm.ssm_update_pallas(pool, jnp.int32(1), slots, jnp.int32(count), a, dtx, *bc, interpret=True)
    xla_pool, xla_y = ssm.ssm_update_xla(pool, jnp.int32(1), slots, jnp.int32(count), a, dtx, *bc)
    want_pool = np.asarray(pool).copy()
    for u in range(count):
        s = int(slots[u])
        want_pool[1, s], y = _recurrence(np.asarray(pool[1, s]), *(np.asarray(v[u]) for v in (a, dtx, b, c)))
        np.testing.assert_allclose(got_y[u], y, atol=1e-5)
        np.testing.assert_allclose(xla_y[u], y, atol=1e-5)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-5)
    np.testing.assert_allclose(xla_pool, want_pool, atol=1e-5)


# The update kernel's three forms (``ssm_update_pallas`` reads them off its
# operands): H 64 heads in two head blocks of 32.
FORMS = {
    "one-group": 0,                # b, c [U, N]
    "groups-spanning-a-block": 8,  # [U, 8, N]: a block of 32 heads spans four groups
    "groups-inside-a-block": 2,    # [U, 2, N]: a block lies inside one group
}
# (count, the row whose decay is 0, the entries behind ``count`` name a LIVE slot again)
SITUATIONS = {
    "count-0": (0, None, False),
    "count-below-U": (3, None, False),
    "count-U": (5, None, False),
    "a-fresh-row": (3, 1, False),
    "a-repeated-slot-behind-count": (3, None, True),
}


@pytest.mark.parametrize("situation", SITUATIONS)
@pytest.mark.parametrize("form", FORMS)
def test_the_update_kernel_leaves_the_xla_forms_pool_bit_for_bit(form, situation):
    """``ssm_update_pallas`` in interpret mode against ``ssm_update_xla``.

    The POOL is compared bit for bit: the state's multiply-add ``a H + dt x (x)
    b`` is float32 on the vector unit in both, and the inputs here are rounded
    to bfloat16's 8 significant bits, so that both products are EXACT in
    float32 and the one rounding left is the add's: a CPU that fuses the
    multiply into the add in one form and not in the other still gives the same
    bits, and a state or product kept narrower than float32 does not. Live
    entries equal the XLA form's, every other slot is as it was.

    ``y = H . c`` is a sum of N float32 products that the kernel takes in
    another order (the matrix unit at ``HIGHEST``; here XLA's dot): each side
    is within ``N u sum|H c|`` of the true sum (u = 2^-24), so they differ by
    at most twice that; the limit is four times, element by element."""
    G = FORMS[form]
    count, fresh, repeated = SITUATIONS[situation]
    ks = jax.random.split(jax.random.key(7 + G), 6)
    L, S, H, P, N, U = 2, 7, 64, 8, 16, 5
    short = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    pool = short(jax.random.normal(ks[0], (L, S, H, P, N), jnp.float32))
    slots = jax.random.permutation(ks[1], S - 1)[:U].astype(jnp.int32)
    if repeated:
        slots = slots.at[count:].set(slots[1])
    a = short(jax.random.uniform(ks[2], (U, H)))
    if fresh is not None:
        a = a.at[fresh].set(0.0)
    dtx = short(jax.random.normal(ks[3], (U, H, P)))
    bc = (U, G, N) if G else (U, N)
    b, c = short(jax.random.normal(ks[4], bc)), short(jax.random.normal(ks[5], bc))
    args = (pool, jnp.int32(1), slots, jnp.int32(count), a, dtx, b, c)
    want_pool, want_y = ssm.ssm_update_xla(*args)
    got_pool, got_y = ssm.ssm_update_pallas(*args, interpret=True)
    assert got_pool.dtype == jnp.float32 and got_y.dtype == jnp.float32 and got_y.shape == (U, H, P)
    np.testing.assert_array_equal(got_pool, want_pool)
    live = np.asarray(slots[:count]).tolist()
    untouched = [s for s in range(S) if s not in live]
    np.testing.assert_array_equal(got_pool[1, untouched], pool[1, untouched])
    np.testing.assert_array_equal(got_pool[0], pool[0])
    if fresh is not None:  # a fresh row's state is dt x (x) b alone, whatever the slot held
        bh = np.repeat(np.asarray(b[fresh]), H // G, axis=0)[:, None, :] if G else np.asarray(b[fresh])
        np.testing.assert_array_equal(got_pool[1, slots[fresh]], np.asarray(dtx[fresh])[..., None] * bh)
    if count:
        h = np.asarray(want_pool[1][slots[:count]], np.float64)
        ch = np.asarray(jnp.repeat(c, H // G, axis=1) if G else c[:, None, :], np.float64)[:count, :, None, :]
        limit = 4 * N * 2.0**-24 * np.sum(np.abs(h * ch), axis=-1)
        assert np.all(np.abs(np.asarray(got_y[:count]) - np.asarray(want_y[:count])) <= limit)


def test_a_head_block_is_whole_groups_or_lies_inside_one():
    assert ssm._head_block(64) == ssm._head_block(128) == 32  # one group: as it was
    assert ssm._head_block(64, 8) == 32 and ssm._head_block(16, 8) == 16 and ssm._head_block(4, 2) == 4
    assert ssm._head_block(96, 12) == 96  # 32, 16, 8 neither hold nor divide 12 heads
    b = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
    blocks = ssm._block_groups(b, 2, 32, 8)  # H 64: two head blocks of four groups
    np.testing.assert_array_equal(blocks, b.reshape(2, 2, 4, 3))
    inside = ssm._block_groups(b[:, :2], 4, 4, 8)  # H 16, G 2, blocks of 4 heads: two a group
    np.testing.assert_array_equal(inside[:, :, 0], b[:, [0, 0, 1, 1]])


def _mixer_case(G, heads=8, seed=3):
    model = get_model_config("tiny-nemotron-h", mamba_n_heads=heads, mamba_n_groups=G)
    pub = dict(PUBLISHED, mamba_num_heads=heads, n_groups=G)
    params = llama.init_params(model, jax.random.key(seed))
    lp = {**jax.tree.map(lambda a: a[0], params["mamba_layers"]), "input_norm": params["layers"]["input_norm"][0]}
    return model, pub, params, lp


T_BUCKET, ROWS, CAP = 48, 8, 16


def _flat_step(segments):
    slot, start, qlen, pos0, kind, t = [], [], [], [], [], 0
    for s, p0, n, k in segments:
        for off in range(0, n, CAP):
            w = min(CAP, n - off)
            slot.append(s), start.append(t), qlen.append(w), pos0.append(p0 + off), kind.append(k)
            t += w
    pad = ROWS - len(slot)
    i32 = lambda a, fill: jnp.asarray(a + [fill] * pad, jnp.int32)  # noqa: E731
    return i32(slot, 0), i32(start, t), i32(qlen, 0), i32(pos0, 0), i32(kind, 0)


def _mix_step(model, lp, pool, x, slot, start, qlen, pos0, kind):
    from llmd_tpu.models import mamba

    t = jnp.arange(T_BUCKET)
    ends = start + qlen
    row_of = jnp.clip(jnp.searchsorted(ends, t, side="right"), 0, ROWS - 1).astype(jnp.int32)
    rows = ssm.state_rows(slot, start, qlen, pos0, kind, row_of, t < ends[-1])
    h = rms_norm(x, lp["input_norm"], model.rms_norm_eps)[:, None, :]
    out, pool = mamba.mix(h, lp, pool, jnp.int32(0), rows, model, None, row_cap=CAP)
    return x + out[:, 0], pool


@pytest.mark.parametrize("G", [1, 2, 8])
@pytest.mark.parametrize("split", [1, 15, 16, 17, 33])
def test_the_grouped_scan_and_norm_equal_the_recurrence(G, split, monkeypatch):
    """A 40-token prompt prefilled as [0, split) and [split, 40), then one
    decode token, through the mixer with B and C in G groups and the gated
    norm over each group's channels, the Pallas parts interpreted: the
    reference's token-by-token recurrence."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    model, pub, params, lp = _mixer_case(G)
    x = jax.random.normal(jax.random.key(4), (41, model.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._mamba(params["layers"], params["mamba_layers"], jnp.int32(0), jnp.int32(0), x,
                                     rc.freeze(pub, ref.KEYS)))
    pool = ssm.StatePool(
        ssm=jax.random.normal(jax.random.key(9), (1, 5, model.mamba_n_heads, model.mamba_d_head, model.mamba_d_state)),
        conv=jax.random.normal(jax.random.key(8), (1, 5, model.mamba_d_conv - 1, model.mamba_conv_dim)),
    )
    pad = lambda a: jnp.concatenate([a, jnp.zeros((T_BUCKET - a.shape[0], a.shape[1]))])  # noqa: E731
    y1, pool = _mix_step(model, lp, pool, pad(x[:split]), *_flat_step([(1, 0, split, ssm.KIND_PREFILL)]))
    y2, pool = _mix_step(model, lp, pool, pad(x[split:40]), *_flat_step([(1, split, 40 - split, ssm.KIND_PREFILL)]))
    y3, pool = _mix_step(model, lp, pool, pad(x[40:]), *_flat_step([(1, 40, 1, ssm.KIND_DECODE)]))
    got = np.concatenate([y1[:split], y2[:40 - split], y3[:1]])
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("plan", ["xla", "interpret"])
def test_one_group_through_the_grouped_branches_equals_the_one_group_branches(plan):
    """``ssm_scan``, ``ssm_update_xla`` and ``ssm_update_pallas`` hold the
    row arithmetic twice: ``Bm`` [T, N] takes the one-group branch (granite's
    programs, kept as they were) and [T, G, N] the grouped one. At G = 1 the
    two must agree, so that they cannot drift apart (ROADMAP M4: the fold)."""
    H, P, N, T = 8, 4, 16, T_BUCKET
    ks = jax.random.split(jax.random.key(11), 7)
    pool = jax.random.normal(ks[0], (1, 5, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (T, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (T, H)))
    dA = -dt * jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm, Cm = jax.random.normal(ks[4], (T, N)), jax.random.normal(ks[5], (T, N))
    # a prefill chunk of 33 tokens in rows of 16 behind position 7, and two decode rows
    seg = [(1, 7, 33, ssm.KIND_PREFILL), (2, 50, 1, ssm.KIND_DECODE), (3, 9, 1, ssm.KIND_DECODE)]
    slot, start, qlen, pos0, kind = _flat_step(seg)
    t, ends = jnp.arange(T), start + qlen
    row_of = jnp.clip(jnp.searchsorted(ends, t, side="right"), 0, ROWS - 1).astype(jnp.int32)
    rows = ssm.state_rows(slot, start, qlen, pos0, kind, row_of, t < ends[-1])
    with jax.default_matmul_precision("highest"):
        one = ssm.ssm_update(pool, jnp.int32(0), rows, x, dt, dA, Bm, Cm, plan)
        grp = ssm.ssm_update(pool, jnp.int32(0), rows, x, dt, dA, Bm[:, None], Cm[:, None], plan)
        np.testing.assert_allclose(grp[0], one[0], atol=1e-6)
        np.testing.assert_allclose(grp[1], one[1], atol=1e-6)
        assert float(jnp.abs(one[0] - pool).max()) > 0.1  # the decode rows' slots moved
        one = ssm.ssm_scan(*one[:1], jnp.int32(0), rows, x, dt, dA, Bm, Cm, one[1], CAP, plan)
        grp = ssm.ssm_scan(*grp[:1], jnp.int32(0), rows, x, dt, dA, Bm[:, None], Cm[:, None], grp[1], CAP, plan)
    for a, b in zip(jax.tree.leaves(grp), jax.tree.leaves(one)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_gated_norm_runs_over_each_groups_channels():
    """G = 2 is not the norm over all channels at once (the reference's
    wrong-on-purpose control), and the program follows the grouped one."""
    model, pub, params, lp = _mixer_case(2)
    x = jax.random.normal(jax.random.key(5), (9, model.hidden_size), jnp.float32)
    args = (params["layers"], params["mamba_layers"], jnp.int32(0), jnp.int32(0), x)
    with jax.default_matmul_precision("highest"):
        grouped = np.asarray(ref._mamba(*args, rc.freeze(pub, ref.KEYS)))
        whole = np.asarray(ref._mamba(*args, rc.freeze(dict(pub, probe_norm_whole=True), ref.KEYS)))
        one = np.asarray(ref._mamba(*args, rc.freeze(dict(pub, probe_one_group=True), ref.KEYS)))
    assert np.abs(grouped - whole).max() > 1e-3 and np.abs(grouped - one).max() > 1e-3
    pool = ssm.StatePool(ssm=jnp.zeros((1, 3, 8, 8, 16)), conv=jnp.zeros((1, 3, 3, model.mamba_conv_dim)))
    pad = jnp.concatenate([x, jnp.zeros((T_BUCKET - 9, model.hidden_size))])
    got, _ = _mix_step(model, lp, pool, pad, *_flat_step([(1, 0, 9, ssm.KIND_PREFILL)]))
    np.testing.assert_allclose(got[:9], grouped, atol=3e-5)


# --- the non-gated experts ----------------------------------------------------------


@pytest.mark.parametrize("width", [72, 200])
def test_the_non_gated_grouped_experts_equal_the_dense_combine(width, monkeypatch):
    """The grouped kernel (interpreted) over experts of a width that is no
    multiple of 128, stored padded: two grouped matmuls with relu^2 between
    them give the dense combine's sum, the held share and the shared expert
    included."""
    monkeypatch.setenv("LLMD_PALLAS", "interpret")
    cfg = get_model_config("tiny-nemotron-h", hidden_size=128, head_dim=32, moe_intermediate_size=width,
                           shared_expert_intermediate_size=2 * width)
    assert cfg.moe_storage_width % 128 == 0 and cfg.moe_storage_width > width
    layers = llama.init_params(cfg, jax.random.key(2))["layers"]
    lp = {k: (a if k.startswith("we_") else a[1]) for k, a in layers.items()}
    h = jax.random.normal(jax.random.key(3), (1, 21, 128), jnp.float32)
    got, census = moe.moe_block_grouped(h, lp, cfg, emit_census=True, layer=jnp.int32(1))
    want = moe.moe_block(h, moe.experts_of_layer(lp, jnp.int32(1)), cfg)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert int(census[0]) == 1 and int(census[2]) == 21 * 2 and 0 < int(census[3]) < int(census[2])
    # and against the plain formula, from the unpadded columns
    ht = rms_norm(h[0], jnp.ones(128), 0.0) * 0 + h[0]
    w, ids = moe.router_topk(ht, lp["router"], 2, cfg, lp["router_bias"])
    plain = moe.relu2(ht @ lp["ws_up"]) @ lp["ws_down"]
    for e in range(cfg.held_experts):
        we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)[:, None]
        plain = plain + we * (moe.relu2(ht @ layers["we_up"][1, e, :, :width]) @ layers["we_down"][1, e, :width])
    np.testing.assert_allclose(got[0], plain, atol=2e-4, rtol=2e-4)


def test_the_ranks_shares_and_the_shared_expert_once_add_up_to_the_uncut_block():
    """THE SHARE TEST: one ``E`` block over all 8 experts (the uncut
    reference) = the sum of what each of 8 ranks' program computes of it from
    the one expert it holds, with the shared expert, which every rank
    computes alike, counted once."""
    whole = get_model_config("tiny-nemotron-h", held_experts=8)
    params = llama.init_params(whole, jax.random.key(7))
    lp_all = params["layers"]
    x = jax.random.normal(jax.random.key(8), (13, whole.hidden_size), jnp.float32)
    i = 2
    dims = rc.freeze(PUBLISHED, ref.KEYS)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref._experts(lp_all, jnp.int32(i), x, dims, 0)) - np.asarray(x)
        shared = uncut - (np.asarray(ref._experts(lp_all, jnp.int32(i), x, dims, 0, shared=False)) - np.asarray(x))
    h = rms_norm(x, lp_all["post_norm"][i], whole.rms_norm_eps)[None]
    total = np.zeros_like(uncut)
    for rank in range(8):
        cfg = get_model_config("tiny-nemotron-h", held_experts=1, held_experts_first=rank)
        lp = {k: a[i] for k, a in lp_all.items()}
        lp.update(we_up=lp["we_up"][rank: rank + 1], we_down=lp["we_down"][rank: rank + 1])
        part = np.asarray(moe.moe_block_grouped(h, lp, cfg)[0])
        with jax.default_matmul_precision("highest"):  # the rank's own reference agrees with its program
            rlp = {k: (a[:, rank: rank + 1] if k.startswith("we_") else a) for k, a in lp_all.items()}
            rpart = np.asarray(ref._experts(rlp, jnp.int32(i), x, dims, rank)) - np.asarray(x)
        np.testing.assert_allclose(part, rpart, atol=3e-5)
        total += part - shared
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
    assert np.abs(shared).max() > 1e-2 and np.abs(uncut - shared).max() > 1e-2
