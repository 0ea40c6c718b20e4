"""State-space (Mamba-2) state over the FLAT token stream.

A state-space layer keeps a state of fixed size a sequence, not pages a
token: the SSM state ``[heads, d_head, d_state]`` (float32) and the causal
conv's last ``d_conv - 1`` inputs. Both live in the STATE POOL, a slot a
sequence (``StatePool``: ``ssm [Lm, slots, H, P, N]``, ``conv [Lm, slots,
d_conv - 1, C]``), which the step program takes donated beside the KV pool
and updates in place.

One flat step mixes rows of several sequences: decode rows (one token) and
prefill chunks (up to a budget of tokens, cut into rows of at most 64). The
recurrence ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t``
runs over each sequence's tokens of this step, entering with its slot's
state (zeros at position 0, whatever the slot held) and leaving its last
state there:

- ``ssm_update`` (scope ``llmd.ssm.update``): the decode rows. One token a
  row, bound by the state's bytes (4 MiB read and written a row a layer at
  128 x 64 x 128 in float32). On a TPU a Pallas kernel with the pool
  aliased in place: the rows that decode are compacted in front, the grid
  runs (head blocks, entries), an entry's block is ``pool[layer,
  slot[entry], block]``, and the entries behind the last live one name ITS
  block again, so they move nothing. The state's multiply-add runs on the
  vector unit; ``y``, a sum across lanes, on the matrix unit
  (``_update_kernel``), so that the body hides under the block's copies.
- ``ssm_scan`` (scope ``llmd.ssm.scan``): the prefill rows, as the chunked
  (SSD) form with the ROW as the chunk: a loop over the step's prefill rows
  that carries the running state, computes a row's outputs from the state it
  entered with and from its own tokens (a masked ``[row, row]`` product per
  head), and writes the state back where a sequence's last row ends. XLA
  operations; the einsums that touch the float32 state run at ``highest``
  precision.
- ``causal_conv`` : the depthwise conv over the same ragged stream, whose
  first ``d_conv - 1`` tokens of a sequence read the slot's conv state.

``StateRows`` is what all three need of the step's packing, derived once a
step from the per-row metadata and the rows' slot ids.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST

# Row kinds of the flat step (engine/runner.py::_KIND_*).
KIND_PREFILL, KIND_DECODE = 0, 1


class StatePool(NamedTuple):
    """The per-sequence state of the state-space layers, a slot a sequence.
    Slot ids come from the engine's state allocator; a retained snapshot is
    a slot like any other, and copying ``[:, src] -> [:, dst]`` of both
    leaves is a capture or a seed."""

    ssm: jax.Array   # [Lm, slots, H, P, N] float32
    conv: jax.Array  # [Lm, slots, d_conv - 1, C] model dtype


class StateRows(NamedTuple):
    """The step's packing as the state-space layers read it. A SEGMENT is
    the run of consecutive live rows of one slot: a decode row, or the rows
    a prefill chunk was cut into."""

    slot: jax.Array       # [B] the row's slot
    row_start: jax.Array  # [B] first stream token
    qlen: jax.Array       # [B] tokens (0 = pad row)
    seg_start: jax.Array  # [B] bool: first row of its segment
    seg_end: jax.Array    # [B] bool: last row of its segment
    seg_t0: jax.Array     # [B] first stream token of the row's segment
    seg_len: jax.Array    # [B] tokens of the row's segment
    fresh: jax.Array      # [B] bool: the segment starts at position 0
    seg_local: jax.Array  # [T] a token's index within its segment
    live: jax.Array       # [T] bool
    scan_rows: jax.Array  # [B] the prefill rows first, in stream order
    n_scan: jax.Array     # [] how many
    upd_rows: jax.Array   # [B] the decode rows first
    n_upd: jax.Array      # [] how many


def state_rows(slot, row_start, qlen, pos0, kind, row_of, live_t) -> StateRows:
    """Derive ``StateRows`` on the device from the flat step's per-row
    arrays ([B]) and its token -> row map ([T])."""
    B = slot.shape[0]
    live = qlen > 0
    prev_slot = jnp.concatenate([slot[:1] - 1, slot[:-1]])
    prev_live = jnp.concatenate([jnp.zeros(1, bool), live[:-1]])
    next_slot = jnp.concatenate([slot[1:], slot[-1:] - 1])
    next_live = jnp.concatenate([live[1:], jnp.zeros(1, bool)])
    seg_start = live & ~(prev_live & (prev_slot == slot))
    seg_end = live & ~(next_live & (next_slot == slot))
    seg_id = jnp.cumsum(seg_start) - 1  # [B], -1 before the first
    sid = jnp.clip(seg_id, 0, B - 1)
    # Values of the segment's first row, carried to its other rows.
    first_row = jax.lax.cummax(jnp.where(seg_start, jnp.arange(B), 0))
    seg_t0 = row_start[first_row]
    fresh = pos0[first_row] == 0
    seg_len = jax.ops.segment_sum(
        jnp.where(live, qlen, 0), sid, num_segments=B
    )[sid]
    t = jnp.arange(row_of.shape[0])
    seg_local = jnp.where(live_t, t - seg_t0[row_of], 0)
    is_scan = live & (kind == KIND_PREFILL)
    is_upd = live & (kind == KIND_DECODE)
    return StateRows(
        slot=slot, row_start=row_start, qlen=qlen, seg_start=seg_start,
        seg_end=seg_end, seg_t0=seg_t0, seg_len=seg_len, fresh=fresh,
        seg_local=seg_local.astype(jnp.int32), live=live_t,
        scan_rows=jnp.argsort(~is_scan, stable=True).astype(jnp.int32),
        n_scan=jnp.sum(is_scan).astype(jnp.int32),
        upd_rows=jnp.argsort(~is_upd, stable=True).astype(jnp.int32),
        n_upd=jnp.sum(is_upd).astype(jnp.int32),
    )


# ---------------------------------------------------------------------- #
# the causal conv over the ragged stream


@jax.named_scope("llmd.ssm.conv")
def causal_conv(xbc, conv_w, conv_pool, layer, rows: StateRows):
    """Depthwise causal conv of ``K = conv_w.shape[0]`` taps over the flat
    stream. ``xbc`` [T, C]; ``conv_w`` [K, C], tap ``k`` multiplying the
    input ``K - 1 - k`` tokens back; ``conv_pool`` [Lm, slots, K - 1, C], a
    slot's last K - 1 inputs, oldest first. Returns (out [T, C] float32
    without bias, the pool with every segment's new last inputs)."""
    T, C = xbc.shape
    K = conv_w.shape[0]
    S = conv_pool.shape[1]
    w = conv_w.astype(jnp.float32)
    xf = xbc.astype(jnp.float32)
    out = xf * w[K - 1]
    for j in range(1, K):
        back = jnp.concatenate([jnp.zeros((j, C), jnp.float32), xf[:-j]])
        out = out + jnp.where(
            (rows.seg_local >= j)[:, None], back, 0.0
        ) * w[K - 1 - j]
    # What a segment's first K - 1 tokens read of the slot's conv state:
    # token l of the segment reads state entries (K - 1) + l - j, j > l.
    old = jax.lax.dynamic_index_in_dim(conv_pool, layer, 0, keepdims=False)
    old = jnp.where(
        rows.fresh[:, None, None], 0.0, old[rows.slot].astype(jnp.float32)
    )  # [B, K-1, C]
    corr, idx = [], []
    for l in range(K - 1):
        c = sum(
            old[:, (K - 1) + l - j] * w[K - 1 - j] for j in range(l + 1, K)
        )
        on = rows.seg_start & (l < rows.seg_len)
        corr.append(jnp.where(on[:, None], c, 0.0))
        idx.append(jnp.where(on, rows.seg_t0 + l, T))
    out = out.at[jnp.concatenate(idx)].add(
        jnp.concatenate(corr), mode="drop"
    )
    # The segment's new state: its last K - 1 inputs, the old ones where
    # the segment is shorter than that.
    new = []
    for k in range(K - 1):
        o = rows.seg_len - (K - 1) + k  # offset within the segment
        tok = xf[jnp.clip(rows.seg_t0 + o, 0, T - 1)]
        prev = jnp.take_along_axis(
            old, jnp.clip(rows.seg_len + k, 0, K - 2)[:, None, None], axis=1
        )[:, 0]
        new.append(jnp.where((o >= 0)[:, None], tok, prev))
    new = jnp.stack(new, axis=1).astype(conv_pool.dtype)  # [B, K-1, C]
    dst = jnp.where(rows.seg_end, rows.slot, S)
    conv_pool = conv_pool.at[layer, dst].set(new, mode="drop")
    return out, conv_pool


# ---------------------------------------------------------------------- #
# decode rows: one token a row


def _update_kernel(
    slots_ref, cnt_ref, layer_ref,  # scalar prefetch
    h_ref, ax_ref, b_ref, c_ref,    # inputs
    o_ref, y_ref,                   # outputs (o aliases the pool)
    *, hb: int, hpg: int = 0,
):
    """``hpg`` 0: the block's heads share ONE b and c (row 0: one group, or
    a block inside a group). Else the block spans several groups of ``hpg``
    heads and b, c hold a row a group. ``c`` comes padded with zero rows to
    whole sublane tiles.

    A head's ``[P, N]`` tile has the state dimension on the LANES, so ``y =
    H . c`` sums across lanes. The vector unit did that a register at a time
    and stored a one-lane column a head: 4.0 us a 1 MiB block where the
    block's two copies take 3.2 (chip, PR 50). The matrix unit, idle in this
    kernel, takes it instead: ``c . H^T`` over the whole block at ``HIGHEST``
    (float32 in six passes; as close to ``ssm_update_xla``'s sum as a
    float32 sum in another order), whose result is a lane-dense ROW of
    ``hb * P`` a group. The body is then 1.4 us and hides under the copies.
    The state's own multiply-add stays on the vector unit in float32."""
    del slots_ref, layer_ref
    i = pl.program_id(1)
    cnt = cnt_ref[0]
    P, N = h_ref.shape[1:]

    @pl.when(i < cnt)
    def _():
        b = b_ref[...]  # [1, N], or [the block's groups, N]
        for hh in range(hb):
            a = ax_ref[0, :, hh : hh + 1]   # [P, 1] decay
            xc = ax_ref[1, :, hh : hh + 1]  # [P, 1] dt * x
            bh = b[hh // hpg : hh // hpg + 1] if hpg else b
            o_ref[hh] = h_ref[hh] * a + xc * bh  # [P, N]
        y = jax.lax.dot_general(
            c_ref[...], o_ref[...].reshape(hb * P, N), (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )  # [rows of c, hb * P]: row g holds every head's product with group g's c
        per = (hpg or hb) * P  # a group's heads x P lanes of the row
        for g in range(hb * P // per):
            y_ref[:, g * per : (g + 1) * per] = y[g : g + 1, g * per : (g + 1) * per]

    @pl.when(cnt == 0)
    def _():
        # No decode row in this step: every entry names one block, which is
        # written back once, as it was.
        o_ref[...] = h_ref[...]


def _head_block(H: int, hpg: int = 0) -> int:
    """Heads a block of the pool. ``hpg`` (heads a group of B and C, where
    there are several groups): a block is then whole groups or lies inside
    one, so that its b and c are rows of one operand block."""
    for hb in (32, 16, 8):
        if H % hb == 0 and (not hpg or hb % hpg == 0 or hpg % hb == 0):
            return hb
    return H


def _block_groups(b, nb: int, hb: int, hpg: int):
    """``b`` [U, G, N] as [U, nb, the groups of a head block, N]."""
    gpb = max(1, hb // hpg)
    idx = [(j * hb) // hpg + r for j in range(nb) for r in range(gpb)]
    U, _, N = b.shape
    return jnp.take(b, jnp.asarray(idx, jnp.int32), axis=1).reshape(U, nb, gpb, N)


def ssm_update_pallas(ssm, layer, slots, count, a, dtx, b, c, *, interpret=False):
    """``ssm`` [Lm, S, H, P, N] f32 updated in place for entries
    ``[0, count)``: ``H = a * H + dtx (x) b`` and ``y = H . c``. ``slots``
    [U] i32, ``a`` [U, H] f32 (the decay; 0 starts from zeros), ``dtx`` [U,
    H, P] f32, ``b``, ``c`` [U, N] f32, or [U, G, N] where the heads read B
    and C in G groups (head h its group ``h // (H / G)``'s). Returns (ssm, y
    [U, H, P] f32; rows past ``count`` hold nothing)."""
    _, _, H, P, N = ssm.shape
    U = slots.shape[0]
    grouped = b.ndim == 3
    hpg = H // b.shape[1] if grouped else 0
    hb = _head_block(H, hpg)
    nb = H // hb
    ax = jnp.stack([jnp.broadcast_to(a[:, :, None], dtx.shape), dtx], axis=1)
    ax = ax.reshape(U, 2, nb, hb, P).transpose(0, 2, 1, 4, 3)  # [U, nb, 2, P, hb]
    count = jnp.reshape(count, (1,)).astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def eff(i, cnt):
        return jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0))

    pool_spec = pl.BlockSpec(
        (None, None, hb, P, N),
        lambda j, i, sl, cnt, ly: (ly[0], sl[eff(i, cnt)], j, 0, 0),
    )
    if grouped:
        # A head block's groups: [U, nb, groups of the block, N], the block
        # (entry, head block)'s.
        b, c = (_block_groups(v, nb, hb, hpg) for v in (b, c))
    else:
        b, c = b[:, None, None, :], c[:, None, None, :]
    # The matrix unit takes c in whole sublane tiles: zero rows behind the
    # block's groups.
    c = jnp.pad(c, ((0, 0), (0, 0), (0, -c.shape[2] % 8), (0, 0)))

    def bc_spec(v):
        return pl.BlockSpec(
            (None, None, v.shape[2], N),
            lambda j, i, sl, cnt, ly: (eff(i, cnt), j if grouped else 0, 0, 0),
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, U),
        in_specs=[
            pool_spec,
            pl.BlockSpec(
                (None, None, 2, P, hb),
                lambda j, i, sl, cnt, ly: (eff(i, cnt), j, 0, 0, 0),
            ),
            bc_spec(b), bc_spec(c),
        ],
        out_specs=[
            pool_spec,
            # y lane-dense: a row of the block's heads x P.
            pl.BlockSpec((None, None, 1, hb * P), lambda j, i, sl, cnt, ly: (i, j, 0, 0)),
        ],
    )
    ssm, y = pl.pallas_call(
        # hpg 0 where the block's heads share one row of b and c.
        functools.partial(_update_kernel, hb=hb, hpg=hpg if hb > hpg else 0),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
            jax.ShapeDtypeStruct((U, nb, 1, hb * P), jnp.float32),
        ],
        # Operand 3 (after the three prefetched scalars) is the pool.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(slots.astype(jnp.int32), count, layer, ssm, ax, b, c)
    return ssm, y.reshape(U, H, P)


def ssm_update_xla(ssm, layer, slots, count, a, dtx, b, c):
    """``ssm_update_pallas`` as XLA operations (a gather, the update, a
    scatter that drops the entries past ``count``)."""
    S, H = ssm.shape[1], ssm.shape[2]
    U = slots.shape[0]
    plane = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    if b.ndim == 3:  # [U, G, N]: a head reads its group's
        b, c = (jnp.repeat(v, H // v.shape[1], axis=1)[:, :, None, :] for v in (b, c))
        h = plane[slots] * a[:, :, None, None] + dtx[..., None] * b
        y = jnp.sum(h * c, axis=-1)
    else:
        h = plane[slots] * a[:, :, None, None] + dtx[..., None] * b[:, None, None, :]
        y = jnp.sum(h * c[:, None, None, :], axis=-1)
    dst = jnp.where(jnp.arange(U) < count, slots, S)
    return ssm.at[layer, dst].set(h, mode="drop"), y


def ssm_update(ssm, layer, rows: StateRows, x, dt, dA, Bm, Cm, plan: str):
    """The decode rows of a step. ``x`` [T, H, P], ``dt``, ``dA`` [T, H]
    f32, ``Bm``, ``Cm`` [T, N] (one group) or [T, G, N]. Returns (ssm, y [T, H, P] f32 that holds the
    decode rows' outputs and zeros elsewhere)."""
    T = x.shape[0]
    r = rows.upd_rows
    tok = jnp.clip(rows.row_start[r], 0, T - 1)
    a = jnp.where(rows.fresh[r][:, None], 0.0, jnp.exp(dA[tok]))
    dtx = dt[tok][:, :, None] * x[tok].astype(jnp.float32)
    args = (
        ssm, layer, rows.slot[r], rows.n_upd, a, dtx,
        Bm[tok].astype(jnp.float32), Cm[tok].astype(jnp.float32),
    )
    with jax.named_scope("llmd.ssm.update"):
        if plan == "xla":
            ssm, y_u = ssm_update_xla(*args)
        else:
            ssm, y_u = ssm_update_pallas(*args, interpret=plan == "interpret")
    dst = jnp.where(jnp.arange(r.shape[0]) < rows.n_upd, tok, T)
    y = jnp.zeros(x.shape, jnp.float32).at[dst].set(y_u, mode="drop")
    return ssm, y


# ---------------------------------------------------------------------- #
# prefill rows: the chunked scan, a row a chunk


def _copy_kernel(slot_ref, layer_ref, src_ref, dst_ref):
    del slot_ref, layer_ref
    dst_ref[...] = src_ref[...]


def _slot_spec(hb, P, N):
    return pl.BlockSpec(
        (None, None, hb, P, N), lambda j, sl, ly: (ly[0], sl[0], j, 0, 0)
    )


def _scalars(slot, layer):
    return (
        jnp.reshape(slot, (1,)).astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
    )


def read_slot(ssm, layer, slot, plan: str):
    """``ssm[layer, slot]`` as a value. On a TPU a Pallas copy, so that the
    pool's only consumers are custom calls: an XLA slice of it inside the
    layer scan made the compiler re-lay the whole pool out, a 3.4 GB copy in
    and out every step (compiler, PR 37)."""
    _, _, H, P, N = ssm.shape
    if plan == "xla":
        return jax.lax.dynamic_slice(
            ssm, (layer, slot, 0, 0, 0), (1, 1, H, P, N)
        )[0, 0]
    hb = _head_block(H)
    # A Pallas call inside a loop body is named after the body unless a
    # scope is open AT the call.
    with jax.named_scope("llmd.ssm.scan"):
      return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // hb,),
            in_specs=[_slot_spec(hb, P, N)],
            out_specs=pl.BlockSpec((hb, P, N), lambda j, sl, ly: (j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((H, P, N), ssm.dtype),
        interpret=plan == "interpret",
    )(*_scalars(slot, layer), ssm)


def write_slot(ssm, layer, slot, value, plan: str):
    """``ssm`` with ``[layer, slot] = value``, in place (the pool aliased)."""
    _, _, H, P, N = ssm.shape
    if plan == "xla":
        return jax.lax.dynamic_update_slice(
            ssm, value[None, None], (layer, slot, 0, 0, 0)
        )
    hb = _head_block(H)

    def kernel(slot_ref, layer_ref, pool_ref, src_ref, dst_ref):
        del pool_ref  # aliased to the output; only [layer, slot] is written
        _copy_kernel(slot_ref, layer_ref, src_ref, dst_ref)

    with jax.named_scope("llmd.ssm.scan"):
      return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // hb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((hb, P, N), lambda j, sl, ly: (j, 0, 0)),
            ],
            out_specs=_slot_spec(hb, P, N),
        ),
        out_shape=jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
        input_output_aliases={2: 0},
        interpret=plan == "interpret",
    )(*_scalars(slot, layer), ssm, value)


@jax.named_scope("llmd.ssm.scan")
def ssm_scan(ssm, layer, rows: StateRows, x, dt, dA, Bm, Cm, y, row_cap: int,
             plan: str = "xla"):
    """The prefill rows of a step, in stream order, the running state
    carried from a row to the next row of its segment. ``y`` [T, H, P] f32
    comes in holding the decode rows' outputs and leaves with the prefill
    rows' added. ``Bm``, ``Cm`` [T, N], or [T, G, N] where the heads read
    them in G groups. ``row_cap`` bounds a row's tokens (the runner cuts chunks
    to it). The pool's LAST slot is scratch: a row that does not end its
    segment writes there, so every row makes one read and one write."""
    T, H, P = x.shape
    N = Bm.shape[-1]
    G = Bm.shape[1] if Bm.ndim == 3 else 0  # 0: one group, ``Bm`` [T, N]
    Lr = row_cap
    scratch = ssm.shape[1] - 1
    pad = lambda a: jnp.concatenate(  # noqa: E731
        [a, jnp.zeros((Lr, *a.shape[1:]), a.dtype)]
    )
    xp, dtp, dAp = pad(x.astype(jnp.float32)), pad(dt), pad(dA)
    Bp, Cp = pad(Bm.astype(jnp.float32)), pad(Cm.astype(jnp.float32))
    yp = pad(y)
    tril = jnp.tril(jnp.ones((Lr, Lr), bool))

    def body(i, carry):
        hc, yp, ssm = carry
        r = rows.scan_rows[i]
        t0, q, slot = rows.row_start[r], rows.qlen[r], rows.slot[r]
        # The state the segment enters with: its slot's, zeros at position 0.
        enters = rows.seg_start[r] & ~rows.fresh[r]
        held = read_slot(ssm, layer, jnp.where(enters, slot, scratch), plan)
        hc = jnp.where(
            rows.seg_start[r], jnp.where(rows.fresh[r], 0.0, held), hc
        )
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, Lr, 0)  # noqa: E731
        m = jnp.arange(Lr) < q
        xs, bs, cs_ = sl(xp), sl(Bp), sl(Cp)
        dts = jnp.where(m[:, None], sl(dtp), 0.0)
        cum = jnp.cumsum(jnp.where(m[:, None], sl(dAp), 0.0), axis=0)  # [Lr, H]
        # Within the row: y_l += sum_{s <= l} exp(cum_l - cum_s) dt_s (C_l . B_s) x_s
        if not G:
            g = jnp.einsum("ln,sn->ls", cs_, bs, precision=HIGHEST)
            decay = jnp.where(
                tril[:, :, None], jnp.exp(cum[:, None, :] - cum[None, :, :]), 0.0
            )  # [l, s, H]
            w = g[:, :, None] * decay * dts[None, :, :]
            y_row = jnp.einsum("lsh,shp->lhp", w, xs, precision=HIGHEST)
            # From the state the row entered with.
            y_row = y_row + jnp.exp(cum)[:, :, None] * jnp.einsum(
                "ln,hpn->lhp", cs_, hc, precision=HIGHEST
            )
            to_end = jnp.exp(cum[-1][None, :] - cum) * dts  # [Lr, H]
            hn = jnp.exp(cum[-1])[:, None, None] * hc + jnp.einsum(
                "shp,sn->hpn", to_end[:, :, None] * xs, bs, precision=HIGHEST
            )
        else:
            # B and C in G groups of R heads: the same sums with the head
            # axis split into (group, head of the group).
            byg = lambda a: a.reshape(*a.shape[:-1], G, H // G)  # noqa: E731
            g = jnp.einsum("lgn,sgn->lsg", cs_, bs, precision=HIGHEST)
            decay = jnp.where(
                tril[:, :, None], jnp.exp(cum[:, None, :] - cum[None, :, :]), 0.0
            )
            w = g[..., None] * byg(decay) * byg(dts)[None]  # [l, s, G, R]
            xg = xs.reshape(Lr, G, H // G, P)
            y_row = jnp.einsum("lsgr,sgrp->lgrp", w, xg, precision=HIGHEST)
            y_row = y_row + byg(jnp.exp(cum))[..., None] * jnp.einsum(
                "lgn,grpn->lgrp", cs_, hc.reshape(G, H // G, P, N),
                precision=HIGHEST,
            )
            y_row = y_row.reshape(Lr, H, P)
            to_end = jnp.exp(cum[-1][None, :] - cum) * dts
            hn = jnp.exp(cum[-1])[:, None, None] * hc + jnp.einsum(
                "sgrp,sgn->grpn", byg(to_end)[..., None] * xg, bs,
                precision=HIGHEST,
            ).reshape(H, P, N)
        old = jax.lax.dynamic_slice_in_dim(yp, t0, Lr, 0)
        yp = jax.lax.dynamic_update_slice_in_dim(
            yp, jnp.where(m[:, None, None], y_row, old), t0, 0
        )
        ssm = write_slot(
            ssm, layer, jnp.where(rows.seg_end[r], slot, scratch), hn, plan
        )
        return hn, yp, ssm

    h0 = jnp.zeros((H, P, N), jnp.float32)
    _, yp, ssm = jax.lax.fori_loop(0, rows.n_scan, body, (h0, yp, ssm))
    return ssm, yp[:T]
