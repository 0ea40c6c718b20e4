"""Gated delta-rule state (HF ``qwen3_next``'s Gated DeltaNet) over the FLAT
token stream: the second recurrence the state pool serves.

A delta-rule layer keeps, a value head, a state ``S`` in ``R^{Dk x Dv}`` (key
x value, float32) in the state pool ``ssm [Lg, slots, H, Dk, Dv]`` beside the
conv's last inputs (``ops/ssm.py::StatePool``, the pool, the rows and the
conv of the Mamba-2 mixers). Its step READS the state before it writes it:

    S' = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T;   o_t = S_t^T q_t

where Mamba-2's ``H = a H + dt x B^T`` needs nothing of the old state but a
scale. So

- ``gdn_update`` (scope ``llmd.gdn.update``): the decode rows. On a TPU a
  Pallas kernel over (head blocks, entries) with the pool aliased in place,
  laid out as ``ssm_update_pallas`` is; a block is loaded once and stored
  once, and between the two come the decay, the product with ``k``, the
  rank-1 update and the product with ``q``. 2 x 64 KiB a head a row at 128 x
  128: bound by the state's bytes.
- ``gdn_scan`` (scope ``llmd.gdn.scan``): the prefill rows, the CHUNKED delta
  rule with the flat step's row (<= 64 tokens) as the chunk. Within a row the
  deltas depend on each other, ``d_t = beta_t (v_t - gamma_t S_0^T k_t -
  sum_{s<t} (gamma_t / gamma_s)(k_s . k_t) d_s)`` with ``gamma_t = exp(g_1 +
  .. + g_t)``: a unit-lower-triangular system ``(I + A) D = R`` (the WY / UT
  form), which no masked ``[row, row]`` product of the inputs gives
  (``ssm_scan``'s way). Its inverse is the finite series ``sum_j (-A)^j`` (A
  is strictly lower, so nilpotent), summed by repeated squaring in float32 at
  ``HIGHEST``; everything else is einsums over ``[heads, row, ..]``. XLA
  operations; the running state is carried from a row to the next row of its
  segment and written where a sequence's last row ends, every other row
  writing to the pool's last slot, the scratch (``ssm_scan``'s convention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmd_tpu.ops.ssm import HIGHEST, StateRows, _copy_kernel, _scalars, _slot_spec

# A head block of the pool holds at most this many bytes (in and out, double
# buffered: four of them in VMEM).
_BLOCK_BYTES = 1 << 20


def head_block(H: int, Dk: int, Dv: int) -> int:
    """Heads a block of the pool: 16 of 32 at 128 x 128 (1 MiB)."""
    for hb in (16, 8, 4, 2):
        if H % hb == 0 and hb * Dk * Dv * 4 <= _BLOCK_BYTES:
            return hb
    return 1


# ---------------------------------------------------------------------- #
# decode rows: one token a row


def _update_kernel(
    slots_ref, cnt_ref, layer_ref,  # scalar prefetch
    s_ref, qk_ref, vab_ref,         # inputs
    o_ref, y_ref,                   # outputs (o aliases the pool)
    *, hb: int,
):
    del slots_ref, layer_ref
    i = pl.program_id(1)
    cnt = cnt_ref[0]

    @pl.when(i < cnt)
    def _():
        for hh in range(hb):
            q = qk_ref[0, :, hh : hh + 1]   # [Dk, 1]
            k = qk_ref[1, :, hh : hh + 1]
            v = vab_ref[0, hh : hh + 1, :]  # [1, Dv]
            a = vab_ref[1, hh : hh + 1, :]  # the decay, the head's one number
            b = vab_ref[2, hh : hh + 1, :]  # beta, alike
            s = s_ref[hh] * a               # [Dk, Dv]
            d = b * (v - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * d
            o_ref[hh] = s
            y_ref[hh : hh + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)

    @pl.when(cnt == 0)
    def _():
        # No decode row in this step: every entry names one block, which is
        # written back once, as it was.
        o_ref[...] = s_ref[...]


def gdn_update_pallas(pool, layer, slots, count, a, beta, q, k, v, *, interpret=False):
    """``pool`` [Lg, S, H, Dk, Dv] f32 updated in place for entries ``[0,
    count)`` by one token each. ``slots`` [U] i32; ``a`` [U, H] f32 (the
    decay exp(g); 0 starts from zeros), ``beta`` [U, H]; ``q``, ``k`` [U, H,
    Dk], ``v`` [U, H, Dv] f32, a value head's own. Returns (pool, y [U, H, Dv]
    f32; rows past ``count`` hold nothing)."""
    _, _, H, Dk, Dv = pool.shape
    U = slots.shape[0]
    hb = head_block(H, Dk, Dv)
    nb = H // hb
    # q and k as columns a head ([Dk, hb]: heads on the lanes), v, the decay
    # and beta as rows ([hb, Dv]).
    qk = jnp.stack([q, k], axis=1).reshape(U, 2, nb, hb, Dk).transpose(0, 2, 1, 4, 3)
    wide = lambda x: jnp.broadcast_to(x[:, :, None], v.shape)  # noqa: E731
    vab = jnp.stack([v, wide(a), wide(beta)], axis=1).reshape(U, 3, nb, hb, Dv)
    vab = vab.transpose(0, 2, 1, 3, 4)  # [U, nb, 3, hb, Dv]
    count = jnp.reshape(count, (1,)).astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def eff(i, cnt):
        # Entries behind the last live one name ITS blocks: nothing moves.
        return jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0))

    pool_spec = pl.BlockSpec(
        (None, None, hb, Dk, Dv),
        lambda j, i, sl, cnt, ly: (ly[0], sl[eff(i, cnt)], j, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, U),
        in_specs=[
            pool_spec,
            pl.BlockSpec(
                (None, None, 2, Dk, hb),
                lambda j, i, sl, cnt, ly: (eff(i, cnt), j, 0, 0, 0),
            ),
            pl.BlockSpec(
                (None, None, 3, hb, Dv),
                lambda j, i, sl, cnt, ly: (eff(i, cnt), j, 0, 0, 0),
            ),
        ],
        out_specs=[
            pool_spec,
            pl.BlockSpec((None, None, hb, Dv), lambda j, i, sl, cnt, ly: (i, j, 0, 0)),
        ],
    )
    pool, y = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((U, nb, hb, Dv), jnp.float32),
        ],
        # Operand 3 (after the three prefetched scalars) is the pool.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(slots.astype(jnp.int32), count, layer, pool, qk, vab)
    return pool, y.reshape(U, H, Dv)


def gdn_update_xla(pool, layer, slots, count, a, beta, q, k, v):
    """``gdn_update_pallas`` as XLA operations (a gather, the update, a
    scatter that drops the entries past ``count``)."""
    S, U = pool.shape[1], slots.shape[0]
    plane = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    s = plane[slots] * a[:, :, None, None]  # [U, H, Dk, Dv]
    d = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=2))
    s = s + k[..., None] * d[:, :, None, :]
    y = jnp.sum(s * q[..., None], axis=2)
    dst = jnp.where(jnp.arange(U) < count, slots, S)
    return pool.at[layer, dst].set(s, mode="drop"), y


def gdn_update(pool, layer, rows: StateRows, q, k, v, g, beta, plan: str):
    """The decode rows of a step. ``q``, ``k`` [T, H, Dk], ``v`` [T, H, Dv],
    ``g`` (log decay), ``beta`` [T, H], all f32 and a value head's own.
    Returns (pool, y [T, H, Dv] f32 that holds the decode rows' outputs and
    zeros elsewhere)."""
    T = q.shape[0]
    r = rows.upd_rows
    tok = jnp.clip(rows.row_start[r], 0, T - 1)
    a = jnp.where(rows.fresh[r][:, None], 0.0, jnp.exp(g[tok]))
    args = (
        pool, layer, rows.slot[r], rows.n_upd, a, beta[tok], q[tok], k[tok], v[tok],
    )
    with jax.named_scope("llmd.gdn.update"):
        if plan == "xla":
            pool, y_u = gdn_update_xla(*args)
        else:
            pool, y_u = gdn_update_pallas(*args, interpret=plan == "interpret")
    dst = jnp.where(jnp.arange(r.shape[0]) < rows.n_upd, tok, T)
    y = jnp.zeros(v.shape, jnp.float32).at[dst].set(y_u, mode="drop")
    return pool, y


# ---------------------------------------------------------------------- #
# prefill rows: the chunked delta rule, a row a chunk


def read_slot(pool, layer, slot, plan: str):
    """``pool[layer, slot]`` as a value; on a TPU a Pallas copy, so that the
    pool's only consumers are custom calls (``ssm.read_slot`` says why)."""
    _, _, H, Dk, Dv = pool.shape
    if plan == "xla":
        return jax.lax.dynamic_slice(
            pool, (layer, slot, 0, 0, 0), (1, 1, H, Dk, Dv)
        )[0, 0]
    hb = head_block(H, Dk, Dv)
    # A Pallas call inside a loop body is named after the body unless a
    # scope is open AT the call.
    with jax.named_scope("llmd.gdn.scan"):
        return pl.pallas_call(
            _copy_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(H // hb,),
                in_specs=[_slot_spec(hb, Dk, Dv)],
                out_specs=pl.BlockSpec((hb, Dk, Dv), lambda j, sl, ly: (j, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((H, Dk, Dv), pool.dtype),
            interpret=plan == "interpret",
        )(*_scalars(slot, layer), pool)


def write_slot(pool, layer, slot, value, plan: str):
    """``pool`` with ``[layer, slot] = value``, in place (the pool aliased)."""
    _, _, H, Dk, Dv = pool.shape
    if plan == "xla":
        return jax.lax.dynamic_update_slice(
            pool, value[None, None], (layer, slot, 0, 0, 0)
        )
    hb = head_block(H, Dk, Dv)

    def kernel(slot_ref, layer_ref, pool_ref, src_ref, dst_ref):
        del pool_ref  # aliased to the output; only [layer, slot] is written
        _copy_kernel(slot_ref, layer_ref, src_ref, dst_ref)

    with jax.named_scope("llmd.gdn.scan"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(H // hb,),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((hb, Dk, Dv), lambda j, sl, ly: (j, 0, 0)),
                ],
                out_specs=_slot_spec(hb, Dk, Dv),
            ),
            out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            input_output_aliases={2: 0},
            interpret=plan == "interpret",
        )(*_scalars(slot, layer), pool, value)


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def unit_lower_inverse(n):
    """``(I - n)^-1`` for ``n`` [.., L, L] STRICTLY lower triangular: the
    series ``I + n + n^2 + .. + n^(L-1)`` (n is nilpotent) as the product
    ``(I + n)(I + n^2)(I + n^4)..``, log2(L) squarings."""
    L = n.shape[-1]
    inv = jnp.eye(L, dtype=n.dtype) + n
    p, have = n, 2  # inv holds the powers below ``have``
    while have < L:
        p = _mm("...ls,...st->...lt", p, p)
        inv = inv + _mm("...ls,...st->...lt", inv, p)
        have *= 2
    return inv


@jax.named_scope("llmd.gdn.scan")
def gdn_scan(pool, layer, rows: StateRows, q, k, v, g, beta, y, row_cap: int,
             plan: str = "xla"):
    """The prefill rows of a step, in stream order, the running state carried
    from a row to the next row of its segment. Operands as ``gdn_update``'s;
    ``y`` [T, H, Dv] f32 comes in holding the decode rows' outputs and leaves
    with the prefill rows' added. ``row_cap`` bounds a row's tokens (the
    runner cuts chunks to it). The pool's LAST slot is scratch: a row that
    does not end its segment writes there, so every row makes one read and
    one write."""
    T, H, Dk = q.shape
    Dv = v.shape[-1]
    Lr = row_cap
    scratch = pool.shape[1] - 1

    def heads_first(a):  # [T, H, ..] -> [H, T + Lr, ..], zeros behind
        a = jnp.concatenate([a, jnp.zeros((Lr, *a.shape[1:]), a.dtype)])
        return jnp.swapaxes(a, 0, 1)

    qp, kp, vp, yp = (heads_first(a) for a in (q, k, v, y))
    gp, bp = heads_first(g), heads_first(beta)  # [H, T + Lr]
    tril = jnp.tril(jnp.ones((Lr, Lr), bool))
    strict = jnp.tril(jnp.ones((Lr, Lr), bool), -1)

    def body(i, carry):
        sc, yp, pool = carry
        r = rows.scan_rows[i]
        t0, n, slot = rows.row_start[r], rows.qlen[r], rows.slot[r]
        # The state the segment enters with: its slot's, zeros at position 0.
        enters = rows.seg_start[r] & ~rows.fresh[r]
        held = read_slot(pool, layer, jnp.where(enters, slot, scratch), plan)
        sc = jnp.where(
            rows.seg_start[r], jnp.where(rows.fresh[r], 0.0, held), sc
        )
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, Lr, 1)  # noqa: E731
        m = (jnp.arange(Lr) < n)[None, :]
        qs, ks, vs = sl(qp), sl(kp), sl(vp)  # [H, Lr, D]
        # A token behind the row's end moves nothing: decay 1, beta 0.
        bs = jnp.where(m, sl(bp), 0.0)
        cum = jnp.cumsum(jnp.where(m, sl(gp), 0.0), axis=1)  # [H, Lr]
        gam = jnp.exp(cum)
        # gamma_l / gamma_s for s <= l.
        decay = jnp.where(tril, jnp.exp(cum[:, :, None] - cum[:, None, :]), 0.0)
        # (I + A) D = R, A[l, s] = beta_l (gamma_l / gamma_s)(k_l . k_s), s < l.
        a_neg = jnp.where(
            strict, -bs[:, :, None] * decay * _mm("hld,hsd->hls", ks, ks), 0.0
        )
        rhs = bs[:, :, None] * (vs - gam[:, :, None] * _mm("hlk,hkv->hlv", ks, sc))
        d = _mm("hls,hsv->hlv", unit_lower_inverse(a_neg), rhs)
        # o_l = gamma_l S_0^T q_l + sum_{s <= l} (gamma_l / gamma_s)(k_s . q_l) d_s
        y_row = gam[:, :, None] * _mm("hlk,hkv->hlv", qs, sc) + _mm(
            "hls,hsv->hlv", decay * _mm("hld,hsd->hls", qs, ks), d
        )
        to_end = jnp.exp(cum[:, -1:] - cum)  # gamma_L / gamma_s
        sn = gam[:, -1][:, None, None] * sc + _mm(
            "hsk,hsv->hkv", to_end[:, :, None] * ks, d
        )
        old = jax.lax.dynamic_slice_in_dim(yp, t0, Lr, 1)
        yp = jax.lax.dynamic_update_slice_in_dim(
            yp, jnp.where(m[:, :, None], y_row, old), t0, 1
        )
        pool = write_slot(
            pool, layer, jnp.where(rows.seg_end[r], slot, scratch), sn, plan
        )
        return sn, yp, pool

    s0 = jnp.zeros((H, Dk, Dv), jnp.float32)
    _, yp, pool = jax.lax.fori_loop(0, rows.n_scan, body, (s0, yp, pool))
    return pool, jnp.swapaxes(yp, 0, 1)[:T]
