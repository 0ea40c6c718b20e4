"""Grouped GEMM for MoE expert compute — the DeepGEMM role.

The reference's wide-EP decode path routes MoE through DeepGEMM's masked
grouped GEMMs (`--moe-backend deep_gemm`, guides/wide-ep-lws/modelserver/
gpu/vllm/base/decode.yaml:128) so each expert multiplies ONLY its routed
tokens. The TPU-native equivalent: tokens sorted by expert id feed a
ragged/grouped matmul — a Pallas kernel on TPU (``gmm``, below: megablox's
grouped matmul with one more scalar-prefetch operand, the layer),
`lax.ragged_dot` elsewhere — instead of the one-hot masked contraction that
burns E/top_k redundant FLOPs.

The kernel's weight operand is the STACKED leaf ``[L, E, K, N]`` of all
layers and a layer index: its weight block is ``[layer, group, k tile, n
tile]``, as the layer-indexed attention and KV-write kernels index the
cache. A ``[E, K, N]`` slice of one layer never exists, so XLA has nothing to
materialise in front of the custom call (it fuses a scanned slice into an XLA
consumer, and copies it for a Pallas one: 1.1-1.2 GB a layer read and written
every step, PERF.md section 6, PR 32).

FLOPs per token: 3 * k * H * F (exactly the routed work) vs the dense
combine's 3 * E * H * F.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from llmd_tpu import ops


def _use_kernel(H: int, F: int, mesh=None) -> bool:
    """The kernel wants lane-tiled contraction/output dims; anything else
    (tiny models, gpt-oss's 2,880) takes the XLA ragged_dot, which is
    correct everywhere.
    LLMD_PALLAS=interpret forces the kernel in interpret mode so CPU CI
    parity-tests the same glue (tiling, padding, sorting) TPUs run. The
    same decision ladder as every other op (ops._decide: env lever,
    geometry, the platform of ``mesh``'s devices), recorded the same way;
    the kernel runs per device, so there is no mesh rule to pass."""
    return ops._decide(
        "grouped_gemm", H % 128 == 0 and F % 128 == 0, 1, mesh
    ) != "xla"


# What the kernel's blocks may take of VMEM. The call asks for no limit of its
# own, so the compiler's scoped default bounds it (16 MiB on a v5e), and
# the compiler wants room beside the blocks for the operands it loads: a
# described v5e accepts every expert shape of the registry at 12 MiB of blocks
# (tests/test_chip_compile.py) and refuses some at 14.5.
_VMEM_BUDGET = 12 << 20
# Lanes of a weight tile where K itself has to be split (see gmm_tiles).
_TN_SPLIT_K = 1024


def _even_tile(total: int, most: int) -> int:
    """The tile (a multiple of 128) that covers ``total`` in the fewest
    tiles of at most ``most``, of even length: 1,408 under 1,152 is 768 +
    640, not 1,152 + 256. A short last tile costs a masked pass."""
    parts = -(-total // most)
    return -(-total // (parts * 128)) * 128


def gmm_tiles(
    K: int, N: int, w_bytes: int, x_bytes: int = 2,
    budget: int = _VMEM_BUDGET,
) -> tuple[int, int]:
    """The kernel's (tk, tn) for a [*, K] x [G, K, N] grouped matmul, K and N
    multiples of 128, from the expert's shape alone (sized for the largest
    row tile, 128, so that a step's row count never moves them).

    The grid is (n tiles, active m tiles, k tiles) and every step moves one
    tk x tn weight block: at 128 x 128 (32 KB) the kernel is bound by its
    grid steps at an eighth of the HBM bandwidth, so the block is made as
    large as ``budget`` lets the double-buffered activation, weight and f32
    output blocks plus the f32 accumulator be. What a sweep on a v5e kept
    (PERF.md section 6, PR 30):

    - ``tk`` is the whole K wherever that fits with any ``tn``: steps of
      one row tile then find their activation block in place, where a split
      K fetches it again at every step (15-17 % at 512 tokens).
    - ``tn`` is then the widest that fits, in tiles of even length: the
      whole N for 2,048 x 768 and 768 x 2,048, 768 + 640 for N 1,408
      (1,024 + 384 read 10 % slower than either).
    - Where not even ``tn`` 128 fits beside the whole K (Mixtral's down
      projection), ``tn`` is N in even tiles of at most ``_TN_SPLIT_K``
      and ``tk`` the fewest even tiles of K that fit beside it.

    Never under 128 x 128.
    """
    tm = 128

    def blocks(tk: int, tn: int) -> int:
        return 2 * (tm * tk * x_bytes + tk * tn * w_bytes + tm * tn * 4) + tm * tn * 4

    def widest(total: int, fits) -> int:
        return max(
            (t for t in range(128, total + 1, 128) if fits(t)), default=128
        )

    if blocks(K, 128) <= budget:
        return K, _even_tile(N, widest(N, lambda tn: blocks(K, tn) <= budget))
    tn = _even_tile(N, min(N, _TN_SPLIT_K))
    return _even_tile(K, widest(K, lambda tk: blocks(tk, tn) <= budget)), tn


def _row_tile(T: int) -> tuple[int, int]:
    """The kernel's row tile for T rows (sublane-aligned, at most 128) and
    the zero rows that pad T up to a multiple of it."""
    tm = min(128, -(-max(T, 1) // 8) * 8)
    return tm, (-T) % tm


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def gmm(
    lhs: jax.Array,          # [m, k] rows sorted by group, m % tm == 0
    rhs: jax.Array,          # [L, G, k, n]: every layer's groups, stacked
    group_sizes: jax.Array,  # [G] i32, sums to m
    layer: jax.Array,        # [1] i32: the layer of ``rhs`` to multiply by
    tiling: tuple[int, int, int],
    interpret: bool = False,
) -> jax.Array:              # [m, n] f32
    """``lhs[rows of group g] @ rhs[layer, g]`` for every group with rows.

    megablox's ``gmm`` (jax.experimental.pallas.ops.tpu.megablox) with the
    layer as a scalar-prefetch operand beside the group metadata (where its
    group offset was, which nothing here shards by): the same metadata, grid
    (n tiles, active row tiles, k tiles), f32 accumulator and masking of a
    short last k tile and of a row tile's rows of other groups, so the result
    is what megablox gives on ``rhs[layer]``, bit for bit. What its call
    cannot say is the weight block ``(layer[0], group, k tile, n tile)`` of a
    4-D operand. The jitted function's name is the device event's
    (``%gmm``), which the benchmark's readers match: keep it."""
    m, k = lhs.shape
    G, n = rhs.shape[1], rhs.shape[3]
    tm, tk, tn = tiling
    tiles_k, k_rem = -(-k // tk), k % tk
    both_bf16 = lhs.dtype == rhs.dtype == jnp.bfloat16
    input_dtype = jnp.bfloat16 if both_bf16 else jnp.float32
    metadata, num_active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=G, visit_empty_groups=False,
    )

    def kernel(metadata, layer, lhs, rhs, out, acc):
        del layer
        group_offsets, group_ids, m_tile_ids = metadata
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero_acc():
            acc[...] = jnp.zeros_like(acc)

        def mask_k_rem(x, dim):
            iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, dim)
            return jnp.where(iota < k_rem, x.astype(jnp.float32), 0).astype(x.dtype)

        def accum(last: bool):
            a, b = lhs[...], rhs[...]
            if last and k_rem:  # the short last k tile reads past K
                a, b = mask_k_rem(a, 1), mask_k_rem(b, 0)
            acc[...] += jax.lax.dot_general(
                a.astype(input_dtype), b.astype(input_dtype),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            if last:  # store this group's rows of the row tile, keep the rest
                group = group_ids[grid_id]
                rows = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
                    jnp.int32, (tm, tn), 0
                )
                start, end = group_offsets[group], group_offsets[group + 1]
                out[...] = jax.lax.select(
                    (rows >= start) & (rows < end), acc[...], out[...]
                )

        jax.lax.cond(
            k_i == tiles_k - 1,
            functools.partial(accum, True), functools.partial(accum, False),
        )

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # index maps: (n tile, active row tile, k tile, metadata, layer);
            # metadata = (group offsets, group of a row tile, its m tile)
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda ni, gi, ki, meta, lyr: (meta[2][gi], ki)
                ),
                pl.BlockSpec(
                    (None, None, tk, tn),
                    lambda ni, gi, ki, meta, lyr: (lyr[0], meta[1][gi], ki, ni),
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, gi, ki, meta, lyr: (meta[2][gi], ni)
            ),
            grid=(-(-n // tn), num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            # not all of rhs: one k x n block a visited row tile
            bytes_accessed=lhs.size * lhs.itemsize * -(-n // tn)
            + k * n * rhs.itemsize * metadata[1].size + m * n * 4,
        ),
        interpret=interpret,
    )(metadata, layer, lhs, rhs)


def layer_of(w: jax.Array, layer) -> jax.Array:
    """One layer's ``[E, ..]`` of a stacked expert leaf ``[L, E, ..]`` (a
    leaf that is one layer's already passes): for the consumers that are
    XLA operations, which fuse the slice as they fuse a scanned leaf's."""
    if w.ndim == 3:
        return w
    return jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)


@jax.named_scope("llmd.moe.gmm")
def grouped_matmul(
    x: jax.Array,            # [T, K_dim] tokens sorted by group
    w: jax.Array,            # [G, K_dim, N], or all layers' [L, G, K_dim, N]
    group_sizes: jax.Array,  # [G] i32, sums to T
    mesh=None,               # the mesh whose devices run this, if any
    layer=None,              # i32 scalar: the layer of a 4-D ``w``
) -> jax.Array:              # [T, N]
    """The kernel reads layer ``layer`` of a stacked ``w`` in place; a 3-D
    ``w`` (the wide-EP local experts, bare arrays) is its one-layer case.
    ``lax.ragged_dot`` has no such operand: there the layer is indexed here,
    a slice that XLA fuses into its consumer."""
    T, K_dim = x.shape
    N = w.shape[-1]
    group_sizes = group_sizes.astype(jnp.int32)
    if not _use_kernel(K_dim, N, mesh):
        return jax.lax.ragged_dot(
            x, layer_of(w, layer), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
    if w.ndim == 3:
        w, layer = w[None], 0
    # The kernel requires m % tile_m == 0 and a sublane-aligned tile: pad
    # rows up to the (8-aligned) tile. Rows past the groups' sum — these zero
    # rows, and the rows of picks whose expert is held on another rank
    # (moe_apply_grouped) — belong to no group: the kernel visits no tile for
    # them, and whatever their output rows hold is sliced off or masked by the
    # caller.
    tm, pad = _row_tile(T)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, K_dim), x.dtype)], axis=0)
    out = gmm(
        x, w, group_sizes, jnp.asarray(layer, jnp.int32).reshape(1),
        tiling=(tm, *gmm_tiles(
            K_dim, N, w.dtype.itemsize, x.dtype.itemsize, _VMEM_BUDGET
        )),
        interpret=ops._interpret(),
    )
    return out[:T].astype(x.dtype)


def grouped_census(
    group_sizes: jax.Array,  # [G] rows per held group
    picks: int,              # the router's picks, T x k: a trace-time number
) -> jax.Array:              # [4] i32
    """One grouped expert layer's line of the step's count: (1 call, the
    groups with at least one row, the router's picks, the picks whose expert
    is held here = the rows the layer multiplies). A group without rows costs
    the kernel no tile and no weight byte; gate, up and down of a layer see
    the same group sizes and share this one line. Where every expert is held
    the last two are equal."""
    return jnp.stack([
        jnp.int32(1), jnp.sum(group_sizes > 0, dtype=jnp.int32),
        jnp.int32(picks), jnp.sum(group_sizes, dtype=jnp.int32),
    ])


def expert_mlp_grouped(
    x_sorted: jax.Array,     # [T', H] rows sorted by expert
    group_sizes: jax.Array,  # [E]
    we_gate: jax.Array | None,  # [E, H, F] (bf16, or int8 with scales), or
    we_up: jax.Array,        # [E, H, F]   all layers' [L, E, ..] with
    we_down: jax.Array,      # [E, F, H]   ``layer``; ``we_gate`` None: the
                             # non-gated experts, down(relu(up x)^2)
    scales: tuple | None = None,  # int8 experts: (s_gate [E,F], s_up [E,F], s_down [E,H])
    biases: tuple | None = None,  # gpt-oss experts: (b_gate [E,F], b_up [E,F], b_down [E,H])
    cfg=None,                # ModelConfig for the activation family
    mesh=None,               # the mesh whose devices run this, if any
    layer=None,              # i32 scalar: the layer of stacked weights
) -> jax.Array:              # [T', H]
    from llmd_tpu.models.moe import expert_glu, relu2

    T = x_sorted.shape[0]
    E = we_down.shape[-3]
    if scales is not None:
        from llmd_tpu.ops.quant import grouped_matmul_q

        mm = lambda x, w, s: grouped_matmul_q(  # noqa: E731
            x, layer_of(w, layer), s, group_sizes
        )
    else:
        mm = lambda x, w, s: grouped_matmul(x, w, group_sizes, mesh, layer)  # noqa: E731
    s_gate, s_up, s_down = scales if scales is not None else (None,) * 3
    if we_gate is None:
        # Two grouped matmuls a layer, relu^2 between them.
        act = relu2(mm(x_sorted, we_up, s_up))
        return mm(act.astype(x_sorted.dtype), we_down, s_down)
    gate = mm(x_sorted, we_gate, s_gate)
    up = mm(x_sorted, we_up, s_up)
    gid = None
    if biases is not None:
        gid = jnp.repeat(
            jnp.arange(E, dtype=jnp.int32), group_sizes, total_repeat_length=T
        )
        gate = gate + biases[0][gid]
        up = up + biases[1][gid]
    act = (
        expert_glu(gate, up, cfg) if cfg is not None
        else jax.nn.silu(gate) * up  # bare-array callers (tests)
    )
    out = mm(act.astype(x_sorted.dtype), we_down, s_down)
    if biases is not None:
        out = out + biases[2][gid].astype(out.dtype)
    return out


def held_slots(ids: jax.Array, cfg, E: int) -> jax.Array:
    """The router's picks ``ids`` (any shape, ids over the router's whole
    width) as slots of the ``E`` experts held here, ``cfg.
    held_experts_first`` onward. A pick whose expert lives on another rank
    gets slot ``E``, one past the held groups."""
    local = ids - cfg.held_experts_first
    return jnp.where((local >= 0) & (local < E), local, E)


def moe_apply_grouped(
    ht: jax.Array,       # [T, H]
    weights: jax.Array,  # [T, k] f32 combine weights (scaled/normalized)
    ids: jax.Array,      # [T, k] i32 expert ids over the router's width
    we_gate: jax.Array | None,  # one layer's [E, ..] HELD experts, or all
    we_up: jax.Array,    # layers' stacked [L, E, ..] with ``layer``; the
    we_down: jax.Array,  # non-gated experts have no ``we_gate``
    scales: tuple | None = None,
    biases: tuple | None = None,
    cfg=None,
    mesh=None,
    emit_census: bool = False,
    layer=None,
) -> jax.Array:          # [T, H] f32
    """Route -> sort-by-expert -> grouped MLP -> weighted unsort-combine,
    over the experts held here (``cfg.held_experts_first`` onward, as many
    as the weights hold): the sum of the held experts' terms. A pick of an
    expert held elsewhere sorts past the last group, so it is in no group:
    no row of the matmuls, no weight byte. With ``emit_census`` the return
    is ``(y, census)``, ``census`` this layer's ``grouped_census`` line."""
    T, H = ht.shape
    k = ids.shape[1]
    E = we_down.shape[-3]
    share = cfg is not None and not cfg.holds_all_experts
    flat_ids = ids.reshape(-1)                       # [T*k]
    if share:
        flat_ids = held_slots(flat_ids, cfg, E)
    # Explicitly stable: equal expert ids keep token order, so the sorted
    # row layout — and the f32 scatter-add accumulation order below — is
    # deterministic across backends (XLA's default sort is NOT guaranteed
    # stable everywhere; tests/test_wide_ep.py pins this).
    order = jnp.argsort(flat_ids, stable=True)
    tok = order // k                                 # source token per slot
    xs = ht[tok]                                     # [T*k, H]
    group_sizes = jnp.bincount(flat_ids, length=E + 1)[:E]
    ys = expert_mlp_grouped(
        xs, group_sizes, we_gate, we_up, we_down, scales=scales,
        biases=biases, cfg=cfg, mesh=mesh, layer=layer,
    )
    w_sorted = weights.reshape(-1)[order]
    if share:
        # Rows past the held picks were in no group: nothing computed them.
        in_group = (jnp.arange(T * k) < jnp.sum(group_sizes))[:, None]
        ys = jnp.where(in_group, ys, 0)
    y = (
        jnp.zeros((T, H), jnp.float32)
        .at[tok]
        .add(ys.astype(jnp.float32) * w_sorted[:, None])
    )
    if not emit_census:
        return y
    return y, grouped_census(group_sizes, T * k)
