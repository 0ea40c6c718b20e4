"""Wide expert parallelism: shard_map dispatch/combine over ICI.

The TPU-native replacement for the reference's DeepEP/NVSHMEM all-to-all
kernels (docs/architecture/foundations/wide-expert-parallelism.md:20-30;
`--all2all-backend deepep_low_latency|deepep_high_throughput`, wide-ep-lws
decode.yaml:127): experts are sharded over the flattened (dp, tp) mesh axes,
tokens are dispatched to their experts' shards with ONE ``lax.all_to_all``,
computed locally, and combined back with a second all_to_all. XLA lowers
both onto ICI; there is no NVSHMEM equivalent to manage.

Shape discipline (XLA requires static shapes): dispatch is capacity-based
GShard-style — each shard sends at most C token-slots to every other shard.
Slots past capacity are dropped (their combine weight contributes zero), so
``capacity_factor`` trades padding FLOPs against drop probability; tests and
the decode path size C for zero drops, matching the numerics of the dense
path exactly. Drops are never silent: the census (below) counts them.

Three composable perf layers sit on top of the base dispatch:

- **Overlap** (``overlap`` = N microbatches): the per-shard token slab is
  split into N independent dispatch→grouped-GEMM→combine chains. No chain
  reads another's results, so XLA's latency-hiding scheduler is free to
  issue microbatch i+1's dispatch all-to-all while microbatch i's expert
  matmul still occupies the MXU — the software-pipelined form of the
  reference's DBO, but *within* one MoE layer. Off by default
  (``ParallelConfig.moe_overlap``); byte-identical to the monolithic path
  at zero-drop capacity because every per-token result depends only on
  that token's own slots (grouped-GEMM rows are row-independent and the
  per-row contraction order is fixed).
- **Placement** (EPLB, :mod:`llmd_tpu.parallel.eplb`): the router emits
  *logical* expert ids; an optional placement table maps them to
  *physical* slots — hot experts replicated across shards, cold ones
  packed — before the shard/slot split. Balanced placement collapses
  dispatch skew, which is what lets capacity track the mean.
- **Census**: a per-call ``[E+2]`` stats vector — routed tokens per
  logical expert (EPLB's input signal), dropped slots (a real metric,
  not silent zeroing), and the step's max per-destination demand as a
  fraction of the zero-skew share (the adaptive capacity_factor's input).
  Replicated via psum/pmax so the runner reads it without extra
  collectives.

Local expert compute runs the grouped GEMM (``ops.grouped_gemm``, the
DeepGEMM role): received slots sorted by local expert id feed
the Pallas kernel on TPU or ``lax.ragged_dot`` elsewhere, sized by the
*received* group sizes so balanced placement directly shrinks padded FLOPs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llmd_tpu.config import ModelConfig
from llmd_tpu.models.moe import router_topk

EP_SPEC = P(("dp", "tp"))

# Census vector layout: [0:E] routed (valid) tokens per LOGICAL expert,
# [E] dropped valid slots, [E+1] max per-destination dispatch demand as a
# multiple of the zero-skew share T*k/W (i.e. the capacity_factor this
# step actually required). Sums accumulate; the demand element maxes.
CENSUS_EXTRA = 2


def census_size(cfg: ModelConfig) -> int:
    return cfg.num_experts + CENSUS_EXTRA


def census_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Combine two census deltas: counts add, the demand element maxes."""
    return jnp.concatenate([a[:-1] + b[:-1], jnp.maximum(a[-1:], b[-1:])])


def census_zero(cfg: ModelConfig) -> jax.Array:
    return jnp.zeros((census_size(cfg),), jnp.float32)


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _capacity(t: int, k: int, W: int, capacity_factor: float) -> int:
    """Per-shard send capacity to EACH destination for t local tokens.
    Zero-drop bound is t * k (every local slot targets the same shard)."""
    return min(
        _round_up(max(int(math.ceil(t * k / W * capacity_factor)), 8), 8),
        _round_up(t * k, 8),
    )


def moe_block_ep(
    h: jax.Array,  # [B, Q, H]
    lp: dict,
    cfg: ModelConfig,
    mesh,
    capacity_factor: float = 2.0,
    overlap: int = 0,
    placement: dict | None = None,
    emit_census: bool = False,
):
    """EP MoE on [B, Q, H]; call inside jit with params EP-sharded.

    ``overlap`` > 1 splits each shard's tokens into that many independent
    dispatch/compute/combine microbatches (see module docstring).
    ``placement`` carries replicated EPLB tables ({"phys_to_logical",
    "replicas", "n_replicas"} as device arrays); when given, the ``we_*``
    leaves in ``lp`` must already be remapped to the physical layout.
    With ``emit_census`` the return is ``(y, census_delta)`` where
    ``census_delta`` is the replicated [E+2] f32 stats vector.
    """
    B, Q, H = h.shape
    axes = EP_SPEC[0]
    W = math.prod(mesh.shape[a] for a in axes)
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    E_phys = E if placement is None else int(placement["phys_to_logical"].shape[0])
    if E % W:
        raise ValueError(f"num_experts {E} not divisible by EP world {W}")
    if E_phys % W:
        raise ValueError(
            f"physical experts {E_phys} not divisible by EP world {W}"
        )
    n_mb = max(int(overlap), 1)
    T = B * Q
    Tp = _round_up(T, W * n_mb)
    ht = h.reshape(T, H)
    valid = jnp.arange(Tp, dtype=jnp.int32) < T
    if Tp > T:
        ht = jnp.concatenate([ht, jnp.zeros((Tp - T, H), h.dtype)], axis=0)

    t_loc = Tp // W
    t_mb = t_loc // n_mb
    C = _capacity(t_mb, k, W, capacity_factor)

    local = functools.partial(
        _moe_ep_local, cfg=cfg, W=W, C=C, axes=axes, n_mb=n_mb,
        E_phys=E_phys, emit_census=emit_census, mesh=mesh,
    )
    # Per-param specs: experts (and their int8 channel scales) sharded over
    # the flattened EP axes; router + shared expert replicated. Passing a
    # dict through shard_map keeps the bf16 and int8 layouts in one code
    # path — the scale leaves just ride along when present.
    ep = P(("dp", "tp"))
    specs_by_name = {
        "router": P(None, None), "router_bias": P(None),
        "we_gate": P(ep[0], None, None), "we_up": P(ep[0], None, None),
        "we_down": P(ep[0], None, None),
        "we_gate_scale": P(ep[0], None), "we_up_scale": P(ep[0], None),
        "we_down_scale": P(ep[0], None),
        "we_gate_b": P(ep[0], None), "we_up_b": P(ep[0], None),
        "we_down_b": P(ep[0], None),
        "ws_gate": P(None, None), "ws_up": P(None, None),
        "ws_down": P(None, None),
        "ws_gate_scale": P(None), "ws_up_scale": P(None),
        "ws_down_scale": P(None),
    }
    sub = {k: lp[k] for k in specs_by_name if k in lp}
    if not cfg.shared_expert_intermediate_size:
        for k in list(sub):
            if k.startswith("ws_"):
                del sub[k]
    if "router_bias" not in sub:
        sub["router_bias"] = jnp.zeros((E,), jnp.float32)
    place = placement if placement is not None else {}
    place_specs = {k: P(*([None] * v.ndim)) for k, v in place.items()}
    out_specs = (EP_SPEC, P()) if emit_census else EP_SPEC
    out = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            EP_SPEC, EP_SPEC, {k: specs_by_name[k] for k in sub}, place_specs
        ),
        out_specs=out_specs,
        check_vma=False,
    )(ht, valid, sub, place)
    if emit_census:
        y, census = out
        return y[:T].reshape(B, Q, H), census
    return out[:T].reshape(B, Q, H)


def _dispatch_compute_combine(
    xc, wc, destc, e_localc, validc, p, *, cfg, W, C, axes, E_loc, mesh
):
    """One microbatch chain: dispatch a2a → grouped experts → combine a2a.

    xc: [t, H] tokens; wc: [t, k] combine weights; destc/e_localc: [t*k]
    physical shard / local-slot per routed slot; validc: [t*k] real-token
    mask. Returns (y [t, H] f32-accumulated, dropped_valid_slots scalar,
    max_dest_demand scalar).
    """
    t, H = xc.shape
    k = cfg.num_experts_per_tok
    tk = t * k

    # Rank of each slot within its destination's send queue (stable
    # order). Padding slots are masked OUT of the competition so they
    # never consume capacity and the demand census counts real tokens.
    onehot_dest = (
        jax.nn.one_hot(destc, W, dtype=jnp.int32) * validc[:, None]
    )  # [tk, W]
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot_dest, axis=0), destc[:, None], axis=1
    )[:, 0] - 1  # [tk]
    demand = jnp.max(jnp.sum(onehot_dest, axis=0))  # hottest destination
    keep = (rank < C) & validc
    dropped = jnp.sum(validc & ~keep)
    slot = jnp.where(keep, rank, C)  # overflow lands in a scratch slot

    # Scatter into [W, C+1, ...] send buffers (scratch slot C dropped below).
    src_tok = jnp.repeat(jnp.arange(t), k)
    send_x = jnp.zeros((W, C + 1, H), xc.dtype).at[destc, slot].set(xc[src_tok])
    send_e = jnp.zeros((W, C + 1), jnp.int32).at[destc, slot].set(e_localc)
    send_v = jnp.zeros((W, C + 1), jnp.bool_).at[destc, slot].set(keep)

    # Dispatch: one ICI all-to-all (the deepep dispatch equivalent).
    recv_x = jax.lax.all_to_all(send_x[:, :C], axes, 0, 0)  # [W, C, H]
    recv_e = jax.lax.all_to_all(send_e[:, :C], axes, 0, 0)
    recv_v = jax.lax.all_to_all(send_v[:, :C], axes, 0, 0)

    xr = recv_x.reshape(W * C, H)
    er = recv_e.reshape(W * C)
    vr = recv_v.reshape(W * C)

    # Local experts via grouped GEMM (DeepGEMM role): sort received slots
    # by local expert id so each expert multiplies only its rows, sized
    # by the RECEIVED group sizes (bincount) so balanced placement
    # shrinks the ragged work directly. The sort is explicitly stable:
    # equal expert ids keep arrival order, so the f32 row layout — and
    # therefore any accumulation the kernel does — is deterministic
    # across backends. Invalid slots carry zero inputs (the send buffers
    # initialize to zero), so their MLP output is zero; the vr mask
    # stays as belt-and-braces.
    from llmd_tpu.ops.grouped_gemm import expert_mlp_grouped

    order = jnp.argsort(er, stable=True)
    group_sizes = jnp.bincount(er, length=E_loc)
    scales = None
    if "we_gate_scale" in p:
        scales = (p["we_gate_scale"], p["we_up_scale"], p["we_down_scale"])
    biases = None
    if "we_gate_b" in p:
        biases = (p["we_gate_b"], p["we_up_b"], p["we_down_b"])
    ys = expert_mlp_grouped(
        xr[order], group_sizes, p["we_gate"], p["we_up"], p["we_down"],
        scales=scales, biases=biases, cfg=cfg, mesh=mesh,
    )
    yr = (
        jnp.zeros_like(xr).at[order].set(ys)
        * vr[:, None].astype(xr.dtype)
    )

    # Combine: reverse all-to-all returns each slot to its source shard.
    back = jax.lax.all_to_all(yr.reshape(W, C, H), axes, 0, 0)  # [W, C, H]
    back = jnp.concatenate([back, jnp.zeros((W, 1, H), back.dtype)], axis=1)

    gathered = back[destc, slot]  # [tk, H]; scratch slot = zeros
    w_flat = (wc.reshape(-1) * keep.astype(wc.dtype))[:, None]
    y = jnp.sum(
        (gathered.astype(jnp.float32) * w_flat).reshape(t, k, H), axis=1
    )
    return y, dropped, demand


def _moe_ep_local(
    ht, valid, p: dict, place: dict, *,
    cfg: ModelConfig, W: int, C: int, axes, n_mb: int, E_phys: int,
    emit_census: bool, mesh,
):
    """Per-shard body: route → [n_mb x (dispatch a2a → local experts →
    combine a2a)] → shared expert.

    ht: [t, H] local tokens; valid: [t] real-token mask (padding rows are
    excluded from dispatch); p holds this shard's params (we_*:
    [E_loc, ...] local PHYSICAL experts, plus channel scales when
    int8-quantized); place holds the replicated EPLB tables (empty dict =
    identity layout).
    """
    t, H = ht.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    E_loc = E_phys // W

    # Router on the full local slab (microbatches slice its output, so
    # overlap never perturbs routing numerics).
    weights, ids = router_topk(ht, p["router"], k, cfg, p["router_bias"])
    flat_logical = ids.reshape(-1)  # [tk] LOGICAL expert ids
    tk = t * k
    if place:
        # Logical → physical through the EPLB tables: a hot expert's
        # slots round-robin over its replicas (deterministic spreader:
        # the slot's position modulo the replica count), so one logical
        # expert's traffic splits across the distinct shards hosting it.
        n_rep = place["n_replicas"][flat_logical]  # [tk]
        which = jnp.arange(tk, dtype=jnp.int32) % jnp.maximum(n_rep, 1)
        flat_phys = place["replicas"][flat_logical, which]
    else:
        flat_phys = flat_logical
    dest = flat_phys // E_loc  # destination shard per slot
    e_local = flat_phys % E_loc  # expert slot on that shard
    valid_slot = jnp.repeat(valid, k)  # [tk]

    t_mb = t // n_mb
    km = t_mb * k
    ys, drops, demands = [], [], []
    for i in range(n_mb):
        ts, ks = slice(i * t_mb, (i + 1) * t_mb), slice(i * km, (i + 1) * km)
        y_i, d_i, dem_i = _dispatch_compute_combine(
            ht[ts], weights[ts], dest[ks], e_local[ks], valid_slot[ks], p,
            cfg=cfg, W=W, C=C, axes=axes, E_loc=E_loc, mesh=mesh,
        )
        ys.append(y_i)
        drops.append(d_i)
        demands.append(dem_i)
    y = jnp.concatenate(ys, axis=0).astype(ht.dtype) if n_mb > 1 else (
        ys[0].astype(ht.dtype)
    )

    if "ws_gate" in p:
        from llmd_tpu.models.moe import shared_expert_ffn

        y = y + shared_expert_ffn(ht, p)
    if not emit_census:
        return y

    # Census: replicated [E+2] f32. Routed-token counts are over LOGICAL
    # ids (EPLB's signal must see through its own remap) and valid slots
    # only; the demand element is normalized by the microbatch's
    # zero-skew share t_mb*k/W so it reads directly as the
    # capacity_factor this step required.
    counts = jnp.bincount(
        flat_logical, weights=valid_slot.astype(jnp.float32), length=E
    )
    dropped = jnp.sum(jnp.stack(drops)).astype(jnp.float32)
    demand = jnp.max(jnp.stack(demands)).astype(jnp.float32)
    sums = jax.lax.psum(
        jnp.concatenate([counts, dropped[None]]), axes
    )
    need = jax.lax.pmax(demand, axes) * (W / (t_mb * k))
    census = jnp.concatenate([sums, need[None]])
    return y, census
