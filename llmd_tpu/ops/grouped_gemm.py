"""Grouped GEMM for MoE expert compute — the DeepGEMM role.

The reference's wide-EP decode path routes MoE through DeepGEMM's masked
grouped GEMMs (`--moe-backend deep_gemm`, guides/wide-ep-lws/modelserver/
gpu/vllm/base/decode.yaml:128) so each expert multiplies ONLY its routed
tokens. The TPU-native equivalent: tokens sorted by expert id feed a
ragged/grouped matmul — jax's Pallas megablocks kernel (`megablox.gmm`)
on TPU, `lax.ragged_dot` elsewhere — instead of the one-hot masked
contraction that burns E/top_k redundant FLOPs.

FLOPs per token: 3 * k * H * F (exactly the routed work) vs the dense
combine's 3 * E * H * F.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from llmd_tpu import ops


def _use_megablox(H: int, F: int, mesh=None) -> bool:
    """megablox wants lane-tiled contraction/output dims; anything else
    (tiny models) takes the XLA ragged_dot, which is correct everywhere.
    LLMD_PALLAS=interpret forces the kernel in interpret mode so CPU CI
    parity-tests the same glue (tiling, padding, sorting) TPUs run. The
    same decision ladder as every other op (ops._decide: env lever,
    geometry, the platform of ``mesh``'s devices), recorded the same way;
    the kernel runs per device, so there is no mesh rule to pass."""
    return ops._decide(
        "grouped_gemm", H % 128 == 0 and F % 128 == 0, 1, mesh
    ) != "xla"


@jax.named_scope("llmd.moe.gmm")
def grouped_matmul(
    x: jax.Array,            # [T, K_dim] tokens sorted by group
    w: jax.Array,            # [G, K_dim, N]
    group_sizes: jax.Array,  # [G] i32, sums to T
    mesh=None,               # the mesh whose devices run this, if any
) -> jax.Array:              # [T, N]
    T, K_dim = x.shape
    G, _, N = w.shape
    if _use_megablox(K_dim, N, mesh):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        # gmm requires m % tile_m == 0 and a sublane-aligned tile: pad rows
        # up to the (8-aligned) tile. Pad rows are zero and land in the
        # LAST group (group_sizes must sum to m); their zero outputs are
        # sliced off below.
        tm = min(128, -(-max(T, 1) // 8) * 8)
        pad = (-T) % tm
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, K_dim), x.dtype)], axis=0)
            group_sizes = group_sizes.at[-1].add(pad)
        out = gmm(
            x, w, group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32,
            tiling=(tm, 128, 128),
            interpret=ops._interpret(),
        )
        return out[:T].astype(x.dtype)
    return jax.lax.ragged_dot(
        x, w, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def expert_mlp_grouped(
    x_sorted: jax.Array,     # [T', H] rows sorted by expert
    group_sizes: jax.Array,  # [E]
    we_gate: jax.Array,      # [E, H, F] (bf16, or int8 with scales)
    we_up: jax.Array,        # [E, H, F]
    we_down: jax.Array,      # [E, F, H]
    scales: tuple | None = None,  # int8 experts: (s_gate [E,F], s_up [E,F], s_down [E,H])
    biases: tuple | None = None,  # gpt-oss experts: (b_gate [E,F], b_up [E,F], b_down [E,H])
    cfg=None,                # ModelConfig for the activation family
    mesh=None,               # the mesh whose devices run this, if any
) -> jax.Array:              # [T', H]
    from llmd_tpu.models.moe import expert_glu

    T = x_sorted.shape[0]
    E = we_gate.shape[0]
    if scales is not None:
        from llmd_tpu.ops.quant import grouped_matmul_q

        mm = lambda x, w, s: grouped_matmul_q(x, w, s, group_sizes)  # noqa: E731
    else:
        mm = lambda x, w, s: grouped_matmul(x, w, group_sizes, mesh)  # noqa: E731
    s_gate, s_up, s_down = scales if scales is not None else (None,) * 3
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


def moe_apply_grouped(
    ht: jax.Array,       # [T, H]
    weights: jax.Array,  # [T, k] f32 combine weights (scaled/normalized)
    ids: jax.Array,      # [T, k] i32 expert ids
    we_gate: jax.Array,
    we_up: jax.Array,
    we_down: jax.Array,
    scales: tuple | None = None,
    biases: tuple | None = None,
    cfg=None,
    mesh=None,
) -> jax.Array:          # [T, H] f32
    """Route -> sort-by-expert -> grouped MLP -> weighted unsort-combine."""
    T, H = ht.shape
    k = ids.shape[1]
    E = we_gate.shape[0]
    flat_ids = ids.reshape(-1)                       # [T*k]
    # Explicitly stable: equal expert ids keep token order, so the sorted
    # row layout — and the f32 scatter-add accumulation order below — is
    # deterministic across backends (XLA's default sort is NOT guaranteed
    # stable everywhere; tests/test_wide_ep.py pins this).
    order = jnp.argsort(flat_ids, stable=True)
    tok = order // k                                 # source token per slot
    xs = ht[tok]                                     # [T*k, H]
    group_sizes = jnp.bincount(flat_ids, length=E)
    ys = expert_mlp_grouped(
        xs, group_sizes, we_gate, we_up, we_down, scales=scales,
        biases=biases, cfg=cfg, mesh=mesh,
    )
    w_sorted = weights.reshape(-1)[order]
    return (
        jnp.zeros((T, H), jnp.float32)
        .at[tok]
        .add(ys.astype(jnp.float32) * w_sorted[:, None])
    )
