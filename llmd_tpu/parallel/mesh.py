"""Device mesh and sharding rules.

The reference scales with NCCL/NVSHMEM process groups per parallelism kind
(TP all-reduce, DP supervisor ranks, DeepEP all-to-all; SURVEY.md 2.4/2.5).
TPU-native, all of them are axes of ONE ``jax.sharding.Mesh`` laid out over
ICI, and XLA inserts the collectives:

- axis "tp"  -- tensor parallelism: weight matrices sharded on the
  head/ffn dimension; activations replicated; XLA emits psum over ICI where
  the reference runs NCCL all-reduce.
- axis "dp"  -- data parallelism for attention: the batch dimension is
  sharded; KV caches are fully local to each dp group (the property wide-EP
  exploits to avoid MLA KV replication, reference
  docs/architecture/foundations/wide-expert-parallelism.md:5-30).
- experts are sharded over BOTH axes flattened ("dp","tp") -- wide EP: every
  chip owns E/world experts while attention runs DP x TP. The MoE layer uses
  shard_map + lax.all_to_all where the reference dispatches DeepEP/NVSHMEM
  kernels (wide-expert-parallelism.md:20-30).

Mesh axis order is ("dp", "tp") with tp innermost so TP collectives ride the
fastest ICI dimension on a real slice.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmd_tpu.config import ParallelConfig

DP_AXIS = "dp"
TP_AXIS = "tp"
# Expert parallelism spans the flattened (dp, tp) axes.
EP_AXES = (DP_AXIS, TP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    dp: int
    tp: int

    @property
    def world(self) -> int:
        return self.dp * self.tp

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def build_mesh(
    parallel: ParallelConfig | None = None,
    devices: list | None = None,
) -> MeshContext:
    """Build the (dp, tp) mesh.

    With a TPU slice, jax.devices() ordering already follows the physical
    torus; jax.make_mesh picks an ICI-friendly assignment.
    """
    if devices is None:
        devices = jax.devices()
    if parallel is None:
        parallel = ParallelConfig(
            tensor_parallel_size=len(devices), data_parallel_size=1
        )
    dp, tp = parallel.data_parallel_size, parallel.tensor_parallel_size
    if dp * tp > len(devices):
        raise ValueError(f"mesh {dp}x{tp} needs {dp*tp} devices, have {len(devices)}")
    devs = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    mesh = Mesh(devs, (DP_AXIS, TP_AXIS))
    return MeshContext(mesh=mesh, dp=dp, tp=tp)


# ----------------------------------------------------------------------- #
# Sharding rules: map param-tree leaf names -> PartitionSpec.
# Layer stacks carry a leading L dim, hence the leading None.

PARAM_SPECS: dict[str, P] = {
    # [V, H]: shard vocab so the embed gather load-balances over tp.
    "embed": P(TP_AXIS, None),
    "final_norm": P(),
    # [H, V]: column-parallel; logits all-gathered on the vocab axis.
    "lm_head": P(None, TP_AXIS),
    # layers.* ([L, ...])
    "input_norm": P(None, None),
    "post_norm": P(None, None),
    "wq": P(None, None, TP_AXIS),   # [L, H, Nq*D] head-sharded
    # Fused projections (runner._maybe_fuse, tp == 1 only): replicated.
    "wqkv": P(None, None, None),
    "w_gu": P(None, None, None),
    "wk": P(None, None, TP_AXIS),
    "wv": P(None, None, TP_AXIS),
    "wo": P(None, TP_AXIS, None),   # [L, Nq*D, H] row-parallel -> psum
    "bq": P(None, TP_AXIS),
    "bk": P(None, TP_AXIS),
    "bv": P(None, TP_AXIS),
    "bo": P(None, None),          # [L, H] row-parallel output, replicated
    "sinks": P(None, TP_AXIS),    # [L, Nq] per-q-head sink logits
    "attn_q_norm": P(None, None),  # [L, D] per-head norm, replicated
    "attn_k_norm": P(None, None),
    # Learned sparse attention's indexer (one device only; replicated).
    "wi_q": P(None, None, None),    # [L, H, J*Di]
    "wi_k": P(None, None, None),    # [L, H, Di] one shared key per token
    "wi_w": P(None, None, None),    # [L, H, J] per-head score weights
    "wi_k_norm": P(None, None),     # [L, Di] LayerNorm weight on the key
    "wi_k_norm_b": P(None, None),   # [L, Di] and its bias
    # State-space mixers (models/mamba.py; one device only; replicated).
    "m_in": P(None, None, None),      # [Lm, H, 2*d_in + 2N + heads]
    "m_conv_w": P(None, None, None),  # [Lm, K, C]
    "m_conv_b": P(None, None),        # [Lm, C]
    "m_A_log": P(None, None),         # [Lm, heads]
    "m_dt_bias": P(None, None),
    "m_D": P(None, None),
    "m_norm": P(None, None),          # [Lm, d_in]
    "m_out": P(None, None, None),     # [Lm, d_in, H]
    # Gated delta-rule mixers (models/gdn.py; one device only; replicated).
    "g_in": P(None, None, None),      # [Lg, H, q | k | v | z]
    "g_ba": P(None, None, None),      # [Lg, H, b | a]
    "g_conv_w": P(None, None, None),  # [Lg, K, C]
    "g_A_log": P(None, None),         # [Lg, value heads]
    "g_dt_bias": P(None, None),
    "g_norm": P(None, None),          # [Lg, value head dim]
    "g_out": P(None, None, None),     # [Lg, value dim, H]
    # LoRA: down-projections replicated (rank is tiny), up-projections
    # head-sharded like their base weights.
    "la_q": P(None, None, None, None),       # [L, A+1, H, r]
    "lb_q": P(None, None, None, TP_AXIS),    # [L, A+1, r, Nq*D]
    "la_v": P(None, None, None, None),
    "lb_v": P(None, None, None, TP_AXIS),    # [L, A+1, r, K*D]
    "w_gate": P(None, None, TP_AXIS),  # [L, H, F]
    "w_up": P(None, None, TP_AXIS),
    "w_down": P(None, TP_AXIS, None),  # [L, F, H]
    # MoE: experts sharded over the flattened (dp, tp) axes = wide EP.
    "router": P(None, None, None),       # [L, H, E] replicated (tiny)
    "router_bias": P(None, None),        # [L, E] replicated (V3 noaux_tc)
    "we_gate": P(None, EP_AXES, None, None),  # [L, E, H, Fm]
    "we_up": P(None, EP_AXES, None, None),
    "we_down": P(None, EP_AXES, None, None),  # [L, E, Fm, H]
    "we_gate_b": P(None, EP_AXES, None),      # gpt-oss expert biases
    "we_up_b": P(None, EP_AXES, None),
    "we_down_b": P(None, EP_AXES, None),
    "ws_sig": P(None, None, None),       # [L, H, 1] the shared expert's own gate
    "ws_gate": P(None, None, TP_AXIS),   # shared expert, TP like dense mlp
    "ws_up": P(None, None, TP_AXIS),
    "ws_down": P(None, TP_AXIS, None),
    # MLA (DeepSeek family): down-projections + latent norms replicated
    # (latent is shared by all heads); per-head up-projections column-
    # sharded, output row-parallel. The latent KV cache replicates across
    # tp (kv_cache_heads == 1) — its small row width is the point.
    "wkv_a": P(None, None, None),        # [L, H, rank+rope]
    "kv_norm": P(None, None),
    "wkv_b": P(None, None, TP_AXIS),     # [L, rank, nh*(nope+v)] head-sharded
    "wq_a": P(None, None, None),         # [L, H, q_rank]
    "q_norm": P(None, None),
    "wq_b": P(None, None, TP_AXIS),      # [L, q_rank, nh*(nope+rope)]
}

# KV cache [L, num_pages, K, page, 2D] (head-major within a page so one
# (page, head) DMA is contiguous for the Pallas kernel): shard kv heads over
# tp; each dp group holds its own full pool (allocated per dp rank at the
# engine level).
KV_CACHE_SPEC = P(None, None, TP_AXIS, None, None)


def kv_cache_spec(num_kv_heads: int, tp: int) -> P:
    """KV-cache PartitionSpec, degrading gracefully for GQA.

    When tp exceeds (or doesn't divide) the KV head count the heads are
    replicated across the tp axis — same policy as the reference engine's
    GQA handling where each TP rank holds a full KV head copy rather than
    a fractional head. Under jit/GSPMD this is a layout choice only;
    results are identical.
    """
    if num_kv_heads % tp == 0:
        return KV_CACHE_SPEC
    warnings.warn(
        f"num_kv_heads={num_kv_heads} not divisible by tp={tp}: replicating "
        f"the KV pool on every tp device ({tp}x the per-chip HBM of the "
        "sharded layout). Pick tp <= num_kv_heads for production configs.",
        stacklevel=2,
    )
    return P()


def param_specs(params: dict) -> dict:
    """PartitionSpec tree matching a model param tree."""

    def spec_for(name: str) -> P:
        if name.endswith("_scale"):
            # int8 channel scales (llmd_tpu.ops.quant): the weight's shape
            # minus its contraction (-2) axis, so the spec is the base
            # weight's spec with that axis dropped.
            base = spec_for(name[: -len("_scale")])
            return P(*base[:-2], base[-1])
        if name not in PARAM_SPECS:
            raise KeyError(f"no sharding rule for param {name!r}")
        return PARAM_SPECS[name]

    out: dict = {}
    for k, v in params.items():
        if isinstance(v, dict):
            out[k] = {kk: spec_for(kk) for kk in v}
        else:
            out[k] = spec_for(k)
    return out


def shard_params(params: dict, ctx: MeshContext) -> dict:
    """Place a param tree onto the mesh per PARAM_SPECS.

    Single-process: plain device_put. Multi-host (jax.distributed world,
    mesh spanning processes): every host holds the full tree on host
    memory (deterministic init / every host reads the checkpoint — the
    reference's LWS ranks do the same HF download per pod), and each
    process contributes the shards its local devices own via
    make_array_from_callback; no host ever transfers non-addressable data.
    """
    specs = param_specs(params)
    multihost = jax.process_count() > 1

    def put(x, s):
        sharding = ctx.sharding(*s)
        if not multihost:
            return jax.device_put(x, sharding)
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx, arr=arr: arr[idx]
        )

    return jax.tree.map(
        put, params, specs, is_leaf=lambda x: not isinstance(x, dict)
    )
