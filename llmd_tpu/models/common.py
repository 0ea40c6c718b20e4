"""Shared model building blocks (functional, jit-friendly)."""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StepInput:
    """Device inputs for one forward step (static shapes per bucket).

    token_ids:  [B, Q] input token ids (padded)
    positions:  [B, Q] absolute positions (padded rows repeat last valid)
    query_lens: [B] valid token count per row
    kv_lens:    [B] total valid kv length per seq AFTER this step's writes
    page_table: [B, max_pages] physical page ids
    """

    token_ids: jax.Array
    positions: jax.Array
    query_lens: jax.Array
    kv_lens: jax.Array
    page_table: jax.Array
    # Per-sequence LoRA adapter slot ([B] i32, 0 = base model); None when
    # the model has no adapters (keeps the pytree/compile cache stable
    # for non-LoRA configs).
    lora_ids: jax.Array | None = None
    # Ring-view page table for sliding-window layers ([B, max_pages] i32,
    # entries repeat modulo the per-sequence ring length); None unless the
    # engine runs with CacheConfig.swa_ring.
    swa_page_table: jax.Array | None = None
    # Flattened-token layout (`--ragged-qlens`): when set, the "batch"
    # axis is a packed token stream — token_ids/positions are [T, 1],
    # query_lens/kv_lens are per TOKEN (kv_len = position + 1, which IS
    # the causal mask derived from cu_q_lens), and page_table stays the
    # COMPACT per-row table [R, max_pages] indexed through this [T] i32
    # token -> row map. None keeps the bucketed [B, Q] layout.
    token_rows: jax.Array | None = None
    # Run-addressed KV-write plan for the flattened layout:
    # ((src, off, cnt), phys_main, phys_swa) where each run writes
    # ``cnt`` consecutive stream tokens into one physical page at slots
    # [off, off+cnt) — the same-page-safe addressing the Pallas write
    # kernel needs (per-token decode writes would violate its
    # distinct-pages pipeline precondition). ``src`` indexes the padded
    # [K, T + 2*page, 2D] token slab (src = page + t0 - off, so slab row
    # off+j holds token t0+j). phys_swa is None without a SWA ring.
    flat_runs: tuple | None = None
    # The flat attention's shared-prefix runs over ``page_table``
    # (engine/prefix_runs.py): (run_lead, run_blocks), [T] i32 each. A call
    # without a window reads the main pool through that table and takes
    # them; a sliding layer's call never does.
    attn_runs: tuple | None = None
    # State-space layers (flattened layout only): the step's packing as
    # they read it, derived from the per-row metadata and the rows' slots
    # of the state pool (ops/ssm.py::StateRows). None for every other model.
    state_rows: tuple | None = None

    @property
    def valid(self) -> jax.Array:  # [B, Q] bool
        B, Q = self.token_ids.shape
        return jnp.arange(Q)[None, :] < self.query_lens[:, None]


class StepCtx(NamedTuple):
    """What one forward knows: built once by ``llama.forward_hidden``, handed
    to the ``mix`` of every layer. A kind reads what it needs."""

    cfg: ModelConfig
    inp: StepInput
    mesh: object        # the Mesh, or None
    world_size: int
    kv_rep: int
    # (cos, sin) over ``inp.positions`` per entry of ``cfg.rope_specs``: the
    # model's own table first, then each one a kind of layer has to itself.
    ropes: tuple
    valid: jax.Array    # ``inp.valid``, traced once
    cp: int             # the ring degree of this program's prefill attention; 0: no ring
    hoisted: dict       # kind -> what its ``hoist`` traced for the step
    # (cos [n, ...], sin [n, ...]): ``ropes`` stacked, for the one scan whose
    # layers differ in their table (``LayerCtx.rope`` traced); None elsewhere.
    rope_stack: tuple | None = None


class LayerCtx(NamedTuple):
    """What one layer knows: built by the layer scans beside its weights."""

    plane: jax.Array | int   # the layer's plane of its cache
    table: jax.Array         # its pool's page table
    run_phys: jax.Array | None = None  # its pool's page a run of the flat write plan
    window: jax.Array | None = None    # sliding window (0: none); None: no layer slides
    # The layer's RoPE table: its index into ``StepCtx.ropes`` (static
    # wherever the layer's kind is: a cycle body's position, a homogeneous
    # run), None: the kind has no table and q, k stay as projected; traced
    # (a row of ``StepCtx.rope_stack``) in a scan over layers of several kinds.
    rope: jax.Array | int | None = 0
    # "window" | "full" where the model mixes the two: the flat attention
    # call's ``llmd.attn.<kind>`` scope.
    attn_kind: str | None = None
    moe_layer: jax.Array | None = None  # its index into the stacked expert leaves


def no_halves(h, lp, cache, step: StepCtx, layer: LayerCtx):
    raise NotImplementedError(
        f"{step.cfg.name}: dual-batch overlap needs the mixer's write and "
        "read apart, which this layer's kind does not offer"
    )


class MixerKind(NamedTuple):
    """A KIND of layer, by its mixer: what ``llama.forward_hidden`` dispatches
    every layer of every model on (``llama.mixer_kinds``). To add one: a
    module with an ``init`` and a ``mix``, and its entry there."""

    stack: str    # params[stack]: the kind's weights, a layer's at its plane
    # ("layers": the model has this one kind, its leaves lie with the norms)
    pool: int     # its cache: 0 the paged KV pool, 1 the per-sequence pool
    init: Callable  # (cfg, n, mk, dtype) -> the n stacked mixers' leaves
    mix: Callable   # (h, lp, cache, step, layer) -> (mixer output, cache)
    # (h, lp, cache, step, layer) -> (cache, read, project): the write for
    # the whole batch, then ``project(read(rows))`` -> the rows' mixer
    # output, read-only, for dual-batch overlap's two half chains (two
    # stages: the rows' residual is sliced between them, as it always was)
    halves: Callable = no_halves
    # (cfg, inp) -> what the kind wants traced once a step, outside the
    # layer scans (``step.hoisted[kind]``)
    hoist: Callable = lambda cfg, inp: None


def norm_weight(cfg: ModelConfig, mk, dt, name: str, shape: tuple) -> jax.Array:
    """An RMS norm's weight: ones, or seeded near 0 where the model stores it
    zero-centred (applied as 1 + w)."""
    if cfg.norm_zero_centered:
        return mk(name, shape, scale=0.02)
    return jnp.ones(shape, dt)


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, zero_centered: bool = False
) -> jax.Array:
    """``zero_centered`` (qwen3_next's block, final and q/k norms): the
    weight is stored around 0 and applied as 1 + w, in float32 before the
    cast back (HF ``Qwen3NextRMSNorm``)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if zero_centered:
        w = 1.0 + weight.astype(jnp.float32)
        return (xf * jax.lax.rsqrt(var + eps) * w).astype(dtype)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float
) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dtype) * weight + bias


SUPPORTED_ROPE_TYPES = ("default", "linear", "llama3", "yarn")


def rope_type(scaling: dict | None) -> str:
    """The scaling's kind. ``mrope_section`` beside ``default`` (Qwen-VL
    style: each rotary frequency reads one of three position rows) is the
    ordinary rope for text, whose three rows are equal; the program
    serves 1-D positions only, so the sections change nothing here."""
    if not scaling:
        return "default"
    return scaling.get("rope_type") or scaling.get("type") or "default"


def _yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_sm_scale_mult(scaling: dict | None) -> float:
    """DeepSeek-style yarn splits the attention temperature correction:
    with mscale_all_dim set, cos/sin stay (nearly) unscaled and the
    softmax scale is multiplied by mscale^2 instead (HF DeepseekV3
    Attention.__init__). 1.0 for every other rope config."""
    if rope_type(scaling) != "yarn":
        return 1.0
    m_all = float(scaling.get("mscale_all_dim") or 0.0)
    if not m_all:
        return 1.0
    m = _yarn_mscale(float(scaling["factor"]), m_all)
    return m * m


def _inv_freq_and_factor(
    head_dim: int, theta: float, scaling: dict | None
) -> tuple[jax.Array, float]:
    """Inverse frequencies + cos/sin post-factor per HF rope_scaling.

    llama3 (Llama-3.1+): low-frequency bands divided by `factor`, high
    kept, smooth interpolation between (_compute_llama3_parameters).
    yarn (DeepSeek V2/V3, long-context Qwen): NTK-by-parts interpolation
    with linear ramp between beta_fast/beta_slow correction dims plus an
    attention factor on cos/sin (_compute_yarn_parameters)."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    rt = rope_type(scaling)
    if rt == "default":
        return inv_freq, 1.0
    factor = float(scaling["factor"])
    if rt == "linear":
        return inv_freq / factor, 1.0
    if rt == "llama3":
        low = float(scaling["low_freq_factor"])
        high = float(scaling["high_freq_factor"])
        orig = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * jnp.pi / inv_freq
        scaled = jnp.where(wavelen > orig / low, inv_freq / factor, inv_freq)
        smooth = (orig / wavelen - low) / (high - low)
        smoothed = (1.0 - smooth) / factor * inv_freq + smooth * inv_freq
        is_medium = (wavelen >= orig / high) & (wavelen <= orig / low)
        return jnp.where(is_medium, smoothed, scaled), 1.0
    if rt == "yarn":
        orig = float(
            scaling.get("original_max_position_embeddings") or 0.0
        ) or None
        if orig is None:
            raise ValueError("yarn rope_scaling needs original_max_position_embeddings")
        attention_factor = scaling.get("attention_factor")
        if attention_factor is None:
            mscale = scaling.get("mscale")
            m_all = scaling.get("mscale_all_dim")
            if mscale and m_all:
                attention_factor = _yarn_mscale(factor, float(mscale)) / _yarn_mscale(
                    factor, float(m_all)
                )
            else:
                attention_factor = _yarn_mscale(factor)
        beta_fast = float(scaling.get("beta_fast") or 32)
        beta_slow = float(scaling.get("beta_slow") or 1)

        def correction_dim(rot: float) -> float:
            return (head_dim * math.log(orig / (rot * 2 * math.pi))) / (
                2 * math.log(theta)
            )

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3),
            0.0,
            1.0,
        )
        extrapolation_factor = 1.0 - ramp
        inv_freq = (
            inv_freq / factor * ramp + inv_freq * extrapolation_factor
        )
        return inv_freq, float(attention_factor)
    raise NotImplementedError(f"rope_scaling type {rt!r} not supported")


def rope_tables(
    positions: jax.Array, head_dim: int, theta: float,
    scaling: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embedding: [..., head_dim//2], f32."""
    inv_freq, factor = _inv_freq_and_factor(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., half]
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, Q, N, D] with tables [B, Q, half] (HF 'split-half' layout).
    Tables of fewer than D / 2 frequencies rotate the first ``2 * half``
    dimensions among themselves and pass the rest (a partial rotation)."""
    half = cos.shape[-1]
    if 2 * half < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., : 2 * half], cos, sin), x[..., 2 * half :]], axis=-1
        )
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdot(x: jax.Array, lp: dict, name: str) -> jax.Array:
    """``x @ lp[name]``, transparently taking the int8 path when the param
    tree carries a ``<name>_scale`` (see llmd_tpu.ops.quant): the weight
    streams from HBM as int8 and multiplies on the MXU natively."""
    scale = lp.get(name + "_scale")
    if scale is None:
        return x @ lp[name]
    from llmd_tpu.ops.quant import qdot

    return qdot(x, lp[name], scale)
