"""TPU compute kernels (Pallas) and their XLA reference fallbacks.

``paged_attention`` / ``write_kv_pages`` (and their layer-indexed
``*_full`` variants for the scan-carry cache layout) dispatch at trace
time: the Pallas decode kernels on a TPU for Q=1 with tile-compatible
geometry, the XLA fallbacks otherwise — each choice recorded under
``record_plans`` so a server can say which one its programs took. Env
LLMD_PALLAS=off disables the kernels; =interpret forces interpret mode
(CPU parity testing).

Sharded meshes (tp/dp > 1) run the SAME kernels per device under
shard_map — the role FlashInfer plays under vLLM TP in the reference
stack (docker/Dockerfile.cuda:71-72). Layout contract:

  - q/attention-output heads shard over tp (they arrive sharded: wq/wo
    are tp-sharded in PARAM_SPECS); the KV pool's kv-head axis shards
    over tp when tp divides num_kv_heads (kv_cache_spec).
  - the batch shards over dp for attention reads; KV WRITES replicate
    the (tiny) per-step K/V slabs across dp so every dp replica of the
    pool applies identical updates and replicas never diverge — the
    pool itself is never partitioned over dp (each dp group keeps a
    full copy, matching the engine's per-rank-pool design).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llmd_tpu.ops.paged_attention import (
    paged_attention_xla,
    paged_attention_xla_blocked,
    scatter_kv_scales,
    scatter_kv_scales_flat,
)
from llmd_tpu.ops.paged_attention import write_kv_pages as write_kv_pages_xla
from llmd_tpu.ops.kv_write import (
    write_kv_pages_decode,
    write_kv_pages_decode_full,
    write_kv_pages_flat_full,
)
from llmd_tpu.ops.ragged_paged_attention import (
    decode_paged_attention,
    decode_paged_attention_full,
    flat_paged_attention_full,
)
from llmd_tpu.ops.sparse_attention import (  # noqa: F401  (entry points of learned sparse attention)
    IndexedPool,
    sparse_attention_full_flat,
    write_index_keys_full_flat,
)


def _mode() -> str:
    return os.environ.get("LLMD_PALLAS", "auto")


def _on_tpu(mesh=None) -> bool:
    """Whether the program being traced runs on TPUs: the devices of the
    mesh it is given, or the default backend's where there is none. A
    backend that fails to start raises here; it never selects a path."""
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return device.platform == "tpu"


def _interpret() -> bool:
    return _mode() == "interpret"


# Which plan each op took, recorded at trace time into the dict the caller
# supplies (the runner keeps one and the server reports it): op name -> set
# of "pallas" | "pallas_shard" | "xla:<reason>", reason one of off (the
# env lever), geometry (shape the kernel does not tile), platform (not a
# TPU), mesh (axes the shard_map rules do not divide).
_plan_sink: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "llmd_kernel_plans", default=None
)


@contextlib.contextmanager
def record_plans(sink: dict[str, set[str]]):
    token = _plan_sink.set(sink)
    try:
        yield sink
    finally:
        _plan_sink.reset(token)


def note_plan(op: str, label: str) -> None:
    sink = _plan_sink.get()
    if sink is not None:
        sink.setdefault(op, set()).add(label)


def _mesh_dims(mesh) -> tuple[int, int] | None:
    if mesh is None or not ({"dp", "tp"} <= set(mesh.axis_names)):
        return None
    return mesh.shape["dp"], mesh.shape["tp"]


def _geometry_ok(Q, page, D, D2, need_lane_d: bool) -> bool:
    """Per-shard kernel geometry: decode shape (Q==1), sublane-tiled pages
    (page % 8), packed K/V halves (D2 == 2D). ``need_lane_d``: the
    ATTENTION kernel matmuls over D, so D itself must be lane-tiled
    (D % 128); the WRITE kernel only moves [.., D2] slabs, so D2 % 128
    suffices (head_dim-64 models keep the in-place write)."""
    if not (Q == 1 and page % 8 == 0 and D2 == 2 * D and D2 % 128 == 0):
        return False
    return not (need_lane_d and D % 128 != 0)


def _mesh_plan(world_size, mesh, B=None, H=None, K=None) -> str:
    """Shared tail of every dispatch decision once geometry/platform pass:
    "direct" (single device), "shard" (per-device kernels under
    shard_map), or "xla". Divisibility gates, each skipped when the axis
    is irrelevant to the caller (None): tp | H (q heads stay local),
    tp | K for K > 1 (the pool's kv-head axis is tp-sharded; K == 1 MLA
    latent pools replicate), dp | B (batch rows split evenly — writes
    replicate the batch instead and pass B=None)."""
    if world_size == 1:
        return "direct"
    dims = _mesh_dims(mesh)
    if dims is None:
        return "xla"
    dp, tp = dims
    if H is not None and H % tp:
        return "xla"
    if K is not None and K > 1 and K % tp:
        return "xla"
    if B is not None and B % dp:
        return "xla"
    return "shard"


def _choose(geometry_ok, world_size, mesh, **divisors) -> tuple[str, str]:
    """One dispatch decision as (plan, label): the env lever, the geometry
    gate, the platform of the mesh (compiled on a TPU, or interpreted on
    request), then _mesh_plan."""
    if _mode() == "off":
        return "xla", "xla:off"
    if not geometry_ok:
        return "xla", "xla:geometry"
    if not (_interpret() or _on_tpu(mesh)):
        return "xla", "xla:platform"
    plan = _mesh_plan(world_size, mesh, **divisors)
    return plan, {
        "direct": "pallas", "shard": "pallas_shard", "xla": "xla:mesh",
    }[plan]


def _decide(op, geometry_ok, world_size, mesh, **divisors) -> str:
    """_choose, recorded under ``op``."""
    plan, label = _choose(geometry_ok, world_size, mesh, **divisors)
    note_plan(op, label)
    return plan


def _plan(op, Q, page, D, D2, world_size, need_lane_d, mesh, B, H, K):
    """Dense-kernel dispatch."""
    return _decide(
        op, _geometry_ok(Q, page, D, D2, need_lane_d), world_size, mesh,
        B=B, H=H, K=K,
    )


def _plan_write(op, Q, page, D, D2, world_size, mesh, have_plan=True):
    """Write-kernel dispatch: no head/batch divisibility gates — the
    sharded write replicates the batch across dp and _kv_head_axis
    degrades to a replicated head axis when tp does not divide K.
    ``have_plan``: the flat write also needs the host's run plan."""
    return _decide(
        op, have_plan and _geometry_ok(Q, page, D, D2, need_lane_d=False),
        world_size, mesh,
    )


def _mla_geometry_ok(Q, page, Dl, rank) -> bool:
    """MLA kernel geometry: latent-width tiling instead of D2 == 2D."""
    return Q == 1 and page % 8 == 0 and Dl % 128 == 0 and rank % 128 == 0


def _plan_mla(op, Q, page, Dl, rank, world_size, mesh, B, H):
    """MLA attention dispatch; the latent pool replicates over tp (K
    folds away)."""
    return _decide(
        op, _mla_geometry_ok(Q, page, Dl, rank), world_size, mesh, B=B, H=H
    )


# Scalar-prefetch operands live in SMEM. What a kernel gets of it, by
# device kind — measured for the v5e only, with its compiler (libtpu 0.0.34:
# "Used 1.04M of 1.00M smem"). A kind that is not listed has not been
# measured: page_table_smem bounds nothing there and the chip's compiler has
# the last word.
_SMEM_BYTES = {"TPU v5 lite": 1 << 20}
_SMEM_RESERVE = 4096


def _page_table_smem_bytes(rows: int, max_pages: int, tokens: int) -> int:
    """SMEM an attention kernel needs to prefetch a [rows, max_pages] page
    table: the compiler lays a 2-D i32 table out in (8, 128) tiles, and the
    kernels prefetch up to four per-token i32 vectors (the flat call's
    rows, horizons and its two of shared-prefix runs) and a few words of
    their own beside it. Calibrated by compiling for v5e: [120, 2048] with
    2064 tokens fits, [124, 2048] does not."""
    table = -(-rows // 8) * 8 * -(-max_pages // 128) * 128 * 4
    return table + 4 * -(-tokens // 128) * 128 * 4 + _SMEM_RESERVE


def page_table_smem(
    cfg, page, max_pages, world_size, mesh, decode_rows,
    flat_rows=0, flat_tokens=0,
) -> tuple[int, int] | None:
    """``(bytes needed, bytes there are)`` of SMEM, per device, for the
    largest page table the attention kernels of ``cfg``'s step programs
    scalar-prefetch — the decode programs' ``decode_rows`` and the flat
    step's ``flat_rows`` x ``flat_tokens``. None where nothing bounds it:
    those programs take the XLA attention (the dispatch ladder above), or
    the device's SMEM has not been measured."""
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    have = _SMEM_BYTES.get(device.device_kind)
    if have is None:
        return None
    if cfg.is_mla:
        tiles = _mla_geometry_ok(
            1, page, cfg.kv_cache_entry_dim, cfg.kv_lora_rank
        )
        K = None
    else:
        D = cfg.head_dim
        tiles = _geometry_ok(1, page, D, 2 * D, need_lane_d=True)
        K = cfg.num_kv_heads
    need = 0
    # (rows in a device's table, tokens its kernel walks) under each plan:
    # the decode table splits over dp with the batch; the flat step's
    # compact table stays replicated and only its tokens split.
    for rows, tokens, replicated in (
        (decode_rows, decode_rows, False), (flat_rows, flat_tokens, True),
    ):
        if not rows:
            continue
        plan, _ = _choose(
            tiles, world_size, mesh, B=tokens, H=cfg.num_heads, K=K
        )
        if plan == "xla":
            continue
        dp = mesh.shape["dp"] if plan == "shard" else 1
        need = max(need, _page_table_smem_bytes(
            rows if replicated else rows // dp, max_pages, tokens // dp
        ))
    return (need, have) if need else None


# Above this context size the dense XLA attention's [B, Q, .., S] score
# tensor dominates memory (it grows as chunk x context); switch to the
# blocked online-softmax form.
_DENSE_XLA_MAX_S = 4096


def _split_cache(kv_cache):
    """(data, scales) view of a pool: int8 pools travel as a 2-tuple
    (data i8, scales f32 — ops/quant_kv.py layout); float pools as a
    bare array with scales None."""
    if isinstance(kv_cache, tuple):
        return kv_cache
    return kv_cache, None


def _attention_xla(q, kv_slice, page_table, kv_lens, positions, sm_scale,
                   window=None, sinks=None, scales=None, sel=None):
    S = page_table.shape[1] * kv_slice.shape[-2]
    if S > _DENSE_XLA_MAX_S:
        # The blocked online-softmax path handles Q==1 too — long-context
        # DECODE through the XLA fallback (e.g. sink models) must not
        # gather the whole padded context per step.
        return paged_attention_xla_blocked(
            q, kv_slice, page_table, kv_lens, positions, sm_scale,
            window=window, sinks=sinks, scales=scales, sel=sel,
        )
    return paged_attention_xla(
        q, kv_slice, page_table, kv_lens, positions, sm_scale, window=window,
        sinks=sinks, scales=scales, sel=sel,
    )


def _decode_write_prep(k, v, page_table, positions, page):
    """[B,1,K,D] k/v -> (kv_new [B,K,2D], phys [B], offset [B])."""
    B, _, K, D = k.shape
    kv_new = jnp.concatenate([k, v], axis=-1).reshape(B, K, 2 * D)
    pos = positions[:, 0]
    phys = jnp.take_along_axis(page_table, (pos // page)[:, None], axis=1)[:, 0]
    return kv_new, phys, pos % page


def _kv_head_axis(K: int, tp: int) -> str | None:
    # K == 1 (MLA latent pool) and non-dividing K keep the head axis
    # replicated — matching kv_cache_spec's allocation-time policy.
    return "tp" if tp > 1 and K > 1 and K % tp == 0 else None


def _write_sharded(mesh, kv_cache, kv_new, layer, phys, offset, valid, full):
    """Per-device in-place writes with the batch REPLICATED across dp:
    the slabs are tiny (B x K x 2D), and identical writes on every dp
    replica keep the un-partitioned pool consistent."""
    K = kv_new.shape[1]
    tp_k = _kv_head_axis(K, mesh.shape["tp"])
    cache_spec = (
        P(None, None, tp_k, None, None) if full else P(None, tp_k, None, None)
    )
    interpret = _interpret()

    if full:

        def local(cache, kv_new, layer, phys, offset, valid):
            return write_kv_pages_decode_full(
                cache, kv_new, layer, phys, offset, valid, interpret=interpret
            )

        args = (kv_cache, kv_new, layer, phys, offset, valid)
        in_specs = (cache_spec, P(None, tp_k, None), P(), P(), P(), P())
    else:

        def local(cache, kv_new, phys, offset, valid):
            return write_kv_pages_decode(
                cache, kv_new, phys, offset, valid, interpret=interpret
            )

        args = (kv_cache, kv_new, phys, offset, valid)
        in_specs = (cache_spec, P(None, tp_k, None), P(), P(), P())

    return shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=cache_spec,
        check_vma=False,
    )(*args)


def write_kv_pages(
    kv_cache, k, v, page_table, positions, valid, world_size=1, mesh=None
):
    """Scatter this step's K/V into the (single-layer) paged cache.

    Decode (Q==1) on TPU uses the Pallas in-place kernel — the XLA
    scatter copies the whole pool per step when the buffer is not
    donated; the kernel DMAs only the written slabs. Prefill and
    non-TPU paths keep the XLA scatter.
    """
    B, Q, K, D = k.shape
    num_pages, Kc, page, D2 = kv_cache.shape
    plan = _plan_write("kv_write", Q, page, D, D2, world_size, mesh)
    if plan != "xla":
        kv_new, phys, offset = _decode_write_prep(k, v, page_table, positions, page)
        if plan == "direct":
            return write_kv_pages_decode(
                kv_cache, kv_new, phys, offset, valid[:, 0], interpret=_interpret()
            )
        return _write_sharded(
            mesh, kv_cache, kv_new, None, phys, offset, valid[:, 0], full=False
        )
    return write_kv_pages_xla(kv_cache, k, v, page_table, positions, valid)


def write_kv_pages_full(
    kv_cache_full, layer, k, v, page_table, positions, valid,
    world_size=1, mesh=None,
):
    """Layer-indexed write on the FULL [L, ...] cache (scan-carry layout).

    The whole point: a lax.scan over layers that slices the cache pays a
    pool-sized copy per layer (slice + update, or xs->ys buffers); the
    Pallas variant indexes [layer, page] inside the kernel so only the
    written slabs move. Fallback (CPU / prefill / non-divisible
    sharding): dynamic slice + XLA scatter + dynamic update — the
    carry-update pattern XLA optimizes in place where it can.

    Int8 pools (tuple cache): k/v rows quantize on device first; the
    int8 data rides the same dispatch below (the Pallas kernel moves
    HALF the bytes), and the tiny per-row scales scatter via XLA.
    """
    kv_cache_full, kv_scales = _split_cache(kv_cache_full)
    if kv_scales is not None:
        from llmd_tpu.ops.quant_kv import quantize_kv_rows

        k8, v8, srow = quantize_kv_rows(k, v)
        data = write_kv_pages_full(
            kv_cache_full, layer, k8, v8, page_table, positions, valid,
            world_size=world_size, mesh=mesh,
        )
        # Slice + scatter + update-slice on the layer's scale pool
        # ([P, K, page, 2]): the full-array layer-indexed scatter reads
        # cleaner but defeats XLA's in-place aliasing (the attention
        # read is a second consumer), copying the whole scale pool per
        # layer — measured 10x slower e2e. The slice form pays ~2
        # layer-slices per step (~1/128 of the data bytes).
        ssl = jax.lax.dynamic_index_in_dim(kv_scales, layer, 0, keepdims=False)
        ssl = scatter_kv_scales(ssl, srow, page_table, positions, valid)
        return (data, jax.lax.dynamic_update_index_in_dim(kv_scales, ssl, layer, 0))
    B, Q, K, D = k.shape
    L, num_pages, Kc, page, D2 = kv_cache_full.shape
    plan = _plan_write("kv_write", Q, page, D, D2, world_size, mesh)
    if plan != "xla":
        kv_new, phys, offset = _decode_write_prep(k, v, page_table, positions, page)
        if plan == "direct":
            return write_kv_pages_decode_full(
                kv_cache_full, kv_new, layer, phys, offset, valid[:, 0],
                interpret=_interpret(),
            )
        return _write_sharded(
            mesh, kv_cache_full, kv_new, layer, phys, offset, valid[:, 0],
            full=True,
        )
    sl = jax.lax.dynamic_index_in_dim(kv_cache_full, layer, 0, keepdims=False)
    sl = write_kv_pages_xla(sl, k, v, page_table, positions, valid)
    return jax.lax.dynamic_update_index_in_dim(kv_cache_full, sl, layer, 0)


def write_kv_pages_full_flat(
    kv_cache_full, layer, k, v, page_table, rows, positions, valid, runs,
    world_size=1, mesh=None,
):
    """Flattened-token (``cu_q_lens``) layer-indexed KV write: k/v arrive
    as the packed ``[T, 1, K, D]`` token stream, ``page_table`` stays the
    COMPACT per-row table indexed through ``rows`` ([T] token -> row),
    and the TPU path lands the stream via run-addressed page
    read-modify-writes (``runs`` = (src, off, cnt) + this pool's phys —
    same-page-safe where the per-token decode kernel's pipeline is not).
    XLA fallback: gather the per-token table rows, then the plain
    scatter (distinct (page, slot) targets per live token).
    A sparse-attention pool (``IndexedPool``) has its K/V written here and
    its indexer keys by ``write_index_keys_full_flat``.
    """
    if isinstance(kv_cache_full, IndexedPool):
        return dataclasses.replace(kv_cache_full, kv=write_kv_pages_full_flat(
            kv_cache_full.kv, layer, k, v, page_table, rows, positions,
            valid, runs, world_size=world_size, mesh=mesh,
        ))
    kv_cache_full, kv_scales = _split_cache(kv_cache_full)
    if kv_scales is not None:
        from llmd_tpu.ops.quant_kv import quantize_kv_rows

        k8, v8, srow = quantize_kv_rows(k, v)
        data = write_kv_pages_full_flat(
            kv_cache_full, layer, k8, v8, page_table, rows, positions,
            valid, runs, world_size=world_size, mesh=mesh,
        )
        ssl = jax.lax.dynamic_index_in_dim(kv_scales, layer, 0, keepdims=False)
        # Per-token enumerated scatter: the decode-path dense-slab form
        # assumes one token per page, which the flattened stream breaks
        # (a prefill chunk's tokens share pages).
        ssl = scatter_kv_scales_flat(
            ssl, srow, page_table, rows, positions, valid
        )
        return (data, jax.lax.dynamic_update_index_in_dim(kv_scales, ssl, layer, 0))
    B, Q, K, D = k.shape
    L, num_pages, Kc, page, D2 = kv_cache_full.shape
    plan = _plan_write(
        "flat_kv_write", Q, page, D, D2, world_size, mesh,
        have_plan=runs is not None,
    )
    if plan != "xla":
        src, off, cnt, phys = runs
        kv_new = jnp.concatenate([k, v], axis=-1).reshape(B, K, 2 * D)
        if plan == "direct":
            return write_kv_pages_flat_full(
                kv_cache_full, kv_new, layer, src, phys, off, cnt,
                interpret=_interpret(),
            )
        tp_k = _kv_head_axis(K, mesh.shape["tp"])
        cache_spec = P(None, None, tp_k, None, None)
        interpret = _interpret()

        def local(cache, kv_new, layer, src, phys, off, cnt):
            return write_kv_pages_flat_full(
                cache, kv_new, layer, src, phys, off, cnt,
                interpret=interpret,
            )

        return shard_map(
            local, mesh=mesh,
            in_specs=(
                cache_spec, P(None, tp_k, None), P(), P(), P(), P(), P(),
            ),
            out_specs=cache_spec,
            check_vma=False,
        )(kv_cache_full, kv_new, layer, src, phys, off, cnt)
    pt_tok = page_table[rows]  # [T, max_pages]
    sl = jax.lax.dynamic_index_in_dim(kv_cache_full, layer, 0, keepdims=False)
    sl = write_kv_pages_xla(sl, k, v, pt_tok, positions, valid)
    return jax.lax.dynamic_update_index_in_dim(kv_cache_full, sl, layer, 0)


def paged_attention_full_flat(
    q, kv_cache_full, layer, rows, page_table, kv_lens, positions,
    sm_scale=None, world_size=1, mesh=None, window=None, sinks=None,
    runs=None,
):
    """Flattened-token (``cu_q_lens``) layer-indexed attention: q is the
    packed ``[T, 1, H, D]`` stream, ``kv_lens`` is per TOKEN (position +
    1 — causality within a row derived from the packing), and the TPU
    kernel iterates 16-token tiles of the stream against the compact
    per-row table through the scalar-prefetched token -> row map, with
    or without a sliding window; ``runs`` (a call without one) are the
    step's shared-prefix runs, a token each. XLA fallback gathers per-token
    table rows and reuses the bucketed reference path."""
    kv_cache_full, kv_scales = _split_cache(kv_cache_full)
    L, num_pages, K, page, D2 = kv_cache_full.shape
    T, Q, H, D = q.shape
    plan = _plan(
        "flat_attention", Q, page, D, D2, world_size, True, mesh, T, H, K
    )
    if window is not None:
        window = jnp.asarray(window, jnp.int32)
    if plan == "direct":
        return flat_paged_attention_full(
            q, kv_cache_full, layer, rows, page_table, kv_lens,
            sm_scale=sm_scale, interpret=_interpret(), window=window,
            sinks=sinks, scales=kv_scales, runs=runs,
        )
    if plan == "shard":
        tp_k = _kv_head_axis(K, mesh.shape["tp"])
        interpret = _interpret()
        win = jnp.zeros((), jnp.int32) if window is None else window
        use_win = window is not None
        sk = jnp.zeros((H,), jnp.float32) if sinks is None else sinks
        use_sinks = sinks is not None
        scale_spec = (
            (P(None, None, tp_k, None, None),) if kv_scales is not None else ()
        )
        scale_arg = (kv_scales,) if kv_scales is not None else ()
        # A token's run is a matter of its shard's tiles: the host plans
        # them so (engine/prefix_runs.py), and they split with the tokens.
        run_spec = (P("dp"), P("dp")) if runs is not None else ()
        run_arg = tuple(runs) if runs is not None else ()

        def local(q, cache, layer, rows, pt, kl, win, sk, *rest):
            rn, sc = rest[:len(run_arg)], rest[len(run_arg):]
            return flat_paged_attention_full(
                q, cache, layer, rows, pt, kl, sm_scale=sm_scale,
                interpret=interpret, window=win if use_win else None,
                sinks=sk if use_sinks else None,
                scales=sc[0] if sc else None, runs=rn or None,
            )

        # The compact table stays REPLICATED: any token shard may
        # reference any row; tokens (q/rows/kv_lens) split over dp.
        return shard_map(
            local, mesh=mesh,
            in_specs=(
                P("dp", None, "tp", None), P(None, None, tp_k, None, None),
                P(), P("dp"), P(None, None), P("dp"), P(), P("tp"),
                *run_spec, *scale_spec,
            ),
            out_specs=P("dp", None, "tp", None),
            check_vma=False,
        )(q, kv_cache_full, layer, rows, page_table, kv_lens, win, sk,
          *run_arg, *scale_arg)
    pt_tok = page_table[rows]  # [T, max_pages]
    sl = jax.lax.dynamic_index_in_dim(kv_cache_full, layer, 0, keepdims=False)
    ssl = (
        None if kv_scales is None
        else jax.lax.dynamic_index_in_dim(kv_scales, layer, 0, keepdims=False)
    )
    return _attention_xla(
        q, sl, pt_tok, kv_lens, positions, sm_scale, window=window,
        sinks=sinks, scales=ssl,
    )


def paged_attention(
    q, kv_cache, page_table, kv_lens, positions, sm_scale=None,
    world_size=1, mesh=None,
):
    """Decode attention. Sharded meshes run the kernel per device under
    shard_map: q/output heads over tp, batch over dp, pool heads over tp
    (dp replicas of the pool read-only here)."""
    num_pages, K, page, D2 = kv_cache.shape
    B, Q, H, D = q.shape
    plan = _plan("attention", Q, page, D, D2, world_size, True, mesh, B, H, K)
    if plan == "direct":
        return decode_paged_attention(
            q, kv_cache, page_table, kv_lens, sm_scale=sm_scale,
            interpret=_interpret(),
        )
    if plan == "shard":
        tp_k = _kv_head_axis(K, mesh.shape["tp"])
        interpret = _interpret()

        def local(q, cache, pt, kl):
            return decode_paged_attention(
                q, cache, pt, kl, sm_scale=sm_scale, interpret=interpret
            )

        return shard_map(
            local, mesh=mesh,
            in_specs=(
                P("dp", None, "tp", None), P(None, tp_k, None, None),
                P("dp", None), P("dp"),
            ),
            out_specs=P("dp", None, "tp", None),
            check_vma=False,
        )(q, kv_cache, page_table, kv_lens)
    return _attention_xla(q, kv_cache, page_table, kv_lens, positions, sm_scale)


def mla_paged_attention_full(
    q_eff, latent_cache_full, layer, page_table, kv_lens, positions,
    rank, sm_scale, world_size=1, mesh=None,
):
    """Layer-indexed MLA latent attention on the FULL [L, ...] cache.

    Pallas for decode (Q==1, lane-tiled latent width); sharded meshes
    split the query heads over tp and the batch over dp against the
    replicated latent pool (rows are a few hundred bytes; every head
    reads the same latent). XLA gather fallback otherwise.
    """
    from llmd_tpu.ops.mla_attention import mla_paged_attention_xla
    from llmd_tpu.ops.mla_decode import mla_decode_paged_attention_full

    L, num_pages, one, page, Dl = latent_cache_full.shape
    B, Q, H, _ = q_eff.shape
    plan = _plan_mla("mla_attention", Q, page, Dl, rank, world_size, mesh, B, H)
    if plan == "direct":
        return mla_decode_paged_attention_full(
            q_eff, latent_cache_full, layer, page_table, kv_lens,
            rank=rank, sm_scale=sm_scale, interpret=_interpret(),
        )
    if plan == "shard":
        interpret = _interpret()

        def local(q_eff, cache, layer, pt, kl):
            return mla_decode_paged_attention_full(
                q_eff, cache, layer, pt, kl, rank=rank,
                sm_scale=sm_scale, interpret=interpret,
            )

        return shard_map(
            local, mesh=mesh,
            in_specs=(
                P("dp", None, "tp", None),
                P(None, None, None, None, None),
                P(), P("dp", None), P("dp"),
            ),
            out_specs=P("dp", None, "tp", None),
            check_vma=False,
        )(q_eff, latent_cache_full, layer, page_table, kv_lens)
    sl = jax.lax.dynamic_index_in_dim(
        latent_cache_full, layer, 0, keepdims=False
    )
    return mla_paged_attention_xla(
        q_eff, sl, page_table, kv_lens, positions, rank=rank, sm_scale=sm_scale
    )


def paged_attention_full(
    q, kv_cache_full, layer, page_table, kv_lens, positions,
    sm_scale=None, world_size=1, mesh=None, window=None, sinks=None,
):
    """Layer-indexed attention on the FULL [L, ...] cache (see
    write_kv_pages_full). ``window`` is an optional i32 scalar sliding
    window (0/None = full attention; a traced per-layer value inside the
    layer scan). Int8 pools (tuple cache) dequantize per row at the
    read: the Pallas kernel DMAs half the HBM bytes and folds the scales
    around its matmuls; the XLA fallback dequantizes gathered pages."""
    kv_cache_full, kv_scales = _split_cache(kv_cache_full)
    L, num_pages, K, page, D2 = kv_cache_full.shape
    B, Q, H, D = q.shape
    plan = _plan("attention", Q, page, D, D2, world_size, True, mesh, B, H, K)
    if window is not None:
        window = jnp.asarray(window, jnp.int32)
    if plan == "direct":
        return decode_paged_attention_full(
            q, kv_cache_full, layer, page_table, kv_lens, sm_scale=sm_scale,
            interpret=_interpret(), window=window, sinks=sinks,
            scales=kv_scales,
        )
    if plan == "shard":
        tp_k = _kv_head_axis(K, mesh.shape["tp"])
        interpret = _interpret()
        win = jnp.zeros((), jnp.int32) if window is None else window
        use_win = window is not None
        # Sinks are per-q-head: shard over tp with the q heads (zeros
        # placeholder keeps the shard_map arity fixed when absent).
        sk = jnp.zeros((H,), jnp.float32) if sinks is None else sinks
        use_sinks = sinks is not None
        if kv_scales is not None:
            # Scales shard with the pool's head axis.

            def local_q(q, cache, sc, layer, pt, kl, win, sk):
                return decode_paged_attention_full(
                    q, cache, layer, pt, kl, sm_scale=sm_scale,
                    interpret=interpret, window=win if use_win else None,
                    sinks=sk if use_sinks else None, scales=sc,
                )

            return shard_map(
                local_q, mesh=mesh,
                in_specs=(
                    P("dp", None, "tp", None), P(None, None, tp_k, None, None),
                    P(None, None, tp_k, None, None),
                    P(), P("dp", None), P("dp"), P(), P("tp"),
                ),
                out_specs=P("dp", None, "tp", None),
                check_vma=False,
            )(q, kv_cache_full, kv_scales, layer, page_table, kv_lens, win, sk)

        def local(q, cache, layer, pt, kl, win, sk):
            return decode_paged_attention_full(
                q, cache, layer, pt, kl, sm_scale=sm_scale,
                interpret=interpret, window=win if use_win else None,
                sinks=sk if use_sinks else None,
            )

        return shard_map(
            local, mesh=mesh,
            in_specs=(
                P("dp", None, "tp", None), P(None, None, tp_k, None, None),
                P(), P("dp", None), P("dp"), P(), P("tp"),
            ),
            out_specs=P("dp", None, "tp", None),
            check_vma=False,
        )(q, kv_cache_full, layer, page_table, kv_lens, win, sk)
    sl = jax.lax.dynamic_index_in_dim(kv_cache_full, layer, 0, keepdims=False)
    ssl = (
        None if kv_scales is None
        else jax.lax.dynamic_index_in_dim(kv_scales, layer, 0, keepdims=False)
    )
    return _attention_xla(
        q, sl, page_table, kv_lens, positions, sm_scale, window=window,
        sinks=sinks, scales=ssl,
    )
