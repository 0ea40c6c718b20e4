"""Model runner: owns device state (params + KV pool) and the jitted steps.

Multi-host (reference wide-EP LWS shape, docs/infrastructure/
multi-node.md:3-41): when ``jax.distributed`` is initialized with >1
process, ONE runner spans the global mesh. The leader (process 0) runs the
scheduler and broadcasts each step's host inputs (fixed-size header + array
payload via ``multihost_utils.broadcast_one_to_all``); followers sit in
``follower_loop`` mirroring every dispatch so all processes execute the
same XLA programs in lockstep — the property a real LWS deployment relies
on. Sampled tokens come back replicated so every host reads them locally.

TPU-first scheduling shapes (everything static per bucket, traced once):

- **batched prefill**: all scheduled prompt chunks run in ONE call
  [B_bucket, Q_bucket] -- one weight read per step instead of one per
  sequence (HBM bandwidth is the bottleneck; see SURVEY.md section 7
  "hard parts").
- **multi-step decode**: K decode iterations fused into one jit call with a
  ``lax.fori_loop`` that feeds each sampled token back as the next input
  ON DEVICE. The host gets one packed transfer per K tokens, which
  amortizes dispatch/transfer latency (the reference fights the same battle
  with --enable-dbo / DP supervisor batching; on a remote-dispatch TPU
  runtime the roundtrip is the whole game).
- stop conditions are reconciled on host AFTER the window: tokens past a
  stop are discarded and never committed to the prefix cache.

KV pool: ONE jax.Array [L, pages, K, page, 2D] (head-major within a page so
a (page, head) slab is one contiguous DMA), sharded over tp on the KV head
axis, donated through every step so XLA updates it in place.
"""

from __future__ import annotations

import collections
import concurrent.futures
import functools
import gc
import logging
import os
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from llmd_tpu import ops
from llmd_tpu.config import EngineConfig, state_slot_spec, swa_ring_spec
from llmd_tpu.engine import payload, prefix_runs
from llmd_tpu.engine.sampler import (
    SamplingInputs,
    sample_tokens,
    spec_seed,
)
from llmd_tpu.engine.scheduler import ScheduledSeq, token_slot_count
from llmd_tpu.models import llama
from llmd_tpu.models.common import StepInput
from llmd_tpu.obs import profiling
from llmd_tpu.ops import ssm as ssm_ops
from llmd_tpu.ops.ragged_paged_attention import PAGES_PER_BLOCK
from llmd_tpu.ops.ragged_paged_attention import TILE as ATTENTION_TILE
from llmd_tpu.parallel import distributed as dist
from llmd_tpu.parallel.mesh import MeshContext, kv_cache_spec, shard_params

# Multi-host dispatch opcodes (fixed-size i32 header broadcast leader ->
# followers before each step's array payload). KV_GATHER/KV_SCATTER are
# the staging legs of P/D transfer + tiered offload over a multi-process
# mesh: every process dispatches the same SPMD gather/scatter program
# (the gather all-gathers the tp-sharded head axis to a replicated
# bundle the leader can stage; the scatter writes broadcast values into
# each process's own pool shards).
_OP_STOP, _OP_PREFILL, _OP_DECODE = 0, 1, 2
_OP_KV_GATHER, _OP_KV_SCATTER = 3, 4
_OP_EMBED, _OP_LORA = 5, 6
_OP_KV_COPY = 7
_OP_VERIFY = 8  # speculative-decoding verify step ([B, 1+k] positions)
# 9 is retired and not reused: an opcode's number is its identity on the
# lockstep wire.
# Unified single-dispatch step: an entire window=1 engine step — chunked-
# prefill token runs, plain decode rows, and one-shot [B, 1+k] verify
# rows — packed into ONE ragged program (header QK slot packs
# (Q_bucket << 20) | T_bucket; the payload is a flat token stream plus
# per-row (start, qlen, kind) metadata).
_OP_UNIFIED = 10
# Genuinely ragged flattened-token step (`--ragged-qlens`): the unified
# step's forward runs over the PACKED [T_bucket] token stream itself
# (cu_q_lens row offsets; per-token causality = position + 1) instead of
# gathering into a padded [B, Q] view — a decode row costs ONE token, a
# verify row 1 + its own draft length. Header QK carries T_bucket
# directly (no Q packing: the flat family has no per-row column bucket).
_OP_FLAT = 11
# Lockstep liveness heartbeat: broadcast by an idle leader so followers'
# bounded header wait can distinguish "leader idle" from "leader dead".
# No device work — followers just absorb it and keep waiting.
_OP_HEARTBEAT = 12
# Symmetric int8-wire scatter (the q8 twin of _OP_KV_SCATTER): the leader
# broadcasts (i8 data + f16 K/V-half scales) instead of canonical staging
# bytes — HALF the DCN bytes per imported page, so multi-host streamed
# imports ride the same wire saving the q8 gather already gives exports.
# Int8 pools scatter the wire form directly; float pools dequantize on
# device.
_OP_KV_SCATTER_Q8 = 13

# Row kinds of the unified step's (start, qlen, kind) metadata. Only
# verify-ness reaches the device (it selects the sample positions: verify
# rows sample every position, the rest sample the last); the full kind is
# broadcast anyway so followers and debugging tools see the same step
# structure the leader staged.
_KIND_PREFILL, _KIND_DECODE, _KIND_VERIFY = 0, 1, 2

# The step programs: opcode -> the kind engine/payload.py describes the
# inputs of (one description for the lockstep wire and the device buffer).
_STEP_KINDS = {
    _OP_PREFILL: "prefill", _OP_VERIFY: "verify", _OP_UNIFIED: "unified",
    _OP_FLAT: "flat", _OP_DECODE: "decode",
}

# Max tokens one unified row carries: prefill chunks longer than this are
# split into consecutive sub-rows of the SAME sequence (each layer writes
# the whole step's KV before attention reads, so a later sub-row attends
# the earlier sub-rows' fresh KV — the chunked-prefill invariant, just
# within one program). Bounds the [B, Q] padding a mixed step pays: a
# decode row pads to the Q bucket, so Q must stay small relative to the
# token stream, not grow to the largest chunk.
_UNIFIED_ROW_TOKENS = 64
# wait_step's pause between two polls of the intake while the device runs:
# long enough that the serving threads get the interpreter, short against
# a step (what it adds to the gap is half of it on average).
# 0.1 ms because a sleep hands the interpreter to whichever thread wants it
# and comes back when that thread lets go: what a pause REALLY costs, with
# the poll's own work, is counted as step_ready_lag_bound_ms_total.
_POLL_S = 1e-4

log = logging.getLogger(__name__)


def _buckets(limit: int, start: int = 8) -> tuple[int, ...]:
    out, b = [], start
    while b < limit:
        out.append(b)
        b *= 2
    out.append(limit)
    return tuple(dict.fromkeys(out))


def pad_to_bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _padded_ids(page_ids, pad_to: int) -> np.ndarray:
    """[n] i32 ids padded to ``pad_to`` by repeating the last id (a
    duplicate gather/scatter of the same page is idempotent)."""
    ids = np.asarray(page_ids, np.int32)
    if pad_to > len(ids):
        ids = np.concatenate([ids, np.full(pad_to - len(ids), ids[-1], np.int32)])
    return ids


@jax.jit
def _quantize_rows_q8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 with SEPARATE scales for the K and V halves of each
    (token, head) row (the row packs K|V along the last 2D axis, and
    RoPE'd keys are routinely an order of magnitude larger than values —
    one shared amax would crush the value half to a few int8 levels).
    Returns (q [..., 2D] i8, scales [..., 2] f16). Module-level jit: one
    compile per shape, NOT per call."""
    *lead, D2 = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, 2, D2 // 2)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    # Quantize against the f16-ROUNDED scale — the value the consumer
    # will actually dequantize with (avoids a systematic per-row bias of
    # up to ~2^-11 from the f32->f16 scale rounding).
    scale = scale.astype(jnp.float16).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q.reshape(*lead, D2), scale[..., 0].astype(jnp.float16)


@functools.partial(jax.jit, static_argnames=("dtype_name",))
def _dequantize_rows_q8(
    q: jax.Array, s: jax.Array, dtype_name: str
) -> jax.Array:
    *lead, D2 = q.shape
    qf = q.astype(jnp.float32).reshape(*lead, 2, D2 // 2)
    out = qf * s.astype(jnp.float32)[..., None]
    return out.reshape(*lead, D2).astype(jnp.dtype(dtype_name))


def _fuse_projection_tree(params: dict) -> dict:
    """Pure tree transform behind ModelRunner._maybe_fuse (jitted there)."""

    def fuse(d: dict, names: list[str], out_name: str) -> None:
        if not all(n in d for n in names):
            return
        d[out_name] = jnp.concatenate([d[n] for n in names], axis=-1)
        if all(f"{n}_scale" in d for n in names):
            d[f"{out_name}_scale"] = jnp.concatenate(
                [d[f"{n}_scale"] for n in names], axis=-1
            )
        for n in names:
            d.pop(n, None)
            d.pop(f"{n}_scale", None)

    out = dict(params)
    for key in ("layers", "dense_layers"):
        if key not in out:
            continue
        d = dict(out[key])
        fuse(d, ["wq", "wk", "wv"], "wqkv")
        fuse(d, ["w_gate", "w_up"], "w_gu")
        out[key] = d
    return out


@dataclass
class StepResult:
    """Sampled tokens for each row; [B, K] (K=1 for single-shot calls)."""

    tokens: np.ndarray
    logprobs: np.ndarray


@dataclass
class PendingPrefill:
    """Dispatched-but-unread prefill programs of one engine step: the
    packed [B, 2] device outputs per Q-bucket group plus each group's
    source row indices. ``wait_step`` folds every group into one
    coalesced host transfer."""

    entries: list[tuple[jax.Array, list[int]]]
    n: int


@dataclass
class PendingDecode:
    """Dispatched-but-unread decode-side programs of one engine step,
    awaiting the coalesced readback: (packed device output, source row
    indices, K) per program — the packed layout is [B, 2K]. Plain
    steps carry ONE entry; a speculative one-shot step may SPLIT its
    rows between the verify program (rows
    that drafted) and the plain one-token decode program (the rest), so
    low-repetition traffic pays verify columns only for rows that
    actually drafted."""

    entries: list[tuple[jax.Array, list[int], int]]
    n: int
    k: int  # widest K across entries == the StepResult window width


@dataclass
class StagedVerify:
    """Host arrays for a speculative verify dispatch built AHEAD of the
    tokens (and drafts) they depend on: page/ring tables and sampling
    knobs are final at staging time; tokens, positions, qlens, kvlens
    and seeds are filled by ``dispatch_staged_verify`` once the previous
    step's readback has committed and the drafts are proposed."""

    seqs: list[ScheduledSeq]
    arrays: dict
    B: int
    q: int  # 1 + spec_ngram_k (the verify shape family's static Q)
    all_greedy: bool


@dataclass
class StagedDecode:
    """Host arrays for a decode dispatch built AHEAD of the tokens they
    feed on (async stepping): everything shape- and page-dependent is
    final at staging time; only ``first``/``start`` (and seeded rows'
    seeds) depend on the previous step's readback and are filled by
    ``dispatch_staged_decode`` right before dispatch."""

    seqs: list[ScheduledSeq]
    arrays: dict
    B: int
    k: int
    all_greedy: bool
    # Filled, and its payload on the device already (``prepare_staged``).
    prepared: "_Prepared | None" = None


@dataclass
class StagedUnified:
    """Host arrays for a unified single-dispatch step built AHEAD of the
    tokens/drafts they depend on (async prestaging): the ROW STRUCTURE
    (prefill chunks split into <= _UNIFIED_ROW_TOKENS sub-rows, one row
    per decode seq at its planned width) and everything row-dependent
    but token-independent — page/ring tables, sampling knobs, lora
    slots — are final at staging; the packed token stream, per-row
    (start, qlen, kind) metadata and seeds are filled by
    ``dispatch_staged_unified`` once the previous step's readback has
    committed and any drafts are proposed."""

    prefills: list[ScheduledSeq]
    decodes: list[ScheduledSeq]
    row_seqs: list[ScheduledSeq]  # one entry per unified row
    row_off: list[int]  # prefill sub-row token offset within its chunk
    row_plan: list[int]  # planned row width (actual qlen <= plan)
    prefill_rows: list[int]  # row index of each prefill seq's LAST sub-row
    decode_rows: list[int]  # row index of each decode seq
    arrays: dict
    B: int
    Q: int  # static per-row column count (bucketed max row width)
    T: int  # token-stream bucket (bucketed sum of planned widths)
    S: int  # sample columns per row (spec_q on speculative engines, 1)
    all_greedy: bool
    # Flattened-token staging (`--ragged-qlens`): dispatch rides the
    # _OP_FLAT program over the packed stream (B is the FIXED row-
    # metadata width, T a fine-grained flat bucket) instead of the
    # bucketed [B, Q] gather.
    flat: bool = False
    # Filled, and its payload on the device already (``prepare_staged``).
    prepared: "_Prepared | None" = None


@dataclass
class _Prepared:
    """A staged step made ready AHEAD of its dispatch: its payload buffer on
    the device, and what the fill changed of the runner (the rng it drew the
    seeds from, the counters it added to), so that a step which is staged
    anew before it is ever dispatched (a top-up, a rollback) is forgotten
    whole (``ModelRunner.unprepare``)."""

    step: jax.Array
    rng_state: dict
    counters: dict


@dataclass
class PendingUnified:
    """One dispatched-but-unread unified step: the packed [B, 2S] device
    output plus the row maps that split it back into prefill first-token
    results and decode/verify windows at ``wait_step``'s single
    coalesced readback."""

    packed: jax.Array
    S: int
    prefill_rows: list[int]
    decode_rows: list[int]
    n_prefills: int
    n_decodes: int


@dataclass
class WaitTiming:
    """When the last ``wait_step`` learnt that its outputs were ready, began
    to read them back (behind ``at_ready``) and had them parsed (monotonic
    s), and the most the notice can have lagged the device: from the last
    ``is_ready()`` that was false (the wait's entry where the first was true)
    to the first that was true; 0 for a blocking wait. ``ready_at`` less that
    bound is the last false look: a step's HOLD on the device starts there."""

    ready_lag_bound_s: float = 0.0
    ready_at: float = 0.0
    read_from: float = 0.0
    read_at: float = 0.0

    @property
    def readback_s(self) -> float:
        return self.read_at - self.read_from


class ModelRunner:
    # Step payload buffers put on the device and their bytes (_put_step;
    # EngineStats fields of the same names). One a step program.
    step_h2d_transfers_total = 0
    step_h2d_bytes_total = 0
    # [token_slots] i32 on the device: the token sampled last for each
    # running request, at ``Request.token_slot``. Every step program writes
    # what it sampled for its rows and hands the array on (donated, like
    # the pools); a decode row whose token the host has not read yet takes
    # its input from there (``_token_input``), which is what lets the
    # engine dispatch step N+1 before step N's readback. ONE shape: no
    # program is compiled for it. (``_build_programs`` leaves host zeros
    # where there is none yet, which is all that a runner put together
    # without ``__init__``, to lower a program for a described chip, ever
    # hands its programs; ``__init__`` puts the array on the device.)
    last_tokens = None
    # What a step's fill and put add to (``_Prepared.counters``).
    _FILL_COUNTERS = (
        "step_h2d_transfers_total", "step_h2d_bytes_total",
        "live_tokens_total", "padded_tokens_total",
        "attn_shared_tile_tokens_total", "attn_prefix_run_keys_total",
        "attn_decode_keys_total", "sparse_bound_tokens_total",
        "sparse_unbound_tokens_total", "indexer_keys_scored_total",
        "indexer_keys_written_total", "sparse_rows_selected_total",
        "latent_rows_written_total", "ssm_update_rows_total",
        "ssm_scan_tokens_total", "gdn_update_rows_total",
        "gdn_scan_rows_total", "gdn_scan_tokens_total",
    )

    def __init__(
        self,
        config: EngineConfig,
        mesh_ctx: MeshContext,
        params: dict | None = None,
        swa_spec=None,
    ) -> None:
        self.config = config
        self.cfg = config.model
        config.check_sparse_attention()
        config.check_state_space()
        self.ctx = mesh_ctx
        self.max_pages = config.cache.max_pages_per_seq(self.cfg.max_model_len)
        self.page = config.cache.page_size
        # SWA ring geometry (CacheConfig.swa_ring). The ENGINE passes its
        # resolved spec so allocator/scheduler and pool/table geometry
        # share one source of truth; a standalone runner resolves its own.
        self._swa_spec_arg = swa_spec

        if params is None:
            if config.weights_path:
                from llmd_tpu.models.loader import load_params

                params = load_params(self.cfg, config.weights_path)
            else:
                params = llama.init_params(self.cfg, jax.random.key(config.seed))
        params = self._maybe_fuse(params)
        self.params = shard_params(params, mesh_ctx)
        # Wide-EP MoE live state. ep_capacity is the LIVE capacity factor
        # (the adaptive controller may move it; every change rebuilds the
        # jitted programs so each compiled family sees exactly one static
        # capacity). The census buffer is the [E+2] accumulator
        # (moe_ep.CENSUS layout: per-expert routed tokens, dropped slots,
        # max dispatch demand) threaded through every forward and drained
        # by the engine's stats refresh — no extra per-step host
        # transfer beyond the read the stats path already does.
        pc = config.parallel
        self.ep_capacity = float(pc.ep_capacity_factor)
        self._ep_active = bool(self.cfg.is_moe) and pc.moe_backend == "ep"
        self.moe_overlap = int(pc.moe_overlap) if self._ep_active else 0
        self._moe_census = None
        if self._ep_active:
            from llmd_tpu.parallel.moe_ep import census_size

            self._moe_census = jax.device_put(
                np.zeros(census_size(self.cfg), np.float32),
                mesh_ctx.replicated,
            )
        elif (
            self.cfg.is_moe and pc.moe_backend == "grouped"
            and mesh_ctx.world == 1
        ):
            # The one-device grouped backend threads the grouped expert
            # matmul's count through the same argument (llama.
            # forward_hidden): [4] i32, grouped MoE layer calls, the groups
            # with rows, the router's picks and the picks whose expert is
            # held here (ops.grouped_gemm.grouped_census). Every step
            # program adds its own, hands the sum back in its packed
            # output and this accumulator back at zero (_count_row), so
            # the count comes home with the step's one readback
            # (wait_step) and is the step's own. The wide-EP path keeps
            # its census above and is not counted here; nor is the embed
            # program, which is no step.
            self._moe_census = jax.device_put(
                np.zeros(4, np.int32), mesh_ctx.replicated
            )
        self.moe_grouped_calls_total = 0
        self.moe_groups_with_rows_total = 0
        self.moe_picks_total = 0
        self.moe_picks_held_total = 0
        # Pristine logical [L, E, ...] expert leaves, stashed on first
        # EPLB remap so later placements regather from the un-replicated
        # originals; the host-side Placement mirrors params["moe_placement"].
        self._logical_experts: dict | None = None
        self.moe_placement = None
        # SWA ring (CacheConfig.swa_ring): sliding-window layers live in a
        # second, smaller pool indexed through a ring-view page table.
        self.swa = self._swa_spec_arg or swa_ring_spec(
            self.cfg, config.cache, config.scheduler
        ) or state_slot_spec(self.cfg, config.scheduler)
        # State-space layers (``state_pool``): the second pool is the STATE
        # pool, a slot a sequence (ops/ssm.py::StatePool), under the same
        # argument, the same donation and the same device copies as the ring.
        self.kv_cache = self._alloc_kv()
        self.kv_swa = self._alloc_swa()
        # Bytes of one page id over all of a pool's layers (every plane an
        # IndexedPool or an int8 pool carries under that id).
        self.kv_page_bytes, self.kv_swa_page_bytes = (
            0 if pool is None else sum(
                a.nbytes // a.shape[1] for a in jax.tree.leaves(pool)
            )
            for pool in (self.kv_cache, self.kv_swa)
        )
        self._multihost = dist.is_multihost()
        # Serializes lockstep broadcast+dispatch pairs so NON-engine
        # threads (P/D fetch staging, embeds, adapter installs) can
        # originate ops: followers mirror in receive order, so each
        # leader op must be broadcast AND dispatched atomically.
        self._dispatch_lock = threading.RLock()
        # Set by stop_followers: once _OP_STOP is broadcast the followers
        # are gone, and any later lockstep broadcast (e.g. from an
        # orphaned streamed-fetch thread) would block forever in a
        # collective nobody answers — refuse loudly instead.
        self._stopped = False  # llmd: guarded_by(_dispatch_lock)
        # Lockstep liveness: every collective leg runs under a bounded
        # wait (LLMD_LOCKSTEP_TIMEOUT_S; 0 disables) so a dead peer is a
        # loud RuntimeError within the budget instead of an infinite
        # hang, and an idle leader heartbeats (_OP_HEARTBEAT) so the
        # followers' bounded header wait can tell "idle leader" from
        # "dead leader".
        try:
            self.lockstep_timeout_s = float(
                os.environ.get("LLMD_LOCKSTEP_TIMEOUT_S", "300") or 0
            )
        except ValueError:
            self.lockstep_timeout_s = 300.0
        self._lockstep_pool = None
        self._last_broadcast = 0.0
        # The FIRST collective round carries cold-cache jit compiles and
        # weight-load skew across hosts (the deploy startupProbe budgets
        # hours for it) — bounding it would declare a healthy group dead
        # mid-startup. The wait arms after one successful collective,
        # mirroring the serving watchdog's first-step exemption.
        self._lockstep_warmed = False
        # Mid-serving compile grace: the first dispatch of each
        # (op, B, QK) shape family jit-compiles on every host, and
        # per-host persistent-cache skew (one host hits the cache,
        # another compiles for minutes) can legitimately exceed the
        # liveness budget long after startup. After a first-of-family
        # dispatch each side grants its NEXT bounded wait one unbounded
        # pass — the peer is compiling, not dead. Both sides see every
        # header, so the seen-sets stay in sync.
        self._lockstep_seen_shapes: set = set()
        self._lockstep_compile_grace = False
        self._hb_stop = threading.Event()
        if self._multihost and dist.is_leader() and self.lockstep_timeout_s:
            threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name="llmd-lockstep-hb",
            ).start()
        self._np_rng = np.random.default_rng(config.seed ^ 0x5EED)

        if config.parallel.enable_dbo and not ops._on_tpu(mesh_ctx.mesh):
            # Never a silent regression: see ParallelConfig.enable_dbo
            # for the full substrate condition.
            log.warning(
                "enable_dbo is ON without a TPU backend: profiled on the "
                "CPU mesh, the half-batch split MULTIPLIES all-to-all ops "
                "~3.8x (2.4x collective device-time) with nothing to hide "
                "them behind — steps run ~1.9x slower. EXPERIMENTAL: "
                "enable only on a real multi-chip slice and trust the "
                "bench delta (docs/architecture/dbo.md)"
            )
        if self.moe_overlap > 1 and not ops._on_tpu(mesh_ctx.mesh):
            # Same substrate condition as DBO: see ParallelConfig.
            # moe_overlap and the bench moe_ep part's on/off delta.
            log.warning(
                "moe_overlap=%d without a TPU backend: the microbatched "
                "EP dispatch only pays where the all-to-all runs "
                "asynchronously on a real ICI fabric; on the CPU mesh the "
                "extra collective launches are pure overhead. EXPERIMENTAL: "
                "graduate via the bench moe_ep part on a real slice "
                "(docs/architecture/wide-ep.md)", self.moe_overlap,
            )
        # Context-parallel ring prefill (ParallelConfig.cp_prefill): armed
        # only for non-MLA models on a mesh whose dp axis matches the cp
        # degree (the config validates cp == data_parallel_size). The
        # dedicated _forward_cp family serves chunk widths divisible by
        # cp and at least cp_prefill_min_tokens; everything else keeps
        # the monolithic program.
        self.cp_prefill = (
            int(pc.cp_prefill)
            if pc.cp_prefill > 1 and not self.cfg.is_mla
            else 0
        )
        self.cp_min_tokens = int(pc.cp_prefill_min_tokens)
        # Ring collective steps dispatched (cp per cp-prefill call);
        # drained into EngineStats.cp_ring_steps_total.
        self.cp_ring_steps_total = 0
        sched = config.scheduler
        self.batch_buckets = sched.decode_batch_buckets or _buckets(sched.max_num_seqs)
        self.prefill_batch_buckets = (
            sched.prefill_batch_buckets or _buckets(sched.max_num_seqs, start=1)
        )
        self.prefill_buckets = sched.prefill_token_buckets or _buckets(
            sched.max_num_batched_tokens, start=16
        )
        # op -> the kernel plans its traces took (ops.record_plans).
        self.kernel_plans: dict[str, set[str]] = {}
        # Which step programs were traced, and when: (unix time, family,
        # [B, Q] — flat: [T, 1]; the windows: [B, tokens a row]). A trace
        # after warm-up is a shape nobody warmed: seconds inside a step.
        self.traced_programs: collections.deque = collections.deque(maxlen=256)
        self.programs_traced = 0
        self._tracing = ""  # the program being traced (_note_traced)
        # "family:shape" of the step program dispatched last (the
        # llmd.step span's ``program``).
        self.last_program = ""
        self.last_wait = WaitTiming()  # of the newest wait_step
        self._build_programs()
        self.last_tokens = jax.device_put(
            np.zeros(self.token_slots, np.int32), mesh_ctx.replicated
        )
        self._check_page_table_fits_smem()
        # Padding-efficiency accounting (EngineStats padded/live tokens):
        # every dispatch path adds its live token count and the padded
        # compute width the traced shape actually paid for.
        self.live_tokens_total = 0
        self.padded_tokens_total = 0
        # Of the flat stream's live tokens, those in 16-token granules
        # that hold ONE row only: the tiles whose context the flat
        # attention reads once for all their tokens
        # (ops/ragged_paged_attention.py; EngineStats field of the name).
        self.attn_shared_tile_tokens_total = 0
        # Of the keys the flat steps' plain decode tokens read (their
        # horizons, summed), those in a shared-prefix run's blocks: read
        # once a tile for all the run's members (engine/prefix_runs.py;
        # EngineStats fields of the names). Both 0 where no call takes runs.
        self.attn_prefix_run_keys_total = 0
        self.attn_decode_keys_total = 0
        # Learned sparse attention (EngineStats fields of the same names),
        # from the positions each flat step already holds: computed query
        # tokens with more / no more than indexer_topk cached tokens, the
        # cached keys the indexer scored for the former, and the indexer
        # keys written (one a computed token; every layer writes its own).
        self.sparse_bound_tokens_total = 0
        self.sparse_unbound_tokens_total = 0
        self.indexer_keys_scored_total = 0
        self.indexer_keys_written_total = 0
        # The same over a latent cache (models/mla_dsa.py), x the layers:
        # per computed token min(cached tokens, indexer_topk) selected rows,
        # and one latent row written.
        self.sparse_rows_selected_total = 0
        self.latent_rows_written_total = 0
        # State-space layers (EngineStats fields of the same names): decode
        # rows updated and prefill tokens scanned, x the mixer layers.
        self.ssm_update_rows_total = 0
        self.ssm_scan_tokens_total = 0
        # The same of gated delta-rule mixers (ops/gdn.py), which count under
        # names of their own: decode rows updated, prefill ROWS (the scan's
        # chunks) and tokens scanned, each x the mixer layers.
        self.gdn_update_rows_total = 0
        self.gdn_scan_rows_total = 0
        self.gdn_scan_tokens_total = 0

    @property
    def state_pool(self) -> bool:
        """The second pool holds recurrent state, a slot a sequence (a model
        with state-space layers), not the sliding layers' ring pages."""
        return self.swa is not None and self.swa.recurrent

    def _build_programs(self) -> None:
        """(Re)build every jitted forward program. Called at init and
        whenever a trace-time MoE static changes — an adaptive
        ep_capacity step or an EPLB remap (the we_* leaves change shape)
        — so no compiled family ever runs a stale capacity/placement."""
        sched = self.config.scheduler
        # ``last_tokens``' length, which is also the "no entry" a row's
        # ``tok_slot`` carries (a write there is dropped).
        self.token_slots = token_slot_count(sched.max_num_seqs)
        if self.last_tokens is None:
            self.last_tokens = np.zeros(self.token_slots, np.int32)
        self._forward = self._build_forward()
        self._forward_cp = (
            self._build_forward(cp=self.cp_prefill) if self.cp_prefill else None
        )
        self._multi = self._build_multi()
        # Speculative decoding (SchedulerConfig.speculative_ngram): the
        # verify step scores [B, 1 + spec_ngram_k] positions per decode
        # row in one forward — its own traced shape family (Q static per
        # engine, B over the decode batch buckets).
        self.spec_q = (
            1 + sched.spec_ngram_k if sched.speculative_ngram else 0
        )
        self._verify = self._build_verify() if self.spec_q else None
        # Decode depths warmup precompiles: the depths the scheduler can
        # pick. On speculative engines it never takes the fused-window
        # branch, so warming decode_window there would be dead compile
        # time.
        self.decode_windows = (
            (1,) if sched.speculative_ngram
            else tuple(sorted({1, sched.decode_window}))
        )
        # Unified single-dispatch step (SchedulerConfig.unified_step): one
        # ragged program packs a whole window=1 step — prefill chunk
        # runs, plain decode rows, one-shot verify rows. Sample columns
        # per row: verify rows need spec_q, everything else 1.
        self.unified_s = max(self.spec_q, 1)
        # Per-row width cap (long chunks split into sub-rows); must cover
        # the verify family's 1 + k columns.
        self.unified_row_cap = max(_UNIFIED_ROW_TOKENS, self.unified_s)
        self.unified_q_buckets = _buckets(self.unified_row_cap, start=8)
        # Row-count bound: every scheduled seq is one row, plus at most
        # budget // cap extra sub-rows from chunk splitting.
        self.unified_row_buckets = _buckets(
            sched.max_num_seqs
            + sched.max_num_batched_tokens // self.unified_row_cap,
            start=1,
        )
        self._unified = (
            self._build_unified() if sched.unified_step else None
        )
        # Step payload layouts by (op, B, QK), and the (program, B, QK,
        # greedy) that have had their first call (_run_step).
        self._layouts: dict[tuple[int, int, int], payload.PayloadLayout] = {}
        self._called: set = set()
        # Genuinely ragged flattened-token step (SchedulerConfig.
        # ragged_qlens): the unified step's forward runs over the packed
        # [T] token stream with cu_q_lens row offsets — no [B, Q]
        # padding. ONE T-bucket dimension (16-token granules, so padding
        # waste is bounded by 15 tokens/step) replaces the bucketed
        # unified family's (rows x Q x T) cross-product; the row-
        # metadata width is FIXED at the largest row bucket (metadata is
        # O(rows), not O(tokens) — a few KB). Plain MLA keeps the
        # bucketed layout (``mla.KIND`` writes and reads a row's whole
        # context there); MLA with an indexer exists on the flat stream only
        # (``mla_dsa.KIND``: ops/sparse_mla.py).
        self._flat = None
        self.flat_rows = 0
        self.flat_t_buckets: tuple[int, ...] = ()
        # Does the flat step plan shared-prefix runs for its attention
        # (engine/prefix_runs.py)? Wherever the flat stream's attention is
        # the paged kernel over ``page_table``: every flat model but the
        # latent ones (ops/sparse_mla.py gathers rows).
        self._plans_runs = False
        if sched.unified_step and sched.ragged_qlens and (
            not self.cfg.is_mla or self.cfg.sparse_attention
        ):
            limit = sched.max_num_batched_tokens + max(self.unified_s, 1)
            limit = -(-limit // 16) * 16
            self.flat_t_buckets = tuple(range(16, limit + 1, 16))
            self.flat_rows = self.unified_row_buckets[-1]
            self._plans_runs = not self.cfg.is_mla
            self._flat = self._build_flat()

    def _check_page_table_fits_smem(self) -> None:
        """The attention kernels scalar-prefetch the whole page table
        into SMEM; refuse a geometry whose table cannot fit there now,
        with the flags that size it, instead of letting the chip's
        compiler refuse the first request's program."""
        fit = ops.page_table_smem(
            self.cfg, self.page, self.max_pages, self.ctx.world,
            self.ctx.mesh, decode_rows=self.batch_buckets[-1],
            flat_rows=self.flat_rows,
            flat_tokens=self.flat_t_buckets[-1] if self.flat_rows else 0,
        )
        if fit is None or fit[0] <= fit[1]:
            return
        sched = self.config.scheduler
        raise ValueError(
            f"the page table ({self.max_pages} pages a row) needs {fit[0]} B "
            f"of the {fit[1]} B of SMEM the attention kernels prefetch it "
            f"into: lower --max-model-len ({self.cfg.max_model_len}; pages "
            f"= max-model-len / block-size {self.page}) or --max-num-seqs "
            f"({sched.max_num_seqs}; rows grow with it and with "
            f"--max-num-batched-tokens / {self.unified_row_cap}), or "
            "LLMD_PALLAS=off for the XLA attention"
        )

    # ------------------------------------------------------------------ #
    # Wide-EP MoE control plane (census drain, adaptive capacity, EPLB)

    # Expert param leaves remapped by an EPLB placement (present subset
    # only: bf16 weights, int8 channel scales, gpt-oss biases).
    _EXPERT_LEAVES = (
        "we_gate", "we_up", "we_down",
        "we_gate_scale", "we_up_scale", "we_down_scale",
        "we_gate_b", "we_up_b", "we_down_b",
    )

    def drain_moe_census(self) -> np.ndarray | None:
        """Read-and-reset the MoE census accumulator ([E+2] f32: routed
        tokens per logical expert, dropped slots, max dispatch demand as
        a capacity-factor multiple). Called by the engine's stats refresh
        once per step — the read rides the sync the stats path already
        does."""
        if not self._ep_active:
            return None
        from llmd_tpu.parallel.distributed import replicated_to_host

        out = np.asarray(replicated_to_host(self._moe_census))
        self._moe_census = jax.device_put(
            np.zeros_like(out), self.ctx.replicated
        )
        return out

    def set_ep_capacity(self, factor: float) -> None:
        """Move the live EP capacity factor (adaptive controller step).
        Rebuilds the jitted programs: capacity is a trace-time static, so
        every compiled family must re-trace at the new value."""
        if float(factor) == self.ep_capacity:
            return
        self.ep_capacity = float(factor)
        self._build_programs()

    def apply_expert_placement(self, placement) -> None:
        """Install an EPLB placement (parallel.eplb.Placement) at a step
        boundary: regather the ``we_*`` leaves from the pristine logical
        layout into the physical one ([L, E_phys, ...], hot experts
        replicated), publish the routing tables into
        ``params["moe_placement"]`` (the router maps logical ids through
        them inside moe_block_ep), and rebuild the programs — the leaf
        shapes changed, so every family re-traces exactly once per
        placement epoch."""
        if not self._ep_active:
            raise RuntimeError("EPLB requires moe_backend='ep'")
        self._require_single_host("apply_expert_placement (EPLB)")
        from llmd_tpu.parallel.mesh import param_specs

        layers = dict(self.params["layers"])
        names = [k for k in self._EXPERT_LEAVES if k in layers]
        if self._logical_experts is None:
            self._logical_experts = {k: layers[k] for k in names}
        idx = jnp.asarray(placement.phys_to_logical, jnp.int32)
        specs = param_specs({k: self._logical_experts[k] for k in names})
        with self._dispatch_lock:
            for k in names:
                # llmd: allow(trace-discipline) -- control-plane only: runs once per EPLB placement epoch (eplb_interval_steps), never on the step path; out_shardings is per-leaf so the gather lands sharded without a host roundtrip
                gather = jax.jit(
                    lambda w, i: jnp.take(w, i, axis=1),
                    out_shardings=self.ctx.sharding(*specs[k]),
                )
                layers[k] = gather(self._logical_experts[k], idx)
            tables = {
                "phys_to_logical": placement.phys_to_logical,
                "replicas": placement.replicas,
                "n_replicas": placement.n_replicas,
            }
            self.params = {
                **self.params,
                "layers": layers,
                "moe_placement": {
                    k: jax.device_put(
                        np.asarray(v, np.int32), self.ctx.replicated
                    )
                    for k, v in tables.items()
                },
            }
            self.moe_placement = placement
            self._build_programs()

    # ------------------------------------------------------------------ #

    def _maybe_fuse(self, params: dict) -> dict:
        """Fuse q|k|v and gate|up projections into single matmuls (one
        activation quantization + one bigger MXU dot instead of three).

        Lossless by construction: per-output-channel int8 scales (and
        bf16 weights) concatenate exactly, so the fused dot equals the
        separate dots bit-for-bit. Only when the layout allows: tp == 1
        (the fused output axis cannot ride the per-projection TP shard),
        no LoRA (adapters add to q/v, fine — but kept simple), non-MLA.

        Runs as ONE jitted call with the unfused tree donated — eager
        per-tensor concats would transiently double the projection
        weights on device and fragment the arena (the same init-OOM
        pattern the jitted quantize call avoids, models/llama.py).
        """
        cfg = self.cfg
        if (
            not self.config.parallel.fuse_projections
            or self.ctx.tp > 1
            or cfg.is_mla
            or cfg.num_lora_adapters
        ):
            return params
        # Jitted only so the donated tree fuses in-place instead of
        # transiently doubling HBM (see docstring).
        # llmd: allow(trace-discipline) -- one-shot at __init__ weight load, never on the step path
        return jax.jit(_fuse_projection_tree, donate_argnums=0)(
            jax.tree.map(jnp.asarray, params)
        )

    @functools.cached_property
    def kv_rep(self) -> int:
        """KV-head replication factor for the pool's head axis.

        When tp exceeds (but is a multiple of) the KV head count, each kv
        head is stored tp/K times consecutively so the head axis shards
        over tp: per-chip KV becomes pool/K instead of the full replicated
        pool the plain spec degrades to (the reference's FlashInfer-under-
        TP layouts make the same trade). GQA stays exact — q head h reads
        expanded head h // (Nq / (K*rep)), which holds h's kv head."""
        K, tp, Nq = self.cfg.kv_cache_heads, self.ctx.tp, self.cfg.num_heads
        if (
            not self.cfg.is_mla
            and tp > 1
            and K % tp != 0
            and tp % K == 0
            and Nq % tp == 0
        ):
            return tp // K
        return 1

    def _alloc_kv(self):
        c = self.config.cache
        layers = (
            len(self.swa.full_layers) if self.swa is not None
            else self.cfg.num_layers
        )
        kv = self._alloc_pool(layers, c.num_blocks)
        if not self.cfg.sparse_attention:
            return kv
        # Learned sparse attention: the indexer's one key per token, a
        # second plane under the SAME page ids (ops/sparse_attention.py).
        plane = (layers, c.num_blocks, c.page_size, self.cfg.indexer_head_dim)
        return ops.IndexedPool(kv=kv, index=jnp.zeros(
            plane, jnp.dtype(c.dtype), device=self.ctx.replicated
        ))

    def _alloc_swa(self):
        """The second pool: the sliding-window ring pool, or the state pool
        of a model with state-space layers (None where there is neither)."""
        if self.swa is None:
            return None
        if self.state_pool:
            m, rep = self.cfg, self.ctx.replicated
            # One slot past the allocator's: the scan's scratch (ops/ssm.py).
            Lm, S = len(self.swa.state_layers), self.swa.num_swa_blocks + 1
            state, conv = m.state_shapes
            return ssm_ops.StatePool(
                ssm=jnp.zeros((Lm, S, *state), jnp.float32, device=rep),
                conv=jnp.zeros((Lm, S, *conv), jnp.dtype(m.dtype), device=rep),
            )
        return self._alloc_pool(len(self.swa.swa_layers), self.swa.num_swa_blocks)

    def _alloc_pool(self, num_layers: int, num_blocks: int):
        c = self.config.cache
        shape = (
            num_layers,
            num_blocks,
            self.cfg.kv_cache_heads * self.kv_rep,  # MLA: one latent "head"
            c.page_size,
            self.cfg.kv_cache_entry_dim,
        )
        if c.quantized and self.cfg.is_mla:
            # Latent rows ([rank | rope] padded to lanes) need their own
            # scale layout; the K|V midpoint split is wrong for them —
            # refuse rather than silently degrade accuracy (same policy
            # as the int8 transfer encoding).
            raise ValueError(
                "kv cache dtype 'int8' is not supported for MLA models"
            )
        if self.cfg.is_mla:
            # The latent pool replicates across tp BY DESIGN: rows are a
            # few hundred bytes and every head reads the same latent —
            # not the GQA mis-configuration kv_cache_spec warns about.
            spec = jax.sharding.PartitionSpec()
        else:
            spec = kv_cache_spec(shape[2], self.ctx.tp)
        sharding = self.ctx.sharding(*spec)
        if c.quantized:
            # Int8 pool: (data i8, per-row K/V-half scales f32 in the
            # pool layout [L, P, K, page, 2]) — see ops/quant_kv.py for
            # the layout contract. Scales share the data pool's head
            # sharding (same axis position).
            sshape = (shape[0], shape[1], shape[2], shape[3], 2)
            if dist.is_multihost():
                return jax.jit(
                    lambda: (
                        jnp.zeros(shape, jnp.int8),
                        jnp.ones(sshape, jnp.float32),
                    ),
                    out_shardings=(sharding, sharding),
                )()
            return (
                jnp.zeros(shape, jnp.int8, device=sharding),
                jnp.ones(sshape, jnp.float32, device=sharding),
            )
        if dist.is_multihost():
            # Global pool spanning processes: allocate via a jitted zeros
            # so no host ever materializes (or addresses) the full array.
            dt = jnp.dtype(c.dtype)
            return jax.jit(
                lambda: jnp.zeros(shape, dt), out_shardings=sharding
            )()
        return jnp.zeros(shape, jnp.dtype(c.dtype), device=sharding)

    @property
    def kv_quantized(self) -> bool:
        return isinstance(self.kv_cache, tuple)

    @property
    def _kv_data(self) -> jax.Array:
        if isinstance(self.kv_cache, ops.IndexedPool):
            return self.kv_cache.kv
        return self.kv_cache[0] if self.kv_quantized else self.kv_cache

    @property
    def staging_dtype(self) -> np.dtype:
        """Canonical dtype of dequantized staging bundles (transfer wire
        'exact' form, offload host pages): the model compute dtype for
        int8 pools, the pool dtype otherwise."""
        if self.kv_quantized:
            return np.dtype(jnp.dtype(self.cfg.dtype))
        return np.dtype(self._kv_data.dtype)

    @property
    def staging_dtype_name(self) -> str:
        return self.staging_dtype.name

    def kv_bytes(self) -> int:
        return sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves((self.kv_cache, self.kv_swa))
        )

    def set_lora_weights(self, lora_id: int, weights: dict) -> None:
        """Install adapter weights into slot ``lora_id`` (1-based).

        ``weights`` maps la_q/lb_q/la_v/lb_v to stacked
        ``[num_layers, ...]`` arrays matching the slot's shape; A and B
        must be installed together per projection (setting only B would
        silently compose with whatever A the slot holds — zeros on
        checkpoint-loaded models, i.e. an identity adapter). Slots
        initialize with B == 0 (adapter == base model), so serving an
        adapter name before its weights load is safe; this is the hook
        checkpoint loading and dynamic adapter registration use.
        """
        if not (0 < lora_id <= self.cfg.num_lora_adapters):
            raise ValueError(f"lora_id {lora_id} out of range")
        for a, b in (("la_q", "lb_q"), ("la_v", "lb_v")):
            if (a in weights) != (b in weights):
                raise ValueError(
                    f"LoRA install must pair {a} with {b}: partial updates "
                    "compose with stale/zero factors and silently serve the "
                    "wrong adapter"
                )
        for k in weights:
            if k not in ("la_q", "lb_q", "la_v", "lb_v"):
                raise KeyError(f"unknown LoRA tensor {k!r}")
        # Multi-host: the per-slot update is a plain SPMD program over
        # the sharded params — broadcast the factors (header: B carries
        # a pair-presence bitmask, QK the slot id) and apply everywhere.
        mask = (1 if "la_q" in weights else 0) | (2 if "la_v" in weights else 0)
        layers = self.params["layers"]
        arrays = {
            # Normalized to the slot's (L, *factor) shape so the payload
            # spec is derivable from the shared params structure.
            k: np.ascontiguousarray(np.asarray(v, np.float32)).reshape(
                layers[k].shape[0], *layers[k].shape[2:]
            )
            for k, v in weights.items()
        }
        with self._dispatch_lock:
            arrays = self._sync_locked(_OP_LORA, mask, lora_id, False, arrays)
            self._exec_lora(arrays, lora_id)

    def _exec_lora(self, arrays: dict, lora_id: int) -> None:
        layers = dict(self.params["layers"])
        for k, v in arrays.items():
            arr = layers[k]
            layers[k] = arr.at[:, lora_id].set(
                jnp.asarray(v, arr.dtype).reshape(arr.shape[0], *arr.shape[2:])
            )
        self.params = {**self.params, "layers": layers}

    def _replicate_out(self, packed: jax.Array) -> jax.Array:
        """Multi-host: pin the packed host transfer to full replication so
        every process can read it locally (single-host: no-op)."""
        if not dist.is_multihost():
            return packed
        return jax.lax.with_sharding_constraint(packed, self.ctx.replicated)

    def _fwd_hidden(self, params, kv_cache, kv_swa, inp, census, dbo=False,
                    cp=0):
        """llama.forward_hidden under this runner's trace-time MoE/EP
        statics (live ep_capacity, moe_overlap, the EPLB placement riding
        in ``params["moe_placement"]``), threading the census accumulator
        when armed. Returns (hidden, kv_cache, kv_swa, census) uniformly
        so every builder shares one call shape. Builders are recreated by
        _build_programs whenever a static here changes, so each compiled
        family sees exactly one value."""
        cfg = self.cfg
        moe_backend = (
            self.config.parallel.moe_backend if cfg.is_moe else "dense"
        )
        kw = {}
        ring = self.swa is not None
        if ring:
            kw["kv_swa"] = kv_swa
        if census is not None:
            kw["moe_census"] = census
        # Runs at trace time only: the model's forward being traced, as
        # a span of the program ``_note_traced`` named, and every op
        # noting which kernel plan it took.
        with profiling.span(
            "llmd.runner.trace", program=self._tracing
        ), ops.record_plans(self.kernel_plans):
            out = llama.forward_hidden(
                params, kv_cache, inp, cfg, self.ctx.world,
                mesh=self.ctx.mesh, moe_backend=moe_backend,
                ep_capacity_factor=self.ep_capacity, kv_rep=self.kv_rep,
                dbo=dbo, moe_overlap=self.moe_overlap,
                moe_placement=params.get("moe_placement"),
                cp_prefill=cp,
                **kw,
            )
        if census is not None:
            census = out[-1]
            out = out[:-1]
        hidden, kv_cache = out[0], out[1]
        if ring:
            kv_swa = out[2]
        return hidden, kv_cache, kv_swa, census

    @property
    def _counts_grouped(self) -> bool:
        """The census argument is the grouped expert matmul's [4] count
        (armed in __init__), not the wide-EP census."""
        return self._moe_census is not None and not self._ep_active

    def _count_row(self, packed: jax.Array, census):
        """Last lines of every step program's body. The grouped count
        leaves the device as two more rows of the packed output (which may
        be two columns wide), [calls, groups with rows, 0, ...] and [picks,
        picks held, 0, ...] in f32 (exact: a program counts at most
        iterations x layers x tokens x top-k, far under 2**24), and the
        accumulator goes back zeroed for the next step. Any other census
        (wide-EP, none) passes through."""
        if not self._counts_grouped:
            return packed, census
        rows = jnp.zeros((2, packed.shape[1]), packed.dtype)
        rows = rows.at[:, :2].set(census.reshape(2, 2).astype(packed.dtype))
        return jnp.concatenate([packed, rows]), jnp.zeros_like(census)

    @staticmethod
    def _token_input(f: dict, last_tokens, host, row_of, is_first):
        """The token input of every step program the pipelined step
        dispatches without drafts: ``host`` (the stream the host packed),
        but the first token of a row flagged ``tok_dev`` is the one the
        device sampled last for the row's sequence, ``last_tokens[
        tok_slot]``: the step that sampled it may not have been read back
        yet. ``row_of`` maps ``host``'s elements to rows and ``is_first``
        marks a row's first token (both broadcast against ``host``)."""
        slot = jnp.clip(f["tok_slot"], 0, last_tokens.shape[0] - 1)
        from_device = (f["tok_dev"] != 0)[row_of] & is_first
        return jnp.where(from_device, last_tokens[slot][row_of], host)

    @staticmethod
    def _keep_sampled(f: dict, last_tokens, sampled):
        """``last_tokens`` with what the step sampled for each of its rows
        ([B]) at the row's ``tok_slot``; a row without one (a pad row, a
        chunk that does not complete its prompt, a speculative row)
        carries the array's length, and its write is dropped."""
        return last_tokens.at[f["tok_slot"]].set(
            sampled.astype(jnp.int32), mode="drop"
        )

    def _note_traced(self, family: str, shape) -> None:
        """First line of every jitted step program's body, so it runs
        once per trace of the program (a new shape, or a rebuilt family):
        counts it and logs (unix time, family, shape)."""
        shape = tuple(int(d) for d in shape)
        self.traced_programs.append((time.time(), family, shape))
        self.programs_traced += 1
        self._tracing = f"{family}:{shape}"

    @staticmethod
    def _rows_inputs(f: dict) -> tuple[StepInput, SamplingInputs]:
        """The [B, Q] step programs' (prefill, verify) inputs out of their
        unpacked payload."""
        inp = StepInput(
            token_ids=f["tokens"],
            positions=f["positions"],
            query_lens=f["qlens"],
            kv_lens=f["kvlens"],
            page_table=f["page_table"],
            lora_ids=f.get("lora"),
            swa_page_table=f.get("swa_table"),
        )
        s = SamplingInputs(
            temperature=f["temp"], top_k=f["top_k"], top_p=f["top_p"],
            seeds=f["seeds"],
        )
        return inp, s

    def _build_forward(self, cp: int = 0):
        """The prefill/one-shot-step program. ``cp`` > 1 builds the
        context-parallel ring variant (ops/ring_attention.py): same call
        shape, attention sharded over the mesh dp axis — a separate
        compiled family the dispatcher selects by chunk width."""
        cfg = self.cfg
        dbo = self.config.parallel.enable_dbo
        replicate = self._replicate_out
        ring = self.swa is not None

        @functools.partial(
            jax.jit,
            donate_argnums=(1, 2, 4) if ring else (1, 4),
            static_argnames=("B", "Q", "all_greedy"),
        )
        def llmd_prefill_step(params, kv_cache, kv_swa, step, last_tokens,
                              census=None, B=0, Q=0, all_greedy=False):
            self._note_traced("prefill_cp" if cp else "prefill", (B, Q))
            f = self._layout(_OP_PREFILL, B, Q).unpack(step)
            inp, s = self._rows_inputs(f)
            hidden, kv_cache, kv_swa, census = self._fwd_hidden(
                params, kv_cache, kv_swa, inp, census, dbo=dbo, cp=cp
            )
            B = hidden.shape[0]
            last = jnp.maximum(inp.query_lens - 1, 0)
            h_last = hidden[jnp.arange(B), last]
            logits = llama.compute_logits(params, h_last, cfg)
            tokens, logprobs = sample_tokens(logits, s, all_greedy)
            # Pack into one array => one host transfer for the whole step.
            packed = jnp.concatenate(
                [tokens.astype(jnp.float32)[:, None], logprobs[:, None]], axis=1
            )
            packed, census = self._count_row(packed, census)
            last_tokens = self._keep_sampled(f, last_tokens, tokens)
            return kv_cache, kv_swa, replicate(packed), last_tokens, census

        return llmd_prefill_step

    def _build_verify(self):
        """Speculative verify: the prefill forward over [B, 1+k] rows
        (chunked-prefill/ragged-paged-attention path — no new kernel,
        just a new traced shape family), sampling at EVERY position
        instead of only the last. Row i feeds [last committed token,
        draft_0..draft_{m-1}] with per-row draft-length masks
        (query_lens); position j's sample is the target token for output
        index j, computed under the draft's context. KV for all 1+k
        positions is written provisionally — the scheduler truncates
        past the accepted prefix before any page commit."""
        cfg = self.cfg
        dbo = self.config.parallel.enable_dbo
        replicate = self._replicate_out
        ring = self.swa is not None

        @functools.partial(
            jax.jit,
            donate_argnums=(1, 2, 4) if ring else (1, 4),
            static_argnames=("B", "Q", "all_greedy"),
        )
        def llmd_verify_step(params, kv_cache, kv_swa, step, last_tokens,
                             census=None, B=0, Q=0, all_greedy=False):
            self._note_traced("verify", (B, Q))
            inp, s = self._rows_inputs(
                self._layout(_OP_VERIFY, B, Q).unpack(step)
            )
            hidden, kv_cache, kv_swa, census = self._fwd_hidden(
                params, kv_cache, kv_swa, inp, census, dbo=dbo
            )
            B, Q, H = hidden.shape
            logits = llama.compute_logits(params, hidden.reshape(B * Q, H), cfg)
            flat = SamplingInputs(
                temperature=jnp.repeat(s.temperature, Q),
                top_k=jnp.repeat(s.top_k, Q),
                top_p=jnp.repeat(s.top_p, Q),
                seeds=s.seeds.reshape(B * Q),
            )
            tokens, logprobs = sample_tokens(logits, flat, all_greedy)
            # Same packed [B, 2Q] layout as the fused decode window, so
            # wait_step's coalesced readback handles both identically.
            packed = jnp.concatenate(
                [
                    tokens.reshape(B, Q).astype(jnp.float32),
                    logprobs.reshape(B, Q),
                ],
                axis=1,
            )
            packed, census = self._count_row(packed, census)
            # (how many of a row's samples are accepted is the host's to
            # say: a speculative row keeps no entry)
            return kv_cache, kv_swa, replicate(packed), last_tokens, census

        return llmd_verify_step

    def _build_unified(self):
        """Unified single-dispatch step: ONE ragged program for an entire
        window=1 engine step. The host ships a packed token stream
        ``[T]`` plus per-row (start, qlen, kind) metadata; the device
        gathers it into the bucketed ``[B, Q]`` view and runs the SAME
        prefill/ragged-paged-attention forward every other shape family
        uses — chunked-prefill rows, plain decode rows (qlen 1), and
        one-shot verify rows (qlen 1 + draft) side by side, masked by
        ``query_lens`` exactly like the verify family's padding. Long
        prefill chunks arrive pre-split into consecutive sub-rows of the
        same sequence (each layer writes the whole step's KV before
        attention reads, so later sub-rows attend earlier sub-rows'
        fresh KV — the cross-step chunked-prefill invariant, inside one
        program). Sampling gathers an ``[B, S]`` plane of positions
        (verify rows: every draft position; all other rows: the last
        valid position) so prefill-chunk first-tokens and decode/verify
        tokens sample ON DEVICE in the same call, and the whole step
        comes back as one packed ``[B, 2S]`` transfer — one dispatch,
        one coalesced readback, where the split engine pays one per
        program."""
        cfg = self.cfg
        dbo = self.config.parallel.enable_dbo
        replicate = self._replicate_out
        ring = self.swa is not None
        S = self.unified_s

        @functools.partial(
            jax.jit,
            donate_argnums=(1, 2, 4) if ring else (1, 4),
            static_argnames=("B", "Q", "T", "all_greedy"),
        )
        def llmd_unified_step(
            params,
            kv_cache,
            kv_swa,  # ring pool (None unless swa_ring)
            step: jax.Array,  # the step's packed inputs (_put_step)
            last_tokens: jax.Array,  # [token_slots] the tokens sampled last
            census=None,  # [E+2] MoE census accumulator, or None
            B: int = 0,  # rows
            Q: int = 0,  # columns a row
            T: int = 0,  # the stream bucket
            all_greedy: bool = False,
        ):
            self._note_traced("unified", (B, Q))
            f = self._layout(_OP_UNIFIED, B, (Q << 20) | T).unpack(step)
            stream = f["stream"]  # [T] packed token stream
            row_start = f["row_start"]  # [B] row's offset into the stream
            pos0 = f["pos0"]  # [B] absolute position of the row's first token
            qlens = f["qlens"]  # [B] valid token count per row
            kvlens = f["kvlens"]  # [B] kv length after this row's writes
            verify_row = f["kind"] == _KIND_VERIFY  # [B] bool
            page_table = f["page_table"]  # [B, max_pages]
            swa_table = f.get("swa_table")  # [B, max_pages] ring view, or None
            lora_ids = f.get("lora")  # [B] i32 adapter slots, or None
            temperature, top_k, top_p = f["temp"], f["top_k"], f["top_p"]
            seeds = f["seeds"]  # [B, S]
            cols = jnp.arange(Q)
            gidx = jnp.clip(
                row_start[:, None] + cols[None, :], 0, stream.shape[0] - 1
            )
            tokens = jnp.where(
                cols[None, :] < qlens[:, None], stream[gidx], 0
            )
            tokens = self._token_input(
                f, last_tokens, tokens, jnp.arange(B)[:, None],
                cols[None, :] == 0,
            )
            last = jnp.maximum(qlens - 1, 0)
            # Pad columns repeat the last real position (the prefill
            # convention every family shares).
            positions = pos0[:, None] + jnp.minimum(
                cols[None, :], last[:, None]
            )
            inp = StepInput(
                token_ids=tokens,
                positions=positions,
                query_lens=qlens.astype(jnp.int32),
                kv_lens=kvlens.astype(jnp.int32),
                page_table=page_table,
                lora_ids=lora_ids,
                swa_page_table=swa_table,
            )
            hidden, kv_cache, kv_swa, census = self._fwd_hidden(
                params, kv_cache, kv_swa, inp, census, dbo=dbo
            )
            H = hidden.shape[-1]
            scols = jnp.arange(S)
            # Verify rows sample every scored position (the one-shot
            # verify layout); everything else samples its last valid
            # position in column 0 (duplicate pad samples are dropped
            # host-side).
            samp = jnp.where(
                verify_row[:, None],
                jnp.minimum(scols[None, :], last[:, None]),
                last[:, None],
            )
            h = hidden[jnp.arange(B)[:, None], samp]  # [B, S, H]
            logits = llama.compute_logits(params, h.reshape(B * S, H), cfg)
            flat = SamplingInputs(
                temperature=jnp.repeat(temperature, S),
                top_k=jnp.repeat(top_k, S),
                top_p=jnp.repeat(top_p, S),
                seeds=seeds.reshape(B * S),
            )
            tok, logp = sample_tokens(logits, flat, all_greedy)
            packed = jnp.concatenate(
                [
                    tok.reshape(B, S).astype(jnp.float32),
                    logp.reshape(B, S),
                ],
                axis=1,
            )  # [B, 2S]
            packed, census = self._count_row(packed, census)
            last_tokens = self._keep_sampled(
                f, last_tokens, tok.reshape(B, S)[:, 0]
            )
            return kv_cache, kv_swa, replicate(packed), last_tokens, census

        return llmd_unified_step

    def _build_flat(self):
        """Genuinely ragged flattened-token step (`cu_q_lens`): the SAME
        engine step the bucketed unified program runs, but the forward
        iterates the packed ``[T]`` token stream itself. The device
        derives the per-token view from the per-row metadata — token t
        belongs to the row whose ``[row_start, row_start + qlen)`` span
        holds it (``searchsorted`` over the cu_q_lens ends; pad rows
        carry ``row_start = total`` so the boundary array stays
        monotonic), its position is ``pos0[row] + (t - row_start[row])``
        and its causal horizon is ``position + 1`` — so a decode row
        costs ONE token of the stream, a verify row ``1 + its own draft
        length`` (per-row adaptive verify depth: hot-draft rows run deep
        windows while backed-off rows run depth 1 in the same program),
        and nothing pads to a per-row column bucket. KV lands through
        the run-addressed flat write plan (same-page-safe Pallas writes
        on TPU); sampling gathers each row's positions out of the packed
        hidden stream and the step still comes back as ONE ``[B, 2S]``
        transfer."""
        cfg = self.cfg
        replicate = self._replicate_out
        ring = self.swa is not None
        S = self.unified_s

        @functools.partial(
            jax.jit,
            donate_argnums=(1, 2, 4) if ring else (1, 4),
            static_argnames=("T", "all_greedy"),
        )
        def llmd_flat_step(
            params,
            kv_cache,
            kv_swa,  # ring pool (None unless swa_ring)
            step: jax.Array,  # the step's packed inputs (_put_step)
            last_tokens: jax.Array,  # [token_slots] the tokens sampled last
            census=None,  # [E+2] MoE census accumulator, or None
            T: int = 0,  # the stream bucket (sizes the layout)
            all_greedy: bool = False,
        ):
            self._note_traced("flat", (T, 1))
            B = self.flat_rows
            f = self._layout(_OP_FLAT, B, T).unpack(step)
            stream = f["stream"]  # [T] packed token stream
            row_start = f["row_start"]  # [B] cu_q_lens offsets (pad rows: total)
            pos0 = f["pos0"]  # [B] absolute position of the row's first token
            qlens = f["qlens"]  # [B] valid token count per row
            verify_row = f["kind"] == _KIND_VERIFY  # [B] bool
            page_table = f["page_table"]  # [B, max_pages] COMPACT per-row table
            swa_table = f.get("swa_table")  # [B, max_pages] ring view, or None
            state_slots = f.get("state_slots")  # [B] state-pool slots, or None
            lora_ids = f.get("lora")  # [B] i32 adapter slots, or None
            temperature, top_k, top_p = f["temp"], f["top_k"], f["top_p"]
            seeds = f["seeds"]  # [B, S]
            # The flat write plan, [R] each: run slab starts, first in-page
            # slot, token count (0 = pad), physical page in the main pool
            # and (ring) in the ring pool.
            wsrc, woff, wcnt = f["wsrc"], f["woff"], f["wcnt"]
            wphys, wphys_swa = f["wphys"], f.get("wphys_swa")
            t = jnp.arange(T)
            ends = row_start + qlens  # non-decreasing (pad rows = total)
            row_of = jnp.clip(
                jnp.searchsorted(ends, t, side="right"), 0, B - 1
            ).astype(jnp.int32)
            live = t < ends[-1]
            local = t - row_start[row_of]
            positions_t = jnp.where(live, pos0[row_of] + local, 0)
            stream = self._token_input(
                f, last_tokens, stream, row_of, local == 0
            )
            inp = StepInput(
                token_ids=jnp.where(live, stream, 0)[:, None],
                positions=positions_t[:, None],
                query_lens=live.astype(jnp.int32),
                # Per-token causal horizon derived from the packing:
                # position + 1 — the whole causal mask the bucketed
                # layout needed [B, Q] positions for.
                kv_lens=jnp.where(live, positions_t + 1, 0).astype(jnp.int32),
                page_table=page_table,
                lora_ids=(
                    lora_ids[row_of] if lora_ids is not None else None
                ),
                swa_page_table=swa_table,
                token_rows=row_of,
                flat_runs=((wsrc, woff, wcnt), wphys, wphys_swa),
                # The attention's shared-prefix runs, [T] each.
                attn_runs=(
                    (f["run_lead"], f["run_blocks"]) if "run_lead" in f
                    else None
                ),
                # A segment's "starts at position 0" comes from pos0: the
                # payload carries the slots alone.
                state_rows=None if state_slots is None else ssm_ops.state_rows(
                    state_slots, row_start, qlens, pos0, f["kind"], row_of,
                    live,
                ),
            )
            hidden, kv_cache, kv_swa, census = self._fwd_hidden(
                params, kv_cache, kv_swa, inp, census
            )
            H = hidden.shape[-1]
            scols = jnp.arange(S)
            last = jnp.maximum(qlens - 1, 0)
            samp_local = jnp.where(
                verify_row[:, None],
                jnp.minimum(scols[None, :], last[:, None]),
                last[:, None],
            )  # [B, S] offsets within each row
            flat_idx = jnp.clip(row_start[:, None] + samp_local, 0, T - 1)
            h = hidden[flat_idx, 0]  # [B, S, H]
            logits = llama.compute_logits(params, h.reshape(B * S, H), cfg)
            flat_s = SamplingInputs(
                temperature=jnp.repeat(temperature, S),
                top_k=jnp.repeat(top_k, S),
                top_p=jnp.repeat(top_p, S),
                seeds=seeds.reshape(B * S),
            )
            tok, logp = sample_tokens(logits, flat_s, all_greedy)
            packed = jnp.concatenate(
                [
                    tok.reshape(B, S).astype(jnp.float32),
                    logp.reshape(B, S),
                ],
                axis=1,
            )  # [B, 2S]
            packed, census = self._count_row(packed, census)
            last_tokens = self._keep_sampled(
                f, last_tokens, tok.reshape(B, S)[:, 0]
            )
            return kv_cache, kv_swa, replicate(packed), last_tokens, census

        return llmd_flat_step

    def _build_multi(self):
        cfg = self.cfg
        dbo = self.config.parallel.enable_dbo
        replicate = self._replicate_out
        ring = self.swa is not None

        @functools.partial(
            jax.jit,
            donate_argnums=(1, 2, 4) if ring else (1, 4),
            static_argnames=("B", "k_steps", "all_greedy"),
        )
        def llmd_decode_window(
            params,
            kv_cache,
            kv_swa,  # ring pool (None unless swa_ring)
            step: jax.Array,  # the step's packed inputs (_put_step)
            last_tokens: jax.Array,  # [token_slots] the tokens sampled last
            census=None,  # [E+2] MoE census accumulator, or None
            B: int = 0,  # rows
            k_steps: int = 1,
            all_greedy: bool = False,
        ):
            self._note_traced("decode_window", (B, k_steps))
            f = self._layout(_OP_DECODE, B, k_steps).unpack(step)
            first_token = self._token_input(
                f, last_tokens, f["first"], jnp.arange(B), True
            )  # [B]
            start_pos = f["start"]  # [B] position of first_token
            page_table = f["page_table"]  # [B, max_pages]
            swa_table = f.get("swa_table")  # [B, max_pages] ring view, or None
            active = f["active"] != 0  # [B] bool (pad rows False)
            lora_ids = f.get("lora")  # [B] i32 adapter slots, or None
            temperature, top_k, top_p = f["temp"], f["top_k"], f["top_p"]
            seeds = f["seeds"]  # [B, K]

            def body(i, carry):
                kv_cache, kv_swa, census, tok, out_t, out_l = carry
                pos = start_pos + i
                inp = StepInput(
                    token_ids=tok[:, None],
                    positions=pos[:, None],
                    query_lens=jnp.where(active, 1, 0).astype(jnp.int32),
                    kv_lens=jnp.where(active, pos + 1, 0).astype(jnp.int32),
                    page_table=page_table,
                    lora_ids=lora_ids,
                    swa_page_table=swa_table,
                )
                hidden, kv_cache, kv_swa, census = self._fwd_hidden(
                    params, kv_cache, kv_swa, inp, census, dbo=dbo
                )
                logits = llama.compute_logits(params, hidden[:, 0, :], cfg)
                s = SamplingInputs(
                    temperature=temperature,
                    top_k=top_k,
                    top_p=top_p,
                    seeds=jax.lax.dynamic_index_in_dim(
                        seeds, i, axis=1, keepdims=False
                    ),
                )
                nxt, logp = sample_tokens(logits, s, all_greedy)
                out_t = jax.lax.dynamic_update_index_in_dim(out_t, nxt, i, axis=1)
                out_l = jax.lax.dynamic_update_index_in_dim(out_l, logp, i, axis=1)
                return kv_cache, kv_swa, census, nxt, out_t, out_l

            out_t = jnp.zeros((B, k_steps), jnp.int32)
            out_l = jnp.zeros((B, k_steps), jnp.float32)
            kv_cache, kv_swa, census, nxt, out_t, out_l = jax.lax.fori_loop(
                0, k_steps, body,
                (kv_cache, kv_swa, census, first_token, out_t, out_l),
            )
            packed = jnp.concatenate(
                [out_t.astype(jnp.float32), out_l], axis=1
            )  # [B, 2K]
            packed, census = self._count_row(packed, census)
            last_tokens = self._keep_sampled(f, last_tokens, nxt)
            return kv_cache, kv_swa, replicate(packed), last_tokens, census

        return llmd_decode_window

    # ------------------------------------------------------------------ #
    # multi-host KV staging programs (lockstep-dispatched on all procs)

    @functools.cached_property
    def _replicated_gather(self):
        """Gather pages -> CANONICAL heads, output fully replicated: the
        all-gather of the tp-sharded head axis rides ICI, after which the
        leader's host download is a local replica read. Int8 pools
        dequantize in-program to the staging dtype."""
        rep = self.kv_rep
        dt = jnp.dtype(self.staging_dtype) if self.kv_quantized else None

        def gather(kv, ids):
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import dequantize_pages

                d, s = kv[0][:, ids], kv[1][:, ids]
                if rep > 1:
                    d, s = d[:, :, ::rep], s[:, :, ::rep]
                return dequantize_pages(d, s, dt)
            out = kv[:, ids]
            if rep > 1:
                out = out[:, :, ::rep]
            return out

        return jax.jit(gather, out_shardings=self.ctx.replicated)

    @functools.cached_property
    def _replicated_gather_q8(self):
        """Q8-wire gather: float pools quantize in-program; int8 pools
        ship their bytes directly (lossless wrt the pool, half the
        staging bytes, zero quantize work)."""
        rep = self.kv_rep

        def gather(kv, ids):
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import pool_scales_to_wire

                d, s = kv[0][:, ids], kv[1][:, ids]
                if rep > 1:
                    d, s = d[:, :, ::rep], s[:, :, ::rep]
                # Pool scales are f32 ON the f16 grid — the wire's f16
                # form is a lossless cast.
                return d, pool_scales_to_wire(s).astype(jnp.float16)
            out = kv[:, ids]
            if rep > 1:
                out = out[:, :, ::rep]
            return _quantize_rows_q8(out)

        return jax.jit(gather, out_shardings=self.ctx.replicated)

    @functools.cached_property
    def _scatter_canonical(self):
        """Scatter canonical-head bundles into the pool (head expansion
        on device); every process writes its own shards of the result.
        Int8 pools quantize the incoming float rows in-program."""
        rep = self.kv_rep

        def scatter(kv, ids, vals):
            if rep > 1:
                vals = jnp.repeat(vals, rep, axis=2)
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import quantize_pages

                d, s = quantize_pages(vals)
                return (
                    kv[0].at[:, ids].set(d),
                    kv[1].at[:, ids].set(s),
                )
            # Heterogeneous-pool local claims (e.g. bf16 producer -> f32
            # consumer) cast at the write.
            return kv.at[:, ids].set(vals.astype(kv.dtype))

        return jax.jit(scatter, donate_argnums=(0,))

    @functools.cached_property
    def _scatter_q8_direct(self):
        """Scatter a q8-wire bundle (q8 data + wire-layout scales)
        straight into an int8 pool — no dequant/requant round trip."""
        rep = self.kv_rep

        def scatter(kv, ids, d, s_wire):
            from llmd_tpu.ops.quant_kv import wire_scales_to_pool

            s = wire_scales_to_pool(s_wire)  # [L, n, K, page, 2]
            if rep > 1:
                d = jnp.repeat(d, rep, axis=2)
                s = jnp.repeat(s, rep, axis=2)
            return (
                kv[0].at[:, ids].set(d),
                kv[1].at[:, ids].set(s.astype(kv[1].dtype)),
            )

        return jax.jit(scatter, donate_argnums=(0,))

    # ------------------------------------------------------------------ #
    # layer-group staging programs (the v3 group-framed KV transfer:
    # docs/architecture/kv-cache.md "layer-streamed import"). The layer
    # index rides as a TRACED [Lg] array, so one program per (Lg, page
    # count) shape family serves every group offset — not one per l0.

    @functools.cached_property
    def _replicated_gather_group(self):
        """Layer-sliced gather -> canonical heads, fully replicated:
        [Lg, n, K, page, 2D] of layers ``l_ids``."""
        rep = self.kv_rep
        dt = jnp.dtype(self.staging_dtype) if self.kv_quantized else None

        def gather(kv, l_ids, ids):
            li = l_ids[:, None]
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import dequantize_pages

                d, s = kv[0][li, ids[None, :]], kv[1][li, ids[None, :]]
                if rep > 1:
                    d, s = d[:, :, ::rep], s[:, :, ::rep]
                return dequantize_pages(d, s, dt)
            out = kv[li, ids[None, :]]
            if rep > 1:
                out = out[:, :, ::rep]
            return out

        return jax.jit(gather, out_shardings=self.ctx.replicated)

    @functools.cached_property
    def _replicated_gather_group_q8(self):
        """Layer-sliced q8-wire gather (the grouped twin of
        :attr:`_replicated_gather_q8`)."""
        rep = self.kv_rep

        def gather(kv, l_ids, ids):
            li = l_ids[:, None]
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import pool_scales_to_wire

                d, s = kv[0][li, ids[None, :]], kv[1][li, ids[None, :]]
                if rep > 1:
                    d, s = d[:, :, ::rep], s[:, :, ::rep]
                return d, pool_scales_to_wire(s).astype(jnp.float16)
            out = kv[li, ids[None, :]]
            if rep > 1:
                out = out[:, :, ::rep]
            return _quantize_rows_q8(out)

        return jax.jit(gather, out_shardings=self.ctx.replicated)

    @functools.cached_property
    def _scatter_canonical_group(self):
        """Layer-sliced scatter of a canonical [Lg, n, ...] bundle into
        pool layers ``l_ids`` (the grouped twin of
        :attr:`_scatter_canonical`). Int8 pools quantize in-program."""
        rep = self.kv_rep

        def scatter(kv, l_ids, ids, vals):
            li = l_ids[:, None]
            if rep > 1:
                vals = jnp.repeat(vals, rep, axis=2)
            if isinstance(kv, tuple):
                from llmd_tpu.ops.quant_kv import quantize_pages

                d, s = quantize_pages(vals)
                return (
                    kv[0].at[li, ids[None, :]].set(d),
                    kv[1].at[li, ids[None, :]].set(s),
                )
            return kv.at[li, ids[None, :]].set(vals.astype(kv.dtype))

        return jax.jit(scatter, donate_argnums=(0,))

    @functools.cached_property
    def _scatter_q8_direct_group(self):
        """Layer-sliced q8-wire scatter into an int8 pool (the grouped
        twin of :attr:`_scatter_q8_direct`)."""
        rep = self.kv_rep

        def scatter(kv, l_ids, ids, d, s_wire):
            from llmd_tpu.ops.quant_kv import wire_scales_to_pool

            li = l_ids[:, None]
            s = wire_scales_to_pool(s_wire)  # [Lg, n, K, page, 2]
            if rep > 1:
                d = jnp.repeat(d, rep, axis=2)
                s = jnp.repeat(s, rep, axis=2)
            return (
                kv[0].at[li, ids[None, :]].set(d),
                kv[1].at[li, ids[None, :]].set(s.astype(kv[1].dtype)),
            )

        return jax.jit(scatter, donate_argnums=(0,))

    def _pool(self, swa: bool):
        """Select the staging target: the main pool or the SWA ring pool.
        The staging programs themselves are pool-agnostic (the pool is an
        argument), so both pools share them."""
        if not swa and isinstance(self.kv_cache, ops.IndexedPool):
            raise RuntimeError(
                "page staging moves K and V only; it would drop the indexer "
                "keys of a sparse-attention pool"
            )
        return self.kv_swa if swa else self.kv_cache

    def _pool_data(self, swa: bool) -> jax.Array:
        kv = self._pool(swa)
        return kv[0] if isinstance(kv, tuple) else kv

    @functools.cached_property
    def _copy_pool_pages(self):
        """Device-to-device page copy within one pool (hybrid-APC
        sliding-section capture/seed; no host bytes move)."""

        def copy(kv, src, dst):
            # Every leaf of a pool (an int8 pool's scales, a state pool's
            # conv state) carries its page or slot ids on axis 1. Page by
            # page, a slice read and a slice written into the donated pool:
            # in place whatever the pool's size. One gather of the pages
            # (``a.at[:, dst].set(a[:, src])``) is re-laid by the chip's
            # compiler through a copy of the WHOLE pool once a ring pool has
            # 21 layers of 4,352 pages (2.79 GiB of temporaries beside 14 GiB
            # of weights and pools: the program did not load, PR 51). The
            # callers' pages are disjoint (a ring's against a section's), so
            # read-then-write a page is read-all-then-write-all.
            def leaf(a):
                def page(i, a):
                    one = jax.lax.dynamic_slice_in_dim(a, src[i], 1, axis=1)
                    return jax.lax.dynamic_update_slice_in_dim(a, one, dst[i], axis=1)

                return jax.lax.fori_loop(0, src.shape[0], page, a)

            return jax.tree.map(leaf, kv)

        return jax.jit(copy, donate_argnums=(0,))

    def copy_pages_on_device(
        self, src_ids: list[int], dst_ids: list[int], swa: bool = False
    ) -> None:
        """Copy pool pages src -> dst on device (lockstep in multi-host:
        a plain SPMD program every process mirrors)."""
        arrays = {
            "src": np.asarray(src_ids, np.int32),
            "dst": np.asarray(dst_ids, np.int32),
        }
        if self._multihost:
            with self._dispatch_lock:
                arrays = self._sync_locked(
                    _OP_KV_COPY, len(src_ids), int(swa), False, arrays
                )
                self._exec_kv_copy(arrays, swa)
            return
        self._exec_kv_copy(arrays, swa)

    def _exec_kv_copy(self, arrays: dict, swa: bool) -> None:
        out = self._copy_pool_pages(
            self._pool(swa), jnp.asarray(arrays["src"]),
            jnp.asarray(arrays["dst"]),
        )
        if swa:
            self.kv_swa = out
        else:
            self.kv_cache = out

    def _exec_kv_gather(self, arrays: dict, q8: bool, swa: bool = False):
        fn = self._replicated_gather_q8 if q8 else self._replicated_gather
        return fn(self._pool(swa), jnp.asarray(arrays["ids"]))

    def _exec_kv_scatter(self, arrays: dict, n: int, swa: bool = False) -> None:
        data = self._pool_data(swa)
        Kc = data.shape[2] // self.kv_rep
        shape = (data.shape[0], n, Kc, self.page, data.shape[4])
        vals = np.frombuffer(
            np.ascontiguousarray(arrays["vals_u8"]).data,
            dtype=self.staging_dtype,
        ).reshape(shape)
        out = self._scatter_canonical(
            self._pool(swa), jnp.asarray(arrays["ids"]), jnp.asarray(vals)
        )
        if swa:
            self.kv_swa = out
        else:
            self.kv_cache = out

    def _exec_kv_scatter_q8(self, arrays: dict, swa: bool = False) -> None:
        ids = jnp.asarray(arrays["ids"])
        q8 = jnp.asarray(arrays["q8"])
        scales = jnp.asarray(arrays["scales"])
        if self.kv_quantized:
            out = self._scatter_q8_direct(self._pool(swa), ids, q8, scales)
        else:
            vals = _dequantize_rows_q8(q8, scales, self.staging_dtype_name)
            out = self._scatter_canonical(self._pool(swa), ids, vals)
        if swa:
            self.kv_swa = out
        else:
            self.kv_cache = out

    def _kv_gather_lockstep(self, ids: np.ndarray, q8: bool, swa: bool = False):
        """Leader leg of a multi-host page gather: broadcast the op so
        every process dispatches the same program; return the (replicated)
        result. Any leader thread may call — the dispatch lock keeps each
        broadcast+dispatch pair atomic in the totally ordered op stream.
        The header's 4th slot carries the pool selector (main vs SWA
        ring) for KV ops."""
        assert dist.is_leader(), "KV staging ops originate on the leader"
        with self._dispatch_lock:
            arrays = self._sync_locked(
                _OP_KV_GATHER, len(ids), int(q8), bool(swa), {"ids": ids}
            )
            return self._exec_kv_gather(arrays, q8, swa)

    # ------------------------------------------------------------------ #
    # host-side input prep

    @staticmethod
    def _overwrite_seeded_rows(
        seeds: np.ndarray, seqs: list[ScheduledSeq], K: int
    ) -> None:
        """Deterministic per (request seed, output index): resubmitting
        the same seeded request reproduces its tokens regardless of
        batch-mates or window size. ``sampler.spec_seed`` is the ONE
        derivation every dispatch path uses — prefill, fused decode
        windows, and the one-shot speculative verify step apply it here
        on host — or seeded speculative acceptance silently loses its
        byte-parity guarantee."""
        for i, s in enumerate(seqs):
            sp = s.request.sampling
            if sp.seed is not None:
                pos = s.request.num_dispatched_outputs
                for j in range(K):
                    seeds[i, j] = np.uint32(spec_seed(sp.seed, pos + j))

    @staticmethod
    def _sampling_knobs(seqs: list[ScheduledSeq], B: int):
        """(temp, top_k, top_p) rows for a dispatch — shared by every
        path that stages sampling inputs. Seeds are deliberately NOT
        here: they come from the stateful rng, which must advance in
        dispatch order only (see stage_decode)."""
        temp = np.zeros(B, np.float32)
        top_k = np.zeros(B, np.int32)
        top_p = np.ones(B, np.float32)
        for i, s in enumerate(seqs):
            sp = s.request.sampling
            temp[i] = 0.0 if sp.greedy else sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
        return temp, top_k, top_p

    def _sampling_arrays(self, seqs: list[ScheduledSeq], B: int, K: int = 1):
        temp, top_k, top_p = self._sampling_knobs(seqs, B)
        seeds = self._np_rng.integers(0, 2**32, size=(B, K), dtype=np.uint32)
        self._overwrite_seeded_rows(seeds, seqs, K)
        return temp, top_k, top_p, seeds

    def _token_slot(self, seq: ScheduledSeq) -> int:
        """The ``tok_slot`` of the row that samples for ``seq``: its
        request's entry of ``last_tokens`` where the sample is the
        request's next token for certain (a decode row; a chunk that
        completes the prompt), else none (a chunk inside the prompt, a
        speculative row, a request no scheduler admitted)."""
        req = seq.request
        if seq.draft_tokens is not None or req.token_slot < 0:
            return self.token_slots
        if seq.start_pos + seq.num_tokens < req.num_prompt_tokens:
            return self.token_slots
        return req.token_slot

    def _lora_array(self, seqs: list[ScheduledSeq], B: int) -> np.ndarray:
        """[B] adapter slots (pad rows 0 = base model) for the payload."""
        ids = np.zeros(B, np.int32)
        for i, s in enumerate(seqs):
            ids[i] = s.request.lora_id
        return ids

    def _require_single_host(self, what: str) -> None:
        """Paths not mirrored to followers must refuse loudly in a
        multi-host world: a leader-only device program whose shardings
        span follower-owned devices would deadlock the whole group."""
        if self._multihost:
            raise NotImplementedError(
                f"{what} is not supported in multi-host mode (the "
                "prefill/decode serving steps and the KV staging ops are "
                "broadcast to follower processes; see deploy/guides/"
                "wide-ep-lws/README.md scope notes)"
            )

    def _page_table(self, seqs: list[ScheduledSeq], B: int) -> np.ndarray:
        pt = np.zeros((B, self.max_pages), np.int32)
        for i, s in enumerate(seqs):
            ids = s.request.block_ids
            pt[i, : len(ids)] = ids
        return pt

    def _swa_table(self, seqs: list[ScheduledSeq], B: int) -> np.ndarray:
        """Ring-view table for sliding layers: logical page l of sequence
        i maps to ring[l % R]. Same [B, max_pages] shape as the main table
        so every kernel path is unchanged; the repeats past the window are
        exactly the pages the window-skip never reads. Rows are immutable
        once a sequence's ring is allocated, so they memoize on the
        request (scheduler._release invalidates)."""
        pt = np.zeros((B, self.max_pages), np.int32)
        for i, s in enumerate(seqs):
            req = s.request
            ring = req.swa_block_ids
            if not ring:
                continue
            row = req.swa_table_row
            if row is None or len(row) != self.max_pages:
                row = np.asarray(ring, np.int32)[
                    np.arange(self.max_pages) % len(ring)
                ]
                req.swa_table_row = row
            pt[i] = row
        return pt

    # ------------------------------------------------------------------ #
    # multi-host lockstep dispatch (leader broadcasts, followers mirror)

    def _payload_spec(self, op: int, B: int, QK: int):
        """(name, shape, dtype) tuple layout for one op's array payload —
        the contract both sides of the broadcast derive independently.

        KV ops reuse the header slots: B carries the page count and QK the
        q8 flag (gather). Scatter payload geometry derives from the pool
        config both sides share."""
        if op == _OP_HEARTBEAT:
            # Liveness tick only; a 1-slot dummy keeps the payload leg's
            # pytree non-empty (both sides derive the same shape).
            return [("hb", (1,), np.int32)]
        if op == _OP_KV_GATHER:
            return [("ids", (B,), np.int32)]
        if op == _OP_KV_COPY:
            return [("src", (B,), np.int32), ("dst", (B,), np.int32)]
        if op == _OP_EMBED:
            return [
                ("tokens", (B, QK), np.int32),
                ("positions", (B, QK), np.int32),
                ("qlens", (B,), np.int32),
            ]
        if op == _OP_LORA:
            # B slot = pair-presence bitmask (1: q pair, 2: v pair); the
            # factor shapes derive from the shared params structure.
            layers = self.params["layers"]
            spec = []
            for bit, a, b in ((1, "la_q", "lb_q"), (2, "la_v", "lb_v")):
                if B & bit:
                    for k in (a, b):
                        s = layers[k].shape
                        spec.append((k, (s[0], *s[2:]), np.float32))
            return spec
        if op == _OP_KV_SCATTER:
            # QK carries the pool selector (main vs SWA ring): the two
            # pools have different layer counts, so the payload geometry
            # both sides derive depends on it.
            data = self._pool_data(bool(QK))
            Kc = data.shape[2] // self.kv_rep
            nbytes = (
                data.shape[0] * B * Kc * self.page
                * data.shape[4] * self.staging_dtype.itemsize
            )
            return [("ids", (B,), np.int32), ("vals_u8", (nbytes,), np.uint8)]
        if op == _OP_KV_SCATTER_Q8:
            # Same header contract as _OP_KV_SCATTER (B = padded page
            # count, QK = pool selector); the payload is the q8 wire
            # form — i8 rows + f16 per-(token, head) K/V-half scales.
            data = self._pool_data(bool(QK))
            Kc = data.shape[2] // self.kv_rep
            L, D2 = data.shape[0], data.shape[4]
            return [
                ("ids", (B,), np.int32),
                ("q8", (L, B, Kc, self.page, D2), np.int8),
                ("scales", (L, B, Kc, self.page, 2), np.float16),
            ]
        # A step program's inputs: the description its device buffer is
        # laid out by as well (engine/payload.py).
        return payload.step_fields(
            _STEP_KINDS[op], B, QK, max_pages=self.max_pages, page=self.page,
            sample_cols=self.unified_s,
            ring=self.swa is not None and not self.state_pool,
            lora=bool(self.cfg.num_lora_adapters), state=self.state_pool,
            runs=self._plans_runs,
        )

    def _layout(self, op: int, B: int, QK: int) -> payload.PayloadLayout:
        """Where a step program's inputs lie in its one device buffer: a
        function of the program kind and its shape, the same on the side
        that packs (``_put_step``) and in the program's trace."""
        key = (op, B, QK)
        layout = self._layouts.get(key)
        if layout is None:
            layout = self._layouts[key] = payload.PayloadLayout(
                self._payload_spec(op, B, QK)
            )
        return layout

    def _put_step(self, op: int, B: int, QK: int, arrays: dict) -> jax.Array:
        """A step's host inputs onto the device: packed through the
        program's layout into ONE buffer, ONE transfer (the per-array
        hand-over was 10-17 of them, ~0.27 ms each on a v5e host)."""
        buf = self._layout(op, B, QK).pack(arrays)
        self.step_h2d_transfers_total += 1
        self.step_h2d_bytes_total += buf.nbytes
        return jnp.asarray(buf)

    def _bounded(self, fn, what: str):
        """Run one lockstep collective leg with a bounded wait.

        ``broadcast_one_to_all`` blocks until EVERY process participates;
        a dead/wedged peer turns that into an infinite hang that no
        watchdog above can attribute. The collective runs on a dedicated
        single worker thread and the caller waits at most
        ``lockstep_timeout_s`` — on expiry the group is declared dead
        with a loud RuntimeError (the step fails fast; the serving
        watchdog then 503s /health and terminates streams). The worker
        thread stays parked in the dead collective, which is fine: the
        process is about to be restarted by the platform anyway.

        Startup exemption: the first collective of a process runs
        UNBOUNDED (cold-compile/weight-load skew legitimately exceeds
        any liveness budget; the startup probe owns that phase), and the
        wait arms once one collective has completed."""
        timeout = self.lockstep_timeout_s
        if not timeout or timeout <= 0:
            return fn()
        if not self._lockstep_warmed:
            out = fn()
            self._lockstep_warmed = True
            return out
        if self._lockstep_compile_grace:
            # The previous dispatch opened a new shape family: the peer
            # may be inside a legitimately-long jit compile of it, not
            # dead. One unbounded pass, then the bound re-arms.
            self._lockstep_compile_grace = False
            return fn()
        if self._lockstep_pool is None:
            self._lockstep_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="llmd-lockstep"
            )
        fut = self._lockstep_pool.submit(fn)
        try:
            return fut.result(timeout)
        except concurrent.futures.TimeoutError:
            # llmd: allow(concurrency) -- one-way latch (False->True only): leader legs hold the dispatch lock already; the follower mirror loop is its process's sole lockstep thread
            self._stopped = True  # no further broadcasts into a dead group
            raise RuntimeError(
                f"lockstep {what} did not complete within {timeout:.0f}s: "
                "a peer process is dead or wedged (set "
                "LLMD_LOCKSTEP_TIMEOUT_S to tune; 0 disables)"
            ) from None

    def _heartbeat_loop(self) -> None:
        """Leader-side liveness ticks: when no real op has been broadcast
        for a third of the lockstep budget, send _OP_HEARTBEAT so idle
        followers' bounded header wait keeps getting fed."""
        period = max(self.lockstep_timeout_s / 3.0, 1.0)
        while not self._hb_stop.wait(period / 2):
            # llmd: allow(concurrency) -- double-checked peek: re-read under the dispatch lock below before broadcasting; a stale False only costs one loop turn
            if self._stopped:
                return
            if not self._lockstep_warmed:
                continue  # startup phase: followers wait unbounded anyway
            if time.monotonic() - self._last_broadcast < period:
                continue
            try:
                with self._dispatch_lock:
                    if self._stopped:
                        return
                    self._sync_locked(
                        _OP_HEARTBEAT, 0, 0, False,
                        {"hb": np.zeros(1, np.int32)},
                    )
            except RuntimeError:
                log.exception("lockstep heartbeat failed; group is dead")
                return

    def _sync_locked(self, op: int, B: int, QK: int, greedy: bool, arrays: dict) -> dict:
        """Leader leg: broadcast header + payload; identity single-host."""
        if not self._multihost:
            return arrays
        if self._stopped:
            raise RuntimeError(
                "lockstep dispatch after stop_followers: the follower "
                "processes have exited and a broadcast would hang"
            )
        from jax.experimental import multihost_utils as mhu

        spec = self._payload_spec(op, B, QK)
        staged = tuple(
            np.ascontiguousarray(arrays[name]).astype(dt, copy=False)
            for name, _, dt in spec
        )

        def _broadcast():
            # Injection site: a stalled collective is indistinguishable
            # from a dead peer — exactly what the bounded wait bounds.
            from llmd_tpu import faults as _faults

            _faults.delay("lockstep.sync.stall")
            mhu.broadcast_one_to_all(
                np.asarray([op, B, QK, int(greedy)], np.int32),
                is_source=True,
            )
            return mhu.broadcast_one_to_all(staged, is_source=True)

        payload = self._bounded(_broadcast, f"broadcast of op {op}")
        self._last_broadcast = time.monotonic()
        if op != _OP_HEARTBEAT:
            shape_key = (op, B, QK, bool(greedy))
            if shape_key not in self._lockstep_seen_shapes:
                self._lockstep_seen_shapes.add(shape_key)
                # Followers compile this family during their exec of
                # this dispatch; the next broadcast must not bound it.
                self._lockstep_compile_grace = True
        return {name: arr for (name, _, _), arr in zip(spec, payload)}

    def follower_loop(self) -> None:
        """Run on every non-leader process: mirror the leader's dispatches
        until a stop is broadcast. Blocks for the life of the deployment."""
        from jax.experimental import multihost_utils as mhu

        assert self._multihost and not dist.is_leader(), (
            "follower_loop is for non-leader processes of a multi-host world"
        )
        # With the leader heartbeating every timeout/3 when idle, a
        # header wait past the full budget means the leader is dead —
        # the follower raises loudly instead of hanging forever. The
        # payload leg after a header is bounded the same way (a leader
        # dying mid-broadcast must not wedge the group).
        while True:
            hdr = self._bounded(
                lambda: mhu.broadcast_one_to_all(
                    np.zeros(4, np.int32), is_source=False
                ),
                "header wait (leader liveness)",
            )
            op, B, QK, greedy = (int(v) for v in np.asarray(hdr))
            if op == _OP_STOP:
                return
            spec = self._payload_spec(op, B, QK)
            zeros = tuple(np.zeros(shp, dt) for _, shp, dt in spec)
            payload = self._bounded(
                lambda: mhu.broadcast_one_to_all(zeros, is_source=False),
                f"payload wait for op {op}",
            )
            arrays = {name: arr for (name, _, _), arr in zip(spec, payload)}
            if op == _OP_HEARTBEAT:
                continue  # liveness tick only; nothing to dispatch
            shape_key = (op, B, QK, bool(greedy))
            if shape_key not in self._lockstep_seen_shapes:
                self._lockstep_seen_shapes.add(shape_key)
                # The leader compiles this family during its own exec;
                # the next header wait must not bound that compile.
                self._lockstep_compile_grace = True
            if op == _OP_PREFILL:
                self._exec_prefill(arrays, bool(greedy))
            elif op == _OP_VERIFY:
                self._exec_verify(arrays, bool(greedy))
            elif op == _OP_UNIFIED:
                # QK packs (Q_bucket << 20) | T_bucket; the exec only
                # needs the static per-row column count.
                self._exec_unified(arrays, QK >> 20, bool(greedy))
            elif op == _OP_FLAT:
                self._exec_flat(arrays, bool(greedy))
            elif op == _OP_KV_GATHER:
                # Participate in the SPMD gather (the all-gather collective
                # needs every process); the replicated result is dropped —
                # only the leader stages it to the network. ``greedy``
                # carries the pool selector for KV ops.
                self._exec_kv_gather(arrays, bool(QK), bool(greedy))
            elif op == _OP_KV_SCATTER:
                self._exec_kv_scatter(arrays, B, bool(QK))
            elif op == _OP_KV_SCATTER_Q8:
                self._exec_kv_scatter_q8(arrays, bool(QK))
            elif op == _OP_KV_COPY:
                self._exec_kv_copy(arrays, bool(QK))
            elif op == _OP_EMBED:
                # greedy slot carries the lora id; the replicated pooled
                # output is only read on the leader.
                self._exec_embed(arrays, greedy)
            elif op == _OP_LORA:
                self._exec_lora(arrays, QK)
            elif op == _OP_DECODE:
                self._exec_decode(arrays, QK, bool(greedy))
            else:
                # An unknown opcode means leader and follower disagree on
                # the dispatch protocol (e.g. an opcode added without a
                # follower arm): the follower would mirror the WRONG
                # program and desynchronize the SPMD collective stream.
                # Crash loudly instead of hanging the whole group.
                raise RuntimeError(
                    f"follower received unknown lockstep opcode {op}; "
                    "leader and follower builds disagree on the dispatch "
                    "protocol"
                )

    def stop_followers(self) -> None:
        if self._multihost and dist.is_leader():
            from jax.experimental import multihost_utils as mhu

            with self._dispatch_lock:
                if self._stopped:
                    return
                self._stopped = True
                self._hb_stop.set()
                mhu.broadcast_one_to_all(
                    np.asarray([_OP_STOP, 0, 0, 0], np.int32), is_source=True
                )

    def _run_step(
        self, program, op: int, rows: int, qk: int, arrays: dict,
        step: jax.Array | None = None, **statics,
    ) -> jax.Array:
        """One step program: its inputs onto the device as one buffer,
        then the jitted call.

        A shape's FIRST call (trace, lowering, then a compile or a cache
        read: seconds of host work that build ~50k tracers and equations,
        all of them cyclic garbage when it returns) runs with the cyclic
        collector off and ends in one young collection. Left on, lowering
        the shapes warmed next reads ~0.3 s slower each once a step also
        allocates its payload buffer: the 10 s of a 32-bucket ladder that
        refused PR 34 for ``setup_s``. Measured on the chip, not explained
        to the bottom: the cost follows the state of the process's heap
        and collector, not the program lowered (PERF.md section 6, PR
        35). A host that runs with the collector off
        keeps it off; a warmed shape never comes here twice."""
        if step is None:  # (else: put ahead, ``prepare_staged``)
            step = self._put_step(op, rows, qk, arrays)
        key = (program, rows, qk, statics["all_greedy"])
        first = key not in self._called
        self._called.add(key)
        first = first and gc.isenabled()
        if first:
            gc.disable()
        try:
            (
                self.kv_cache, self.kv_swa, packed, self.last_tokens,
                self._moe_census,
            ) = program(
                self.params, self.kv_cache, self.kv_swa, step,
                self.last_tokens, census=self._moe_census, **statics,
            )
        finally:
            if first:
                gc.enable()
                gc.collect(0)
        return packed

    def _exec_prefill(self, arrays: dict, all_greedy: bool) -> jax.Array:
        # Program selection is shape-deterministic (Q rides the lockstep
        # broadcast), so leader and followers always pick the same family.
        B, Q = arrays["tokens"].shape
        fwd = self._forward
        if (
            self._forward_cp is not None
            and Q % self.cp_prefill == 0
            and Q >= max(self.cp_min_tokens, self.cp_prefill)
        ):
            fwd = self._forward_cp
            self.cp_ring_steps_total += self.cp_prefill
        return self._run_step(
            fwd, _OP_PREFILL, B, Q, arrays, B=B, Q=Q, all_greedy=all_greedy
        )

    def _exec_verify(self, arrays: dict, all_greedy: bool) -> jax.Array:
        B, Q = arrays["tokens"].shape
        return self._run_step(
            self._verify, _OP_VERIFY, B, Q, arrays,
            B=B, Q=Q, all_greedy=all_greedy,
        )

    def _exec_unified(
        self, arrays: dict, Q: int, all_greedy: bool, step=None
    ) -> jax.Array:
        B, T = arrays["row_start"].shape[0], arrays["stream"].shape[0]
        return self._run_step(
            self._unified, _OP_UNIFIED, B, (Q << 20) | T, arrays, step,
            B=B, Q=Q, T=T, all_greedy=all_greedy,
        )

    def _exec_flat(self, arrays: dict, all_greedy: bool, step=None) -> jax.Array:
        T = arrays["stream"].shape[0]
        return self._run_step(
            self._flat, _OP_FLAT, self.flat_rows, T, arrays, step,
            T=T, all_greedy=all_greedy,
        )

    def _exec_decode(
        self, arrays: dict, K: int, all_greedy: bool, step=None
    ) -> jax.Array:
        B = arrays["first"].shape[0]
        return self._run_step(
            self._multi, _OP_DECODE, B, K, arrays, step,
            B=B, k_steps=K, all_greedy=all_greedy,
        )

    # ------------------------------------------------------------------ #
    # KV page staging (the HBM<->host leg of the P/D transfer path;
    # reference TPUConnectorHMA host-memory-assisted pattern)

    def snapshot_pages_device(
        self,
        page_ids: list[int],
        pad_to: int,
        layers: tuple[int, int] | None = None,
    ) -> jax.Array:
        """On-device snapshot of pages (padded to ``pad_to`` by repeating
        the last id): [L, pad_to, K, page, 2D] in CANONICAL heads.

        Returns immediately (async dispatch) with an INDEPENDENT device
        buffer — the engine may donate/mutate the pool right after; jax
        sequences the enqueued gather before any later pool write. The
        blocking host download happens later via ``download_pages`` on a
        staging thread, off the engine thread and off the TTFT path.

        ``layers=(l0, Lg)`` snapshots only that layer slice ([Lg, ...]) —
        the v3 group-framed transfer's per-layer-group export unit
        (single-host only; multi-host producers stay on the monolithic
        lockstep gather).

        Multi-host: the gather is lockstep-broadcast so every process
        dispatches the same SPMD program; the output is fully replicated
        (head-axis all-gather over ICI), so the later download is a local
        replica read on the leader.
        """
        ids = _padded_ids(page_ids, pad_to)
        if self._multihost:
            assert layers is None, "layer-group staging is single-host only"
            return self._kv_gather_lockstep(ids, q8=False)
        # Canonical transfer format keeps the ORIGINAL heads (peers with
        # different tp interoperate byte-exact); int8 pools dequantize
        # in-program to the staging dtype.
        if layers is not None:
            l0, lg = layers
            return self._replicated_gather_group(
                self.kv_cache,
                jnp.arange(l0, l0 + lg, dtype=jnp.int32),
                jnp.asarray(ids),
            )
        return self._replicated_gather(self.kv_cache, jnp.asarray(ids))

    def snapshot_swa_pages_device(self, page_ids: list[int], pad_to: int) -> jax.Array:
        """On-device snapshot of SWA RING pages (sliding-layer pool):
        [L_swa, pad_to, K, page, 2D] canonical heads, dequantized to the
        staging dtype for int8 pools. Same async-dispatch contract as
        snapshot_pages_device; the P/D export of a ring engine ships the
        trailing in-window ring pages through this."""
        assert self.swa is not None, "no SWA ring pool on this runner"
        ids = _padded_ids(page_ids, pad_to)
        if self._multihost:
            return self._kv_gather_lockstep(ids, q8=False, swa=True)
        return self._replicated_gather(self.kv_swa, jnp.asarray(ids))

    def snapshot_pages_device_q8(
        self,
        page_ids: list[int],
        pad_to: int,
        layers: tuple[int, int] | None = None,
    ) -> tuple[jax.Array, jax.Array]:
        """INT8-quantized snapshot for the transfer plane: per-(token,
        head)-row symmetric int8 + f16 scales, computed ON DEVICE so the
        HBM -> host staging moves HALF the bytes. Returns (q8, scales)
        with q8 [L, pad_to, K, page, 2D] i8 and scales
        [L, pad_to, K, page, 2] f16 (separate K/V half scales). Opt-in
        and lossy (~0.4% per-half rel-err) for FLOAT pools; for int8
        pools the pool bytes ship directly (lossless wrt the pool, no
        quantize work). The default transfer dtype stays pool-exact."""
        ids = _padded_ids(page_ids, pad_to)
        if self._multihost:
            assert layers is None, "layer-group staging is single-host only"
            return self._kv_gather_lockstep(ids, q8=True)
        if layers is not None:
            l0, lg = layers
            return self._replicated_gather_group_q8(
                self.kv_cache,
                jnp.arange(l0, l0 + lg, dtype=jnp.int32),
                jnp.asarray(ids),
            )
        return self._replicated_gather_q8(self.kv_cache, jnp.asarray(ids))

    @staticmethod
    def download_pages(snapshot: jax.Array) -> np.ndarray:
        """Blocking HBM -> host download of a snapshot (staging thread).

        Multi-host snapshots are fully replicated global arrays: the read
        is a local replica fetch (no collective, safe off-thread)."""
        if isinstance(snapshot, jax.Array) and not snapshot.is_fully_addressable:
            return np.ascontiguousarray(snapshot.addressable_shards[0].data)
        return np.ascontiguousarray(jax.device_get(snapshot))

    def upload_pages_device(self, pages: np.ndarray) -> jax.Array:
        """Async host -> HBM upload of a canonical bundle (fetch thread:
        creates an independent device array, touches no engine state, so
        the upload overlaps later pulls and the producer's own staging)."""
        return jnp.asarray(pages, dtype=self.staging_dtype)

    def upload_pages_device_q8(self, q8: np.ndarray, scales: np.ndarray):
        """Upload an int8-quantized bundle (half the host -> HBM bytes).

        Float pools dequantize ON DEVICE into the pool dtype; int8 pools
        keep the wire form — (q8, wire scales) scatter straight into the
        pool with no dequant/requant round trip."""
        if self.kv_quantized:
            return (jnp.asarray(q8), jnp.asarray(scales))
        return _dequantize_rows_q8(
            jnp.asarray(q8), jnp.asarray(scales), self.staging_dtype_name
        )

    def scatter_pages_from_device(
        self,
        page_ids: list[int],
        vals,
        swa: bool = False,
        layers: tuple[int, int] | None = None,
    ) -> None:
        """Device -> pool scatter of an already-uploaded chunk (head
        expansion device-side). ``vals`` is a float bundle, or a
        (q8, wire scales) pair — int8 pools scatter the pair directly;
        float pools dequantize on device first (the local fast path hands
        q8 device snapshots to any consumer pool dtype). ``swa`` targets
        the SWA ring pool; ``layers=(l0, Lg)`` writes only that layer
        slice (the v3 group-streamed import).

        Thread-safe: the whole pool read-modify-write runs under the
        dispatch lock, so the streamed import's FETCH-thread scatters
        interleave with (never tear) the engine thread's step dispatches
        — the same discipline the multi-host streamed path rides."""
        self._require_single_host("scatter_pages_from_device (P/D staging)")
        # Device chunks may come from ANOTHER engine's mesh (the local
        # fast path claims the producer's snapshots; e.g. a tp=1
        # producer feeding a tp=8 consumer): re-place them replicated on
        # THIS runner's mesh so the donated-pool scatter sees consistent
        # devices.
        place = lambda x: jax.device_put(x, self.ctx.replicated)  # noqa: E731
        ids = place(np.asarray(page_ids, np.int32))
        l_ids = (
            None if layers is None
            else place(
                np.arange(layers[0], layers[0] + layers[1], dtype=np.int32)
            )
        )
        with self._dispatch_lock:
            if isinstance(vals, tuple):
                if self.kv_quantized:
                    if l_ids is not None:
                        out = self._scatter_q8_direct_group(
                            self._pool(swa), l_ids, ids,
                            place(vals[0]), place(vals[1]),
                        )
                    else:
                        out = self._scatter_q8_direct(
                            self._pool(swa), ids, place(vals[0]), place(vals[1])
                        )
                    if swa:
                        self.kv_swa = out
                    else:
                        self.kv_cache = out
                    return
                vals = _dequantize_rows_q8(
                    vals[0], vals[1], self.staging_dtype_name
                )
            if l_ids is not None:
                out = self._scatter_canonical_group(
                    self._pool(swa), l_ids, ids, place(vals)
                )
            else:
                out = self._scatter_canonical(self._pool(swa), ids, place(vals))
            if swa:
                self.kv_swa = out
            else:
                self.kv_cache = out

    def gather_pages(self, page_ids: list[int]) -> np.ndarray:
        """Stage pages HBM -> host: returns [L, n, K, page, 2D] ndarray.

        Page count is padded to a bucket (ids repeat the last page) so XLA
        compiles one gather per bucket, not per transfer size.
        """
        n = len(page_ids)
        bucket = pad_to_bucket(n, _buckets(max(self.config.cache.num_blocks, n)))
        ids = _padded_ids(page_ids, bucket)
        # Canonical (original-heads, dequantized) bundle either way:
        # replicated copies are a local layout detail, and peers with
        # different tp/pool-dtype configs must interoperate.
        if self._multihost:
            snap = self._kv_gather_lockstep(ids, q8=False)
        else:
            snap = self._replicated_gather(self.kv_cache, jnp.asarray(ids))
        return np.ascontiguousarray(self.download_pages(snap)[:, :n])

    def scatter_pages(
        self,
        page_ids: list[int],
        pages: np.ndarray,
        swa: bool = False,
        layers: tuple[int, int] | None = None,
    ) -> None:
        """Stage pages host -> HBM into the given physical page slots
        (``swa`` targets the SWA ring pool; ``layers=(l0, Lg)`` writes
        only that layer slice of the pool — the v3 group-streamed
        import's per-cell write, single-host only).

        Pads the page count up to a bucket by repeating the last (id, value)
        pair — a duplicate scatter of identical values is idempotent — so
        XLA compiles one scatter program per bucket, not per transfer size.

        Thread-safe on the single-host path: the pool read-modify-write
        holds the dispatch lock, so streamed-import fetch threads and the
        engine step thread interleave safely (multi-host already
        serialized through the lockstep dispatch).
        """
        n = len(page_ids)
        if n == 0:
            return
        bucket = pad_to_bucket(n, _buckets(max(self.config.cache.num_blocks, n)))
        ids = np.asarray(page_ids, np.int32)
        if bucket > n:
            ids = np.concatenate([ids, np.full(bucket - n, ids[-1], np.int32)])
            pages = np.concatenate(
                [pages, np.repeat(pages[:, -1:], bucket - n, axis=1)], axis=1
            )
        if self._multihost:
            assert layers is None, "layer-group staging is single-host only"
            # Lockstep scatter: canonical-head values broadcast to every
            # process (one collective), head expansion (and int8-pool
            # quantization) on device. QK slot = pool selector.
            assert dist.is_leader(), "KV staging ops originate on the leader"
            vals = np.ascontiguousarray(
                np.asarray(pages).astype(self.staging_dtype, copy=False)
            )
            with self._dispatch_lock:
                arrays = self._sync_locked(
                    _OP_KV_SCATTER, bucket, int(swa), False,
                    {"ids": ids, "vals_u8": vals.view(np.uint8).reshape(-1)},
                )
                self._exec_kv_scatter(arrays, bucket, swa)
            return
        vals = jnp.asarray(np.asarray(pages), dtype=self.staging_dtype)
        with self._dispatch_lock:
            if layers is not None:
                l0, lg = layers
                out = self._scatter_canonical_group(
                    self._pool(swa),
                    jnp.arange(l0, l0 + lg, dtype=jnp.int32),
                    jnp.asarray(ids),
                    vals,
                )
            else:
                out = self._scatter_canonical(
                    self._pool(swa), jnp.asarray(ids), vals
                )
            if swa:
                self.kv_swa = out
            else:
                self.kv_cache = out

    def scatter_pages_q8(
        self,
        page_ids: list[int],
        q8: np.ndarray,
        scales: np.ndarray,
        swa: bool = False,
    ) -> None:
        """Stage an int8-wire bundle host -> HBM (the symmetric twin of
        the q8 gather): (q8 [L, n, K, page, 2D] i8, scales
        [L, n, K, page, 2] f16) canonical heads. Multi-host broadcasts
        the wire form — HALF the DCN bytes of the canonical
        _OP_KV_SCATTER leg — with head expansion (and for float pools
        the dequant) on every process's device. Same bucket-padding and
        locking discipline as :meth:`scatter_pages`."""
        n = len(page_ids)
        if n == 0:
            return
        bucket = pad_to_bucket(n, _buckets(max(self.config.cache.num_blocks, n)))
        ids = np.asarray(page_ids, np.int32)
        q8 = np.asarray(q8)
        scales = np.asarray(scales)
        if bucket > n:
            ids = np.concatenate([ids, np.full(bucket - n, ids[-1], np.int32)])
            q8 = np.concatenate(
                [q8, np.repeat(q8[:, -1:], bucket - n, axis=1)], axis=1
            )
            scales = np.concatenate(
                [scales, np.repeat(scales[:, -1:], bucket - n, axis=1)], axis=1
            )
        arrays = {
            "ids": ids,
            "q8": np.ascontiguousarray(q8, np.int8),
            "scales": np.ascontiguousarray(scales, np.float16),
        }
        if self._multihost:
            assert dist.is_leader(), "KV staging ops originate on the leader"
            with self._dispatch_lock:
                arrays = self._sync_locked(
                    _OP_KV_SCATTER_Q8, bucket, int(swa), False, arrays
                )
                self._exec_kv_scatter_q8(arrays, swa)
            return
        with self._dispatch_lock:
            self._exec_kv_scatter_q8(arrays, swa)

    # ------------------------------------------------------------------ #

    def run_embed(
        self, prompts: list[list[int]], lora_id: int = 0
    ) -> np.ndarray:
        """Mean-pooled, L2-normalized final hidden states: [n, H] f32.

        The /v1/embeddings surface (OpenAI API; the reference's vllmgrpc
        Embed verb, request-handling.md:50-86). Runs the decoder stack
        over a throwaway KV scratch pool — embeddings never touch the
        serving cache, so this is safe to run concurrently with the step
        loop (params are read-only)."""
        if self.state_pool:
            raise NotImplementedError(
                f"{self.cfg.name}: the embedding program has no state pool; "
                "state-space layers run on the flat step only"
            )
        if not prompts:
            return np.zeros((0, self.cfg.hidden_size), np.float32)
        maxlen = max(len(p) for p in prompts)
        limit = min(self.cfg.max_model_len, self.prefill_buckets[-1])
        if maxlen > limit:
            raise ValueError(
                f"embedding input length {maxlen} exceeds the embed limit "
                f"{limit} (min of max_model_len and max_num_batched_tokens)"
            )
        # Requests larger than one device batch run in slices.
        max_b = self.batch_buckets[-1]
        if len(prompts) > max_b:
            return np.concatenate([
                self.run_embed(prompts[i : i + max_b], lora_id)
                for i in range(0, len(prompts), max_b)
            ])
        n = len(prompts)
        Q = pad_to_bucket(maxlen, self.prefill_buckets)
        B = pad_to_bucket(n, self.batch_buckets)
        tokens = np.zeros((B, Q), np.int32)
        positions = np.zeros((B, Q), np.int32)
        qlens = np.zeros(B, np.int32)
        for i, p in enumerate(prompts):
            m = len(p)
            tokens[i, :m] = p
            positions[i, :m] = np.arange(m)
            positions[i, m:] = max(m - 1, 0)
            qlens[i] = m
        arrays = {"tokens": tokens, "positions": positions, "qlens": qlens}
        if self._multihost:
            # A plain SPMD program like any step — broadcast the host
            # inputs (lora_id rides the header's 4th slot) and dispatch
            # on every process; the replicated output is read locally.
            # The lock covers broadcast ordering only; single-host embeds
            # run lock-free so an embed compile never stalls the step
            # loop (params are read-only, scratch is program-internal).
            with self._dispatch_lock:
                arrays = self._sync_locked(_OP_EMBED, B, Q, lora_id, arrays)
                pooled = self._exec_embed(arrays, lora_id)
        else:
            pooled = self._exec_embed(arrays, lora_id)
        return np.asarray(pooled[:n])

    def _exec_embed(self, arrays: dict, lora_id: int) -> jax.Array:
        B, Q = arrays["tokens"].shape
        page = self.page
        pages_per_seq = -(-Q // page)
        # Page table / lora ids derive from (B, Q, lora_id) identically
        # on every process — not broadcast.
        pt = jnp.asarray(
            np.arange(B * pages_per_seq, dtype=np.int32).reshape(
                B, pages_per_seq
            )
        )
        qlens = jnp.asarray(arrays["qlens"])
        inp = StepInput(
            token_ids=jnp.asarray(arrays["tokens"]),
            positions=jnp.asarray(arrays["positions"]),
            query_lens=qlens,
            kv_lens=qlens,
            page_table=pt,
            lora_ids=(
                jnp.full(B, lora_id, jnp.int32)
                if self.cfg.num_lora_adapters
                else None
            ),
            # Embeds are one-shot: the sliding group can use a full-length
            # identity view of its own scratch (no ring needed — the ring
            # is just a table pattern).
            swa_page_table=pt if self.swa is not None else None,
        )
        return self._embed_fn(self.params, inp)

    @functools.cached_property
    def _embed_fn(self):
        cfg, world, mesh = self.cfg, self.ctx.world, self.ctx.mesh
        kv_rep = self.kv_rep
        moe_backend = self.config.parallel.moe_backend if cfg.is_moe else "dense"
        ep_capacity = self.config.parallel.ep_capacity_factor
        ring = self.swa is not None
        data_shape = self._kv_data.shape
        data_dtype = self._kv_data.dtype
        quantized = self.kv_quantized
        page = self.page
        swa = self.swa
        num_layers = self.cfg.num_layers
        replicate = self._replicate_out

        @jax.jit
        def embed(params, inp: StepInput):
            # Scratch pools are created INSIDE the jit (SPMD-consistent
            # on a multi-host mesh; XLA also frees them at program end
            # instead of holding host-side references).
            B, Q = inp.token_ids.shape
            pages_per_seq = -(-Q // page)

            def scratch_pool(n_layers: int):
                shape = (
                    n_layers, B * pages_per_seq, data_shape[2], page,
                    data_shape[4],
                )
                if quantized:
                    return (
                        jnp.zeros(shape, jnp.int8),
                        jnp.ones((*shape[:3], page, 2), jnp.float32),
                    )
                return jnp.zeros(shape, data_dtype)

            if ring:
                scratch_kv = scratch_pool(len(swa.full_layers))
                scratch_swa = scratch_pool(len(swa.swa_layers))
            else:
                scratch_kv = scratch_pool(num_layers)
                scratch_swa = None
            if ring:
                hidden, _, _ = llama.forward_hidden(
                    params, scratch_kv, inp, cfg, world, mesh=mesh,
                    moe_backend=moe_backend, ep_capacity_factor=ep_capacity,
                    kv_rep=kv_rep, kv_swa=scratch_swa,
                )
            else:
                hidden, _ = llama.forward_hidden(
                    params, scratch_kv, inp, cfg, world, mesh=mesh,
                    moe_backend=moe_backend, ep_capacity_factor=ep_capacity,
                    kv_rep=kv_rep,
                )
            valid = inp.valid[..., None].astype(jnp.float32)  # [B, Q, 1]
            summed = jnp.sum(hidden.astype(jnp.float32) * valid, axis=1)
            denom = jnp.maximum(jnp.sum(valid, axis=1), 1.0)
            mean = summed / denom
            out = mean / jnp.maximum(
                jnp.linalg.norm(mean, axis=-1, keepdims=True), 1e-12
            )
            return replicate(out)

        return embed

    def run_prefill(
        self, seqs: list[ScheduledSeq], sync: bool = True
    ) -> StepResult:
        """Dispatch all scheduled prompt chunks and read the tokens back.

        ``sync=False`` is the P/D eager-ACK path: the forward is ENQUEUED
        but the sampled token is never read back (zeros returned). Valid
        only when no caller consumes the tokens — export-only prefills,
        whose response the routing sidecar discards. Device program order
        keeps the subsequently enqueued KV snapshots correct without any
        host synchronization; a forward fault surfaces on the snapshot
        consumers (staging download / consumer scatter) instead of here.
        """
        pending = self.dispatch_prefill(seqs)
        if not sync:
            return StepResult(
                np.zeros((len(seqs), 1), np.int32),
                np.zeros((len(seqs), 1), np.float32),
            )
        res, _ = self.wait_step(pending, None)
        return res

    def _dispatching(self, family: str, **shape):
        """The ``llmd.runner.dispatch`` span of one step program's
        hand-over to the device (lockstep broadcast + the jitted call
        returning); notes the program as ``last_program``."""
        self.last_program = family + ":" + ",".join(
            f"{k}={v}" for k, v in shape.items()
        )
        return profiling.span("llmd.runner.dispatch", program=self.last_program)

    def dispatch_prefill(self, seqs: list[ScheduledSeq]) -> PendingPrefill:
        """Enqueue all scheduled prompt chunks, batched by Q bucket; no
        host readback (that is ``wait_step``'s single coalesced fetch).

        Rows are grouped so a single long chunk doesn't pad every short
        chunk up to its bucket (padded compute stays ~sum of real tokens,
        not B_bucket x max_chunk).
        """
        groups: dict[int, list[int]] = {}
        for i, s in enumerate(seqs):
            groups.setdefault(
                pad_to_bucket(s.num_tokens, self.prefill_buckets), []
            ).append(i)
        entries = []
        for q_bucket, idxs in sorted(groups.items()):
            packed = self._dispatch_prefill_group(
                [seqs[i] for i in idxs], q_bucket
            )
            entries.append((packed, idxs))
        return PendingPrefill(entries, len(seqs))

    def _dispatch_prefill_group(
        self, seqs: list[ScheduledSeq], Q: int
    ) -> jax.Array:
        n = len(seqs)
        B = pad_to_bucket(n, self.prefill_batch_buckets)
        tokens = np.zeros((B, Q), np.int32)
        positions = np.zeros((B, Q), np.int32)
        qlens = np.zeros(B, np.int32)
        kvlens = np.zeros(B, np.int32)
        tok_slot = np.full(B, self.token_slots, np.int32)
        for i, s in enumerate(seqs):
            req, start, m = s.request, s.start_pos, s.num_tokens
            tokens[i, :m] = req.tokens_between(start, start + m)
            positions[i, :m] = np.arange(start, start + m)
            positions[i, m:] = start + max(m - 1, 0)
            qlens[i] = m
            kvlens[i] = start + m
            tok_slot[i] = self._token_slot(s)
        temp, top_k, top_p, seeds = self._sampling_arrays(seqs, B, 1)
        arrays = {
            "tokens": tokens, "positions": positions, "qlens": qlens,
            "kvlens": kvlens, "page_table": self._page_table(seqs, B),
            "temp": temp, "top_k": top_k, "top_p": top_p,
            "seeds": seeds[:, 0], "tok_slot": tok_slot,
        }
        if self.swa is not None:
            arrays["swa_table"] = self._swa_table(seqs, B)
        if self.cfg.num_lora_adapters:
            arrays["lora"] = self._lora_array(seqs, B)
        live = int(qlens.sum())
        self.live_tokens_total += live
        self.padded_tokens_total += B * Q - live
        all_greedy = all(s.request.sampling.greedy for s in seqs)
        with self._dispatch_lock, self._dispatching("prefill", B=B, Q=Q):
            arrays = self._sync_locked(_OP_PREFILL, B, Q, all_greedy, arrays)
            return self._exec_prefill(arrays, all_greedy)

    def run_decode(self, seqs: list[ScheduledSeq], k_steps: int = 1) -> StepResult:
        """K fused decode iterations for the running batch (K=1 = one token)."""
        pending = self.dispatch_decode(seqs, k_steps)
        _, res = self.wait_step(None, pending)
        return res

    def dispatch_decode(
        self, seqs: list[ScheduledSeq], k_steps: int = 1
    ) -> PendingDecode:
        """Stage + enqueue the decode program; no host readback."""
        return self.dispatch_staged_decode(self.stage_decode(seqs, k_steps))

    @profiling.spanned("llmd.runner.build")
    def stage_decode(
        self, seqs: list[ScheduledSeq], k_steps: int = 1
    ) -> StagedDecode:
        """Build the decode dispatch's host arrays AHEAD of the previous
        step's readback (async stepping overlaps this with device
        execution). The page/ring tables — the O(B x max_pages) cost —
        are final here because the scheduler already allocated every page
        the speculated tokens need; ``first``/``start`` and seeded rows'
        seeds are filled at dispatch, once the tokens they depend on are
        committed."""
        n = len(seqs)
        B = pad_to_bucket(n, self.batch_buckets)
        active = np.zeros(B, np.uint8)
        active[:n] = 1
        # Seeds are NOT drawn here: the stateful rng must be consumed at
        # dispatch time in dispatch order, or async staging (which runs
        # a step early and re-runs on a rollback restage) would shift
        # the draw stream relative to a synchronous engine and break
        # unseeded-sampling parity.
        temp, top_k, top_p = self._sampling_knobs(seqs, B)
        arrays = {
            "first": np.zeros(B, np.int32), "start": np.zeros(B, np.int32),
            "page_table": self._page_table(seqs, B), "active": active,
            "temp": temp, "top_k": top_k, "top_p": top_p,
            "seeds": np.zeros((B, k_steps), np.uint32),
            **self._token_source_arrays(B),
        }
        if self.swa is not None:
            arrays["swa_table"] = self._swa_table(seqs, B)
        if self.cfg.num_lora_adapters:
            arrays["lora"] = self._lora_array(seqs, B)
        all_greedy = all(s.request.sampling.greedy for s in seqs)
        return StagedDecode(list(seqs), arrays, B, k_steps, all_greedy)

    def _token_source_arrays(self, B: int) -> dict:
        """``tok_slot`` / ``tok_dev`` at rest (no entry, the host's token):
        filled at dispatch, like the tokens themselves."""
        return {
            "tok_slot": np.full(B, self.token_slots, np.int32),
            "tok_dev": np.zeros(B, np.uint8),
        }

    def _fill_token_source(self, a: dict, r: int, seq: ScheduledSeq) -> int:
        """Decode row ``r``'s input token and where it comes from, at
        dispatch: the host's where the host has it, else (a step of the
        sequence is in flight and not read back) the device's own
        ``last_tokens`` entry. Returns the token for the host's stream (0
        where the device feeds it)."""
        req = seq.request
        a["tok_slot"][r] = self._token_slot(seq)
        if not req.num_pending_tokens:
            return req.token_at(req.num_computed_tokens)
        assert a["tok_slot"][r] < self.token_slots, req.request_id
        a["tok_dev"][r] = 1
        return 0

    def dispatch_staged_decode(self, staged: StagedDecode) -> PendingDecode:
        """Fill the readback-dependent slots of a staged decode and
        enqueue it. By dispatch time the previous step has committed, so
        ``num_computed_tokens``/``all_token_ids`` hold exactly what a
        synchronous engine would see here — async staging never changes
        the dispatched bytes, only when the host work happened."""
        if staged.prepared is None:
            self._fill_decode(staged)
        n = len(staged.seqs)
        with self._dispatch_lock, self._dispatching(
            "decode_window", B=staged.B, K=staged.k
        ):
            arrays = self._sync_locked(
                _OP_DECODE, staged.B, staged.k, staged.all_greedy,
                staged.arrays,
            )
            packed = self._exec_decode(
                arrays, staged.k, staged.all_greedy, self._take_step(staged)
            )
        return PendingDecode(
            [(packed, list(range(n)), staged.k)], n, staged.k
        )

    def _fill_decode(self, staged: StagedDecode) -> None:
        """The host half of ``dispatch_staged_decode``: tokens (or where
        the device finds them), positions and seeds into
        ``staged.arrays``."""
        first = staged.arrays["first"]
        start = staged.arrays["start"]
        # ONE [B, K] rng block per decode dispatch, drawn here so the
        # stateful stream advances in dispatch order (byte-parity with a
        # synchronous engine for unseeded sampling); explicitly seeded
        # rows then overwrite theirs per (request seed, output index).
        seeds = self._np_rng.integers(
            0, 2**32, size=(staged.B, staged.k), dtype=np.uint32
        )
        staged.arrays["seeds"] = seeds
        for i, s in enumerate(staged.seqs):
            first[i] = self._fill_token_source(staged.arrays, i, s)
            start[i] = s.start_pos
        self._overwrite_seeded_rows(seeds, staged.seqs, staged.k)
        n = len(staged.seqs)
        self.live_tokens_total += n * staged.k
        self.padded_tokens_total += (staged.B - n) * staged.k

    def prepare_staged(self, staged: StagedDecode | StagedUnified) -> None:
        """Fill ``staged`` and put its payload on the device AHEAD of its
        dispatch, while the step in front of it still runs: with its decode
        rows' tokens taken from the device, nothing of the fill waits for
        that step, so the dispatch itself is the jitted call alone. The
        caller dispatches ``staged`` next, or forgets it (``unprepare``)
        before it stages the step anew. (Not in a lockstep group: the
        broadcast of the filled arrays IS the dispatch there.)"""
        if self._multihost or staged.prepared is not None:
            return
        rng = self._np_rng.bit_generator.state
        counters = {k: getattr(self, k) for k in self._FILL_COUNTERS}
        if isinstance(staged, StagedDecode):
            self._fill_decode(staged)
            shape = (_OP_DECODE, staged.B, staged.k)
        else:
            self._fill_unified(staged)
            shape = (
                (_OP_FLAT, staged.B, staged.T) if staged.flat
                else (_OP_UNIFIED, staged.B, (staged.Q << 20) | staged.T)
            )
        staged.prepared = _Prepared(
            self._put_step(*shape, staged.arrays), rng, counters
        )

    def shape_is_warm(self, staged: StagedDecode | StagedUnified) -> bool:
        """Has the step program ``staged`` goes to been called at its shape
        (``_run_step``'s key)? A first call is seconds of tracing and
        lowering, and its time follows the Python path it is reached on
        (PERF.md section 7 (k)): the engine keeps it on the path the set-up
        bound was set with."""
        if isinstance(staged, StagedDecode):
            key = (self._multi, staged.B, staged.k)
        elif staged.flat:
            key = (self._flat, self.flat_rows, staged.T)
        else:
            key = (self._unified, staged.B, (staged.Q << 20) | staged.T)
        return (*key, staged.all_greedy) in self._called

    def unprepare(self, staged) -> None:
        """Forget that ``staged`` was prepared: it will not be dispatched
        as it is. The seeds' rng and the counters go back to where its fill
        found them (nothing else draws or counts in between: the prepared
        step is the next one out)."""
        p = getattr(staged, "prepared", None)
        if p is None:
            return
        staged.prepared = None
        self._np_rng.bit_generator.state = p.rng_state
        for k, v in p.counters.items():
            setattr(self, k, v)

    @staticmethod
    def _take_step(staged) -> jax.Array | None:
        """The payload ``prepare_staged`` put ahead, once."""
        p, staged.prepared = staged.prepared, None
        return None if p is None else p.step

    @profiling.spanned("llmd.runner.build")
    def stage_spec_verify(self, seqs: list[ScheduledSeq]) -> StagedVerify:
        """Build the verify dispatch's host arrays AHEAD of the previous
        step's readback (async stepping). The page/ring tables are final
        here — the scheduler already allocated pages for the
        max-acceptance position of every row; tokens/positions/qlens/
        kvlens (which depend on the committed position and the drafts
        proposed from committed history) and seeds are filled at
        dispatch."""
        n = len(seqs)
        # Prefill-style row buckets (powers of two from 1): a mixed step
        # verifies only its drafting rows, often just one or two — padding
        # those up to the decode batch buckets (from 8) would waste more
        # verify columns than the drafts save.
        B = pad_to_bucket(n, self.prefill_batch_buckets)
        Q = self.spec_q
        temp, top_k, top_p = self._sampling_knobs(seqs, B)
        arrays = {
            "tokens": np.zeros((B, Q), np.int32),
            "positions": np.zeros((B, Q), np.int32),
            "qlens": np.zeros(B, np.int32),
            "kvlens": np.zeros(B, np.int32),
            "page_table": self._page_table(seqs, B),
            "temp": temp, "top_k": top_k, "top_p": top_p,
            "seeds": np.zeros((B, Q), np.uint32),
        }
        if self.swa is not None:
            arrays["swa_table"] = self._swa_table(seqs, B)
        if self.cfg.num_lora_adapters:
            arrays["lora"] = self._lora_array(seqs, B)
        all_greedy = all(s.request.sampling.greedy for s in seqs)
        return StagedVerify(list(seqs), arrays, B, Q, all_greedy)

    def dispatch_staged_verify(self, staged: StagedVerify) -> PendingDecode:
        """Fill the readback/draft-dependent slots of a staged verify and
        enqueue it. Each row feeds [next input token, draft...]; pad
        positions repeat the last real position and are masked from KV
        writes by query_lens (the prefill convention), so a short draft
        can never deposit KV past its own columns."""
        tokens = staged.arrays["tokens"]
        positions = staged.arrays["positions"]
        qlens = staged.arrays["qlens"]
        kvlens = staged.arrays["kvlens"]
        # ONE [B, Q] rng block per verify dispatch, drawn in dispatch
        # order (see dispatch_staged_decode's seed-parity note); seeded
        # rows overwrite theirs with the shared per-(request seed,
        # output-index) derivation, which is what makes seeded
        # acceptance exact.
        seeds = self._np_rng.integers(
            0, 2**32, size=(staged.B, staged.q), dtype=np.uint32
        )
        staged.arrays["seeds"] = seeds
        for i, s in enumerate(staged.seqs):
            req = s.request
            nc = req.num_computed_tokens
            draft = s.draft_tokens or []
            m = 1 + len(draft)
            tokens[i, :m] = [req.token_at(nc), *draft]
            tokens[i, m:] = 0
            positions[i, :m] = np.arange(nc, nc + m)
            positions[i, m:] = nc + m - 1
            qlens[i] = m
            kvlens[i] = nc + m
        self._overwrite_seeded_rows(seeds, staged.seqs, staged.q)
        live = int(qlens.sum())
        self.live_tokens_total += live
        self.padded_tokens_total += staged.B * staged.q - live
        with self._dispatch_lock, self._dispatching(
            "verify", B=staged.B, Q=staged.q
        ):
            arrays = self._sync_locked(
                _OP_VERIFY, staged.B, staged.q, staged.all_greedy,
                staged.arrays,
            )
            packed = self._exec_verify(arrays, staged.all_greedy)
        n = len(staged.seqs)
        return PendingDecode(
            [(packed, list(range(n)), staged.q)], n, staged.q
        )

    @staticmethod
    def _slice_staged_rows(
        arrays: dict, idxs: list[int], B: int, names: tuple[str, ...]
    ) -> dict:
        """Re-bucket the row-independent staged arrays (page/ring
        tables, sampling knobs, lora slots) for a subset of rows: one
        vectorized gather per array instead of re-walking the requests'
        block lists inside the blocking host region (the async+spec
        mixed-step restage cost this avoids is the dominant part of
        ``step_host_gap_ms`` on mixed traffic)."""
        rows = np.asarray(idxs, np.int64)
        out = {}
        for name in names:
            if name not in arrays:
                continue
            src = arrays[name]
            dst = np.zeros((B, *src.shape[1:]), src.dtype)
            if name == "top_p":
                dst[:] = 1.0  # pad rows keep the neutral knob
            dst[: len(rows)] = src[rows]
            out[name] = dst
        return out

    _ROW_SLICE_NAMES = (
        "page_table", "swa_table", "state_slots", "temp", "top_k", "top_p",
        "lora",
    )

    def _subset_staged_verify(
        self, staged: StagedVerify, seqs: list[ScheduledSeq],
        idxs: list[int],
    ) -> StagedVerify:
        """Derive a subset StagedVerify from prestaged full-batch verify
        arrays (async+spec mixed steps): the row-independent arrays are
        sliced by the subset index set; the dispatch-filled arrays
        (tokens/positions/qlens/kvlens/seeds) are fresh zeros as
        ``stage_spec_verify`` would build them."""
        n = len(idxs)
        B = pad_to_bucket(n, self.prefill_batch_buckets)
        Q = self.spec_q
        arrays = self._slice_staged_rows(
            staged.arrays, idxs, B, self._ROW_SLICE_NAMES
        )
        arrays.update({
            "tokens": np.zeros((B, Q), np.int32),
            "positions": np.zeros((B, Q), np.int32),
            "qlens": np.zeros(B, np.int32),
            "kvlens": np.zeros(B, np.int32),
            "seeds": np.zeros((B, Q), np.uint32),
        })
        sub = [seqs[i] for i in idxs]
        all_greedy = all(s.request.sampling.greedy for s in sub)
        return StagedVerify(sub, arrays, B, Q, all_greedy)

    def _subset_staged_decode(
        self, staged: StagedVerify,
        seqs: list[ScheduledSeq], idxs: list[int], k_steps: int,
    ) -> StagedDecode:
        """Derive a subset StagedDecode from prestaged verify
        arrays — the degrade path when staged drafting rows turned out
        not to draft at dispatch time."""
        n = len(idxs)
        B = pad_to_bucket(n, self.batch_buckets)
        arrays = self._slice_staged_rows(
            staged.arrays, idxs, B, self._ROW_SLICE_NAMES
        )
        active = np.zeros(B, np.uint8)
        active[:n] = 1
        arrays.update({
            "first": np.zeros(B, np.int32),
            "start": np.zeros(B, np.int32),
            "active": active,
            "seeds": np.zeros((B, k_steps), np.uint32),
            **self._token_source_arrays(B),
        })
        sub = [seqs[i] for i in idxs]
        all_greedy = all(s.request.sampling.greedy for s in sub)
        return StagedDecode(sub, arrays, B, k_steps, all_greedy)

    def dispatch_spec_split(
        self,
        seqs: list[ScheduledSeq],
        staged: StagedVerify | None = None,
    ) -> PendingDecode:
        """Mixed speculative step: rows that drafted ride the verify
        program, the rest ride the plain one-token decode program — two
        enqueues, still ONE coalesced readback (both packed outputs join
        wait_step's single transfer). Keeps non-drafting rows from
        paying 1 + k verify columns for nothing. ``staged`` reuses the
        async pipeline's prestaged full-batch verify arrays: the
        row-independent page-table/knob rows are SLICED by the subset
        index sets instead of being rebuilt inside the blocking host
        region."""
        drafted = [i for i, s in enumerate(seqs) if s.draft_tokens]
        plain = [i for i, s in enumerate(seqs) if not s.draft_tokens]
        entries: list[tuple[jax.Array, list[int], int]] = []
        reuse = (
            staged is not None
            and len(staged.seqs) == len(seqs)
            and all(a is b for a, b in zip(staged.seqs, seqs))
        )
        if reuse:
            sub_v = self._subset_staged_verify(staged, seqs, drafted)
        else:
            sub_v = self.stage_spec_verify([seqs[i] for i in drafted])
        pv = self.dispatch_staged_verify(sub_v)
        entries.append((pv.entries[0][0], drafted, self.spec_q))
        if plain:
            if reuse:
                sub_d = self._subset_staged_decode(staged, seqs, plain, 1)
            else:
                sub_d = self.stage_decode([seqs[i] for i in plain], k_steps=1)
            pd = self.dispatch_staged_decode(sub_d)
            entries.append((pd.entries[0][0], plain, 1))
        return PendingDecode(entries, len(seqs), self.spec_q)

    @profiling.spanned("llmd.runner.build")
    def stage_unified(
        self, prefills: list[ScheduledSeq], decodes: list[ScheduledSeq]
    ) -> StagedUnified:
        """Build a unified step's host arrays AHEAD of the tokens/drafts
        they depend on (async prestaging). The row structure — prefill
        chunks split into <= ``unified_row_cap`` sub-rows, one row per
        decode seq at its PLANNED width — is fixed by the schedule, so
        the page/ring tables and sampling knobs (the O(rows x max_pages)
        cost) are final here; the packed stream, per-row (start, qlen,
        kind) metadata and seeds fill at dispatch."""
        row_seqs: list[ScheduledSeq] = []
        row_off: list[int] = []
        row_plan: list[int] = []
        prefill_rows: list[int] = []
        for s in prefills:
            for off, w in self._chunk_rows(s):
                row_seqs.append(s)
                row_off.append(off)
                row_plan.append(w)
            prefill_rows.append(len(row_seqs) - 1)
        decode_rows = [0] * len(decodes)
        for i in self._decode_row_order(decodes):
            s = decodes[i]
            decode_rows[i] = len(row_seqs)
            row_seqs.append(s)
            row_off.append(0)
            row_plan.append(s.num_tokens)
        n = len(row_seqs)
        flat = self._flat is not None
        if flat:
            # Flattened-token staging: the row-metadata width is FIXED
            # (one traced B — metadata is O(rows), a few KB) and the
            # stream buckets over the fine-grained flat T set, so the
            # shape family is the T axis alone.
            B = self.flat_rows
            T = pad_to_bucket(sum(row_plan), self.flat_t_buckets)
        else:
            B = pad_to_bucket(n, self.unified_row_buckets)
            T = pad_to_bucket(sum(row_plan), self.prefill_buckets)
        Q = pad_to_bucket(max(row_plan), self.unified_q_buckets)
        S = self.unified_s
        temp, top_k, top_p = self._sampling_knobs(row_seqs, B)
        arrays = {
            "stream": np.zeros(T, np.int32),
            "row_start": np.zeros(B, np.int32),
            "pos0": np.zeros(B, np.int32),
            "qlens": np.zeros(B, np.int32),
            "kvlens": np.zeros(B, np.int32),
            "kind": np.zeros(B, np.uint8),
            "page_table": self._page_table(row_seqs, B),
            "temp": temp, "top_k": top_k, "top_p": top_p,
            "seeds": np.zeros((B, S), np.uint32),
            **self._token_source_arrays(B),
        }
        if self.state_pool:
            # A row's slot of the state pool (its sequence's "ring" of one).
            arrays["state_slots"] = np.zeros(B, np.int32)
            arrays["state_slots"][:n] = [
                s.request.swa_block_ids[0] for s in row_seqs
            ]
        elif self.swa is not None:
            arrays["swa_table"] = self._swa_table(row_seqs, B)
        if self.cfg.num_lora_adapters:
            arrays["lora"] = self._lora_array(row_seqs, B)
        all_greedy = all(s.request.sampling.greedy for s in row_seqs)
        return StagedUnified(
            list(prefills), list(decodes), row_seqs, row_off, row_plan,
            prefill_rows, decode_rows, arrays, B, Q, T, S, all_greedy,
            flat=flat,
        )

    def dispatch_unified(
        self, prefills: list[ScheduledSeq], decodes: list[ScheduledSeq]
    ) -> PendingUnified:
        """Stage + enqueue the whole window=1 step as ONE program."""
        return self.dispatch_staged_unified(self.stage_unified(prefills, decodes))

    def dispatch_staged_unified(self, staged: StagedUnified) -> PendingUnified:
        """Fill the readback/draft-dependent slots of a staged unified
        step and enqueue it: pack every row's actual tokens into the
        flat stream (prefill sub-rows read their chunk slice; decode
        rows feed [next committed token]; drafting rows feed
        [next, draft...] and become verify-kind rows), then dispatch one
        program. ONE [B, S] rng block per dispatch, drawn here so the
        stateful stream advances in dispatch order; SEEDED rows
        overwrite theirs per (request seed, output index), so column 0
        of a seeded non-verify row equals the split engine's one-sample
        seed exactly — greedy and seeded streams stay byte-identical to
        the split engine. (Unseeded sampled rows draw from a
        differently-shaped rng block than the split dispatches would,
        so hot sampling is reproducible within a mode, not across the
        unified/split switch — the same contract as spec on/off.)"""
        a = staged.arrays
        if staged.prepared is None:
            self._fill_unified(staged)
        step = self._take_step(staged)
        if staged.flat:
            with self._dispatch_lock, self._dispatching("flat", T=staged.T):
                arrays = self._sync_locked(
                    _OP_FLAT, staged.B, staged.T, staged.all_greedy, a
                )
                packed = self._exec_flat(arrays, staged.all_greedy, step)
        else:
            with self._dispatch_lock, self._dispatching(
                "unified", B=staged.B, Q=staged.Q, T=staged.T
            ):
                arrays = self._sync_locked(
                    _OP_UNIFIED, staged.B, (staged.Q << 20) | staged.T,
                    staged.all_greedy, a,
                )
                packed = self._exec_unified(
                    arrays, staged.Q, staged.all_greedy, step
                )
        return PendingUnified(
            packed, staged.S, list(staged.prefill_rows),
            list(staged.decode_rows), len(staged.prefills),
            len(staged.decodes),
        )

    @profiling.spanned("llmd.runner.build")
    def _fill_unified(self, staged: StagedUnified) -> None:
        """The host half of ``dispatch_staged_unified``: the packed
        stream, the per-row metadata, the seeds and (flat) the KV-write
        runs, into ``staged.arrays``."""
        a = staged.arrays
        stream, row_start = a["stream"], a["row_start"]
        pos0, qlens, kvlens = a["pos0"], a["qlens"], a["kvlens"]
        kind = a["kind"]
        a["seeds"] = self._np_rng.integers(
            0, 2**32, size=(staged.B, staged.S), dtype=np.uint32
        )
        n_pre_rows = (
            staged.prefill_rows[-1] + 1 if staged.prefill_rows else 0
        )
        if staged.decode_rows:
            # A decode seq keeps the seeds of its place among the decodes,
            # wherever ``_decode_row_order`` put its row.
            rows = np.asarray(staged.decode_rows)
            a["seeds"][rows] = a["seeds"][n_pre_rows:n_pre_rows + len(rows)].copy()
        sparse_topk = self.cfg.indexer_topk
        t = 0
        for r, (seq, off, _plan) in enumerate(
            zip(staged.row_seqs, staged.row_off, staged.row_plan)
        ):
            req = seq.request
            if r < n_pre_rows:
                start = seq.start_pos + off
                w = min(staged.row_plan[r], seq.num_tokens - off)
                toks = req.tokens_between(start, start + w)
                kind[r] = _KIND_PREFILL
                if off + w == seq.num_tokens:  # the sub-row that samples
                    a["tok_slot"][r] = self._token_slot(seq)
            else:
                start = seq.start_pos
                draft = seq.draft_tokens or []
                toks = [self._fill_token_source(a, r, seq), *draft]
                kind[r] = _KIND_VERIFY if draft else _KIND_DECODE
                w = len(toks)
            stream[t : t + w] = toks
            row_start[r] = t
            if staged.flat:
                g = ATTENTION_TILE
                self.attn_shared_tile_tokens_total += max(
                    0, (t + w) // g * g - -(-t // g) * g
                )
            pos0[r] = start
            qlens[r] = w
            kvlens[r] = start + w
            t += w
            if sparse_topk:
                # Tokens at positions [lo, start + w) see more than top-k
                # cached tokens (position + 1 of them each).
                lo = max(start, sparse_topk)
                bound = max(0, start + w - lo)
                self.sparse_bound_tokens_total += bound
                self.sparse_unbound_tokens_total += w - bound
                self.indexer_keys_scored_total += (
                    bound * (lo + start + w + 1) // 2
                )
                self.indexer_keys_written_total += w
                if self.cfg.is_mla:
                    # Positions [start, lo) read position + 1 rows each.
                    free = max(0, min(start + w, lo) - start)
                    self.sparse_rows_selected_total += self.cfg.num_layers * (
                        bound * sparse_topk + free * (2 * start + free + 1) // 2
                    )
                    self.latent_rows_written_total += self.cfg.num_layers * w
        self._overwrite_seeded_rows(a["seeds"], staged.row_seqs, staged.S)
        self.live_tokens_total += t
        if self.state_pool:
            Lm = len(self.swa.state_layers)
            n_dec = len(staged.row_seqs) - n_pre_rows
            if self.cfg.delta_rule:
                self.gdn_update_rows_total += n_dec * Lm
                self.gdn_scan_rows_total += n_pre_rows * Lm
                self.gdn_scan_tokens_total += (t - n_dec) * Lm
            else:
                self.ssm_update_rows_total += n_dec * Lm
                self.ssm_scan_tokens_total += (t - n_dec) * Lm
        if staged.flat:
            # Pad rows carry row_start = total so the cu_q_lens boundary
            # array the device searchsorts stays monotonic.
            row_start[len(staged.row_seqs):] = t
            self._fill_flat_runs(staged, a)
            if self._plans_runs:
                self._fill_prefix_runs(staged, a)
            self.padded_tokens_total += staged.T - t
        else:
            self.padded_tokens_total += staged.B * staged.Q - t

    def _fill_flat_runs(self, staged: StagedUnified, a: dict) -> None:
        """Host half of the flat KV-write plan: walk each row's token
        span page by page and emit one run per maximal span of
        consecutive stream tokens landing in one page, so runs target
        distinct pages (the Pallas write pipeline's precondition: it
        loads run r+1's page before run r's is stored). A chunk's
        sub-rows are consecutive in the stream, so where one ends inside
        a page the next goes on in the SAME run (two runs there would
        lose the first one's rows). ``src`` is pre-shifted (page + t0 -
        off) so the kernel's fixed-size slab DMA lands token t0+j at
        page row off+j. The run width derives from (B, T, page) on both
        lockstep sides; see the _OP_FLAT payload spec for the bound's
        derivation.
        """
        page = self.page
        rn = 2 * staged.B + -(-staged.T // page)
        wsrc = np.zeros(rn, np.int32)
        woff = np.zeros(rn, np.int32)
        wcnt = np.zeros(rn, np.int32)
        wphys = np.zeros(rn, np.int32)
        pt = a["page_table"]
        st = a.get("swa_table")
        wphys_swa = np.zeros(rn, np.int32) if st is not None else None
        i = 0
        for r in range(len(staged.row_seqs)):
            t0 = int(a["row_start"][r])
            p0 = int(a["pos0"][r])
            w = int(a["qlens"][r])
            consumed = 0
            while consumed < w:
                p = p0 + consumed
                pg, o = p // page, p % page
                take = min(page - o, w - consumed)
                src = page + t0 + consumed - o
                if (
                    i and o and wphys[i - 1] == pt[r, pg]
                    and wsrc[i - 1] == src and woff[i - 1] + wcnt[i - 1] == o
                ):
                    wcnt[i - 1] += take
                    consumed += take
                    continue
                wsrc[i] = src
                woff[i] = o
                wcnt[i] = take
                wphys[i] = pt[r, pg]
                if wphys_swa is not None:
                    wphys_swa[i] = st[r, pg]
                i += 1
                consumed += take
        assert i <= rn, (i, rn)
        a["wsrc"], a["woff"], a["wcnt"], a["wphys"] = wsrc, woff, wcnt, wphys
        if wphys_swa is not None:
            a["wphys_swa"] = wphys_swa

    def _decode_row_order(self, decodes: list[ScheduledSeq]):
        """The order a step's decode seqs take their rows in: where the
        attention reads shared-prefix runs, seqs that start on the same
        page side by side (a run's members are neighbours in the stream);
        as they come everywhere else, and where none share. The collect
        goes by ``decode_rows``, so outputs are per seq as ever."""
        if not self._plans_runs or len(decodes) < prefix_runs.RUN_MIN_MEMBERS:
            return range(len(decodes))
        return prefix_runs.group_order([
            s.request.block_ids[0] if s.request.block_ids else -1 - i
            for i, s in enumerate(decodes)
        ])

    def _fill_prefix_runs(self, staged: StagedUnified, a: dict) -> None:
        """Host half of the attention's shared-prefix runs: the plain
        decode tokens of the stream, read against the step's page table."""
        block_keys = PAGES_PER_BLOCK * self.page
        n = len(staged.row_seqs)
        rows = np.flatnonzero(a["kind"][:n] == _KIND_DECODE)
        a["run_lead"], a["run_blocks"], keys = prefix_runs.plan_runs(
            a["page_table"], rows, a["row_start"][rows].astype(np.int64),
            a["kvlens"][rows], staged.T, block_keys, self.page,
            shards=self.ctx.dp,  # the stream's tokens split over dp
        )
        self.attn_prefix_run_keys_total += keys
        self.attn_decode_keys_total += int(a["kvlens"][rows].sum())

    def _chunk_rows(self, s: ScheduledSeq) -> list[tuple[int, int]]:
        """(offset, width) of a prefill chunk's sub-rows of at most
        ``unified_row_cap`` tokens."""
        cap = self.unified_row_cap
        return [
            (off, min(cap, s.num_tokens - off))
            for off in range(0, max(s.num_tokens, 1), cap)
        ]

    @profiling.spanned("llmd.runner.build")
    def restage_unified(
        self,
        staged: StagedUnified,
        prefills: list[ScheduledSeq],
        decodes: list[ScheduledSeq],
    ) -> StagedUnified:
        """Derive the staging of a batch that differs from the one
        ``staged`` was built for by rows DROPPED (a rollback) or ADDED (a
        top-up admission): the rows both share keep their row-independent
        arrays (page/ring tables, knobs, lora slots), SLICED out of the
        prestaged arrays via ``_slice_staged_rows`` — one vectorized
        gather each — instead of re-walking the requests' block lists
        inside the blocking host region; only the added rows are built;
        the dispatch-filled arrays come back as fresh zeros."""
        keep_of: dict[int, list[int]] = {}
        for r, s in enumerate(staged.row_seqs):
            keep_of.setdefault(id(s), []).append(r)
        src: list[int] = []  # row of staged.arrays; -1: a row to build
        row_seqs: list[ScheduledSeq] = []
        row_off: list[int] = []
        row_plan: list[int] = []
        prefill_rows: list[int] = []
        for s in prefills:
            kept = keep_of.get(id(s))
            if kept is not None:
                subs = [(r, staged.row_off[r], staged.row_plan[r]) for r in kept]
            else:
                subs = [(-1, off, w) for off, w in self._chunk_rows(s)]
            for r, off, w in subs:
                src.append(r)
                row_seqs.append(s)
                row_off.append(off)
                row_plan.append(w)
            prefill_rows.append(len(src) - 1)
        decode_rows = [0] * len(decodes)
        for i in self._decode_row_order(decodes):
            s = decodes[i]
            kept = keep_of.get(id(s))
            decode_rows[i] = len(src)
            src.append(kept[0] if kept is not None else -1)
            row_seqs.append(s)
            row_off.append(0)
            row_plan.append(s.num_tokens)
        if staged.flat:
            B = self.flat_rows
            T = pad_to_bucket(sum(row_plan), self.flat_t_buckets)
        else:
            B = pad_to_bucket(len(src), self.unified_row_buckets)
            T = pad_to_bucket(sum(row_plan), self.prefill_buckets)
        Q = pad_to_bucket(max(row_plan), self.unified_q_buckets)
        S = staged.S
        arrays = self._slice_staged_rows(
            staged.arrays, [max(r, 0) for r in src], B, self._ROW_SLICE_NAMES
        )
        added = [i for i, r in enumerate(src) if r < 0]
        if added:
            seqs = [row_seqs[i] for i in added]
            n = len(added)
            built = dict(zip(
                ("temp", "top_k", "top_p"), self._sampling_knobs(seqs, n)
            ))
            built["page_table"] = self._page_table(seqs, n)
            if "state_slots" in arrays:
                built["state_slots"] = [
                    s.request.swa_block_ids[0] for s in seqs
                ]
            if "swa_table" in arrays:
                built["swa_table"] = self._swa_table(seqs, n)
            if "lora" in arrays:
                built["lora"] = self._lora_array(seqs, n)
            for name, rows in built.items():
                arrays[name][added] = rows
        arrays.update({
            "stream": np.zeros(T, np.int32),
            "row_start": np.zeros(B, np.int32),
            "pos0": np.zeros(B, np.int32),
            "qlens": np.zeros(B, np.int32),
            "kvlens": np.zeros(B, np.int32),
            "kind": np.zeros(B, np.uint8),
            "seeds": np.zeros((B, S), np.uint32),
            **self._token_source_arrays(B),
        })
        all_greedy = all(s.request.sampling.greedy for s in row_seqs)
        return StagedUnified(
            list(prefills), list(decodes), row_seqs, row_off, row_plan,
            prefill_rows, decode_rows, arrays, B, Q, T, S, all_greedy,
            flat=staged.flat,
        )

    def prefill_group_count(self, seqs: list[ScheduledSeq]) -> int:
        """How many Q-bucket programs ``dispatch_prefill`` would enqueue
        for these chunks — the engine's unified-step eligibility probe
        (a single-group prefill-only step is already one dispatch)."""
        return len({
            pad_to_bucket(s.num_tokens, self.prefill_buckets) for s in seqs
        })

    def wait_step(
        self,
        prefill: PendingPrefill | None,
        decode: PendingDecode | None,
        unified: PendingUnified | None = None,
        poll=None,
        at_ready=None,
    ) -> tuple[StepResult | None, StepResult | None]:
        """Block on one engine step's token readback: every dispatched
        program's packed output comes back in a SINGLE coalesced
        transfer (one host round-trip per step, however many prefill
        bucket groups and decode windows the step dispatched — or ONE
        packed array for a unified single-dispatch step, split back into
        prefill/decode results by its row maps).

        ``poll`` (the pipelined step's intake): called again and again
        while the device runs, a short sleep apart so that the threads
        that bring requests get the interpreter, until the outputs are
        there; what it admits meanwhile costs the device nothing.

        ``at_ready`` (the pipelined step's early dispatch): called once,
        the moment the outputs are known to be ready and BEFORE they are
        read back, so that the next program is on the device's queue while
        the host reads, commits and delivers this one. A ``device_get`` of
        a ready 1-2 KB output does not queue behind a running program
        (0.47-0.50 ms under a 7 or 20 ms program against 0.55 ms alone on
        a v5e; PERF.md section 6, PR 49).

        Two spans, ``llmd.runner.wait`` ends when the host KNOWS that every
        output is ready, ``llmd.runner.readback`` holds the transfer and
        the parsing; ``at_ready`` runs between them. ``last_wait`` keeps
        the instants and the bound on how late the first was."""
        packs: list[jax.Array] = []
        if prefill is not None:
            packs.extend(p for p, _ in prefill.entries)
        if decode is not None:
            packs.extend(p for p, _, _ in decode.entries)
        if unified is not None:
            packs.append(unified.packed)
        if not packs:
            now = time.monotonic()
            self.last_wait = WaitTiming(0.0, now, now, now)
            if at_ready is not None:
                at_ready()
            return None, None
        with profiling.span("llmd.runner.wait"):
            if poll is None:
                jax.block_until_ready(packs)
            else:
                t_unready = time.monotonic()
                while True:
                    poll()
                    if all(p.is_ready() for p in packs):
                        break
                    t_unready = time.monotonic()
                    time.sleep(_POLL_S)
            t_ready = time.monotonic()
        if at_ready is not None:
            at_ready()
        t_from = time.monotonic()
        with profiling.span("llmd.runner.readback") as span:
            if dist.is_multihost():
                hosts = [dist.replicated_to_host(p) for p in packs]
            else:
                hosts = [np.asarray(a) for a in jax.device_get(packs)]
            span.set_metadata(bytes=sum(a.nbytes for a in hosts))
            results = self._split_results(prefill, decode, unified, hosts)
            self.last_wait = WaitTiming(
                0.0 if poll is None else t_ready - t_unready,
                t_ready, t_from, time.monotonic(),
            )
        return results

    def _split_results(
        self,
        prefill: PendingPrefill | None,
        decode: PendingDecode | None,
        unified: PendingUnified | None,
        hosts: list[np.ndarray],
    ) -> tuple[StepResult | None, StepResult | None]:
        """``wait_step``'s packed outputs, on the host, as the step's
        prefill and decode results (and the MoE count lanes added up)."""
        if self._counts_grouped:
            for arr in hosts:  # _count_row's lines, below every result row
                self.moe_grouped_calls_total += int(arr[-2, 0])
                self.moe_groups_with_rows_total += int(arr[-2, 1])
                self.moe_picks_total += int(arr[-1, 0])
                self.moe_picks_held_total += int(arr[-1, 1])
        pres = dres = None
        base = 0
        if prefill is not None:
            tokens = np.zeros((prefill.n, 1), np.int32)
            logprobs = np.zeros((prefill.n, 1), np.float32)
            for gi, (_, idxs) in enumerate(prefill.entries):
                arr = hosts[gi]
                for row, i in enumerate(idxs):
                    tokens[i] = arr[row, :1].astype(np.int32)
                    logprobs[i] = arr[row, 1:2]
            pres = StepResult(tokens, logprobs)
            base = len(prefill.entries)
        if decode is not None:
            K = decode.k
            tokens = np.zeros((decode.n, K), np.int32)
            logprobs = np.zeros((decode.n, K), np.float32)
            for gi, (_, idxs, k) in enumerate(decode.entries):
                arr = hosts[base + gi]
                m = len(idxs)
                if idxs == list(range(decode.n)):
                    # Single whole-batch entry (the common, spec-off
                    # case): one vectorized block copy.
                    tokens[:, :k] = arr[:m, :k].astype(np.int32)
                    logprobs[:, :k] = arr[:m, k : 2 * k]
                else:
                    rows = np.asarray(idxs, np.int64)
                    tokens[rows, :k] = arr[:m, :k].astype(np.int32)
                    logprobs[rows, :k] = arr[:m, k : 2 * k]
            dres = StepResult(tokens, logprobs)
        if unified is not None:
            arr = hosts[-1]
            S = unified.S
            if unified.n_prefills:
                # A prefill seq's first-token sample sits in column 0 of
                # its LAST sub-row (every sample column of a non-verify
                # row is the last-position sample).
                rows = np.asarray(unified.prefill_rows, np.int64)
                pres = StepResult(
                    arr[rows, :1].astype(np.int32), arr[rows, S : S + 1]
                )
            if unified.n_decodes:
                rows = np.asarray(unified.decode_rows, np.int64)
                dres = StepResult(
                    arr[rows, :S].astype(np.int32), arr[rows, S : 2 * S]
                )
        return pres, dres

    # ------------------------------------------------------------------ #

    def warmup(
        self,
        prefill_shapes: list[tuple[int, int]] | None = None,
        decode_shapes: list[tuple[int, int]] | None = None,
    ) -> int:
        """Precompile the (bucketed) shapes the scheduler will produce.

        The reference faces the same startup-compile problem on TPU
        (SKIP_JAX_PRECOMPILE + 240x30s startup probes, SURVEY.md 3.4); here
        warmup is explicit. Defaults compile the largest prefill shape and
        the largest decode batch at windows {1, decode_window}. Returns the
        number of programs compiled.
        """
        sched = self.config.scheduler
        flat = self._flat is not None
        if prefill_shapes is None:
            # With the flattened step on, EVERY window=1 step kind —
            # prefill-only, pure-decode, mixed, one-shot verify — rides
            # the ONE flat program, so the split prefill/verify families
            # are reachable only through the P/D eager-ACK producer path
            # (which keeps its own dispatch) and explicit API calls:
            # warm them only where a producer role makes them hot.
            if flat and not self.config.kv_role:
                prefill_shapes = []
            else:
                # The lone-prefill shape (B=1) is the P/D TTFT-critical
                # one; compile it alongside the largest so the first
                # single request never eats a compile.
                prefill_shapes = [
                    (self.prefill_batch_buckets[-1], self.prefill_buckets[-1])
                ]
                if self.prefill_batch_buckets[0] == 1:
                    prefill_shapes.append((1, self.prefill_buckets[-1]))
        if decode_shapes is None:
            decode_shapes = [
                (self.batch_buckets[-1], k) for k in self.decode_windows
            ]
            if flat and len(self.decode_windows) == 1:
                # Window=1 decode steps ride the flat program; the plain
                # decode family stays reachable only via explicit
                # run_decode calls, which this engine (decode_windows ==
                # {1}) never makes.
                decode_shapes = []
        count = 0
        for B, Q in prefill_shapes:
            for greedy in (True, False):
                self._warm_prefill(B, Q, greedy)
                count += 1
        for B, K in decode_shapes:
            for greedy in (True, False):
                self._warm_decode(B, K, greedy)
                count += 1
        if self.spec_q and not flat:
            # The speculative verify family: one Q (= 1 + spec_ngram_k)
            # at the largest row bucket plus the lone-row shape (mixed
            # steps often verify a single drafting row). The flat engine
            # verifies inside the flat program instead.
            for B in {1, self.prefill_batch_buckets[-1]}:
                for greedy in (True, False):
                    self._warm_verify(B, greedy)
                    count += 1
        if flat:
            # The flat family's one shape axis is T: warm the largest
            # stream bucket (the saturated-step shape).
            for greedy in (True, False):
                self._warm_flat(self.flat_t_buckets[-1], greedy)
                count += 1
        elif self._unified is not None:
            # The unified mixed-step family at its largest row/column/
            # stream buckets — the shape a saturated mixed step lands on.
            for greedy in (True, False):
                self._warm_unified(
                    self.unified_row_buckets[-1],
                    self.unified_q_buckets[-1],
                    self.prefill_buckets[-1],
                    greedy,
                )
                count += 1
        return count

    def window1_shape_families(self) -> int:
        """Distinct (program, shape-bucket) combinations the engine can
        dispatch for WINDOW=1 step kinds — prefill chunks, plain decode,
        one-shot verify, mixed — i.e. the compile surface warmup and
        serving draw from. The flattened-token step collapses the
        bucketed (rows x Q x T) unified cross-product plus the split
        prefill/verify families to the flat T axis alone."""
        if self._flat is not None:
            return len(self.flat_t_buckets)
        n = len(self.prefill_batch_buckets) * len(self.prefill_buckets)
        n += len(self.batch_buckets)  # plain decode at window 1
        if self.spec_q:
            n += len(self.prefill_batch_buckets)  # one-shot verify rows
        if self._unified is not None:
            n += (
                len(self.unified_row_buckets)
                * len(self.unified_q_buckets)
                * len(self.prefill_buckets)
            )
        return n

    def _warm_arrays(self, op: int, B: int, QK: int) -> dict:
        """A step program's inputs at rest: every field of its payload
        zero, ``top_p`` at its neutral 1."""
        arrays = self._layout(op, B, QK).zeros()
        arrays["top_p"][:] = 1.0
        return arrays

    def _warm_flat(self, T: int, all_greedy: bool = False) -> None:
        B = self.flat_rows
        arrays = self._warm_arrays(_OP_FLAT, B, T)
        with self._dispatch_lock:
            arrays = self._sync_locked(_OP_FLAT, B, T, all_greedy, arrays)
            self._exec_flat(arrays, all_greedy)

    def _warm_unified(
        self, B: int, Q: int, T: int, all_greedy: bool = False
    ) -> None:
        arrays = self._warm_arrays(_OP_UNIFIED, B, (Q << 20) | T)
        with self._dispatch_lock:
            arrays = self._sync_locked(
                _OP_UNIFIED, B, (Q << 20) | T, all_greedy, arrays
            )
            self._exec_unified(arrays, Q, all_greedy)

    def _warm_prefill(self, B: int, Q: int, all_greedy: bool = False) -> None:
        arrays = self._warm_arrays(_OP_PREFILL, B, Q)
        with self._dispatch_lock:
            arrays = self._sync_locked(_OP_PREFILL, B, Q, all_greedy, arrays)
            self._exec_prefill(arrays, all_greedy)

    def _warm_verify(self, B: int, all_greedy: bool = False) -> None:
        Q = self.spec_q
        arrays = self._warm_arrays(_OP_VERIFY, B, Q)
        with self._dispatch_lock:
            arrays = self._sync_locked(_OP_VERIFY, B, Q, all_greedy, arrays)
            self._exec_verify(arrays, all_greedy)

    def _warm_decode(self, B: int, K: int, all_greedy: bool = False) -> None:
        arrays = self._warm_arrays(_OP_DECODE, B, K)
        with self._dispatch_lock:
            arrays = self._sync_locked(_OP_DECODE, B, K, all_greedy, arrays)
            self._exec_decode(arrays, K, all_greedy)
