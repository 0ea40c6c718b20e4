"""Llama-class decoder (covers Llama-2/3, Qwen2, Mixtral/MoE via config).

Functional, TPU-first: layer params are STACKED along a leading L axis and
the forward pass is one ``lax.scan`` over layers -- one XLA while-loop body
instead of L inlined layers, so compile time is O(1) in depth and the paged
KV cache ([L, pages, K, page, 2D], head-major pages) is scanned in lock-step.

Reference parity: this is the model-execution role the reference delegates
to vLLM (docs/architecture/core/model-servers.md:3-25); the MoE path is the
wide-EP target (docs/architecture/foundations/wide-expert-parallelism.md).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from llmd_tpu.config import ModelConfig
from llmd_tpu.models import attention, dsa, mla, mla_dsa
from llmd_tpu.models.common import (
    LayerCtx, MixerKind, StepCtx, StepInput, norm_weight, param_dtype, pdot,
    rms_norm, rope_tables,
)
from llmd_tpu.models.moe import (
    STACKED_EXPERT_LEAVES,
    experts_of_layer,
    moe_block,
    moe_block_grouped,
)


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Deterministic random init (used for tests/bench and as the template
    for weight loading)."""
    dt = param_dtype(cfg)
    H, L = cfg.hidden_size, cfg.num_layers
    F, V = cfg.intermediate_size, cfg.vocab_size

    def mk(name: str, shape: tuple[int, ...], scale: float | None = None) -> jax.Array:
        # zlib.crc32 is stable across processes (Python's hash() is salted).
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2**31))
        if scale is None:
            scale = shape[-2] ** -0.5 if len(shape) >= 2 else 1.0
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    def norm_w(name: str, shape: tuple[int, ...]) -> jax.Array:
        return norm_weight(cfg, mk, dt, name, shape)

    kinds = mixer_kinds(cfg)

    def layer_stack(n: int, moe: bool, prefix: str = "",
                    attention: bool = True,
                    n_ffn: int | None = None) -> dict[str, jax.Array]:
        """n stacked layers: the model's one mixer kind + dense-MLP or MoE.
        ``attention`` False leaves the mixer's weights out (a model whose
        layers differ in their mixer stacks those per kind). ``n_ffn``: how
        many of the layers have an FFN, whose leaves (``is_ffn_leaf``) are
        stacked over those layers alone."""

        def mkp(name, shape, scale=None):
            return mk(prefix + name, shape, scale)

        layers: dict[str, jax.Array] = {
            "input_norm": norm_w(prefix + "input_norm", (n, H)),
        }
        if attention:
            layers.update(kinds[0].init(cfg, n, mkp, dt))
        n = n if n_ffn is None else n_ffn
        layers["post_norm"] = norm_w(prefix + "post_norm", (n, H))
        if moe:
            # The router scores every expert; the leaves hold the experts
            # this rank holds (all of them unless cfg says otherwise).
            Er, E, Fm = cfg.num_experts, cfg.held_experts, cfg.moe_intermediate_size
            layers["router"] = mkp("router", (n, H, Er), scale=H**-0.5)
            if cfg.router_scoring == "sigmoid":
                # V3-style selection-only correction bias (noaux_tc),
                # seeded: a zero bias would leave the selection untested.
                layers["router_bias"] = mkp(
                    "router_bias", (n, Er), scale=0.1
                ).astype(jnp.float32)
            elif cfg.router_logit_bias:
                # gpt-oss's real logit bias: the leaf must exist in the
                # init tree (load_params' shape contract).
                layers["router_bias"] = jnp.zeros((n, Er), jnp.float32)
            gated = cfg.moe_activation != "relu2"
            if gated:
                layers["we_gate"] = mkp("we_gate", (n, E, H, Fm))
            layers["we_up"] = mkp("we_up", (n, E, H, Fm))
            layers["we_down"] = mkp("we_down", (n, E, Fm, H))
            if (pad := cfg.moe_storage_width - Fm) > 0:
                # Stored at a width the grouped kernel tiles: zero columns
                # and zero rows, exact (ModelConfig.moe_storage_width).
                layers["we_up"] = jnp.pad(
                    layers["we_up"], ((0, 0), (0, 0), (0, 0), (0, pad))
                )
                layers["we_down"] = jnp.pad(
                    layers["we_down"], ((0, 0), (0, 0), (0, pad), (0, 0))
                )
            if cfg.moe_activation == "swiglu_oss":
                layers["we_gate_b"] = jnp.zeros((n, E, Fm), dt)
                layers["we_up_b"] = jnp.zeros((n, E, Fm), dt)
                layers["we_down_b"] = jnp.zeros((n, E, H), dt)
            if cfg.shared_expert_intermediate_size:
                Fs = cfg.shared_expert_intermediate_size
                if gated:
                    layers["ws_gate"] = mkp("ws_gate", (n, H, Fs))
                layers["ws_up"] = mkp("ws_up", (n, H, Fs))
                layers["ws_down"] = mkp("ws_down", (n, Fs, H))
                if cfg.shared_expert_gate:
                    layers["ws_sig"] = mkp("ws_sig", (n, H, 1))
        else:
            layers["w_gate"] = mkp("w_gate", (n, H, F))
            layers["w_up"] = mkp("w_up", (n, H, F))
            layers["w_down"] = mkp("w_down", (n, F, H))
        return layers

    n_dense = cfg.first_dense_layers if cfg.is_moe else 0
    params: dict = {
        "embed": mk("embed", (V, H), scale=0.02),
        "layers": layer_stack(
            L - n_dense, moe=cfg.is_moe, attention=kinds[0].stack == "layers",
            n_ffn=None if cfg.layer_ffn is None else len(cfg.ffn_layers),
        ),
        "final_norm": norm_w("final_norm", (H,)),
    }
    for kind in dict.fromkeys(k for k in kinds if k.stack != "layers"):
        # A per-kind stack beside the one every layer shares (norms, router,
        # experts): a layer indexes its kind's by its plane.
        params[kind.stack] = kind.init(cfg, kinds.count(kind), mk, dt)
    if n_dense:
        params["dense_layers"] = layer_stack(n_dense, moe=False, prefix="dense_")
    if not cfg.tie_word_embeddings:
        params["lm_head"] = mk("lm_head", (H, V))
    if cfg.quantization == "int8":
        from llmd_tpu.ops.quant import quantize_param_tree

        # ONE jitted call with the bf16 tree donated: eager per-tensor
        # quantization leaves the device arena fragmented enough that the
        # first big prefill later OOMs (observed on v5e at 3B scale).
        params = jax.jit(quantize_param_tree, donate_argnums=0)(params)
    return params


def _mlp(h: jax.Array, lp: dict) -> jax.Array:
    if "w_gu" in lp:  # fused gate|up (runner._maybe_fuse; lossless)
        gu = pdot(h, lp, "w_gu")
        F = gu.shape[-1] // 2
        return pdot(jax.nn.silu(gu[..., :F]) * gu[..., F:], lp, "w_down")
    gate = jax.nn.silu(pdot(h, lp, "w_gate"))
    return pdot(gate * pdot(h, lp, "w_up"), lp, "w_down")


# The attention kind of a model whose layers differ in their mixer: the
# grouped-query kind over a stack of its own.
ATTENTION = attention.KIND._replace(stack="attn_layers")


def mixer_kinds(cfg: ModelConfig) -> tuple[MixerKind, ...]:
    """Each layer's mixer kind. A model of one kind has it L times, over the
    shared stack (``stack == "layers"``: its leaves lie with the norms)."""
    if not cfg.state_space:
        if cfg.is_mla:
            kind = mla_dsa.KIND if cfg.sparse_attention else mla.KIND
        else:
            kind = dsa.KIND if cfg.sparse_attention else attention.KIND
        return (kind,) * cfg.num_layers
    if cfg.delta_rule:
        from llmd_tpu.models import gdn

        by_type = {"linear_attention": gdn.KIND, "full_attention": ATTENTION}
    else:
        from llmd_tpu.models import mamba

        by_type = {"mamba": mamba.KIND, "attention": ATTENTION}
    return tuple(by_type[t] for t in cfg.layer_types)


def is_ffn_leaf(name: str) -> bool:
    """Leaves of ``params["layers"]`` that belong to a layer's FFN (its
    pre-norm, router, experts, shared expert, dense MLP): stacked over the
    layers that HAVE one (``ModelConfig.layer_ffn``), indexed by the layer's
    place among those. Every other leaf is stacked over all layers."""
    return name == "post_norm" or name.startswith(
        ("router", "we_", "ws_", "w_gate", "w_up", "w_down", "w_gu")
    )


def _scan_period(kinds: tuple[int, ...]) -> int | None:
    """Smallest period c <= 4 of a layer-kind pattern (None if aperiodic).

    gpt-oss alternates sliding/full every layer (c=2); periodic patterns
    let the hybrid-pool scan run over CYCLES with the pool choice static
    per sub-layer — no lax.cond, so XLA keeps both pool carries in place.
    The bound c <= 4 is the longest cycle whose body is worth unrolling: a
    longer period (one attention layer in ten, or a pattern no deeper than
    its period) falls to the aperiodic branch, one scan per homogeneous run
    (period 10 at depth 10: three runs, 5 + 1 + 4). A model whose layers
    differ in their MIXER cycles too, by ``_kind_cycles``.
    """
    n = len(kinds)
    for c in (2, 3, 4):
        if n % c == 0 and n > c and all(kinds[i] == kinds[i % c] for i in range(n)):
            return c
    return None


def _kind_cycles(kinds: tuple) -> tuple[int, int] | None:
    """(c, n): the layer pattern ``kinds`` (any hashable a layer: its mixer
    kind and whether it has an FFN) STARTS with n >= 2 whole cycles of c <= 4
    layers that are not all alike, the (c, n) that covers most layers; None
    where no such cycle repeats. What lies behind the cycles is the caller's
    to peel. Nemotron-H's ``[ME][ME][M.][*E]`` has c = 4; one attention layer
    in ten (or a homogeneous run, whose period is 1) has none."""
    best = None
    for c in (2, 3, 4):
        if len(set(kinds[:c])) < 2:
            continue
        n = 1
        while kinds[n * c : (n + 1) * c] == kinds[:c]:
            n += 1
        if n >= 2 and (best is None or n * c > best[0] * best[1]):
            best = (c, n)
    return best


def forward_hidden(
    params: dict,
    kv_cache: jax.Array,  # [L_full, pages, K * kv_rep, page, 2D]
    inp: StepInput,
    cfg: ModelConfig,
    world_size: int = 1,
    mesh=None,
    moe_backend: str = "dense",
    ep_capacity_factor: float = 2.0,
    kv_rep: int = 1,
    dbo: bool = False,
    kv_swa: jax.Array | None = None,
    moe_overlap: int = 0,
    moe_placement: dict | None = None,
    moe_census: jax.Array | None = None,
    cp_prefill: int = 0,
):
    """Run the decoder stack; returns (hidden [B, Q, H], new kv_cache) —
    or (hidden, new kv_cache, new kv_swa) when ``kv_swa`` is given.
    When ``moe_census`` (the runner's accumulator) is given, the updated
    census is appended to the return tuple.

    ``moe_overlap``/``moe_placement``/``moe_census`` plumb the wide-EP
    perf layers into ``moe_block_ep`` (parallel/moe_ep.py): microbatched
    overlapped dispatch, the EPLB physical-placement tables, and the
    [E+2] per-expert routed-token / dropped-slot / dispatch-demand stats
    vector (merged across layers as a scan output: counts add, demand
    maxes). The first two are no-ops unless ``moe_backend == "ep"``. Under
    the one-device ``"grouped"`` backend ``moe_census`` is the [2] i32
    count of the grouped expert matmul (ops/grouped_gemm.py::
    grouped_census: grouped MoE layer calls, groups with rows as the
    kernel sees them; both add) and rides the same scan output.

    ``kv_swa`` (CacheConfig.swa_ring) is a second, smaller pool holding
    ONLY the sliding-window layers; those layers index it through
    ``inp.swa_page_table``, the ring-view table whose entries repeat
    modulo the per-sequence ring length. The attention kernels are
    unchanged: their window-skip never reads logical pages older than the
    window, which are exactly the ring slots that have been overwritten.

    ``moe_backend="ep"`` routes MoE layers through the shard_map all-to-all
    dispatch/combine (wide-EP; requires ``mesh``). ``kv_rep`` and
    ``cp_prefill`` are the grouped-query kind's (models/attention.py).

    ``dbo`` (dual-batch overlap — the reference's --enable-dbo for wide-EP
    decode, wide-ep decode.yaml:125-126): each layer writes KV for the
    FULL batch once, then runs the read-only attention + FFN pipeline as
    two independent half-batch chains. Half 1's attention carries no data
    dependency on half 0's MoE dispatch, so XLA's latency-hiding
    scheduler can overlap the EP all-to-all of one half with the other
    half's attention compute. Half-batch EP calls get a doubled
    capacity_factor so absolute per-expert capacity matches the full
    batch; numerics are then exact unless EP capacity binds (a half's
    routing demand is compared against full capacity separately, so DBO
    can only drop FEWER tokens, never different ones below capacity).
    Requires an even batch."""
    B, Q = inp.token_ids.shape
    x = params["embed"][inp.token_ids]  # [B, Q, H]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    # One rope table per KIND of layer (``cfg.rope_specs``: the model's own,
    # then each one ``rope_parameters`` gives a kind to itself), built once
    # and hoisted out of the scans; MLA rotates only its rope sub-dim. Which
    # of them a layer takes is ``cfg.layer_rope`` (None: it does not rotate).
    rope_dim = cfg.qk_rope_head_dim if cfg.is_mla else cfg.rotary_dim
    ropes = tuple(
        rope_tables(inp.positions, rope_dim, theta, scaling)
        for theta, scaling in cfg.rope_specs
    )
    rope_static = cfg.layer_rope
    res_mult = cfg.residual_multiplier

    def _res(y):
        """A residual branch under the model's multiplier (1 for most)."""
        return y if res_mult == 1.0 else y * res_mult

    def norm(x, w):
        return rms_norm(x, w, cfg.rms_norm_eps, cfg.norm_zero_centered)

    # DBO also requires the HALF batch to stay dp-divisible, or the split
    # would silently demote attention from the sharded Pallas kernel to
    # the pool-slicing XLA fallback (ops._mesh_plan's B % dp gate) —
    # slower and memory-hungrier, the opposite of the knob's intent.
    _dp = mesh.shape["dp"] if mesh is not None and "dp" in mesh.axis_names else 1
    # Flattened-token layout (inp.token_rows): the batch axis IS the
    # packed token stream; attention/writes route through the cu_q_lens
    # entry points below. DBO keeps the bucketed layout only (its
    # half-batch table slicing assumes per-row tables).
    flat = inp.token_rows is not None
    if cfg.state_space and (not flat or inp.state_rows is None or kv_swa is None):
        raise NotImplementedError(
            f"{cfg.name}: state-space layers run on the flat step only, with "
            "the state pool and the rows' slots; this program has no state"
        )
    use_dbo = (
        bool(dbo) and not flat and B >= 2 and B % 2 == 0
        and (B // 2) % _dp == 0
    )
    half = B // 2
    layer_kinds = mixer_kinds(cfg)
    # What every layer's ``mix`` is handed: built once. ``cp``: the ring
    # degree where this program's attention may run as a ring (a kind that
    # has no ring ignores it).
    step = StepCtx(
        cfg=cfg, inp=inp, mesh=mesh, world_size=world_size, kv_rep=kv_rep,
        ropes=ropes, valid=inp.valid,
        cp=cp_prefill if (
            cp_prefill > 1 and mesh is not None and not flat and not use_dbo
            and Q % cp_prefill == 0
        ) else 0,
        hoisted={k: k.hoist(cfg, inp) for k in dict.fromkeys(layer_kinds)},
    )

    grouped = moe_backend == "grouped" and world_size == 1
    use_census = (
        moe_census is not None and cfg.is_moe
        and (moe_backend == "ep" or grouped)
    )

    def _census_merge(a, b):
        if grouped:  # grouped_census: calls and groups with rows both add
            return a + b
        # Census layout (moe_ep): counts in [:-1] add, the max-demand
        # element in [-1] maxes.
        return jnp.concatenate([a[:-1] + b[:-1], jnp.maximum(a[-1:], b[-1:])])

    def _ffn(h2, lp, use_moe: bool, cap_scale: float = 1.0, moe_layer=None):
        """FFN/MoE of one slice; returns (y, census_delta | None).
        ``moe_layer``: the layer's index into ``lp``'s stacked expert
        leaves (the layer scans below do not slice them)."""
        if use_moe:
            if grouped:
                out = moe_block_grouped(
                    h2, lp, cfg, mesh=mesh, emit_census=use_census,
                    layer=moe_layer,
                )
                return out if use_census else (out, None)
            # The other backends consume the experts in XLA operations.
            lp = experts_of_layer(lp, moe_layer)
            if moe_backend == "ep":
                from llmd_tpu.parallel.moe_ep import moe_block_ep

                out = moe_block_ep(
                    h2, lp, cfg, mesh,
                    capacity_factor=ep_capacity_factor * cap_scale,
                    overlap=moe_overlap, placement=moe_placement,
                    emit_census=use_census,
                )
                return out if use_census else (out, None)
            # Sharded jit without the EP backend: the dense combine is
            # the only path GSPMD can partition (expert weights are
            # EP-sharded; the grouped kernel has no partitioning rule
            # — multi-device MoE should run moe_backend="ep", whose
            # shard_map body uses the grouped GEMM locally).
            return moe_block(h2, lp, cfg), None
        return _mlp(h2, lp), None

    def _tail(x_sl, attn_sl, lp, use_moe, cap_scale: float = 1.0,
              moe_layer=None):
        """Post-attention chain of one (micro)batch slice: residual +
        post-norm + FFN/MoE + residual. Returns (x, census_delta)."""
        x_sl = x_sl + _res(attn_sl)
        h2 = norm(x_sl, lp["post_norm"])
        y, cd = _ffn(h2, lp, use_moe, cap_scale, moe_layer)
        return x_sl + _res(y), cd

    def _tails_dbo(pairs):
        """Concatenate DBO half-chain _tail results; merge census deltas."""
        xs, cds = zip(*pairs)
        cd = cds[0]
        for c in cds[1:]:
            cd = c if cd is None else _census_merge(cd, c)
        return jnp.concatenate(xs, axis=0), cd

    def layer_body(x, cache, lp, kind: MixerKind, layer: LayerCtx,
                   use_moe: bool, ffn: bool = True):
        """One decoder layer: norm, the kind's ``mix``, residual, FFN tail;
        returns (x, cache, census_delta | None). ``cache`` is the kind's,
        ``layer.plane`` the layer's plane of it; ``layer.moe_layer`` its
        index into ``params["layers"]``, whose expert leaves ``lp`` holds
        whole (the dense prefix shifts one against the other). ``ffn``
        False: the mixer's residual branch alone (a layer without FFN, or a
        caller that runs the FFN itself)."""
        h = norm(x, lp["input_norm"])
        if use_dbo:
            # DBO: one full-batch write, then two independent
            # read-only half chains (attention -> MoE).
            cache, read, project = kind.halves(h, lp, cache, step, layer)
            outs = []
            for sl in (slice(0, half), slice(half, B)):
                attn_sl = read(sl)
                outs.append(_tail(
                    x[sl], project(attn_sl), lp, use_moe, 2.0, layer.moe_layer
                ))
            x2, cd = _tails_dbo(outs)
            return x2, cache, cd
        out, cache = kind.mix(h, lp, cache, step, layer)
        x = x + _res(out)
        if not ffn:
            return x, cache, None
        # the mixer's residual already applied above; _tail adds 0
        x, cd = _tail(x, 0.0, lp, use_moe, moe_layer=layer.moe_layer)
        return x, cache, cd

    # DeepSeek-style dense prefix: the first N layers (N static, 1-3)
    # run unrolled with their own dense-MLP weights; the homogeneous MoE
    # (or dense) remainder rides lax.scan with the cache(s) as CARRY —
    # the layer-indexed kernels write/read cache[plane] in place so no
    # pool-sized slice ever materializes.
    n_dense = cfg.first_dense_layers if cfg.is_moe else 0
    # Per-layer sliding windows (gpt-oss alternating / Qwen2 upper-layer /
    # Mistral uniform patterns); None for full-attention models keeps the
    # scan signature (and compile cache) unchanged.
    sliding = cfg.sliding_window > 0 and not cfg.is_mla
    win_static = cfg.layer_windows
    windows = jnp.asarray(win_static, jnp.int32) if sliding else None
    # Layer-group assignment. Without the ring every layer shares one pool
    # and its plane is the global layer id; with it, sliding layers index
    # their own pool (planes count within the group) via the ring table.
    ring = kv_swa is not None and sliding
    kinds = tuple(1 if (ring and w > 0) else 0 for w in win_static)
    # The model's layers name more than one stack: each kind's leaves lie
    # in its own, beside the stack every layer shares.
    per_kind = len({k.stack for k in layer_kinds}) > 1
    if per_kind:
        # Each mixer kind names its cache (the second pool is the state
        # pool of the state-space mixers; attention keeps the paged pool).
        kinds = tuple(k.pool for k in layer_kinds)
    plane, counts = [], [0, 0]
    for knd in kinds:
        plane.append(counts[knd])
        counts[knd] += 1
    caches = [kv_cache, kv_swa]
    tables = [inp.page_table, inp.swa_page_table]
    # Flattened layout: the run plan shares (src, off, cnt) across pools;
    # only the physical page per run differs (main table vs ring view).
    run_physes = [None, None]
    if flat and inp.flat_runs is not None:
        run_physes = [inp.flat_runs[1], inp.flat_runs[2]]

    census = moe_census if use_census else None

    mixes_kinds = sliding and len({w > 0 for w in win_static}) == 2

    def kind_name(i: int):
        """"window" | "full" for layer ``i`` where the model mixes the two."""
        if not mixes_kinds:
            return None
        return "window" if win_static[i] > 0 else "full"

    for i in range(n_dense):
        lp_i = jax.tree.map(lambda a: a[i], params["dense_layers"])
        g = kinds[i]
        x, caches[g], _ = layer_body(
            x, caches[g], lp_i, layer_kinds[i], LayerCtx(
                plane=jnp.int32(plane[i]), table=tables[g],
                run_phys=run_physes[g],
                window=None if windows is None else windows[i],
                rope=rope_static[i], attn_kind=kind_name(i),
            ), use_moe=False,
        )

    n_scan = cfg.num_layers - n_dense
    scan_kinds = kinds[n_dense:]
    scan_ropes = rope_static[n_dense:]
    plane_arr = jnp.asarray(plane[n_dense:], jnp.int32)
    win_arr = windows[n_dense:] if windows is not None else None
    # The stacked expert leaves [L, E, ..] do not ride the scans as ``xs``:
    # XLA fuses a scanned slice into an XLA consumer and MATERIALISES it for
    # a Pallas one, all E x 3 x K x N of a layer read and written in every
    # layer of every step. The bodies close over the whole leaves (loop
    # invariant, like the rope table) and get the layer's index into them
    # beside its plane; the grouped kernel reads its layer in place.
    experts = {
        k: a for k, a in params["layers"].items() if k in STACKED_EXPERT_LEAVES
    }
    lp_all = {k: a for k, a in params["layers"].items() if k not in experts}
    layer_arr = jnp.arange(n_scan, dtype=jnp.int32)
    # Layers without FFN (cfg.layer_ffn): the FFN leaves are stacked over
    # the layers that have one, so such a model's layers carry a second
    # index. None where every layer has its FFN: the layer's id serves, and
    # the scans' signature is what it was.
    has_ffn = (True,) * n_scan if cfg.layer_ffn is None else cfg.layer_ffn
    ffn_arr = None
    if cfg.layer_ffn is not None:
        ffn_arr = jnp.asarray(
            [sum(has_ffn[: i + 1]) - 1 for i in range(n_scan)], jnp.int32
        )

    def layer_leaves(lid, fid, with_ffn: bool = True) -> dict:
        """One layer's slices of the leaves the scans do not carry."""
        return {
            k: jax.lax.dynamic_index_in_dim(
                a, fid if is_ffn_leaf(k) else lid, 0, keepdims=False
            )
            for k, a in lp_all.items() if with_ffn or not is_ffn_leaf(k)
        }

    def kind_leaves(kind: MixerKind, pid) -> dict:
        """A layer's slices of its kind's own stack (none where the model
        has the one kind, whose leaves lie in the shared stack)."""
        if not per_kind:
            return {}
        return {
            k: jax.lax.dynamic_index_in_dim(a, pid, 0, keepdims=False)
            for k, a in params[kind.stack].items()
        }

    def _reduce_census(stacked):
        """Reduce per-layer census deltas [n, E+2] into the accumulator:
        counts sum over layers; the demand element takes the max. (The
        grouped count's [n, 2] lines sum.)"""
        if grouped:
            return jnp.sum(stacked, axis=0)
        return jnp.concatenate([
            jnp.sum(stacked[:, :-1], axis=0),
            jnp.max(stacked[:, -1:], axis=0),
        ])

    def scan_group(x, cache, census, table, lp, plane_ids, layer_ids, wins,
                   kind: MixerKind, run_phys=None, rope=0, attn_kind=None,
                   ffn_ids=None, ffn: bool = True):
        """One homogeneous run of layers sharing a pool/table. The census
        delta rides the scan as a per-layer OUTPUT (stacked then reduced)
        so the carry signature — and the compile cache — only changes
        when the census is actually armed. ``lp`` None: the run is a PART
        of the stack, and its body indexes the whole leaves by the layer
        id (what a scan does with its ``xs``); a static slice of the
        leaves in front of the scan would be a copy of the run's weights
        in every step (5 ms of a 28 ms decode step at 6,144 wide, PERF.md
        section 6, PR 33). ``kind``: the run's MIXER kind; where the
        model's layers differ in it, its own stack is indexed by the
        layer's plane as the shared stack is by its id. ``ffn_ids`` / ``ffn``
        (a model with layers without FFN): the run's indices into the FFN
        leaves, and whether the run's layers have one. ``rope``: the run's
        table (``LayerCtx.rope``), or an array of one row a layer."""

        def fn(carry, scanned):
            x, cache = carry
            lp_s, pid, lid, per = scanned
            fid = per.get("ffn", lid)
            if lp_s is None:
                lp_s = layer_leaves(lid, fid, ffn)
            lp_s = {**lp_s, **kind_leaves(kind, pid)}
            x, cache, cd = layer_body(
                x, cache, {**lp_s, **experts}, kind, LayerCtx(
                    plane=pid, table=table, run_phys=run_phys,
                    window=per.get("window"), rope=per.get("rope", rope),
                    attn_kind=attn_kind, moe_layer=fid,
                ), use_moe=cfg.is_moe, ffn=ffn,
            )
            return (x, cache), cd

        per = {
            k: a for k, a in (("window", wins), ("ffn", ffn_ids)) if a is not None
        }
        if isinstance(rope, jax.Array):
            per["rope"] = rope
        scanned = (lp, plane_ids, layer_ids, per)
        (x, cache), cds = jax.lax.scan(fn, (x, cache), scanned)
        if census is not None and cds is not None:
            census = _census_merge(census, _reduce_census(cds))
        return x, cache, census

    if len(set(scan_kinds)) <= 1 and not per_kind:
        g = scan_kinds[0] if scan_kinds else 0
        rope = scan_ropes[0] if scan_ropes else 0
        if len(set(scan_ropes)) > 1:
            # The one place a layer's table is not static: ONE scan over
            # layers that share a pool and differ in their table (a model that
            # mixes kinds, served without the ring). A layer takes its row of
            # the stacked tables; the identity where its kind has none.
            step = step._replace(rope_stack=(
                jnp.stack([c for c, _ in ropes] + [jnp.ones_like(ropes[0][0])]),
                jnp.stack([s for _, s in ropes] + [jnp.zeros_like(ropes[0][1])]),
            ))
            rope = jnp.asarray(
                [len(ropes) if r is None else r for r in scan_ropes], jnp.int32
            )
        x, caches[g], census = scan_group(
            x, caches[g], census, tables[g], lp_all, plane_arr, layer_arr,
            win_arr, layer_kinds[0], run_physes[g], rope,
        )
    elif not per_kind and (
        c := _scan_period(tuple(zip(scan_kinds, scan_ropes)))
    ) is not None:
        # Hybrid periodic pattern (gpt-oss alternating, Mellum2's three
        # sliding layers to one full): ONE scan over CYCLES of c layers, both
        # pools in the carry. Within a cycle the pool, the window, the RoPE
        # table and the attention call's scope are static per position, so
        # both pool carries update in place every step. The leaves stay loop
        # invariants indexed by the scanned ids, as in ``scan_group``: a
        # reshaped ``xs`` would hand each cycle a copy of its c layers.
        n_cyc = n_scan // c

        def resh(a):
            return a.reshape(n_cyc, c)

        def cyc(carry, scanned):
            x, *cc = carry
            plane_c, layer_c = scanned
            cd_cyc = None
            for j in range(c):
                g = scan_kinds[j]  # periodic: the same for every cycle
                lid = layer_c[j]
                x, cc[g], cd = layer_body(
                    x, cc[g], {**layer_leaves(lid, lid), **experts},
                    layer_kinds[n_dense + j], LayerCtx(
                        plane=plane_c[j], table=tables[g],
                        run_phys=run_physes[g],
                        window=win_static[n_dense + j] if g else None,
                        rope=scan_ropes[j], moe_layer=lid,
                        attn_kind=kind_name(n_dense + j),
                    ), use_moe=cfg.is_moe,
                )
                if cd is not None:
                    cd_cyc = cd if cd_cyc is None else _census_merge(cd_cyc, cd)
            return (x, *cc), cd_cyc

        (x, caches[0], caches[1]), cds = jax.lax.scan(
            cyc, (x, caches[0], caches[1]), (resh(plane_arr), resh(layer_arr))
        )
        if census is not None and cds is not None:
            census = _census_merge(census, _reduce_census(cds))
    else:
        off = 0
        if per_kind and (cyc_n := _kind_cycles(
            tuple(zip(layer_kinds, has_ffn, rope_static))
        )) is not None:
            # A model whose BLOCKS are one mixer each (nemotron_h), as
            # layers of mixer (+ FFN where an expert block follows): the
            # whole cycles as ONE scan body, both pools in the carry, the
            # pool, the mixer and the FFN static per position of the cycle.
            # A block runs under its own scope, so the op profile splits a
            # cycle by kind. The leaves stay loop invariants indexed by the
            # scanned ids, as in ``scan_group``.
            c, n_cyc = cyc_n
            off = c * n_cyc

            def resh(a):
                return a[:off].reshape(n_cyc, c)

            def cyc(carry, scanned):
                x, *cc = carry
                plane_c, layer_c, ffn_c = scanned
                cd_cyc = None
                for j in range(c):
                    kind, g = layer_kinds[j], kinds[j]
                    lid, fid = layer_c[j], layer_c[j] if ffn_c is None else ffn_c[j]
                    lp_s = {
                        **layer_leaves(lid, fid, has_ffn[j]),
                        **kind_leaves(kind, plane_c[j]), **experts,
                    }
                    # A block's scope is its kind's: "mamba", "gdn", "attn".
                    name = kind.stack.removesuffix("_layers")
                    with jax.named_scope(f"llmd.block.{name}"):
                        x, cc[g], _ = layer_body(
                            x, cc[g], lp_s, kind, LayerCtx(
                                plane=plane_c[j], table=tables[g],
                                run_phys=run_physes[g], rope=rope_static[j],
                            ), use_moe=cfg.is_moe, ffn=False,
                        )
                    if has_ffn[j]:
                        with jax.named_scope(
                            "llmd.block.moe" if cfg.is_moe else "llmd.block.mlp"
                        ):
                            x, cd = _tail(
                                x, 0.0, lp_s, cfg.is_moe, moe_layer=fid
                            )
                        if cd is not None:
                            cd_cyc = (
                                cd if cd_cyc is None else _census_merge(cd_cyc, cd)
                            )
                return (x, *cc), cd_cyc

            (x, caches[0], caches[1]), cds = jax.lax.scan(
                cyc, (x, caches[0], caches[1]),
                (resh(plane_arr), resh(layer_arr),
                 None if ffn_arr is None else resh(ffn_arr)),
            )
            if census is not None and cds is not None:
                census = _census_merge(census, _reduce_census(cds))
        # Aperiodic hybrid (e.g. Qwen2 upper-layer sliding), and what lies
        # behind a per-kind model's whole cycles: contiguous homogeneous
        # runs, one scan each.
        while off < n_scan:
            g = scan_kinds[off]
            ln = 1
            while (
                off + ln < n_scan and scan_kinds[off + ln] == g
                and has_ffn[off + ln] == has_ffn[off]
                and scan_ropes[off + ln] == scan_ropes[off]
            ):
                ln += 1
            sl = slice(off, off + ln)
            x, caches[g], census = scan_group(
                x, caches[g], census, tables[g], None,
                plane_arr[sl], layer_arr[sl],
                win_arr[sl] if g and win_arr is not None else None,
                layer_kinds[n_dense + off], run_physes[g], scan_ropes[off],
                kind_name(n_dense + off),
                None if ffn_arr is None else ffn_arr[sl], has_ffn[off],
            )
            off += ln

    hidden = norm(x, params["final_norm"])
    out = (hidden, caches[0]) if kv_swa is None else (
        hidden, caches[0], caches[1]
    )
    if moe_census is not None:
        # Non-EP/non-MoE callers that still pass an accumulator get it
        # back unchanged — the runner's plumbing stays uniform.
        out = (*out, census if use_census else moe_census)
    return out


@jax.named_scope("llmd.lm_head")
def compute_logits(params: dict, hidden: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Project hidden states [N, H] -> logits [N, V] (f32 for sampling)."""
    if cfg.tie_word_embeddings:
        logits = (hidden @ params["embed"].T).astype(jnp.float32)
    else:
        logits = pdot(hidden, params, "lm_head").astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits
