"""Lower the main-path Pallas kernels with the TPU's own compiler, for a v5e
that is described and not attached (on-chip-measurement guide, section 2.3).

Interpret mode cannot see what Mosaic refuses — a DMA off the sublane
tiling, a vector type the chip lacks, a page table larger than SMEM — so
every kernel the serving step calls is compiled here at the real widths of
the models it serves. Nothing runs: a pass says the chip's compiler accepts
the kernel, never that its result is right (chip_smoke.py checks that on the
chip). Run this file alone; the cases take a second or two each.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from llmd_tpu.ops.grouped_gemm import grouped_matmul
from llmd_tpu.ops.kv_write import (
    write_kv_pages_decode_full,
    write_kv_pages_flat_full,
)
from llmd_tpu.ops.mla_decode import mla_decode_paged_attention_full
from llmd_tpu.ops.ragged_paged_attention import (
    decode_paged_attention_full,
    flat_paged_attention_full,
)

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
PAGE, PAGES = 16, 2048
# (layers, q heads, kv heads, head dim): llama-3.2-3b and qwen3-30b-a3b.
LLAMA = (28, 24, 8, 128)
QWEN3 = (48, 32, 4, 128)
# k-exaone-236b-a23b.1chip's ring pool: its 6 sliding layers, 64 q / 8 kv heads.
EXAONE = (6, 64, 8, 128)
# mellum2-12b-a2.5b.1chip's ring pool: its 21 sliding layers (a window of
# 1,024), 32 q / 4 kv heads; 24 rings of 73 pages + 40 sections of 65, seen
# through a ring-view table of 32,768-token rows.
MELLUM2 = (21, 32, 4, 128)
MELLUM2_RING_PAGES, MELLUM2_TABLE = 24 * 73 + 40 * 65, (34, 2048)
# Serving defaults: 64 sequences, a 2048-token step, 8192-token contexts:
# 96 flat rows x 512 pages, and up to 2064 tokens a step.
ROWS, SEQS, MAX_PAGES = 96, 64, 512


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    # llmd: allow(broad-except) -- whatever keeps the TPU compiler from describing a chip here (no libtpu, no plugin) means these cases cannot run: skip, with the reason
    except Exception as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return topo.devices[0]


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool(model, dtype):
    L, _, K, D = model
    return ((L, PAGES, K, PAGE, 2 * D), dtype)


def _scales(model):
    L, _, K, _ = model
    return ((L, PAGES, K, PAGE, 2), F32)


def _flat_attention(model, dtype, T, table=(ROWS, MAX_PAGES)):
    """The flat kernel without a window, as the step programs call it: with
    the host's shared-prefix runs as two more prefetch arrays of T."""
    _, H, _, D = model
    args = [
        ((T, 1, H, D), BF16), _pool(model, dtype), ((), I32), ((T,), I32),
        (table, I32), ((T,), I32), ((T,), I32), ((T,), I32),
    ]
    if dtype == I8:
        return (
            lambda q, kv, l, r, pt, kl, lead, blocks, sc: flat_paged_attention_full(
                q, kv, l, r, pt, kl, scales=sc, runs=(lead, blocks)
            ),
            args + [_scales(model)],
        )
    return (
        lambda q, kv, l, r, pt, kl, lead, blocks, **kw: flat_paged_attention_full(
            q, kv, l, r, pt, kl, runs=(lead, blocks), **kw
        ),
        args,
    )


def _window_attention(T, model=EXAONE, pool_pages=PAGES, table=(ROWS, MAX_PAGES)):
    """The flat kernel with a window, as the sliding layers of a model that
    mixes the two kinds call it (``window`` a traced per-layer scalar): the
    16-token tile with each token's window start as a fifth prefetch array.
    ``table`` is the ring VIEW's shape (logical page -> the ring's page):
    as wide as the main table, whatever the ring holds."""
    L, H, K, D = model
    return (
        lambda q, kv, l, r, pt, kl, w: flat_paged_attention_full(
            q, kv, l, r, pt, kl, window=w
        ),
        [
            ((T, 1, H, D), BF16), ((L, pool_pages, K, PAGE, 2 * D), BF16),
            ((), I32), ((T,), I32), (table, I32), ((T,), I32), ((), I32),
        ],
    )


def _sink_attention(T):
    """The flat kernel with attention sinks (gpt-oss: 64 q / 8 kv heads of
    64, a sink logit a query head): a tile carries them once a token."""
    model = (24, 64, 8, 128)
    fn, args = _flat_attention(model, BF16, T)
    return (
        lambda q, kv, l, r, pt, kl, lead, blocks, s: fn(
            q, kv, l, r, pt, kl, lead, blocks, sinks=s
        ),
        args + [((model[1],), F32)],
    )


def _sparse_attention(T):
    """The flat kernel under an indexer's mask, at the geometry of
    keye-vl-2.0-30b-a3b.1chip: 6 layers of 24,576 pages, 34 flat rows x 2,048
    pages (32,768-token contexts), the mask a [T, 32768] plane."""
    L, H, K, D = 6, 32, 4, 128
    rows, max_pages = 34, 2048
    return (
        lambda q, kv, l, r, pt, kl, lead, blocks, sel: flat_paged_attention_full(
            q, kv, l, r, pt, kl, sel=sel, runs=(lead, blocks)
        ),
        [
            ((T, 1, H, D), BF16), ((L, 24576, K, PAGE, 2 * D), BF16), ((), I32),
            ((T,), I32), ((rows, max_pages), I32), ((T,), I32), ((T,), I32),
            ((T,), I32), ((T, max_pages * PAGE), jnp.bool_),
        ],
    )


def _indexer(T, J=16, Di=64, pages=24576):
    """The indexer's scoring at the same geometry: one layer's plane of
    24,576 pages of [16, 64] keys, 34 flat rows x 2,048 pages in SMEM, 16
    index heads a token. (deepseek-v3.2.1chip: 64 heads x 128, no lane pad,
    over all five layers' planes as one of 5 x 24,576 pages.)"""
    from llmd_tpu.ops.sparse_attention import index_scores_pallas

    rows, max_pages = 34, 2048

    def under_the_references_precision(*args):
        # The benchmark's comparison calls the scoring inside the reference's
        # ``default_matmul_precision("highest")``, which reaches the kernel's
        # dot: the chip's compiler refuses that for bfloat16 operands
        # ("Bad lhs type") unless the kernel states its own.
        with jax.default_matmul_precision("highest"):
            return index_scores_pallas(*args)

    return under_the_references_precision, [
        ((T, J, Di), BF16), ((T, J), BF16), ((pages, PAGE, Di), BF16),
        ((rows, max_pages), I32), ((T,), I32), ((T,), I32),
    ]


def _latent_write(T, device):
    """The flat write of deepseek-v3.2.1chip: the stream's latent rows
    (640 lanes, one "head") and indexer keys (128 lanes) through the run plan
    into layer ``l`` of both planes of the pool, in place."""
    from llmd_tpu.ops.sparse_attention import IndexedPool
    from llmd_tpu.ops.sparse_mla import write_latent_rows_full_flat

    L, pages, Dl, Di, rows, max_pages = 5, 24576, 640, 128, 34, 2048
    runs = 2 * rows + -(-T // PAGE)

    def write(kv, index, l, latent, keys, pt, r, pos, valid, src, off, cnt, phys):
        pool = write_latent_rows_full_flat(
            IndexedPool(kv=kv, index=index), l, latent, keys, pt, r, pos,
            valid, (src, off, cnt, phys), mesh=_one_chip_mesh(device),
        )
        return pool.kv, pool.index

    return write, [
        ((L, pages, 1, PAGE, Dl), BF16), ((L, pages, PAGE, Di), BF16), ((), I32),
        ((T, Dl), BF16), ((T, Di), BF16), ((rows, max_pages), I32),
        ((T,), I32), ((T,), I32), ((T,), jnp.bool_), *[((runs,), I32)] * 4,
    ]


def _decode_attention(model, dtype):
    _, H, _, D = model
    args = [
        ((SEQS, 1, H, D), BF16), _pool(model, dtype), ((), I32),
        ((SEQS, MAX_PAGES), I32), ((SEQS,), I32),
    ]
    if dtype == I8:
        return (
            lambda q, kv, l, pt, kl, sc: decode_paged_attention_full(
                q, kv, l, pt, kl, scales=sc
            ),
            args + [_scales(model)],
        )
    return decode_paged_attention_full, args


def _flat_write(model, dtype, T):
    _, _, K, D = model
    runs = 2 * ROWS + -(-T // PAGE)  # the runner's bound on runs a step
    return write_kv_pages_flat_full, [
        _pool(model, dtype), ((T, K, 2 * D), dtype), ((), I32),
        *[((runs,), I32)] * 4,
    ]


def _decode_write(model, dtype):
    _, _, K, D = model
    return write_kv_pages_decode_full, [
        _pool(model, dtype), ((SEQS, K, 2 * D), dtype), ((), I32),
        *[((SEQS,), I32)] * 3,
    ]


def _mla_decode():
    # deepseek-v2-lite: 16 heads over a 512 + 64 latent padded to 640 lanes.
    H, Dl, rank = 16, 640, 512
    return (
        functools.partial(
            mla_decode_paged_attention_full, rank=rank, sm_scale=0.1
        ),
        [
            ((SEQS, 1, H, Dl), BF16), ((27, PAGES, 1, PAGE, Dl), BF16),
            ((), I32), ((SEQS, MAX_PAGES), I32), ((SEQS,), I32),
        ],
    )


def _one_chip_mesh(device):
    # The grouped GEMM picks its kernel from the devices of the mesh it is
    # given: hand it the described chip (the default backend is the CPU).
    return Mesh(np.asarray([device]).reshape(1, 1), ("dp", "tp"))


def _gmm(hidden, ffn, experts, device):
    # The weight operand is the stacked leaf of all layers and a layer index,
    # as forward_hidden hands it over (two layers stand for any number: the
    # block is one layer's one expert's). The tile comes from
    # grouped_gemm.gmm_tiles: a weight block that overflows VMEM shows here
    # (the call sets no limit of its own).
    mesh = _one_chip_mesh(device)
    return (
        lambda x, w, g, layer: grouped_matmul(x, w, g, mesh, layer),
        [((2048, hidden), BF16), ((2, experts, hidden, ffn), BF16),
         ((experts,), I32), ((), I32)],
    )


# (hidden, expert width, experts): the two expert shapes of the benchmark's
# cells, and the registry's shapes whose whole K does not fit VMEM beside a
# wide tile. Each compiles as gate/up (hidden -> width) and down.
GMM_MODELS = {
    "qwen3-30b-a3b": (2048, 768, 128),
    "deepseek-v2-lite": (2048, 1408, 64),
    "mixtral-8x7b": (4096, 14336, 8),
    "mixtral-8x22b": (6144, 16384, 8),
    "deepseek-r1": (7168, 2048, 256),
    # one rank's 16 of the 128 experts (the held share)
    "k-exaone-236b-a23b": (6144, 2048, 16),
    # one rank's 36 of the 72 experts
    "granite-4.0-h-small": (4096, 768, 36),
    # one rank's 16 of the 128 non-gated experts, stored at 1,920 = 15 x 128
    # lanes for their 1,856 (ModelConfig.moe_storage_width)
    "nemotron-3-nano-30b-a3b": (2688, 1920, 16),
}
# granite-4.0-h-small.1chip's attention layer (1 layer, 32 q / 8 kv heads) and
# its state pool: 9 mixers x 97 slots of 128 heads x 64 x state 128.
GRANITE = (1, 32, 8, 128)
STATE_POOL = (9, 97, 128, 64, 128)


# nemotron-3-nano-30b-a3b.1chip's state pool: 12 mixers x 257 slots of 64 heads
# x 64 x state 128, B and C in 8 groups: a head block of 32 spans four.
GROUPED_STATE_POOL, GROUPS = (12, 257, 64, 64, 128), 8


def _ssm_update(rows, pool=STATE_POOL, groups=0):
    from llmd_tpu.ops.ssm import ssm_update_pallas

    _, _, H, P, N = pool
    bc = (rows, groups, N) if groups else (rows, N)
    return ssm_update_pallas, [
        (pool, F32), ((), I32), ((rows,), I32), ((), I32), ((rows, H), F32),
        ((rows, H, P), F32), (bc, F32), (bc, F32),
    ]


def _layer_metric(name: str):
    """The benchmark's reader ``perfbench/layer_metrics/<name>.py`` as a module."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "perfbench" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def _first_pool_of_each_update(hlo_text: str) -> set:
    """What the benchmark's two update rooflines take H, P and N from
    (``perfbench/layer_metrics/kernels.ssm_update_roofline.py`` and
    ``kernels.ssm_grouped_update_roofline.py``): the FIRST five-dimensional
    ``f32[...]`` in the operand text of every ``%llmd.ssm.update`` call, found
    with the reader's own pattern."""
    reader = _layer_metric("kernels.ssm_grouped_update_roofline")
    calls = [ln.strip() for ln in hlo_text.splitlines()
             if "tpu_custom_call" in ln and ln.strip().startswith("%llmd.ssm.update")]
    assert calls
    return {tuple(int(x) for x in reader.POOL.search(ln.partition(" custom-call(")[2]).groups()) for ln in calls}


def _ssm_slot(write: bool):
    from llmd_tpu.ops.ssm import read_slot, write_slot

    if write:
        return (lambda p, l, s, v: write_slot(p, l, s, v, "pallas")), [
            (STATE_POOL, F32), ((), I32), ((), I32), (STATE_POOL[2:], F32)]
    return (lambda p, l, s: read_slot(p, l, s, "pallas")), [(STATE_POOL, F32), ((), I32), ((), I32)]


# qwen3-next-80b-a3b.1chip: 3 attention layers of 16 q / 2 kv heads at head
# size 256 (the first the flat kernels meet); its state pool: 9 delta-rule
# layers x 121 slots of 32 value heads x a [128, 128] state.
QWEN3_NEXT = (3, 16, 2, 256)
DELTA_POOL = (9, 121, 32, 128, 128)


def _gdn_update(rows):
    from llmd_tpu.ops.gdn import gdn_update_pallas

    _, _, H, Dk, Dv = DELTA_POOL
    return gdn_update_pallas, [
        (DELTA_POOL, F32), ((), I32), ((rows,), I32), ((), I32), ((rows, H), F32), ((rows, H), F32),
        ((rows, H, Dk), F32), ((rows, H, Dk), F32), ((rows, H, Dv), F32),
    ]


def _gdn_slot(write: bool):
    from llmd_tpu.ops.gdn import read_slot, write_slot

    if write:
        return (lambda p, l, s, v: write_slot(p, l, s, v, "pallas")), [
            (DELTA_POOL, F32), ((), I32), ((), I32), (DELTA_POOL[2:], F32)]
    return (lambda p, l, s: read_slot(p, l, s, "pallas")), [(DELTA_POOL, F32), ((), I32), ((), I32)]


CASES = {
    "flat_attention-qwen3-next-80b-a3b": lambda d: _flat_attention(QWEN3_NEXT, BF16, 144),
    "flat_write-qwen3-next-80b-a3b": lambda d: _flat_write(QWEN3_NEXT, BF16, 144),
    "gdn_update-qwen3-next-80b-a3b": lambda d: _gdn_update(32),
    "gdn_slot_read-qwen3-next-80b-a3b": lambda d: _gdn_slot(False),
    "gdn_slot_write-qwen3-next-80b-a3b": lambda d: _gdn_slot(True),
    "flat_attention-bf16": lambda d: _flat_attention(LLAMA, BF16, 2064),
    "flat_attention-int8": lambda d: _flat_attention(LLAMA, I8, 256),
    "flat_attention-qwen3-30b-a3b": lambda d: _flat_attention(QWEN3, BF16, 256),
    "flat_attention-qwen3-30b-a3b-528": lambda d: _flat_attention(QWEN3, BF16, 528),
    "flat_attention-sinks": lambda d: _sink_attention(256),
    "sparse_attention-keye-vl-2.0-30b-a3b": lambda d: _sparse_attention(144),
    "indexer-keye-vl-2.0-30b-a3b": lambda d: _indexer(144),
    "indexer-deepseek-v3.2": lambda d: _indexer(144, J=64, Di=128, pages=5 * 24576),
    "latent_write-deepseek-v3.2": lambda d: _latent_write(144, d),
    "flat_attention-k-exaone-236b-a23b": lambda d: _flat_attention(EXAONE, BF16, 528),
    "window_attention-k-exaone-236b-a23b": lambda d: _window_attention(528),
    "window_attention-mellum2-12b-a2.5b": lambda d: _window_attention(
        128, MELLUM2, MELLUM2_RING_PAGES, MELLUM2_TABLE
    ),
    "flat_write-k-exaone-236b-a23b": lambda d: _flat_write(EXAONE, BF16, 528),
    "flat_write-bf16": lambda d: _flat_write(LLAMA, BF16, 2064),
    "flat_write-int8": lambda d: _flat_write(LLAMA, I8, 256),
    "flat_write-qwen3-30b-a3b": lambda d: _flat_write(QWEN3, BF16, 256),
    "decode_attention-bf16": lambda d: _decode_attention(LLAMA, BF16),
    "decode_attention-int8": lambda d: _decode_attention(LLAMA, I8),
    "decode_write-bf16": lambda d: _decode_write(LLAMA, BF16),
    "decode_write-int8": lambda d: _decode_write(LLAMA, I8),
    "mla_decode-deepseek-v2-lite": lambda d: _mla_decode(),
    "flat_attention-granite-4.0-h-small": lambda d: _flat_attention(GRANITE, BF16, 528),
    # the full layers' call of mellum2-12b-a2.5b.1chip (7 of 28 layers; 34 flat
    # rows of 32,768 tokens) and nemotron-3-nano-30b-a3b.1chip's attention
    # blocks (2 kv heads of 16 queries: a tile's operand is 256 query rows)
    "flat_attention-mellum2-12b-a2.5b": lambda d: _flat_attention((7, 32, 4, 128), BF16, 128, (34, 2048)),
    "flat_attention-nemotron-3-nano-30b-a3b": lambda d: _flat_attention((6, 32, 2, 128), BF16, 144, (136, 256)),
    "flat_write-granite-4.0-h-small": lambda d: _flat_write(GRANITE, BF16, 528),
    "ssm_update-granite-4.0-h-small": lambda d: _ssm_update(40),
    "ssm_update-nemotron-3-nano-30b-a3b": lambda d: _ssm_update(136, GROUPED_STATE_POOL, GROUPS),
    "ssm_update-groups-inside-a-block": lambda d: _ssm_update(40, (2, 9, 64, 64, 128), 2),
    "ssm_slot_read-granite-4.0-h-small": lambda d: _ssm_slot(False),
    "ssm_slot_write-granite-4.0-h-small": lambda d: _ssm_slot(True),
    **{
        f"gmm-{name}": lambda d, m=m: _gmm(m[0], m[1], m[2], d)
        for name, m in GMM_MODELS.items()
    },
    **{
        f"gmm_down-{name}": lambda d, m=m: _gmm(m[1], m[0], m[2], d)
        for name, m in GMM_MODELS.items()
    },
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = CASES[case](v5e)
    on_chip = SingleDeviceSharding(v5e)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", [
    "flat_attention-qwen3-30b-a3b", "sparse_attention-keye-vl-2.0-30b-a3b",
    "flat_attention-k-exaone-236b-a23b", "window_attention-k-exaone-236b-a23b",
    "window_attention-mellum2-12b-a2.5b", "flat_attention-granite-4.0-h-small",
])
def test_the_attention_rooflines_can_read_the_tiled_call(v5e, case):
    """The benchmark's attention rooflines take a call's shapes from its HLO
    text (``perfbench/layer_metrics/kernels.*_attention_roofline.py``): the
    FIRST output ``bf16[T,K,G,D]``, the pool the only 5-dimensional
    operand. The tiled call tiles through its BlockSpecs, not through a
    reshape of the stream, so the reader still finds both."""
    reader = _layer_metric("kernels.sparse_attention_roofline")
    fn, shapes = CASES[case](v5e)
    on_chip = SingleDeviceSharding(v5e)
    hlo = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in shapes
    ]).compile().as_text()
    (call,) = [ln.strip() for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    T, _, H, D = shapes[0][0]
    K = shapes[1][0][2]
    assert call.split(" = ")[1].startswith(f"bf16[{T},{K},{H // K},{D}]")
    flops, nbytes = reader.call_cost(call, 2048)
    assert flops == 4.0 * T * 2048 * H * D and nbytes > 0


def test_a_layer_scan_hands_the_kernel_the_stacked_leaf(v5e):
    """What PR 32 removed, guarded without a chip: XLA fuses a scanned slice
    of the stacked expert leaf into an XLA consumer and MATERIALISES it for a
    Pallas one (`%dynamic-slice_bitcast_fusion`, the largest device operation
    of four cells of five). With the leaf closed over and the layer an
    operand, the custom call reads the 4-D parameter itself and nothing in
    the loop body makes one layer's ``[E, K, N]``."""
    L, E, K, N, T = 2, 128, 2048, 768, 256  # qwen3-30b-a3b's gate, two layers
    mesh = _one_chip_mesh(v5e)

    def step(x, w, sizes):
        def layer_body(x, layer):
            y = grouped_matmul(x, w, sizes, mesh, layer)
            return x + jnp.pad(y, ((0, 0), (0, K - N))), None

        return jax.lax.scan(layer_body, x, jnp.arange(L, dtype=I32))[0]

    on_chip = SingleDeviceSharding(v5e)
    hlo = jax.jit(step).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in [((T, K), BF16), ((L, E, K, N), BF16), ((E,), I32)]
    ]).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    name, operands = calls[0].split(" = ")[0], calls[0].split("custom-call(")[1]
    # The event's name is a contract: the benchmark's readers match ^%gmm.
    assert name.strip().startswith("%gmm")
    assert f"bf16[{L},{E},{K},{N}]" in operands
    leaf = operands.split(")")[0].split(", ")[-1].replace("/*index=5*/", "")
    # ... and that operand is the loop's pass-through of the parameter, not
    # a copy: the instruction that defines it is a get-tuple-element.
    assert leaf.startswith("%get-tuple-element"), leaf
    sizes = {
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"bf16\[([\d,]+)\]", hlo)
    }
    assert L * E * K * N in sizes and E * K * N not in sizes  # in any shape


def test_page_table_at_the_smem_bound_compiles(v5e):
    """``ops.page_table_smem`` refuses at start-up what this compiler would
    refuse at the first request: it knows the described chip by its
    ``device_kind``, and the largest table it lets through compiles."""
    from llmd_tpu import ops
    from llmd_tpu.config import ModelConfig

    L, H, K, D = LLAMA
    cfg = ModelConfig(num_heads=H, num_kv_heads=K, head_dim=D)
    mesh = Mesh(np.asarray([v5e]).reshape(1, 1), ("dp", "tp"))
    rows, tokens = 120, 2064

    def smem(rows):
        return ops.page_table_smem(
            cfg, PAGE, 2048, 1, mesh, decode_rows=SEQS, flat_rows=rows,
            flat_tokens=tokens,
        )

    need, have = smem(rows)
    assert need <= have < smem(rows + 8)[0]
    on_chip = SingleDeviceSharding(v5e)
    shapes = [
        ((tokens, 1, H, D), BF16), _pool(LLAMA, BF16), ((), I32),
        ((tokens,), I32), ((rows, 2048), I32), ((tokens,), I32),
        ((tokens,), I32), ((tokens,), I32),
    ]
    jax.jit(
        lambda q, kv, l, r, pt, kl, lead, blocks: flat_paged_attention_full(
            q, kv, l, r, pt, kl, runs=(lead, blocks)
        )
    ).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in shapes
    ]).compile()


def test_the_state_space_mixers_update_the_state_pool_in_place(v5e):
    """granite-4.0-h-small.1chip's nine mixers over its state pool (97 slots:
    32 running, 64 snapshots, the scan's scratch; 3.45 GiB), a saturated flat
    step of 528 tokens in 40 rows, under the layer scan that carries the pool:
    the Pallas calls are named after their scopes (the benchmark's readers
    match ``^%llmd\\.ssm\\.``), both pools come out aliased, and nothing
    copies the pool: the compiled program's temporaries stay under 0.5 GiB
    (an XLA slice of the pool inside the scan made them 3.4 GiB: the compiler
    re-laid the whole pool out, in and back, every step)."""
    from llmd_tpu.models import mamba
    from llmd_tpu.models.registry import get_model_config
    from llmd_tpu.ops import ssm

    cfg = get_model_config("granite-4.0-h-small", num_layers=10,
                           layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    Lm, S, T, B = 9, 97, 528, 40
    mesh = _one_chip_mesh(v5e)
    weights = jax.eval_shape(lambda: mamba.init_layers(
        cfg, Lm, lambda name, shape, scale=None: jnp.zeros(shape, BF16), BF16))

    def step(weights, pool, h, slot, start, qlen, pos0, kind):
        t = jnp.arange(T)
        ends = start + qlen
        row_of = jnp.clip(jnp.searchsorted(ends, t, side="right"), 0, B - 1).astype(I32)
        rows = ssm.state_rows(slot, start, qlen, pos0, kind, row_of, t < ends[-1])

        def layer(carry, l):
            h, pool = carry
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False), weights)
            out, pool = mamba.mix(h, lp, pool, l, rows, cfg, mesh)
            return (h + out, pool), None

        return jax.lax.scan(layer, (h, pool), jnp.arange(Lm, dtype=I32))[0]

    on_chip = SingleDeviceSharding(v5e)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)  # noqa: E731
    pool = ssm.StatePool(
        ssm=sds((Lm, S, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), F32),
        conv=sds((Lm, S, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim), BF16),
    )
    compiled = jax.jit(step, donate_argnums=1).lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), weights), pool,
        sds((T, 1, cfg.hidden_size), BF16), *[sds((B,), I32)] * 5,
    ).compile()
    calls = {ln.split(" = ")[0].strip().rstrip(".0123456789")
             for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln}
    assert calls == {"%llmd.ssm.update", "%llmd.ssm.scan"}
    # the update's smaller operands stand BEHIND the pool (or have fewer dimensions)
    assert _first_pool_of_each_update(compiled.as_text()) == {(Lm, S, 128, 64, 128)}
    m = compiled.memory_analysis()
    pool_bytes = Lm * S * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 0.5 * 2**30


@pytest.fixture(scope="module")
def nemotron_step(v5e):
    """nemotron-3-nano-30b-a3b.1chip's runner as shapes on the described chip
    (``perfbench/rehearse_compile_mixer.py``): 16 layers in four cycles, both
    pools at the cell's sizes."""
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from perfbench import rehearse_compile_mixer as rc
    from perfbench.topologies.engine_mixer import engine_config

    conf = json.loads((root / rc.CONFIG).read_text())
    return rc, rc.build_runner(engine_config(conf, 0, False), v5e)


@pytest.mark.parametrize("T", [128, 272])
def test_the_mixer_only_hybrids_steps_compile_with_both_pools_in_place(nemotron_step, T):
    """The decode-only step of 128 resident rows and the saturated step of the
    new geometry, for the described v5e: both pools aliased (2.0 GiB of pages,
    6.1 GiB of state), nothing copies them, the whole step under the chip's
    15.75 GiB; the Pallas calls carry the names the benchmark's readers match
    (``%gmm``, ``%llmd.ssm.update``, ``%llmd.ssm.scan``) and the operand forms
    they parse: the experts' stacked ``[L, E, K, N]`` leaf at the ``%gmm``
    calls (two a layer: up, down), the state pool ``f32[Lm, slots, H, P, N]``
    at the update."""
    rc, r = nemotron_step
    _lowered, compiled = rc.compile_step(r, T)
    text = compiled.as_text()
    calls = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    names = {ln.split(" = ")[0].rstrip(".0123456789") for ln in calls}
    assert {"%gmm", "%llmd.ssm.update", "%llmd.ssm.scan"} <= names
    # the cycle body's three FFN positions: an up and a down projection each
    gmm_shapes = [re.search(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]", ln.partition(" custom-call(")[2])
                  for ln in calls if ln.startswith("%gmm")]
    assert len(gmm_shapes) == 6 and all(gmm_shapes)
    assert {tuple(int(x) for x in m.groups()) for m in gmm_shapes} == {(12, 16, 2688, 1920), (12, 16, 1920, 2688)}
    update = [ln for ln in calls if ln.startswith("%llmd.ssm.update")]
    # the pool is the FIRST five-dimensional f32 of each, which is where the roofline's reader looks
    assert len(update) == 3 and _first_pool_of_each_update(text) == {(12, 257, 64, 64, 128)}
    for scope in ("llmd.block.mamba", "llmd.block.moe", "llmd.block.attn"):
        assert scope in text
    m = compiled.memory_analysis()
    pools = 4 * 33024 * 2 * 16 * 256 * 2 + 12 * 257 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert m.alias_size_in_bytes >= pools
    assert m.temp_size_in_bytes < 0.5 * 2**30
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
    assert total < 15.75 * 2**30


def test_a_one_group_models_step_keeps_its_pallas_calls(nemotron_step, v5e):
    """granite's architecture in miniature, its flat step lowered for the
    described chip: six Pallas calls, as before the mixer learned groups (the
    state update, the scan's slot read and write, the KV write, flat attention
    and the scan's second read), and the update's B and C the one-group
    operands: ``[rows, 1, 1, N]``, C's one row padded to the matrix unit's
    sublane tile of 8."""
    from llmd_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from llmd_tpu.models.registry import get_model_config

    rc, _r = nemotron_step
    config = EngineConfig(
        model=get_model_config("tiny-granite-hybrid"),
        cache=CacheConfig(page_size=16, num_blocks=256, dtype="bfloat16"),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=32),
    )
    r = rc.build_runner(config, v5e)
    lowered, _none = rc.compile_step(r, r.flat_t_buckets[-1], compile=False)
    text = lowered.as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 6
    update = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and "x4x8x16xf32>" in ln and ") -> (" in ln]
    assert update and all("x1x1x16xf32>" in ln and "x1x8x16xf32>" in ln for ln in update)


def test_the_delta_rule_hybrids_step_compiles_with_both_pools_in_place(v5e):
    """qwen3-next-80b-a3b.1chip's saturated step for the described v5e
    (``perfbench/rehearse_compile_gdn.py``): 12 layers in three cycles ``L L L
    F`` as ONE scanned body, both pools aliased (2.25 GiB of pages at head size
    256, 2.2 GiB of state), nothing copies them, the whole step under the
    chip's 15.75 GiB; the Pallas calls carry the names the benchmark's readers
    match (``%gmm``, ``%llmd.gdn.update``, ``%llmd.gdn.scan``, the attention
    layer's ``%llmd.block.attn``) and the operand forms they parse."""
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from perfbench import rehearse_compile_gdn as rc
    from perfbench.topologies.engine_gdn import engine_config

    conf = json.loads((root / rc.CONFIG).read_text())
    r = rc.build_runner(engine_config(conf, 0, False), v5e)
    assert list(r.flat_t_buckets)[:8] == list(range(16, 144, 16))
    _lowered, compiled = rc.mixer.compile_step(r, 144)
    text = compiled.as_text()
    calls = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    names = {ln.split(" = ")[0].rstrip(".0123456789") for ln in calls}
    assert {"%gmm", "%llmd.gdn.update", "%llmd.gdn.scan", "%llmd.block.attn"} <= names
    # the cycle body's four FFN positions: gate, up and down each
    gmm_shapes = [re.search(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]", ln.partition(" custom-call(")[2])
                  for ln in calls if ln.startswith("%gmm")]
    assert len(gmm_shapes) == 12 and all(gmm_shapes)
    assert {tuple(int(x) for x in m.groups()) for m in gmm_shapes} == {(12, 64, 2048, 512), (12, 64, 512, 2048)}
    update = [ln for ln in calls if ln.startswith("%llmd.gdn.update")]
    assert len(update) == 3 and all("f32[9,121,32,128,128]" in ln.partition(" custom-call(")[2] for ln in update)
    attn = [ln for ln in calls if ln.startswith("%llmd.block.attn")]
    assert len(attn) == 2 and all("bf16[3,24576,2,16,512]" in ln for ln in attn)  # the KV write and the flat attention
    for scope in ("llmd.block.gdn", "llmd.block.moe", "llmd.gdn.conv"):
        assert scope in text
    m = compiled.memory_analysis()
    pools = 3 * 24576 * 2 * 16 * 512 * 2 + 9 * 121 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert m.alias_size_in_bytes >= pools
    assert m.temp_size_in_bytes < 0.5 * 2**30
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
    assert total < 15.75 * 2**30
