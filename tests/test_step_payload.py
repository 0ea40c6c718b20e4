"""The packed step payload (engine/payload.py): one buffer, one transfer.

A step program's host inputs travel as ONE int32 buffer laid out by the
program kind's description. The bars: every dtype comes back bit for bit,
the layout is a function of the shapes alone, every step program gives the
tokens and log-probabilities it gives when handed its inputs field by field
(the hand-over this replaced, held here), a step costs one transfer, and a
warmed shape neither traces nor compiles again.
"""

import gc
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmd_tpu import jaxrt
from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams, payload
from llmd_tpu.engine.payload import ALIGN, PayloadLayout, step_fields

# (kind, B, QK) at shapes the tiny engines below really dispatch.
SHAPES = {
    "prefill": (4, 16),
    "verify": (2, 5),
    "unified": (8, (8 << 20) | 32),
    "flat": (9, 48),
    "decode": (8, 4),
}
F32_EDGES = np.array(
    [0.0, -0.0, 1.0, -1.0, 0.5, np.finfo(np.float32).tiny,
     np.finfo(np.float32).smallest_subnormal, np.finfo(np.float32).max,
     np.finfo(np.float32).eps, 1e-30, 0.999999, np.inf],
    np.float32,
)


def _random_field(rng, shape, dtype):
    dtype = np.dtype(dtype)
    n = int(np.prod(shape))
    if dtype == np.float32:
        a = rng.choice(F32_EDGES, size=n)
        a[n // 2 :] = rng.standard_normal(n - n // 2).astype(np.float32)
    elif dtype == np.uint32:
        a = rng.integers(0, 2**32, size=n, dtype=np.uint32)
        a[: min(n, 3)] = [2**32 - 1, 2**31, 2**31 + 7][: min(n, 3)]
    elif dtype == np.int32:
        a = rng.integers(-(2**31), 2**31, size=n, dtype=np.int64)
    elif dtype == np.bool_:
        a = rng.integers(0, 2, size=n)
    else:  # uint8: row kinds, active flags
        a = rng.integers(0, 256, size=n)
    return np.asarray(a).astype(dtype).reshape(shape)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype.itemsize == 4 else a.astype(np.int32)


@pytest.mark.parametrize(
    "kind,ring,lora",
    [(k, r, lo) for k in payload.KINDS for r, lo in ((False, False), (True, True))]
    + [("all-dtypes", False, False)],
)
def test_round_trip_is_bit_identical(kind, ring, lora):
    if kind == "all-dtypes":
        spec = [
            ("i", (3, 5), np.int32), ("f", (130,), np.float32),
            ("u", (7, 2), np.uint32), ("b", (9,), np.bool_),
            ("k", (129,), np.uint8), ("one", (1,), np.float32),
        ]
    else:
        B, QK = SHAPES[kind]
        spec = step_fields(
            kind, B, QK, max_pages=6, page=4, sample_cols=5,
            ring=ring, lora=lora,
        )
    layout = PayloadLayout(spec)
    rng = np.random.default_rng(zlib.crc32(kind.encode()))
    arrays = {n: _random_field(rng, s, d) for n, s, d in spec}
    buf = layout.pack(arrays)
    assert buf.dtype == np.int32 and buf.shape == (layout.words,)
    back = jax.jit(layout.unpack)(jnp.asarray(buf))
    assert sorted(back) == sorted(n for n, _, _ in spec)
    for name, shape, dtype in spec:
        got = np.asarray(back[name])
        assert got.dtype == np.dtype(dtype) and got.shape == tuple(shape), name
        assert np.array_equal(_bits(got), _bits(arrays[name])), name
    # Aligned, in order, no overlap; the length depends on the shapes alone.
    end = 0
    for f in layout.fields:
        assert f.offset % ALIGN == 0 and f.offset >= end, f
        end = f.offset + f.size
    assert end <= layout.words and layout.words % ALIGN == 0
    other = PayloadLayout(spec)
    assert other.words == layout.words and other.fields == layout.fields
    assert np.array_equal(
        other.pack({n: np.zeros(s, d) for n, s, d in spec}) != 0,
        np.zeros(layout.words, bool),
    )


def test_ring_and_lora_add_their_fields_to_every_kind():
    for kind in payload.KINDS:
        B, QK = SHAPES[kind]
        kw = dict(max_pages=6, page=4, sample_cols=5)
        plain = [n for n, _, _ in step_fields(kind, B, QK, ring=False, lora=False, **kw)]
        both = [n for n, _, _ in step_fields(kind, B, QK, ring=True, lora=True, **kw)]
        extra = ["swa_table", "lora"] + (["wphys_swa"] if kind == "flat" else [])
        assert sorted(both) == sorted(plain + extra), kind


def test_pack_refuses_what_the_description_does_not_say():
    layout = PayloadLayout([("temp", (4,), np.float32)])
    with pytest.raises(ValueError, match="temp"):
        layout.pack({"temp": np.zeros(4, np.float64)})
    with pytest.raises(ValueError, match="temp"):
        layout.pack({"temp": np.zeros(5, np.float32)})
    with pytest.raises(ValueError, match="neither"):
        PayloadLayout([("h", (4,), np.float16)])
    with pytest.raises(ValueError, match="kind"):
        step_fields("embed", 1, 1, max_pages=1, page=1, sample_cols=1,
                    ring=False, lora=False)


# --------------------------------------------------------------------- #
# every step program: packed against per-field inputs

RING_MODEL = dict(
    num_layers=4, num_heads=4, num_kv_heads=2, sliding_window=8,
    layer_types=("sliding_attention", "full_attention") * 2,
)
# Scheduler settings that make each step program THE path of a small run.
PROGRAMS = {
    "flat": dict(unified_step=True, ragged_qlens=True),
    "unified": dict(unified_step=True, ragged_qlens=False),
    "decode_window": dict(unified_step=False, decode_window=4),
    "prefill": dict(unified_step=False),
    "verify": dict(unified_step=False, speculative_ngram=True),
}
# Periodic prompts: the tiny model's greedy output loops, so the n-gram
# proposer drafts and the verify program runs (tests/test_spec_decode.py).
PROMPTS = [
    [1, 5, 9, 13] * 3,
    [3, 3, 7, 1, 3, 3, 7, 1],
    list(range(40, 75)),  # longer than the budget: chunked
]
N_OUT = 20
SAMPLING = [
    SamplingParams(temperature=0.0, max_tokens=N_OUT, ignore_eos=True,
                   logprobs=True),
    SamplingParams(temperature=0.3, top_k=20, top_p=0.9, seed=2**31 + 5,
                   max_tokens=N_OUT, ignore_eos=True, logprobs=True),
    SamplingParams(temperature=0.0, max_tokens=N_OUT, ignore_eos=True,
                   logprobs=True),
]


def _engine(program, extras, async_s=False):
    ring = "ring" in extras
    lora = "lora" in extras
    model_kw = dict(RING_MODEL) if ring else {}
    if lora:
        model_kw.update(num_lora_adapters=2, lora_rank=4)
    return LLMEngine(EngineConfig(
        model=tiny_model_config(**model_kw),
        cache=CacheConfig(
            page_size=4, num_blocks=64, dtype="float32", swa_ring=ring
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            **PROGRAMS[program],
        ),
        seed=0,
    ), _synchronous_step=not async_s)


class _PerField:
    """The hand-over the packed buffer replaced, held by the test: one
    device array a field, the program handed the dict as it is."""

    def __init__(self, layout):
        self.fields = layout.fields

    def put(self, arrays):
        return {f.name: jnp.asarray(arrays[f.name]) for f in self.fields}

    def unpack(self, step):
        return dict(step)


def _per_field(eng):
    r = eng.runner
    packed_layout = r._layout

    def put(op, B, QK, arrays):
        return _PerField(packed_layout(op, B, QK)).put(arrays)

    r._layout = lambda op, B, QK: _PerField(packed_layout(op, B, QK))
    r._put_step = put
    return eng


def _run(eng, lora):
    families = set()
    if lora:
        layers = eng.runner.params["layers"]
        rng = np.random.default_rng(11)
        eng.set_lora_weights(1, {
            k: rng.normal(0.0, 0.5, (layers[k].shape[0], *layers[k].shape[2:]))
            .astype(np.float32)
            for k in ("la_q", "lb_q", "la_v", "lb_v")
        })
    dispatching = eng.runner._dispatching

    def noting(family, **shape):  # a split step dispatches two programs
        families.add(family)
        return dispatching(family, **shape)

    eng.runner._dispatching = noting
    for i, (p, sp) in enumerate(zip(PROMPTS, SAMPLING)):
        eng.add_request(list(p), sp, lora_id=i % 2 if lora else 0)
    reqs = list(eng.scheduler.waiting)
    while eng.has_work():
        eng.step()
    return (
        [list(r.output_token_ids) for r in reqs],
        [list(r.output_logprobs) for r in reqs],
        families,
    )


@pytest.mark.parametrize(
    "program,extras",
    [(p, e) for p in PROGRAMS for e in ("", "ring+lora")]
    + [("flat", "async")],
)
def test_step_program_matches_per_field_inputs(program, extras):
    lora = "lora" in extras
    async_s = "async" in extras
    toks, logps, families = _run(_engine(program, extras, async_s), lora)
    ref_t, ref_l, _ = _run(_per_field(_engine(program, extras, async_s)), lora)
    assert program in families, families
    assert all(len(t) == N_OUT for t in toks)
    assert toks == ref_t
    assert logps == ref_l  # bit-identical: the same values, the same program


# --------------------------------------------------------------------- #
# the counter that says it engages; nothing built twice


@pytest.mark.parametrize("program", ["flat", "unified"])
def test_one_transfer_a_step(program):
    eng = _engine(program, "")
    base = eng.runner.step_h2d_transfers_total  # none before the first step
    assert base == 0
    _run(eng, False)
    s = eng.stats
    assert s.engine_steps_total > 5
    assert s.step_h2d_transfers_total == s.engine_steps_total
    assert s.step_h2d_transfers_total == s.step_dispatches_total
    # The bytes are the layouts' own, a few KB a step here.
    assert s.step_h2d_bytes_total % (4 * ALIGN) == 0
    assert s.step_h2d_bytes_total >= 4 * ALIGN * s.step_h2d_transfers_total


def test_split_engine_transfers_once_a_program():
    eng = _engine("prefill", "")
    _run(eng, False)
    s = eng.stats
    assert s.step_h2d_transfers_total == s.step_dispatches_total > 0


def test_a_warmed_bucket_neither_traces_nor_compiles():
    counters = jaxrt.CompileCounters().install()
    eng = _engine("flat", "")
    r = eng.runner
    for T in r.flat_t_buckets[:2]:
        r._warm_flat(T, True)
    jax.block_until_ready(r.kv_cache)
    traced, built = r.programs_traced, counters.snapshot()["programs"]
    transfers = r.step_h2d_transfers_total
    for _ in range(2):
        for T in r.flat_t_buckets[:2]:
            r._warm_flat(T, True)
    jax.block_until_ready(r.kv_cache)
    assert r.programs_traced == traced
    assert counters.snapshot()["programs"] == built
    assert r.step_h2d_transfers_total == transfers + 4


def test_a_first_call_runs_without_the_collector():
    """A shape's first call (trace, lowering) runs with the cyclic
    collector off and hands it back on; a warmed shape leaves it alone, and
    so does a host that had it off."""
    eng = _engine("flat", "")
    r = eng.runner
    real, seen = r._flat, []

    def spy(*args, **kw):
        seen.append(gc.isenabled())
        return real(*args, **kw)

    r._flat = spy
    T0, T1 = r.flat_t_buckets[:2]
    r._warm_flat(T0, True)
    r._warm_flat(T0, True)
    assert seen == [False, True] and gc.isenabled()
    gc.disable()
    try:
        r._warm_flat(T1, True)
        assert not gc.isenabled()
    finally:
        gc.enable()


def test_layouts_are_built_once_a_shape():
    eng = _engine("flat", "")
    r = eng.runner
    a = r._layout(11, r.flat_rows, 16)
    assert r._layout(11, r.flat_rows, 16) is a
    assert r._layout(11, r.flat_rows, 32) is not a
    names = [n for n, _, _ in r._payload_spec(11, r.flat_rows, 16)]
    assert [f.name for f in a.fields] == names  # the lockstep wire's fields
