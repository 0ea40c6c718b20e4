#!/usr/bin/env python3
"""One sha256 of the lowered text of every step program an engine would warm,
and one of the flattened ``init_params`` tree: what "the traced program did not
change" means, made checkable. Two trees whose lines are equal trace the same
programs and draw the same weights. ``lower(...).as_text()`` carries no source
locations, EXCEPT inside a Pallas kernel: its Mosaic body is serialized with
debug info, the file and line of every frame that traced the call, so the same
kernel called from another line reads different. A body is hashed as its
assembly without them (``without_kernel_locations``). Nothing runs and nothing
compiles: no time, no rate.

    python3 scripts/step_program_digest.py TARGET [TARGET ...] [--device cpu|v5e|here]
    python3 scripts/step_program_digest.py --suite cpu     # the tiny presets, on this backend
    python3 scripts/step_program_digest.py --suite bench --device v5e   # BENCHMARK.json's configurations

A TARGET is a ``perfbench/configs/*.json`` file (read through its topology's
own ``engine_config``; the runner is built over SHAPES, at the benchmark's own
sizes, on one device) or a registry preset with sizes,
``name[:key=value,...]`` (a real runner, tiny weights; a key is a field of
``ModelConfig``, ``ParallelConfig``, ``SchedulerConfig`` or ``CacheConfig``,
its value JSON: ``tiny:num_heads=8,tensor_parallel_size=8``).

``--device``: ``here`` lowers for the backend JAX runs on (the CPU's XLA
fallbacks in this sandbox, the Pallas programs on the chip), ``v5e`` for a
DESCRIBED v5e chip with none attached (configuration files only), ``cpu``
holds JAX to the CPU. Lines are ``<target> <program> <sha256>``, a
``init_params`` line a target (a configuration file's at its ``rehearse``
size), and the same as JSON under ``--out``.
"""

from __future__ import annotations

import argparse
import base64
import collections
import dataclasses
import hashlib
import json
import os
import pathlib
import re
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SUITES = {
    # The paths tier-1 runs: every tiny preset, and the plain model with each
    # option that the attention body branches on.
    "cpu": [
        "tiny-moe", "tiny-swa", "tiny-swa:swa_ring=true", "tiny-mla", "tiny-dsa",
        "tiny-exaone:swa_ring=true", "tiny-mellum2:swa_ring=true", "tiny-granite-hybrid", "tiny-nemotron-h",
        "tiny-qwen3-next", "tiny-mla-dsa",
        "tiny:num_lora_adapters=2",
        "tiny:attention_bias=true,attention_sinks=true",
        "tiny:num_heads=8,num_kv_heads=2,tensor_parallel_size=8",
        "tiny-moe:tensor_parallel_size=4,data_parallel_size=2,moe_backend=\"ep\",enable_dbo=true,"
        "ragged_qlens=false",
        "tiny-mla:num_experts=8,num_experts_per_tok=2,tensor_parallel_size=4,data_parallel_size=2,"
        "moe_backend=\"ep\",enable_dbo=true",
        "tiny:data_parallel_size=2,cp_prefill=2,cp_prefill_min_tokens=32",
    ],
}


class _Lowered(Exception):
    pass


_KERNEL_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def without_kernel_locations(text: str) -> str:
    """``text`` with every ``tpu_custom_call``'s Mosaic body (base64 bytecode
    in its ``backend_config``) replaced by the body's assembly printed without
    debug info."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    if not _KERNEL_BODY.search(text):
        return text
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def assembly(match) -> str:
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return _KERNEL_BODY.sub(assembly, text)


def parse_target(target: str):
    """``name[:key=value,...]`` -> (name, {key: value})."""
    name, _, rest = target.partition(":")
    kw = {}
    for item in filter(None, rest.split(",")):
        key, _, value = item.partition("=")
        try:
            kw[key] = json.loads(value)
        except json.JSONDecodeError:
            kw[key] = value
    return name, kw


def preset_config(target: str):
    """The EngineConfig of a registry preset at a small geometry."""
    from llmd_tpu.config import (
        CacheConfig, EngineConfig, ModelConfig, ParallelConfig, SchedulerConfig,
    )
    from llmd_tpu.models.registry import get_model_config

    name, kw = parse_target(target)
    parts = {cls: {} for cls in (ModelConfig, ParallelConfig, SchedulerConfig, CacheConfig)}
    for key, value in kw.items():
        owner = next(
            (c for c in parts if key in {f.name for f in dataclasses.fields(c)}), None
        )
        if owner is None:
            raise SystemExit(f"{target}: no configuration class has a field {key!r}")
        parts[owner][key] = tuple(value) if isinstance(value, list) else value
    model = get_model_config(name, **parts[ModelConfig])
    return EngineConfig(
        model=model,
        cache=CacheConfig(**{"page_size": 8, "num_blocks": 64, "dtype": model.dtype,
                             **parts[CacheConfig]}),
        scheduler=SchedulerConfig(**{"max_num_seqs": 4, "max_num_batched_tokens": 32,
                                     **parts[SchedulerConfig]}),
        parallel=ParallelConfig(**parts[ParallelConfig]),
    )


def file_config(path: pathlib.Path, rehearse: bool):
    """The EngineConfig a ``perfbench/configs`` file describes, through the
    ``engine_config`` of the topology its cells name."""
    import importlib

    conf = json.loads(path.read_text())
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    rel = str(path.resolve().relative_to(ROOT))
    names = [c["name"] for c in manifest["configs"] if c["file"] == rel]
    cells = [w["name"] for w in manifest["workloads"] if w["config"] in names]
    if not cells:
        raise SystemExit(f"{rel}: no cell of BENCHMARK.json runs this configuration")
    cell = json.loads((ROOT / "perfbench" / "cells" / f"{cells[0]}.json").read_text())
    topology = importlib.import_module(f"perfbench.topologies.{cell['topology']}")
    if not hasattr(topology, "engine_config"):  # a topology of ``engine``'s own configuration
        topology = importlib.import_module("perfbench.topologies.engine")
    return topology.engine_config(conf, seed=0, rehearse=rehearse)


def engine_swa_spec(config):
    """The second pool's spec as ``LLMEngine`` resolves it: the ring or the
    state slots, plus the retained sections' pages."""
    from llmd_tpu.config import state_slot_spec, swa_ring_spec, swa_section_count

    cache, sched = config.cache, config.scheduler
    swa = swa_ring_spec(config.model, cache, sched) or state_slot_spec(config.model, sched)
    if swa is not None and cache.enable_prefix_caching and cache.swa_section_cache > 0:
        extra = swa_section_count(cache, sched) * swa.max_section_pages(cache.page_size)
        swa = dataclasses.replace(swa, num_swa_blocks=swa.num_swa_blocks + extra)
    return swa


def real_runner(config):
    """``ModelRunner`` as the engine builds it (a preset's weights are tiny)."""
    from llmd_tpu.engine.runner import ModelRunner
    from llmd_tpu.parallel.mesh import build_mesh

    return ModelRunner(config, build_mesh(config.parallel), swa_spec=engine_swa_spec(config))


def abstract_runner(config, device):
    """A ``ModelRunner`` whose parameters and pools are shapes on ``device``
    (a described chip, or a real one left empty): what ``__init__`` sets that
    ``_build_programs`` and the warm-up helpers read, in its order, and
    nothing that allocates (``perfbench/rehearse_compile*.py`` do the same
    for one configuration each)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from llmd_tpu.config import ParallelConfig
    from llmd_tpu.engine.runner import ModelRunner, _buckets
    from llmd_tpu.models import llama
    from llmd_tpu.parallel.mesh import build_mesh

    cfg, sched = config.model, config.scheduler
    here = SingleDeviceSharding(device)

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree
        )

    config.check_sparse_attention()
    config.check_state_space()
    r = object.__new__(ModelRunner)
    r.config, r.cfg = config, cfg
    r.ctx = build_mesh(ParallelConfig(), devices=[device])
    r.max_pages = config.cache.max_pages_per_seq(cfg.max_model_len)
    r.page = config.cache.page_size
    params = jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.key(0))
    r.params = shapes(jax.eval_shape(r._maybe_fuse, params))
    r.ep_capacity, r._ep_active, r.moe_overlap = 2.0, False, 0
    r._moe_census = None
    if cfg.is_moe and config.parallel.moe_backend == "grouped":
        r._moe_census = jax.ShapeDtypeStruct((4,), np.int32, sharding=here)
    r.moe_placement = None
    r.swa = engine_swa_spec(config)
    r.kv_cache = shapes(jax.eval_shape(r._alloc_kv))
    r.kv_swa = shapes(jax.eval_shape(r._alloc_swa))
    r._multihost, r._dispatch_lock = False, threading.RLock()
    r.cp_prefill, r.cp_min_tokens = 0, 512
    r.batch_buckets = sched.decode_batch_buckets or _buckets(sched.max_num_seqs)
    r.prefill_batch_buckets = sched.prefill_batch_buckets or _buckets(sched.max_num_seqs, start=1)
    r.prefill_buckets = sched.prefill_token_buckets or _buckets(sched.max_num_batched_tokens, start=16)
    r.kernel_plans = {}
    r.traced_programs = collections.deque(maxlen=256)
    r.programs_traced, r._tracing = 0, ""
    r._build_programs()
    r._check_page_table_fits_smem()
    return r


def lowered_text(r, attrs: tuple, warm, *args) -> tuple[str, str]:
    """(program, lowered text) of the one of ``r.<attrs>`` that the runner's
    own warm-up helper ``warm(*args)`` calls, at the shape it calls it with."""
    import jax

    leaf = jax.tree.leaves(r.kv_cache)[0]
    abstract = isinstance(leaf, jax.ShapeDtypeStruct)
    real = {a: getattr(r, a) for a in attrs if getattr(r, a) is not None}
    got = {}

    def lower_only(attr):
        def call(*a, **kw):
            def shape(x):  # a real runner lowers on its real arrays
                if not abstract or isinstance(x, jax.ShapeDtypeStruct):
                    return x
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=leaf.sharding)

            a, dyn = jax.tree.map(shape, (a, {"census": kw.pop("census")}))
            got[attr] = without_kernel_locations(real[attr].lower(*a, **dyn, **kw).as_text())
            raise _Lowered

        return call

    for attr in real:
        setattr(r, attr, lower_only(attr))
    try:
        warm(*args)
    except _Lowered:
        pass
    finally:
        for attr in real:
            setattr(r, attr, real[attr])
    return next(iter(got.items()))


def step_programs(r):
    """(label, attrs, warm helper, args) of the step programs the engine warms
    or its ladder reaches: the flat step at its smallest and largest T bucket;
    where there is none (latent attention), the bucketed unified step, the
    prefill groups and the decode windows at their smallest, a middle and
    their largest buckets; the ring prefill where it is armed."""
    def ends(seq, mid: bool = False):
        seq = list(seq)
        picks = [seq[0], seq[len(seq) // 2], seq[-1]] if mid else [seq[0], seq[-1]]
        return list(dict.fromkeys(picks))

    out = []
    if r._flat is not None:
        for T in ends(r.flat_t_buckets):
            out.append((f"[T={T}]", ("_flat",), r._warm_flat, T))
    else:
        T = r.prefill_buckets[-1]
        for B, Q in zip(ends(r.unified_row_buckets, True), ends(r.unified_q_buckets, True)):
            out.append((f"[B={B},Q={Q},T={T}]", ("_unified",), r._warm_unified, B, Q, T))
    if r._flat is None or r.config.kv_role or r._forward_cp is not None:
        shapes = [(B, r.prefill_buckets[-1]) for B in ends(r.prefill_batch_buckets)]
        if r._forward_cp is not None:  # below cp_prefill_min_tokens: the monolithic program
            shapes.append((1, r.prefill_buckets[0]))
        for B, Q in shapes:
            out.append((f"[B={B},Q={Q}]", ("_forward", "_forward_cp"), r._warm_prefill, B, Q))
    if r._flat is None or len(r.decode_windows) > 1:
        for B in ends(r.batch_buckets):
            for K in r.decode_windows:
                out.append((f"[B={B},K={K}]", ("_multi",), r._warm_decode, B, K))
    return out


def init_digest(cfg) -> str:
    """Names, shapes, dtypes and VALUES of ``init_params(cfg, key(0))``."""
    import jax
    import numpy as np

    from llmd_tpu.models import llama

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(llama.init_params(cfg, jax.random.key(0)))[0]
    for path, leaf in leaves:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n".encode())
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    return h.hexdigest()


def digests(target: str, device) -> list[tuple[str, str, str]]:
    """[(target, program, sha256)] of one target."""
    path = pathlib.Path(target)
    if path.suffix == ".json":
        name = path.stem
        r = abstract_runner(file_config(path, rehearse=False), device)
        tiny = file_config(path, rehearse=True).model
    else:
        name = target
        config = preset_config(target)
        r, tiny = real_runner(config), config.model
    lines = []
    for label, attrs, warm, *args in step_programs(r):
        for greedy in (True, False):
            attr, text = lowered_text(r, attrs, warm, *args, greedy)
            kind = "greedy" if greedy else "sampled"
            lines.append((name, f"{attr}{label} {kind}", hashlib.sha256(text.encode()).hexdigest()))
            print(*lines[-1], flush=True)
    lines.append((name, "init_params", init_digest(tiny)))
    print(*lines[-1], flush=True)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("targets", nargs="*")
    ap.add_argument("--suite", choices=("cpu", "bench"))
    ap.add_argument("--device", choices=("here", "cpu", "v5e"), default="here")
    ap.add_argument("--out", help="write the lines as JSON here too")
    args = ap.parse_args()
    if args.device != "here":
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.suite == "cpu" or any(":" in t and "_parallel_size" in t for t in args.targets):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=8".strip()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    targets = list(args.targets)
    if args.suite == "bench":
        manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
        targets += [c["file"] for c in manifest["configs"]]
    elif args.suite:
        targets += SUITES[args.suite]
    if not targets:
        ap.error("give a target or --suite")
    device = jax.devices()[0]
    if args.device == "v5e":
        from jax.experimental import topologies

        device = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    lines = []
    for target in targets:
        lines += digests(target, device)
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(
            {"backend": f"{args.device}:{device.platform}",
             "digests": {f"{t} {p}": d for t, p, d in lines}}, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
