"""``rehearse_compile.py`` for a configuration of ``topologies/engine_state``
(state-space mixers over a state pool beside the paged KV pool, a held share
of the experts): compile its saturated flat step for a DESCRIBED TPU v5e, with
no chip attached, and print ``memory_analysis()``. Settles the pools' sizes
before the first chip call: bytes and "accepted"/"refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile_state.py [pages ...]

``rehearse_compile.py`` (not this PR's to edit) builds one pool over every
layer and its ``EngineConfig`` through ``topologies/engine.py``; this file
builds the main pool over the attention layers and the state pool (running
slots + retained snapshots + the scan's scratch slot) and the configuration
through ``topologies/engine_state.py``, optionally at other page counts than
the file's. The state pool must come out ALIASED and the temporaries small: a
compile that copies the pool shows it here as gigabytes of temporaries.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import sys
import threading

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CONFIG = "perfbench/configs/granite-4.0-h-small.1chip.json"
GIB = 2.0 ** 30


class _Compiled(Exception):
    pass


def rehearse(conf: dict, device) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding

    from llmd_tpu.config import ParallelConfig, state_slot_spec, swa_section_count
    from llmd_tpu.engine import runner as runner_mod
    from llmd_tpu.engine.runner import ModelRunner, _buckets
    from llmd_tpu.models import llama
    from llmd_tpu.parallel.mesh import build_mesh
    from llmd_tpu.ops.ssm import StatePool
    from perfbench.topologies.engine_state import engine_config

    config = engine_config(conf, seed=0, rehearse=False)
    cfg, cache, sched = config.model, config.cache, config.scheduler
    here = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree)

    swa = state_slot_spec(cfg, sched)
    # As LLMEngine sizes it: the running slots plus the retained snapshots.
    sections = swa_section_count(cache, sched)
    swa = dataclasses.replace(swa, num_swa_blocks=swa.num_swa_blocks + sections)
    r = object.__new__(ModelRunner)
    r.config, r.cfg = config, cfg
    r.ctx = build_mesh(ParallelConfig(), devices=[device])
    r.max_pages, r.page = cache.max_pages_per_seq(cfg.max_model_len), cache.page_size
    r.swa = swa
    r.ep_capacity, r._ep_active, r.moe_overlap = 2.0, False, 0
    r._moe_census, r.moe_placement = None, None
    r.cp_prefill, r.cp_min_tokens = 0, 512
    r._multihost, r._dispatch_lock = False, threading.RLock()
    r.batch_buckets = sched.decode_batch_buckets or _buckets(sched.max_num_seqs)
    r.prefill_batch_buckets = sched.prefill_batch_buckets or _buckets(sched.max_num_seqs, start=1)
    r.prefill_buckets = sched.prefill_token_buckets or _buckets(sched.max_num_batched_tokens, start=16)
    r.kernel_plans = {}
    r.traced_programs, r.programs_traced, r._tracing = [], 0, None
    params = jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.key(0))
    r.params = on_chip(jax.eval_shape(runner_mod._fuse_projection_tree, params))
    dt = jax.numpy.dtype(cache.dtype)

    def pool(layers: int, pages: int):
        return jax.ShapeDtypeStruct(
            (layers, pages, cfg.kv_cache_heads, cache.page_size, cfg.kv_cache_entry_dim), dt, sharding=here)

    r.kv_cache = pool(len(swa.full_layers), cache.num_blocks)
    lm, slots = len(swa.state_layers), swa.num_swa_blocks + 1  # + the scan's scratch slot
    r.kv_swa = StatePool(
        ssm=jax.ShapeDtypeStruct((lm, slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                                 jax.numpy.float32, sharding=here),
        conv=jax.ShapeDtypeStruct((lm, slots, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                                  jax.numpy.dtype(cfg.dtype), sharding=here))
    r._build_programs()
    r._check_page_table_fits_smem()
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))  # noqa: E731
    print(f"== depth {cfg.num_layers} ({''.join('M' if t == 'mamba' else 'A' for t in cfg.layer_types)}): weights "
          f"{nbytes(r.params) / GIB:.2f} GiB, main pool {nbytes(r.kv_cache) / GIB:.2f} GiB "
          f"({cache.num_blocks} pages x {len(swa.full_layers)} layers), state pool {nbytes(r.kv_swa) / GIB:.2f} GiB "
          f"({slots} slots x {lm} layers: {sched.max_num_seqs} running, {sections} snapshots, 1 scratch)", flush=True)

    def lower_only(label, jitted):
        def call(*args, **kw):
            def shape(a):
                if isinstance(a, jax.ShapeDtypeStruct) or a is None:
                    return a
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here)

            args = jax.tree.map(shape, args, is_leaf=lambda x: x is None)
            compiled = jitted.lower(*args, **kw).compile()
            m = compiled.memory_analysis()
            total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            text = compiled.as_text()
            print(f"   {label}: arguments {m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
                  f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased {m.alias_size_in_bytes / GIB:.2f} GiB, total "
                  f"{total / GIB:.2f} GiB of 15.75 GiB usable; plans "
                  f"{dict((k, sorted(v)) for k, v in r.kernel_plans.items())}; custom calls named: "
                  f"{sorted({n for n in ('llmd.ssm.update', 'llmd.ssm.scan', 'gmm') if n in text})}", flush=True)
            raise _Compiled

        return call

    real = r._flat
    r._flat = lower_only(f"flat step T={r.flat_t_buckets[-1]} greedy", real)
    try:
        r._warm_flat(r.flat_t_buckets[-1], True)
    except _Compiled:
        pass
    finally:
        r._flat = real


def main() -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / CONFIG).read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for pages in [int(a) for a in sys.argv[1:]] or [conf["engine"]["num_pages"]]:
        try:
            rehearse(dict(conf, engine=dict(conf["engine"], num_pages=pages)), topo.devices[0])
        except Exception as e:  # noqa: BLE001  (a refused compile is this script's answer)
            print(f"== {pages} pages: REFUSED: {type(e).__name__}: {str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
