"""Compile each configuration's saturated step for a DESCRIBED TPU v5e, with
no chip attached, and print ``memory_analysis()``: arguments (weights + KV
pool), temporaries and what is aliased in place. Settles depth and geometry
before the first chip call. A script, not a test, and not a measurement:
nothing runs, so it gives bytes and "accepted"/"refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile.py [config ...]

It builds the runner's jitted step programs the way ``ModelRunner.__init__``
does, but over shapes (``jax.eval_shape``) on the described device, and asks
the runner's own warm-up helpers for the shapes; this reaches into the
runner's private construction, which is acceptable for a rehearsal script
and for nothing the benchmark measures with.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import threading

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


class _Compiled(Exception):
    pass


def rehearse(name: str, conf: dict, device) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding

    from llmd_tpu.engine import runner as runner_mod
    from llmd_tpu.engine.runner import ModelRunner, _buckets
    from llmd_tpu.models import llama
    from llmd_tpu.parallel.mesh import build_mesh
    from llmd_tpu.config import ParallelConfig
    from perfbench.topologies.engine import engine_config

    config = engine_config(conf, seed=0, rehearse=False)
    cfg = config.model
    here = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree)

    r = object.__new__(ModelRunner)
    r.config, r.cfg = config, cfg
    r.ctx = build_mesh(ParallelConfig(), devices=[device])
    r.max_pages = config.cache.max_pages_per_seq(cfg.max_model_len)
    r.page = config.cache.page_size
    r.swa = None
    r.ep_capacity, r._ep_active, r.moe_overlap = 2.0, False, 0
    r._moe_census, r.moe_placement = None, None
    r.cp_prefill, r.cp_min_tokens = 0, 512
    r._multihost, r._dispatch_lock = False, threading.RLock()
    sched = config.scheduler
    r.batch_buckets = sched.decode_batch_buckets or _buckets(sched.max_num_seqs)
    r.prefill_batch_buckets = sched.prefill_batch_buckets or _buckets(sched.max_num_seqs, start=1)
    r.prefill_buckets = sched.prefill_token_buckets or _buckets(sched.max_num_batched_tokens, start=16)
    r.kernel_plans = {}
    r.kv_swa = None
    r.traced_programs, r.programs_traced, r._tracing = [], 0, None  # what _note_traced writes
    params = jax.eval_shape(lambda k: llama.init_params(cfg, k), jax.random.key(0))
    fused = jax.eval_shape(runner_mod._fuse_projection_tree, params) if not cfg.is_mla else params
    r.params = on_chip(fused)
    pool = (cfg.num_layers, config.cache.num_blocks, cfg.kv_cache_heads,
            config.cache.page_size, cfg.kv_cache_entry_dim)
    r.kv_cache = jax.ShapeDtypeStruct(pool, jax.numpy.dtype(config.cache.dtype), sharding=here)
    if cfg.sparse_attention:  # the indexer's key plane under the same page ids (runner._alloc_kv)
        from llmd_tpu import ops

        plane = (cfg.num_layers, config.cache.num_blocks, config.cache.page_size, cfg.indexer_head_dim)
        r.kv_cache = ops.IndexedPool(
            kv=r.kv_cache, index=jax.ShapeDtypeStruct(plane, r.kv_cache.dtype, sharding=here))
    r._build_programs()
    r._check_page_table_fits_smem()

    gib = 2.0 ** 30
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(r.params))
    pool_b = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(r.kv_cache))
    print(f"== {name}: {cfg.num_layers} layers, weights {weights / gib:.2f} GiB, "
          f"KV pool {pool_b / gib:.2f} GiB ({config.cache.num_blocks} pages x {r.page} tokens)")

    def lower_only(label, jitted):
        def call(*args, **kw):
            def shape(a):
                if isinstance(a, jax.ShapeDtypeStruct) or a is None:
                    return a
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here)

            args = jax.tree.map(shape, args, is_leaf=lambda x: x is None)
            dyn = {k: jax.tree.map(shape, v) for k, v in kw.items() if k in ("census",)}
            static = {k: v for k, v in kw.items() if k not in dyn}
            compiled = jitted.lower(*args, **dyn, **static).compile()
            m = compiled.memory_analysis()
            total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
            print(f"   {label}: arguments {m.argument_size_in_bytes / gib:.2f} GiB, "
                  f"temporaries {m.temp_size_in_bytes / gib:.2f} GiB, "
                  f"aliased {m.alias_size_in_bytes / gib:.2f} GiB, "
                  f"total {total / gib:.2f} GiB of {15.75:.2f} GiB usable; plans {dict((k, sorted(v)) for k, v in r.kernel_plans.items())}")
            raise _Compiled

        return call

    def attempt(label, attr, warm, *a):
        real = getattr(r, attr)
        setattr(r, attr, lower_only(label, real))
        try:
            warm(*a)
        except _Compiled:
            pass
        finally:
            setattr(r, attr, real)

    if r._flat is not None:
        attempt(f"flat step T={r.flat_t_buckets[-1]} greedy", "_flat", r._warm_flat, r.flat_t_buckets[-1], True)
    else:
        B, Q, T = r.unified_row_buckets[-1], r.unified_q_buckets[-1], r.prefill_buckets[-1]
        attempt(f"unified step rows={B} Q={Q} T={T} greedy", "_unified", r._warm_unified, B, Q, T, True)
        attempt(f"decode rows={r.batch_buckets[-1]} greedy", "_multi", r._warm_decode, r.batch_buckets[-1], 1, True)
        pb, pq = r.prefill_batch_buckets[-1], r.prefill_buckets[-1]
        attempt(f"prefill rows={pb} Q={pq} greedy", "_forward", r._warm_prefill, pb, pq, True)

    # The init itself: one jitted call, weights in the served dtype.
    init = jax.jit(lambda k: llama.init_params(cfg, k), out_shardings=here)
    m = init.lower(jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=here)).compile().memory_analysis()
    print(f"   jitted init: outputs {m.output_size_in_bytes / gib:.2f} GiB, temporaries {m.temp_size_in_bytes / gib:.2f} GiB")


def main() -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    wanted = set(sys.argv[1:])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for c in manifest["configs"]:
        if wanted and c["name"] not in wanted:
            continue
        rehearse(c["name"], json.loads((ROOT / c["file"]).read_text()), topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
