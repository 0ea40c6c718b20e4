"""``rehearse_compile_state.py`` for a configuration of ``topologies/engine_mixer``
(blocks of one mixer each scanned in cycles, B and C in groups, non-gated
experts, 128 resident sequences over the state pool): compile its flat step for
a DESCRIBED TPU v5e, with no chip attached, at the T buckets given (default: a
decode-only step of ``max_num_seqs`` rows and the saturated step), and print
``memory_analysis()``. Settles the pools' sizes before the first chip call:
bytes and "accepted"/"refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile_mixer.py [T ...]

Both pools must come out ALIASED and the temporaries small: a compile that
copies a pool shows it here as gigabytes of temporaries. ``compile_step`` is
also what ``tests/test_chip_compile.py`` holds the step's kernel names and
operand forms with.
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
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
CONFIG = "perfbench/configs/nemotron-3-nano-30b-a3b.1chip.json"
GIB = 2.0 ** 30
NAMES = ("llmd.ssm.update", "llmd.ssm.scan", "gmm", "llmd.block.mamba", "llmd.block.moe", "llmd.block.attn")


class _Lowered(Exception):
    pass


def build_runner(config, device):
    """A ``ModelRunner`` of ``config`` whose parameters and pools are shapes
    on ``device`` (a described chip): everything ``_build_programs`` needs and
    nothing that touches a device."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from llmd_tpu.config import ParallelConfig, state_slot_spec, swa_section_count
    from llmd_tpu.engine import runner as runner_mod
    from llmd_tpu.engine.runner import ModelRunner, _buckets
    from llmd_tpu.models import llama
    from llmd_tpu.ops.ssm import StatePool
    from llmd_tpu.parallel.mesh import build_mesh

    cfg, cache, sched = config.model, config.cache, config.scheduler
    here = SingleDeviceSharding(device)
    swa = state_slot_spec(cfg, sched)
    # As LLMEngine sizes it: the running slots plus the retained snapshots.
    swa = dataclasses.replace(swa, num_swa_blocks=swa.num_swa_blocks + swa_section_count(cache, sched))
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
    params = jax.eval_shape(runner_mod._fuse_projection_tree, params)
    r.params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), params)
    r.kv_cache = jax.ShapeDtypeStruct(
        (len(swa.full_layers), cache.num_blocks, cfg.kv_cache_heads, cache.page_size, cfg.kv_cache_entry_dim),
        jax.numpy.dtype(cache.dtype), sharding=here)
    lm, slots = len(swa.state_layers), swa.num_swa_blocks + 1  # + the scan's scratch slot
    r.kv_swa = StatePool(
        ssm=jax.ShapeDtypeStruct((lm, slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                                 jax.numpy.float32, sharding=here),
        conv=jax.ShapeDtypeStruct((lm, slots, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                                  jax.numpy.dtype(cfg.dtype), sharding=here))
    r._build_programs()
    r._check_page_table_fits_smem()
    return r


def compile_step(r, T: int, compile: bool = True):
    """The runner's greedy flat step at bucket ``T``: (lowered, compiled or
    None)."""
    import jax

    here = r.kv_cache.sharding
    got = {}

    def lower_only(jitted):
        def call(*args, **kw):
            def shape(a):
                if isinstance(a, jax.ShapeDtypeStruct) or a is None:
                    return a
                return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here)

            got["lowered"] = jitted.lower(*jax.tree.map(shape, args, is_leaf=lambda x: x is None), **kw)
            raise _Lowered

        return call

    real = r._flat
    r._flat = lower_only(real)
    try:
        r._warm_flat(T, True)
    except _Lowered:
        pass
    finally:
        r._flat = real
    lowered = got["lowered"]
    return lowered, lowered.compile() if compile else None


def rehearse(conf: dict, device, buckets: list) -> None:
    import jax

    from perfbench.topologies.engine_mixer import engine_config

    config = engine_config(conf, seed=0, rehearse=False)
    r = build_runner(config, device)
    cfg, sched = config.model, config.scheduler
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))  # noqa: E731
    slots = r.kv_swa.ssm.shape[1]
    print(f"== {cfg.name}: {cfg.num_layers} layers ({len(cfg.mamba_layers)} mamba, {len(cfg.attention_layers)} attention, "
          f"{len(cfg.ffn_layers)} with FFN): weights {nbytes(r.params) / GIB:.2f} GiB, main pool "
          f"{nbytes(r.kv_cache) / GIB:.2f} GiB ({config.cache.num_blocks} pages x {r.kv_cache.shape[0]} layers), state pool "
          f"{nbytes(r.kv_swa) / GIB:.2f} GiB ({slots} slots x {r.kv_swa.ssm.shape[0]} layers: {sched.max_num_seqs} running, "
          f"{slots - 1 - sched.max_num_seqs} snapshots, 1 scratch); T buckets {list(r.flat_t_buckets)}", flush=True)
    for T in buckets or [sched.max_num_seqs, r.flat_t_buckets[-1]]:
        _lowered, compiled = compile_step(r, T)
        m = compiled.memory_analysis()
        total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        text = compiled.as_text()
        print(f"   flat step T={T} greedy: arguments {m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased {m.alias_size_in_bytes / GIB:.2f} GiB, total "
              f"{total / GIB:.2f} GiB of 15.75 GiB usable; plans "
              f"{dict((k, sorted(v)) for k, v in r.kernel_plans.items())}; names in the HLO: "
              f"{[n for n in NAMES if n in text]}", flush=True)


def main() -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / CONFIG).read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    try:
        rehearse(conf, topo.devices[0], [int(a) for a in sys.argv[1:]])
    except Exception as e:  # noqa: BLE001  (a refused compile is this script's answer)
        print(f"== REFUSED: {type(e).__name__}: {str(e)[:1200]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
