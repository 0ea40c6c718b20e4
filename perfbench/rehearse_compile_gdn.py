"""``rehearse_compile_mixer.py`` for the configuration of ``topologies/engine_gdn``
(Gated DeltaNet layers over the state pool, gated attention at head size 256,
64 of 512 experts held): compile its flat step for a DESCRIBED TPU v5e, with
no chip attached, at the T buckets given (default: a decode-only step of
``max_num_seqs`` rows and the saturated step), and print ``memory_analysis()``.
Settles the pools' sizes before the first chip call: bytes and "accepted" /
"refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile_gdn.py [T ...]

Both pools must come out ALIASED and the temporaries small. ``build_runner`` is
also what ``tests/test_chip_compile.py`` holds the step's kernel names with.
"""

from __future__ import annotations

import json
import sys

from perfbench import rehearse_compile_mixer as mixer

ROOT = mixer.ROOT
CONFIG = "perfbench/configs/qwen3-next-80b-a3b.1chip.json"
GIB = mixer.GIB
NAMES = ("llmd.gdn.update", "llmd.gdn.scan", "gmm", "llmd.block.gdn", "llmd.block.moe", "llmd.block.attn")


def build_runner(config, device):
    """``rehearse_compile_mixer.build_runner`` with the state pool in THIS
    model's shapes (that function writes the Mamba-2 mixers' keys out)."""
    import jax

    from llmd_tpu.ops.ssm import StatePool

    r = mixer.build_runner(config, device)
    state, conv = config.model.state_shapes
    lm, slots = r.kv_swa.ssm.shape[:2]
    here = r.kv_cache.sharding
    r.kv_swa = StatePool(
        ssm=jax.ShapeDtypeStruct((lm, slots, *state), jax.numpy.float32, sharding=here),
        conv=jax.ShapeDtypeStruct((lm, slots, *conv), jax.numpy.dtype(config.model.dtype), sharding=here))
    return r


def rehearse(conf: dict, device, buckets: list) -> None:
    import jax

    from perfbench.topologies.engine_gdn import engine_config

    config = engine_config(conf, seed=0, rehearse=False)
    r = build_runner(config, device)
    cfg, sched = config.model, config.scheduler
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))  # noqa: E731
    slots = r.kv_swa.ssm.shape[1]
    print(f"== {cfg.name}: {cfg.num_layers} layers ({len(cfg.mamba_layers)} delta-rule, {len(cfg.attention_layers)} "
          f"attention): weights {nbytes(r.params) / GIB:.2f} GiB, main pool {nbytes(r.kv_cache) / GIB:.2f} GiB "
          f"({config.cache.num_blocks} pages x {r.kv_cache.shape[0]} layers), state pool {nbytes(r.kv_swa) / GIB:.2f} GiB "
          f"({slots} slots x {r.kv_swa.ssm.shape[0]} layers: {sched.max_num_seqs} running, "
          f"{slots - 1 - sched.max_num_seqs} snapshots, 1 scratch); T buckets {list(r.flat_t_buckets)}", flush=True)
    for T in buckets or [sched.max_num_seqs, r.flat_t_buckets[-1]]:
        _lowered, compiled = mixer.compile_step(r, T)
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
        print(f"== REFUSED: {type(e).__name__}: {str(e)[:1500]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
