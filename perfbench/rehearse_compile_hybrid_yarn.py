"""``rehearse_compile_hybrid.py`` for the configuration of
``topologies/engine_hybrid_yarn`` (Mellum2: window and full attention three to
one over two KV pools, a RoPE table per layer type, the periodic cycle scan, a
held share of the experts): compile its saturated flat step for a DESCRIBED TPU
v5e, with no chip attached, at any depths, and print ``memory_analysis()`` and
what the compiled text says of the scan. Settles the depth before the first
chip call: bytes and "accepted"/"refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile_hybrid_yarn.py [depth ...]

The runner over shapes is ``rehearse_compile_hybrid.rehearse``'s (that file is
not this PR's to edit): this one hands it THIS configuration's
``engine_config`` and file, and reads the compiled text for a per-layer COPY
of an expert leaf's layer (``[16, 2304, 896]`` and its transposes, 66 MB each)
or of a pool plane: none, where the leaves are indexed in place as
``scan_group`` indexes them. (That ``S S S F`` x n is ONE scan body is held by
``tests/test_mellum2.py`` on the traced program.)
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import rehearse_compile_hybrid as base  # noqa: E402  (sets JAX_PLATFORMS before jax loads)

CONFIG = "perfbench/configs/mellum2-12b-a2.5b.1chip.json"


def text_report(text: str, conf: dict, pools: list) -> dict:
    """The compiled text's copies, slices and transposes of a whole expert
    layer or pool plane."""
    e, h, f = conf["num_experts"], conf["hidden_size"], conf["moe_intermediate_size"]
    big = {f"[{e},{h},{f}]", f"[{e},{f},{h}]", f"[{e},{h},{2 * f}]"}
    big |= {"[" + ",".join(str(d) for d in p[1:]) + "]" for p in pools}  # a pool's plane
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(r"= \S+ (copy|dynamic-slice|transpose)\(", line) and any(s in line.split("=", 1)[1][:80] for s in big)
    ]
    return {"large_copies": copies}


def main() -> int:
    import jax
    from jax.experimental import topologies

    from perfbench.topologies import engine_hybrid, engine_hybrid_yarn

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((ROOT / CONFIG).read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    engine_hybrid.engine_config = engine_hybrid_yarn.engine_config  # what ``base.rehearse`` builds from
    seen: dict = {}
    lower = jax.stages.Lowered.compile

    def compile_and_keep(self, *a, **kw):
        compiled = lower(self, *a, **kw)
        seen["text"] = compiled.as_text()
        return compiled

    jax.stages.Lowered.compile = compile_and_keep
    for depth in [int(a) for a in sys.argv[1:]] or [conf["num_hidden_layers"]]:
        seen.clear()
        try:
            base.rehearse(dict(conf, num_hidden_layers=depth), topo.devices[0])
        except Exception as e:  # noqa: BLE001  (a refused compile is this script's answer)
            print(f"== depth {depth}: REFUSED: {type(e).__name__}: {str(e)[:600]}", flush=True)
            continue
        if "text" in seen:
            geo = conf["engine"]
            kv = (conf["num_key_value_heads"], geo["page_size"], 2 * conf["head_dim"])
            pools = [(0, geo["num_pages"], *kv)]
            print("   compiled text:", json.dumps(text_report(seen["text"], conf, pools)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
