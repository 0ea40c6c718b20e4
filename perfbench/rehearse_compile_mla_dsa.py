"""``rehearse_compile.py`` for the configuration of
``topologies/engine_longctx_latent`` (DeepSeek-V3.2: a latent pool beside its
plane of indexer keys on the flat step, 16 of 256 experts held): compile its
saturated flat step for a DESCRIBED TPU v5e, with no chip attached, and print
``memory_analysis()``. Settles depth and geometry before the first chip call:
bytes and "accepted" / "refused", never a time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse_compile_mla_dsa.py

``rehearse_compile.rehearse`` builds its ``EngineConfig`` through
``topologies/engine.py``, which maps neither the indexer nor the held share:
for the call it is given this configuration's own function.
"""

from __future__ import annotations

import json
import sys

from perfbench import rehearse_compile as base
from perfbench.topologies import engine, engine_longctx_latent

CONFIG = "perfbench/configs/deepseek-v3.2.1chip.json"


def main() -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((base.ROOT / CONFIG).read_text())
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    engine.engine_config = engine_longctx_latent.engine_config
    try:
        base.rehearse("deepseek-v3.2.1chip", conf, topo.devices[0])
    except Exception as e:  # noqa: BLE001  (a refused compile is this script's answer)
        print(f"== REFUSED: {type(e).__name__}: {str(e)[:3000]}", flush=True)
    finally:
        engine.engine_config = engine_longctx_latent.stock_engine_config
    return 0


if __name__ == "__main__":
    sys.exit(main())
