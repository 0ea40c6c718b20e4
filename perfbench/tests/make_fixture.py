"""Record the small trace the trace-reduction test reads. Run once on the
chip (PR 24); writes chiprun_out/fixture/. A few steps of a small jitted
program under ``pb.step`` spans, with sleeps between them so that the
device has idle gaps to find."""

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench import trace_reduce  # noqa: E402

out = ROOT / "chiprun_out" / "fixture"
shutil.rmtree(out, ignore_errors=True)


@jax.jit
def step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) * 0.01
    return x


x = jnp.ones((1024, 1024), jnp.bfloat16)
step(x).block_until_ready()
jax.profiler.start_trace(str(out / "trace"))
for _ in range(4):
    with jax.profiler.TraceAnnotation("pb.step"):
        x = step(x)
        x.block_until_ready()
    time.sleep(0.002)
jax.profiler.stop_trace()
path = trace_reduce.find_xplane(str(out / "trace"))
shutil.copy(path, out / "fixture_v5e.xplane.pb")
r = trace_reduce.reduce(trace_reduce.load(path))
(out / "fixture_v5e.expected.json").write_text(json.dumps({
    "chips": r["chips"], "busy_s": r["busy_s"], "window_s": r["window_s"],
    "top_op": trace_reduce.top_ops(r["op_seconds"], 1)[0][0],
    "idle_gaps": r["idle_gaps"][:3], "device": jax.devices()[0].device_kind,
}, indent=1))
print(json.dumps({"fixture_bytes": (out / "fixture_v5e.xplane.pb").stat().st_size, "busy_s": r["busy_s"], "window_s": r["window_s"]}))
