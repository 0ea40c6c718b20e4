"""Record the second small trace: a few steps of a small jitted program under
the PROGRAM's spans (``llmd.*``, llmd_tpu/obs/profiling.py) and through its
profiler control, with a sleep in one host phase of each step that outlasts
the rest of the step's host time, so that each idle gap of the device is
covered for more than half by a known span: schedule before step 2, finish
after step 3, build (inside launch) before the others. Run once on the chip
(PR 25); writes chiprun_out/fixture_llmd/. The test reads it back and checks
what the four ``device.idle_*_share`` read and that they add up to the idle
share."""

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llmd_tpu.obs import profiling  # noqa: E402
from perfbench import idle_split, trace_reduce  # noqa: E402

out = ROOT / "chiprun_out" / "fixture_llmd"
shutil.rmtree(out, ignore_errors=True)


@jax.jit
def llmd_fixture_step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) * 0.01
    return x


x = jnp.ones((1024, 1024), jnp.bfloat16)
llmd_fixture_step(x).block_until_ready()
profiling.start(out / "discard")  # the first start costs more: not into the fixture
profiling.stop()
profiling.start(out / "trace")
SHORT = 0.0003  # ~1.2 ms on the chip's host: the long sleeps are sized against that
for i in range(5):
    with profiling.span("llmd.serve.intake", added=1):
        time.sleep(SHORT)
    with profiling.span("llmd.step") as step:
        with profiling.span("llmd.step.admit"):
            pass
        with profiling.span("llmd.sched.schedule", prefills=1, decodes=i):
            time.sleep(0.030 if i == 2 else SHORT)
        with profiling.span("llmd.runner.launch"):
            with profiling.span("llmd.runner.build"):
                time.sleep(0.008)
            with profiling.span("llmd.runner.dispatch", program="fixture:T=1024"):
                x = llmd_fixture_step(x)
        with profiling.span("llmd.runner.wait"):
            x.block_until_ready()
        with profiling.span("llmd.step.finish", outputs=1):
            time.sleep(0.030 if i == 3 else SHORT)
        step.set_metadata(kind="mixed", rows=1 + i, tokens=1024)
    with profiling.span("llmd.serve.deliver", outputs=1):
        time.sleep(SHORT)
profiling.stop()
path = trace_reduce.find_xplane(str(out / "trace"))
shutil.copy(path, out / "fixture_llmd_v5e.xplane.pb")
loaded = trace_reduce.load(path)
r = trace_reduce.reduce(loaded)
(out / "fixture_llmd_v5e.expected.json").write_text(json.dumps({
    "chips": r["chips"], "busy_s": r["busy_s"], "window_s": r["window_s"],
    "idle_by_host_s": r["idle_by_host_s"], "idle_gaps": r["idle_gaps"][:5],
    "shares": {p: idle_split.share(r, p) for p in ("schedule", "launch", "finish", "unattributed")},
    "device": jax.devices()[0].device_kind,
}, indent=1))
print(json.dumps({"fixture_bytes": (out / "fixture_llmd_v5e.xplane.pb").stat().st_size,
                  "busy_s": r["busy_s"], "window_s": r["window_s"], "idle_by_host_s": r["idle_by_host_s"]}))
