"""``kernels.ssm_grouped_time_share`` (the file beside this one) without the
Pallas calls: device time of the state-space mixers' XLA part (both
projections, the conv and its pool's re-layout, gate and norm, the copies
around the update kernel, the scan's per-row einsums) over device busy time.
The definition's ``"part": "xla"`` says so to that reader."""

import importlib.util
import pathlib


def read(ctx, definition):
    path = pathlib.Path(__file__).with_name("kernels.ssm_grouped_time_share.py")
    spec = importlib.util.spec_from_file_location("perfbench_reader_ssm_grouped_time_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx, definition)
