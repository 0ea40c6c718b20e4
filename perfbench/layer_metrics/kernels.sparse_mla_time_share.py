"""Device time of the sparse latent read (the selection's positions, the row
gather, the 128-head products over the selected rows) over device busy time,
in %: perfbench/sparse_mla_trace.py says how its events are found."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from perfbench import sparse_mla_trace  # noqa: E402


def read(ctx, definition):
    return sparse_mla_trace.share(ctx)
