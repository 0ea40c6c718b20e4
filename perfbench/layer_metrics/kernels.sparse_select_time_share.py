"""Device time of the selection (the counting passes that find each token's
top-k-th index score, and the mask) over device busy time, in %: matched by
the shapes in each event's HLO text (perfbench/sparse_trace.py says why)."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from perfbench import sparse_trace  # noqa: E402


def read(ctx, definition):
    return sparse_trace.share(ctx, "select")
