"""Roofline share of the sparse latent read: the least time the traced slice's
selected rows can take over the read's measured device time, in %; not
capped (perfbench/sparse_mla_trace.py has the count)."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from perfbench import sparse_mla_trace  # noqa: E402


def read(ctx, definition):
    return sparse_mla_trace.roofline(ctx)
