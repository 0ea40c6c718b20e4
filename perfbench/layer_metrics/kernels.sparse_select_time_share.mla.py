"""Device time of the selection (``llmd.sparse_select``: the 32 counting passes
over [T, max_model_len] that find each token's top-k-th index score, and the
mask) over device busy time, in %: ``perfbench/sparse_trace.py``'s shapes, for
a configuration that states its indexer as ``index_*`` keys."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from perfbench import sparse_trace  # noqa: E402


def read(ctx, definition):
    config = ctx.get("config") or {}
    if "index_head_dim" not in config:
        return None
    as_sa = dict(config, sa_config={"indexer_head_dim": config["index_head_dim"]})
    return sparse_trace.share(dict(ctx, config=as_sa), "select")
