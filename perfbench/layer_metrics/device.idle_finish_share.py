"""One of the four shares ``device.idle_share`` splits into: perfbench/idle_split.py."""

from perfbench import idle_split


def read(ctx, definition):
    return idle_split.share(ctx.get("trace"), definition["phase"])
