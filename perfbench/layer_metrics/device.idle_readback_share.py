"""One of the five shares the pipelined step's idle time splits into: perfbench/idle_turn.py."""

from perfbench import idle_turn


def read(ctx, definition):
    return idle_turn.share(ctx, definition["part"])
