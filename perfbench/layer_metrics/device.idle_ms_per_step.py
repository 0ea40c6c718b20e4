"""Idle ms a step of the traced slice, the no-load gaps left out: perfbench/idle_turn.py."""

from perfbench import idle_turn


def read(ctx, definition):
    return idle_turn.idle_ms_per_step(ctx)
