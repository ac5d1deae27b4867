"""Median idle time on the first chip between the end of one chunk's program
and the start of the next (``XLA Modules`` line), in an application cell."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.launch_gap_ms(ctx)
