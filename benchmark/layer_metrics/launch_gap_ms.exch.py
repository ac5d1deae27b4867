"""Median idle time on the first chip between the end of one dispatch's
program and the start of the next, in an exchange cell."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.launch_gap_ms(ctx)
