"""Time per iteration, on the worst chip, with a collective in flight or
waited for and no other op running. Nothing to read on one chip."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.collective_exposed_ms(ctx)
