"""1 - union of the chip's op intervals / window, on the worst chip, in an
exchange cell."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.idle_share(ctx)
