"""Device time per iteration in the configuration's stencil kernels, mean
over chips."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.class_ms_per_iter(ctx, ("stencil",))
