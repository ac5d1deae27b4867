"""Share of the roofline of the configuration's stencil kernels, counted
per call from the compiled depth and shapes of the run."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.roofline_share(ctx, "stencil")
