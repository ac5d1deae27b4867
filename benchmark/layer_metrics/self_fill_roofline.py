"""Share of the HBM roofline of the self-fill kernels, per exchange."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.roofline_share(ctx, "halo")
