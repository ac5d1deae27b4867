"""Device self time per iteration in EVERY op under a scope of the halo
layer (``stencil.halo.*``, the self-fill and remote-DMA kernels): Pallas,
collective or XLA pack/unpack alike. Mean over chips, in an application
cell."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.class_ms(ctx, "halo")
