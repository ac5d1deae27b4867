"""Share of the HBM roofline of the self-fill kernels on the bytes their
DMAs really move (the program's ``halo.self_fill.bytes_dma``), where
``self_fill_roofline`` counts each halo cell once."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.self_fill_moved(ctx)
