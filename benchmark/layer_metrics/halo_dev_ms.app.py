"""Device time per iteration in the halo layer of an application cell:
self-fill kernels and collectives, mean over chips."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.class_ms_per_iter(ctx, ("halo", "collective"))
