"""Device time per exchange in self-fill kernels and collectives, mean over
chips, in an exchange cell."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.class_ms_per_iter(ctx, ("halo", "collective"))
