"""Device time per iteration in ops that are neither a stencil kernel, a
halo kernel nor a collective: loop-carried copies, layout changes,
overlap-shell re-sweeps, pack and unpack. Mean over chips."""

from benchmark import layer_lib


def read(ctx):
    return layer_lib.glue_ms_per_iter(ctx)
