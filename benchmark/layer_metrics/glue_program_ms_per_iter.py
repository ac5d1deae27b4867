"""Device self time per iteration in ops under a ``stencil.*`` scope that
are neither a stencil kernel nor of the halo layer: the orchestration the
program wrote in ``jnp`` (shell re-sweeps, sphere masks, reshapes and
stacking round the kernels). Mean over chips."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.class_ms(ctx, "glue_program")
