"""Device self time per iteration in Pallas custom-calls the program named
``stencil.kernel.<a stencil kernel>``, mean over chips: the stencil kernels
found by name. Must agree with ``kernel_ms_per_iter`` (found by shape);
where it does not, the table printed with it says which call differs."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.class_ms(ctx, "kernel")
