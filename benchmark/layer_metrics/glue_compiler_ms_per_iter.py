"""Device self time per iteration in ops with no ``stencil.*`` scope: what
the compiler added (loop-carried copies, layout changes), plus the
containers' own time. Mean over chips."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.class_ms(ctx, "glue_compiler")
