"""Seconds of the application's ``run()`` in the backend: XLA and Mosaic
compiling on a persistent-cache miss, retrieval and load on a hit (the
program's ``compile.backend`` spans, and what it folded of them, under a
top-level span of ``run()``)."""

from benchmark import compile_lib


def read(ctx):
    return compile_lib.stage_seconds(ctx, "backend")
