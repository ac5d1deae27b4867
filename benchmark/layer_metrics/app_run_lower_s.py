"""Seconds of the application's ``run()`` in lowering jaxprs to MLIR
(Mosaic's module generation in it): the program's ``compile.lower`` spans
(and what it folded of them) under a top-level span of ``run()``."""

from benchmark import compile_lib


def read(ctx):
    return compile_lib.stage_seconds(ctx, "lower")
