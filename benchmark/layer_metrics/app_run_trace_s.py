"""Seconds of the application's ``run()`` in jax tracing its programs: the
program's outermost ``compile.trace`` spans (and what it folded of them)
under a top-level span of ``run()``, the Pallas kernel bodies included."""

from benchmark import compile_lib


def read(ctx):
    return compile_lib.stage_seconds(ctx, "trace")
