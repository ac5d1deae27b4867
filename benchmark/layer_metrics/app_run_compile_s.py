"""Seconds of the application's ``run()`` in build, compile or cache load,
the first call and its own warm-up (the program's ``*.warmup`` spans)."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.app_run_seconds(ctx, "compile")
