"""Seconds of the application's ``run()`` in its own timed chunks and what
follows them (the program's ``*.steps`` span)."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.app_run_seconds(ctx, "steps")
