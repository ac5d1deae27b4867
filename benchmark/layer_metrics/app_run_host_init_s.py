"""Seconds of the application's ``run()`` in realize and host-side initial
data with its transfer (the program's ``*.realize`` and ``*.init`` spans)."""

from benchmark import scope_lib


def read(ctx):
    return scope_lib.app_run_seconds(ctx, "host_init")
