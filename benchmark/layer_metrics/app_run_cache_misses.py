"""Executables the application's ``run()`` compiled because the persistent
cache did not hold them: the program's ``compile.backend`` spans under a
top-level span of ``run()`` that read ``cache: miss``, and the misses of
what it folded."""

from benchmark import compile_lib


def read(ctx):
    return compile_lib.cache_misses(ctx)
