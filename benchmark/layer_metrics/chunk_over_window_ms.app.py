"""What a chunk of the application's OWN loop costs over the window's bare
call and ``block_until_ready``, in an application cell: the median wall of
the step chunks that ``run()`` made before the window (``*.iter``, of as
many iterations as the window dispatches; not the exchange-only
``astaroth.exchange``), less the window's median dispatch. Host clock on
both sides; the table ``benchmark/chunk_lib.py`` prints splits both into
the enqueue and the wait."""

from benchmark import chunk_lib


def read(ctx):
    return chunk_lib.over_window_ms(ctx)
