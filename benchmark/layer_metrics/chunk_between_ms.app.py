"""Median host time between two step chunks of the application's own loop
inside ``run()``, in an application cell: from one chunk's ``t1_ns`` to the
next one's ``t0_ns`` (statistics, the span, bookkeeping and, in Astaroth,
the exchange-only chunk the loop times after every iteration). Nothing
where ``run()`` made fewer than two."""

from benchmark import chunk_lib


def read(ctx):
    return chunk_lib.between_ms(ctx)
