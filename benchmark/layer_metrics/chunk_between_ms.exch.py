"""Median host time between two ``exchange.iter`` chunks of the exchange
application's own loop inside ``run()``, in an exchange cell: as
``chunk_between_ms.app``."""

from benchmark import chunk_lib


def read(ctx):
    return chunk_lib.between_ms(ctx)
