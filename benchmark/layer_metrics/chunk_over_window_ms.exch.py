"""What a chunk of the exchange application's OWN loop (``exchange.iter``)
costs over the window's bare call and ``block_until_ready``, in an exchange
cell: as ``chunk_over_window_ms.app``."""

from benchmark import chunk_lib


def read(ctx):
    return chunk_lib.over_window_ms(ctx)
