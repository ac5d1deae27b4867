"""Median over the window's dispatches of dispatch time / exchanges in the
dispatch, in milliseconds."""

import statistics


def read(ctx):
    w = ctx["window"]
    return 1e3 * statistics.median(w["dispatch_s"]) / w["iters_per_dispatch"]
