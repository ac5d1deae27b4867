"""95th percentile, over the window's dispatches, of the host time from
dispatch to the return of block_until_ready, per iteration in the dispatch
(nearest rank; the sample count is the window's dispatch count)."""

from benchmark.harness import percentile


def read(ctx):
    w = ctx["window"]
    per = [1e3 * t / w["iters_per_dispatch"] for t in w["dispatch_s"]]
    return percentile(per, 0.95)
