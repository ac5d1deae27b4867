"""Device self time per iteration in a Krylov solver's reductions, mean
over chips: every op whose innermost scope is ``stencil.solver.dot`` (the
dot products and norms of an iteration, each a pass over whole arrays whose
scalar the next kernel of the same program reads). Printed beside it: the
vector updates under ``stencil.solver.axpy``. Both are part of
``glue_program_ms_per_iter``. Nothing where no op carries the scope."""

from benchmark import scope_lib

DOT = "stencil.solver.dot"
AXPY = "stencil.solver.axpy"


def read(ctx):
    if scope_lib.scoped(ctx) is None:
        return None
    chips = ctx["trace"]["chips"]
    per = len(chips) * ctx["window"]["iterations"] * 1e6

    def under(scope):
        ops = [op["self"] for chip in chips for op in chip["ops"]
               if op["scope"] == scope]
        return sum(ops) / per if ops else None

    dot, axpy = under(DOT), under(AXPY)
    if dot is None:
        return None
    ctx["say"](f"solver: {dot:.4f} ms an iteration under {DOT}, "
               + ("no op" if axpy is None else f"{axpy:.4f} ms")
               + f" under {AXPY}")
    return dot
