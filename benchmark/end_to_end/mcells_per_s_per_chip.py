"""Global cells x iterations completed in the window / window seconds /
chips / 1e6. Every chunk counted ended in block_until_ready; the whole
window's time is the denominator, gaps between dispatches included."""


def read(ctx):
    w, f = ctx["window"], ctx["facts"]
    return f["global_cells"] * w["iterations"] / w["seconds"] / w["chips"] / 1e6
