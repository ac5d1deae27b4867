"""One box operator of NPB MG on one level (``make_pallas_mg_box``):
``resid`` (r = v - A u) or ``psinv`` (u = u + S r), one call over the owned
cells of its recorded build's result shape (a whole block, less halo and
padding: the tight-x levels share the finest level's padding, which is the
layout the kernel takes). A cell reads the box's array and the centre array
and writes the result: three arrays, 12 bytes in float32, whatever
implements it (the halo reads are LEFT OUT, so the share errs low).
Operations are NPB's own, as ``mg.f`` writes the two with its partial sums:
``resid`` 15 a cell, ``psinv`` 16 (of the 58 a finest-level cell an
iteration that NPB counts: 2 x 15 + 16 + 3 + 2.9 at the top, an eighth
more a level down). The two builds of a level have one shape, so the
smaller count serves both: the share errs low.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_mg_box",)
FLOPS_RESID, FLOPS_PSINV = 15, 16
ARRAYS_MOVED = 3            # the box's array and the centre read, one written


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": ARRAYS_MOVED * cells * facts["itemsize"],
            "flops": min(FLOPS_RESID, FLOPS_PSINV) * cells,
            "note": "two arrays read, one written, per call; 15 flop a "
                    "cell (resid; psinv has 16); halo reads left out "
                    "(lower bound)"}
