"""One fused Astaroth RK3 substep (``make_pallas_substep``): one call
writes the ``out`` fields of its recorded build's result shapes (8 whole
blocks today), less halo and padding, and reads as many ``in`` cells.
Substeps 1 and 2 also read the previous stage's ``out``; those reads are
LEFT OUT, so the share this gives is a lower bound.

Operations per cell, tallied from ``reference/astaroth.py``:
- a first derivative: 3 x (sub + mul) + 2 adds + 1 mul = 9
- a second derivative: 1 mul + 3 x (add + mul) + 3 adds + 2 muls = 12
- a cross derivative: 3 x (3 add/sub + mul) + 2 adds + 2 muls = 16
- lnrho and entropy need gradient and laplacian: 3*9 + 3*12 = 63 each
- the 3 velocity and 3 potential components need gradient and full
  hessian: 3*9 + 3*12 + 3*16 = 111 each
  -> derivatives: 2*63 + 6*111 = 792
- the four right-hand sides (continuity 8, induction 24, momentum ~140,
  entropy ~90, with 4 exponentials counted as one operation each) and the
  RK3 combination (8 fields x 5): ~300
-> 1092, rounded down to 1000 so that the count errs low.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_substep",)
FLOPS_PER_CELL = 1000       # for all the fields of a cell together
FIELDS = 8


def work(build: dict, facts: dict) -> dict:
    written = [call_cells(shape, facts) for shape in build["out_shapes"]]
    q = len(written)
    return {"per": "call",
            "bytes": 2 * sum(written) * facts["itemsize"],
            "flops": FLOPS_PER_CELL * sum(written) // FIELDS,
            "note": f"{q} fields read + {q} written per call; the "
                    "previous-stage reads of substeps 1 and 2 left out "
                    "(lower bound)"}
