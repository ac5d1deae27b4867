"""NPB MG's restriction ``rprj3`` where a Pallas kernel implements it
(``make_pallas_mg_rprj3``; where plain XLA does, the trace holds no such
call and the operator's time is read by scope, in the scope table): one call reads the fine level's owned cells (its
first operand: a tight-x level, which shares the finest level's padding)
and writes an eighth as many coarse cells: 4.5 bytes a fine cell in
float32. Operations as ``mg.f`` writes it: 24 a COARSE cell (four partial
sums of 3 additions, 8 more and 4 multiplications), 3 a fine cell.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_mg_rprj3",)
FLOPS_PER_COARSE_CELL = 24


def work(build: dict, facts: dict) -> dict:
    fine = call_cells(build["in_shapes"][0], facts)
    coarse = fine // 8
    return {"per": "call",
            "bytes": (fine + coarse) * facts["itemsize"],
            "flops": FLOPS_PER_COARSE_CELL * coarse,
            "note": "the fine level read, an eighth as many coarse cells "
                    "written, per call; halo reads left out (lower bound)"}
