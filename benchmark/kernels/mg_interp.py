"""NPB MG's prolongation ``interp`` where a Pallas kernel implements it
(``make_pallas_mg_interp``; where plain XLA does, the trace holds no such
call and the operator's time is read by scope, in the scope table): one call writes the fine level's owned cells (its
recorded build's result shape: a tight-x level, which shares the finest
level's padding) from an eighth as many coarse cells, and below the finest
level that is all it moves: 4.5 bytes a fine cell in float32 (the finest
level ALSO reads its u, 8.5: left out, so the share errs low). Operations
as ``mg.f`` writes it: 23 a COARSE cell (its three partial sums' 4
additions and the eight parities' 19), 2.9 a fine cell.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_mg_interp",)
FLOPS_PER_COARSE_CELL = 23


def work(build: dict, facts: dict) -> dict:
    fine = call_cells(build["out_shapes"][0], facts)
    coarse = fine // 8
    return {"per": "call",
            "bytes": (fine + coarse) * facts["itemsize"],
            "flops": FLOPS_PER_COARSE_CELL * coarse,
            "note": "an eighth as many coarse cells read, the fine level "
                    "written, per call; the finest level's read of its own "
                    "u left out (lower bound)"}
