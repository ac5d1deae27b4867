"""Half a sweep of HPCG's symmetric Gauss-Seidel in the eight-colour order
(``make_pallas_hpcg_symgs``): one call updates, in place, the owned planes
of ONE z parity of its recorded build's result shape (a whole block, less
ring and padding), their four in-plane colours in the sweep's order. What
the call must move, whatever implements it: ``x`` read whole (the planes it
updates and the planes beside them), ``r`` read and ``x`` written on half
the planes: 4 + 2 + 2 = 8 bytes a cell of the level in float32 (the ring's
reads are LEFT OUT, so the share errs low). Operations: an updated row is
26 additions (r and its up to 26 neighbours) and one multiplication, 27,
and a call updates half the level's rows (HPCG counts 2 a nonzero: the
share errs low).
"""

from benchmark.layer_lib import call_cells
from benchmark.reference.hpcg import FLOPS_PER_ROW_SWEEP

FAMILIES = ("make_pallas_hpcg_symgs",)
BYTES_PER_CELL_F32 = 8      # x read whole; r read, x written on half


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": 2 * cells * facts["itemsize"],
            "flops": FLOPS_PER_ROW_SWEEP * (cells // 2),
            "note": "x read whole, r read and x written on half the planes, "
                    f"per call; {FLOPS_PER_ROW_SWEEP} flop an updated row; "
                    "ring reads left out (lower bound)"}
