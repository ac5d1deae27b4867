"""HPCG's operator alone, ``Ap = A p`` (``make_pallas_hpcg_spmv``): one call
over the owned cells of its recorded build's result shape (a whole block,
less ring and padding). A cell reads ``p`` and writes ``Ap``: two arrays, 8
bytes in float32, whatever implements it (the reads of the ring and of the
two neighbouring planes' rows are LEFT OUT, so the share errs low).
Operations are the row's own: 26 additions or subtractions of neighbours
and the diagonal's multiplication, 27 a row (HPCG counts 2 a nonzero, 54 an
interior row: the share errs low there too).
"""

from benchmark.layer_lib import call_cells
from benchmark.reference.hpcg import FLOPS_PER_ROW_SPMV

FAMILIES = ("make_pallas_hpcg_spmv",)
ARRAYS_MOVED = 2            # p read, Ap written


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": ARRAYS_MOVED * cells * facts["itemsize"],
            "flops": FLOPS_PER_ROW_SPMV * cells,
            "note": "one array read, one written, per call; "
                    f"{FLOPS_PER_ROW_SPMV} flop a row; ring reads left out "
                    "(lower bound)"}
