"""The per-step jacobi sweep kernel (``make_pallas_jacobi_sweep``): one
iteration per call. It reads ``curr`` and the int32 sphere codes and
writes ``next``: three arrays over the cells of the call's own result shape
(the recorded build's, less halo and padding; the codes are 4 bytes a cell
too). Operations as in ``jacobi_multistep.py``: 6 per cell.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_jacobi_sweep",)
FLOPS_PER_CELL_STEP = 6


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": cells * (2 * facts["itemsize"] + 4),
            "flops": FLOPS_PER_CELL_STEP * cells,
            "note": "curr + sphere codes read, next written, per call"}
