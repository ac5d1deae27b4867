"""The temporal multistep jacobi kernel (``ops/pallas_stencil.py``): k
steps per pass over the block, staged in VMEM.

What ONE CALL must move, whatever k is: ``curr`` read once and ``next``
written once over the cells of the call's own result shape (the recorded
build's, less halo and padding), not the domain's block: a sweep split
into several calls adds up to one block. The depth k is read from the
recorded build (the wavefront grid runs nz + 2k steps, as
``chip_smoke._multistep_depth`` reads it), never assumed. Operations per cell and step, from the reference's
equation (``reference/jacobi3d.py``): five additions and one division for
the average, i.e. 6 (the two sphere selects are not counted).
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_jacobi_multistep", "_make_multistep_row_tiled")
FLOPS_PER_CELL_STEP = 6


def depth(build: dict, facts: dict) -> int:
    nz = (build["out_shapes"][0][-3]
          - (facts["padded_zyx"][0] - facts["block_zyx"][0]))
    return (build["grid"][-1] - nz) // 2


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    k = depth(build, facts)
    return {"per": "call", "k": k,
            "bytes": 2 * cells * facts["itemsize"],
            "flops": FLOPS_PER_CELL_STEP * cells * k,
            "note": f"curr read once + next written once per call, k={k}"}
