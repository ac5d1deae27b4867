"""One stream-collide pass of D3Q19 lattice-Boltzmann
(``make_pallas_lbm_step``): one call updates the owned cells of its
recorded build's result shape (a whole block, less halo and padding). A
cell reads its 19 streamed populations, one from each array of the current
lattice, and writes the 19 of the next: 38 arrays, 152 bytes in float32,
whatever implements the pass (the halo cells read and the halo and padding
rows a whole-plane stream carries are LEFT OUT, so the share errs low).
Operations are the reference's own, counted term by term from
``benchmark/reference/lbm.py`` as it is written (``FLOPS_PER_CELL``: 18 for
the density, 27 for the momentum, 4 for the velocity, 6 for its square, 12
for the diagonals' ``c.u``, 7 an equilibrium and 3 shared, 3 a relaxation:
260); the program shares the even part of an opposite pair's equilibria and
does fewer, so the share errs low there too.
"""

from benchmark.layer_lib import call_cells
from benchmark.reference.lbm import FLOPS_PER_CELL, Q

FAMILIES = ("make_pallas_lbm_step",)
ARRAYS_MOVED = 2 * Q        # 19 populations read, 19 written


def work(build: dict, facts: dict) -> dict:
    cells = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": ARRAYS_MOVED * cells * facts["itemsize"],
            "flops": FLOPS_PER_CELL * cells,
            "note": f"{Q} arrays read, {Q} written, per call; "
                    f"{FLOPS_PER_CELL} flop a cell by the reference's "
                    "count; halo reads left out (lower bound)"}
