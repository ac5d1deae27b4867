"""HPCG's injection between two tight-x levels where a Pallas kernel
implements it (``make_pallas_hpcg_restrict``; the 128^3 -> 64^3 pair is
plain XLA under the kernel's name: the trace holds no such call there and
its time is read by scope, in the scope table): one call writes the coarse
level's owned cells (its recorded build's result shape: a tight-x level,
which shares the finest level's padding), each from ONE fine cell of the
even rows of the even planes. Those rows, a quarter of the fine level (two
coarse levels' worth: a row's odd columns ride with its even ones), are
what the operator must read, and the coarse level is written: 12 bytes a
coarse cell in float32, the ``bytes_min`` of the program's own
``hpcg.iter_plan``. No operation: injection copies.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_hpcg_restrict",)
COARSE_CELLS_MOVED = 2 + 1      # a quarter of the fine level read, one written


def work(build: dict, facts: dict) -> dict:
    coarse = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": COARSE_CELLS_MOVED * coarse * facts["itemsize"],
            "flops": 0,
            "note": "a quarter of the fine level read, the coarse level "
                    "written, per call (lower bound: what injection must "
                    "move). The kernel streams every even fine plane WHOLE, "
                    "at lane-tile granularity, and is not charged for it: "
                    "348 MB a call from 512^3 where 201 MB are counted"}
