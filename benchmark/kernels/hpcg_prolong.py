"""HPCG's prolongation between two tight-x levels where a Pallas kernel
implements it (``make_pallas_hpcg_prolong``; the 64^3 -> 128^3 pair is
plain XLA under the kernel's name: the trace holds no such call there and
its time is read by scope, in the scope table): one call adds the coarse
level onto the even rows of the even planes of the fine level, IN PLACE,
over the owned cells of its recorded build's result shape (the fine level:
a tight-x level, which shares the finest level's padding). What the
operator must move: the coarse level read (an eighth of the fine level)
and a quarter of the fine level read and written back (whole rows: a row's
odd columns ride with its even ones): 5/8 of a fine cell a fine cell, 2.5
bytes in float32, the ``bytes_min`` of the program's own
``hpcg.iter_plan``. One addition a coarse cell.
"""

from benchmark.layer_lib import call_cells

FAMILIES = ("make_pallas_hpcg_prolong",)
# eighths of the fine level: a quarter read, a quarter written, the coarse
# level read
EIGHTHS_MOVED = 2 + 2 + 1


def work(build: dict, facts: dict) -> dict:
    fine = call_cells(build["out_shapes"][0], facts)
    return {"per": "call",
            "bytes": EIGHTHS_MOVED * fine * facts["itemsize"] // 8,
            "flops": fine // 8,
            "note": "the coarse level read, a quarter of the fine level "
                    "read and written back, per call (lower bound: what "
                    "the prolongation must move). The kernel streams every "
                    "even fine plane WHOLE both ways, at lane-tile "
                    "granularity, and is not charged for it: 625 MB a call "
                    "onto 512^3 where 336 MB are counted"}
