"""The in-place periodic halo fills of single-block axes
(``ops/halo_fill.make_self_fill``), one kernel per axis and exchange.

What ONE EXCHANGE must move: every halo cell's source read once and the
halo cell written once, for every quantity. The halo cells of a block are
its allocation with halos less its compute region, from the realized
radii (an axis with no halo, as x in the tight-x layout, adds none). The
128-lane and 8-row tiles the kernels actually rewrite are the
amplification this share exposes, so they are not counted. No arithmetic.
"""

FAMILIES = ("make_self_fill",)


def halo_cells(facts: dict) -> int:
    held = cells = 1
    for n, (rm, rp) in zip(facts["block_zyx"], facts["radius_zyx"]):
        held *= n + rm + rp
        cells *= n
    return held - cells


def work(build: dict, facts: dict) -> dict:
    n = halo_cells(facts) * facts["quantities"]
    return {"per": "iteration", "bytes": 2 * n * facts["itemsize"],
            "flops": 0,
            "note": f"{n} halo cells read and written once per exchange"}
