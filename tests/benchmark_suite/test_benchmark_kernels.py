"""The kernels' byte and operation functions against hand-worked values."""

import pytest

from _bench_util import ROOT  # noqa: F401  (puts the repo on sys.path)
from benchmark.harness import load_module

F512 = {"block_zyx": [512, 512, 512], "itemsize": 4, "quantities": 1,
        "radius_zyx": [[1, 1], [1, 1], [0, 0]], "padded_zyx": [514, 528, 512]}
OUT512 = [(514, 528, 512)]          # the block with its halos and y pad
CELLS = 512 ** 3


@pytest.mark.parametrize("k", [2, 10, 12])
def test_multistep_bytes_do_not_depend_on_k(k):
    mod = load_module("kernels", "jacobi_multistep")
    build = {"kernel": "make_pallas_jacobi_multistep", "grid": (512 + 2 * k,),
             "out_shapes": OUT512}
    w = mod.work(build, F512)
    assert w["per"] == "call" and w["k"] == k
    # curr read once + next written once: 2 x 512^3 x 4 B = 1.07 GB
    assert w["bytes"] == 2 * CELLS * 4 == 1_073_741_824
    assert w["flops"] == 6 * CELLS * k


def test_multistep_row_tiled_reads_depth_from_last_grid_axis():
    mod = load_module("kernels", "jacobi_multistep")
    build = {"kernel": "_make_multistep_row_tiled", "grid": (3, 768 + 24),
             "out_shapes": [(770, 784, 768)]}
    facts = dict(F512, block_zyx=[768, 768, 768], padded_zyx=[770, 784, 768])
    assert mod.depth(build, facts) == 12
    assert mod.work(build, facts)["bytes"] == 2 * 768 ** 3 * 4


def test_sweep_moves_three_arrays_a_call():
    mod = load_module("kernels", "jacobi_sweep")
    w = mod.work({"grid": (512,), "out_shapes": OUT512}, F512)
    assert w["per"] == "call" and w["bytes"] == 12 * CELLS
    assert w["flops"] == 6 * CELLS
    # 1.61 GB at 819 GB/s is 1.97 ms: the four-chip cell's kernel floor
    assert 1.96e-3 < w["bytes"] / 819e9 < 1.97e-3


def test_astaroth_substep_reads_and_writes_eight_fields():
    mod = load_module("kernels", "astaroth_substep")
    facts = {"block_zyx": [256, 256, 256], "itemsize": 4, "quantities": 8,
             "padded_zyx": [262, 272, 256]}
    w = mod.work({"out_shapes": [(262, 272, 256)] * 8}, facts)
    assert w["bytes"] == 16 * 256 ** 3 * 4 == 1_073_741_824
    assert w["flops"] == 1000 * 256 ** 3
    # memory is the bound under the table: 1.31 ms against 0.085 ms
    assert w["bytes"] / 819e9 > 10 * w["flops"] / 197e12


def test_self_fill_counts_every_halo_cell_once_per_exchange():
    mod = load_module("kernels", "self_fill")
    facts = {"block_zyx": [512, 512, 512], "itemsize": 4, "quantities": 4,
             "radius_zyx": [[3, 3], [3, 3], [3, 3]]}
    assert mod.halo_cells(facts) == 518 ** 3 - 512 ** 3 == 4_774_104
    w = mod.work({}, facts)
    assert w["per"] == "iteration" and w["flops"] == 0
    assert w["bytes"] == 2 * 4 * 4_774_104 * 4 == 152_771_328
    # the tight-x layout has no x halo: only y and z faces count
    tight = dict(facts, block_zyx=[256, 256, 256], quantities=8,
                 radius_zyx=[[3, 3], [3, 3], [0, 0]])
    assert mod.halo_cells(tight) == (262 * 262 - 256 * 256) * 256


def test_roofline_share_stays_under_100_whatever_k(monkeypatch):
    """A deeper temporal block makes the call longer and moves the same
    bytes: the share can only fall."""
    from benchmark import layer_lib

    mod = load_module("kernels", "jacobi_multistep")
    shares = []
    for k, call_ms in ((10, 9.8), (12, 11.8), (24, 23.5)):
        build = {"kernel": "make_pallas_jacobi_multistep",
                 "grid": (512 + 2 * k,), "out_shapes": OUT512}
        op = {"cls": "stencil", "kernel": "jacobi_multistep", "build": build,
              "dur": call_ms * 1e6, "start": 0.0, "self": call_ms * 1e6}
        ctx = {"trace": {"chips": [{"ops": [op]}]},
               "window": {"iterations": k}, "facts": F512,
               "kernels": {"stencil": {"jacobi_multistep": mod}},
               "peak": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
               "say": lambda _: None}
        shares.append(layer_lib.roofline_share(ctx, "stencil"))
    assert shares[0] == pytest.approx(13.38, abs=0.05)
    assert shares == sorted(shares, reverse=True) and max(shares) < 100


# a later PR that splits a sweep into calls over parts of the block: each
# call is counted for the cells of its own result shape, so the parts add
# up to one block's bytes and the share cannot pass 100 % by the split
HALVES = {"z": [(258, 528, 512)] * 2, "y": [(514, 272, 512)] * 2,
          "y, less padding than the block": [(514, 264, 512)] * 2}


@pytest.mark.parametrize("axis", sorted(HALVES))
@pytest.mark.parametrize("kernel", ["jacobi_multistep", "jacobi_sweep"])
def test_calls_over_half_blocks_add_up_to_one_block(kernel, axis):
    mod = load_module("kernels", kernel)
    whole = mod.work({"grid": (532,), "out_shapes": OUT512}, F512)
    halves = [mod.work({"grid": (s[0] - 2 + 20,), "out_shapes": [s]}, F512)
              for s in HALVES[axis]]
    total = sum(h["bytes"] for h in halves)
    if "less padding" in axis:
        assert 0.9 * whole["bytes"] < total < whole["bytes"]   # errs low
    else:
        assert total == whole["bytes"]
        assert sum(h["flops"] for h in halves) == whole["flops"]
    if kernel == "jacobi_multistep":
        assert {h["k"] for h in halves} == {10} == {whole["k"]}


def test_astaroth_substep_counts_the_fields_its_call_writes():
    """Four of the eight fields a call, or half the block: half the bytes."""
    mod = load_module("kernels", "astaroth_substep")
    facts = {"block_zyx": [256, 256, 256], "itemsize": 4, "quantities": 8,
             "padded_zyx": [262, 272, 256]}
    whole = mod.work({"out_shapes": [(262, 272, 256)] * 8}, facts)
    for part in ([(262, 272, 256)] * 4, [(134, 272, 256)] * 8):
        w = mod.work({"out_shapes": part}, facts)
        assert 2 * w["bytes"] == whole["bytes"]
        assert 2 * w["flops"] == whole["flops"]


def test_split_sweep_share_stays_where_it_was():
    """Two half-block calls in the time of one whole call read the same
    share; counted for the whole block each they would read twice it."""
    from benchmark import layer_lib

    mod = load_module("kernels", "jacobi_sweep")

    def share(shapes, ms_each):
        ops = [{"cls": "stencil", "kernel": "jacobi_sweep", "dur": ms_each * 1e6,
                "start": i * ms_each * 1e6, "self": ms_each * 1e6,
                "build": {"grid": (s[0] - 2,), "out_shapes": [s]}}
               for i, s in enumerate(shapes)]
        ctx = {"trace": {"chips": [{"ops": ops}]}, "window": {"iterations": 1},
               "facts": F512, "kernels": {"stencil": {"jacobi_sweep": mod}},
               "peak": {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12},
               "say": lambda _: None}
        return layer_lib.roofline_share(ctx, "stencil")

    whole = share(OUT512, 2.73)
    assert whole == pytest.approx(72.0, abs=0.5)
    assert share(HALVES["z"], 2.73 / 2) == pytest.approx(whole)
