"""Machine check of BASELINE.md's HBM-traffic claims from the compiled
Mosaic kernels (VERDICT r4 item 6).

scripts/export_traffic.py lowers the production Pallas kernels for the TPU
platform (jax.export — full Mosaic pipeline, no hardware) and reports every
``tpu.enqueue_dma``'s direction, extent, and conditionality. These tests
assert the byte movement that the performance story rests on:

- the temporal-blocked jacobi multistep moves ONE plane in and one out per
  grid step regardless of k (the ~1/k HBM-traffic claim);
- the astaroth substep's steady-state fetch is exactly (tz, ty+16, px) per
  field — input amplification (ty+16)/ty x px/nx, the documented
  1.125 x lane-pad factor (~1.12 at the 256^3 production ty=128);
- the x self-fill rewrites exactly the two edge lane-tiles per z batch
  (the ~42x RMW amplification any inline-x-halo layout pays).

Subprocess pattern as in test_overlap_hlo.py: jax.export's lowering
recursion is incompatible with pytest's rewritten frames.
"""

import json
import os
import subprocess
import sys
from collections import Counter

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "scripts", "export_traffic.py")


def _report(*args) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    last = None
    for attempt in range(2):  # lowering is host-heavy; retry once under load
        try:
            proc = subprocess.run(
                [sys.executable, _SCRIPT, *args],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
                cwd=_REPO,
            )
        except subprocess.TimeoutExpired:
            if attempt == 0:
                continue
            raise
        last = proc
        if proc.returncode == 0:
            return json.loads(proc.stdout.strip().splitlines()[-1])
    assert last.returncode == 0, f"{args}: {last.stderr[-3000:]}"


def _groups(kernel) -> Counter:
    return Counter((d["dir"], tuple(d["shape"])) for d in kernel["dmas"])


def test_multistep_traffic_is_k_independent():
    r4 = _report("multistep", "4")
    r8 = _report("multistep", "8")
    for rep in (r4, r8):
        (k,) = rep["kernels"]
        pz, py, px = rep["padded"]
        plane = (1, py, px)
        ins = [d for d in k["dmas"] if d["dir"] == "in"]
        outs = [d for d in k["dmas"] if d["dir"] == "out"]
        # every HBM transfer is exactly ONE padded plane — no k-scaled
        # extent exists anywhere in the kernel
        assert ins and all(tuple(d["shape"]) == plane for d in ins + outs)
        assert len(ins) <= 2 and len(outs) == 1
        assert all(d["loop_depth"] == 0 for d in k["dmas"])
        # z-wavefront pipeline: fill + drain extend the plane sweep by
        # 2(k-1) steps
        assert k["grid"] == [pz + 2 * rep["k"] - 2]
    # identical DMA inventory at k=4 and k=8: per-step HBM bytes do not
    # scale with k, so traffic per advanced step falls ~1/k
    def inventory(rep):
        return sorted(_groups(rep["kernels"][0]).items())

    assert inventory(r4) == inventory(r8)
    # static upper bound: k fused steps enqueue <= 3 planes/step over
    # pz + 2k - 2 steps, vs the serialized path's k * (1 read + 1 write)
    # full-array sweeps
    for rep in (r4, r8):
        k = rep["k"]
        pz = rep["padded"][0]
        fused_planes = 3 * (pz + 2 * k - 2)
        serial_planes = 2 * k * pz
        assert fused_planes / serial_planes < 2.2 / k


@pytest.mark.parametrize(
    "n,tight",
    [(64, False), (128, True), (256, True), (256, False)],
    ids=["64-inline", "128-tight-x", "256-production-tight", "256-inline"],
)
def test_substep_steady_state_amplification(n, tight):
    rep = _report("substep", str(n), *(["tight"] if tight else []))
    (k,) = rep["kernels"]
    tz, ty = rep["tiles"]
    pz, py, px = rep["padded"]
    nz, ny, nx = rep["base"]
    g = _groups(k)
    # strip-start window: (tz + 2*3, ty + 16, px) once per field
    assert g[("in", (tz + 6, ty + 16, px))] == 8
    # steady per-tile fetch: (tz, ty+16, px) per field (one prefetch site;
    # a strip's first tile is covered by the window DMA instead)
    assert g[("in", (tz, ty + 16, px))] == 8
    # out-buffer read (substep > 0 consumes the previous stage's out):
    # full-row tiles, both branches
    assert g[("in", (tz, ty, px))] == 16
    # write-back: one full-row tile per field, unconditional
    assert g[("out", (tz, ty, px))] == 8
    assert all(
        d["if_depth"] == 0 for d in k["dmas"] if d["dir"] == "out"
    )
    assert k["grid"] == [ny // ty, nz // tz]
    # steady-state input amplification: PARSED bytes of the per-field
    # stage fetch vs the compulsory (tz, ty, nx) fp32 tile. Must equal the
    # documented (ty+16)/ty x px/nx model exactly — at the 256^3
    # production pick ty=128 the y factor is 144/128 = 1.125 ("~1.12")
    stage_bytes = [
        d["bytes"] for d in k["dmas"]
        if d["dir"] == "in" and tuple(d["shape"]) == (tz, ty + 16, px)
    ]
    compulsory = tz * ty * nx * 4
    amp = stage_bytes[0] / compulsory
    assert amp == pytest.approx((1 + 16 / ty) * (px / nx), rel=1e-12)
    if tight:
        # tight-x (Radius.without_x): px == nx — the lane-pad x factor the
        # layout exists to remove is exactly 1 in the compiled artifact
        assert px == nx
    if n == 256:
        # the production pick's documented y window: ty=128 -> 1.125
        assert ty == 128


def test_fill_y_moves_row_tiles_only():
    rep = _report("fill-y")
    (k,) = rep["kernels"]
    tzb = rep["tzb"]
    pz, py, px = rep["padded"]
    r = rep["radius"]
    tile = (tzb, 8, px)
    ins = [d for d in k["dmas"] if d["dir"] == "in"]
    outs = [d for d in k["dmas"] if d["dir"] == "out"]
    # every transfer is one 8-row window of a z batch. 256 rows are a
    # multiple of 8, so neither destination window holds an owned row and
    # neither is read: a batch reads the two source windows (spelled twice,
    # at step 0 and prefetched a step ahead) and writes them onto the two
    # destination windows — the 8-row-tile economics of ops/halo_fill.py
    assert all(tuple(d["shape"]) == tile for d in ins + outs)
    assert len(ins) == 4 and all(d["if_depth"] == 1 for d in ins)
    assert len(outs) == 2 and all(d["if_depth"] == 0 for d in outs)
    assert all(d["loop_depth"] == 0 for d in k["dmas"])
    assert k["grid"] == [-(-pz // tzb)]
    # written rows per batch vs the 2r logical halo rows: the 8-row
    # minimum write granularity
    written = sum(d["bytes"] for d in outs)
    logical = 2 * r * tzb * px * 4
    assert written / logical == pytest.approx(16 / 6, rel=1e-12)


def test_fill_z_stages_whole_planes():
    rep = _report("fill-z")
    (k,) = rep["kernels"]
    pz, py, px = rep["padded"]
    r = rep["radius"]
    g = _groups(k)
    plane = (r, py, px)
    # one grid step, two staged copies (top r planes -> lo halo, first r
    # planes -> hi halo), each a read + write of exactly r whole planes:
    # z halos have NO write amplification (the untiled dim)
    assert g[("in", plane)] == 2 and g[("out", plane)] == 2
    assert len(k["dmas"]) == 4
    assert all(d["if_depth"] == 0 and d["loop_depth"] == 0 for d in k["dmas"])
    assert k["grid"] == [1]


def test_fill_x_rewrites_edge_lane_tiles_only():
    rep = _report("fill-x")
    (k,) = rep["kernels"]
    tzb = rep["tzb"]
    pz, py, px = rep["padded"]
    tile = (tzb, py, 128)
    ins = [d for d in k["dmas"] if d["dir"] == "in"]
    outs = [d for d in k["dmas"] if d["dir"] == "out"]
    # every transfer is one (TZB, py, 128) edge lane-tile; exactly the two
    # edge tiles are written per batch, nothing else of the array is touched
    assert ins and all(tuple(d["shape"]) == tile for d in ins + outs)
    assert len(outs) == 2 and all(d["if_depth"] == 0 for d in outs)
    assert k["grid"] == [-(-pz // tzb)]
    # PARSED write-back bytes per batch against the logical halo columns
    # actually filled (2r per side pair at r=3 symmetric): the documented
    # ~42x RMW amplification any inline-x-halo layout pays
    # (ops/halo_fill.py:14-19)
    r = rep["radius"]
    written = sum(d["bytes"] for d in outs)
    logical = 2 * r * tzb * py * 4
    assert written / logical == pytest.approx(256 / 6, rel=1e-12)
