"""The loop ``apps/jacobi3d.run(768, 768, 768)`` builds on one chip (tight-x
layout, the application's default 10 iterations a dispatch, nothing else
passed), compiled at its real size for a described ``v5e:2x2``: the
planner turns to row strips, the program is the row-tiled multistep at
k = 10 and holds no whole-block ``copy``, and two buffers are all it
allocates. Nothing runs; a compile that passes is not a chip result.

The topology is described inside a module-scoped fixture of
``tests/conftest.py`` (the on-chip-measurement guide, section 2): only the
worker that gets this file loads libtpu.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

N, ITERS = 768, 10
_COPY = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+copy\(", re.M)
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom-call\(.*"
                   r'custom_call_target="tpu_custom_call"', re.M)


def test_the_768_loop_is_the_row_tiled_multistep_at_depth_10(
        topo, as_on_the_chip):
    from jax.experimental import pallas as pl

    from stencil_tpu.domain.grid import GridSpec
    from stencil_tpu.geometry import Dim3, Radius
    from stencil_tpu.obs import scopes, telemetry
    from stencil_tpu.ops.jacobi import make_jacobi_loop
    from stencil_tpu.parallel import HaloExchange, grid_mesh

    one = Dim3(1, 1, 1)
    spec = GridSpec(Dim3(N, N, N), one, Radius.constant(1).without_x())
    ex = HaloExchange(spec, grid_mesh(one, list(topo.devices)[:1]))
    grids, orig = [], pl.pallas_call

    def recording(kernel, *args, **kw):
        grids.append((kernel.__qualname__.split(".")[0], kw.get("grid")))
        return orig(kernel, *args, **kw)

    scopes.clear()
    pl.pallas_call = recording
    try:
        make_jacobi_loop(ex, ITERS)     # as run() calls it: nothing pinned
        rec = scopes._registry[scopes.JACOBI_LOOP][-1]
        compiled = rec["fn"].lower(*rec["args"]).compile()
    finally:
        pl.pallas_call = orig

    staged = telemetry.get().records(
        kind="counter", name="kernel.multistep.staging")[-1]
    assert staged["k"] == ITERS and 0 < staged["rows"] < N
    assert staged["strips"] == -(-N // staged["rows"])
    assert staged["vmem_bytes"] <= 46 * 1024 * 1024
    # the wavefront runs nz + 2k steps, once a strip
    assert ("_make_multistep_row_tiled",
            (staged["strips"], N + 2 * ITERS)) in grids

    text = compiled.as_text()
    calls = _CALL.findall(text)
    assert calls == ["jacobi_multistep_rows.1"], calls
    assert "stencil.kernel.jacobi_multistep_rows" in text
    assert "while(" not in text, "one k = 10 pass needs no loop"
    p = spec.padded()
    block = f"{p.z},{p.y},{p.x}]"
    whole = [(i, s) for i, s in _COPY.findall(text) if block in s]
    assert not whole, f"whole-block copies: {whole}"

    mem = compiled.memory_analysis()
    buffer = 4 * p.z * p.y * p.x
    assert buffer == 1_854_504_960
    # curr and nxt, donated and aliased to the results; sel is not read
    assert mem.argument_size_in_bytes == 2 * buffer
    assert mem.alias_size_in_bytes == 2 * buffer
    assert mem.temp_size_in_bytes == 0
