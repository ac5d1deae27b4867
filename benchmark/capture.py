"""Taking the program's own compiled loop, and the facts of its kernels.

An application's ``run()`` builds its loop inside itself and returns no
loop, and a perf PR may not edit the benchmark. So an adapter wraps, for
the length of one ``run()`` call, the builder where the application looks
it up (as ``chip_smoke.PallasRecorder`` wraps ``pallas_call``), keeps the
compiled function that ``run()`` built, the arguments it was built with
and the constant inputs of its first call, and dispatches that very
function in the window. Whatever a later PR makes ``run()`` choose is then
what the window drives.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def patched(obj, name: str, wrapper):
    """``obj.name`` replaced by ``wrapper(original)`` inside the block."""
    orig = getattr(obj, name)
    setattr(obj, name, wrapper(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


class BuilderCapture:
    """Records every call of a loop builder: ``built[i]`` holds ``fn`` (the
    object the builder returned, the one to dispatch), ``args``, ``kwargs``
    and ``first_call`` (the positional arguments of the function's first
    call, e.g. the jacobi sphere codes)."""

    def __init__(self, extra_kwargs=None):
        self.built = []
        self.extra_kwargs = dict(extra_kwargs or {})

    def __call__(self, builder):
        def recording_builder(*args, **kwargs):
            kwargs = dict(kwargs, **self.extra_kwargs)
            fn = builder(*args, **kwargs)
            rec = {"fn": fn, "args": args, "kwargs": kwargs,
                   "first_call": None}
            self.built.append(rec)

            def call(*xs, **kw):
                if rec["first_call"] is None:
                    rec["first_call"] = xs
                return fn(*xs, **kw)

            return call

        return recording_builder

    @property
    def last(self) -> dict:
        if not self.built:
            raise RuntimeError("the application built no loop through the "
                               "wrapped builder: nothing to dispatch")
        return self.built[-1]


class PallasBuilds:
    """Every ``pl.pallas_call`` built while active, as plain facts: the
    kernel function's outer name, the ``name=`` the call was given (the
    stem of its instruction's name in the compiled module), the grid, the
    result shapes, whether it is an interpret-mode build and, from its
    first call, the operand shapes. The kernel descriptions under
    ``kernels/`` compute bytes and operations from these, never from
    constants."""

    def __enter__(self):
        import jax
        from jax.experimental import pallas as pl

        self._pl, self._orig, self.builds = pl, pl.pallas_call, []

        def recording(kernel, *args, **kw):
            gs = kw.get("grid_spec")
            grid = kw.get("grid") or getattr(gs, "grid", ()) or ()
            if isinstance(grid, int):
                grid = (grid,)
            outs = jax.tree.leaves(kw.get("out_shape"))
            rec = {
                "kernel": kernel.__qualname__.split(".")[0],
                "name": kw.get("name"),
                "grid": tuple(int(g) for g in grid),
                "out_shapes": [tuple(int(d) for d in o.shape) for o in outs],
                "out_dtypes": [str(o.dtype) for o in outs],
                "interpret": bool(kw.get("interpret", False)),
                "n_operands": None,
                "in_shapes": [],
                "calls_traced": 0,
            }
            self.builds.append(rec)
            fn = self._orig(kernel, *args, **kw)

            def call(*xs, **kws):
                leaves = jax.tree.leaves(xs)
                rec["n_operands"] = len(leaves)
                rec["in_shapes"] = [tuple(int(d) for d in a.shape)
                                    for a in leaves]
                rec["calls_traced"] += 1
                return fn(*xs, **kws)

            return call

        pl.pallas_call = recording
        return self

    def __exit__(self, *exc):
        self._pl.pallas_call = self._orig

    def summary(self) -> list:
        return [f"{b['kernel']} grid={b['grid']} out={b['out_shapes']} "
                f"operands={b['n_operands']} interpret={b['interpret']}"
                for b in self.builds]


def spec_facts(spec, chips: int, itemsize: int, quantities: int) -> dict:
    """What the kernels' byte functions and the readers need of a realized
    domain's layout."""
    g, b, r, d = spec.global_size, spec.base, spec.radius, spec.dim
    return {
        "global_zyx": [g.z, g.y, g.x],
        "global_cells": g.x * g.y * g.z,
        "block_zyx": [b.z, b.y, b.x],
        "dims_zyx": [d.z, d.y, d.x],
        "radius_zyx": [[r.z(-1), r.z(1)], [r.y(-1), r.y(1)],
                       [r.x(-1), r.x(1)]],
        "padded_zyx": list(spec.block_shape_zyx()),
        "chips": chips,
        "itemsize": itemsize,
        "quantities": quantities,
    }
