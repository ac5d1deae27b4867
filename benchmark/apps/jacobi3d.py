"""Adapter onto ``stencil_tpu.apps.jacobi3d``: the user's arguments go to
the application's own ``run()``, and the window dispatches the very loop
that call compiled, on the domain it realized (see ``benchmark/capture.py``
for why). Layout, partition, method, overlap, temporal depth and row tiling
are the application's choices; they are printed as facts, never passed.
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import (expect, lower_precision_sample,
                                   max_abs_err, reference_boxes)
from benchmark.reference import jacobi3d as reference

CORE = (8, 8, 16)          # cells of a sampled box's core (z, y, x)
N_RANDOM_BOXES = 8
# |program - float64 reference| over the sampled boxes after the first
# chunk. Values lie in [0, 1]; see PERF.md section 2 for the readings this
# was set from (sound fp32 runs far below, the bfloat16 control far above).
MAX_ABS_ERR = 2e-6


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import jacobi3d as app

        args = dict(config["rehearsal_args" if rehearsal else "args"])
        chunk = mix.get("iters_per_dispatch", "default")
        if chunk != "default":
            args["chunk"] = int(chunk)
        # the rehearsal walks the kernels too: run() only takes the Pallas
        # path on a TPU, so on the CPU the builders are told to interpret
        force = {"use_pallas": True, "interpret": True} if rehearsal else {}
        loops = capture.BuilderCapture(force)
        steps = capture.BuilderCapture(force)
        with capture.PallasBuilds() as pallas, \
                capture.patched(app, "make_jacobi_loop", loops), \
                capture.patched(app, "make_jacobi_step", steps):
            result = app.run(args.pop("x"), args.pop("y"), args.pop("z"),
                             devices=devices, **args)
        if loops.built:
            rec, k = loops.last, int(loops.last["args"][1])
        else:
            rec, k = steps.last, 1
        self.loop = rec["fn"]
        self.domain = dd = result["domain"]
        self.handle = h = result["handle"]
        self.sel = rec["first_call"][2]
        self.curr, self.nxt = dd.get_curr(h), dd.get_next(h)
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(self.curr.dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize, 1)
        self.facts.update(
            iters_per_dispatch=k, dtype=str(dtype),
            chosen={
                "global_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "method": result["method"],
                "overlap": result["overlap"],
                "loop_builder": ("make_jacobi_loop" if loops.built
                                 else "make_jacobi_step"),
                "loop_kwargs": str(rec["kwargs"]),
                "iters_per_dispatch": k,
                "pallas_builds": pallas.summary(),
            })
        expect(config, self.facts)
        self._fill = fields.make_fill(spec, dd.sharding(), dtype.name)
        self._finite = fields.make_all_finite(spec, dd.sharding())
        self._reader = fields.BoxReader(spec)
        self._seed = None

    def seed(self, seed: int) -> None:
        self._seed = int(seed)
        self.curr = None               # drop the old buffer before the new
        self.curr = self._fill(fields.seed_words(seed), np.uint32(0))
        self.domain.set_curr(self.handle, self.curr)

    def dispatch(self):
        self.curr, self.nxt = self.loop(self.curr, self.nxt, self.sel)
        return self.curr

    def _boxes(self):
        f = self.facts
        return fields.plan_boxes(
            f["global_zyx"], f["dims_zyx"], CORE, self._seed, N_RANDOM_BOXES,
            through=[reference.sphere_surface_point(f["global_zyx"])])

    def sample(self):
        return [(o, self._reader.read(self.curr, o, CORE))
                for o in self._boxes()]

    def compare(self, sample):
        want = reference_boxes(reference, self._seed, CORE, self.facts,
                               [o for o, _ in sample], np.float64)
        err = max(max_abs_err(got, ref)
                  for (_, got), (_, ref) in zip(sample, want))
        return [("first_chunk_max_abs_err", err, MAX_ABS_ERR)]

    def control(self, sample):
        return self.compare(lower_precision_sample(
            reference, self._seed, CORE, self.facts, sample))

    def finite(self) -> bool:
        return bool(self._finite(self.curr))


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
