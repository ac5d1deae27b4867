"""Adapter onto ``stencil_tpu.apps.astaroth``: the user's arguments go to
the application's own ``run()``, and the window dispatches the very step
that call compiled, on the domain it realized (see ``benchmark/capture.py``).
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import expect, max_abs_err
from benchmark.reference import astaroth as reference

CORE = (8, 8, 8)
N_RANDOM_BOXES = 8
# One number per field: max |program - float64 reference| after the first
# chunk, as a share of the largest change the reference makes to that field
# in the sampled boxes. Each field is judged against its own increment
# because the increments differ by three orders (dt = 1e-8: the velocities
# move by 3e-4 an iteration, the entropy by 3e-5, lnrho and the potentials
# by 5e-7), so one absolute limit would cover the momentum equation alone.
# It is taken over the sampled cells whose seeded value is under SMALL: a
# float32 there steps 16 times finer than in [0.5, 1), where the slow
# fields' increment is only ten steps and a sound run is off by an eighth
# of it, iteration after iteration. Some 350 of the 5,632 sampled cells a
# field, 30 of them in the box across the periodic wrap. The velocities
# and the entropy are held against the bfloat16 right-hand side; lnrho and
# the potentials, where that is 1 % of an increment and under the float32
# state's own rounding, against their equation left out. Readings in
# PERF.md section 2.
SMALL = 1.0 / 16
REL_ERR_LIMIT = {"lnrho": 0.03, "uux": 1e-4, "uuy": 1e-4, "uuz": 1e-4,
                 "ax": 0.03, "ay": 0.03, "az": 0.03, "entropy": 5e-4}


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import astaroth as app

        args = dict(config["rehearsal_args" if rehearsal else "args"])
        chunk = mix.get("iters_per_dispatch", "default")
        if chunk != "default":
            args["chunk"] = int(chunk)
        # the rehearsal walks the kernels too: run() only takes the Pallas
        # path on a TPU, so on the CPU the builder is told to interpret
        steps = capture.BuilderCapture(
            {"use_pallas": True, "interpret": True} if rehearsal else {})
        with capture.PallasBuilds() as pallas, \
                capture.patched(app, "make_astaroth_step", steps):
            result = app.run(devices=devices, **args)
        rec = steps.last
        k = int(rec["kwargs"].get("iters", 1))
        self.step = rec["fn"]
        self.domain = dd = result["domain"]
        self.handles = hs = result["handles"]
        self.names = list(hs)
        if tuple(self.names) != reference.FIELDS:
            raise RuntimeError(f"fields {self.names} != {reference.FIELDS}")
        self.curr = {n: dd.get_curr(hs[n]) for n in self.names}
        self.nxt = {n: dd.get_next(hs[n]) for n in self.names}
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(self.curr[self.names[0]].dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize,
                                        len(self.names))
        self.facts.update(
            iters_per_dispatch=k, dtype=str(dtype),
            chosen={
                "global_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "step_kwargs": str({a: b for a, b in rec["kwargs"].items()
                                    if a != "info"}),
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
        words = fields.seed_words(seed)
        for q, n in enumerate(self.names):
            self.curr[n] = None
            self.curr[n] = self._fill(words, np.uint32(q))
            self.domain.set_curr(self.handles[n], self.curr[n])

    def dispatch(self):
        self.curr, self.nxt = self.step(self.curr, self.nxt)
        return self.curr

    def _boxes(self):
        f = self.facts
        return fields.plan_boxes(f["global_zyx"], f["dims_zyx"], CORE,
                                 self._seed, N_RANDOM_BOXES)

    def sample(self):
        return [(o, {n: self._reader.read(self.curr[n], o, CORE)
                     for n in self.names}) for o in self._boxes()]

    def _reference(self, origin, iters, dtype=np.float64, rate_dtype=None):
        return reference.box_after(self._seed, origin, CORE, iters,
                                   self.facts["global_zyx"], dtype, rate_dtype)

    def compare(self, sample):
        k = self.facts["iters_per_dispatch"]
        err = dict.fromkeys(self.names, 0.0)
        moved = dict.fromkeys(self.names, 0.0)
        for origin, got in sample:
            seeded, ref = self._reference(origin, 0), self._reference(origin, k)
            for n in self.names:
                fine = seeded[n] < SMALL
                if fine.any():
                    err[n] = max(err[n], max_abs_err(got[n][fine],
                                                     ref[n][fine]))
                moved[n] = max(moved[n], max_abs_err(ref[n], seeded[n]))
        return [(f"first_chunk_rel_err.{n}", err[n] / moved[n],
                 REL_ERR_LIMIT[n]) for n in self.names]

    def control(self, sample):
        """The reference with its right-hand sides in bfloat16 and its
        state in float32, put in the program's place."""
        import ml_dtypes

        k = self.facts["iters_per_dispatch"]
        return self.compare([(o, self._reference(o, k, np.float32,
                                                 ml_dtypes.bfloat16))
                             for o, _ in sample])

    def faults(self, sample):
        """Per field, a program that leaves that field's equation out: the
        sample with the field as it was seeded. Each has to fail that
        field's number."""
        out = []
        for n in self.names:
            broken = [(o, dict(got, **{n: self._reference(o, 0)[n]}))
                      for o, got in sample]
            out.append((f"{n} left out", self.compare(broken)))
        return out

    def finite(self) -> bool:
        return all(bool(self._finite(a)) for a in self.curr.values())


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
