"""Adapter onto ``stencil_tpu.apps.hpcg``: the user's arguments go to the
application's own ``run()``, and the window dispatches the very iteration
that call compiled, on the hierarchy of domains it realized (see
``benchmark/capture.py``). Layout, which levels run a Pallas kernel, the
order of the sweep's colours and the length of a set are the application's
choices; they are printed as facts (its own ``hpcg.iter_plan``), never
passed, and what the configuration says of them is held to that plan
(:func:`check_plan`). What the SOURCE fixes (the operator, the colours'
order, four levels, sets of 50) is the reference's.

The seeded state (``benchmark/reference/hpcg.py`` ``seeded_state``): x, r,
p and b of the finest level dense in [-1, 1) from ``fields.py``'s hash on
the owned cells and ZERO on the ghost ring and padding (the Dirichlet face:
the program reads it and never writes it), ``rtz`` > 0 and the set's count
k of 1 .. 48 from the seed. Everything else the program holds (z, the
scratch, the lower levels) is written before it is read. So the FIRST
dispatch is a general iteration on every operator and level, neither a
set's first nor its last, and it is the one compared with the float64
reference of that one iteration over the whole grid (the three scalars are
sums over every row, so nothing less than the whole grid gives them).
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import expect, max_abs_err
from benchmark.reference import hpcg as reference

# what a user can pass on the command line of apps/hpcg.py
USER_ARGS = ("n", "x", "y", "z", "sets", "dtype")
# what the window drives, for the fidelity test
DRIVES = ("stencil_tpu.apps.hpcg", "stencil_tpu.apps.hpcg", "make_hpcg_iter",
          "step")

LANE = 128
CORE = (16, 16, 16)
N_RANDOM_BOXES = 8
ARRAYS = ("x", "r", "p")
DRAW = {"x": reference.DRAW_X, "r": reference.DRAW_R, "p": reference.DRAW_P,
        "b": reference.DRAW_B}
# max |program - float64 reference| over the sampled boxes after the first
# dispatch, and the three scalars relative. The seeded x, r and p lie in
# [-1, 1) and rtz is set so that beta is of order one: after the iteration
# x stays within +-1.1, p within +-3 and r within +-1.5. A float32 run
# rounds at 6e-8 a term over the few hundred terms of a V-cycle (every row
# is a sum of 27 over sixteen colour updates a sweep on four levels).
# Readings at 512^3 on the chip (PERF.md section 2; 16 seeds: three of
# ``control.py`` and thirteen runs): sound runs read at most 6.0e-8 (x),
# 7.0e-8 (r), 2.8e-7 (p) and 2.3e-7 relative (the scalars); the bfloat16
# control at least 2.8e-3, 5.6e-3, 8.0e-3 and 1.5e-3; the x-wrapped fault
# 1.0e-5 (x), 5.4e-3 (r), 1.9e-2 (p) and 1.1e-4 (the scalars) on the boxes,
# which hold both x faces. At the 16^3 rehearsal a sum has 4,096 terms to
# average over: sound p reads up to 1.8e-6 and the scalars 2.4e-6.
MAX_ABS_ERR = {"x": 3e-6, "r": 1e-5, "p": 2e-5}
MAX_REL_ERR = 1e-5          # alpha, beta, normr
# the dispatch that opens a set inside the program, made after the window:
# how far what it leaves is from a set's FIRST iteration by the program's own
# alpha, p and A p (its scratch): x = alpha p (from x = 0), r = b - alpha A p
# (from r = b), beta = 0, normr0 = |b| (relative). Rounding alone is one
# operation's; a program that kept its x or its r reads what they held
# (PERF.md section 2: ``restart skipped``)
MAX_RESTART_ERR = 1e-5
# normr over normr0 at the end of the set that dispatch opened: the solve
# converges (PERF.md section 2; 1 is no progress)
MAX_SET_RESIDUAL = 0.03
# cells that may differ AT ALL: the ghost ring and padding of every array
# of every level from zero, and b's cells from what the seed made
EXACT = 0


def check_plan(plan: dict) -> None:
    """What the configuration says of the program, held to the program's
    own ``hpcg.iter_plan``: four levels, each half the one above; a level
    whose rows are whole lane tiles lies ``tight_x`` with A in the Pallas
    box kernel (``hpcg_resid``; ``hpcg_spmv`` on the finest) and the sweep
    in ``hpcg_symgs``. (The colours' order, the three reductions, the set's
    length and that nothing goes to the host inside a dispatch are the same
    in every build: the comparison with the reference holds the first
    three, ``tests/test_hpcg.py`` the lowered program.) ``ops/hpcg``
    falls to XLA by itself wherever a kernel does not take a block, and
    such a run would be as ``correct``: it is refused here, since it is not
    the cell the configuration names."""
    bad = []
    levels = plan["levels"]
    if len(levels) != reference.LEVELS:
        bad.append(f"{len(levels)} levels, not {reference.LEVELS}")
    for above, below in zip(levels, levels[1:]):
        if [2 * m for m in below["grid"]] != list(above["grid"]):
            bad.append(f"level {below['level']} {below['grid']} is not half "
                       f"level {above['level']} {above['grid']}")
    for i, lv in enumerate(levels):
        tight = lv["grid"][2] % LANE == 0
        want = "tight_x" if tight else "inline"
        if lv["layout"] != want:
            bad.append(f"level {lv['level']} ({lv['grid'][2]} a row) lies "
                       f"{lv['layout']}, not {want}")
        if not tight:
            continue
        kernels = ["hpcg_symgs"] + (["hpcg_spmv"] if i == 0 else [])
        if i + 1 < len(levels):
            kernels.append("hpcg_resid")
        for name in kernels:
            impl = lv["operators"].get(name, {}).get("impl")
            if impl != "pallas":
                bad.append(f"level {lv['level']}'s {name} is {impl}, not "
                           f"pallas")
    if bad:
        raise RuntimeError("hpcg.iter_plan is not the configuration's: "
                           + "; ".join(bad))


def boxes(global_zyx, seed: int):
    """Origins (z, y, x of the core's first cell; every box inside the
    grid): the eight corners; an edge and a face of each axis at mid-grid;
    one odd-origin box that crosses a lane tile, a row group and a z-parity
    seam of the sweep's kernel; the rest drawn from the seed."""
    g, c = np.asarray(global_zyx), np.asarray(CORE)
    last, mid = g - c, (g - c) // 2
    out = [tuple(int(last[a]) if (corner >> a) & 1 else 0 for a in range(3))
           for corner in range(8)]
    for axis in range(3):
        edge = [0, 0, 0]
        edge[axis] = int(mid[axis])             # the edge along this axis
        out.append(tuple(edge))
        face = [int(m) for m in mid]
        face[axis] = int(last[axis])            # the far face across it
        out.append(tuple(face))
    seam = [min(int(l), max(0, s)) for l, s in
            zip(last, (g[0] // 2 - 7, 64 - 7, LANE - 7))]
    out.append(tuple(seam))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    for _ in range(N_RANDOM_BOXES):
        out.append(tuple(int(rng.randint(0, l + 1)) for l in last))
    return out


def make_fill(spec, sharding, dtype):
    """``fill(seed words, q) -> stacked array``: draw q of the seeded
    state on the owned cells, zero on the ring and the padding; one compile
    serves every seed and array."""
    import jax
    import jax.numpy as jnp

    def fill(seed, q):
        (z, y, x), owned, _, _ = fields._cells(spec)
        u = fields._uniform_traced(seed, q, z, y, x, 0)
        return jnp.where(owned, reference.from_uniform(jnp, u),
                         0.0).astype(dtype)

    return jax.jit(fill, out_shardings=sharding)


def make_moved(sharding):
    """``moved(a, b) -> int``: allocated cells whose bits differ."""
    import jax
    import jax.numpy as jnp

    def moved(a, b):
        return jnp.sum(a != b, dtype=jnp.int32)

    return jax.jit(moved, in_shardings=(sharding, sharding))


def make_ring_count(spec, sharding):
    """``count(a) -> int``: cells of the ghost ring and the padding that
    do not hold zero."""
    import jax
    import jax.numpy as jnp

    def count(a):
        _, owned, _, _ = fields._cells(spec)
        return jnp.sum((a != 0) & ~owned, dtype=jnp.int32)

    return jax.jit(count, in_shardings=(sharding,))


def make_restart_err():
    """``err(x, r, p, t, b, alpha, beta, normr0) -> float``: the state a
    dispatch left against a set's first iteration (``MAX_RESTART_ERR``)."""
    import jax
    import jax.numpy as jnp

    def err(x, r, p, t, b, alpha, beta, normr0):
        far = jnp.maximum(jnp.max(jnp.abs(x - alpha * p)),
                          jnp.max(jnp.abs(r - (b - alpha * t))))
        norm = jnp.abs(normr0 / jnp.sqrt(jnp.sum(b * b)) - 1)
        return jnp.maximum(jnp.maximum(far, jnp.abs(beta)), norm)

    return jax.jit(err)


def make_kept():
    """``kept(x, r, b) -> float``: what ``make_restart_err`` would read of
    the NEXT dispatch if it kept this x (``max |x|``) or this r (``max |r -
    b|``): the smaller of the two."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, r, b: jnp.minimum(jnp.max(jnp.abs(x)),
                                               jnp.max(jnp.abs(r - b))))


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import hpcg as app
        from stencil_tpu.obs import telemetry

        args = dict(config["rehearsal_args" if rehearsal else "args"])
        if mix.get("iters_per_dispatch", "default") != "default":
            raise RuntimeError("hpcg dispatches ONE iteration (the residual "
                               "is read between them): a mix cannot pin more")
        # the rehearsal walks the kernels where a level takes them: run()
        # only takes the Pallas path on a TPU, so on the CPU the builder is
        # told to interpret
        steps = capture.BuilderCapture(
            {"use_pallas": True, "interpret": True} if rehearsal else {})
        with capture.PallasBuilds() as pallas, \
                capture.patched(app, "make_hpcg_iter", steps):
            result = app.run(devices=devices, **args)
        rec = steps.last
        self.step = rec["fn"]
        self.domain = dd = result["domain"]
        self.levels = result["levels"]
        self.handles = hs = result["handles"]
        self._app = app
        self.state = app.take_state(self.levels, result["scalars"])
        self.b = dd.get_curr(hs["b"])
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(self.b.dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize,
                                        len(hs))
        plan = telemetry.get().records(kind="counter",
                                       name="hpcg.iter_plan")[-1]
        self.facts.update(
            iters_per_dispatch=1, dtype=str(dtype),
            chosen={
                "grid_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "iter_kwargs": str(rec["kwargs"]),
                "levels": len(self.levels),
                "iter_plan": "; ".join(
                    f"{lv['level']}:{'x'.join(map(str, lv['grid']))} "
                    f"{lv['layout']} " + ",".join(
                        f"{name[5:]}={op['impl']}"
                        for name, op in lv["operators"].items())
                    for lv in plan["levels"]),
                "pallas_builds": pallas.summary(),
            })
        expect(config, self.facts)
        check_plan(plan)
        self.shape = tuple(self.facts["global_zyx"])
        sharding = dd.sharding()
        self._fill = make_fill(spec, sharding, dtype.name)
        self._moved = make_moved(sharding)
        self._rings = [make_ring_count(lv.spec, lv.sharding())
                       for lv, _ in self.levels]
        self._finite = [fields.make_all_finite(lv.spec, lv.sharding())
                        for lv, _ in self.levels]
        self._reader = fields.BoxReader(spec)
        self._restart_err, self._kept_err = make_restart_err(), make_kept()
        self._seed = self._next_set_of = None

    def _arrays(self):
        """Every array the program holds, with its level's index."""
        yield from ((0, self.state[q]) for q in self._app.FINE)
        for i, held in enumerate(self.state["coarse"]):
            yield from ((i + 1, a) for a in held.values())

    def seed(self, seed: int) -> None:
        import jax

        self._seed = int(seed)
        self._next_set_of = None
        words = fields.seed_words(seed)
        for name in ARRAYS:
            self.state[name] = None     # drop the old buffer before the new
            self.state[name] = self._fill(words, np.uint32(DRAW[name]))
        self.b = None
        self.domain.set_curr(self.handles["b"], None)
        self.b = self._fill(words, np.uint32(DRAW["b"]))
        self.domain.set_curr(self.handles["b"], self.b)
        rtz, k = reference.seeded_scalars(seed, self.shape)
        # where the program's own scalars lie: a scalar placed elsewhere
        # would have the iteration compiled again for it
        for name, value in (("rtz", rtz), ("k", k), ("normr0", 1),
                            ("normr", 0), ("alpha", 0), ("beta", 0)):
            old = self.state[name]
            self.state[name] = jax.device_put(
                np.asarray(value, old.dtype), old.sharding)

    def dispatch(self):
        self.state = self.step(self.state, self.b)
        return self.state

    def sample(self):
        """The sampled boxes of x, r and p and the three scalars, as the
        first dispatch left them."""
        got = {"boxes": [(o, {q: self._reader.read(self.state[q], o, CORE)
                              for q in ARRAYS})
                         for o in boxes(self.shape, self._seed)]}
        got.update({q: float(self.state[q])
                    for q in ("alpha", "beta", "normr")})
        return got

    def _reference(self, sample, **how):
        """The reference's sample: the same boxes and scalars after ONE
        iteration from the seeded state, over the whole grid (``how``:
        ``dtype``, ``wrap_x``; the plain one is kept for the seed)."""
        key = (self._seed, tuple(sorted(how)))
        if getattr(self, "_kept", (None,))[0] == key:
            return self._kept[1]
        state, b = reference.seeded_state(
            self._seed, self.shape, **({"dtype": how["dtype"]}
                                       if "dtype" in how else {}))
        after = reference.cg_iteration(state, b,
                                       wrap_x=how.get("wrap_x", False))
        out = {"boxes": [
            (o, {q: np.array(after[q][tuple(slice(a, a + n)
                                            for a, n in zip(o, CORE))])
                 for q in ARRAYS}) for o, _ in sample["boxes"]]}
        out.update({q: float(after[q]) for q in ("alpha", "beta", "normr")})
        if not how:
            self._kept = (key, out)
        return out

    def _next_set(self):
        """After the window (not timed), once a seed: the set in hand is
        driven to its end, the dispatch that opens the next one is held to
        a set's first iteration, and that set is driven to ITS end.
        ``(restart error, what a kept x or r would have read, normr /
        normr0 at the set's end)``; a program whose count does not reach a
        set's end in a set's dispatches reads infinity."""
        import jax

        if self._next_set_of == self._seed:
            return self._next_set_read

        def finish() -> bool:
            for _ in range(reference.SET_ITERS):
                if int(self.state["k"]) >= reference.SET_ITERS:
                    break
                jax.block_until_ready(self.dispatch())
            return int(self.state["k"]) >= reference.SET_ITERS

        read = (float("inf"),) * 3
        if finish():
            st = self.state
            kept = float(self._kept_err(st["x"], st["r"], self.b))
            st = self.dispatch()                        # opens a set
            restart = float(self._restart_err(
                *(st[q] for q in ("x", "r", "p", "t")), self.b,
                *(st[q] for q in ("alpha", "beta", "normr0"))))
            if int(st["k"]) == 1 and finish():
                read = (restart, kept, float(self.state["normr"])
                        / float(self.state["normr0"]))
        self._next_set_of, self._next_set_read = self._seed, read
        return read

    def _errors(self, got, want):
        err = dict.fromkeys(ARRAYS, 0.0)
        for (_, a), (_, b) in zip(got["boxes"], want["boxes"]):
            for q in ARRAYS:
                err[q] = max(err[q], max_abs_err(np.asarray(a[q]),
                                                 np.asarray(b[q])))
        rel = max(abs(got[q] - want[q]) / abs(want[q])
                  for q in ("alpha", "beta", "normr"))
        return err, rel

    @staticmethod
    def _first_iter_checks(err, rel):
        return [(f"first_iter_max_abs_err.{q}", err[q], MAX_ABS_ERR[q])
                for q in ARRAYS] + [
            ("first_iter_max_rel_err.scalars", rel, MAX_REL_ERR)]

    def compare(self, sample):
        checks = self._first_iter_checks(
            *self._errors(sample, self._reference(sample)))
        fresh = self._fill(fields.seed_words(self._seed),
                           np.uint32(DRAW["b"]))
        b_moved = int(self._moved(self.b, fresh))
        del fresh
        restart, _, residual = self._next_set()
        ring = sum(int(self._rings[i](a)) for i, a in self._arrays())
        return checks + [
            ("restart_max_err", restart, MAX_RESTART_ERR),
            ("last_set_normr_over_normr0", residual, MAX_SET_RESIDUAL),
            ("ring_cells_moved", ring, EXACT),
            ("b_cells_moved", b_moved, EXACT)]

    def _in_place_of_the_program(self, sample, **how):
        """The checks with a reference computed ``how`` where the program's
        sample stood (what only the program's state shows reads as sound)."""
        return self._first_iter_checks(*self._errors(
            self._reference(sample, **how), self._reference(sample))) + [
            ("restart_max_err", 0.0, MAX_RESTART_ERR),
            ("last_set_normr_over_normr0", 0.0, MAX_SET_RESIDUAL),
            ("ring_cells_moved", 0, EXACT), ("b_cells_moved", 0, EXACT)]

    def control(self, sample):
        """The reference computed in bfloat16 (state and arithmetic), put
        in the program's place."""
        import ml_dtypes

        return self._in_place_of_the_program(sample,
                                             dtype=ml_dtypes.bfloat16)

    def faults(self, sample):
        """A program that forms ``x -+ 1`` by a lane roll and lets it wrap:
        the reference with x taken periodically in every operator, put in
        the program's place. And one that opens a set without ``x <- 0`` or
        without ``r <- b``: what the kept array would have read."""
        return [("x wrapped",
                 self._in_place_of_the_program(sample, wrap_x=True)),
                ("restart skipped", [("restart_max_err", self._next_set()[1],
                                      MAX_RESTART_ERR)])]

    def finite(self) -> bool:
        return all(bool(self._finite[i](a)) for i, a in self._arrays())


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
