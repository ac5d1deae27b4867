"""Adapter onto ``stencil_tpu.apps.mg``: the user's arguments go to the
application's own ``run()``, and the window dispatches the very iteration
that call compiled, on the hierarchy of domains it realized (see
``benchmark/capture.py``). Mesh, layout, which levels run a Pallas kernel
and the iterations a dispatch are the application's choices; they are
printed as facts (its own ``mg.cycle_plan``), never passed. What the
SOURCE fixes is taken from the reference and never from the program:
``psinv``'s four class weights are the reference's table's for the class
asked for, and a program built with others is refused. What the
configuration says of the layout is held to the plan (:func:`check_plan`).

The seeded state: u and r of the finest level dense in [-1, 1) from
``fields.py``'s hash (every allocated cell holds the value of the cell it
mirrors, so the halos are the periodic wrap the iteration expects on
entry), v = +1 at ten cells and -1 at ten drawn from the seed. The lower
levels keep what they hold: the iteration writes each before it reads it.
So the FIRST dispatch is a dense iteration on every operator and level,
and it is the one compared with the float64 reference.
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import expect, max_abs_err
from benchmark.reference import mg as reference

# what a user can pass on the command line of apps/mg.py: the keys a
# configuration's ``args`` and ``rehearsal_args`` may hold
USER_ARGS = ("klass", "n", "nit", "dtype")
# what the window drives, for the fidelity test: the module whose run() is
# called once, where that run() looks its builder up, the builder, and the
# session attribute that holds the function it returned
DRIVES = ("stencil_tpu.apps.mg", "stencil_tpu.apps.mg", "make_mg_iter",
          "step")

LANE = 128                  # cells of a lane tile: a row of whole tiles
CORE = (16, 16, 16)
N_RANDOM_BOXES = 8
NAMES = ("u", "r")
# max |program - float64 reference| over the sampled boxes after the first
# dispatch. The seeded u and r lie in [-1, 1); after one iteration u stays
# within +-1.6 and r = v - A u within +-4.5 (A's centre weight is -8/3 and
# its 20 others sum to +8/3). A float32 run rounds at 6e-8 a term over the
# few hundred terms between the seeded state and a fine cell. Readings in
# PERF.md section 2: sound float32 runs stay under 2e-6 (u) and 5e-6 (r) on
# every seed, the bfloat16 control reads 7e-3 and more on both, the
# corners-left-out control 0.02 (u) and 0.2 (r) and more.
MAX_ABS_ERR = {"u": 2e-5, "r": 4e-5}
# cells that may differ AT ALL: v's, over the whole finest level, from what
# the seed makes (checked after the window)
EXACT = 0


def source_smoother(args: dict):
    """``psinv``'s four class weights as the source's table has them for
    what the user asked for (``reference.CLASSES``; a bare ``n=`` takes
    class B's, as the application says of itself): what the reference is
    computed with, whatever the program passed to its builder."""
    klass = args.get("klass")
    return reference.S_LARGE if klass is None else reference.CLASSES[klass][2]


def check_plan(levels, x_blocks: int) -> None:
    """What the configuration says of the program, held to the program's
    own ``mg.cycle_plan`` (``levels``, finest first): a level whose rows
    are whole lane tiles on an unsplit x axis lies ``tight_x`` and the
    others ``inline``; on a ``tight_x`` level ``resid`` and ``psinv`` are
    the Pallas box kernel, and so are the transfers between two such
    levels. ``ops/mg`` falls to XLA by itself wherever a kernel does not
    take a block, and such a run would be as ``correct``: it is refused
    here, since it is not the cell the configuration names."""
    bad = []
    tight = {}
    for lv in levels:
        want = ("tight_x" if x_blocks == 1 and lv["grid"][2] % LANE == 0
                else "inline")
        if lv["layout"] != want:
            bad.append(f"level {lv['level']} ({lv['grid'][2]}^3) lies "
                       f"{lv['layout']}, not {want}")
        tight[lv["level"]] = lv["layout"] == "tight_x"
    for lv in levels:
        k = lv["level"]
        if not tight[k]:
            continue
        kernels = ["mg_resid", "mg_psinv"]
        if tight.get(k - 1):
            kernels += ["mg_rprj3", "mg_interp"]     # between k and k - 1
        for name in kernels:
            impl = lv["operators"].get(name, {}).get("impl")
            if impl != "pallas":
                bad.append(f"level {k}'s {name} is {impl}, not pallas")
    if bad:
        raise RuntimeError("mg.cycle_plan is not the configuration's: "
                           + "; ".join(bad))


def boxes(n: int, dims_zyx, seed: int):
    """Origins (z, y, x of the core's first cell; a box wraps): one whose
    core crosses the periodic wrap on every axis, the domain's first and
    last corner, one whose core crosses a block boundary on every split
    axis, one that holds a charge, the rest drawn from the seed."""
    c = np.asarray(CORE)
    out = [tuple(int(v) for v in n - c // 2), (0, 0, 0),
           tuple(int(v) for v in n - c)]
    if any(d > 1 for d in dims_zyx):
        out.append(tuple(int(n // d - h) if d > 1 else n // 3
                         for d, h in zip(dims_zyx, c // 2)))
    plus, _ = reference.seeded_charges(seed, n)
    out.append(tuple(int(p - h) % n for p, h in zip(plus[0], c // 2)))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    for _ in range(N_RANDOM_BOXES):
        out.append(tuple(int(rng.randint(0, n)) for _ in range(3)))
    return out


def make_fill(spec, sharding, dtype):
    """``fill(seed words, q) -> stacked array``: quantity q (0 u, 1 r) of
    the seeded finest level at every allocated cell's wrapped coordinate,
    halos included, so one compile serves every seed and both."""
    import jax

    def fill(seed, q):
        (z, y, x), _, _, _ = fields._cells(spec)
        u = fields._uniform_traced(seed, q, z, y, x, 0)
        return reference.from_uniform(jax.numpy, u).astype(dtype)

    return jax.jit(fill, out_shardings=sharding)


def make_charges(spec, sharding, dtype):
    """``charges(cells, signs) -> v``: ``signs[i]`` at cell ``cells[i]``
    (z, y, x) and at every allocated cell that mirrors it, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    def charges(cells, signs):
        (z, y, x), _, _, _ = fields._cells(spec)
        v = jnp.zeros(spec.stacked_shape_zyx(), dtype)
        for i in range(2 * reference.CHARGES):
            hit = (z == cells[i, 0]) & (y == cells[i, 1]) & (x == cells[i, 2])
            v = jnp.where(hit, signs[i], v)
        return v

    return jax.jit(charges, out_shardings=sharding)


def make_moved(spec, sharding):
    """``moved(a, b) -> int``: owned cells whose bits differ."""
    import jax
    import jax.numpy as jnp

    def moved(a, b):
        _, owned, _, _ = fields._cells(spec)
        return jnp.sum((a != b) & owned, dtype=jnp.int32)

    return jax.jit(moved, in_shardings=(sharding, sharding))


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import mg as app
        from stencil_tpu.obs import telemetry

        args = dict(config["rehearsal_args" if rehearsal else "args"])
        chunk = mix.get("iters_per_dispatch", "default")
        if chunk != "default":
            args["chunk"] = int(chunk)
        # the rehearsal walks the kernel where a level takes it: run() only
        # takes the Pallas path on a TPU, so on the CPU the builder is told
        # to interpret
        steps = capture.BuilderCapture(
            {"use_pallas": True, "interpret": True} if rehearsal else {})
        with capture.PallasBuilds() as pallas, \
                capture.patched(app, "make_mg_iter", steps):
            result = app.run(devices=devices, **args)
        rec = steps.last
        k = int(rec["kwargs"].get("iters", 1))
        self.step = rec["fn"]
        self.smoother = source_smoother(args)
        if tuple(rec["kwargs"]["smoother"]) != self.smoother:
            raise RuntimeError(
                f"the program built psinv with the weights "
                f"{tuple(rec['kwargs']['smoother'])}; the source's table "
                f"gives {self.smoother} for {args}")
        self.domain = dd = result["domain"]
        self.levels = result["levels"]
        self.handles = hs = result["handles"]
        if tuple(hs) != NAMES + ("v",):
            raise RuntimeError(f"quantities {tuple(hs)} != {NAMES + ('v',)}")
        self.state = self._take_state()
        self.v = dd.get_curr(hs["v"])
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(self.v.dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize,
                                        len(hs))
        self.n = self.facts["global_zyx"][0]
        plan = telemetry.get().records(kind="counter",
                                       name="mg.cycle_plan")[-1]
        self.facts.update(
            iters_per_dispatch=k, dtype=str(dtype),
            chosen={
                "grid_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "iter_kwargs": str({a: b for a, b in rec["kwargs"].items()
                                    if a != "smoother"}),
                "smoother": str(self.smoother),
                "iters_per_dispatch": k,
                "levels": len(self.levels),
                "cycle_plan": "; ".join(
                    f"{lv['level']}:{lv['grid'][0]}^3 {lv['layout']} " + ",".join(
                        f"{name[3:]}={op['impl']}"
                        for name, op in lv["operators"].items())
                    for lv in plan["levels"]),
                "pallas_builds": pallas.summary(),
            })
        expect(config, self.facts)
        check_plan(plan["levels"], spec.dim.x)
        if len(self.levels) != len(reference.levels(self.n)):
            raise RuntimeError(
                f"{len(self.levels)} levels for {self.n}^3: the source has "
                f"{len(reference.levels(self.n))}, down to 2^3")
        sharding = dd.sharding()
        self._fill = make_fill(spec, sharding, dtype.name)
        self._charges = make_charges(spec, sharding, dtype.name)
        self._moved = make_moved(spec, sharding)
        self._finite = [fields.make_all_finite(lv.spec, lv.sharding())
                        for lv, _ in self.levels]
        self._reader = fields.BoxReader(spec)
        self._seed = None

    def _take_state(self):
        """The hierarchy's arrays, out of their domains (a dispatch donates
        them: a domain would be left holding a deleted buffer)."""
        state = {q: [lv.get_curr(hs[q]) for lv, hs in self.levels]
                 for q in NAMES}
        for lv, hs in self.levels:
            for q in NAMES:
                lv.set_curr(hs[q], None)
        return state

    def _seeded_v(self):
        plus, minus = reference.seeded_charges(self._seed, self.n)
        cells = np.asarray(plus + minus, np.int32)
        signs = np.asarray([1.0] * len(plus) + [-1.0] * len(minus),
                           self.facts["dtype"])
        return self._charges(cells, signs)

    def seed(self, seed: int) -> None:
        self._seed = int(seed)
        words = fields.seed_words(seed)
        for q, name in enumerate(NAMES):
            self.state[name][0] = None     # drop the old buffer before the new
            self.state[name][0] = self._fill(words, np.uint32(q))
        self.v = None
        self.domain.set_curr(self.handles["v"], None)
        self.v = self._seeded_v()
        self.domain.set_curr(self.handles["v"], self.v)

    def dispatch(self):
        self.state = self.step(self.state, self.v)
        return self.state

    def _boxes(self):
        return boxes(self.n, self.facts["dims_zyx"], self._seed)

    def sample(self):
        return [(o, {q: self._reader.read(self.state[q][0], o, CORE)
                     for q in NAMES}) for o in self._boxes()]

    def _reference(self, origins, **how):
        return reference.first_iteration_boxes(
            self._seed, self.n, self.smoother, origins, CORE, **how)

    def compare(self, sample):
        if self.facts["iters_per_dispatch"] != 1:
            raise RuntimeError("the reference follows ONE iteration; a mix "
                               "that pins more a dispatch needs its own")
        err = dict.fromkeys(NAMES, 0.0)
        ref = self._reference([o for o, _ in sample])
        for (_, got), want in zip(sample, ref):
            for q in NAMES:
                err[q] = max(err[q], max_abs_err(got[q], want[q]))
        fresh = self._seeded_v()
        v_moved = int(self._moved(self.v, fresh))
        del fresh
        return [("first_iter_max_abs_err.u", err["u"], MAX_ABS_ERR["u"]),
                ("first_iter_max_abs_err.r", err["r"], MAX_ABS_ERR["r"]),
                ("v_cells_moved", v_moved, EXACT)]

    def _in_place_of_the_program(self, sample, **how):
        origins = [o for o, _ in sample]
        return self.compare(list(zip(origins, self._reference(origins,
                                                              **how))))

    def control(self, sample):
        """The reference computed in bfloat16 (state and arithmetic), put
        in the program's place."""
        import ml_dtypes

        return self._in_place_of_the_program(sample,
                                             dtype=ml_dtypes.bfloat16)

    def faults(self, sample):
        """A program whose box reads no corner: the reference with the
        corner weights of ``resid`` and ``rprj3`` left out, put in the
        program's place."""
        return [("corners left out",
                 self._in_place_of_the_program(sample, corners=False))]

    def finite(self) -> bool:
        return all(bool(check(a)) for q in NAMES
                   for check, a in zip(self._finite, self.state[q]))


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
