"""Adapter onto ``stencil_tpu.apps.lbm``: the user's arguments go to the
application's own ``run()``, and the window dispatches the very step that
call compiled, on the domain it realized (see ``benchmark/capture.py``).
Mesh, layout, kernel and the steps a dispatch are the application's
choices; they are printed as facts (its own ``lbm.step_plan``), never
passed. What the SOURCE fixes is taken from the reference and never from
the program: the lattice's order and the relaxation rate of the viscosity
asked for, and a program built with another is refused. What the
configuration says of the layout and of the exchange is held to the plan
(:func:`check_plan`).

The seeded state (``reference.seeded_population``): ``rho`` in [0.9, 1.1),
each component of ``u`` in [-0.05, 0.05), every population at its
equilibrium times ``1 + eps_i``, ``eps_i`` in [-0.01, 0.01), from
``fields.py``'s hash of seed, draw and cell, so the first step's
relaxation is not a no-op. Owned cells only: every halo and padding cell
of the current lattice starts as garbage, which the step's own exchange
must overwrite wherever the kernel reads. The next lattice keeps what it
holds: the step writes every owned cell of it before any is read.
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import expect, max_abs_err
from benchmark.reference import lbm as reference

# what a user can pass on the command line of apps/lbm.py: the keys a
# configuration's ``args`` and ``rehearsal_args`` may hold
USER_ARGS = ("n", "x", "y", "z", "nu", "dtype", "steps")
# what the window drives, for the fidelity test: the module whose run() is
# called once, where that run() looks its builder up, the builder, and the
# session attribute that holds the function it returned
DRIVES = ("stencil_tpu.apps.lbm", "stencil_tpu.apps.lbm", "make_lbm_step",
          "step")

Q = reference.Q
CORE = (8, 8, 16)           # z, y, x: a core crosses a seam of 8-row chunks
N_RANDOM_BOXES = 4
# The most a direction of an axis phase may carry: of the 19 populations 5
# move toward any one side (one axis vector and four diagonals).
CARRIED_A_DIRECTION = 5
# max |program - float64 reference| over every population of the sampled
# boxes after the first dispatch (5 steps). The populations lie in [0.024,
# 0.35); a float32 step rounds at 6e-8 relative over a few dozen terms, and
# five steps add up. Readings in PERF.md section 2: sound float32 runs stay
# under 2e-7 on every seed, the reference in bfloat16 reads 2e-4 and more,
# the edge halos left unfilled 0.02 and more.
MAX_ABS_ERR = 5e-6
# the least a population may be at the window's end (they start at 0.024
# and more, and a BGK step at omega = 2/7 keeps them near their equilibria)
MIN_POPULATION = 0.0


def check_plan(plan: dict) -> None:
    """What the configuration says of the program, held to the program's
    own ``lbm.step_plan``: the domain lies tight-x, the stream-collide pass
    is the Pallas kernel, and no direction of the exchange carries more
    than the 5 populations that move toward it (a plan of one radius
    carries 19). ``ops/lbm`` falls to XLA by itself wherever the kernel
    does not take a block, and such a run would be as ``correct``: it is
    refused here, since it is not the cell the configuration names."""
    bad = []
    if plan["layout"] != "tight_x":
        bad.append(f"the domain lies {plan['layout']}, not tight_x")
    if plan["kernel"] != "pallas":
        bad.append(f"the stream-collide pass is {plan['kernel']}, not pallas")
    for side, carried in sorted(plan["carried"].items()):
        if len(carried) > CARRIED_A_DIRECTION:
            bad.append(f"direction {side} carries {len(carried)} "
                       f"populations, over {CARRIED_A_DIRECTION}")
    if bad:
        raise RuntimeError("lbm.step_plan is not the configuration's: "
                           + "; ".join(bad))


def boxes(global_zyx, seed: int):
    """Origins (z, y, x of the core's first cell; a box wraps): a core
    astride each of the domain's 12 edges (two axes wrap at once), each of
    its 6 faces (one wraps), one in the middle, the rest drawn from the
    seed."""
    g = np.asarray(global_zyx)
    c = np.asarray(CORE)
    mid = g // 2 - c // 2
    out = []
    for free in range(3):                   # the edges run along ``free``
        a, b = [ax for ax in range(3) if ax != free]
        for at_a in (0, 1):
            for at_b in (0, 1):
                o = mid.copy()
                # astride the low (cell 0) or the high side of the wrap
                o[a] = (g[a] - c[a] // 2) if at_a else (g[a] - c[a] // 2 - 1)
                o[b] = (g[b] - c[b] // 2) if at_b else (g[b] - c[b] // 2 - 1)
                o[free] = mid[free] + (3 * (2 * at_a + at_b)) % (g[free] // 4)
                out.append(tuple(int(v) % int(n) for v, n in zip(o, g)))
    for axis in range(3):                   # the faces
        for side in (0, 1):
            o = mid.copy()
            o[axis] = g[axis] - c[axis] // 2 - side
            out.append(tuple(int(v) % int(n) for v, n in zip(o, g)))
    out.append(tuple(int(v) for v in mid))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    for _ in range(N_RANDOM_BOXES):
        out.append(tuple(int(rng.randint(0, n)) for n in g))
    return out


def make_fill(spec, sharding, dtype):
    """``fill(seed words, draw of eps, c, w) -> stacked array``: one seeded
    population on the owned cells, garbage everywhere else, so one compile
    serves every seed and all 19."""
    import jax
    import jax.numpy as jnp

    def fill(seed, q_eps, c, w):
        (z, y, x), owned, _, (rz, ry, rx) = fields._cells(spec)

        def draw(q):
            return fields._uniform_traced(seed, q, z, y, x, 0)

        u32 = jnp.uint32
        good = reference.seeded_population(
            jnp, c, w, draw(u32(reference.DRAW_RHO)),
            [draw(u32(reference.DRAW_U + a)) for a in range(3)], draw(q_eps))
        junk = fields._uniform_traced(seed, q_eps, rz, ry, rx, fields.GARBAGE)
        return jnp.where(owned, good, junk).astype(dtype)

    return jax.jit(fill, out_shardings=sharding)


def make_sound(spec, sharding):
    """``sound(lattice) -> (all finite, least population, largest |u|,
    mass)`` over the owned cells of the 19 arrays."""
    import jax
    import jax.numpy as jnp

    def sound(lattice):
        _, owned, _, _ = fields._cells(spec)
        finite = jnp.all(jnp.stack([jnp.all(jnp.isfinite(a) | ~owned)
                                    for a in lattice]))
        least = jnp.min(jnp.stack([jnp.min(jnp.where(owned, a, jnp.inf))
                                   for a in lattice]))
        rho = sum(lattice)
        mom = [sum(c[axis] * a for c, a in zip(reference.VELOCITIES, lattice)
                   if c[axis]) for axis in range(3)]
        speed = jnp.sqrt(mom[0] ** 2 + mom[1] ** 2 + mom[2] ** 2) / rho
        return (finite, least, jnp.max(jnp.where(owned, speed, 0.0)),
                jnp.sum(jnp.where(owned, rho, 0.0)))

    return jax.jit(sound, in_shardings=([sharding] * Q,))


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import lbm as app
        from stencil_tpu.obs import telemetry
        from stencil_tpu.ops import pallas_lbm

        if tuple(pallas_lbm.VELOCITIES) != tuple(reference.VELOCITIES):
            raise RuntimeError("the program's populations are not in the "
                               "reference's order: nothing can be compared")
        args = dict(config["rehearsal_args" if rehearsal else "args"])
        chunk = mix.get("iters_per_dispatch", "default")
        if chunk != "default":
            args["chunk"] = int(chunk)
        # the rehearsal walks the kernel: run() only takes the Pallas path
        # on a TPU, so on the CPU the builder is told to interpret
        steps = capture.BuilderCapture(
            {"use_pallas": True, "interpret": True} if rehearsal else {})
        with capture.PallasBuilds() as pallas, \
                capture.patched(app, "make_lbm_step", steps):
            result = app.run(devices=devices, **args)
        rec = steps.last
        k = int(rec["kwargs"].get("iters", 1))
        self.step = rec["fn"]
        self.omega = reference.omega_of(float(args.get("nu", 1.0)))
        built = float(rec["args"][1] if len(rec["args"]) > 1
                      else rec["kwargs"]["omega"])
        if abs(built - self.omega) > 1e-12:
            raise RuntimeError(
                f"the program built its collision with omega = {built}; "
                f"the source's 1 / (3 nu + 1/2) is {self.omega} for {args}")
        self.domain = dd = result["domain"]
        self.handles = hs = result["handles"]
        if len(hs) != Q:
            raise RuntimeError(f"{len(hs)} quantities: D3Q19 has {Q}")
        # out of the domain: a dispatch donates both lattices
        self.curr = [dd.get_curr(h) for h in hs]
        self.nxt = [dd.get_next(h) for h in hs]
        for h in hs:
            dd.set_curr(h, None)
            dd.set_next(h, None)
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(self.curr[0].dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize, Q)
        plan = telemetry.get().records(kind="counter",
                                       name="lbm.step_plan")[-1]
        self.facts.update(
            iters_per_dispatch=k, dtype=str(dtype),
            chosen={
                "grid_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "step_kwargs": str(rec["kwargs"]),
                "omega": self.omega,
                "iters_per_dispatch": k,
                "layout": plan["layout"],
                "kernel": plan["kernel"],
                "carried": "; ".join(f"{side}: {len(pops)} {pops}" for side,
                                     pops in sorted(plan["carried"].items())),
                "halo_bytes_sent": plan["halo_bytes_sent"],
                "halo_bytes_if_all": plan["halo_bytes_if_all"],
                "pallas_builds": pallas.summary(),
            })
        expect(config, self.facts)
        check_plan(plan)
        sharding = dd.sharding()
        self._fill = make_fill(spec, sharding, dtype.name)
        self._sound = make_sound(spec, sharding)
        self._reader = fields.BoxReader(spec)
        self._seed = None
        self._say = say
        self.least = self.mass_seeded = None

    def seed(self, seed: int) -> None:
        self._seed = int(seed)
        words = fields.seed_words(seed)
        for i, (c, w) in enumerate(zip(reference.VELOCITIES,
                                       reference.WEIGHTS)):
            self.curr[i] = None         # drop the old buffer before the new
            self.curr[i] = self._fill(
                words, np.uint32(reference.DRAW_EPS + i),
                np.asarray(c, np.float32), np.float32(w))
        self.mass_seeded = float(self._sound(self.curr)[3])

    def dispatch(self):
        self.curr, self.nxt = self.step(self.curr, self.nxt)
        return self.curr

    def sample(self):
        return [(o, [self._reader.read(a, o, CORE) for a in self.curr])
                for o in boxes(self.facts["global_zyx"], self._seed)]

    def _reference(self, origins, **how):
        return [reference.first_chunk_box(
            fields.uniform, self._seed, o, CORE,
            self.facts["iters_per_dispatch"], self.facts["global_zyx"],
            self.omega, **how) for o in origins]

    def compare(self, sample):
        err = 0.0
        for (_, got), want in zip(sample,
                                  self._reference([o for o, _ in sample])):
            for a, b in zip(got, want):
                err = max(err, max_abs_err(a, b))
        return [("first_chunk_max_abs_err", err, MAX_ABS_ERR)]

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
        """A program whose exchange leaves the y-z edge halos unfilled: the
        reference whose diagonal populations pull 0 across the domain's
        y-z edges, put in the program's place."""
        return [("edge halos left unfilled",
                 self._in_place_of_the_program(sample, edges=False))]

    def finite(self) -> bool:
        """Every population finite and positive on every owned cell; says
        what the state has come to (float32 sums on the device)."""
        fin, least, speed, mass = (float(v) for v in self._sound(self.curr))
        self.least = least
        self._say(f"state: least population {least:.6g}, largest |u| "
                  f"{speed:.6g}, mass {self.mass_seeded:.9g} seeded -> "
                  f"{mass:.9g} ({mass / self.mass_seeded - 1:+.3e})")
        return bool(fin) and least > MIN_POPULATION


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
