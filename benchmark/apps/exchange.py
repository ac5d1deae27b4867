"""Adapter onto ``stencil_tpu.apps.exchange_weak``: the user's arguments go
to the application's own ``run()``, and the window dispatches the very
exchange loop that call compiled (``HaloExchange.make_loop``, which
``time_exchange`` calls), on the domain it realized.
"""

from __future__ import annotations

import numpy as np

from benchmark import capture, fields
from benchmark.apps_common import expect


class Session:
    def __init__(self, config, mix, devices, rehearsal, say):
        from stencil_tpu.apps import exchange_weak as app
        from stencil_tpu.parallel.exchange import HaloExchange

        args = dict(config["rehearsal_args" if rehearsal else "args"])
        chunk = mix.get("iters_per_dispatch", "default")
        if chunk != "default":
            args["chunk"] = int(chunk)
        loops = capture.BuilderCapture()
        with capture.PallasBuilds() as pallas, \
                capture.patched(HaloExchange, "make_loop", loops):
            result = app.run(args.pop("x"), args.pop("y"), args.pop("z"),
                             devices=devices, **args)
        rec = loops.built[0]           # the main chunk; a tail comes second
        k = int(rec["args"][1])
        self.loop = rec["fn"]
        self.domain = dd = result["domain"]
        self.state = dict(dd.curr_state())
        self.builds = pallas.builds
        spec = dd.spec
        dtype = np.dtype(next(iter(self.state.values())).dtype)
        self.facts = capture.spec_facts(spec, len(devices), dtype.itemsize,
                                        len(self.state))
        ex = dd.halo_exchange
        self.facts.update(
            iters_per_dispatch=k, dtype=str(dtype),
            chosen={
                "global_xyz": str(spec.global_size),
                "partition_xyz": str(spec.dim),
                "radius": str(spec.radius),
                "method": result["method"],
                "exchanges_per_dispatch": k,
                "self_fill_axes": sorted(getattr(ex, "_self_fills", {})),
                "bytes_logical": result["bytes_logical"],
                "bytes_moved": result["bytes_moved"],
                "pallas_builds": pallas.summary(),
            })
        expect(config, self.facts)
        self._fill = fields.make_fill(spec, dd.sharding(), dtype.name)
        self._check = fields.make_halo_check(spec, dd.sharding())
        self._finite = fields.make_all_finite(spec, dd.sharding())
        self._seed = None

    def _wrong(self):
        words = fields.seed_words(self._seed)
        wrong = halo = 0
        for q, arr in self.state.items():
            w, n = self._check(arr, words, np.uint32(q))
            wrong, halo = wrong + int(w), halo + int(n)
        return wrong, halo

    def seed(self, seed: int) -> None:
        self._seed = int(seed)
        words = fields.seed_words(seed)
        for q in list(self.state):
            self.state[q] = None
            self.state[q] = self._fill(words, np.uint32(q))
        wrong, halo = self._wrong()
        # the checker must see unfilled halos as wrong, or a zero count
        # after the exchange would prove nothing
        if not wrong > 0.99 * halo > 0:
            raise RuntimeError(f"halo check is blind: {wrong} of {halo} "
                               f"unfilled halo cells read as wrong")
        self.facts["halo_cells_checked"] = halo

    def dispatch(self):
        self.state = self.loop(self.state)
        return self.state

    def sample(self):
        return self._wrong()[0]        # every halo cell, on the device

    def compare(self, sample):
        return [("halo_cells_wrong_first_chunk", sample, 0),
                ("halo_cells_wrong_after_window", self._wrong()[0], 0)]

    def control(self, sample):
        """The exchange states no arithmetic, only that halos arrive bit
        for bit: the control delivers them through bfloat16 (what a lossy
        wire would), and must be counted wrong."""
        import jax.numpy as jnp

        kept = self.state
        self.state = {q: a.astype(jnp.bfloat16).astype(a.dtype)
                      for q, a in kept.items()}
        try:
            wrong = self._wrong()[0]
        finally:
            self.state = kept
        return [("halo_cells_wrong_first_chunk", wrong, 0),
                ("halo_cells_wrong_after_window", wrong, 0)]

    def finite(self) -> bool:
        return all(bool(self._finite(a)) for a in self.state.values())


def open(config, mix, devices, rehearsal, say):  # noqa: A001
    return Session(config, mix, devices, rehearsal, say)
