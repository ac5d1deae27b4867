"""The chunk spans of the seven applications' ``run()``: every chunk under
``*.steps`` is run by ``utils/sync.timed_chunk`` and recorded by
``Recorder.chunk_span``, so it says what the host did in it (``enqueue_s``,
``wait_s``), what it waited with (``sync``) and which compiled loop it ran
(``module``); chunks of one run do not overlap."""

import jax
import pytest

from stencil_tpu.obs import scopes, telemetry
from stencil_tpu.utils import sync

ONE = slice(0, 1)
# the compiled loop(s) each chunk span may name
MODULE_OF = {
    "jacobi.iter": (scopes.JACOBI_LOOP, scopes.JACOBI_STEP),
    "jacobi.exchange": (scopes.EXCHANGE_LOOP,),
    "astaroth.iter": (scopes.ASTAROTH_ITER,),
    "astaroth.exchange": (scopes.EXCHANGE_LOOP,),
    "exchange.iter": (scopes.EXCHANGE_LOOP,),
    "iso3dfd.iter": (scopes.ISO3DFD_LOOP,),
    "mg.iter": (scopes.MG_ITER,),
    "lbm.step": (scopes.LBM_STEP,),
    "hpcg.iter": (scopes.HPCG_ITER,),
}


@pytest.fixture
def rec():
    """A fresh process-default recorder with no sink: the chunk spans are
    kept for an in-process reader all the same."""
    yield telemetry.configure(heartbeat_thread=False)
    telemetry.configure(heartbeat_thread=False)


def _jacobi(**kw):
    from stencil_tpu.apps import jacobi3d

    jacobi3d.run(16, 16, 16, iters=5, chunk=2, devices=jax.devices()[ONE],
                 **kw)


def _jacobi_behind_a_wrapper():
    """As the benchmark's adapter has it (``benchmark/capture.py``): the
    loop the builder returned behind a plain function, so the span's
    ``module`` cannot come from the loop object."""
    from unittest import mock

    from stencil_tpu.apps import jacobi3d

    build = jacobi3d.make_jacobi_loop

    def wrapped(*args, **kw):
        loop = build(*args, **kw)
        return lambda *xs: loop(*xs)

    with mock.patch.object(jacobi3d, "make_jacobi_loop", wrapped):
        _jacobi()


def _astaroth(**kw):
    from stencil_tpu.apps import astaroth

    astaroth.run(nx=8, iters=2, devices=jax.devices()[ONE], **kw)


def _exchange():
    from stencil_tpu.apps._bench_common import time_exchange
    from stencil_tpu.geometry import Dim3, Radius

    time_exchange(Dim3(16, 16, 16), Radius.constant(1), iters=5,
                  devices=jax.devices()[ONE], quantities=1, chunk=2)


def _iso3dfd():
    from stencil_tpu.apps import iso3dfd

    iso3dfd.run(24, 24, 24, iters=3, devices=jax.devices()[ONE])


def _mg():
    from stencil_tpu.apps import mg

    mg.run(n=8, nit=2, devices=jax.devices()[ONE])


def _lbm():
    from stencil_tpu.apps import lbm

    lbm.run(x=8, y=8, z=8, steps=4, chunk=2, devices=jax.devices()[ONE])


def _hpcg():
    from stencil_tpu.apps import hpcg

    hpcg.run(n=16, sets=1, devices=jax.devices()[ONE])


# case -> (run, the steps span, {chunk span: (chunks, sync)})
CASES = {
    "jacobi": (_jacobi, "jacobi.steps", {"jacobi.iter": (3, "hard_sync")}),
    "jacobi-guarded": (lambda: _jacobi(health_every=2), "jacobi.steps",
                       {"jacobi.iter": (3, "hard_sync")}),
    "jacobi-wrapped": (_jacobi_behind_a_wrapper, "jacobi.steps",
                       {"jacobi.iter": (3, "hard_sync")}),
    # with a sink the run also times three exchange-only chunks
    "jacobi-metrics": (_jacobi, "jacobi.steps",
                       {"jacobi.iter": (3, "hard_sync"),
                        "jacobi.exchange": (3, "hard_sync")}),
    "astaroth": (_astaroth, "astaroth.steps",
                 {"astaroth.iter": (2, "hard_sync"),
                  "astaroth.exchange": (2, "hard_sync")}),
    "astaroth-guarded": (lambda: _astaroth(health_every=1), "astaroth.steps",
                         {"astaroth.iter": (2, "hard_sync"),
                          "astaroth.exchange": (2, "hard_sync")}),
    "astaroth-exchange-only": (lambda: _astaroth(no_compute=True),
                               "astaroth.steps",
                               {"astaroth.exchange": (2, "hard_sync")}),
    "exchange": (_exchange, "exchange.steps",
                 {"exchange.iter": (3, "hard_sync")}),
    "iso3dfd": (_iso3dfd, "iso3dfd.steps", {"iso3dfd.iter": (3, "hard_sync")}),
    "mg": (_mg, "mg.steps", {"mg.iter": (2, "hard_sync")}),
    "lbm": (_lbm, "lbm.steps", {"lbm.step": (2, "hard_sync")}),
    "hpcg": (_hpcg, "hpcg.steps", {"hpcg.iter": (50, "scalar")}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_every_chunk_span_says_what_the_host_did_in_it(rec, case, tmp_path):
    run, steps_name, want = CASES[case]
    if case.endswith("-metrics"):
        rec = telemetry.configure(metrics_out=str(tmp_path / "m.jsonl"),
                                  heartbeat_thread=False)
    run()
    steps = rec.records(kind="span", name=steps_name)[-1]
    chunks = sorted((r for r in rec.records(kind="span")
                     if r.get("parent") == steps_name and "iters" in r),
                    key=lambda r: r["t0_ns"])
    got = {}
    for r in chunks:
        got.setdefault(r["name"], []).append(r["sync"])
    assert got == {name: [how] * n for name, (n, how) in want.items()}
    for r in chunks:
        assert telemetry.validate_record(r) == []
        assert r["sync"] in sync.SYNCS and r["module"] in scopes.MODULES
        assert r["module"] in MODULE_OF[r["name"]]
        assert r["enqueue_s"] > 0 and r["wait_s"] > 0 and r["iters"] >= 1
        wall_s = (r["t1_ns"] - r["t0_ns"]) / 1e9
        assert r["enqueue_s"] + r["wait_s"] <= wall_s + 1e-8
        assert steps["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= steps["t1_ns"]
    for a, b in zip(chunks, chunks[1:]):
        assert a["t1_ns"] <= b["t0_ns"]

