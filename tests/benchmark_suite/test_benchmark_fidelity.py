"""Adapter fidelity: what the window dispatches is the object the
application's own ``run()`` built in this process (identity, not equality
of arguments), on ``run()``'s own domain; and a ``run()`` that chooses
otherwise changes what the adapter drives with no change to the
benchmark's files."""

import importlib

import pytest

from _bench_util import ROOT, open_session  # noqa: F401

# cell -> (application module, builder attribute on it, session attribute)
APPS = {
    "jacobi512.steady": ("stencil_tpu.apps.jacobi3d", "make_jacobi_loop", "loop"),
    "jacobi512x4.weak": ("stencil_tpu.apps.jacobi3d", "make_jacobi_loop", "loop"),
    "astaroth256.steady": ("stencil_tpu.apps.astaroth", "make_astaroth_step",
                           "step"),
    "exchange512.r3q4": ("stencil_tpu.parallel.exchange", "HaloExchange.make_loop",
                         "loop"),
}
RUNS = {"jacobi512.steady": "stencil_tpu.apps.jacobi3d",
        "jacobi512x4.weak": "stencil_tpu.apps.jacobi3d",
        "astaroth256.steady": "stencil_tpu.apps.astaroth",
        "exchange512.r3q4": "stencil_tpu.apps.exchange_weak"}


def _record_returns(monkeypatch, owner, name, into):
    orig = getattr(owner, name)

    def recording(*a, **kw):
        out = orig(*a, **kw)
        into.append(out)
        return out

    monkeypatch.setattr(owner, name, recording)


@pytest.mark.parametrize("cell", sorted(APPS))
def test_window_dispatches_the_loop_run_built_on_runs_domain(cell, monkeypatch):
    modname, attr, session_attr = APPS[cell]
    owner = importlib.import_module(modname)
    if "." in attr:
        cls, attr = attr.split(".")
        owner = getattr(owner, cls)
    built, results = [], []
    _record_returns(monkeypatch, owner, attr, built)
    _record_returns(monkeypatch, importlib.import_module(RUNS[cell]), "run",
                    results)
    session = open_session(cell)
    assert len(results) == 1, "the adapter calls the application's run() once"
    assert session.domain is results[0]["domain"]
    driven = getattr(session, session_attr)
    assert any(driven is fn for fn in built), (
        "the dispatched function is not one the application's builder "
        "returned during run()")
    assert callable(driven)
    assert session.facts["iters_per_dispatch"] >= 1


def test_a_run_that_chooses_deep_halo_changes_what_the_adapter_drives(
        monkeypatch):
    """The PR that makes deep halos the default edits ``jacobi3d.run``; the
    benchmark must see it without an edit to any of its files."""
    from stencil_tpu.apps import jacobi3d

    before = open_session("jacobi512x4.weak")
    assert before.facts["radius_zyx"][0] == [1, 1]
    assert "'temporal_k': None" in before.facts["chosen"]["loop_kwargs"]

    orig = jacobi3d.run
    monkeypatch.setattr(jacobi3d, "run",
                        lambda *a, **kw: orig(*a, **dict(kw, deep_halo=2)))
    after = open_session("jacobi512x4.weak")
    assert after.facts["radius_zyx"][0] == [2, 2]
    assert "'temporal_k': 2" in after.facts["chosen"]["loop_kwargs"]
    assert after.loop is not before.loop
    # and the check still holds the deeper-halo program to the reference
    import jax

    after.seed(2_147_483_659)
    jax.block_until_ready(after.dispatch())
    (name, err, limit), = after.compare(after.sample())
    assert err <= limit


def test_a_pinned_dispatch_belongs_to_the_mix_not_the_configuration():
    """``iters_per_dispatch`` is the user's --chunk: a number in the traffic
    file pins it, and the application is told nothing else."""
    import jax

    from benchmark.harness import load_json, load_module

    config = load_json(ROOT, "benchmark/configs/jacobi3d-512-f32.json")
    mix = dict(load_json(ROOT, "benchmark/traffic/steady.json"),
               iters_per_dispatch=1)
    session = load_module("apps", "jacobi3d").open(
        config, mix, jax.devices()[:1], True, lambda _: None)
    assert session.facts["iters_per_dispatch"] == 1
    assert session.facts["chosen"]["loop_builder"] == "make_jacobi_step"
