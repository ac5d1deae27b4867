"""The comparison that decides ``correct``, shown to fail: the lower-
precision control at the rehearsal size, and the harness driven with the
timed path broken underneath."""

import pytest

from _bench_util import CELLS, open_session, rehearse
from benchmark import control


@pytest.mark.parametrize("cell", CELLS)
def test_sound_runs_pass_and_the_bfloat16_control_fails(cell):
    session = open_session(cell)
    rows = control.readings(session, [1_000_003, 2_147_483_659, 4_300_000_037])
    assert control.verdict(rows, say=lambda _: None)
    for _, sound, ctrl, _ in rows:
        assert all(v <= lim for _, v, lim in sound)
        assert any(v > lim for _, v, lim in ctrl)
        # the control misses a limit by a wide margin, not by luck
        assert max(v / max(lim, 1e-30) for _, v, lim in ctrl) > 4 or \
            max(v for _, v, _ in ctrl) > 100


def test_astaroth_judges_every_field_against_its_own_increment():
    """One number a field. The bfloat16 right-hand side (float32 state)
    fails the three velocities and the entropy; on lnrho and the potentials
    it is under the float32 state's own rounding, and they are held against
    their equation left out, which fails that field alone."""
    from benchmark.reference.astaroth import FIELDS

    session = open_session("astaroth256.steady")
    rows = control.readings(session, [1_000_003, 2_147_483_659])
    slow = {"lnrho", "ax", "ay", "az"}
    for _, sound, ctrl, faults in rows:
        assert [n for n, _, _ in sound] == [
            f"first_chunk_rel_err.{f}" for f in FIELDS]
        assert not control.failing(sound)
        assert set(control.failing(ctrl)) == {
            f"first_chunk_rel_err.{f}" for f in FIELDS if f not in slow}
        assert [what for what, _ in faults] == [f"{f} left out" for f in FIELDS]
        for field, (_, checks) in zip(FIELDS, faults):
            assert control.failing(checks) == [f"first_chunk_rel_err.{field}"]
            value = next(v for n, v, _ in checks if n.endswith("." + field))
            assert 0.2 < value <= 1.0     # against limits of 0.03 and less


def _frozen(session):
    """A step that returns its state unchanged."""
    first = next(a for a in ("curr", "state") if hasattr(session, a))
    session.dispatch = lambda: getattr(session, first)
    return session


def _halo_cell_altered(session):
    """An exchange that leaves one halo cell of one quantity wrong."""
    real = session.dispatch

    def dispatch():
        out = real()
        q = next(iter(out))
        out[q] = out[q].at[0, 0, 0, 0, 8, 0].add(1.0)
        session.state = out
        return out

    session.dispatch = dispatch
    return session


def _slow_fields_frozen(session):
    """A step that leaves out the continuity and induction equations: the
    four fields that move least (the review's planted fault, REVIEW 24)."""
    import jax.numpy as jnp

    real = session.dispatch

    def dispatch():
        kept = {n: jnp.copy(session.curr[n]) for n in ("lnrho", "ax", "ay", "az")}
        real()
        session.curr.update(kept)
        return session.curr

    session.dispatch = dispatch
    return session


def test_astaroth_with_four_equations_left_out_is_not_correct(capsys):
    result, rc = rehearse("astaroth256.steady", wrap_session=_slow_fields_frozen)
    assert rc == 3 and result["correct"] is False
    out = capsys.readouterr().out
    bad = [l.split()[2].rstrip(":") for l in out.splitlines() if "NOT OK" in l]
    assert bad == [f"first_chunk_rel_err.{f}" for f in ("lnrho", "ax", "ay", "az")]


@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_comes_out_not_correct(cell):
    breaker = _halo_cell_altered if "exchange" in cell else _frozen
    result, rc = rehearse(cell, wrap_session=breaker)
    assert rc == 3
    assert result["correct"] is False
