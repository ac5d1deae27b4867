"""Plan DB — round-trip, corruption rejection, stale-schema migration.

The DB is the production artifact (tuned plans replayed with zero
probes), so its failure modes must be LOUD: a corrupt or
future-versioned file raises PlanDBError instead of silently emptying,
the known v0 legacy layout migrates forward, and writes are atomic
(tmp + rename — no torn DB on a crash). No jax anywhere in this file.
"""

import json
import os

import pytest

from stencil_tpu.geometry import Dim3, Radius
from stencil_tpu.plan import db as plandb
from stencil_tpu.plan.ir import PlanChoice, PlanConfig


def _config(q=4, grid=(64, 64, 64), platform="cpu"):
    return PlanConfig.make(Dim3.of(grid), Radius.constant(2),
                           ["float32"] * q, 8, platform)


def _choice():
    return PlanChoice(partition=(2, 2, 2), method="axis-composed")


def test_roundtrip(tmp_path):
    path = str(tmp_path / "plans.json")
    db = plandb.empty_db()
    cfg = _config()
    entry = plandb.make_entry(cfg, _choice(), "probe", measured_s=0.0262,
                              probes=[{"label": "x", "trimean_s": 0.03}])
    plandb.record(db, entry)
    plandb.save_db(path, db)
    assert not [e for e in os.listdir(tmp_path) if e.startswith(".tmp-")]
    loaded = plandb.load_db(path)
    got = plandb.lookup(loaded, cfg)
    assert got is not None
    assert PlanChoice.from_json(got["choice"]) == _choice()
    assert got["measured_s"] == pytest.approx(0.0262)
    # a permuted-dtype config resolves to the same entry (multiset key)
    assert plandb.lookup(loaded, _config()) is got


def test_missing_file_is_empty():
    db = plandb.load_db("/nonexistent/plans.json")
    assert db == plandb.empty_db()


def test_corruption_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    plandb.save_db(path, plandb.empty_db())
    with open(path, "r+") as f:
        f.truncate(10)  # torn JSON
    with pytest.raises(plandb.PlanDBError, match="unreadable"):
        plandb.load_db(path)


def test_wrong_kind_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        json.dump({"v": 1, "kind": "not-a-plan-db", "entries": {}}, f)
    with pytest.raises(plandb.PlanDBError):
        plandb.load_db(path)


def test_future_version_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        json.dump({"v": 99, "kind": plandb.DB_KIND, "entries": {}}, f)
    with pytest.raises(plandb.PlanDBError, match="newer"):
        plandb.load_db(path)


def test_tampered_entry_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    db = plandb.empty_db()
    plandb.record(db, plandb.make_entry(_config(), _choice(), "probe"))
    plandb.save_db(path, db)
    raw = json.load(open(path))
    key = next(iter(raw["entries"]))
    raw["entries"][key]["choice"]["method"] = "warp-drive"
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.raises(plandb.PlanDBError, match="method"):
        plandb.load_db(path)


def test_entry_key_mismatch_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    db = plandb.empty_db()
    plandb.record(db, plandb.make_entry(_config(), _choice(), "probe"))
    plandb.save_db(path, db)
    raw = json.load(open(path))
    key = next(iter(raw["entries"]))
    raw["entries"]["{}"] = raw["entries"].pop(key)  # moved under a bogus key
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.raises(plandb.PlanDBError):
        plandb.load_db(path)


def test_v0_flat_layout_migrates(tmp_path):
    # the pre-schema prototype: a flat {config-key: choice-json} mapping
    path = str(tmp_path / "plans.json")
    cfg = _config()
    with open(path, "w") as f:
        json.dump({cfg.key(): _choice().to_json()}, f)
    db = plandb.load_db(path)
    assert db["v"] == plandb.DB_VERSION
    entry = plandb.lookup(db, cfg)
    assert entry is not None and entry["source"] == "legacy"
    assert PlanChoice.from_json(entry["choice"]) == _choice()
    # migrated DBs re-save as v1 and reload cleanly
    plandb.save_db(path, db)
    assert plandb.load_db(path)["v"] == plandb.DB_VERSION


def test_v0_garbage_rejected(tmp_path):
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        json.dump({"some": "junk"}, f)
    with pytest.raises(plandb.PlanDBError):
        plandb.load_db(path)


def test_save_refuses_invalid():
    with pytest.raises(plandb.PlanDBError, match="refusing"):
        plandb.save_db("/tmp/never-written.json",
                       {"v": 1, "kind": "nope", "entries": {}})


def test_prune_filters_and_guard(tmp_path):
    db = plandb.empty_db()
    plandb.record(db, plandb.make_entry(_config(q=1), _choice(), "seed"))
    plandb.record(db, plandb.make_entry(_config(q=2), _choice(), "probe"))
    plandb.record(db, plandb.make_entry(
        _config(q=2, platform="tpu"), _choice(), "probe"))
    with pytest.raises(ValueError, match="filter"):
        plandb.prune_db(db)
    assert plandb.prune_db(db, source="seed") == 1
    assert plandb.prune_db(db, platform="tpu") == 1
    assert len(db["entries"]) == 1
    assert plandb.prune_db(db, older_than_s=3600.0) == 0  # all fresh


# -- entries that use the retired two-level keys (written by PRs 17 to 29) ---


# what an entry that nothing can serve any more carries in its choice
_RETIRED = {
    "two-level": {"hierarchy": ["z", 2], "host_placement": [1, 0]},
    "remote-dma": {"method": "remote-dma"},
    "fused": {"method": "remote-dma", "kernel_variant": "fused"},
    "persistent": {"method": "remote-dma", "kernel_variant": "persistent",
                   "multistep_k": 2},
}


def _db_with_retired_entry(tmp_path, retired="two-level"):
    """A DB file as the parent tree wrote it: one one-level composed entry
    (the keys present and null) and one under a plan since retired (z split
    over two hosts; the kernel-initiated transport or one of its kernel
    variants)."""
    flat, hier = _config(q=1), _config(q=2, grid=(32, 32, 32))
    stored = dict(_choice().to_json(), hierarchy=None, host_placement=None)
    db = plandb.empty_db()
    for cfg, extra in ((flat, {}), (hier, _RETIRED[retired])):
        entry = plandb.make_entry(cfg, _choice(), "probe", measured_s=0.01)
        entry["choice"] = {**stored, **extra}
        db["entries"][cfg.key()] = entry
    path = str(tmp_path / "plans.json")
    with open(path, "w") as f:
        json.dump(db, f, indent=1, sort_keys=True)
    return path, flat, hier


def _file_bytes(path):
    with open(path, "rb") as f:
        return f.read()


@pytest.mark.parametrize("retired", list(_RETIRED))
@pytest.mark.parametrize("case", ["served-and-skipped", "file-untouched",
                                  "pruned"])
def test_entry_with_retired_keys(tmp_path, capfd, case, retired):
    path, flat, hier = _db_with_retired_entry(tmp_path, retired)
    before = _file_bytes(path)
    capfd.readouterr()
    db = plandb.load_db(path)
    if case == "served-and-skipped":
        got = plandb.lookup(db, flat)
        assert PlanChoice.from_json(got["choice"]) == _choice()
        assert plandb.lookup(db, hier) is None      # a miss, not a flat plan
        assert plandb.lookup(db, hier) is None
        err = capfd.readouterr().err
        assert err.count("retired key") == 1, err   # once a load
        assert "32x32x32 on 8 cpu" in err and "64x64x64" not in err
    elif case == "file-untouched":
        plandb.lookup(db, flat)
        plandb.lookup(db, hier)
        assert _file_bytes(path) == before
        assert os.listdir(tmp_path) == ["plans.json"]
    else:
        # no filter needed: nothing can serve the entry, so any prune
        # drops it; the one-level entry stays
        assert plandb.prune_db(db) == 1
        assert list(db["entries"]) == [flat.key()]
        plandb.save_db(path, db)
        capfd.readouterr()
        assert plandb.lookup(plandb.load_db(path), flat) is not None
        assert "retired" not in capfd.readouterr().err


@pytest.mark.parametrize("retired", list(_RETIRED))
def test_plan_tool_shows_and_prunes_retired_entries(tmp_path, capsys,
                                                    retired):
    from stencil_tpu.apps import plan_tool

    path, flat, hier = _db_with_retired_entry(tmp_path, retired)
    key = plandb.retired_key(plandb.load_db(path)["entries"][hier.key()])
    assert key == {"two-level": "hierarchy", "remote-dma": "method",
                   "fused": "method", "persistent": "method"}[retired]
    capsys.readouterr()
    assert plan_tool.main(["show", "--db", path]) == 0
    rows = capsys.readouterr().out.splitlines()
    assert sum(f"retired({key})" in r for r in rows) == 1
    assert sum(_choice().label() in r for r in rows) == 1
    assert plan_tool.main(["prune", "--db", path]) == 0
    assert list(plandb.load_db(path)["entries"]) == [flat.key()]


@pytest.mark.parametrize("argv,named", [
    (["explain", "--method", "remote-dma"], "remote-dma"),
    (["autotune", "--no-probe", "--methods", "remote-dma"], "remote-dma"),
    (["autotune", "--no-probe", "--variants", "fused"], "--variants"),
], ids=["explain-method", "autotune-methods", "autotune-variants"])
def test_plan_tool_refuses_the_retired_spellings(argv, named, capsys):
    from stencil_tpu.apps import plan_tool

    with pytest.raises(SystemExit) as e:
        plan_tool.main(argv)
    out = capsys.readouterr()
    assert e.value.code not in (0, None)
    assert named in out.err + str(e.value.code)
