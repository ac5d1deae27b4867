"""On-disk plan DB: tuned exchange plans keyed by canonical config.

The serving-stack analogue of an inference engine's tuned-config cache:
``autotune`` persists each winning :class:`~stencil_tpu.plan.ir.PlanChoice`
under its :class:`~stencil_tpu.plan.ir.PlanConfig` key, so production
runs replay plans with ZERO probe runs (the ``plan.cache_hit`` gauge is
the proof; scripts/ci_plan_gate.py pins it).

Format: one JSON file, schema v1, validated like the metrics JSONL
(one schema authority, :func:`validate_db`):

    {"v": 1, "kind": "stencil-plan-db",
     "entries": {"<canonical config key>": {
        "config":   {...PlanConfig.to_json()...},
        "choice":   {...PlanChoice.to_json()...},
        "source":   "probe" | "static" | "seed" | "legacy",
        "static_cost_s": float | null,
        "measured_s":    float | null,     # per-exchange trimean (probe/seed)
        "probes":   [{"label": ..., "trimean_s": ...}, ...],
        "written_t": float,
        "note":     str | null}},
     "calibrations": {"<platform>": {        # optional; absent = modeled
        "calibration": {...score() override...},
        "provenance": "fitted(n=…, r2=…)", "n": int, "r2": float, ...}}}

Discipline mirrors ckpt/snapshot.py: writes are tmp + fsync + atomic
rename (a crash never leaves a torn DB), corrupt or future-versioned
files are REJECTED (:class:`PlanDBError`) rather than silently emptied,
and the known legacy layout (v0: a flat ``{key: choice}`` mapping from
the pre-schema prototype) is migrated forward on load.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional

from ..utils import logging as log
from .ir import (
    METHODS, PlanChoice, PlanConfig, retired_choice_key, validate_placement,
)

DB_VERSION = 1
DB_KIND = "stencil-plan-db"
SOURCES = ("probe", "static", "seed", "legacy")
_TMP_PREFIX = ".tmp-"


class PlanDBError(ValueError):
    """Corrupt, unparseable, or future-versioned plan DB."""


def empty_db() -> dict:
    return {"v": DB_VERSION, "kind": DB_KIND, "entries": {}}


def make_entry(config: PlanConfig, choice: PlanChoice, source: str,
               static_cost_s: Optional[float] = None,
               measured_s: Optional[float] = None,
               probes: Optional[list] = None,
               note: Optional[str] = None) -> dict:
    if source not in SOURCES:
        raise ValueError(f"unknown plan source {source!r} "
                         f"(known: {', '.join(SOURCES)})")
    return {
        "config": config.to_json(),
        "choice": choice.to_json(),
        "source": source,
        "static_cost_s": static_cost_s,
        "measured_s": measured_s,
        "probes": list(probes or []),
        "written_t": time.time(),
        "note": note,
    }


def retired_key(entry) -> Optional[str]:
    """The retired choice key (``hierarchy`` / ``host_placement``, or a
    ``method`` / ``kernel_variant`` of the retired transport) an
    entry uses, or None — see :func:`~.ir.retired_choice_key`. Such an
    entry stays valid on disk and is a miss to :func:`lookup`;
    :func:`prune_db` removes it."""
    if not isinstance(entry, dict):
        return None
    return retired_choice_key(entry.get("choice"))


def validate_entry(key: str, entry) -> List[str]:
    errs: List[str] = []
    if not isinstance(entry, dict):
        return [f"entry {key!r} is not an object"]
    try:
        cfg = PlanConfig.from_json(entry["config"])
    except (KeyError, TypeError, ValueError) as e:
        return [f"entry {key!r}: bad config ({e})"]
    if cfg.key() != key:
        errs.append(f"entry {key!r}: key does not match its config "
                    f"(canonical {cfg.key()!r})")
    if retired_key(entry) is not None:
        # well-formed for the program that wrote it, and kept on disk
        # until pruned: lookup() never serves it
        return errs
    try:
        choice = PlanChoice.from_json(entry["choice"])
    except (KeyError, TypeError, ValueError) as e:
        return errs + [f"entry {key!r}: bad choice ({e})"]
    if choice.method not in METHODS:
        errs.append(f"entry {key!r}: unknown method {choice.method!r}")
    if len(choice.partition) != 3 or any(
            not isinstance(p, int) or p < 1 for p in choice.partition):
        errs.append(f"entry {key!r}: partition must be 3 positive ints")
    if choice.multistep_k < 1:
        errs.append(f"entry {key!r}: multistep_k must be >= 1")
    # placement rides schema v1: an ABSENT field is the identity
    # assignment (every pre-placement entry — legacy v0 migrations
    # included — deserializes to None and replays unchanged); a present
    # one must be a permutation of the config's mesh positions
    perr = validate_placement(choice.placement, cfg.ndev)
    if perr is not None:
        errs.append(f"entry {key!r}: {perr}")
    if entry.get("source") not in SOURCES:
        errs.append(f"entry {key!r}: unknown source {entry.get('source')!r}")
    for fld in ("static_cost_s", "measured_s"):
        v = entry.get(fld)
        if v is not None and not isinstance(v, (int, float)):
            errs.append(f"entry {key!r}: {fld} must be numeric or null")
    return errs


def validate_calibration_row(platform: str, row) -> List[str]:
    """Violations of one fitted-calibration row (``calibrations``
    section). The row is what :func:`stencil_tpu.plan.calibrate.fit`
    returns: the score() override dict plus its fit provenance."""
    pfx = f"calibration {platform!r}"
    if not isinstance(row, dict):
        return [f"{pfx} is not an object"]
    errs: List[str] = []
    if not isinstance(row.get("calibration"), dict):
        errs.append(f"{pfx}: missing calibration override dict")
    if not isinstance(row.get("provenance"), str) or not row.get("provenance"):
        errs.append(f"{pfx}: provenance must be a non-empty string")
    n = row.get("n")
    if not isinstance(n, int) or isinstance(n, bool) or n < 2:
        errs.append(f"{pfx}: n must be an int >= 2 (a fit from fewer "
                    "samples is refused at fit time, never persisted)")
    if not isinstance(row.get("r2"), (int, float)):
        errs.append(f"{pfx}: r2 must be numeric")
    return errs


def validate_db(obj) -> List[str]:
    """Schema violations of a parsed DB (empty = valid v1)."""
    if not isinstance(obj, dict):
        return [f"not an object: {type(obj).__name__}"]
    errs: List[str] = []
    if obj.get("kind") != DB_KIND:
        errs.append(f"unknown kind {obj.get('kind')!r}")
    if obj.get("v") != DB_VERSION:
        errs.append(f"unknown schema version {obj.get('v')!r}")
    entries = obj.get("entries")
    if not isinstance(entries, dict):
        errs.append("entries must be an object")
        return errs
    for key, entry in entries.items():
        errs.extend(validate_entry(key, entry))
    # "calibrations" rides schema v1 the way placement rides entries: an
    # ABSENT section is "no fitted rows, DEFAULT_CALIBRATION applies"
    # (every pre-observatory DB loads unchanged); a present one maps
    # platform -> fitted row
    if "calibrations" in obj:
        cals = obj["calibrations"]
        if not isinstance(cals, dict):
            errs.append("calibrations must be an object")
        else:
            for platform, row in cals.items():
                errs.extend(validate_calibration_row(platform, row))
    return errs


def migrate_db(obj: dict) -> dict:
    """Bring a stale-schema DB forward to v1.

    Known legacy layout (v0, the pre-schema prototype): a flat
    ``{config-key: choice-json}`` mapping with no version envelope. Its
    entries become v1 entries with ``source="legacy"`` and no recorded
    cost — a lookup hit still replays them, and ``plan_tool prune
    --source legacy`` clears them once re-tuned. Anything newer than
    DB_VERSION is refused (a downgrade must not silently rewrite a
    future DB)."""
    if not isinstance(obj, dict):
        raise PlanDBError(f"plan DB is not an object: {type(obj).__name__}")
    v = obj.get("v")
    if v == DB_VERSION and obj.get("kind") == DB_KIND:
        return obj
    if isinstance(v, int) and v > DB_VERSION:
        raise PlanDBError(
            f"plan DB schema v{v} is newer than this build's v{DB_VERSION}"
        )
    if "v" not in obj and "kind" not in obj:
        # v0 flat mapping: every value must parse as a choice
        entries = {}
        for key, val in obj.items():
            try:
                cfg = PlanConfig.from_json(json.loads(key))
                choice = PlanChoice.from_json(val)
            except (KeyError, TypeError, ValueError,
                    json.JSONDecodeError) as e:
                raise PlanDBError(f"legacy plan DB entry {key!r}: {e}")
            entries[cfg.key()] = make_entry(
                cfg, choice, "legacy", note="migrated from v0 flat layout"
            )
        return {"v": DB_VERSION, "kind": DB_KIND, "entries": entries}
    raise PlanDBError(
        f"unrecognized plan DB envelope (v={obj.get('v')!r}, "
        f"kind={obj.get('kind')!r})"
    )


def load_db(path: str) -> dict:
    """Parse + migrate + validate; missing file -> empty DB. Corruption
    raises :class:`PlanDBError` — callers decide whether to degrade
    (autotune warns and runs un-persisted) or fail (the CI gate)."""
    if not os.path.exists(path):
        return empty_db()
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise PlanDBError(f"unreadable plan DB {path}: {e}")
    obj = migrate_db(obj)
    errs = validate_db(obj)
    if errs:
        raise PlanDBError(
            f"invalid plan DB {path}: {errs[0]}"
            + (f" (+{len(errs) - 1} more)" if len(errs) > 1 else "")
        )
    retired = sorted(
        "{}x{}x{} on {} {}".format(*e["config"]["grid"], e["config"]["ndev"],
                                   e["config"]["platform"])
        for e in obj["entries"].values() if retired_key(e) is not None)
    if retired:
        log.warn(
            f"plan DB {path}: {len(retired)} entries use a retired key "
            "(hierarchy/host_placement, or the method/kernel_variant of "
            "the retired kernel-initiated transport) and "
            "are never served — `plan_tool prune` removes them: "
            + "; ".join(retired))
    return obj


def save_db(path: str, db: dict) -> None:
    """Atomic write: tmp + fsync + rename (ckpt rename discipline)."""
    errs = validate_db(db)
    if errs:
        raise PlanDBError(f"refusing to write invalid plan DB: {errs[0]}")
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f"{_TMP_PREFIX}{os.path.basename(path)}-{os.getpid()}")
    with open(tmp, "w") as f:
        json.dump(db, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def lookup(db: dict, config: PlanConfig) -> Optional[dict]:
    """The entry tuned for ``config`` (exact canonical-key match). An
    entry that uses a retired choice key is a miss (:func:`load_db`
    warned about it)."""
    entry = db["entries"].get(config.key())
    return None if retired_key(entry) is not None else entry


def record(db: dict, entry: dict) -> dict:
    """Insert/replace ``entry`` under its config's canonical key."""
    key = PlanConfig.from_json(entry["config"]).key()
    db["entries"][key] = entry
    return entry


def record_calibration(db: dict, platform: str, row: dict) -> dict:
    """Install/replace the fitted calibration row for ``platform``."""
    errs = validate_calibration_row(platform, row)
    if errs:
        raise PlanDBError(f"refusing to record calibration: {errs[0]}")
    db.setdefault("calibrations", {})[platform] = row
    return row


def lookup_calibration(db: dict, platform: str) -> Optional[dict]:
    """The fitted calibration row for ``platform``, or None (the
    absent-section default: DEFAULT_CALIBRATION, provenance modeled)."""
    return (db.get("calibrations") or {}).get(platform)


def prune_db(db: dict, platform: Optional[str] = None,
             source: Optional[str] = None,
             older_than_s: Optional[float] = None) -> int:
    """Drop entries matching every given filter, and every entry that
    uses a retired choice key (:func:`retired_key`: nothing can serve
    it); returns the count. With none of the latter at least one filter
    is required — "prune everything" must be an explicit
    ``source=...``/``platform=...`` decision, not a default."""
    now = time.time()

    def matches(entry) -> bool:
        return not (
            (platform is not None
             and entry["config"].get("platform") != platform)
            or (source is not None and entry.get("source") != source)
            or (older_than_s is not None
                and now - entry.get("written_t", 0) < older_than_s))

    filtered = not (platform is None and source is None
                    and older_than_s is None)
    doomed = [key for key, entry in db["entries"].items()
              if retired_key(entry) is not None
              or (filtered and matches(entry))]
    if not filtered and not doomed:
        raise ValueError("prune_db requires at least one filter")
    for key in doomed:
        del db["entries"][key]
    return len(doomed)
