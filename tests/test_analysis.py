"""stencil_tpu/analysis: the lint engine, the plan/HLO conformance
auditor, and the jit recompile/host-sync audit (ISSUE 13).

Lint tests run the real engine over per-rule good/bad fixture snippets
in a temp tree (including nested/aliased imports for pure-stdlib and
the suppression-pragma edge cases). The plan auditor is pinned to agree
for all four exchange methods at 16^3 on the 8-virtual-device CPU mesh
(conftest.py) and to TRIP when an IR prediction is perturbed; the jit
audit must pass on the clean jacobi chunk loop and fail on both
injected fixtures.
"""

import json
import os

import pytest

from stencil_tpu.analysis import astlint
from stencil_tpu.analysis.astlint import lint_paths, load_baseline, \
    write_baseline


def _lint_snippet(tmp_path, relpath, src, rules=None):
    fpath = tmp_path / relpath
    fpath.parent.mkdir(parents=True, exist_ok=True)
    fpath.write_text(src)
    findings, errors = lint_paths([str(fpath)], repo_root=str(tmp_path),
                                  rules=rules)
    assert not errors, errors
    return findings


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- pure-stdlib --------------------------------------------------------------


def test_pure_stdlib_flags_nested_and_aliased_imports(tmp_path):
    findings = _lint_snippet(tmp_path, "obs/ledger.py", (
        "import json\n"
        "from numpy import array as arr\n"       # aliased third-party
        "def append(path):\n"
        "    import jax\n"                        # nested: still flagged
        "    return jax, arr\n"
    ))
    mine = [f for f in findings if f.rule == "pure-stdlib"]
    assert len(mine) == 2
    assert {f.line for f in mine} == {2, 4}


def test_pure_stdlib_rejects_relative_imports(tmp_path):
    findings = _lint_snippet(tmp_path, "obs/status.py",
                             "from .telemetry import Recorder\n")
    assert _rules(findings) == ["pure-stdlib"]
    assert "file path" in findings[0].message


def test_pure_stdlib_clean_on_stdlib_only(tmp_path):
    findings = _lint_snippet(tmp_path, "obs/watchdog.py", (
        "import json\nimport os\n"
        "try:\n    import fcntl\nexcept ImportError:\n    fcntl = None\n"
    ))
    assert findings == []


def test_pure_stdlib_does_not_apply_elsewhere(tmp_path):
    findings = _lint_snippet(tmp_path, "lib/other.py", "import jax\n")
    assert [f for f in findings if f.rule == "pure-stdlib"] == []


# -- telemetry-vocab ----------------------------------------------------------


def test_vocab_flags_typo_and_passes_known_and_dynamic(tmp_path):
    bad = _lint_snippet(tmp_path, "lib/site.py", (
        "def emit(rec):\n"
        "    rec.counter('recover.rollbck', value=1)\n"
    ))
    assert _rules(bad) == ["telemetry-vocab"]
    good = _lint_snippet(tmp_path, "lib/site.py", (
        "def emit(rec, kind):\n"
        "    rec.gauge('exchange.trimean_s', 1.0)\n"
        "    rec.counter(f'census.{kind}', value=1)\n"   # generic: exempt
        "    rec.emit('gauge', 'jacobi.mcells_per_s', value=1.0)\n"
    ))
    assert good == []


def test_vocab_includes_name_fields_and_analysis(tmp_path):
    good = _lint_snippet(tmp_path, "lib/site.py", (
        "def emit(rec):\n"
        "    rec.meta('analysis.plan_verdict', method='x', ok=1)\n"
        "    rec.meta('recover.aborted', reason='r', step=1)\n"
    ))
    assert good == []


# -- atomic-write -------------------------------------------------------------


def test_atomic_write_flags_plain_dump(tmp_path):
    bad = _lint_snippet(tmp_path, "lib/w.py", (
        "import json\n"
        "def save(path, doc):\n"
        "    with open(path, 'w') as f:\n"
        "        json.dump(doc, f)\n"
    ))
    assert _rules(bad) == ["atomic-write"]


def test_atomic_write_not_silenced_by_str_replace(tmp_path):
    # a str.replace in scope is NOT the atomic protocol: only
    # os/shutil.replace (or a .rename) counts
    bad = _lint_snippet(tmp_path, "lib/w.py", (
        "import json\n"
        "def save(path, doc):\n"
        "    key = path.replace('-', '_')\n"
        "    with open(path, 'w') as f:\n"
        "        json.dump({key: doc}, f)\n"
    ))
    assert _rules(bad) == ["atomic-write"]


def test_atomic_write_passes_tmp_rename_protocol(tmp_path):
    good = _lint_snippet(tmp_path, "lib/w.py", (
        "import json, os\n"
        "def save(path, doc):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        json.dump(doc, f)\n"
        "        os.fsync(f.fileno())\n"
        "    os.replace(tmp, path)\n"
    ))
    assert good == []


# -- no-bare-assert -----------------------------------------------------------


def test_assert_flagged_only_at_public_api_boundaries(tmp_path):
    src = (
        "class Dom:\n"
        "    def realize(self, n):\n"
        "        assert n >= 1\n"              # public method: flagged
        "    def _inner(self, n):\n"
        "        assert n >= 1\n"              # private: exempt
        "def make_loop(k):\n"
        "    assert k > 0\n"                   # public function: flagged
        "    def body(x):\n"
        "        assert x is not None\n"       # nested: exempt
        "    return body\n"
        "def assert_consistent(a):\n"
        "    assert a\n"                       # assert_* checker: exempt
    )
    findings = _lint_snippet(tmp_path, "lib/api.py", src,
                             rules=["no-bare-assert"])
    assert {f.line for f in findings} == {3, 7}


def test_assert_flagged_under_module_level_conditional(tmp_path):
    # a def under a module-level if/try (feature gates, optional-dep
    # fallbacks) is just as public as one at the top level
    findings = _lint_snippet(tmp_path, "lib/api.py", (
        "import sys\n"
        "if sys.platform == 'linux':\n"
        "    def realize(n):\n"
        "        assert n >= 1\n"
    ), rules=["no-bare-assert"])
    assert {f.line for f in findings} == {4}


def test_assert_rule_skips_tests_and_scripts(tmp_path):
    for rel in ("tests/test_x.py", "scripts/probe.py"):
        findings = _lint_snippet(tmp_path, rel,
                                 "def run(n):\n    assert n\n",
                                 rules=["no-bare-assert"])
        assert findings == [], rel


# -- fstring-placeholder ------------------------------------------------------


def test_placeholder_flagged_at_raise_and_log_sites(tmp_path):
    bad = _lint_snippet(tmp_path, "lib/e.py", (
        "def fail(name, log):\n"
        "    log.warn('method {name} is slow')\n"
        "    raise ValueError('unknown method {name!r}')\n"
    ))
    assert _rules(bad) == ["fstring-placeholder"]
    assert len(bad) == 2


def test_placeholder_passes_fstrings_format_and_escapes(tmp_path):
    good = _lint_snippet(tmp_path, "lib/e.py", (
        "def fail(name):\n"
        "    raise ValueError(f'unknown method {name}')\n"
        "def fail2(name):\n"
        "    raise ValueError('unknown method {}'.format(name))\n"
        "def fail3():\n"
        "    raise ValueError('literal braces {{x}} are fine')\n"
        "def fail4(name):\n"
        "    raise ValueError('config shape: {\"a\": 1} etc')\n"
    ))
    assert good == []


# -- host-sync-in-hot-loop ----------------------------------------------------


def test_host_sync_flagged_in_traced_bodies(tmp_path):
    bad = _lint_snippet(tmp_path, "lib/hot.py", (
        "import time\n"
        "import jax\n"
        "def make_step():\n"
        "    def body(x):\n"
        "        t = time.time()\n"            # trace-time constant
        "        return x + t\n"
        "    return jax.jit(body)\n"
    ))
    assert _rules(bad) == ["host-sync-in-hot-loop"]
    assert "trace-time constant" in bad[0].message


def test_host_sync_propagates_through_called_helpers(tmp_path):
    bad = _lint_snippet(tmp_path, "lib/hot.py", (
        "import jax\n"
        "def helper(x):\n"
        "    return float(x.item())\n"         # reached from traced body
        "def make_step():\n"
        "    def body(x):\n"
        "        return helper(x)\n"
        "    return jax.jit(body)\n"
    ), rules=["host-sync-in-hot-loop"])
    assert bad and all(f.rule == "host-sync-in-hot-loop" for f in bad)


def test_host_sync_ignores_host_code(tmp_path):
    good = _lint_snippet(tmp_path, "lib/host.py", (
        "import time\n"
        "def time_loop(fn, state):\n"
        "    t0 = time.perf_counter()\n"
        "    state = fn(state)\n"
        "    return state, time.perf_counter() - t0\n"
    ), rules=["host-sync-in-hot-loop"])
    assert good == []


# -- suppression pragmas ------------------------------------------------------


def test_inline_disable_honored_same_line_and_line_above(tmp_path):
    good = _lint_snippet(tmp_path, "lib/api.py", (
        "def realize(n):\n"
        "    assert n >= 1  # lint: disable=no-bare-assert\n"
        "    # lint: disable=no-bare-assert (documented: perf-critical)\n"
        "    assert n < 100\n"
    ), rules=["no-bare-assert"])
    assert good == []


def test_unknown_rule_in_disable_rejected_loudly(tmp_path):
    findings = _lint_snippet(tmp_path, "lib/api.py", (
        "def realize(n):\n"
        "    assert n >= 1  # lint: disable=no-bear-assert\n"
    ))
    rules = _rules(findings)
    assert "bad-pragma" in rules            # the typo'd pragma is loud
    assert "no-bare-assert" in rules        # ...and suppresses nothing


def test_unknown_rule_filter_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        lint_paths(["stencil_tpu"], rules=["no-such-rule"])


# -- baseline workflow --------------------------------------------------------


def test_baseline_roundtrip_and_resurface_on_edit(tmp_path):
    src = "def realize(n):\n    assert n >= 1\n"
    f = tmp_path / "lib" / "api.py"
    f.parent.mkdir(parents=True)
    f.write_text(src)
    findings, _ = lint_paths([str(f)], repo_root=str(tmp_path),
                             rules=["no-bare-assert"])
    assert len(findings) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), findings)
    fps = load_baseline(str(bl))
    assert findings[0].fingerprint in fps

    # line shifts do NOT invalidate the baseline entry...
    f.write_text("import os\n\n" + src)
    again, _ = lint_paths([str(f)], repo_root=str(tmp_path),
                          rules=["no-bare-assert"])
    assert again[0].fingerprint in fps
    # ...but editing the offending line resurfaces the finding
    f.write_text(src.replace("n >= 1", "n >= 2"))
    edited, _ = lint_paths([str(f)], repo_root=str(tmp_path),
                           rules=["no-bare-assert"])
    assert edited[0].fingerprint not in fps


def test_fingerprints_distinct_across_same_basename_files(tmp_path):
    # identical offending lines in a/util.py and b/util.py must NOT
    # collide: baselining one would silently suppress the other
    src = "def realize(n):\n    assert n >= 1\n"
    for d in ("a", "b"):
        f = tmp_path / d / "util.py"
        f.parent.mkdir(parents=True)
        f.write_text(src)
    findings, _ = lint_paths([str(tmp_path / "a"), str(tmp_path / "b")],
                             repo_root=str(tmp_path),
                             rules=["no-bare-assert"])
    assert len(findings) == 2
    assert findings[0].fingerprint != findings[1].fingerprint


def test_malformed_baseline_is_loud(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text('{"not": "a baseline"}')
    with pytest.raises(ValueError, match="v1 lint baseline"):
        load_baseline(str(bl))


def test_committed_tree_lints_clean_against_committed_baseline():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings, errors = lint_paths(astlint.DEFAULT_PATHS, repo_root=root)
    assert not errors, errors
    baseline = load_baseline(os.path.join(root, "lint-baseline.json"))
    new = [f for f in findings if f.fingerprint not in baseline]
    assert new == [], [f.render() for f in new]


# -- plan conformance auditor -------------------------------------------------


@pytest.mark.parametrize("method", ["axis-composed", "direct26",
                                    "auto-spmd"])
def test_plan_auditor_agrees_per_method(method):
    from stencil_tpu.analysis import verify_plan as vp

    configs = vp.sweep_configs(size=16, radius=2,
                               partitions=[(2, 2, 2)],
                               methods=[method],
                               qsets=[("float32", "float32")])
    res = vp.run_sweep(configs)
    assert res["checked"] == 1 and res["failed"] == 0, [
        v.to_json() for v in res["verdicts"]]


def test_plan_auditor_trips_on_perturbed_prediction():
    from stencil_tpu.analysis import verify_plan as vp

    configs = vp.sweep_configs(size=16, radius=2, partitions=[(2, 2, 2)],
                               methods=["axis-composed"],
                               qsets=[("float32",)])
    res = vp.run_sweep(configs, perturb_collectives=1)
    assert res["failed"] == 1
    v = res["verdicts"][0]
    bad = [c for c in v.checks if not c["ok"]]
    assert bad and bad[0]["name"] == "collectives_per_exchange"
    assert bad[0]["predicted"] == bad[0]["actual"] + 1


def test_plan_auditor_skips_infeasible_loudly():
    from stencil_tpu.analysis import verify_plan as vp

    configs = vp.sweep_configs(size=16, radius=2, partitions=[(3, 3, 3)],
                               methods=["axis-composed"],
                               qsets=[("float32",)])
    res = vp.run_sweep(configs)
    assert res["checked"] == 0 and res["skipped"] == 1
    assert "devices" in res["verdicts"][0].reason


def test_verify_plan_cli_exit2_when_nothing_analyzed(capsys):
    from stencil_tpu.apps import lint_tool

    rc = lint_tool.main(["verify-plan", "--partitions", "3x3x3",
                         "--quantities", "f32"])
    assert rc == 2
    assert "nothing analyzed" in capsys.readouterr().err


def test_verify_plan_emits_schema_valid_records(tmp_path):
    from stencil_tpu.analysis import verify_plan as vp
    from stencil_tpu.obs import telemetry

    out = tmp_path / "m.jsonl"
    rec = telemetry.Recorder(sink=str(out), app="test")
    configs = vp.sweep_configs(size=16, radius=2, partitions=[(2, 2, 2)],
                               methods=["axis-composed"],
                               qsets=[("float32",)])
    vp.run_sweep(configs, rec=rec)
    rec.close()
    n_ok, errors = telemetry.validate_jsonl(
        out.read_text().splitlines())
    assert errors == [] and n_ok >= 2


def test_run_sweep_restores_x64_flag():
    # the fp64 sweep flips jax_enable_x64 for itself and must restore
    # it — a leak would make a following jit-audit certify fp64-sel
    # programs the apps never run (infeasible partition: no compiles)
    import jax

    from stencil_tpu.analysis import verify_plan as vp

    configs = vp.sweep_configs(size=16, radius=2, partitions=[(3, 3, 3)],
                               methods=["axis-composed"],
                               qsets=[("float64",)])
    assert jax.config.jax_enable_x64  # conftest turns it on
    try:
        jax.config.update("jax_enable_x64", False)
        vp.run_sweep(configs)
        assert jax.config.jax_enable_x64 is False
    finally:
        jax.config.update("jax_enable_x64", True)


# -- jit audit ----------------------------------------------------------------


def test_jit_audit_passes_clean_loop():
    from stencil_tpu.analysis.jit_audit import run_audit

    r = run_audit(size=16, iters=10, chunk=4)
    assert r.ok and r.recompiles == 0 and r.transfer_trips == []
    assert r.steps == 10


def test_jit_audit_fails_on_injected_recompile():
    from stencil_tpu.analysis.jit_audit import run_audit

    r = run_audit(size=16, iters=10, chunk=4, inject="recompile")
    assert not r.ok and r.recompiles >= 1


def test_jit_audit_fails_on_injected_host_sync():
    from stencil_tpu.analysis.jit_audit import run_audit

    r = run_audit(size=16, iters=10, chunk=4, inject="host-sync")
    assert not r.ok and len(r.transfer_trips) >= 1
    assert "isallow" in r.transfer_trips[0]


def test_jit_audit_rejects_unknown_inject():
    from stencil_tpu.analysis.jit_audit import run_audit

    with pytest.raises(ValueError, match="unknown inject"):
        run_audit(inject="sleep")
