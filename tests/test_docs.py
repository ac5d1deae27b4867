"""The documents name what exists.

``README.md``, ``PERF.md`` and ``ROADMAP.md`` are read by every later
session before the code is: a path in backticks that is not in the tree,
or a command line whose flag the parser does not take, sends the reader
after code that is gone. Pure text and ``argparse``; nothing runs.

What counts as a repository path: a backticked token without spaces that
has a ``/`` in it, or a bare file name with a source extension. A
``:line`` or ``::test`` suffix is cut, a glob must match something, and a
path may be written from the root, from ``stencil_tpu/`` or from any
directory the sentence is about (``kernels/jacobi_multistep.py``): it has
to be the tail of a tracked path. ``plan/cost.score`` names ``score`` in
``plan/cost.py``: the module must exist and hold the name. A path of
another repository is written
with that repository's name in front (``socal-ucr/stencil:src/...``); one
whose first directory no directory of this tree is named after
(``bin/jacobi3d.cu``) is foreign as it stands.
"""

import fnmatch
import importlib
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "PERF.md", "ROADMAP.md")
SOURCE_EXT = (".py", ".md", ".sh", ".yml", ".cpp")
_TOKEN = re.compile(r"`([^`\n]+)`")
_SUFFIX = re.compile(r"(::.*|:[\d][\d,\- ]*)$")


def _tracked():
    """Files git would commit (tracked, or new and not ignored), and
    every directory above them, as '/'-joined paths."""
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=REPO, capture_output=True, text=True, check=True).stdout
    files = [f for f in out.splitlines()
             if os.path.exists(os.path.join(REPO, f))]
    dirs = {f.rsplit("/", i)[0] for f in files
            for i in range(1, f.count("/") + 1)}
    return files, sorted(dirs)


def _read(doc):
    with open(os.path.join(REPO, doc)) as f:
        return f.read()


def _candidates(text):
    for tok in _TOKEN.findall(text):
        tok = tok.strip()
        if not tok or re.search(r"[\s<>$(){}=|,]", tok) or "://" in tok:
            continue
        path = _SUFFIX.sub("", tok).rstrip("/")
        if "/" in path or path.endswith(SOURCE_EXT):
            yield tok, path


def _holds(files, module, name):
    """``module`` (``plan/cost``) is the tail of a tracked ``.py`` file
    that has ``name`` as a word."""
    for f in files:
        if ("/" + f).endswith("/" + module + ".py"):
            with open(os.path.join(REPO, f)) as fh:
                if re.search(rf"\b{re.escape(name)}\b", fh.read()):
                    return True
    return False


def _missing(doc):
    files, dirs = _tracked()
    dir_names = {d.rsplit("/", 1)[-1] for d in dirs}
    known = ["/" + p for p in files + dirs]
    missing = []
    for tok, path in _candidates(_read(doc)):
        if "/" in path and path.split("/", 1)[0] not in dir_names:
            continue  # another repository's path
        tail = "/" + path
        if any(ch in path for ch in "*?["):
            ok = any(fnmatch.fnmatch(k, "*" + tail) for k in known)
        else:
            ok = any(k.endswith(tail) or k.endswith(tail + ".py")
                     for k in known)
        if not ok and "/" in path:
            # module.attribute: plan/cost.score, obs/telemetry.KNOWN_NAMES
            head, last = path.rsplit("/", 1)
            module, dot, name = last.partition(".")
            ok = bool(dot) and _holds(files, f"{head}/{module}",
                                      name.split(".")[0])
        if not ok:
            missing.append(tok)
    return sorted(set(missing))


@pytest.mark.parametrize("doc", DOCS)
def test_backticked_paths_exist(doc):
    assert _missing(doc) == []


_COMMAND = re.compile(r"python3? -m stencil_tpu\.apps\.(\w+)((?:[^\n`]|\\\n)*)")


def _parser_flags(app, monkeypatch):
    """Every option string the app's parser (and its sub-parsers) takes,
    collected while ``main(["--help"])`` builds it."""
    import argparse

    mod = importlib.import_module(f"stencil_tpu.apps.{app}")
    flags = set()
    real = argparse.ArgumentParser.add_argument

    def spy(self, *names, **kw):
        flags.update(n for n in names if n.startswith("-"))
        return real(self, *names, **kw)

    with monkeypatch.context() as m:
        m.setattr(argparse.ArgumentParser, "add_argument", spy)
        with pytest.raises(SystemExit):
            mod.main(["--help"])
    return flags


def test_readme_commands_exist(capsys, monkeypatch):
    wrong = []
    seen = {}
    for app, rest in _COMMAND.findall(_read("README.md")):
        if not os.path.isfile(
                os.path.join(REPO, "stencil_tpu", "apps", app + ".py")):
            wrong.append(f"stencil_tpu.apps.{app}")
            continue
        if app not in seen:
            seen[app] = _parser_flags(app, monkeypatch)
            capsys.readouterr()
        for flag in re.findall(r"(?<![\w-])(--[a-z][\w-]*)", rest):
            if flag not in seen[app]:
                wrong.append(f"stencil_tpu.apps.{app} {flag}")
    assert seen, "README.md shows no application command line"
    assert sorted(set(wrong)) == []


# What PR 46 deleted: the kernel-initiated transport, its two kernel variants
# and their CPU stand-in, with the options, gates and probes that selected
# them. A document that still names one sends the reader after code that is
# gone. ``plan/ir.py`` keeps the stored spellings it refuses
# (``retired_choice_key``); ``benchmark/`` is the yardstick's and is not
# this test's to hold; ``CHANGES.md`` says what went, by name.
_DELETED = re.compile(
    r"remote.dma|remote_emu|fused_stencil|persistent_stencil"
    r"|set_fused_exchange|set_persistent_exchange|FUSED_VARIANT"
    r"|PERSISTENT_VARIANT|launches_per_chunk|dmas_per_exchange"
    r"|RemoteDmaPhaseIR|FusedPhaseIR|make_fused_astaroth_loop"
    r"|_compile_jacobi_(fused|remote|persistent)|kernel_launch_census"
    r"|ci_fused_gate|ci_persistent_gate|probe_persistent"
    r"|fused\.overlap_fraction|fused_jacobi|persistent_jacobi|fused_exchange"
    r"|--fused\b|--variants\b|--perturb-dmas"
    r"|--kernel-variant[ =](fused|persistent)"
    r"|kernel_variant\s*=\s*[\"']?(fused|persistent)", re.IGNORECASE)
_HOLDS_THE_RETIRED_SPELLINGS = ("stencil_tpu/plan/ir.py",)
_SAY_NOTHING_DELETED = {
    "README.md": ("README.md",),
    "COMPONENTS.md": ("COMPONENTS.md",),
    "ROADMAP.md": ("ROADMAP.md",),
    "PERF.md": ("PERF.md",),
    "ci.yml": (".github/",),
    "verify-skill": (".claude/skills/verify/SKILL.md",),
    "package": ("stencil_tpu/",),
    "scripts": ("scripts/",),
    "entry-points": ("chip_smoke.py", "__graft_entry__.py", "pytest.ini",
                     "lint-baseline.json", "perf-legs.json"),
}


@pytest.mark.parametrize("what", sorted(_SAY_NOTHING_DELETED))
def test_nothing_names_what_pr_46_deleted(what):
    files, _ = _tracked()
    held = [f for f in files
            if f.startswith(_SAY_NOTHING_DELETED[what])
            and f not in _HOLDS_THE_RETIRED_SPELLINGS]
    assert held, what
    named = []
    for f in held:
        try:
            text = _read(f)
        except UnicodeDecodeError:
            continue
        named += [f"{f}:{i}: {m.group(0)}"
                  for i, line in enumerate(text.splitlines(), 1)
                  for m in [_DELETED.search(line)] if m]
    assert named == []
