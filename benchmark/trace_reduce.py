"""From a profiler trace (``.xplane.pb``) to numbers. Imports nothing of
``stencil_tpu``: what this file computes is the yardstick, and no PR that
claims a gain may change it.

A v5e trace, as looked at by hand in PR 24 (see PERF.md): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
program launch, i.e. per dispatched chunk), ``XLA Ops`` (one event per HLO
instruction, nested: a ``while`` holds its body's ops) and ``Async XLA Ops``
(copies and collectives in flight); host threads under ``/host:CPU`` on the
same time base, where the benchmark's own ``bench.*`` annotations land.

``load`` reads the file into plain lists; ``classify`` labels every device
op as a stencil kernel, a halo kernel, a collective or glue, given the
kernel descriptions the cell's configuration names; the functions below it
are what the per-layer readers call.
"""

from __future__ import annotations

import re
from collections import defaultdict

COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-broadcast|send|recv)(-start|-done)?$")
CONTAINERS = {"while", "conditional", "call"}
PALLAS_TARGETS = ("tpu_custom_call", "mosaic")
_SHAPE = re.compile(r"\b(?:pred|[a-z]+\d+(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+)+$")     # ``fusion.3`` -> ``fusion``


# ------------------------------------------------------------ HLO text


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _dims(text: str):
    return [tuple(int(d) for d in m.split(",") if d)
            for m in _SHAPE.findall(text)]


def parse_hlo(text: str) -> dict:
    """``instr``, ``opcode``, ``target``, result and operand shapes of one
    HLO instruction as the trace prints it. A bare instruction name
    (``fusion.3``) gives the opcode by dropping the numeric suffix."""
    text = text.strip()
    out = {"instr": text, "opcode": "", "target": None, "results": [],
           "operands": []}
    if " = " not in text:
        name = text.lstrip("%")
        out["instr"] = name
        out["opcode"] = _SUFFIX.sub("", name)
        return out
    lhs, rest = text.split(" = ", 1)
    out["instr"] = lhs.strip().lstrip("%")
    rest = rest.lstrip()
    if rest.startswith("("):
        end = _balanced(rest, 0)
    else:
        end = rest.find(" ")
        end = len(rest) if end < 0 else end
    out["results"] = _dims(rest[:end])
    call = rest[end:].lstrip()
    par = call.find("(")
    if par < 0:
        out["opcode"] = call.split(",")[0].strip()
        return out
    out["opcode"] = call[:par].strip()
    close = _balanced(call, par)
    out["operands"] = _dims(call[par:close])
    m = _TARGET.search(call[close:])
    if m:
        out["target"] = m.group(1)
    return out


def label(op: dict) -> str:
    """``<instruction>:<opcode>[:<target>]``."""
    parts = [op["instr"], op["opcode"]]
    if op.get("target"):
        parts.append(op["target"])
    return ":".join(parts)


# ------------------------------------------------------------ loading


def _events(line):
    out = []
    for e in line.events:
        out.append((e.name, float(e.start_ns), float(e.duration_ns), e))
    return out


def _text_of(name: str, event) -> str:
    """The fullest HLO text the event carries: its name if that is the
    instruction's text, else the longest string statistic that is one."""
    if " = " in name:
        return name
    best = name
    try:
        for key, val in event.stats:
            if isinstance(val, str) and " = " in val and "(" in val \
                    and len(val) > len(best):
                best = val
    except TypeError:
        pass
    return best


def load(path: str) -> dict:
    """Plain-Python view of a trace: per chip the module launches, the ops
    (with self time, parsed HLO) and the async ops; the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            chip = {"id": int(m.group(1)), "modules": [], "ops": [],
                    "async": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    chip["modules"] = [(n, s, d) for n, s, d, _ in
                                       _events(line)]
                elif line.name == "XLA Ops":
                    chip["ops"] = _ops(_events(line))
                elif line.name == "Async XLA Ops":
                    chip["async"] = [
                        dict(parse_hlo(_text_of(n, e)), start=s, dur=d)
                        for n, s, d, e in _events(line)]
            chips[chip["id"]] = chip
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for n, s, d, _ in _events(line):
                    if n.startswith(_HOST_PREFIX):
                        host.append((n, s, d))
    host.sort(key=lambda h: h[1])
    return {"chips": [chips[k] for k in sorted(chips)], "host": host}


def _ops(events) -> list:
    """Events of one ``XLA Ops`` line as dicts with ``self`` time: the
    duration less what nested events cover."""
    events.sort(key=lambda e: (e[1], -e[2]))
    ops, stack = [], []
    for name, start, dur, ev in events:
        op = dict(parse_hlo(_text_of(name, ev)), start=start, dur=dur,
                  self=dur)
        while stack and stack[-1]["start"] + stack[-1]["dur"] <= start:
            stack.pop()
        if stack:
            stack[-1]["self"] -= dur
            op["parent"] = stack[-1]["instr"]
        stack.append(op)
        ops.append(op)
    for op in ops:
        op["self"] = max(op["self"], 0.0)
    return ops


# ------------------------------------------------------------ classes


def is_pallas(op: dict) -> bool:
    return op["opcode"] == "custom-call" and any(
        t in (op.get("target") or "") for t in PALLAS_TARGETS)


def match_build(op: dict, builds) -> dict | None:
    """The recorded ``pallas_call`` build whose call this custom-call is:
    same number of operands and the same result shapes. Where several
    builds share both (a transfer onto a level and the box operator on it),
    the one whose ``name=`` is the stem of the instruction's name
    (``hpcg_prolong.1`` -> ``hpcg_prolong``); an older trace names no
    kernel, and then the first such build is all there is."""
    want = sorted(op["results"])
    same = [b for b in builds
            if sorted(tuple(s) for s in b["out_shapes"]) == want]
    hits = [b for b in same if b["n_operands"] == len(op["operands"])] or same
    stem = _SUFFIX.sub("", op["instr"])
    named = [b for b in hits if b.get("name") == stem]
    return (named or hits or [None])[0]


def classify(trace: dict, kernels: dict, builds) -> None:
    """Give every op a ``cls``: ``stencil`` / ``halo`` (a Pallas kernel of
    the configuration's lists, with ``kernel`` the description's name and
    ``build`` the recorded build), ``collective``, ``container`` or
    ``glue``. ``kernels`` maps role -> {name: module}; a module's
    ``FAMILIES`` are the ``pallas_call`` kernels it describes."""
    family = {}
    for role, mods in kernels.items():
        for name, mod in mods.items():
            for fam in mod.FAMILIES:
                family[fam] = (role, name)
    known = [b for b in builds if b["kernel"] in family]
    for chip in trace["chips"]:
        for op in chip["ops"]:
            if COLLECTIVE.match(op["opcode"]):
                op["cls"] = "collective"
            elif op["opcode"] in CONTAINERS:
                op["cls"] = "container"
            elif is_pallas(op):
                b = match_build(op, known)
                if b is None:          # no recorded build has its shapes
                    op["cls"] = "glue"
                else:
                    op["cls"], op["kernel"] = family[b["kernel"]]
                    op["build"] = b
            else:
                op["cls"] = "glue"


# ------------------------------------------------------------ arithmetic


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_intervals(chip, pred=lambda op: True):
    return [(op["start"], op["start"] + op["dur"]) for op in chip["ops"]
            if pred(op)]


def busy_ns(chip) -> float:
    return measure(union(op_intervals(chip)))


def class_ns(chip, cls: str, kernel: str | None = None) -> float:
    """Self time of the chip's ops of one class (and one kernel)."""
    return sum(op["self"] for op in chip["ops"] if op.get("cls") == cls
               and (kernel is None or op.get("kernel") == kernel))


def in_flight(chip) -> list:
    """Intervals with a collective in flight or waited for: the async
    line's collectives where the trace has them, the synchronous
    collectives, and each ``-start`` op paired with the next ``-done`` of
    its kind on the ops line (first started, first done)."""
    out = [(a["start"], a["start"] + a["dur"]) for a in chip["async"]
           if COLLECTIVE.match(a["opcode"])]
    waiting = {}
    for op in sorted(chip["ops"], key=lambda o: o["start"]):
        m = COLLECTIVE.match(op["opcode"])
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            waiting.setdefault(kind, []).append(op["start"])
        elif phase == "-done" and waiting.get(kind):
            out.append((waiting[kind].pop(0), op["start"] + op["dur"]))
        else:
            out.append((op["start"], op["start"] + op["dur"]))
    return out


def exposed_collective_ns(chip) -> float:
    """Time with a collective in flight or waited for and no other op
    running on the chip."""
    other = op_intervals(
        chip, lambda op: op.get("cls") in ("stencil", "halo", "glue"))
    return measure(subtract(union(in_flight(chip)), union(other)))


def launch_gaps_ns(chip) -> list:
    """Idle time between the end of one program launch and the start of
    the next."""
    mods = sorted(chip["modules"], key=lambda m: m[1])
    return [max(0.0, b[1] - (a[1] + a[2])) for a, b in zip(mods, mods[1:])]


def host_span_at(host, t: float) -> str:
    """The innermost ``bench.*`` span of the host that covers time ``t``."""
    best, best_dur = "outside bench spans", None
    for name, s, d in host:
        if s > t:
            break
        if s <= t < s + d and (best_dur is None or d < best_dur):
            best, best_dur = name, d
    return best


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: on the first chip, the ops with most
    self time and the idle gaps summed by what the host was doing."""
    if not trace["chips"]:
        return {"device_ops": [], "idle_gaps": []}
    chip = trace["chips"][0]
    by_op = defaultdict(float)
    for op in chip["ops"]:
        by_op[label(op)] += op["self"]
    busy = union(op_intervals(chip))
    gaps = defaultdict(float)
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        gaps[host_span_at(trace["host"], 0.5 * (e0 + s1))] += s1 - e0
    rank = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}
