"""One cell, once: set-up in timed phases, a measured window, the check
against the reference, the result line.

This file knows no application, cell, metric or kernel by name. What
belongs to one of them is a file that ``BENCHMARK.json`` (or the cell's
configuration) names and that is found here by that name:

- ``configs/<config>.json``      the deployment, as a user would state it
- ``traffic/<mix>.json``         the dispatch pattern
- ``apps/<app>.py``              the adapter onto the application
- ``end_to_end/<metric>.py``     one end-to-end metric from the window
- ``layer_metrics/<metric>.py``  one per-layer metric from the trace
- ``kernels/<kernel>.py``        one kernel: trace pattern, bytes, operations

and the cell's own two sets of six chip runs are a seventh file, which only
the tests read: ``tests/benchmark_suite/data/sets/<cell>.json``
(``bounds_check.py`` holds the bounds against every file of that directory).
So a later PR adds cells, mixes, metrics and kernels as files and entries
and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
import traceback
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NO_CHIP_RC = 2
REHEARSAL_RC = 3


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by file path: metric names hold dots,
    so these are files, not packages."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    modname = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            config = next(c for c in bench["configs"]
                          if c["name"] == cell["config"])
            return cell, config
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[c['name'] for c in bench['workloads']]}")


class Phases:
    """Seconds of every set-up phase, each printed as it ends."""

    def __init__(self, t_start: float):
        self.last = t_start
        self.seconds = {}

    def end(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = now - self.last
        say(f"setup phase {name}: {self.seconds[name]:.3f} s")
        self.last = now


class CompileCounter:
    """Compilations and persistent-cache traffic, from JAX's own events."""

    def __init__(self, jax):
        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def run_window(session, seconds: float, annotate) -> dict:
    """Closed loop, one client: dispatch a chunk, wait for it, the next one,
    until ``seconds`` have passed; the chunk running then is finished and
    counted. Host clock from dispatch to the return of block_until_ready."""
    import jax

    times, enqueue, failed = [], [], 0
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    with annotate("bench.window"):
        t0_ns = time.time_ns()      # the program's spans carry this clock
        t0 = time.perf_counter()
        end = t0 + seconds
        now = t0
        while now < end:
            t = mid = now
            try:
                with annotate("bench.dispatch"):
                    out = session.dispatch()
                mid = time.perf_counter()
                with annotate("bench.sync"):
                    jax.block_until_ready(out)
            except Exception:  # a dispatch that raised is a failed one
                traceback.print_exc()
                failed += 1
                enqueue.append(mid - t)
                times.append(time.perf_counter() - t)
                break
            now = time.perf_counter()
            enqueue.append(mid - t)
            times.append(now - t)
        t1 = time.perf_counter()
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    k = session.facts["iters_per_dispatch"]
    return {"seconds": t1 - t0, "t0_ns": t0_ns, "dispatch_s": times,
            "enqueue_s": enqueue, "dispatches": len(times),
            "iters_per_dispatch": k,
            "iterations": k * (len(times) - failed), "failed": failed,
            "host": {"cpu_s": usage1.ru_utime + usage1.ru_stime
                     - usage0.ru_utime - usage0.ru_stime,
                     "preempted": usage1.ru_nivcsw - usage0.ru_nivcsw,
                     "major_faults": usage1.ru_majflt - usage0.ru_majflt}}


def stalls(window: dict) -> dict:
    """Where a run's time went when it reads far off: the dispatches over
    twice the median (count, seconds), the slowest with the host's part
    (``dispatch()`` until it returned) beside the whole, and what the
    process's own accounting saw of the host in the window. Printed by
    every run and carried on the result line beside the contract's keys."""
    import statistics

    ms = [1e3 * t for t in window["dispatch_s"]]
    med = statistics.median(ms)
    slow = sorted(range(len(ms)), key=lambda i: -ms[i])[:3]
    over = [t for t in ms if t > 2 * med]
    return {"dispatch_ms_median": med, "over_twice_the_median": len(over),
            "over_twice_the_median_s": sum(over) / 1e3,
            "slowest_index_ms_enqueue_ms": [
                [i, ms[i], 1e3 * window["enqueue_s"][i]] for i in slow],
            "host": window["host"]}


def find_trace(trace_dir: str) -> str:
    for base, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def device_facts(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "device_kind": d0.device_kind, "count": len(devices)}


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _plain(x):
    """A check's number as JSON takes it."""
    return x if isinstance(x, (bool, int, float)) else float(x)


def main(args, t_start: float) -> int:
    result, rc = run_cell(args, t_start)
    if rc == 0:
        print(json.dumps(result), flush=True)
    return rc


def open_session(args, t_start: float, wrap_session=None):
    """Everything up to a session that is ready to be seeded: the cell's
    files, the devices, the compile cache, the application's own run().
    ``wrap_session`` (tests only) may replace the session, e.g. to break
    the timed path underneath and see ``correct`` come out false."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config_entry = find_cell(bench, args.workload)
    config = load_json(ROOT, config_entry["file"])
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    chips = int(cell["chips"])
    rehearsal = bool(args.rehearsal)
    if rehearsal:
        # the CPU walk-through: must be set before JAX starts
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(chips, 1)}").strip()

    phases = Phases(t_start)
    import jax

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) < chips):
        print(f"benchmark: cell {cell['name']} needs {chips} TPU chip(s); "
              f"found {len(devices)} x {devices[0].platform!r}. Nothing ran.",
              file=sys.stderr)
        return None, NO_CHIP_RC
    devices = devices[:chips]
    peaks = load_json(HERE, "peaks.json")
    device = device_facts(devices)
    if rehearsal:
        peak = next(iter(peaks.values()))
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = "off (rehearsal)"
    else:
        if device["kind"] not in peaks:
            raise SystemExit(f"device kind {device['kind']!r} is not in "
                             f"peaks.json ({sorted(peaks)}): no default")
        peak = peaks[device["kind"]]
        from stencil_tpu.utils.jax_cache import configure_compile_cache

        cache_dir = configure_compile_cache()
        # every program is cached, however short its compile: a second run
        # of a cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = CompileCounter(jax)
    say(f"cell={cell['name']} config={config_entry['name']} "
        f"traffic={cell['traffic']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} rehearsal={rehearsal}")
    say(f"device platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    adapter = load_module("apps", config["app"])
    phases.end("import")

    # the application's own run(): realize, init, compile or cache load
    session = adapter.open(config, mix, devices, rehearsal, say)
    for key, val in sorted(session.facts.get("chosen", {}).items()):
        say(f"application chose {key} = {val}")
    say("facts: " + json.dumps({k: v for k, v in session.facts.items()
                                  if k != "chosen"}, default=str))
    phases.end("app_run")
    if wrap_session is not None:
        session = wrap_session(session)
    return types.SimpleNamespace(
        bench=bench, cell=cell, config=config, mix=mix, chips=chips,
        rehearsal=rehearsal, devices=devices, device=device, peak=peak,
        counter=counter, phases=phases, session=session), 0


def run_cell(args, t_start: float, wrap_session=None):
    """(result line as a dict, exit code). No result without a chip."""
    import jax

    opened, rc = open_session(args, t_start, wrap_session)
    if rc:
        return None, rc
    bench, cell, config, mix = (opened.bench, opened.cell, opened.config,
                                opened.mix)
    chips, rehearsal, devices = opened.chips, opened.rehearsal, opened.devices
    device, peak, counter = opened.device, opened.peak, opened.counter
    phases, session = opened.phases, opened.session

    session.seed(args.seed)
    phases.end("seed")

    jax.block_until_ready(session.dispatch())       # the window's own call
    sample = session.sample()
    phases.end("first_chunk_check")

    for _ in range(int(mix.get("warmup_dispatches", 2))):
        jax.block_until_ready(session.dispatch())
    session.finite()                                # warms the end check
    phases.end("warmup")
    say(f"compile cache before the window: hits={counter.hits} "
        f"misses={counter.misses} compilations={counter.compiles}")

    trace_dir = None
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the bench.* spans are enough
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    annotate = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t_start
    compiles_before = counter.compiles
    try:
        window = run_window(session, seconds, annotate)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    window["setup_s"] = setup_s
    window["compiles"] = counter.compiles - compiles_before
    window["chips"] = chips
    say(f"window: {window['seconds']:.4f} s, {window['dispatches']} "
        f"dispatches of {window['iters_per_dispatch']} iteration(s) "
        f"(the sample count of every percentile), {window['failed']} failed")
    stalled = stalls(window)
    say("stalls: " + json.dumps(stalled))
    memory = memory_peak(devices)

    # correct: the first chunk against the float64 reference, and the end
    t_ref = time.perf_counter()
    checks = list(session.compare(sample))
    finite = bool(session.finite())
    checks.append(("nonfinite_after_window", 0 if finite else 1, 0))
    checks.append(("compilations_in_window", window["compiles"], 0))
    correct = True
    compared = []
    for name, value, limit in checks:
        ok = bool(value <= limit) and value == value
        correct = correct and ok
        compared.append(f"check {name}: value={value!r} limit={limit!r} "
                        f"{'ok' if ok else 'NOT OK'}")
        say(compared[-1])
    say(f"reference and checks took {time.perf_counter() - t_ref:.3f} s "
        f"(after the window; not in setup_s)")
    failed = window["failed"] + (0 if finite else window["dispatches"])

    metrics = {}
    result = {"correct": correct, "attempted": window["dispatches"],
              "failed": min(failed, window["dispatches"]), "metrics": metrics,
              "device": dict(device, memory_peak_bytes=memory),
              "stalls": stalled}
    ctx = {"window": window, "facts": session.facts, "peak": peak,
           "phases": phases.seconds, "say": say}
    if not args.trace:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                value = load_module("end_to_end", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        try:
            from benchmark import trace_reduce

            trace = trace_reduce.load(find_trace(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        kernels = {role: {n: load_module("kernels", n) for n in names}
                   for role, names in config.get("kernels", {}).items()}
        trace_reduce.classify(trace, kernels, session.builds)
        ctx.update(trace=trace, kernels=kernels, builds=session.builds)
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                value = load_module("layer_metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = [trace_reduce.busy_ns(c) / 1e9 for c in trace["chips"]]
        if busy:
            result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = window["seconds"]
        result["breakdown"] = trace_reduce.breakdown(trace)
    say("set-up by phase: " + json.dumps(phases.seconds))
    # each number compared beside its limit: last in the line, last on stderr
    result["checks"] = {name: {"value": _plain(value), "limit": _plain(limit)}
                        for name, value, limit in checks}
    print("\n".join(compared), file=sys.stderr, flush=True)

    if rehearsal:
        say("rehearsal line (not a result): " + json.dumps(result))
        say("rehearsal complete: not a chip result")
        return result, REHEARSAL_RC
    return result, 0
