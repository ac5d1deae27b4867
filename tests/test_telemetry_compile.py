"""Compile stages as spans (obs/telemetry.CompileWatcher): every outermost
trace, lowering and backend compile is a child of the span open when it
happened, by module name; the rest is folded; nothing fires on a compiled
call; the kernel bodies' trace is a span of its own; the apps' chunk spans
carry the fields of a span."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from stencil_tpu.obs import scopes, telemetry

STAGES = ("compile.trace", "compile.lower", "compile.backend")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def rec():
    """A fresh process-default recorder: the watcher writes to that one."""
    yield telemetry.configure(heartbeat_thread=False)
    telemetry.configure(heartbeat_thread=False)


def _loop(module, scale=2.0):
    def fn(x):
        return jnp.roll(x, 1) * scale + jnp.sin(x)

    return scopes.jit_loop(module, fn)


def _new(rec, names=STAGES + ("compile.other", "kernel.trace")):
    return [r for r in rec.records(kind="span") if r["name"] in names]


@pytest.mark.parametrize("module", scopes.MODULES)
def test_a_named_loop_leaves_its_three_stages_under_the_open_span(rec, module):
    loop = _loop(module)
    x = jnp.ones(8) + 0     # the argument's own programs compile out here
    with rec.span("jacobi.warmup", phase="compile"):
        loop(x).block_until_ready()
    parent = rec.records(kind="span", name="jacobi.warmup")[-1]
    stages = [r for r in _new(rec, STAGES) if r.get("module") == module]
    assert [r["name"] for r in stages] == list(STAGES)
    for r in stages:
        assert telemetry.validate_record(r) == []
        assert r["parent"] == "jacobi.warmup" and r["fun"] == module
        assert r["phase"] == "compile"
        assert parent["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= parent["t1_ns"]
    assert stages[-1]["cache"] in ("hit", "miss", "off")
    assert "cache" not in stages[0]
    # the compiled program's call path fires nothing
    n = len(rec.records())
    with rec.span("jacobi.steps", phase="step"):
        loop(x).block_until_ready()
    assert [r["name"] for r in rec.records()[n:]] == ["jacobi.steps"]


def test_stages_outside_any_span_have_no_parent_and_unnamed_ones_fold(rec):
    telemetry.watch_compiles()
    loop = _loop(scopes.EXCHANGE_LOOP, scale=3.0)
    loop(jnp.ones(8)).block_until_ready()
    telemetry.flush_compile_stages()
    mine = _new(rec, STAGES)
    assert [r["name"] for r in mine] == list(STAGES)
    assert all("parent" not in r for r in mine)
    jax.jit(lambda v: v * 5.0 - 1.0)(jnp.ones(8)).block_until_ready()
    telemetry.flush_compile_stages()
    folds = _new(rec, ("compile.other",))
    assert {r["stage"] for r in folds} == {"trace", "lower", "backend"}
    for r in folds:
        assert telemetry.validate_record(r) == []
        assert "parent" not in r and r["count"] >= 1
        assert r["seconds"] == pytest.approx(sum(r["funs"].values()))
    assert any("<lambda>" in r["funs"] for r in folds)
    # a second flush writes nothing twice
    n = len(rec.records())
    telemetry.flush_compile_stages()
    assert len(rec.records()) == n


def test_a_fold_is_written_when_its_parent_closes(rec):
    with rec.span("exchange.warmup", phase="compile"):
        jax.jit(lambda v: v * 7.0 - 2.0)(jnp.ones(8)).block_until_ready()
        assert not _new(rec, ("compile.other",))
    names = [r["name"] for r in rec.records(kind="span")]
    assert names[-1] == "exchange.warmup"
    folds = _new(rec, ("compile.other",))
    assert folds and all(r["parent"] == "exchange.warmup" for r in folds)
    backend = [r for r in folds if r["stage"] == "backend"][0]
    assert backend["hits"] + backend["misses"] <= backend["count"]
    assert isinstance(backend["missed"], list)


def test_the_persistent_cache_reads_miss_then_hit(rec, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        x = jnp.ones(24) + 0
        for _ in range(2):      # the same program, built twice
            with rec.span("iso3dfd.warmup", phase="compile"):
                _loop(scopes.ISO3DFD_LOOP, scale=11.0)(x).block_until_ready()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    backend = [r for r in _new(rec, ("compile.backend",))
               if r["module"] == scopes.ISO3DFD_LOOP]
    assert [r["cache"] for r in backend] == ["miss", "hit"]
    assert "retrieval_s" not in backend[0]
    assert 0 <= backend[1]["retrieval_s"] <= backend[1]["seconds"]
    watcher = telemetry.watch_compiles()
    assert watcher.cache_hits >= 1 and watcher.cache_misses >= 1


def test_thousands_of_inner_stages_leave_a_handful_and_evict_nothing(rec):
    rec.counter("kernel.multistep.staging", value=1, module="m", k=10,
                rows=1, strips=1, halo_rows=0, rows_computed=2, rows_kept=1,
                vmem_bytes=0)

    def fn(x):
        for i in range(5000):
            x = jax.jit(lambda v, i=i: v + i)(x)
        return x

    loop = scopes.jit_loop(scopes.ASTAROTH_ITER, fn)
    x = jnp.ones(4) + 0
    telemetry.flush_compile_stages()    # the argument's own programs
    before = len(rec.records())
    with rec.span("astaroth.warmup", phase="compile"):
        loop.trace(x)
    added = rec.records()[before:]
    assert len(added) <= 4, [r["name"] for r in added]
    assert added[-1]["name"] == "astaroth.warmup"
    trace = [r for r in added if r["name"] == "compile.trace"]
    assert len(trace) == 1 and trace[0]["module"] == scopes.ASTAROTH_ITER
    assert rec.records(kind="counter", name="kernel.multistep.staging")
    assert len(rec.records()) < telemetry.KEEP_RECORDS


def test_installing_twice_installs_once(rec):
    first = telemetry.watch_compiles()
    assert first is not None and telemetry.watch_compiles() is first
    x = jnp.ones(8) + 0
    n = first.backend_compiles
    with rec.span("jacobi.warmup"), rec.span("jacobi.exchange_warmup"):
        _loop(scopes.JACOBI_STEP, scale=13.0)(x)
    assert len(_new(rec, ("compile.backend",))) == 1
    assert first.backend_compiles == n + 1


def test_importing_and_get_install_nothing_and_start_no_backend():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import stencil_tpu\n"
        "from stencil_tpu.obs import telemetry, scopes\n"
        "rec = telemetry.get()\n"
        "assert telemetry._watcher is None\n"
        "jax = sys.modules.get('jax')\n"
        "if jax is not None:\n"
        "    from jax._src import monitoring, xla_bridge\n"
        "    assert not monitoring.get_event_duration_listeners()\n"
        "    assert not monitoring.get_event_listeners()\n"
        "    assert not monitoring.get_scalar_listeners()\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "import jax\n"
        "with rec.span('jacobi.realize'):\n"
        "    pass\n"
        "from jax._src import monitoring, xla_bridge\n"
        "assert telemetry._watcher is not None\n"
        "assert len(monitoring.get_event_duration_listeners()) == 1\n"
        "print('ok')\n" % ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_listener_degrades_and_never_raises_into_a_compile(rec):
    watcher = telemetry.CompileWatcher()
    calls = {}

    class Monitoring:           # a jax without the scalar start event
        @staticmethod
        def register_event_listener(fn):
            calls["event"] = fn

        @staticmethod
        def register_event_duration_secs_listener(fn):
            calls["duration"] = fn

    watcher.install(Monitoring)
    trace = "/jax/core/compile/jaxpr_trace_duration"
    with rec.span("jacobi.warmup"):
        calls["duration"](trace, 0.25, fun_name=scopes.JACOBI_LOOP)
        calls["duration"](trace, 0.5)                  # no fun_name: folded
        calls["duration"](trace, "not a number", fun_name=object())
        calls["duration"](None, None)
        calls["event"](None)
        watcher.flush("jacobi.warmup")
    named = _new(rec, ("compile.trace",))
    assert len(named) == 1 and named[0]["seconds"] == 0.25
    assert named[0]["t1_ns"] - named[0]["t0_ns"] == 250_000_000
    fold = _new(rec, ("compile.other",))
    assert len(fold) == 1 and fold[0]["funs"] == {"(unnamed)": 0.5}


def test_kernel_trace_once_per_traced_invocation_never_on_a_compiled_call(rec):
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    call = scopes.kernel_call(
        "jacobi_sweep", body, interpret=True,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))

    def fn(x):
        return call(call(x)) + 1.0

    loop = scopes.jit_loop(scopes.JACOBI_LOOP, fn)
    x = jnp.ones((8, 128), jnp.float32) + 0
    with rec.span("jacobi.warmup", phase="compile"):
        out = loop(x)
    assert float(out[0, 0]) == 5.0
    kernels = _new(rec, ("kernel.trace",))
    assert len(kernels) == 2
    trace = [r for r in _new(rec, ("compile.trace",))
             if r["module"] == scopes.JACOBI_LOOP][0]
    for r in kernels:
        assert telemetry.validate_record(r) == []
        assert r["kernel"] == "jacobi_sweep" and r["parent"] == "jacobi.warmup"
        assert trace["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= trace["t1_ns"]
    assert sum(r["seconds"] for r in kernels) <= trace["seconds"]
    n = len(rec.records())
    loop(x).block_until_ready()
    assert len(rec.records()) == n


def test_a_top_level_span_carries_device_memory_where_the_backend_has_it(
        rec, monkeypatch):
    class Device:
        def __init__(self, in_use, peak):
            self._stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

        def memory_stats(self):
            return self._stats

    with rec.span("jacobi.realize"):      # the CPU keeps no statistics
        pass
    assert "mem_peak_bytes" not in rec.records(name="jacobi.realize")[-1]
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Device(10, 40), Device(30, 35)])
    with rec.span("jacobi.init"):
        with rec.span("jacobi.warmup"):
            pass
    top = rec.records(name="jacobi.init")[-1]
    assert top["mem_bytes_in_use"] == 30 and top["mem_peak_bytes"] == 40
    assert "mem_peak_bytes" not in rec.records(name="jacobi.warmup")[-1]
    with rec.span("health.check"):      # top-level, not a phase of run()
        pass
    assert "mem_peak_bytes" not in rec.records(name="health.check")[-1]


def test_child_span_has_the_fields_of_a_span(rec):
    with rec.span("exchange.steps", phase="exchange"):
        r = rec.child_span("exchange.iter", 1_000, 0.5, wall_s=2.0,
                           phase="exchange", iters=4)
    assert r["t0_ns"] == 1_000 and r["t1_ns"] == 1_000 + 2_000_000_000
    assert r["parent"] == "exchange.steps" and r["seconds"] == 0.5
    assert telemetry.validate_record(r) == []
    alone = rec.child_span("exchange.iter", 5, 1.0)
    assert "parent" not in alone and alone["t1_ns"] == 5 + 1_000_000_000


def test_an_apps_chunk_spans_lie_inside_its_steps_span(rec):
    from stencil_tpu.apps._bench_common import time_exchange
    from stencil_tpu.geometry import Dim3, Radius

    time_exchange(Dim3(16, 16, 16), Radius.constant(1), iters=5,
                  devices=jax.devices()[:1], quantities=1, chunk=2)
    steps = rec.records(kind="span", name="exchange.steps")[-1]
    chunks = rec.records(kind="span", name="exchange.iter")
    assert len(chunks) == 3
    for r in chunks:
        assert r["parent"] == "exchange.steps"
        assert steps["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= steps["t1_ns"]
        assert r["t1_ns"] - r["t0_ns"] == pytest.approx(
            r["seconds"] * r["iters"] * 1e9, rel=1e-6, abs=2)
    # the tail chunk's loop was warmed in exchange.warmup, not in the steps
    under = {r["parent"] for r in _new(rec, STAGES)}
    assert under == {"exchange.warmup"}
