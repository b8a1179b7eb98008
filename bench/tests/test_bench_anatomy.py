"""The readers of the engine's own record (``engine.iter_ms_p95``,
``stream.emit_ms_per_iter``, ``setup.program_load_s``) on a scripted
``ServingMetrics`` and compile record, their silence on a program that
keeps no such record, and the engine's phase scopes in a profile started
the way the benchmark starts it."""
import sys
import types

import pytest

import e2e
import run
import smoke
import xplane
from repro.obs import compiles
from repro.serving.metrics import Iteration, ServingMetrics


def _ctx(metrics, open_=10.0, close=20.0):
    r = types.SimpleNamespace(metrics=metrics, open=open_, close=close)
    return types.SimpleNamespace(run=r, pct=e2e.pct, notes={})


def _scripted():
    """Iterations committing at 9.0 (before the window), then every 40 ms
    from 10.0 with one 100 ms stall at the fifth; prompt chunks ride every
    third; each hands 2 ms of tokens to the streams; one past the close."""
    m = ServingMetrics()
    ends = [9.0] + [10.0 + 0.04 * i for i in range(5)]
    ends += [ends[-1] + 0.1] + [ends[-1] + 0.1 + 0.04 * i
                                for i in range(1, 10)] + [20.5]
    for i, t in enumerate(ends):
        m.open_iteration(Iteration(
            t_commit=t, emit_s=0.002,
            rollback="cancellation" if i == 6 else None))
        m.on_mixed_step(8, 64 if i % 3 == 0 else 0, 0.5)
        m.on_iteration_timing(0.03, 0.01)
    return m


def test_iteration_gap_p95_over_the_window():
    reader = run.load_module("metrics", "engine.iter_ms_p95")
    ctx = _ctx(_scripted())
    # 15 gaps end inside [10, 20): the first (9.0 -> 10.0) is 1000 ms
    gaps = [1000.0] + [40.0] * 4 + [100.0] + [40.0] * 9
    assert reader.read(ctx) == pytest.approx(e2e.pct(gaps, 0.95))
    note = ctx.notes["engine.iter_ms"]
    assert note["rollbacks"] == 1 and note["compiles"] == 0
    assert note["prefill_p50_p95_n"][2] + note["decode_p50_p95_n"][2] == 15


def test_emit_ms_per_window_iteration():
    reader = run.load_module("metrics", "stream.emit_ms_per_iter")
    assert reader.read(_ctx(_scripted())) == pytest.approx(2.0)


def test_program_load_seconds_from_engine_to_window(monkeypatch):
    reader = run.load_module("metrics", "setup.program_load_s")
    rec = compiles.CompileRecord()
    rec.engine_t = 2.0
    for name, t, s, cache in (("jit(state)", 1.0, 5.0, None),
                              ("jit(paged_sample_step)", 3.0, 2.5, "hit"),
                              ("jit(feed_fixup)", 4.0, 0.5, "hit"),
                              ("jit(paged_sample_step)", 9.5, 8.0, "miss"),
                              ("jit(paged_sample_step)", 12.0, 7.0, "miss")):
        rec.events.append(compiles.Compile(name, t, s, cache))
    monkeypatch.setattr(compiles, "RECORD", rec)
    ctx = _ctx(ServingMetrics())
    assert reader.read(ctx) == pytest.approx(2.5 + 0.5 + 8.0)
    assert ctx.notes["setup.programs"] == {
        "count": 3, "cache_hits": 2, "in_window": ["jit(paged_sample_step)"]}


def test_the_readers_are_silent_on_a_program_without_the_record(
        monkeypatch):
    """A program that keeps no iteration or compile record (a version
    older than the records) reads as no value, not an error."""
    bare = types.SimpleNamespace(steps=[], timings=[])
    for name in ("engine.iter_ms_p95", "stream.emit_ms_per_iter"):
        assert run.load_module("metrics", name).read(_ctx(bare)) is None
    monkeypatch.setitem(sys.modules, "repro.obs.compiles", None)
    reader = run.load_module("metrics", "setup.program_load_s")
    assert reader.read(_ctx(bare)) is None


def test_a_benchmark_profile_holds_the_engine_phases(tmp_path):
    """A CPU profile started as the benchmark starts it
    (``xplane.start``, not ``obs.profiling.start``) around a tiny served
    run: the trace reduction finds the engine's phase scopes and its
    dispatch scope on the host."""
    import client
    sp = smoke.spec()
    engine, _, _ = run.build(sp, 2 ** 33 + 5, use_pallas=False,
                             log=lambda s: None)
    assert engine.lookahead
    import jax
    import traffic
    arrivals = traffic.schedule(sp["mix"], 4.0, 1.0, 2 ** 33 + 5,
                                sp["conf"]["model"]["vocab_size"])
    tdir = str(tmp_path)
    marks = {}

    def enter():
        marks["ann"] = jax.profiler.TraceAnnotation(xplane.WINDOW)
        marks["ann"].__enter__()

    def leave():
        marks["ann"].__exit__(None, None, None)

    xplane.start(tdir)
    try:
        client.serve(engine, arrivals, sp["mix"], 1.0,
                     hooks=[(-0.4, enter), (0.9, leave)])
    finally:
        xplane.stop()
    tr = xplane.load(tdir)
    lo, hi = tr["window_ns"]
    # the CPU has no device plane: one op across the window stands in
    tr["device"] = {"/device:TPU:0": [("fusion", lo, hi)]}
    counts = xplane.reduce(tr)["host_counts"]
    for phase in ("engine.iteration", "engine.plan", "engine.lookahead",
                  "engine.enqueue", "engine.sync", "engine.commit",
                  "engine.emit", "engine.intake", "paged_sample_step"):
        assert counts.get(phase, 0) > 0, (phase, sorted(counts))
