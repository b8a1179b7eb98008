"""The engine's per-iteration record (``ServingMetrics.iterations``), the
process-wide compile and GC record (``repro.obs.compiles``), profiler
scopes that emit whoever started the profiler, and the ``Tracer``'s clock
offset against the profiler's clock."""
import asyncio
import glob
import os
import threading
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import CAT_ITER, Tracer, compiles, profiling
from repro.serving import ElasticEngine, Request, SamplingParams
from repro.serving.metrics import ServingMetrics
from repro.serving.session import StreamSession

BLOCK = 8


@pytest.fixture(scope="module")
def smoke_state():
    from repro.data import make_source
    from repro.launch.train import build_flexrank_state
    from repro.models import common as cm
    from repro.models import transformer as tfm
    cfg = get_config("gpt2-small", smoke=True)
    source = make_source(cfg.vocab_size, 64, 4, seed=0)
    dense = cm.instantiate(tfm.model_spec(cfg), jax.random.PRNGKey(0))
    params_fact, table, infos = build_flexrank_state(cfg, dense, source)
    return cfg, params_fact, table, infos


def _mk(state, **kw):
    cfg, params_fact, table, infos = state
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("prefill_chunk", 8)
    return ElasticEngine(cfg, params_fact, table, infos, **kw)


def _requests(cfg, lengths, max_new=4, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=max_new, budget=1.0,
                    sampling=(SamplingParams(temperature=0.8, top_k=8,
                                             seed=seed) if i % 2 else None))
            for i, n in enumerate(lengths)]


def _serve_session(eng, reqs):
    """Serve ``reqs`` through a StreamSession on a worker thread; returns
    the streamed tokens per request."""

    async def main():
        session = StreamSession(stream_buffer=4)
        session.loop = asyncio.get_running_loop()
        worker = threading.Thread(target=eng.serve_session, args=(session,))
        worker.start()

        async def client(rq):
            h = session.submit(rq)
            return [t async for t in h.tokens()]

        outs = await asyncio.gather(*[client(r) for r in reqs])
        session.close()
        await session.join()
        worker.join()
        return outs

    return asyncio.run(main())


# ------------------------------------------------ the iteration record

@pytest.mark.parametrize("lookahead", [False, True])
def test_iteration_record_is_ordered_and_keeps_the_timing_split(
        smoke_state, lookahead):
    eng = _mk(smoke_state, lookahead=lookahead)
    reqs = _requests(eng.cfg, [5, 19, 11, 3])
    streamed = _serve_session(eng, reqs)
    assert [len(s) for s in streamed] == [r.max_new_tokens for r in reqs]
    m = eng.last_metrics
    recs = m.iterations
    assert recs and len(recs) == m.summary()["mixed_iterations"]
    assert m.timing_log == [(r.dispatch_s, r.host_s, r.overlap_s)
                            for r in recs]
    assert m.iteration_log == [(r.decode_rows, r.prefill_tokens)
                               for r in recs]
    for r in recs:
        assert r.t_plan <= r.t_enqueue <= r.t_planned
        assert r.t_sync0 <= r.t_sync1 <= r.t_commit
        assert r.sampled_rows >= 1 and r.width >= r.decode_rows
        assert r.rollback is None and r.emit_s >= 0.0 and r.gc_s >= 0.0
        if lookahead:
            # the planning turn and the commit are the iteration's host
            # phases; the next iteration's planning runs between them
            assert r.t_planned <= r.t_sync0
            host = (r.t_planned - r.t_plan) + (r.t_commit - r.t_sync1)
            assert r.overlap_s == pytest.approx(
                max(0.0, r.t_sync0 - r.t_enqueue), abs=1e-9)
        else:
            assert r.t_enqueue == r.t_planned == r.t_sync0
            host = r.t_commit - r.t_plan - r.dispatch_s
            assert r.overlap_s == 0.0
        assert r.host_s == pytest.approx(host, abs=1e-9)
    ends = [r.t_commit for r in recs]
    assert ends == sorted(ends)
    if lookahead:
        # iteration i+1 was planned and enqueued before i committed
        assert any(b.t_planned <= a.t_sync0 for a, b in zip(recs, recs[1:]))
    # every token is a decode row or a request's first token, and each
    # was handed to its stream inside some commit
    assert sum(r.decode_rows for r in recs) + len(reqs) == sum(
        map(len, streamed))
    assert sum(r.emit_s for r in recs) > 0.0


def test_a_forced_rollback_is_marked_on_its_iteration(smoke_state):
    eng = _mk(smoke_state, lookahead=True)
    eng.lookahead_fault = lambda i: i == 2
    eng.generate(_requests(eng.cfg, [6, 9]), mode="continuous")
    marked = [r.rollback for r in eng.last_metrics.iterations
              if r.rollback is not None]
    assert marked == ["fault_injection"]
    assert eng.last_metrics.rollback_reasons == {"fault_injection": 1}


@pytest.mark.parametrize("lookahead,rollback", [
    (False, False), (True, False), (True, True)])
def test_a_new_token_bucket_compiles_in_its_iteration(smoke_state,
                                                      lookahead, rollback):
    """Short prompts warm the narrowest token width; a later long prompt
    needs a wider bucket, and the step program compiled for it is charged
    to the first iteration at that width, by the program's name. With
    every speculative plan rolled back, a program compiled for a plan
    that was thrown away is charged to the iteration it held up."""
    eng = _mk(smoke_state, lookahead=lookahead, max_batch=2,
              prefill_chunk=24)
    eng.generate(_requests(eng.cfg, [3, 4], max_new=3), mode="continuous")
    seen = {r.width for r in eng.last_metrics.iterations}
    if rollback:
        eng.lookahead_fault = lambda i: True
    rec = compiles.RECORD
    n0 = len(rec.events)
    eng.generate(_requests(eng.cfg, [30, 4], max_new=3), mode="continuous")
    step = "jit(paged_sample_step)"
    recs = eng.last_metrics.iterations
    new = [r for r in recs if r.width not in seen]
    assert new, "the long prompt never needed a wider bucket"
    assert step in new[0].compile_funs
    assert new[0].compiles >= 1 and new[0].compile_s > 0.0
    # nothing lost, nothing counted twice: every step compile of the run
    # is charged to exactly one iteration
    ran = [c for c in rec.events[n0:] if c.fun_name == step]
    assert sum(r.compile_funs.count(step) for r in recs) == len(ran)


# --------------------------------------------------- the compile record

def test_the_compile_record_installs_once_and_names_cache_outcomes():
    assert compiles.install() is compiles.install() is compiles.RECORD
    mark = compiles.RECORD.mark()

    def installed_twice(x):
        return x - 2

    jax.jit(installed_twice)(jnp.arange(3.0)).block_until_ready()
    events, _ = compiles.RECORD.since(mark)
    assert [c.fun_name for c in events].count("jit(installed_twice)") == 1
    rec = compiles.CompileRecord(clock=iter([10.0, 11.0, 12.0]).__next__)
    tr = Tracer()
    rec.subscribe(tr)
    rec._on_event("/jax/compilation_cache/cache_hits")
    rec._on_duration("/jax/core/compile/backend_compile_duration", 2.5,
                     fun_name="jit(paged_sample_step)")
    rec._on_event("/jax/compilation_cache/cache_misses")
    rec._on_duration("/jax/core/compile/backend_compile_duration", 8.0,
                     fun_name="jit(feed_fixup)")
    rec._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                     fun_name="jit(f)")
    rec._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    assert [(c.fun_name, c.t_end, c.seconds, c.cache) for c in rec.events] \
        == [("jit(paged_sample_step)", 10.0, 2.5, "hit"),
            ("jit(feed_fixup)", 11.0, 8.0, "miss"),
            ("jit(f)", 12.0, 0.5, None)]
    st = rec.statusz()
    assert (st["count"], st["seconds"], st["cache_hits"],
            st["cache_misses"]) == (3, 11.0, 1, 1)
    instants = [e for e in tr.chrome_events() if e["name"] == "compile"]
    assert [e["args"]["fun"] for e in instants] == [
        "jit(paged_sample_step)", "jit(feed_fixup)", "jit(f)"]
    assert instants[0]["cat"] == "compile"


def test_the_compile_record_sees_real_compiles_and_gc():
    rec = compiles.install()
    mark = rec.mark()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    import gc
    gc.collect()
    events, gc_s = rec.since(mark)
    assert any(c.fun_name == "jit(<lambda>)" and c.seconds > 0
               for c in events)
    assert gc_s > 0.0


def test_statusz_shows_the_compile_record(smoke_state):
    eng = _mk(smoke_state)
    eng.generate(_requests(eng.cfg, [5]), mode="continuous")
    st = eng.statusz()["compiles"]
    assert st["count"] >= 1 and st["seconds"] > 0
    assert compiles.RECORD.engine_t is not None


# ------------------------------------------------------ profiler scopes

def _profile(fn, tmp_path):
    """Run ``fn`` under a profiler session started the way the benchmark
    starts it (``jax.profiler.start_trace``, no Python tracer)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    return jax.profiler.ProfileData.from_file(paths[0])


def test_scopes_emit_under_any_profiler_session(tmp_path):
    assert profiling.annotate("engine.plan") is profiling._NULL_CTX

    def work():
        with profiling.annotate("engine.plan"):
            with profiling.step("engine.iteration", 7):
                time.sleep(0.001)

    pd = _profile(work, tmp_path)
    names = {e.name for p in pd.planes for line in p.lines
             for e in line.events}
    assert {"engine.plan", "engine.iteration"} <= names


def test_disabled_scopes_allocate_nothing():
    profiling.annotate("engine.plan")          # resolve the lookup once
    tracemalloc.start()
    for _ in range(10_000):
        with profiling.annotate("engine.plan"):
            pass
        with profiling.step("engine.iteration", 3):
            pass
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 1024, f"disabled scopes allocated {peak} bytes"


# ----------------------------------------------------- the clock offset

def test_tracer_export_lays_over_the_profiler_clock(tmp_path):
    """One span emitted both ways — a ``Tracer`` span and a profiler scope
    around the same work — lands at the same wall time within 1 ms."""
    tr = Tracer()
    time.sleep(0.05)                     # the span starts well after t0

    def work():
        with jax.profiler.TraceAnnotation("probe"):
            t0 = tr.now()
            time.sleep(0.02)
            t1 = tr.now()
        tr.complete("probe", CAT_ITER, t0, t1)

    pd = _profile(work, tmp_path)
    env = pd.find_plane_with_name("Task Environment")
    start_ns = dict(env.stats)["profile_start_time"]
    (prof,) = [e for p in pd.planes for line in p.lines for e in line.events
               if e.name == "probe"]
    chrome = tr.to_chrome()
    (ev,) = [e for e in chrome["traceEvents"] if e["name"] == "probe"]
    mine_ns = chrome["otherData"]["t0_unix_ns"] + ev["ts"] * 1e3
    assert abs((start_ns + prof.start_ns) - mine_ns) < 1e6
    assert abs(prof.duration_ns - ev["dur"] * 1e3) < 1e6


def test_metrics_record_without_an_engine():
    """Callers of the callbacks alone (the speculative rounds, scripted
    tests) still get one record per iteration, unstamped."""
    m = ServingMetrics(clock=iter([0.0, 1.0]).__next__)
    m.on_submit(0)
    m.on_mixed_step(2, 5, 0.25)
    m.on_iteration_timing(0.1, 0.2)
    m.on_iteration_timing(0.3, 0.4, overlap_s=0.1)
    (a, b) = m.iterations
    assert (a.decode_rows, a.prefill_tokens, a.occupancy) == (2, 5, 0.25)
    assert a.t_commit is None and b.occupancy is None
    assert m.timing_log == [(0.1, 0.2, 0.0), (0.3, 0.4, 0.1)]
    assert m.summary()["cache_occupancy_mean"] == 0.25
