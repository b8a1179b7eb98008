"""The benchmark's one command: one cell, one seed, one measured window.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``), a
traffic mix (``bench/mixes/``) and has a file of its own
(``bench/cells/<cell>.json``: the offered rate, the check's sample and
limits). Per-layer metrics are readers in ``bench/metrics/<metric>.py``
and kernel work counts in ``bench/work/<kernel>.py``: a later cell or
metric adds files and entries.

Set-up (timed as ``setup_s``, from process start until the window opens):
the FlexRank state is made on the device from the seed (``state.py``), the
engine deploys the served row, every program shape the engine can
dispatch is warmed (``warm.py``), and the lead-in traffic brings the batch
to steady occupancy. Then the window: open-loop clients on
``StreamSession`` with the engine's ``serve_session`` on a worker thread
(``client.py``).
``--trace 1`` also takes a profiler trace of a few seconds in mid-window
and prints the per-layer metrics instead of the end-to-end ones.

After the window: device memory is read, the program's state freed, and
the plain reference (``reference.py``) checks the served greedy tokens.
The last line of standard output is one JSON object; without an
accelerator, or with fewer chips than the cell asks for, the command exits
with code 3 and prints none.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse                                           # noqa: E402
import gc                                                 # noqa: E402
import importlib.util                                     # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import shutil                                             # noqa: E402
import sys                                                # noqa: E402
import tempfile                                           # noqa: E402
import threading                                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_CHIP = 3
TRACE_S = 4.0


class NoChip(RuntimeError):
    pass


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_spec(root: str, workload: str) -> dict:
    """Everything of one cell, found by the names in ``BENCHMARK.json``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "conf": _json(os.path.join(root, config["file"])),
        "mix": _json(os.path.join(HERE, "mixes", cell["traffic"] + ".json")),
        "cellfile": _json(os.path.join(HERE, "cells", workload + ".json")),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache loads included) and
    the persistent cache's hits and misses."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def configure_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    every program in it (the small feed-fixup ones too), and no size
    limit: a limit below one run's programs, such as one set by
    ``JAX_COMPILATION_CACHE_MAX_SIZE``, evicts each entry before the next
    run asks for it, since runs compile in the same order. The path is
    the checkout's own even where ``JAX_COMPILATION_CACHE_DIR`` names
    another, so that no two checkouts share a cache."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform not in ("tpu", "gpu")
                         or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Context:
    """What the per-layer readers read: the clients' records, the engine's
    counters, the trace reduction and the work counts."""

    def __init__(self, run, conf, peak, red, trace_pc, its, ranks):
        import e2e
        import reference
        self.run, self.conf, self.model = run, conf, conf["model"]
        self.peak, self.red, self.trace_pc = peak, red, trace_pc
        self.its = its
        self.pct = e2e.pct
        self.window_records = e2e.window_records(run)
        self._work = {}
        self.per_position_flops = load_module("work", "model").linear_flops(
            reference.groups(conf), ranks)
        self.notes = {}
        self.trace_iterations = (
            sum(1 for t, *_ in run.metrics.steps
                if trace_pc[0] <= t < trace_pc[1]) if trace_pc else 0)

    def work(self, kernel: str):
        if kernel not in self._work:
            self._work[kernel] = load_module("work", kernel)
        return self._work[kernel]

    def items_between(self, lo, hi):
        import items
        return items.between(self.its, lo, hi)

    def op_seconds(self, prefix: str) -> float:
        import xplane as trace
        return trace.op_seconds(self.red, prefix)

    def roofline(self, kernel: str):
        """Percent of the kernel's roofline over the traced window, or
        None where the trace shows no such kernel."""
        import peaks
        if self.red is None:
            return None
        seconds = self.op_seconds(kernel)
        its = self.items_between(*self.trace_pc)
        if seconds <= 0.0 or not its:
            return None
        flops, nbytes = self.work(kernel).work(its, self.model)
        share, bound = peaks.roofline_share(flops, nbytes, seconds, self.peak)
        self.notes[kernel] = bound
        return share


def _sample(run, k: int, seed: int, greedy: bool):
    """The window requests the check compares, greedy or sampled: the
    longest, and ``k - 1`` more drawn from the seed."""
    import numpy as np
    pool = [r for r in run.records if r.arrival.phase == "window"
            and r.arrival.greedy == greedy and r.complete]
    if not pool:
        return []
    pool.sort(key=lambda r: (len(r.arrival.prompt) + len(r.tokens),
                             r.arrival.index))
    longest, rest = pool[-1], pool[:-1]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed), 0xC4E, int(greedy)]))
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check(spec, seed, run, program_row_params, log=print):
    """Compare the served tokens with the plain reference: each greedy
    token against the reference's first choice, each sampled token against
    its top k. Returns (numbers compared as {name: {value, limit}},
    correct)."""
    import numpy as np
    import reference
    conf, mix, cf = spec["conf"], spec["mix"], spec["cellfile"]["check"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    length = 1 << (longest - 1).bit_length()
    t = time.perf_counter()
    ref = reference.Reference(conf, seed, mix["budget"], length,
                              top_k=mix["sampling"]["top_k"])
    t_ref = time.perf_counter() - t
    # a kind of request the mix sends has to be compared
    kinds = [(name, greedy, fn) for name, greedy, fn, share in (
        ("greedy_gap", True, ref.gaps, mix["greedy_share"]),
        ("topk_gap", False, ref.topk_gaps, 1.0 - mix["greedy_share"]))
        if share > 0]
    widest, served = {}, {}
    for name, greedy, fn in kinds:
        sample = _sample(run, cf["requests"], seed, greedy)
        gaps = [float(fn(r.arrival.prompt, np.asarray(r.tokens, np.int32)).max())
                for r in sample]
        widest[name] = max(gaps) if gaps else None
        served[name] = sum(len(r.tokens) for r in sample)
    log(f"# check: reference built in {t_ref:.3f} s, {served} served tokens "
        f"compared in {time.perf_counter() - t - t_ref:.3f} s")
    numbers = {name: {"value": widest[name], "limit": cf[name + "_limit"],
                      "tokens": served[name]} for name in widest}
    numbers["row_params"] = {"value": program_row_params,
                             "limit": ref.deployed}
    ok = (all(n["value"] is not None and n["value"] <= n["limit"]
              for name, n in numbers.items() if name != "row_params")
          and program_row_params == ref.deployed)
    return numbers, ok


def build(spec: dict, seed: int, *, use_pallas=None, log=print):
    """Set-up before the traffic: the state from the seed, the engine, its
    served row deployed and every shape the engine can dispatch warmed.
    Returns (engine, row, deployed parameters of the row)."""
    import state
    import warm
    from repro.serving import ElasticEngine
    from repro.serving.kv_cache import PagedKVCache

    import jax
    conf, mix = spec["conf"], spec["mix"]
    ec = conf["engine"]
    # the precision the configuration states for every float32 matmul the
    # program traces (JAX's default on a TPU is one bfloat16 pass)
    jax.config.update("jax_default_matmul_precision",
                      conf["weights"]["matmul_precision"])
    t = time.perf_counter()
    st = state.make_state(conf, seed)
    engine = ElasticEngine(
        st.cfg, st.params, st.table, st.infos, max_batch=ec["max_batch"],
        max_len=ec["max_len"], block_size=ec["block_size"],
        num_blocks=ec["num_blocks"], prefill_chunk=ec["prefill_chunk"],
        lookahead=ec["lookahead"], device_sampling=ec["device_sampling"],
        prefix_cache=ec["prefix_cache"], use_pallas=use_pallas)
    row = engine.router.route(mix["budget"])
    t_state = time.perf_counter() - t
    t = time.perf_counter()
    params = engine._realize(row)
    t_deploy = time.perf_counter() - t
    t = time.perf_counter()
    runs = warm.warm(engine, params, PagedKVCache(
        st.cfg, max_batch=ec["max_batch"], max_len=ec["max_len"],
        block_size=ec["block_size"], num_blocks=ec["num_blocks"]))
    log(f"# set-up: state {t_state:.3f} s, deploy row {row} "
        f"({engine.router.deployed_params(row)} parameters) {t_deploy:.3f} s, "
        f"warm {runs} programs {time.perf_counter() - t:.3f} s")
    return engine, row, engine.router.deployed_params(row)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, use_pallas=None, fault=None,
             log=None):
    """One run of one cell. Returns the result object (the last line).
    ``fault`` (tests only) is called with the engine before the window."""
    import jax
    import client
    import e2e
    import items as items_mod
    import peaks
    import reference
    import traffic

    log = log or (lambda s: print(s, flush=True))
    cell, conf, mix, cf = (spec["cell"], spec["conf"], spec["mix"],
                           spec["cellfile"])
    device = device_info(cell["chips"], require_chip)
    clock = CompileClock()
    ec = conf["engine"]
    engine, row, row_params = build(spec, seed, use_pallas=use_pallas, log=log)
    log(f"# compiles in set-up so far: {clock.count} ({clock.seconds:.3f} s); "
        f"persistent cache hits {clock.hits}, misses {clock.misses}")
    if fault is not None:
        fault(engine)

    arrivals = traffic.schedule(mix, cf["rate_per_s"], seconds, seed,
                                conf["model"]["vocab_size"])
    marks = {}

    built = clock.count

    def opened():
        marks["open"] = (time.perf_counter(), clock.count, clock.seconds)
        # nothing should compile in the window; what does is named in the
        # log, with the shapes that missed the jit cache
        jax.config.update("jax_log_compiles", True)
        jax.config.update("jax_explain_cache_misses", True)

    def closed():
        marks["close"] = (time.perf_counter(), clock.count, clock.seconds)
        jax.config.update("jax_log_compiles", False)
        jax.config.update("jax_explain_cache_misses", False)

    hooks = []
    tdir = None
    if trace:
        import xplane as trace_mod
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        span = min(TRACE_S, seconds / 3)
        begin = max(0.0, seconds / 2 - span / 2)
        started = threading.Event()

        def start_profiler():
            trace_mod.start(tdir)
            started.set()

        def enter():
            started.wait(30.0)
            marks["trace_lo"] = time.perf_counter()
            # made here: made before the profiler runs, it would record
            # nothing
            marks["ann"] = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            marks["ann"].__enter__()

        def leave():
            marks["ann"].__exit__(None, None, None)
            marks["trace_hi"] = time.perf_counter()
            marks["stopper"] = threading.Thread(target=trace_mod.stop)
            marks["stopper"].start()

        def launch():
            marks["starter"] = threading.Thread(target=start_profiler)
            marks["starter"].start()

        # the profiler starts off the loop thread, ahead of the traced span
        hooks = [(begin - 2.0, launch), (begin, enter), (begin + span, leave)]

    run = client.serve(engine, arrivals, mix, seconds, on_open=opened,
                       on_close=closed, hooks=hooks)
    setup_s = run.open - T0
    for name in ("starter", "stopper"):
        if name in marks:
            marks[name].join(120.0)
    in_window = marks["close"][1] - marks["open"][1]
    attempted, failed = e2e.failures(run)
    log(f"# window: {attempted} requests due in {seconds:g} s, "
        f"{failed} not served whole, compiles in the lead-in "
        f"{marks['open'][1] - built}, in the window {in_window} "
        f"({marks['close'][2] - marks['open'][2]:.3f} s), "
        f"setup_s {setup_s:.3f}")
    device["memory_peak_bytes"] = memory_peak(cell["chips"])

    metrics, breakdown, notes = {}, None, {}
    if not trace:
        for m in spec["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else e2e.METRICS[m["name"]](run)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        import xplane as trace_mod
        red = trace_mod.reduce(trace_mod.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        table, deployed = reference.ladder(conf)
        ranks = {g[0]: int(r) for g, r in zip(
            reference.groups(conf),
            table[reference.route(deployed, mix["budget"])])}
        ctx = Context(run, conf, peaks.peaks(device["kind"]), red,
                      (marks["trace_lo"], marks["trace_hi"]),
                      items_mod.items(run.records, ec["prefill_chunk"]),
                      ranks)
        for m in spec["per_layer"]:
            reader = load_module("metrics", m["name"])
            if (reader.UNIT, reader.LAYER, reader.MOVES) != (
                    m["unit"], m["layer"], m["moves"]):
                raise ValueError(f"{m['name']}: reader and BENCHMARK.json "
                                 "disagree on unit, layer or moves")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        notes = ctx.notes
        log(f"# trace: window {red['window_s']:.3f} s, busy "
            f"{red['busy_s']:.3f} s, {ctx.trace_iterations} iterations; "
            f"bounds {notes}")

    # the program's state goes before the reference runs
    del engine
    gc.collect()
    numbers, ok = check(spec, seed, run, row_params, log=log)
    result = {"correct": bool(ok and failed == 0), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(ROOT, args.workload)
    configure_cache()
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    for name, n in result["check"].items():
        print(f"check {name}: {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
