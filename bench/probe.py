"""Chip-side measurements that set a cell's numbers; not part of a run.

    python bench/probe.py --workload <cell> --out <file.json> \\
        [--rates 3,4,5] [--sweep-seconds 20] [--trace-out <file.json>] \\
        [--seeds 1,2,3] [--check-seconds 10]

In one process (set-up is long, and the chip belongs to one process):

* ``--rates``: a rate sweep, one window per offered rate (run twice, the
  second measured: the first compiles what that rate's ramp reaches), for
  the knee (the highest rate with no growing backlog: TTFT of the
  window's last third no worse than its first third's);
* ``--trace-out``: one traced window at the cell's rate, its trace reduced
  and a slice of its raw events kept (the tests' recorded trace);
* ``--seeds``: the check's readings at the cell's rate, for each seed the
  program's gaps (served greedy tokens against the reference) and each
  control's (the first choice of the reference at a lower precision
  against the float32 reference's), over every greedy request of the
  window.

The engine is built once; each seed swaps in its own weights (the
profile table does not depend on the seed), so the compiled programs are
reused.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import threading
import time

import run as bench_run


def _stats(gaps):
    import numpy as np
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"widest": float(g.max()) if g.size else None,
            "mean": float(g.mean()) if g.size else None,
            "flips": int((g > 0).sum()), "positions": int(g.size)}


def sweep(spec, engine, seed, rates, seconds, shapes, clock, out):
    import client
    import e2e
    import traffic
    mix = spec["mix"]
    for rate in rates:
        arrivals = traffic.schedule(mix, rate, seconds, seed,
                                    spec["conf"]["model"]["vocab_size"])
        # the first pass compiles what this rate's ramp reaches; the second
        # is the one measured
        for _ in range(2):
            marks = {}
            shapes.clear()
            start = clock.count
            run = client.serve(
                engine, arrivals, mix, seconds,
                on_open=lambda: marks.update(open=(clock.count, set(shapes))),
                on_close=lambda: marks.update(close=clock.count))
        win = sorted(e2e.window_records(run), key=lambda r: r.due)
        third = max(1, len(win) // 3)
        first = [(r.times[0] - r.due) * 1e3 for r in win[:third] if r.times]
        last = [(r.times[0] - r.due) * 1e3 for r in win[-third:] if r.times]
        row = {"rate": rate, "requests": len(win),
               "tokens_per_s": e2e.METRICS["tokens_per_s"](run),
               "ttft_p90_ms": e2e.METRICS["ttft_p90_ms"](run),
               "itl_p50_ms": e2e.METRICS["itl_p50_ms"](run),
               "itl_p95_ms": e2e.METRICS["itl_p95_ms"](run),
               "ttft_p50_first_third_ms": e2e.pct(first, 0.5),
               "ttft_p50_last_third_ms": e2e.pct(last, 0.5),
               "compiles_in_window": marks["close"] - marks["open"][0],
               "compiles_before_window": marks["open"][0] - start,
               "shapes_lead_in": sorted(marks["open"][1]),
               "shapes_all": sorted(shapes),
               "occupancy_mean": (sum(o for *_, o in run.metrics.steps)
                                  / max(1, len(run.metrics.steps)))}
        print(json.dumps(row), flush=True)
        out.setdefault("sweep", []).append(row)


def traced(spec, engine, seed, seconds, path, out):
    import client
    import traffic
    import xplane
    import jax
    mix = spec["mix"]
    rate = spec["cellfile"]["rate_per_s"]
    arrivals = traffic.schedule(mix, rate, seconds, seed,
                                spec["conf"]["model"]["vocab_size"])
    tdir = tempfile.mkdtemp(prefix="probe-trace-")
    marks = {}

    def start():
        marks["t"] = threading.Thread(target=xplane.start, args=(tdir,))
        marks["t"].start()

    def enter():
        marks["t"].join()
        marks["lo"] = time.perf_counter()
        marks["ann"] = jax.profiler.TraceAnnotation(xplane.WINDOW)
        marks["ann"].__enter__()

    def leave():
        marks["ann"].__exit__(None, None, None)
        marks["hi"] = time.perf_counter()
        marks["s"] = threading.Thread(target=xplane.stop)
        marks["s"].start()

    mid = seconds / 2
    client.serve(engine, arrivals, mix, seconds,
                 hooks=[(mid - 4.0, start), (mid - 2.0, enter),
                        (mid + 2.0, leave)])
    marks["s"].join()
    from jax.profiler import ProfileData
    import glob
    pb = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
    pd = ProfileData.from_file(pb)
    layout = [{"plane": p.name, "lines": [[l.name, len(list(l.events))]
                                          for l in p.lines]}
              for p in pd.planes]
    with open(path, "w") as f:
        json.dump({"layout": layout}, f)
    clipped = xplane.load(tdir)
    lo = clipped["window_ns"][0]
    keep = lo + 300_000_000                      # the first 0.3 s
    small = {"window_ns": [lo, keep],
             "device": {c: [e for e in evs if e[1] < keep]
                        for c, evs in clipped["device"].items()},
             "host": [e for e in clipped["host"] if e[2] < keep]}
    with open(path, "w") as f:
        json.dump({"layout": layout, "trace": small,
                   "host_pc": [marks["lo"], marks["hi"]]}, f)
    red = xplane.reduce(clipped)
    red.pop("host_counts", None)
    out["trace"] = red
    print(json.dumps({"trace": {k: red[k] for k in ("window_s", "busy_s",
                                                      "device_ops",
                                                      "idle_gaps")}}),
          flush=True)


def readings(spec, engine, row, seeds, seconds, out):
    import numpy as np
    import client
    import reference
    import state
    import traffic
    conf, mix = spec["conf"], spec["mix"]
    rate = spec["cellfile"]["rate_per_s"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    length = 1 << (longest - 1).bit_length()
    cfg = engine.cfg
    for seed in seeds:
        t = time.perf_counter()
        times = {}
        engine._deployed.clear()
        engine.params_fact = None
        gc.collect()
        engine.params_fact = state.make_params(cfg, conf, seed)
        engine._realize(row)
        times["state_deploy"] = time.perf_counter() - t
        arrivals = traffic.schedule(mix, rate, seconds, seed,
                                    conf["model"]["vocab_size"])
        run = client.serve(engine, arrivals, mix, seconds)
        times["serve"] = time.perf_counter() - t - times["state_deploy"]
        engine._deployed.clear()
        engine.params_fact = None
        gc.collect()
        t1 = time.perf_counter()
        ref = reference.Reference(conf, seed, mix["budget"], length)
        times["reference"] = time.perf_counter() - t1
        prog, ctrl, served = [], {k: [] for k in reference.CONTROLS}, 0
        greedy = [r for r in run.records if r.arrival.phase == "window"
                  and r.arrival.greedy and r.complete]
        for r in greedy:
            toks = np.asarray(r.tokens, np.int32)
            prog.append(ref.gaps(r.arrival.prompt, toks))
            for k in ctrl:
                ctrl[k].append(ref.control_gaps(r.arrival.prompt, toks, k))
            served += len(toks)
        del ref
        gc.collect()
        row_out = {"seed": seed, "requests": len(greedy), "tokens": served,
                   "program": _stats(prog),
                   "controls": {k: _stats(v) for k, v in ctrl.items()},
                   "program_per_request": [float(g.max()) for g in prog],
                   "times": times, "seconds": time.perf_counter() - t}
        print(json.dumps(row_out), flush=True)
        out.setdefault("readings", []).append(row_out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--sweep-seconds", type=float, default=20.0)
    ap.add_argument("--trace-out", default="")
    ap.add_argument("--trace-seconds", type=float, default=12.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--check-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = bench_run.load_spec(bench_run.ROOT, args.workload)
    bench_run.configure_cache()
    out = {"workload": args.workload,
           "device": bench_run.device_info(spec["cell"]["chips"], True)}
    clock = bench_run.CompileClock()
    t = time.perf_counter()
    engine, row, params = bench_run.build(spec, args.seed)
    out["setup"] = {"seconds": time.perf_counter() - t,
                    "compiles": clock.count, "compile_s": clock.seconds,
                    "since_start_s": time.perf_counter() - bench_run.T0}
    print(json.dumps(out["setup"]), flush=True)
    shapes = set()
    step = engine._sample_jit

    def recording(p, caches, tok, sampling):
        shapes.add((int(tok.shape[1]), int(caches["block_tables"].shape[1]),
                    int(caches["sample_ids"].shape[0]),
                    sampling["top_k"] is not None))
        return step(p, caches, tok, sampling)

    engine._sample_jit = recording
    try:
        if args.rates:
            sweep(spec, engine, args.seed,
                  [float(x) for x in args.rates.split(",")],
                  args.sweep_seconds, shapes, clock, out)
        if args.trace_out:
            traced(spec, engine, args.seed, args.trace_seconds,
                   args.trace_out, out)
        if args.seeds:
            readings(spec, engine, row,
                     [int(x) for x in args.seeds.split(",")],
                     args.check_seconds, out)
    finally:
        out["memory_peak_bytes"] = bench_run.memory_peak(1)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
