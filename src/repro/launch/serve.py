"""Serving launcher: build (or load) an elastic model, serve a stream of
requests at mixed budgets through the GAR-deployed submodels with the
continuous-batching engine (paged KV cache, iteration-level join, with
``--prefill-chunk`` chunked prefill fused into decode iterations, and with
``--spec-draft-rank`` nested self-speculative decoding: a low-rank prefix
row drafts up to ``--spec-len`` tokens per round, the full row verifies
them in one multi-token forward). With ``--temperature`` the speculative
rounds run stochastic (Leviathan) acceptance — distribution-exact vs
target-only sampling — unless ``--spec-no-stochastic`` restores the
verify-only fallback; ``--spec-adaptive-k`` lets each sequence's draft
length track its trailing acceptance rate.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small --smoke \
      --requests 6 --budgets 0.4,0.7,1.0 --engine continuous \
      --prefill-chunk 64 --spec-draft-rank 0.7 --spec-len 4 \
      --temperature 0.8 --spec-adaptive-k
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.data import make_source
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build_flexrank_state
from repro.models import common as cm
from repro.models import transformer as tfm
from repro.serving import ElasticEngine, Request, SamplingParams, SpecConfig


def _run_stream(engine, reqs, args):
    """Asyncio front door: submit ``reqs`` open-loop (Poisson gaps when
    ``--arrival-rate`` is set), echo every token as it streams, optionally
    cancel every ``--cancel-nth`` request after its second token. Returns
    per-request Results in submission order (cancelled ones included)."""
    import asyncio
    import threading

    from repro.serving.session import StreamSession, stream_request

    async def _drive():
        session = StreamSession(stream_buffer=8)
        session.loop = asyncio.get_running_loop()
        worker = threading.Thread(target=engine.serve_session,
                                  args=(session,), daemon=True)
        worker.start()
        rng = np.random.default_rng(args.seed + 1)

        async def client(i, rq):
            cancel_after = (2 if args.cancel_nth
                            and (i + 1) % args.cancel_nth == 0 else None)
            h = session.submit(rq)
            toks = []
            async for tok in h.tokens():
                toks.append(tok)
                print(f"req {i} token[{len(toks) - 1}] = {tok}", flush=True)
                if cancel_after is not None and len(toks) >= cancel_after:
                    print(f"req {i}: cancelling mid-stream", flush=True)
                    h.cancel()
            result = await h.wait_result()
            state = "cancelled" if (result is not None
                                    and result.cancelled) else "done"
            print(f"req {i}: {state}, {len(toks)} tokens streamed",
                  flush=True)
            return result

        tasks = []
        for i, rq in enumerate(reqs):
            if args.arrival_rate > 0 and i:
                await asyncio.sleep(rng.exponential(1.0 / args.arrival_rate))
            tasks.append(asyncio.create_task(client(i, rq)))
        results = await asyncio.gather(*tasks)
        session.close()
        await session.join()
        worker.join()
        return list(results)

    return asyncio.run(_drive())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--budgets", default="0.4,0.7,1.0")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "continuous", "drain"],
                    help="continuous = paged cache + mid-decode joins; "
                         "drain = seed-style static batches")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prompt tokens per chunk for mixed prefill/decode "
                         "iterations (0 = full-prompt prefill at admission)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="total tokens per mixed or speculative iteration "
                         "(0 = max_batch + prefill_chunk)")
    ap.add_argument("--prefill-order", default="fifo",
                    choices=["fifo", "srpf"],
                    help="who gets prefill budget first when it spills "
                         "over: admission order, or shortest remaining "
                         "prefill first")
    ap.add_argument("--spec-draft-rank", type=float, default=0.0,
                    help="budget fraction of the speculative draft row "
                         "(0 = speculation off); drafts run on the nested "
                         "low-rank prefix submodel, the full row verifies")
    ap.add_argument("--spec-len", type=int, default=4,
                    help="max draft tokens proposed per speculative round")
    ap.add_argument("--spec-adaptive-k", action="store_true",
                    help="adapt each sequence's draft length to its "
                         "trailing acceptance-rate EWMA within "
                         "[0, --spec-len]")
    ap.add_argument("--spec-no-stochastic", action="store_true",
                    help="verify-only fallback for sampled requests "
                         "(k = 0 rounds, token-identical to the "
                         "non-speculative engine) instead of stochastic "
                         "accept/resample")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for all requests "
                         "(0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation when sampling (0 = off)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching: refcounted KV blocks "
                         "with a token-prefix index, so requests sharing a "
                         "prompt prefix reuse its K/V instead of "
                         "re-prefilling (default follows the "
                         "REPRO_PREFIX_CACHE env knob, off otherwise)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the asyncio streaming front door "
                         "(open-loop arrivals, per-token streaming) instead "
                         "of the closed-batch generate() driver")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="with --stream: mean Poisson request arrival rate "
                         "in req/s (0 = submit everything immediately)")
    ap.add_argument("--cancel-nth", type=int, default=0,
                    help="with --stream: cancel every Nth request "
                         "mid-stream after 2 tokens (0 = never) — "
                         "exercises client-cancellation unwinding")
    ap.add_argument("--lookahead", action="store_true",
                    help="one-iteration lookahead pipelining: dispatch "
                         "iteration i+1 from speculatively-advanced "
                         "scheduler state before committing i (default "
                         "follows the REPRO_ASYNC env knob, off otherwise)")
    ap.add_argument("--no-lookahead", action="store_true",
                    help="force lookahead off regardless of REPRO_ASYNC")
    ap.add_argument("--host-sampling", action="store_true",
                    help="sample on the host (the oracle path: gathered "
                         "logits ship off-device, python per-sequence "
                         "draws) instead of the default device-resident "
                         "fused sampling")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run here "
                         "(loads in Perfetto / chrome://tracing; a .jsonl "
                         "suffix writes one event per line instead)")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus text exposition of the run's "
                         "metrics registry here (a .jsonl suffix appends "
                         "a flat snapshot line instead)")
    ap.add_argument("--jax-profile", default="", metavar="DIR",
                    help="bracket the serve in a jax.profiler device trace "
                         "written to DIR (TensorBoard/Perfetto-loadable); "
                         "also turns on TraceAnnotation scopes around the "
                         "jitted dispatches")
    ap.add_argument("--statusz-port", type=int, default=None, metavar="PORT",
                    help="serve the live telemetry plane on this port "
                         "(0 = ephemeral, printed at startup): GET "
                         "/metrics (Prometheus text), /statusz (live "
                         "engine JSON), /debug/trace (flight-recorder "
                         "dump as Chrome trace JSON)")
    ap.add_argument("--status-linger", type=float, default=0.0, metavar="S",
                    help="keep the status server (and process) up S "
                         "seconds after generation finishes so the "
                         "endpoints can be scraped post-run")
    ap.add_argument("--trace-ring", type=int, default=0, metavar="N",
                    help="record traces into a bounded drop-oldest ring of "
                         "N events (the always-on flight recorder) instead "
                         "of the unbounded post-hoc tracer")
    ap.add_argument("--watchdog", action="store_true",
                    help="evaluate the anomaly watchdog every engine "
                         "iteration (stall, TTFT/inter-token SLO, "
                         "fragmentation spike, spec-acceptance and "
                         "prefix-hit-rate collapse; see "
                         "docs/observability.md for default thresholds)")
    ap.add_argument("--postmortem-dir", default="", metavar="DIR",
                    help="where watchdog firings write their postmortem "
                         "bundles (ring dump + metrics snapshot + live "
                         "state); empty = no bundles, the firing still "
                         "traces and counts")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    rng = np.random.default_rng(args.seed)
    source = make_source(cfg.vocab_size, 64, 4, seed=args.seed)

    dense = cm.instantiate(tfm.model_spec(cfg), jax.random.PRNGKey(args.seed))
    params_fact, table, infos = build_flexrank_state(cfg, dense, source)
    spec = (SpecConfig(draft_rank=args.spec_draft_rank,
                       spec_len=args.spec_len,
                       stochastic=not args.spec_no_stochastic,
                       adaptive_k=args.spec_adaptive_k)
            if args.spec_draft_rank else None)
    live_plane = args.statusz_port is not None or args.watchdog
    if args.trace_ring:
        tracer = obs.RingTracer(args.trace_ring)
    elif args.trace_out:
        tracer = obs.make_tracer(True)
    elif live_plane:
        # a live serve must stay bounded: flight-record by default
        tracer = obs.RingTracer()
    else:
        tracer = None
    registry = (obs.MetricsRegistry()
                if args.metrics_out or live_plane else None)
    watchdog = (obs.Watchdog(postmortem_dir=args.postmortem_dir or None)
                if args.watchdog else None)
    lookahead = (True if args.lookahead
                 else False if args.no_lookahead else None)
    engine = ElasticEngine(cfg, params_fact, table, infos,
                           max_batch=args.max_batch, max_len=args.max_len,
                           block_size=args.block_size,
                           prefill_chunk=args.prefill_chunk or None,
                           token_budget=args.token_budget or None,
                           prefill_order=args.prefill_order,
                           spec=spec,
                           device_sampling=not args.host_sampling,
                           prefix_cache=True if args.prefix_cache else None,
                           lookahead=lookahead,
                           tracer=tracer, registry=registry,
                           watchdog=watchdog,
                           costaudit=True if live_plane else None)
    server = None
    if args.statusz_port is not None:
        # the ring recorder supports ?last_s=N windowed dumps; the plain
        # post-hoc tracer always dumps everything it has
        trace_fn = (tracer.dump if isinstance(tracer, obs.RingTracer)
                    else lambda last_s=None: tracer.to_chrome())
        server = obs.StatusServer(registry=registry,
                                  status_fn=engine.statusz,
                                  trace_fn=trace_fn,
                                  port=args.statusz_port)
        server.start()
        print(f"# statusz: {server.url} "
              f"(/metrics /statusz /debug/trace)", flush=True)

    budgets = [float(b) for b in args.budgets.split(",")]
    sampling = (SamplingParams(temperature=args.temperature,
                               top_k=args.top_k, seed=args.seed)
                if args.temperature > 0 else None)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=args.prompt_len).astype(np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=args.max_new,
                            budget=budgets[i % len(budgets)],
                            sampling=sampling))
    with obs.profiling.profile(args.jax_profile):
        if args.stream:
            results = _run_stream(engine, reqs, args)
        else:
            results = engine.generate(reqs, mode=args.engine)
    if args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            engine.tracer.export_jsonl(args.trace_out)
        else:
            engine.tracer.export_chrome(args.trace_out)
        print(f"# trace: {len(engine.tracer)} events -> {args.trace_out}")
    if args.metrics_out:
        if args.metrics_out.endswith(".jsonl"):
            registry.snapshot_jsonl(args.metrics_out)
        else:
            registry.write_prometheus(args.metrics_out)
        print(f"# metrics -> {args.metrics_out}")
    for i, (rq, rs) in enumerate(zip(reqs, results)):
        print(f"req {i}: budget={rq.budget:.2f} -> row {rs.budget_row} "
              f"({rs.deployed_params:,} params) tokens={rs.tokens[:12].tolist()}...")
    if engine.last_metrics is not None:
        s = engine.last_metrics.summary()
        print(f"# serving: {s['tokens_per_s']:.1f} tok/s, "
              f"ttft mean {s['ttft_mean_s']*1e3:.1f} ms "
              f"(queue {s['ttft_queue_mean_s']*1e3:.1f} + "
              f"prefill {s['ttft_prefill_mean_s']*1e3:.1f} + "
              f"first-decode {s['ttft_first_decode_mean_s']*1e3:.1f}), "
              f"cache occupancy peak {s['cache_occupancy_peak']:.2f}, "
              f"preemptions {s['preemptions']}")
        print(f"# iteration split: dispatch {s['dispatch_ms_mean']:.2f} ms "
              f"/ host {s['host_ms_mean']:.2f} ms "
              f"({'device' if not args.host_sampling else 'host'} sampling)")
        if args.prefill_chunk:
            print(f"# chunked prefill: chunk={args.prefill_chunk}, "
                  f"budget={engine.token_budget}, "
                  f"{s['mixed_iterations']:.0f} mixed iterations")
        if engine.prefix_cache:
            print(f"# prefix cache: {s['prefix_hits']:.0f} hits, "
                  f"{s['prefix_hit_tokens']:.0f} prompt tokens reused")
        if args.spec_draft_rank and s["spec_rounds"]:
            mode = ("verify-only" if args.temperature > 0
                    and args.spec_no_stochastic
                    else "stochastic" if args.temperature > 0 else "greedy")
            k_mode = ("adaptive<=" if args.spec_adaptive_k else "") \
                + str(args.spec_len)
            print(f"# spec decode ({mode}): "
                  f"draft_rank={args.spec_draft_rank}, k={k_mode}, "
                  f"{s['spec_rounds']:.0f} rounds, "
                  f"acceptance {s['spec_acceptance_rate']:.2f}, "
                  f"mean accepted len {s['spec_mean_accepted_len']:.2f}")
    if watchdog is not None:
        for rec in watchdog.fired:
            where = f" -> {rec['bundle']}" if rec["bundle"] else ""
            print(f"# watchdog fired: {rec['rule']} — {rec['reason']}{where}")
    if server is not None:
        if args.status_linger > 0:
            print(f"# statusz lingering {args.status_linger}s at "
                  f"{server.url}", flush=True)
            time.sleep(args.status_linger)
        server.stop()
    return results


if __name__ == "__main__":
    main()
