"""Smoke run of the elastic serving path on a TPU, at gpt2-small full width.

    python chip_smoke.py                # one chip: build, serve, kernel checks
    python chip_smoke.py --four-chips   # four chips: sharded training vs one

One process runs every phase: a child process could not reach a chip its
parent holds. The default run builds the FlexRank state of gpt2-small from
seeded random weights (12 layers, d768, vocab 50257), serves mixed-budget
requests through ``ElasticEngine`` three ways (``generate()``, the
``StreamSession`` front door with lookahead, speculative decoding with
stochastic sampling), checks that the served step holds the Pallas kernels
and that they agree with the jnp oracle, and reports set-up and compile
seconds, tokens/s and peak device memory. Those numbers are one run's
information, not a benchmark.

The last line of standard output is ``{"ok": true, "device": {...}}``, and
it is printed only when every phase passed: a failure raises and the exit
code is not 0. Without a TPU the script exits non-zero before any phase.
The phases are functions of a ``SmokeConfig``, so a test can rehearse them
on the CPU with the smoke-size model and interpret-mode kernels.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib.metadata
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# max |kernel - oracle| logits over max |oracle logit|, both sides computed
# with float32 matmuls. The two paths differ only in attention: float32
# sums of at most 256 keys (context) and 64 lanes (head dim) in another
# order, about 320 * 2**-24 = 2e-5 relative per layer, added up over 12
# layers to 2.4e-4; doubled for exp and reciprocal implementations and
# again as margin. A wrong block, mask or context length is O(1).
KERNEL_RTOL = 1e-3
# loss difference between four-chip data-parallel training and the same
# steps on one chip, over the run's largest loss: only the order of the
# float32 reductions over the batch differs, an error that scales with the
# magnitudes summed, not with one step's (possibly near-zero) KD loss
# (7e-6 measured with four virtual CPU devices at smoke size)
FOUR_CHIP_RTOL = 1e-3

ARCH = "gpt2-small"
SEED = 0
BUDGETS = (0.4, 0.7, 1.0)
# the speculative run: draft row, draft length and the sampled requests
SPEC_DRAFT_RANK = 0.7
SPEC_LEN = 4
TEMPERATURE = 0.8
TOP_K = 40
# --four-chips: training steps, sequence length, batch
TRAIN_STEPS, TRAIN_SEQ_LEN, TRAIN_BATCH = 3, 64, 8


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What the CPU rehearsal shrinks; the defaults are the chip run."""
    smoke: bool = False             # True: the arch's tiny CPU-test config
    calib_batches: int = 8          # FlexRank calibration batches (4 x 64)
    requests: int = 8
    prompt_len: tuple = (64, 128)   # inclusive range
    max_new: int = 32
    max_batch: int = 8
    max_len: int = 256
    prefill_chunk: int = 64
    use_pallas: object = None       # None: the engine's choice (TPU: kernels)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def print_device_line(info: dict) -> None:
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    print(f"# device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} libtpu={libtpu}",
          flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache loads
    included) from the moment it is installed."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


# ------------------------------------------------------------------ build

def build_state(sc: SmokeConfig):
    """FlexRank state from seeded random weights: calibration moments ->
    DataSVD -> DP table. Returns (cfg, (params_fact, table, infos))."""
    from repro.configs import get_config
    from repro.data import make_source
    from repro.launch.train import build_flexrank_state
    from repro.models import common as cm
    from repro.models import transformer as tfm
    cfg = get_config(ARCH, smoke=sc.smoke)
    source = make_source(cfg.vocab_size, 64, 4, seed=SEED)
    dense = cm.instantiate(tfm.model_spec(cfg), jax.random.PRNGKey(SEED))
    state = build_flexrank_state(cfg, dense, source,
                                 calib_batches=sc.calib_batches)
    jax.block_until_ready(state[0])
    return cfg, state


def make_engine(sc: SmokeConfig, cfg, state, **kw):
    from repro.serving import ElasticEngine
    return ElasticEngine(cfg, *state, max_batch=sc.max_batch,
                         max_len=sc.max_len, prefill_chunk=sc.prefill_chunk,
                         device_sampling=True, use_pallas=sc.use_pallas, **kw)


def make_requests(sc: SmokeConfig, cfg, *, sampled: bool = False,
                  budgets=None):
    """``sc.requests`` seeded prompts, budgets dealt round-robin; greedy,
    or temperature/top-k sampled."""
    from repro.serving import Request, SamplingParams
    rng = np.random.default_rng(SEED)
    lo, hi = sc.prompt_len
    budgets = budgets or BUDGETS
    sampling = (SamplingParams(temperature=TEMPERATURE, top_k=TOP_K,
                               seed=SEED) if sampled else None)
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new_tokens=sc.max_new,
                    budget=budgets[i % len(budgets)],
                    sampling=sampling)
            for i in range(sc.requests)]


def check_complete(reqs, results, what: str) -> None:
    """Every request finished, uncancelled, with its full token count."""
    assert len(results) == len(reqs), (what, len(results), len(reqs))
    for i, (rq, rs) in enumerate(zip(reqs, results)):
        assert rs is not None and not rs.cancelled, (what, i)
        got = len(rs.tokens) - len(rq.prompt)
        assert got == rq.max_new_tokens, (what, i, got, rq.max_new_tokens)


# ------------------------------------------------------------------ serve

def serve_generate(engine, reqs):
    """Closed-batch ``generate()``. Returns (results, wall seconds)."""
    t0 = time.perf_counter()
    results = engine.generate(reqs, mode="continuous")
    return results, time.perf_counter() - t0


def serve_stream(engine, reqs, timeout_s: float = 600.0):
    """The ``StreamSession`` front door: every request submitted at once
    from an asyncio loop, tokens consumed one at a time. Returns a list of
    (streamed tokens, Result). An exception on the engine's thread is
    raised here instead of leaving the clients waiting."""
    from repro.serving.session import StreamSession, stream_request

    errors = []

    def work(session):
        try:
            engine.serve_session(session)
        except BaseException as e:      # re-raised on the caller's thread
            errors.append(e)

    async def drive():
        session = StreamSession(stream_buffer=8)
        session.loop = asyncio.get_running_loop()
        worker = threading.Thread(target=work, args=(session,), daemon=True)
        worker.start()
        clients = asyncio.ensure_future(asyncio.gather(
            *(stream_request(session, rq) for rq in reqs)))
        # before close() the engine side only ends by failing
        died = asyncio.ensure_future(session.join())
        await asyncio.wait({clients, died}, timeout=timeout_s,
                           return_when=asyncio.FIRST_COMPLETED)
        if not clients.done():
            clients.cancel()
            died.cancel()
            session.close()
            raise errors[0] if errors else TimeoutError("stream run hung")
        session.close()
        await asyncio.wait_for(died, timeout_s)
        worker.join(timeout_s)
        assert not worker.is_alive(), "engine thread did not stop"
        return clients.result()

    out = asyncio.run(drive())
    if errors:
        raise errors[0]
    return out


def serve_spec(sc: SmokeConfig, cfg, engine):
    """One speculative run at the full budget on ``engine`` (its budget
    rows are already deployed): the nested draft row proposes ``spec_len``
    tokens, the full row verifies, and the temperature/top-k sampled
    requests take the stochastic accept/resample path
    (``paged_verify_accept_step`` and the probs variant of the sampling
    kernel). Returns (results, metrics summary)."""
    from repro.serving import SpecConfig
    engine.spec = SpecConfig(draft_rank=SPEC_DRAFT_RANK, spec_len=SPEC_LEN)
    reqs = make_requests(sc, cfg, sampled=True, budgets=(1.0,))
    results = engine.generate(reqs, mode="continuous")
    check_complete(reqs, results, "spec")
    summary = engine.last_metrics.summary()
    assert summary["spec_rounds"] > 0, "no speculative round ran"
    return results, summary


# ---------------------------------------------------- no hidden reference

def mixed_operands(sc: SmokeConfig, cfg, seed: int = 1):
    """One full mixed iteration's operands over a randomly filled paged
    cache: slot 0 prefills a ``prefill_chunk``-token chunk from position 0,
    every other slot decodes one token at a random position, blocks are
    scattered over the pool, pads point at the null row. Returns
    (tokens (1, T), caches)."""
    bs = 16
    mb = -(-sc.max_len // bs)
    nb = sc.max_batch * mb + 1                 # + the null block 0
    rng = np.random.default_rng(seed)
    chunk = sc.prefill_chunk
    decode_pos = rng.integers(chunk, sc.max_len, sc.max_batch - 1)
    width = sc.max_batch + chunk               # the engine's token budget
    slot_ids = np.full(width, sc.max_batch, np.int32)      # pads: null row
    positions = np.zeros(width, np.int32)
    slot_ids[:chunk] = 0
    positions[:chunk] = np.arange(chunk)
    slot_ids[chunk:chunk + sc.max_batch - 1] = np.arange(1, sc.max_batch)
    positions[chunk:chunk + sc.max_batch - 1] = decode_pos
    tables = np.zeros((sc.max_batch + 1, mb), np.int32)
    tables[:sc.max_batch] = (1 + rng.permutation(nb - 1)).reshape(
        sc.max_batch, mb)
    sample_ids = np.arange(chunk - 1, chunk + sc.max_batch - 1,
                           dtype=np.int32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 2 * len(cfg.segments)))
    pools = [{kv: jax.random.normal(next(keys), (seg.count, nb, bs,
                                                 cfg.num_kv_heads,
                                                 cfg.resolved_head_dim))
              for kv in ("k", "v")} for seg in cfg.segments]
    tokens = rng.integers(0, cfg.vocab_size, (1, width)).astype(np.int32)
    caches = {"slot_ids": jnp.asarray(slot_ids),
              "positions": jnp.asarray(positions),
              "block_tables": jnp.asarray(tables), "segments": pools,
              "sample_ids": jnp.asarray(sample_ids)}
    return jnp.asarray(tokens), caches


def served_step_custom_calls(engine, params, tokens, caches) -> int:
    """``tpu_custom_call`` sites (Pallas kernels) in the compiled HLO of the
    engine's own fused mixed-step-plus-sampling program."""
    s = caches["sample_ids"].shape[0]
    zeros = jnp.zeros(s, jnp.int32)
    sampling = {"temperature": jnp.zeros(s, jnp.float32), "top_k": None,
                "seed": zeros, "req_id": zeros, "purpose": zeros,
                "position": zeros}
    hlo = engine._sample_jit.lower(params, caches, tokens,
                                   sampling).compile().as_text()
    return hlo.count("custom_call_target=\"tpu_custom_call\"")


def kernel_vs_oracle(cfg, params, tokens, caches, use_pallas):
    """Gathered logits of one mixed step with the kernels (``use_pallas``)
    and with the jnp oracle, both at float32 matmul precision. Returns
    (max abs difference, max abs oracle logit, greedy-token agreement)."""
    from repro.models import transformer as tfm

    def logits(mode):
        step = jax.jit(lambda p, c, t: tfm.paged_mixed_step(
            p, cfg, c, t, use_pallas=mode)[0])
        return np.asarray(step(params, caches, tokens)[0])

    with jax.default_matmul_precision("highest"):
        ker, ref = logits(use_pallas), logits(False)
    err = float(np.max(np.abs(ker - ref)))
    scale = float(np.max(np.abs(ref)))
    agree = float(np.mean(ker.argmax(-1) == ref.argmax(-1)))
    return err, scale, agree


# ------------------------------------------------------------ the phases

def run_serving(sc: SmokeConfig, *, on_chip: bool,
                clock: CompileClock | None = None) -> dict:
    """Every one-chip phase in order; raises on the first failure. Returns
    the numbers worth reporting. With a ``clock``, each phase line also
    gives the compile seconds spent in it."""
    report = {}
    mark = [clock.seconds if clock else 0.0]

    def compiled() -> str:
        if clock is None:
            return ""
        spent, mark[0] = clock.seconds - mark[0], clock.seconds
        return f", compile {spent:.3f} s"

    t0 = time.perf_counter()
    cfg, state = build_state(sc)
    report["setup_s"] = time.perf_counter() - t0
    print(f"# build: {cfg.name} d{cfg.d_model} x{cfg.num_layers} "
          f"vocab {cfg.vocab_size}, {state[1].table.shape[0]} budget rows, "
          f"{sc.calib_batches} calibration batches: set-up "
          f"{report['setup_s']:.3f} s{compiled()}", flush=True)

    engine = make_engine(sc, cfg, state)
    if on_chip:
        assert engine.use_pallas is True, engine.use_pallas
    reqs = make_requests(sc, cfg)
    cold, cold_s = serve_generate(engine, reqs)
    check_complete(reqs, cold, "generate")
    warm, warm_s = serve_generate(engine, reqs)
    check_complete(reqs, warm, "generate (warm)")
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    new_tokens = sum(rq.max_new_tokens for rq in reqs)
    report["tokens_per_s"] = new_tokens / warm_s
    print(f"# generate: {len(reqs)} requests x {sc.max_new} tokens over "
          f"budgets {BUDGETS}: cold {cold_s:.3f} s, warm {warm_s:.3f} s "
          f"= {report['tokens_per_s']:.1f} tokens/s{compiled()}", flush=True)

    # the serve.py --stream path on the same engine (and so the same
    # compiled programs), with the one-iteration lookahead on
    t0 = time.perf_counter()
    engine.lookahead = True
    streamed = serve_stream(engine, reqs)
    stream_s = time.perf_counter() - t0
    check_complete(reqs, [r for _, r in streamed], "stream")
    for i, ((toks, res), ref) in enumerate(zip(streamed, warm)):
        np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                      ref.tokens[len(reqs[i].prompt):])
        np.testing.assert_array_equal(res.tokens, ref.tokens)
    summary = engine.last_metrics.summary()
    assert summary["lookahead_iterations"] > 0, "the lookahead never ran"
    print(f"# stream: {len(reqs)} requests, lookahead on "
          f"({summary['lookahead_iterations']:.0f} lookahead iterations, "
          f"{summary['rollbacks']:.0f} rollbacks), tokens equal to "
          f"generate(), {stream_s:.3f} s{compiled()}", flush=True)

    t0 = time.perf_counter()
    _, spec = serve_spec(sc, cfg, engine)
    spec_s = time.perf_counter() - t0
    print(f"# spec: draft_rank {SPEC_DRAFT_RANK}, k {SPEC_LEN}, "
          f"temperature {TEMPERATURE}, top-k {TOP_K}: "
          f"{spec['spec_rounds']:.0f} rounds, acceptance "
          f"{spec['spec_acceptance_rate']:.3f}, every request complete, "
          f"{spec_s:.3f} s{compiled()}", flush=True)

    row = engine._budget_row(1.0)
    params = engine._realize(row)
    tokens, caches = mixed_operands(sc, cfg)
    report["custom_calls"] = served_step_custom_calls(engine, params, tokens,
                                                      caches)
    if on_chip:
        assert report["custom_calls"] > 0, "no Pallas kernel in served step"
    err, scale, agree = kernel_vs_oracle(cfg, params, tokens, caches,
                                         engine.use_pallas)
    report.update(kernel_err=err, logit_scale=scale, greedy_agree=agree)
    print(f"# kernels: {report['custom_calls']} tpu_custom_call sites in "
          f"the served sample step; mixed-step logits kernel vs oracle "
          f"max abs err {err:.3e} (max |logit| {scale:.3e}, tolerance "
          f"{KERNEL_RTOL:g} x that), greedy agreement {agree:.3f}"
          f"{compiled()}", flush=True)
    assert err <= KERNEL_RTOL * scale, (err, scale)
    return report


def run_four_chips(sc: SmokeConfig) -> None:
    """``train.main --mode flexrank_kd --mesh-shape 4,1`` against the same
    steps on one chip, in this process."""
    from repro.launch import train
    assert len(jax.devices()) == 4, jax.devices()
    args = ["--arch", ARCH, "--mode", "flexrank_kd",
            "--steps", str(TRAIN_STEPS), "--seq-len", str(TRAIN_SEQ_LEN),
            "--batch", str(TRAIN_BATCH), "--seed", str(SEED)]
    if sc.smoke:
        args.append("--smoke")
    t0 = time.perf_counter()
    params4, losses4 = train.main(args + ["--mesh-shape", "4,1"])
    t4 = time.perf_counter() - t0
    big = max(jax.tree.leaves(params4), key=lambda a: a.size)
    devices = len(big.sharding.device_set)
    assert devices == 4, (big.shape, big.sharding)
    t0 = time.perf_counter()
    _, losses1 = train.main(args)
    t1 = time.perf_counter() - t0
    l4, l1 = np.asarray(losses4), np.asarray(losses1)
    rel = float(np.max(np.abs(l4 - l1)) / np.max(np.abs(l1)))
    print(f"# four chips: largest weight {big.shape} on {devices} devices; "
          f"losses 4 chips {losses4} vs 1 chip {losses1}, max difference "
          f"{rel:.3e} of the largest loss (tolerance {FOUR_CHIP_RTOL:g}); "
          f"wall {t4:.3f} s vs {t1:.3f} s", flush=True)
    assert rel <= FOUR_CHIP_RTOL, (losses4, losses1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded training on four chips against "
                         "the same steps on one chip")
    args = ap.parse_args(argv)
    info = device_info()
    print_device_line(info)
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {info['platform']}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    sc = SmokeConfig()
    if args.four_chips:
        run_four_chips(sc)
    else:
        report = run_serving(sc, on_chip=True, clock=clock)
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        print(f"# report: set-up {report['setup_s']:.3f} s, compile "
              f"{clock.seconds:.3f} s over {clock.count} programs "
              f"(persistent-cache loads included), generate() "
              f"{report['tokens_per_s']:.1f} tokens/s, peak device memory "
              f"{peak} bytes", flush=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
