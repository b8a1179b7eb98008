"""Elastic serving engine: continuous batching over nested FlexRank submodels.

Holds one set of shared FlexRank weights plus the nested profile table; each
request names a budget, the scheduler routes it to a GAR-deployed row
("train once, deploy everywhere") and the engine serves it through:

  * **chunked prefill fused into decode iterations** (``prefill_chunk``
    set): each iteration builds one flat token batch — every decoding
    sequence contributes its next token, and the remaining per-iteration
    token budget is filled with FIFO prompt chunks of at most
    ``prefill_chunk`` tokens — and runs it through a single
    ``paged_mixed_step`` forward (Sarathi/vLLM-style stall-free batching).
    Long prompts no longer stop the world: decodes advance every iteration
    and TTFT stops scaling with the running batch's prompt lengths,
  * a block-paged KV cache with a free-list allocator (``kv_cache``) whose
    blocks arrive chunk-by-chunk during prefill,
  * iteration-level continuous batching (``batcher``): finished sequences
    free their slot mid-flight and waiting requests join the running batch
    without draining it,
  * budget-aware admission + youngest-first preemption on cache pressure
    (``scheduler``), with recompute semantics (greedy decode makes the
    regenerated tokens identical) — the victim may be *mid-prefill*, in
    which case its partial chunks are discarded with its blocks.

  * **nested self-speculative decoding** (``spec`` set): the low-rank
    prefix row of the same nested decomposition proposes up to ``spec_len``
    tokens per round and the full row verifies them in ONE multi-token
    ``paged_verify_step`` forward; greedy acceptance is token-identical to
    target-only decoding, and stochastic (temperature/top-k) acceptance is
    Leviathan accept/resample — *distribution*-identical to target-only
    sampling. Per-sequence draft lengths adapt to trailing acceptance when
    ``SpecConfig.adaptive_k`` is set. Each sequence holds a draft + target
    cache slot pair over one shared allocator; rejected drafts roll back
    via ``truncate_slot``. See ``repro.spec`` for the round anatomy.

Knobs: ``prefill_chunk`` (prompt tokens per chunk; ``None`` is a
*deprecation shim* for the retired PR-1 full-prompt path — continuous
serving then runs the same mixed iterations with a full-prompt-sized
chunk, so the old benchmark-baseline flag still resolves), ``token_budget``
(total tokens per mixed iteration, default ``max_batch + prefill_chunk``;
decode tokens are reserved first, so a long prefill can never starve
running decodes), ``prefill_order`` (``"fifo"`` admission order vs
``"srpf"`` shortest-remaining-prefill-first when budget spills over),
``spec`` (a ``repro.spec.SpecConfig`` turning on speculative decoding;
per-request override via ``Request.spec_len``), ``device_sampling``
(default True; the ``REPRO_DEVICE_SAMPLING`` env knob flips the default).
Sampling is per-request (``Request.sampling``): greedy argmax by default,
temperature / top-k otherwise. With device sampling the whole
token-emission path is device-resident — the forward gathers only the
sample positions for the LM head and draws in-jit with
``(seed, req_id, purpose, position)``-keyed counter-based PRNG, so each
iteration transfers int32 ids only and recompute after preemption replays
identical draws by construction; ``device_sampling=False`` keeps the host
sampler (sequential per-request numpy stream, the test oracle — greedy
stays bit-identical across the two paths). See ``scheduler`` for the
waiting -> prefilling -> decoding state machine.

Families outside the paged path (mamba/rwkv/zamba/MLA/enc-dec) fall back to
the drain-batch engine, itself upgraded to single-pass prefill.
"""
from __future__ import annotations

import os
import threading
import time
import warnings
from typing import Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro.configs.base import ModelConfig
from repro.core import flexrank as FR
from repro.models import transformer as tfm
from repro.obs import CAT_ITER, CAT_SCHED, compiles, make_tracer, profiling
from repro.serving import device_sampling as dsamp
from repro.serving.batcher import ContinuousBatcher
from repro.serving.kv_cache import CacheOOM, PagedKVCache
from repro.serving.metrics import Iteration, ServingMetrics
from repro.serving.sampling import DRAW_TARGET, SamplerState
from repro.serving.scheduler import (BudgetRouter, Request, Result, Scheduler,
                                     Sequence)

if TYPE_CHECKING:    # runtime import is lazy: repro.spec imports serving
    from repro.spec import SpecConfig    # submodules (cycle otherwise)

__all__ = ["ElasticEngine", "Request", "Result", "CacheOOM"]


def _named(name: str, fn):
    """``fn`` under ``name``: the name JAX gives its compiled programs, as
    the compile record (``obs/compiles.py``) and ``jax_log_compiles``
    report them."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _ImmediateLog:
    """Plan log for the synchronous engine: every emission fires the moment
    planning records it — byte-identical event order to the pre-pipeline
    loop. ``emit``/``finish``/``cancel_finish`` are the shared surface the
    planner writes against; the pipelined engine swaps in ``_DeferredLog``
    and nothing in the planner changes."""

    deferred = False

    def __init__(self, engine, metrics, results):
        self.engine = engine
        self.metrics = metrics
        self.results = results

    def emit(self, fn, *args, **kw):
        fn(*args, **kw)

    def finish(self, seq):
        self.engine._finish(seq, self.metrics, self.results)

    def cancel_finish(self, seq):
        self.engine._finish_cancelled(seq, self.metrics, self.results)

    def flush(self):
        pass


class _DeferredLog:
    """Plan log for the pipelined engine: emissions buffer as
    ``(fn, args, kwargs)`` with every argument captured eagerly, by value,
    at plan time, and fire in plan order when the iteration commits. A
    rolled-back plan's log is dropped wholesale — no metric, trace event,
    result, or stream emission from an abandoned speculation ever escapes.
    Deferred ``finish``/``cancel_finish`` closures read the sequence's
    ``generated`` list at flush time, i.e. AFTER the commit patched the
    plan's placeholder tokens with the real sampled values."""

    deferred = True

    def __init__(self, engine, metrics, results):
        self.engine = engine
        self.metrics = metrics
        self.results = results
        self._buf: list = []

    def emit(self, fn, *args, **kw):
        self._buf.append((fn, args, kw))

    def finish(self, seq):
        self._buf.append((self.engine._finish,
                          (seq, self.metrics, self.results), {}))

    def cancel_finish(self, seq):
        self._buf.append((self.engine._finish_cancelled,
                          (seq, self.metrics, self.results), {}))

    def flush(self):
        buf, self._buf = self._buf, []
        for fn, args, kw in buf:
            fn(*args, **kw)


class _MixedPlan:
    """One mixed iteration's full decision record: what the planner decided
    (decode slots, prompt chunks, sample rows), the predicted state advance
    it already applied with placeholder tokens, and the patch lists the
    commit uses to swap the real sampled values in. ``plog`` holds every
    deferred emission; ``registers`` the prefix-index insertions that must
    wait for the commit (the canonical K/V only exists on device once the
    dispatch ran); ``admissions`` the (sequence, prefix-hit) pairs the
    commit re-probes for prefix-hit drift."""

    __slots__ = ("plog", "empty", "decode_slots", "decode_seqs", "chunks",
                 "sample_ids", "metas", "finish_rows", "gen_patches",
                 "feed_rows", "registers", "admissions", "cancel_cursor",
                 "total_chunk", "rec", "tokens_dev", "sampled")

    def __init__(self, plog):
        self.plog = plog
        self.empty = True
        self.decode_slots: list = []
        self.decode_seqs: list = []
        self.chunks: list = []
        self.sample_ids: list = []
        self.metas: list = []
        self.finish_rows: dict = {}
        self.gen_patches: list = []     # (seq, generated index, sample row)
        self.feed_rows: dict = {}       # slot -> (seq, sample row)
        self.registers: list = []       # (slot, seq, upto, block prefix)
        self.admissions: list = []      # (seq, prefix-hit tokens at plan)
        self.cancel_cursor = 0
        self.total_chunk = 0
        self.rec: Optional[Iteration] = None   # the iteration's record
        self.tokens_dev = None
        self.sampled = None


class ElasticEngine:
    def __init__(self, cfg: ModelConfig, params_fact, table, infos, *,
                 max_batch: int = 8, max_len: int = 256,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefill_order: str = "fifo",
                 spec: "Optional[SpecConfig]" = None,
                 device_sampling: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 lookahead: Optional[bool] = None,
                 tracer=None, registry=None,
                 watchdog=None, costaudit=None,
                 use_pallas=None):
        self.cfg = cfg
        self.params_fact = params_fact
        self.table = table
        self.infos = infos
        self.max_batch = max_batch
        self.max_len = max_len
        self.block_size = block_size
        self.num_blocks = num_blocks
        # Pallas kernels for paged attention and sampling: on by default
        # exactly when the backend is a TPU (resolved once, here); False is
        # the jnp oracle, and "interpret" runs the kernels through the
        # Pallas interpreter (tests on the CPU)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = use_pallas
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # deprecation shim for the retired PR-1 full-prompt prefill path:
        # ``prefill_chunk=None`` now serves through the same mixed loop with
        # a chunk the size of the longest possible prompt — one iteration
        # per whole prompt, semantically the old baseline, one code path
        self._chunk = prefill_chunk if prefill_chunk is not None else max_len
        if prefill_order not in ("fifo", "srpf"):
            raise ValueError(f"unknown prefill_order {prefill_order!r}")
        self.prefill_order = prefill_order
        if token_budget is None and prefill_chunk is not None:
            token_budget = max_batch + prefill_chunk
        if token_budget is not None and token_budget < max_batch + 1:
            raise ValueError(
                f"token_budget {token_budget} leaves no room for prefill "
                f"beside {max_batch} decode slots (need >= max_batch + 1)")
        # self.token_budget keeps the PR-2/PR-3 semantics: the user's value,
        # or max_batch + prefill_chunk when only the chunk knob is set, or
        # None when neither is — the spec decoder substitutes its larger
        # speculative default (max_batch * (spec_len + 1) + chunk) ONLY in
        # that last case; with a chunked budget, speculation deliberately
        # yields to seated prefills round by round
        self.token_budget = token_budget
        # effective per-iteration budget for the mixed loop
        self._mixed_budget = (token_budget if token_budget is not None
                              else max_batch + self._chunk)
        self.spec = spec
        # device-resident sampling (the default): every iteration's LM head
        # runs only over the gathered sample positions and the
        # temperature/top-k draw happens in-jit, so the host receives int32
        # token ids instead of a [T, vocab] logits tensor.
        # ``device_sampling=False`` keeps the host sampler as the oracle
        # path (sequential-stream draws, PR-4 bit-identical); the
        # REPRO_DEVICE_SAMPLING env knob flips the default for whole test
        # suites (the CI sampling matrix).
        if device_sampling is None:
            env = os.environ.get("REPRO_DEVICE_SAMPLING")
            device_sampling = env != "0" if env is not None else True
        self.device_sampling = bool(device_sampling)
        # automatic prefix caching (kv_cache.PagedKVCache): admitted
        # requests probe a hash-of-token-prefix index and share full prompt
        # blocks already resident instead of re-prefilling them; greedy
        # token streams are bit-identical either way. ``None`` resolves via
        # the REPRO_PREFIX_CACHE env knob (default off) so whole test
        # suites flip it like the other serving matrices.
        if prefix_cache is None:
            prefix_cache = os.environ.get("REPRO_PREFIX_CACHE", "0") == "1"
        self.prefix_cache = bool(prefix_cache)
        # one-iteration-lookahead pipelining: plan + dispatch iteration i+1
        # from speculatively advanced scheduler/cache state before syncing
        # and committing iteration i, so host planning runs under the
        # device dispatch instead of after it. Commit i validates the
        # speculation (forced faults, cancellations, prefix-hit drift) and
        # rolls the host state back for a replan when it lost the race.
        # Requires device sampling (the host oracle must read logits
        # between dispatch and commit, which is exactly the sync the
        # pipeline removes) — engines without it silently run the serial
        # loop. ``None`` resolves via the REPRO_ASYNC env knob (default
        # off) so whole suites flip it like the other serving matrices.
        if lookahead is None:
            lookahead = os.environ.get("REPRO_ASYNC", "0") == "1"
        self.lookahead = bool(lookahead)
        # fault injection for the rollback test harness: when set, called
        # at every speculative plan's validation with the committed
        # iteration index; returning True forces a rollback + replan (the
        # replanned iteration is NOT re-validated — forward progress)
        self.lookahead_fault = None
        # emulated per-iteration device latency (seconds), chained onto the
        # sampled-token future via io_callback: the saturation benchmark's
        # stand-in for an accelerator-bound dispatch gap on CPU-only hosts
        self._dispatch_delay = 0.0
        # client cancellation plane: a monotone, lock-guarded log of
        # req_ids. Plans record the log length they consumed up to; the
        # committed cursor only advances when the consuming plan commits,
        # so a rolled-back speculative plan re-applies the same entries on
        # replan and entries arriving mid-speculation invalidate it.
        self._cancel_list: List[int] = []
        self._cancel_lock = threading.Lock()
        self._cancel_cursor = 0
        self._seq_index: Dict[int, Sequence] = {}
        self._session = None
        # observability (repro.obs): ``tracer`` collects structured span/
        # instant events (request lifecycle, iteration phases, scheduler
        # decisions, allocator traffic) for Chrome-trace/JSONL export —
        # None resolves via the REPRO_TRACE env knob to the no-op
        # NULL_TRACER, whose hot-loop cost is one attribute check per
        # guarded call site. ``registry`` (a repro.obs.MetricsRegistry)
        # keeps Prometheus-exportable counters/gauges/histograms; None
        # disables that path entirely.
        self.tracer = tracer if tracer is not None else make_tracer()
        self.registry = registry
        self._deployed: Dict[int, object] = {}
        # deployed-param cost per budget row, computed ONCE (the seed redid
        # this O(rows) scan inside every routing call)
        self._cost_table = np.asarray(
            [FR.deployed_param_count(cfg, infos, table, k)
             for k in range(table.table.shape[0])], np.int64)
        self.router = BudgetRouter(self._cost_table)
        # live telemetry plane (repro.obs): ``watchdog`` (a Watchdog) is
        # ticked once per engine iteration with the loop's heartbeat
        # signals and captures a postmortem bundle when a rule fires;
        # ``costaudit`` accumulates measured dispatch seconds per
        # (row, batch-bucket) against the analytic cost model — pass an
        # instance, or True to build one against this engine's cost table
        self.watchdog = watchdog
        if costaudit is True:
            from repro.obs import CostModelAudit
            costaudit = CostModelAudit(cfg, self._cost_table,
                                       max_len=max_len, registry=registry)
        self.costaudit = costaudit
        # live-state handle for ``statusz()``: the serving loops park their
        # local scheduler/cache/batcher here so the status server can
        # snapshot them from its own thread mid-run
        self._live: Dict[str, object] = {}
        self._iterations = 0
        self.last_metrics: Optional[ServingMetrics] = None
        # the process's compile and GC record (installed once per process):
        # iterations are charged what ended inside their phases, and a
        # live tracer gets a ``compile`` instant per program
        self._compiles = compiles.install()
        self._compiles.engine_t = time.perf_counter()
        if self.tracer.enabled:
            self._compiles.subscribe(self.tracer)
        self._decode_jit = jax.jit(_named(
            "decode_step",
            lambda p, st, tok: tfm.decode_step(p, self.cfg, st, tok)))
        self._prefill_jit = jax.jit(_named(
            "prefill", lambda p, st, tok: tfm.prefill(p, self.cfg, st, tok)))
        # caches donated: K/V pools update in place instead of copying the
        # whole pool every step
        self._mixed_jit = jax.jit(_named(
            "paged_mixed_step",
            lambda p, caches, tok: tfm.paged_mixed_step(
                p, self.cfg, caches, tok, use_pallas=self.use_pallas)),
            donate_argnums=(1,))
        # verify forward for speculative rounds: ``tfm.paged_verify_step``
        # (k+1 scored positions per sequence) IS the mixed-step computation,
        # so sharing the jit object shares its compile cache — a row served
        # both speculatively and not compiles each width bucket once
        self._verify_jit = self._mixed_jit
        # device-resident sampling path: the fused forward + in-jit draw
        # returns int32 token ids only (probs variant feeds the speculative
        # draft phase, which keeps the warped q rows on device for the
        # accept test); the verify variant fuses Leviathan acceptance
        self._sample_jit = jax.jit(_named(
            "paged_sample_step",
            lambda p, caches, tok, sampling: dsamp.paged_sample_step(
                p, self.cfg, caches, tok, sampling,
                use_pallas=self.use_pallas)),
            donate_argnums=(1,))
        self._sample_probs_jit = jax.jit(_named(
            "paged_sample_probs_step",
            lambda p, caches, tok, sampling: dsamp.paged_sample_step(
                p, self.cfg, caches, tok, sampling,
                use_pallas=self.use_pallas, return_probs=True)),
            donate_argnums=(1,))
        self._verify_accept_jit = jax.jit(_named(
            "paged_verify_accept_step",
            lambda p, caches, tok, accept, chunk_sampling:
            dsamp.paged_verify_accept_step(
                p, self.cfg, caches, tok, accept, chunk_sampling,
                use_pallas=self.use_pallas)),
            donate_argnums=(1,))
        self._drain_sample_jit = jax.jit(_named(
            "sample_rows",
            lambda rows, sampling: dsamp.sample_rows(
                rows, sampling, use_pallas=self.use_pallas)))
        # identity on the sampled tokens, routed through a host callback
        # that sleeps ``_dispatch_delay`` seconds on the runtime thread
        # (GIL released) before the token future resolves — emulated
        # accelerator latency for the saturation benchmark. The callback is
        # a stable bound method so the jit cache holds one trace per shape.
        self._delay_jit = jax.jit(_named(
            "dispatch_delay",
            lambda t: io_callback(self._sleep_cb,
                                  jax.ShapeDtypeStruct(t.shape, t.dtype), t)))
        # pipelined feed fixup: patch a dispatch's token batch from the
        # previous iteration's unsynced device token vector in ONE jitted
        # call — eager scatter/gather dispatch here costs ~2ms/iteration
        # on CPU, more than the dispatch gap the pipeline hides (one trace
        # per fixup count, bounded by max_batch)
        self._fixup_jit = jax.jit(_named(
            "feed_fixup",
            lambda tok, pos, prev, rows: tok.at[0, pos].set(prev[rows])))

    # ------------------------------------------------------------ routing

    def _budget_row(self, budget: float) -> int:
        return self.router.route(budget)

    def _realize(self, row: int):
        """GAR-deploy the budget row (cached) — paper Algorithm 1 'deploy'."""
        if row not in self._deployed:
            self._deployed[row] = FR.gar_deploy(
                self.params_fact, self.cfg, self.infos, self.table, row)
        return self._deployed[row]

    def spec_draft_row(self, row: int) -> Optional[int]:
        """Draft row for serving ``row`` speculatively: the largest nested
        prefix row within ``spec.draft_rank`` of the full model, strictly
        below the target. ``None`` (speculation off for this row) when spec
        is unset or no smaller prefix row exists."""
        if self.spec is None:
            return None
        return FR.nested_prefix_row(self.table, row, self.spec.draft_rank,
                                    self._cost_table)

    # ------------------------------------------------------- cancellation

    def cancel(self, req_id: int) -> None:
        """Thread-safe, best-effort client cancellation. The engine applies
        it at the next plan boundary: a waiting request leaves its queue, a
        seated one frees its slot and blocks mid-flight, and an in-flight
        lookahead that already assumed the request rolls back. Tokens
        generated before the cancel take effect stay delivered; the request
        finishes with ``Result.cancelled = True``. Unknown or already
        finished ids are ignored."""
        with self._cancel_lock:
            self._cancel_list.append(int(req_id))

    def _sleep_cb(self, t):
        time.sleep(self._dispatch_delay)
        return t

    # ----------------------------------------------------------- generate

    def generate(self, requests: List[Request], *, mode: str = "auto",
                 metrics: Optional[ServingMetrics] = None) -> List[Result]:
        """Serve ``requests`` to completion. ``mode``: 'continuous' (paged
        cache + iteration-level batching; chunked prefill when the
        ``prefill_chunk`` knob is set), 'drain' (seed-style static batches),
        or 'auto' (continuous whenever the family supports it)."""
        if mode not in ("auto", "continuous", "drain"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "auto":
            mode = "continuous" if tfm.paged_compatible(self.cfg) else "drain"
        if mode == "drain":
            return self.generate_drain(requests)
        if not tfm.paged_compatible(self.cfg):
            raise ValueError(
                f"{self.cfg.name}: paged continuous batching covers "
                "attn/attn_dense stacks only (ROADMAP open item); "
                "use mode='drain' or 'auto'")
        return self._generate_continuous(requests, metrics=metrics)

    # ----------------------------------------- continuous batching path

    def _generate_continuous(self, requests: List[Request], *,
                             metrics: Optional[ServingMetrics] = None
                             ) -> List[Result]:
        metrics = metrics or ServingMetrics(tracer=self.tracer,
                                            registry=self.registry)
        self.last_metrics = metrics
        sched = Scheduler(self.router, tracer=self.tracer)
        self._live = {"sched": sched, "metrics": metrics}
        if self.watchdog is not None:
            self.watchdog.bind(
                tracer=self.tracer,
                trace_fn=(self.tracer.to_chrome if self.tracer.enabled
                          else None),
                state_fn=self.statusz, registry=self.registry)
        with self._cancel_lock:
            self._cancel_list = []
        self._cancel_cursor = 0
        self._seq_index = {}
        submitted = []
        for r in requests:
            if len(r.prompt) == 0:
                raise ValueError("empty prompt")
            seq = sched.submit(r)
            metrics.on_submit(seq.req_id)
            self._seq_index[seq.req_id] = seq
            submitted.append(seq)
        results: Dict[int, Result] = {}
        if self.prefill_chunk is None and self.spec is None:
            warnings.warn(
                "the full-prompt prefill path is retired: continuous "
                "serving without prefill_chunk now runs mixed iterations "
                "with a full-prompt-sized chunk (set prefill_chunk "
                "explicitly to silence this)", DeprecationWarning,
                stacklevel=3)
        while sched.has_waiting():
            row = sched.next_row()
            draft_row = self.spec_draft_row(row)
            if draft_row is not None:
                from repro.spec import SpecDecoder
                SpecDecoder(self, row=row, draft_row=draft_row,
                            spec=self.spec, sched=sched, metrics=metrics,
                            results=results).serve()
            else:
                self._serve_row_mixed(row, sched, metrics, results)
        return [results[s.req_id] for s in submitted]

    # ------------------------------------------- streaming session serving

    def serve_session(self, session, *,
                      metrics: Optional[ServingMetrics] = None,
                      idle_wait_s: float = 0.02) -> Dict[int, Result]:
        """Serve a live ``serving.session.StreamSession`` until it closes:
        requests arrive open-loop on the session's event loop, are drained
        into a persistent scheduler at commit boundaries, and every
        committed token streams back through the submitting client's
        ``StreamHandle`` as it lands. Runs on the caller's (worker) thread;
        returns the full req_id -> Result map when the session closes and
        the last in-flight request drains."""
        metrics = metrics or ServingMetrics(tracer=self.tracer,
                                            registry=self.registry)
        self.last_metrics = metrics
        sched = Scheduler(self.router, tracer=self.tracer)
        self._live = {"sched": sched, "metrics": metrics}
        if self.watchdog is not None:
            self.watchdog.bind(
                tracer=self.tracer,
                trace_fn=(self.tracer.to_chrome if self.tracer.enabled
                          else None),
                state_fn=self.statusz, registry=self.registry)
        with self._cancel_lock:
            self._cancel_list = []
        self._cancel_cursor = 0
        self._seq_index = {}
        results: Dict[int, Result] = {}
        self._session = session
        session.bind(self)
        try:
            while True:
                self._drain_intake(sched, metrics)
                if not sched.has_waiting():
                    if session.closed:
                        break
                    session.wait_for_work(idle_wait_s)
                    continue
                row = sched.next_row()
                draft_row = self.spec_draft_row(row)
                if draft_row is not None:
                    from repro.spec import SpecDecoder
                    SpecDecoder(self, row=row, draft_row=draft_row,
                                spec=self.spec, sched=sched,
                                metrics=metrics, results=results).serve()
                else:
                    self._serve_row_mixed(row, sched, metrics, results)
        finally:
            self._session = None
            session.mark_done()
        return results

    def _drain_intake(self, sched: Scheduler, metrics: ServingMetrics
                      ) -> None:
        """Pull newly submitted session requests into the scheduler. Called
        from commit boundaries and the idle loop ONLY — never inside a
        speculative plan, so rollback snapshots never race an arrival."""
        if self._session is None:
            return
        with profiling.annotate("engine.intake"):
            for request, handle in self._session.drain_new():
                if len(request.prompt) == 0:
                    raise ValueError("empty prompt")
                seq = sched.submit(request)
                metrics.on_submit(seq.req_id)
                self._seq_index[seq.req_id] = seq
                self._session.register(handle, seq.req_id)

    def _finish(self, seq: Sequence, metrics, results) -> None:
        metrics.on_finish(seq.req_id)
        tokens = np.concatenate([np.asarray(seq.request.prompt, np.int32),
                                 np.asarray(seq.generated, np.int32)])
        results[seq.req_id] = Result(
            tokens=tokens, budget_row=seq.row,
            deployed_params=self.router.deployed_params(seq.row),
            ttft_s=metrics.traces[seq.req_id].ttft)
        seq.state = "finished"
        if self._session is not None:
            self._session.finish(seq.req_id, results[seq.req_id])

    def _finish_cancelled(self, seq: Sequence, metrics, results) -> None:
        """Close out a cancelled request: its slot/queue position is already
        unwound by the planner; the Result keeps the prompt plus whatever
        was generated (and streamed) before the cancel took effect."""
        metrics.on_cancel(seq.req_id)
        tokens = np.concatenate([np.asarray(seq.request.prompt, np.int32),
                                 np.asarray(seq.generated, np.int32)])
        results[seq.req_id] = Result(
            tokens=tokens, budget_row=seq.row,
            deployed_params=self.router.deployed_params(seq.row),
            ttft_s=metrics.traces[seq.req_id].ttft, cancelled=True)
        seq.state = "finished"
        if self._session is not None:
            self._session.finish(seq.req_id, results[seq.req_id])

    def _block_holders(self, cache, batcher):
        """Seated sequences that actually own blocks — the only useful
        victims (evicting a zero-block mid-prefill seat frees nothing)."""
        return [s for s in batcher.active_sequences()
                if cache.slots[batcher.slot_of(s)].blocks]

    def _evict(self, victim, sched, cache, batcher, metrics,
               reason: str = "cache_pressure", plog=None) -> int:
        """Preempt one sequence: free its slot + blocks, re-queue at the row
        front for recompute. Returns the vacated slot. ``reason`` lands in
        the scheduler-decision trace event (the why of the preemption:
        ``cache_pressure`` — a decoding slot could not reserve its next
        token — ``prefill_pinned`` — every block was held by
        half-prefilled sequences and nothing could move — or
        ``rollback_recompute`` — an abandoned speculative dispatch wrote
        device K/V into a block this sequence holds after rollback). With a
        ``plog``, the metric/trace emissions defer to the plan's commit (a
        rolled-back plan's preemptions never surface); the state change
        itself is immediate either way."""
        vslot = batcher.slot_of(victim)
        vstate = victim.state                # requeue resets it to waiting
        batcher.leave(vslot)
        cache.free_slot(vslot)
        sched.requeue_front(victim)
        emit = (plog.emit if plog is not None
                else lambda fn, *a, **kw: fn(*a, **kw))
        emit(metrics.on_preempt, victim.req_id)
        if self.tracer.enabled:
            emit(self.tracer.instant,
                 "preempt", CAT_SCHED,
                 args={"req": victim.req_id, "slot": vslot, "reason": reason,
                       "policy": "youngest_first", "state": vstate})
        return vslot

    def _reserve_or_preempt(self, sched, cache, batcher, metrics, plog=None):
        """Reserve next-token room for every decoding slot; under cache
        pressure evict the youngest block-holding sequence (decoding OR
        mid-prefill; freed + re-queued for recompute) until the rest fit."""
        for slot in batcher.decode_slots():
            while (cache.token_append_needs_block(slot)
                   and cache.allocator.free_count == 0):
                victim = Scheduler.pick_victim(
                    self._block_holders(cache, batcher))
                if (victim is batcher.slots[slot]
                        and batcher.num_active == 1):
                    raise CacheOOM(
                        f"sequence {victim.req_id} alone exceeds the pool")
                vslot = self._evict(victim, sched, cache, batcher, metrics,
                                    reason="cache_pressure", plog=plog)
                if vslot == slot:
                    break                      # the appender itself was evicted
            seq = batcher.slots[slot]
            if seq is not None and seq.state == "decoding":
                cache.append_token(slot)

    # -------------------------------------------- live telemetry plane

    def _watchdog_tick(self, metrics: ServingMetrics, cache,
                       *, decoding: bool) -> None:
        """One per-iteration watchdog evaluation with the loop's cheap
        heartbeat signals (see obs/watchdog.py for the rules)."""
        self.watchdog.tick(
            progress_tokens=metrics.generated_tokens + metrics.prefill_tokens,
            decode_tokens=metrics.generated_tokens,
            decoding=decoding,
            metrics=metrics,
            fragmentation=cache.allocator.fragmentation(),
            free_blocks=cache.allocator.free_count,
            spec_accept_ewma=metrics.accept_ewma,
            spec_rounds=metrics.spec_rounds,
            prefix_stats=cache.stats if cache.prefix_cache else None)

    def statusz(self) -> dict:
        """Live engine snapshot for the ``/statusz`` endpoint and the
        watchdog's postmortem ``state.json``: per-request lifecycle
        states, per-row queue depths, KV occupancy/fragmentation, prefix
        cache hit rate, and adaptive-k state. Built to be called from the
        status-server thread while the engine runs — live structures are
        read best-effort (list-copied before iteration; any race that
        still slips through marks the snapshot ``partial`` instead of
        failing the scrape)."""
        out: Dict[str, object] = {
            "engine": {
                "arch": self.cfg.name,
                "max_batch": self.max_batch, "max_len": self.max_len,
                "block_size": self.block_size,
                "prefill_chunk": self.prefill_chunk,
                "token_budget": self.token_budget,
                "device_sampling": self.device_sampling,
                "prefix_cache": self.prefix_cache,
                "rows": len(self._cost_table),
                "row_params": self._cost_table.tolist(),
                "spec": None if self.spec is None else {
                    "draft_rank": self.spec.draft_rank,
                    "spec_len": self.spec.spec_len,
                    "adaptive_k": self.spec.adaptive_k},
            },
            "iterations": self._iterations,
        }
        try:
            live = dict(self._live)
            metrics = live.get("metrics") or self.last_metrics
            if metrics is not None:
                reqs = {}
                for req_id, tr in list(metrics.traces.items()):
                    state = ("finished" if tr.finish_t is not None
                             else "decoding" if tr.first_token_t is not None
                             else "prefilling" if tr.admit_t is not None
                             else "waiting")
                    reqs[req_id] = {
                        "state": state, "new_tokens": tr.new_tokens,
                        "preemptions": tr.preemptions,
                        "prefix_hit_tokens": tr.prefix_hit_tokens,
                        "ttft_s": tr.ttft}
                out["requests"] = reqs
                out["progress"] = {
                    "generated_tokens": metrics.generated_tokens,
                    "prefill_tokens": metrics.prefill_tokens,
                    "preemptions": metrics.preemptions,
                    "spec_rounds": metrics.spec_rounds,
                    "spec_accept_ewma": metrics.accept_ewma}
            sched = live.get("sched")
            if sched is not None:
                out["queues"] = {row: len(q)
                                 for row, q in list(sched.queues.items())}
            cache = live.get("cache")
            if cache is not None:
                out["serving_row"] = live.get("row")
                out["speculating"] = live.get("spec")
                out["kv"] = cache.statusz()
            batcher = live.get("batcher")
            if batcher is not None:
                out["adaptive_k"] = {
                    s.req_id: {"k": s.spec_k,
                               "accept_ewma": s.spec_accept_ewma}
                    for s in list(batcher.active_sequences())}
        except Exception as e:       # racing the engine thread; keep what
            out["partial"] = repr(e)  # rendered and say so
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.statusz()
        if self.costaudit is not None:
            out["costaudit"] = self.costaudit.statusz()
        out["compiles"] = self._compiles.statusz()
        return out

    # ------------------------------ chunked prefill / mixed iterations

    def _bucket_tokens(self, used: int, budget: Optional[int] = None) -> int:
        """Flat-batch width bucket: smallest power of two >= used (floor 8),
        capped at the token budget — O(log budget) jit traces, and pure
        decode iterations don't pay for unused prefill budget. ``budget``
        overrides ``self._mixed_budget`` (the spec decoder carries its own)."""
        if budget is None:
            budget = self._mixed_budget
        t = 8
        while t < used:
            t *= 2
        return min(t, max(budget, used))

    def _serve_row_mixed(self, row: int, sched: Scheduler,
                         metrics: ServingMetrics,
                         results: Dict[int, Result]) -> None:
        """One budget row's chunked-prefill loop: every iteration advances
        the whole decode batch by one token and pushes FIFO prompt chunks
        through the same fused forward, under ``token_budget`` tokens.

        Token emission is device-resident by default: the forward gathers
        only the sample positions (decode slots + finishing chunks) for the
        LM head and samples in-jit, so each iteration transfers int32 token
        ids only. ``device_sampling=False`` keeps the host oracle: the
        gathered ``[S, vocab]`` rows ship to the host, greedy argmaxes just
        those rows on device, stochastic rows draw off the sequential
        sampler stream (PR-4 bit-identical).

        Two drivers share one planner (``_plan_iteration``): the serial loop
        (plan -> dispatch -> sync -> commit, the PR-2 semantics), and —
        with ``lookahead`` set and device sampling on — the one-iteration
        pipeline that dispatches iteration ``i+1`` from speculatively
        advanced host state before syncing and committing ``i``."""
        with profiling.annotate("engine.row_setup"):
            params = self._realize(row)
            cache = PagedKVCache(self.cfg, max_batch=self.max_batch,
                                 max_len=self.max_len,
                                 block_size=self.block_size,
                                 num_blocks=self.num_blocks,
                                 prefix_cache=self.prefix_cache)
            cache.tracer = self.tracer
            batcher = ContinuousBatcher(self.max_batch)
        self._live.update(row=row, cache=cache, batcher=batcher, spec=False)
        try:
            if self.lookahead and self.device_sampling:
                self._serve_row_pipelined(row, params, sched, cache, batcher,
                                          metrics, results)
            else:
                self._serve_row_sync(row, params, sched, cache, batcher,
                                     metrics, results)
        finally:
            # the row drained: free its pools now, not when the cyclic GC
            # reaches the cache (its allocator's evict hook points back at
            # it), so a later row's pools never sit beside them
            cache.pools = []

    def _apply_cancellations(self, sched, cache, batcher, plog) -> int:
        """Apply every uncommitted cancellation-log entry: a waiting request
        leaves its row queue, a seated one frees its slot and blocks
        mid-flight; both finish with ``Result.cancelled``. Unknown, already
        finished, or already unwound ids are ignored — entries are applied
        idempotently, because a speculative plan's consumption only commits
        with the plan (the committed cursor advances at commit, so a
        rolled-back or still-in-flight plan's entries are re-applied by the
        next plan and naturally no-op the second time). Returns the log
        length consumed (the plan's ``cancel_cursor``)."""
        with self._cancel_lock:
            n = len(self._cancel_list)
            entries = self._cancel_list[self._cancel_cursor: n]
        for req_id in entries:
            seq = self._seq_index.get(req_id)
            if seq is None or seq.state == "finished":
                continue
            if sched.remove_waiting(seq):
                plog.cancel_finish(seq)
                continue
            for slot, s in enumerate(batcher.slots):
                if s is seq:
                    batcher.leave(slot)
                    cache.free_slot(slot)
                    plog.cancel_finish(seq)
                    break
        if not plog.deferred:
            self._cancel_cursor = n
        return n

    def _plan_iteration(self, row: int, sched, cache, batcher,
                        metrics, plog) -> _MixedPlan:
        """One mixed iteration's scheduling half, shared by both drivers:
        apply cancellations, seat waiting requests (probing the prefix
        cache), reserve decode room (preempting under pressure), plan the
        FIFO prompt chunks, and pick the sample rows. All metric/trace/
        finish emissions route through ``plog`` — immediate in the serial
        driver, deferred to commit in the pipeline. State changes (seats,
        blocks, preemptions) are applied eagerly; the pipelined driver
        snapshots around this call and rolls them back when the speculation
        loses. Returns an ``empty`` plan when the row drained."""
        tr = self.tracer
        plan = _MixedPlan(plog)
        while True:
            plan.cancel_cursor = self._apply_cancellations(
                sched, cache, batcher, plog)
            # admission: seat waiting requests; blocks arrive per chunk
            for slot in batcher.free_slots():
                if not sched.has_waiting(row):
                    break
                seq = sched.pop(row)
                plog.emit(metrics.on_admit, seq.req_id)
                if tr.enabled:
                    plog.emit(tr.instant, "admit", CAT_SCHED,
                              args={"req": seq.req_id, "row": row,
                                    "slot": slot, "reason": "slot_free",
                                    "attempt": seq.admissions})
                if seq.request.max_new_tokens <= 0:
                    plog.finish(seq)
                    continue
                if seq.prompt_len > self.max_len:
                    raise CacheOOM(f"sequence of {seq.prompt_len} tokens "
                                   f"exceeds max_len {self.max_len}")
                cache.open_slot(slot)
                # prefix-cache probe: any full prompt blocks already
                # resident map straight into the slot, and prefill resumes
                # past them (a full hit leaves exactly the final chunk)
                hit = cache.probe_prefix(slot, seq.request.prompt)
                if hit:
                    seq.prefill_pos = hit
                    plog.emit(metrics.on_prefix_hit, seq.req_id, hit,
                              cache.cached_blocks)
                plan.admissions.append((seq, hit))
                batcher.seat_prefill(slot, seq)
            if batcher.num_active == 0:
                return plan                  # row drained (all slots free)

            # decode priority: reserve next-token room before any prefill
            self._reserve_or_preempt(sched, cache, batcher, metrics,
                                     plog=plog)
            decode_slots = batcher.decode_slots()

            # FIFO chunk plan under the leftover budget, clipped to what the
            # free list can actually cover right now
            budget_left = self._mixed_budget - len(decode_slots)
            prefilling = [batcher.slots[s] for s in batcher.prefill_slots()]
            chunks = []                      # (slot, seq, start, n)
            for seq, want in Scheduler.plan_prefill_chunks(
                    prefilling, budget_left, self._chunk,
                    order=self.prefill_order):
                slot = batcher.slot_of(seq)
                got = cache.extend_slot(slot, want, clip=True)
                if got:
                    chunks.append((slot, seq, seq.prefill_pos, got))

            if not decode_slots and not chunks:
                if batcher.num_active == 0:
                    continue                 # everyone was preempted
                self._unstick(sched, cache, batcher, metrics, plog=plog)
                continue
            break

        # sample plan: only decode slots and finishing chunks ever have
        # their next-token distribution read — mid-chunk prompt tokens
        # get no LM-head row at all (sample-position gather)
        sample_ids, metas = [], []
        for i, slot in enumerate(decode_slots):
            seq = batcher.slots[slot]
            sample_ids.append(i)
            metas.append((seq.sampler, DRAW_TARGET,
                          seq.prompt_len + len(seq.generated)))
            plan.decode_seqs.append(seq)
        flat = len(decode_slots)
        finish_rows: Dict[int, int] = {}
        for slot, seq, start, n in chunks:
            if start + n == seq.prompt_len:
                finish_rows[slot] = len(sample_ids)
                sample_ids.append(flat + n - 1)
                metas.append((seq.sampler, DRAW_TARGET, seq.prompt_len))
            flat += n
        plan.empty = False
        plan.decode_slots = decode_slots
        plan.chunks = chunks
        plan.sample_ids = sample_ids
        plan.metas = metas
        plan.finish_rows = finish_rows
        plan.total_chunk = sum(n for _, _, _, n in chunks)
        return plan

    def _serve_row_sync(self, row: int, params, sched, cache, batcher,
                        metrics: ServingMetrics,
                        results: Dict[int, Result]) -> None:
        """The serial driver: plan, dispatch, sync, commit — byte-identical
        event order and token streams to the pre-pipeline loop."""
        plog = _ImmediateLog(self, metrics, results)
        while True:
            with profiling.step("engine.iteration", self._iterations):
                if not self._serial_iteration(row, params, sched, cache,
                                              batcher, metrics, results,
                                              plog):
                    return

    def _serial_iteration(self, row: int, params, sched, cache, batcher,
                          metrics: ServingMetrics, results: Dict[int, Result],
                          plog: _ImmediateLog) -> bool:
        """One iteration of the serial loop, recorded in an ``Iteration``;
        False when the row drained."""
        tr = self.tracer
        rec = Iteration(t_plan=metrics.now())
        mark = self._compiles.mark()
        self._drain_intake(sched, metrics)
        with profiling.annotate("engine.plan"):
            plan = self._plan_iteration(row, sched, cache, batcher,
                                        metrics, plog)
        if plan.empty:
            return False
        decode_slots, chunks = plan.decode_slots, plan.chunks
        self._size_record(rec, plan)
        disp0 = metrics.now()
        if tr.enabled:
            tr.complete("plan", CAT_ITER, rec.t_plan, disp0,
                        args={"decode": len(decode_slots),
                              "chunks": len(chunks)})
        with profiling.annotate("engine.enqueue"):
            out = self._dispatch_mixed(
                params, cache, batcher, decode_slots, chunks,
                plan.sample_ids,
                plan.metas if self.device_sampling else None)
        rec.t_enqueue = rec.t_planned = rec.t_sync0 = metrics.now()
        with profiling.annotate("engine.sync"):
            if self.device_sampling:
                logits, sampled = None, np.asarray(out)
            else:
                # greedy fast path: argmax only the gathered sample rows,
                # never the full flat-token batch
                logits = out
                sampled = np.array(jnp.argmax(logits[0], axis=-1), np.int32)
        rec.t_sync1 = metrics.now()
        disp_s = rec.t_sync1 - disp0
        emit0 = self._emit_s()
        metrics.open_iteration(rec)
        with profiling.annotate("engine.commit"):
            total_chunk = self._commit_serial(plan, sampled, logits, cache,
                                              batcher, metrics, results)
        rec.t_commit = it1 = metrics.now()
        rec.emit_s = self._emit_s() - emit0
        self._charge(rec, mark)
        metrics.on_iteration_timing(disp_s, it1 - rec.t_plan - disp_s)
        if tr.enabled:
            tr.complete("dispatch", CAT_ITER, disp0, disp0 + disp_s,
                        args={"sample_rows": len(plan.sample_ids)})
            tr.complete("commit", CAT_ITER, disp0 + disp_s, it1,
                        args={"decode": len(decode_slots),
                              "prefill": total_chunk})
        if self.registry is not None:
            metrics.on_cache_stats(cache.allocator.free_count,
                                   cache.allocator.fragmentation(),
                                   prefix=cache.stats)
            metrics.on_queue_depths(
                {r: len(q) for r, q in sched.queues.items()})
        self._iterations += 1
        if self.costaudit is not None:
            self.costaudit.observe(row, rec.width, disp_s)
        if self.watchdog is not None:
            self._watchdog_tick(metrics, cache, decoding=bool(decode_slots))
        return True

    def _commit_serial(self, plan: _MixedPlan, sampled, logits, cache,
                       batcher, metrics: ServingMetrics,
                       results: Dict[int, Result]) -> int:
        """The serial loop's commit: decode tokens, then prefill chunks
        (a finishing chunk's first generated token sits at its reserved
        sample row), each token streamed as it lands. Returns the prompt
        tokens committed."""
        decode_slots = plan.decode_slots
        # commit decodes first: `advance` must only see sequences that
        # actually decoded this iteration, not freshly flipped ones
        sampled_b = np.zeros(self.max_batch, np.int32)
        for i, slot in enumerate(decode_slots):
            seq = batcher.slots[slot]
            if logits is not None and not seq.sampler.greedy:
                sampled[i] = seq.sampler.sample(np.asarray(logits[0, i]))
            sampled_b[slot] = sampled[i]
            metrics.on_token(seq.req_id)
            if self._session is not None:
                self._session.emit(seq.req_id, len(seq.generated),
                                   int(sampled[i]))
        for slot in batcher.advance(sampled_b):
            seq = batcher.leave(slot)
            cache.free_slot(slot)
            self._finish(seq, metrics, results)

        total_chunk = 0
        for slot, seq, start, n in plan.chunks:
            seq.prefill_pos = start + n
            total_chunk += n
            metrics.on_prefill_chunk(n)
            # the chunk's K/V is on device now — index every prompt
            # block it completed so later admissions can share it
            cache.register_prefix(slot, seq.request.prompt,
                                  seq.prefill_pos)
            if seq.prefill_pos == seq.prompt_len:
                metrics.on_prefill_end(seq.req_id)
                ri = plan.finish_rows[slot]
                first = int(sampled[ri])
                if logits is not None and not seq.sampler.greedy:
                    first = seq.sampler.sample(np.asarray(logits[0, ri]))
                if self._session is not None:
                    self._session.emit(seq.req_id, len(seq.generated),
                                       first)
                seq.generated.append(first)
                metrics.on_first_token(seq.req_id)
                if seq.done:             # max_new_tokens == 1
                    batcher.leave(slot)
                    cache.free_slot(slot)
                    self._finish(seq, metrics, results)
                else:
                    batcher.to_decoding(slot, first)
        metrics.on_mixed_step(len(decode_slots), total_chunk,
                              cache.occupancy())
        return total_chunk

    # --------------------------------------------- the iteration record

    def _size_record(self, rec: Iteration, plan: _MixedPlan) -> None:
        rec.sampled_rows = len(plan.sample_ids)
        rec.width = self._bucket_tokens(len(plan.decode_slots)
                                        + plan.total_chunk)

    def _emit_s(self) -> float:
        return self._session.emit_s if self._session is not None else 0.0

    def _charge(self, rec: Iteration, mark) -> None:
        """Charge ``rec`` with the compiles and GC seconds the process
        recorded since ``mark``."""
        events, gc_s = self._compiles.since(mark)
        rec.gc_s += gc_s
        if events:
            rec.compiles += len(events)
            rec.compile_s += sum(c.seconds for c in events)
            rec.compile_funs += tuple(c.fun_name for c in events)

    # ------------------------------------- one-iteration-lookahead pipeline

    def _session_emit(self, seq: Sequence, idx: int) -> None:
        """Deferred per-token stream emission: runs at the owning plan's
        commit, AFTER ``_commit_apply`` patched the placeholder at
        ``generated[idx]`` with the real sampled value."""
        if self._session is not None:
            self._session.emit(seq.req_id, idx, int(seq.generated[idx]))

    def _advance_predicted(self, plan: _MixedPlan, cache, batcher,
                           metrics) -> None:
        """Apply the planned iteration's commit to host state NOW, with
        placeholder token 0 everywhere a sampled value would go, recording
        patch lists for the real commit. The prediction is *exact* in
        control flow: finishes are count-based (``max_new_tokens``, no stop
        tokens anywhere in this engine), preemption and block accounting
        never depend on token values, and prompt-block prefix registration
        hashes prompt tokens only — the commit merely patches values into
        ``generated``/feeds and flushes the deferred emissions."""
        plog = plan.plog
        sampled_b = np.zeros(self.max_batch, np.int32)
        for i, slot in enumerate(plan.decode_slots):
            seq = plan.decode_seqs[i]
            plan.gen_patches.append((seq, len(seq.generated), i))
            plog.emit(metrics.on_token, seq.req_id)
            plog.emit(self._session_emit, seq, len(seq.generated))
        for slot in batcher.advance(sampled_b):
            seq = batcher.leave(slot)
            cache.free_slot(slot)
            plog.finish(seq)
        # surviving decode slots were fed placeholder 0 by ``advance``; the
        # next plan's dispatch patches its copies from this iteration's
        # device token vector (``_feed_fixups``) and the commit re-feeds the
        # real host value
        for i, slot in enumerate(plan.decode_slots):
            if batcher.slots[slot] is plan.decode_seqs[i]:
                plan.feed_rows[slot] = (plan.decode_seqs[i], i)

        for slot, seq, start, n in plan.chunks:
            seq.prefill_pos = start + n
            plog.emit(metrics.on_prefill_chunk, n)
            # prompt-prefix registration is value-exact at plan time (it
            # hashes prompt tokens; the block K/V lands when the already
            # enqueued dispatch executes, strictly before any later
            # dispatch could read it through a hit)
            cache.register_prefix(slot, seq.request.prompt, seq.prefill_pos)
            if seq.prefill_pos == seq.prompt_len:
                plog.emit(metrics.on_prefill_end, seq.req_id)
                ri = plan.finish_rows[slot]
                idx = len(seq.generated)
                plan.gen_patches.append((seq, idx, ri))
                plog.emit(self._session_emit, seq, idx)
                seq.generated.append(0)      # placeholder first token
                plog.emit(metrics.on_first_token, seq.req_id)
                if seq.done:                 # max_new_tokens == 1
                    batcher.leave(slot)
                    cache.free_slot(slot)
                    plog.finish(seq)
                else:
                    batcher.to_decoding(slot, 0)
                    plan.feed_rows[slot] = (seq, ri)
        plog.emit(metrics.on_mixed_step, len(plan.decode_slots),
                  plan.total_chunk, cache.occupancy())

    @staticmethod
    def _feed_fixups(plan: _MixedPlan, pending: _MixedPlan) -> List[tuple]:
        """Device-side token patches for ``plan``'s dispatch: every decode
        entry whose host feed is still ``pending``'s placeholder takes its
        real value from ``pending``'s (unsynced) device token vector.
        Returns ``(flat position in plan's token batch, sample row in
        pending's token vector)`` pairs — decode entries occupy flat
        positions ``0..len(decode_slots)-1`` in dispatch order."""
        fixups = []
        for i, slot in enumerate(plan.decode_slots):
            pf = pending.feed_rows.get(slot)
            if pf is not None and pf[0] is plan.decode_seqs[i]:
                fixups.append((i, pf[1]))
        return fixups

    def _snapshot_row(self, sched, cache, batcher) -> dict:
        """Double-buffered host state for one speculative plan: scheduler
        queues (all rows — cancellation can touch any), cache bookkeeping
        (pools excluded; see ``PagedKVCache.snapshot``), batcher seats, and
        every reachable Sequence's mutable fields."""
        seqs = {s.req_id: s for s in batcher.active_sequences()}
        for q in sched.queues.values():
            for s in q:
                seqs[s.req_id] = s
        return {"sched": sched.snapshot(), "cache": cache.snapshot(),
                "batcher": batcher.snapshot(),
                "seqs": [(s, s.snapshot()) for s in seqs.values()]}

    def _restore_row(self, snap: dict, sched, cache, batcher) -> None:
        sched.restore(snap["sched"])
        cache.restore(snap["cache"])
        batcher.restore(snap["batcher"])
        for s, ss in snap["seqs"]:
            s.restore(ss)

    def _commit_apply(self, plan: _MixedPlan, batcher) -> None:
        """Patch the committed iteration's real sampled values into host
        state: ``generated`` placeholders and next-token feeds. Guarded for
        idempotent replay after a rollback restored older state — a patch
        only applies where the placeholder still exists (an index past
        ``generated`` means the sequence was reset for recompute; a slot
        holding a different sequence means it was unwound)."""
        sampled = plan.sampled
        for seq, idx, row in plan.gen_patches:
            if idx < len(seq.generated):
                seq.generated[idx] = int(sampled[row])
        for slot, (seq, row) in plan.feed_rows.items():
            if batcher.slots[slot] is seq and seq.state == "decoding":
                batcher.feed(slot, int(sampled[row]))

    def _commit_iteration(self, pending: _MixedPlan, batcher,
                          metrics: ServingMetrics) -> None:
        """Sync the pending iteration's device tokens (the pipeline's ONLY
        host<->device sync) and commit it: patch real values in, advance
        the committed cancellation cursor, flush the deferred emissions."""
        rec = pending.rec
        mark = self._compiles.mark()
        rec.t_sync0 = metrics.now()
        with profiling.annotate("engine.sync"):
            pending.sampled = np.asarray(pending.tokens_dev)
        rec.t_sync1 = metrics.now()
        emit0 = self._emit_s()
        metrics.open_iteration(rec)
        with profiling.annotate("engine.commit"):
            self._commit_apply(pending, batcher)
            self._cancel_cursor = max(self._cancel_cursor,
                                      pending.cancel_cursor)
            pending.plog.flush()
        rec.t_commit = metrics.now()
        rec.emit_s = self._emit_s() - emit0
        self._charge(rec, mark)

    def _validate_speculation(self, plan: _MixedPlan,
                              cache) -> Optional[str]:
        """Did the just-committed iteration invalidate the in-flight
        speculative plan? Returns a rollback reason or None. Checks, in
        order: forced fault injection (the test harness hook), cancellation
        entries that arrived after the plan consumed the log (rolling back
        lets them take effect one iteration sooner), and prefix-hit drift —
        an admission that would hit more cached prompt blocks if re-probed
        now (defensive: registration is plan-time-eager, so drift requires
        an index mutation outside the planner)."""
        if (self.lookahead_fault is not None
                and self.lookahead_fault(self._iterations)):
            return "fault_injection"
        with self._cancel_lock:
            n = len(self._cancel_list)
        if n > plan.cancel_cursor:
            return "cancellation"
        for seq, hit in plan.admissions:
            if (seq.state == "prefilling"
                    and cache.peek_prefix(seq.request.prompt) > hit):
                return "prefix_drift"
        return None

    def _rollback(self, snap: dict, touched: List[int],
                  pending: Optional[_MixedPlan], sched, cache, batcher,
                  metrics: ServingMetrics, reason: str) -> None:
        """Unwind a lost speculation: restore the pre-plan snapshot, then
        repair what cannot be restored — the abandoned dispatch already
        WROTE device K/V into every block it allocated (``touched``), so
        those blocks' prefix-index entries drop and any restored sequence
        still holding one is evicted for recompute (identity-preserving:
        recompute replays the same tokens). Finally replay the committed
        iteration's value patches, which the restore undid (its emissions
        already flushed and stay flushed)."""
        if pending is not None:
            pending.rec.rollback = reason
        self._restore_row(snap, sched, cache, batcher)
        for b in touched:
            cache._unregister_block(b)
        if touched:
            tset = set(touched)
            for slot, seq in enumerate(batcher.slots):
                st = cache.slots[slot]
                if (seq is not None and st is not None
                        and not tset.isdisjoint(st.blocks)):
                    self._evict(seq, sched, cache, batcher, metrics,
                                reason="rollback_recompute")
        if pending is not None:
            self._commit_apply(pending, batcher)
        metrics.on_rollback(reason)
        if self.tracer.enabled:
            self.tracer.instant(
                "rollback", CAT_ITER,
                args={"reason": reason, "iter": self._iterations,
                      "touched": len(touched)})

    def _finalize_iteration(self, row: int, pending: _MixedPlan, sched,
                            cache, metrics: ServingMetrics) -> None:
        """Per-committed-iteration bookkeeping for the pipelined driver:
        the dispatch/host timing split (``dispatch_s`` is only the visible
        sync wait; host work that ran under the in-flight dispatch is
        ``overlap_s``), trace spans anchored at the real enqueue/sync
        times, registry stats, cost-model audit, watchdog heartbeat."""
        tr = self.tracer
        rec = pending.rec
        sync_s = rec.t_sync1 - rec.t_sync0
        overlap_s = max(0.0, rec.t_sync0 - rec.t_enqueue)
        host_s = (rec.t_planned - rec.t_plan) + (rec.t_commit - rec.t_sync1)
        metrics.on_iteration_timing(sync_s, host_s, overlap_s=overlap_s)
        if tr.enabled:
            tr.complete("dispatch", CAT_ITER, rec.t_enqueue, rec.t_sync1,
                        args={"sample_rows": len(pending.sample_ids),
                              "overlap_s": round(overlap_s, 6)})
            tr.complete("commit", CAT_ITER, rec.t_sync1, rec.t_commit,
                        args={"decode": len(pending.decode_slots),
                              "prefill": pending.total_chunk})
        if self.registry is not None:
            metrics.on_cache_stats(cache.allocator.free_count,
                                   cache.allocator.fragmentation(),
                                   prefix=cache.stats)
            metrics.on_queue_depths(
                {r: len(q) for r, q in sched.queues.items()})
        self._iterations += 1
        if self.costaudit is not None:
            # estimated device time: visible sync wait plus the host work
            # the dispatch ran under
            self.costaudit.observe(row, rec.width, sync_s + overlap_s)
        if self.watchdog is not None:
            self._watchdog_tick(metrics, cache,
                                decoding=bool(pending.decode_slots))

    def _serve_row_pipelined(self, row: int, params, sched, cache, batcher,
                             metrics: ServingMetrics,
                             results: Dict[int, Result]) -> None:
        """The one-iteration-lookahead driver. Each loop turn plans and
        *dispatches* iteration ``i+1`` from speculatively advanced host
        state while the device still runs iteration ``i``, then syncs and
        commits ``i`` and validates the speculation:

            plan i+1  ->  dispatch i+1 (chained on i's device tokens)
                      ->  predicted advance of host state (placeholders)
                      ->  sync + commit i  ->  validate i+1
                      ->  [rollback + replan on a lost race]

        Dispatch ``i+1`` feeds ``i``'s sampled tokens *on device* (feed
        fixups gather from the unsynced token vector), so the host never
        waits for ``i`` before launching ``i+1`` — planning and commit run
        entirely in the dispatch gap. Token streams are bit-identical to
        the serial driver: the planner is shared, control flow never
        depends on token values (count-based finishes), and keyed device
        PRNG draws depend only on (seed, req, purpose, position). New
        session arrivals are drained at commit boundaries only, after
        validation, so a rollback can never lose an admission."""
        tr = self.tracer
        pending: Optional[_MixedPlan] = None
        snap = None
        while True:
            with profiling.step("engine.iteration", self._iterations):
                speculating = pending is not None
                if speculating:
                    snap = self._snapshot_row(sched, cache, batcher)
                    cache.allocator.begin_alloc_log()
                    metrics.on_lookahead()
                plog = _DeferredLog(self, metrics, results)
                rec = Iteration(t_plan=metrics.now())
                mark = self._compiles.mark()
                with profiling.annotate("engine.lookahead" if speculating
                                        else "engine.plan"):
                    plan = self._plan_iteration(row, sched, cache, batcher,
                                                metrics, plog)
                    plan.rec = rec
                    if not plan.empty:
                        self._size_record(rec, plan)
                        fixups = (self._feed_fixups(plan, pending)
                                  if speculating else [])
                        with profiling.annotate("engine.enqueue"):
                            plan.tokens_dev = self._dispatch_mixed_async(
                                params, cache, batcher, plan,
                                pending.tokens_dev if speculating else None,
                                fixups)
                        rec.t_enqueue = metrics.now()
                        self._advance_predicted(plan, cache, batcher,
                                                metrics)
                rec.t_planned = metrics.now()
                self._charge(rec, mark)
                if tr.enabled:
                    # every "lookahead" span ends in exactly one
                    # "lookahead_commit" or "rollback" instant (CI invariant)
                    tr.complete("lookahead" if speculating else "plan",
                                CAT_ITER, rec.t_plan, rec.t_planned,
                                args={"decode": len(plan.decode_slots),
                                      "chunks": len(plan.chunks),
                                      "empty": plan.empty})
                if speculating:
                    self._commit_iteration(pending, batcher, metrics)
                    reason = self._validate_speculation(plan, cache)
                    touched = cache.allocator.end_alloc_log()
                    if reason is None:
                        if tr.enabled:
                            tr.instant("lookahead_commit", CAT_ITER,
                                       args={"iter": self._iterations})
                    else:
                        # the thrown-away plan's record is dropped; what
                        # stalled its planning turn held up this commit
                        pr = pending.rec
                        pr.compiles += rec.compiles
                        pr.compile_s += rec.compile_s
                        pr.compile_funs += rec.compile_funs
                        pr.gc_s += rec.gc_s
                        with profiling.annotate("engine.rollback"):
                            self._rollback(snap, touched, pending, sched,
                                           cache, batcher, metrics, reason)
                    self._finalize_iteration(row, pending, sched, cache,
                                             metrics)
                    pending = None
                    if reason is not None:
                        self._drain_intake(sched, metrics)
                        continue             # replan from committed state
                self._drain_intake(sched, metrics)
                if plan.empty:
                    plan.plog.flush()        # cancel/zero-token finishes
                    break
                pending = plan

    @staticmethod
    def _pack_flat(entries, width: int, null_slot: int):
        """Flat-token layout shared by the mixed and speculative paths:
        ``entries`` are (slot, tokens, start) runs — ``tokens`` land at
        positions ``start..start+n-1`` of ``slot``'s sequence; pads point
        ``slot_ids`` at ``null_slot`` (a block-table row of null blocks) so
        their reads/writes never touch a live sequence."""
        tok = np.zeros(width, np.int32)
        sid = np.full(width, null_slot, np.int32)
        pos = np.zeros(width, np.int32)
        i = 0
        for slot, toks, start in entries:
            n = len(toks)
            tok[i: i + n] = toks
            sid[i: i + n] = slot
            pos[i: i + n] = np.arange(start, start + n, dtype=np.int32)
            i += n
        return tok, sid, pos

    @staticmethod
    def _bucket_rows(n: int) -> int:
        """Sample-row width bucket (power of two, floor 4) — O(log B) jit
        traces over the gathered LM-head width."""
        t = 4
        while t < n:
            t *= 2
        return t

    @staticmethod
    def _pack_sample_ids(sample_ids, width: int) -> np.ndarray:
        """Gather indices padded to ``width``; pads score flat token 0 and
        are discarded host-side (keyed draws are stateless, so the wasted
        pad draws cannot disturb any sequence's stream)."""
        out = np.zeros(width, np.int32)
        out[: len(sample_ids)] = sample_ids
        return out

    @staticmethod
    def _sampler_fields(sampler, temp, topk, seed, req, i: int) -> None:
        """Write one non-greedy sampler's device knobs into row ``i`` of
        the packed operand arrays — the ONE place the host sampler's key
        is exported to the device keying (mixed iterations and speculative
        accept operands must agree bitwise, or cross-engine token identity
        breaks). The seed keeps its low 32 bits (int32 view; the host
        generator rejects negatives, so user seeds are non-negative and
        collisions need seeds 2^32 apart)."""
        temp[i] = sampler.params.temperature
        topk[i] = sampler.params.top_k
        seed[i] = np.int64(sampler.seed).astype(np.uint32).view(np.int32)
        req[i] = sampler.req_id

    @staticmethod
    def _pack_sampling(metas, width: int) -> Dict:
        """Device-sampling operands for ``width`` gathered rows. ``metas``:
        one ``(sampler, purpose, position)`` per live row, aligned with
        ``sample_ids``. Greedy rows carry temperature 0 (in-jit argmax) and
        top-k 0 (no truncation). ``top_k`` is an array even when no row
        truncates: a None there is a second program for every step shape,
        compiled where a batch first samples only greedy rows. Such a batch
        skips the top-k threshold at run time (``ops.topk_threshold``)."""
        temp = np.zeros(width, np.float32)
        topk = np.zeros(width, np.int32)
        seed = np.zeros(width, np.int32)
        req = np.zeros(width, np.int32)
        purpose = np.zeros(width, np.int32)
        pos = np.zeros(width, np.int32)
        for i, (sampler, pur, p) in enumerate(metas):
            if not sampler.greedy:
                ElasticEngine._sampler_fields(sampler, temp, topk, seed,
                                              req, i)
            purpose[i] = pur
            pos[i] = p
        return {
            "temperature": jnp.asarray(temp),
            "top_k": jnp.asarray(topk),
            "seed": jnp.asarray(seed), "req_id": jnp.asarray(req),
            "purpose": jnp.asarray(purpose), "position": jnp.asarray(pos),
        }

    def _build_mixed_operands(self, cache, batcher, decode_slots, chunks,
                              sample_ids):
        """Shared dispatch-operand builder: the flat token batch (decode
        tokens then chunks, padded to a width bucket), its slot/position
        maps, block tables, pools, and the padded sample-row gather.
        Returns ``(tok, caches, rows)``."""
        entries = [(slot, [batcher.next_token(slot)],
                    cache.slots[slot].num_tokens - 1)
                   for slot in decode_slots]
        entries += [(slot, np.asarray(seq.request.prompt[start: start + n],
                                      np.int32), start)
                    for slot, seq, start, n in chunks]
        used = len(decode_slots) + sum(n for _, _, _, n in chunks)
        width = self._bucket_tokens(used)
        tok, sid, pos = self._pack_flat(entries, width, self.max_batch)
        rows = self._bucket_rows(len(sample_ids))
        caches = {
            "slot_ids": jnp.asarray(sid),
            "positions": jnp.asarray(pos),
            "block_tables": cache.device_tables(null_rows=1),
            "segments": cache.pools,
            "sample_ids": jnp.asarray(self._pack_sample_ids(sample_ids,
                                                            rows)),
        }
        return tok, caches, rows

    def _dispatch_mixed(self, params, cache, batcher, decode_slots, chunks,
                        sample_ids, metas=None):
        """Build the flat token batch and enqueue one fused forward over it.

        With ``metas`` (device-sampling path) the step samples in-jit and
        returns the (S_pad,) int32 tokens, still on the device: syncing
        them is the whole device->host traffic of the iteration. Without
        it, returns the gathered (1, S_pad, V) logits rows for host-side
        sampling (the oracle path)."""
        tok, caches, rows = self._build_mixed_operands(
            cache, batcher, decode_slots, chunks, sample_ids)
        if metas is not None:
            sampling = self._pack_sampling(metas, rows)
            with profiling.annotate("paged_sample_step"):
                tokens, new_caches = self._sample_jit(params, caches,
                                                      jnp.asarray(tok[None]),
                                                      sampling)
            cache.update_pools(new_caches)
            if self._dispatch_delay > 0.0:
                tokens = self._delay_jit(tokens)
            return tokens
        with profiling.annotate("paged_mixed_step"):
            logits, new_caches = self._mixed_jit(params, caches,
                                                 jnp.asarray(tok[None]))
        cache.update_pools(new_caches)
        return logits

    def _dispatch_mixed_async(self, params, cache, batcher,
                              plan: _MixedPlan, prev_tokens_dev, fixups):
        """Pipelined dispatch: enqueue the planned iteration's fused
        forward + in-jit sampling WITHOUT syncing — returns the device
        token vector as a future the commit materialises later. Decode
        entries whose host feed is still the previous iteration's
        placeholder are patched on device from ``prev_tokens_dev`` (the
        unsynced previous token vector) per ``fixups``, so launching this
        iteration never waits for the previous one."""
        tok, caches, rows = self._build_mixed_operands(
            cache, batcher, plan.decode_slots, plan.chunks, plan.sample_ids)
        tok_dev = tok[None]
        if fixups:
            flat_pos = np.asarray([i for i, _ in fixups], np.int32)
            prev_rows = np.asarray([r for _, r in fixups], np.int32)
            tok_dev = self._fixup_jit(tok_dev, flat_pos, prev_tokens_dev,
                                      prev_rows)
        sampling = self._pack_sampling(plan.metas, rows)
        with profiling.annotate("paged_sample_step"):
            tokens, new_caches = self._sample_jit(params, caches, tok_dev,
                                                  sampling)
        cache.update_pools(new_caches)
        if self._dispatch_delay > 0.0:
            tokens = self._delay_jit(tokens)
        return tokens

    def _unstick(self, sched, cache, batcher, metrics, plog=None):
        """No decode token and no chunk could be scheduled: every block is
        pinned by half-prefilled sequences. Evict the youngest block-holding
        sequence so the head of the line can make progress; a lone sequence
        that still cannot fit means the prompt exceeds the pool."""
        holders = self._block_holders(cache, batcher)
        assert holders, "stuck with no block holders"
        if batcher.num_active == 1:
            raise CacheOOM(f"sequence {holders[0].req_id} alone exceeds "
                           "the pool")
        self._evict(Scheduler.pick_victim(holders), sched, cache, batcher,
                    metrics, reason="prefill_pinned", plog=plog)

    # ------------------------------------------------ drain-batch (legacy)

    def generate_drain(self, requests: List[Request]) -> List[Result]:
        """Seed-compatible static batching: group by budget row, pad into
        fixed slots, drain each batch fully before the next one starts.
        Kept as the benchmark baseline; prefill is single-pass now instead
        of the seed's per-token teacher-forced loop."""
        out: List[Optional[Result]] = [None] * len(requests)
        rows: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            rows.setdefault(self._budget_row(r.budget), []).append(i)
        for row, idxs in rows.items():
            params = self._realize(row)
            results = self._serve_batch(params, row,
                                        [requests[i] for i in idxs], idxs)
            for i, res in zip(idxs, results):
                out[i] = res
        return out  # type: ignore[return-value]

    def _serve_batch(self, params, row: int, reqs: List[Request],
                     req_ids: List[int]) -> List[Result]:
        results = []
        for chunk_start in range(0, len(reqs), self.max_batch):
            chunk = reqs[chunk_start: chunk_start + self.max_batch]
            b = len(chunk)
            # samplers keyed by submission index, matching the continuous
            # engines' req_ids — same request, same stochastic stream
            samplers = [SamplerState(r.sampling, rid) for r, rid in
                        zip(chunk, req_ids[chunk_start: chunk_start + b])]
            state = tfm.init_decode_state(self.cfg, b, self.max_len,
                                          dtype=jnp.float32)
            toks = [list(map(int, r.prompt)) for r in chunk]
            max_new = max(r.max_new_tokens for r in chunk)
            plen = max(len(t) for t in toks)
            padded = np.zeros((b, plen), np.int32)
            for i, t in enumerate(toks):
                padded[i, : len(t)] = t

            def _next(logits_last, step):
                # device path: same keyed DRAW_TARGET discipline as the
                # continuous engines (position = true sequence index, so a
                # request draws identical device tokens through every
                # engine path); host path keeps the sequential stream
                if self.device_sampling:
                    metas = [(s, DRAW_TARGET, len(toks[i]) + step)
                             for i, s in enumerate(samplers)]
                    sampling = self._pack_sampling(metas, b)
                    return np.asarray(self._drain_sample_jit(
                        logits_last, sampling))[:, None]
                cur = np.array(jnp.argmax(logits_last, axis=-1),
                               np.int32)[:, None]
                for i, s in enumerate(samplers):
                    if not s.greedy:
                        cur[i, 0] = s.sample(np.asarray(logits_last[i]))
                return cur

            logits, state = self._prefill_jit(params, state, jnp.asarray(padded))
            cur = _next(logits[:, -1], 0)
            outs = [padded, cur]
            for t in range(max_new - 1):
                logits, state = self._decode_jit(params, state, jnp.asarray(cur))
                cur = _next(logits[:, 0], t + 1)
                outs.append(cur)
            seq = np.concatenate(outs, axis=1)
            dp = self.router.deployed_params(row)
            for i, r in enumerate(chunk):
                results.append(Result(
                    tokens=seq[i, : len(toks[i]) + r.max_new_tokens],
                    budget_row=row, deployed_params=dp))
        return results
