"""SpecDecoder: draft/verify rounds for one budget row of the serving engine.

Round anatomy (greedy acceptance is token-identical to target-only
decoding; stochastic acceptance is *distribution*-identical — see below):

  1. **plan** — for every decoding sequence, reserve cache room for the
     round. The one mandatory verify token keeps the mixed engine's
     semantics (evict youngest block holders under pressure); everything
     speculative — extra verify positions and draft-slot growth — is
     opportunistic and *shrinks* instead of evicting (``k`` degrades toward
     0, never the other way around). Per-sequence draft lengths come from
     the adaptive-k controller (``SpecConfig.request_spec_len``) and the
     round's extras budget is dealt fairly across sequences
     (``Scheduler.split_spec_extras``), so a round's worst-case ``k + 1``
     verify tokens per sequence respect ``token_budget``.
  2. **draft** — the low-rank prefix row proposes up to ``k`` tokens
     autoregressively through the same flat-token paged forward the mixed
     engine uses, writing the *draft* cache slot. Greedy sequences propose
     the draft row's argmax; stochastic sequences *sample* each proposal
     from the draft row's warped (temperature/top-k) distribution with a
     position-keyed ``DRAW_DRAFT`` uniform, and the proposal distribution
     ``q`` is kept for the accept test. The draft cache is warmed lazily:
     the first draft step of each round streams whatever committed tokens
     the draft slot is missing (``gap``), so a fresh sequence (or a
     preemption-recomputed one — in-flight draft state is simply dropped
     with the slots) decodes immediately at ``k = 0`` while its draft
     cache catches up chunk by chunk.
  3. **verify** — ONE full-row ``paged_verify_step`` scores every
     sequence's ``k+1`` positions (last committed token + drafts) and
     returns full logits rows (never argmax — the stochastic accept test
     needs the whole per-position distribution); target prefill chunks of
     not-yet-decoding sequences ride the same forward, so speculation
     composes with chunked prefill.
  4. **accept** — greedy: longest accepted prefix (drafts matching the full
     row's greedy choice commit, the first mismatch is replaced by the full
     row's own token). Stochastic: Leviathan accept/reject per position —
     draft ``x`` with proposal distribution ``q`` survives against the
     target's warped distribution ``p`` iff ``u <= p(x) / q(x)`` (keyed
     ``DRAW_ACCEPT`` uniform); the first rejection commits a resample from
     the normalized residual ``max(p - q, 0)`` (``DRAW_RESIDUAL``), and an
     all-accepted round commits a bonus token straight from the target's
     last row (``DRAW_TARGET``) — so every round commits >= 1 token and
     the committed tokens are exactly distributed as target-only sampling
     (``stochastic_accept`` below carries the proof sketch). Both cache
     slots then roll back via ``truncate_slot`` — rejected draft tokens
     release their blocks and rewind the write positions. The accepted
     count feeds the sequence's adaptive-k EWMA.

Replay discipline: every stochastic draw the decoder makes is keyed by
(seed, req_id, purpose, position) — never consumed off the sequential
stream — so dropping in-flight drafts (rollback, mid-round preemption)
cannot drift a sequence's randomness: the recomputed attempt re-derives
the same uniforms at the same positions, and a whole serve() run is a
deterministic function of the workload. Note the *realized* tokens of a
recomputed stochastic sequence may still differ from a preemption-free
run when the recomputed rounds draft different positions (a ``k = 0``
warmup commit draws ``DRAW_TARGET`` where a drafted round would have
drawn ``DRAW_DRAFT``/``DRAW_ACCEPT``); both paths are exact samplers of
the same target distribution, which is the invariant stochastic
speculation maintains (greedy keeps bitwise token identity).

Dual-slot layout: the decoder's ``PagedKVCache`` carries ``2 * max_batch``
slots over ONE shared ``BlockAllocator`` — seat ``s`` writes target K/V at
slot ``s`` and draft K/V at slot ``max_batch + s`` (draft and target K/V
differ: the projections run at different ranks). Eviction always frees the
pair.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import CAT_SCHED, CAT_SPEC, profiling
from repro.serving.batcher import ContinuousBatcher
from repro.serving.kv_cache import CacheOOM, PagedKVCache
from repro.serving.metrics import ServingMetrics
from repro.serving.sampling import (DRAW_ACCEPT, DRAW_DRAFT, DRAW_RESIDUAL,
                                    DRAW_TARGET, SamplerState, sample_from,
                                    sample_token)
from repro.serving.scheduler import Scheduler, Sequence

from repro.spec.config import SpecConfig


def stochastic_accept(sampler: SamplerState, committed: int,
                      drafts: List[int], draft_probs: List[np.ndarray],
                      target_rows: np.ndarray) -> Tuple[List[int], int]:
    """Leviathan-style stochastic acceptance for one sequence's round.

    ``drafts[j]`` was sampled from the draft row's warped distribution
    ``draft_probs[j]`` for position ``committed + j``; ``target_rows[j]``
    is the full row's logits for that position (row ``len(drafts)`` is the
    all-accepted bonus position). Returns ``(tokens_to_commit,
    num_accepted_drafts)`` — always at least one token.

    Exactness sketch (Leviathan et al. 2023): at each position the
    committed token is ``x ~ q`` kept with probability ``min(1, p(x)/q(x))``
    or, failing that, a draw from the residual ``(p - min(p, q)) /
    (1 - sum_v min(p(v), q(v)))``; marginalizing over ``x`` gives
    ``min(p, q) + (1 - sum min(p, q)) * residual = p`` exactly, for any
    proposal ``q`` — including ``q`` warped by a different model than
    ``p``, which is the nested-draft case. Positions use independent keyed
    uniforms, so the round commits an exact sample of the target chain.
    """
    out: List[int] = []
    for j, x in enumerate(drafts):
        p = sampler.probs(target_rows[j])
        q = draft_probs[j]
        pos = committed + j
        # accept with prob min(1, p/q): u*q <= p avoids the q == 0 division
        # (q[x] > 0 whenever x was actually proposed from q)
        if sampler.uniform(pos, DRAW_ACCEPT) * q[x] <= p[x]:
            out.append(int(x))
            continue
        residual = np.maximum(p - q, 0.0)
        tot = float(residual.sum())
        # a (numerically) empty residual means p <= q everywhere, where the
        # accept test almost surely passed; fall back to p itself
        r = residual / tot if tot > 1e-12 else p
        out.append(sample_from(r, sampler.uniform(pos, DRAW_RESIDUAL)))
        return out, j
    # every draft survived: bonus token straight from the target's k-th row
    bonus_pos = committed + len(drafts)
    out.append(sampler.sample_at(bonus_pos, target_rows[len(drafts)]))
    return out, len(drafts)


@dataclasses.dataclass
class RoundPlan:
    """One decoding sequence's reservation for the current round."""
    seat: int                    # batcher seat == target slot id
    seq: Sequence
    committed: int               # L: prompt + generated tokens
    gap_fed: int                 # draft-warmup tokens fed this round
    k: int                       # draft proposals this round (may be 0)
    drafts: List[int] = dataclasses.field(default_factory=list)
    # warped draft distribution per proposal (stochastic sequences only):
    # the accept test needs q, not just the proposed token. Host path:
    # float64 numpy rows; device path: float32 rows that never leave the
    # device (``q_rows``) — they flow straight into the fused verify step
    draft_probs: List[np.ndarray] = dataclasses.field(default_factory=list)
    q_rows: List = dataclasses.field(default_factory=list)


class SpecDecoder:
    """Drives one budget row's speculative continuous-batching loop.

    Borrows the engine's jitted forwards (``_mixed_jit`` for draft steps,
    ``_verify_jit`` for the full-row verify) and its finish/metrics
    plumbing; owns the dual-slot cache discipline and the acceptance logic
    (greedy longest-accepted-prefix, stochastic accept/resample).
    """

    def __init__(self, engine, *, row: int, draft_row: int, spec: SpecConfig,
                 sched: Scheduler, metrics: ServingMetrics, results: Dict):
        self.engine = engine
        self.cfg = engine.cfg
        self.row = row
        self.draft_row = draft_row
        self.spec = spec
        self.sched = sched
        self.metrics = metrics
        self.results = results
        self.max_batch = engine.max_batch
        self.tracer = engine.tracer
        self.target_params = engine._realize(row)
        self.draft_params = engine._realize(draft_row)
        # 2x slots, one allocator: seat s -> target slot s, draft slot B + s
        self.cache = PagedKVCache(
            self.cfg, max_batch=2 * engine.max_batch, max_len=engine.max_len,
            block_size=engine.block_size, num_blocks=engine.num_blocks,
            prefix_cache=engine.prefix_cache)
        self.cache.tracer = self.tracer
        self.batcher = ContinuousBatcher(engine.max_batch)
        self._round_tables = None    # device block tables, valid per round
        self._disp_s = 0.0           # per-round device-dispatch seconds
        self._zero_q_cache: Dict[int, object] = {}
        chunk = engine.prefill_chunk or engine.max_len
        self.prefill_chunk = chunk
        # verify-token budget per round; prefill chunks take the leftover
        self.token_budget = engine.token_budget or (
            engine.max_batch * (spec.spec_len + 1) + chunk)

    # ------------------------------------------------------------- slots

    def _draft_slot(self, seat: int) -> int:
        return self.max_batch + seat

    def _zero_q(self, k_cap: int):
        """Cached (k_cap, V) zero proposal rows — q padding for greedy /
        pad plans in the fused accept operands (allocating fresh
        full-vocab zeros every round would sit in the decode hot loop)."""
        if k_cap not in self._zero_q_cache:
            self._zero_q_cache[k_cap] = jnp.zeros(
                (k_cap, self.cfg.vocab_size), jnp.float32)
        return self._zero_q_cache[k_cap]

    def _free_pair(self, seat: int) -> None:
        """Free BOTH of a seat's cache slots (the paired-slot discipline:
        a sequence never releases one side without the other)."""
        self.cache.free_slot(seat)
        self.cache.free_slot(self._draft_slot(seat))

    def _apply_cancellations(self) -> None:
        """Round-boundary cancellation sweep. The speculative decoder is
        commit-serial (no in-flight lookahead), so entries apply
        immediately and the committed cursor advances in one step; a
        seated victim must release its slot PAIR, which is why this does
        not reuse ``ElasticEngine._apply_cancellations`` (that frees a
        single slot)."""
        eng = self.engine
        with eng._cancel_lock:
            n = len(eng._cancel_list)
            entries = eng._cancel_list[eng._cancel_cursor: n]
        for req_id in entries:
            seq = eng._seq_index.get(req_id)
            if seq is None or seq.state == "finished":
                continue
            if self.sched.remove_waiting(seq):
                eng._finish_cancelled(seq, self.metrics, self.results)
                continue
            for seat, s in enumerate(self.batcher.slots):
                if s is seq:
                    self.batcher.leave(seat)
                    self._free_pair(seat)
                    eng._finish_cancelled(seq, self.metrics, self.results)
                    break
        eng._cancel_cursor = n

    def _stream_commit(self, seq: Sequence, commit) -> None:
        """Stream a round's committed tokens, indexed by their positions in
        ``seq.generated`` — call strictly BEFORE extending the list. Values
        are real here (commit-serial), so no deferral is needed."""
        sess = self.engine._session
        if sess is None:
            return
        base = len(seq.generated)
        for j, tok in enumerate(commit):
            sess.emit(seq.req_id, base + j, int(tok))

    def _block_holders(self) -> List[Sequence]:
        """Seated sequences holding blocks in either slot of their pair."""
        out = []
        for seq in self.batcher.active_sequences():
            seat = self.batcher.slot_of(seq)
            if (self.cache.slots[seat].blocks
                    or self.cache.slots[self._draft_slot(seat)].blocks):
                out.append(seq)
        return out

    def _evict(self, victim: Sequence, *, reason: str = "cache_pressure") -> int:
        """Preempt one sequence: free both slots, drop its (implicitly
        in-flight) draft state, re-queue at the row front for recompute."""
        seat = self.batcher.slot_of(victim)
        vstate = victim.state
        self.batcher.leave(seat)
        self._free_pair(seat)
        self.sched.requeue_front(victim)
        self.metrics.on_preempt(victim.req_id)
        if self.tracer.enabled:
            self.tracer.instant(
                "preempt", CAT_SCHED,
                args={"req": victim.req_id, "slot": seat, "reason": reason,
                      "policy": "youngest_first", "state": vstate})
        return seat

    # -------------------------------------------------------------- loop

    def serve(self) -> None:
        self.engine._live.update(row=self.row, cache=self.cache,
                                 batcher=self.batcher, spec=True)
        try:
            self._serve_rounds()
        finally:
            # the row drained: free its pools now, as
            # ElasticEngine._serve_row_mixed does
            self.cache.pools = []

    def _serve_rounds(self) -> None:
        eng, sched, tr = self.engine, self.sched, self.tracer
        while True:
            it0 = self.metrics.now()
            self._disp_s = 0.0
            eng._drain_intake(sched, self.metrics)
            self._apply_cancellations()
            # admission: seat waiting requests with a slot PAIR each
            for seat in self.batcher.free_slots():
                if not sched.has_waiting(self.row):
                    break
                seq = sched.pop(self.row)
                self.metrics.on_admit(seq.req_id)
                if tr.enabled:
                    tr.instant("admit", CAT_SCHED,
                               args={"req": seq.req_id, "row": self.row,
                                     "slot": seat, "reason": "slot_free",
                                     "attempt": seq.admissions})
                if seq.request.max_new_tokens <= 0:
                    eng._finish(seq, self.metrics, self.results)
                    continue
                if seq.prompt_len > eng.max_len:
                    raise CacheOOM(f"sequence of {seq.prompt_len} tokens "
                                   f"exceeds max_len {eng.max_len}")
                self.cache.open_slot(seat)
                self.cache.open_slot(self._draft_slot(seat))
                # prefix-cache probe on the TARGET slot only; the draft
                # slot aliases the target's prompt blocks later, once the
                # sequence reaches decoding (share_prefix in _plan_round)
                hit = self.cache.probe_prefix(seat, seq.request.prompt)
                if hit:
                    seq.prefill_pos = hit
                    self.metrics.on_prefix_hit(seq.req_id, hit,
                                               self.cache.cached_blocks)
                self.batcher.seat_prefill(seat, seq)
            if self.batcher.num_active == 0:
                break                            # row drained

            plans = self._plan_round()
            chunks = self._plan_prefill(plans)
            if not plans and not chunks:
                if self.batcher.num_active == 0:
                    continue                     # everyone was preempted
                self._unstick()
                continue
            plan_end = self.metrics.now()
            if tr.enabled:
                tr.complete("plan", CAT_SPEC, it0, plan_end,
                            args={"plans": len(plans),
                                  "chunks": len(chunks),
                                  "draft_tokens": sum(p.k for p in plans)})

            # every block the round touches was reserved during planning,
            # so one table snapshot serves all k+1 dispatches (host-side:
            # the jitted forwards donate their cache operand, so a device
            # copy could not be reused across dispatches)
            self._round_tables = self.cache.host_tables(null_rows=1)
            if eng.device_sampling:
                self._draft_phase_device(plans)
                draft_end = self.metrics.now()
                self._verify_and_commit_device(plans, chunks)
            else:
                self._draft_phase(plans)
                draft_end = self.metrics.now()
                self._verify_and_commit(plans, chunks)
            self._round_tables = None
            it1 = self.metrics.now()
            if tr.enabled:
                if draft_end > plan_end:
                    tr.complete("draft", CAT_SPEC, plan_end, draft_end,
                                args={"drafters": sum(1 for p in plans
                                                      if p.k > 0)})
                tr.complete("verify", CAT_SPEC, draft_end, it1,
                            args={"plans": len(plans), "chunks": len(chunks)})
            self.metrics.on_iteration_timing(
                self._disp_s, it1 - it0 - self._disp_s)
            if eng.registry is not None:
                self.metrics.on_cache_stats(
                    self.cache.allocator.free_count,
                    self.cache.allocator.fragmentation(),
                    prefix=self.cache.stats)
                self.metrics.on_queue_depths(
                    {r: len(q) for r, q in sched.queues.items()})
            # live telemetry heartbeat: speculative rounds tick the
            # watchdog like mixed iterations (the cost audit skips them —
            # a round interleaves draft- and verify-row dispatches, so
            # there is no clean per-row attribution; see obs/costaudit.py)
            eng._iterations += 1
            if eng.watchdog is not None:
                eng._watchdog_tick(self.metrics, self.cache,
                                   decoding=bool(self.batcher.decode_slots()))

    # ----------------------------------------------------------- planning

    def _reserve_mandatory(self, seat: int) -> bool:
        """Guarantee the seat's one mandatory verify token, evicting the
        youngest block holder under pressure (mixed-engine semantics).
        Returns False if the seat's own sequence got evicted."""
        while self.cache.extend_slot(seat, 1, clip=True) == 0:
            victim = Scheduler.pick_victim(self._block_holders())
            if (victim is self.batcher.slots[seat]
                    and self.batcher.num_active == 1):
                raise CacheOOM(
                    f"sequence {victim.req_id} alone exceeds the pool")
            if self._evict(victim) == seat:
                return False                     # the seat itself went
        return True

    def _plan_round(self) -> List[RoundPlan]:
        plans: List[RoundPlan] = []
        decode_seats = self.batcher.decode_slots()
        # token-budget accounting: mandatory verify tokens are the decode
        # reserve (like the mixed engine's one-per-slot); speculative
        # EXTRAS consume what remains after keeping one prefill chunk's
        # worth for seated prefills — a small explicit token_budget throttles
        # speculation rather than starving prefill behind it
        extras_left = self.token_budget - len(decode_seats)
        if self.batcher.prefill_slots():
            extras_left -= min(self.prefill_chunk,
                               self.engine.max_len)
        # adaptive-k wants are read once per round per sequence (the probe
        # counter advances on read), then granted fairly: a tight budget
        # shaves every drafter evenly instead of letting early seats hoard.
        # Sequences still warming their draft cache cannot propose this
        # round, so they want 0 — their share goes to seats that can draft
        wants = []
        for seat in decode_seats:
            seq = self.batcher.slots[seat]
            want = self.spec.request_spec_len(seq)
            dslot = self._draft_slot(seat)
            # draft-KV sharing: an empty draft slot aliases its target's
            # full prompt blocks (refcount++) instead of re-prefilling the
            # prompt at the draft row — the K/V pools are rank-agnostic,
            # and acceptance only ever commits target-model tokens, so the
            # draft's proposal quality is the only thing sharing can
            # change, never the committed stream
            if (self.cache.prefix_cache
                    and self.spec.request_can_draft(seq)
                    and self.cache.slots[dslot].num_tokens == 0):
                self.cache.share_prefix(seat, dslot, seq.prompt_len)
            gap = (seq.prompt_len + len(seq.generated)
                   - self.cache.slots[dslot].num_tokens)
            wants.append(0 if gap > self.spec.gap_chunk else want)
        grants = dict(zip(decode_seats,
                          Scheduler.split_spec_extras(wants, extras_left)))
        for seat in decode_seats:
            seq = self.batcher.slots[seat]
            if seq is None or seq.state != "decoding":
                continue                         # evicted while reserving
            committed = seq.prompt_len + len(seq.generated)
            tgt = self.cache.slots[seat]
            assert tgt.num_tokens == committed - 1, (tgt.num_tokens, committed)
            if not self._reserve_mandatory(seat):
                continue

            dslot = self._draft_slot(seat)
            gap = committed - self.cache.slots[dslot].num_tokens
            assert gap >= 1, gap
            want_k = grants[seat]                # 0 while warming the draft
            # speculation degrades under pressure, it never evicts: clamp
            # to the round's extras budget and the max_len headroom
            # (extend_slot raises past max_len even with clip), then clip
            # to the free list
            want_k = max(0, min(want_k, extras_left))
            want_k = min(want_k,
                         self.engine.max_len - self.cache.slots[seat].num_tokens)
            # opportunistic verify room beyond the mandatory token
            k = self.cache.extend_slot(seat, want_k, clip=True)
            # draft slot: gap feed + the k-1 proposal writes, clip-only;
            # a sequence that can never draft (stochastic sampler,
            # spec_len=0 opt-out) skips warmup entirely — its draft slot
            # stays blockless and no draft-row forward runs for it
            fed = (min(gap, self.spec.gap_chunk)
                   if self.spec.request_can_draft(seq) else 0)
            head = self.engine.max_len - self.cache.slots[dslot].num_tokens
            if fed > head:
                fed, k = head, 0
            if k > 0:
                k = min(k, head - fed + 1)
            need = fed + max(0, k - 1)
            got = self.cache.extend_slot(dslot, need, clip=True)
            if got < need:
                if k > 0 and got >= fed:
                    k = got - fed + 1            # fewer proposals fit
                else:
                    fed, k = got, 0              # partial warmup only
            # release verify room we are no longer going to use
            self.cache.truncate_slot(seat, committed + k)
            extras_left -= k
            plans.append(RoundPlan(seat=seat, seq=seq, committed=committed,
                                   gap_fed=fed, k=k))
        # a later seat's mandatory reservation may have evicted an earlier
        # planned sequence — its plan (and reservations) went with it
        return [p for p in plans if self.batcher.slots[p.seat] is p.seq]

    def _plan_prefill(self, plans: List[RoundPlan]):
        """Target-side prefill chunks riding the verify forward, under the
        leftover token budget (verify tokens are reserved first — drafts
        never starve running decodes, and decodes never starve prefill
        below the budget the mixed engine would give it)."""
        spent = sum(p.k + 1 for p in plans)
        budget_left = self.token_budget - spent
        prefilling = [self.batcher.slots[s]
                      for s in self.batcher.prefill_slots()]
        chunks = []
        for seq, want in Scheduler.plan_prefill_chunks(
                prefilling, budget_left, self.prefill_chunk,
                order=self.engine.prefill_order):
            seat = self.batcher.slot_of(seq)
            got = self.cache.extend_slot(seat, want, clip=True)
            if got:
                chunks.append((seat, seq, seq.prefill_pos, got))
        return chunks

    def _unstick(self) -> None:
        holders = self._block_holders()
        assert holders, "stuck with no block holders"
        if self.batcher.num_active == 1:
            raise CacheOOM(f"sequence {holders[0].req_id} alone exceeds "
                           "the pool")
        self._evict(Scheduler.pick_victim(holders), reason="round_stalled")

    # ------------------------------------------------------------ forward

    def _bucket(self, used: int) -> int:
        return self.engine._bucket_tokens(used, self.token_budget)

    def _dispatch(self, fn, params, entries):
        """Run one flat-token forward. ``entries``: (slot, tokens, start)
        triples — ``tokens`` land at positions ``start..start+n-1`` of
        ``slot`` (the engine's shared ``_pack_flat`` layout). Returns the
        (T_padded, V) logits as a device array."""
        used = sum(len(t) for _, t, _ in entries)
        width = self._bucket(used)
        tok, sid, pos = self.engine._pack_flat(entries, width,
                                               2 * self.max_batch)
        caches = {
            "slot_ids": jnp.asarray(sid),
            "positions": jnp.asarray(pos),
            "block_tables": jnp.asarray(self._round_tables),
            "segments": self.cache.pools,
        }
        t0 = self.metrics.now()
        name = ("paged_verify_step" if fn is self.engine._verify_jit
                else "paged_mixed_step")
        with profiling.annotate(name):
            logits, new_caches = fn(params, caches, jnp.asarray(tok[None]))
            jax.block_until_ready(logits)
        self._disp_s += self.metrics.now() - t0
        self.cache.update_pools(new_caches)
        return logits[0]            # device array: callers argmax on device

    def _propose(self, p: RoundPlan, greedy: np.ndarray, logits,
                 flat_idx: int, step: int) -> None:
        """Record draft proposal number ``step`` (1-based) for plan ``p``
        from the draft-row logits at flat position ``flat_idx``. Greedy
        sequences take the (device-computed) argmax; stochastic sequences
        sample from the draft row's warped distribution with the
        position-keyed ``DRAW_DRAFT`` uniform and keep the distribution
        for the verify pass's accept test."""
        sampler = p.seq.sampler
        if sampler.greedy:
            p.drafts.append(int(greedy[flat_idx]))
            return
        q = sampler.probs(np.asarray(logits[flat_idx]))
        pos = p.committed + step - 1             # index of the proposed token
        p.drafts.append(sample_from(q, sampler.uniform(pos, DRAW_DRAFT)))
        p.draft_probs.append(q)

    def _draft_phase(self, plans: List[RoundPlan]) -> None:
        """Autoregressive draft proposals (+ lazy draft-cache warmup)."""
        eng = self.engine
        # step 1: per sequence, the committed tokens its draft cache lacks
        entries, emitters = [], []
        for p in plans:
            if p.gap_fed == 0:
                continue
            committed = (list(map(int, p.seq.request.prompt))
                         + p.seq.generated)
            dslot = self._draft_slot(p.seat)
            # planning already extended the draft slot by gap_fed (+ k-1),
            # so the feed starts at its previous write position
            start = (self.cache.slots[dslot].num_tokens
                     - p.gap_fed - max(0, p.k - 1))
            toks = committed[start: start + p.gap_fed]
            entries.append((dslot, toks, start))
            if p.k > 0:
                emitters.append((p, len(entries) - 1))
        if not entries:
            return
        flat_end = np.cumsum([len(t) for _, t, _ in entries]) - 1
        logits = self._dispatch(eng._mixed_jit, self.draft_params, entries)
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        for p, ei in emitters:
            self._propose(p, greedy, logits, int(flat_end[ei]), 1)

        # steps 2..k: one proposal per participating sequence per step
        max_k = max((p.k for p in plans), default=0)
        for step in range(2, max_k + 1):
            live = [p for p in plans if p.k >= step]
            entries = [(self._draft_slot(p.seat), [p.drafts[-1]],
                        p.committed + step - 2) for p in live]
            logits = self._dispatch(eng._mixed_jit, self.draft_params,
                                    entries)
            greedy = np.asarray(jnp.argmax(logits, axis=-1))
            for i, p in enumerate(live):
                self._propose(p, greedy, logits, i, step)

    # ----------------------------------- device-resident draft + verify

    def _dispatch_device(self, jit_fn, params, entries, sample_ids, width,
                         *extra):
        """One fused flat-token forward on the device-sampling path:
        gathers ``sample_ids`` for the LM head (padded to ``width``), runs
        the jitted step, and returns its outputs (int32 tokens / accept
        results — the round's whole device->host traffic) synced to host
        timing."""
        eng = self.engine
        used = sum(len(t) for _, t, _ in entries)
        tok, sid, pos = eng._pack_flat(entries, self._bucket(used),
                                       2 * self.max_batch)
        caches = {
            "slot_ids": jnp.asarray(sid),
            "positions": jnp.asarray(pos),
            "block_tables": jnp.asarray(self._round_tables),
            "segments": self.cache.pools,
            "sample_ids": jnp.asarray(
                eng._pack_sample_ids(sample_ids, width)),
        }
        t0 = self.metrics.now()
        name = ("paged_verify_step" if jit_fn is eng._verify_accept_jit
                else "paged_sample_step")
        with profiling.annotate(name):
            out = jit_fn(params, caches, jnp.asarray(tok[None]), *extra)
            self.cache.update_pools(out[-1])
            jax.block_until_ready(out[:-1])
        self._disp_s += self.metrics.now() - t0
        return out[:-1]

    def _record_draft(self, emitters, tokens, probs) -> None:
        """Record one draft dispatch's proposals: tokens land host-side,
        the warped q rows of stochastic drafters stay on device for the
        fused accept test."""
        for i, p in enumerate(emitters):
            p.drafts.append(int(tokens[i]))
            if not p.seq.sampler.greedy:
                p.q_rows.append(probs[i])

    def _draft_phase_device(self, plans: List[RoundPlan]) -> None:
        """Autoregressive draft proposals with in-jit sampling: greedy
        drafters argmax on device, stochastic drafters draw their
        position-keyed ``DRAW_DRAFT`` proposal in-jit and the proposal
        distribution ``q`` never visits the host — each step transfers one
        int32 per drafting sequence."""
        eng = self.engine
        # step 1: gap feeds + first proposal for plans that can draft
        entries, emitters, sample_ids = [], [], []
        for p in plans:
            if p.gap_fed == 0:
                continue
            committed = (list(map(int, p.seq.request.prompt))
                         + p.seq.generated)
            dslot = self._draft_slot(p.seat)
            start = (self.cache.slots[dslot].num_tokens
                     - p.gap_fed - max(0, p.k - 1))
            entries.append((dslot, committed[start: start + p.gap_fed],
                            start))
            if p.k > 0:
                emitters.append(p)
                sample_ids.append(sum(len(t) for _, t, _ in entries) - 1)
        if not entries:
            return
        metas = [(p.seq.sampler, DRAW_DRAFT, p.committed) for p in emitters]
        self._record_draft(emitters, *self._draft_step(
            entries, sample_ids, metas))

        # steps 2..k: one proposal per participating sequence per step
        max_k = max((p.k for p in plans), default=0)
        for step in range(2, max_k + 1):
            live = [p for p in plans if p.k >= step]
            entries = [(self._draft_slot(p.seat), [p.drafts[-1]],
                        p.committed + step - 2) for p in live]
            metas = [(p.seq.sampler, DRAW_DRAFT, p.committed + step - 1)
                     for p in live]
            self._record_draft(live, *self._draft_step(
                entries, list(range(len(live))), metas))

    def _draft_step(self, entries, sample_ids, metas):
        """One draft-row dispatch; returns host tokens and (device) q rows
        — the probs output is only materialized when a stochastic drafter
        actually emits this step (a distinct jit trace)."""
        eng = self.engine
        width = eng._bucket_rows(len(sample_ids))
        sampling = eng._pack_sampling(metas, width)
        want_probs = any(not sampler.greedy for sampler, _, _ in metas)
        if want_probs:
            ((tokens, probs),) = self._dispatch_device(
                eng._sample_probs_jit, self.draft_params, entries,
                sample_ids, width, sampling)
        else:
            (tokens,) = self._dispatch_device(
                eng._sample_jit, self.draft_params, entries, sample_ids,
                width, sampling)
            probs = None
        return np.asarray(tokens), probs

    def _verify_and_commit_device(self, plans: List[RoundPlan],
                                  chunks) -> None:
        """The fused device round: ONE ``paged_verify_accept_step`` scores
        every plan's ``k+1`` positions, runs Leviathan accept/resample (or
        the greedy prefix rule) in-jit, and samples the finishing chunks'
        first tokens — the host receives ``(accepted_len, commit tokens)``
        per sequence as int32 and replays only the cache rollback."""
        eng, metrics = self.engine, self.metrics
        entries = []
        for p in plans:
            feed = self.batcher.next_token(p.seat)
            entries.append((p.seat, [feed] + p.drafts, p.committed - 1))
        for seat, seq, start, n in chunks:
            entries.append((seat,
                            list(map(int, seq.request.prompt[start:
                                                             start + n])),
                            start))

        # gathered-row layout (static per trace): P_pad verify runs of
        # exactly k_cap+1 rows — short runs repeat their first row — then
        # the finishing chunks' final-token rows
        k_cap = max([self.spec.spec_len] + [p.k for p in plans])
        p_pad = 1
        while p_pad < max(len(plans), 1):
            p_pad *= 2
        sample_ids: List[int] = []
        off = 0
        for p in plans:
            ids = list(range(off, off + p.k + 1))
            sample_ids += ids + [off] * (k_cap + 1 - len(ids))
            off += p.k + 1
        sample_ids += [0] * ((p_pad - len(plans)) * (k_cap + 1))
        chunk_meta, finish_rows = [], {}
        flat = off
        for seat, seq, start, n in chunks:
            if start + n == seq.prompt_len:
                finish_rows[seat] = len(chunk_meta)
                sample_ids.append(flat + n - 1)
                chunk_meta.append((seq.sampler, DRAW_TARGET,
                                   seq.prompt_len))
            flat += n
        c_pad = 0
        if chunk_meta:
            c_pad = 1
            while c_pad < len(chunk_meta):
                c_pad *= 2
            sample_ids += [0] * (c_pad - len(chunk_meta))

        # accept operands; q rows ride along on device only when some plan
        # is stochastic (greedy-only rounds skip the warp entirely)
        drafts = np.zeros((p_pad, k_cap), np.int32)
        ks = np.zeros(p_pad, np.int32)
        committed = np.zeros(p_pad, np.int32)
        temp = np.zeros(p_pad, np.float32)
        topk = np.zeros(p_pad, np.int32)
        seed = np.zeros(p_pad, np.int32)
        req = np.zeros(p_pad, np.int32)
        any_stoch = False
        q_rows = []
        zero_q = self._zero_q(k_cap)
        for pi, p in enumerate(plans):
            drafts[pi, : p.k] = p.drafts
            ks[pi] = p.k
            committed[pi] = p.committed
            s = p.seq.sampler
            if not s.greedy:
                any_stoch = True
                eng._sampler_fields(s, temp, topk, seed, req, pi)
            if p.q_rows:
                q_rows.append(jnp.concatenate(
                    [jnp.stack(p.q_rows), zero_q[len(p.q_rows):]])
                    if len(p.q_rows) < k_cap else jnp.stack(p.q_rows))
            else:
                q_rows.append(zero_q)
        accept = {"k": jnp.asarray(ks), "drafts": jnp.asarray(drafts),
                  "committed": jnp.asarray(committed),
                  "temperature": jnp.asarray(temp)}
        if any_stoch:
            accept["seed"] = jnp.asarray(seed)
            accept["req_id"] = jnp.asarray(req)
            if topk.any():
                accept["top_k"] = jnp.asarray(topk)
            accept["q"] = jnp.stack(q_rows
                                    + [zero_q] * (p_pad - len(plans)))
        chunk_sampling = (eng._pack_sampling(chunk_meta, c_pad)
                          if chunk_meta else None)

        commit_d, m_d, chunk_d = self._dispatch_device(
            eng._verify_accept_jit, self.target_params, entries, sample_ids,
            len(sample_ids), accept, chunk_sampling)
        commit_h, m_h = np.asarray(commit_d), np.asarray(m_d)
        chunk_h = None if chunk_d is None else np.asarray(chunk_d)

        # host-side commit: extend sequences, roll back rejected tails
        drafted = verified = accepted_total = committed_total = 0
        drafting_seqs = sum(1 for p in plans if p.k > 0)
        for pi, p in enumerate(plans):
            m = int(m_h[pi])
            commit = [int(x) for x in commit_h[pi, : m + 1]]
            commit = commit[: p.seq.remaining]
            decision = self.spec.observe_round(p.seq, p.k, m)
            if decision is not None and self.tracer.enabled:
                self.tracer.instant("adaptive_k", CAT_SCHED, args=decision)
            drafted += p.k
            verified += p.k + 1
            accepted_total += m
            committed_total += len(commit)
            self._stream_commit(p.seq, commit)
            p.seq.generated.extend(commit)
            for _ in commit:
                metrics.on_token(p.seq.req_id)
            if p.seq.done:
                self.batcher.leave(p.seat)
                self._free_pair(p.seat)
                eng._finish(p.seq, metrics, self.results)
                continue
            self.cache.truncate_slot(p.seat, p.committed + m)
            if p.k > 0:
                self.cache.truncate_slot(
                    self._draft_slot(p.seat),
                    min(p.committed + m, p.committed + p.k - 1))
            self.batcher.feed(p.seat, commit[-1])

        total_chunk = 0
        for seat, seq, start, n in chunks:
            seq.prefill_pos = start + n
            total_chunk += n
            metrics.on_prefill_chunk(n)
            self.cache.register_prefix(seat, seq.request.prompt,
                                       seq.prefill_pos)
            if seq.prefill_pos == seq.prompt_len:
                metrics.on_prefill_end(seq.req_id)
                first = int(chunk_h[finish_rows[seat]])
                self._stream_commit(seq, [first])
                seq.generated.append(first)
                metrics.on_first_token(seq.req_id)
                if seq.done:                     # max_new_tokens == 1
                    self.batcher.leave(seat)
                    self._free_pair(seat)
                    eng._finish(seq, metrics, self.results)
                else:
                    self.batcher.to_decoding(seat, first)

        metrics.on_mixed_step(committed_total, total_chunk,
                              self.cache.occupancy())
        if plans:
            metrics.on_spec_round(drafted, verified, accepted_total,
                                  drafting_seqs)

    # ----------------------------------------------------------- commit

    def _first_token(self, seq: Sequence, logits_row) -> int:
        """Prefill-completion token. Sequences participating in stochastic
        speculation draw it position-keyed (``DRAW_TARGET`` at index
        ``prompt_len``) so their entire draw history is keyed; verify-only
        sequences keep the sequential stream (cross-engine identity)."""
        sampler = seq.sampler
        if not sampler.greedy and self.spec.request_can_draft(seq):
            return sampler.sample_at(seq.prompt_len, np.asarray(logits_row))
        return sample_token(seq, logits_row)

    def _verify_and_commit(self, plans: List[RoundPlan], chunks) -> None:
        eng, metrics = self.engine, self.metrics
        entries = []
        for p in plans:
            feed = self.batcher.next_token(p.seat)
            entries.append((p.seat, [feed] + p.drafts, p.committed - 1))
        for seat, seq, start, n in chunks:
            toks = list(map(int, seq.request.prompt[start: start + n]))
            entries.append((seat, toks, start))
        logits = self._dispatch(eng._verify_jit, self.target_params, entries)
        greedy = np.asarray(jnp.argmax(logits, axis=-1))

        # acceptance per sequence: greedy longest-accepted-prefix, or
        # Leviathan accept/resample for stochastic drafters
        flat = 0
        drafted = verified = accepted_total = committed_total = 0
        drafting_seqs = sum(1 for p in plans if p.k > 0)
        for p in plans:
            run = p.k + 1
            sampler = p.seq.sampler
            if sampler.greedy:
                targets = [int(greedy[flat + j]) for j in range(run)]
                m = 0
                while m < p.k and p.drafts[m] == targets[m]:
                    m += 1
                commit = targets[: m + 1]
            elif self.spec.request_can_draft(p.seq):
                rows = np.asarray(logits[flat: flat + run])
                commit, m = stochastic_accept(sampler, p.committed,
                                              p.drafts, p.draft_probs, rows)
            else:
                # verify-only fallback (``stochastic=False`` or the
                # ``spec_len=0`` opt-out): one sequential-stream draw,
                # token-identical to the non-speculative engines
                assert p.k == 0, (p.seq.req_id, p.k)
                m = 0
                commit = [sample_token(p.seq, logits[flat])]
            commit = commit[: p.seq.remaining]
            flat += run
            decision = self.spec.observe_round(p.seq, p.k, m)
            if decision is not None and self.tracer.enabled:
                self.tracer.instant("adaptive_k", CAT_SCHED, args=decision)
            drafted += p.k
            verified += run
            accepted_total += m
            committed_total += len(commit)
            self._stream_commit(p.seq, commit)
            p.seq.generated.extend(commit)
            for _ in commit:
                metrics.on_token(p.seq.req_id)
            if p.seq.done:
                self.batcher.leave(p.seat)
                self._free_pair(p.seat)
                eng._finish(p.seq, metrics, self.results)
                continue
            # rollback: rejected verify room and rejected draft tail
            self.cache.truncate_slot(p.seat, p.committed + m)
            dslot = self._draft_slot(p.seat)
            if p.k > 0:
                self.cache.truncate_slot(
                    dslot, min(p.committed + m, p.committed + p.k - 1))
            self.batcher.feed(p.seat, commit[-1])

        # prefill chunks commit exactly as in the mixed engine
        total_chunk = 0
        for seat, seq, start, n in chunks:
            seq.prefill_pos = start + n
            total_chunk += n
            metrics.on_prefill_chunk(n)
            self.cache.register_prefix(seat, seq.request.prompt,
                                       seq.prefill_pos)
            if seq.prefill_pos == seq.prompt_len:
                metrics.on_prefill_end(seq.req_id)
                first = self._first_token(seq, logits[flat + n - 1])
                self._stream_commit(seq, [first])
                seq.generated.append(first)
                metrics.on_first_token(seq.req_id)
                if seq.done:                     # max_new_tokens == 1
                    self.batcher.leave(seat)
                    self._free_pair(seat)
                    eng._finish(seq, metrics, self.results)
                else:
                    self.batcher.to_decoding(seat, first)
            flat += n

        metrics.on_mixed_step(committed_total, total_chunk,
                              self.cache.occupancy())
        if plans:
            metrics.on_spec_round(drafted, verified, accepted_total,
                                  drafting_seqs)
