"""The load generator: asyncio clients on the served path.

The engine runs ``ElasticEngine.serve_session`` on a worker thread; each
request of the schedule is an asyncio task that sleeps until the request
is due, submits it to the ``StreamSession`` and consumes its tokens one by
one, stamping each with the host clock as it arrives. Every time the
benchmark reports is taken here, on the client side, from when the
request was due.

The window's requests all finish (the tail keeps arriving meanwhile, so
the load stays as it was), or ``timeout_s`` after the window closes the
rest count as failed; then the submitted requests still open are
cancelled, the rest never sent, and the session closed.
"""
from __future__ import annotations

import asyncio
import dataclasses
import threading
import time
from typing import List, Optional

from repro.serving import Request, SamplingParams
from repro.serving.metrics import ServingMetrics
from repro.serving.session import StreamSession


@dataclasses.dataclass
class Record:
    """What one client saw of its request. Times are ``time.perf_counter``."""
    arrival: object
    due: float
    sent: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    req_id: Optional[int] = None
    cancelled: bool = False

    @property
    def complete(self) -> bool:
        return len(self.tokens) == self.arrival.max_new and not self.cancelled


class IterationLog(ServingMetrics):
    """The engine's own ``ServingMetrics``, with a host-clock stamp on each
    iteration's commit and timing callback, so per-iteration counters can
    be read over the window alone."""

    def __init__(self):
        super().__init__()
        self.steps = []          # (t, decode tokens, prefill tokens, occupancy)
        self.timings = []        # (t, dispatch s, host s)

    def on_mixed_step(self, decode_tokens, prefill_tokens, occupancy):
        self.steps.append((time.perf_counter(), decode_tokens, prefill_tokens,
                           occupancy))
        super().on_mixed_step(decode_tokens, prefill_tokens, occupancy)

    def on_iteration_timing(self, dispatch_s, host_s, overlap_s=0.0):
        self.timings.append((time.perf_counter(), dispatch_s, host_s))
        super().on_iteration_timing(dispatch_s, host_s, overlap_s)


def make_request(a, mix: dict) -> Request:
    sampling = None if a.greedy else SamplingParams(
        temperature=mix["sampling"]["temperature"],
        top_k=mix["sampling"]["top_k"], seed=a.seed)
    return Request(prompt=a.prompt, max_new_tokens=a.max_new,
                   budget=mix["budget"], sampling=sampling)


@dataclasses.dataclass
class Run:
    """A served schedule: records plus the clock marks around the window."""
    records: List[Record]
    start: float
    open: float
    close: float
    metrics: IterationLog


def serve(engine, arrivals, mix: dict, window_s: float, *,
          on_open=None, on_close=None, hooks=(), timeout_s: float = 240.0):
    """Serve the schedule; returns a ``Run``. ``on_open``/``on_close`` run
    on the loop thread as the window opens and closes; ``hooks`` are
    (seconds after open, callable) pairs, e.g. to start and stop a trace.
    An exception on the engine's thread is raised here."""
    metrics = IterationLog()
    errors = []

    def work(session):
        try:
            engine.serve_session(session, metrics=metrics)
        except BaseException as e:          # re-raised on the caller's thread
            errors.append(e)
            session.mark_done()

    async def client(rec: Record, session: StreamSession, handles: dict):
        await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
        rec.sent = time.perf_counter()
        h = session.submit(make_request(rec.arrival, mix))
        handles[rec.arrival.index] = h
        async for tok in h.tokens():
            rec.times.append(time.perf_counter())
            rec.tokens.append(int(tok))
        rec.req_id = h.req_id
        rec.cancelled = bool(h.result is None or h.result.cancelled)

    async def marks_at(t: float, fn):
        await asyncio.sleep(max(0.0, t - time.perf_counter()))
        return fn()

    async def drive():
        session = StreamSession(stream_buffer=8)
        session.loop = asyncio.get_running_loop()
        worker = threading.Thread(target=work, args=(session,), daemon=True)
        worker.start()
        start = time.perf_counter() + 0.05
        w_open = start + mix["lead_in_s"]
        w_close = w_open + window_s
        records = [Record(a, start + a.due) for a in arrivals]
        handles = {}
        tasks = {r.arrival.index: asyncio.ensure_future(
            client(r, session, handles)) for r in records}
        side = [asyncio.ensure_future(marks_at(w_open, on_open or (lambda: None))),
                asyncio.ensure_future(marks_at(w_close, on_close or (lambda: None)))]
        side += [asyncio.ensure_future(marks_at(w_open + dt, fn))
                 for dt, fn in hooks]
        window = [tasks[r.arrival.index] for r in records
                  if r.arrival.phase == "window"]
        died = asyncio.ensure_future(session.join())
        deadline = w_close + timeout_s
        pending = set(window)
        while pending and not died.done():
            done, pending = await asyncio.wait(
                pending | {died}, timeout=max(0.0, deadline - time.perf_counter()),
                return_when=asyncio.FIRST_COMPLETED)
            pending.discard(died)
            if time.perf_counter() >= deadline:
                break
        await asyncio.gather(*side)
        # what is still open (the tail; window requests only past the
        # deadline, which then count as failed) is cancelled or never sent
        for idx, t in tasks.items():
            if t.done():
                continue
            if idx in handles:
                handles[idx].cancel()
            else:
                t.cancel()
        await asyncio.gather(*tasks.values(), return_exceptions=True)
        session.close()
        await asyncio.wait_for(died, timeout_s)
        worker.join(timeout_s)
        if worker.is_alive():
            raise RuntimeError("the engine thread did not stop")
        if errors:
            raise errors[0]
        return Run(records, start, w_open, w_close, metrics)

    return asyncio.run(drive())
