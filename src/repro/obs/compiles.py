"""Process-wide record of what stalls the host besides the engine's own
work: XLA compiles (persistent-cache loads included) and Python GC.

JAX reports every backend compile through ``jax.monitoring`` as a
``/jax/core/compile/backend_compile_duration`` event carrying the jitted
function's name; a load from the persistent compilation cache reports the
same event, preceded on its thread by a ``cache_hits`` count (a compile
that consulted the cache and missed, by ``cache_misses``). One listener
pair turns them into ``Compile`` entries stamped on ``time.perf_counter``
(the clock of ``ServingMetrics`` and the ``Tracer``). A ``gc.callbacks``
hook sums the seconds the collector held the interpreter.

JAX's listeners are process-wide and cannot be removed, so the record is
too: ``install()`` registers them once and returns the one ``RECORD``.
``ElasticEngine`` installs it and attributes the entries to its
iterations through ``mark()``/``since()``; the benchmark's
``setup.program_load_s`` reads it directly.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time
import weakref
from typing import List, Optional, Tuple

from repro.obs.tracer import CAT_COMPILE

__all__ = ["Compile", "CompileRecord", "RECORD", "install"]

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass(frozen=True)
class Compile:
    """One backend compile or persistent-cache load. ``cache``: "hit" (a
    load), "miss" (compiled after the cache missed), or None (compiled
    with the cache off)."""
    fun_name: str
    t_end: float
    seconds: float
    cache: Optional[str]


class CompileRecord:
    """Append-only compile entries plus cumulative GC seconds; see the
    module docstring. ``engine_t`` is when the newest ``ElasticEngine``
    was built (the start of the set-up span its programs load in)."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.events: List[Compile] = []
        self.gc_s = 0.0
        self.engine_t: Optional[float] = None
        self._tracers = weakref.WeakSet()   # get a ``compile`` instant
        self._outcome = threading.local()
        self._gc_t0: Optional[float] = None
        self._installed = False
        self._lock = threading.Lock()

    # ---------------------------------------------------------- listeners

    def install(self) -> "CompileRecord":
        with self._lock:
            if not self._installed:
                import jax
                jax.monitoring.register_event_listener(self._on_event)
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                gc.callbacks.append(self._on_gc)
                self._installed = True
        return self

    def subscribe(self, tracer) -> None:
        """Emit a ``compile`` instant to ``tracer`` per entry from now on
        (held weakly: a dropped tracer unsubscribes itself)."""
        with self._lock:
            self._tracers.add(tracer)

    def _on_event(self, event, **_):
        if event == _HIT:
            self._outcome.cache = "hit"
        elif event == _MISS:
            self._outcome.cache = "miss"

    def _on_duration(self, event, duration, **kw):
        if event != _COMPILE:
            return
        cache = getattr(self._outcome, "cache", None)
        self._outcome.cache = None
        c = Compile(str(kw.get("fun_name", "?")), self._clock(),
                    float(duration), cache)
        self.events.append(c)            # list.append is atomic
        with self._lock:
            tracers = list(self._tracers)
        for tr in tracers:
            tr.instant("compile", CAT_COMPILE,
                       args={"fun": c.fun_name,
                             "seconds": round(c.seconds, 6),
                             "cache": c.cache})

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = self._clock()
        elif self._gc_t0 is not None:
            self.gc_s += self._clock() - self._gc_t0
            self._gc_t0 = None

    # ------------------------------------------------------------- reads

    def mark(self) -> Tuple[int, float]:
        """A position to attribute from: (entries so far, GC seconds)."""
        return len(self.events), self.gc_s

    def since(self, mark: Tuple[int, float]) -> Tuple[List[Compile], float]:
        """Entries recorded and GC seconds spent since ``mark``."""
        n, gc_s = mark
        return self.events[n:], self.gc_s - gc_s

    @property
    def count(self) -> int:
        return len(self.events)

    @property
    def seconds(self) -> float:
        return sum(c.seconds for c in self.events)

    def statusz(self) -> dict:
        """Counts and seconds so far, and the last eight entries."""
        evs = list(self.events)
        return {
            "count": len(evs),
            "seconds": round(sum(c.seconds for c in evs), 6),
            "cache_hits": sum(1 for c in evs if c.cache == "hit"),
            "cache_misses": sum(1 for c in evs if c.cache == "miss"),
            "gc_s": round(self.gc_s, 6),
            "recent": [[c.fun_name, round(c.seconds, 6), c.cache]
                       for c in evs[-8:]],
        }


RECORD = CompileRecord()


def install() -> CompileRecord:
    """Register the listeners (once per process) and return ``RECORD``."""
    return RECORD.install()
