"""Profiling hooks: ``jax.profiler`` scopes for the serving stack.

``annotate(name)`` wraps a host-side region in a
``jax.profiler.TraceAnnotation`` scope whenever a profiler session
listens (``TraceAnnotation.is_enabled()``), whoever started it: this
module's ``start``, ``jax.profiler.start_trace`` called directly, or a
profiler server. The engine thread's phases (``engine.plan``,
``engine.sync``, ``engine.commit``, ...; see ``docs/observability.md``)
and its jitted dispatches (``paged_sample_step``, ``paged_mixed_step``, the
speculative steps) are such scopes, so they land on the device trace's
clock beside the XLA ops they launch. With no session listening the call
returns a shared reusable null context: the hot loop pays one function
call and one check per scope. ``step(name, n)`` is the same for a
``StepTraceAnnotation`` (one per engine iteration).

``start(dir)``/``stop()`` bracket a ``jax.profiler`` device trace
(TensorBoard/Perfetto-loadable); ``profile(dir)`` is the context-manager
form and a no-op when ``dir`` is falsy, which is how the launcher wires
its ``--jax-profile <dir>`` flag:

    with profiling.profile(args.jax_profile):
        engine.generate(...)
"""
from __future__ import annotations

import contextlib
from typing import Optional

__all__ = ["annotate", "step", "start", "stop", "profile"]

_NULL_CTX = contextlib.nullcontext()
_listening = None


def _session_listens() -> bool:
    """Whether a profiler session records host scopes now."""
    global _listening
    if _listening is None:
        import jax
        _listening = jax.profiler.TraceAnnotation.is_enabled
    return _listening()


def annotate(name: str):
    """TraceAnnotation scope while a profiler session listens, else a
    shared null context (reentrant and reusable — safe to hand out every
    call)."""
    if not _session_listens():
        return _NULL_CTX
    import jax
    return jax.profiler.TraceAnnotation(name)


def step(name: str, step_num: int):
    """StepTraceAnnotation scope (the profiler's step marker) while a
    session listens, else the shared null context."""
    if not _session_listens():
        return _NULL_CTX
    import jax
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def start(log_dir: str) -> None:
    """Start a device trace into ``log_dir``."""
    import jax
    jax.profiler.start_trace(log_dir)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


@contextlib.contextmanager
def profile(log_dir: Optional[str]):
    """Bracket a region with a device trace when ``log_dir`` is set; a
    transparent no-op otherwise."""
    if not log_dir:
        yield
        return
    start(log_dir)
    try:
        yield
    finally:
        stop()
