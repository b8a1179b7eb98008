"""Reduce a profiler trace to device busy time, op times and idle gaps.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain
events, ``{"device": [(name, start_ns, end_ns), ...] per chip, "host":
[(thread, name, start_ns, end_ns), ...]}``, restricted to the traced
window (the host annotation ``WINDOW``). ``reduce`` works on that form, so
the tests can feed it a small recorded trace.

* busy: the union of the device op intervals, per chip, averaged over the
  chips used; idle share is ``1 - busy / window``;
* op time: the summed durations of the device ops of one base name (the
  HLO instruction's name without its ``.N`` suffix: on a TPU an op's event
  is named by its HLO text); a Pallas kernel shows under its kernel name;
* idle gaps: the intervals between merged busy intervals, each named by
  the innermost host event that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.trace_window"


def start(log_dir: str) -> None:
    """Start the profiler without its Python tracer, which records every
    Python call of every thread (some 400,000 events a second here) and
    slows the host it is meant to observe. JAX's own host events stay."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()
_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(name: str) -> str:
    """``%paged_prefill_attention.26 = f32[96,12,64]... custom-call(...)``
    (a TPU op's event name is its HLO text) -> ``paged_prefill_attention``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def _device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:(TPU|GPU):\d+", name) is not None


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host = {}, []
    for plane in pd.planes:
        if _device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, e.name, e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events)
    return clip({"device": device, "host": host})


def clip(raw: dict) -> dict:
    """Keep what lies inside the ``WINDOW`` annotation, cut at its edges."""
    marks = [(s, e) for _, n, s, e in raw["host"] if n == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    lo, hi = marks[0]

    def cut(s, e):
        return max(s, lo), min(e, hi)

    device = {}
    for chip, evs in raw["device"].items():
        device[chip] = [(n, *cut(s, e)) for n, s, e in evs if e > lo and s < hi]
    host = [(t, n, *cut(s, e)) for t, n, s, e in raw["host"]
            if e > lo and s < hi and n != WINDOW]
    return {"device": device, "host": host, "window_ns": [lo, hi]}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_at(host, t):
    """The innermost host event covering ``t`` (latest start), or None."""
    best = None
    for _, n, s, e in host:
        if s <= t < e and (best is None or s > best[0]):
            best = (s, n)
    return best[1] if best else None


def reduce(tr: dict, top: int = 10) -> dict:
    lo, hi = tr["window_ns"]
    window_s = (hi - lo) * 1e-9
    chips = [c for c, evs in tr["device"].items() if evs]
    if not chips:
        raise ValueError("no device op ran in the traced window")
    busy, ops, gaps = 0.0, {}, []
    for chip in chips:
        evs = tr["device"][chip]
        merged = _merge([(s, e) for _, s, e in evs])
        busy += sum(e - s for s, e in merged) * 1e-9
        for n, s, e in evs:
            b = base_name(n)
            ops[b] = ops.get(b, 0.0) + (e - s) * 1e-9 / len(chips)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    named = [[_host_at(tr["host"], (s + e) / 2) or "no host event", d * 1e-9]
             for d, s, e in gaps[:top]]
    annotations = {}
    for _, n, _, _ in tr["host"]:
        annotations[n] = annotations.get(n, 0) + 1
    return {
        "window_s": window_s,
        "busy_s": busy / len(chips),
        "ops": ops,
        "device_ops": sorted(([n, t] for n, t in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": named,
        "host_counts": annotations,
    }


def op_seconds(red: dict, prefix: str) -> float:
    """Device seconds of the ops whose base name starts with ``prefix``."""
    return sum(t for n, t in red["ops"].items() if n.startswith(prefix))
