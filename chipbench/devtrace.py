"""Profiler trace of the window, and its reduction to device numbers.

``capture`` wraps a callable in ``jax.profiler`` with the Python tracer
off; the harness's own spans go into the same trace as
``jax.profiler.TraceAnnotation`` named ``bench:<span>``.  ``load`` reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into plain tuples,
and everything after that is plain Python, so a test can feed it a small
trace written by hand:

  * busy time is the union of the device's op intervals inside the
    ``bench:window`` span;
  * a kernel's time is the union of the intervals of the ops whose name
    or XLA module contains one of its names;
  * each idle gap of the device is charged to the innermost harness span
    that holds the middle of the gap.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import jax

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"


@dataclass
class Op:
    name: str
    module: str
    start: float                 # seconds on the trace's clock
    end: float


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    ops: list[Op] = field(default_factory=list)
    spans: list[Span] = field(default_factory=list)
    devices: int = 1


def capture(fn, log_dir: str):
    """Run ``fn()`` under the profiler; returns its result."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _device_plane(name: str) -> bool:
    """One plane per chip: ``/device:TPU:<n>``."""
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def op_name(text: str) -> str:
    """An op's own name: a TPU trace names each op by its whole HLO
    instruction (``%paged_attention.1 = bf16[...] custom-call(...)``),
    whose operands may name other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> Trace:
    """Device ops and harness spans of the one trace under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    planes = list(jax.profiler.ProfileData.from_file(paths[0]).planes)
    out = Trace()
    devices = [p for p in planes if _device_plane(p.name)]
    for plane in devices:
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
        for ln in ops:
            for ev in ln.events:
                stats = dict(ev.stats)
                start = ev.start_ns * 1e-9
                out.ops.append(Op(op_name(ev.name),
                                  str(stats.get("hlo_module", "")),
                                  start, start + ev.duration_ns * 1e-9))
    for plane in planes:
        if plane in devices:
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    start = ev.start_ns * 1e-9
                    out.spans.append(Span(ev.name, start,
                                          start + ev.duration_ns * 1e-9))
    out.devices = max(1, len(devices))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: dict[str, float]              # device time by module/op name
    idle_by_span: dict[str, float]      # idle seconds by harness span
    trace: Trace

    def kernel_s(self, names) -> float:
        """Seconds in which an op of one of ``names`` ran (union)."""
        lo, hi = self.window
        hits = [(o.start, o.end) for o in self.trace.ops
                if any(n in o.name or n in o.module for n in names)]
        return _total(_clip(union(hits), lo, hi)) / self.trace.devices

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.trace.spans if s.name == WINDOW]
        return (w[0].start, w[0].end)

    def top_ops(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def reduce(tr: Trace) -> Reduced:
    windows = [s for s in tr.spans if s.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, "
                           f"found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    busy = _clip(union((o.start, o.end) for o in tr.ops), lo, hi)
    op_s: dict[str, float] = {}
    for o in tr.ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            key = f"{o.module}/{o.name}" if o.module else o.name
            op_s[key] = op_s.get(key, 0.0) + d / tr.devices
    gaps = []
    edge = lo
    for s, e in busy:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    spans = sorted(tr.spans, key=lambda s: (s.start, -s.end))
    idle: dict[str, float] = {}
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        owner = WINDOW
        for sp in spans:
            if sp.start > mid:
                break
            if sp.end >= mid and sp.name != WINDOW:
                owner = sp.name          # later start: nested deeper
        idle[owner] = idle.get(owner, 0.0) + (ge - gs)
    return Reduced(window_s=hi - lo, busy_s=_total(busy) / tr.devices,
                   op_s=op_s, idle_by_span=idle, trace=tr)
