"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

``jax.profiler.ProfileData`` reads the file.  On a TPU every chip is a plane
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation that ran, and its ``XLA Modules`` line one event per run of a
compiled program, named ``jit_<function>(<fingerprint>)``.  The host plane's
lines hold the ``jax.profiler.TraceAnnotation`` spans the benchmark wraps
around its own calls (``bench.window``, ``bench.analysis``, ...), on the
same clock.

Busy time is the union of a device's operation intervals inside the
``bench.window`` span; idle time is the rest of that window.  Each idle gap
is named by the innermost ``bench.*`` span that covers its middle, which
says what the host was doing while the chip waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start ns, end ns, name)

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]           # device plane -> op events
    modules: Dict[str, List[Interval]]       # device plane -> program runs
    spans: List[Interval]                    # the benchmark's host spans

    @property
    def window(self) -> Tuple[float, float]:
        """The ``bench.window`` span."""
        win = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return win[0][0], win[0][1]


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, device_prefix: str = DEVICE_PREFIX) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            if device and line.name in ("XLA Ops", "XLA Modules"):
                dest = ops if line.name == "XLA Ops" else modules
                dest.setdefault(plane.name, []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif not device:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for d in set(ops) | set(modules):
        ops.setdefault(d, [])
        modules.setdefault(d, [])
    return Trace(ops, modules, spans)


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as disjoint
    sorted pieces."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: str) -> float:
    lo, hi = trace.window
    return sum(e - s for s, e in merge(trace.ops[device], lo, hi))


def busy_s(trace: Trace) -> float:
    """Busy seconds inside the window, averaged over the traced chips."""
    devs = sorted(trace.ops)
    return sum(busy_ns(trace, d) for d in devs) / len(devs) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float:
    """1 - busy / window, per chip, averaged over the traced chips."""
    return 1.0 - busy_s(trace) / window_s(trace)


def program_name(event_name: str) -> str:
    """``jit__trial_losses(1181...)`` -> ``jit__trial_losses``."""
    return event_name.split("(", 1)[0]


def module_runs(trace: Trace, program: str) -> List[Interval]:
    """Runs of the compiled program ``program`` (``jit_<function>``) that
    start inside the window, on every chip."""
    lo, hi = trace.window
    return [m for evs in trace.modules.values() for m in evs
            if program_name(m[2]) == program and lo <= m[0] < hi]


def op_name(event_name: str) -> str:
    """``%copy.3 = f32[...] copy(...)`` -> ``copy.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time inside the window,
    as ``[name, seconds]`` averaged over the traced chips."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for evs in trace.ops.values():
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[op_name(name)] = tot.get(op_name(name), 0.0) + d
    k = len(trace.ops)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def enclosing_span(trace: Trace, t: float) -> Optional[str]:
    """The innermost benchmark span that covers time ``t``."""
    inside = [s for s in trace.spans if s[0] <= t <= s[1]]
    return min(inside, key=lambda s: s[1] - s[0])[2] if inside else None


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps inside the window, over all chips, as
    ``[what the host was doing, seconds]``."""
    lo, hi = trace.window
    gaps: List[Tuple[float, str]] = []
    for dev in sorted(trace.ops):
        edges = [lo]
        for s, e in merge(trace.ops[dev], lo, hi):
            edges += [s, e]
        edges.append(hi)
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                what = enclosing_span(trace, (a + b) / 2) or "outside spans"
                label = what if len(trace.ops) == 1 else \
                    f"{what} on {dev.split('/device:')[-1]}"
                gaps.append((b - a, label))
    gaps.sort(key=lambda g: -g[0])
    return [[label, ns / 1e9] for ns, label in gaps[:n]]
