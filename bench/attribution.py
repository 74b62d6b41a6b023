"""Device idle time split by what the program was doing, from a profiler
trace of a window run with the program's telemetry plane enabled.

An enabled plane (``repro.obs.TELEMETRY.enable()``) makes every live span
of the program a ``jax.profiler.TraceAnnotation`` of the same name, so the
host plane of the trace holds them on the device trace's clock, beside
the benchmark's own ``bench.*`` spans.  ``program_spans`` reads the spans
whose names carry the program's layer prefixes (``bench.traces.load``
keeps only the benchmark's).

``idle_by_span`` splits each chip's idle time inside ``bench.window`` by
the innermost span, benchmark or program and on any host thread, that
covers each instant: exact interval intersection, not the gap midpoints
``traces.idle_gaps`` names gaps by.  Time whose innermost span is no
program span below ``risk.analysis`` (the analysis's self time, or a
``bench.*`` span) is unattributed: what the spans cannot name yet.

    trace = traces.load(path)
    program = attribution.program_spans(path)
    attribution.staging_idle_share(trace, program)    # %, or None
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench import traces
from bench.traces import Interval

PROGRAM_PREFIXES = ("risk.", "transfer.", "pipeline.", "host.")
ANALYSIS_SPAN = "risk.analysis"
# the host slicing, enqueueing and waiting for a tenant's YET slice
STAGING_SPANS = ("transfer.put", "transfer.wait", "risk.slice")


def program_spans(path: str,
                  device_prefix: str = traces.DEVICE_PREFIX
                  ) -> List[Interval]:
    """The program's spans on the host planes of the trace at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in data.planes
            if not plane.name.startswith(device_prefix)
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIXES)]


def _idle(busy: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The complement of the disjoint sorted ``busy`` pieces in
    ``[lo, hi]``."""
    edges = [lo] + [t for piece in busy for t in piece] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _split(idle: Sequence[Tuple[float, float]],
           spans: Sequence[Tuple[float, float, str, float]],
           out: Dict[Optional[str], float]) -> None:
    """Add to ``out`` each idle nanosecond under the name of the shortest
    span that covers it (None where no span does).  ``spans`` are
    ``(start, end, name, duration)`` sorted by start."""
    cuts = sorted({t for piece in idle for t in piece}
                  | {t for s, e, _, _ in spans for t in (s, e)})
    active: List[Tuple[float, float, str, float]] = []
    nxt = gi = 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > a]
        while gi < len(idle) and idle[gi][1] <= a:
            gi += 1
        # cuts hold every idle edge, so [a, b] is wholly idle or busy
        if gi < len(idle) and idle[gi][0] <= a:
            name = (min(active, key=lambda sp: (sp[3], sp[2]))[2]
                    if active else None)
            out[name] = out.get(name, 0.0) + (b - a)


def idle_by_span(trace: traces.Trace, program: Sequence[Interval]
                 ) -> Dict[Optional[str], float]:
    """Each chip's idle time inside the window, as a share of the window
    averaged over the chips, keyed by the innermost covering span's name.
    The shares add up to ``traces.idle_share``."""
    lo, hi = trace.window
    spans = sorted((max(s, lo), min(e, hi), name, e - s)
                   for s, e, name in [*trace.spans, *program]
                   if min(e, hi) > max(s, lo))
    out: Dict[Optional[str], float] = {}
    for dev in sorted(trace.ops):
        _split(_idle(traces.merge(trace.ops[dev], lo, hi), lo, hi), spans,
               out)
    return {name: ns / len(trace.ops) / (hi - lo)
            for name, ns in out.items()}


def attributed(name: Optional[str]) -> bool:
    """Whether idle time under this innermost span has a program name."""
    return (name is not None and name.startswith(PROGRAM_PREFIXES)
            and name != ANALYSIS_SPAN)


def staging_idle_share(trace: traces.Trace, program: Sequence[Interval]
                       ) -> Optional[float]:
    """% of the window, averaged over chips, in which the chip is idle
    while the host slices, enqueues or waits for a YET slice; None where
    the trace holds no program span."""
    if not program:
        return None
    parts = idle_by_span(trace, program)
    return 100.0 * sum(parts.get(name, 0.0) for name in STAGING_SPANS)


def unattributed_idle_share(trace: traces.Trace,
                            program: Sequence[Interval]) -> Optional[float]:
    """% of the window, averaged over chips, in which the chip is idle and
    no program span below ``risk.analysis`` covers the host; None where
    the trace holds no program span."""
    if not program:
        return None
    return 100.0 * sum(share for name, share
                       in idle_by_span(trace, program).items()
                       if not attributed(name))
