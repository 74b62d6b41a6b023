"""Unified telemetry plane: spans + metrics across the serving stack.

One process-global :class:`~repro.obs.telemetry.Telemetry` instance
(``repro.obs.TELEMETRY``, disabled by default) collects everything the
previously siloed stat surfaces recorded — ``TenantTimeline`` stamps,
engine trace counters, ``PagedKVCache`` page accounting, swap-store and
staging-lane logs, fault injections and heartbeat verdicts, and the
risk pipeline's stages — as one falsifiable schema that `obs.export` can
dump (Chrome-trace/Perfetto JSON, Prometheus text).  Live spans are also
``jax.profiler.TraceAnnotation`` spans, so a profiler session (the
benchmark's ``--trace 1``) holds them on the device trace's clock.

Naming scheme
=============

Every span and metric name is lowercase, dot-separated:
``<layer>.<noun>[.<detail>]``.  The first segment is the emitting layer
and doubles as the Chrome-trace category:

========== ==========================================================
prefix      layer
========== ==========================================================
``sched``   `serving.multitenant` — scheduler rounds, admission passes
``round``   `serving.continuous` — decode micro-round dispatch/collect
``admit``   `serving.continuous` — batched admission (plan/prefill)
``engine``  `serving.engine` — blocking/dispatch prefill + decode
``kv``      `serving.kvcache` — paged-pool page accounting
``swap``    `serving.swap` — host-tier swap store, per staging lane
``transfer`` `core.transfer` — staging-engine chunk windows, put/wait
``risk``    `risk.analysis` — an analysis, its plan, tables, slices, gather
``pipeline`` `core.pipeline` — compute dispatch, draining the waiters
``host``    `obs.telemetry` — garbage collections (``host.gc``)
``fault``   `distributed.fault` — injected faults
``heartbeat`` `distributed.fault` — liveness verdicts
``shard``   `distributed.sharding` — per-mesh-shard placements
``trace``   jit compile (trace-time) events, any layer
``timeline`` ``TenantTimeline`` entries re-expressed as spans
``journal`` `serving.journal` — WAL appends/bytes (crash safety)
``recovery`` `serving` — checkpoint saves, journal replay, pool restore
========== ==========================================================

Kinds:

* **spans** — closed ``[t_start, t_end)`` intervals on one monotonic
  clock (`time.perf_counter`), with parent/child links from a
  per-thread span stack, e.g. ``sched.step`` > ``round.dispatch`` >
  ``round.cow``.  Retrospective spans (device windows stamped by
  handles, ``record_timeline``) carry ``parent_id=None`` and stay in
  the in-memory ring only: the profiler sees live spans alone.
* **events** — zero-length spans (``fault.round``, ``kv.alloc``).
* **counters** — monotonically increasing (``kv.pages_allocated``,
  ``trace.decode``, ``transfer.bytes``).  Unit suffixes where not
  obvious: ``*_bytes``, ``*_pages``, ``*_s``.
* **gauges** — last-write-wins (``heartbeat.suspects``,
  ``sched.backlog``).
* **histograms** — count/sum/min/max summaries (``round.steps_live``).

Cost contract: with the plane disabled (the default) every hook is one
attribute check — no span objects, no counter mutations, no
allocations (`tests/test_obs.py` pins this on the decode round path);
enabling it changes no numerics and no jit compile counts.
"""
from repro.obs.telemetry import (NULL_SPAN, Span, Telemetry, TELEMETRY,
                                 get_telemetry, record_timeline)

__all__ = ["NULL_SPAN", "Span", "Telemetry", "TELEMETRY", "get_telemetry",
           "record_timeline"]
