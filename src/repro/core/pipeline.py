"""Overlapped multi-tenant execution pipeline (paper Figs 11/13, executable).

The simulator in :mod:`repro.core.simulator` *models* the paper's winning
schedule: with sequential transfers, tenant k+1's host->device staging rides
the link while tenant k's compute occupies its pdev, so the makespan is
``max(transfer chain, compute chains)`` instead of their sum.  Before this
module existed, the executable path did not honour that contract — it staged
every tenant chunk (blocking per chunk) and only then dispatched compute, so
the measured wall time was ``sum(transfers) + compute`` and the simulator's
predicted overlap never materialised.

:class:`PipelineExecutor` is the executable counterpart of the simulated
schedule — the **simulator-vs-executable overlap contract**:

* sequential mode — chunks are staged one at a time (each transfer owns the
  full link, paper Fig 10); the moment chunk k is device-resident its jitted
  compute is *dispatched* (asynchronously) and the executor immediately
  starts staging chunk k+1.  Transfer(k+1) therefore overlaps compute(k),
  which is exactly the double-buffering the simulator's ``simulate()``
  timeline assumes.
* concurrent mode — every transfer is enqueued up front (streams share the
  link, BW/n each, Fig 8); each tenant's compute is dispatched as soon as its
  chunk lands, in staging order.
* per-pdev serialisation — compute for tenants of one pdev is dispatched in
  slot order onto the same device, whose execution stream serialises them
  (the paper: "the NVIDIA driver executes them sequentially").
* straggler reordering — the previous step's slowest tenant is staged first
  (:func:`repro.core.transfer.reorder_for_stragglers`), so its data is ready
  earliest.

Every run returns a :class:`PipelineReport` whose :class:`TenantTimeline`
entries carry per-tenant ``transfer_start/transfer_end/compute_start/
compute_end`` wall-clock timestamps (relative to run start).  A dedicated
waiter thread blocks on each tenant's output *concurrently with the staging
loop* and stamps ``compute_end`` the moment the output is ready, so the
realised-overlap signal used by :meth:`PipelineReport.overlaps` —

    ``compute_start(k) <= transfer_start(k+1) < compute_end(k)``

(transfer k+1 began inside compute k's execution window) — is falsifiable in
both directions: a blocking stage-everything schedule fails the left
inequality (every transfer precedes every compute; this rejection is
structural, independent of timing noise), and a dispatch whose compute
drained before the next chunk was staged fails the right one.  One
measurement caveat on the right inequality: ``compute_end`` is stamped at
waiter-thread wakeup, so gaps shorter than a thread wakeup (~tens of µs)
are not resolved — the signal is meaningful for ms-scale tenant computes,
not µs-scale toys.  There is one waiter per pdev, and a pdev's tenants
complete in dispatch order (its device stream serialises them), so the
stamps carry no cross-pdev ordering skew.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from repro.core.tenancy import TenantTask, VirtualDevicePool
from repro.core.transfer import StagingEngine, reorder_for_stragglers
from repro.obs.telemetry import Telemetry, get_telemetry

StageFn = Callable[[TenantTask], Any]           # task -> host pytree
ComputeFn = Callable[[TenantTask, Any], Any]    # (task, device pytree) -> out


@dataclasses.dataclass
class TenantTimeline:
    """Wall-clock activity windows of one tenant, relative to run start."""
    vdev: int
    pdev: int
    slot: int
    transfer_start: float
    transfer_end: float
    compute_start: float      # jitted-call dispatch time (async)
    compute_end: float        # block_until_ready return time

    @property
    def transfer_s(self) -> float:
        return self.transfer_end - self.transfer_start

    @property
    def compute_s(self) -> float:
        return self.compute_end - self.compute_start


def timeline_overlaps(timeline: Sequence[TenantTimeline]) -> List[bool]:
    """For each consecutive staged pair (k, k+1): did tenant k+1's transfer
    start *inside* tenant k's compute window?  All-True on a multi-tenant
    sequential run means the paper's overlap is realised (see the module
    docstring for why this predicate is falsifiable).  Shared by
    :class:`PipelineReport` and the benchmark harness (which reads the same
    timeline off a risk ``RunReport``)."""
    return [a.compute_start <= b.transfer_start < a.compute_end
            for a, b in zip(timeline, timeline[1:])]


@dataclasses.dataclass
class PipelineReport:
    results: Dict[int, Any]            # vdev -> device output
    timeline: List[TenantTimeline]     # in staging order
    wall_s: float
    mode: str

    def per_tenant_s(self) -> Dict[int, float]:
        return {tl.vdev: tl.compute_s for tl in self.timeline}

    def overlaps(self) -> List[bool]:
        return timeline_overlaps(self.timeline)

    def overlap_realised(self) -> bool:
        # majority-of-pairs, matching every live consumer of
        # timeline_overlaps (benchmarks + tests): noise on a shared host can
        # legitimately drain isolated pairs early, while a blocking schedule
        # structurally scores zero pairs
        ov = self.overlaps()
        return sum(ov) > len(ov) // 2 if ov else False


class CompletionWaiter:
    """Daemon thread that stamps ``TenantTimeline.compute_end`` the moment a
    dispatched device output is ready.

    This is the shared half of the overlap-measurement contract: the
    dispatching thread records ``transfer_*``/``compute_start`` and submits
    ``(output, timeline_entry)``; the waiter blocks on the output
    *concurrently with whatever the dispatcher does next* (staging the next
    chunk, assembling the next tenant's batch) and stamps ``compute_end`` at
    readiness, which is what makes the :func:`timeline_overlaps` predicate
    falsifiable on the right inequality.  Used per-pdev by
    :class:`PipelineExecutor` and as the per-engine waiter of
    :class:`repro.serving.multitenant.MultiTenantScheduler`.

    ``submit`` returns a :class:`threading.Event` set once the entry is
    stamped (or the wait raised), so callers can join a single item without
    closing the waiter.  Device errors surfacing on the blocking wait are
    recorded in :attr:`errors` — the thread keeps serving later items so a
    poisoned output can neither hang subsequent tickets nor leak the thread.
    """

    def __init__(self, clock: Callable[[], float],
                 name: str = "completion-waiter"):
        self._clock = clock
        self._q: "queue.Queue" = queue.Queue()
        self.errors: List[BaseException] = []
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()

    def submit(self, out: Any, entry: TenantTimeline,
               on_ready: Optional[Callable[[Any], None]] = None
               ) -> threading.Event:
        """Stamp ``entry.compute_end`` when ``out`` is ready; returns an
        event set after the stamp (and optional ``on_ready(out)``) ran."""
        stamped = threading.Event()
        self._q.put((out, entry, on_ready, stamped))
        return stamped

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            out, entry, on_ready, stamped = item
            try:
                jax.block_until_ready(out)
                entry.compute_end = self._clock()
                if on_ready is not None:
                    on_ready(out)
            except BaseException as e:   # device errors surface on block
                self.errors.append(e)    # re-raised by the owner
            finally:
                stamped.set()

    def close(self) -> None:
        """Drain remaining items, then stop and join the thread."""
        self._q.put(None)
        self._thread.join()


class PipelineExecutor:
    """Event-driven executor: stage chunk k+1 while chunk k computes.

    The executor owns a :class:`StagingEngine` (for placement + the staging
    log) but drives its non-blocking ``put``/``wait`` primitives instead of
    the stage-everything entry point, interleaving compute dispatch with the
    transfer chain.  Spans: ``pipeline.start`` around starting the waiter
    threads, ``pipeline.dispatch`` around each compute call,
    ``pipeline.drain`` while the host waits for the last outputs.
    """

    def __init__(self, pool: VirtualDevicePool, mode: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None):
        self.pool = pool
        self.mode = mode or pool.cfg.transfer_mode
        assert self.mode in ("sequential", "concurrent")
        self.tel = get_telemetry(telemetry)
        self.engine = StagingEngine(pool, self.mode, telemetry=self.tel)

    # ------------------------------------------------------------------
    def run(self, tasks: Sequence[TenantTask], stage_fn: StageFn,
            compute_fn: ComputeFn,
            straggler_hist: Optional[Dict[int, float]] = None,
            ) -> PipelineReport:
        """Execute every tenant task; returns results + per-tenant timeline.

        ``stage_fn(task)`` builds the host pytree for one tenant (cheap slice
        of pinned host data); ``compute_fn(task, device_tree)`` must be an
        *asynchronously dispatching* call (a jitted function) — the pipeline
        only blocks on outputs after every tenant has been dispatched.
        """
        t0 = time.perf_counter()
        now = lambda: time.perf_counter() - t0
        order = reorder_for_stragglers(tasks, straggler_hist)
        timeline: Dict[int, TenantTimeline] = {}
        results: Dict[int, Any] = {}

        # CompletionWaiter per pdev: blocks on each dispatched output
        # concurrently with the staging loop and stamps compute_end the
        # moment it is ready — this is what makes the overlap predicate
        # falsifiable (see module docstring).  The main thread only writes a
        # tenant's timeline entry before submitting it, the waiter only
        # stamps compute_end after.  One waiter per pdev: tenants of a pdev
        # complete in dispatch order anyway (the device stream serialises
        # them), so within-pdev blocking in dispatch order stamps *exact*
        # completion times, and a slow pdev can no longer inflate another
        # pdev's compute_end (the per-tenant times feed the
        # StragglerDetector, so skew there would mis-steer the next run's
        # staging order).
        with self.tel.span("pipeline.start"):
            waiters: Dict[int, CompletionWaiter] = {
                p: CompletionWaiter(now, name="pipeline-waiter")
                for p in {t.pdev for t in order}}

        def dispatch(task: TenantTask, chunk) -> None:
            self.engine.wait(chunk, t0)    # overlap point: compute of already
            te = now()                     # dispatched tenants keeps running
            with self.tel.span("pipeline.dispatch"):
                out = compute_fn(task, chunk.arrays)
            timeline[task.vdev] = TenantTimeline(
                task.vdev, task.pdev, task.slot,
                chunk.enqueue_s, te, now(), 0.0)
            waiters[task.pdev].submit(
                out, timeline[task.vdev],
                on_ready=functools.partial(results.__setitem__, task.vdev))

        try:
            if self.mode == "sequential":
                # one transfer on the link at a time; compute(k) is already
                # in flight while put+wait stages chunk k+1 (double buffering)
                for task in order:
                    dispatch(task, self.engine.put(task, stage_fn(task), t0))
            else:
                # all transfers share the link from t~0; dispatch each
                # tenant's compute as its chunk lands, in staging order
                chunks = [self.engine.put(task, stage_fn(task), t0)
                          for task in order]
                for task, chunk in zip(order, chunks):
                    dispatch(task, chunk)
        finally:
            # always drain + reap the waiters, even when staging raises
            with self.tel.span("pipeline.drain"):
                for w in waiters.values():
                    w.close()
        waiter_err = [e for w in waiters.values() for e in w.errors]
        if waiter_err:
            raise waiter_err[0]
        return PipelineReport(results, [timeline[t.vdev] for t in order],
                              now(), self.mode)
