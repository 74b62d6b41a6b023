"""Overlapped multi-tenant execution pipeline (paper Figs 11/13, executable).

The simulator in :mod:`repro.core.simulator` *models* the paper's winning
schedule on its one shared network link: with sequential transfers, tenant
k+1's host->device staging rides the link while tenant k's compute occupies
its pdev, so the makespan is ``max(transfer chain, compute chains)`` instead
of their sum.  A schedule that staged every tenant chunk before dispatching
any compute would take ``sum(transfers) + compute`` and never show that
overlap.

:class:`PipelineExecutor` is the executable counterpart of the simulated
schedule — the **simulator-vs-executable overlap contract**:

* sequential mode — staging is sequential *per lane*.  A lane is the tasks
  of one pdev in staging order, and it rides that pdev's own host->device
  link (on a TPU host every chip has its own DMA path; the paper's single
  shared link is what the simulator models).  At most one chunk of a lane
  is in flight, so each transfer has its link to itself (paper Fig 10);
  lanes run at the same time.  One dispatcher thread drives every lane,
  because ``put`` is asynchronous: it enqueues each lane's head chunk, then,
  in staging order, waits for a lane's chunk, *dispatches* its jitted
  compute (asynchronously) and at once enqueues that lane's next chunk.
  Transfer(k+1) of a lane therefore overlaps compute(k) of the same lane,
  which is the double-buffering the simulator's ``simulate()`` timeline
  assumes.  With one pdev there is one lane, and the calls are put, wait,
  dispatch, put, wait, dispatch, ...
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
realised-overlap signal —

    ``compute_start(k) <= transfer_start(k+1) < compute_end(k)``

(transfer k+1 began inside compute k's execution window) — is falsifiable in
both directions: a schedule that staged everything before any compute
would fail the left inequality (every transfer would precede every compute;
this rejection is structural, independent of timing noise), and a dispatch whose compute
drained before the next chunk was staged fails the right one.
:func:`timeline_overlaps` applies it to consecutive entries of one
timeline (serving's single-engine timelines); :func:`lane_overlaps`, which
:meth:`PipelineReport.overlaps` uses, applies it to consecutive tenants of
the same pdev, since across lanes transfers start before the previous
lane's compute by design.  One measurement caveat on the right inequality:
``compute_end`` is stamped at waiter-thread wakeup, so gaps shorter than a
thread wakeup (~tens of µs) are not resolved — the signal is meaningful
for ms-scale tenant computes, not µs-scale toys.  There is one waiter per
pdev, and a pdev's tenants complete in dispatch order (its device stream
serialises them), so the stamps carry no cross-pdev ordering skew.

Counter (enabled plane): ``transfer.lanes_parallel_puts``, each put enqueued
while another lane's chunk is still in flight — 0 with one pdev.
"""
from __future__ import annotations

import collections
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
    docstring for why this predicate is falsifiable).  Serving's
    single-engine timelines use it as it is; the executor's, which stage one
    lane per pdev, go through :func:`lane_overlaps`."""
    return [a.compute_start <= b.transfer_start < a.compute_end
            for a, b in zip(timeline, timeline[1:])]


def lane_overlaps(timeline: Sequence[TenantTimeline]) -> List[bool]:
    """:func:`timeline_overlaps` over each pdev's lane: the pairs are
    consecutive tenants of the same pdev, in timeline order."""
    lanes: Dict[int, List[TenantTimeline]] = {}
    for e in timeline:
        lanes.setdefault(e.pdev, []).append(e)
    return [ok for lane in lanes.values() for ok in timeline_overlaps(lane)]


@dataclasses.dataclass
class PipelineReport:
    results: Dict[int, Any]            # vdev -> device output
    timeline: List[TenantTimeline]     # in staging order
    wall_s: float
    mode: str

    def per_tenant_s(self) -> Dict[int, float]:
        return {tl.vdev: tl.compute_s for tl in self.timeline}

    def overlaps(self) -> List[bool]:
        return lane_overlaps(self.timeline)

    def overlap_realised(self) -> bool:
        # majority-of-pairs, matching every live consumer of the overlap
        # predicate (benchmarks + tests): noise on a shared host can
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
    """Event-driven executor: stage chunk k+1 while chunk k computes, one
    staging lane per pdev.

    The executor owns a :class:`StagingEngine` (for placement + the staging
    log) and drives its non-blocking ``put`` and blocking ``wait``,
    interleaving compute dispatch with the lanes' transfer chains.  Spans:
    ``pipeline.start`` around starting the waiter threads,
    ``pipeline.dispatch`` around each compute call, ``pipeline.drain``
    while the host waits for the last outputs.
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
                # one transfer at a time on each pdev's link, the lanes at
                # once; compute(k) of a lane is in flight while its chunk
                # k+1 stages (double buffering)
                lanes: Dict[int, collections.deque] = {}
                for task in order:
                    lanes.setdefault(task.pdev, collections.deque()).append(
                        task)
                in_flight: collections.deque = collections.deque()

                def put(task: TenantTask) -> None:
                    if in_flight:
                        self.tel.count("transfer.lanes_parallel_puts")
                    in_flight.append(
                        (task, self.engine.put(task, stage_fn(task), t0)))

                for lane in lanes.values():
                    put(lane.popleft())
                while in_flight:
                    task, chunk = in_flight.popleft()
                    dispatch(task, chunk)
                    if lanes[task.pdev]:
                        put(lanes[task.pdev].popleft())
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
