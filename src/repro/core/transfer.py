"""Host -> accelerator staging engines (paper §V-D1).

Two modes:
  * CONCURRENT — enqueue every tenant chunk at once; all transfers share the
    host link (each attains ~BW/n, Fig 8/10).
  * SEQUENTIAL — enqueue chunks one at a time in slot-major tenant order;
    each transfer gets full link bandwidth and tenant k's compute overlaps
    tenant k+1's staging (the paper's winning strategy).

`jax.device_put` is asynchronous, so SEQUENTIAL staging naturally overlaps
the already-dispatched tenant's compute.  The engine records per-chunk wall
times for the benchmark harness.

The engine exposes two levels of API: non-blocking :meth:`StagingEngine.put`
/ :meth:`StagingEngine.wait` primitives that the overlapped executor in
:mod:`repro.core.pipeline` interleaves with compute dispatch (the paper's
winning schedule), and the stage-everything :meth:`StagingEngine.stage`
entry point (the pre-pipeline blocking schedule, kept for A/B benchmarks).

Spans: ``transfer.put`` around enqueueing a chunk, ``transfer.wait``
around blocking until it is device-resident; ``transfer.stage`` is the
retrospective enqueue-to-ready window of each chunk.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.tenancy import TenantTask, TenancyConfig, VirtualDevicePool
from repro.obs.telemetry import Telemetry, get_telemetry


def _tree_bytes(tree: Any) -> int:
    """Total payload bytes of a pytree (host or device leaves)."""
    return sum(getattr(a, "nbytes", 0) for a in jax.tree.leaves(tree))


@dataclasses.dataclass
class StagedChunk:
    task: TenantTask
    arrays: Any                   # device-resident pytree
    enqueue_s: float
    ready_s: Optional[float] = None
    base_s: float = 0.0           # perf_counter() origin of the timestamps


class StagingEngine:
    def __init__(self, pool: VirtualDevicePool, mode: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None):
        self.pool = pool
        self.mode = mode or pool.cfg.transfer_mode
        assert self.mode in ("sequential", "concurrent")
        self.log: List[Dict[str, float]] = []
        self.tel = get_telemetry(telemetry)

    # ------------------------------------------------------------------
    def _put(self, host_tree, device) -> Any:
        if device is None:
            return jax.tree.map(jax.numpy.asarray, host_tree)
        return jax.tree.map(lambda a: jax.device_put(a, device), host_tree)

    # -- non-blocking primitives (used by core.pipeline) ----------------
    def put(self, task: TenantTask, host_tree: Any,
            t0: Optional[float] = None) -> StagedChunk:
        """Enqueue one tenant chunk's host->device transfer (asynchronous:
        ``jax.device_put`` returns immediately).  ``t0`` anchors the chunk's
        timestamps; without it the enqueue instant is the origin."""
        base = t0 if t0 is not None else time.perf_counter()
        with self.tel.span("transfer.put"):
            arrays = self._put(host_tree, self.pool.device_of(task.vdev))
        return StagedChunk(task, arrays, time.perf_counter() - base,
                           base_s=base)

    def wait(self, chunk: StagedChunk, t0: Optional[float] = None) -> StagedChunk:
        """Block until the chunk is device-resident; records the ready time
        against the same origin ``put`` used (or an explicit ``t0``).
        While the caller blocks here, previously dispatched compute keeps
        running on its device — this is the pipeline's overlap point."""
        with self.tel.span("transfer.wait"):
            jax.block_until_ready(chunk.arrays)
        base = t0 if t0 is not None else chunk.base_s
        chunk.ready_s = time.perf_counter() - base
        self.log.append({"vdev": chunk.task.vdev, "ready_s": chunk.ready_s,
                         "mode": self.mode})
        if self.tel.enabled:
            # the staging-lane span: enqueue -> device-resident, stamped
            # against the same origin the chunk's log times use
            nbytes = _tree_bytes(chunk.arrays)
            self.tel.record_span("transfer.stage", base + chunk.enqueue_s,
                                 base + chunk.ready_s, vdev=chunk.task.vdev,
                                 pdev=chunk.task.pdev, slot=chunk.task.slot,
                                 mode=self.mode, bytes=nbytes)
            self.tel.count("transfer.bytes", nbytes)
            self.tel.count("transfer.chunks")
        return chunk

    def stage(self, tasks: Sequence[TenantTask],
              chunk_of: Callable[[TenantTask], Any],
              block: bool = False) -> List[StagedChunk]:
        """Stage every tenant chunk per the configured mode.

        ``chunk_of(task)`` returns the host pytree for that tenant.  In
        sequential mode each chunk blocks until on-device before the next is
        enqueued (full-bandwidth transfers); concurrent mode enqueues all and
        only then (optionally) waits.

        This is the *stage-everything* entry point (the pre-pipeline blocking
        path, kept for A/B benchmarking); the overlapped executor in
        :mod:`repro.core.pipeline` drives :meth:`put`/:meth:`wait` directly so
        compute dispatch can interleave with staging.
        """
        t0 = time.perf_counter()
        out: List[StagedChunk] = []
        if self.mode == "sequential":
            for t in tasks:
                c = self.put(t, chunk_of(t), t0)
                self.wait(c, t0)
                out.append(c)
        else:
            for t in tasks:
                out.append(self.put(t, chunk_of(t), t0))
            if block:
                for c in out:
                    self.wait(c, t0)
        return out


@dataclasses.dataclass
class MeshStagedChunk:
    """One logical host->device transfer split across per-device lanes."""
    chunks: Dict[Any, StagedChunk]      # device -> its slice's StagedChunk
    host_tree: Any                      # original host pytree (for assembly)
    sharding_of: Callable[[Any], Any]   # leaf -> target NamedSharding


class MeshStagingLanes:
    """Per-mesh-slice staging: one sequential :class:`StagingEngine` per
    device of the mesh (the PR-1 multi-host staging item, revived).

    A host pytree destined for a sharded placement is split per device along
    the target sharding's index map and each slice rides its own lane —
    every lane is an independent sequential engine, so each transfer gets its
    slice of the link while slices of *different* lanes overlap.  ``wait``
    reassembles the staged single-device shards into committed global arrays
    with :func:`jax.make_array_from_single_device_arrays` (replicated leaves
    degenerate to one full copy per lane).
    """

    def __init__(self, mesh, telemetry: Optional[Telemetry] = None):
        self.mesh = mesh
        self.tel = get_telemetry(telemetry)
        devs = [d for d in mesh.devices.reshape(-1)]
        # each lane reports its own ``transfer.stage`` spans (pdev = lane
        # ordinal) onto the same plane
        self.engines = {
            d: StagingEngine(VirtualDevicePool(
                TenancyConfig(1, 1, "sequential"), devices=[d]),
                telemetry=self.tel)
            for d in devs}

    @property
    def n_lanes(self) -> int:
        return len(self.engines)

    def put(self, host_tree: Any, sharding_of: Callable[[Any], Any],
            slot: int = 0) -> MeshStagedChunk:
        chunks: Dict[Any, StagedChunk] = {}
        for lane, (dev, eng) in enumerate(self.engines.items()):
            def slice_leaf(a, _dev=dev):
                idx = sharding_of(a).devices_indices_map(a.shape)[_dev]
                return a[idx]
            task = TenantTask(vdev=0, pdev=lane, slot=slot, start=0, stop=1)
            chunks[dev] = eng.put(task, jax.tree.map(slice_leaf, host_tree))
        return MeshStagedChunk(chunks, host_tree, sharding_of)

    def wait(self, staged: MeshStagedChunk) -> Any:
        """Block every lane, then assemble the global sharded arrays."""
        with self.tel.span("transfer.assemble", lanes=len(staged.chunks)):
            for dev, chunk in staged.chunks.items():
                self.engines[dev].wait(chunk)
        devs = list(staged.chunks)

        def assemble(path_leaves):
            host, *shards = path_leaves
            sharding = staged.sharding_of(host)
            return jax.make_array_from_single_device_arrays(
                host.shape, sharding, list(shards))

        return jax.tree.map(
            lambda *leaves: assemble(leaves), staged.host_tree,
            *[staged.chunks[d].arrays for d in devs])


def reorder_for_stragglers(tasks: Sequence[TenantTask],
                           last_step_times: Optional[Dict[int, float]],
                           ) -> List[TenantTask]:
    """Straggler mitigation: stage the slowest tenant of the previous step
    first so its data is ready earliest (DESIGN.md §7)."""
    if not last_step_times:
        return list(tasks)
    return sorted(tasks, key=lambda t: -last_step_times.get(t.vdev, 0.0))
