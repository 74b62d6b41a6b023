"""Aggregate Risk Analysis engine (paper Algorithm 1-3) with multi-tenancy.

An analysis prices one layer, or a portfolio of layers over the same ELTs
(a tower), in one step program: ``RiskTables`` with per-layer terms give a
``(T, L)`` year-loss table, one layer's terms a ``(T,)`` one.

Three execution paths over the same numerics (kernels/ops.aggregate_loss):

* ``run_single`` — one jit'd call over all trials (baseline, Algorithm 1 with
  N=1).
* ``run_tenant_chunked`` — the paper's deployment: the trial axis splits over
  ``n_pdev x tenants_per_pdev`` virtual devices and runs on the overlapped
  :class:`repro.core.pipeline.PipelineExecutor`: tenant k's jitted compute is
  dispatched the moment its chunk is device-resident, so tenant k+1's staging
  overlaps tenant k's compute (the paper's winning schedule, Fig 13) and each
  pdev's execution stream serialises its tenants.  Each pdev stages over its
  own lane, one chunk in flight a lane, the lanes at once.
* ``make_sharded_step`` — pjit over a mesh (trials sharded over every mesh
  axis) for the production dry-run; this is the "beyond-paper" scale-out.

Hot-path overhead control (all observable, asserted in tests/test_pipeline.py):

* **One trace per deployment** — tenant plans are uniform-padded
  (``VirtualDevicePool.plan(..., uniform=True)``), so ragged trial remainders
  share one chunk shape and the jitted step compiles once; ``trace_count``
  counts actual traces.
* **Resident tables** — the un-splittable ELT + occurrence-term tables (the
  cause of the paper's §V-B sub-linear scaling) are uploaded to each pdev
  once and cached on the engine keyed by table identity, so repeated runs
  (serving bursts, ``examples/risk_realtime.py``) stop re-staging ~120 MB per
  step; ``table_uploads`` counts actual uploads.  Layer aggregate terms stay
  dynamic arguments (a scalar pair, or ``(L,)`` each for a tower) — what-if
  pricing perturbs them without touching the cache or recompiling.

Spans (``repro.obs``; one attribute check each on a disabled plane): an
analysis is ``risk.analysis`` (attr ``layers``) > ``risk.plan``,
``risk.tables``, then the
executor's ``pipeline.start``, ``risk.slice`` / ``transfer.put`` /
``transfer.wait`` / ``pipeline.dispatch`` per tenant and
``pipeline.drain``, then ``risk.gather`` (YLT reassembly, every layer,
which reads the results back).  Each dispatched step counts
``risk.lookup.<path>``, the lookup its program was traced with
(``kernels.ops.aggregate_lookup``: ``vmem`` or ``xla`` on the Pallas
path, ``vmem.layers8`` for a tower of 8 layers).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.risk_app import RiskAppConfig
from repro.core.pipeline import PipelineExecutor, TenantTimeline
from repro.core.tenancy import TenancyConfig, TenantTask, VirtualDevicePool
from repro.kernels import ops as kops
from repro.obs.telemetry import Telemetry, get_telemetry
from repro.risk.tables import RiskTables

# resident per-pdev table sets kept per engine (LRU on table identity)
_TABLE_CACHE_SLOTS = 4


@dataclasses.dataclass
class RunReport:
    ylt: np.ndarray
    wall_s: float
    per_tenant_s: Dict[int, float]
    staging_log: List[Dict[str, float]]
    timeline: List[TenantTimeline]


def _loss_args(tables: RiskTables):
    return (jnp.asarray(tables.elt_losses), jnp.asarray(tables.occ_ret),
            jnp.asarray(tables.occ_lim), jnp.asarray(tables.agg_ret),
            jnp.asarray(tables.agg_lim))


class AggregateRiskAnalysis:
    def __init__(self, cfg: RiskAppConfig,
                 tenancy: Optional[TenancyConfig] = None,
                 devices: Optional[list] = None,
                 telemetry: Optional[Telemetry] = None):
        self.cfg = cfg
        self.tel = get_telemetry(telemetry)
        self.tenancy = tenancy or TenancyConfig(
            n_pdev=max(1, len(devices or jax.devices())),
            tenants_per_pdev=cfg.tenants_per_device,
            transfer_mode=cfg.transfer_mode)
        self.pool = VirtualDevicePool(self.tenancy,
                                      devices or jax.devices())
        self._step = jax.jit(self._trial_losses, static_argnames=("chunk",))
        self.trace_count = 0          # incremented at trace time only
        # (ELT shape, occurrence-term shape) -> the risk.lookup.* counter of
        # the traced step
        self._lookup_counters: Dict[Tuple, str] = {}
        self.table_uploads = 0        # host->device ELT/term table stagings
        # key -> (host refs pinning the key's id()s, {pdev: device arrays})
        self._table_cache: "collections.OrderedDict[Tuple, Tuple]" = \
            collections.OrderedDict()

    # ------------------------------------------------------------------
    def _trial_losses(self, yet, elt, occ_ret, occ_lim, agg_ret, agg_lim,
                      chunk: int):
        self.trace_count += 1         # side effects run only while tracing
        layers = occ_ret.shape[0] if occ_ret.ndim == 2 else None
        self._lookup_counters[elt.shape, occ_ret.shape] = "risk.lookup." + \
            kops.aggregate_lookup(elt.shape[0], layers=layers)
        return kops.aggregate_loss(yet, elt, occ_ret, occ_lim, agg_ret,
                                   agg_lim, chunk=chunk)

    # ------------------------------------------------------------------
    # sampled elements per large array in the cache-staleness tripwire
    _FP_SAMPLES = 256

    @classmethod
    def _table_fingerprint(cls, host: Tuple[np.ndarray, ...]) -> Tuple:
        """Cheap content check guarding the id()-keyed cache against
        in-place mutation.  Small arrays (the per-ELT occurrence terms) are
        fingerprinted in full; the large ELT table by shape/dtype plus a
        strided ``_FP_SAMPLES``-element sample (every ``size // samples``-th
        element in C order), read in place whatever the array's layout, so
        it stays O(1) in table size for a column-major table too (a TPU's
        ``device_get`` of the ELT is one).
        This is a *tripwire*, not a guarantee: a sparse in-place edit of the
        big table can slip past the sample (see the cache contract in
        :meth:`_resident_tables`)."""
        out = []
        for a in host:
            if a.size <= 4 * cls._FP_SAMPLES:
                out.append((a.shape, str(a.dtype), a.tobytes()))
            else:
                step = max(1, a.size // cls._FP_SAMPLES)
                at = np.unravel_index(
                    np.arange(cls._FP_SAMPLES) * step, a.shape)
                out.append((a.shape, str(a.dtype), a[at].tobytes()))
        return tuple(out)

    def _resident_tables(self, tables: RiskTables) -> Dict[int, Tuple]:
        """Per-pdev device copies of the un-splittable ELT + occurrence
        terms, cached across runs; LRU-capped at ``_TABLE_CACHE_SLOTS``
        table sets.

        Cache contract: tables handed to the engine are treated as
        **immutable** — derive what-if variants with ``dataclasses.replace``
        and fresh arrays (as ``examples/risk_realtime.py`` does) rather than
        mutating in place.  The cache is keyed by host-array identity (the
        entry pins the arrays, so ids cannot be recycled) and revalidated
        against :meth:`_table_fingerprint`: full content for the small term
        arrays, a strided sample of the big ELT.  Whole-table and term
        mutations therefore trigger a re-upload, but a sparse in-place edit
        of the ELT that misses every sampled element can still serve stale
        device copies — honour the contract."""
        host = (tables.elt_losses, tables.occ_ret, tables.occ_lim)
        key = tuple(id(a) for a in host)
        fp = self._table_fingerprint(host)
        if key in self._table_cache:
            if self._table_cache[key][2] == fp:
                self._table_cache.move_to_end(key)
                return self._table_cache[key][1]
            del self._table_cache[key]      # mutated in place: stale copy
        by_pdev: Dict[int, Tuple] = {}
        for p in range(self.tenancy.n_pdev):
            dev = (self.pool.devices[p]
                   if self.pool.devices is not None else None)
            by_pdev[p] = tuple(
                jax.device_put(a, dev) if dev is not None else jnp.asarray(a)
                for a in host)
            self.table_uploads += 1
        self._table_cache[key] = (host, by_pdev, fp)
        while len(self._table_cache) > _TABLE_CACHE_SLOTS:
            self._table_cache.popitem(last=False)
        return by_pdev

    def clear_table_cache(self) -> None:
        """Release every resident table set (host pins + per-pdev device
        copies).  Long-lived engines cycling through many table sets should
        call this when a working set retires — the LRU cap bounds entry
        count, not bytes, and at paper scale one entry pins ~120 MB per
        pdev."""
        self._table_cache.clear()

    # ------------------------------------------------------------------
    def run_single(self, tables: RiskTables) -> np.ndarray:
        """Whole-YET single-device run (Algorithm 1, N=1)."""
        args = _loss_args(tables)
        ylt = self._step(jnp.asarray(tables.yet), *args,
                         chunk=min(self.cfg.chunk_events,
                                   tables.yet.shape[1]))
        return np.asarray(ylt)

    # ------------------------------------------------------------------
    def run_tenant_chunked(self, tables: RiskTables,
                           straggler_hist: Optional[Dict[int, float]] = None
                           ) -> RunReport:
        """Multi-tenant execution per the tenancy plan, on the event-driven
        pipeline: compute(k) dispatches as soon as chunk k lands, and
        staging of chunk k+1 overlaps it."""
        tel = self.tel
        with tel.span("risk.analysis", layers=tables.num_layers):
            t_start = time.perf_counter()
            with tel.span("risk.plan"):
                tasks = self.pool.plan(tables.num_trials, uniform=True)
            with tel.span("risk.tables"):
                resident = self._resident_tables(tables)
            agg_ret = np.asarray(tables.agg_ret, np.float32)
            agg_lim = np.asarray(tables.agg_lim, np.float32)
            chunk = min(self.cfg.chunk_events, tables.yet.shape[1])

            def stage_fn(t: TenantTask):
                with tel.span("risk.slice"):
                    sl = tables.yet[t.start:t.stop]
                    if t.pad:     # neutral rows: pad event id 0 -> loss 0
                        sl = np.concatenate(
                            [sl, np.zeros((t.pad, sl.shape[1]), sl.dtype)])
                return {"yet": sl}

            def compute_fn(t: TenantTask, arrays):
                elt, occ_ret, occ_lim = resident[t.pdev]
                out = self._step(arrays["yet"], elt, occ_ret, occ_lim,
                                 agg_ret, agg_lim, chunk=chunk)
                tel.count(self._lookup_counters[elt.shape, occ_ret.shape])
                return out

            ylt = np.zeros(tables.ylt_shape, np.float32)
            ex = PipelineExecutor(self.pool, telemetry=tel)
            rep = ex.run(tasks, stage_fn, compute_fn, straggler_hist)
            with tel.span("risk.gather"):
                for t in tasks:
                    ylt[t.start:t.stop] = \
                        np.asarray(rep.results[t.vdev])[:t.size]
            return RunReport(ylt, time.perf_counter() - t_start,
                             rep.per_tenant_s(), ex.engine.log, rep.timeline)

    # ------------------------------------------------------------------
    def make_sharded_step(self, mesh, chunk: Optional[int] = None):
        """pjit'd analysis step with trials sharded over every mesh axis
        (embarrassingly parallel leading axis -> all axes are data axes)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        axes = tuple(mesh.axis_names)
        c = chunk or self.cfg.chunk_events

        def step(yet, elt, occ_ret, occ_lim, agg_ret, agg_lim):
            yet = jax.lax.with_sharding_constraint(
                yet, NamedSharding(mesh, P(axes,)))
            return kops.aggregate_loss(yet, elt, occ_ret, occ_lim,
                                       agg_ret, agg_lim, chunk=c)

        return jax.jit(step)

    def input_specs(self, num_trials: Optional[int] = None):
        """ShapeDtypeStructs for the dry-run (no allocation)."""
        cfg = self.cfg
        T = num_trials or cfg.num_trials
        K, M, cat = cfg.events_per_trial, cfg.num_elts, cfg.event_catalog
        f32, i32 = jnp.float32, jnp.int32
        layer = (cfg.num_layers,) if cfg.num_layers > 1 else ()
        return {
            "yet": jax.ShapeDtypeStruct((T, K), i32),
            "elt": jax.ShapeDtypeStruct((cat + 1, M), f32),
            "occ_ret": jax.ShapeDtypeStruct(layer + (M,), f32),
            "occ_lim": jax.ShapeDtypeStruct(layer + (M,), f32),
            "agg_ret": jax.ShapeDtypeStruct(layer, f32),
            "agg_lim": jax.ShapeDtypeStruct(layer, f32),
        }
