"""Overlapped execution pipeline: equivalence, caching, overlap contract.

Covers the simulator-vs-executable overlap contract documented in
repro.core.pipeline: the overlapped run_tenant_chunked must be bit-identical
to run_single across tenancy configs, must not retrace or re-upload resident
tables on repeated runs, and its timeline must show tenant k+1's transfer
starting before tenant k's compute ends, k and k+1 on one pdev's staging
lane; the lanes of several pdevs stage at once, one chunk in flight a
lane.  The multi-device case runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag
must precede jax initialisation, which this process has already done).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.risk_app import RiskAppConfig
from repro.core.pipeline import (PipelineExecutor, PipelineReport,
                                 TenantTimeline, lane_overlaps,
                                 timeline_overlaps)
from repro.core.tenancy import TenancyConfig, VirtualDevicePool
from repro.core.transfer import StagedChunk
from repro.obs.telemetry import Telemetry
from repro.risk.analysis import AggregateRiskAnalysis
from repro.risk.tables import generate


@pytest.fixture(scope="module")
def cfg():
    return RiskAppConfig().reduced()


@pytest.fixture(scope="module")
def tables(cfg):
    return generate(cfg, seed=0)


@pytest.mark.parametrize("tenants,mode", [(1, "sequential"),
                                          (2, "sequential"),
                                          (4, "sequential"),
                                          (1, "concurrent"),
                                          (2, "concurrent"),
                                          (4, "concurrent")])
def test_overlapped_bit_identical_to_single(cfg, tables, tenants, mode):
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, tenants, mode))
    single = ara.run_single(tables)
    rep = ara.run_tenant_chunked(tables)
    np.testing.assert_array_equal(rep.ylt, single)
    assert len(rep.per_tenant_s) == tenants
    assert rep.timeline is not None and len(rep.timeline) == tenants


def test_ragged_trials_bit_identical(cfg):
    """67 trials over 4 vdevs: uniform padding must not perturb results."""
    t67 = generate(dataclasses.replace(cfg, num_trials=67), seed=3)
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 4))
    np.testing.assert_array_equal(ara.run_tenant_chunked(t67).ylt,
                                  ara.run_single(t67))


def test_no_retrace_across_runs_and_ragged_remainders(cfg, tables):
    """Uniform padding -> one chunk shape -> exactly one trace, even with a
    ragged remainder, and re-runs hit the jit cache."""
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 4))
    t0 = ara.trace_count
    ara.run_tenant_chunked(tables)
    assert ara.trace_count == t0 + 1       # one compile for all 4 tenants
    ara.run_tenant_chunked(tables)
    t67 = generate(dataclasses.replace(cfg, num_trials=67), seed=1)
    # 67 = 4x16+3: unpadded this would need two traces (17- and 16-row)
    ara.run_tenant_chunked(t67)
    ara.run_tenant_chunked(t67)
    assert ara.trace_count == t0 + 2       # only the new 17-row shape


def test_resident_tables_uploaded_once(cfg, tables):
    """Second run must not re-stage the un-splittable ELT/term tables."""
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2))
    ara.run_tenant_chunked(tables)
    uploads = ara.table_uploads
    ara.run_tenant_chunked(tables)
    assert ara.table_uploads == uploads    # cache hit, no second upload
    # perturbing only the layer aggregate terms (what-if pricing) keeps
    # table identity, so still no upload
    t2 = dataclasses.replace(tables, agg_ret=tables.agg_ret * 1.5)
    ara.run_tenant_chunked(t2)
    assert ara.table_uploads == uploads
    # genuinely new tables do upload
    ara.run_tenant_chunked(generate(cfg, seed=9))
    assert ara.table_uploads > uploads


def test_resident_cache_detects_inplace_mutation(cfg, tables):
    """Fingerprint revalidation of the id()-keyed cache: whole-table and
    term mutations re-upload instead of serving stale device copies.  (The
    documented contract still forbids in-place mutation — a *sparse* ELT
    edit can slip past the sampled fingerprint; these are the tripwire
    cases it must catch.)"""
    t = generate(cfg, seed=11)
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2))
    before = ara.run_tenant_chunked(t).ylt.copy()
    uploads = ara.table_uploads
    t.elt_losses *= 2.0                    # same array object, new content
    after = ara.run_tenant_chunked(t).ylt
    assert ara.table_uploads > uploads     # stale entry evicted + re-staged
    np.testing.assert_array_equal(after, ara.run_single(t))
    assert not np.array_equal(before, after)
    # single-element edit of the (small, fully-fingerprinted) term arrays
    uploads = ara.table_uploads
    t.occ_ret[0] *= 0.5
    np.testing.assert_array_equal(ara.run_tenant_chunked(t).ylt,
                                  ara.run_single(t))
    assert ara.table_uploads > uploads


def test_table_fingerprint_reads_a_column_major_table_in_place():
    """The cache check on every analysis samples the ELT where it lies: a
    column-major table (what a TPU's ``device_get`` gives) fingerprints as
    its row-major copy does, without copying the table."""
    import tracemalloc
    rng = np.random.default_rng(0)
    elt = np.asfortranarray(rng.random((200_001, 15), dtype=np.float32))
    terms = np.ones((8, 15), np.float32)
    want = AggregateRiskAnalysis._table_fingerprint(
        (np.ascontiguousarray(elt), terms))
    tracemalloc.start()
    got = AggregateRiskAnalysis._table_fingerprint((elt, terms))
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert got == want
    assert peak < elt.nbytes // 100, peak


def test_sequential_timeline_overlaps(cfg):
    """transfer(k+1) starts inside compute(k)'s window — the paper's
    overlap, with the falsifiable predicate from core.pipeline.  Uses a
    workload big enough that each tenant's compute outlasts one staging
    step (the predicate is honest: it would fail on a blocking schedule)."""
    big = dataclasses.replace(cfg, num_trials=32768, events_per_trial=128,
                              chunk_events=128)
    tb = generate(big, seed=0)
    ara = AggregateRiskAnalysis(big, TenancyConfig(1, 4, "sequential"))
    ara.run_tenant_chunked(tb)                      # warm: exclude compile
    rep = ara.run_tenant_chunked(tb)
    tl = rep.timeline
    assert len(tl) == 4
    # majority of pairs overlapped: a blocking schedule scores 0 (its
    # transfers all precede its computes), while noise on a shared host can
    # legitimately drain isolated pairs early
    from repro.core.pipeline import timeline_overlaps
    ov = timeline_overlaps(tl)
    assert sum(ov) > len(ov) // 2, ov
    for e in tl:
        assert e.transfer_start <= e.transfer_end <= e.compute_start \
            <= e.compute_end


def test_straggler_reorder_with_pipeline(cfg, tables):
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 4))
    hist = {0: 5.0, 1: 1.0, 2: 3.0, 3: 0.5}
    rep = ara.run_tenant_chunked(tables, straggler_hist=hist)
    np.testing.assert_array_equal(rep.ylt, ara.run_single(tables))
    # slowest previous tenant is staged (and therefore timed) first
    assert rep.timeline[0].vdev == 0


def test_executor_generic_payload():
    """The executor is workload-agnostic: any stage_fn/compute_fn pair."""
    import jax.numpy as jnp
    pool = VirtualDevicePool(TenancyConfig(1, 3, "sequential"))
    tasks = pool.plan(30, uniform=True)
    data = np.arange(30, dtype=np.float32)
    ex = PipelineExecutor(pool)
    rep = ex.run(tasks,
                 lambda t: data[t.start:t.stop],
                 lambda t, x: jnp.asarray(x) * 2.0)
    assert rep.mode == "sequential"
    out = np.concatenate([np.asarray(rep.results[t.vdev]) for t in tasks])
    np.testing.assert_array_equal(out, data * 2.0)
    assert rep.wall_s > 0 and len(rep.timeline) == 3


def test_executor_propagates_waiter_errors():
    """A device error surfacing in the waiter thread must re-raise on the
    main thread, not silently yield a partial result dict."""
    class Boom:
        def block_until_ready(self):
            raise RuntimeError("device boom")

    pool = VirtualDevicePool(TenancyConfig(1, 2, "sequential"))
    tasks = pool.plan(4, uniform=True)
    ex = PipelineExecutor(pool)
    with pytest.raises(RuntimeError, match="device boom"):
        ex.run(tasks, lambda t: np.float32([1.0]), lambda t, x: Boom())


def test_executor_reaps_waiter_on_stage_error():
    """stage_fn raising mid-loop must not leak a blocked waiter thread."""
    import threading

    def bad_stage(t):
        raise ValueError("bad stage")

    pool = VirtualDevicePool(TenancyConfig(1, 2, "sequential"))
    ex = PipelineExecutor(pool)
    with pytest.raises(ValueError, match="bad stage"):
        ex.run(pool.plan(4, uniform=True), bad_stage, lambda t, x: x)
    assert not any(th.name == "pipeline-waiter" and th.is_alive()
                   for th in threading.enumerate())


class _RecordingEngine:
    """Stands in for the executor's StagingEngine: records each put and
    wait as (call, pdev, slot) and stages the host tree as it is."""

    def __init__(self, calls):
        self.calls = calls
        self.log = []

    def put(self, task, host_tree, t0=None):
        self.calls.append(("put", task.pdev, task.slot))
        return StagedChunk(task, host_tree, 0.0)

    def wait(self, chunk, t0=None):
        self.calls.append(("wait", chunk.task.pdev, chunk.task.slot))
        return chunk


def _record_sequential(n_pdev, tenants):
    """One sequential run over n_pdev x tenants on the recording engine:
    the calls in order, and the plane's counters."""
    pool = VirtualDevicePool(TenancyConfig(n_pdev, tenants, "sequential"))
    tel = Telemetry(enabled=True)
    ex = PipelineExecutor(pool, telemetry=tel)
    calls = []
    ex.engine = _RecordingEngine(calls)

    def compute(t, x):
        calls.append(("dispatch", t.pdev, t.slot))
        return x

    tasks = pool.plan(4 * n_pdev * tenants, uniform=True)
    rep = ex.run(tasks, lambda t: np.float32([t.vdev]), compute)
    assert sorted(rep.results) == [t.vdev for t in tasks]
    return calls, tel.counter_snapshot()


def test_lanes_enqueue_every_head_before_the_first_wait():
    calls, _ = _record_sequential(4, 2)
    first_wait = next(i for i, c in enumerate(calls) if c[0] == "wait")
    heads = [calls.index(("put", p, 0)) for p in range(4)]
    assert max(heads) < first_wait, calls
    # waits and dispatches follow the staging order, slot-major
    order = [(p, s) for s in range(2) for p in range(4)]
    assert [c[1:] for c in calls if c[0] == "wait"] == order
    assert [c[1:] for c in calls if c[0] == "dispatch"] == order


def test_lane_holds_one_chunk_in_flight():
    calls, _ = _record_sequential(4, 2)
    in_flight = {p: 0 for p in range(4)}
    for call, pdev, slot in calls:
        if call == "put":
            in_flight[pdev] += 1
            assert in_flight[pdev] == 1, calls
        elif call == "wait":
            in_flight[pdev] -= 1
    for p in range(4):
        assert calls.index(("put", p, 1)) > calls.index(("wait", p, 0))
        # the lane's next chunk is enqueued as soon as its compute dispatched
        assert calls.index(("put", p, 1)) == \
            calls.index(("dispatch", p, 0)) + 1


def test_one_lane_keeps_put_wait_dispatch_order():
    calls, _ = _record_sequential(1, 2)
    assert calls == [("put", 0, 0), ("wait", 0, 0), ("dispatch", 0, 0),
                     ("put", 0, 1), ("wait", 0, 1), ("dispatch", 0, 1)]


@pytest.mark.parametrize("n_pdev,parallel_puts", [(1, 0), (4, 7)])
def test_lanes_parallel_puts_counter(n_pdev, parallel_puts):
    """Each put enqueued while another lane's chunk is in flight counts:
    none on one pdev; on four, three of the four heads and every
    next chunk (the other lanes' chunks are still in flight)."""
    _, counters = _record_sequential(n_pdev, 2)
    assert counters.get("transfer.lanes_parallel_puts", 0) == parallel_puts


def _tl(pdev, slot, transfer, compute):
    return TenantTimeline(2 * slot + pdev, pdev, slot, transfer[0],
                          transfer[1], compute[0], compute[1])


def test_lane_overlaps_pairs_tenants_of_one_pdev():
    """Two lanes staged at once: each lane's second transfer starts inside
    its first compute, so every lane pair overlaps, while the global
    staging order pairs chunks of different lanes that do not.  A blocking
    schedule (every transfer before any compute) scores no pair."""
    lanes = [_tl(0, 0, (0, 7), (7, 31)), _tl(1, 0, (0, 7), (8, 32)),
             _tl(0, 1, (7.5, 14), (31, 55)), _tl(1, 1, (8.5, 15), (32, 56))]
    assert lane_overlaps(lanes) == [True, True]
    assert not all(timeline_overlaps(lanes))
    blocking = [_tl(0, 0, (0, 7), (28, 52)), _tl(1, 0, (7, 14), (29, 53)),
                _tl(0, 1, (14, 21), (52, 76)), _tl(1, 1, (21, 28), (53, 77))]
    assert lane_overlaps(blocking) == [False, False]
    rep = PipelineReport({}, lanes, 56.0, "sequential")
    assert rep.overlaps() == [True, True] and rep.overlap_realised()


def test_uniform_plan_shapes():
    pool = VirtualDevicePool(TenancyConfig(2, 2))
    tasks = pool.plan(67, uniform=True)
    assert all(t.padded_size == 17 for t in tasks)
    assert sum(t.size for t in tasks) == 67
    assert {t.size + t.pad for t in tasks} == {17}
    # non-uniform plan keeps the legacy contract
    legacy = pool.plan(67)
    assert all(t.padded_size is None and t.pad == 0 for t in legacy)


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import dataclasses
    import numpy as np
    from repro.configs.risk_app import RiskAppConfig
    from repro.core.tenancy import TenancyConfig
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import generate
    import jax

    devs = jax.devices()
    assert len(devs) == 8, devs
    cfg = dataclasses.replace(RiskAppConfig().reduced(), num_trials=4096)
    tables = generate(cfg, seed=0)
    for tenants, mode in [(1, "sequential"), (2, "sequential"),
                          (2, "concurrent")]:
        ara = AggregateRiskAnalysis(cfg, TenancyConfig(8, tenants, mode),
                                    devices=devs)
        rep = ara.run_tenant_chunked(tables)
        np.testing.assert_array_equal(rep.ylt, ara.run_single(tables))
        assert len(rep.per_tenant_s) == 8 * tenants
        # chunks really live on their pdev
        placed = {t.vdev: t.pdev for t in ara.pool.plan(tables.num_trials)}
        assert len(set(placed.values())) == 8
    # overlap contract on real multi-device: warm, then check the timeline
    # (transfer k+1 inside compute k's window — needs compute that outlasts
    # one staging step, hence the bigger workload).  A blocking schedule
    # scores 0/8 pairs (its transfers all precede its computes), so a
    # majority of overlapped pairs distinguishes the schedules even on a
    # noisy shared-CPU host where individual pairs can legitimately drain
    # early under contention.  The pairs are consecutive tenants of one
    # pdev (its staging lane): across lanes, transfers start before the
    # previous lane's compute by design.
    from repro.core.pipeline import lane_overlaps
    big = dataclasses.replace(RiskAppConfig().reduced(), num_trials=65536,
                              events_per_trial=64, chunk_events=64)
    tbig = generate(big, seed=0)
    ara = AggregateRiskAnalysis(big, TenancyConfig(8, 2, "sequential"),
                                devices=devs)
    ara.run_tenant_chunked(tbig)
    ov = lane_overlaps(ara.run_tenant_chunked(tbig).timeline)
    assert len(ov) == 8, ov
    assert sum(ov) > len(ov) // 2, ov
    print("MULTI_DEVICE_OK")
""")


def test_multi_device_pipeline_subprocess(cfg):
    """8 host devices need XLA_FLAGS before jax init -> subprocess."""
    env = dict(os.environ)
    # append (not prepend): the last repetition of a flag wins, and earlier
    # suite imports (launch.dryrun) may have left a device-count in XLA_FLAGS
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MULTI_DEVICE_OK" in proc.stdout
