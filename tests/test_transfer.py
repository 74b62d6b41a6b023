"""Staging engine: modes, ordering, logs (single-device host)."""
import time

import numpy as np
import pytest

from repro.core.tenancy import TenancyConfig, VirtualDevicePool
from repro.core.transfer import StagingEngine


@pytest.fixture
def pool():
    return VirtualDevicePool(TenancyConfig(1, 4, "sequential"))


def _chunks(tasks, rng):
    data = {t.vdev: rng.normal(size=(t.size, 8)).astype(np.float32)
            for t in tasks}
    return data


def test_sequential_staging_order_and_log(pool, rng):
    tasks = pool.plan(64)
    data = _chunks(tasks, rng)
    eng = StagingEngine(pool)
    # sequential: each chunk is on the device before the next is enqueued,
    # every timestamp against one origin
    t0 = time.perf_counter()
    staged = [eng.wait(eng.put(t, {"x": data[t.vdev]}, t0)) for t in tasks]
    assert [c.task.vdev for c in staged] == [t.vdev for t in tasks]
    assert [e["vdev"] for e in eng.log] == [t.vdev for t in tasks]
    assert all(e["mode"] == "sequential" for e in eng.log)
    # every chunk has a ready timestamp, monotonically increasing
    times = [c.ready_s for c in staged]
    assert all(t is not None for t in times)
    assert times == sorted(times)
    assert all(c.enqueue_s <= c.ready_s for c in staged)
    # data round-trips
    np.testing.assert_array_equal(np.asarray(staged[0].arrays["x"]),
                                  data[staged[0].task.vdev])


def test_concurrent_staging(pool, rng):
    tasks = pool.plan(64)
    data = _chunks(tasks, rng)
    eng = StagingEngine(pool, mode="concurrent")
    # concurrent: every chunk enqueued, then each waited for
    staged = [eng.put(t, {"x": data[t.vdev]}) for t in tasks]
    assert all(c.ready_s is None for c in staged) and not eng.log
    for c in staged:
        eng.wait(c)
    assert len(staged) == 4
    assert all(c.ready_s is not None for c in staged)
    assert all(e["mode"] == "concurrent" for e in eng.log)


def test_stage_covers_all_items(pool, rng):
    tasks = pool.plan(37)  # ragged split
    data = _chunks(tasks, rng)
    eng = StagingEngine(pool)
    staged = [eng.wait(eng.put(t, {"x": data[t.vdev]})) for t in tasks]
    total = sum(c.arrays["x"].shape[0] for c in staged)
    assert total == 37
