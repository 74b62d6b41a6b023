"""Idle time split by the innermost host span: intervals worked by hand,
and recorded v5e traces with and without the program's spans."""
import pathlib

import pytest

from bench import attribution, traces

DATA = pathlib.Path(__file__).parent / "data"
WITHOUT_SPANS = DATA / "risk_v5e.xplane.pb"
# one v5e chip, the paper's widths, 2 tenants, the plane on: two analyses
# in a 10.699759324 s `bench.window`
WITH_SPANS = DATA / "risk_v5e_spans.xplane.pb"

# one chip, a 100 ns window: busy [10,40] and [70,80], so idle [0,10],
# [40,70] and [80,100]; a collection on another thread inside the wait
OPS = [(10, 40, "%fusion.1 = f32[8] add(x)"), (70, 80, "%copy.2 = x")]
BENCH = [(0, 100, "bench.window"), (5, 95, "bench.analysis")]
PROGRAM = [(8, 90, "risk.analysis"), (38, 45, "risk.slice"),
           (45, 55, "transfer.put"), (55, 72, "transfer.wait"),
           (60, 65, "host.gc"), (82, 88, "risk.gather")]


def _trace(*chips):
    return traces.Trace({f"/device:TPU:{i}": ops for i, ops in
                         enumerate(chips)},
                        {f"/device:TPU:{i}": [] for i in range(len(chips))},
                        BENCH)


def test_idle_split_by_innermost_span_by_hand():
    t = _trace(OPS)
    parts = attribution.idle_by_span(t, PROGRAM)
    # [0,5] window, [5,8] analysis, [8,10] risk.analysis, [40,45] slice,
    # [45,55] put, [55,60] + [65,70] wait, [60,65] gc, [80,82] + [88,90]
    # risk.analysis, [82,88] gather, [90,95] analysis, [95,100] window
    assert parts == pytest.approx({
        "bench.window": 0.10, "bench.analysis": 0.08, "risk.analysis": 0.06,
        "risk.slice": 0.05, "transfer.put": 0.10, "transfer.wait": 0.10,
        "host.gc": 0.05, "risk.gather": 0.06})
    assert sum(parts.values()) == pytest.approx(traces.idle_share(t),
                                                abs=1e-9)
    staging = attribution.staging_idle_share(t, PROGRAM)
    unattributed = attribution.unattributed_idle_share(t, PROGRAM)
    assert staging == pytest.approx(25.0)
    assert unattributed == pytest.approx(24.0)
    named = sum(v for k, v in parts.items()
                if attribution.attributed(k)) * 100
    assert named + unattributed == pytest.approx(100 * traces.idle_share(t),
                                                 abs=1e-7)


def test_idle_split_averages_over_chips_and_clips_to_the_window():
    # a second chip busy all window halves every share; a span that
    # starts before the window counts only inside it
    t = _trace(OPS, [(0, 100, "%fusion.1 = f32[8] add(x)")])
    program = PROGRAM + [(-50, 3, "host.gc")]
    parts = attribution.idle_by_span(t, program)
    assert parts["host.gc"] == pytest.approx((5 + 3) / 2 / 100)
    assert parts["bench.window"] == pytest.approx((5 - 3 + 5) / 2 / 100)
    assert sum(parts.values()) == pytest.approx(traces.idle_share(t),
                                                abs=1e-9)
    assert attribution.staging_idle_share(t, program) == pytest.approx(12.5)


def test_no_program_spans_reads_nothing():
    recorded = traces.load(str(WITHOUT_SPANS))
    program = attribution.program_spans(str(WITHOUT_SPANS))
    assert program == []
    assert attribution.staging_idle_share(recorded, program) is None
    assert attribution.unattributed_idle_share(recorded, program) is None
    # with no program span the whole idle share is unattributed
    parts = attribution.idle_by_span(recorded, program)
    assert set(parts) == {"bench.window", "bench.analysis"}
    assert sum(parts.values()) == pytest.approx(
        traces.idle_share(recorded), abs=1e-9)


@pytest.fixture(scope="module")
def with_spans():
    return (traces.load(str(WITH_SPANS)),
            attribution.program_spans(str(WITH_SPANS)))


def test_recorded_program_spans(with_spans):
    recorded, program = with_spans
    counts = {}
    for _, _, name in program:
        counts[name] = counts.get(name, 0) + 1
    assert counts == {"risk.analysis": 2, "risk.plan": 2, "risk.tables": 2,
                      "risk.slice": 4, "transfer.put": 4, "transfer.wait": 4,
                      "pipeline.dispatch": 4, "pipeline.drain": 2,
                      "risk.gather": 2}
    # the benchmark's own spans and labels are what they were
    assert sorted({s[2] for s in recorded.spans}) == ["bench.analysis",
                                                      "bench.window"]
    assert all(g[0] == "bench.analysis" for g in traces.idle_gaps(recorded))


def test_recorded_idle_split_against_the_spans_read_by_hand(with_spans):
    """The chip is idle through both ``risk.tables`` spans (152.348 ms and
    151.077 ms).  Staging, read off the span edges and the first operation
    of each analysis (ns from the window's start): slices of 19,280 and
    16,680, puts of 472,580 and 491,950, and the waits up to the first
    operation, 154,334,030 -> 181,276,147 and the whole second wait,
    28,499,000; besides, gaps of a few ns to 0.7 us between operations
    while the second tenant's chunk is awaited add 1.4 us in all."""
    recorded, program = with_spans
    lo, hi = recorded.window
    tables = [e - s for s, e, n in program if n == "risk.tables"]
    parts = attribution.idle_by_span(recorded, program)
    assert parts["risk.tables"] * (hi - lo) == pytest.approx(sum(tables),
                                                             abs=1)
    assert sum(tables) == pytest.approx(0.303425e9, abs=1e3)
    staging_ns = (19280 + 472580 + (181276147 - 154334030)
                  + 16680 + 491950 + 28499000)
    assert attribution.staging_idle_share(recorded, program) == \
        pytest.approx(100 * staging_ns / (hi - lo), abs=100 * 2e3 / (hi - lo))
    unattributed = attribution.unattributed_idle_share(recorded, program)
    assert unattributed == pytest.approx(0.025765, abs=1e-6)
    assert unattributed < traces.idle_share(recorded) * 100 / 5
    assert sum(parts.values()) == pytest.approx(traces.idle_share(recorded),
                                                abs=1e-9)
