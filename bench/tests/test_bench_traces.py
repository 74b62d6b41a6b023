"""Trace reduction: a recorded v5e trace of two risk analyses, and
intervals worked by hand."""
import pathlib

import pytest

from bench import traces

RECORDED = pathlib.Path(__file__).parent / "data" / "risk_v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    # one v5e chip, 131,072 trials x 1000 events x 15 ELTs, 2 tenants:
    # two analyses inside `bench.window`, each in `bench.analysis`
    return traces.load(str(RECORDED))


def test_recorded_trace_planes_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    names = sorted({s[2] for s in recorded.spans})
    assert names == ["bench.analysis", "bench.window"]
    lo, hi = recorded.window
    assert (hi - lo) / 1e9 == pytest.approx(10.751674601)


def test_recorded_trace_program_runs(recorded):
    runs = traces.module_runs(recorded, "jit__trial_losses")
    assert len(runs) == 4                         # 2 analyses x 2 tenants
    for s, e, _ in runs:
        assert (e - s) / 1e9 == pytest.approx(2.5824, abs=1e-3)
    assert traces.module_runs(recorded, "jit_other") == []


def test_recorded_trace_busy_and_idle(recorded):
    busy = traces.busy_s(recorded)
    runs = traces.module_runs(recorded, "jit__trial_losses")
    # every op runs inside a program run, and the kernel fills nearly all
    assert sum(e - s for s, e, _ in runs) / 1e9 >= busy > 10.3
    assert traces.idle_share(recorded) == pytest.approx(
        1 - busy / traces.window_s(recorded))
    top = traces.top_ops(recorded, 3)
    assert top[0][0] == "_trial_losses.1"
    assert top[0][1] == pytest.approx(4 * 2.5758, abs=5e-3)
    gaps = traces.idle_gaps(recorded, 3)
    assert len(gaps) == 3 and all(g[0] == "bench.analysis" for g in gaps)
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0


def _trace(ops, spans):
    return traces.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": []}, spans)


def test_merge_clips_and_joins():
    ivs = [(5, 8, "a"), (0, 2, "b"), (1, 3, "c"), (7, 12, "d")]
    assert traces.merge(ivs, 1, 10) == [(1, 3), (5, 10)]


def test_busy_idle_and_gaps_by_hand():
    ops = [(10, 30, "%fusion.1 = f32[8] add(x)"), (20, 40, "%copy.2 = x"),
           (70, 80, "%fusion.1 = f32[8] add(x)")]
    spans = [(0, 100, "bench.window"), (35, 75, "bench.analysis"),
             (50, 60, "bench.stage")]
    t = _trace(ops, spans)
    # busy [10,40] + [70,80] = 40 ns of a 100 ns window
    assert traces.busy_s(t) == pytest.approx(40e-9)
    assert traces.idle_share(t) == pytest.approx(0.6)
    assert traces.top_ops(t) == [["fusion.1", pytest.approx(30e-9)],
                                 ["copy.2", pytest.approx(20e-9)]]
    # gaps: [0,10] mid 5 (window), [40,70] mid 55 (stage), [80,100] mid 90
    assert traces.idle_gaps(t) == [["bench.stage", pytest.approx(30e-9)],
                                   ["bench.window", pytest.approx(20e-9)],
                                   ["bench.window", pytest.approx(10e-9)]]


def test_gaps_name_the_chip_when_there_are_several():
    t = traces.Trace({"/device:TPU:0": [(0, 10, "a")],
                      "/device:TPU:1": [(0, 4, "a")]},
                     {}, [(0, 10, "bench.window")])
    assert traces.busy_s(t) == pytest.approx(7e-9)
    assert traces.idle_gaps(t) == [["bench.window on TPU:1",
                                    pytest.approx(6e-9)]]


def test_a_trace_without_the_window_span_is_refused():
    t = _trace([(0, 10, "a")], [(0, 10, "bench.analysis")])
    with pytest.raises(ValueError, match="bench.window"):
        traces.busy_s(t)
