"""The harness end to end on the CPU at a small size: a cell is found from
its files by name, a sound run comes out correct, and a run whose timed
path is broken underneath, or whose step is the bfloat16 control, does
not.  Nothing here looks for a chip: ``require_chip=False``."""
import json
import pathlib
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import risk as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
SMALL = dict(num_trials=256, events_per_trial=40, num_elts=3,
             event_catalog=512, chunk_events=16)


def small_cell(workload="risk-paper-1chip"):
    cell = harness.resolve(BENCHMARK, workload)
    cell.config.update(SMALL)
    return cell


def run(cell, seed=2**31 + 5, seconds=0.2):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            require_chip=False)


def test_sound_run_is_correct_and_reports_its_metrics():
    out = run(small_cell())
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"trials_per_s", "setup_s"}
    assert out["metrics"]["trials_per_s"]["unit"] == "trials/s"
    assert out["device"]["count"] >= 1
    assert list(out)[-1] == "checks"
    c = out["checks"]["ylt_rel_err"]
    assert c["value"] < c["limit"]
    assert out["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}


@pytest.fixture
def altered_answer(monkeypatch):
    """The step's year-loss of its largest trial is 1% off where the
    kernel produces it."""
    from repro.kernels import ops
    real = ops.aggregate_loss

    def altered(*a, **k):
        out = real(*a, **k)
        return out.at[jnp.argmax(out)].multiply(1.01)

    monkeypatch.setattr(ops, "aggregate_loss", altered)


@pytest.fixture
def tenant_left_out(monkeypatch):
    """Every other tenant's step returns nothing but zeros: half of each
    analysis, the part another chip would hold, never comes back."""
    from repro.core import pipeline
    real = pipeline.PipelineExecutor.run

    def run_without(self, tasks, stage_fn, compute_fn, *a, **k):
        def compute(t, arrays):
            out = compute_fn(t, arrays)
            return jnp.zeros_like(out) if t.vdev % 2 else out
        return real(self, tasks, stage_fn, compute, *a, **k)

    monkeypatch.setattr(pipeline.PipelineExecutor, "run", run_without)


@pytest.fixture
def bfloat16_control(monkeypatch):
    """The control: the reference in bfloat16 in the step's place."""
    from repro.kernels import ops

    def control(ids, elt, ret, lim, agg_ret, agg_lim, chunk=128,
                variant=None):
        bf = jnp.bfloat16
        loss = reference._trial_losses(ids, elt, ret, lim, bf).astype(bf)
        return jnp.minimum(jnp.maximum(loss - jnp.asarray(agg_ret, bf), 0),
                           jnp.asarray(agg_lim, bf)).astype(jnp.float32)

    monkeypatch.setattr(ops, "aggregate_loss", control)


@pytest.mark.parametrize("fault", ["altered_answer", "tenant_left_out",
                                   "bfloat16_control"])
def test_broken_timed_path_is_not_correct(fault, request):
    request.getfixturevalue(fault)
    out = run(small_cell())
    assert out["correct"] is False
    c = out["checks"]["ylt_rel_err"]
    assert c["value"] > c["limit"]


def test_four_chip_cell_asks_for_four_chips():
    cell = small_cell("risk-paper-4chip")
    assert cell.chips == cell.config["devices"] == 4
    import jax
    with pytest.raises(harness.NoChip):
        harness.chip_devices(len(jax.devices()) + 1, require_chip=False)
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.chip_devices(1)


def test_a_cell_runs_on_its_configurations_layout():
    """A configuration states how many chips its deployment spans, and a
    cell that gives it another number is refused before anything runs."""
    cell = small_cell("risk-paper-4chip")
    cell.chips = 1
    with pytest.raises(ValueError, match="over 4 chips, the cell gives 1"):
        run(cell)


def test_each_pair_of_configuration_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCHMARK["configs"]]
    sources = [c["source"] for c in BENCHMARK["configs"]]
    assert len(set(files)) == len(files) and len(set(sources)) == len(sources)


def test_main_prints_no_result_without_a_chip(capsys):
    lines = []
    rc = harness.main(["--workload", "risk-paper-1chip", "--seed", "1",
                       "--seconds", "1"], time.perf_counter(), lines.append)
    assert rc == 1 and lines == []
    assert "no TPU" in capsys.readouterr().err


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "risk-paper-1chip", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


TOY_DRIVER = (
    "from bench.harness import DriverResult\n"
    "def run(ctx):\n"
    "    ctx.start_window()\n"
    "{window}"
    "    ctx.end_window()\n"
    "    w = ctx.config['width']\n"
    "    return DriverResult(attempted=ctx.traffic['items'], failed=0,\n"
    "        end_to_end={{'widgets_per_s': 2.0 * w}},\n"
    "        checks={{'gap': (0.0, 1.0)}}, counters={{'w': w}})\n")


def add_toy_files(bench_dir, window=""):
    """A toy system's configuration, traffic mix, driver and per-layer
    metric, each a new file under ``bench_dir``; returns the new entries of
    ``BENCHMARK.json`` that name them."""
    for d in ("configs", "traffic", "drivers", "metrics"):
        (bench_dir / d).mkdir(exist_ok=True)
    (bench_dir / "configs" / "toy.json").write_text(
        json.dumps({"driver": "toy", "width": 3}))
    (bench_dir / "traffic" / "trickle.json").write_text(
        json.dumps({"loop": "closed", "reports": ["widgets_per_s"],
                    "clients": 1, "items": 4}))
    (bench_dir / "drivers" / "toy.py").write_text(
        TOY_DRIVER.format(window=window))
    (bench_dir / "metrics" / "toy_share.py").write_text(
        "def read(ctx):\n    return 10.0 * ctx.counters['w']\n")
    rel = bench_dir.name
    return {"configs": [{"name": "toy", "file": f"{rel}/configs/toy.json"}],
            "workloads": [{"name": "toy-cell", "config": "toy",
                           "traffic": "trickle", "chips": 1}],
            "end_to_end": [{"name": "widgets_per_s", "unit": "1/s",
                            "workloads": ["toy-cell"]}],
            "per_layer": [{"name": "toy_share", "unit": "%",
                           "moves": "widgets_per_s"}]}


def toy_cell(tmp_path, window=""):
    """A cell of a toy system, from files of its own under ``tmp_path``."""
    bench_dir = tmp_path / "bench"
    bench_dir.mkdir()
    bm = add_toy_files(bench_dir, window)
    bm["end_to_end"].append({"name": "setup_s", "unit": "s"})
    return harness.resolve(bm, "toy-cell", bench_dir, tmp_path)


def test_a_cell_is_found_from_its_files_by_name(tmp_path):
    """A new cell needs only new files: a configuration naming a driver, a
    traffic mix naming the metrics it reports and a per-layer metric's
    reader."""
    cell = toy_cell(tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["widgets_per_s",
                                                    "setup_s"]
    assert [m["name"] for m, _ in cell.per_layer] == ["toy_share"]
    out = harness.run_cell(cell, 3, 0.1, False, time.perf_counter(),
                           require_chip=False)
    assert out["correct"] and out["attempted"] == 4
    assert out["metrics"]["widgets_per_s"]["value"] == 6.0
    assert out["metrics"]["setup_s"]["value"] >= 0
    reader = cell.per_layer[0][1]
    assert reader.read(harness.ReaderContext(None, {"w": 3}, {}, {})) == 30.0


def declared(benchmark, workload):
    """The metrics ``BENCHMARK.json`` says a cell reports: an end-to-end
    metric in the cells its ``workloads`` lists, or in every cell without
    one; a per-layer metric likewise, or without the key in every cell
    that reports the end-to-end metric it moves."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = {m["name"] for m in benchmark["end_to_end"] if listed(m)}
    per_layer = {m["name"] for m in benchmark["per_layer"]
                 if listed(m) and ("workloads" in m or m["moves"] in e2e)}
    return e2e, per_layer


def resolved(cell):
    return ({m["name"] for m in cell.end_to_end},
            {m["name"] for m, _ in cell.per_layer})


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_each_cell_reports_what_the_benchmark_declares(workload):
    cell = harness.resolve(BENCHMARK, workload)
    assert resolved(cell) == declared(BENCHMARK, workload)


def test_new_cells_need_only_new_files_and_entries(tmp_path):
    """A second risk cell and a cell of another driver, which reports no
    ``trials_per_s``, are added to a copy of the benchmark by new files and
    new entries alone, and both resolve to what is declared and run."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    bm = json.loads(json.dumps(BENCHMARK))
    new = add_toy_files(bench_dir)
    conf = json.loads((bench_dir / "configs" / "risk-paper.json").read_text())
    conf.update(SMALL, tenants_per_device=4)
    (bench_dir / "configs" / "risk-small-t4.json").write_text(
        json.dumps(conf))
    new["configs"].append({"name": "risk-small-t4",
                           "file": "bench/configs/risk-small-t4.json"})
    new["workloads"].append({"name": "risk-small-t4", "chips": 1,
                             "config": "risk-small-t4",
                             "traffic": "risk-analyses"})
    for key, entries in new.items():
        bm[key] = bm[key] + entries
    assert all(p.read_bytes() == b for p, b in before.items())
    assert bm["workloads"][:len(BENCHMARK["workloads"])] == \
        BENCHMARK["workloads"]
    risk = harness.resolve(bm, "risk-small-t4", bench_dir, tmp_path)
    toy = harness.resolve(bm, "toy-cell", bench_dir, tmp_path)
    assert resolved(risk) == declared(bm, "risk-small-t4") == (
        {"trials_per_s", "setup_s"},
        {"risk_step_roofline", "device_idle_share.risk"})
    # trials_per_s is declared without a workloads key, so the contract
    # reads it as reported in every cell: the toy cell shows the one entry
    # a first cell of another kind has to narrow.
    assert resolved(toy) == ({"widgets_per_s", "setup_s"}, {"toy_share"})
    assert declared(bm, "toy-cell")[0] - resolved(toy)[0] == {"trials_per_s"}
    for cell, metric in ((risk, "trials_per_s"), (toy, "widgets_per_s")):
        out = harness.run_cell(cell, 2**31 + 9, 0.1, False,
                               time.perf_counter(), require_chip=False)
        assert out["correct"] is True
        assert set(out["metrics"]) == {metric, "setup_s"}


def test_a_traffic_mix_may_report_only_known_metrics(tmp_path):
    bench_dir = tmp_path / "bench"
    bench_dir.mkdir()
    bm = add_toy_files(bench_dir)
    bm["end_to_end"] = [{"name": "setup_s", "unit": "s"}]
    with pytest.raises(KeyError, match="widgets_per_s"):
        harness.resolve(bm, "toy-cell", bench_dir, tmp_path)


def test_a_compile_inside_the_window_is_not_correct(tmp_path):
    cell = toy_cell(tmp_path, window=(
        "    import jax, jax.numpy as jnp\n"
        "    jax.jit(lambda x: x * 3 + 1)(jnp.zeros(7)).block_until_ready()\n"))
    out = harness.run_cell(cell, 3, 0.1, False, time.perf_counter(),
                           require_chip=False)
    assert out["correct"] is False
    assert out["checks"]["compiles_in_window"]["value"] >= 1
