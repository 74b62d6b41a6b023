"""The benchmark's harness: finds a cell's files by name, runs its driver
and builds the result line (see ``bench/run.py``)."""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {name} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}_{path.stem}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything the files name for one workload."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: Any
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Tuple[Dict[str, Any], Any]]     # (entry, reader module)


def resolve(benchmark: Dict[str, Any], workload: str,
            bench_dir: pathlib.Path = BENCH,
            root: pathlib.Path = ROOT) -> Cell:
    from bench.traffic import generator
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    traffic = generator.load(w["traffic"], bench_dir / "traffic")
    driver = load_module(bench_dir / "drivers" / f"{config['driver']}.py",
                         "driver")
    # The traffic mix names the end-to-end metrics its cells report; a
    # per-layer metric is read in every cell that reports the one it moves.
    names = {"setup_s", *traffic["reports"]}
    known = {m["name"] for m in benchmark["end_to_end"]}
    if names - known:
        raise KeyError(f"traffic {w['traffic']!r} reports "
                       f"{sorted(names - known)}, not in BENCHMARK.json")
    e2e = [m for m in benchmark["end_to_end"] if m["name"] in names]
    per_layer = [(m, load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                 "metric"))
                 for m in benchmark["per_layer"] if m["moves"] in names]
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e,
                per_layer)


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at the fixed
    ``<checkout>/.jax_cache``, whatever the environment says, so that two
    checkouts on one machine share nothing.  The program's own cache helper
    reads ``JAX_COMPILATION_CACHE_DIR``, so it is pointed there too.  Every
    program is kept, however short its compile."""
    import jax
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Programs that XLA compiled or loaded from the persistent cache since
    the clock started, and the seconds that took (``jax.monitoring`` fires
    this event around both)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count, self.seconds = 0, 0.0
        monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listen)


def chip_devices(chips: int, require_chip: bool = True) -> List[Any]:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Context:
    """What a driver gets: the cell, the seed, its devices, and the hooks
    that open and close the measured window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: List[Any], t_start: float):
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.config, self.traffic = cell.config, cell.traffic
        self.devices = devices
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_data = None
        self.compiles = CompileClock()
        self.compiles_in_window: Optional[int] = None
        self._tracedir: Optional[tempfile.TemporaryDirectory] = None
        self._window: Optional[contextlib.ExitStack] = None

    @staticmethod
    def span(name: str):
        """A host span on the profiler's clock (free when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_window(self) -> float:
        """End of set-up: everything before this call counts in
        ``setup_s``.  Returns the window's start on ``perf_counter``."""
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        print(f"bench: set-up compiled or loaded {self.compiles.count} "
              f"programs in {self.compiles.seconds:.3f} s", file=sys.stderr)
        self._compiles_before = self.compiles.count
        self._window = contextlib.ExitStack()
        if self.trace:
            self._tracedir = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._tracedir.name,
                                     profiler_options=opts)
            self._window.enter_context(self.span("bench.window"))
        return time.perf_counter()

    def end_window(self) -> None:
        """Close the window, stop the profiler and reduce its trace, and
        read the device memory peak (before any reference runs)."""
        import jax
        from bench import traces as tr
        self.compiles_in_window = self.compiles.count - self._compiles_before
        self.compiles.close()
        self._window.close()
        if self.trace:
            jax.profiler.stop_trace()
            self.trace_data = tr.load(tr.newest_xplane(self._tracedir.name))
            self._tracedir.cleanup()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


@dataclasses.dataclass
class DriverResult:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]                  # values besides setup_s
    checks: Dict[str, Tuple[float, float]]        # name -> (value, limit)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True) -> Dict[str, Any]:
    """Drive one run of ``cell`` and return the result line as a dict."""
    import jax
    devices = chip_devices(cell.chips, require_chip)
    ctx = Context(cell, seed, seconds, trace, devices, t_start)
    res: DriverResult = cell.driver.run(ctx)
    # nothing may compile inside the measured window
    checks = dict(res.checks, compiles_in_window=(ctx.compiles_in_window, 0))
    metrics: Dict[str, Dict[str, Any]] = {}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out: Dict[str, Any] = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": res.attempted, "failed": res.failed,
        "metrics": metrics, "device": device}
    if not trace:
        values = dict(res.end_to_end, setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench import traces as tr
        from bench.counts import peaks
        reader_ctx = ReaderContext(ctx.trace_data, res.counters, cell.config,
                                   peaks(d0.device_kind))
        for m, reader in cell.per_layer:
            v = reader.read(reader_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s(ctx.trace_data)
        device["window_s"] = tr.window_s(ctx.trace_data)
        out["breakdown"] = {"device_ops": tr.top_ops(ctx.trace_data),
                            "idle_gaps": tr.idle_gaps(ctx.trace_data)}
    # the numbers compared come last in the line, each beside its limit
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer metric's reader gets."""
    trace: Any                   # bench.trace.Trace of the window
    counters: Dict[str, Any]     # the driver's counts of what it ran
    config: Dict[str, Any]
    peak: Dict[str, float]       # the chip's entry of bench/peaks.json


def main(argv: Optional[List[str]], t_start: float,
         emit: Callable[[str], None] = print) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = resolve(benchmark, args.workload)
    enable_compile_cache(ROOT)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    emit(json.dumps(out))
    return 0

