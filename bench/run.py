#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload risk-paper-1chip --seed 7 --seconds 40 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, which names its driver,
``bench/drivers/<driver>.py``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The driver builds the system from the
seed, warms it up, measures for ``--seconds`` and checks what the timed path
produced against a plain reference.  With ``--trace 1`` the window runs
under the profiler and each per-layer metric of the cell is read by its own
reader, ``bench/metrics/<metric>.py``, from the reduced trace and the
driver's counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end ones, or with
``--trace 1`` per-layer ones), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit, also printed as
the last lines of standard error.  The run exits non-zero and prints no
result when JAX finds no TPU, or fewer chips than the cell asks for, or
when the program (``src/``) is not beside the benchmark.
"""
import time

T_START = time.perf_counter()   # set-up counts from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent

if __name__ == "__main__":
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program at {ROOT / 'src'}; nothing was measured",
              file=sys.stderr)
        sys.exit(2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import main
    sys.exit(main(sys.argv[1:], T_START))
