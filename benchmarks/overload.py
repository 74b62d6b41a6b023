"""Trace-driven overload harness: the serving stack past saturation.

The paper's sharing argument is an *efficiency* claim; this harness checks
the *robustness* half — what the shared device does when offered more work
than it can hold.  It drives the continuous-batching scheduler with
deterministic seeded traces:

* **open-loop** arrivals — Poisson interarrivals at a configurable multiple
  of the measured service capacity (2x = the oversubscribed regime the
  acceptance criteria name), heavy-tail lognormal prompt/output mixes, a
  small fraction of high-priority (tier 0) requests among bulk tier-1
  traffic;
* **closed-loop** burst — the whole trace submitted at once (backlog
  driven), the worst-case admission pressure.

Each trace runs twice on one shared compiled engine — preemption+swap ON
vs OFF (the no-preemption baseline row) — and once more with the
:class:`repro.distributed.fault.FaultPlane` injecting round drops,
admission stalls and poisoned swap reads.  Rows record per-priority
p50/p99 TTFT, goodput-per-page (useful completed tokens per device page
allocated), preemption / swap-in / swap-drop counts, shed + rejected +
failed counts and the injected-fault survival accounting.  The fault run
additionally audits two-tier page conservation at drain
(``assert_conserved(host_pages=...)``) — a violated invariant fails the
bench loudly rather than skewing a row.

    PYTHONPATH=src python -m benchmarks.run --only overload
    PYTHONPATH=src python -m benchmarks.run --json out.json \\
        --only serving,overload
"""
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

Row = Tuple[str, float, str]


def make_trace(n: int, seed: int, mean_gap_s: float,
               vocab: int, max_prompt: int = 16, hi_every: int = 5,
               lo_steps: Tuple[int, int] = (12, 48),
               ) -> List[Dict[str, Any]]:
    """Deterministic request specs: Poisson arrival offsets, heavy-tail
    lognormal prompt/output lengths, every ``hi_every``-th request tier 0
    (short, latency-sensitive) among bulk tier 1 (long, throughput, output
    budget clipped to ``lo_steps``)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_gap_s, n)
    offs = np.cumsum(gaps) - gaps[0]
    specs: List[Dict[str, Any]] = []
    for i in range(n):
        hi = hi_every > 0 and i % hi_every == hi_every - 1
        plen = int(np.clip(rng.lognormal(2.0, 0.6), 4, max_prompt))
        steps = (int(np.clip(rng.lognormal(1.8, 0.4), 4, 8)) if hi
                 else int(np.clip(rng.lognormal(
                     np.log(1.3 * lo_steps[0]), 0.4), *lo_steps)))
        specs.append(dict(
            arrival=float(offs[i]),
            tenant=f"hi-{i % 2}" if hi else f"lo-{i % 3}",
            prompt=rng.integers(1, vocab, plen).astype(np.int32),
            max_new_tokens=steps,
            priority=0 if hi else 1))
    return specs


def drive(sched, specs: List[Dict[str, Any]], open_loop: bool = True,
          ) -> List[Any]:
    """Run a trace to completion: submit each spec when the wall clock
    passes its arrival offset (open loop) or all upfront (closed-loop
    burst), stepping the scheduler in between; drain the rest.  Returns
    every terminal response — completed, rejected and failed."""
    from repro.serving.multitenant import Request

    out: List[Any] = []
    start = time.perf_counter()
    i = 0
    while i < len(specs) or sched.pending():
        now = time.perf_counter() - start
        while i < len(specs) and (not open_loop
                                  or specs[i]["arrival"] <= now):
            s = specs[i]
            sched.submit(Request(s["tenant"], s["prompt"],
                                 s["max_new_tokens"],
                                 priority=s["priority"]))
            i += 1
        r = sched.step()
        if r:
            out.extend(r)
        if r is None and i < len(specs) and not sched.pending():
            # idle gap before the next arrival: sleep it off
            time.sleep(max(0.0, min(
                specs[i]["arrival"] - (time.perf_counter() - start), 0.05)))
    out.extend(sched.drain())
    return out


def _ttft_ms(responses: List[Any], priority: int) -> np.ndarray:
    v = [r.ttft_s * 1e3 for r in responses
         if r.outcome == "completed" and r.priority == priority
         and r.ttft_s is not None]
    return np.asarray(v) if v else np.asarray([float("nan")])


def _summarise(responses: List[Any], sched, ceng,
               c0: Tuple[int, int, int, int], extra: str = "",
               ) -> Tuple[float, str]:
    hi, lo = _ttft_ms(responses, 0), _ttft_ms(responses, 1)
    n = {o: sum(r.outcome == o for r in responses)
         for o in ("completed", "rejected", "failed")}
    useful = sum(r.tokens.size for r in responses
                 if r.outcome == "completed")
    pre0, res0, drop0, pages0 = c0
    pages = max(ceng.kv.pages_allocated - pages0, 1)
    shed = sum(int(s["shed"]) for s in sched.stats.values())
    derived = (f"completed={n['completed']};rejected={n['rejected']};"
               f"failed={n['failed']};shed={shed};"
               f"hi_p50_ttft_ms={np.percentile(hi, 50):.1f};"
               f"hi_p99_ttft_ms={np.percentile(hi, 99):.1f};"
               f"lo_p50_ttft_ms={np.percentile(lo, 50):.1f};"
               f"lo_p99_ttft_ms={np.percentile(lo, 99):.1f};"
               f"preemptions={ceng.preemptions - pre0};"
               f"restores={ceng.restores - res0};"
               f"swap_drops={ceng.kv.swap_drops - drop0};"
               f"goodput_tok_per_page={useful / pages:.2f}" + extra)
    return float(np.percentile(hi, 99)), derived


def bench_serving_overload() -> List[Row]:
    """2x-oversubscribed open-loop trace + closed-loop burst, preemption
    A/B, and a fault-injected run — the PR-6 acceptance rows.  One shared
    compiled engine serves every run (jit caches are per-engine), reset by
    draining between runs."""
    import jax
    from repro.configs import get_config
    from repro.distributed.fault import FaultPlane
    from repro.models import params as pp
    from repro.models.model import build_model
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.multitenant import MultiTenantScheduler

    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    # 4 rows and pages for ~4 long rings (16 prompt + 96 decode = 14 pages
    # each): slots, not pages, are the contended resource, so a tier-0
    # arrival against a full slot table exercises the slot-exhaustion
    # preemption path (victim swapped to host, restored when a slot frees)
    kw = dict(capacity=4, page_size=8, num_pages=64, inner_steps=4,
              max_prompt_len=16)
    n_req = 24
    # tier-1 rows must hold slots for much longer than a tier-0 request
    # can afford to wait: with ~250-step budgets a lo row occupies its slot
    # for ~60 micro-rounds, so natural retirements are far apart and a
    # blocked tier-0 arrival genuinely needs preemption (short lo budgets
    # degenerate: slots turn over faster than a swap cycle costs, and
    # waiting beats preempting)
    lo_steps = (192, 384)
    hi_every = 6
    # placeholder: calibrated below from the measured burst service rate,
    # so the "2x" in the row names holds whatever this host's speed is
    gap_s = 0.02

    # ONE shared engine across every run: jit caches are per-engine, and a
    # per-run fresh engine would spend the first arrivals' wall-clock on
    # compiles, collapsing any open-loop trace into a burst.  The fault
    # plane is swapped in and out around the injected run, and all engine
    # counters are read as deltas.
    ceng = ContinuousBatchingEngine(engine, **kw)

    def run(preempt: bool, open_loop: bool, plane: Optional[FaultPlane],
            seed: int):
        ceng.fault_plane = plane
        if ceng.swap_store is not None:
            ceng.swap_store.fault_plane = plane
        sched = MultiTenantScheduler(
            engine, mode="continuous", continuous_engine=ceng,
            preemption=preempt, fault_plane=plane, max_backlog=2 * n_req)
        c0 = (ceng.preemptions, ceng.restores, ceng.kv.swap_drops,
              ceng.kv.pages_allocated)
        t0 = time.perf_counter()
        rs = drive(sched, make_trace(n_req, seed, gap_s, cfg.vocab_size,
                                     hi_every=hi_every, lo_steps=lo_steps),
                   open_loop)
        wall = time.perf_counter() - t0
        ceng.fault_plane = None
        if ceng.swap_store is not None:
            ceng.swap_store.fault_plane = None
        return rs, sched, c0, wall

    # warm: *every* admission shape first — prefill jits key on
    # (batch size, prompt bucket), and an open-loop trace groups
    # admissions differently than the closed-loop warm burst does, so any
    # shape left cold becomes a several-hundred-ms compile stall in the
    # middle of a timed row (the stall backs up every later arrival into
    # one burst and lands entirely on whichever A/B row runs first) —
    # then the evict/restore jits (a forced preempt-restore cycle) and a
    # burst of the trace itself.  The *second* warm burst measures this
    # host's steady-state service rate, and the open-loop interarrival
    # gap is calibrated to offer 2x that — a hard-coded gap is 10x
    # oversubscribed on a loaded CI box and undersubscribed on a fast
    # idle one, and either extreme degenerates (all-queued burst / tier-0
    # lands in a free slot, no preemption)
    _warm_admission_shapes(engine, ceng, cfg, max_prompt=16)
    _warm_preempt(engine, ceng, cfg)
    run(True, False, None, seed=0)
    _, _, _, service_wall = run(True, False, None, seed=0)
    gap_s = service_wall / n_req / 2.0

    out: List[Row] = []
    rs, sched, c0, wall = run(True, True, None, seed=0)
    hi99_pre, derived = _summarise(rs, sched, ceng, c0)
    out.append((f"serving/overload_open2x_preempt_{n_req}r", wall * 1e6,
                derived))
    rs, sched, c0, wall = run(False, True, None, seed=0)
    hi99_base, derived = _summarise(rs, sched, ceng, c0)
    out.append((f"serving/overload_open2x_nopreempt_{n_req}r", wall * 1e6,
                derived + f";hi_p99_vs_preempt="
                          f"{hi99_base / max(hi99_pre, 1e-9):.2f}x"))

    rs, sched, c0, wall = run(True, False, None, seed=0)
    _, derived = _summarise(rs, sched, ceng, c0)
    out.append((f"serving/overload_burst_preempt_{n_req}r", wall * 1e6,
                derived))

    plane = FaultPlane(drop_round_every=9, stall_admission_every=7,
                       poison_swap_every=3)
    rs, sched, c0, wall = run(True, True, plane, seed=0)
    # robustness contract: every request reached exactly one terminal
    # outcome and the two-tier page ledger balances at drain
    assert len(rs) == n_req, (len(rs), n_req)
    ceng.kv.assert_conserved(
        host_pages=ceng.swap_store.pages() if ceng.swap_store else 0)
    _, derived = _summarise(
        rs, sched, ceng, c0,
        extra=(f";faults_injected={plane.total_injected()};"
               f"faults_survived={sched.faults_survived};"
               f"heartbeat_suspects={sched.heartbeat_suspects}"))
    out.append((f"serving/overload_faults_{n_req}r", wall * 1e6, derived))
    return out


def _warm_admission_shapes(engine, ceng, cfg, max_prompt: int) -> None:
    """Compile every (admission batch size, prompt bucket) prefill shape
    the trace can produce: k in 1..capacity same-bucket requests admitted
    together, for each bucket up to ``max_prompt``.  Short budgets keep
    each warm run to a few rounds."""
    from repro.serving.multitenant import MultiTenantScheduler, Request

    rng = np.random.default_rng(2)
    buckets = sorted({ceng.bucket_len(p)
                      for p in range(4, max_prompt + 1)})
    for bucket in buckets:
        for k in range(1, ceng.capacity + 1):
            sched = MultiTenantScheduler(engine, mode="continuous",
                                         continuous_engine=ceng)
            for j in range(k):
                sched.submit(Request(
                    f"warm-b{bucket}-{j}",
                    rng.integers(1, cfg.vocab_size,
                                 bucket).astype(np.int32),
                    max_new_tokens=4))
            sched.drain()


def _warm_preempt(engine, ceng, cfg) -> None:
    """Compile the evict/restore jits before any timed row: fill every slot
    with long tier-1 rows, then submit a tier-0 request so the scheduler
    preempts a victim, and drain (restore included)."""
    from repro.serving.multitenant import MultiTenantScheduler, Request

    sched = MultiTenantScheduler(engine, mode="continuous",
                                 continuous_engine=ceng, preemption=True)
    rng = np.random.default_rng(1)
    for i in range(ceng.capacity):
        sched.submit(Request(f"warm-lo{i}",
                             rng.integers(1, cfg.vocab_size,
                                          16).astype(np.int32),
                             max_new_tokens=48, priority=1))
    sched.step()
    sched.submit(Request("warm-hi",
                         rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                         max_new_tokens=4, priority=0))
    sched.drain()


# ----------------------------------------------------------------------
# Crash recovery: kill-and-restart wall-time row
# ----------------------------------------------------------------------
_RECOVERY_CHILD = r"""
import json, os, sys
import numpy as np, jax
from repro.configs import get_config
from repro.distributed.fault import FaultPlane
from repro.models import params as pp
from repro.models.model import build_model
from repro.serving.continuous import ContinuousBatchingEngine
from repro.serving.engine import ServingEngine
from repro.serving.multitenant import MultiTenantScheduler, Request

phase, root = sys.argv[1], sys.argv[2]
cfg = get_config("internlm2-1.8b").reduced()
params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
engine = ServingEngine(cfg, params)
fp = FaultPlane(crash_at_round=12) if phase == "crash" else None
ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                num_pages=24, inner_steps=4,
                                max_prompt_len=16, fault_plane=fp)
sched = MultiTenantScheduler(
    engine, mode="continuous", continuous_engine=ceng,
    journal=os.path.join(root, "journal.jsonl"),
    checkpoint_dir=os.path.join(root, "ckpt"), checkpoint_every=3)
rng = np.random.default_rng(0)
if phase == "crash":
    for i in range(4):
        sched.submit(Request(
            "r%d" % i, rng.integers(1, cfg.vocab_size, 8 + i).astype(np.int32),
            max_new_tokens=24 + 2 * i, seed=7 + i,
            temperature=0.8 if i % 2 else None))
    sched.drain()                      # SIGKILLed at dispatched round 12
    sys.exit(3)                        # must never get here
import time
t0 = time.perf_counter()
s = sched.recover()
resp = sched.drain()
wall = time.perf_counter() - t0
print(json.dumps({
    "wall_s": wall, "rounds_replayed": s.rounds_replayed,
    "tokens_preserved": s.tokens_preserved,
    "tokens_replayed": s.tokens_replayed,
    "restored_live": s.restored_live,
    "restored_swapped": s.restored_swapped, "requeued": s.requeued,
    "completed": sum(r.outcome == "completed" for r in resp)
                 + len(s.already_complete)}))
"""


def bench_serving_recovery() -> List[Row]:
    """Kill-and-restart: a journalled+checkpointed serving child is
    SIGKILLed mid-round by the :class:`~repro.distributed.fault.
    FaultPlane` crash injector, then a fresh process recovers from the
    (journal, latest checkpoint) pair and drains to completion.  Rows
    report the recovery wall time (journal replay + checkpoint load +
    pool rebuild + replayed decode rounds), the rounds replayed, and the
    preserved-vs-lost token split (lost = emitted after the checkpoint,
    regenerated bitwise by deterministic replay — never silently gone).
    Both children are pinned to the CPU (the parent may hold the chip,
    which one process owns at a time), so the rows say platform=cpu."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    root = tempfile.mkdtemp(prefix="bench_recovery_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (os.path.abspath("src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    crash = subprocess.run(
        [sys.executable, "-c", _RECOVERY_CHILD, "crash", root],
        env=env, capture_output=True, timeout=600)
    if crash.returncode != -9:
        raise RuntimeError(
            f"crash child exited {crash.returncode}, expected SIGKILL:\n"
            f"{crash.stderr.decode()[-2000:]}")
    rec = subprocess.run(
        [sys.executable, "-c", _RECOVERY_CHILD, "recover", root],
        env=env, capture_output=True, timeout=600)
    if rec.returncode != 0:
        raise RuntimeError(
            f"recovery child failed:\n{rec.stderr.decode()[-2000:]}")
    r = json.loads(rec.stdout.decode().strip().splitlines()[-1])
    if r["completed"] != 4:
        raise RuntimeError(f"recovery lost requests: {r}")
    return [
        ("recovery: wall time (SIGKILL -> drained)", r["wall_s"],
         "s;platform=cpu"),
        ("recovery: rounds replayed", float(r["rounds_replayed"]),
         "rounds;platform=cpu"),
        ("recovery: tokens preserved (checkpointed)",
         float(r["tokens_preserved"]), "tokens;platform=cpu"),
        ("recovery: tokens replayed (post-ckpt, regenerated)",
         float(r["tokens_replayed"]), "tokens;platform=cpu"),
        ("recovery: requests completed after restart",
         float(r["completed"]), "requests;platform=cpu"),
    ]


ALL = [bench_serving_overload, bench_serving_recovery]
