"""Observability plane: spans/metrics core, instrumentation contracts,
exporters, and the plane's spans on the profiler's clock.

* telemetry core: span nesting/parent links from the per-thread stack,
  zero-length events, the bounded ring (drops counted, never grown),
  counters/gauges/histograms and their snapshots;
* overhead contract: a disabled plane allocates nothing on the decode
  micro-round path (``spans_opened`` and the counter table stay flat),
  and an enabled plane changes no compile counts and no tokens;
* the occupancy regression (PR 8): ``occupancy()`` is derived from the
  per-round collect log, so a dispatched-but-uncollected round no longer
  deflates it the way the old ``row_steps / (rounds * inner * capacity)``
  quotient did;
* exporters: Chrome-trace JSON round-trips with parent links intact
  (round.jit > round.dispatch > sched.step) and the Prometheus text
  exposition parses back to the counter table; a golden-file run pins
  the engine-level counter/span-name schema;
* profiler: a live span is a ``TraceAnnotation`` inside its parent's,
  collections are ``host.gc`` spans only while ``enable()`` holds, and
  the risk pipeline's spans nest under ``risk.analysis`` without moving
  its numbers or its compile count.
"""
import gc
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.risk_app import RiskAppConfig
from repro.core.pipeline import TenantTimeline
from repro.core.tenancy import TenancyConfig
from repro.models import params as pp
from repro.models.model import build_model
from repro.obs.export import (chrome_trace, parse_prometheus_text,
                              prometheus_text, stats_line,
                              write_chrome_trace)
from repro.obs.telemetry import (NULL_SPAN, record_timeline, Telemetry,
                                 TELEMETRY)
from repro.risk.analysis import AggregateRiskAnalysis
from repro.risk.tables import generate
from repro.serving.continuous import ContinuousBatchingEngine
from repro.serving.engine import ServingEngine
from repro.serving.multitenant import MultiTenantScheduler, Request

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "obs_serving_counters.json")


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    return ServingEngine(cfg, params)


def _mk_reqs(engine, rng, n, plen=12, steps=8, tenant="a", **kw):
    return [Request(tenant, rng.integers(1, engine.cfg.vocab_size,
                                         plen).astype(np.int32),
                    max_new_tokens=steps, **kw) for _ in range(n)]


def _drain_lockstep(ceng, reqs):
    """Admit/dispatch/collect in lockstep — deterministic by construction
    (no ``handle.ready()`` timing races)."""
    queue = list(reqs)
    done = []
    while queue or ceng.active_count():
        free = ceng.free_slot_count()
        if queue and free:
            batch, queue = queue[:free], queue[free:]
            flags = ceng.try_admit_batch(batch)
            assert all(flags)
        h = ceng.dispatch_round()
        done.extend(ceng.collect(h).finished)
    return done


# ---------------------------------------------------------------------
# telemetry core
# ---------------------------------------------------------------------
def test_span_nesting_and_parent_links():
    tel = Telemetry(enabled=True)
    with tel.span("sched.step", mode="continuous") as outer:
        with tel.span("round.dispatch") as inner:
            tel.event("kv.alloc", slot=3)
            inner.note(steps=4)
        outer.note(responses=2)
    spans = {s.name: s for s in tel.spans()}
    assert set(spans) == {"sched.step", "round.dispatch", "kv.alloc"}
    assert spans["sched.step"].parent_id is None
    assert spans["round.dispatch"].parent_id == spans["sched.step"].span_id
    assert spans["kv.alloc"].parent_id == spans["round.dispatch"].span_id
    assert spans["kv.alloc"].duration == 0.0
    assert spans["round.dispatch"].attrs == {"steps": 4}
    assert spans["sched.step"].attrs == {"mode": "continuous",
                                         "responses": 2}
    # children close inside their parent's window on the same clock
    assert (spans["sched.step"].t_start <= spans["round.dispatch"].t_start
            <= spans["round.dispatch"].t_end <= spans["sched.step"].t_end)
    assert tel.spans_opened == 3 and tel.spans_dropped == 0


def test_ring_buffer_drops_oldest_and_reset():
    tel = Telemetry(enabled=True, max_spans=4)
    for i in range(6):
        tel.event("kv.alloc", i=i)
    spans = tel.spans()
    assert len(spans) == 4
    assert [s.attrs["i"] for s in spans] == [2, 3, 4, 5]   # oldest dropped
    assert tel.spans_dropped == 2
    assert tel.spans_opened == 6                # opened counts the dropped
    tel.count("kv.pages_allocated", 3)
    tel.reset()
    assert tel.spans() == [] and tel.counter_snapshot() == {}
    assert tel.spans_dropped == 0 and tel.enabled


def test_disabled_plane_is_free():
    tel = Telemetry()          # disabled by default
    assert tel.span("sched.step") is NULL_SPAN        # shared singleton
    with tel.span("sched.step") as sp:
        sp.note(anything=1)
    tel.event("kv.alloc")
    tel.count("c"), tel.gauge("g", 1.0), tel.observe("h", 2.0)
    assert tel.record_span("round.device", 0.0, 1.0) is None
    assert tel.spans_opened == 0 and tel.spans() == []
    assert tel.metric_snapshot() == {"counters": {}, "gauges": {},
                                     "histograms": {}}


def test_metrics_and_snapshots():
    tel = Telemetry(enabled=True)
    tel.count("kv.pages_allocated", 4)
    tel.count("kv.pages_allocated")
    tel.gauge("kv.free_pages", 7)
    for v in (0.5, 2.0, 1.0):
        tel.observe("round.wall_s", v)
    snap = tel.metric_snapshot()
    assert snap["counters"] == {"kv.pages_allocated": 5}
    assert snap["gauges"] == {"kv.free_pages": 7}
    assert snap["histograms"]["round.wall_s"] == {
        "count": 3, "sum": 3.5, "min": 0.5, "max": 2.0}
    line = stats_line(tel, keys=("kv.pages_allocated", "missing"), step=9)
    assert line == "obs: kv.pages_allocated=5 missing=0 step=9"


def test_record_timeline_mirrors_entry_as_spans():
    tel = Telemetry(enabled=True)
    entry = TenantTimeline(vdev=1, pdev=0, slot=2, transfer_start=0.1,
                           transfer_end=0.3, compute_start=0.3,
                           compute_end=0.9)
    record_timeline(tel, entry, base=tel.t0, tenant="a", nv=4)
    tr, = tel.spans(name="timeline.transfer")
    cp, = tel.spans(name="timeline.compute")
    assert cp.parent_id == tr.span_id
    assert tr.attrs["nv"] == 4 and tr.attrs["slot"] == 2
    assert tr.duration == pytest.approx(0.2)
    assert cp.duration == pytest.approx(0.6)


# ---------------------------------------------------------------------
# satellite 1: occupancy derived from the round log
# ---------------------------------------------------------------------
def test_occupancy_not_deflated_by_inflight_round(engine, rng):
    """Old formula counted a dispatched round's capacity before its live
    steps landed; the round-log version only scores collected rounds.
    On a drained engine the two agree exactly."""
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16)
    old = lambda: (ceng.row_steps
                   / (ceng.rounds * ceng.inner_steps * ceng.capacity))
    assert all(ceng.try_admit_batch(_mk_reqs(engine, rng, 2, steps=8)))
    h = ceng.dispatch_round()
    ceng.collect(h)
    assert ceng.occupancy() == pytest.approx(1.0)      # round 1: all live
    h = ceng.dispatch_round()                          # round 2 in flight
    assert old() == pytest.approx(0.5)           # the PR-3..7 deflation bug
    assert ceng.occupancy() == pytest.approx(1.0)      # unaffected
    ceng.collect(h)                                    # rows retire here
    assert ceng.active_count() == 0
    # drained: the old quotient and the round-log derivation agree
    assert ceng.occupancy() == pytest.approx(old()) == pytest.approx(1.0)


# ---------------------------------------------------------------------
# satellite 3: overhead contract
# ---------------------------------------------------------------------
def test_disabled_plane_allocates_nothing_on_decode_path(engine, rng):
    """Layers resolve ``telemetry=None`` to the global plane; with it
    disabled a full admit/decode/collect run must not open a single span
    or touch a counter (``spans_opened`` counts every allocation ever
    attempted, including ones a ring would drop)."""
    assert not TELEMETRY.enabled
    before = (TELEMETRY.spans_opened, TELEMETRY.counter_snapshot(),
              TELEMETRY.metric_snapshot())
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16)
    done = _drain_lockstep(ceng, _mk_reqs(engine, rng, 3, steps=6))
    assert len(done) == 3
    assert (TELEMETRY.spans_opened, TELEMETRY.counter_snapshot(),
            TELEMETRY.metric_snapshot()) == before


def test_enabled_plane_changes_no_compile_counts(engine, rng):
    """The test_continuous compile-count contract, replayed with the
    plane on: trace-time counters fire exactly once per trace and the
    engine's trace counts are unchanged by instrumentation."""
    tel = Telemetry(enabled=True)
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=32,
                                    telemetry=tel)
    cfg = engine.cfg
    mk = lambda plen, steps: Request("a", rng.integers(
        1, cfg.vocab_size, plen).astype(np.int32), max_new_tokens=steps)
    ceng.run_all([mk(6, 1), mk(8, 5), mk(7, 9)])
    ceng.run_all([mk(12, 2), mk(16, 7)])
    ceng.run_all([mk(5, 11), mk(14, 3)])
    # identical to the uninstrumented contract in test_continuous.py
    assert ceng.decode_traces == 1
    assert ceng.admit_traces == 2
    assert ceng.prefill_traces == 4
    assert ceng.prefill_calls == 5
    # and the plane's trace-time counters mirror them exactly
    c = tel.counter_snapshot()
    assert c["trace.decode"] == 1
    assert c["trace.admit"] == 2
    assert c["trace.prefill"] == 4
    assert c["admit.prefill_calls"] == 5


def test_tokens_identical_enabled_vs_disabled(engine, rng):
    """Instrumentation changed no numerics: the same request mix decodes
    to bitwise-identical tokens with the plane on and off."""
    prompts = [rng.integers(1, engine.cfg.vocab_size, n).astype(np.int32)
               for n in (12, 8, 15)]
    outs = []
    for tel in (Telemetry(), Telemetry(enabled=True)):
        ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                        inner_steps=4, max_prompt_len=16,
                                        telemetry=tel)
        reqs = [Request("a", p.copy(), max_new_tokens=7) for p in prompts]
        done = {id(r): t for (r, t, _c) in _drain_lockstep(ceng, reqs)}
        outs.append([done[id(r)] for r in reqs])
    for off, on in zip(*outs):
        np.testing.assert_array_equal(off, on)


# ---------------------------------------------------------------------
# scheduler-level run: layer coverage, preemption spans, heartbeat
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def sched_run(engine):
    """One preempting 2-tenant scheduler run on an instance plane: tier-1
    rows fill both slots, a late tier-0 arrival forces swap-out/restore."""
    tel = Telemetry(enabled=True)
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    num_pages=24, inner_steps=4,
                                    max_prompt_len=16, telemetry=tel)
    sched = MultiTenantScheduler(engine, mode="continuous",
                                 continuous_engine=ceng, preemption=True,
                                 telemetry=tel)
    rng = np.random.default_rng(0)
    for i in range(2):
        sched.submit(Request(f"t{i}", rng.integers(
            1, engine.cfg.vocab_size, 12).astype(np.int32),
            max_new_tokens=40, priority=1))
    sched.step()
    sched.submit(Request("hi", rng.integers(
        1, engine.cfg.vocab_size, 8).astype(np.int32),
        max_new_tokens=4, priority=0))
    responses = sched.drain()
    sched.close()
    return tel, sched, ceng, responses


def test_trace_covers_all_layers(sched_run):
    """The ISSUE acceptance: one serving run records spans from the
    scheduler, engine-round, KV-pool, swap and transfer layers."""
    tel, _sched, ceng, responses = sched_run
    assert ceng.preemptions >= 1 and ceng.restores >= 1
    assert {r.tenant: r.outcome for r in responses} == {
        "t0": "completed", "t1": "completed", "hi": "completed"}
    layers = {s.name.split(".", 1)[0] for s in tel.spans()}
    assert {"sched", "round", "admit", "kv", "swap", "transfer",
            "timeline", "admission"} <= layers
    for name in ("swap.out", "swap.restore", "swap.fetch",
                 "transfer.stage", "kv.alloc", "round.device"):
        assert tel.spans(name=name), f"no {name} spans recorded"
    c = tel.counter_snapshot()
    assert c["swap.preemptions"] == ceng.preemptions
    assert c["swap.restores"] == ceng.restores
    assert c["heartbeat.beats"] > 0


def test_chrome_trace_roundtrip_and_nesting(sched_run, tmp_path):
    """Chrome-trace JSON survives a dump/load round trip and the span
    tree reconstructs from args: round.jit > round.dispatch > sched.step."""
    tel, *_ = sched_run
    path = tmp_path / "trace.json"
    write_chrome_trace(tel, str(path))
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(events) == len(tel.spans())
    assert doc["otherData"]["spans_opened"] == tel.spans_opened
    by_id = {e["args"]["span_id"]: e for e in events}
    chains = set()
    for e in events:
        if e["name"] != "round.jit":
            continue
        parent = by_id[e["args"]["parent_id"]]
        grand = by_id[parent["args"]["parent_id"]]
        chains.add((e["name"], parent["name"], grand["name"]))
        # a child's [ts, ts+dur) window lies inside its parent's
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    assert ("round.jit", "round.dispatch", "sched.step") in chains
    # counter snapshot rides the same timeline as "C" events
    cvals = {e["name"]: e["args"]["value"]
             for e in doc["traceEvents"] if e["ph"] == "C"}
    assert cvals["swap.preemptions"] == tel.counter_snapshot()[
        "swap.preemptions"]


def test_prometheus_roundtrip(sched_run):
    tel, *_ = sched_run
    parsed = parse_prometheus_text(prometheus_text(tel))
    snap = tel.metric_snapshot()
    for name, value in snap["counters"].items():
        key = "repro_" + name.replace(".", "_")
        assert parsed[key] == pytest.approx(value)
    for name, value in snap["gauges"].items():
        assert parsed["repro_" + name.replace(".", "_")] == pytest.approx(
            value)


def test_heartbeat_suspects_surface_as_gauges(engine, rng):
    """Satellite: a zero-timeout heartbeat marks every scheduler round
    suspect; the verdicts surface as the plane's counter + gauge and in
    the periodic stats line."""
    tel = Telemetry(enabled=True)
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16,
                                    telemetry=tel)
    sched = MultiTenantScheduler(engine, mode="continuous",
                                 continuous_engine=ceng,
                                 heartbeat_timeout_s=0.0, telemetry=tel)
    for req in _mk_reqs(engine, rng, 2, steps=6):
        sched.submit(req)
    sched.drain()
    sched.close()
    assert sched.heartbeat_suspects > 0
    c = tel.counter_snapshot()
    assert c["heartbeat.missed"] == sched.heartbeat_suspects
    assert tel.metric_snapshot()["gauges"]["heartbeat.suspects"] == \
        sched.heartbeat_suspects
    line = stats_line(tel, keys=("heartbeat.suspects",))
    assert f"heartbeat.suspects={sched.heartbeat_suspects}" in line


def test_heartbeat_verdicts_on_global_plane():
    """HeartbeatMonitor itself (no scheduler) mirrors verdicts onto the
    global plane when enabled — and stays silent when disabled."""
    from repro.distributed.fault import HeartbeatMonitor
    hb = HeartbeatMonitor(timeout_s=0.0)
    assert hb.suspect()                       # disabled global: no record
    assert not TELEMETRY.enabled
    assert "heartbeat.verdicts" not in TELEMETRY.counter_snapshot()
    TELEMETRY.enable()
    try:
        assert hb.suspect()
        assert TELEMETRY.counter_snapshot()["heartbeat.verdicts"] == 1
        assert TELEMETRY.spans(name="heartbeat.suspect")
    finally:
        TELEMETRY.disable()
        TELEMETRY.reset()


# ---------------------------------------------------------------------
# satellite 4: golden-file schema pin for a deterministic run
# ---------------------------------------------------------------------
def _state_kind_counters(arch):
    """One deterministic admit -> round -> preempt -> restore -> drain
    cycle on ``arch``, returning only its per-state-kind ``kv.cross.*`` /
    ``kv.ssm.*`` counters (the PR-9 paged-state-pool schema)."""
    tel = Telemetry(enabled=True)
    cfg = get_config(arch).reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params)
    ceng = ContinuousBatchingEngine(eng, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16,
                                    telemetry=tel)
    rng = np.random.default_rng(3)
    reqs = [Request(t, rng.integers(1, cfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=6) for t in ("a", "b")]
    assert all(ceng.try_admit_batch(reqs))
    ceng.collect(ceng.dispatch_round())
    ticket = ceng.preempt(0)
    assert ceng.try_restore(ticket)
    assert len(_drain_lockstep(ceng, [])) == 2
    return {k: float(v) for k, v in sorted(tel.counter_snapshot().items())
            if k.startswith(("kv.cross.", "kv.ssm."))}


def test_golden_counters_and_span_names(engine, rng):
    """Lockstep 2-tenant engine-level run (no ready()-timing races):
    the counter table and the span-name multiset are pinned by a golden
    file, so a renamed or silently-dropped metric fails loudly.  The
    ``state_kind_counters`` section pins the PR-9 per-kind schema — an
    enc-dec and a pure-SSM arch each through a full admit/preempt/restore
    cycle, keeping only their ``kv.cross.*`` / ``kv.ssm.*`` rows.
    Regenerate with REPRO_REGEN_GOLDEN=1 after an intentional change."""
    tel = Telemetry(enabled=True)
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16,
                                    telemetry=tel)
    reqs = [Request(t, rng.integers(1, engine.cfg.vocab_size,
                                    12).astype(np.int32), max_new_tokens=6)
            for t in ("a", "b", "a", "b")]
    done = _drain_lockstep(ceng, reqs)
    assert len(done) == 4
    names: dict = {}
    for s in tel.spans():
        names[s.name] = names.get(s.name, 0) + 1
    got = {"counters": {k: float(v)
                        for k, v in sorted(tel.counter_snapshot().items())},
           "span_names": dict(sorted(names.items())),
           "state_kind_counters": {
               "whisper-base": _state_kind_counters("whisper-base"),
               "mamba2-2.7b": _state_kind_counters("mamba2-2.7b")}}
    if os.environ.get("REPRO_REGEN_GOLDEN"):
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(got, f, indent=2, sort_keys=True)
    with open(GOLDEN) as f:
        want = json.load(f)
    assert got == want


# ---------------------------------------------------------------------
# the plane on the profiler's clock, and the risk pipeline's spans
# ---------------------------------------------------------------------
RISK_SPANS = ("risk.plan", "risk.tables", "pipeline.start", "risk.slice",
              "transfer.put", "transfer.wait", "pipeline.dispatch",
              "pipeline.drain", "risk.gather")


@pytest.fixture(scope="module")
def risk_setup():
    cfg = RiskAppConfig().reduced()
    return cfg, generate(cfg, seed=0)


def test_live_span_lands_in_the_profiler_trace(tmp_path):
    """An enabled plane's span is a profiler annotation of the same name,
    on the same host line and inside its parent's."""
    from jax.profiler import ProfileData
    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("risk.analysis"):
            with tel.span("risk.slice"):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("risk.analysis", "risk.slice"):
                    found[e.name] = (plane.name, line.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
    outer, inner = found["risk.analysis"], found["risk.slice"]
    assert outer[:2] == inner[:2]
    assert outer[2] <= inner[2] <= inner[3] <= outer[3]
    # the ring holds the same two spans, the child linked to its parent
    spans = {s.name: s for s in tel.spans()}
    assert spans["risk.slice"].parent_id == spans["risk.analysis"].span_id


def test_collections_are_spans_only_while_enabled():
    """``enable()`` hooks the collector and ``disable()`` unhooks it; a
    plane constructed enabled, as the tests' planes are, never hooks."""
    assert not any(isinstance(getattr(cb, "__self__", None), Telemetry)
                   for cb in gc.callbacks)
    built_on = Telemetry(enabled=True)
    gc.collect()
    assert built_on._on_gc not in gc.callbacks
    assert built_on.spans(name="host.gc") == []
    tel = Telemetry().enable()
    try:
        assert tel._on_gc in gc.callbacks
        with tel.span("risk.analysis"):
            gc.collect()
    finally:
        tel.disable()
    assert tel._on_gc not in gc.callbacks
    colls = tel.spans(name="host.gc")
    outer, = tel.spans(name="risk.analysis")
    full = [c for c in colls if c.attrs == {"generation": 2}]
    assert full and all(c.parent_id == outer.span_id for c in full)
    gc.collect()
    assert len(tel.spans(name="host.gc")) == len(colls)


def test_disabled_plane_allocates_nothing_on_risk_path(risk_setup):
    """With the global plane off, a whole analysis opens no span and
    touches no counter: each hook is one attribute check."""
    cfg, tables = risk_setup
    assert not TELEMETRY.enabled
    assert TELEMETRY._on_gc not in gc.callbacks
    before = (TELEMETRY.spans_opened, TELEMETRY.metric_snapshot())
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2))
    assert ara.tel is TELEMETRY
    ara.run_tenant_chunked(tables)
    assert (TELEMETRY.spans_opened, TELEMETRY.metric_snapshot()) == before


@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_risk_spans_nest_under_the_analysis(risk_setup, mode):
    """An enabled plane records each stage of an analysis under
    ``risk.analysis``: per tenant one slice, put, wait and dispatch.  The
    YLT is bit-identical to a run with the plane off, and the step
    compiles as often."""
    cfg, tables = risk_setup
    tenants = 4
    off = AggregateRiskAnalysis(cfg, TenancyConfig(1, tenants, mode),
                                telemetry=Telemetry())
    tel = Telemetry(enabled=True)
    on = AggregateRiskAnalysis(cfg, TenancyConfig(1, tenants, mode),
                               telemetry=tel)
    want = off.run_tenant_chunked(tables).ylt
    got = on.run_tenant_chunked(tables).ylt
    np.testing.assert_array_equal(got, want)
    assert on.trace_count == off.trace_count == 1
    spans = tel.spans()
    by_id = {s.span_id: s for s in spans}
    root, = [s for s in spans if s.name == "risk.analysis"]
    assert root.parent_id is None
    counts = {}
    for s in spans:
        counts[s.name] = counts.get(s.name, 0) + 1
        if s.name in RISK_SPANS:
            up = s
            while up.parent_id is not None:
                up = by_id[up.parent_id]
            assert up is root, s.name
            assert root.t_start <= s.t_start <= s.t_end <= root.t_end
    assert {n: counts.get(n, 0) for n in RISK_SPANS} == {
        "risk.plan": 1, "risk.tables": 1, "pipeline.start": 1,
        "risk.slice": tenants,
        "transfer.put": tenants, "transfer.wait": tenants,
        "pipeline.dispatch": tenants, "pipeline.drain": 1, "risk.gather": 1}


# ---------------------------------------------------------------------
# launch driver end to end (the --trace-out acceptance)
# ---------------------------------------------------------------------
def test_serve_driver_writes_trace_and_metrics(tmp_path, capsys):
    """`launch.serve --trace-out/--metrics-out` on the preempting demo
    mix produces a loadable Chrome trace with spans from the scheduler,
    round, pool and swap layers plus a parsable Prometheus file."""
    from repro.launch import serve
    trace = tmp_path / "trace.json"
    prom = tmp_path / "metrics.prom"
    try:
        rc = serve.main(["--mode", "continuous", "--tenants", "2",
                         "--requests", "3", "--capacity", "2",
                         "--priority", "3", "--new-tokens", "24",
                         "--stats-every", "4",
                         "--trace-out", str(trace),
                         "--metrics-out", str(prom)])
    finally:
        TELEMETRY.disable()       # the driver enables the global plane
        TELEMETRY.reset()
    assert rc == 0
    out = capsys.readouterr().out
    assert "obs: " in out                       # periodic stats line fired
    assert "heartbeat.suspects=" in out
    doc = json.loads(trace.read_text())
    layers = {e["name"].split(".", 1)[0]
              for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"sched", "round", "admit", "kv", "swap", "transfer"} <= layers
    parsed = parse_prometheus_text(prom.read_text())
    assert parsed["repro_swap_preemptions"] >= 1
    assert parsed["repro_swap_restores"] >= 1
