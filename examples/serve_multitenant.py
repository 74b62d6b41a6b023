"""Multi-tenant serving: three applications share one accelerator.

    PYTHONPATH=src python examples/serve_multitenant.py

The scheduler round-robins tenant slots on the engine's dispatch/await
halves: tenant k+1's batch assembly and staging are enqueued while tenant
k's on-device ``lax.scan`` decode loop is still running — the paper's
transfer-under-compute multi-tenancy applied to inference serving.  Prints
per-tenant utilisation (cf. paper Fig 14) and the realised overlap pairs,
then replays the same workload under continuous batching for comparison.

Continuous vs slot-based serving
--------------------------------
The *slot-based* schedules (``mode="overlapped"`` / ``"blocking"``) serve
one tenant batch at a time: every row in the batch is padded to the longest
prompt and decoded for the batch-max ``max_new_tokens``, and the device
drains completely between batches.  With ragged request mixes that padding
is pure waste — a 4-token dashboard query rides along for a 16-token
report's full decode.

``mode="continuous"`` instead keeps a fixed-capacity slot table resident on
the device (``repro.serving.continuous.ContinuousBatchingEngine``).  Each
outer step admits queued requests into free slots (prefill + scatter into a
paged KV-cache, ``repro.serving.kvcache.PagedKVCache``), runs one masked
fixed-step decode micro-round over *all* slots, and retires rows that hit
their budget, returning their cache pages to a free list.  Requests from
different tenants, with different prompt lengths and token budgets, decode
side by side; a finished row's lane is refilled within a round or two
instead of padding out the batch.  The decode step is shape-stable (paged
gather/scatter, fixed capacity), so the ragged mix costs one compile total
— and greedy decoding stays token-exact with the blocking engine on the
same padded prompt.  Admissions picked in one scheduling step are batched:
same-bucket prompts share a single prefill call.  The trade-off: lanes are
masked rather than compacted, so very low occupancy wastes compute on dead
rows.

One pool, many state kinds
--------------------------
The pool behind the slot table is a *paged-state pool*, not just an
attention KV-cache: every arch config registers the state kinds its slots
carry (``repro.serving.kvcache.state_kinds``) and ``mode="continuous"``
serves all of them.  Attention KV pages behave exactly as above;
encoder-decoder archs (whisper) add per-request read-only cross-attention
pages, written once at admission and gathered each decode step; SSM and
hybrid archs (mamba2, jamba) keep their recurrent slot state resident in
the slot table and checkpoint it as fixed-width host records on
swap-out, scattering it back bitwise on restore.  Preemption victims are
chosen regardless of kind — an SSM row swaps out and resumes
token-exactly just like an attention row — and the page/record ledger is
audited per kind at drain.  ``ContinuousBatchingEngine.supported_modes(
cfg)`` (or ``python -m repro.launch.serve --list-archs``) reports each
arch's state kinds, preemptability and exactness class without building
the model; per-request non-token inputs (vision patch embeds, encoder
frames) ride on ``Request.extra_inputs``.

Prefix sharing (refcounts + copy-on-write)
------------------------------------------
Real tenant traffic repeats itself: every pricing-desk query carries the
same system prompt, dashboards re-issue identical requests.  With
``prefix_sharing=True`` (the default) the paged pool is *content-shared*:
each page-aligned block of the padded prompt is keyed by the bytes of the
whole prompt up to its end, admission maps already-registered blocks onto
the existing pages (refcount++) instead of allocating and re-prefilling
them, and the first decode write into a shared page forks it (copy page,
remap the writer's table slot) so neighbours never see the divergence.  A
request whose entire padded prompt is registered skips its prefill call
outright, reusing the cached first-token logits.  Greedy decode stays
bit-identical to the unshared path — blocks are shared only when their
full token prefix is byte-equal, which makes the page contents bitwise
interchangeable.  Sliding-window archs participate too: their chain keys
are salted with the window phase (ring length + block offset), so pages
whose contents depend on which tokens the window has wrapped past only
match when the whole wrapped prefix matches — a byte-identical refresh
admitted while the original is in flight shares its ring pages and skips
prefill (the original's ring writes then CoW-fork), while
shared-system-prompt mixes with distinct suffixes correctly never share.
The final section replays a shared-system-prompt workload with sharing
off and on and prints the pages/prefill saved.

Paged-attention backends (jnp gather vs fused Pallas)
-----------------------------------------------------
Within a decode micro-round the paged pool can be read two ways
(``backend=`` on the continuous engine, ``--kernel-backend`` on the launch
driver):

* ``"jnp"`` (default) gathers each row's full logical window into a dense
  ``[capacity, bucket, Hkv, D]`` tensor per decode step.  Simple, bitwise
  the PR-3 math — but it moves O(bucket) pool bytes per emitted token even
  when most lanes are short or masked: the exact redundant-transfer tax
  the paper's sequential staging removes for the risk pipeline.
* ``"pallas"`` streams page-sized KV blocks in place through the fused
  paged-attention kernel (``repro.kernels.paged_attention``): the page
  table is a scalar-prefetch operand, so each grid cell's index map routes
  straight to its physical page and only referenced pages are ever read;
  online softmax accumulates across pages, and admission KV scatters
  page-granularly into its allocated pages.  Bytes per round drop to
  O(live tokens), and greedy decode stays token-exact with the jnp path
  (locked in by ``tests/test_paged_attention.py``).

When does which win?  On a real TPU the pallas backend is the one that
scales: the gather path's dense materialisation is pure HBM traffic the
fused kernel never issues, and its advantage grows with bucket length and
lane raggedness.  On CPU, Pallas runs in *interpret mode* — every grid
cell is emulated — so its wall time there is an artefact (often slower
than jnp); use jnp for CPU throughput, pallas to validate kernel semantics
and to track the bytes-moved structure (``bench_paged_attention`` carries
both columns).  The backends section decodes one workload on both and
checks the tokens agree.

Overload: priorities, preemption, shedding
------------------------------------------
The last section oversubscribes a deliberately tiny engine (2 slots) with
long tier-1 report jobs, then lands a tier-0 dashboard query mid-flight.
With preemption on, the scheduler swaps a tier-1 victim's KV pages out to
the host store, serves the tier-0 request in the freed slot, and restores
the victim token-exactly afterwards — the victim's final tokens are
bitwise what an uninterrupted run produces.  A ``max_backlog`` bound sheds
the lowest-priority queued work with an explicit REJECTED outcome instead
of letting the queue grow past the SLO; every submitted request always
reaches exactly one terminal outcome (completed / rejected / failed).

Crash safety: journal, checkpoint, recover
------------------------------------------
The same swap machinery doubles as the crash-recovery data plane.  Arm it
by giving the scheduler a durable write-ahead journal and a checkpoint
cadence::

    sched = MultiTenantScheduler(engine, mode="continuous", ...,
                                 journal="state/journal.jsonl",
                                 checkpoint_dir="state/checkpoints",
                                 checkpoint_every=8)

Every ``submit`` is fsync'd to the journal *before* the request is queued
(so a crash between the two re-queues it on recovery — never a lost
request), every collected micro-round appends a ROUND_COMMIT with
cumulative per-request token counts, and every terminal outcome is
journalled with its tokens.  Every ``checkpoint_every`` committed rounds
the scheduler quiesces the engine (one pipeline bubble) and snapshots the
*whole* serving state to disk: each live slot as the same per-kind
``SwapRecord`` preemption takes (attention pages, cross-attention pages,
SSM slot state — whatever the arch registers), the host swap tier under
its original tickets, the queued requests, the restore queue, and the
prefix-trie chain keys.  After a crash — SIGKILL included, mid-round or
mid-preemption — a *fresh* scheduler over the same journal rebuilds
everything::

    sched = MultiTenantScheduler(engine2, mode="continuous", ...,
                                 journal="state/journal.jsonl",
                                 checkpoint_dir="state/checkpoints")
    summary = sched.recover()      # then sched.drain() as usual

Checkpointed live slots re-enter the pool through the ordinary restore
jit (same staging lanes — a checkpoint taken on a 1x8 mesh restores on
any mesh), requests submitted after the checkpoint re-queue from the
journal, and the rounds committed after the checkpoint are *replayed*.

The exactness contract: decode is deterministic under seeded sampling
(the per-slot PRNG key folds in the emitted-token index, independent of
round composition), so for every non-MoE arch the replayed rounds
regenerate **bitwise-identical tokens** — a recovered request finishes
with exactly the tokens an uninterrupted run produces, and post-
checkpoint RETIRE records in the journal double as a cross-check oracle
(``summary.replay_check``).  MoE archs recover completion-level exact,
matching their ``supported_modes()`` exactness class.  On the launch
driver the equivalent knobs are ``--journal-dir`` /
``--checkpoint-every`` / ``--recover``.

Observability
-------------
Every layer this example exercises is instrumented against the telemetry
plane in ``repro.obs`` (disabled by default — the runs here cost nothing
extra).  Enabling it *before* building the stack lights up everything:

    from repro.obs import TELEMETRY
    TELEMETRY.enable()
    ... build engine/scheduler, run a workload ...
    from repro.obs.export import write_chrome_trace, prometheus_text
    write_chrome_trace(TELEMETRY, "trace.json")   # open in ui.perfetto.dev
    print(prometheus_text(TELEMETRY))             # counters/gauges/summaries

The span tree nests ``sched.step`` > ``round.dispatch`` > ``round.jit``
with retrospective ``round.device`` windows, ``kv.*`` counters for page
alloc/share/CoW-fork/evict, ``swap.*`` spans for preemption tiering and
``transfer.stage`` spans per staging lane (see ``repro/obs/__init__.py``
for the full naming scheme).  On the launch driver the equivalent knobs
are ``--trace-out`` / ``--metrics-out`` / ``--stats-every N``.
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core.pipeline import timeline_overlaps
from repro.core.tenancy import TenancyConfig
from repro.models import params as pp
from repro.models.model import build_model
from repro.serving.engine import ServingEngine
from repro.serving.multitenant import MultiTenantScheduler, Request

WORKLOADS = {"pricing-desk": (12, 24, 8),     # requests, prompt, new
             "batch-report": (6, 48, 16),
             "dashboard": (18, 12, 4)}


def submit_all(sched, cfg, seed=7):
    rng = np.random.default_rng(seed)
    for tenant, (n, plen, new) in WORKLOADS.items():
        for _ in range(n):
            sched.submit(Request(tenant,
                                 rng.integers(1, cfg.vocab_size,
                                              plen).astype(np.int32),
                                 max_new_tokens=new))


def report(sched, responses, label):
    print(f"\n=== {label}: served {len(responses)} requests across "
          f"{len(WORKLOADS)} tenants ===")
    print(f"{'tenant':>14} {'reqs':>5} {'tokens':>7} {'busy ms':>8} "
          f"{'share':>6}")
    for t, rep in sorted(sched.utilization_report().items()):
        print(f"{t:>14} {rep['requests']:>5.0f} {rep['tokens']:>7.0f} "
              f"{rep['busy_s'] * 1e3:>8.0f} {rep['busy_share'] * 100:>5.1f}%")
    lat = np.asarray([r.latency_s for r in responses])
    print(f"latency p50 {np.percentile(lat, 50) * 1e3:.0f} ms, "
          f"p99 {np.percentile(lat, 99) * 1e3:.0f} ms")
    ov = timeline_overlaps(sched.timeline)
    print(f"overlap pairs (staging k+1 inside decode k): {sum(ov)}/{len(ov)}")


def main():
    cfg = get_config("h2o-danube-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params, temperature=0.8)

    # slot-based: tenant batches staged under the running decode
    sched = MultiTenantScheduler(engine, max_batch=4,
                                 tenancy=TenancyConfig(1, 3))
    submit_all(sched, cfg)
    report(sched, sched.drain(), "slot-based (overlapped)")

    # continuous batching: paged KV-cache + persistent slot table
    sched = MultiTenantScheduler(
        engine, tenancy=TenancyConfig(1, 3), mode="continuous",
        continuous=dict(capacity=6, page_size=16, inner_steps=4,
                        max_prompt_len=64))
    submit_all(sched, cfg)
    report(sched, sched.drain(), "continuous (paged KV-cache)")
    eng = sched.continuous_engine
    print(f"micro-rounds={eng.rounds} x {eng.inner_steps} steps, "
          f"slot occupancy={eng.occupancy()*100:.1f}%, "
          f"pages reused={eng.kv.pages_reused}/{eng.kv.pages_allocated}")

    # prefix sharing: every tenant's queries repeat a 32-token system
    # prompt, and half of each tenant's requests are exact repeats
    # (dashboard refreshes) — the content-shared pool maps the common
    # blocks onto existing pages and skips repeat prefills entirely.
    # h2o-danube's sliding window would salt its chain keys with the
    # window phase, so only the byte-identical refreshes would share;
    # this section uses a full-attention arch so the shared system
    # prompt itself also maps onto common pages.
    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    rng = np.random.default_rng(11)
    system_prompt = rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
    originals, refreshes = [], []
    for t in range(3):
        for q in range(3):
            user = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
            prompt = np.concatenate([system_prompt, user])
            originals.append(Request(f"tenant-{t}", prompt,
                                     max_new_tokens=6))
            refreshes.append(Request(f"tenant-{t}", prompt.copy(),
                                     max_new_tokens=6))
    reqs = originals + refreshes     # refreshes arrive after their original
    print("\n=== prefix sharing: shared system prompt + repeated queries "
          "===")
    for sharing in (False, True):
        sched = MultiTenantScheduler(
            engine, tenancy=TenancyConfig(1, 3), mode="continuous",
            continuous=dict(capacity=6, page_size=16, inner_steps=4,
                            max_prompt_len=64, prefix_sharing=sharing))
        for r in reqs:
            sched.submit(r)
        sched.drain()
        eng = sched.continuous_engine
        print(f"sharing={'on ' if sharing else 'off'}: "
              f"pages allocated={eng.kv.pages_allocated:3d} "
              f"(shared mappings={eng.kv.pages_shared}, "
              f"cow forks={eng.kv.cow_forks}) "
              f"prefill calls={eng.prefill_calls} "
              f"skipped={eng.prefill_skips}")

    # paged-attention backends: the fused pallas kernels read pages in
    # place (no dense per-row KV gather) and must reproduce the jnp
    # backend's greedy tokens exactly — see the docstring section for when
    # each wins
    print("\n=== paged-attention backend: jnp gather vs fused pallas ===")
    from repro.serving.continuous import ContinuousBatchingEngine
    rng = np.random.default_rng(13)
    reqs = [Request(f"tenant-{i % 3}",
                    rng.integers(1, cfg.vocab_size,
                                 8 + 8 * (i % 2)).astype(np.int32),
                    max_new_tokens=4) for i in range(6)]
    tokens = {}
    for backend in ("jnp", "pallas"):
        eng = ContinuousBatchingEngine(engine, capacity=3, page_size=8,
                                       inner_steps=4, max_prompt_len=32,
                                       backend=backend)
        tokens[backend] = {id(r): t for r, t in eng.run_all(reqs)}
        blocks = eng.kv.max_blocks
        print(f"backend={backend:6s}: rounds={eng.rounds} "
              f"(dense window={'-' if backend == 'pallas' else f'{blocks} blocks/row/step'}; "
              f"pages streamed in place={'yes' if backend == 'pallas' else 'no'})")
    agree = all(np.array_equal(tokens["jnp"][id(r)], tokens["pallas"][id(r)])
                for r in reqs)
    print(f"tokens identical across backends: {agree}")

    # oversubscribed: 2 slots, long tier-1 reports in flight, a tier-0
    # dashboard query arriving mid-decode — preemption swaps a victim's
    # pages to the host tier, serves the query, restores token-exactly
    print("\n=== overload: priority preemption + load shedding ===")
    ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                    inner_steps=4, max_prompt_len=16)
    sched = MultiTenantScheduler(engine, mode="continuous",
                                 continuous_engine=ceng,
                                 preemption=True, max_backlog=4)
    rng = np.random.default_rng(17)
    reports = [Request(f"report-{i}",
                       rng.integers(1, cfg.vocab_size, 12).astype(np.int32),
                       max_new_tokens=40, priority=1) for i in range(2)]
    for r in reports:
        sched.submit(r)
    sched.step()                       # reports admitted, decode in flight
    dash = Request("dashboard",
                   rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                   max_new_tokens=4, priority=0)
    sched.submit(dash)                 # tier 0 against a full slot table
    backlog = [Request(f"backlog-{i}",
                       rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                       max_new_tokens=4, priority=1) for i in range(6)]
    for r in backlog:                  # 6 queued > max_backlog=4: 2 shed
        sched.submit(r)
    responses = {r.tenant: r for r in sched.drain()}
    shed = sum(int(s["shed"]) for s in sched.stats.values())
    print(f"preemptions={ceng.preemptions} restores={ceng.restores} "
          f"shed={shed}")
    for name in ("dashboard", *(r.tenant for r in reports)):
        resp = responses[name]
        print(f"  {name:>11}: {resp.outcome:9s} ttft={resp.ttft_s:.3f}s "
              f"swapped_out={resp.preemptions}x")
    n_rej = sum(r.outcome == 'rejected' for r in responses.values())
    print(f"  backlog: {sum(r.outcome == 'completed' for r in responses.values()) - 3} completed, "
          f"{n_rej} explicitly rejected (shed)")
    # the preempted report's tokens are bitwise an uninterrupted run's
    victim, = [r for r in reports if responses[r.tenant].preemptions]
    (
        _, want
    ), = ceng.run_all([Request("oracle", victim.prompt.copy(), 40)])
    exact = np.array_equal(want, responses[victim.tenant].tokens)
    print(f"  preempted row token-exact vs uninterrupted run: {exact}")


if __name__ == "__main__":
    main()
