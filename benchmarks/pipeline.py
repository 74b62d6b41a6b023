"""Executable-pipeline benchmarks on the real (CPU-reduced) stack:

* **risk pipeline** — :class:`repro.core.pipeline.PipelineExecutor` (stage
  k+1 under compute k).  Emits per-tenant transfer/compute windows so the
  harness can verify transfer(k+1) starts before compute(k) ends, plus
  resident-table-cache and trace-count rows for the repeated-run (serving)
  regime.
* **serving blocking vs overlapped** — the multi-tenant scheduler on the
  engine's host-blocking ``generate`` loop against the dispatch/await split
  (prefill + on-device ``lax.scan`` decode enqueued without blocking, tenant
  k+1's batch assembly + staging running under tenant k's decode).  Emits
  wall-time rows for both schedules plus the realised overlap-pair count
  from the serving ``TenantTimeline`` (same falsifiable predicate as the
  risk pipeline rows).
* **paged-attention backends** — the serving decode's dense jnp KV gather
  against the fused Pallas page-streaming kernel at several (bucket,
  page-size) points, with pages-touched and bytes-moved derived columns
  (the structural metric that transfers to real accelerators) plus a
  serving-level per-round A/B.

Run with ``python -m benchmarks.run --only pipeline [--json out.json]``.
Scale trials/devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
``--device-time`` switches the timers from bare wall time to
``jax.block_until_ready``-bracketed device timing: each timed call blocks
on every device array it returned before the clock stops, so on real
accelerators the number is time-to-device-completion instead of
time-to-enqueue (deferred from PR 1; on CPU the two coincide for the
host-blocking drains and differ only for benches that return device
arrays).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]

# set by ``benchmarks.run --device-time``: bracket every timed call with
# jax.block_until_ready on its result (device timing, not enqueue timing)
DEVICE_TIME = False


_BLOCK = None                                  # jax.block_until_ready, lazy


def _ready(result):
    """Under --device-time, block on every device array in ``result``
    before the caller stops its clock; otherwise a pass-through."""
    global _BLOCK
    if DEVICE_TIME and result is not None:
        if _BLOCK is None:                     # resolve once, outside the
            import jax                         # per-sample timed region
            _BLOCK = jax.block_until_ready
        _BLOCK(result)
    return result


def _best_of(fn, n: int = 3) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        _ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _min_ab(fn_a, fn_b, n: int = 9) -> Tuple[float, float, float, float]:
    """Interleaved A/B times; returns (min_a, min_b, med_a, med_b).

    The minimum is the noise-robust estimator on shared/throttled CPU hosts
    (scheduling noise is strictly additive); the median is reported alongside
    for drift tracking.  Under --device-time each call is bracketed by
    ``jax.block_until_ready`` on its return value."""
    ts_a, ts_b = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        _ready(fn_a())
        ts_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _ready(fn_b())
        ts_b.append(time.perf_counter() - t0)
    return (min(ts_a), min(ts_b),
            sorted(ts_a)[n // 2], sorted(ts_b)[n // 2])


def bench_pipeline_overlap() -> List[Row]:
    import jax
    from repro.configs.risk_app import RiskAppConfig
    from repro.core.tenancy import TenancyConfig
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import generate

    devices = jax.devices()
    n_pdev = len(devices)
    # transfer-heavy shape: big YET, one cache-resident ELT, single event
    # chunk — staging is a large share of the step, which is the regime the
    # overlap targets (paper Fig 13; on TPU the DMA engines make this the
    # common case, on CPU hosts compute shares cores with the memcpy)
    cfg = dataclasses.replace(RiskAppConfig().reduced(), num_trials=131072,
                              events_per_trial=128, event_catalog=512,
                              num_elts=1, chunk_events=128)
    tables = generate(cfg, seed=0)
    tenancy = TenancyConfig(n_pdev, 2, "sequential")
    ara = AggregateRiskAnalysis(cfg, tenancy, devices=devices)

    ara.run_tenant_chunked(tables)    # warm (uniform padding -> one trace)

    out: List[Row] = []
    t_ovl = _best_of(lambda: ara.run_tenant_chunked(tables), n=9)
    tag = f"{n_pdev}p_2v"
    from repro.core.pipeline import lane_overlaps
    rep = ara.run_tenant_chunked(tables)
    # falsifiable overlap signal: transfer(k+1) began inside compute(k)'s
    # execution window, k and k+1 consecutive tenants of one pdev's lane
    # (see repro.core.pipeline module docstring).  A schedule that staged
    # everything first would score 0 pairs; noise on a shared host can
    # drain isolated pairs early, so "realised" = majority of pairs
    # overlapped.
    overlaps = lane_overlaps(rep.timeline)
    out.append((f"pipeline/overlapped_{tag}", t_ovl * 1e6,
                f"trials={cfg.num_trials};"
                f"overlap_pairs={sum(overlaps)}/{len(overlaps)};"
                f"overlap_realised={sum(overlaps) > len(overlaps) // 2}"))
    for tl in rep.timeline:
        out.append((f"pipeline/tenant_v{tl.vdev}", tl.compute_s * 1e6,
                    f"pdev={tl.pdev};slot={tl.slot};"
                    f"tr={tl.transfer_start * 1e3:.2f}-"
                    f"{tl.transfer_end * 1e3:.2f}ms;"
                    f"cp={tl.compute_start * 1e3:.2f}-"
                    f"{tl.compute_end * 1e3:.2f}ms"))

    # repeated-run regime: resident tables + trace cache must both hit
    up0, tr0 = ara.table_uploads, ara.trace_count
    t_rerun = _best_of(lambda: ara.run_tenant_chunked(tables), n=2)
    out.append(("pipeline/rerun_resident", t_rerun * 1e6,
                f"table_uploads_delta={ara.table_uploads - up0};"
                f"trace_delta={ara.trace_count - tr0}"))
    return out


def bench_serving_overlap() -> List[Row]:
    import jax
    from repro.configs import get_config
    from repro.core.pipeline import timeline_overlaps
    from repro.models import params as pp
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine
    from repro.serving.multitenant import MultiTenantScheduler, Request

    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    tenants, requests, steps, plen = 3, 12, 16, 32
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(requests)]

    def run(overlapped: bool) -> MultiTenantScheduler:
        sched = MultiTenantScheduler(engine, max_batch=4,
                                     overlapped=overlapped)
        for i, p in enumerate(prompts):
            sched.submit(Request(f"tenant-{i % tenants}", p,
                                 max_new_tokens=steps))
        sched.drain()                     # reaps the waiter thread too
        return sched

    run(False)                 # warm: prefill + per-token decode compiles
    run(True)                  # warm: prefill + scanned decode-loop compile

    out: List[Row] = []
    t_blk, t_ovl, med_blk, med_ovl = _min_ab(lambda: run(False),
                                             lambda: run(True), n=5)
    tag = f"{tenants}t_{requests}r_{steps}s"
    out.append((f"serving/blocking_{tag}", t_blk * 1e6,
                f"median_us={med_blk * 1e6:.0f};arch=internlm2-1.8b-reduced"))
    sched = run(True)
    ov = timeline_overlaps(sched.timeline)
    out.append((f"serving/overlapped_{tag}", t_ovl * 1e6,
                f"speedup={t_blk / t_ovl:.2f}x;"
                f"median_us={med_ovl * 1e6:.0f};"
                f"overlap_pairs={sum(ov)}/{len(ov)};"
                f"overlap_realised={sum(ov) > len(ov) // 2}"))
    for i, tl in enumerate(sched.timeline):
        out.append((f"serving/batch{i}_slot{tl.slot}", tl.compute_s * 1e6,
                    f"tr={tl.transfer_start * 1e3:.2f}-"
                    f"{tl.transfer_end * 1e3:.2f}ms;"
                    f"cp={tl.compute_start * 1e3:.2f}-"
                    f"{tl.compute_end * 1e3:.2f}ms"))
    return out


def bench_serving_continuous() -> List[Row]:
    """Continuous batching (paged KV-cache + persistent slot table) vs the
    slot-based overlapped schedule on a *ragged* request mix — the regime
    the new subsystem targets: mixed prompt lengths and token budgets, where
    slot batches pad every row to the batch max and drain between tenants.

    Emits wall-time A/B rows plus the occupancy comparison the paper's
    utilisation argument predicts: decode micro-rounds (device decode steps)
    sustained per wall-second, useful-token throughput, and the continuous
    engine's slot-occupancy / page-reuse counters.
    """
    import jax
    from repro.configs import get_config
    from repro.core.pipeline import timeline_overlaps
    from repro.models import params as pp
    from repro.models.model import build_model
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.multitenant import MultiTenantScheduler, Request

    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    # one shared continuous engine: its jitted decode round / admission are
    # compiled once and reused across every timed run
    ceng = ContinuousBatchingEngine(engine, capacity=4, page_size=8,
                                    inner_steps=8, max_prompt_len=16)
    # every tenant's slot batch pairs one 256-token straggler with three
    # 32-token rows, so the slot path decodes 256 serial padded steps per
    # batch while continuous retires the short rows and refills their lanes
    tenants, per_tenant = 3, 4
    steps_pat = [256, 32, 32, 32]
    rng = np.random.default_rng(0)
    mix = []
    for i in range(per_tenant):
        for t in range(tenants):
            mix.append((f"tenant-{t}",
                        rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                        steps_pat[i % len(steps_pat)]))
    useful_tokens = sum(s for _, _, s in mix)

    def run(mode: str) -> MultiTenantScheduler:
        sched = MultiTenantScheduler(
            engine, max_batch=4, mode=mode,
            continuous_engine=ceng if mode == "continuous" else None)
        for tenant, p, s in mix:
            sched.submit(Request(tenant, p, max_new_tokens=s))
        sched.drain()
        return sched

    run("overlapped")          # warm: per-steps decode-loop compiles
    run("continuous")          # warm: round + per-bucket admission compiles

    t_slot, t_cont, med_slot, med_cont = _min_ab(
        lambda: run("overlapped"), lambda: run("continuous"), n=5)

    # fresh measured runs for the occupancy counters (deltas per run).
    # micro-rounds/wall-second compares each schedule's decode granule —
    # the boundary at which it can admit/retire work: one padded batch
    # decode for the slot path vs one masked inner_steps round for
    # continuous — the headline occupancy claim of the A/B.
    d0 = engine.decode_steps
    t0 = time.perf_counter()
    sched_slot = run("overlapped")
    wall_slot = time.perf_counter() - t0
    slot_steps = engine.decode_steps - d0
    slot_batches = len(sched_slot.timeline)

    r0, rs0, pr0 = ceng.rounds, ceng.row_steps, ceng.kv.pages_reused
    t0 = time.perf_counter()
    sched_cont = run("continuous")
    wall_cont = time.perf_counter() - t0
    cont_rounds = ceng.rounds - r0
    cont_steps = cont_rounds * ceng.inner_steps
    cont_row_steps = ceng.row_steps - rs0

    tag = f"{tenants}t_{len(mix)}r_ragged"
    out: List[Row] = []
    out.append((f"serving/slotbatch_{tag}", t_slot * 1e6,
                f"median_us={med_slot * 1e6:.0f};"
                f"micro_rounds_per_s={slot_batches / wall_slot:.1f};"
                f"decode_steps={slot_steps};"
                f"steps_per_s={slot_steps / wall_slot:.1f};"
                f"useful_tok_per_s={useful_tokens / wall_slot:.1f}"))
    ov = timeline_overlaps(sched_cont.timeline)
    out.append((f"serving/continuous_{tag}", t_cont * 1e6,
                f"speedup={t_slot / t_cont:.2f}x;"
                f"median_us={med_cont * 1e6:.0f};"
                f"micro_rounds_per_s={cont_rounds / wall_cont:.1f};"
                f"decode_steps={cont_steps};"
                f"steps_per_s={cont_steps / wall_cont:.1f};"
                f"useful_tok_per_s={useful_tokens / wall_cont:.1f};"
                f"occupancy={cont_row_steps / max(cont_steps * ceng.capacity, 1):.2f};"
                f"pages_reused={ceng.kv.pages_reused - pr0};"
                f"overlap_pairs={sum(ov)}/{len(ov)}"))
    return out


def bench_serving_prefix_sharing() -> List[Row]:
    """Refcounted prefix sharing + batched admission vs the PR-3 continuous
    baseline (one B=1 prefill per admission, private pages per request) on
    the workload the sharing targets: N tenants whose every query carries
    the same system prompt, with a tail of exact repeat queries (dashboard
    refreshes).

    Emits the cold-run allocator comparison the tentpole's acceptance
    criteria name — pages allocated and prefill calls with sharing+batching
    off vs on — plus steady-state (warm trie) deltas and the wall-time A/B.
    Token-exactness of the shared path is locked in by
    ``tests/test_continuous.py``, not re-checked here.
    """
    import jax
    from repro.configs import get_config
    from repro.models import params as pp
    from repro.models.model import build_model
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.multitenant import Request

    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    tenants, queries = 4, 2
    page, sys_len, user_len, new_tok = 16, 48, 16, 8
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(1, cfg.vocab_size, sys_len).astype(np.int32)
    originals, repeats = [], []
    for t in range(tenants):
        for _ in range(queries):
            user = rng.integers(1, cfg.vocab_size,
                                user_len).astype(np.int32)
            originals.append(Request(
                f"tenant-{t}", np.concatenate([system_prompt, user]),
                max_new_tokens=new_tok))
        repeats.append(Request(f"tenant-{t}",
                               originals[-1].prompt.copy(),
                               max_new_tokens=new_tok))
    mix = originals + repeats        # repeats arrive after their originals

    def make(shared: bool) -> ContinuousBatchingEngine:
        # the baseline disables both tentpole halves: B=1 admission prefill
        # and private pages per request — exactly mode="continuous" as of
        # PR 3
        return ContinuousBatchingEngine(
            engine, capacity=8, page_size=page, inner_steps=4,
            max_prompt_len=sys_len + user_len, prefix_sharing=shared,
            batch_admission=shared)

    ceng_base, ceng_share = make(False), make(True)
    # cold-run counters: what one pass over the workload allocates/prefills
    ceng_base.run_all(mix)
    pages_base, calls_base = (ceng_base.kv.pages_allocated,
                              ceng_base.prefill_calls)
    ceng_share.run_all(mix)
    pages_share, calls_share = (ceng_share.kv.pages_allocated,
                                ceng_share.prefill_calls)
    skips_cold = ceng_share.prefill_skips
    shared_cold, forks_cold, pristine_cold = (
        ceng_share.kv.pages_shared, ceng_share.kv.cow_forks,
        ceng_share.kv.pristine_forks)

    # steady state: the trie retains the shared chains, so a repeat pass
    # shares nearly everything
    p0, c0, s0 = (ceng_share.kv.pages_allocated, ceng_share.prefill_calls,
                  ceng_share.prefill_skips)
    ceng_share.run_all(mix)
    steady_pages = ceng_share.kv.pages_allocated - p0
    steady_calls = ceng_share.prefill_calls - c0
    steady_skips = ceng_share.prefill_skips - s0

    t_base, t_share, med_base, med_share = _min_ab(
        lambda: ceng_base.run_all(mix), lambda: ceng_share.run_all(mix),
        n=5)

    # reuse-aware pristine-preserve A/B: on a share-nothing workload the
    # PR-4 preserve-always policy pays one page copy per admission to cache
    # chains nobody ever re-shares; the reuse-aware default (preserve only
    # after a recorded sharing hit) should pay none — while the shared
    # workload above keeps its pristine cache (hits recorded)
    lonely = [Request(f"t{i}", rng.integers(1, cfg.vocab_size,
                                            sys_len).astype(np.int32),
                      max_new_tokens=new_tok) for i in range(8)]
    policy_rows = []
    for policy in ("always", True):
        ceng_p = ContinuousBatchingEngine(
            engine, capacity=8, page_size=page, inner_steps=4,
            max_prompt_len=sys_len + user_len, preserve_pristine=policy)
        ceng_p.run_all(lonely)
        policy_rows.append((policy, ceng_p.kv.pristine_forks,
                            ceng_p.kv.pages_allocated))

    tag = f"{tenants}t_{len(mix)}r_sysprompt"
    out: List[Row] = []
    (_, forks_always, pages_always), (_, forks_reuse, pages_reuse) = \
        policy_rows
    out.append((f"serving/pristine_policy_sharenothing_{tag}",
                float(forks_always),
                f"pristine_forks_always={forks_always};"
                f"pristine_forks_reuse_aware={forks_reuse};"
                f"pages_allocated_always={pages_always};"
                f"pages_allocated_reuse_aware={pages_reuse};"
                f"copies_eliminated={forks_always - forks_reuse}"))
    out.append((f"serving/prefix_unshared_{tag}", t_base * 1e6,
                f"median_us={med_base * 1e6:.0f};"
                f"pages_allocated={pages_base};"
                f"prefill_calls={calls_base};"
                f"arch=internlm2-1.8b-reduced"))
    out.append((f"serving/prefix_shared_{tag}", t_share * 1e6,
                f"speedup={t_base / t_share:.2f}x;"
                f"median_us={med_share * 1e6:.0f};"
                f"pages_allocated={pages_share};"
                f"pages_saved={1 - pages_share / pages_base:.0%};"
                f"prefill_calls={calls_share};"
                f"prefill_call_ratio="
                f"{calls_base / max(calls_share, 1):.1f}x;"
                f"prefill_skips={skips_cold};"
                f"pages_shared={shared_cold};"
                f"cow_forks={forks_cold};"
                f"pristine_forks={pristine_cold};"
                f"steady_pages={steady_pages};"
                f"steady_prefill_calls={steady_calls};"
                f"steady_prefill_skips={steady_skips}"))
    return out


def bench_paged_attention() -> List[Row]:
    """Paged-attention backend A/B: the dense jnp gather (materialise every
    row's full logical window per decode step) against the fused Pallas
    kernel (stream page blocks in place through the page table) at several
    (bucket, page-size) points, plus a serving-level per-round comparison.

    Two metric families per point:

    * **wall/device time** — honest but, for the pallas rows on CPU, an
      *interpret-mode emulation artefact* (every grid cell is a Python-level
      block evaluation): rank them for drift, not for speed.  On real TPUs
      the time ratio follows the bytes ratio.
    * **derived traffic columns** — pages touched and pool bytes moved per
      call, computed from the page tables: the gather path always touches
      ``C x NB`` page blocks *and* materialises them as a dense
      ``[C, NB*P, Hkv, D]`` intermediate (written then re-read by the
      attention einsum); the fused path touches only the live pages (+ the
      shared SENTINEL page for table padding) and materialises nothing.
      This is the structural O(bucket) -> O(live-tokens) claim, measured
      from the same tables the kernels consume.

    The serving-level rows run one ragged workload through both backends of
    the continuous engine and derive per-round pool traffic from the
    allocator's live-page counts at each dispatch.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.serving.kvcache import (POS_SENTINEL, PagedKVCache,
                                       paged_attend)

    cfg = get_config("internlm2-1.8b").reduced()
    C, Hkv, D, H = 4, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    rng = np.random.default_rng(0)
    out: List[Row] = []
    bf16 = 2                                   # pool bytes per element

    for bucket, page in ((64, 8), (128, 16), (256, 16)):
        NB = bucket // page
        NP_ = PagedKVCache.RESERVED + C * NB
        k_pool = jnp.asarray(rng.standard_normal((NP_, page, Hkv, D)),
                             jnp.bfloat16)
        v_pool = jnp.asarray(rng.standard_normal((NP_, page, Hkv, D)),
                             jnp.bfloat16)
        pos_pool = np.full((NP_, page), POS_SENTINEL, np.int32)
        page_table = np.full((C, NB), PagedKVCache.SENTINEL, np.int32)
        next_page, live_pages, live_tokens = PagedKVCache.RESERVED, 0, 0
        for c in range(C):                     # ragged: 1/4 .. 4/4 of NB
            nb_c = max(1, ((c + 1) * NB) // C)
            pos = nb_c * page - 1
            live_pages += nb_c
            live_tokens += pos + 1
            for b in range(nb_c):
                page_table[c, b] = next_page
                pos_pool[next_page] = np.arange(b * page, (b + 1) * page)
                next_page += 1
        pt = jnp.asarray(page_table)
        pp_ = jnp.asarray(pos_pool)
        pos = jnp.asarray([max(1, ((c + 1) * NB) // C) * page - 1
                           for c in range(C)], jnp.int32)
        q = jnp.asarray(rng.standard_normal((C, H, D)).astype(np.float32))

        fn_jnp = jax.jit(lambda q, k, v, pp_, pt, ps: paged_attend(
            q, {"k": k, "v": v}, pt, ps, cfg, pos_pool=pp_, backend="jnp"))
        fn_pal = jax.jit(lambda q, k, v, pp_, pt, ps: paged_attend(
            q, {"k": k, "v": v}, pt, ps, cfg, pos_pool=pp_,
            backend="pallas"))
        a = fn_jnp(q, k_pool, v_pool, pp_, pt, pos)     # warm + validate
        b = fn_pal(q, k_pool, v_pool, pp_, pt, pos)
        ok = bool(np.allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                              atol=3e-6))
        t_jnp, t_pal, med_jnp, med_pal = _min_ab(
            lambda: fn_jnp(q, k_pool, v_pool, pp_, pt, pos),
            lambda: fn_pal(q, k_pool, v_pool, pp_, pt, pos))

        page_bytes = page * Hkv * D * bf16 * 2          # k + v
        dense_blocks = C * NB                           # every table entry
        gather_bytes = dense_blocks * page_bytes        # pool reads
        dense_interm = dense_blocks * page_bytes * 2    # write + re-read
        fused_blocks = live_pages + 1                   # + shared SENTINEL
        fused_bytes = fused_blocks * page_bytes
        tag = f"{bucket}b_{page}p"
        out.append((f"paged/attend_jnp_{tag}", t_jnp * 1e6,
                    f"median_us={med_jnp * 1e6:.0f};"
                    f"pages_touched={dense_blocks};"
                    f"bytes_moved={gather_bytes + dense_interm};"
                    f"dense_intermediate_bytes={dense_interm};"
                    f"live_tokens={live_tokens}"))
        out.append((f"paged/attend_pallas_{tag}", t_pal * 1e6,
                    f"median_us={med_pal * 1e6:.0f};"
                    f"pages_touched={fused_blocks};"
                    f"bytes_moved={fused_bytes};"
                    f"dense_intermediate_bytes=0;"
                    f"live_tokens={live_tokens};"
                    f"bytes_saved={(gather_bytes + dense_interm) / fused_bytes:.1f}x;"
                    f"matches_jnp={ok};interp_emulation=True"))

    # serving-level per-round A/B on a ragged continuous workload
    from repro.models import params as pp2
    from repro.models.model import build_model
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.engine import ServingEngine
    from repro.serving.multitenant import Request

    from repro.serving.kvcache import attn_subs
    params, _ = pp2.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    engine = ServingEngine(cfg, params)
    reqs = [Request(f"t{i % 2}",
                    rng.integers(1, cfg.vocab_size,
                                 8 + 8 * (i % 3)).astype(np.int32),
                    max_new_tokens=4 + 4 * (i % 2)) for i in range(8)]
    n_attn = len(attn_subs(cfg))

    rows = {}
    for backend in ("jnp", "pallas"):
        ceng = ContinuousBatchingEngine(engine, capacity=4, page_size=8,
                                        inner_steps=4, max_prompt_len=32,
                                        backend=backend)
        live_at_dispatch = []
        orig = ceng.dispatch_round

        def probe(ceng=ceng, live=live_at_dispatch, orig=orig):
            kv = ceng.kv
            live.append(kv.num_pages - kv.RESERVED - kv.free_pages()
                        - kv.cached_pages())
            return orig()

        ceng.dispatch_round = probe
        ceng.run_all(reqs)                      # warm (compiles)
        live_at_dispatch.clear()
        r0 = ceng.rounds
        t = _best_of(lambda: ceng.run_all(reqs), n=3)
        rounds = (ceng.rounds - r0) // 3
        rows[backend] = (t, rounds, float(np.mean(live_at_dispatch)), ceng)

    page_bytes = 8 * Hkv * D * bf16 * 2
    n_layers = n_attn * rows["jnp"][3].n_stages
    per_round = {}
    for backend, (t, rounds, live_mean, ceng) in rows.items():
        steps = ceng.inner_steps
        if backend == "jnp":
            blocks = 4 * ceng.kv.max_blocks             # capacity x NB
            traffic = steps * n_layers * blocks * page_bytes * 3
        else:
            traffic = steps * n_layers * (live_mean + 1) * page_bytes
        per_round[backend] = traffic
        out.append((f"paged/serving_round_{backend}", t / max(rounds, 1) * 1e6,
                    f"rounds_per_drain={rounds};"
                    f"mean_live_pages={live_mean:.1f};"
                    f"pool_bytes_per_round={traffic:.0f};"
                    + (f"bytes_improvement="
                       f"{per_round['jnp'] / traffic:.1f}x;"
                       f"interp_emulation=True" if backend == "pallas"
                       else "dense_window=full")))
    return out


_MESH_CHILD = r'''
import dataclasses, json, os, sys, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()
import numpy as np
import jax
from repro.configs import get_config
from repro.distributed.sharding import parse_mesh, serving_sharder
from repro.models import params as pp
from repro.models.model import build_model
from repro.serving.continuous import ContinuousBatchingEngine
from repro.serving.engine import ServingEngine

# reduced() clamps to 2 KV heads; re-widen so 8 ways divide the pools
cfg = dataclasses.replace(get_config("internlm2-1.8b").reduced(),
                          num_heads=16, num_kv_heads=8)
params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
rng = np.random.default_rng(0)


class R:
    def __init__(self, rid, prompt, n):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = n
        self.temperature = 0.0
        self.top_k = 0
        self.seed = 0


prompts = [rng.integers(1, cfg.vocab_size, 8 + 4 * (i % 3)).astype(np.int32)
           for i in range(8)]


def run(sh, capacity, num_pages):
    eng = ServingEngine(cfg, params, sh=sh)
    ceng = ContinuousBatchingEngine(eng, capacity=capacity, page_size=8,
                                    num_pages=num_pages, inner_steps=2,
                                    max_prompt_len=32)
    ceng.run_all([R(-1, prompts[0], 2)])           # warm the jit caches
    t0 = time.perf_counter()
    out = ceng.run_all([R(i, p, 8) for i, p in enumerate(prompts)])
    dt = time.perf_counter() - t0
    # completion order depends on capacity; key by request id
    return dt, {req.rid: toks for req, toks in out}, ceng


base_dt, base_toks, bceng = run(None, 4, 48)
# one sharded engine instance at 2x the slots: per-device KV stays flat
# because the pool splits 8 ways along KV heads
mesh_dt, mesh_toks, mceng = run(serving_sharder(parse_mesh("1x8")), 8, 96)
exact = all(np.array_equal(base_toks[i], mesh_toks[i])
            for i in range(len(prompts)))
name = mceng.kv.attn_subs[0]
pool = mceng.state["caches"][name]["k"]
shard_bytes = next(iter(pool.addressable_shards)).data.nbytes
print(json.dumps({
    "base_s": base_dt, "mesh_s": mesh_dt, "token_exact": bool(exact),
    "base_capacity": 4, "mesh_capacity": 8,
    "n_shards": len(pool.sharding.device_set),
    "pool_bytes_full": int(pool.nbytes), "pool_bytes_shard": int(shard_bytes),
    "decode_traces": mceng.decode_traces}))
'''


def bench_serving_mesh() -> List[Row]:
    """One mesh-sharded engine instance against the single-device baseline:
    same eight-request greedy workload, but the 1x8 engine runs 2x the slot
    capacity while each device holds 1/8 of the KV pool.  Spawned as a
    subprocess because the mesh needs 8 host devices and XLA_FLAGS is fixed
    at interpreter start.  The child is pinned to the CPU (the parent may
    hold the chip, which one process owns at a time), so its rows are
    labelled platform=cpu; a failed child raises."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh serving child exited {proc.returncode}:\n"
                           f"{proc.stderr[-2000:]}")
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    cap_ratio = rep["mesh_capacity"] / rep["base_capacity"]
    shard_frac = rep["pool_bytes_shard"] / rep["pool_bytes_full"]
    tag = (f"platform=cpu;token_exact={rep['token_exact']};mesh=1x8;"
           f"decode_traces={rep['decode_traces']}")
    return [
        ("pipeline/serving_mesh_base_cap4_drain", rep["base_s"] * 1e6,
         f"{tag};capacity={rep['base_capacity']}"),
        ("pipeline/serving_mesh_1x8_cap8_drain", rep["mesh_s"] * 1e6,
         f"{tag};capacity={rep['mesh_capacity']}"),
        ("pipeline/serving_mesh_capacity_per_engine_x", cap_ratio,
         f"derived;slots_per_instance_vs_single_device;{tag}"),
        ("pipeline/serving_mesh_pool_shard_fraction", shard_frac,
         f"derived;per_device_kv_bytes/full={rep['pool_bytes_shard']}"
         f"/{rep['pool_bytes_full']};n_shards={rep['n_shards']}"),
    ]


def bench_serving_archs() -> List[Row]:
    """Continuous serving across the state-kind-representative archs the
    paged-state pool (PR 9) unlocks: whisper-base (attn KV pages plus
    read-only cross-attention pages written once at admission), mamba2-2.7b
    (no pages at all — checkpointed SSM slot records) and h2o-danube-1.8b
    (sliding-window attn with window-phase chain keys).  Each drains a small
    ragged request mix and reports wall time plus the pool's per-kind
    counters; every row also replays the same requests through the blocking
    oracle and asserts token-exactness, so the bench doubles as an
    end-to-end smoke for every non-attention serving path."""
    import jax
    from repro.configs import get_config
    from repro.models import params as pp
    from repro.models.model import build_model
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.engine import ServingEngine, resolve_extra_inputs
    from repro.serving.multitenant import Request

    out: List[Row] = []
    for arch in ("whisper-base", "mamba2-2.7b", "h2o-danube-1.8b"):
        cfg = get_config(arch).reduced()
        params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
        engine = ServingEngine(cfg, params)
        ceng = ContinuousBatchingEngine(engine, capacity=2, page_size=8,
                                        inner_steps=4, max_prompt_len=16)
        rng = np.random.default_rng(0)
        reqs = [Request(f"t{i}", rng.integers(1, cfg.vocab_size,
                        int(n)).astype(np.int32), max_new_tokens=8)
                for i, n in enumerate((5, 9, 13))]
        # warm: admission + round jits compile outside the timed drain
        ceng.run_all([Request("warm", reqs[0].prompt.copy(),
                              max_new_tokens=2)])
        t0 = time.perf_counter()
        done = {req.tenant: toks for req, toks in ceng.run_all(list(reqs))}
        dt = time.perf_counter() - t0
        exact = True
        for req in reqs:
            # blocking replay under the continuous path's conventions: the
            # prompt left-padded to its admission bucket and the same
            # resolved per-request extras (e.g. default zero enc-dec frames)
            b = ceng.bucket_len(req.prompt.size)
            padded = np.zeros((1, b), np.int32)
            padded[0, b - req.prompt.size:] = req.prompt
            extra = {k: np.asarray(v)[None] for k, v in
                     resolve_extra_inputs(cfg, req).items()}
            ref = engine.generate(padded, req.max_new_tokens,
                                  extra_inputs=extra or None,
                                  seed=req.seed).tokens[0]
            exact = exact and np.array_equal(done[req.tenant], ref)
        kinds = "+".join(k.name for k in ceng.kv.state_kinds)
        out.append((f"serving/archs_{arch}_drain", dt * 1e6,
                    f"token_exact={exact};kinds={kinds};"
                    f"rounds={ceng.rounds};"
                    f"pages_shared={ceng.kv.pages_shared};"
                    f"cross_pages={ceng.kv.num_cross_pages}"))
    return out


ALL = [bench_pipeline_overlap, bench_serving_overlap,
       bench_serving_continuous, bench_serving_prefix_sharing,
       bench_paged_attention, bench_serving_mesh, bench_serving_archs]
