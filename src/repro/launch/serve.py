"""Multi-tenant serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --tenants 3 --requests 12 --mode continuous

Builds the model (``--reduced``, the default, cuts it to CPU-test widths;
``--no-reduced`` keeps the published widths), spins up the multi-tenant
scheduler and drains a synthetic request mix, printing per-tenant
utilisation (the serving analogue of the paper's Fig 14 utilisation table)
plus the realised staging/decode overlap pairs.  :func:`build_stack` and
:func:`drain` are the pieces ``chip_smoke.py`` shares with :func:`main`.
``--mode`` selects the schedule:

* ``continuous`` — continuous batching over a persistent slot table with a
  paged KV-cache: requests are admitted into an in-flight decode (same
  prompt-bucket admissions batched into one prefill call; with
  ``--prefix-sharing`` common prompt prefixes map onto existing pages with
  copy-on-write) and retired rows are evicted, so the device never drains
  between tenant batches (also prints micro-round occupancy and
  page-sharing stats).  The decode round reads the pool through the fused
  page-streaming Pallas kernels on a TPU and the dense jnp gather
  elsewhere; ``--kernel-backend`` overrides that (pallas runs in interpret
  mode on the CPU, where it demonstrates structure, not speed).  Overload
  knobs: ``--priority K`` marks every K-th request
  tier 0, ``--swap`` (default on) lets blocked tier-0 arrivals preempt
  tier-1 rows via host-tier KV swap (token-exact restore), and
  ``--max-backlog N`` sheds the lowest-priority queued work past N with
  an explicit REJECTED outcome;
* ``overlapped`` (default) — tenant-slot batching with up to
  ``--stage-depth`` batches staged under the running decode;
* ``blocking`` — the legacy host-blocking schedule (A/B baseline).

Every arch in ``configs/`` serves under every mode (PR 9): the continuous
slot table decomposes per-request state into registered kinds — paged
attention KV, write-once cross-attention pages (encoder-decoder archs) and
checkpointable SSM slot state (SSM/hybrid archs) — and all of them swap,
so preemption works for every family.  ``--list-archs`` prints the
capability table (state kinds, preemptable, prefix sharing, exactness
class) per arch without building a model.  Encoder-decoder archs prefill
from synthetic deterministic frames, vision archs from synthetic patch
embeddings — distinct per request, so their chain keys only share when
content (prompt *and* extras) is byte-identical.  Sliding-window archs
prefix-share through window-phase chain keys: the ring layout is part of
block identity, and a block is shareable only when the *whole* prompt
content feeding its window is identical.


Crash safety (``--mode continuous`` only — the other schedules do not
journal round commits or retirements, so pairing them with
``--journal-dir`` is rejected): ``--journal-dir DIR`` arms the durable
write-ahead request journal (``DIR/journal.jsonl``, fsync'd per record —
every submission is on disk *before* it is queued) and engine checkpoints
(``DIR/checkpoints/engine_<N>/``); ``--checkpoint-every K`` snapshots the
whole serving state — every live slot's per-kind host record, the host
swap tier, queue/priority state and the prefix-trie keys — every K
committed decode rounds (engine quiesced; one pipeline bubble per
checkpoint).  After a crash (SIGKILL included), re-running with the same
``--journal-dir`` plus ``--recover`` rebuilds a fresh engine from the
latest checkpoint, re-queues journalled-but-never-checkpointed requests,
and *replays* the rounds committed after the checkpoint.  The exactness
contract matches ``--list-archs``: non-MoE archs recover bitwise
token-exact (seeded sampling folds the per-slot key by emitted-token
index, so replayed rounds regenerate identical tokens); MoE archs recover
completion-exact per their ``supported_modes`` exactness class.

Observability: ``--trace-out trace.json`` enables the telemetry plane and
writes a Chrome-trace/Perfetto JSON of every span the run recorded
(scheduler steps > round dispatch > kernel windows, KV pool activity, swap
lanes); ``--metrics-out metrics.prom`` writes the Prometheus text
exposition of the counters/gauges; ``--stats-every N`` prints a compact
``obs: k=v`` line every N scheduling steps (including the heartbeat
suspect gauge).  Any of the three lights up the global plane before the
stack is built; without them telemetry stays disabled and costs nothing.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import ArchConfig, get_config
from repro.core.tenancy import TenancyConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pp
from repro.models.model import build_model
from repro.serving.engine import ServingEngine
from repro.serving.multitenant import (MODES, MultiTenantScheduler, Request,
                                       Response)


def list_archs() -> int:
    """Print the per-arch serving capability table (no model is built:
    the probe reads the arch config alone)."""
    from repro.configs import ARCH_IDS
    from repro.serving.continuous import ContinuousBatchingEngine
    hdr = (f"{'arch':<28} {'modes':<32} {'state kinds':<16} "
           f"{'preempt':<8} {'share':<14} {'exactness'}")
    print(hdr)
    print("-" * len(hdr))
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        modes = ContinuousBatchingEngine.supported_modes(cfg)
        cont = modes["continuous"]
        share = ("window-phase" if cont["window_phase_keys"]
                 else ("yes" if cont["prefix_sharing"] else "no"))
        print(f"{arch:<28} "
              f"{'/'.join(m for m in MODES if modes[m]['supported']):<32} "
              f"{'+'.join(cont['state_kinds']):<16} "
              f"{'yes' if cont['preemptable'] else 'no':<8} "
              f"{share:<14} {cont['exactness']}")
    return 0


def synth_extra_inputs(cfg, rng) -> dict:
    """Deterministic synthetic non-token prefill inputs for one request:
    encoder frames for enc-dec archs, patch embeddings for vision archs
    (distinct per call — distinct extras never share pages)."""
    extra = {}
    if cfg.enc_dec:
        extra["frames"] = rng.normal(
            size=(cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)
    if cfg.num_patches:
        extra["patch_embeds"] = rng.normal(
            size=(cfg.num_patches, 1024)).astype(np.float32)
    return extra


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--list-archs", action="store_true",
                    help="print the per-arch serving capability table "
                         "(modes, state kinds, preemptable, prefix "
                         "sharing, exactness class) and exit")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="cut the arch to CPU-test widths (d_model 64); "
                         "--no-reduced serves it at its published widths")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mode", choices=MODES, default=None,
                    help="serving schedule (default: overlapped)")
    ap.add_argument("--blocking", action="store_true",
                    help="legacy alias for --mode blocking")
    ap.add_argument("--stage-depth", type=int, default=1,
                    help="overlapped mode: batches staged ahead of the "
                         "one being awaited")
    ap.add_argument("--capacity", type=int, default=4,
                    help="continuous mode: slot-table rows")
    ap.add_argument("--page-size", type=int, default=16,
                    help="continuous mode: KV-cache page size (tokens)")
    ap.add_argument("--inner-steps", type=int, default=4,
                    help="continuous mode: decode steps per micro-round")
    ap.add_argument("--prefix-sharing", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="continuous mode: refcounted prefix sharing + "
                         "copy-on-write over the paged pool")
    ap.add_argument("--batch-admission",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="continuous mode: batch same-bucket admissions "
                         "into one prefill call")
    ap.add_argument("--kernel-backend", choices=("jnp", "pallas"),
                    default=None,
                    help="paged-attention backend: 'jnp' gathers each "
                         "row's dense logical window per decode step (A/B "
                         "baseline), 'pallas' streams pages in place "
                         "through the fused kernels (compiled on a TPU, "
                         "interpret mode on the CPU).  Default: pallas on "
                         "a TPU, jnp elsewhere")
    ap.add_argument("--preserve-pristine", choices=("never", "reuse",
                                                    "always"),
                    default="reuse",
                    help="pristine-preserve policy for shared chains: "
                         "'reuse' copies a written pristine page only "
                         "after its chain recorded a sharing hit, "
                         "'always' is the PR-4 one-copy-per-admission "
                         "behaviour, 'never' disables preservation")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend a common system-prompt prefix of this "
                         "many tokens to every request (demo workload for "
                         "--prefix-sharing)")
    ap.add_argument("--priority", type=int, default=0, metavar="K",
                    help="continuous mode: mark every K-th request as "
                         "tier 0 (highest priority; admitted first, shed "
                         "last, preempts tier-1 rows under slot/page "
                         "pressure when --swap is on).  0 = single-tier "
                         "traffic (default)")
    ap.add_argument("--swap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="continuous mode: preemption via KV tiering — a "
                         "blocked higher-priority arrival swaps a lower-"
                         "priority victim's pages out to the host store "
                         "and restores them token-exactly when capacity "
                         "frees (--no-swap = admission waits instead).  "
                         "Every state kind swaps: attention and cross-"
                         "attention pages as blocks, SSM slot state as "
                         "fixed-width checkpoint records — SSM/hybrid and "
                         "encoder-decoder rows are ordinary victims")
    ap.add_argument("--max-backlog", type=int, default=None, metavar="N",
                    help="continuous mode: SLO backlog bound — when more "
                         "than N requests are queued, the lowest-priority "
                         "(then latest-deadline) queued work is shed with "
                         "an explicit REJECTED outcome instead of growing "
                         "the queue (default: unbounded)")
    ap.add_argument("--mesh", default=None, metavar="AxB",
                    help="device mesh spec, e.g. '1x8': shard the paged KV "
                         "pool and the fused decode along KV heads across "
                         "the 'model' axis (B devices); page tables, "
                         "free-list, prefix trie and refcounts stay "
                         "host-global, so admission/prefix-sharing/CoW/"
                         "preemption behave identically.  '1x1' is bitwise "
                         "token-exact with the default single-device path; "
                         "wider meshes are greedy token-exact.  Requires "
                         "A*B visible devices (e.g. XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8).  "
                         "Default: no mesh (single device)")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="continuous mode: arm crash safety — write the "
                         "durable request journal to DIR/journal.jsonl "
                         "(fsync'd write-ahead of every queue mutation) "
                         "and engine checkpoints to DIR/checkpoints/ "
                         "(default: no journal)")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                    help="continuous mode: checkpoint the full serving "
                         "state every K committed decode rounds (needs "
                         "--journal-dir; 0 = journal only, no checkpoints)")
    ap.add_argument("--crash-at-round", type=int, default=0, metavar="N",
                    help="continuous mode: SIGKILL this process at the "
                         "N-th dispatched decode round (FaultPlane crash "
                         "injection — no unwind, no flush; exit code 137)."
                         "  Pair with --journal-dir, then re-run with "
                         "--recover to demonstrate kill-and-restart "
                         "(default: 0 = never)")
    ap.add_argument("--recover", action="store_true",
                    help="recover from --journal-dir instead of submitting "
                         "synthetic requests: rebuild the engine from the "
                         "latest checkpoint, re-queue journalled-but-"
                         "unfinished work and replay rounds past the "
                         "checkpoint (token-exact for non-MoE archs), "
                         "then drain to completion")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run's "
                         "telemetry spans to PATH (enables the telemetry "
                         "plane)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus text-format metrics to PATH "
                         "(enables the telemetry plane)")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="print a one-line telemetry summary every N "
                         "scheduling steps (enables the telemetry plane; "
                         "0 = never)")
    return ap


def serve_mode(args) -> str:
    return args.mode or ("blocking" if args.blocking else "overlapped")


def build_stack(args, cfg: Optional[ArchConfig] = None
                ) -> Tuple[ArchConfig, MultiTenantScheduler]:
    """Model, engine and scheduler as ``args`` (from :func:`build_parser`)
    describe them; weights are random from ``PRNGKey(0)``.  ``cfg``
    replaces the ``--arch``/``--reduced`` lookup (a depth-cut config)."""
    mode = serve_mode(args)
    if cfg is None:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    params, _ = pp.split(build_model(cfg).init(jax.random.PRNGKey(0)))
    from repro.distributed.sharding import parse_mesh, serving_sharder
    sh = serving_sharder(parse_mesh(args.mesh)) if args.mesh else None
    engine = ServingEngine(cfg, params, sh=sh,
                           kernel_backend=args.kernel_backend)
    preserve = {"never": False, "reuse": True,
                "always": "always"}[args.preserve_pristine]
    crash_kw = {}
    if args.journal_dir:
        crash_kw = dict(
            journal=os.path.join(args.journal_dir, "journal.jsonl"),
            checkpoint_dir=os.path.join(args.journal_dir, "checkpoints"),
            checkpoint_every=args.checkpoint_every)
    if args.crash_at_round:
        from repro.distributed.fault import FaultPlane
        crash_kw["fault_plane"] = FaultPlane(
            crash_at_round=args.crash_at_round)
    sched = MultiTenantScheduler(
        engine, max_batch=args.max_batch,
        tenancy=TenancyConfig(1, args.tenants), mode=mode,
        stage_depth=args.stage_depth,
        preemption=args.swap, max_backlog=args.max_backlog,
        continuous=dict(capacity=args.capacity, page_size=args.page_size,
                        inner_steps=args.inner_steps,
                        prefix_sharing=args.prefix_sharing,
                        batch_admission=args.batch_admission,
                        preserve_pristine=preserve,
                        max_prompt_len=max(64, 2 * args.prompt_len
                                           + args.shared_prefix_len)),
        **crash_kw)
    return cfg, sched


def drain(sched: MultiTenantScheduler, late: Sequence[Request] = (),
          stats_every: int = 0) -> List[Response]:
    """Step the scheduler until nothing is pending.  ``late`` requests are
    submitted after the first step (so they land against full slots);
    ``stats_every`` prints a telemetry line every that many steps."""
    late = list(late)
    responses: List[Response] = []
    steps = 0
    while sched.pending() or late:
        r = sched.step()
        if r:
            responses.extend(r)
        steps += 1
        if late:                 # tier-0 burst lands against full slots
            for req in late:
                sched.submit(req)
            late = []
        if stats_every and steps % stats_every == 0:
            from repro.obs import TELEMETRY
            from repro.obs.export import stats_line
            print(stats_line(
                TELEMETRY,
                keys=("heartbeat.beats", "kv.pages_allocated",
                      "kv.free_pages", "swap.preemptions", "swap.restores",
                      "heartbeat.suspects"),
                step=steps, pending=sched.pending()))
    return responses


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.list_archs:
        return list_archs()
    mode = serve_mode(args)
    if args.journal_dir and mode != "continuous":
        # only the continuous collect loop journals ROUND_COMMIT/RETIRE; a
        # journal written under another mode would replay every completed
        # request as pending
        ap.error("--journal-dir requires --mode continuous")
    if not args.journal_dir and (args.recover or args.checkpoint_every):
        ap.error("--recover/--checkpoint-every need --journal-dir")

    from repro.obs import TELEMETRY
    obs_on = bool(args.trace_out or args.metrics_out or args.stats_every)
    if obs_on:
        # light the global plane before the stack is built so every layer
        # (engine, scheduler, pool, swap store, staging lanes) records
        TELEMETRY.enable()

    enable_compile_cache()
    cfg, sched = build_stack(args)

    if args.recover:
        s = sched.recover()
        print(f"recovered from checkpoint step={s.checkpoint_step}: "
              f"live={s.restored_live} swapped={s.restored_swapped} "
              f"requeued={s.requeued} "
              f"already_complete={len(s.already_complete)} "
              f"rounds_replayed={s.rounds_replayed} "
              f"tokens preserved={s.tokens_preserved} "
              f"replayed={s.tokens_replayed}")

    rng = np.random.default_rng(0)
    shared_prefix = rng.integers(1, cfg.vocab_size,
                                 args.shared_prefix_len).astype(np.int32)
    late: list = []         # tier-0 arrivals held back to land mid-flight
    for i in range(0 if args.recover else args.requests):
        tenant = f"tenant-{i % args.tenants}"
        prompt = rng.integers(1, cfg.vocab_size,
                              args.prompt_len).astype(np.int32)
        if args.shared_prefix_len:
            prompt = np.concatenate([shared_prefix, prompt])
        tier0 = args.priority > 0 and i % args.priority == args.priority - 1
        # per-tenant queues are FIFO within a tenant, so the high tier
        # rides its own interactive lane — otherwise a tier-0 arrival
        # queued behind its tenant's earlier tier-1 work is invisible to
        # the priority-aware admission (which only compares queue heads)
        if tier0:
            tenant += "-hi"
        req = Request(tenant, prompt,
                      max(4, args.new_tokens // 4) if tier0
                      else args.new_tokens,
                      priority=0 if tier0 else 1,
                      extra_inputs=synth_extra_inputs(cfg, rng) or None)
        # tier-0 requests arrive *after* the tier-1 work has filled the
        # slot table, so the demo exercises the preemption path instead of
        # just admitting the high tier first
        if tier0 and mode == "continuous":
            late.append(req)
        else:
            sched.submit(req)
    if not sched.pending():                   # all-tier-0 traffic: no hold
        for req in late:
            sched.submit(req)
        late = []

    # manual drain loop (same semantics as sched.drain()) so the periodic
    # stats line can fire between scheduling steps
    responses = drain(sched, late, args.stats_every)
    sched.close()
    n_done = sum(r.outcome == "completed" for r in responses)
    print(f"served {len(responses)} requests "
          f"(completed={n_done} "
          f"rejected={sum(r.outcome == 'rejected' for r in responses)} "
          f"failed={sum(r.outcome == 'failed' for r in responses)})")
    for t, rep in sorted(sched.utilization_report().items()):
        print(f"  {t}: requests={rep['requests']:.0f} "
              f"tokens={rep['tokens']:.0f} busy={rep['busy_s']*1e3:.0f}ms "
              f"share={rep['busy_share']*100:.1f}%")
    lat = [r.latency_s for r in responses if r.outcome == "completed"]
    if lat:
        print(f"latency p50={np.percentile(lat,50)*1e3:.0f}ms "
              f"p99={np.percentile(lat,99)*1e3:.0f}ms")
    from repro.core.pipeline import timeline_overlaps
    ov = timeline_overlaps(sched.timeline)
    print(f"schedule={mode} overlap_pairs={sum(ov)}/{len(ov)} "
          f"(staging of slot k+1 inside slot k's decode window)")
    if mode == "continuous":
        eng = sched.continuous_engine
        print(f"micro-rounds={eng.rounds} x {eng.inner_steps} steps, "
              f"slot occupancy={eng.occupancy()*100:.1f}%, "
              f"pages reused={eng.kv.pages_reused}/{eng.kv.pages_allocated}, "
              f"backend={eng.backend}, mesh={args.mesh or 'none'}")
        print(f"prefix sharing={'on' if eng.prefix_sharing else 'off'}: "
              f"pages allocated={eng.kv.pages_allocated} "
              f"shared={eng.kv.pages_shared} cow_forks={eng.kv.cow_forks} "
              f"pristine_forks={eng.kv.pristine_forks}; "
              f"prefill calls={eng.prefill_calls} "
              f"skipped={eng.prefill_skips} "
              f"(batch admission={'on' if eng.batch_admission else 'off'})")
        shed = sum(int(s["shed"]) for s in sched.stats.values())
        print(f"overload: preemption={'on' if args.swap else 'off'} "
              f"preemptions={eng.preemptions} restores={eng.restores} "
              f"shed={shed} heartbeat_suspects={sched.heartbeat_suspects}")
    if args.trace_out:
        from repro.obs.export import write_chrome_trace
        write_chrome_trace(TELEMETRY, args.trace_out)
        print(f"trace: {len(TELEMETRY.spans())} spans "
              f"({TELEMETRY.spans_opened} opened, "
              f"{TELEMETRY.spans_dropped} dropped) -> {args.trace_out}")
    if args.metrics_out:
        from repro.obs.export import write_metrics
        write_metrics(TELEMETRY, args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
