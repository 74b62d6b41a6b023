#!/usr/bin/env python3
"""Smoke run of the repository's two main paths on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the multi-device phases

One process, no children.  With no arguments it runs, on one chip:

* **serving** — ``internlm2-1.8b`` at its published widths (24 layers,
  d_model 2048, 16 heads / 8 KV heads, d_ff 8192, vocab 92544; random
  weights from ``PRNGKey(0)``) through ``repro.launch.serve``'s own
  ``build_stack``/``drain`` in continuous mode: 8 requests from 3 tenants,
  prompts of 256-1024 tokens, 32 new tokens each.  It then checks that the
  decode round's compiled program holds the fused kernel
  (``tpu_custom_call``) and compares one decode round's logits with the
  jnp-gather backend on the same chip;
* **risk** — the paper's aggregate risk analysis at its widths (1000 events
  per trial, 15 ELTs, a 2,000,000-event catalog, tables resident on the
  device), trials cut from 1,000,000 to 131,072, 2 tenants on one device in
  both transfer modes, each YLT checked against
  ``kernels.ref.aggregate_loss_chunked_ref`` run on the chip.

``--chips 4`` runs the risk pipeline over 4 devices x 2 tenants against
1 device x 2 tenants, and greedy serving on a ``1x4`` mesh (depth cut to 8
layers so a replicated and an unplaced weight copy fit one chip together)
against one device, token for token.

Data comes from ``--seed``.  The script exits non-zero, printing no result
line, when JAX finds no TPU, when the repository's ``src/`` is not beside
it, or when any phase fails.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

ARCH = "internlm2-1.8b"
PROMPT_LENS = (256, 512, 1024)
RISK_TRIALS = 131_072
# the fused kernel keeps softmax weights in float32 where the reference's
# f32 x bf16 contraction rounds them to bf16: their outputs agree to this
# share of the output's scale
KERNEL_RTOL = 2e-2
# that rounding, carried through 24 bf16 layers of random weights whose
# logits are nearly tied, can flip a greedy token, after which a row reads
# other inputs.  Rows whose tokens all matched must agree to this share of
# the reference logits' range, and at least this share of rows must match
LOGIT_RANGE_SHARE = 0.05
SAME_ROWS = 0.5
YLT_RTOL, YLT_ATOL = 1e-5, 1e-3
REF_SLAB = 8192                 # trials per reference call on the chip


class CompileClock:
    """Seconds JAX spent tracing, lowering, compiling or loading programs
    from the persistent cache while the block ran."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __enter__(self):
        from jax import monitoring
        self.seconds = 0.0
        self._t0 = time.perf_counter()

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.seconds += duration
        self._listen = listen
        monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listen)
        self.wall = time.perf_counter() - self._t0
        return False


def report(phase: str, clock: CompileClock, **fields) -> None:
    extra = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] compile_s={clock.seconds:.3f} "
          f"wall_s={clock.wall:.3f} {extra}".rstrip(), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serve_args(*, reduced: bool, backend: Optional[str], capacity: int,
               new_tokens: int, max_prompt: int, tenants: int,
               mesh: Optional[str] = None):
    from repro.launch import serve
    argv = ["--arch", ARCH, "--reduced" if reduced else "--no-reduced",
            "--mode", "continuous", "--tenants", str(tenants),
            "--capacity", str(capacity), "--max-batch", str(capacity),
            # serve sizes the page pool for prompts up to twice this
            "--prompt-len", str(max_prompt // 2),
            "--new-tokens", str(new_tokens)]
    if backend:
        argv += ["--kernel-backend", backend]
    if mesh:
        argv += ["--mesh", mesh]
    return serve.build_parser().parse_args(argv)


def make_requests(vocab: int, rng, n: int, tenants: int,
                  prompt_lens: Sequence[int], new_tokens: int) -> List[Any]:
    from repro.serving.multitenant import Request
    return [Request(f"tenant-{i % tenants}",
                    rng.integers(1, vocab, prompt_lens[i % len(prompt_lens)]
                                 ).astype(np.int32), new_tokens)
            for i in range(n)]


def serve_requests(args, reqs, cfg=None):
    """Drain ``reqs`` through serve's own stack and check every one
    completed with its tokens in the vocabulary; returns (continuous
    engine, tokens in request order)."""
    from repro.launch import serve
    cfg, sched = serve.build_stack(args, cfg=cfg)
    for r in reqs:
        sched.submit(r)
    responses = serve.drain(sched)
    sched.close()
    check(len(responses) == len(reqs),
          f"{len(responses)} responses for {len(reqs)} requests")
    check(all(r.outcome == "completed" for r in responses),
          f"outcomes {[r.outcome for r in responses]}")
    check(all(len(r.tokens) == q.max_new_tokens and
              ((0 <= r.tokens) & (r.tokens < cfg.vocab_size)).all()
              for r, q in zip(responses, reqs)), "token count or range")
    by_tenant: Dict[str, List[np.ndarray]] = {}
    for r in responses:
        by_tenant.setdefault(r.tenant, []).append(np.asarray(r.tokens))
    # per-tenant queues are FIFO, so each tenant's responses come back in
    # its submission order
    tokens = [by_tenant[r.tenant].pop(0) for r in reqs]
    return sched.continuous_engine, tokens


def round_hlo(eng) -> str:
    """Compiled text of the greedy decode-round program for ``eng``'s
    current slot table (the program ``dispatch_round`` runs)."""
    return eng._round_jit.lower(eng.params, eng.state,
                                steps=eng.inner_steps, all_greedy=True,
                                any_topk=False).compile().as_text()


def first_round(eng, reqs):
    """Admit ``reqs`` into ``eng``'s free slots and run one decode round:
    (tokens emitted, logits after the round), one row per request in
    ``reqs`` order."""
    check(all(eng.try_admit_batch(reqs)), "admission failed")
    slot = {id(s.req): i for i, s in enumerate(eng._slots) if s is not None}
    rows = [slot[id(r)] for r in reqs]
    h = eng.dispatch_round()
    eng.collect(h)
    return (np.asarray(h.emitted)[:, rows],
            np.asarray(eng.state["logits"][:, :eng.cfg.vocab_size])[rows])


def kernel_check(eng, seed: int):
    """The fused decode read against the dense-gather reference on
    ``eng``'s own first-layer pool, page table and positions, with random
    queries: (max abs difference, max abs reference output)."""
    import jax
    from repro.kernels.paged_attention import paged_attention_decode_pallas
    from repro.kernels.ref import paged_attention_decode_ref
    st, cfg = eng.state, eng.cfg
    pool = st["caches"][eng.kv.attn_subs[0]]
    k, v = pool["k"][0], pool["v"][0]
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (eng.capacity, cfg.num_heads, cfg.head_dim)
                          ).astype(k.dtype)
    pos = st["pos"] - 1                    # each row's newest position
    args = (q, k, v, st["pos_pool"], st["page_table"], pos)
    got = jax.jit(lambda *a: paged_attention_decode_pallas(
        *a, window=cfg.sliding_window))(*args)
    want = jax.jit(lambda q, k, v, pp, pt, p: paged_attention_decode_ref(
        q, k, v, pt, p, pos_pool=pp, window=cfg.sliding_window))(*args)
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def serving_phase(*, reduced: bool = False, seed: int = 0,
                  backend: Optional[str] = None, n_requests: int = 8,
                  tenants: int = 3,
                  prompt_lens: Sequence[int] = PROMPT_LENS,
                  new_tokens: int = 32) -> Dict[str, Any]:
    """Serve ``n_requests`` through continuous batching, then compare one
    decode round against the jnp-gather backend on the same weights."""
    import jax
    from repro.serving.continuous import ContinuousBatchingEngine

    from repro.configs import get_config
    rng = np.random.default_rng(seed)
    args = serve_args(reduced=reduced, backend=backend, capacity=n_requests,
                      new_tokens=new_tokens, max_prompt=max(prompt_lens),
                      tenants=tenants)
    vocab = (get_config(ARCH).reduced() if reduced
             else get_config(ARCH)).vocab_size
    reqs = make_requests(vocab, rng, n_requests, tenants, prompt_lens,
                         new_tokens)
    with CompileClock() as clock:
        eng, _ = serve_requests(args, reqs)
    cfg, engine = eng.cfg, eng.engine
    report("serve", clock, arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, heads=cfg.num_heads,
           kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
           requests=n_requests, tenants=tenants,
           prompt_lens="/".join(str(len(r.prompt)) for r in reqs),
           new_tokens=new_tokens, backend=eng.backend,
           pages=eng.kv.num_pages, page_size=eng.kv.page_size)

    on_tpu = jax.default_backend() == "tpu"
    with CompileClock() as clock:
        fused = "tpu_custom_call" in round_hlo(eng)
        if on_tpu:
            check(eng.backend == "pallas", f"backend {eng.backend} on TPU")
            check(fused, "no tpu_custom_call in the decode round")
        # one decode round on the same fresh rows, fused kernel vs the dense
        # jnp gather: the drained engine first, then (its pool released) a
        # second engine over the same weights
        cmp_reqs = make_requests(cfg.vocab_size, rng, n_requests, tenants,
                                 prompt_lens, new_tokens)
        kw = dict(capacity=eng.capacity, page_size=eng.page_size,
                  inner_steps=eng.inner_steps,
                  max_prompt_len=eng.max_prompt_len)
        backend = eng.backend
        tok_k, lg_k = first_round(eng, cmp_reqs)
        kern_diff, kern_scale = kernel_check(eng, seed)
        del eng
        gc.collect()
        ref = ContinuousBatchingEngine(engine, prefix_sharing=False,
                                       backend="jnp", **kw)
        tok_r, lg_r = first_round(ref, cmp_reqs)
        del ref
        gc.collect()
    check(np.isfinite(lg_k).all() and np.isfinite(lg_r).all(),
          "non-finite logits")
    diff = np.abs(lg_k - lg_r).max(axis=-1)                 # per request
    span = float(lg_r.max() - lg_r.min())
    agree = float((tok_k == tok_r).mean())
    # rows whose every emitted token matched read identical inputs, so
    # their logits differ by rounding alone; a diverged row's do not
    same = (tok_k == tok_r).all(axis=0)
    same_diff = float(diff[same].max()) if same.any() else float("nan")
    peak = None
    if on_tpu:
        peak = jax.devices()[0].memory_stats().get("peak_bytes_in_use")
    report("serve-check", clock, backend=f"{backend}-vs-jnp",
           tpu_custom_call=fused, round_steps=kw["inner_steps"],
           max_abs_logit_diff=float(diff.max()),
           max_abs_logit_diff_same_tokens=same_diff,
           rows_same_tokens=f"{int(same.sum())}/{same.size}",
           ref_logit_range=span, greedy_token_agreement=agree,
           kernel_max_abs_diff=kern_diff, kernel_ref_max_abs=kern_scale,
           peak_bytes_in_use=peak)
    check(kern_diff <= KERNEL_RTOL * kern_scale,
          f"decode kernel differs from the reference by {kern_diff} "
          f"(reference max {kern_scale})")
    check(same.mean() >= SAME_ROWS, f"{int(same.sum())} rows token-equal")
    check(same_diff <= LOGIT_RANGE_SHARE * span,
          f"logit diff {same_diff} over {LOGIT_RANGE_SHARE} of range {span}")
    return {"max_abs_logit_diff": float(diff.max()),
            "max_abs_logit_diff_same_tokens": same_diff,
            "greedy_agreement": agree, "kernel_max_abs_diff": kern_diff,
            "tpu_custom_call": fused, "peak_bytes_in_use": peak}


def mesh_serving_phase(*, mesh: str = "1x4", layers: Optional[int] = 8,
                       reduced: bool = False, seed: int = 0,
                       backend: Optional[str] = None, n_requests: int = 8,
                       tenants: int = 3,
                       prompt_lens: Sequence[int] = PROMPT_LENS,
                       new_tokens: int = 32) -> Dict[str, Any]:
    """Greedy serving on a ``mesh`` against one device, token for token."""
    from repro.configs import get_config
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    kw = dict(reduced=reduced, backend=backend, capacity=n_requests,
              new_tokens=new_tokens, max_prompt=max(prompt_lens),
              tenants=tenants)
    reqs = make_requests(cfg.vocab_size, np.random.default_rng(seed),
                         n_requests, tenants, prompt_lens, new_tokens)
    results = {}
    for name, m in ((f"mesh-{mesh}", mesh), ("single", None)):
        with CompileClock() as clock:
            eng, tokens = serve_requests(serve_args(mesh=m, **kw), reqs, cfg)
        spread = ""
        if m:
            # every device holds its own slice of KV heads (at full width
            # the heads split over the whole mesh); none holds the pool
            n_dev = int(np.prod([int(x) for x in m.split("x")]))
            split = eng.sh.extent("kv", cfg.num_kv_heads)
            check(reduced or split == n_dev,
                  f"KV heads split {split} ways on {n_dev} devices")
            for pool in eng.state["caches"].values():
                for arr in pool.values():
                    shards = arr.addressable_shards
                    devs = {s.device for s in shards}
                    check(len(devs) == n_dev,
                          f"pool on {len(devs)} devices, mesh has {n_dev}")
                    check(all(s.data.shape[3] == cfg.num_kv_heads // split
                              for s in shards), "pool not split on KV heads")
            shard = next(iter(eng.state["caches"].values()))["k"]
            spread = (f"{len(shard.addressable_shards)} shards of "
                      f"{shard.addressable_shards[0].data.shape}")
        report(f"serve-{name}", clock, layers=cfg.num_layers,
               d_model=cfg.d_model, kv_heads=cfg.num_kv_heads,
               backend=eng.backend, pool=spread or "one device")
        results[name] = tokens
        del eng
        gc.collect()
    a, b = results[f"mesh-{mesh}"], results["single"]
    exact = all(np.array_equal(x, y) for x, y in zip(a, b))
    print(f"[serve-mesh-vs-single] token_exact={exact} "
          f"rows={len(a)} tokens_per_row={new_tokens}", flush=True)
    check(exact, "mesh serving is not token-exact with one device")
    return {"token_exact": exact}


# ---------------------------------------------------------------------------
# risk
# ---------------------------------------------------------------------------
def reference_ylt(tables, chunk: int, slab: int = REF_SLAB) -> np.ndarray:
    """``aggregate_loss_chunked_ref`` on the default device, ``slab``
    trials per call (the padded event axis as ``kernels.ops`` pads it)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import aggregate_loss_chunked_ref
    K = tables.yet.shape[1]
    chunk = min(chunk, K)
    pad = (-K) % chunk
    fn = jax.jit(aggregate_loss_chunked_ref, static_argnames=("chunk",))
    elt = jnp.asarray(tables.elt_losses)
    ret, lim = jnp.asarray(tables.occ_ret), jnp.asarray(tables.occ_lim)
    out = []
    for s in range(0, tables.num_trials, slab):
        ids = np.pad(tables.yet[s:s + slab], ((0, 0), (0, pad)))
        out.append(np.asarray(fn(jnp.asarray(ids), elt, ret, lim,
                                 np.float32(tables.agg_ret),
                                 np.float32(tables.agg_lim), chunk=chunk)))
    return np.concatenate(out)


def risk_tables(*, reduced: bool, trials: Optional[int], seed: int,
                tenants: int):
    from repro.configs.risk_app import CONFIG as PAPER
    from repro.launch import risk
    from repro.risk.tables import generate
    cfg = risk.build_config(reduced, trials, tenants)
    print(f"[risk-config] events_per_trial={cfg.events_per_trial} "
          f"elts={cfg.num_elts} catalog={cfg.event_catalog} "
          f"trials={cfg.num_trials} (cut from {PAPER.num_trials}) "
          f"tenants_per_device={cfg.tenants_per_device} "
          f"chunk_events={cfg.chunk_events}", flush=True)
    t0 = time.perf_counter()
    tables = generate(cfg, seed)
    print(f"[risk-data] generate_s={time.perf_counter() - t0:.3f} "
          f"yet_bytes={tables.yet.nbytes} elt_bytes={tables.elt_losses.nbytes}",
          flush=True)
    return cfg, tables


def risk_phase(*, reduced: bool = False, trials: Optional[int] = RISK_TRIALS,
               seed: int = 0, tenants: int = 2,
               modes: Sequence[str] = ("sequential", "concurrent")
               ) -> Dict[str, Any]:
    """Tenant-chunked analysis on one device in each transfer mode, each
    YLT checked against the jnp reference on the same device."""
    import jax
    from repro.kernels import ops as kops
    from repro.risk.analysis import AggregateRiskAnalysis
    cfg, tables = risk_tables(reduced=reduced, trials=trials, seed=seed,
                              tenants=tenants)
    with CompileClock() as clock:
        want = reference_ylt(tables, cfg.chunk_events)
    report("risk-reference", clock, trials=tables.num_trials)
    out = {}
    for mode in modes:
        mcfg = dataclasses.replace(cfg, transfer_mode=mode)
        with CompileClock() as clock:
            rep = AggregateRiskAnalysis(
                mcfg, devices=jax.devices()[:1]).run_tenant_chunked(tables)
        err = float(np.max(np.abs(rep.ylt - want) /
                           np.maximum(np.abs(want), 1e-30)))
        report(f"risk-{mode}", clock, devices=1, tenants=tenants,
               kernel="pallas" if kops.pallas_enabled() else "jnp",
               run_wall_s=f"{rep.wall_s:.3f}",
               trials_per_s=f"{tables.num_trials / rep.wall_s:.1f}",
               max_rel_err_vs_ref=err, ylt_mean=float(rep.ylt.mean()))
        np.testing.assert_allclose(rep.ylt, want, rtol=YLT_RTOL,
                                   atol=YLT_ATOL)
        out[mode] = err
    return out


def risk_multi_device_phase(*, reduced: bool = False,
                            trials: Optional[int] = RISK_TRIALS,
                            seed: int = 0, tenants: int = 2
                            ) -> Dict[str, Any]:
    """Every device x ``tenants`` against 1 device x ``tenants``."""
    import jax
    from repro.risk.analysis import AggregateRiskAnalysis
    cfg, tables = risk_tables(reduced=reduced, trials=trials, seed=seed,
                              tenants=tenants)
    ylts = {}
    for devs in (jax.devices(), jax.devices()[:1]):
        with CompileClock() as clock:
            rep = AggregateRiskAnalysis(
                cfg, devices=devs).run_tenant_chunked(tables)
        report(f"risk-{len(devs)}dev", clock, devices=len(devs),
               tenants_per_device=tenants, run_wall_s=f"{rep.wall_s:.3f}",
               ylt_mean=float(rep.ylt.mean()))
        ylts[len(devs)] = rep.ylt
    n = len(jax.devices())
    equal = bool(np.array_equal(ylts[n], ylts[1]))
    with CompileClock() as clock:
        want = reference_ylt(tables, cfg.chunk_events)
    report("risk-reference", clock, trials=tables.num_trials)
    print(f"[risk-{n}dev-vs-1dev] ylt_equal={equal}", flush=True)
    check(equal, f"YLT over {n} devices differs from 1 device")
    np.testing.assert_allclose(ylts[n], want, rtol=YLT_RTOL, atol=YLT_ATOL)
    return {"ylt_equal": equal}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: both main paths on one chip; 4: only the "
                         "multi-device phases, against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        serving_phase(seed=args.seed)
        gc.collect()
        risk_phase(seed=args.seed)
    else:
        risk_multi_device_phase(seed=args.seed)
        gc.collect()
        mesh_serving_phase(mesh=f"1x{args.chips}", seed=args.seed)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
