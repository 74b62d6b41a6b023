"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 50 --ckpt-dir /tmp/ckpt

Wires together: config -> model bundle -> sharded init -> prefetch feed ->
supervised step loop with checkpoint/restart (distributed.fault) and
straggler-aware staging.  On this CPU container use --reduced; on a real
cluster drop it and pass --mesh pod/multipod.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.data.tokens import DataConfig, PrefetchFeed, synth_batch
from repro.distributed import checkpoint as ckpt
from repro.distributed.fault import HeartbeatMonitor, StragglerDetector
from repro.distributed.sharding import Sharder, null_sharder, param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pp
from repro.models.model import build_model
from repro.training.optimizer import make_optimizer
from repro.training.train_loop import build_train_step, init_train_state


def make_state_and_step(cfg, mesh=None, seed: int = 0):
    sh = (Sharder(mesh, fsdp=cfg.fsdp, seq_shard=cfg.fsdp)
          if mesh is not None else null_sharder())
    bundle = build_model(cfg)
    opt = make_optimizer(cfg)
    boxed = bundle.init(jax.random.PRNGKey(seed))
    params, axes = pp.split(boxed)
    if mesh is not None:
        shards = param_shardings(sh, axes, jax.eval_shape(lambda: params))
        params = jax.tree.map(
            lambda v, s: jax.device_put(v, s) if s is not None else v,
            params, shards)
    state = init_train_state(bundle, opt, params)
    step_fn = jax.jit(build_train_step(bundle, sh, opt), donate_argnums=(0,))
    return bundle, state, step_fn, sh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle, state, step_fn, sh = make_state_and_step(cfg)
    n_params = pp.count_params(state["params"])
    print(f"arch={cfg.name} params={n_params:,}")

    dc = DataConfig(args.batch, args.seq, cfg.vocab_size)
    feed = PrefetchFeed(dc, cfg)
    monitor = HeartbeatMonitor(timeout_s=600)
    detector = StragglerDetector()

    losses = []
    t_start = time.perf_counter()
    for i in range(args.steps):
        t0 = time.perf_counter()
        batch = next(feed)
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor.beat()
        detector.update({0: time.perf_counter() - t0})
        if args.ckpt_dir and (i + 1) % args.save_every == 0:
            ckpt.save(args.ckpt_dir, i + 1, state)
        if (i + 1) % args.log_every == 0:
            dt = time.perf_counter() - t0
            print(f"step {i+1:4d} loss {loss:.4f} "
                  f"aux {float(metrics['aux']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f} ms")
    feed.close()
    wall = time.perf_counter() - t_start
    print(f"done: {args.steps} steps in {wall:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert np.isfinite(losses).all(), "NaN/Inf loss"
    return 0


if __name__ == "__main__":
    sys.exit(main())
