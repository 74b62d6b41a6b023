"""Serving engine: jit'd prefill + decode with sampling.

The engine owns compiled step functions for one model on one device/mesh;
multi-tenant request scheduling (several tenants sharing the accelerator,
the paper's "multiple applications on one pGPU") sits above it in
:mod:`repro.serving.multitenant`.

Two generation paths share the same sampling semantics:

* :meth:`ServingEngine.generate` — the host-blocking reference loop: one
  jitted decode call per token, sampling on the host between calls.  Kept
  as the A/B baseline and the semantic oracle for the scanned path.
* :meth:`ServingEngine.dispatch` / :meth:`ServingEngine.await_result` — the
  split halves.  ``dispatch`` enqueues the jitted prefill plus a single
  on-device ``lax.scan`` decode loop (sampling folded into the scanned
  step, so the host never round-trips per token) and returns a
  :class:`PendingGeneration` handle *without blocking*; ``await_result``
  blocks on the handle and materialises tokens + prefill/decode timings.
  Between the two calls the host is free — that gap is where the
  multi-tenant scheduler assembles and stages the next tenant's batch
  underneath this tenant's on-device decode (the paper's transfer/compute
  overlap applied to serving).

Both paths draw sampling keys identically (``PRNGKey(seed)`` for the first
token, then ``fold_in(key, step)`` per decode step), so for a fixed seed
they are token-for-token exchangeable — ``tests/test_serving_overlap.py``
locks that in across architectures.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.distributed.sharding import Sharder, null_sharder
from repro.kernels.ops import default_kernel_backend
from repro.models.model import ModelBundle, build_model
from repro.obs.telemetry import Telemetry, get_telemetry


def sample_rows(logits: jax.Array, temps: jax.Array, topks: jax.Array,
                keys: jax.Array, *, all_greedy: bool = False,
                any_topk: bool = True) -> jax.Array:
    """Per-row sampling: greedy where ``temps <= 0``, else temperature
    (optionally top-k truncated) categorical with a *per-row* PRNG key.

    This is the shared sampling mechanism of the per-request paths: the
    split engine threads (temps, topks, keys) through the ``lax.scan``
    decode-loop carry (see :meth:`ServingEngine.dispatch`), and the
    continuous-batching engine threads the same triple through its
    persistent slot-table carry — one sampler, two schedulers.

    logits: (B, V); temps: (B,) float; topks: (B,) int (0 disables top-k);
    keys: (B, 2) uint32 PRNG keys.  Greedy rows ignore temperature and keys
    entirely, so they stay token-exact with the host-blocking ``generate``
    loop regardless of their neighbours' sampling params.

    ``all_greedy`` / ``any_topk`` are *static* strength hints the caller
    derives on the host from the live rows (the row-wise masks make them
    semantics-preserving): an all-greedy step is a bare argmax — the
    vocab-wide sort and categorical draw would otherwise dominate a small
    model's decode step — and ``any_topk=False`` skips the sort.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if all_greedy:
        return greedy
    scaled = logits
    if any_topk:
        v = logits.shape[-1]
        srt = jnp.sort(logits, axis=-1)                   # ascending
        kidx = jnp.clip(v - topks, 0, v - 1).astype(jnp.int32)
        thresh = jnp.take_along_axis(srt, kidx[:, None], axis=-1)
        keep = (topks[:, None] <= 0) | (logits >= thresh)
        scaled = jnp.where(keep, logits, -jnp.inf)
    scaled = scaled / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def resolve_extra_inputs(cfg: ArchConfig, req: Any) -> Dict[str, np.ndarray]:
    """Per-request non-token prefill inputs (encoder frames, vision patch
    embeds), resolved from ``req.extra_inputs`` with per-arch defaults.

    Encoder-decoder archs cannot prefill without ``frames``, so a request
    that carries none gets deterministic zero frames — the *same* default
    on every path (blocking batch build, continuous admission), which keeps
    the A/B token-exactness contract intact for requests that never set
    extras.  Arrays are per-request (no batch axis); batching paths stack
    them."""
    extra = dict(getattr(req, "extra_inputs", None) or {})
    if cfg.enc_dec and "frames" not in extra:
        extra["frames"] = np.zeros((cfg.encoder_seq_len, cfg.d_model),
                                   np.float32)
    return extra


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, steps)
    prefill_s: float
    decode_s: float
    steps: int

    @property
    def tokens_per_s(self) -> float:
        return self.tokens.size / max(self.decode_s, 1e-9)


@dataclasses.dataclass
class PendingGeneration:
    """Handle for an in-flight generation (prefill + scanned decode both
    enqueued on the device; nothing host-blocking held here).

    ``tokens`` is the (B, steps) device array the scan will fill;
    ``prefill_logits`` the prefill output, kept so :meth:`ServingEngine.
    await_result` can split the ready-time into prefill/decode phases.
    Timestamps are absolute ``perf_counter`` values.
    """
    tokens: jax.Array
    prefill_logits: jax.Array
    steps: int
    t_start: float                # dispatch() entry
    t_dispatched: float           # dispatch() return (host enqueue cost end)

    def ready(self) -> bool:
        """Non-blocking probe: has the scanned decode finished?  Conservative
        for outputs without an ``is_ready`` probe (duck-typed stand-ins):
        reports False rather than claiming a still-running decode is done."""
        is_ready = getattr(self.tokens, "is_ready", None)
        return bool(is_ready()) if is_ready is not None else False


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params: Any,
                 sh: Optional[Sharder] = None, temperature: float = 0.0,
                 kernel_backend: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None):
        self.cfg = cfg
        self.tel = get_telemetry(telemetry)
        self.bundle: ModelBundle = build_model(cfg)
        self.sh = sh or null_sharder()
        if self.sh.mesh is not None:
            # commit the weights onto the mesh replicated (serving shards
            # activations/KV along heads, never the weights) so every jit
            # sees consistently-placed inputs
            params = jax.tree.map(
                lambda a: self.sh.place(a, (None,) * jnp.ndim(a)), params)
        self.params = params
        self.temperature = temperature
        # default paged-attention backend for serving layers built on this
        # engine ("jnp" dense gather | "pallas" fused page-streaming
        # kernels; unset = the platform's, pallas on a TPU); the engine's
        # own dense ring-cache paths are unaffected, but
        # ContinuousBatchingEngine inherits this unless overridden
        self.kernel_backend = kernel_backend or default_kernel_backend()
        self.prefill_traces = 0     # compiles (one per (batch, seq) shape)
        self.prefill_calls = 0      # host invocations

        def prefill_fn(p, b):
            self.prefill_traces += 1     # python side effect: trace time only
            self.tel.count("trace.engine_prefill")
            return self.bundle.prefill_fn(p, b, self.sh)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(
            lambda p, t, c, i: self.bundle.decode_fn(p, t, c, i, self.sh))

        def decode_loop(params, logits0, caches, idx, temp, key,
                        *, steps: int, greedy: bool):
            self.tel.count("trace.engine_decode")   # trace time only
            # sampling folded into the scanned step: token i is sampled from
            # logits i with key i, then decoded to produce logits i+1, and
            # key i+1 = fold_in(key i, i) — the exact key/logits schedule of
            # the host loop in generate(), so the two paths are token-exact.
            def sample(logits, key):
                if greedy:
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return jax.random.categorical(key, logits / temp,
                                              axis=-1).astype(jnp.int32)

            def step(carry, i):
                logits, caches, key = carry
                tok = sample(logits, key)
                new_logits, new_caches = self.bundle.decode_fn(
                    params, tok[:, None], caches, idx + i, self.sh)
                return (new_logits, new_caches,
                        jax.random.fold_in(key, i)), tok

            (_, _, _), toks = jax.lax.scan(
                step, (logits0, caches, key),
                jnp.arange(steps, dtype=jnp.int32))
            return toks.T                      # (steps, B) -> (B, steps)

        self._decode_loop = jax.jit(decode_loop,
                                    static_argnames=("steps", "greedy"))

        def decode_loop_rows(params, logits0, caches, idx, temps, topks,
                             keys, *, steps: int, all_greedy: bool,
                             any_topk: bool):
            self.tel.count("trace.engine_decode_rows")   # trace time only
            # per-request sampling params ride the scan carry: each row keeps
            # its own (temperature, top_k, key), same key/logits schedule as
            # the scalar path so greedy rows stay token-exact with generate()
            def step(carry, i):
                logits, caches, keys = carry
                tok = sample_rows(logits, temps, topks, keys,
                                  all_greedy=all_greedy, any_topk=any_topk)
                new_logits, new_caches = self.bundle.decode_fn(
                    params, tok[:, None], caches, idx + i, self.sh)
                keys = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
                return (new_logits, new_caches, keys), tok

            (_, _, _), toks = jax.lax.scan(
                step, (logits0, caches, keys),
                jnp.arange(steps, dtype=jnp.int32))
            return toks.T

        self._decode_loop_rows = jax.jit(
            decode_loop_rows,
            static_argnames=("steps", "all_greedy", "any_topk"))
        self.decode_steps = 0       # scanned decode steps enqueued (benchmarks)

    # ------------------------------------------------------------------
    def state_kinds(self):
        """The per-request state kinds this arch's serving rows carry
        (attention KV pages / cross-attention pages / SSM records) — the
        capability probe :meth:`repro.serving.continuous.
        ContinuousBatchingEngine.supported_modes` and ``launch/serve.py
        --list-archs`` are built on."""
        from repro.serving.kvcache import state_kinds
        return state_kinds(self.cfg)

    # ------------------------------------------------------------------
    def prefill(self, batch: Dict[str, Any]):
        """Counted, jit-compiled prefill shared by both generation paths
        (and by admission layers above the engine): returns (last-token
        logits, caches, cache index).  One compile per (batch, seq) shape.
        Prefill rows are bitwise independent of their batch neighbours, so
        callers may batch several requests' padded prompts into one call and
        slice the rows back out — the contract the continuous engine's
        batched admission prefill is built on (it keeps its own jit so its
        per-engine trace counters stay isolated).  The dense per-bucket
        caches returned here feed :func:`repro.serving.kvcache.
        paged_scatter` during paged admission — with ``kernel_backend=
        "pallas"`` the scatter lands page-granularly in the allocated pages
        (no dense intermediate hop), which is the compute side of the fused
        prefill-scatter pipeline."""
        self.prefill_calls += 1
        if not self.tel.enabled:
            return self._prefill(self.params, batch)
        with self.tel.span("engine.prefill",
                           batch=int(batch["tokens"].shape[0]),
                           seq=int(batch["tokens"].shape[1])):
            out = self._prefill(self.params, batch)
        self.tel.count("engine.prefill_calls")
        return out

    # ------------------------------------------------------------------
    def _sample(self, logits: jax.Array, key) -> jax.Array:
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.temperature,
                                      axis=-1).astype(jnp.int32)

    def _make_batch(self, prompts: np.ndarray,
                    extra_inputs: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        return batch

    # ------------------------------------------------------------------
    # Blocking reference path (one jitted decode call per token)
    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new_tokens: int = 16,
                 extra_inputs: Optional[Dict[str, Any]] = None,
                 seed: int = 0) -> GenerationResult:
        """prompts: (B, S) int32.  Greedy/temperature sampling."""
        batch = self._make_batch(prompts, extra_inputs)
        self.decode_steps += int(max_new_tokens)
        t0 = time.perf_counter()
        logits, caches, idx = self.prefill(batch)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0

        key = jax.random.PRNGKey(seed)
        out = []
        t0 = time.perf_counter()
        tok = self._sample(logits, key)
        for step in range(max_new_tokens):
            out.append(np.asarray(tok))
            logits, caches = self._decode(self.params, tok[:, None], caches,
                                          idx + step)
            key = jax.random.fold_in(key, step)
            tok = self._sample(logits, key)
        jax.block_until_ready(logits)
        t_done = time.perf_counter()
        decode_s = t_done - t0
        if self.tel.enabled:
            self.tel.record_span("engine.generate",
                                 t_done - prefill_s - decode_s, t_done,
                                 batch=int(prompts.shape[0]),
                                 steps=int(max_new_tokens))
            self.tel.count("engine.decode_steps", int(max_new_tokens))
        return GenerationResult(np.stack(out, axis=1), prefill_s, decode_s,
                                max_new_tokens)

    # ------------------------------------------------------------------
    # Split path: dispatch (non-blocking enqueue) / await (materialise)
    # ------------------------------------------------------------------
    def dispatch(self, prompts: np.ndarray, max_new_tokens: int = 16,
                 extra_inputs: Optional[Dict[str, Any]] = None,
                 seed: int = 0,
                 temperatures: Optional[Any] = None,
                 top_ks: Optional[Any] = None,
                 seeds: Optional[Any] = None) -> PendingGeneration:
        """Enqueue prefill + the full on-device decode loop; never blocks on
        device results, so the caller can stage other work under it.

        ``temperatures``/``top_ks``/``seeds`` (each (B,), any one optional)
        switch the scanned sampler to per-request params threaded through the
        scan carry via :func:`sample_rows`; left as None, the engine-level
        scalar path runs (token-exact with ``generate``, same key schedule).
        """
        if not self.tel.enabled:
            return self._dispatch_inner(prompts, max_new_tokens,
                                        extra_inputs, seed, temperatures,
                                        top_ks, seeds)
        with self.tel.span("engine.dispatch", batch=int(prompts.shape[0]),
                           steps=int(max_new_tokens)):
            return self._dispatch_inner(prompts, max_new_tokens,
                                        extra_inputs, seed, temperatures,
                                        top_ks, seeds)

    def _dispatch_inner(self, prompts, max_new_tokens, extra_inputs, seed,
                        temperatures, top_ks, seeds) -> PendingGeneration:
        batch = self._make_batch(prompts, extra_inputs)
        t_start = time.perf_counter()
        logits, caches, idx = self.prefill(batch)
        self.decode_steps += int(max_new_tokens)
        self.tel.count("engine.decode_steps", int(max_new_tokens))
        if temperatures is not None or top_ks is not None or seeds is not None:
            b = prompts.shape[0]
            temps = np.full(b, self.temperature, np.float32) \
                if temperatures is None else np.asarray(temperatures, np.float32)
            topks = np.zeros(b, np.int32) if top_ks is None \
                else np.asarray(top_ks, np.int32)
            seed_arr = np.full(b, seed, np.int64) if seeds is None \
                else np.asarray(seeds)
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seed_arr])
            toks = self._decode_loop_rows(
                self.params, logits, caches, idx, jnp.asarray(temps),
                jnp.asarray(topks), keys, steps=int(max_new_tokens),
                all_greedy=bool((temps <= 0).all()),
                any_topk=bool((topks > 0).any()))
            return PendingGeneration(toks, logits, int(max_new_tokens),
                                     t_start, time.perf_counter())
        # temperature is passed unclamped: greedy is static, so the
        # logits/temp division is never traced when temperature <= 0
        toks = self._decode_loop(
            self.params, logits, caches, idx,
            jnp.float32(self.temperature), jax.random.PRNGKey(seed),
            steps=int(max_new_tokens), greedy=self.temperature <= 0.0)
        return PendingGeneration(toks, logits, int(max_new_tokens),
                                 t_start, time.perf_counter())

    def await_result(self, handle: PendingGeneration) -> GenerationResult:
        """Block until the handle's generation is device-complete and return
        the materialised tokens.  ``prefill_s``/``decode_s`` are time-to-
        ready from dispatch entry: with host work interleaved between
        dispatch and await they measure pipeline latency, not exclusive
        device occupancy (the scheduler's timeline carries the honest
        per-window stamps)."""
        with self.tel.span("engine.await", steps=handle.steps):
            jax.block_until_ready(handle.prefill_logits)
            t_prefill = time.perf_counter()
            tokens = np.asarray(handle.tokens)  # blocks on the scanned decode
            t_done = time.perf_counter()
        return GenerationResult(tokens, t_prefill - handle.t_start,
                                t_done - t_prefill, handle.steps)
