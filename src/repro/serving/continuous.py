"""Continuous batching over a persistent slot table (paged KV-cache decode).

The slot-based scheduler serves one tenant batch at a time: the device runs
that batch's scanned decode to completion, padded rows and all, before the
next tenant's batch starts.  :class:`ContinuousBatchingEngine` instead keeps
a fixed-capacity *slot table* resident on the device and interleaves three
events per outer step, the serving analogue of the paper's fine-grained
multi-tenant sharing:

* **admission** — queued requests are prefilled at their (page-aligned)
  prompt bucket, their KV written into :class:`repro.serving.kvcache.
  PagedKVCache` pages, and their sampling state (per-request temperature /
  top-k / PRNG key, last logits, position, remaining budget) scattered into
  free slot rows.  Same-bucket admissions are *batched* into one prefill
  call (width padded to a power of two, so admission compiles once per
  (bucket, width tier) instead of once per request), and with
  ``prefix_sharing`` each request's longest chain of already-registered
  full-prefix blocks is mapped onto existing pages instead of fresh ones —
  a request whose whole padded prompt is registered (and whose prefill
  logits are still cached) skips its prefill call entirely;
* **one decode micro-round** — a single jitted ``lax.scan`` of
  ``inner_steps`` masked decode steps over *all* capacity rows.  The step is
  shape-stable (paged gather/scatter, fixed capacity), so ragged
  ``max_new_tokens`` mixes and mixed prompt buckets never retrace it: one
  compile per batch capacity, plus one prefill/admission compile per prompt
  bucket (``decode_traces`` / ``admit_traces`` count them for the tests);
* **retirement** — rows whose token budget ran out are collected on the
  host, their pages' refcounts dropped (a page returns to the free list only
  when its last reader retires; trie-registered pristine pages linger as
  evictable cache), their slots freed for the next admission.

Rows are masked, not compacted: an inactive row samples into the void (its
emission is dropped), writes its K/V to the reserved TRASH page and keeps
its SSM state frozen, so retirement costs no reshape or recompile — that is
the "masked fixed-step scan with early-exit accounting" deferred from PR 2.

Copy-on-write rides the dispatch path: decode writes land at ``pos % ring``,
so the blocks a round will write are known on the host before the round's
jit runs.  :meth:`ContinuousBatchingEngine.dispatch_round` resolves each of
them through :meth:`repro.serving.kvcache.PagedKVCache.note_write` — a
shared page is forked (one jitted page-copy + page-table remap per fork)
before any row can write into it, so the round's scan itself never needs
refcounts and stays one compile per (capacity, sampling tier).

The paged-pool state pytree is *donated* to the round / admission / CoW
jits (``donate_argnums``): XLA updates the pools in place instead of copying
the whole pool every micro-round, and the tests pin that down by checking
the old state buffers are deleted after a round.

``backend`` selects how the round's jit reads the paged pool: ``"jnp"``
gathers each row's full logical window into a dense ``[C, bucket, Hkv, D]``
tensor per decode step (the PR-3 path, kept as the A/B baseline and
numerics oracle), ``"pallas"`` streams page-sized KV blocks in place
through the fused paged-attention kernel (page-table indexing inside the
kernel grid, online softmax across pages — O(live pages) bytes per round
instead of O(capacity x bucket)) and scatters admission KV page-granularly
(see :mod:`repro.kernels.paged_attention`).  Both backends share every
other part of the engine — allocator, CoW, donation, compile-count
contract — and greedy decode is token-exact across them
(``tests/test_paged_attention.py``).

Compile-count contract: one decode-round trace per (capacity, sampling
tier); one admission-scatter trace per (prompt bucket, ring); one prefill
trace per (prompt bucket, power-of-two admission width); one trace each for
the CoW page-copy and the skip-prefill admission variant (per page-table
width).  ``decode_traces`` / ``admit_traces`` / ``prefill_traces`` /
``admit_skip_traces`` count them for the tests.

Greedy token-exactness: an admitted request decodes through exactly the same
prefill (same left-padded bucket prompt; batched prefill rows are
bitwise row-independent) and per-token math (see
:func:`repro.serving.kvcache.paged_attention_decode`) as
``ServingEngine.generate`` on that padded prompt, with the same
``PRNGKey(seed)`` / ``fold_in(key, local_step)`` schedule — so each row's
tokens match the blocking engine row-for-row, independent of what its
neighbours in the slot table are doing (``tests/test_continuous.py``).
Prefix sharing preserves this bit-for-bit: a block is shared only when the
whole padded prompt up to its end is byte-identical (so the page already
holds exactly what this request's prefill would have written), forks copy
pages before the first divergent write, and cached admission logits are the
stored output of the identical earlier prefill.

Preemption (overload survival, PR 6): a live row can be *swapped out* —
its page blocks and entire per-slot decode state snapshotted to a
:class:`repro.serving.swap.HostSwapStore`, its pages freed through the
ordinary allocator accounting (shared prefix pages stay under their other
readers; only the victim's private suffix is uniquely host-held), and its
slot vacated for a higher-priority admission.  :meth:`ContinuousBatching
Engine.try_restore` later re-admits it: still-registered unwritten prefix
blocks are re-shared straight from the trie, everything else stages back
through the swap store's sequential :class:`repro.core.transfer.
StagingEngine` (prefetched ahead of re-admission), and the slot's scalars
(pos / remaining / lstep / PRNG key / last logits) are rebuilt bitwise — so
the resumed decode is token-exact with an uninterrupted run.  Preemption
requires a quiesced engine (no round in flight) — the scheduler
force-collects first.

State kinds (PR 9): every arch in ``configs/`` serves continuously.  The
slot table's per-request state decomposes into the kinds registered by
:func:`repro.serving.kvcache.state_kinds` — ``attn`` (the paged KV above,
bitwise-unchanged for pure-attention archs), ``cross`` (encoder-decoder
cross-attention KV, paged into the pool's separate per-request cross space:
written once at admission from the batched prefill, gathered read-only by
every decode step, snapshot/restored verbatim on preemption) and ``ssm``
(slot-table SSM state, checkpointed as fixed-width records by
:func:`repro.models.ssm.checkpoint_slot_state` on swap-out and scattered
back on restore).  ``can_preempt`` derives from the kinds — every kind is
swappable, so any arch with swap enabled preempts, SSM/hybrid rows
included.  Sliding-window archs participate in prefix sharing through
window-phase chain keys (see :meth:`repro.serving.kvcache.PagedKVCache.
chain_keys`).  The skip-prefill fast path stays gated to pure-attention
archs: it is the one admission variant that must rebuild *every* per-slot
state from pages plus cached logits alone.  :meth:`ContinuousBatchingEngine.
supported_modes` is the public capability probe per arch
(``launch/serve.py --list-archs``).

MoE routing couples rows through expert capacity, so MoE archs run
continuously but are only *statistically* exchangeable with the blocking
engine, not bitwise — batched admission prefill and prefix sharing sit
inside the same caveat (expert-capacity routing couples prefill rows, so a
shared page holds *a* valid prefill of its chain, not necessarily the one a
solo prefill of this request would produce).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ATTN, MOE, NONE, ArchConfig
from repro.distributed.fault import InjectedFault
from repro.kernels.ops import default_kernel_backend, resolve_interpret
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_cross_attention, apply_embedding,
                                 apply_mlp, apply_rmsnorm, apply_unembed,
                                 pad_vocab)
from repro.obs.telemetry import Telemetry, get_telemetry
from repro.serving.engine import (ServingEngine, resolve_extra_inputs,
                                  sample_rows)
from repro.serving.kvcache import (BACKENDS, POS_SENTINEL, PagedKVCache,
                                   paged_attention_decode, paged_scatter,
                                   ssm_subs, state_kinds)
from repro.serving.swap import HostSwapStore, SwapRecord


@dataclasses.dataclass
class _Slot:
    """Host-side record of one occupied slot-table row."""
    req: Any                       # duck-typed: .prompt/.max_new_tokens/...
    target: int
    temp: float                    # resolved sampling params, mirrored on
    top_k: int                     # the host so dispatch_round can pick the
    bucket: int = 0                # static sampling tier
    ring: int = 0
    planned: int = 0               # decode steps already dispatched (the
    tokens: List[int] = dataclasses.field(  # CoW write scan runs at dispatch)
        default_factory=list)
    priority: int = 1              # 0 = highest; victims are picked among
    preemptions: int = 0           # strictly lower tiers only
    chain_keys: List[bytes] = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None  # wall stamp of the first collected token


@dataclasses.dataclass
class RoundHandle:
    """One dispatched (not yet collected) decode micro-round."""
    emitted: jax.Array             # (steps, C) int32, -1 where row inactive
    act: jax.Array                 # (steps, C) bool
    steps: int
    t_start: float
    t_dispatched: float
    rnd: int = -1                  # round ordinal, for the round-span event

    def ready(self) -> bool:
        """Non-blocking probe: has the round's device work finished?
        Conservative (False) for duck-typed stand-ins without a probe."""
        is_ready = getattr(self.emitted, "is_ready", None)
        return bool(is_ready()) if is_ready is not None else False


@dataclasses.dataclass
class CollectResult:
    finished: List[Tuple[Any, np.ndarray, int]]   # (request, tokens, slot)
    active_steps: np.ndarray       # (C,) decode steps each row was live for
    slot_reqs: List[Optional[Any]]  # slot -> request, pre-retirement snapshot
    # retired slot records aligned with `finished` (TTFT stamp, preemption
    # count); a separate list so `finished` keeps its 3-tuple shape
    retired: List[Any] = dataclasses.field(default_factory=list)


class ContinuousBatchingEngine:
    """Masked fixed-step scan decode over a persistent slot table.

    Drive it either through :class:`repro.serving.multitenant.
    MultiTenantScheduler` (``mode="continuous"``) or directly::

        eng = ContinuousBatchingEngine(engine, capacity=4)
        for req, tokens in eng.run_all(requests): ...
    """

    def __init__(self, engine: ServingEngine, capacity: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 inner_steps: int = 4, max_prompt_len: int = 128,
                 prefix_sharing: bool = True,
                 preserve_pristine: Any = True,
                 batch_admission: bool = True,
                 logits_cache_size: int = 32,
                 backend: Optional[str] = None,
                 pallas_interpret: Optional[bool] = None,
                 swap: bool = True,
                 swap_store: Optional[HostSwapStore] = None,
                 fault_plane: Optional[Any] = None,
                 admission_retry_limit: int = 8,
                 telemetry: Optional[Telemetry] = None):
        cfg = engine.cfg
        self.engine = engine
        self.cfg = cfg
        self.sh = engine.sh
        self.params = engine.params
        self.bundle = engine.bundle
        self.capacity = capacity
        self.inner_steps = inner_steps
        self.max_prompt_len = max_prompt_len
        self.n_stages = cfg.num_layers // cfg.stage_period
        self.sched = cfg.block_schedule()[:cfg.stage_period]
        self.page_size = page_size
        # the per-request state kinds this arch's rows carry (attn / cross /
        # ssm) — capability flags below all derive from this tuple
        self.state_kinds = state_kinds(cfg)
        self.ssm_subs = ssm_subs(cfg)
        # enc-dec: the whole encoder output's cross KV pages per request,
        # written once at admission into the pool's separate cross space
        self.cross_blocks = (-(-cfg.encoder_seq_len // page_size)
                             if cfg.enc_dec else 0)
        max_ring = self._ring_len(self.bucket_len(max_prompt_len))
        self.kv = PagedKVCache(cfg, capacity, page_size,
                               -(-max_ring // page_size), num_pages,
                               cross_blocks=self.cross_blocks)
        # prefix sharing needs a refcounted attention page space; sliding-
        # window archs share through window-phase chain keys (the ring
        # layout is part of block identity, see PagedKVCache.chain_keys)
        self.prefix_sharing = bool(prefix_sharing and self.kv.attn_subs)
        # pristine-preserve policy: False = never copy; True (default) =
        # reuse-aware (preserve a sole-owner registered page only once its
        # chain has recorded a sharing hit); "always" = PR-4 behaviour
        # (one page copy per admission even on share-nothing traffic)
        self.preserve_pristine = preserve_pristine
        self.batch_admission = batch_admission
        # paged-attention backend: "jnp" gathers the dense logical window
        # per decode step (A/B baseline), "pallas" streams pages in place
        # through the fused kernels; inherited from the engine when unset,
        # else the platform's (pallas compiled on a TPU, jnp elsewhere)
        if backend is None:
            backend = (getattr(engine, "kernel_backend", None)
                       or default_kernel_backend())
        if backend not in BACKENDS:
            raise ValueError(f"backend {backend!r}: must be one of "
                             f"{BACKENDS}")
        self.backend = backend
        self.pallas_interpret = resolve_interpret(pallas_interpret)
        # skip-prefill full hits need every per-slot state to be
        # reconstructable from shared pages + cached logits alone: cross
        # pages are per-request and SSM states are neither paged nor
        # cached, so only pure-attention archs ever skip a prefill
        self._pure_attn = {k.name for k in self.state_kinds} == {"attn"}
        self.logits_cache_size = int(logits_cache_size)
        self._logits_cache: "collections.OrderedDict[bytes, jax.Array]" = \
            collections.OrderedDict()
        self.state = self._init_state()
        self._slots: List[Optional[_Slot]] = [None] * capacity
        self._free_slots: List[int] = list(range(capacity - 1, -1, -1))
        # preemption (KV tiering): derived from the registered state kinds
        # — every kind is swappable (attn/cross pages snapshot as blocks,
        # SSM states as fixed-width records), so any arch preempts when
        # swap is enabled
        self.fault_plane = fault_plane
        self.can_preempt = bool(swap) and all(
            k.swappable for k in self.state_kinds)
        self.swap_store = (swap_store if swap_store is not None
                           else (HostSwapStore(fault_plane=fault_plane,
                                               sharder=self.sh)
                                 if self.can_preempt else None))
        # lane/shard ordinal for telemetry: the mesh slice this engine's
        # slot table is committed to (0 on the single-device path)
        self.pdev = (min(d.id for d in self.sh.mesh.devices.reshape(-1))
                     if self.sh.mesh is not None else 0)
        self.admission_retry_limit = int(admission_retry_limit)
        self.rejected: List[Any] = []   # run_all's terminal REJECTED requests
        # trace counters: python side effects run only while jit traces
        self.decode_traces = 0
        self.admit_traces = 0
        self.admit_skip_traces = 0
        self.prefill_traces = 0
        self.restore_traces = 0
        self.prefill_calls = 0     # host-side prefill invocations (batched)
        self.prefill_skips = 0     # admissions served from the logits cache
        self.rounds = 0
        self.row_steps = 0         # sum over rounds of live rows per step
        self.preemptions = 0
        self.restores = 0
        # telemetry plane (the global one unless injected); the per-round
        # (steps, capacity, live_steps) log mirrors the ``round.device``
        # span events and is what occupancy() is derived from — it is
        # engine accounting, kept even when the plane is disabled
        self.tel = get_telemetry(telemetry)
        self._round_log: List[Tuple[int, int, int]] = []
        # pool + swap store report onto the same plane
        self.kv.tel = self.tel
        if self.swap_store is not None and swap_store is None:
            self.swap_store.retarget_telemetry(self.tel)
        self._build_jits()

    # ------------------------------------------------------------------
    def bucket_len(self, prompt_len: int) -> int:
        """Prompts are left-padded to a page-aligned bucket so admission
        (prefill + KV scatter) compiles once per bucket, not per length."""
        p = self.page_size
        return max(p, -(-prompt_len // p) * p)

    def _ring_len(self, bucket: int) -> int:
        w = self.cfg.sliding_window
        return min(bucket, w) if w is not None else bucket

    def active_count(self) -> int:
        return sum(s is not None for s in self._slots)

    def live_after(self, steps: int) -> bool:
        """Will any current row still be live after ``steps`` more decode
        steps?  Host-side: a row's collected tokens exclude any in-flight
        round, so with one round of ``steps`` in flight this answers "is a
        follow-up round worth dispatching" — False means pipelining another
        round would decode an all-masked slot table."""
        return any(s is not None and s.target - len(s.tokens) > steps
                   for s in self._slots)

    def free_slot_count(self) -> int:
        return len(self._free_slots)

    def live_priorities(self) -> List[int]:
        """Priorities of every live row, in no particular order.  Public
        accessor so schedulers never depend on the slot-table layout (which
        the mesh-sharded engine is free to rearrange)."""
        return [s.priority for s in self._slots if s is not None]

    def occupancy(self) -> float:
        """Fraction of row-steps that decoded a live row, over *collected*
        micro-rounds.

        Derived from the per-round span events recorded at collect time
        (``round.device``: steps, capacity, live row-steps), not from the
        ``rounds`` counter — ``rounds`` increments at dispatch while
        ``row_steps`` lags until collect, so the old
        ``row_steps / (rounds * inner_steps * capacity)`` quotient counted
        a dispatched-but-uncollected round's masked rows in the
        denominator and deflated occupancy whenever it was read with a
        round in flight (exactly the retire-before-dispatch fast path's
        steady state, and any periodic stats line).  On a drained engine
        the two agree (tests/test_obs.py pins old == new on an all-live
        round)."""
        total = sum(steps * cap for steps, cap, _ in self._round_log)
        if not total:
            return 0.0
        return sum(live for _, _, live in self._round_log) / total

    @classmethod
    def supported_modes(cls, cfg: ArchConfig) -> Dict[str, Dict[str, Any]]:
        """Capability probe: what each serving mode offers for ``cfg``.

        Every arch in ``configs/`` serves under every mode (PR 9) — the
        probe's job is the *qualifiers*: which state kinds the slot table
        carries, whether rows can be swap-preempted, whether prefix sharing
        applies (and through window-phase keys on sliding-window archs),
        and whether continuous decode is bitwise or only statistically
        exchangeable with the blocking reference (MoE capacity routing
        couples rows).  ``launch/serve.py --list-archs`` renders this table
        without instantiating any engine."""
        kinds = state_kinds(cfg)
        names = [k.name for k in kinds]
        moe = any(mlp == MOE for _, mlp in cfg.block_schedule())
        cont = {
            "supported": True,
            "state_kinds": names,
            "preemptable": all(k.swappable for k in kinds),
            "prefix_sharing": "attn" in names,
            "window_phase_keys": ("attn" in names
                                  and cfg.sliding_window is not None),
            "exactness": "statistical" if moe else "bitwise",
        }
        return {"blocking": {"supported": True, "exactness": "reference"},
                "overlapped": {"supported": True,
                               "exactness": cont["exactness"]},
                "continuous": cont}

    # ------------------------------------------------------------------
    def _init_state(self) -> Dict[str, Any]:
        cfg, c = self.cfg, self.capacity
        caches: Dict[str, Any] = dict(self.kv.make_pools(self.n_stages))
        for i, (mixer, _) in enumerate(self.sched):
            if mixer != ATTN:
                st = ssm_mod.init_ssm_state(cfg, c)
                caches[f"sub{i}"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (self.n_stages,) + a.shape), st)
        if self.cross_blocks:
            caches["cross"] = self.kv.make_cross_pools(self.n_stages)
        st = {
            "caches": caches,
            "page_table": self.kv.make_page_table(),
            "pos_pool": self.kv.make_pos_pool(),
            "logits": jnp.zeros((c, pad_vocab(cfg.vocab_size)), jnp.float32),
            "pos": jnp.zeros((c,), jnp.int32),
            "ring": jnp.ones((c,), jnp.int32),
            "remaining": jnp.zeros((c,), jnp.int32),
            "temps": jnp.zeros((c,), jnp.float32),
            "topks": jnp.zeros((c,), jnp.int32),
            "keys": jnp.zeros((c, 2), jnp.uint32),
            "lstep": jnp.zeros((c,), jnp.int32),
        }
        if self.cross_blocks:
            # per-slot cross page rows (the cross space's page table)
            st["cross_pt"] = jnp.full((c, self.cross_blocks),
                                      PagedKVCache.SENTINEL, jnp.int32)
        if self.sh.mesh is not None:
            # commit the slot-table pytree onto the mesh up front: the KV
            # pools (self- and cross-attention) partition along KV heads,
            # everything else replicates.  Donation then keeps every
            # round's output on the same layout, so nothing reshards
            # mid-serve and jit never sees mixed-device committed inputs.
            st = jax.tree.map(
                lambda a: self.sh.place(a, (None,) * a.ndim), st)
            for name in self.kv.attn_subs:
                st["caches"][name] = {
                    k: self.sh.place(v, (None, None, None, "kv", None))
                    for k, v in st["caches"][name].items()}
            if self.cross_blocks:
                st["caches"]["cross"] = {
                    k: self.sh.place(v, (None, None, None, "kv", None))
                    for k, v in st["caches"]["cross"].items()}
        return st

    # ------------------------------------------------------------------
    def _build_jits(self) -> None:
        cfg, sh = self.cfg, self.sh
        sched = self.sched
        p_sz = self.kv.page_size
        trash = PagedKVCache.TRASH
        has_attn = bool(self.kv.attn_subs)
        enc_dec = bool(self.cross_blocks)
        backend, interp = self.backend, self.pallas_interpret

        def decode_step(params, st, all_greedy, any_topk):
            active = st["remaining"] > 0
            tok = sample_rows(st["logits"], st["temps"], st["topks"],
                              st["keys"], all_greedy=all_greedy,
                              any_topk=any_topk)
            pos, ring, pt = st["pos"], st["ring"], st["page_table"]
            if has_attn:
                slot_log = jnp.mod(pos, ring)
                blk = slot_log // p_sz
                off = jnp.mod(slot_log, p_sz)
                page = jnp.take_along_axis(pt, blk[:, None], axis=1)[:, 0]
                page = jnp.where(active, page, trash)
                pos_pool = st["pos_pool"].at[page, off].set(
                    jnp.where(active, pos, POS_SENTINEL))
                # the fused kernel reads positions per page in place; only
                # the dense-gather backend materialises the (C, L) view
                kpos = (None if backend == "pallas"
                        else pos_pool[pt].reshape(pt.shape[0], -1))
            else:
                page = off = kpos = None
                pos_pool = st["pos_pool"]

            x = apply_embedding(params["embed"], tok[:, None], cfg, sh)
            if not cfg.use_rope:
                # _sinusoid_at broadcasts (C, 1, 1) positions to (C, 1, d)
                # — the per-row twin of decode_fn's scalar call
                from repro.models.model import _sinusoid_at
                x = x + _sinusoid_at(pos[:, None, None],
                                     cfg.d_model).astype(x.dtype)

            if enc_dec:
                # encoder-decoder body: paged self-attention, then a
                # read-only gather of the slot's cross KV pages — the
                # per-row twin of decode_fn's (dec_stages, self, cross)
                # scan, same residual structure operation for operation
                S_enc = cfg.encoder_seq_len
                nbc = self.cross_blocks
                aname = self.kv.attn_subs[0]
                cpt = st["cross_pt"]

                def body(h, xs):
                    sp, self_cache, ck_pool, cv_pool = xs
                    a, nci = paged_attention_decode(
                        sp["attn"], apply_rmsnorm(sp["norm1"], h),
                        self_cache, pt, kpos, page, off, pos, cfg, sh,
                        pos_pool=pos_pool, backend=backend, interpret=interp)
                    h = h + a
                    # (C, nbc, P, Hkv, D) -> (C, S_enc, Hkv, D): the pool
                    # pads past S_enc with zeros the static slice drops, so
                    # the gathered view is bitwise the prefill's cross KV
                    ck = ck_pool[cpt].reshape(
                        cpt.shape[0], nbc * p_sz,
                        *ck_pool.shape[-2:])[:, :S_enc]
                    cv = cv_pool[cpt].reshape(
                        cpt.shape[0], nbc * p_sz,
                        *cv_pool.shape[-2:])[:, :S_enc]
                    c_out = apply_cross_attention(
                        sp["cross"], apply_rmsnorm(sp["norm_c"], h),
                        (ck.astype(h.dtype), cv.astype(h.dtype)), cfg, sh)
                    h = h + c_out
                    m = apply_mlp(sp["mlp"], apply_rmsnorm(sp["norm2"], h),
                                  cfg, sh)
                    return h + m, nci

                cross = st["caches"]["cross"]
                h, new_self = jax.lax.scan(
                    body, x, (params["dec_stages"], st["caches"][aname],
                              cross["k"], cross["v"]))
                new_caches = {aname: new_self, "cross": cross}
            else:
                def body(h, xs):
                    stage_params, stage_cache = xs
                    nc = {}
                    for i, (mixer, mlp) in enumerate(sched):
                        sub = stage_params[f"sub{i}"]
                        hin = apply_rmsnorm(sub["norm1"], h)
                        if mixer == ATTN:
                            hout, nci = paged_attention_decode(
                                sub["attn"], hin, stage_cache[f"sub{i}"], pt,
                                kpos, page, off, pos, cfg, sh,
                                pos_pool=pos_pool, backend=backend,
                                interpret=interp)
                        else:
                            hout, nci = ssm_mod.apply_ssm_decode(
                                sub["mamba"], hin, stage_cache[f"sub{i}"],
                                cfg, sh)
                            # frozen state for masked rows (attention rows
                            # are masked by redirecting their write to
                            # TRASH instead)
                            nci = jax.tree.map(
                                lambda new, old: jnp.where(
                                    active.reshape(
                                        (-1,) + (1,) * (new.ndim - 1)),
                                    new, old),
                                nci, stage_cache[f"sub{i}"])
                        nc[f"sub{i}"] = nci
                        h = h + hout
                        if mlp != NONE:
                            hin = apply_rmsnorm(sub["norm2"], h)
                            if mlp == MOE:
                                hout, _ = moe_mod.apply_moe(sub["moe"], hin,
                                                            cfg, sh)
                            else:
                                hout = apply_mlp(sub["mlp"], hin, cfg, sh)
                            h = h + hout
                    return h, nc

                h, new_caches = jax.lax.scan(
                    body, x, (params["stages"], st["caches"]))
            h = apply_rmsnorm(params["final_norm"], h)
            new_logits = apply_unembed(params["embed"], h, cfg, sh)[:, 0]

            if all_greedy:               # keys unused by every live row
                keys = st["keys"]
            else:
                keys_next = jax.vmap(jax.random.fold_in)(st["keys"],
                                                         st["lstep"])
                keys = jnp.where(active[:, None], keys_next, st["keys"])
            new_st = {
                **st,
                "caches": new_caches,
                "pos_pool": pos_pool,
                "logits": jnp.where(active[:, None], new_logits,
                                    st["logits"]),
                "pos": pos + active,
                "remaining": st["remaining"] - active,
                "keys": keys,
                "lstep": st["lstep"] + active,
            }
            return new_st, (jnp.where(active, tok, -1), active)

        def round_fn(params, st, *, steps: int, all_greedy: bool,
                     any_topk: bool):
            self.decode_traces += 1          # incremented at trace time only
            self.tel.count("trace.decode")
            st, (emitted, act) = jax.lax.scan(
                lambda c, _: decode_step(params, c, all_greedy, any_topk),
                st, None, length=steps)
            return st, emitted, act

        # the slot-table state pytree is donated everywhere it is threaded
        # through a jit: XLA aliases the page pools input->output and
        # updates them in place instead of copying the whole pool per call
        # (the donation test asserts the old buffers die)
        self._round_jit = jax.jit(
            round_fn, static_argnames=("steps", "all_greedy", "any_topk"),
            donate_argnums=(1,))

        def prefill_fn(params, batch):
            self.prefill_traces += 1
            self.tel.count("trace.prefill")
            return self.bundle.prefill_fn(params, batch, sh)

        self._prefill_jit = jax.jit(prefill_fn)

        def cow_fn(st, src, dst, slot, blk):
            """Copy-on-write fork: copy page ``src`` -> ``dst`` in every
            attention pool and the position pool, and repoint the writer's
            page-table entry.  All operands dynamic: compiles once."""
            new = dict(st)
            nc = dict(st["caches"])
            for name in self.kv.attn_subs:
                pool = st["caches"][name]
                nc[name] = {"k": pool["k"].at[:, dst].set(pool["k"][:, src]),
                            "v": pool["v"].at[:, dst].set(pool["v"][:, src])}
            new["caches"] = nc
            new["pos_pool"] = st["pos_pool"].at[dst].set(st["pos_pool"][src])
            new["page_table"] = st["page_table"].at[slot, blk].set(dst)
            return new

        self._cow_jit = jax.jit(cow_fn, donate_argnums=(0,))

        def admit_skip_fn(st, logits0, slot, pages, remaining, temp, topk,
                          key, bucket, ring):
            """Skip-prefill admission (full prefix hit): every KV block is
            already resident in shared pages and the first-token logits come
            from the cache, so only the page-table row and the slot's
            sampling state are written.  bucket/ring are dynamic: one trace
            per page-row width."""
            self.admit_skip_traces += 1
            self.tel.count("trace.admit_skip")
            new = dict(st)
            row = jnp.full((self.kv.max_blocks,), PagedKVCache.SENTINEL,
                           jnp.int32).at[:pages.shape[0]].set(pages)
            new["page_table"] = st["page_table"].at[slot].set(row)
            new["logits"] = st["logits"].at[slot].set(logits0)
            new["pos"] = st["pos"].at[slot].set(bucket)
            new["ring"] = st["ring"].at[slot].set(ring)
            new["remaining"] = st["remaining"].at[slot].set(remaining)
            new["temps"] = st["temps"].at[slot].set(temp)
            new["topks"] = st["topks"].at[slot].set(topk)
            new["keys"] = st["keys"].at[slot].set(key)
            new["lstep"] = st["lstep"].at[slot].set(0)
            return new

        self._admit_skip_jit = jax.jit(admit_skip_fn, donate_argnums=(0,))

        def admit_fn(st, caches_p, logits0, slot, pages, cross_pages,
                     remaining, temp, topk, key, *, bucket: int, ring: int):
            self.admit_traces += 1
            self.tel.count("trace.admit")
            new = dict(st)
            # enc-dec prefill returns {"self": ..., "cross": ...}; remap the
            # self caches onto the (single) attention sublayer so the page
            # scatter below is kind-agnostic
            caches_attn = ({self.kv.attn_subs[0]: caches_p["self"]}
                           if enc_dec else caches_p)
            nb = pages.shape[0] if pages is not None else 0
            if nb:
                row = jnp.full((self.kv.max_blocks,), PagedKVCache.SENTINEL,
                               jnp.int32).at[:nb].set(pages)
                new["page_table"] = st["page_table"].at[slot].set(row)
                name = self.kv.attn_subs[0]
                pos_src = caches_attn[name]["pos"][0, 0]         # (ring,)
                pos_vals = jnp.full((nb * p_sz,), POS_SENTINEL,
                                    jnp.int32).at[:ring].set(pos_src)
                new["pos_pool"] = st["pos_pool"].at[pages].set(
                    pos_vals.reshape(nb, p_sz))
            nc = {}
            for i, (mixer, _) in enumerate(sched):
                sname = f"sub{i}"
                cur = st["caches"][sname]
                if mixer == ATTN:
                    def to_pages(leaf, pool_leaf):
                        # fused compute-then-scatter: the bucket's freshly
                        # prefilled KV goes straight into its allocated
                        # pages (page-granular on the pallas backend)
                        pad = nb * p_sz - ring
                        v = jnp.pad(leaf[:, 0],
                                    ((0, 0), (0, pad), (0, 0), (0, 0)))
                        v = v.reshape(self.n_stages, nb, p_sz,
                                      *leaf.shape[3:])
                        return paged_scatter(pool_leaf, pages, v,
                                             backend=backend,
                                             interpret=interp, sh=sh)
                    nc[sname] = {"k": to_pages(caches_attn[sname]["k"],
                                               cur["k"]),
                                 "v": to_pages(caches_attn[sname]["v"],
                                               cur["v"])}
                else:
                    nc[sname] = jax.tree.map(
                        lambda t, cp: t.at[:, slot].set(cp[:, 0]),
                        cur, caches_attn[sname])
            if enc_dec:
                # write-once cross KV scatter into the slot's private cross
                # pages (pool dtype == compute dtype: bitwise the prefill's
                # cross KV; the tail of the last page pads with zeros the
                # decode gather's static slice drops)
                S_enc = cfg.encoder_seq_len
                nbc = self.cross_blocks

                def cross_to_pages(leaf, pool_leaf):
                    v = jnp.pad(leaf[:, 0], ((0, 0), (0, nbc * p_sz - S_enc),
                                             (0, 0), (0, 0)))
                    v = v.reshape(self.n_stages, nbc, p_sz, *leaf.shape[3:])
                    return pool_leaf.at[:, cross_pages].set(
                        v.astype(pool_leaf.dtype))

                cross = st["caches"]["cross"]
                nc["cross"] = {
                    "k": cross_to_pages(caches_p["cross"]["k"], cross["k"]),
                    "v": cross_to_pages(caches_p["cross"]["v"], cross["v"])}
                new["cross_pt"] = st["cross_pt"].at[slot].set(cross_pages)
            new["caches"] = nc
            new["logits"] = st["logits"].at[slot].set(logits0[0])
            new["pos"] = st["pos"].at[slot].set(bucket)
            new["ring"] = st["ring"].at[slot].set(ring)
            new["remaining"] = st["remaining"].at[slot].set(remaining)
            new["temps"] = st["temps"].at[slot].set(temp)
            new["topks"] = st["topks"].at[slot].set(topk)
            new["keys"] = st["keys"].at[slot].set(key)
            new["lstep"] = st["lstep"].at[slot].set(0)
            return new

        self._admit_jit = jax.jit(admit_fn,
                                  static_argnames=("bucket", "ring"),
                                  donate_argnums=(0,))

        def evict_fn(st, slot):
            """Vacate a preempted (or terminally failed) row: zero its
            remaining budget and point its whole page-table row at SENTINEL,
            so the stale table can neither decode garbage nor address pages
            reallocated to newer requests.  All operands dynamic: one
            trace."""
            new = dict(st)
            new["remaining"] = st["remaining"].at[slot].set(0)
            new["page_table"] = st["page_table"].at[slot].set(
                jnp.full((self.kv.max_blocks,), PagedKVCache.SENTINEL,
                         jnp.int32))
            if enc_dec:
                new["cross_pt"] = st["cross_pt"].at[slot].set(
                    jnp.full((self.cross_blocks,), PagedKVCache.SENTINEL,
                             jnp.int32))
            return new

        self._evict_jit = jax.jit(evict_fn, donate_argnums=(0,))

        def restore_fn(st, kv_blocks, pos_rows, logits, slot, pages,
                       scatter_pages, pos, remaining, temp, topk, key,
                       lstep, ring, cross, state):
            """Swap-in: scatter a preempted request's snapshot blocks into
            freshly allocated pages and rebuild its slot row bitwise.
            ``pages`` is the full SENTINEL-padded page-table row and the
            snapshot is padded to the same width, so this traces ONCE
            whatever the victim's ring; ``scatter_pages`` redirects both
            the padding's and the re-shared blocks' writes to TRASH —
            re-shared device pages already hold the identical pristine
            content, and TRASH is never read as valid, exactly like
            masked-row writes.  ``cross`` / ``state`` are the per-kind
            halves of the record — ``{"kv", "pages"}`` for an enc-dec
            victim's cross pages, a sub->record tree for SSM slot state —
            and are None (empty pytrees, so still one trace) on archs
            without that kind."""
            self.restore_traces += 1
            self.tel.count("trace.restore")
            new = dict(st)
            new["page_table"] = st["page_table"].at[slot].set(pages)
            new["pos_pool"] = st["pos_pool"].at[scatter_pages].set(pos_rows)
            nc = dict(st["caches"])
            for name in self.kv.attn_subs:
                cur = st["caches"][name]
                nc[name] = {
                    "k": cur["k"].at[:, scatter_pages].set(
                        kv_blocks[name]["k"].astype(cur["k"].dtype)),
                    "v": cur["v"].at[:, scatter_pages].set(
                        kv_blocks[name]["v"].astype(cur["v"].dtype))}
            if cross is not None:
                cp = st["caches"]["cross"]
                nc["cross"] = {
                    n: cp[n].at[:, cross["pages"]].set(
                        cross["kv"][n].astype(cp[n].dtype))
                    for n in ("k", "v")}
                new["cross_pt"] = st["cross_pt"].at[slot].set(cross["pages"])
            if state is not None:
                for sname, leaves in state.items():
                    nc[sname] = ssm_mod.restore_slot_state(
                        st["caches"][sname], slot, leaves)
            new["caches"] = nc
            new["logits"] = st["logits"].at[slot].set(logits)
            new["pos"] = st["pos"].at[slot].set(pos)
            new["ring"] = st["ring"].at[slot].set(ring)
            new["remaining"] = st["remaining"].at[slot].set(remaining)
            new["temps"] = st["temps"].at[slot].set(temp)
            new["topks"] = st["topks"].at[slot].set(topk)
            new["keys"] = st["keys"].at[slot].set(key)
            new["lstep"] = st["lstep"].at[slot].set(lstep)
            return new

        self._restore_jit = jax.jit(restore_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def try_admit(self, req: Any) -> bool:
        """Admit one request into a free slot; False when no slot or no
        pages are available right now (caller keeps it queued)."""
        return self.try_admit_batch([req])[0]

    def try_admit_batch(self, reqs: List[Any]) -> List[bool]:
        """Admit up to ``len(self._free_slots)`` requests in one go.

        Three phases:

        1. *plan* — per request: bucket/ring, padded prompt, chain keys and
           a provisional full-prefix-hit probe (can this admission reuse
           cached prefill logits?);
        2. *prefill* — one batched prefill call per prompt bucket for every
           plan that cannot skip it, width padded to the next power of two
           (``batch_admission=False`` keeps the PR-3 one-call-per-request
           baseline); rows are sliced back out per request — batched prefill
           is bitwise row-independent, so this changes nothing downstream;
        3. *admit* — sequential per request: re-probe the trie (earlier
           members of this very batch have registered by now, so same-batch
           prefix sharing works), allocate shared+fresh pages, scatter KV /
           sampling state, register the new chain blocks.

        Returns one admitted-flag per request; rejected requests (slot or
        page pressure) are untouched and stay with the caller.  An injected
        admission stall (fault plane) raises before any prefill or page
        allocation, so the whole batch stays with the caller too.
        """
        if self.fault_plane is not None and reqs:
            self.fault_plane.admission_fault()
        with self.tel.span("admit.batch", n=len(reqs)) as admit_span:
            flags = self._try_admit_batch_inner(reqs)
            admit_span.note(admitted=sum(flags))
        return flags

    def _try_admit_batch_inner(self, reqs: List[Any]) -> List[bool]:
        flags = [False] * len(reqs)
        plans: List[Dict[str, Any]] = []
        for i, req in enumerate(reqs):
            if len(plans) >= len(self._free_slots):
                break
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            if prompt.size > self.max_prompt_len:
                raise ValueError(
                    f"prompt of {prompt.size} tokens exceeds max_prompt_len="
                    f"{self.max_prompt_len}")
            bucket = self.bucket_len(prompt.size)
            ring = self._ring_len(bucket)
            padded = np.zeros((bucket,), np.int32)
            padded[bucket - prompt.size:] = prompt
            extra = resolve_extra_inputs(self.cfg, req)
            salt = b""
            if extra:
                # non-token prefill inputs (merged patch embeddings, encoder
                # frames) feed the prefilled KV, so they are part of block
                # identity: requests share pages only under identical extras
                dg = hashlib.sha256()
                for name in sorted(extra):
                    arr = np.ascontiguousarray(np.asarray(extra[name]))
                    dg.update(name.encode())
                    dg.update(arr.tobytes())
                salt = dg.digest()
            keys = (self.kv.chain_keys(padded, ring=ring, salt=salt)
                    if self.prefix_sharing else [])
            # provisional only — the authoritative share decision re-probes
            # at admit time; this just decides whether to prefill
            skip = bool(keys and self._pure_attn
                        and len(self.kv.lookup_chain(keys)) == len(keys)
                        and keys[-1] in self._logits_cache)
            plans.append(dict(i=i, req=req, bucket=bucket,
                              ring=ring, padded=padded, extra=extra,
                              keys=keys, skip=skip, logits=None,
                              caches=None))
        if not plans:
            return flags
        groups: Dict[Any, List[Dict[str, Any]]] = {}
        for pl in plans:
            if not pl["skip"]:
                gk = (pl["bucket"], tuple(sorted(pl["extra"])))
                groups.setdefault(gk, []).append(pl)
        for (bucket, extra_names), grp in groups.items():
            chunks = [grp] if self.batch_admission else [[pl] for pl in grp]
            for chunk in chunks:
                width = 1 << (len(chunk) - 1).bit_length()
                tokens = np.zeros((width, bucket), np.int32)
                for j, pl in enumerate(chunk):
                    tokens[j] = pl["padded"]
                batch = {"tokens": jnp.asarray(tokens)}
                for name in extra_names:
                    # stack the chunk's extras; padding rows are zeros (row-
                    # independent prefill: the pad rows are sliced away)
                    first = np.asarray(chunk[0]["extra"][name])
                    rows = ([np.asarray(pl["extra"][name]) for pl in chunk]
                            + [np.zeros_like(first)] * (width - len(chunk)))
                    batch[name] = jnp.asarray(np.stack(rows))
                with self.tel.span("admit.prefill", bucket=bucket,
                                   width=width, n=len(chunk)):
                    logits, caches, _ = self._prefill_jit(
                        self.params, batch)
                self.prefill_calls += 1
                self.tel.count("admit.prefill_calls")
                for j, pl in enumerate(chunk):
                    pl["logits"] = logits[j:j + 1]
                    pl["caches"] = jax.tree.map(lambda a, j=j: a[:, j:j + 1],
                                                caches)
        for pl in plans:
            flags[pl["i"]] = self._admit_planned(pl)
        return flags

    def _admit_planned(self, pl: Dict[str, Any]) -> bool:
        """Phase 3 of :meth:`try_admit_batch`: page mapping + state scatter
        for one planned request.  False leaves the allocator untouched."""
        req, bucket, ring = pl["req"], pl["bucket"], pl["ring"]
        kv = self.kv
        nb = kv.blocks_for(ring) if kv.attn_subs else 0
        shared: List[int] = []
        will_write: Any = ()
        target = int(req.max_new_tokens)
        if nb and self.prefix_sharing:
            shared = kv.lookup_chain(pl["keys"])[:nb]
            # blocks this request's decode ring-writes will touch: each is
            # charged one page of fork headroom at allocation time
            will_write = {((bucket + t) % ring) // self.page_size
                          for t in range(min(target, ring))}
        cached_logits = None
        if pl["skip"]:
            cached_logits = self._logits_cache.get(pl["keys"][-1])
            if len(shared) < nb or cached_logits is None:
                # the chain (or its logits) was evicted between planning and
                # admission: no prefill result to fall back on — requeue
                return False
            self._logits_cache.move_to_end(pl["keys"][-1])
        slot = self._free_slots[-1]
        pages = None
        if nb:
            pages = kv.alloc_shared(slot, shared, nb - len(shared),
                                    will_write)
            if pages is None:
                return False                 # pool pressure: retry later
        cross_pages = None
        if self.cross_blocks:
            cross_pages = kv.alloc_cross(slot)
            if cross_pages is None:
                if pages is not None:
                    kv.free(slot)            # undo the attn half
                return False                 # cross-space pressure
        self._free_slots.pop()
        temp = getattr(req, "temperature", None)
        if temp is None:
            temp = self.engine.temperature
        topk = int(getattr(req, "top_k", 0) or 0)
        key = jax.random.PRNGKey(int(getattr(req, "seed", 0) or 0))
        if pl["skip"]:
            self.state = self._admit_skip_jit(
                self.state, cached_logits, np.int32(slot),
                jnp.asarray(pages), np.int32(target), np.float32(temp),
                np.int32(topk), key, np.int32(bucket), np.int32(ring))
            self.prefill_skips += 1
            self.tel.count("admit.prefill_skips")
        else:
            self.state = self._admit_jit(
                self.state, pl["caches"], pl["logits"], slot,
                None if pages is None else jnp.asarray(pages),
                None if cross_pages is None else jnp.asarray(cross_pages),
                target, float(temp), topk, key, bucket=bucket, ring=ring)
            if self.prefix_sharing and self._pure_attn and pl["keys"]:
                self._logits_cache_put(pl["keys"][-1], pl["logits"][0])
        if self.prefix_sharing and pl["keys"]:
            kv.register(slot, pl["keys"][:nb])
        self._slots[slot] = _Slot(req, target, float(temp), topk,
                                  bucket=bucket, ring=ring,
                                  priority=int(getattr(req, "priority", 1)),
                                  chain_keys=list(pl["keys"][:nb]))
        return True

    def _logits_cache_put(self, key: bytes, row: jax.Array) -> None:
        cache = self._logits_cache
        cache[key] = row
        cache.move_to_end(key)
        while len(cache) > self.logits_cache_size:
            cache.popitem(last=False)

    # ------------------------------------------------------------------
    # decode micro-rounds
    # ------------------------------------------------------------------
    def _resolve_round_writes(self) -> None:
        """Pre-dispatch copy-on-write scan: the blocks each live row will
        write in the coming round are known on the host (``pos % ring``), so
        every shared or pristine-registered page among them is forked — page
        copied device-side, writer's table remapped — *before* the round's
        jit can touch it.  Without sharing, every page is exclusively owned
        and the scan is skipped entirely (PR-3 semantics)."""
        if not (self.prefix_sharing and self.kv.attn_subs):
            return
        preserve = bool(self.preserve_pristine)
        require_hit = self.preserve_pristine != "always"
        for c, s in enumerate(self._slots):
            if s is None:
                continue
            n = min(self.inner_steps, s.target - s.planned)
            if n <= 0:
                continue
            blks = sorted({((s.bucket + s.planned + t) % s.ring)
                           // self.page_size for t in range(n)})
            for blk in blks:
                fork = self.kv.note_write(c, blk, preserve=preserve,
                                          require_hit=require_hit)
                if fork is not None:
                    src, dst = fork
                    self.state = self._cow_jit(
                        self.state, np.int32(src), np.int32(dst),
                        np.int32(c), np.int32(blk))
            s.planned += n

    def dispatch_round(self) -> RoundHandle:
        """Enqueue one masked micro-round (non-blocking); the caller may
        admit the next requests while it runs on the device.  An injected
        round drop (fault plane) raises before the copy-on-write scan — the
        slot table is untouched, so a bare re-dispatch is sound."""
        if self.fault_plane is not None:
            self.fault_plane.round_fault()
        rnd = self.rounds
        with self.tel.span("round.dispatch", round=rnd, pdev=self.pdev):
            t0 = time.perf_counter()
            with self.tel.span("round.cow"):
                self._resolve_round_writes()
            # static sampling tier from the live rows (an all-greedy round
            # is a bare argmax; at most 3 round variants ever compile)
            live = [s for s in self._slots if s is not None]
            all_greedy = all(s.temp <= 0 for s in live)
            any_topk = any(s.top_k > 0 for s in live)
            with self.tel.span("round.jit", steps=self.inner_steps,
                               all_greedy=all_greedy):
                self.state, emitted, act = self._round_jit(
                    self.params, self.state, steps=self.inner_steps,
                    all_greedy=all_greedy, any_topk=any_topk)
            self.rounds += 1
        return RoundHandle(emitted, act, self.inner_steps, t0,
                           time.perf_counter(), rnd=rnd)

    def collect(self, handle: RoundHandle) -> CollectResult:
        """Materialise a round's emissions, append tokens to their rows and
        retire rows that hit their budget (pages evicted to the free list)."""
        emitted = np.asarray(handle.emitted)
        act = np.asarray(handle.act)
        slot_reqs = [s.req if s is not None else None for s in self._slots]
        active_steps = act.sum(axis=0).astype(np.int64)
        live_steps = int(active_steps.sum())
        self.row_steps += live_steps
        # the round-span event: the dispatch->materialised device window
        # with its live/total row-step split.  occupancy() and the
        # scheduler's busy split derive from this log, not from the
        # dispatch-time ``rounds`` counter
        self._round_log.append((handle.steps, self.capacity, live_steps))
        self.tel.record_span("round.device", handle.t_start,
                             time.perf_counter(), round=handle.rnd,
                             steps=handle.steps, capacity=self.capacity,
                             live_steps=live_steps, pdev=self.pdev)
        finished: List[Tuple[Any, np.ndarray, int]] = []
        retired: List[_Slot] = []
        for c, s in enumerate(self._slots):
            if s is None:
                continue
            row = emitted[act[:, c], c]
            if row.size and s.t_first is None:
                # first token materialised on the host: the TTFT stamp
                # (survives preemption — a restored slot keeps its stamp)
                s.t_first = time.perf_counter()
            s.tokens.extend(int(t) for t in row)
            if len(s.tokens) >= s.target:
                finished.append((s.req,
                                 np.asarray(s.tokens[:s.target], np.int32),
                                 c))
                retired.append(s)
                self.kv.free(c)
                self._slots[c] = None
                self._free_slots.append(c)
        return CollectResult(finished, active_steps, slot_reqs, retired)

    # ------------------------------------------------------------------
    # preemption: swap-out / swap-in (KV tiering)
    # ------------------------------------------------------------------
    def _snapshot_slot(self, slot: int, preempting: bool = False
                       ) -> SwapRecord:
        """Host-gather one live slot as a :class:`SwapRecord` — a pure
        read (sharers, allocator and device state untouched), shared by
        :meth:`preempt` (which then vacates the slot) and
        :meth:`snapshot_live` (engine checkpoints, which don't).

        Caller contract: no decode round may be in flight, so the slot's
        collected tokens are caught up with its dispatched steps.
        ``preempting`` bumps the record's preemption count — a checkpoint
        snapshot is not a preemption."""
        s = self._slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is empty")
        if not self.can_preempt:
            raise RuntimeError(
                "engine cannot preempt: swap disabled or the arch "
                "registered an unswappable state kind")
        if self.prefix_sharing:
            assert s.planned == len(s.tokens), \
                "slot snapshot with a decode round in flight"
        kv, st = self.kv, self.state
        pages = np.asarray(kv.owned_pages(slot), np.int32)
        # snapshots are padded to the page-table width so the restore jit
        # sees one shape whatever the victim's ring (padding scatters to
        # TRASH and is never read back) — and the snapshot *gathers* here
        # index with the same fixed width, else each distinct victim page
        # count compiles its own device gather (a mid-trace stall the
        # first time a 1-page victim is preempted after a 2-page warm-up);
        # the pad gathers SENTINEL's content and is zeroed host-side
        mb, nb = kv.max_blocks, len(pages)
        padded = np.zeros(mb, np.int32)
        padded[:nb] = pages

        def grab(pool):
            arr = np.array(pool[:, padded])
            arr[:, nb:] = 0
            return arr

        host_kv = {name: {"k": grab(st["caches"][name]["k"]),
                          "v": grab(st["caches"][name]["v"])}
                   for name in kv.attn_subs}
        host_pos = np.array(st["pos_pool"][padded])
        host_pos[nb:] = POS_SENTINEL
        # per-kind halves of the snapshot: the cross row is always full
        # width (one gather shape per arch) and SSM slot state checkpoints
        # as fixed-width records — both pure reads, like the page gather
        host_cross = None
        n_cross = 0
        if self.cross_blocks:
            cpages = np.asarray(kv.cross_pages_of(slot), np.int32)
            host_cross = {n: np.array(st["caches"]["cross"][n][:, cpages])
                          for n in ("k", "v")}
            n_cross = len(cpages)
        host_state = None
        if self.ssm_subs:
            host_state = {sname: ssm_mod.checkpoint_slot_state(
                              st["caches"][sname], slot)
                          for sname in self.ssm_subs}
        n_state = len(self.ssm_subs)
        written = {((s.bucket + t) % s.ring) // self.page_size
                   for t in range(min(len(s.tokens), s.ring))}
        private = kv.private_blocks(slot)
        return SwapRecord(
            req=s.req, priority=s.priority, target=s.target, temp=s.temp,
            top_k=s.top_k, bucket=s.bucket, ring=s.ring,
            tokens=list(s.tokens), chain_keys=list(s.chain_keys),
            written=written, pos=int(st["pos"][slot]),
            remaining=int(st["remaining"][slot]),
            lstep=int(st["lstep"][slot]), key=np.asarray(st["keys"][slot]),
            logits=np.asarray(st["logits"][slot]), host_kv=host_kv,
            host_pos=host_pos, n_private=len(private),
            preemptions=s.preemptions + (1 if preempting else 0),
            t_first=s.t_first, host_cross=host_cross, n_cross=n_cross,
            host_state=host_state, n_state=n_state)

    def preempt(self, slot: int) -> int:
        """Swap a live row out to the host tier and vacate its slot.

        Snapshots *every* page block of the victim (K/V per attention
        sublayer + position rows — a pure read, so sharers are untouched)
        plus the complete per-slot decode state, parks it in the swap
        store, then drops the page references through the ordinary
        allocator accounting: shared prefix pages keep serving their other
        readers, registered pristine pages linger as cache, and only the
        victim's private suffix is uniquely host-held (the ledger count).

        Caller contract: no decode round may be in flight (the scheduler
        force-collects first), so the slot's collected tokens are caught up
        with its dispatched steps.  Returns the swap-store ticket.
        """
        rec = self._snapshot_slot(slot, preempting=True)
        nb = len(self.kv.owned_pages(slot))
        with self.tel.span("swap.out", slot=slot, pages=nb,
                           private=rec.n_private, pdev=self.pdev):
            ticket = self.swap_store.put(rec)
            self.kv.swap_out(slot, rec.n_private, cross_blocks=rec.n_cross,
                             state_records=rec.n_state)
            self.state = self._evict_jit(self.state, np.int32(slot))
        self._slots[slot] = None
        self._free_slots.append(slot)
        self.preemptions += 1
        self.tel.count("swap.preemptions")
        return ticket

    def snapshot_live(self) -> List[Tuple[int, SwapRecord]]:
        """Engine-checkpoint gather: every live slot as a
        :class:`SwapRecord`, in slot order, without vacating anything —
        the same per-kind host snapshot preemption takes, reused as the
        checkpoint format.  Caller contract: no round in flight."""
        return [(c, self._snapshot_slot(c))
                for c, s in enumerate(self._slots) if s is not None]

    def restore_from(self, live: List[SwapRecord],
                     swapped: Dict[int, SwapRecord]) -> int:
        """Rebuild a *fresh* engine from a checkpoint: re-park the host
        tier's ``swapped`` records under their original tickets (seeding
        the two-tier ledger of the empty pool), then re-admit every
        checkpointed-``live`` record through the ordinary restore jit —
        pages re-allocate, prefix chains re-register and re-share, and
        each slot resumes with bitwise the scalars/pages it was
        checkpointed with.  Returns the number of live slots rebuilt."""
        if not live and not swapped:
            return 0
        assert self.swap_store is not None, "restore_from needs a swap store"
        assert self.active_count() == 0, "restore_from on a non-empty engine"
        with self.tel.span("recovery.restore", live=len(live),
                           swapped=len(swapped), pdev=self.pdev):
            self.swap_store.restore_records(swapped)
            for rec in swapped.values():
                self.kv.adopt_swapped(rec.n_private,
                                      cross_blocks=rec.n_cross,
                                      state_records=rec.n_state)
            for rec in live:
                # the fresh pool's two-tier ledger must cover this record
                # before try_restore's swap_in debits it (a checkpointed
                # live slot was never swap_out'd, so nothing credited it)
                self.kv.adopt_swapped(rec.n_private,
                                      cross_blocks=rec.n_cross,
                                      state_records=rec.n_state)
                ticket = self.swap_store.put(rec)
                if not self.try_restore(ticket):
                    # the checkpointed working set fit the pool when it was
                    # taken; a fresh pool of the same geometry must re-fit
                    raise RuntimeError(
                        "recovery: pool/slot pressure rebuilding a "
                        "checkpointed live slot")
        self.tel.count("recovery.slots_restored", len(live))
        return len(live)

    def try_restore(self, ticket: int) -> bool:
        """Swap a preempted request back into a free slot, token-exactly.

        Blocks the victim never wrote whose chain is *still* registered are
        re-shared straight from the trie (their pages hold bitwise the
        snapshot content); every other block gets a fresh page and receives
        the staged host copy (re-shared blocks' scatter is redirected to
        TRASH).  The slot scalars are restored bitwise, so the remaining
        decode — same ``fold_in(key, lstep)`` schedule, same positions,
        same page content — is indistinguishable from an uninterrupted run.

        Returns False (allocator untouched, record kept) on slot or page
        pressure; raises :class:`InjectedFault` on a poisoned swap read
        (record kept intact for the retry).
        """
        if not self._free_slots:
            return False
        kv = self.kv
        rec = self.swap_store.record(ticket)
        # SSM-only archs have no attention page space: nothing to allocate
        # (or scatter) on the attn side, the record is all slot state
        nb = kv.blocks_for(rec.ring) if kv.attn_subs else 0
        # pristine prefix: contiguous blocks the decode ring never wrote
        pristine = 0
        while pristine < nb and pristine not in rec.written:
            pristine += 1
        shared: List[int] = []
        if self.prefix_sharing and rec.chain_keys:
            shared = kv.lookup_chain(rec.chain_keys)[:pristine]
        will_write = ({((rec.pos + t) % rec.ring) // self.page_size
                       for t in range(min(rec.remaining, rec.ring))}
                      if nb else ())
        slot = self._free_slots[-1]
        pages = (kv.alloc_shared(slot, shared, nb - len(shared), will_write)
                 if nb else np.zeros((0,), np.int32))
        if pages is None:
            return False
        cross_pages = None
        if self.cross_blocks:
            cross_pages = kv.alloc_cross(slot)
            if cross_pages is None:
                if nb:
                    kv.free(slot)    # undo the attn half; retry later
                return False
        try:
            arrays = self.swap_store.fetch(ticket)
        except InjectedFault:
            kv.free(slot)            # undo both kinds; record intact
            raise
        self._free_slots.pop()
        # pad the page row to the table width (SENTINEL) and redirect both
        # the padding's and the re-shared blocks' scatter to TRASH: the
        # snapshot was padded the same way, so the restore jit traces once
        mb = kv.max_blocks
        row = np.full((mb,), PagedKVCache.SENTINEL, np.int32)
        row[:nb] = pages
        scatter = np.full((mb,), PagedKVCache.TRASH, np.int32)
        scatter[len(shared):nb] = np.asarray(pages)[len(shared):nb]
        cross_arg = None
        if self.cross_blocks:
            cross_arg = {"kv": arrays["cross"],
                         "pages": jnp.asarray(cross_pages)}
        state_arg = arrays.get("state")
        with self.tel.span("swap.restore", slot=slot, pages=nb,
                           reshared=len(shared), pdev=self.pdev):
            self.state = self._restore_jit(
                self.state, arrays["kv"], arrays["pos"],
                jnp.asarray(rec.logits), np.int32(slot), jnp.asarray(row),
                jnp.asarray(scatter), np.int32(rec.pos),
                np.int32(rec.remaining), np.float32(rec.temp),
                np.int32(rec.top_k), jnp.asarray(rec.key),
                np.int32(rec.lstep), np.int32(rec.ring),
                cross_arg, state_arg)
            kv.swap_in(rec.n_private, cross_blocks=rec.n_cross,
                       state_records=rec.n_state)
        self.swap_store.pop(ticket)
        if self.prefix_sharing and rec.chain_keys:
            # unwritten restored blocks hold bitwise their chains' prefill
            # content: re-register them so later identical prefixes (or a
            # second preemption of this request) can re-share
            kv.register(slot, rec.chain_keys[:pristine])
        self._slots[slot] = _Slot(
            rec.req, rec.target, rec.temp, rec.top_k, bucket=rec.bucket,
            ring=rec.ring, planned=len(rec.tokens), tokens=list(rec.tokens),
            priority=rec.priority, preemptions=rec.preemptions,
            chain_keys=list(rec.chain_keys), t_first=rec.t_first)
        self.restores += 1
        self.tel.count("swap.restores")
        return True

    def drop_swapped(self, ticket: int) -> SwapRecord:
        """Abandon a swapped-out record (terminal failure after the restore
        retry budget): its host blocks leave the ledger without a restore.
        Returns the record so the caller can surface the request."""
        rec = self.swap_store.pop(ticket)
        self.kv.swap_in(rec.n_private, restored=False,
                        cross_blocks=rec.n_cross, state_records=rec.n_state)
        return rec

    def fail_live(self) -> List[Any]:
        """Terminal failure path (round-fault limit exceeded): vacate every
        live row — pages freed through the ordinary accounting, rows
        evicted device-side — and return the abandoned requests so the
        caller can surface them as FAILED.  Caller contract: no round in
        flight."""
        failed: List[Any] = []
        for c, s in enumerate(self._slots):
            if s is None:
                continue
            failed.append(s.req)
            self.kv.free(c)
            self.state = self._evict_jit(self.state, np.int32(c))
            self._slots[c] = None
            self._free_slots.append(c)
        return failed

    # ------------------------------------------------------------------
    def run_all(self, requests) -> List[Tuple[Any, np.ndarray]]:
        """FIFO-drain a request list without a scheduler: admit as slots and
        pages free up (same-bucket admissions batched into one prefill), one
        micro-round per iteration.  Returns (request, tokens) in completion
        order.

        Overload contract (PR 6): a head request the pool cannot admit no
        longer raises.  When nothing is in flight (so no retirement can
        ever free pages) admission is retried up to
        ``admission_retry_limit`` times — injected admission stalls are
        transient, pool-too-small is not — after which the head request is
        dropped into ``self.rejected`` (terminal REJECTED) and the drain
        continues, so a 2x oversubscribed burst finishes without an
        exception and without a hang.
        """
        queue: Deque[Any] = collections.deque(requests)
        done: List[Tuple[Any, np.ndarray]] = []
        stall = 0
        while queue or self.active_count():
            progress = False
            while queue and self._free_slots:
                take = [queue.popleft() for _ in
                        range(min(len(queue), len(self._free_slots)))]
                try:
                    flags = self.try_admit_batch(take)
                except InjectedFault:
                    for req in reversed(take):
                        queue.appendleft(req)
                    break
                for req, ok in reversed(list(zip(take, flags))):
                    if not ok:
                        queue.appendleft(req)
                progress = progress or any(flags)
                if not all(flags):
                    break              # pool pressure: decode frees pages
            if queue and not self.active_count() and not progress:
                stall += 1
                if stall > self.admission_retry_limit:
                    self.rejected.append(queue.popleft())
                    stall = 0
                continue               # nothing live: a round would be
            stall = 0                  # all-masked, retry admission instead
            try:
                res = self.collect(self.dispatch_round())
            except InjectedFault:
                continue               # dropped round: state untouched
            done.extend((req, toks) for req, toks, _ in res.finished)
        return done
