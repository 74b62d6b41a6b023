"""Paged KV-cache for continuous batching (vLLM-style, JAX-functional).

The slot-based serving paths keep one dense KV cache per padded batch; a
batch's cache lives and dies with its dispatch, so short requests pay for the
longest row and the device idles while a finished batch's tail rows pad out.
:class:`PagedKVCache` breaks the cache into fixed-size *pages* drawn from a
shared pool so a persistent slot table (see :mod:`repro.serving.continuous`)
can admit and retire requests independently:

* **fixed-size pages** — the K/V pool per attention sublayer is
  ``(n_stages, num_pages, page_size, Hkv, D)``; a shared position pool
  ``(num_pages, page_size)`` carries the absolute token position of every
  cache entry (the validity source for the attention mask, exactly like the
  dense cache's ``pos`` plane).
* **per-sequence page tables** — ``(capacity, max_blocks)`` int32 mapping a
  slot's logical cache blocks to physical pages.  Unused blocks point at the
  reserved ``SENTINEL`` page whose positions stay at ``POS_SENTINEL`` so
  gathered padding is always masked out.
* **free-list allocation / eviction** — a host-side LIFO free list; admission
  takes ``blocks_for(ring_len)`` pages, retirement returns them.  LIFO makes
  page reuse immediate, which the eviction tests exploit.  Allocation
  failure (pool pressure) is a soft "not now": the request stays queued.
  Re-allocating a slot that still owns pages raises (it would silently leak
  the old pages off both the free list and the owned map).
* **refcounted prefix sharing (vLLM-style block sharing)** — every page
  carries a reference count; a host-side prefix trie maps the *chain key* of
  each page-aligned token block (the bytes of the whole padded prompt up to
  and including that block — KV at position ``j`` depends on every token
  ``<= j``, so block identity requires full-prefix identity) to the physical
  page holding its KV.  Admission looks up the longest full-block prefix of
  the new request's padded prompt and maps those blocks onto the existing
  pages (:meth:`PagedKVCache.alloc_shared`), allocating fresh pages only for
  the unshared suffix.  Because prefill is deterministic and row-independent,
  a shared page is bitwise what the new request's own prefill would have
  written, so greedy decode stays token-exact.
* **copy-on-write** — the decode ring writes back into prompt blocks
  (logical slot ``pos % ring``), so the first write into a block whose page
  is shared (refcount > 1) forks it: a fresh page is allocated, the engine
  copies the old page's K/V + positions device-side and remaps only the
  writer's page-table slot (:meth:`PagedKVCache.note_write`).  A sole-owner
  write into a trie-registered (pristine) page optionally *preserves* the
  pristine copy the same way — the old page stays in the trie as a cached,
  refcount-0 page that later identical prefixes can re-share, and that the
  allocator evicts (leaf-most chain entry first) when the free list runs
  dry.  Preservation is *reuse-aware* by default: a pristine page is only
  worth a copy once its chain has recorded at least one sharing hit
  (``_hits``), so share-nothing traffic registers its blocks but never pays
  the one-page-copy-per-admission churn (``require_hit=False`` restores the
  PR-4 always-preserve behaviour for A/B).  Forks can never deadlock:
  ``cow_reserve`` counts the *mandatory* forks outstanding — pending
  first-writes whose page is currently multi-referenced (refcount > 1) —
  and every allocation keeps ``available() >= cow_reserve``.  The reserve
  is derived from actual sharer counts, not one page per to-be-written
  block (the PR-4 coarse charge), so admission no longer rejects requests
  whose writes target exclusively owned pages the pool can in fact hold;
  an admission that *shares* pages picks up the reserve its new sharers
  impose (both on its own pending writes and on other slots' pending
  writes into the pages it is joining).
* **gather/scatter attention reads, two backends** — :func:`paged_attention_
  decode` writes the new token's K/V at ``(page, offset)`` per row and reads
  the logical window through :func:`paged_attend`, which dispatches on
  ``backend``: ``"jnp"`` gathers the window into a dense ``[C, NB*P, Hkv,
  D]`` view (the PR-3 path, kept as the A/B baseline and numerics oracle —
  O(bucket) bytes per emitted token), ``"pallas"`` streams page-sized KV
  blocks directly from the pool inside a fused kernel (page-table indexing
  in the kernel grid's index maps, online softmax across pages — O(live
  pages) bytes, no dense KV ever materialised; see :mod:`repro.kernels.
  paged_attention`).  Admission's KV writes go through :func:`paged_scatter`
  with the same switch (dense ``at[].set`` vs an aliased page-granular
  scatter kernel).  Either way the decode step has a single static shape
  regardless of the prompt-length mix (shape-stable: one compile).

State kinds (PR 9): the pool is no longer attention-only.  Each arch
registers a tuple of :class:`StateKind` descriptors (:func:`state_kinds`):

* ``attn`` — the refcounted/CoW/prefix-shared page space above, bitwise
  unchanged for pure-attention archs;
* ``cross`` — encoder-decoder cross-attention KV, paged into a *separate*
  page space (``cross_blocks`` per slot, written once at admission, read-only
  thereafter: no refcounts, no CoW, no trie — every admission takes a fresh
  private row and retirement returns it).  Pool dtype is the compute dtype,
  matching the blocking engine's prefill output bitwise;
* ``ssm`` — SSM/hybrid slot state is *not* paged (it lives dense in the slot
  table) but is checkpointable as fixed-width per-slot records
  (:func:`repro.models.ssm.checkpoint_slot_state`), so SSM rows participate
  in swap-preemption through the same per-kind host ledger.

The two-tier conservation audit extends per kind: ``assert_conserved``
accepts either the historical int (attention blocks only) or a
``{"attn": n, "cross": n, "ssm": n}`` dict audited against the per-kind
``swapped_by_kind()`` ledger and the swap store's ``pages_by_kind()``.

Sliding-window archs share prefixes through *window-phase* chain keys:
``chain_keys(padded, ring=...)`` emits one key per ring block tagged with
``(ring, window base, block)`` — prefill clips the cache to the last
``ring`` positions, so ring block ``b`` holds absolute positions ``base +
b*P .. base + (b+1)*P - 1`` (``base = bucket - ring``) and two requests may
share it only when bucket, ring and every token through the block's last
position agree.  Non-windowed archs keep the historical untagged keys
byte-for-byte.

Masked (inactive) rows redirect their writes to the reserved ``TRASH`` page,
which no active row's page table ever references — a retired slot's stale
page table can therefore neither corrupt pages reallocated to newer requests
nor resurrect stale positions.

Conservation contract (the allocator's audit, asserted by the property
tests): every non-reserved page is exactly one of *free* (on the free list),
*cached* (refcount 0 but trie-registered, reusable and evictable) or *live*
(refcount > 0), with ``free + cached + live == num_pages - RESERVED``; each
page's refcount equals the number of (slot, block) page-table references to
it; and ``available() = free + cached >= cow_reserve`` so every mandatory
copy-on-write fork is guaranteed a page.  Without sharing (no registration)
this degenerates to the PR-3 contract ``free + sum(owned) == num_pages -
RESERVED``.  Preemption (see :mod:`repro.serving.swap`) adds a *host tier*
on top without disturbing the device invariant: a swap-out frees the
victim's device pages through the ordinary accounting and records its
private blocks in the ``swapped_pages`` ledger, which
``assert_conserved(host_pages=...)`` audits against the swap store.

Exactness contract: the dense decode path (:func:`repro.models.layers.
apply_attention_decode`) treats a prefix cache of length ``s_c`` as a ring —
token ``pos`` lands in slot ``pos % s_c`` — and masks validity with
``kpos <= pos`` (plus the sliding window).  The paged read/write replicates
that ring slot-for-slot (logical slot ``j`` holds exactly what dense slot
``j`` holds, in the same order after the gather's reshape), with the same
bf16 storage casts, einsum equations and mask constants, so greedy decode
through the paged path is token-exact with ``ServingEngine.generate`` on the
same padded prompt (``tests/test_continuous.py`` locks this in, including
after pages have been freed and reused).
"""
from __future__ import annotations

from typing import (Dict, Iterable, List, NamedTuple, Optional, Set, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ATTN, ArchConfig
from repro.distributed.sharding import Sharder
from repro.models.layers import _project_qkv, apply_rope
from repro.obs.telemetry import get_telemetry

POS_SENTINEL = 2 ** 30     # matches init_kv_cache's "empty slot" position


def attn_subs(cfg: ArchConfig) -> List[str]:
    """Names of the attention sublayers in one stage (``sub{i}``)."""
    sched = cfg.block_schedule()[:cfg.stage_period]
    return [f"sub{i}" for i, (mixer, _) in enumerate(sched) if mixer == ATTN]


def ssm_subs(cfg: ArchConfig) -> List[str]:
    """Names of the SSM sublayers in one stage (``sub{i}``)."""
    sched = cfg.block_schedule()[:cfg.stage_period]
    return [f"sub{i}" for i, (mixer, _) in enumerate(sched) if mixer != ATTN]


class StateKind(NamedTuple):
    """One kind of per-request serving state the pool accounts for.

    ``paged`` — lives in a shared device page space (attention KV in the
    refcounted/CoW space, cross-attention KV in its private space);
    ``swappable`` — has a fixed-width host snapshot representation, so rows
    carrying it can be preemption victims.
    """
    name: str
    paged: bool
    swappable: bool


def state_kinds(cfg: ArchConfig) -> Tuple[StateKind, ...]:
    """The state kinds an arch's slot rows carry, in canonical order.

    Every kind is currently swappable — attention/cross pages snapshot as
    page blocks, SSM states as fixed-width per-slot records — which is what
    lifts the old "SSM rows are never victims" restriction.
    """
    kinds: List[StateKind] = []
    if attn_subs(cfg):
        kinds.append(StateKind("attn", paged=True, swappable=True))
    if cfg.enc_dec:
        kinds.append(StateKind("cross", paged=True, swappable=True))
    if ssm_subs(cfg):
        kinds.append(StateKind("ssm", paged=False, swappable=True))
    return tuple(kinds)


class PagedKVCache:
    """Page pool + per-slot page tables + host free list / refcounts / trie.

    Device state (pools / position pool / page tables) is *built* here but
    owned functionally by the engine's state pytree — every jitted update
    returns new arrays.  This class keeps the host-side truth: which pages
    are free, cached or live, each page's refcount, the prefix trie, the
    copy-on-write reserve, and the allocation/sharing/reuse counters the
    eviction and sharing tests assert on.  It never touches device arrays:
    the engine applies the device-side half of every fork/remap this class
    decides (see :meth:`note_write`).
    """

    SENTINEL = 0           # page-table padding: never written, never valid
    TRASH = 1              # masked rows' write target: never read as valid
    RESERVED = 2

    def __init__(self, cfg: ArchConfig, capacity: int, page_size: int,
                 max_blocks: int, num_pages: Optional[int] = None,
                 cross_blocks: int = 0):
        self.cfg = cfg
        self.capacity = capacity
        self.page_size = page_size
        self.max_blocks = max(max_blocks, 1)
        self.attn_subs = attn_subs(cfg)
        self.state_kinds = state_kinds(cfg)
        # cross-attention page space: per-request, written once at admission,
        # read-only thereafter — so it needs no refcounts, trie or CoW, just
        # its own free list.  Sized to hold every slot's row plus the two
        # reserved pages (SENTINEL for vacated page-table rows).
        self.cross_blocks = int(cross_blocks)
        self.num_cross_pages = (self.RESERVED + capacity * self.cross_blocks
                                if self.cross_blocks else 0)
        self._cross_free: List[int] = list(
            range(self.num_cross_pages - 1, self.RESERVED - 1, -1))
        self._cross_owned: Dict[int, List[int]] = {}
        if num_pages is None:
            num_pages = self.RESERVED + capacity * self.max_blocks
        if num_pages < self.RESERVED + self.max_blocks:
            raise ValueError("num_pages cannot hold even one full sequence")
        self.num_pages = num_pages
        # LIFO free list: freshly freed pages are reallocated first
        self._free: List[int] = list(range(num_pages - 1, self.RESERVED - 1,
                                           -1))
        self._owned: Dict[int, List[int]] = {}
        self._ref: Dict[int, int] = {}          # page -> live slot references
        self._prefix: Dict[bytes, int] = {}     # block chain key -> page
        self._page_key: Dict[int, bytes] = {}   # inverse of _prefix
        # refcount-0 but trie-registered pages: page -> (chain depth, age)
        self._cached: Dict[int, Tuple[int, int]] = {}
        self._cache_seq = 0
        # slot -> block indices not yet first-written (each may need a fork)
        self._pending: Dict[int, Set[int]] = {}
        # page -> sharing hits recorded while trie-registered (cleared on
        # unregister): the evidence the reuse-aware preserve policy needs
        self._hits: Dict[int, int] = {}
        self._ever_used: set = set()
        self.pages_allocated = 0
        self.pages_reused = 0
        self.pages_shared = 0
        self.cross_pages_allocated = 0
        self.cow_forks = 0
        self.pristine_forks = 0
        # host tier (preemption swap): page blocks whose only copy lives in
        # the host-side swap store right now.  Device conservation is
        # untouched by swapping — a victim's device pages go through the
        # ordinary free() accounting — but the *two-tier* audit
        # (assert_conserved(host_pages=...)) checks this ledger against the
        # store, so a swap record can neither leak nor double-count blocks.
        # Per state kind: ``swapped_pages`` keeps its historical meaning
        # (attention blocks), cross pages and SSM records get their own
        # ledgers — audited per kind by assert_conserved(host_pages=dict).
        self.swapped_pages = 0
        self.swapped_cross = 0
        self.swapped_state = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_drops = 0
        # telemetry plane: every host counter above is mirrored as a
        # ``kv.*`` metric.  The owning engine re-points this at its own
        # plane; standalone pools report to the global one.
        self.tel = get_telemetry(None)

    # ------------------------------------------------------------------
    # host-side allocator
    # ------------------------------------------------------------------
    def blocks_for(self, ring_len: int) -> int:
        return -(-ring_len // self.page_size)        # ceil div

    def free_pages(self) -> int:
        return len(self._free)

    def cached_pages(self) -> int:
        return len(self._cached)

    def available(self) -> int:
        """Pages an allocation can draw on: free plus evictable cached."""
        return len(self._free) + len(self._cached)

    def ref(self, page: int) -> int:
        return self._ref.get(page, 0)

    def hits(self, page: int) -> int:
        """Sharing hits recorded against ``page`` while trie-registered."""
        return self._hits.get(page, 0)

    @property
    def cow_reserve(self) -> int:
        """Headroom the allocator must keep for *mandatory* copy-on-write
        forks: pending first-writes whose page is multi-referenced right
        now.  Derived from actual sharer counts (a pending write into an
        exclusively owned page costs nothing — if it is registered, the
        write merely unregisters or optionally preserves it, and
        preservation moves a page from free to cached without shrinking
        ``available()``)."""
        need = 0
        for slot, blks in self._pending.items():
            pages = self._owned.get(slot, ())
            for b in blks:
                if self._ref.get(pages[b], 0) > 1:
                    need += 1
        return need

    def chain_keys(self, padded: np.ndarray, ring: Optional[int] = None,
                   salt: bytes = b"") -> List[bytes]:
        """Chain key per page block of a padded prompt: the bytes of the
        whole prompt up to and including the block's last cached position,
        so two requests share a block only when every earlier token (padding
        included) agrees — exactly the condition under which the block's KV
        is bitwise equal.

        ``ring`` (sliding-window archs) keys by *(content chain, window
        phase)*: prefill clips the cache to the last ``ring`` positions, so
        ring block ``b`` holds absolute positions ``base + b*P`` onward
        (``base = bucket - ring``) and its key is the prompt bytes through
        the block's last cached position plus a ``(ring, base, block)`` tag
        — requests with a different bucket or window hold different
        positions in the "same" ring block and must never collide.  When
        the ring covers the whole bucket (``ring is None`` or ``ring >=
        bucket``) keys are byte-identical to the historical untagged form.

        ``salt`` prefixes every key (non-token prefill inputs — encoder
        frames, vision patch embeds — change the KV a block holds, so they
        must be part of block identity)."""
        t = np.ascontiguousarray(np.asarray(padded, np.int32).reshape(-1))
        p = self.page_size
        bucket = t.size
        if ring is None or ring >= bucket:
            return [salt + t[:(b + 1) * p].tobytes()
                    for b in range(bucket // p)]
        base = bucket - ring
        return [salt + t[:min(base + (b + 1) * p, bucket)].tobytes()
                + b"|w%d:%d:%d" % (ring, base, b)
                for b in range(-(-ring // p))]

    def lookup_chain(self, keys: Iterable[bytes]) -> List[int]:
        """Pages of the longest registered full-block prefix of ``keys``."""
        pages: List[int] = []
        for key in keys:
            page = self._prefix.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def alloc(self, slot: int, n_blocks: int) -> Optional[np.ndarray]:
        """Take ``n_blocks`` fresh pages for ``slot``; None if the pool is
        short (the caller leaves the request queued and retries after
        eviction)."""
        return self.alloc_shared(slot, [], n_blocks, ())

    def alloc_shared(self, slot: int, shared: List[int], n_fresh: int,
                     will_write: Iterable[int]) -> Optional[np.ndarray]:
        """Build ``slot``'s page row: ``shared`` (a prefix of existing pages,
        refcounts incremented) followed by ``n_fresh`` fresh pages.

        ``will_write`` are the block indices the request will write during
        its decode; ``cow_reserve`` headroom is charged only for those that
        land on *shared* pages (refcount > 1 once this admission joins) —
        the mandatory forks — plus any pending writes of other slots whose
        page this admission newly makes shared.  Writes into exclusively
        owned pages are free: the PR-4 coarse one-page-per-block charge
        rejected admissions the pool could in fact hold.  Returns None
        (nothing changed) when the pool cannot cover ``n_fresh`` plus the
        post-admission reserve — the request stays queued.
        """
        if slot in self._owned:
            # silently overwriting would leak the old pages off both the
            # free list and the owned map (PR-3 bug); the engine retires a
            # slot before reusing it, so this is always a caller bug
            raise ValueError(
                f"slot {slot} already owns pages; free() it before "
                f"re-allocating")
        will_write = set(will_write)
        # reviving a cached shared page takes it out of the evictable set,
        # so it costs availability exactly like a fresh page does
        revived = sum(self._ref.get(p, 0) == 0 for p in shared)
        # post-admission reserve: every pending write (existing slots' and
        # this one's) whose page will be multi-referenced after the shared
        # refcounts are bumped needs a guaranteed fork page
        shared_set = set(shared)
        reserve = 0
        for s2, blks in self._pending.items():
            pages2 = self._owned.get(s2, ())
            for b in blks:
                p = pages2[b]
                if self._ref.get(p, 0) + (p in shared_set) > 1:
                    reserve += 1
        for b in will_write:
            if b < len(shared) and self._ref.get(shared[b], 0) + 1 > 1:
                reserve += 1
        if self.available() - n_fresh - revived < reserve:
            self.tel.count("kv.alloc_blocked")
            return None
        for p in shared:
            if self._ref.get(p, 0) == 0:        # revive a cached page
                self._cached.pop(p, None)
            self._ref[p] = self._ref.get(p, 0) + 1
            self._hits[p] = self._hits.get(p, 0) + 1
        fresh = [self._take_page() for _ in range(n_fresh)]
        for p in fresh:
            self._ref[p] = 1
        self._owned[slot] = list(shared) + fresh
        self._pending[slot] = will_write
        self.pages_allocated += n_fresh
        self.pages_shared += len(shared)
        tel = self.tel
        if tel.enabled:
            tel.count("kv.pages_allocated", n_fresh)
            if shared:
                tel.count("kv.pages_shared", len(shared))
                tel.count("kv.prefix_hits")
            tel.gauge("kv.free_pages", len(self._free))
            # zero-length span so the pool's activity lands on the trace
            # timeline (parents under the enclosing admission span)
            tel.event("kv.alloc", slot=slot, fresh=n_fresh,
                      shared=len(shared))
        return np.asarray(self._owned[slot], np.int32)

    def alloc_cross(self, slot: int) -> Optional[np.ndarray]:
        """Take ``slot``'s row of ``cross_blocks`` pages from the cross page
        space; None when the space is short (the request stays queued).
        Cross pages are private and written once, so there is nothing to
        share and no reserve to keep."""
        if slot in self._cross_owned:
            raise ValueError(
                f"slot {slot} already owns cross pages; free() it before "
                f"re-allocating")
        if len(self._cross_free) < self.cross_blocks:
            self.tel.count("kv.alloc_blocked")
            return None
        pages = [self._cross_free.pop() for _ in range(self.cross_blocks)]
        self._cross_owned[slot] = pages
        self.cross_pages_allocated += len(pages)
        if self.tel.enabled:
            self.tel.count("kv.cross.pages_allocated", len(pages))
        return np.asarray(pages, np.int32)

    def cross_pages_of(self, slot: int) -> List[int]:
        """The slot's cross page row (block order), read-only."""
        return list(self._cross_owned.get(slot, ()))

    def _free_cross(self, slot: int) -> int:
        pages = self._cross_owned.pop(slot, None)
        if not pages:
            return 0
        self._cross_free.extend(pages)
        if self.tel.enabled:
            self.tel.count("kv.cross.pages_freed", len(pages))
        return len(pages)

    def register(self, slot: int, keys: List[bytes]) -> None:
        """Enter ``slot``'s pages into the prefix trie under their chain
        keys.  First registration wins (duplicate-content pages from one
        admission batch stay private); already-shared prefix pages are
        naturally skipped because their key is present."""
        pages = self._owned.get(slot, [])
        for blk, key in enumerate(keys):
            if blk >= len(pages):
                break
            page = pages[blk]
            if key in self._prefix or page in self._page_key:
                continue
            self._prefix[key] = page
            self._page_key[page] = key

    def note_write(self, slot: int, blk: int, preserve: bool = True,
                   require_hit: bool = True) -> Optional[Tuple[int, int]]:
        """Resolve ``slot``'s upcoming decode write into block ``blk``.

        Returns ``(src, dst)`` when the engine must copy page ``src`` to the
        freshly mapped page ``dst`` (device-side) before the round runs:

        * refcount > 1 — mandatory copy-on-write fork (other requests, or
          the trie's cached readers, still read ``src``);
        * sole owner of a trie-registered page with ``preserve``, a free
          page at hand, and — under the default reuse-aware policy
          (``require_hit``) — at least one sharing hit recorded against the
          page: pristine-preserving fork, ``src`` stays in the trie as a
          cached page so later identical prefixes can re-share it.  Without
          a recorded hit there is no evidence the chain is ever re-used, so
          the copy is skipped (the share-nothing fast path);
          ``require_hit=False`` restores the PR-4 preserve-always policy.

        Otherwise returns None; a sole-owner write into a registered page
        that is not preserved simply unregisters it (its content is about
        to diverge from its chain key).  Idempotent per block: after the
        first resolution the slot owns the page exclusively and
        unregistered, so later ring wraps fall through.
        """
        pages = self._owned.get(slot)
        if pages is None:
            return None
        page = pages[blk]
        pending = self._pending.get(slot)
        if pending is not None:
            pending.discard(blk)
        if self._ref.get(page, 0) > 1:
            dst = self._take_page()
            self._ref[page] -= 1
            self._ref[dst] = 1
            pages[blk] = dst
            self.cow_forks += 1
            self.pages_allocated += 1
            self.tel.count("kv.cow_forks")
            return page, dst
        if page in self._page_key:
            if (preserve and self._free
                    and (not require_hit or self._hits.get(page, 0) > 0)):
                # moves a page free -> live and a page live -> cached, so
                # available() (free + cached) is unchanged: preservation
                # can never eat into the mandatory-fork reserve
                dst = self._free.pop()
                self.pages_reused += dst in self._ever_used
                self._ever_used.add(dst)
                self._ref[dst] = 1
                pages[blk] = dst
                self._ref[page] = 0
                self._cached[page] = (blk, self._cache_seq)
                self._cache_seq += 1
                self.pristine_forks += 1
                self.pages_allocated += 1
                self.tel.count("kv.pristine_forks")
                return page, dst
            self._unregister(page)
        return None

    def owned_pages(self, slot: int) -> List[int]:
        """The slot's current page row (block order), read-only."""
        return list(self._owned.get(slot, ()))

    def private_blocks(self, slot: int) -> List[int]:
        """Block indices whose page content exists nowhere but this slot's
        row: refcount 1 and not trie-registered.  These are a preemption
        victim's *private suffix* — the only blocks a swap-out actually
        moves to the host tier.  Every other block's content stays
        device-resident after the victim's refcounts drop: shared pages
        keep serving their other readers, and registered pristine pages
        linger as evictable cache."""
        return [b for b, p in enumerate(self._owned.get(slot, ()))
                if self._ref.get(p, 0) == 1 and p not in self._page_key]

    def trie_keys(self) -> List[bytes]:
        """Every chain key currently registered in the prefix trie, sorted
        — recorded in engine checkpoints so recovery can audit that the
        rebuilt pool re-registered each restored slot's live chains (the
        refcount-0 cached tail is a cache and is deliberately *not* part
        of the recovery contract)."""
        return sorted(self._prefix)

    def swapped_by_kind(self) -> Dict[str, int]:
        """Host-tier ledger per state kind: attention page blocks, cross
        page blocks, SSM state records (one per SSM sublayer per victim)."""
        return {"attn": self.swapped_pages, "cross": self.swapped_cross,
                "ssm": self.swapped_state}

    def swap_out(self, slot: int, host_blocks: int, cross_blocks: int = 0,
                 state_records: int = 0) -> int:
        """Preemption swap-out: retire a victim slot's page references —
        exactly :meth:`free` (cross row included), shared prefix pages are
        never pulled out from under their other sequences — and account the
        snapshot against the per-kind host ledger: ``host_blocks`` attention
        page blocks (the victim's private suffix, see
        :meth:`private_blocks`), ``cross_blocks`` cross pages and
        ``state_records`` SSM state records.  The engine snapshots content
        *before* calling this; the allocator only moves the ledger.
        Returns the attention pages whose refcount dropped to 0."""
        released = self.free(slot)
        self.swapped_pages += host_blocks
        self.swapped_cross += cross_blocks
        self.swapped_state += state_records
        self.swap_outs += 1
        self.tel.count("kv.swap_out_blocks", host_blocks)
        if cross_blocks:
            self.tel.count("kv.cross.swap_out_blocks", cross_blocks)
        if state_records:
            self.tel.count("kv.ssm.swap_out_records", state_records)
        self.tel.gauge("kv.swapped_pages", self.swapped_pages)
        return released

    def adopt_swapped(self, host_blocks: int, cross_blocks: int = 0,
                      state_records: int = 0) -> None:
        """Crash recovery: seed the host-tier ledger of a *fresh* pool for
        a checkpointed swap record re-parked in the store without ever
        having been swapped out of this pool instance.  The two-tier
        conservation audit (:meth:`assert_conserved` with ``host_pages``)
        holds from the first post-recovery drain, not only after the
        record's eventual restore."""
        self.swapped_pages += host_blocks
        self.swapped_cross += cross_blocks
        self.swapped_state += state_records
        self.tel.gauge("kv.swapped_pages", self.swapped_pages)

    def swap_in(self, host_blocks: int, restored: bool = True,
                cross_blocks: int = 0, state_records: int = 0) -> None:
        """Account a record's blocks leaving the host tier — either restored
        into fresh device pages / slot rows (``restored``) or dropped with a
        terminally failed swap record."""
        assert self.swapped_pages >= host_blocks, \
            (self.swapped_pages, host_blocks)
        assert self.swapped_cross >= cross_blocks, \
            (self.swapped_cross, cross_blocks)
        assert self.swapped_state >= state_records, \
            (self.swapped_state, state_records)
        self.swapped_pages -= host_blocks
        self.swapped_cross -= cross_blocks
        self.swapped_state -= state_records
        if restored:
            self.swap_ins += 1
            self.tel.count("kv.swap_in_blocks", host_blocks)
        else:
            self.swap_drops += 1
            self.tel.count("kv.swap_drop_blocks", host_blocks)
        self.tel.gauge("kv.swapped_pages", self.swapped_pages)

    def free(self, slot: int) -> int:
        """Retire a slot: decrement its pages' refcounts and return its
        cross row (if any) to the cross free list.  Attention pages
        reaching refcount 0 return to the free list — or stay behind as
        cached (evictable) pristine pages when still trie-registered, so a
        later identical prefix can re-share them.  Returns the number of
        attention pages whose refcount dropped to 0."""
        self._free_cross(slot)
        released = 0
        for blk, page in enumerate(self._owned.pop(slot, [])):
            self._ref[page] -= 1
            if self._ref[page] == 0:
                released += 1
                if page in self._page_key:
                    self._cached[page] = (blk, self._cache_seq)
                    self._cache_seq += 1
                else:
                    self._free.append(page)
        self._pending.pop(slot, None)
        if self.tel.enabled and released:
            self.tel.count("kv.pages_freed", released)
            self.tel.gauge("kv.free_pages", len(self._free))
        return released

    # ------------------------------------------------------------------
    def _unregister(self, page: int) -> None:
        key = self._page_key.pop(page, None)
        if key is not None:
            self._prefix.pop(key, None)
        self._cached.pop(page, None)
        self._hits.pop(page, None)

    def _take_page(self) -> int:
        """Pop a free page; when the free list is dry, evict a cached
        pristine page — leaf-most chain entry first (deepest block, then
        oldest), so short shared prefixes survive longest."""
        if self._free:
            page = self._free.pop()
        else:
            page = max(self._cached,
                       key=lambda q: (self._cached[q][0],
                                      -self._cached[q][1]))
            self._unregister(page)
            self.tel.count("kv.evictions")
        self.pages_reused += page in self._ever_used
        self._ever_used.add(page)
        return page

    # ------------------------------------------------------------------
    def assert_conserved(
            self, host_pages: Optional[Union[int, Dict[str, int]]] = None
    ) -> None:
        """Audit the allocator (tests): page conservation, refcount
        integrity, trie consistency, fork-reserve headroom, and — when a
        cross page space exists — cross-row conservation.

        With ``host_pages`` (the swap store's current block count) the audit
        extends to the host tier: the device invariant must hold unchanged
        — a swapped victim's pages went through the ordinary free/realloc
        accounting — *and* every block the allocator believes is
        host-resident is in the store exactly once, so a swap round-trip
        conserves pages across both tiers.  An int audits the attention
        ledger only (``swapped_pages == host_pages``, the historical form);
        a dict (``store.pages_by_kind()``) audits every kind's ledger."""
        usable = self.num_pages - self.RESERVED
        live = {p for p, r in self._ref.items() if r > 0}
        free_set = set(self._free)
        cached_set = set(self._cached)
        assert len(self._free) == len(free_set), "free list has duplicates"
        assert not (free_set & live), "free page still referenced"
        assert not (free_set & cached_set), "page both free and cached"
        assert not (cached_set & live), "cached page still referenced"
        assert all(p in self._page_key for p in cached_set), \
            "cached page not trie-registered"
        assert len(free_set) + len(cached_set) + len(live) == usable, \
            (len(free_set), len(cached_set), len(live), usable)
        counts: Dict[int, int] = {}
        for pages in self._owned.values():
            for p in pages:
                counts[p] = counts.get(p, 0) + 1
        for p in live | set(counts):
            assert self._ref.get(p, 0) == counts.get(p, 0), \
                (p, self._ref.get(p, 0), counts.get(p, 0))
        for key, p in self._prefix.items():
            assert self._page_key.get(p) == key, "trie inverse out of sync"
        for p in self._hits:
            assert p in self._page_key, "hit count on an unregistered page"
        for slot, blks in self._pending.items():
            assert slot in self._owned, "pending writes on a retired slot"
            assert all(b < len(self._owned[slot]) for b in blks)
        # the refcount-derived reserve (mandatory forks outstanding) must
        # always be coverable, so a copy-on-write fork can never fail
        assert self.available() >= self.cow_reserve, \
            (self.available(), self.cow_reserve)
        if self.cross_blocks:
            cross_live = [p for pages in self._cross_owned.values()
                          for p in pages]
            cross_free = set(self._cross_free)
            assert len(cross_free) == len(self._cross_free), \
                "cross free list has duplicates"
            assert len(set(cross_live)) == len(cross_live), \
                "cross page owned twice"
            assert not (cross_free & set(cross_live)), \
                "cross page both free and owned"
            assert len(cross_free) + len(cross_live) == \
                self.num_cross_pages - self.RESERVED, \
                (len(cross_free), len(cross_live), self.num_cross_pages)
        ledger = self.swapped_by_kind()
        assert all(v >= 0 for v in ledger.values()), ledger
        if host_pages is not None:
            if isinstance(host_pages, dict):
                want = {k: 0 for k in ledger}
                want.update(host_pages)
                assert ledger == want, (ledger, want)
            else:
                assert self.swapped_pages == host_pages, \
                    (self.swapped_pages, host_pages)
        self.tel.count("kv.conservation_checks")

    # ------------------------------------------------------------------
    # device-state constructors (engine holds the results in its pytree)
    # ------------------------------------------------------------------
    def make_page_table(self) -> jax.Array:
        return jnp.full((self.capacity, self.max_blocks), self.SENTINEL,
                        jnp.int32)

    def make_pos_pool(self) -> jax.Array:
        return jnp.full((self.num_pages, self.page_size), POS_SENTINEL,
                        jnp.int32)

    def make_pools(self, n_stages: int) -> Dict[str, Dict[str, jax.Array]]:
        cfg = self.cfg
        shape = (n_stages, self.num_pages, self.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        return {name: {"k": jnp.zeros(shape, jnp.bfloat16),
                       "v": jnp.zeros(shape, jnp.bfloat16)}
                for name in self.attn_subs}

    def make_cross_page_table(self) -> jax.Array:
        return jnp.full((self.capacity, self.cross_blocks), self.SENTINEL,
                        jnp.int32)

    def make_cross_pools(self, n_stages: int) -> Dict[str, jax.Array]:
        """Cross-attention page pool, in the *compute* dtype: the blocking
        engine decodes straight from prefill's cross KV (compute dtype), so
        storing bf16 here would break bitwise parity with it."""
        from repro.models.layers import dtype_of
        cfg = self.cfg
        shape = (n_stages, self.num_cross_pages, self.page_size,
                 cfg.num_kv_heads, cfg.head_dim)
        dt = dtype_of(cfg.compute_dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


# ---------------------------------------------------------------------------
# pure gather/scatter primitives (used inside the jitted decode step)
# ---------------------------------------------------------------------------
def paged_write(pool: jax.Array, pages: jax.Array, offsets: jax.Array,
                values: jax.Array) -> jax.Array:
    """Scatter one entry per row: ``pool[pages[c], offsets[c]] = values[c]``.
    Masked rows all target the TRASH page; their collisions are benign
    because TRASH is never read as valid."""
    return pool.at[pages, offsets].set(values)


BACKENDS = ("jnp", "pallas")


def paged_attend(q: jax.Array, pool: Dict[str, jax.Array],
                 page_table: jax.Array, positions: jax.Array,
                 cfg: ArchConfig, *, kpos: Optional[jax.Array] = None,
                 pos_pool: Optional[jax.Array] = None,
                 backend: str = "jnp", interpret: Optional[bool] = None,
                 sh: Optional[Sharder] = None) -> jax.Array:
    """Paged attention read, backend-switched.

    q: (C, H, D) already-roped queries; pool: {"k","v"} (NP, P, Hkv, D);
    page_table: (C, NB); positions: (C,).  Returns (C, H, D) float32.

    * ``backend="jnp"`` — gather the logical window dense through the page
      table and run the PR-3 reference math (:func:`repro.kernels.ref.
      paged_attention_decode_ref`): bitwise the historical path, O(C * NB *
      P) pool bytes touched per call.  Needs ``kpos`` (the decode step
      pre-gathers it once and shares it across sublayers).
    * ``backend="pallas"`` — the fused kernel (:func:`repro.kernels.
      paged_attention.paged_attention_decode_pallas`): pages stream through
      the grid's index maps, online softmax across pages, no dense KV.
      Needs ``pos_pool`` (positions are read per page, in place, so the
      dense kpos gather is skipped too).  Token-exact with jnp for greedy
      decode; logits agree to f32 rounding (see the kernel module).

    ``sh`` routes the pallas backend through the shard_map dispatch when a
    mesh is active (pallas_call has no GSPMD partitioning rules); the jnp
    backend partitions under plain GSPMD and ignores it.
    """
    if backend == "pallas":
        from repro.kernels.paged_attention import paged_attention_decode_sharded
        return paged_attention_decode_sharded(
            q, pool["k"], pool["v"], pos_pool, page_table, positions, sh,
            window=cfg.sliding_window, interpret=interpret)
    if backend != "jnp":
        raise ValueError(f"backend {backend!r}: must be one of {BACKENDS}")
    from repro.kernels.ref import paged_attention_decode_ref
    return paged_attention_decode_ref(
        q, pool["k"], pool["v"], page_table, positions, kpos=kpos,
        pos_pool=pos_pool, window=cfg.sliding_window)


def paged_scatter(pool: jax.Array, pages: jax.Array, values: jax.Array, *,
                  backend: str = "jnp", interpret: Optional[bool] = None,
                  sh: Optional[Sharder] = None) -> jax.Array:
    """Admission-time KV scatter, backend-switched: write ``values``
    (S, nb, P, Hkv, D) into ``pool`` (S, NP, P, Hkv, D) at ``pages`` (nb,).

    ``"jnp"`` is the dense ``at[].set`` hop; ``"pallas"`` the aliased
    page-granular scatter kernel that writes prefill KV straight into its
    allocated pages (:func:`repro.kernels.paged_attention.
    paged_prefill_scatter_pallas`).  Both cast to the pool dtype and are
    bit-exact with each other."""
    if backend == "pallas":
        from repro.kernels.paged_attention import paged_prefill_scatter_sharded
        return paged_prefill_scatter_sharded(pool, pages, values, sh,
                                             interpret=interpret)
    if backend != "jnp":
        raise ValueError(f"backend {backend!r}: must be one of {BACKENDS}")
    from repro.kernels.ref import paged_scatter_ref
    return paged_scatter_ref(pool, pages, values)


def paged_attention_decode(p, x, pool: Dict[str, jax.Array],
                           page_table: jax.Array, kpos: Optional[jax.Array],
                           write_page: jax.Array, write_off: jax.Array,
                           positions: jax.Array, cfg: ArchConfig,
                           sh: Sharder, *,
                           pos_pool: Optional[jax.Array] = None,
                           backend: str = "jnp",
                           interpret: Optional[bool] = None):
    """Single-token GQA decode against a paged cache (per-row positions).

    Mirrors :func:`repro.models.layers.apply_attention_decode` operation for
    operation (same projections, rope at the row's absolute position, bf16
    cache casts, validity mask ``kpos <= pos`` with optional sliding window,
    identical einsum contractions) — only the cache storage is paged and the
    window read goes through :func:`paged_attend` (``backend`` selects the
    dense gather or the fused page-streaming kernel).  The logical view may
    be longer than a row's ring (page-table padding points at the SENTINEL
    page), but padded entries carry ``POS_SENTINEL`` so their bias is -1e30
    and their softmax weight underflows to exactly 0.

    x: (C, 1, d); kpos: (C, L) gathered positions including this step's
    write (jnp backend; pallas reads positions per page from ``pos_pool``
    instead); positions: (C,) absolute position of the new token.
    Returns (out (C, 1, d), new pool dict).
    """
    cdt_x = x.dtype
    H, D = cfg.num_heads, cfg.head_dim
    C = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, x, cfg, sh)
    if cfg.use_rope:
        q = apply_rope(q, positions[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, positions[:, None], cfg.rope_theta)
    k_pool = paged_write(pool["k"], write_page, write_off,
                         k_new[:, 0].astype(pool["k"].dtype))
    v_pool = paged_write(pool["v"], write_page, write_off,
                         v_new[:, 0].astype(pool["v"].dtype))
    o = paged_attend(q[:, 0], {"k": k_pool, "v": v_pool}, page_table,
                     positions, cfg, kpos=kpos, pos_pool=pos_pool,
                     backend=backend, interpret=interpret, sh=sh)
    # merge the head-sharded attention output with an all-gather (pure data
    # movement, bitwise-safe) before the replicated wo contraction
    o = sh.constrain(o, (None, None, None))
    o = o.reshape(C, 1, H * D).astype(cdt_x)
    from repro.models.layers import dtype_of
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(dtype_of(
        cfg.compute_dtype)))
    return out, {"k": k_pool, "v": v_pool}
