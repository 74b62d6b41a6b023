"""Fused Pallas paged-attention kernels: page-table-aware decode + scatter.

The serving hot path (PRs 3-4) reads the paged KV-cache with a jnp gather
that materialises every sequence's full logical window as a dense
``[C, bucket, Hkv, D]`` tensor per decode step — O(bucket) HBM traffic per
emitted token, regardless of how many tokens are actually live.  That is
exactly the "redundant data movement" tax the paper's sequential-transfer
mode eliminates for the risk pipeline; these kernels eliminate it for
serving by reading pages *in place* through the page table:

* :func:`paged_attention_decode_pallas` — vLLM-style fused decode read.
  Grid ``(C, NB)`` with the page axis innermost/sequential; the page table
  and per-row positions ride a :class:`pltpu.PrefetchScalarGridSpec` so each
  K/V/position BlockSpec maps grid cell ``(c, j)`` straight to physical page
  ``page_table[c, j]`` of the pool — the indirection happens in the index
  map, before the block's HBM->VMEM DMA issues, so only the pages a row
  actually references are ever touched and no dense per-sequence KV exists
  at any point.  Online softmax (flash-style running ``m``/``l``/``acc`` in
  VMEM scratch) accumulates across pages; SENTINEL/TRASH pages are masked
  by construction because their position plane holds ``POS_SENTINEL``,
  which fails the ``kpos <= pos`` validity test in-kernel.
* :func:`paged_prefill_scatter_pallas` — admission-time scatter-write.
  Grid ``(n_stages, nb)``; the destination BlockSpec maps block ``j`` to
  physical page ``pages[j]`` and the pool is aliased input->output
  (``input_output_aliases``), so freshly prefilled KV lands directly in its
  allocated pages (cast to the pool dtype in-kernel) without the separate
  materialise-then-``at[].set`` hop, and untouched pages are never copied.

Numerics: the decode kernel mirrors :func:`repro.kernels.ref.
paged_attention_decode_ref` — same f32 score accumulation, the same
``-1e30`` mask bias added to the scores, the same bf16->f32 cache casts —
but the softmax is the online reassociation, so outputs agree to float32
rounding (~1e-6 relative), not bitwise; greedy decode is token-exact in
practice and ``tests/test_paged_attention.py`` locks both levels in.  The
scatter kernel performs no arithmetic beyond the storage cast and is
bit-exact with the jnp path.

``interpret`` defaults to the platform's choice
(:func:`repro.kernels.ops.resolve_interpret`): compiled on a TPU, where the
index-mapped DMAs are the point, and interpreted on the CPU, where wall time
is an emulation artefact and only correctness is checked.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from repro.kernels.ops import resolve_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode: stream pages through the page table, online softmax across pages
# ---------------------------------------------------------------------------
def _decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   window: Optional[int], n_blocks: int, hkv: int, rep: int):
    j = pl.program_id(1)
    c = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[c]
    d = q_ref.shape[-1]
    q = q_ref[0].astype(jnp.float32).reshape(hkv, rep, d)   # (Hkv, rep, D)
    k = k_ref[0].astype(jnp.float32)                        # (P, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    kpos = kpos_ref[...]                                    # (1, P)

    s = jnp.einsum("krd,pkd->krp", q, k,
                   preferred_element_type=jnp.float32) * scale
    # same mask construction as the gather path: a -1e30 *bias* added to the
    # scores (absorbed exactly in f32), validity from the page's position
    # plane — SENTINEL/TRASH pages carry POS_SENTINEL and always fail
    valid = kpos <= pos
    if window is not None:
        valid &= kpos > pos - window
    s = s + jnp.where(valid, 0.0, NEG_INF)[None]

    m_prev, l_prev, acc = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * alpha + p.sum(axis=-1)
    acc_scr[...] = acc * alpha[..., None] + jnp.einsum(
        "krp,pkd->krd", p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        # all-masked rows degenerate to a uniform average (l == L), exactly
        # like full softmax over an all-(-1e30) row; l == 0 cannot happen
        # but is guarded like the flash kernel
        l_safe = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0] = (acc_scr[...] / l_safe[..., None]).reshape(
            hkv * rep, d).astype(o_ref.dtype)


def paged_attention_decode_pallas(q, k_pool, v_pool, pos_pool, page_table,
                                  positions, *, window: Optional[int] = None,
                                  interpret: Optional[bool] = None):
    """Fused single-token GQA decode read against a paged KV pool.

    q: (C, H, D) compute dtype (already roped, this step's K/V already
    scattered into the pool); k_pool/v_pool: (NP, P, Hkv, D) storage dtype;
    pos_pool: (NP, P) int32 absolute positions (POS_SENTINEL marks
    invalid); page_table: (C, NB) int32; positions: (C,) int32 absolute
    position of each row's new token.  Returns (C, H, D) float32.

    Each row streams only the NB pages its table names; table padding
    points at the SENTINEL page whose positions mask it out, so ragged
    rings need no per-row block count.  The position plane is read as
    (NP, 1, P) so each page's block is a whole (1, P) tile.
    """
    C, H, D = q.shape
    NP, P, Hkv, _ = k_pool.shape
    NB = page_table.shape[1]
    rep = H // Hkv
    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(D), window=window,
        n_blocks=NB, hkv=Hkv, rep=rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # page_table, positions
        grid=(C, NB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda c, j, pt, ps: (c, 0, 0)),
            pl.BlockSpec((1, P, Hkv, D),
                         lambda c, j, pt, ps: (pt[c, j], 0, 0, 0)),
            pl.BlockSpec((1, P, Hkv, D),
                         lambda c, j, pt, ps: (pt[c, j], 0, 0, 0)),
            pl.BlockSpec((None, 1, P), lambda c, j, pt, ps: (pt[c, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda c, j, pt, ps: (c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep), jnp.float32),
            pltpu.VMEM((Hkv, rep), jnp.float32),
            pltpu.VMEM((Hkv, rep, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(page_table, positions, q, k_pool, v_pool, pos_pool.reshape(NP, 1, P))


# ---------------------------------------------------------------------------
# prefill: scatter freshly computed KV straight into allocated pages
# ---------------------------------------------------------------------------
def _scatter_kernel(pages_ref, kv_ref, pool_in_ref, pool_out_ref):
    del pages_ref, pool_in_ref           # routing happens in the index maps
    pool_out_ref[...] = kv_ref[...].astype(pool_out_ref.dtype)


def paged_prefill_scatter_pallas(pool, pages, values, *,
                                 interpret: Optional[bool] = None):
    """Write prefill KV blocks into their allocated physical pages.

    pool: (S, NP, P, Hkv, D) storage dtype; pages: (nb,) int32 distinct
    non-reserved page ids; values: (S, nb, P, Hkv, D) compute dtype.
    Returns the pool with ``pool[:, pages[j]] = values[:, j]`` (cast to the
    pool dtype); every other page is bit-untouched.  The pool is aliased
    input->output, so under jit (with the state donated, as the admission
    jit does) the write happens in place — page-block-granular stores, no
    pool copy and no dense scatter intermediate.
    """
    S, _, P, Hkv, D = pool.shape
    nb = pages.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,           # pages
        grid=(S, nb),
        in_specs=[
            pl.BlockSpec((1, 1, P, Hkv, D),
                         lambda i, j, pr: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, P, Hkv, D),
                         lambda i, j, pr: (i, pr[j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, P, Hkv, D),
                               lambda i, j, pr: (i, pr[j], 0, 0, 0)),
    )
    return pl.pallas_call(
        _scatter_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},     # pool (after the scalar operand)
        interpret=resolve_interpret(interpret),
    )(pages, values, pool)


# ---------------------------------------------------------------------------
# mesh dispatch: per-shard kernel invocations along the KV-head axis
# ---------------------------------------------------------------------------
# pallas_call has no GSPMD partitioning rules, so under a mesh the kernels run
# inside shard_map.  Three shapes cover every arch in configs/:
#   * GQA/MHA with Hkv % model-extent == 0 — pools and q both head-sharded;
#     contiguous query-head blocks (H/n = rep·Hkv/n) land exactly on their
#     kv-head group, so each shard is a self-contained decode and the merge
#     is the out-spec all-gather.  No psum: bitwise with the unsharded call.
#   * MQA (Hkv == 1) — pools replicated, q sharded on H; same all-gather.
#   * otherwise — fully replicated specs (every device runs the whole grid).

def _model_axis(sh) -> int:
    """Extent of the "model" mesh axis under ``sh``, 1 when off-mesh."""
    if sh is None or sh.mesh is None or "model" not in sh.mesh.axis_names:
        return 1
    return sh.mesh.shape["model"]


def paged_attention_decode_sharded(q, k_pool, v_pool, pos_pool, page_table,
                                   positions, sh, *,
                                   window: Optional[int] = None,
                                   interpret: Optional[bool] = None):
    """:func:`paged_attention_decode_pallas` partitioned along KV heads."""
    if _model_axis(sh) == 1:
        return paged_attention_decode_pallas(
            q, k_pool, v_pool, pos_pool, page_table, positions,
            window=window, interpret=interpret)
    P = PartitionSpec
    H, Hkv = q.shape[1], k_pool.shape[2]
    if sh.extent("kv", Hkv) > 1:
        q_spec, pool_spec = P(None, "model", None), P(None, None, "model", None)
        out_spec = P(None, "model", None)
    elif Hkv == 1 and sh.extent("heads", H) > 1:
        q_spec, pool_spec = P(None, "model", None), P()
        out_spec = P(None, "model", None)
    else:
        q_spec = pool_spec = out_spec = P()
    fn = jax.shard_map(
        functools.partial(paged_attention_decode_pallas,
                          window=window, interpret=interpret),
        mesh=sh.mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(), P(), P()),
        out_specs=out_spec, check_vma=False)
    return fn(q, k_pool, v_pool, pos_pool, page_table, positions)


def paged_prefill_scatter_sharded(pool, pages, values, sh, *,
                                  interpret: Optional[bool] = None):
    """:func:`paged_prefill_scatter_pallas` partitioned along KV heads."""
    if _model_axis(sh) == 1:
        return paged_prefill_scatter_pallas(pool, pages, values,
                                            interpret=interpret)
    P = PartitionSpec
    Hkv = pool.shape[3]
    if sh.extent("kv", Hkv) > 1:
        kv_spec = P(None, None, None, "model", None)
    else:
        kv_spec = P()
    fn = jax.shard_map(
        functools.partial(paged_prefill_scatter_pallas, interpret=interpret),
        mesh=sh.mesh,
        in_specs=(kv_spec, P(), kv_spec),
        out_specs=kv_spec, check_vma=False)
    return fn(pool, pages, values)
