"""Pallas TPU flash-attention forward (GQA, causal, sliding-window).

This is the TPU-target kernel behind the pure-JAX blockwise path in
models/attention_core.py (which serves as its HLO stand-in on CPU and as the
backward via custom-vjp recompute).  Classic FlashAttention-2 schedule:
grid = (B, Hq, q_blocks, kv_blocks) with the kv axis innermost/sequential;
online-softmax stats (m, l) and the output accumulator live in VMEM scratch
across kv steps; Pallas pipelines the next K/V tile's HBM->VMEM DMA against
the current tile's MXU compute — the same DMA/compute overlap the paper
obtains from multi-tenancy, here inside one kernel.

GQA: the K/V BlockSpec index maps query head h to kv head h // (Hq/Hkv), so
grouped heads share K/V tiles without materialising the repeat.

Validated in interpret mode against models.attention_core.naive_attention
(tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_kv: int, n_kv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = i * block_q + jax.lax.iota(jnp.int32, block_q)
    k_pos = j * block_kv + jax.lax.iota(jnp.int32, block_kv)
    ok = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev, acc = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc = acc * alpha[:, None] + jnp.dot(p, v,
                                         preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    @pl.when(j == n_kv - 1)
    def _finalize():
        l_safe = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).
    ``interpret`` defaults to the platform's choice
    (:func:`repro.kernels.ops.resolve_interpret`)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    bq = math.gcd(Sq, block_q)
    bk = math.gcd(Skv, block_kv)
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / math.sqrt(D)

    qh = jnp.moveaxis(q, 2, 1)                       # (B, Hq, Sq, D)
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, block_q=bq, block_kv=bk,
                               n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qh, kh, vh)
    return jnp.moveaxis(out, 1, 2)
