"""Layer library: norms, RoPE, embeddings, GQA attention, SwiGLU MLP.

Every ``init_*`` returns a Boxed tree (value + logical sharding axes); every
``apply_*`` takes the plain value tree plus a :class:`Sharder` for activation
sharding constraints.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.distributed.sharding import Sharder
from repro.models import params as pp
from repro.models.attention_core import blockwise_attention, naive_attention


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def init_rmsnorm(dim: int, dtype) -> Dict[str, pp.Boxed]:
    return {"scale": pp.ones((dim,), dtype, (None,))}


def apply_rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def apply_rmsnorm_heads(scale, x, eps: float = 1e-6):
    """Per-head qk-norm: x (..., D), scale (D,)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    D = x.shape[-1]
    inv = rope_frequencies(D, theta)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * inv[None, :]      # (S, half)
        ang = ang[None, :, None, :]
    else:
        ang = positions[..., None].astype(jnp.float32) * inv             # (B,S,half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    half = dim // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embedding(key, cfg: ArchConfig) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    v = pad_vocab(cfg.vocab_size)
    out = {"embedding": pp.normal(key, (v, cfg.d_model), 0.02, dt,
                                  ("vocab", "fsdp"))}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        out["unembed"] = pp.normal(k2, (cfg.d_model, v),
                                   0.02 / math.sqrt(cfg.d_model), dt,
                                   ("fsdp", "vocab"))
    return out


def apply_embedding(p, tokens: jax.Array, cfg: ArchConfig, sh: Sharder):
    emb = p["embedding"].astype(dtype_of(cfg.compute_dtype))
    x = jnp.take(emb, tokens, axis=0)
    return sh.constrain(x, ("batch", "seq", None))


def apply_unembed(p, x: jax.Array, cfg: ArchConfig, sh: Sharder):
    """Returns fp32 logits over the padded vocab with pad columns masked."""
    if cfg.tie_embeddings:
        w = p["embedding"].astype(dtype_of(cfg.compute_dtype)).T
    else:
        w = p["unembed"].astype(dtype_of(cfg.compute_dtype))
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)
    logits = sh.constrain(logits, ("batch", None, "vocab"))
    v_pad = w.shape[-1]
    if v_pad != cfg.vocab_size:
        col = jnp.arange(v_pad)
        logits = jnp.where(col[None, None, :] < cfg.vocab_size, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ArchConfig, cross: bool = False) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    d, H, Hkv, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    s_in = 0.02
    s_out = 0.02 / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "wq": pp.normal(ks[0], (d, H * D), s_in, dt, ("fsdp", "heads")),
        "wk": pp.normal(ks[1], (d, Hkv * D), s_in, dt, ("fsdp", "kv")),
        "wv": pp.normal(ks[2], (d, Hkv * D), s_in, dt, ("fsdp", "kv")),
        "wo": pp.normal(ks[3], (H * D, d), s_out, dt, ("heads", "fsdp")),
    }
    if cfg.qk_norm:
        p["q_norm"] = pp.ones((D,), dt, (None,))
        p["k_norm"] = pp.ones((D,), dt, (None,))
    return p


def _project_qkv(p, x, x_kv, cfg: ArchConfig, sh: Sharder):
    cdt = dtype_of(cfg.compute_dtype)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, S = x.shape[0], x.shape[1]
    Skv = x_kv.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt)).reshape(B, S, H, D)
    k = jnp.einsum("bsd,dh->bsh", x_kv, p["wk"].astype(cdt)).reshape(B, Skv, Hkv, D)
    v = jnp.einsum("bsd,dh->bsh", x_kv, p["wv"].astype(cdt)).reshape(B, Skv, Hkv, D)
    q = sh.constrain(q, ("batch", None, "heads", None))
    k = sh.constrain(k, ("batch", None, "kv", None))
    v = sh.constrain(v, ("batch", None, "kv", None))
    if cfg.qk_norm:
        q = apply_rmsnorm_heads(p["q_norm"], q)
        k = apply_rmsnorm_heads(p["k_norm"], k)
    return q, k, v


def apply_attention(p, x, cfg: ArchConfig, sh: Sharder, *,
                    positions: Optional[jax.Array] = None,
                    causal: bool = True, return_kv: bool = False):
    """Full-sequence (train / prefill) self-attention."""
    q, k, v = _project_qkv(p, x, x, cfg, sh)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = blockwise_attention(q, k, v, causal=causal, window=cfg.sliding_window)
    o = sh.constrain(o, ("batch", None, "attn_out", None))
    o = o.reshape(x.shape[0], x.shape[1], -1)
    cdt = dtype_of(cfg.compute_dtype)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cdt))
    out = sh.constrain(out, ("batch", "seq", None))
    if return_kv:
        return out, (k, v)
    return out


def apply_cross_attention(p, x, kv_cache: Tuple[jax.Array, jax.Array],
                          cfg: ArchConfig, sh: Sharder) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V (no masking)."""
    cdt = dtype_of(cfg.compute_dtype)
    H, D = cfg.num_heads, cfg.head_dim
    B, S = x.shape[0], x.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt)).reshape(B, S, H, D)
    if cfg.qk_norm:
        q = apply_rmsnorm_heads(p["q_norm"], q)
    k, v = kv_cache
    o = naive_attention(q, k, v, causal=False)
    o = o.reshape(B, S, -1)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cdt))


def precompute_cross_kv(p, enc_out, cfg: ArchConfig, sh: Sharder):
    cdt = dtype_of(cfg.compute_dtype)
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    B, S = enc_out.shape[0], enc_out.shape[1]
    k = jnp.einsum("bsd,dh->bsh", enc_out, p["wk"].astype(cdt)).reshape(B, S, Hkv, D)
    v = jnp.einsum("bsd,dh->bsh", enc_out, p["wv"].astype(cdt)).reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        k = apply_rmsnorm_heads(p["k_norm"], k)
    return k, v


def apply_attention_decode(p, x, cache: Dict[str, jax.Array], cfg: ArchConfig,
                           sh: Sharder, cache_index: jax.Array):
    """Single-token decode with a (possibly ring) KV cache.

    cache: {"k": (B, S_c, Hkv, D), "v": ..., "pos": (B, S_c) absolute positions}
    Returns (out, new_cache).
    """
    cdt = dtype_of(cfg.compute_dtype)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, x, x, cfg, sh)
    # absolute position of the new token
    pos = cache_index.astype(jnp.int32)
    if cfg.use_rope:
        q = apply_rope(q, jnp.full((B, 1), pos), cfg.rope_theta)
        k_new = apply_rope(k_new, jnp.full((B, 1), pos), cfg.rope_theta)
    s_c = cache["k"].shape[1]
    slot = jnp.mod(pos, s_c)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    kpos = jax.lax.dynamic_update_slice(
        cache["pos"], jnp.full((B, 1), pos, cache["pos"].dtype), (0, slot))
    window = cfg.sliding_window
    # validity: positions <= pos and within window if SWA
    valid = kpos[0] <= pos
    if window is not None:
        valid &= kpos[0] > pos - window
    bias_pos = jnp.where(valid, 0.0, -1e30)
    rep = H // Hkv
    qr = q.reshape(B, 1, Hkv, rep, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qr, k.astype(qr.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = s + bias_pos[None, None, None, None, :]
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhrk,bkhd->bqhrd", pattn, v.astype(qr.dtype),
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H * D).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(cdt))
    new_cache = {"k": k, "v": v, "pos": kpos}
    return out, new_cache


def init_kv_cache(cfg: ArchConfig, batch: int, seq_len: int,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    s_c = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    shape = (batch, s_c, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        # empty slots get a far-future position so `kpos <= pos` masks them out
        "pos": jnp.full((batch, s_c), 2 ** 30, jnp.int32),
    }


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s_in = 0.02
    s_out = 0.02 / math.sqrt(2 * max(cfg.num_layers, 1))
    return {
        "w_gate": pp.normal(ks[0], (d, ff), s_in, dt, ("fsdp", "ff")),
        "w_up": pp.normal(ks[1], (d, ff), s_in, dt, ("fsdp", "ff")),
        "w_down": pp.normal(ks[2], (ff, d), s_out, dt, ("ff", "fsdp")),
    }


def apply_mlp(p, x, cfg: ArchConfig, sh: Sharder):
    cdt = dtype_of(cfg.compute_dtype)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cdt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    h = sh.constrain(h, ("batch", None, "ff"))
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cdt))
    return sh.constrain(out, ("batch", "seq", None))
