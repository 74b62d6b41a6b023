"""Pure-jnp oracles for every kernel in this package.

These are the single source of truth for numerics: the Pallas kernels must
match them (tests sweep shapes/dtypes with assert_allclose), and the model
stack calls them through :mod:`repro.kernels.ops` when the Pallas path is off
(CPU) or unavailable.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality) — chunked form and step oracle
# ---------------------------------------------------------------------------
def ssd_chunked_ref(x, dt, a_log_decay, B, C, chunk: int,
                    initial_state: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (Mamba-2, arXiv:2405.21060 §6).

    x : (b, L, H, P)   per-head inputs
    dt: (b, L, H)      positive step sizes (already softplus'ed)
    a_log_decay: (b, L, H)  log a_t = A * dt_t (A negative)
    B : (b, L, H, N)   input projections (already head-expanded)
    C : (b, L, H, N)   output projections
    Returns (y: (b,L,H,P), final_state: (b,H,P,N)).

    Recurrence: h_t = exp(a_t) h_{t-1} + dt_t * (B_t ⊗ x_t);  y_t = C_t · h_t.
    """
    b, L, H, P = x.shape
    N = B.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    f32 = jnp.float32

    xc = x.astype(f32).reshape(b, nc, chunk, H, P)
    dtc = dt.astype(f32).reshape(b, nc, chunk, H)
    ac = a_log_decay.astype(f32).reshape(b, nc, chunk, H)
    Bc = B.astype(f32).reshape(b, nc, chunk, H, N)
    Cc = C.astype(f32).reshape(b, nc, chunk, H, N)

    a_cum = jnp.cumsum(ac, axis=2)                      # inclusive (b,nc,Q,H)
    a_tot = a_cum[:, :, -1]                             # (b,nc,H)

    # ---- intra-chunk (quadratic within the chunk) ---------------------------
    seg = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]   # (b,nc,l,s,H)
    li = jnp.arange(chunk)
    causal = (li[:, None] >= li[None, :])[None, None, :, :, None]
    Lmat = jnp.where(causal, jnp.exp(seg), 0.0)
    CB = jnp.einsum("bclhn,bcshn->bclsh", Cc, Bc)
    M = CB * Lmat * dtc[:, :, None, :, :]               # dt_s enters at source
    y_diag = jnp.einsum("bclsh,bcshp->bclhp", M, xc)

    # ---- per-chunk end states ----------------------------------------------
    decay_states = jnp.exp(a_tot[:, :, None] - a_cum)   # (b,nc,Q,H)
    states = jnp.einsum("bcshn,bcsh,bcshp->bchpn",
                        Bc, decay_states * dtc, xc)     # (b,nc,H,P,N)

    # ---- inter-chunk recurrence over chunk index ----------------------------
    h0 = (jnp.zeros((b, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(h, inp):
        a_tot_c, s_c = inp                              # (b,H), (b,H,P,N)
        h_new = jnp.exp(a_tot_c)[:, :, None, None] * h + s_c
        return h_new, h                                 # emit state BEFORE chunk

    a_tot_sw = jnp.moveaxis(a_tot, 1, 0)                # (nc,b,H)
    states_sw = jnp.moveaxis(states, 1, 0)              # (nc,b,H,P,N)
    h_final, h_before = lax.scan(step, h0, (a_tot_sw, states_sw))
    h_before = jnp.moveaxis(h_before, 0, 1)             # (b,nc,H,P,N)

    # ---- inter-chunk output contribution ------------------------------------
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       Cc, h_before, jnp.exp(a_cum))
    y = (y_diag + y_off).reshape(b, L, H, P)
    return y.astype(x.dtype), h_final


def ssd_recurrent_ref(x, dt, a_log_decay, B, C,
                      initial_state: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, jax.Array]:
    """Step-by-step recurrence oracle (same contract as ssd_chunked_ref)."""
    b, L, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    h0 = (jnp.zeros((b, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(h, inp):
        x_t, dt_t, a_t, B_t, C_t = inp
        h = jnp.exp(a_t)[..., None, None] * h + \
            dt_t[..., None, None] * (x_t[..., :, None] * B_t[..., None, :])
        y_t = jnp.einsum("bhn,bhpn->bhp", C_t, h)
        return h, y_t

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, a_log_decay, B, C))
    h_final, ys = lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)
    return y.astype(x.dtype), h_final


def ssd_decode_step_ref(state, x_t, dt_t, a_t, B_t, C_t):
    """One decode step.  state: (b,H,P,N); x_t: (b,H,P); dt/a: (b,H);
    B_t/C_t: (b,H,N).  Returns (y_t, new_state)."""
    f32 = jnp.float32
    state = state.astype(f32)
    h = jnp.exp(a_t.astype(f32))[..., None, None] * state + \
        dt_t.astype(f32)[..., None, None] * (
            x_t.astype(f32)[..., :, None] * B_t.astype(f32)[..., None, :])
    y = jnp.einsum("bhn,bhpn->bhp", C_t.astype(f32), h)
    return y.astype(x_t.dtype), h


# ---------------------------------------------------------------------------
# Paged attention (serving decode hot path) — gather/scatter oracles
# ---------------------------------------------------------------------------
def paged_attention_decode_ref(q, k_pool, v_pool, page_table, positions, *,
                               kpos: Optional[jax.Array] = None,
                               pos_pool: Optional[jax.Array] = None,
                               window: Optional[int] = None) -> jax.Array:
    """Dense-gather paged decode read: the numerics source of truth for
    :func:`repro.kernels.paged_attention.paged_attention_decode_pallas` and
    the ``backend="jnp"`` serving path (which calls this directly).

    q: (C, H, D) compute dtype, already roped; k_pool/v_pool:
    (NP, P, Hkv, D) storage dtype; page_table: (C, NB) int32; positions:
    (C,) int32.  Validity comes from ``kpos`` (C, NB*P) — pass it
    pre-gathered (the serving decode step shares one gather across
    sublayers) or let it be gathered here from ``pos_pool`` (NP, P).
    Returns (C, H, D) float32.

    This is operation-for-operation the dense ring-cache decode math of
    :func:`repro.models.layers.apply_attention_decode` (same einsum
    equations, -1e30 mask bias, bf16->f32 cache casts, full-row softmax)
    applied to the page-table-gathered logical view — it materialises the
    dense [C, NB*P, Hkv, D] KV the fused kernel exists to avoid.
    """
    C, H, D = q.shape
    Hkv = k_pool.shape[2]

    def gather(pool):
        g = pool[page_table]                       # (C, NB, P, ...)
        return g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                         + g.shape[3:])

    k = gather(k_pool)                             # (C, L, Hkv, D)
    v = gather(v_pool)
    if kpos is None:
        kpos = gather(pos_pool[..., None])[..., 0]
    valid = kpos <= positions[:, None]
    if window is not None:
        valid &= kpos > positions[:, None] - window
    bias_pos = jnp.where(valid, 0.0, -1e30)        # (C, L)
    rep = H // Hkv
    qr = q.reshape(C, 1, Hkv, rep, D)
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qr, k.astype(qr.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = s + bias_pos[:, None, None, None, :]
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhrk,bkhd->bqhrd", pattn, v.astype(qr.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(C, H, D)


def paged_scatter_ref(pool, pages, values) -> jax.Array:
    """Scatter oracle for the prefill fused-write kernel: write ``values``
    (S, nb, P, ...) into ``pool`` (S, NP, P, ...) at page ids ``pages``
    (nb,), cast to the pool dtype.  Bit-exact contract: the Pallas kernel
    performs the same cast and the same page-granular stores."""
    return pool.at[:, pages].set(values.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Aggregate Risk Analysis (paper Algorithm 3) — trial-loss oracle
# ---------------------------------------------------------------------------
def _per_layer(fn, event_ids, elt_losses, occ_ret, occ_lim, agg_ret, agg_lim,
               **kw):
    """(T, L): ``fn``'s one-layer YLT for each layer's terms."""
    return jnp.stack([fn(event_ids, elt_losses, occ_ret[l], occ_lim[l],
                         agg_ret[l], agg_lim[l], **kw)
                      for l in range(occ_ret.shape[0])], axis=1)


def aggregate_loss_ref(event_ids, elt_losses, occ_ret, occ_lim, agg_ret, agg_lim):
    """Year-loss for each trial (paper Algorithm 3), pure jnp.

    event_ids : (T, K) int32   — per-trial event sequence (0 = no event pad)
    elt_losses: (E_cat, M) f32 — direct-access loss tables for M ELTs
                                 (row 0 must be zero: the pad event)
    occ_ret/occ_lim : (M,) f32 — per-ELT occurrence terms (financial terms I)
    agg_ret/agg_lim : ()  f32  — layer aggregate terms T
    Returns yl: (T,) f32 — the Year Loss Table.

    A portfolio of L layers over the same ELTs gives its terms per layer,
    occ (L, M) and agg (L,), and gets a (T, L) YLT: each layer priced alone.

    Occurrence terms clip each event-occurrence loss per ELT; event losses sum
    across ELTs, accumulate over the trial, then aggregate terms apply:
        l = min(max(l - ret, 0), lim)
    """
    if jnp.ndim(occ_ret) == 2:
        return _per_layer(aggregate_loss_ref, event_ids, elt_losses, occ_ret,
                          occ_lim, agg_ret, agg_lim)
    f32 = jnp.float32
    gathered = elt_losses.astype(f32)[event_ids]          # (T, K, M)
    occ = jnp.clip(gathered - occ_ret[None, None, :], 0.0, None)
    occ = jnp.minimum(occ, occ_lim[None, None, :])
    per_event = occ.sum(axis=-1)                          # (T, K)
    agg = per_event.sum(axis=-1)                          # (T,)
    yl = jnp.minimum(jnp.clip(agg - agg_ret, 0.0, None), agg_lim)
    return yl


def aggregate_loss_chunked_ref(event_ids, elt_losses, occ_ret, occ_lim,
                               agg_ret, agg_lim, chunk: int):
    """Chunked variant (paper §IV-B "chunking"): identical numerics, processes
    the event axis in fixed-size chunks — the structure the Pallas kernel
    mirrors (one chunk per VMEM tile)."""
    if jnp.ndim(occ_ret) == 2:
        return _per_layer(aggregate_loss_chunked_ref, event_ids, elt_losses,
                          occ_ret, occ_lim, agg_ret, agg_lim, chunk=chunk)
    T, K = event_ids.shape
    assert K % chunk == 0, (K, chunk)
    nck = K // chunk

    def body(acc, i):
        ids = lax.dynamic_slice_in_dim(event_ids, i * chunk, chunk, 1)
        g = elt_losses.astype(jnp.float32)[ids]           # (T, chunk, M)
        occ = jnp.clip(g - occ_ret[None, None, :], 0.0, None)
        occ = jnp.minimum(occ, occ_lim[None, None, :])
        return acc + occ.sum(axis=(1, 2)), None

    acc, _ = lax.scan(body, jnp.zeros((T,), jnp.float32), jnp.arange(nck))
    return jnp.minimum(jnp.clip(acc - agg_ret, 0.0, None), agg_lim)
