"""Mixture-of-Experts with capacity-based dispatch.

Baseline (paper-era faithful, GShard/Switch semantics): tokens are routed
top-k, grouped, and *scattered* into a per-group (E, C) capacity buffer; the
expert FFN runs as a dense batched GEMM over the buffer; results gather back.
Scatter/gather dispatch avoids the quadratic one-hot-einsum dispatch cost
(T x E x C x d) that the classic GShard formulation pays — the dispatch is
O(T*k*d) bytes and zero FLOPs.

Sharding: groups over ("pod","data"), experts over "model" (EP).  GSPMD turns
the group-sharded -> expert-sharded reshard into all-to-alls.

An auxiliary load-balance loss (Switch-style) and router-z loss are returned.

Two dispatch paths (EXPERIMENTS.md §Perf):
  * "scatter" — the baseline above.  Faithful GShard-with-capacity semantics,
    but the global scatter is partitioner-hostile: under GSPMD the dispatch
    buffer gets materialised per model shard and all-reduced (measured:
    ~13 TB/device/step on llama4-maverick train_4k).
  * "ep"      — beyond-paper optimised expert parallelism via shard_map:
    route locally, exchange token payloads with a single all-to-all over the
    "model" axis, run the expert GEMMs on local (E/M) experts, all-to-all
    back.  Collectives drop to O(tokens x d) per layer.
Select with MoEConfig.dispatch or env REPRO_MOE_DISPATCH.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, MoEConfig
from repro.distributed.sharding import Sharder
from repro.models import params as pp
from repro.models.layers import dtype_of


def init_moe(key, cfg: ArchConfig) -> Dict[str, Any]:
    mc = cfg.moe
    dt = dtype_of(cfg.param_dtype)
    d, ff, E = cfg.d_model, mc.d_ff_expert, mc.num_experts
    ks = jax.random.split(key, 5)
    s_in = 0.02
    s_out = 0.02 / math.sqrt(2 * max(cfg.num_layers, 1))
    p = {
        "router": pp.normal(ks[0], (d, E), 0.02, jnp.float32, (None, None)),
        "w_gate": pp.normal(ks[1], (E, d, ff), s_in, dt, ("expert", "fsdp", None)),
        "w_up": pp.normal(ks[2], (E, d, ff), s_in, dt, ("expert", "fsdp", None)),
        "w_down": pp.normal(ks[3], (E, ff, d), s_out, dt, ("expert", None, "fsdp")),
    }
    if mc.num_shared_experts:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], cfg, d_ff=mc.d_ff_expert * mc.num_shared_experts)
    return p


def _routing(router_logits: jax.Array, mc: MoEConfig, capacity: int):
    """router_logits: (G, S, E) fp32 -> dispatch metadata.

    Returns ids (G,N), gates (G,N), pos (G,N), keep (G,N) with N = S*top_k,
    plus aux losses.
    """
    G, S, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, mc.top_k)            # (G,S,k)
    # renormalise the kept gates (standard for k>1)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    ids_flat = ids.reshape(G, S * mc.top_k)
    gates_flat = gates.reshape(G, S * mc.top_k)
    onehot = jax.nn.one_hot(ids_flat, E, dtype=jnp.int32)  # (G,N,E)
    pos_in_expert = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)          # (G,N)
    keep = pos < capacity

    # Switch aux loss: E * sum_e f_e * p_e  (f = fraction dispatched, p = mean prob)
    f = jnp.mean(jax.nn.one_hot(ids[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    pmean = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * pmean)
    zloss = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return ids_flat, gates_flat, pos, keep, aux, zloss


def _dispatch_mode(mc: MoEConfig) -> str:
    return os.environ.get("REPRO_MOE_DISPATCH", mc.dispatch)


def apply_moe(p, x: jax.Array, cfg: ArchConfig, sh: Sharder,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (y, aux_losses).  Dispatches per MoEConfig.dispatch."""
    if _dispatch_mode(cfg.moe) == "ep" and sh.mesh is not None:
        B, S = x.shape[0], x.shape[1]
        shape = dict(sh.mesh.shape)
        M = shape.get("model", 1)
        n_dp = math.prod(v for a, v in shape.items() if a in ("pod", "data"))
        if (M > 1 and B % max(n_dp, 1) == 0 and S % M == 0
                and cfg.moe.num_experts % M == 0):
            return apply_moe_ep(p, x, cfg, sh)
    return apply_moe_scatter(p, x, cfg, sh)


def apply_moe_scatter(p, x: jax.Array, cfg: ArchConfig, sh: Sharder,
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Baseline capacity dispatch (GShard semantics, global scatter)."""
    mc = cfg.moe
    cdt = dtype_of(cfg.compute_dtype)
    B, S, d = x.shape
    T = B * S
    gsz = min(mc.group_size, T)
    while T % gsz:
        gsz //= 2
    G = T // gsz
    E = mc.num_experts
    capacity = max(1, int(math.ceil(gsz * mc.top_k * mc.capacity_factor / E)))
    xg = x.reshape(G, gsz, d)
    xg = sh.constrain(xg, ("batch", None, None))

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    ids, gates, pos, keep, aux, zloss = _routing(logits, mc, capacity)
    N = gsz * mc.top_k
    # token index for each of the N=(S*k) choices (row-major (s, k))
    tok = jnp.broadcast_to((jnp.arange(N) // mc.top_k)[None, :], (G, N))

    # ---- scatter tokens into capacity buffer --------------------------------
    xe = jnp.zeros((G, E, capacity, d), cdt)
    gidx = jnp.broadcast_to(jnp.arange(G)[:, None], (G, N))
    pos_c = jnp.where(keep, pos, capacity)                 # dropped -> clipped
    # out-of-range scatter indices are dropped by XLA scatter semantics
    xe = xe.at[gidx, ids, pos_c].add(
        jnp.take_along_axis(xg, tok[..., None], axis=1).astype(cdt),
        mode="drop")
    xe = sh.constrain(xe, ("batch", "expert", None, None))

    # ---- expert FFN (dense batched GEMM over the capacity buffer) ----------
    g = jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(cdt))
    u = jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(cdt))
    ye = sh.constrain(ye, ("batch", "expert", None, None))

    # ---- gather back & combine ---------------------------------------------
    yt = ye[gidx, ids, pos_c]                              # (G, N, d)
    yt = yt * (gates * keep).astype(cdt)[..., None]
    # sum the k choices per token
    yt = yt.reshape(G, gsz, mc.top_k, d).sum(axis=2)
    y = yt.reshape(B, S, d)

    if mc.num_shared_experts and "shared" in p:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["shared"], x, cfg, sh)

    y = sh.constrain(y, ("batch", "seq", None))
    losses = {"moe_aux": aux * mc.aux_loss_weight, "moe_z": zloss * 1e-3}
    return y, losses


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (optimised path)
# ---------------------------------------------------------------------------
def _capacity_scatter(x, ids, n_bins: int, cap: int, valid=None):
    """Scatter rows of x (N, d) into (n_bins, cap, d) by bin id with
    positional capacity; returns (buffer, pos, keep).  Local arrays only."""
    N = ids.shape[0]
    onehot = jax.nn.one_hot(ids, n_bins, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    keep = pos < cap
    if valid is not None:
        keep = keep & valid
    pos_c = jnp.where(keep, pos, cap)
    buf = jnp.zeros((n_bins, cap, x.shape[-1]), x.dtype)
    buf = buf.at[ids, pos_c].add(jnp.where(keep[:, None], x, 0), mode="drop")
    return buf, pos_c, keep


def apply_moe_ep(p, x: jax.Array, cfg: ArchConfig, sh: Sharder,
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """shard_map expert parallelism over the "model" mesh axis.

    Per chip: route local tokens; bucket them by destination model-shard
    (capacity cap_s); ONE all-to-all ships payloads; local capacity dispatch
    over the chip's E/M experts; expert GEMMs; all-to-all back; combine with
    local gates.  All scatters are chip-local, so GSPMD never replicates the
    dispatch buffer (the failure mode of the baseline path).
    """
    mc = cfg.moe
    mesh = sh.mesh
    cdt = dtype_of(cfg.compute_dtype)
    B, S, d = x.shape
    T = B * S
    axes = tuple(mesh.axis_names)
    dp_axes = tuple(a for a in ("pod", "data") if a in axes)
    n_dp = math.prod(mesh.shape[a] for a in dp_axes)
    n_dev = math.prod(mesh.shape.values())
    M = mesh.shape.get("model", 1)
    E = mc.num_experts
    assert E % M == 0, (E, M)
    e_loc = E // M
    t_loc = T // n_dev
    # per-destination-shard send capacity and per-expert local capacity
    cap_s = max(1, int(math.ceil(t_loc * mc.top_k * mc.capacity_factor / M)))
    cap_e = max(1, int(math.ceil(M * cap_s / e_loc)))

    router = p["router"].astype(jnp.float32)
    w_gate, w_up, w_down = (p["w_gate"].astype(cdt), p["w_up"].astype(cdt),
                            p["w_down"].astype(cdt))

    def local(xb, wg, wu, wd):
        # xb: (B_loc, S_loc, d) native block; wg/wu: (e_loc, d, f)
        b_loc, s_loc = xb.shape[0], xb.shape[1]
        xt = xb.reshape(b_loc * s_loc, d)                     # local flatten
        logits = xt.astype(jnp.float32) @ router              # (t_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, mc.top_k)           # (t_loc, k)
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
        # aux losses (psum'ed below)
        f = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
        pmean = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(jax.lax.pmean(f, axes) * jax.lax.pmean(pmean, axes))
        zloss = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), axes)

        ids_f = ids.reshape(-1)                               # (t_loc*k,)
        xk = jnp.repeat(xt.astype(cdt), mc.top_k, axis=0)     # (t_loc*k, d)
        dest = ids_f // e_loc
        send, pos_s, keep_s = _capacity_scatter(xk, dest, M, cap_s)
        # ship the local expert id alongside (encoded, +1 so 0 = empty slot)
        eid = jnp.zeros((M, cap_s), jnp.int32).at[dest, pos_s].add(
            jnp.where(keep_s, ids_f % e_loc + 1, 0), mode="drop")

        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)                # (M, cap_s, d)
        recv_eid = jax.lax.all_to_all(eid, "model", split_axis=0,
                                      concat_axis=0, tiled=False)

        rx = recv.reshape(M * cap_s, d)
        re = recv_eid.reshape(M * cap_s)
        buf, pos_e, keep_e = _capacity_scatter(rx, jnp.maximum(re - 1, 0),
                                               e_loc, cap_e, valid=re > 0)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        # gather back into a2a slots, ship home, combine with gates
        y_slots = y_e[jnp.maximum(re - 1, 0), pos_e]          # (M*cap_s, d)
        y_slots = jnp.where(keep_e[:, None], y_slots, 0)
        back = jax.lax.all_to_all(y_slots.reshape(M, cap_s, d), "model",
                                  split_axis=0, concat_axis=0, tiled=False)
        y_tok = back[dest, pos_s]                             # (t_loc*k, d)
        y_tok = jnp.where(keep_s[:, None], y_tok, 0)
        y = (y_tok.reshape(b_loc * s_loc, mc.top_k, d)
             * gates[..., None].astype(cdt)).sum(axis=1)
        return y.reshape(b_loc, s_loc, d), aux, zloss

    # native residual layout: batch over (pod, data), seq over model — no
    # token-flat reshard at the boundary (GSPMD falls back to
    # replicate-then-reshard on its transpose otherwise)
    blk_spec = P(dp_axes if len(dp_axes) != 1 else dp_axes[0],
                 "model" if "model" in axes else None, None)
    ew_spec = P("model", None, None) if "model" in axes else P(None, None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(blk_spec, ew_spec, ew_spec, ew_spec),
                       out_specs=(blk_spec, P(), P()),
                       check_vma=False)
    # FSDP weight all-gather (if any) happens here, outside shard_map
    wg = jax.lax.with_sharding_constraint(
        w_gate, jax.NamedSharding(mesh, ew_spec))
    wu = jax.lax.with_sharding_constraint(
        w_up, jax.NamedSharding(mesh, ew_spec))
    wd = jax.lax.with_sharding_constraint(
        w_down, jax.NamedSharding(mesh, ew_spec))
    xin = jax.lax.with_sharding_constraint(
        x, jax.NamedSharding(mesh, blk_spec))
    y, aux, zloss = fn(xin, wg, wu, wd)

    if mc.num_shared_experts and "shared" in p:
        from repro.models.layers import apply_mlp
        y = y + apply_mlp(p["shared"], x, cfg, sh)
    y = sh.constrain(y, ("batch", "seq", None))
    losses = {"moe_aux": aux * mc.aux_loss_weight, "moe_z": zloss * 1e-3}
    return y, losses
