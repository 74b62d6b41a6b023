"""One benchmark per paper table/figure.

Each `bench_*` returns a list of (name, us_per_call, derived) rows.  Model
and simulator rows derive from the paper's Table II constants; the
`kernels` rows time the jitted ops on the CPU.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]


# ---------------------------------------------------------------------------
# Table I + Fig 1 + Fig 6 — local scalability
# ---------------------------------------------------------------------------
def bench_table1_scalability() -> List[Row]:
    from repro.core import perfmodel as pm
    out: List[Row] = []
    m = pm.PerfModelInputs(net=pm.FDR)
    # paper Table I measured totals (CUDA, local): 10.928 / 5.53 / 2.857
    paper = {1: 10.928, 2: 5.53, 4: 2.857}
    for n, total in paper.items():
        model_t = pm.t_computation(n, m) + 1.378 / n ** 0.7  # calibrated local
        norm = total / paper[1]
        offset = norm - 1.0 / n
        out.append((f"table1/local_cuda_{n}gpu", total * 1e6,
                    f"paper_norm={norm:.3f};offset={offset:.3f};"
                    f"model={model_t:.3f}s"))
    return out


# ---------------------------------------------------------------------------
# Fig 8 / Fig 10 — concurrent transfer bandwidth sharing
# ---------------------------------------------------------------------------
def bench_fig8_bandwidth() -> List[Row]:
    from repro.core.simulator import effective_bandwidth
    out: List[Row] = []
    for bw, net in ((6000.0, "pinned_local"), (5600.0, "fdr_rcuda")):
        for n in (1, 2, 4, 8, 16):
            eff = effective_bandwidth(n, bw)
            out.append((f"fig8/{net}_{n}streams", 1e6 / eff,
                        f"per_stream_mb_s={eff:.0f}"))
    return out


# ---------------------------------------------------------------------------
# Fig 9 — rCUDA scaling up to 16 remote vdevs (QDR/FDR)
# ---------------------------------------------------------------------------
def bench_fig9_remote_scaling() -> List[Row]:
    from repro.core import perfmodel as pm
    out: List[Row] = []
    for net in (pm.QDR, pm.FDR):
        m = pm.PerfModelInputs(net=net)
        for n in (1, 2, 4, 8, 16):
            t = pm.exec_time_no_mt(n, m)
            out.append((f"fig9/{net.name}_{n}gpu", t * 1e6,
                        f"compute={pm.t_computation(n, m):.3f}s;"
                        f"transfer={pm.t_transfer(n, m):.3f}s"))
    return out


# ---------------------------------------------------------------------------
# Fig 11 / Fig 12 — transfer modes; Fig 13 / Fig 14 — multi-tenancy
# ---------------------------------------------------------------------------
def bench_fig11_transfer_modes() -> List[Row]:
    from repro.core.simulator import SimInputs, simulate_cells
    from repro.core.tenancy import TenancyConfig
    out: List[Row] = []
    for mode in ("concurrent", "sequential"):
        r = simulate_cells(SimInputs(TenancyConfig(4, 1, mode)))
        out.append((f"fig11/{mode}_4pdev", r.makespan * 1e6,
                    f"cells={r.steps()};util={r.utilization*100:.1f}%"))
    return out


def bench_fig13_multitenancy() -> List[Row]:
    from repro.core.simulator import SimInputs, simulate_cells
    from repro.core.tenancy import TenancyConfig
    out: List[Row] = []
    paper_cells = {1: 88, 2: 80, 4: 76}
    for t, want in paper_cells.items():
        r = simulate_cells(SimInputs(TenancyConfig(4, t, "sequential")))
        out.append((f"fig13/{t}vdev_per_pdev", r.makespan * 1e6,
                    f"cells={r.steps()};paper={want};"
                    f"match={r.steps() == want}"))
    return out


def bench_fig14_energy() -> List[Row]:
    from repro.core.simulator import SimInputs, simulate_cells
    from repro.core.tenancy import TenancyConfig
    out: List[Row] = []
    paper = {1: 1145.0, 2: 1094.0, 4: 1041.0}
    for t, want in paper.items():
        r = simulate_cells(SimInputs(TenancyConfig(4, t, "sequential")))
        out.append((f"fig14/energy_{t}vdev", r.makespan * 1e6,
                    f"model={r.energy_ws:.0f}Ws;paper={want:.0f}Ws;"
                    f"util={r.utilization*100:.1f}%"))
    return out


# ---------------------------------------------------------------------------
# Fig 15 / 16 — measured-style sweeps over (pdev, tenants)
# ---------------------------------------------------------------------------
def bench_fig15_16_combinations() -> List[Row]:
    from repro.core import perfmodel as pm
    out: List[Row] = []
    for net in (pm.QDR, pm.FDR):
        m = pm.PerfModelInputs(net=net)
        for p in (1, 2, 4, 6, 12):
            for v in (1, 2, 4):
                if not pm.feasible(p, v, m):
                    continue
                nv = p * v
                t = pm.exec_time_multitenancy(p, v, m)
                overlapped = (pm.t_transfer(nv, m) + pm.t_computation(nv, m)
                              - t)
                out.append((f"fig15_16/{net.name}_{p}p_{v}v", t * 1e6,
                            f"overlapped={max(overlapped,0):.3f}s"))
    return out


# ---------------------------------------------------------------------------
# Figs 17-22 — perf/energy/EDP model surfaces and optima
# ---------------------------------------------------------------------------
def bench_fig17_22_models() -> List[Row]:
    from repro.core import perfmodel as pm
    from repro.core.planner import plan
    out: List[Row] = []
    for net in (pm.QDR, pm.FDR):
        m = pm.PerfModelInputs(net=net)
        for obj in ("time", "energy", "edp"):
            d = plan(m, obj)
            out.append((f"fig17_22/{net.name}_{obj}_opt",
                        d.exec_time_s * 1e6,
                        f"deploy={d.n_pdev}x{d.tenants_per_pdev};"
                        f"energy={d.energy_ws:.0f}Ws;"
                        f"mem={d.memory_per_pdev_mb:.0f}MB"))
    return out


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (CPU wall; interpret-mode Pallas is *not* timed —
# it validates, the jnp path is what executes on CPU)
# ---------------------------------------------------------------------------
def bench_kernels() -> List[Row]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    out: List[Row] = []
    rng = np.random.default_rng(0)
    T, K, M, cat = 2048, 256, 5, 4096
    ids = jnp.asarray(rng.integers(0, cat + 1, (T, K)), jnp.int32)
    elt = jnp.asarray(np.abs(rng.normal(size=(cat + 1, M))), jnp.float32)
    occ_r = jnp.asarray(np.abs(rng.normal(size=M)), jnp.float32)
    occ_l = jnp.asarray(np.abs(rng.normal(size=M)) + 1, jnp.float32)

    f = jax.jit(lambda i: kops.aggregate_loss(i, elt, occ_r, occ_l,
                                              np.float32(1), np.float32(1e9),
                                              chunk=128))
    f(ids).block_until_ready()
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        f(ids).block_until_ready()
    us = (time.perf_counter() - t0) / n * 1e6
    ev_s = T * K * M / (us / 1e6)
    out.append(("kernels/aggregate_loss_2048x256", us,
                f"event_lookups_per_s={ev_s:.2e}"))

    b, L, H, P, N = 2, 512, 8, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))[None, None] * dt
    B = jax.random.normal(ks[3], (b, L, H, N))
    C = jax.random.normal(ks[4], (b, L, H, N))
    g = jax.jit(lambda x: kops.ssd(x, dt, a, B, C, chunk=64)[0])
    g(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        g(x).block_until_ready()
    us = (time.perf_counter() - t0) / n * 1e6
    out.append(("kernels/ssd_scan_b2_L512", us,
                f"tok_per_s={b*L/(us/1e6):.2e}"))
    return out


ALL = [bench_table1_scalability, bench_fig8_bandwidth,
       bench_fig9_remote_scaling, bench_fig11_transfer_modes,
       bench_fig13_multitenancy, bench_fig14_energy,
       bench_fig15_16_combinations, bench_fig17_22_models, bench_kernels]
