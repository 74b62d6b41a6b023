"""Deployment planner walk-through (paper §V-F, Figs 17-22).

    PYTHONPATH=src python examples/deployment_planner.py

Prints the execution-time / energy / EDP surfaces over (#pdev x tenants) for
QDR and FDR InfiniBand with the paper's Table II constants, marks the paper's
reported optima, then re-targets the model to the TPU-v5e staging profile.
"""
from repro.core import energymodel as em
from repro.core import perfmodel as pm
from repro.core.planner import full_surface, plan


def surface_text(m, pw, max_p=12, max_t=6):
    surf = full_surface(m, pw, max_pdev=max_p, max_tenants=max_t)
    best = plan(m, "time")
    lines = ["tenants:" + "".join(f"{v:>9}" for v in range(1, max_t + 1))]
    for p in range(1, max_p + 1):
        row = [f"p={p:<3}"]
        for v in range(1, max_t + 1):
            d = surf.get((p, v))
            if d is None:
                row.append("      oom")
            else:
                mark = "*" if (p, v) == (best.n_pdev,
                                         best.tenants_per_pdev) else " "
                row.append(f"{d.exec_time_s:>8.2f}{mark}")
        lines.append("".join(row))
    return "\n".join(lines)


def main():
    for net, paper_opt in ((pm.QDR, "7x2"), (pm.FDR, "9x2")):
        m = pm.PerfModelInputs(net=net)
        print(f"=== {net.name} — execution time [s] "
              f"(paper optimum {paper_opt}) ===")
        print(surface_text(m, em.K20))
        t = plan(m, "time")
        e = plan(m, "energy")
        x = plan(m, "edp")
        print(f"time-opt  {t.n_pdev}x{t.tenants_per_pdev} = "
              f"{t.exec_time_s:.3f}s   energy-opt {e.n_pdev}x"
              f"{e.tenants_per_pdev} = {e.energy_ws:.0f}Ws   "
              f"edp-opt {x.n_pdev}x{x.tenants_per_pdev}\n")

    print("=== TPU v5e staging profile (beyond-paper target) ===")
    m = pm.PerfModelInputs(net=pm.V5E, compute_time_1pdev=0.35)
    t = plan(m, "time", max_pdev=16)
    print(f"v5e: time-opt {t.n_pdev} chips x {t.tenants_per_pdev} tenants "
          f"-> {t.exec_time_s * 1e3:.0f} ms "
          f"(risk analysis becomes real-time at pod scale)")


if __name__ == "__main__":
    main()
