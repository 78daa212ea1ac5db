"""The simulator's paper tables and figures, one function each.

Prints ``name,us_per_call,derived`` CSV rows: us_per_call = simulated
MPU end-to-end time per workload; derived = the figure's headline ratio
vs the paper value.

Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

from benchmarks import figs, table3_area


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.3f},{derived}")


def main() -> None:
    print("name,us_per_call,derived")

    rows, s = figs.fig8_9_speedup_energy()
    for r in rows:
        emit(f"fig8/{r['workload']}", r["mpu_us"],
             f"speedup={r['speedup']:.2f}")
    emit("fig8/MEAN", sum(r["mpu_us"] for r in rows) / len(rows),
         f"speedup={s['mean_speedup']:.2f};paper={s['paper_speedup']}")
    emit("fig9/MEAN", 0.0,
         f"energy_reduction={s['mean_energy_reduction']:.2f};"
         f"paper={s['paper_energy']}")

    rows, s = figs.fig10_energy_breakdown()
    top = sorted(rows, key=lambda r: -r["fraction"])[:4]
    emit("fig10/breakdown", 0.0,
         ";".join(f"{r['component']}={r['fraction']:.2f}" for r in top))

    rows, s = figs.fig11_smem()
    emit("fig11/MEAN", 0.0,
         f"near_vs_far={s['mean_speedup']:.2f};paper={s['paper']}")

    rows, s = figs.fig12_rowbuffers()
    emit("fig12/MEAN", 0.0,
         f"rb2={s['mean_rb2']:.2f};rb4={s['mean_rb4']:.2f};"
         f"paper_rb2={s['paper_rb2']};paper_rb4={s['paper_rb4']};"
         f"miss1={s['mean_miss1']:.3f};miss4={s['mean_miss4']:.3f}")

    rows, s = figs.fig13_ponb()
    emit("fig13/MEAN", 0.0, f"mpu_vs_ponb={s['mean']:.2f};paper={s['paper']}")

    rows, s = figs.fig14_register_locations()
    emit("fig14/MEAN", 0.0,
         f"N={s['mean_N']:.3f};F={s['mean_F']:.3f};B={s['mean_B']:.3f};"
         f"paper=N0.325/F0.637/B0.038")

    rows, s = figs.fig15_policies()
    emit("fig15/MEAN", 0.0,
         ";".join(f"{k}={v:.2f}" for k, v in s.items() if k != "paper"))

    rows, s = table3_area.run()
    emit("table3/total", 0.0,
         f"overhead_pct={s['total_overhead_pct']:.2f};"
         f"paper={s['paper_overhead_pct']}")


if __name__ == "__main__":
    main()
