"""Benchmark harness aggregator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract:
  * simulator figures: us_per_call = simulated MPU end-to-end time per
    workload; derived = the figure's headline ratio vs the paper value.
  * offload chains: us_per_call = projected v5e time for the fused chain;
    derived = HBM-traffic reduction.
  * roofline cells (if experiments/roofline exists): us_per_call = the
    dominant roofline term; derived = roofline fraction.

Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import json
import pathlib

from benchmarks import figs, offload_bench, table3_area

ROOT = pathlib.Path(__file__).resolve().parents[1]


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.3f},{derived}")


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
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

    rows, s = offload_bench.run()
    for r in rows:
        emit(f"offload/{r['chain']}", r["fused_us_v5e"],
             f"traffic_reduction={r['traffic_reduction']:.2f}")
    emit("offload/MEAN", 0.0,
         f"traffic_reduction={s['mean_traffic_reduction']:.2f}")

    dr_dir = ROOT / "experiments" / "dryrun"
    if dr_dir.exists():
        ok = fail = 0
        for f in sorted(dr_dir.glob("*.json")):
            d = json.loads(f.read_text())
            ok += 1 if d.get("ok") else 0
            fail += 0 if d.get("ok") else 1
        emit("dryrun/cells", 0.0, f"compiled={ok};failed={fail}")

    rl_dir = ROOT / "experiments" / "roofline"
    if rl_dir.exists():
        for f in sorted(rl_dir.glob("*.json")):
            d = json.loads(f.read_text())
            dom_s = {"compute": d["compute_s"], "memory": d["memory_s"],
                     "collective": d["collective_s"]}[d["dominant"]]
            emit(f"roofline/{d['arch']}/{d['shape']}", dom_s * 1e6,
                 f"dominant={d['dominant']};"
                 f"fraction={d['roofline_fraction']:.3f};"
                 f"useful={d['useful_flops_ratio']:.2f}")


if __name__ == "__main__":
    main()
