import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

DOC = """Roofline table: per (arch x shape) on the single-pod 16x16 mesh.

For every cell:
  * exact HLO-level FLOPs from the jaxpr cost model (scan-trip exact);
  * memory term from the kernel-aware analytic byte model;
  * collective term from the dry-run's trip-count-expanded HLO collective
    bytes (per-device local shapes -> bytes through one chip's links);
  * MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference);
  * dominant bottleneck + useful-FLOPs ratio + roofline fraction.

Writes experiments/roofline/<arch>__<shape>.json and prints the table.
Run:  PYTHONPATH=src python -m benchmarks.roofline_table [--arch A]
"""

import argparse
import json
import pathlib
import time

import jax

from repro.configs import ARCH_IDS, TrainConfig, get_config, shapes_for
from repro.launch.dryrun import build_cell, run_cell
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.sharding.constraints import activation_sharding
from repro.roofline.analysis import (
    Roofline,
    analytic_bytes,
    jaxpr_cost,
    model_flops,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
DRYRUN_DIR = ROOT / "experiments" / "dryrun"
OUT_DIR = ROOT / "experiments" / "roofline"


def roofline_for_cell(arch: str, shape_name: str, *, verbose=True) -> dict:
    cfg = get_config(arch)
    shape = next(s for s in shapes_for(cfg) if s.name == shape_name)
    mesh = make_production_mesh()
    mcfg = mesh_config()
    tcfg = TrainConfig()
    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh, mcfg.axes, tcfg)
    with mesh, activation_sharding(mesh, mcfg.axes, mcfg.shape):
        traced = fn.trace(*args)
    cost = jaxpr_cost(traced.jaxpr, with_fusion=False)

    dj = DRYRUN_DIR / f"{arch}__{shape_name}__single.json"
    colls = {}
    if dj.exists():
        colls = json.loads(dj.read_text()).get("collectives", {})
    ici = sum(colls.values())

    rl = Roofline(
        arch=arch, shape=shape_name, mesh=mcfg.shape,
        chips=mcfg.num_devices,
        hlo_flops=cost.flops,
        bytes_fused=cost.bytes_fused,
        bytes_naive=cost.bytes_naive,
        bytes_analytic=analytic_bytes(cfg, shape),
        ici_bytes=ici, dcn_bytes=0.0,
        model_flops=model_flops(cfg, shape),
        collectives=colls,
    )
    rec = rl.to_dict()
    rec["trace_s"] = round(time.time() - t0, 1)
    if verbose:
        print(f"{arch:22s} {shape_name:12s} comp={rl.compute_s*1e3:9.2f}ms "
              f"mem={rl.memory_s*1e3:9.2f}ms coll={rl.collective_s*1e3:9.2f}ms"
              f" dom={rl.dominant:10s} useful={rl.useful_flops_ratio:5.2f} "
              f"roofline={rl.roofline_fraction:6.3f}", flush=True)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{arch}__{shape_name}.json").write_text(
        json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    rows = []
    for arch in archs:
        for s in shapes_for(get_config(arch)):
            try:
                rows.append(roofline_for_cell(arch, s.name))
            except Exception as e:  # noqa: BLE001
                print(f"{arch} {s.name} FAILED: {type(e).__name__}: {e}",
                      flush=True)
    print(f"roofline table: {len(rows)} cells")


if __name__ == "__main__":
    main()
