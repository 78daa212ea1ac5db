"""``python -m repro.analysis.lint`` — sweep offload plans through the
static verifier.

Two target families:

  * ``--all-configs`` — every ``configs/`` architecture, planned for the
    training loss forward AND its gradient (depth shrunk to <= 2 layers,
    small abstract batch: planning and verification never allocate real
    parameters — ``jax.eval_shape`` + ``ShapeDtypeStruct`` inputs all
    the way down).
  * ``--chains`` — every MUST_FUSE chain of ``repro.analysis.chains``,
    the committed fusion contract.

Exit status is non-zero iff any finding of severity >= error survives.
See docs/analysis.md for the rule catalog.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any, Callable, Iterable

from repro.analysis.verifier import Finding, has_errors, verify_plan

# lint plans at a small abstract shape: deep stacks re-plan the same
# per-layer segments, so 2 layers already cover every kernel form
_LINT_LAYERS = 2
_LINT_SEQ = 128
_LINT_BATCH = 2


def _shrunk_config(cfg):
    """A planning-equivalent shallow copy of a registry config."""
    kw: dict[str, Any] = {"num_layers": min(cfg.num_layers, _LINT_LAYERS)}
    if getattr(cfg, "enc_num_layers", 0):
        kw["enc_num_layers"] = min(cfg.enc_num_layers, 1)
    return dataclasses.replace(cfg, **kw)


def config_targets(archs: Iterable[str] | None = None,
                   ) -> Iterable[tuple[str, Callable, tuple]]:
    """Yield (name, fn, abstract_args) for every configs model, forward
    and gradient."""
    import jax

    from repro.configs.base import ShapeConfig
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.launch.inputs import batch_specs
    from repro.models.model import build_model

    shape = ShapeConfig("lint", seq_len=_LINT_SEQ,
                        global_batch=_LINT_BATCH, kind="train")
    for arch in (archs or ARCH_IDS):
        cfg = _shrunk_config(get_config(arch))
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        batch = batch_specs(cfg, shape)

        def fwd(p, b, _loss=model.loss_fn):
            return _loss(p, b, remat=False)[0]

        yield f"{arch}:fwd", fwd, (params, batch)
        yield f"{arch}:grad", jax.grad(fwd), (params, batch)


def verify_target(fn: Callable, args: tuple,
                  donate: tuple = (), policy=None) -> list[Finding]:
    """Plan one target and run the verifier over the resulting plan
    (the rewritten jaxpr rides inside the plan's annotation)."""
    from repro.core import offload_report

    plan = offload_report(fn, *args, policy=policy,
                          donate_argnums=donate)
    return verify_plan(plan)


def run(targets, *, verbose: bool = False) -> int:
    n_err = n_warn = 0
    n_targets = 0
    for name, fn, args, *rest in targets:
        donate = rest[0] if rest else ()
        n_targets += 1
        try:
            findings = verify_target(fn, args, donate)
        except Exception as e:
            print(f"FAIL  {name}: planning raised "
                  f"{type(e).__name__}: {e}")
            n_err += 1
            continue
        errs = [f for f in findings if f.severity == "error"]
        warns = [f for f in findings if f.severity == "warning"]
        n_err += len(errs)
        n_warn += len(warns)
        status = "FAIL" if errs else ("warn" if warns else "ok")
        print(f"{status:4}  {name}  "
              f"({len(errs)} error, {len(warns)} warning)")
        shown = findings if verbose else errs + warns
        for f in shown:
            print(f"      {f}")
    print(f"\n{n_targets} target(s): {n_err} error finding(s), "
          f"{n_warn} warning(s)")
    return 1 if n_err else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="statically verify offload plans (alias safety, "
                    "index bounds, VMEM legality, well-formedness)")
    ap.add_argument("--all-configs", action="store_true",
                    help="sweep every configs/ model, fwd + grad")
    ap.add_argument("--arch", action="append", default=[],
                    help="lint specific arch id(s) (implies config "
                         "sweep for just those)")
    ap.add_argument("--chains", action="store_true",
                    help="sweep every MUST_FUSE chain")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print info-severity findings too")
    args = ap.parse_args(argv)
    if not (args.all_configs or args.arch or args.chains):
        ap.error("nothing to lint: pass --all-configs, --arch or "
                 "--chains")

    def targets():
        if args.all_configs or args.arch:
            yield from ((n, f, a) for n, f, a in
                        config_targets(args.arch or None))
        if args.chains:
            from repro.analysis.chains import chains
            yield from chains()

    return run(targets(), verbose=args.verbose)


if __name__ == "__main__":
    sys.exit(main())
