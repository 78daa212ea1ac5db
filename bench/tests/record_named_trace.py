#!/usr/bin/env python3
"""Record the named chip trace that ``test_scopes.py`` reads.

    python3 bench/tests/record_named_trace.py bench/tests/data/named.xplane.pb

A tiny jitted step (``named_step``) runs three times on a TPU, each
time inside the benchmark's ``bench.step`` span and the engine's span
tree (``engine.step`` holding ``engine.prepare``, ``engine.dispatch``,
``engine.sync``, ``engine.emit``), with a known 10 ms host sleep in
``engine.prepare``.  The step holds one far op under
``decode/mlp`` (a matmul) and one named Pallas kernel under
``decode/mlp/near/fused_elementwise`` (dispatched through the kernel
guard, as the offload rewriter dispatches a near segment).  A 50 ms and
a 20 ms ``bench.wait`` open and close the window; ``bench.sync`` ends
it.  The profiler's ``.xplane.pb`` is copied to the path given.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
PREPARE_SLEEP_S = 0.010
STEPS = 3


def main(out: str) -> int:
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import StepTraceAnnotation
    from jax.profiler import TraceAnnotation as span

    from repro.kernels import ops as kops

    if jax.devices()[0].platform != "tpu":
        print("record_named_trace: needs a TPU", file=sys.stderr)
        return 1

    @jax.jit
    def named_step(x):
        with jax.named_scope("decode"), jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
            with jax.named_scope("near"):
                y = kops.fused_elementwise(lambda a: a * 2.0 + 1.0, [y],
                                           impl="pallas", rows_block=256)
        return y

    x = jnp.ones((1024, 1024), jnp.float32)
    named_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with span("bench.wait"):
            time.sleep(0.05)
        y = x
        for i in range(STEPS):
            with span("bench.step"):
                with StepTraceAnnotation("engine.step", step_num=i + 1,
                                         active=1):
                    with span("engine.prepare"):
                        time.sleep(PREPARE_SLEEP_S)
                    with span("engine.dispatch"):
                        y = named_step(x)
                    with span("engine.sync"):
                        np.asarray(y)
                    with span("engine.emit"):
                        pass
        with span("bench.wait"):
            time.sleep(0.02)
        with span("bench.sync"):
            jax.block_until_ready(y)
        jax.profiler.stop_trace()
        src = sorted(pathlib.Path(tmp).rglob("*.xplane.pb"))[-1]
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out)
    print(f"{out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
