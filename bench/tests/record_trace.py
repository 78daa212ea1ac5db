#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Two tiny jitted programs (``small_admit``, ``small_step``) run three
times each inside the benchmark's host spans, with two known waits, on
a TPU; the profiler's ``.xplane.pb`` is copied to the path given.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as span

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1

    @jax.jit
    def small_admit(x):
        return jnp.sin(x) * 2.0 + 1.0

    @jax.jit
    def small_step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((1024, 1024), jnp.float32)
    small_step(small_admit(x)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with span("bench.wait"):
            time.sleep(0.05)
        for _ in range(3):
            with span("bench.admit"):
                y = small_admit(x)
            with span("bench.step"):
                small_step(y).block_until_ready()
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
