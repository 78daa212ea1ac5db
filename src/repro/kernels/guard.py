"""Guarded kernel dispatch: the paper's far-pipeline fallback as a
runtime mechanism.

Every kernel entry point in ``repro.kernels.ops`` routes through the
process-wide ``KernelGuard``.  A dispatch tries its impl *chain*
(``pallas -> ref``, ``interpret -> ref``) in order: a launch/lowering
failure of one impl demotes to the next, and the pure-jnp ``ref`` path —
the far pipeline, which the MPU design guarantees can always run the
program (§IV-B1) — is the terminal fallback that is never faulted and
never quarantined.  A compiled kernel never demotes to the Pallas
interpreter: that would run a TPU program on the host's emulator in
silence.  Every demotion is counted (``stats()``), so a caller that must
run the kernel can assert that none happened.

After ``threshold`` *consecutive* failures of one (kernel, impl) pair,
that pair is **quarantined**: future chains skip it without attempting
a launch.  Each quarantine (and each ``reset``) bumps ``epoch``, which
is how the rest of the stack reacts without polling details:

* ``core.offload.mpu_offload`` checks the epoch on plan-cache lookups —
  a change invalidates cached plans that dispatch fused segments, and
  while a segment kernel stays quarantined at the policy's resolved
  impl the effective policy is degraded to ``mode="all_far"`` (re-plan
  to the far pipeline, the paper's fallback tier);
* ``serve.engine.Engine`` checks the epoch per step and re-jits its
  entry points, so the re-plan actually reaches the compiled hot path.

Dispatch happens at trace time (kernels live under ``jax.jit``), so the
guard adds zero per-step cost at steady state: an already-compiled
executable keeps whatever impl succeeded; the chain and quarantine are
consulted only when something (re)traces.

Fault injection: a ``serve.faults.FaultInjector`` installed via
``set_injector`` (or the ``faults.inject`` context manager) is asked
before every non-ref attempt and may raise a simulated launch failure —
that is how CI exercises every degradation path without real hardware
faults.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

#: fallback chain per requested impl — ref (the far pipeline) is last.
FALLBACK_CHAIN: dict[str, tuple[str, ...]] = {
    "pallas": ("pallas", "ref"),
    "interpret": ("interpret", "ref"),
    "ref": ("ref",),
}

#: kernels the offload planner dispatches fused segments to — a
#: quarantine of one of these (at the policy's resolved impl) degrades
#: ``mpu_offload`` wrappers to all_far planning.
SEGMENT_KERNELS = frozenset({
    "fused_elementwise", "fused_segment", "fused_segment_grid",
    "fused_matmul", "fused_matmul_dlhs", "fused_matmul_drhs",
    "fused_flash",
})


@functools.cache
def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve_impl(impl: str) -> str:
    """Resolve "auto" to the backend default (pallas on TPU, else ref)."""
    return default_impl() if impl == "auto" else impl


@dataclass
class KernelGuard:
    """Per-process kernel health: failure counts, fallback chain walk,
    and (kernel, impl) quarantine after ``threshold`` consecutive
    failures.  ``epoch`` increments on every quarantine state change
    (including ``reset``) so cached plans/jits can cheaply detect it."""

    threshold: int = 3
    epoch: int = 0
    injector: Any = None            # duck-typed: .kernel_launch(kernel, impl)
    kernel_failures: int = 0        # failed attempts (injected + real)
    kernel_fallbacks: int = 0       # dispatches served by a demoted impl
    quarantines: int = 0            # (kernel, impl) pairs ever quarantined
    _consec: dict[tuple[str, str], int] = field(default_factory=dict)
    _quarantined: set[tuple[str, str]] = field(default_factory=set)

    # -- queries ------------------------------------------------------------
    def is_quarantined(self, kernel: str, impl: str) -> bool:
        return (kernel, impl) in self._quarantined

    def chain(self, kernel: str, impl: str) -> tuple[str, ...]:
        """The impls a dispatch of ``kernel`` should attempt, skipping
        quarantined entries.  Never empty: ref is unquarantinable."""
        base = FALLBACK_CHAIN[resolve_impl(impl)]
        live = tuple(im for im in base
                     if im == "ref" or not self.is_quarantined(kernel, im))
        return live or ("ref",)

    def degraded_for(self, impl: str) -> bool:
        """True when a fused-segment kernel is quarantined at the
        resolved primary impl — the signal ``mpu_offload`` maps to
        ``mode="all_far"`` (plan everything on the far pipeline)."""
        im = resolve_impl(impl)
        if im == "ref":
            return False
        return any((k, im) in self._quarantined for k in SEGMENT_KERNELS)

    def stats(self) -> dict[str, int]:
        return {"kernel_failures": self.kernel_failures,
                "kernel_fallbacks": self.kernel_fallbacks,
                "quarantines": self.quarantines}

    # -- bookkeeping --------------------------------------------------------
    def record_failure(self, kernel: str, impl: str) -> bool:
        """Count one failed attempt; returns True if this failure
        tripped the quarantine.  ref never quarantines (a ref failure
        is a real bug, not a flaky launch)."""
        self.kernel_failures += 1
        if impl == "ref":
            return False
        key = (kernel, impl)
        self._consec[key] = self._consec.get(key, 0) + 1
        if self._consec[key] >= self.threshold and \
                key not in self._quarantined:
            self._quarantined.add(key)
            self.quarantines += 1
            self.epoch += 1
            return True
        return False

    def record_success(self, kernel: str, impl: str) -> None:
        self._consec.pop((kernel, impl), None)

    def reset(self) -> None:
        """Forget all failures and lift every quarantine (bumps epoch so
        degraded plans re-plan near on their next trace)."""
        had = bool(self._quarantined) or bool(self._consec)
        self._consec.clear()
        self._quarantined.clear()
        if had:
            self.epoch += 1

    # -- the guarded dispatch ----------------------------------------------
    def run(self, kernel: str, impl: str, attempt: Callable[[str], Any]):
        """Run ``attempt(im)`` for each impl in the fallback chain until
        one succeeds.  Non-ref attempts first consult the installed
        fault injector (which may raise a simulated launch failure).
        If every impl fails, the last error propagates.

        Each attempt traces under ``jax.named_scope(kernel)``: the
        Pallas call and, after a demotion, the ref path's XLA ops carry
        the kernel's name in the compiled program's op metadata, which
        is how a device trace attributes their time."""
        chain = self.chain(kernel, impl)
        errors: list[Exception] = []
        for i, im in enumerate(chain):
            try:
                if im != "ref" and self.injector is not None:
                    self.injector.kernel_launch(kernel, im)
                with jax.named_scope(kernel):
                    out = attempt(im)
            except Exception as e:  # noqa: BLE001 — demote, don't die
                errors.append(e)
                self.record_failure(kernel, im)
                continue
            self.record_success(kernel, im)
            if i > 0:
                self.kernel_fallbacks += 1
            return out
        raise errors[-1]


#: the process-wide guard every ops dispatch goes through
_GUARD = KernelGuard()


def kernel_guard() -> KernelGuard:
    return _GUARD


def set_injector(injector: Any) -> Any:
    """Install a fault injector on the process guard; returns the
    previous one (``serve.faults.inject`` restores it)."""
    prev = _GUARD.injector
    _GUARD.injector = injector
    return prev
