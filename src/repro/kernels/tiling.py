"""The TPU block tiling rule, shared by the kernels, the planner and the
verifier.

Mosaic lowers a ``BlockSpec`` only when the last two dimensions of its
block are each either the full extent of the array's dimension or a
multiple of the hardware tile: 128 on the lane (last) axis, and on the
sublane (second-to-last) axis 8 rows of 32-bit values — 16 of 16-bit,
32 of 8-bit, since narrower values pack into one 32-bit sublane.  The
Pallas interpreter runs any block, so only the TPU compiler (or this
check) catches a block that breaks the rule.

The kernels pick their block extents with ``tiled_divisor`` so they meet
the rule wherever their VMEM budget allows; where it does not, the
offload planner declines the segment (``repro.core.offload``) and the
static verifier reports it (rule ``tpu-tiling``).
"""
from __future__ import annotations

from typing import Callable, Iterable, Sequence

LANE = 128

#: scoped VMEM the offload kernels ask Mosaic for (``vmem_limit_bytes``),
#: above its 16 MiB default so a 512-row block with a double-buffered
#: f32 weight block fits; a quarter of a v5e core's 128 MiB.  The static
#: verifier sizes each kernel's per-step footprint against it.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def sublane(itemsize: int) -> int:
    """Sublane tile, in rows, of values ``itemsize`` bytes wide."""
    return 8 * max(1, 4 // itemsize)


def _divisors(n: int) -> list[int]:
    small, large = [], []
    d = 1
    while d * d <= n:
        if n % d == 0:
            small.append(d)
            if d * d != n:
                large.append(n // d)
        d += 1
    return small + large[::-1]


def tiled_divisor(n: int, limit: int, full: int,
                  aligns: Sequence[int] = (16, 8),
                  admit: Callable[[int], bool] = lambda d: True) -> int:
    """Largest divisor of ``n`` that is at most ``limit``, passes
    ``admit`` and is either ``full`` (the array's extent) or a multiple
    of an entry of ``aligns``, tried in order.  Without such a divisor,
    the largest admitted divisor at most ``limit`` — a block the rule
    then flags.  ``admit`` must pass 1."""
    divs = [d for d in _divisors(n) if d <= max(limit, 1) and admit(d)]
    for a in aligns:
        ok = [d for d in divs if d == full or d % a == 0]
        if ok:
            return ok[-1]
    return divs[-1]


def block_violation(view: Sequence[int], block: Sequence[int],
                    itemsize: int) -> str | None:
    """Why ``block`` over an array of shape ``view`` breaks the rule, or
    None when it lowers."""
    if block[-1] != view[-1] and block[-1] % LANE:
        return (f"lane block {block[-1]} of a {view[-1]}-wide axis is "
                f"neither the full extent nor a multiple of {LANE}")
    sub = sublane(itemsize)
    if len(block) > 1 and block[-2] != view[-2] and block[-2] % sub:
        return (f"sublane block {block[-2]} of a {view[-2]}-row axis is "
                f"neither the full extent nor a multiple of {sub}")
    return None


def violations(layout: Iterable[tuple], itemsizes: Iterable[int]
               ) -> list[str]:
    """Rule breaches of a kernel layout — ``(view, block, index_map)``
    per array, operands then outputs — one message per breaching block,
    given each array's itemsize in the same order."""
    out = []
    for j, ((view, block, _), isz) in enumerate(zip(layout, itemsizes)):
        why = block_violation(view, block, isz)
        if why is not None:
            out.append(f"block {tuple(block)} of array {j} "
                       f"{tuple(view)}: {why}")
    return out
