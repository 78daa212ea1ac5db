"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Planes named ``/device:TPU:<n>`` are the chips: their ``XLA Ops`` line
holds one event per operation run on the device, their ``XLA Modules``
line one event per program run.  The benchmark's own host spans
(``bench.wait``, ``bench.admit``, ``bench.step``, ``bench.sync``,
recorded with ``jax.profiler.TraceAnnotation``) are on a host plane.
The two clocks agree to about a millisecond on a v5e (a program can
show on the device up to ~1.5 ms before its host span opens), so an
idle gap shorter than that may be put down to the span beside it.  The
traced window runs from the start of the first span to the end of the
``bench.sync`` span that closes the window.

- busy: the union of the op intervals inside the window, averaged over
  the chips;
- modules and ops: device seconds by program (the numeric suffix of its
  name dropped) and by operation (its HLO name, kind and result shape;
  loops and calls, which hold other operations, are left out of the
  operations but not of the union);
- idle by span: each idle interval between busy intervals, attributed
  to the host span that covers its midpoint (``host`` where none does).
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field

import numpy as np

SPANS = ("bench.wait", "bench.admit", "bench.step", "bench.sync")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$|\.\d+$")
_KIND = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")
_CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> tuple[str, bool]:
    """A short label for an ``XLA Ops`` event (whose name may be the whole
    HLO instruction), and whether the op holds other ops."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head, False
    kind = _KIND.search(rest)
    kind = kind.group(1) if kind else "op"
    if kind == "custom-call" and "tpu_custom_call" in rest:
        kind = "custom-call(tpu)"
    shape = _SHAPE.search(" = " + rest)
    label = f"{head} {kind}" + (f" {shape.group(1)}" if shape else "")
    return label, kind in _CONTAINERS


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    modules: dict = field(default_factory=dict)       # name -> seconds
    module_runs: dict = field(default_factory=dict)   # name -> count
    ops: dict = field(default_factory=dict)           # name -> seconds
    idle_by_span: dict = field(default_factory=dict)  # span -> seconds

    def module_seconds(self, fragment: str) -> float:
        """Device seconds of every program whose name holds ``fragment``."""
        return sum(s for n, s in self.modules.items() if fragment in n)

    def module_count(self, fragment: str) -> int:
        return sum(c for n, c in self.module_runs.items() if fragment in n)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(root: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(root).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def _union(starts: np.ndarray, ends: np.ndarray) -> list[tuple[float, float]]:
    order = np.argsort(starts, kind="stable")
    out: list[list[float]] = []
    for s, e in zip(starts[order], ends[order]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_trace(path, n_devices: int = 1) -> TraceSummary:
    """Reduce the trace at ``path`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    spans: list[tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    spans.sort()
    w0 = spans[0][0]
    sync_end = [e for s, e, n in spans if n == "bench.sync"]
    w1 = max(sync_end) if sync_end else max(e for _, e, _ in spans)
    span_starts = np.array([s for s, _, _ in spans])

    out = TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=0.0)
    busy_total = 0.0
    for plane in sorted(devices, key=lambda p: p.name)[:n_devices]:
        starts, ends = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                # every program the trace holds ran in the window: the
                # device is synced before the trace starts and before it
                # stops (and its clock may read a little early)
                for ev in line.events:
                    name = _SUFFIX.sub("", ev.name)
                    out.modules[name] = out.modules.get(name, 0.0) \
                        + ev.duration_ns * 1e-9
                    out.module_runs[name] = out.module_runs.get(name, 0) + 1
            elif line.name == "XLA Ops":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e < w0 or s > w1:
                        continue
                    starts.append(max(s, w0))
                    ends.append(min(e, w1))
                    label, container = op_label(ev.name)
                    if not container:
                        out.ops[label] = out.ops.get(label, 0.0) \
                            + ev.duration_ns * 1e-9
        if not starts:
            continue
        busy = _union(np.array(starts), np.array(ends))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        # idle intervals inside the window, by the host span around them
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            i = int(np.searchsorted(span_starts, mid, side="right")) - 1
            name = spans[i][2] if i >= 0 and spans[i][1] >= mid else "host"
            out.idle_by_span[name] = out.idle_by_span.get(name, 0.0) \
                + float(b - a) * 1e-9
    out.busy_s = float(busy_total / max(1, min(n_devices, len(devices))))
    return out
