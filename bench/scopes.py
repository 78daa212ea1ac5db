"""What the program's own names say in a profiler trace.

``bench/trace.py`` is the benchmark's one reduction of a trace (window,
busy union, device seconds by program and by operation, idle time by
``bench.*`` span).  This module holds what that reduction cannot read,
as functions over the trace's parts, to be folded into it:

- ``pallas_seconds``: device seconds of named Pallas calls among
  ``TraceSummary.ops``, whose labels lead with the call's instruction
  name, which is the ``pallas_call``'s ``name=`` (the metrics
  ``paged_attention_roofline`` and ``near_kernel_ms`` read it);
- ``op_scopes``: the named scope of each device operation.  Its channel
  is the ``tf_op`` stat of the operation's event metadata on the device
  plane, the compiled op's ``op_name``, e.g.
  ``jit(step_impl)/jit(flat_runner)/decode/while/body/closed_call/mlp/
  near/fused_matmul/fused_matmul/pallas_call``.  ``ProfileData`` does
  not expose metadata stats, so they are read from the protobuf;
- ``innermost`` and ``split_idle``: the device's idle time split by
  overlap, each piece of an idle interval to the innermost host span
  over it, ``host`` where none is;
- ``engine_steps``: the serving engine's decode steps from its
  ``engine.*`` spans, for ``engine_host_ms``.

``scoped`` puts them together over one trace file (section 5 of
PERF.md comes from it):

    python3 -m bench.scopes <trace.xplane.pb> [program]

The host and device clocks are not aligned beyond what the spans show:
a decode program never starts before the ``engine.dispatch`` span that
launched it and ends before its ``engine.sync`` closes, which bounds
the skew on v5e to about -1.5..+3.3 ms.  Idle time between dispatch and
sync is therefore read as one, not split between the two.
"""
from __future__ import annotations

import json
import pathlib
import re
import statistics
import sys
from dataclasses import dataclass, field

import numpy as np

from bench.trace import SPANS, find_xplane, op_label

ENGINE_SPANS = ("engine.admit", "engine.step", "engine.prepare",
                "engine.dispatch", "engine.sync", "engine.emit")
HOST_SPANS = SPANS + ENGINE_SPANS
# the kernel guard's names (``repro.kernels.ops``); a Pallas call and
# its ref fallback both trace under the kernel's scope
KERNELS = frozenset({
    "flash_attention", "decode_attention", "paged_decode_attention",
    "rmsnorm", "rotary", "ssd_scan", "wkv6", "adamw_update",
    "fused_elementwise", "fused_segment", "fused_segment_grid",
    "fused_matmul", "fused_matmul_dlhs", "fused_matmul_drhs", "fused_flash",
})
# the Pallas calls the offload planner runs a near segment as; a
# flash-shaped segment runs as ``flash_attention``, which prefill's
# kernel is named too, and is left out
NEAR_KERNELS = frozenset({"fused_segment_grid", "fused_matmul",
                          "fused_matmul_dlhs", "fused_matmul_drhs"})
# op_name components that are JAX's own (calls, loops, transforms), not
# scopes the program set
_TRANSFORMS = frozenset({"body", "cond", "pjit", "checkpoint", "remat",
                         "custom_jvp_call", "custom_vjp_call", "core_call",
                         "shard_map"})
_PHASES = ("decode", "prefill")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_RUN = re.compile(r"\(\d+\)$|\.\d+$")
_PALLAS = re.compile(r"^([A-Za-z_][\w-]*?)(?:\.\d+)? custom-call\(tpu\)")
UNSCOPED = "(unscoped)"
SCAN = "(scan)"


def pallas_seconds(ops: dict, kernels) -> float:
    """Device seconds of the Pallas calls named one of ``kernels`` among
    ``ops`` (``TraceSummary.ops``: label -> seconds).  A program whose
    kernels carry no name (``closed_call.97 custom-call(tpu)``) has
    none."""
    out = 0.0
    for label, secs in ops.items():
        m = _PALLAS.match(label)
        if m and m.group(1) in kernels:
            out += secs
    return out


def scope_path(tf_op: str) -> tuple[str, ...]:
    """The named scopes of an op, outermost first, from its ``tf_op``
    (``op_name`` + ``:`` + type; the first name of a fused op): JAX's call
    and transform components and the primitive's own name (the last
    component) dropped.  An op of a loop itself, outside the body's
    function (a scan slicing its inputs per iteration or stacking its
    outputs), ends in ``(scan)``."""
    name = tf_op.split(";")[0]         # a fusion lists its ops' names
    name = name.rsplit(":", 1)[0] if ":" in name else name
    out: list[str] = []
    loop = False
    for p in [p for p in name.split("/") if p][:-1]:
        if p == "while":
            loop = True
        elif p == "closed_call":
            loop = False
        elif "(" not in p and p not in _TRANSFORMS:
            loop = False
            out.append(p)
    if loop:
        out.append(SCAN)
    return tuple(out)


def kernel_of(path: tuple[str, ...]) -> str | None:
    """The guarded kernel an op belongs to: the innermost kernel scope."""
    for p in reversed(path):
        if p in KERNELS:
            return p
    return None


def layer_of(path: tuple[str, ...]) -> str:
    """The top layer scope: the first scope under the phase (``decode``,
    ``prefill``), else the first scope; ``(unscoped)`` for ops the
    compiler put in."""
    if path and path[0] in _PHASES:
        path = path[1:]
    return path[0] if path else UNSCOPED


# ---------------------------------------------------------------------------
# the event metadata stats, from the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as memoryview slices, fixed ones as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val


def _map_values(entry) -> tuple[int, object]:
    key, val = 0, b""
    for num, v in _fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_scopes(raw: bytes) -> dict[str, dict[str, str]]:
    """Per device plane, each event metadata name (an ``XLA Ops`` event's
    name) -> the scopes of its ``tf_op`` stat, joined by ``/``
    (``(unscoped)`` for none).  Where one name stands for ops with
    different ``tf_op``s (two programs with the same instruction), the
    scopes they share are kept."""
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:                                  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pn, pv in _fields(plane):
            if pn == 2:                               # XPlane.name
                name = bytes(pv).decode()
            elif pn == 4:                             # event_metadata
                metas.append(pv)
            elif pn == 5:                             # stat_metadata
                sid, sm = _map_values(pv)
                for sn, sv in _fields(sm):
                    if sn == 2:
                        stat_names[sid] = bytes(sv).decode()
        if not _DEVICE.match(name):
            continue
        tf_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        paths: dict[str, tuple] = {}
        for entry in metas if tf_id is not None else ():
            _, meta = _map_values(entry)
            ev_name, tf_op = "", None
            for mn, mv in _fields(meta):
                if mn == 2:                           # XEventMetadata.name
                    ev_name = bytes(mv).decode()
                elif mn == 5:                         # XEventMetadata.stats
                    sid, text, ref = None, None, None
                    for sn, sv in _fields(mv):
                        if sn == 1:
                            sid = sv
                        elif sn == 5:
                            text = bytes(sv).decode()
                        elif sn == 7:
                            ref = sv
                    if sid == tf_id:
                        tf_op = text if text is not None \
                            else stat_names.get(ref, "")
            if tf_op is None:
                continue
            path = scope_path(tf_op)
            if ev_name in paths and paths[ev_name] != path:
                a, k = paths[ev_name], 0
                while k < min(len(a), len(path)) and a[k] == path[k]:
                    k += 1
                path = a[:k] + ("?",)
            paths[ev_name] = path
        out[name] = {n: "/".join(p) or UNSCOPED for n, p in paths.items()}
    return out


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def innermost(spans: list, w0: float, w1: float):
    """Pieces (start, end, name) covering [w0, w1], each labelled with the
    innermost span over it (the latest opened that is still open), or
    ``host`` where no span is."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []
    t = w0

    def advance(to: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if to > t:
            pieces.append((t, to, stack[-1][1] if stack else "host"))
            t = to

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        advance(s)
        stack.append((e, name))
    advance(w1)
    return pieces


def split_idle(idle: list, pieces: list) -> dict[str, float]:
    """Seconds of each idle interval (ns) by the piece labels over it."""
    out: dict[str, float] = {}
    if not idle or not pieces:
        return out
    starts = np.array([p[0] for p in pieces])
    for a, b in idle:
        k = max(0, int(np.searchsorted(starts, a, side="right")) - 1)
        while a < b and k < len(pieces):
            _, pe, name = pieces[k]
            hi = min(b, pe)
            if hi > a:
                out[name] = out.get(name, 0.0) + float(hi - a) * 1e-9
                a = hi
            k += 1
    return out


def idle_intervals(starts, ends, w0: float, w1: float) -> list:
    """The gaps in [w0, w1] that no op interval (``starts``, ``ends``)
    covers."""
    if len(starts) == 0:
        return [(w0, w1)] if w1 > w0 else []
    order = np.argsort(starts, kind="stable")
    s = np.asarray(starts)[order]
    e = np.maximum.accumulate(np.asarray(ends)[order])
    gaps = [(w0, s[0])] + list(zip(e[:-1], s[1:])) + [(e[-1], w1)]
    return [(float(max(a, w0)), float(min(b, w1))) for a, b in gaps
            if min(b, w1) > max(a, w0)]


@dataclass
class EngineStep:
    start_s: float          # from the window's start
    dur_s: float
    sync_s: float           # its engine.sync child
    after_admit: bool       # an engine.admit ran since the previous one


def engine_steps(spans: list, w0: float) -> list[EngineStep]:
    """The decode steps: ``engine.step`` spans holding an ``engine.sync``
    child, each with that child's length and whether an ``engine.admit``
    ran since the previous decode step ended."""
    syncs = np.array(sorted(s for s, _, n in spans if n == "engine.sync"))
    sync_len = {s: e - s for s, e, n in spans if n == "engine.sync"}
    admits = np.array(sorted(s for s, _, n in spans if n == "engine.admit"))
    out: list[EngineStep] = []
    prev_end = -np.inf
    for s, e, n in sorted(spans):
        if n != "engine.step":
            continue
        i = int(np.searchsorted(syncs, s, side="left"))
        if i >= len(syncs) or syncs[i] > e:
            continue                    # no decode in this step
        admitted = bool(np.any((admits >= prev_end) & (admits < s)))
        out.append(EngineStep((s - w0) * 1e-9, (e - s) * 1e-9,
                              sync_len[syncs[i]] * 1e-9, admitted))
        prev_end = e
    return out


def engine_host_ms(steps: list[EngineStep]) -> float | None:
    """Median over the decode steps with no admit since the previous one
    of ``engine.step`` minus its ``engine.sync``: the host time per step
    in which the engine keeps the chip waiting."""
    t = [s.dur_s - s.sync_s for s in steps if not s.after_admit]
    return 1e3 * statistics.median(t) if t else None


# ---------------------------------------------------------------------------
# one trace, by scope
# ---------------------------------------------------------------------------

@dataclass
class Scoped:
    # program (numeric suffix dropped) -> scope path -> device seconds
    scopes: dict = field(default_factory=dict)
    ops: dict = field(default_factory=dict)    # named label -> seconds
    steps: list = field(default_factory=list)  # EngineStep per decode
    idle: dict = field(default_factory=dict)   # innermost span -> seconds

    def _program(self, program: str) -> dict:
        out: dict[str, float] = {}
        for name, by_scope in self.scopes.items():
            if program in name:
                for path, s in by_scope.items():
                    out[path] = out.get(path, 0.0) + s
        return out

    def by_kernel(self, program: str) -> dict:
        """Device seconds of the ops of programs named ``program``, by the
        guarded kernel they belong to (``None`` for the rest)."""
        out: dict = {}
        for path, s in self._program(program).items():
            k = kernel_of(tuple(path.split("/")))
            out[k] = out.get(k, 0.0) + s
        return out

    def by_layer(self, program: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for path, s in self._program(program).items():
            k = layer_of(tuple(path.split("/")) if path != UNSCOPED else ())
            out[k] = out.get(k, 0.0) + s
        return out

    def scope_seconds(self, program: str, scope: str) -> float:
        """Device seconds under a scope (a path component, or a run of
        them such as ``near/fused_matmul``) in programs named
        ``program``."""
        want = f"/{scope}/"
        return sum(s for path, s in self._program(program).items()
                   if want in f"/{path}/")

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def scoped(path, n_devices: int = 1) -> Scoped:
    """The trace at ``path`` (a file, or a directory holding one) by
    scope, with the engine's steps and idle time by innermost span over
    the window of ``bench/trace.py`` (first ``bench.*`` span to the end
    of the last ``bench.sync``)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = find_xplane(path)
    raw = path.read_bytes()
    names = op_scopes(raw)
    data = ProfileData.from_serialized_xspace(raw)
    spans, devices = [], []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in line.events if ev.name in HOST_SPANS]
    bench = [s for s in spans if s[2] in SPANS]
    if not bench:
        raise ValueError("the trace holds none of the benchmark's spans")
    w0 = min(s for s, _, _ in bench)
    w1 = max((e for _, e, n in bench if n == "bench.sync"),
             default=max(e for _, e, _ in bench))
    out = Scoped(steps=engine_steps(spans, w0))
    pieces = innermost(spans, w0, w1)
    for plane in sorted(devices, key=lambda p: p.name)[:n_devices]:
        scope_of = names.get(plane.name, {})
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          _RUN.sub("", ev.name))
                         for line in plane.lines if line.name == "XLA Modules"
                         for ev in line.events)
        m_starts = np.array([m[0] for m in modules])
        starts, ends = [], []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e < w0 or s > w1:
                    continue
                starts.append(s)
                ends.append(e)
                label, container = op_label(ev.name)
                if container:
                    continue
                k = int(np.searchsorted(m_starts, s, side="right")) - 1
                prog = modules[k][2] if k >= 0 and modules[k][1] >= s \
                    else "(no program)"
                scope = scope_of.get(ev.name, UNSCOPED)
                secs = ev.duration_ns * 1e-9
                by_scope = out.scopes.setdefault(prog, {})
                by_scope[scope] = by_scope.get(scope, 0.0) + secs
                lead = kernel_of(tuple(scope.split("/"))) or scope
                key = f"{lead} {label}"
                out.ops[key] = out.ops.get(key, 0.0) + secs
        idle = idle_intervals(starts, ends, w0, w1)
        for k, v in split_idle(idle, pieces).items():
            out.idle[k] = out.idle.get(k, 0.0) + v
    return out


def main(argv: list[str]) -> int:
    program = argv[1] if len(argv) > 1 else "step_impl"
    s = scoped(argv[0])
    n = len(s.steps) or 1
    print(json.dumps({
        "steps": len(s.steps), "engine_host_ms": engine_host_ms(s.steps),
        "by_layer_ms": {k: 1e3 * v / n for k, v in sorted(
            s.by_layer(program).items(), key=lambda kv: -kv[1])},
        "by_kernel_ms": {str(k): 1e3 * v / n for k, v in
                         s.by_kernel(program).items()},
        "near_ms": 1e3 * s.scope_seconds(program, "near") / n,
        "idle_ms_per_step": {k: 1e3 * v / n for k, v in s.idle.items()},
        **s.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
