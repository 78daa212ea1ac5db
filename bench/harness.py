"""One run of one cell: build the engine, warm its shapes, drive it for a
fixed window, drain, check the outputs against the plain reference, and
reduce what was recorded to the cell's metrics.

Everything the run needs is found by name from ``BENCHMARK.json``:
the configuration file, ``bench/traffic/<mix>.json``,
``bench/cells/<cell>.json`` (engine sizes and the correctness limit),
``bench/reference/<family>.py`` and ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from bench.flops import Shapes
from bench.generator import plan_requests, prompt_range

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# fixed paths inside the checkout: the offload-plan cache and the
# profiler's scratch directory (emptied after each traced run)
PLAN_CACHE = ROOT / ".bench_cache" / "plans"
TRACE_DIR = ROOT / ".bench_cache" / "trace"

# ModelConfig field for each key of a configuration file
PROGRAM_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias", "hidden_act": "act",
    "torch_dtype": "dtype", "qk_norm": "qk_norm",
}


class DeviceError(RuntimeError):
    """The run cannot measure on what JAX found."""


# ---------------------------------------------------------------------------
# the cell, by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<mix>.json
    sizes: dict             # bench/cells/<cell>.json
    end_to_end: list        # this cell's end-to-end metric entries
    per_layer: list         # this cell's per-layer metric entries


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    work = [w for w in spec["workloads"] if w["name"] == name]
    if not work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = work[0]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        sizes=json.loads(
            (root / "bench" / "cells" / f"{name}.json").read_text()),
        end_to_end=_for_cell(spec["end_to_end"], name),
        per_layer=_for_cell(spec["per_layer"], name))


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(chips: int):
    """The devices the run uses and their peaks; raises DeviceError
    unless JAX runs on enough TPU chips of a known kind."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise DeviceError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices[:chips], load_peaks(devices[0].device_kind)


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: its arch id
    with every published number of the file put in place."""
    from repro.configs import get_config

    over = {dst: config[src] for src, dst in PROGRAM_KEYS.items()
            if src in config}
    return dataclasses.replace(get_config(config["arch"]), **over)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the file of the name before its
    first dot (``decode_step_ms.batch`` reads with ``decode_step_ms``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path).read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclass
class ReqRecord:
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    sent: float = math.nan          # the generator picked it up
    admitted: float = math.nan      # admit() accepted it
    tokens: list = field(default_factory=list)
    times: list = field(default_factory=list)   # host time of each token
    status: str = ""


@dataclass
class StepRecord:
    start: float
    end: float
    lengths: list           # attended length of each active row
    after_admit: bool       # an admit ran since the previous step


@dataclass
class RunRecord:
    cell: str
    shapes: Shapes
    peak: dict
    seconds: float          # the window
    setup_s: float
    requests: list          # ReqRecord of every request that was sent
    attempted: list         # the ReqRecords the run answers for
    steps: list
    admits: list            # (host time, prompt length)
    window_tokens: int
    end_s: float            # host time the drain ended
    trace: object = None    # bench.trace.TraceSummary of a traced run


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (no interpolation)."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


# ---------------------------------------------------------------------------
# engine and warm-up
# ---------------------------------------------------------------------------

def seed32(seed: int) -> int:
    """A 31-bit seed for JAX's key and the engine, from any whole seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def build_engine(pcfg, params, sizes: dict, seed: int):
    """The engine as the serving launcher builds it (the program's
    default offload policy), over weights made by the benchmark."""
    from repro.core.policy import OffloadPolicy
    from repro.serve import Engine

    return Engine(pcfg, params, slots=int(sizes["slots"]),
                  max_len=int(sizes["max_len"]), seed=seed32(seed),
                  offload=True, offload_policy=OffloadPolicy())


def warm_lengths(engine, lo: int, hi: int) -> list[int]:
    """One prompt length per admit shape that prompts of lo..hi tokens
    reach, under the engine's own bucketing."""
    from repro.serve.kv_pool import bucket_length

    top: dict[int, int] = {}
    for n in range(lo, hi + 1):
        top[bucket_length(n, engine.max_len)] = n
    return sorted(top.values())


def warm_up(engine, lengths: list[int], vocab: int) -> None:
    """Admit one two-token request per admit shape and decode it out:
    compiles every admit bucket and the decode step."""
    import jax

    from repro.serve import Request

    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        req = Request(rng.integers(0, vocab, size=n, dtype=np.int32),
                      max_new_tokens=2, rid=-(i + 1))
        if not engine.admit(req):
            raise RuntimeError(f"warm-up could not admit a {n}-token prompt")
        while engine.step():
            pass
    engine.pop_finished()
    jax.block_until_ready(engine.cache)


class CompileCounter:
    """Backend compiles (persistent-cache loads included) seen by JAX."""

    def __init__(self):
        import jax

        self.count = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def drive(engine, planned, seconds: float, backlog: bool,
          spans: bool = False, on_open=None, on_close=None):
    """Offer ``planned`` to the engine through ``admit`` and ``step``.

    The clock reads 0 when the window opens; requests due before it (the
    pre-roll) bring the engine to its steady state and are not answered
    for.  Open loop: a request is sent at its due time whatever the
    engine is doing, and after the window closes the run goes on until
    every request due in the window has its first token.  A backlog is
    served in order; what is queued at the close was never sent, and the
    run ends at the close.  ``on_open`` runs once as the window opens,
    ``on_close`` once as it closes, after a device sync.  Returns (requests sent, attempted, steps,
    admits, window tokens, end time), times in seconds on that clock."""
    import jax

    from repro.serve import Request

    if spans:
        from jax.profiler import TraceAnnotation as span
    else:
        def span(_name):
            return nullcontext()

    clock = time.perf_counter
    todo = deque(planned)
    queue: deque = deque()
    reqs: dict[int, ReqRecord] = {}
    active: dict[int, ReqRecord] = {}
    steps: list[StepRecord] = []
    admits: list[tuple[float, int]] = []
    window_tokens = 0
    admitted_since = False
    opened = closed = False
    start = min([0.0] + [p.due_s for p in planned])
    t0 = clock() - start
    waiting: set = set()        # due in the window, no first token yet

    while True:
        now = clock() - t0
        if not opened and now >= 0:
            opened = True
            if on_open is not None:
                on_open()
        if not closed and now >= seconds:
            closed = True
            with span("bench.sync"):
                jax.block_until_ready(engine.cache)
            if on_close is not None:
                on_close()
            if backlog:
                break
        if closed and not waiting:
            break
        while todo and todo[0].due_s <= now:
            p = todo.popleft()
            r = ReqRecord(p.rid, p.due_s, p.prompt, p.max_new_tokens,
                          sent=now)
            reqs[p.rid] = r
            queue.append(r)
            if r.due >= 0 and not backlog:
                waiting.add(r.rid)
        while queue:
            r = queue[0]
            with span("bench.admit"):
                # greedy: the check compares greedy tokens only
                ok = engine.admit(Request(r.prompt, max_new_tokens=r.max_new,
                                          temperature=0.0, rid=r.rid))
            if not ok:
                break
            queue.popleft()
            r.admitted = clock() - t0
            admits.append((r.admitted, len(r.prompt)))
            active[r.rid] = r
            admitted_since = True
            if backlog and 0 <= r.admitted < seconds:
                waiting.add(r.rid)
        if active:
            ts = clock() - t0
            with span("bench.step"):
                out = engine.step()
            te = clock() - t0
            lengths = []
            for rid, tok in out:
                r = active[rid]
                lengths.append(len(r.prompt) + len(r.tokens) + 1)
                r.tokens.append(int(tok))
                r.times.append(te)
                waiting.discard(rid)
            if out:
                steps.append(StepRecord(ts, te, lengths, admitted_since))
                admitted_since = False
                if 0 <= te <= seconds:
                    window_tokens += len(out)
            for ev in engine.pop_finished():
                r = active.pop(ev.rid, None) or reqs[ev.rid]
                r.status = ev.status
                if ev.status != "ok":
                    waiting.discard(ev.rid)
            continue
        if queue:
            raise RuntimeError("an idle engine refused a request")
        if closed:
            break
        wake = min(todo[0].due_s, seconds) if todo else seconds
        with span("bench.wait"):
            delay = wake - (clock() - t0)
            if delay > 0.002:
                time.sleep(delay - 0.001)
            while clock() - t0 < wake:
                pass
    end = clock() - t0
    sent = [reqs[k] for k in sorted(reqs)]
    if backlog:
        attempted = [r for r in sent if 0 <= r.admitted < seconds]
    else:
        attempted = [r for r in sent if r.due >= 0]
    return sent, attempted, steps, admits, window_tokens, end


# ---------------------------------------------------------------------------
# the whole run
# ---------------------------------------------------------------------------

@dataclass
class Outcome:
    record: RunRecord
    checks: dict            # name -> (value, limit)
    correct: bool
    failed: int
    notes: list             # earlier lines: what the run saw
    memory_peak_bytes: int
    gaps: dict = None       # per-request logit gaps (and the control's)
    # the same checks with the control's gaps in place of the served ones
    control_checks: dict = None
    control_correct: bool = None


def make_params(model, seed: int):
    from bench.weights import make_params as make
    return make(model, seed32(seed))


@dataclass
class Setup:
    """What set-up leaves for the window: the program's config, the
    benchmark's weights and the warmed engine."""
    pcfg: object
    shapes: Shapes
    model: object
    params: object
    engine: object
    counter: CompileCounter


def set_up(cell: Cell, seed: int) -> Setup:
    """Weights from the seed, the engine over them, every admit shape of
    the cell's traffic and the decode step warmed."""
    from repro.models import build_model

    os.environ.setdefault("MPU_PLAN_CACHE", str(PLAN_CACHE))
    pcfg = program_config(cell.config)
    counter = CompileCounter()
    model = build_model(pcfg)
    params = make_params(model, seed)
    engine = build_engine(pcfg, params, cell.sizes, seed)
    lo, hi = prompt_range(cell.traffic)
    warm_up(engine, warm_lengths(engine, lo, hi), pcfg.vocab_size)
    return Setup(pcfg, Shapes.from_config(cell.config), model, params,
                 engine, counter)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peak: dict, *,
             control: bool = False) -> Outcome:
    """Set up, drive, drain and check one run of ``cell``."""
    s = set_up(cell, seed)
    return measure(s, cell, seed, seconds, trace, t_start, devices, peak,
                   control=control)


def measure(s: Setup, cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, devices, peak: dict, *,
            control: bool = False) -> Outcome:
    """Drive the warmed engine for the window, drain, and check the
    outputs; the engine's state is freed before the reference runs.
    Set-up is ``t_start`` to the window's opening."""
    import jax

    from repro.kernels.guard import kernel_guard

    engine, counter = s.engine, s.counter
    planned, backlog = plan_requests(cell.traffic, seed, seconds,
                                     s.pcfg.vocab_size)
    guard0 = dict(kernel_guard().stats())
    serve0 = dict(engine.serve_stats)
    state = {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def on_open():
        # set-up ends, and the window's compile count starts, here
        state["setup_s"] = time.perf_counter() - t_start
        state["compiles0"] = counter.count
        if trace:
            jax.block_until_ready(engine.cache)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def on_close():
        state["compiles"] = counter.count - state["compiles0"]
        if trace:
            jax.profiler.stop_trace()

    sent, attempted, steps, admits, window_tokens, end = drive(
        engine, planned, seconds, backlog, spans=trace, on_open=on_open,
        on_close=on_close)
    jax.block_until_ready(engine.cache)
    setup_s = state["setup_s"]
    compiles_window = state["compiles"]
    compiles_drain = counter.count - state["compiles0"] - compiles_window
    guard1 = dict(kernel_guard().stats())
    serve1 = dict(engine.serve_stats)
    mem = devices[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    notes = []
    report = engine.explain_decode()
    notes.append(f"decode plan: mode {report.policy.mode}, "
                 f"{report.n_fused} near / {report.n_declined} declined")
    notes.append(f"serve_stats before the window: {serve0}")
    notes.append(f"serve_stats after the drain: {serve1}")
    notes.append(f"offload_stats: {engine.offload_stats}")
    notes.append(f"kernel guard: {guard1}")
    lag = [r.sent - r.due for r in sent]
    if lag:
        notes.append(f"generator lag due->sent: p99 {percentile(lag, 99)} s,"
                     f" max {max(lag)} s over {len(lag)} requests")
    notes.append(f"compiles in the window: {compiles_window}; in the "
                 f"drain: {compiles_drain}")
    notes.append(f"requests: planned {len(planned)}, sent {len(sent)}, "
                 f"attempted {len(attempted)}; steps {len(steps)}; window "
                 f"tokens {window_tokens}; the run ended {end - seconds} s "
                 f"after the window closed")
    notes.append(f"memory_peak_bytes: {memory_peak}")
    guard_bad = (guard1["kernel_failures"] - guard0["kernel_failures"]
                 + guard1["kernel_fallbacks"] - guard0["kernel_fallbacks"])

    record = RunRecord(cell.name, s.shapes, peak, seconds, setup_s, sent,
                       attempted, steps, admits, window_tokens, end)
    if trace:
        from bench.trace import reduce_trace
        record.trace = reduce_trace(TRACE_DIR, n_devices=len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        notes.append(f"device seconds by program: {record.trace.modules}, "
                     f"runs: {record.trace.module_runs}")

    # free the program's state before the reference runs
    engine.cache = None
    engine._state = None
    s.engine = engine = None
    gc.collect()

    # a request failed when the engine ended it other than "ok", or when
    # it never answered; one still streaming at the end has not failed
    failed_reqs = [r for r in attempted
                   if r.status not in ("", "ok") or not r.tokens]
    ok = [r for r in sent if r.status == "ok" and r.tokens]
    gaps = check_outputs(cell, s.params, s.shapes, ok, seed, control=control)
    checks, correct = judge(cell, gaps["served"], len(failed_reqs))
    notes.append(f"reference: {len(gaps['served'])} requests, "
                 f"{gaps['tokens']} served tokens compared")
    out = Outcome(record, checks, correct, len(failed_reqs) + guard_bad,
                  notes, memory_peak, gaps)
    if control:
        out.control_checks, out.control_correct = judge(
            cell, gaps["control"], len(failed_reqs))
    return out


def judge(cell: Cell, gaps: list, not_ok: int) -> tuple[dict, bool]:
    """The numbers compared, each with its limit, and whether all keep
    them: the widest logit gap over the sampled requests, and the
    requests due in the window that did not end ``ok``."""
    checks = {
        "max_logit_gap": (max(gaps) if gaps else math.inf,
                          float(cell.sizes["check"]["max_logit_gap"])),
        "requests_not_ok": (not_ok, 0),
    }
    return checks, all(v <= lim for v, lim in checks.values())


def check_outputs(cell: Cell, params, shapes: Shapes, finished: list,
                  seed: int, *, control: bool = False) -> dict:
    """The plain reference over a sample of finished requests, drawn from
    the seed with the longest among them: for each, the widest gap by
    which a served token's reference logit lies below the reference's
    best (and, with ``control``, the same for the token the control's
    lower precision puts first)."""
    ref_mod = load_module(BENCH / "reference" / f"{cell.config['family']}.py")
    n = int(cell.sizes["check"]["sample_requests"])
    pick: list = []
    if finished:
        longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
        rest = [r for r in finished if r is not longest]
        rng = np.random.default_rng(seed)
        idx = rng.permutation(len(rest))[:max(0, n - 1)]
        pick = [longest] + [rest[i] for i in sorted(idx)]
    max_new = int(cell.traffic["output_len"]["max"])
    ref = ref_mod.Reference(params, cell.config,
                            max_len=int(cell.sizes["max_len"]),
                            max_new=max_new)
    out = {"served": [], "control": [], "tokens": 0, "rids": []}
    for r in pick:
        served = np.asarray(r.tokens, np.int32)
        g = ref.gaps(np.asarray(r.prompt, np.int32), served,
                     control=control)
        out["served"].append(float(np.max(g["served"])))
        if control:
            out["control"].append(float(np.max(g["control"])))
        out["tokens"] += len(served)
        out["rids"].append(r.rid)
    return out


def read_metrics(entries: list, record: RunRecord) -> dict:
    """Each metric's reader over the run; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
