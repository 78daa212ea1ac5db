"""Roofline term derivation from the compiled dry-run artifacts.

Three sources, because XLA's ``cost_analysis()`` visits every while-loop
body exactly ONCE (verified: a 10-step scan reports 1/10th the FLOPs of
the unrolled loop), which breaks trip-count accounting for our
scan-over-layers / scan-over-blocks models:

  * ``jaxpr_cost``       exact FLOPs + naive/fused HBM bytes by walking the
                         jaxpr with scan-length multipliers (fused bytes
                         use the Algorithm-1 offload segments — the paper's
                         technique applied to the byte accounting).  The
                         segment bytes come from ``Segment.io_bytes``, so
                         matmul-anchored segments — including the
                         grad-time dlhs/drhs backward forms on train
                         traces — model the kernels' actual re-streaming
                         (fwd/dlhs: weight once per row block; drhs: both
                         operands once per crossing grid block; batched
                         anchors price PER-BATCH row blocks against the
                         full rhs, and flash-shaped attention segments
                         charge zero bytes for the score matrix).
  * ``analytic_bytes``   the kernel-aware HBM-traffic floor (params,
                         optimizer, activation streams, caches) — what the
                         Pallas/TPU execution actually streams.
  * ``collective_bytes`` parsed from the compiled HLO text, with each
                         collective's bytes multiplied by its enclosing
                         while-loops' trip counts (parsed from loop
                         condition constants).

Roofline terms (TPU v5e, per chip):
    compute    = FLOPs / (chips * 197e12)
    memory     = bytes / (chips * 819e9)
    collective = ici_bytes / (chips * 4 * 50e9)  [+ DCN pod term]
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.configs.base import MeshConfig, ModelConfig, ShapeConfig
from repro.core.machine import V5E

_ELEMENTWISE_FLOPS = {
    "exp": 4, "log": 4, "tanh": 6, "logistic": 6, "erf": 6, "rsqrt": 2,
    "sqrt": 2, "sin": 4, "cos": 4, "div": 2, "pow": 8, "integer_pow": 2,
}


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

@dataclass
class JaxprCost:
    flops: float = 0.0
    bytes_naive: float = 0.0   # every eqn round-trips HBM
    bytes_fused: float = 0.0   # Algorithm-1 near segments fused
    unknown_trip_while: int = 0

    def add(self, other: "JaxprCost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes_naive += other.bytes_naive * mult
        self.bytes_fused += other.bytes_fused * mult
        self.unknown_trip_while += other.unknown_trip_while


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:
        return 0


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    k = math.prod(lhs.shape[i] for i in lc) or 1
    b = math.prod(lhs.shape[i] for i in lb) or 1
    m = math.prod(lhs.shape[i] for i in range(len(lhs.shape))
                  if i not in lc and i not in lb) or 1
    n = math.prod(rhs.shape[i] for i in range(len(rhs.shape))
                  if i not in rc and i not in rb) or 1
    return 2.0 * b * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel [*spatial, in/groups, out]
    spatial = math.prod(rhs.shape[:-2]) or 1
    in_per_group = rhs.shape[-2]
    return 2.0 * out.size * spatial * in_per_group


def jaxpr_cost(closed, *, with_fusion: bool = True,
               _depth: int = 0) -> JaxprCost:
    """Walk a ClosedJaxpr; exact w.r.t. scan trip counts.

    ``with_fusion=False`` skips the Algorithm-1 segment pass (fast path
    for FLOP-only accounting on very large jaxprs)."""
    from repro.core.offload import plan_offload

    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    cost = JaxprCost()

    # fused-byte accounting via the offload planner on this (sub)jaxpr
    seg_eqns, seg_io = set(), {}
    if with_fusion:
        try:
            import jax.extend.core as jexc
            wrapper = closed if hasattr(closed, "jaxpr") else \
                jexc.ClosedJaxpr(jaxpr, [])
            plan = plan_offload(wrapper, min_segment=2)
            seg_eqns = {i for s in plan.segments for i in s.all_eqn_idx}
            for s in plan.segments:
                # Segment.io_bytes is the same accounting plan_offload
                # uses (anchored rhs counted once per row block)
                seg_io[s.all_eqn_idx[0]] = float(s.io_bytes())
        except Exception:
            seg_eqns, seg_io = set(), {}

    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        io_bytes = float(sum(
            _aval_bytes(v.aval) for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v, "aval")))
        sub_mult = None
        sub = None
        if name == "jit":
            sub, sub_mult = eqn.params["jaxpr"], 1.0
        elif name == "closed_call":
            sub, sub_mult = eqn.params["call_jaxpr"], 1.0
        elif name == "shard_map":
            # inner jaxpr sees per-shard LOCAL shapes; total executed work
            # across the mesh = local x mesh.size (replication over unused
            # axes is genuinely redundant execution and counts as such)
            sub = eqn.params["jaxpr"]
            sub_mult = float(getattr(eqn.params.get("mesh"), "size", 1))
        elif name in ("custom_jvp_call", "custom_vjp_call"):
            sub = eqn.params["call_jaxpr"]
            sub_mult = 1.0
        elif name in ("remat", "checkpoint", "remat2"):
            sub, sub_mult = eqn.params["jaxpr"], 1.0
        elif name == "scan":
            sub, sub_mult = eqn.params["jaxpr"], float(eqn.params["length"])
        elif name == "while":
            sub, sub_mult = eqn.params["body_jaxpr"], 1.0
            cost.unknown_trip_while += 1
        elif name == "cond":
            branches = eqn.params["branches"]
            branch_costs = [jaxpr_cost(b, with_fusion=with_fusion,
                                       _depth=_depth + 1)
                            for b in branches]
            worst = max(branch_costs, key=lambda c: c.flops)
            cost.add(worst)
            continue

        if sub is not None:
            cost.add(jaxpr_cost(sub, with_fusion=with_fusion,
                                _depth=_depth + 1), sub_mult)
            continue

        # leaf op
        out_sizes = sum(v.aval.size for v in eqn.outvars)
        if name == "dot_general":
            cost.flops += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            cost.flops += _conv_flops(eqn)
        elif name in _ELEMENTWISE_FLOPS:
            cost.flops += out_sizes * _ELEMENTWISE_FLOPS[name]
        elif name.startswith("reduce_") or name in ("cumsum", "cumprod",
                                                    "cummax", "argmax",
                                                    "argmin"):
            cost.flops += sum(v.aval.size for v in eqn.invars
                              if hasattr(v, "aval"))
        else:
            cost.flops += out_sizes
        cost.bytes_naive += io_bytes
        if i in seg_io:
            cost.bytes_fused += seg_io[i]
        elif i not in seg_eqns:
            cost.bytes_fused += io_bytes
    return cost


# ---------------------------------------------------------------------------
# analytic HBM-traffic floor (kernel-aware)
# ---------------------------------------------------------------------------

def analytic_bytes(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """HBM bytes per step assuming near-bank/fused execution: every weight
    read once per pass, flash-attention streams (no score materialization),
    single-pass norms/elementwise, fp32 optimizer sharded update."""
    p_total = cfg.param_count()
    p_active = cfg.active_param_count()
    b, s = shape.global_batch, shape.seq_len
    d = cfg.d_model
    tokens = b * s
    act = 2  # bf16
    h = cfg.resolved_head_dim
    kv_bytes_tok = 2 * cfg.num_kv_heads * h * act  # k+v per token per layer
    n_attn = sum(1 for k in cfg.layer_kinds()
                 if k in ("attention", "shared_attention"))

    if shape.kind == "train":
        # fwd read (bf16 cast) + bwd read + grad write(fp32) + adam r/w
        weights = p_total * (2 + 2 + 4) + p_total * 4 * (2 + 2 + 2)
        # activation streams: ~10 tensor r/w per block fwd, x2 bwd, x1.3
        # remat recompute
        act_bytes = cfg.num_layers * 10 * tokens * d * act * 3.3
        logits = tokens * cfg.vocab_size * 4 * 2  # fwd write + bwd read
        if cfg.moe is not None:
            # every expert weight touched per layer already in `weights`;
            # dispatch buffers ~2x activations of moe layers
            act_bytes *= 1.3
        return float(weights + act_bytes + logits)
    if shape.kind == "prefill":
        weights = p_total * 2
        act_bytes = cfg.num_layers * 8 * tokens * d * act
        cache_write = n_attn * tokens * kv_bytes_tok
        logits = b * cfg.vocab_size * 4
        return float(weights + act_bytes + cache_write + logits)
    # decode: one token; stream active params + the whole KV cache
    weights = p_active * 2
    t_eff = min(s, cfg.sliding_window) if cfg.sliding_window else s
    cache = n_attn * b * t_eff * kv_bytes_tok
    ssm_states = 0.0
    for kind in cfg.layer_kinds():
        if kind == "mamba2" and cfg.ssm:
            d_in = cfg.ssm.expand * d
            nh = d_in // cfg.ssm.head_dim
            ssm_states += 2 * b * nh * cfg.ssm.head_dim * cfg.ssm.state_dim * 4
        if kind == "rwkv6" and cfg.rwkv:
            nh = d // cfg.rwkv.head_dim
            ssm_states += 2 * b * nh * cfg.rwkv.head_dim ** 2 * 4
    act_bytes = cfg.num_layers * 8 * b * d * act
    logits = b * cfg.vocab_size * 4
    return float(weights + cache + ssm_states + act_bytes + logits)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference) per the assignment,
    with N = active params and D = tokens processed."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch  # one token per sequence


# ---------------------------------------------------------------------------
# HLO collective parser (trip-count aware)
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f32|f16|bf16|s32|u32|s8|u8|f64|s64|pred|s16|u16)"
                       r"\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*\(?([a-z0-9]+\[[^\]]*\][^)]*?)\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_WHILE_RE = re.compile(
    r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_COMP_RE = re.compile(r"^%?([\w.\-]+)\s+\([^)]*\)\s*->", re.M)
_CONST_CMP_RE = re.compile(r"constant\((\d+)\)")
_CMP_RE = re.compile(
    r"compare\(%?([\w.\-]+),\s*%?([\w.\-]+)\)[^\n]*direction=(LT|GT|LE|GE|NE)")
_CONST_DEF_RE = r"%?{name}\s*=\s*\w+\[\]\s*constant\((\d+)\)"

_DTYPE_BYTES = {"f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2,
                "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _shape_bytes(sig: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(sig):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def split_computations(hlo: str) -> dict[str, str]:
    """Split HLO module text into named computations."""
    comps: dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        stripped = line.strip()
        if (line.startswith("%") or line.startswith("ENTRY")
                or (not line.startswith(" ") and "->" in line
                    and "{" in line)):
            if cur_name:
                comps[cur_name] = "\n".join(cur_lines)
            name = stripped.split(" ")[0].lstrip("%")
            if name == "ENTRY":
                name = stripped.split(" ")[1].lstrip("%")
            cur_name, cur_lines = name, [line]
        elif cur_name:
            cur_lines.append(line)
    if cur_name:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _trip_count(cond_text: str) -> float:
    """Trip count from a loop condition: the constant operand of the
    comparison that guards the loop (falls back to max constant)."""
    for m in _CMP_RE.finditer(cond_text):
        for operand in (m.group(2), m.group(1)):
            dm = re.search(_CONST_DEF_RE.format(name=re.escape(operand)),
                           cond_text)
            if dm:
                return float(dm.group(1))
    consts = [int(c) for c in _CONST_CMP_RE.findall(cond_text)]
    return float(max(consts)) if consts else 1.0


def collective_bytes(hlo: str) -> dict[str, float]:
    """Sum collective result bytes (post-SPMD local shapes — i.e. bytes
    landing per device), multiplying by enclosing while-loop trip counts
    (parsed from each loop condition's compare constant)."""
    comps = split_computations(hlo)
    # body computation -> trip count
    trip: dict[str, float] = {}
    parent: dict[str, str] = {}
    for comp_name, text in comps.items():
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            trip[body] = _trip_count(comps.get(cond, ""))
            parent[body] = comp_name

    def multiplier(comp: str) -> float:
        mult, seen = 1.0, set()
        while comp in parent and comp not in seen:
            seen.add(comp)
            mult *= trip.get(comp, 1.0)
            comp = parent[comp]
        return mult

    out: dict[str, float] = {}
    for comp_name, text in comps.items():
        mult = multiplier(comp_name) if comp_name in parent else 1.0
        for m in _COLL_RE.finditer(text):
            kind = m.group(2)
            nbytes = _shape_bytes(m.group(1)) * mult
            out[kind] = out.get(kind, 0.0) + nbytes
    return out


# ---------------------------------------------------------------------------
# roofline assembly
# ---------------------------------------------------------------------------

@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: tuple[int, ...]
    chips: int
    hlo_flops: float
    bytes_fused: float
    bytes_naive: float
    bytes_analytic: float
    ici_bytes: float
    dcn_bytes: float
    model_flops: float
    per_device_hbm_peak: float = 0.0
    collectives: dict = field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * V5E.peak_bf16_flops)

    @property
    def memory_s(self) -> float:
        return self.bytes_analytic / (self.chips * V5E.hbm_gbps * 1e9)

    @property
    def collective_s(self) -> float:
        # ici_bytes are parsed from the post-SPMD module: local shapes =
        # bytes through ONE device's links — no further /chips.
        links = V5E.ici_link_gbps * 1e9 * V5E.ici_links
        t = self.ici_bytes / links
        if self.dcn_bytes:
            t += self.dcn_bytes / 25e9  # DCN ~25 GB/s per chip
        return t

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def floor_s(self) -> float:
        """The unavoidable time: useful-FLOPs compute floor or the HBM
        streaming floor, whichever binds (memory-bound shapes like decode
        can never beat the byte floor)."""
        ideal_compute = self.model_flops / (self.chips * V5E.peak_bf16_flops)
        return max(ideal_compute, self.memory_s)

    @property
    def roofline_fraction(self) -> float:
        """floor / achieved-bound: 1.0 == running at the roofline."""
        return self.floor_s / max(self.bound_s, 1e-30)

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": list(self.mesh),
            "chips": self.chips, "hlo_flops": self.hlo_flops,
            "bytes_fused": self.bytes_fused, "bytes_naive": self.bytes_naive,
            "bytes_analytic": self.bytes_analytic,
            "ici_bytes": self.ici_bytes, "dcn_bytes": self.dcn_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "per_device_hbm_peak": self.per_device_hbm_peak,
            "collectives": self.collectives,
        }
