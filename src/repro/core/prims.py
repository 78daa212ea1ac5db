"""The eqn-classification registry — ONE home for the primitive tables.

Both the location annotator (``repro.core.locator``, Algorithm 1 over
jaxprs) and the static plan verifier (``repro.analysis``) classify eqns
by primitive name.  Keeping two copies of these tables is how silent
drift happens — the planner admits a prim the verifier thinks is far, or
vice versa — so the tables live here and everyone imports them.
``tests/test_locator.py`` asserts both consumers reference *these*
objects (identity, not equality).

Tier precedence in ``eqn_tier`` is anchor > reduce > near > layout > far;
a name in several tables (e.g. ``dot_general`` is both FAR and ANCHOR)
resolves to the strongest segmentation capability.
"""
from __future__ import annotations

# elementwise near-bank-capable primitives (value-chain ALU/SFU ops).
# "add_any" is AD's cotangent-accumulation primitive (add_jaxvals_p) —
# backward traces are stitched together with it, so leaving it far would
# cut every grad-time value chain in half.
ELEMENTWISE_PRIMS = {
    "add", "add_any", "sub", "mul", "div", "max", "min", "neg", "abs",
    "exp", "log", "log1p", "expm1", "tanh", "sqrt", "rsqrt", "cbrt",
    "logistic", "sin", "cos", "tan", "erf", "erfc", "erf_inv",
    "integer_pow", "pow", "floor", "ceil", "round", "square",
    "select_n", "convert_element_type", "clamp", "nextafter",
    "eq", "ne", "lt", "le", "gt", "ge", "and", "or", "xor", "not",
    "is_finite", "exp2", "rem", "atan2", "real", "imag",
    "copy", "sign", "population_count", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "stop_gradient",
}

# layout-only primitives the segmenter may absorb into a near-bank
# segment (§IV-B3 multiple-activated-row-buffers: these move no data once
# operands are viewed as [rows, lanes] blocks — broadcasts become
# per-block index remaps, lane splits/concats become block-column
# slices).  They are not ALU work (the planner does not count them
# toward ``min_segment``) and they are not near-eligible on their own;
# ``repro.core.offload.plan_offload`` admits them only when the 2-D
# block views of their operands line up with the surrounding segment.
LAYOUT_PRIMS = {
    "broadcast_in_dim", "reshape", "squeeze", "concatenate", "slice",
}

# anchor tier (§IV-B1 applied to the MXU boundary): primitives that are
# far by opcode (they need the MXU) but may *open* a near-bank segment —
# the offload planner fuses their elementwise prologue/epilogue around
# the contraction so the product tensor never round-trips HBM (the
# fused-GEMM-epilogue pattern).  Sits between near and far: the eqn's
# own location stays F, yet its segment is emitted as one near kernel.
# Three contraction forms qualify (repro.core.offload.try_admit_anchor):
#   fwd   x[M,K] @ w[K,N]        — lhs contracts its lane axis, rc=(0,)
#   dlhs  g[M,N] @ wT            — the grad-time dx: rc=(1,), the [K,N]
#                                  weight read column-major in-kernel
#   drhs  xT[K,M] @ g[M,N]       — the grad-time dw: both operands
#                                  contract ALL their leading (row) dims,
#                                  per-bank f32 accumulation over M
# Each form also admits matching leading batch dims on BOTH operands
# (attention's [B,H,S,D] dots): batch dims become outer grid axes, each
# grid step contracting its own batch slice, with k/n staying per-batch.
# A batched dlhs whose softmaxed output feeds a second batched dot as
# its streamed lhs upgrades to ONE flash-shaped segment (QK^T ->
# scale/row-softmax -> PV, the score matrix never touching HBM); see
# repro.core.offload._try_admit_flash.
ANCHOR_PRIMS = {"dot_general"}

# lane-axis reductions the planner may admit INTO a near segment: with
# every operand viewed as [rows, lanes] blocks, a reduction over the
# last (lane) axis completes inside one block — the row statistic and
# its re-broadcast both happen in VMEM (rmsnorm/softmax row stats).
# Reductions over any other axis stay far.
REDUCE_LANE_PRIMS = {"reduce_sum", "reduce_max"}

# far-bank-only opcode set (hardware policy step 1): MXU / data-movement /
# control primitives that need the full far pipeline (TPU: the MXU and
# XLA's gather/scatter/sort machinery).  Every name here must be a real
# jax primitive name (tests validate against the live registry); note
# the hyphenated scatter variants ("scatter-add") and "remat2" — those
# ARE the primitive names, not typos.
FAR_PRIMS = {
    "dot_general", "conv_general_dilated", "gather", "scatter",
    "scatter-add", "dynamic_slice", "dynamic_update_slice",
    "sort", "top_k", "while", "cond", "scan", "jit", "custom_jvp_call",
    "custom_vjp_call", "remat2",
    "rng_uniform", "rng_bit_generator", "random_bits", "random_seed",
    "random_wrap", "random_fold_in", "iota", "argmax", "argmin",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "cumsum", "cumprod", "cummax", "all_gather",
    "psum", "all_to_all", "ppermute", "reduce_precision",
}

# index-like operands (position -> always-F "address registers")
_INDEX_OPERANDS = {
    "gather": (1,),                  # indices
    "scatter": (1,),
    "scatter-add": (1,),
    "dynamic_slice": None,           # all but operand 0 are starts
    "dynamic_update_slice": None,    # operands 2+ are starts
}


def eqn_tier(name: str) -> str:
    """Segmentation tier of a primitive name.

    ``near``   — elementwise value op, fuses freely
    ``layout`` — layout-only, absorbed when block views line up
    ``anchor`` — MXU contraction that may open a fused segment
    ``reduce`` — lane-axis reduction, admissible inside a segment
    ``far``    — everything else (the far pipeline is the fallback)
    """
    if name in ANCHOR_PRIMS:
        return "anchor"
    if name in REDUCE_LANE_PRIMS:
        return "reduce"
    if name in ELEMENTWISE_PRIMS:
        return "near"
    if name in LAYOUT_PRIMS:
        return "layout"
    return "far"
