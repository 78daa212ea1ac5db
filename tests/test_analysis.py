"""Static plan verifier: clean plans prove out, corrupted plans are
caught with the RIGHT rule id.

The mutation tests are the verifier's own acceptance bar: each one
corrupts a real planner-emitted plan the way a buggy rewrite would
(aliasing a live buffer, smuggling a far prim into a segment, breaking
an operand's block tiling, dropping a segment the decisions table still
claims) and asserts the exact rule fires.

Property test (hypothesis): ``_bcast_row_index`` — the kernel's
interior-broadcast row remap — must agree with plain numpy broadcasting
semantics at every grid index, for random lead/out_lead patterns."""
try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:          # no hypothesis in the image: fallback shim
    from _hyp import st, given, settings
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import (
    PlanVerificationError,
    has_errors,
    verify_paged_decode,
    verify_plan,
)
from repro.analysis.verifier import _bcast_reference_row
from repro.core import OffloadPolicy, mpu_offload, offload_report
from repro.core.offload import OperandSpec
from repro.kernels.fused_elementwise import _bcast_row_index


def _rules(findings):
    return {f.rule for f in findings if f.severity == "error"}


def _ew_chain(x, y):
    h = jnp.tanh(x) * 2.0 + y
    return h * jax.nn.sigmoid(h)


def _gemm_chain(x, w):
    return jnp.tanh(x @ w) * 2.0


def _ew_plan():
    x = jnp.zeros((64, 32))
    y = jnp.zeros((64, 32))
    return offload_report(_ew_chain, x, y, bulk_threshold=64)


# ---------------------------------------------------------------------------
# clean plans verify
# ---------------------------------------------------------------------------

def test_clean_elementwise_plan_verifies():
    plan = _ew_plan()
    assert plan.segments
    assert not has_errors(verify_plan(plan))


def test_clean_gemm_and_grad_plans_verify():
    x = jnp.zeros((128, 64))
    w = jnp.zeros((64, 64))
    plan = offload_report(_gemm_chain, x, w, bulk_threshold=64)
    assert any(s.matmul is not None for s in plan.segments)
    assert not has_errors(verify_plan(plan))

    def gemm_bwd(g, x, w):
        dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))
        dx = jnp.tanh(dx) * 0.5 + x * 0.1
        dw = jax.lax.dot_general(x, g, (((0,), (0,)), ((), ())))
        return dx, dw + 0.01 * w

    g = jnp.zeros((512, 256))
    xg = jnp.zeros((512, 256))
    wg = jnp.zeros((256, 256))
    gplan = offload_report(gemm_bwd, g, xg, wg, bulk_threshold=64)
    forms = {s.matmul.form for s in gplan.segments if s.matmul is not None}
    assert {"dlhs", "drhs"} <= forms
    assert not has_errors(verify_plan(gplan))


def test_clean_flash_plan_verifies():
    def attn(q, k, v):
        s = jnp.einsum("bhsd,bhtd->bhst", q, k) / 8.0
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhst,bhtd->bhsd", p, v)

    q = jnp.zeros((2, 4, 128, 64))
    k = jnp.zeros((2, 4, 128, 64))
    v = jnp.zeros((2, 4, 128, 64))
    plan = offload_report(attn, q, k, v, bulk_threshold=64)
    assert any(s.matmul is not None and s.matmul.flash is not None
               for s in plan.segments)
    assert not has_errors(verify_plan(plan))


def test_explain_renders_verified_column():
    plan = _ew_plan()
    text = str(plan.report())
    assert "verified" in text
    assert "ok" in text


def test_fingerprint_mismatch_is_detected():
    plan = _ew_plan()
    x = jnp.zeros((128, 64))
    w = jnp.zeros((64, 64))
    other = offload_report(_gemm_chain, x, w, bulk_threshold=64)
    assert not has_errors(verify_plan(plan, closed=plan.annotation.jaxpr))
    assert "plan-fingerprint" in _rules(
        verify_plan(plan, closed=other.annotation.jaxpr))


# ---------------------------------------------------------------------------
# mutation tests: each corruption must fire its rule
# ---------------------------------------------------------------------------

def test_mutation_alias_of_live_buffer():
    """Donating an input that is ALSO a program output aliases a buffer
    that outlives the segment."""
    def fn(x):
        return jnp.tanh(x) * 2.0 + 1.0, x

    x = jnp.zeros((64, 32))
    plan = offload_report(fn, x, bulk_threshold=64)
    seg = plan.segments[0]
    bi = next(i for i, s in enumerate(seg.operand_specs)
              if s.role == "bulk")
    seg.donations = [(bi, 0)]
    assert "alias-live" in _rules(verify_plan(plan))


def test_mutation_kaxis_race():
    """Smuggling the contraction's weight stream into the donation list
    must be caught STRUCTURALLY: the grid re-reads the weight at steps
    after the first output block is written."""
    x = jnp.zeros((1024, 1024))
    w = jnp.zeros((1024, 1024))
    plan = offload_report(_gemm_chain, x, w, bulk_threshold=64)
    seg = next(s for s in plan.segments if s.matmul is not None)
    mm = seg.matmul
    seg.operand_specs = seg.operand_specs + [
        OperandSpec(mm.rhs, "bulk", 1024, 1024)]
    seg.donations = [(len(seg.operand_specs) - 1, 0)]
    assert "alias-kaxis-race" in _rules(verify_plan(plan))


def test_mutation_broken_block_tiling():
    plan = _ew_plan()
    seg = plan.segments[0]
    sp = seg.operand_specs[0]
    seg.operand_specs[0] = dataclasses.replace(sp, cols=sp.cols * 2)
    assert "index-bounds" in _rules(verify_plan(plan))


def test_mutation_far_prim_in_segment():
    def fn(x, idx):
        h = jnp.tanh(x) * 2.0 + 1.0
        return h[idx]

    x = jnp.zeros((64, 32))
    idx = jnp.zeros((8,), jnp.int32)
    plan = offload_report(fn, x, idx, bulk_threshold=64)
    seg = plan.segments[0]
    eqns = plan.annotation.jaxpr.jaxpr.eqns
    gi = next(i for i, e in enumerate(eqns)
              if e.primitive.name == "gather")
    seg.eqn_idx = seg.eqn_idx + [gi]
    assert "far-prim-in-segment" in _rules(verify_plan(plan))


def test_mutation_missing_segment_is_decision_drift():
    plan = _ew_plan()
    plan.segments.pop()
    assert "decision-drift" in _rules(verify_plan(plan))
    assert "MISSING-SEGMENT" in str(plan.report())


def test_mutation_vmem_budget_beyond_capacity():
    """A corrupted vmem budget lets the kernel pick an accumulator block
    larger than physical VMEM — the one accumulator case that is an
    error, not the advisory 8-row-floor warning.  (A 16 MiB policy
    budget keeps the 128-wide k block the tiling rule needs at N=32768;
    the mutation then lifts the clamp to a 512-row, 64 MiB
    accumulator.)"""
    x = jnp.zeros((512, 256))
    w = jnp.zeros((256, 32768))
    plan = offload_report(lambda x, w: jnp.tanh(x @ w) * 2.0, x, w,
                          policy=OffloadPolicy(bulk_threshold=64,
                                               vmem_budget=16 << 20))
    seg = next(s for s in plan.segments
               if s.matmul is not None and s.matmul.form == "fwd")
    assert not has_errors(verify_plan(plan))
    seg.vmem_bytes = 1 << 40
    assert "vmem-accumulator" in _rules(verify_plan(plan))


def test_mutation_tpu_tiling():
    """A corrupted vmem budget shrinks the k block of a fused anchor
    below the 128-lane tile: the TPU compiler would refuse the kernel,
    and the verifier says so (rule ``tpu-tiling``)."""
    x = jnp.zeros((64, 512))
    w = jnp.zeros((512, 1024))
    plan = offload_report(lambda x, w: jnp.tanh(x @ w) * 2.0, x, w,
                          bulk_threshold=64)
    seg = next(s for s in plan.segments if s.matmul is not None)
    assert not seg.tiling_violations()
    assert "tpu-tiling" not in _rules(verify_plan(plan))
    seg.vmem_bytes = 4096
    assert "tpu-tiling" in _rules(verify_plan(plan))


def test_planner_declines_untileable_anchor():
    """The LM-head shape: N=152064 leaves the VMEM clamp an 8-wide k
    block, which no TPU block may have — the planner declines the anchor
    (recording why) instead of emitting a kernel that cannot compile."""
    h = jax.ShapeDtypeStruct((4, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((2048, 152064), jnp.bfloat16)
    plan = offload_report(lambda h, w: jnp.tanh(h @ w), h, w)
    assert not any(s.matmul is not None for s in plan.segments)
    (anchor,) = [d for d in plan.decisions if d.tier == "anchor"]
    assert not anchor.fused
    assert anchor.reason.startswith("TPU block tiling: ")
    assert not has_errors(verify_plan(plan))


# ---------------------------------------------------------------------------
# enforcement surfaces
# ---------------------------------------------------------------------------

def test_verify_plans_wrapper_and_accessors():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    y = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    wrapped = mpu_offload(
        _ew_chain,
        policy=OffloadPolicy(bulk_threshold=64, impl="interpret"),
        verify_plans=True)
    np.testing.assert_allclose(np.asarray(wrapped(x, y)),
                               np.asarray(_ew_chain(x, y)),
                               rtol=1e-4, atol=1e-4)
    assert not has_errors(wrapped.verify(x, y))
    assert not has_errors(_ew_plan().verify())


def test_verification_error_carries_findings():
    plan = _ew_plan()
    seg = plan.segments[0]
    sp = seg.operand_specs[0]
    seg.operand_specs[0] = dataclasses.replace(sp, cols=sp.cols * 2)
    findings = [f for f in verify_plan(plan) if f.severity == "error"]
    err = PlanVerificationError(findings)
    assert "index-bounds" in str(err)


# ---------------------------------------------------------------------------
# paged decode tables
# ---------------------------------------------------------------------------

def test_paged_decode_tables_clean():
    tables = np.arange(32, dtype=np.int32).reshape(4, 8) % 16
    lengths = np.array([0, 5, 64, 17], np.int32)
    assert verify_paged_decode(tables, lengths,
                               num_pages=16, page_size=8) == []


def test_paged_decode_out_of_range_entry():
    tables = np.zeros((4, 8), np.int32)
    tables[1, 3] = 99            # gathered even on masked grid steps
    findings = verify_paged_decode(tables, np.zeros((4,), np.int32),
                                   num_pages=16, page_size=8)
    assert "page-table-bounds" in _rules(findings)


def test_paged_decode_length_exceeds_table():
    tables = np.zeros((4, 8), np.int32)
    lengths = np.array([0, 0, 100, 0], np.int32)   # cap is 8 * 8 = 64
    findings = verify_paged_decode(tables, lengths,
                                   num_pages=16, page_size=8)
    assert "page-length-bounds" in _rules(findings)


# ---------------------------------------------------------------------------
# property: the interior-broadcast row remap matches numpy semantics
# ---------------------------------------------------------------------------

@st.composite
def bcast_patterns(draw):
    rank = draw(st.integers(1, 3))
    out_lead = tuple(draw(st.sampled_from([1, 2, 3, 4]))
                     for _ in range(rank))
    lead = tuple(d if draw(st.booleans()) else 1 for d in out_lead)
    rb = draw(st.sampled_from(
        [d for d in (1, 2, 4) if out_lead[-1] % d == 0]))
    return lead, out_lead, rb


@settings(max_examples=120, deadline=None)
@given(bcast_patterns())
def test_bcast_index_map_matches_broadcasting(pattern):
    lead, out_lead, rb = pattern
    rows = int(np.prod(out_lead))
    op_rows = int(np.prod(lead))
    brows, fn = _bcast_row_index(lead, out_lead, rb)
    for i in range(rows // rb):
        bidx = fn(i)
        assert 0 <= bidx and (bidx + 1) * brows <= op_rows
        assert bidx * brows == _bcast_reference_row(i * rb, lead, out_lead)
