"""Matmul-anchored segments + lane-axis reduction fusion.

The PR-3 acceptance contract, extended by the backward-anchoring PR:
  * a qualifying ``dot_general`` OPENS a near segment: its elementwise
    epilogue (bias+gelu, swiglu lane-split gate, residual add, dtype
    cast) and broadcast-compatible prologue fuse into one
    ``fused_matmul_segment`` kernel (K-reduction grid + accumulator
    scratch), so the product tensor never round-trips HBM
  * the grad-time contraction forms anchor too: dx = g @ wT (dlhs,
    weight read column-major) and dw = xT @ g (drhs, M-innermost
    accumulation; jax's adjacent transpose absorbed), with a
    weight-side dequant-cast prologue on the forward form
  * batched contractions ANCHOR since the batched-anchors PR: leading,
    aligned batch dims become outer grid axes (all three forms), and a
    batched QK^T -> scale/softmax -> PV pair fuses flash-shaped;
    disqualified contractions (misaligned batches, rank>2 rhs) stay
    far — correctness never depends on anchoring
  * lane-axis ``reduce_sum``/``reduce_max`` fuse INTO segments as
    (rows, 1) row statistics, so rmsnorm- and softmax-shaped chains are
    a single segment end to end
  * segment-boundary donation keeps working across anchored segments
    (epilogue operands that die at the segment become Pallas
    ``input_output_aliases``)
  * interior broadcasts ([B,1,S,1,D]) fuse via the "bcast" operand role
    (block-index decomposition over the output's leading dims) — the
    former conservative split is gone
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    mpu_offload,
    offload_report,
    plan_offload,
    rewrite_offload,
)


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _check(fn, *args, bulk_threshold=64, rtol=1e-5, atol=1e-5):
    got = mpu_offload(fn, bulk_threshold=bulk_threshold,
                      impl="interpret")(*args)
    want = fn(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# anchoring: epilogues and prologues
# ---------------------------------------------------------------------------

def test_gemm_bias_gelu_single_anchored_segment():
    def fn(x, w, b, y):
        h = x @ w
        return jax.nn.gelu(h + b) + y

    x, w = _rand((8, 64, 32)), _rand((32, 48), 1) * 0.1
    b, y = _rand((48,), 2), _rand((8, 64, 48), 3)
    plan = offload_report(fn, x, w, b, y, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None
    assert seg.matmul.k == 32 and seg.matmul.n == 48
    assert plan.traffic_reduction > 1.5
    _check(fn, x, w, b, y)


def test_gemm_swiglu_lane_split_epilogue_fuses():
    """The fused gate+up projection: [R,2C] product lane-split into the
    silu gate and the linear half inside the anchored kernel."""
    def fn(x, wgu):
        hw = x @ wgu
        a, g = hw[:, :48], hw[:, 48:]
        return jax.nn.silu(a) * g

    x, wgu = _rand((512, 32)), _rand((32, 96), 1) * 0.1
    plan = offload_report(fn, x, wgu, bulk_threshold=64)
    assert len(plan.segments) == 1 and plan.segments[0].matmul is not None
    assert plan.traffic_reduction > 1.5
    assert plan.segments[0].out_cols == [48]     # store only the gated half
    _check(fn, x, wgu)


def test_gemm_prologue_cast_and_scale_absorbed():
    """A bf16->f32 cast + scale chain feeding the lhs is applied per
    [rows_block, k_block] tile inside the kernel, not materialized."""
    def fn(xb, w, y):
        l = xb.astype(jnp.float32) * 0.5
        h = l @ w
        return jnp.tanh(h) + y

    xb = _rand((512, 32)).astype(jnp.bfloat16)
    w, y = _rand((32, 96), 1) * 0.1, _rand((512, 96), 2)
    plan = offload_report(fn, xb, w, y, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and len(seg.matmul.pro_eqns) == 2
    _check(fn, xb, w, y, rtol=5e-3, atol=5e-3)


def test_rhs_dequant_cast_prologue_absorbed():
    """A bf16->f32 cast feeding the WEIGHT side fuses into the anchored
    kernel (applied per [k_block, N] block): the cast tensor is never
    materialized and the raw bf16 bytes are what stream per row block."""
    def fn(x, wb, b):
        w = wb.astype(jnp.float32)
        return jax.nn.gelu(x @ w + b)

    x = _rand((128, 64))
    wb = (_rand((64, 48), 1) * 0.1).astype(jnp.bfloat16)
    b = _rand((48,), 2)
    plan = offload_report(fn, x, wb, b, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and seg.matmul.rhs_pro_eqns
    assert [sp.role for sp in seg.matmul.rhs_specs] == ["bulk_w"]
    assert seg.matmul.rhs_specs[0].var.aval.dtype == jnp.bfloat16
    _check(fn, x, wb, b, rtol=5e-3, atol=5e-3)


def test_rhs_int8_dequant_scale_prologue_absorbed():
    """int8 weight + scalar scale: the whole dequant chain (cast + mul)
    rides the weight side of the kernel."""
    def fn(x, wq, s, b):
        w = wq.astype(jnp.float32) * s
        return jnp.tanh(x @ w) + b

    import numpy as np
    x = _rand((128, 64))
    wq = jnp.asarray(np.random.RandomState(0)
                     .randint(-127, 127, (64, 48)).astype(np.int8))
    s, b = jnp.float32(0.01), _rand((48,), 2)
    plan = offload_report(fn, x, wq, s, b, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and len(seg.matmul.rhs_pro_eqns) == 2
    _check(fn, x, wq, s, b, rtol=1e-4, atol=1e-4)


def test_rhs_per_channel_dequant_scale_prologue_absorbed():
    """int8 weight + PER-CHANNEL [N] scale: the scale's [1, N] param
    lift (jax traces `w * s` as broadcast_in_dim + mul) rides the
    weight prologue as a ``param_w`` block; only the raw int8 weight
    and the [N] scale stream — the f32 weight never exists in HBM."""
    def fn(x, wq, s, b):
        w = wq.astype(jnp.float32) * s
        return jnp.tanh(x @ w) + b

    import numpy as np
    x = _rand((128, 64))
    wq = jnp.asarray(np.random.RandomState(0)
                     .randint(-127, 127, (64, 48)).astype(np.int8))
    s = jnp.abs(_rand((48,), 3)) * 0.01 + 0.001
    b = _rand((48,), 2)
    plan = offload_report(fn, x, wq, s, b, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and len(seg.matmul.rhs_pro_eqns) == 3
    roles = sorted(sp.role for sp in seg.matmul.rhs_specs)
    assert roles == ["bulk_w", "param_w"]
    assert seg.matmul.rhs_specs[0].var.aval.dtype == jnp.int8
    _check(fn, x, wq, s, b, rtol=1e-4, atol=1e-4)


def test_gemm_epilogue_bf16_numerics():
    def fn(x, w, b):
        h = x @ w
        return (jax.nn.gelu(h + b)).astype(jnp.bfloat16)

    x, w, b = _rand((128, 64)), _rand((64, 64), 1) * 0.1, _rand((64,), 2)
    plan = offload_report(fn, x, w, b, bulk_threshold=64)
    assert len(plan.segments) == 1 and plan.segments[0].matmul is not None
    _check(fn, x, w, b, rtol=5e-2, atol=5e-2)


def test_bare_matmul_is_not_anchored():
    """No fused ALU work around the dot -> nothing to win; the matmul
    re-binds far exactly as before."""
    def fn(x, w):
        return x @ w

    x, w = _rand((128, 64)), _rand((64, 64), 1)
    plan = offload_report(fn, x, w, bulk_threshold=64)
    assert len(plan.segments) == 0
    _check(fn, x, w)


def test_batched_dots_anchor():
    """Batch dims became outer grid axes in the batched-anchors PR:
    leading, aligned batch dims on both operands admit, the batch axes
    fold into the segment's row extent, and the rhs re-streams per
    batch slice (here: an attention-shaped QK^T, the dlhs form)."""
    def batched(q, k):
        return jnp.einsum("bsh,bth->bst", q, k) * 2.0

    q, k = _rand((4, 16, 32)), _rand((4, 16, 32), 1)
    plan = offload_report(batched, q, k, bulk_threshold=64)
    assert len(plan.segments) == 1
    mm = plan.segments[0].matmul
    assert mm is not None and mm.form == "dlhs"
    assert mm.batch == 4 and mm.batch_shape == (4,)
    assert plan.segments[0].rows == 4 * 16
    _check(batched, q, k)


def test_batched_fwd_dot_anchors():
    """The fwd form with batch dims: x[B,M,K] @ w[B,K,N] plus an
    elementwise epilogue is one anchored segment per-batch-slice."""
    def fn(x, w):
        return jnp.tanh(jnp.einsum("bmk,bkn->bmn", x, w))

    x, w = _rand((4, 32, 16)), _rand((4, 16, 8), 1) * 0.1
    plan = offload_report(fn, x, w, bulk_threshold=64)
    assert len(plan.segments) == 1
    mm = plan.segments[0].matmul
    assert mm is not None and mm.form == "fwd" and mm.batch == 4
    _check(fn, x, w)


def test_batched_dot_misaligned_batches_stay_far():
    """Only leading, aligned batch dims qualify: a contraction whose
    batch axes differ between operands still falls far (correctness
    never depends on anchoring)."""
    def fn(x, w):
        # rhs batch axis is NOT leading: dimension_numbers put lhs batch
        # at 0 but rhs batch at 1
        return jax.lax.dot_general(
            x, w, (((2,), (0,)), ((0,), (1,)))) * 2.0

    x, w = _rand((4, 16, 32)), _rand((32, 4, 8), 1)
    plan = offload_report(fn, x, w, bulk_threshold=64)
    assert all(s.matmul is None for s in plan.segments)
    _check(fn, x, w)


# ---------------------------------------------------------------------------
# grad-time anchor forms: dGRAD_LHS (g @ wT) and dGRAD_RHS (xT @ g)
# ---------------------------------------------------------------------------

def test_dlhs_grad_contraction_anchors_with_epilogue():
    """dx = g @ wT (rhs contracting its lane axis — the activation
    gradient) anchors; the [K,N] weight is read column-major in-kernel
    and the trailing elementwise chain is the fused epilogue."""
    def fn(g, w, y):
        dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))
        return jnp.tanh(dx) * 0.5 + y

    g, w = _rand((128, 48)), _rand((64, 48), 1) * 0.1
    y = _rand((128, 64), 2)
    plan = offload_report(fn, g, w, y, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and seg.matmul.form == "dlhs"
    assert seg.matmul.k == 48 and seg.matmul.n == 64
    _check(fn, g, w, y)


def test_drhs_grad_contraction_anchors_with_epilogue():
    """dw = xT @ g (both operands contracting their row dims — the
    weight gradient) anchors with M innermost into the [Kb, Nb]
    accumulator; the weight-decay epilogue fuses."""
    def fn(x, g, w):
        dw = jax.lax.dot_general(x, g, (((0,), (0,)), ((), ())))
        return dw + 0.01 * w

    x, g = _rand((128, 64)), _rand((128, 48), 1)
    w = _rand((64, 48), 2)
    plan = offload_report(fn, x, g, w, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and seg.matmul.form == "drhs"
    assert seg.matmul.k == 128 and seg.matmul.n == 48
    _check(fn, x, g, w, rtol=1e-4, atol=1e-4)


def test_drhs_absorbs_adjacent_transpose():
    """jax's transpose rule emits dw as ``dot_general(g, h,
    contract-rows)`` followed by a rank-2 transpose; the planner absorbs
    the pair so the kernel writes the [K, N] layout directly."""
    def fn(g, h, w):
        dwt = jax.lax.dot_general(g, h, (((0,), (0,)), ((), ())))
        return dwt.T * 0.9 + 0.01 * w

    g, h = _rand((128, 32)), _rand((128, 48), 1)
    w = _rand((48, 32), 2)
    plan = offload_report(fn, g, h, w, bulk_threshold=64)
    assert len(plan.segments) == 1
    seg = plan.segments[0]
    assert seg.matmul is not None and seg.matmul.form == "drhs"
    assert seg.matmul.extra_eqns, "the transpose must be absorbed"
    _check(fn, g, h, w, rtol=1e-4, atol=1e-4)


def test_drhs_epilogue_rejects_row_stats_and_layouts():
    """drhs epilogues are lane-blocked: a row softmax on the weight
    gradient cannot fuse (the lane extent is not resident) — the
    segment must split rather than miscompile."""
    def fn(x, g):
        dw = jax.lax.dot_general(x, g, (((0,), (0,)), ((), ())))
        return jax.nn.softmax(dw * 0.5, axis=-1)

    x, g = _rand((128, 64)), _rand((128, 48), 1)
    plan = offload_report(fn, x, g, bulk_threshold=64)
    closed = jax.make_jaxpr(fn)(x, g)
    red_idx = {i for i, e in enumerate(closed.jaxpr.eqns)
               if e.primitive.name in ("reduce_sum", "reduce_max")}
    # the softmax may still fuse as a plain elementwise segment over the
    # materialized dw — it just must not ride inside the drhs kernel
    for s in plan.segments:
        if s.matmul is not None and s.matmul.form == "drhs":
            assert not (red_idx & set(s.all_eqn_idx)), \
                "row stats must not fuse into a drhs epilogue"
    _check(fn, x, g, rtol=1e-4, atol=1e-4)


def test_mlp_grad_trace_anchors_backward_segment():
    """The realistic post-grad trace: jax.grad of a 2-layer MLP loss
    plans with BOTH forward anchors and at least one anchored backward
    (dlhs) segment — the activation gradient fused with the previous
    layer's activation-backward chain."""
    def loss(x, w1, b1, w2):
        h = jax.nn.gelu(x @ w1 + b1)
        o = h @ w2
        return jnp.sum(o * o)

    x = _rand((128, 64))
    w1, b1 = _rand((64, 48), 1) * 0.1, _rand((48,), 2)
    w2 = _rand((48, 32), 3) * 0.1
    gfn = jax.grad(loss, argnums=(1, 2, 3))
    plan = offload_report(gfn, x, w1, b1, w2, bulk_threshold=64)
    forms = [s.matmul.form for s in plan.segments if s.matmul is not None]
    assert "fwd" in forms
    assert any(f in ("dlhs", "drhs") for f in forms), forms
    _check(gfn, x, w1, b1, w2, rtol=1e-4, atol=1e-4)


def test_anchored_segment_epilogue_donation():
    """A residual buffer that dies at the anchored segment is donated:
    the rewritten pallas_call carries input_output_aliases and donated
    execution stays correct call over call."""
    def fn(x, w, y):
        h = x @ w
        return jax.nn.gelu(h) + y

    x, w, y = _rand((128, 64)), _rand((64, 64), 1) * 0.1, _rand((128, 64), 2)
    closed = jax.make_jaxpr(fn)(x, w, y)
    rewritten, plan = rewrite_offload(closed, bulk_threshold=64,
                                      impl="interpret", donate_argnums=(2,))
    assert len(plan.segments) == 1 and plan.segments[0].matmul is not None
    assert plan.donated_hbm_bytes > 0
    from test_offload_compile import _pallas_calls
    aliases = [e.params.get("input_output_aliases", ())
               for e in _pallas_calls(rewritten.jaxpr)]
    assert aliases and any(a for a in aliases), aliases

    wrapped = mpu_offload(fn, bulk_threshold=64, impl="interpret",
                          donate_argnums=(2,))
    want = np.asarray(fn(x, w, y))       # before y's buffer is donated
    np.testing.assert_allclose(np.asarray(wrapped(x, w, y)), want,
                               rtol=1e-5, atol=1e-5)
    y2 = _rand((128, 64), 5)
    want2 = np.asarray(fn(x, w, y2))
    np.testing.assert_allclose(np.asarray(wrapped(x, w, y2)), want2,
                               rtol=1e-5, atol=1e-5)


def test_two_anchored_mlp_layers_two_segments():
    """Back-to-back projections: each dot anchors its own segment and
    the boundary activation flows between them."""
    def fn(x, w1, b1, w2, y):
        h = jax.nn.gelu(x @ w1 + b1)
        return (h @ w2) * 0.5 + y

    x = _rand((256, 32))
    w1, b1 = _rand((32, 64), 1) * 0.1, _rand((64,), 2)
    w2, y = _rand((64, 32), 3) * 0.1, _rand((256, 32), 4)
    plan = offload_report(fn, x, w1, b1, w2, y, bulk_threshold=64)
    anchored = [s for s in plan.segments if s.matmul is not None]
    assert len(anchored) == 2
    _check(fn, x, w1, b1, w2, y)


def test_f64_dot_not_anchored():
    """The anchored kernel accumulates in f32; f64 dots must stay on the
    (exact) unfused XLA path rather than silently losing precision."""
    def fn(x, w, b):
        return jax.nn.gelu(x @ w + b)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((128, 64), jnp.float64),
            jax.ShapeDtypeStruct((64, 64), jnp.float64),
            jax.ShapeDtypeStruct((64,), jnp.float64))
        plan = plan_offload(closed, bulk_threshold=64)
    assert all(s.matmul is None for s in plan.segments)


def test_rhs_buffer_never_donated():
    """An epilogue operand that is ALSO the anchored rhs must not be
    donated: rhs blocks walk the k axis over all rows, so aliasing the
    output into that buffer would clobber rows later row-blocks still
    read (invisible under interpret mode — guarded at plan level)."""
    def fn(x, w):
        wq = jax.lax.sort(w, dimension=1)
        h = x @ wq
        return jax.nn.gelu(h) + wq

    x, w = _rand((64, 64)), _rand((64, 64), 1) * 0.1
    plan = offload_report(fn, x, w, bulk_threshold=64)
    seg = next(s for s in plan.segments if s.matmul is not None)
    donated_vars = {seg.operand_specs[bi].var for bi, _ in seg.donations}
    assert seg.matmul.rhs not in donated_vars
    _check(fn, x, w)


def test_wide_n_row_blocks_shrink_for_vmem():
    """Wide-N dots shrink their row/k blocks so the f32 accumulator
    scratch stays within the VMEM budget instead of failing to
    compile; the planner's traffic accounting follows the same math."""
    from repro.kernels.fused_matmul import (
        _ACC_VMEM_BYTES,
        _row_block,
        matmul_row_blocks,
    )

    assert _row_block(4096, [], 512, 256) == 512      # narrow: full block
    rb = _row_block(4096, [], 512, 16384)
    assert rb < 512 and rb * 16384 * 4 <= _ACC_VMEM_BYTES
    assert matmul_row_blocks(4096, [], 16384) == 4096 // rb


# ---------------------------------------------------------------------------
# lane-axis reductions
# ---------------------------------------------------------------------------

def test_softmax_chain_single_segment():
    def fn(x):
        return jax.nn.softmax(x * 0.125, axis=-1)

    x = _rand((8, 64, 32))
    plan = offload_report(fn, x, bulk_threshold=64)
    assert len(plan.segments) == 1
    assert plan.traffic_reduction > 1.5
    _check(fn, x, atol=1e-6)


def test_rmsnorm_chain_single_segment():
    def fn(x, s):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-5) * s

    x, s = _rand((8, 64, 32)), jnp.ones((32,)) * 1.1
    plan = offload_report(fn, x, s, bulk_threshold=64)
    assert len(plan.segments) == 1
    assert plan.traffic_reduction > 1.5
    _check(fn, x, s, atol=1e-6)


def test_gemm_softmax_epilogue_fuses_reduction():
    """A row softmax directly on the matmul product — the anchored
    epilogue admits the lane reductions too."""
    def fn(x, w):
        return jax.nn.softmax(x @ w, axis=-1)

    x, w = _rand((256, 32)), _rand((32, 64), 1) * 0.2
    plan = offload_report(fn, x, w, bulk_threshold=64)
    assert len(plan.segments) == 1 and plan.segments[0].matmul is not None
    _check(fn, x, w, atol=1e-6)


def test_non_lane_reduction_still_splits():
    """Reductions over a non-lane axis are not near-admissible; the
    chain splits and results stay exact."""
    def fn(x):
        m = jnp.sum(x, axis=0)               # row-axis reduce: far
        return jnp.tanh(x) * 2.0 + m

    x = _rand((64, 32))
    plan = offload_report(fn, x, bulk_threshold=64)
    closed = jax.make_jaxpr(fn)(x)
    red_idx = {i for i, e in enumerate(closed.jaxpr.eqns)
               if e.primitive.name == "reduce_sum"}
    seg_members = {i for s in plan.segments for i in s.all_eqn_idx}
    assert not (red_idx & seg_members)
    _check(fn, x)


def test_reduced_stat_as_segment_output():
    """A row statistic that escapes the segment is stored as a (rows, 1)
    column and reshaped back to its rank-reduced aval."""
    def fn(x):
        e = jnp.exp(x * 0.5)
        return e / jnp.sum(e, axis=-1, keepdims=True), jnp.sum(e, axis=-1)

    x = _rand((64, 32))
    plan = offload_report(fn, x, bulk_threshold=64)
    assert len(plan.segments) == 1
    _check(fn, x, atol=1e-6)


# ---------------------------------------------------------------------------
# interior broadcasts: fixed by the batched-anchors PR
# ---------------------------------------------------------------------------

def test_interior_broadcast_fuses():
    """[B,1,S,1,D] against [B,T,S,U,D] has two non-adjacent broadcast
    dims.  With the "bcast" operand role the row-block index decomposes
    over the output's leading dims and strides only the operand's
    non-broadcast dims, so the whole chain fuses as ONE segment instead
    of conservatively splitting (the former ROADMAP limitation).  Here
    the operand's innermost lead dim is broadcast, so each row block
    reads ONE operand row, fetched in a tile-legal block and picked in
    VMEM.  (The row block divides the innermost output lead dim, 8
    here, so the bulk blocks meet the sublane tile too.)"""
    def fn(a, m):
        return jnp.tanh(a) * m + a * 0.5

    a = _rand((2, 3, 8, 8, 16))
    m = _rand((2, 1, 8, 1, 16), 1)
    plan = offload_report(fn, a, m, bulk_threshold=64)
    assert len(plan.segments) == 1
    roles = {s.role for s in plan.segments[0].operand_specs}
    assert "bcast" in roles, f"expected a bcast operand, got {roles}"
    assert not plan.segments[0].tiling_violations()
    _check(fn, a, m)


def test_interior_broadcast_untileable_row_block_declines():
    """With an innermost output lead dim of 5 the row block must divide
    5, so the [240, 16] bulk operand would be read in 5-row blocks,
    which the TPU sublane tile forbids: the candidate declines with the
    tiling rule as its reason and the function still computes right."""
    def fn(a, m):
        return jnp.tanh(a) * m + a * 0.5

    a = _rand((2, 3, 8, 5, 16))
    m = _rand((2, 1, 8, 1, 16), 1)
    plan = offload_report(fn, a, m, bulk_threshold=64)
    assert len(plan.decisions) == 1 and not plan.segments
    assert plan.decisions[0].reason.startswith(
        "TPU block tiling: block (5, 16)")
    _check(fn, a, m)


def test_interior_broadcast_middle_dim_fuses():
    """The other bcast layout: the broadcast dim is interior but the
    operand's innermost leading dim is NOT broadcast ([B,1,S,D] against
    [B,T,S,D]) — neither rep (rows don't repeat contiguously) nor tile
    (not periodic across batches), so only the bcast role fits."""
    def fn(a, m):
        return jnp.tanh(a) * m + a * 0.5

    a = _rand((2, 6, 8, 16))
    m = _rand((2, 1, 8, 16), 1)
    plan = offload_report(fn, a, m, bulk_threshold=64)
    assert len(plan.segments) == 1
    roles = {s.role for s in plan.segments[0].operand_specs}
    assert "bcast" in roles, f"expected a bcast operand, got {roles}"
    _check(fn, a, m)
