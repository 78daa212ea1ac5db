"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the semantic ground truth the kernels are
validated against (tests sweep shapes/dtypes with assert_allclose).
They are deliberately naive — clarity over speed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def ref_rotary(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x [R, N, H]; positions [R]."""
    h = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    ang = positions[:, None].astype(jnp.float32) * freqs  # [R, H/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def ref_flash_attention(q, k, v, *, causal=True, window=0):
    """q [B,S,NQ,H]; k,v [B,T,NK,H] (GQA)."""
    b, s, nq, h = q.shape
    t, nk = k.shape[1], k.shape[2]
    g = nq // nk
    qg = q.reshape(b, s, nk, g, h)
    scores = jnp.einsum("bskgh,btkh->bskgt", qg, k,
                        preferred_element_type=jnp.float32) / (h ** 0.5)
    q_pos, k_pos = jnp.arange(s), jnp.arange(t)
    ok = jnp.ones((s, t), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(ok[None, :, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", p.astype(v.dtype), v)
    return out.reshape(b, s, nq, h).astype(q.dtype)


def ref_decode_attention(q, k_cache, v_cache, lengths):
    """q [B,NQ,H]; caches [B,T,NK,H]; lengths [B]."""
    b, nq, h = q.shape
    t, nk = k_cache.shape[1], k_cache.shape[2]
    g = nq // nk
    qg = q.reshape(b, nk, g, h)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, k_cache,
                   preferred_element_type=jnp.float32) / (h ** 0.5)
    ok = jnp.arange(t)[None, :] < lengths[:, None]
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, nq, h).astype(q.dtype)


def ref_paged_decode_attention(q, k_pages, v_pages, block_tables, lengths):
    """q [B,NQ,H]; pages [P,NK,page,H]; block_tables [B,NP]; lengths [B].

    Gathers each sequence's pages into a contiguous head-major cache and
    applies masked decode attention — the semantic ground truth for the
    paged Pallas kernel (which never materializes the gather)."""
    b = q.shape[0]
    nk, page, h = k_pages.shape[1:]
    n_pages = block_tables.shape[1]
    kg = k_pages[block_tables]           # [B, NP, NK, page, H]
    vg = v_pages[block_tables]
    k_cache = kg.transpose(0, 1, 3, 2, 4).reshape(b, n_pages * page, nk, h)
    v_cache = vg.transpose(0, 1, 3, 2, 4).reshape(b, n_pages * page, nk, h)
    return ref_decode_attention(q, k_cache, v_cache, lengths)


def ref_mla_decode(q, rows, lengths, *, rank: int, scale: float):
    """Absorbed latent attention: q [B,NQ,C]; rows [B,T,C] (each token's
    ``[c ‖ k_pe]``); lengths [B].  Returns [B,NQ,rank] in rows' dtype:
    the softmax(q · row * scale)-weighted sum of the rows' first
    ``rank`` entries."""
    t = rows.shape[1]
    s = jnp.einsum("bqc,btc->bqt", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    ok = jnp.arange(t)[None, :] < lengths[:, None]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqt,btr->bqr", p.astype(rows.dtype), rows[..., :rank],
                     preferred_element_type=jnp.float32)
    return out.astype(rows.dtype)


def gather_rows(pages, block_tables):
    """[P, page, C] pool + [B, NP] table -> [B, NP * page, C]."""
    b, n_pages = block_tables.shape
    page, c = pages.shape[1:]
    return pages[block_tables].reshape(b, n_pages * page, c)


def ref_paged_mla_decode(q, pages, block_tables, lengths, *, rank: int,
                         scale: float):
    """``ref_mla_decode`` over the pages a block table names."""
    return ref_mla_decode(q, gather_rows(pages, block_tables), lengths,
                          rank=rank, scale=scale)


def ref_moe_experts(x, tile_group, n_tiles, w_gate, w_up, w_down, *,
                    tm: int, act: str = "silu"):
    """Each ``tm``-row tile of x [M, d] through the SwiGLU of expert
    ``tile_group[t]`` (gate/up [E, d, f], down [E, f, d]); tiles past
    ``n_tiles[0]`` give zeros.  Matmul operands in x's dtype, f32
    accumulation."""
    acts = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}
    m = x.shape[0]
    tile = jnp.arange(m) // tm
    group = jnp.where(tile < n_tiles[0], tile_group[tile], -1)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        g = jnp.dot(x, w_gate[e].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, w_up[e].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        h = (acts[act](g) * u).astype(x.dtype)
        ye = jnp.dot(h, w_down[e].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        y = jnp.where((group == e)[:, None], ye, y)
    return y.astype(x.dtype)


def ref_ssd_scan(x, logd, dt, bmat, cmat, state0=None):
    """Sequential SSD oracle.  x [B,S,H,P]; logd,dt [B,S,H];
    bmat,cmat [B,S,N].  Returns (y [B,S,H,P], state [B,H,P,N])."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    state = (jnp.zeros((b, h, p, n), jnp.float32) if state0 is None
             else state0.astype(jnp.float32))

    def step(state, inp):
        xt, ldt, dtt, bt, ct = inp
        da = jnp.exp(ldt)  # [B,H]
        state = state * da[..., None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", xt.astype(jnp.float32), bt.astype(jnp.float32),
            dtt.astype(jnp.float32))
        y = jnp.einsum("bhpn,bn->bhp", state, ct.astype(jnp.float32))
        return state, y

    state, ys = jax.lax.scan(
        step, state,
        (x.transpose(1, 0, 2, 3), logd.transpose(1, 0, 2),
         dt.transpose(1, 0, 2), bmat.transpose(1, 0, 2),
         cmat.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), state


def ref_wkv6(r, k, v, w, u, state0=None):
    """Sequential WKV6 oracle. r,k,w [B,S,H,K]; v [B,S,H,V]; u [H,K]."""
    b, s, h, kk = r.shape
    vv = v.shape[-1]
    state = (jnp.zeros((b, h, kk, vv), jnp.float32) if state0 is None
             else state0.astype(jnp.float32))

    def step(state, inp):
        rt, kt, vt, wt = (a.astype(jnp.float32) for a in inp)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       state + u.astype(jnp.float32)[..., None] * kv)
        return state * wt[..., None] + kv, y

    state, ys = jax.lax.scan(
        step, state, tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w)))
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), state


def ref_adamw(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step):
    """Fused AdamW oracle (fp32 math, params any float dtype)."""
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * gf
    v_new = beta2 * v + (1 - beta2) * gf * gf
    mhat = m_new / (1 - beta1 ** step)
    vhat = v_new / (1 - beta2 ** step)
    update = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * pf
    return (pf - lr * update).astype(p.dtype), m_new, v_new


def ref_fused_elementwise(fn, *args):
    """The oracle for a fused elementwise chain is the chain itself."""
    return fn(*args)
