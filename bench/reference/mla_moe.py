"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight):
multi-head latent attention and a routed MoE with shared experts, after
leading dense layers.

Per layer, with ``h`` the RMSNorm'd input (``modeling_deepseek.py``):

- q = h W_q viewed [heads, nope + rope]; [c ‖ k_pe] = h W_kva, c
  RMSNorm'd; [k_nope ‖ v] = c W_kvb viewed [heads, nope + v]; RoPE on
  q_pe and on the one k_pe all heads share; causal
  softmax((q_nope·k_nope + q_pe·k_pe) / sqrt(nope + rope)) v, then W_o;
- the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``;
- the others: sigmoid router scores s over all experts in float32, the
  top ``num_experts_per_tok`` of s + correction bias chosen, weights
  s[chosen] / sum(s[chosen]) * ``routed_scaling_factor``; y = the sum over
  the chosen experts this chip holds of weight * SwiGLU_e(h) (width
  ``moe_intermediate_size``), plus the shared experts, one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``.

It imports nothing of the program.  It reads the parameter tree by name
(``embed/table``, ``embed/head``, ``final_ln/scale``,
``decoder/lead/<i>`` for the dense layers and, stacked over layers,
``decoder/stack/0/{ln1,attn,ln2,ffn}``; attention ``wq``, ``wkv_a``,
``kv_norm/scale``, ``wkv_b``, ``wo``; MoE ``router``, ``router_bias``,
``gate``/``up``/``down`` [E_held, in, out], ``shared``), and the shapes
from the configuration file.  Every matmul runs in float32 at the
highest precision, attention in blocks of query rows.

Departures from ``modeling_deepseek.py``, each as the program does it:
the chip holds the experts ``held_experts`` names (``n_routed_experts``
of the published 64), and the other experts' part of each MoE layer is
left out, as on one chip of an expert-parallel deployment; the rotated
RoPE slice stays in the de-interleaved order (evens then odds) in q and
k alike, which leaves every score as it is; no group-limited routing
(``n_group`` 1 makes it a no-op); no auxiliary loss (inference).

The gaps are read only at positions whose routing is well conditioned.
A router choice near a tie flips under bf16 rounding, and a flip swaps
a held expert's output in or out of the token, which moves its logits
by about one with random experts: the widest gap over a request's
positions then reads a flip, in bf16 and fp8 alike.  So the reference
runs a second time with every linear operand rounded to bf16, and a
position counts only if in every MoE layer the margin between its
``num_experts_per_tok``-th and next choice score is wider than
``ROUTE_MARGIN`` times the largest change of any choice score between
the two runs; the others read a gap of 0.

``control=True`` rounds every operand of the linear layers (q, kv_a,
kv_b, o, the router, the routed and shared experts, the dense MLP and the
LM head) to fp8 e4m3's 3-bit mantissa, as ``dense.py`` does: the control
that the check must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import HIGHEST, Q_BLOCK, _gaps, _linear, _rms

BF16_BITS = 7         # bf16's stored mantissa bits: the probe run
FP8_BITS = 3          # fp8 e4m3's: the control
# a flip needs a margin below the change of two choice scores, so below
# twice the largest change
ROUTE_MARGIN = 2.0


def _rope_pairs(x, pos, theta):
    """x [S, heads, r]: pairs (2i, 2i+1) rotated by pos * theta^(-2i/r),
    returned de-interleaved [evens ‖ odds]."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, ev * sin + od * cos], -1)


def _swiglu(h, f, bits):
    return _linear(jax.nn.silu(_linear(h, f["gate"], bits))
                   * _linear(h, f["up"], bits), f["down"], bits)


class Reference:
    """Reference logits and gaps for one configuration; shapes are fixed
    per instance (sequences padded to ``max_len``, up to ``max_new``
    positions read), so each pass compiles once."""

    def __init__(self, params, config: dict, *, max_len: int, max_new: int):
        self.params = params
        self.cfg = config
        self.vocab = config["vocab_size"]
        self.seq = -(-max_len // Q_BLOCK) * Q_BLOCK
        self.max_new = max_new
        dec = params["decoder"]
        if set(dec.get("stack", {})) != {"0"} or dec.get("rem") or \
                len(dec.get("lead", {})) != config["first_k_dense_replace"]:
            raise ValueError("the reference reads leading dense layers and "
                             "a stack of one layer kind")
        self._logits = jax.jit(functools.partial(_logits, cfg=config),
                               static_argnames=("bits",))
        self._gaps = jax.jit(_gaps, static_argnums=2)

    def logits(self, tokens: np.ndarray, positions: np.ndarray,
               bits: int = 0) -> np.ndarray:
        """Logits [len(positions), vocab] of ``tokens`` read at
        ``positions`` (test use)."""
        return np.asarray(self._run(tokens, positions, bits)[0][
            :len(positions)])

    def _run(self, tokens, positions, bits):
        n = len(tokens)
        if n > self.seq or len(positions) > self.max_new:
            raise ValueError(f"{n} tokens / {len(positions)} positions "
                             f"exceed {self.seq} / {self.max_new}")
        tok = np.zeros((self.seq,), np.int32)
        tok[:n] = tokens
        pos = np.zeros((self.max_new,), np.int32)
        pos[:len(positions)] = positions
        return self._logits(self.params, jnp.asarray(tok),
                            jnp.asarray(pos), bits=bits)

    def gaps(self, prompt: np.ndarray, served: np.ndarray, *,
             control: bool = False) -> dict:
        """For each served token: the reference's best logit minus the
        served token's, at the position that produced it (prefill for
        the first, then each decode step), or 0 where the position's
        routing is not well conditioned (``_well_routed``).  With
        ``control``, the same gap of the token the fp8 control puts
        first.  ``kept`` is the share of positions read."""
        tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        positions = np.arange(len(prompt) - 1,
                              len(prompt) - 1 + len(served))
        k = len(served)
        ref, (margin, choice) = self._run(tokens, positions, 0)
        _, (_, probe) = self._run(tokens, positions, BF16_BITS)
        keep = np.asarray(_well_routed(margin, choice, probe))[:k]
        srv = np.zeros((self.max_new,), np.int32)
        srv[:k] = served
        gap = np.asarray(self._gaps(ref, jnp.asarray(srv), self.vocab))[:k]
        out = {"served": np.where(keep, gap, 0.0), "kept": float(keep.mean())}
        if control:
            ctl, _ = self._run(tokens, positions, FP8_BITS)
            first = jnp.argmax(ctl[:, :self.vocab], -1).astype(jnp.int32)
            gap = np.asarray(self._gaps(ref, first, self.vocab))[:k]
            out["control"] = np.where(keep, gap, 0.0)
        return out


def _well_routed(margin, choice, probe):
    """Whether each position keeps its routing with room under bf16
    rounding in every MoE layer: its margin [L, P] wider than
    ``ROUTE_MARGIN`` times the largest change of a choice score [L, P,
    E] between the reference and the bf16 probe."""
    change = jnp.max(jnp.abs(choice - probe), -1)
    return jnp.all(margin > ROUTE_MARGIN * change, 0)


def _attention(x, a, pos, cfg, bits):
    seq = x.shape[0]
    nq = cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _linear(x, a["wq"], bits).reshape(seq, nq, nope + rope)
    kv = _linear(x, a["wkv_a"], bits)
    c = _rms(kv[:, :rank], a["kv_norm"]["scale"], eps)
    k_pe = _rope_pairs(kv[:, None, rank:], pos, theta)
    kvb = _linear(c, a["wkv_b"], bits).reshape(seq, nq, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         _rope_pairs(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (seq, nq, rope))], -1)
    v = kvb[..., nope:]
    qb = q.reshape(seq // Q_BLOCK, Q_BLOCK, nq, nope + rope)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,thd->hqt", qi, k,
                       precision=HIGHEST) / np.sqrt(nope + rope)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqt,thd->qhd", w, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(seq // Q_BLOCK), qb))
    return _linear(o.reshape(seq, nq * vd), a["wo"], bits)


def _moe(h, f, cfg, bits):
    """The layer's output, and per token the margin between the last
    chosen and the first unchosen choice score, and the choice scores."""
    k = cfg["num_experts_per_tok"]
    logits = _linear(h, f["router"], bits)
    scores = jax.nn.sigmoid(logits)
    choice = scores + f["router_bias"].astype(jnp.float32)
    top, idx = jax.lax.top_k(choice, k + 1)
    margin, idx = top[:, k - 1] - top[:, k], idx[:, :k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    first, held = cfg.get("held_experts", [0, cfg["n_routed_experts"]])
    y = _swiglu(h, f["shared"], bits)
    for e in range(held):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        y = y + we[:, None] * _swiglu(
            h, {m: f[m][e] for m in ("gate", "up", "down")}, bits)
    return y, (margin, choice)


def _layer(x, p, pos, cfg, bits, dense):
    """The layer's output, and a MoE layer's routing (``_moe``)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p["ln1"]["scale"], eps), p["attn"], pos, cfg,
                       bits)
    h = _rms(x, p["ln2"]["scale"], eps)
    if dense:
        return x + _swiglu(h, p["ffn"], bits), None
    y, routing = _moe(h, p["ffn"], cfg, bits)
    return x + y, routing


def _logits(params, tokens, positions, *, cfg, bits):
    eps = cfg["rms_norm_eps"]
    seq = tokens.shape[0]
    x = params["embed"]["table"].astype(jnp.float32)[tokens]
    pos = jnp.arange(seq)
    dec = params["decoder"]
    for i in range(len(dec["lead"])):
        x, _ = _layer(x, dec["lead"][str(i)], pos, cfg, bits, True)
    x, (margin, choice) = jax.lax.scan(
        lambda x, p: _layer(x, p, pos, cfg, bits, False), x,
        dec["stack"]["0"])
    h = _rms(x[positions], params["final_ln"]["scale"], eps)
    head = params["embed"]["head"] if "head" in params["embed"] \
        else params["embed"]["table"].T
    return _linear(h, head, bits), (margin[:, positions],
                                    choice[:, positions])
