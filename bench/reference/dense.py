"""Plain float32 reference of a dense decoder (the Llama / Qwen3 family).

RMSNorm, rotate-half RoPE over the whole head, grouped-query or full
multi-head causal attention, an optional RMSNorm over each head's q and
k before RoPE (qk_norm), a SwiGLU MLP, and a tied or untied LM head.
It imports nothing of the program.  It reads the parameter tree by
name (``embed/table``, ``embed/head``, ``final_ln/scale`` and, stacked
over layers, ``decoder/stack/0/{ln1,attn,ln2,ffn}``), and the shapes
from the configuration file.  Every matmul runs in float32 at the
highest precision; the sequence goes through all layers at once in a
scan over the stacked weights, attention in blocks of query rows, so
that it fits beside the weights.

``control=True`` computes the same pass with every operand of the
linear layers (q, k, v, o, the MLP and the LM head) rounded to fp8
e4m3's 3-bit mantissa, as a per-tensor-scaled fp8 matmul would see
them (the exponent range is not clipped): the control that the check
must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


def round_mantissa(x, bits: int):
    """Round float32 ``x`` to ``bits`` mantissa bits (nearest, ties to
    even); the exponent is kept as it is."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bias = jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1)
    u = (u + bias) & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _linear(x, w, bits):
    if bits:
        x, w = round_mantissa(x, bits), round_mantissa(w, bits)
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, heads, hd]; rotate-half over the whole head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Reference logits and gaps for one configuration; shapes are fixed
    per instance (sequences padded to ``max_len``, up to ``max_new``
    positions read), so each pass compiles once."""

    def __init__(self, params, config: dict, *, max_len: int, max_new: int):
        self.params = params
        self.cfg = config
        self.nq = config["num_attention_heads"]
        self.nkv = config["num_key_value_heads"]
        self.hd = config.get("head_dim") or config["hidden_size"] // self.nq
        self.vocab = config["vocab_size"]
        self.seq = -(-max_len // Q_BLOCK) * Q_BLOCK
        self.max_new = max_new
        dec = params["decoder"]
        if set(dec.get("stack", {})) != {"0"} or dec.get("rem"):
            raise ValueError("the reference reads a stack of one layer kind")
        self._logits = jax.jit(functools.partial(_logits, cfg=config,
                                                 heads=(self.nq, self.nkv,
                                                        self.hd)),
                               static_argnames=("bits",))
        self._gaps = jax.jit(_gaps, static_argnums=2)

    def logits(self, tokens: np.ndarray, positions: np.ndarray,
               bits: int = 0) -> np.ndarray:
        """Logits [len(positions), vocab] of ``tokens`` read at
        ``positions`` (test use)."""
        return np.asarray(self._run(tokens, positions, bits)[0])

    def _run(self, tokens, positions, bits):
        n = len(tokens)
        if n > self.seq or len(positions) > self.max_new:
            raise ValueError(f"{n} tokens / {len(positions)} positions "
                             f"exceed {self.seq} / {self.max_new}")
        tok = np.zeros((self.seq,), np.int32)
        tok[:n] = tokens
        pos = np.zeros((self.max_new,), np.int32)
        pos[:len(positions)] = positions
        out = self._logits(self.params, jnp.asarray(tok), jnp.asarray(pos),
                           bits=bits)
        return out[:len(positions)], out

    def gaps(self, prompt: np.ndarray, served: np.ndarray, *,
             control: bool = False) -> dict:
        """For each served token: the reference's best logit minus the
        served token's, at the position that produced it (prefill for
        the first, then each decode step).  With ``control``, the same
        gap of the token the fp8 control puts first."""
        tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        positions = np.arange(len(prompt) - 1,
                              len(prompt) - 1 + len(served))
        k = len(served)
        _, ref = self._run(tokens, positions, 0)
        srv = np.zeros((self.max_new,), np.int32)
        srv[:k] = served
        out = {"served": np.asarray(
            self._gaps(ref, jnp.asarray(srv), self.vocab))[:k]}
        if control:
            _, ctl = self._run(tokens, positions, 3)
            first = jnp.argmax(ctl[:, :self.vocab], -1).astype(jnp.int32)
            out["control"] = np.asarray(
                self._gaps(ref, first, self.vocab))[:k]
        return out


def _gaps(logits, tokens, vocab):
    lg = logits[:, :vocab]
    best = jnp.max(lg, -1)
    got = jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]
    return best - got


def _logits(params, tokens, positions, *, cfg, heads, bits):
    nq, nkv, hd = heads
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    qk_norm = bool(cfg.get("qk_norm", False))
    seq = tokens.shape[0]
    table = params["embed"]["table"].astype(jnp.float32)
    x = table[tokens]
    pos = jnp.arange(seq)

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _linear(h, a["wq"], bits).reshape(seq, nq, hd)
        k = _linear(h, a["wk"], bits).reshape(seq, nkv, hd)
        v = _linear(h, a["wv"], bits).reshape(seq, nkv, hd)
        if qk_norm:
            q = _rms(q, a["q_norm"]["scale"], eps)
            k = _rms(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = nq // nkv
        qb = q.reshape(seq // Q_BLOCK, Q_BLOCK, nkv, g, hd)

        def block(args):
            i, qi = args
            s = jnp.einsum("qkgd,tkd->kgqt", qi, k,
                           precision=HIGHEST) / np.sqrt(hd)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :,
                                                         None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", w, v, precision=HIGHEST)

        o = jax.lax.map(block, (jnp.arange(seq // Q_BLOCK), qb))
        x = x + _linear(o.reshape(seq, nq * hd), a["wo"], bits)
        f = p["ffn"]
        h = _rms(x, p["ln2"]["scale"], eps)
        m = jax.nn.silu(_linear(h, f["gate"], bits)) * _linear(h, f["up"],
                                                                bits)
        return x + _linear(m, f["down"], bits), None

    x, _ = jax.lax.scan(layer, x, params["decoder"]["stack"]["0"])
    h = _rms(x[positions], params["final_ln"]["scale"], eps)
    head = params["embed"]["head"] if "head" in params["embed"] \
        else params["embed"]["table"].T
    return _linear(h, head, bits)
