"""Parameter / activation / cache PartitionSpecs per architecture.

Scheme (DESIGN.md §5):
  * TP over ``model``: attention head projections, MLP d_ff, expert dim
    (EP) when divisible, vocab dim of embedding/head.
  * ZeRO-3 (FSDP) over the data axes (``data``, plus ``pod`` multi-pod):
    the other large dim of every weight — parameters and optimizer
    states are fully sharded over all devices.
  * Activations: batch over data axes; heads/d_ff over ``model`` via
    propagation (constraint points added by the perf pass live here).
  * KV caches: batch over data; kv-head dim over ``model`` when
    divisible, else *sequence*-sharded over ``model`` (split-KV decode).

Specs are built by name-based rules over the param tree; stacked-layer
leading axes (scan-over-periods) are detected by extra leading dims and
left unsharded.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Any

# rules: param name -> (spec for its core dims, matching trailing ndim)
# "fsdp" is replaced by the mesh's data axes tuple at build time.
_RULES: dict[str, tuple] = {
    # attention
    "wq": ("fsdp", "model"), "wk": ("fsdp", "model"), "wv": ("fsdp", "model"),
    "wo": ("model", "fsdp"),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    # latent attention: the shared latent projection stays whole, its
    # per-head decompression splits over heads
    "wkv_a": ("fsdp", None), "wkv_b": ("fsdp", "model"),
    # mlp
    "gate": ("fsdp", "model"), "up": ("fsdp", "model"),
    "down": ("model", "fsdp"),
    # moe (3D expert weights get a dedicated rule below)
    "router": ("fsdp", None),
    # mamba2
    "in_proj": ("fsdp", "model"), "out_proj": ("model", "fsdp"),
    "conv_w": (None, "model"), "conv_b": ("model",),
    "A_log": (None,), "D": (None,), "dt_bias": (None,),
    # rwkv6
    "wr": ("fsdp", "model"), "wg": ("fsdp", "model"),
    "wa": ("fsdp", None), "wb": (None, "model"),
    "w0": ("model",), "u": (None, None),
    "cwr": ("fsdp", "model"), "cwk": ("fsdp", "model"),
    "cwv": ("model", "fsdp"),
    "mix_r": (None,), "mix_k": (None,), "mix_v": (None,), "mix_w": (None,),
    "mix_g": (None,), "cmix_r": (None,), "cmix_k": (None,),
    "ln_x_scale": (None,), "ln_x_bias": (None,),
    # embedding / head
    "table": ("model", "fsdp"), "head": ("fsdp", "model"),
    # norms
    "scale": (None,),
}

_MOE_3D = {"gate", "up", "down"}


def _fsdp_axes(mesh_axes: tuple[str, ...]):
    axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _axis_size(entry, sizes: dict[str, int]) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for a in entry:
            n *= sizes.get(a, 1)
        return n
    return sizes.get(entry, 1)


def sanitize_spec(spec: P, shape: tuple[int, ...],
                  sizes: dict[str, int]) -> P:
    """Drop sharding on dims the axis size does not divide (explicit pjit
    shardings must divide; GSPMD padding only applies to propagated ones)."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    out = []
    for dim, entry in zip(shape, entries):
        n = _axis_size(entry, sizes)
        out.append(entry if (n > 1 and dim % n == 0) or n == 1 else None)
    return P(*out)


def mesh_sizes(mesh_axes: tuple[str, ...],
               mesh_shape: tuple[int, ...]) -> dict[str, int]:
    return dict(zip(mesh_axes, mesh_shape))


def param_spec_tree(cfg: ModelConfig, params_shape: Params,
                    mesh_axes: tuple[str, ...],
                    mesh_shape: tuple[int, ...] | None = None,
                    serve: bool = False) -> Params:
    """Build a PartitionSpec pytree mirroring ``params_shape``.

    ``serve=True``: inference weights — REPLICATE over the data axes
    (no per-step ZeRO all-gathers; weights are bf16 and fit), keep the
    model-axis TP shardings (SPerf iteration 3)."""
    fsdp = None if serve else _fsdp_axes(mesh_axes)
    sizes = mesh_sizes(mesh_axes, mesh_shape) if mesh_shape else \
        {a: {"pod": 2, "data": 16, "model": 16}.get(a, 1) for a in mesh_axes}
    model_n = sizes.get("model", 1)
    ep_ok = (cfg.moe is not None
             and cfg.moe.held_range[1] % max(model_n, 1) == 0)

    def rule_for(path, leaf) -> P:
        names = [str(getattr(p, "key", getattr(p, "name", "")))
                 for p in path]
        name = names[-1] if names else ""
        in_moe = cfg.moe is not None and "ffn" in names and \
            "shared" not in names and \
            name in _MOE_3D and len(leaf.shape) >= 3
        if in_moe:
            # expert weights [(stack,) E, d, f] / down [(stack,) E, f, d]:
            # EP over the expert dim when divisible, else TP inside every
            # expert (d_ff over model)
            if ep_ok:
                core = ("model", fsdp, None)
            elif name == "down":
                core = (None, "model", fsdp)
            else:
                core = (None, fsdp, "model")
            extra = len(leaf.shape) - 3
            spec = P(*((None,) * extra), *core)
            return sanitize_spec(spec, leaf.shape, sizes)
        rule = _RULES.get(name)
        if rule is None:
            return P()
        core = tuple(fsdp if r == "fsdp" else r for r in rule)
        extra = len(leaf.shape) - len(core)
        if extra < 0:
            return P()
        spec = P(*((None,) * extra), *core)
        return sanitize_spec(spec, leaf.shape, sizes)

    return jax.tree_util.tree_map_with_path(rule_for, params_shape)


def batch_spec(cfg: ModelConfig, mesh_axes: tuple[str, ...],
               kind: str) -> dict:
    fsdp = _fsdp_axes(mesh_axes)
    spec = {
        "tokens": P(fsdp, None),
        "labels": P(fsdp, None),
    }
    if cfg.frontend != "none":
        spec["frontend"] = P(fsdp, None, None)
    if kind == "decode":
        spec = {"tokens": P(fsdp, None)}
    return spec


def cache_spec_tree(cfg: ModelConfig, cache_shape: Params,
                    mesh_axes: tuple[str, ...],
                    mesh_shape: tuple[int, ...] | None = None) -> Params:
    """KV / recurrent cache specs.  Attention caches [.., B, T, NK, H]:
    kv-heads over model when divisible, else sequence-sharded (split-KV
    decode: each model shard attends over its cache slice — the pod-level
    near-bank pattern)."""
    fsdp = _fsdp_axes(mesh_axes)
    sizes = mesh_sizes(mesh_axes, mesh_shape) if mesh_shape else \
        {a: {"pod": 2, "data": 16, "model": 16}.get(a, 1) for a in mesh_axes}
    model_n = sizes.get("model", 1)
    model = "model" if model_n > 1 else None

    def rule_for(path, leaf) -> P:
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        name = names[-1] if names else ""
        nd = len(leaf.shape)
        if name == "ckv":      # latent rows [B, T, C]: sequence-sharded
            extra = nd - 3
            return sanitize_spec(P(*((None,) * extra), fsdp, model, None),
                                 leaf.shape, sizes)
        if name in ("k", "v"):
            extra = nd - 4  # [B, T, NK, H]
            nk = leaf.shape[-2]
            if model and nk % model_n == 0:
                spec = (fsdp, None, model, None)
            else:
                spec = (fsdp, model, None, None)  # sequence-sharded cache
            return sanitize_spec(P(*((None,) * extra), *spec),
                                 leaf.shape, sizes)
        if name in ("ssm", "wkv"):   # [B, H, P, N] / [B, H, K, V]
            extra = nd - 4
            return sanitize_spec(
                P(*((None,) * extra), fsdp, model, None, None),
                leaf.shape, sizes)
        if name == "conv":      # [B, W-1, C]
            extra = nd - 3
            return sanitize_spec(
                P(*((None,) * extra), fsdp, None, model),
                leaf.shape, sizes)
        if name in ("tshift", "cshift"):  # [B, 1, D]
            extra = nd - 3
            return sanitize_spec(
                P(*((None,) * extra), fsdp, None, None),
                leaf.shape, sizes)
        return P()

    return jax.tree_util.tree_map_with_path(rule_for, cache_shape)


def to_shardings(mesh: Mesh, spec_tree: Params) -> Params:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
