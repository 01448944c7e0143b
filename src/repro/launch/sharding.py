"""Sharding rules: FSDP (+TP) parameter layout and batch/cache specs.

Name-based rules (MaxText-style logical axes, with divisibility fallback):
every parameter leaf name maps to a tuple of logical dims; logical dims map
to mesh axes; any dim whose size is not divisible by its mesh-axis extent
falls back to replication (e.g. hymba's 25 q-heads or paligemma's single kv
head on a 16-way model axis).

The same leaf-name rules apply to optimizer moments and the Pflug
controller's prev_grad (they mirror the params pytree), so the whole train
state inherits the FSDP+TP layout without extra code.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical dimension -> mesh axes (resolved against the active mesh's names)
LOGICAL = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "tp": ("model",),
    "experts": ("model",),
    "none": None,
}

# parameter leaf name -> logical dims per trailing dimension (the stacked
# layer axis, when present, is always unsharded and handled separately)
PARAM_RULES: Dict[str, Tuple[str, ...]] = {
    # embeddings
    "embed": ("tp", "fsdp"),  # (V, D) — vocab on tp, d_model FSDP on data
    "lm_head": ("fsdp", "tp"),  # (D, V)
    # attention
    "wq": ("fsdp", "tp", "none"),  # (D, H, hd)
    "wk": ("fsdp", "tp", "none"),
    "wv": ("fsdp", "tp", "none"),
    "wo": ("tp", "none", "fsdp"),  # (H, hd, D)
    "bq": ("tp", "none"),
    "bk": ("tp", "none"),
    "bv": ("tp", "none"),
    # mlp
    "w_gate": ("fsdp", "tp"),  # (D, F)   [moe: (E, D, F) handled by ndim]
    "w_in": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),  # (F, D)
    "w_recept": ("fsdp", "tp"),
    # moe
    "router": ("fsdp", "tp"),  # (D, E)
    # rwkv time-mix
    "wr": ("fsdp", "tp", "none"),
    "wg": ("fsdp", "tp", "none"),
    "decay_a1": ("fsdp", "none"),
    "decay_a2": ("none", "tp", "none"),
    "decay_w0": ("tp", "none"),
    "bonus_u": ("tp", "none"),
    "ln_out": ("tp", "none"),
    "mu": ("none", "fsdp"),
    "mu_c": ("none", "fsdp"),
    # hymba ssm branch
    "w_xs": ("fsdp", "tp", "none"),
    "w_dt": ("fsdp", "tp"),
    "w_b": ("fsdp", "tp", "none"),
    "w_c": ("fsdp", "tp", "none"),
    "w_os": ("tp", "none", "fsdp"),
    "skip_d": ("tp", "none"),
    # small/replicated
    "scale": ("none",),
    "dt_bias": ("none",),
    "a_log": ("none",),
    "norm_attn": ("none",),
    "norm_ssm": ("none",),
}

# Alternative layouts tried (strictly — every named dim must divide) before
# the lenient PARAM_RULES fallback.  E.g. RWKV-6's 40 heads don't divide a
# 16-way model axis, but head_dim 64 does: shard the head_dim instead so the
# projections stay tensor-parallel.
PARAM_ALTS: Dict[str, list] = {
    "wq": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "wk": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "wv": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "wo": [("tp", "none", "fsdp"), ("none", "tp", "fsdp")],
    "wr": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "wg": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "w_xs": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "w_os": [("tp", "none", "fsdp"), ("none", "tp", "fsdp")],
    "w_b": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "w_c": [("fsdp", "tp", "none"), ("fsdp", "none", "tp")],
    "decay_a2": [("none", "tp", "none"), ("none", "none", "tp")],
    "decay_w0": [("tp", "none"), ("none", "tp")],
    "bonus_u": [("tp", "none"), ("none", "tp")],
    "ln_out": [("tp", "none"), ("none", "tp")],
}

# MoE expert tensors are rank-3 with leading experts dim
MOE_RULES = {
    "w_gate": ("tp", "fsdp", "none"),  # (E, D, F)
    "w_in": ("tp", "fsdp", "none"),
    "w_out": ("tp", "none", "fsdp"),  # (E, F, D)
}

# KV-cache alternatives (strict, tried in order): shard kv heads when they
# divide |model| (classic TP); otherwise shard the cache SEQUENCE dim — for
# GQA archs with few kv heads (qwen1.5-110b kv=8, llama kv=8) this is what
# keeps a 32k-deep cache on-chip (§Perf pair 3).
CACHE_ALTS: Dict[str, list] = {
    "k": [("none", "batch", "none", "tp", "none"),
          ("none", "batch", "tp", "none", "none")],
    "v": [("none", "batch", "none", "tp", "none"),
          ("none", "batch", "tp", "none", "none")],
}

CACHE_RULES: Dict[str, Tuple[str, ...]] = {
    # stacked (L, B, S, KV, hd)
    "k": ("none", "batch", "none", "tp", "none"),
    "v": ("none", "batch", "none", "tp", "none"),
    # rwkv: (L, B, D) / (L, B, H, K, V)
    "x_att": ("none", "batch", "none"),
    "x_ffn": ("none", "batch", "none"),
    "s": ("none", "batch", "tp", "none", "none"),
    # hymba ssm state (L, B, H, N, P)
    "ssm": ("none", "batch", "tp", "none", "none"),
}

BATCH_RULES: Dict[str, Tuple[str, ...]] = {
    "tokens": ("batch", "none"),
    "targets": ("batch", "none"),
    "token": ("batch", "none"),
    "patches": ("batch", "none", "none"),
    "frames": ("batch", "none", "none"),
}


def _resolve(logical: str, mesh: Mesh) -> Optional[Tuple[str, ...]]:
    axes = LOGICAL[logical]
    if axes is None:
        return None
    present = tuple(a for a in axes if a in mesh.axis_names)
    return present or None


def _axis_extent(axes: Optional[Tuple[str, ...]], mesh: Mesh) -> int:
    if not axes:
        return 1
    return int(np.prod([mesh.shape[a] for a in axes]))


def _spec_from_dims(dims, shape, mesh: Mesh, strict: bool) -> Optional[P]:
    dims = list(dims)
    # stacked layer axis (params): rank = len(rule)+1 -> prepend replicated
    while len(dims) < len(shape):
        dims = ["none"] + dims
    if len(dims) > len(shape):  # e.g. biases reusing a longer rule
        dims = dims[-len(shape):]
    out = []
    for size, logical_dim in zip(shape, dims):
        axes = _resolve(logical_dim, mesh)
        if axes and size % _axis_extent(axes, mesh) == 0:
            out.append(axes if len(axes) > 1 else axes[0])
        elif strict and axes:
            return None
        else:
            out.append(None)
    return P(*out)


def spec_for(
    name: str, shape: Tuple[int, ...], mesh: Mesh, rules: Dict[str, Tuple[str, ...]]
) -> P:
    """PartitionSpec for a leaf: alternatives first (all-dims-strict), then
    the lenient per-dim fallback of the primary rule."""
    logical = rules.get(name)
    if logical is None:
        return P()
    for alt in PARAM_ALTS.get(name, []):
        spec = _spec_from_dims(alt, shape, mesh, strict=True)
        if spec is not None:
            return spec
    return _spec_from_dims(logical, shape, mesh, strict=False)


def _param_spec(path, leaf, mesh: Mesh) -> P:
    keys = [p.key for p in path if hasattr(p, "key")]
    if not keys:
        return P()
    name = keys[-1]
    rules = PARAM_RULES
    # MoE expert tensors (under the 'moe' subtree) carry a leading experts dim.
    if "moe" in keys and name in MOE_RULES:
        rules = {**PARAM_RULES, name: MOE_RULES[name]}
    return spec_for(name, leaf.shape, mesh, rules)


def param_shardings(params_shapes: Any, mesh: Mesh):
    """NamedShardings for a params-like pytree (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, _param_spec(path, leaf, mesh)),
        params_shapes,
    )


def named(mesh: Mesh, *dims: str) -> NamedSharding:
    """NamedSharding from logical dim names (no divisibility check)."""
    out = []
    for d in dims:
        axes = _resolve(d, mesh)
        out.append(axes if axes and len(axes) > 1 else (axes[0] if axes else None))
    return NamedSharding(mesh, P(*out))


def batch_shardings(batch_shapes: Dict[str, Any], mesh: Mesh):
    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        for alt in CACHE_ALTS.get(name, []):
            s = _spec_from_dims(alt, leaf.shape, mesh, strict=True)
            if s is not None:
                return NamedSharding(mesh, s)
        rules = {**BATCH_RULES, **CACHE_RULES}
        if name in rules:
            return NamedSharding(mesh, spec_for(name, leaf.shape, mesh, rules))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec, batch_shapes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def place_spanning(tree, sharding: NamedSharding):
    """Place a pytree of host-local arrays under ``sharding``, spanning processes.

    Single-process this is one ``jax.device_put`` of the whole tree (a
    bitwise no-op on the values).  Multi-process, ``device_put`` cannot
    build an array whose shards live on non-addressable devices — each
    process instead materializes only its addressable shards of each leaf
    via ``jax.make_array_from_callback`` (every process must hold the full
    host-side leaves, which sweep dispatch guarantees: cell leaves and keys
    are computed from the same host inputs on every process)."""
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)

    def place(x):
        return jax.make_array_from_callback(np.shape(x), sharding, lambda idx: x[idx])

    return jax.tree.map(place, tree)


def activation_resolver(mesh: Mesh):
    """Resolver for repro.shardctx.activation_sharding: logical activation
    dims -> NamedSharding.  Default: per-dim divisibility fallback.  With
    strict=True, returns None unless EVERY requested dim is satisfiable
    (used by constrain_alt to pick among alternative layouts)."""

    def resolve(logical: Tuple[str, ...], shape: Tuple[int, ...], strict: bool = False):
        if len(logical) != len(shape):
            return None
        dims = []
        for size, l in zip(shape, logical):
            axes = _resolve(l, mesh)
            if axes and size % _axis_extent(axes, mesh) == 0:
                dims.append(axes if len(axes) > 1 else axes[0])
            elif strict and axes:
                return None
            else:
                dims.append(None)
        return NamedSharding(mesh, P(*dims))

    return resolve
