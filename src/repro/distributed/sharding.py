"""Sharding rules: param-tree paths -> PartitionSpecs (GSPMD/pjit).

Strategy (MaxText-style 2D "FSDP + TP"):
  * weight matrices: d_model-ish dim sharded over ``data`` (FSDP — GSPMD
    inserts per-layer all-gathers under the scan), wide dim (d_ff, heads,
    vocab, ssm inner) sharded over ``model`` (tensor parallelism);
  * embeddings: vocab over ``model``;
  * MoE expert stacks: (E, D, F) -> (None, data, model) — weights stay put,
    tokens stay put, contractions reduce over sharded dims;
  * vectors (norm scales, biases, A_log...) replicated unless they span a
    model-sharded dim (qkv biases);
  * the multi-pod ``pod`` axis shards only the batch — gradient reduction
    over pods is then a separate, DCN-crossing all-reduce stage, which is
    the hierarchy a real 2-pod job wants.

Uneven shards (12 heads on 16-way model axis, 51866-vocab, 40 experts) are
legal — GSPMD pads — and the waste shows up honestly in the roofline's
MODEL_FLOPS / HLO_FLOPs ratio.

Activation/batch specs live in `batch_pspec` / `cache_pspec`: batch dims
shard over (pod, data) when divisible; KV-cache sequence dim shards over
``model`` (flash-decode style distributed KV).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ----------------------------------------------------- coadd mesh residency ---


def shard_count(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    """Total number of shards over the given mesh axes."""
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def image_axis_sharding(mesh: Mesh, shard_axes: Tuple[str, ...]) -> NamedSharding:
    """NamedSharding splitting an image-major (M, ...) array over `shard_axes`.

    Used by `PackedDataset.to_mesh` to pin a whole coadd layout onto the mesh
    once: axis 0 (the flattened image axis) is split over every shard axis,
    trailing (H, W, meta...) dims are replicated within a shard.
    """
    return NamedSharding(mesh, P(tuple(shard_axes)))


def shard_local_compaction(
    union_gate: np.ndarray, n_shards: int
) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Per-shard gather indices for a job's union flat gate (DESIGN.md §5).

    ``union_gate`` is the (M,) OR of every query's flat slot gate; a
    NamedSharding over axis 0 gives shard ``s`` the contiguous slab
    ``[s*L, (s+1)*L)`` with ``L = M // n_shards``.  Each shard should map
    only the slab entries some query selected, so this derives, per shard,
    the *local* indices of its gated slots.  The index array is padded to
    one shared static shape (`plan.scan_budget` bucket of the worst shard's
    count — shard_map compiles one program), but each shard also gets its
    OWN bucketed budget: the executor picks one power-of-two tile size
    dividing the shared budget and runs ``ceil(own_budget / tile)`` tiles
    per shard (slack rows past a shard's budget are 0-padded, gate-False
    entries), so quiet shards stop paying the busiest shard's gather+map
    cost (the ROADMAP two-tier budget).

    Returns ``(local_idx (S, G) int32, pad_mask (S, G) bool, G,
    budgets (S,) int32)`` with ``G == budgets.max()``; padding entries
    point at local slot 0 and are masked False in the compacted per-query
    gates, the same duplicate-then-mask discipline as `plan.compact_gate`.
    """
    from repro.core.plan import scan_budget

    m = union_gate.shape[0]
    if m % n_shards:
        raise ValueError(
            f"shard count {n_shards} must divide flat length {m}"
        )
    local_len = m // n_shards
    per_shard = union_gate.reshape(n_shards, local_len)
    counts = per_shard.sum(axis=1)
    budgets = np.array(
        [scan_budget(int(c), local_len) for c in counts], np.int32
    )
    budget = int(budgets.max())
    local_idx = np.zeros((n_shards, budget), np.int32)
    pad_mask = np.zeros((n_shards, budget), bool)
    for s in range(n_shards):
        nz = np.nonzero(per_shard[s])[0][:budget]
        local_idx[s, : len(nz)] = nz
        pad_mask[s, : len(nz)] = True
    return local_idx, pad_mask, budget, budgets


# ---------------------------------------------------------------- params ---

# name -> spec template for the *trailing* dims; leading (stacked-layer /
# group) dims get None.
_MATRIX_RULES = {
    # input embedding: shard d_model — vocab-sharding the gather costs an
    # f32 (B,S,D) all-reduce every step (§Perf B2).  Tied tables (gemma,
    # qwen2-1.5b, mamba2) keep vocab-sharding via the "embedding_tied" rule
    # so the unembed contraction stays collective-free.
    "embedding": (None, "model"),
    "embedding_tied": ("model", None),
    "unembed": ("model", None),
    "w_q": ("data", "model"),
    "w_k": ("data", "model"),
    "w_v": ("data", "model"),
    "w_gate": ("data", "model"),
    "w_up": ("data", "model"),
    "w_o": ("model", "data"),
    "w_down": ("model", "data"),
    "in_proj": ("data", "model"),
    "out_proj": ("model", "data"),
    "router": (None, None),
    "conv_w": (None, "model"),
}
# 3D expert stacks (E, ., .): replicate over `data` — expert weights are
# small relative to the token buffers they contract with, and data-sharding
# their contraction dim makes GSPMD all-reduce the (much larger) activations
# (§Perf A3: 8 GB/layer for granite).  TP over d_ff only.
_EXPERT_RULES = {
    "w_gate": (None, None, "model"),
    "w_up": (None, None, "model"),
    "w_down": (None, "model", None),
}
_VECTOR_RULES = {
    "b_q": ("model",),
    "b_k": ("model",),
    "b_v": ("model",),
    "conv_b": ("model",),
}


def _fit_to_shape(spec_axes, shape, mesh: Mesh) -> P:
    """Drop axis assignments whose dim isn't divisible by the mesh axis.

    Explicit NamedShardings on jit arguments require exact divisibility
    (unlike internal GSPMD propagation) — non-divisible dims (12 q-heads on a
    16-way model axis, vocab 51866, 40 experts...) are replicated instead,
    and the lost parallelism shows up honestly in the roofline.
    """
    fitted = []
    for dim, ax in zip(shape, spec_axes):
        if ax is None:
            fitted.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        fitted.append(ax if dim % size == 0 else None)
    return P(*fitted)


def _spec_for(path: Tuple, leaf, mesh: Mesh) -> P:
    name = None
    for part in reversed(path):
        key = getattr(part, "key", None)
        if isinstance(key, str) and key not in ("moe", "mamba", "attn", "cross", "mlp"):
            name = key
            break
    shape = tuple(leaf.shape)
    ndim = len(shape)
    in_moe = any(getattr(p, "key", None) == "moe" for p in path)

    if name in _MATRIX_RULES:
        if in_moe and name in _EXPERT_RULES:
            base = _EXPERT_RULES[name]
        else:
            base = _MATRIX_RULES[name]
        pad = ndim - len(base)
        if pad < 0:  # smaller than template (shouldn't happen)
            return P()
        return _fit_to_shape([None] * pad + list(base), shape, mesh)
    if name in _VECTOR_RULES:
        base = _VECTOR_RULES[name]
        pad = ndim - len(base)
        return _fit_to_shape([None] * pad + list(base), shape, mesh)
    # scales, A_log, D, dt_bias, biases without rules: replicate.
    return P(*([None] * ndim))


def _substitute_pure_dp(base):
    """pure_dp: model axis becomes extra FSDP — "data"->("data","model"),
    "model"->None (no tensor parallelism)."""
    out = []
    for ax in base:
        if ax == "data":
            out.append(("data", "model"))
        elif ax == "model":
            out.append(None)
        else:
            out.append(ax)
    return out


def param_pspecs(params_shape, mesh: Mesh, pure_dp: bool = False) -> Any:
    """Tree of PartitionSpecs matching a params (or opt-state) shape tree."""
    tied = (
        isinstance(params_shape, dict)
        and "embed" in params_shape
        and "unembed" not in params_shape.get("embed", {})
    )

    def spec(p, l):
        name = getattr(p[-1], "key", None)
        if name == "embedding" and tied:
            s = _fit_to_shape(list(_MATRIX_RULES["embedding_tied"]), tuple(l.shape), mesh)
        else:
            s = _spec_for(p, l, mesh)
        if pure_dp:
            s = _fit_to_shape(_substitute_pure_dp(list(s)), tuple(l.shape), mesh)
        return s

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def strip_axis(tree_specs, axis: str = "data"):
    """Remove one mesh axis from every spec (zero1: compute params keep only
    model-axis TP; the data axis holds sharded fp32 masters + moments)."""

    def strip(spec):
        out = []
        for entry in spec:
            if entry == axis:
                out.append(None)
            elif isinstance(entry, tuple):
                kept = tuple(a for a in entry if a != axis)
                out.append(kept if kept else None)
            else:
                out.append(entry)
        return P(*out)

    return jax.tree.map(strip, tree_specs, is_leaf=lambda x: isinstance(x, P))


def named_shardings(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ----------------------------------------------------------- activations ---
def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _dp_if_divisible(mesh: Mesh, size: int, pure_dp: bool = False):
    axes = dp_axes(mesh)
    if pure_dp and "model" in mesh.shape:
        axes = axes + ("model",)
    while axes:
        total = int(np.prod([mesh.shape[a] for a in axes]))
        if size % total == 0:
            return axes
        axes = axes[1:] if len(axes) > 1 else ()
    if "data" in mesh.shape and size % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_pspec(mesh: Mesh, global_batch: int, extra_dims: int = 1,
                pure_dp: bool = False) -> P:
    """(B, S[, ...]): batch over (pod, data) when divisible, rest replicated."""
    b_axes = _dp_if_divisible(mesh, global_batch, pure_dp)
    return P(b_axes, *([None] * extra_dims))


def cache_pspec(mesh: Mesh, batch: int, leaf_shape, seq_axis: int) -> P:
    """Stacked KV cache (L, B, T, H, Dh): B over data axes, T over model."""
    b_axes = _dp_if_divisible(mesh, batch)
    spec = [None] * len(leaf_shape)
    spec[1] = b_axes
    if "model" in mesh.shape and leaf_shape[seq_axis] % mesh.shape["model"] == 0:
        spec[seq_axis] = "model"
    return P(*spec)
