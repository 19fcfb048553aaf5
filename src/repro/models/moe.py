"""Mixture-of-Experts MLP: top-k routing with GShard-style capacity dispatch.

Train/prefill path: tokens are grouped by batch row; each group dispatches
its tokens into per-expert capacity buffers via one-hot einsums (static
shapes — the TPU/pjit-native formulation; GSPMD turns the expert einsums
into sharded GEMMs + all-to-alls when the expert/ff dims are sharded).
Tokens beyond capacity are dropped (standard GShard semantics); capacity
factor is configurable per run.

Decode path: one-token batches make capacity dispatch degenerate, so decode
computes a dense mixture over the top-k experts' weights — at decode the
layer is weight-bandwidth-bound anyway, and every expert page is touched
once per batch (the vLLM-style argument).

Aux loss: Switch-style load-balancing loss, returned to the trainer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import cast


def _shard_batch(x, cfg: ModelConfig):
    """Pin dim 0 to the mesh's data axes (GSPMD otherwise replicates the
    scatter buffers and inserts full-size all-reduces — §Perf A2)."""
    if not cfg.act_shard_axes:
        return x
    from jax.sharding import PartitionSpec as P

    spec = P(tuple(cfg.act_shard_axes), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def moe_init(key, cfg: ModelConfig) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(f)
    return {
        "router": jax.random.normal(kr, (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(kg, (e, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ku, (e, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(kd, (e, f, d), jnp.float32) * s_out,
    }


def _expert_ffn(params, h, dt):
    """h: (B, E, C, D) -> (B, E, C, D) through per-expert SwiGLU."""
    g = jnp.einsum("becd,edf->becf", h, cast(params["w_gate"], dt))
    u = jnp.einsum("becd,edf->becf", h, cast(params["w_up"], dt))
    a = jax.nn.silu(g) * u
    return jnp.einsum("becf,efd->becd", a, cast(params["w_down"], dt))


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss). Dispatch impl selected by cfg.moe_impl."""
    if cfg.moe_impl == "shard_map":
        return moe_apply_shardmap(params, x, cfg)
    if cfg.moe_impl == "scatter":
        return moe_apply_scatter(params, x, cfg)
    return moe_apply_onehot(params, x, cfg)


def moe_apply_shardmap(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-locality MoE (§Perf A4): the paper's move-compute-to-the-data
    stance expressed directly.

    The routed FFN is token-local given replicated expert weights, so we
    `shard_map` it over every mesh axis the batch divides: tokens never move,
    experts are replicated (they are small), the dispatch is the scatter
    formulation executed device-locally, and the ONLY collectives left are
    the expert-weight gradient psums the backward pass inserts.  GSPMD's
    auto-partitioner (onehot/scatter paths) instead reshards the expanded
    (E*C) buffers through 35 GB/layer all-reduces — explicit beats implicit
    at this granularity.
    """
    if not cfg.act_shard_axes:
        return moe_apply_scatter(params, x, cfg)
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    axes = tuple(cfg.act_shard_axes)
    local_cfg = dataclasses.replace(cfg, act_shard_axes=())

    def body(p, xl):
        out, aux = moe_apply_scatter(p, xl, local_cfg)
        for ax in axes:
            aux = jax.lax.pmean(aux, ax)
        return out, aux

    fn = jax.shard_map(
        body,
        in_specs=(P(), P(axes, None, None)),
        out_specs=(P(axes, None, None), P()),
        check_vma=False,
    )
    return fn(params, x)


def moe_apply_onehot(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style one-hot einsum dispatch (paper-faithful MoE baseline).

    O(T*E*C*D) dispatch FLOPs — kept as the reference implementation and the
    §Perf baseline; `moe_apply_scatter` is the optimized path.
    """
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(s * k * cfg.capacity_factor / e))
    cap = min(cap, s * k)

    logits = (x @ cast(params["router"], dt)).astype(jnp.float32)  # (B,S,E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                            # (B,S,k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)    # renorm

    # Flatten the k slots: T = S*k successive (token, slot) pairs.
    t = s * k
    sel = topi.reshape(b, t)                                        # (B,T)
    w = topv.reshape(b, t)                                          # (B,T)
    onehot = jax.nn.one_hot(sel, e, dtype=jnp.float32)              # (B,T,E)
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0                 # (B,T,E)
    keep = (pos >= 0) & (pos < cap)
    pos = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    dispatch = (onehot[..., None] * slot_oh).astype(dt)             # (B,T,E,C)

    x_slots = jnp.repeat(x, k, axis=1)                              # (B,T,D)
    h = jnp.einsum("btec,btd->becd", dispatch, x_slots)             # (B,E,C,D)
    h = _expert_ffn(params, h, dt)
    combine = dispatch * w[..., None, None].astype(dt)
    out = jnp.einsum("btec,becd->btd", combine, h)                  # (B,T,D)
    out = out.reshape(b, s, k, d).sum(axis=2)

    # Switch load-balancing loss: E * sum_e f_e * p_e.
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(gates, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def moe_apply_scatter(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter/gather capacity dispatch (§Perf hillclimb, MegaBlocks-adjacent).

    Replaces the O(T*E*C*D) one-hot dispatch/combine einsums with
    O(T*k*D) scatter-add into per-expert capacity buffers and a gather back:

      slot  = expert_id * C + position_in_expert     (cumsum over one-hot)
      buf   = zeros(B, E*C, D).at[b, slot].add(x)    (dropped slots -> sink)
      h     = expert_ffn(buf)                        (same batched GEMMs)
      out   = h[b, slot] * gate

    Expert GEMM FLOPs are capacity_factor x the useful compute; everything
    else is data movement.  Token-drop semantics identical to the one-hot
    path (same position-in-expert order), so outputs match exactly.
    """
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(s * k * cfg.capacity_factor / e))
    cap = min(cap, s * k)

    logits = (x @ cast(params["router"], dt)).astype(jnp.float32)   # (B,S,E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                             # (B,S,k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    t = s * k
    sel = topi.reshape(b, t)                                         # (B,T)
    w = topv.reshape(b, t).astype(dt)
    onehot = jax.nn.one_hot(sel, e, dtype=jnp.float32)               # (B,T,E)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1.0        # (B,T)
    keep = (pos >= 0) & (pos < cap)
    slot = jnp.where(keep, sel * cap + pos.astype(jnp.int32), e * cap)

    # Constrain every scatter/gather OPERAND to stay batch-sharded — if the
    # zeros or indices are left unannotated GSPMD replicates the scatter and
    # all-reduces the full (B, E*C, D) buffer (§Perf A2: 35 GB/layer).
    x_slots = _shard_batch(jnp.repeat(x, k, axis=1), cfg)            # (B,T,D)
    slot = _shard_batch(slot, cfg)
    bidx = jnp.arange(b)[:, None]
    zeros = _shard_batch(jnp.zeros((b, e * cap + 1, d), dt), cfg)
    buf = zeros.at[bidx, slot].add(x_slots * keep[..., None].astype(dt))
    buf = _shard_batch(buf, cfg)
    h = _expert_ffn(params, buf[:, : e * cap].reshape(b, e, cap, d), dt)
    h = _shard_batch(h, cfg)
    y = h.reshape(b, e * cap, d)
    y = jnp.concatenate([y, jnp.zeros((b, 1, d), dt)], axis=1)       # sink row
    out = _shard_batch(y[bidx, slot], cfg) * (w * keep.astype(dt))[..., None]
    out = _shard_batch(out.reshape(b, s, k, d).sum(axis=2), cfg)

    frac_tokens = jnp.mean(jax.nn.one_hot(topi[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(gates, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def moe_apply_decode(params, x, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, 1, D). Dense mixture over top-k experts (see module docstring)."""
    dt = x.dtype
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = (x @ cast(params["router"], dt)).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    mix = jnp.zeros_like(gates).at[
        jnp.arange(b)[:, None, None],
        jnp.arange(s)[None, :, None],
        topi,
    ].set(topv)                                                     # (B,S,E)
    g = jnp.einsum("bsd,edf->bsef", x, cast(params["w_gate"], dt))
    u = jnp.einsum("bsd,edf->bsef", x, cast(params["w_up"], dt))
    a = jax.nn.silu(g) * u
    o = jnp.einsum("bsef,efd->bsed", a, cast(params["w_down"], dt))
    return jnp.einsum("bse,bsed->bsd", mix.astype(dt), o)
