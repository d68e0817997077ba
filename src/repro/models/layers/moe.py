"""Mixture-of-experts FFN with dropless sort + ragged_dot dispatch.

TPU adaptation (DESIGN.md §4): instead of a capacity-factor one-hot dispatch
tensor (O(tokens x E x C) memory -- infeasible at deepseek-v2 scale), tokens
are sorted by assigned expert and processed with ``jax.lax.ragged_dot``,
whose TPU lowering is a grouped MXU matmul. Two sharding strategies:

  * "tp"  (default): expert weights sharded on the FFN dim over the `model`
    axis -- no all-to-all, tokens stay put; good when E*d_ff is modest.
  * "ep": expert-parallel via shard_map -- experts sharded over `model`,
    tokens all-gathered per shard, local ragged compute, psum_scatter
    combine. Exercised by the perf-iteration harness.

Router: softmax top-k with optional shared experts (deepseek-v2) and an
aux load-balance loss (Switch-style), returned for logging/training.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers.dense import dense_init
from repro.models.layers.mlp import _act, is_gated, mlp_apply, mlp_init


def moe_init(key, d_model: int, cfg: MoEConfig, activation: str, *,
             lora_ranks: dict, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    e, ff = cfg.num_experts, cfg.expert_d_ff
    gated = is_gated(activation)
    scale = d_model ** -0.5
    def w(k, shape):
        return (jax.random.normal(k, shape) * scale).astype(dtype)
    params = {
        "router": dense_init(ks[0], d_model, e, dtype=jnp.float32),
        # expert weights stacked on a leading expert axis
        "w_up": w(ks[1], (e, d_model, ff)),
        "w_down": w(ks[2], (e, ff, d_model)),
    }
    if gated:
        params["w_gate"] = w(ks[3], (e, d_model, ff))
    if cfg.num_shared_experts:
        shared_ff = (cfg.shared_d_ff or ff) * cfg.num_shared_experts
        params["shared"] = mlp_init(
            jax.random.fold_in(key, 7), d_model, shared_ff, activation,
            lora_ranks={}, dtype=dtype)
    return params


def router_topk(router_logits: jnp.ndarray, top_k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(T, E) logits -> (weights (T,k), experts (T,k), aux_loss scalar)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * p_e
    e = router_logits.shape[-1]
    fraction = jnp.mean(
        jax.nn.one_hot(experts, e, dtype=jnp.float32).sum(axis=1), axis=0)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(fraction * mean_prob)
    return weights, experts, aux


def _expert_ffn_sorted(tokens_rep: jnp.ndarray, group_sizes: jnp.ndarray,
                       params: dict, activation: str) -> jnp.ndarray:
    """ragged grouped FFN: tokens_rep (Tk, d) sorted by expert."""
    up = jax.lax.ragged_dot(tokens_rep, params["w_up"].astype(tokens_rep.dtype),
                            group_sizes)
    if "w_gate" in params:
        gate = jax.lax.ragged_dot(
            tokens_rep, params["w_gate"].astype(tokens_rep.dtype), group_sizes)
        h = _act(activation, gate) * up
    else:
        h = _act(activation, up)
    return jax.lax.ragged_dot(h, params["w_down"].astype(tokens_rep.dtype),
                              group_sizes)


def _expert_ffn_capacity(sorted_tokens: jnp.ndarray,
                         group_sizes: jnp.ndarray, params: dict,
                         activation: str, capacity: int) -> jnp.ndarray:
    """Capacity-bounded grouped FFN (§Perf iteration A).

    ragged_dot's portable lowering is a DENSE dot over all groups -- every
    token visits every local expert (E_local x waste). Since tokens are
    already SORTED by expert, each expert's tokens are contiguous: slice a
    fixed-capacity window per expert, run a batched (E, C, d) x (E, d, f)
    matmul (true grouped MXU work), mask rows beyond the group size, and
    scatter-add back. Tokens beyond capacity are dropped (standard capacity
    factor); compute = E x C x d x f ~= capacity_factor x ideal.
    """
    tk, d = sorted_tokens.shape
    e = group_sizes.shape[0]
    starts = jnp.cumsum(group_sizes) - group_sizes          # (E,)
    offs = jnp.arange(capacity)
    idx = starts[:, None] + offs[None, :]                   # (E, C)
    valid = offs[None, :] < group_sizes[:, None]            # (E, C)
    idx_c = jnp.minimum(idx, tk - 1)
    toks = sorted_tokens[idx_c] * valid[..., None].astype(sorted_tokens.dtype)
    up = jnp.einsum("ecd,edf->ecf", toks,
                    params["w_up"].astype(toks.dtype))
    if "w_gate" in params:
        gate = jnp.einsum("ecd,edf->ecf", toks,
                          params["w_gate"].astype(toks.dtype))
        h = _act(activation, gate) * up
    else:
        h = _act(activation, up)
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(toks.dtype))
    out = out * valid[..., None].astype(out.dtype)
    return jnp.zeros((tk, d), out.dtype).at[idx_c.reshape(-1)].add(
        out.reshape(-1, d))


def moe_apply_ep(params: dict, x: jnp.ndarray, cfg: MoEConfig,
                 activation: str, mesh, ep_axis: str = "model", *,
                 batch_axes=("data",), lora_rank: int = -1,
                 lora_scale: float = 1.0,
                 capacity_factor: float = 0.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map (DESIGN.md §5).

    Experts are sharded over ``ep_axis``; each device routes its local batch
    shard's tokens, computes ONLY its local experts' contributions with
    ragged_dot, and a psum over ``ep_axis`` combines per-token outputs --
    the TPU-native analogue of the all-to-all dispatch.
    """
    from jax.sharding import PartitionSpec as P

    e = cfg.num_experts
    axis_size = mesh.shape[ep_axis]
    local_e = e // axis_size
    orig_shape = x.shape

    def block(xt, router_w, w_up, w_gate, w_down):
        # xt: (b_loc, l, d) local batch shard; expert weights local slice
        d = xt.shape[-1]
        toks = xt.reshape(-1, d)
        t = toks.shape[0]
        logits = toks.astype(jnp.float32) @ router_w           # (T, E) full
        weights, experts, aux = router_topk(logits, cfg.top_k)
        my_idx = jax.lax.axis_index(ep_axis)
        e_lo = my_idx * local_e
        flat_expert = experts.reshape(-1)
        flat_weight = weights.reshape(-1)
        token_idx = jnp.repeat(jnp.arange(t), cfg.top_k)
        local = (flat_expert >= e_lo) & (flat_expert < e_lo + local_e)
        # map non-local assignments to a dummy trailing group with 0 weight
        local_expert = jnp.where(local, flat_expert - e_lo, local_e)
        w_masked = jnp.where(local, flat_weight, 0.0)
        order = jnp.argsort(local_expert, stable=True)
        sorted_tokens = toks[token_idx[order]]
        group_sizes = jnp.bincount(local_expert, length=local_e + 1)
        p_local = {"w_up": jnp.concatenate(
                       [w_up, jnp.zeros_like(w_up[:1])], axis=0),
                   "w_down": jnp.concatenate(
                       [w_down, jnp.zeros_like(w_down[:1])], axis=0)}
        if w_gate is not None:
            p_local["w_gate"] = jnp.concatenate(
                [w_gate, jnp.zeros_like(w_gate[:1])], axis=0)
        if capacity_factor > 0:
            # expected tokens per local expert = T*k/E (global balance);
            # dummy group (overflow of non-local tokens) gets capacity too
            cap = int(capacity_factor * (t * cfg.top_k) / e) + 1
            out_sorted = _expert_ffn_capacity(sorted_tokens, group_sizes,
                                              p_local, activation, cap)
        else:
            out_sorted = _expert_ffn_sorted(sorted_tokens, group_sizes,
                                            p_local, activation)
        contrib = out_sorted * w_masked[order][:, None].astype(out_sorted.dtype)
        combined = jnp.zeros((t, d), out_sorted.dtype).at[
            token_idx[order]].add(contrib)
        combined = jax.lax.psum(combined, ep_axis)
        return combined.reshape(xt.shape), aux

    bspec = P(batch_axes, None, None)
    out, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(bspec, P(), P(ep_axis, None, None),
                  P(ep_axis, None, None) if "w_gate" in params else P(),
                  P(ep_axis, None, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, params["router"]["w"],
      params["w_up"], params.get("w_gate", jnp.zeros((0,))), params["w_down"])
    if "shared" in params:
        out = out + mlp_apply(params["shared"], x, activation, lora_rank=0)
    return out.reshape(orig_shape), aux


def moe_apply(params: dict, x: jnp.ndarray, cfg: MoEConfig, activation: str,
              *, lora_rank: int = -1, lora_scale: float = 1.0
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN. x (..., d). Returns (out, aux_loss)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)                                      # (T, d)
    t = xt.shape[0]
    logits = xt.astype(jnp.float32) @ params["router"]["w"]    # (T, E)
    weights, experts, aux = router_topk(logits, cfg.top_k)     # (T,k)

    # replicate tokens k times, sort by expert id
    tk = t * cfg.top_k
    flat_expert = experts.reshape(tk)                          # (Tk,)
    flat_weight = weights.reshape(tk)
    token_idx = jnp.repeat(jnp.arange(t), cfg.top_k)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_tokens = xt[token_idx[order]]                       # (Tk, d)
    group_sizes = jnp.bincount(flat_expert, length=cfg.num_experts)
    out_sorted = _expert_ffn_sorted(sorted_tokens, group_sizes, params,
                                    activation)
    # unsort + weighted combine back to tokens
    contrib = out_sorted * flat_weight[order][:, None].astype(out_sorted.dtype)
    combined = jnp.zeros((t, d), out_sorted.dtype).at[token_idx[order]].add(contrib)
    if "shared" in params:
        combined = combined + mlp_apply(params["shared"], xt, activation,
                                        lora_rank=0)
    return combined.reshape(orig_shape), aux
