"""Server-side aggregation rules for heterogeneous-rank FedLoRA.

Implements the paper's method and every baseline it compares against
(Table 1), all over one stacked-factor representation:

  bs    (M, d, r_max)   client B factors, zero-padded above r_k
  as_   (M, r_max, n)   client A factors, zero-padded below r_k
  ranks (M,)            client ranks
  n_k   (M,)            client sample counts

Methods
  fedavg    -- homogeneous FedAvg of factors (FedIT); requires equal ranks
  hetlora   -- zero-pad, average B and A SEPARATELY (aggregation bias!)
  flora     -- stacking: dW = sum w_k B_k A_k merged into the base weights,
               adapters re-initialized (cold start) -- bias-free, expensive
  flexlora  -- dW = sum (n_k/N) B_k A_k, SVD realloc (rank collapse!)
  raflora   -- rank-partitioned dW (Eq. 8), SVD realloc  <- the paper

``backend="dense"`` materializes dW (paper-faithful); ``backend="factored"``
uses the QR low-rank SVD (beyond-paper, bit-compatible up to float error);
``backend="kernel"`` is the fused Pallas path (TPU kernels, interpret-mode
on CPU): sqrt-weighted U_c/V_c stacks + (R, R) Gram cores on-chip feeding
``svd_realloc_gram`` -- O((d+n)R) memory, dW never materialized, on every
engine including the sharded one (DESIGN.md §4.3).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import functools

from repro import tracing
from repro.core import partitions as parts
from repro.core.svd import (check_fallback_globals, dense_fallback_term,
                            dense_from_weighted, factored_append_fallback,
                            factored_from_weighted, factored_stack_batched,
                            svd_realloc_dense, svd_realloc_factored,
                            svd_realloc_gram)


@dataclass
class AggregationResult:
    b_g: jnp.ndarray                  # (d, r_max)
    a_g: jnp.ndarray                  # (r_max, n)
    sigma: Optional[jnp.ndarray]      # singular values (r_max,) or None
    merge_delta: Optional[jnp.ndarray] = None  # FLoRA: dW folded into base


def _dq(x):
    """Dequantize a transport ``QuantFactor`` to f32 (duck-typed so this
    core module never imports ``repro.federation``); plain factor arrays
    pass through untouched. The single dequantization point of every
    stack-build path -- all weighting (omega rows, staleness discounts,
    the Eq. 8 fallback) happens downstream on dequantized values, so the
    aggregation math is byte-layout-agnostic (DESIGN.md §12)."""
    if hasattr(x, "q") and hasattr(x, "scale"):
        return x.q.astype(jnp.float32) * x.scale
    return x


def _leading(x) -> int:
    """Leading-axis length of a factor that may be a QuantFactor."""
    return (x.q if hasattr(x, "q") else x).shape[0]


def pad_stack(factors: Sequence[Tuple[jnp.ndarray, jnp.ndarray]],
              r_max: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[(B_k (d, r_k), A_k (r_k, n))] -> padded stacks (M,d,r_max),(M,r_max,n).

    Entries may be transport-quantized (QuantFactor pairs): the sequential
    reference path dequantizes here, at stack-build time."""
    bs, as_ = [], []
    for b, a in factors:
        b, a = _dq(b), _dq(a)
        r = b.shape[-1]
        pad_b = [(0, 0)] * b.ndim
        pad_b[-1] = (0, r_max - r)
        pad_a = [(0, 0)] * a.ndim
        pad_a[-2] = (0, r_max - r)
        bs.append(jnp.pad(b, pad_b))
        as_.append(jnp.pad(a, pad_a))
    return jnp.stack(bs), jnp.stack(as_)


def _weights(n_k: Sequence[float]) -> np.ndarray:
    n = np.asarray(n_k, dtype=np.float64)
    return n / n.sum()


def staleness_discount(n_k: Sequence[float],
                       staleness: Optional[Sequence[int]],
                       gamma: float = 1.0) -> np.ndarray:
    """Staleness-discounted effective sample counts for async aggregation.

    Client k whose update is ``staleness[k]`` aggregations old contributes
    with ``n_k * gamma**staleness[k]`` -- the discount folds into the
    n_k-DERIVED weights (FedAvg weights, FlexLoRA/raFLoRA omega rows, DoRA
    magnitude weights) BEFORE their normalization, so:

    * totals are preserved: every weight family normalizes over the
      discounted counts, so the weights of a fixed client set sum to the
      same total as the synchronous round (no silent global down-weighting
      -- staleness only shifts RELATIVE mass toward fresher clients);
    * ghost clients (n_k = 0) stay exactly zero;
    * the raFLoRA effective-contributor sets and the Eq. 8 fallback are
      untouched (membership is rank-based, not weight-based).

    ``staleness=None``, ``gamma=1``, or an all-zero staleness vector are
    exact no-ops (the input counts are returned unscaled), which is what
    makes ``pipeline_depth=1`` reduce bit-level to the batched engine.
    """
    tracing.count("agg/weight_counts", len(n_k))
    n = np.asarray(n_k, dtype=np.float64)
    if staleness is None or gamma == 1.0:
        return n
    s = np.broadcast_to(np.asarray(staleness, dtype=np.float64), n.shape)
    if not s.any():
        return n
    assert gamma > 0.0, gamma  # gamma<=0 would zero real clients
    return n * np.power(float(gamma), s)


def cohort_weights(n_k: Sequence[float],
                   staleness: Optional[Sequence[int]],
                   present: Optional[Sequence[bool]],
                   gamma: float = 1.0) -> np.ndarray:
    """Normalized per-client aggregation weights of one buffered cohort.

    The SINGLE host-side weight rule of every grouped engine: staleness-
    discounted effective counts (``staleness_discount``), absent clients
    (event-driven ``present`` mask) and ghost clients (n_k = 0) forced to
    exactly zero, normalized to sum to 1 over the cohort. The protocol
    checker (``analysis/protocol.py``) calls this same function at every
    model-checked trigger firing, so a weight-conservation violation there
    is a finding against the implementation, not against a re-derivation.
    """
    w = staleness_discount(n_k, staleness, gamma)
    if present is not None:
        w = np.where(np.asarray(present, dtype=bool), w, 0.0)
    total = w.sum()
    assert total > 0.0, "a cohort aggregated with zero total weight"
    return w / total


# ---------------------------------------------------------------------------
# aggregation rules
# ---------------------------------------------------------------------------

def weighted_avg(stack, w):
    """Weighted average over the leading client axis (any batch axes). The
    single implementation behind every plain-FedAvg reduction -- factor
    stacks AND DoRA magnitudes, eager AND jitted."""
    wshape = (-1,) + (1,) * (stack.ndim - 1)
    return (w.reshape(wshape) * stack).sum(0)


def _avg_factors(bs, as_, w):
    """Weighted client-axis average of both factor stacks (fedavg/hetlora)."""
    return weighted_avg(bs, w), weighted_avg(as_, w)


def _flora_delta(bs, as_, w):
    """FLoRA stacking math: unbiased dW + zeroed (cold-start) adapters.
    The single implementation behind flora, eager AND jitted."""
    dw = jnp.einsum("m,m...dr,m...rn->...dn", w.astype(jnp.float32),
                    bs.astype(jnp.float32), as_.astype(jnp.float32))
    # cold start: fresh (zero) global adapter; dW returned for base merge
    return (jnp.zeros(bs.shape[1:], jnp.float32),
            jnp.zeros(as_.shape[1:], jnp.float32), dw)


def aggregate_fedavg(bs, as_, ranks, n_k) -> AggregationResult:
    """Homogeneous FedAvg of the raw factors (FedIT). Biased mixing of
    B and A -- included as the homogeneous baseline."""
    ranks = np.asarray(ranks)
    assert (ranks == ranks[0]).all(), "fedavg requires homogeneous ranks"
    b_g, a_g = _avg_factors(bs, as_, jnp.asarray(_weights(n_k),
                                                 dtype=bs.dtype))
    return AggregationResult(b_g, a_g, None)


def aggregate_hetlora(bs, as_, ranks, n_k) -> AggregationResult:
    """HetLoRA: zero-padding alignment, separate averaging of B and A.
    E[B]E[A] != E[BA] -- the aggregation bias the later methods remove."""
    b_g, a_g = _avg_factors(bs, as_, jnp.asarray(_weights(n_k),
                                                 dtype=bs.dtype))
    return AggregationResult(b_g, a_g, None)


def aggregate_flora(bs, as_, ranks, n_k) -> AggregationResult:
    """FLoRA: stacking-based, bias-free. The aggregate dW = sum w_k B_k A_k
    is merged into the base weights and adapters restart from scratch
    (cold start). Communication cost O(M (d+n) r) is charged by the cost
    model in benchmarks/bench_cost.py."""
    w = jnp.asarray(_weights(n_k), dtype=jnp.float32)
    b_g, a_g, dw = _flora_delta(bs, as_, w)
    return AggregationResult(b_g, a_g, None, merge_delta=dw)


def aggregate_flexlora(bs, as_, ranks, n_k, *, backend: str = "factored"
                       ) -> AggregationResult:
    """FlexLoRA: rank-agnostic weighted sum + SVD realloc (Eqs. 2-4)."""
    r_max = bs.shape[-1]
    omega = jnp.asarray(parts.omega_flexlora(ranks, n_k, r_max))
    return _weighted_svd(bs, as_, omega, None, None, None, r_max, backend)


def aggregate_raflora(bs, as_, ranks, n_k, *, rank_levels: Sequence[int],
                      global_b=None, global_a=None,
                      backend: str = "factored") -> AggregationResult:
    """raFLoRA: rank-partitioned aggregation (Eq. 8 / Algorithm 1)."""
    r_max = max(rank_levels)
    omega_np, fallback_np = parts.omega_raflora(ranks, n_k, rank_levels)
    omega = jnp.asarray(omega_np)
    fallback = jnp.asarray(fallback_np)
    if not np.any(fallback_np):
        fallback = None
    return _weighted_svd(bs, as_, omega, global_b, global_a, fallback,
                         r_max, backend)


def _weighted_svd(bs, as_, omega, global_b, global_a, fallback, r_max,
                  backend) -> AggregationResult:
    """Weighted-diagonal contraction + SVD realloc.

    Accepts unstacked factors (M, d, r) or factors with ANY number of batch
    axes between the client axis and the matrix axes -- (M, L, d, r) layer
    stacks from lax.scan models, (M, P, L, d, r) shape buckets from the
    batched round engine. Dense/factored backends vmap the pipeline over
    each batch axis in turn; the kernel backend flattens the batch axes and
    lowers the whole bucket through the fused layer-batched Pallas grids
    (stack + Gram cores, never dW -- ``_agg_kernel_stacked``).
    """
    check_fallback_globals(fallback, global_b, global_a)
    if bs.ndim > 3:
        if backend == "kernel":
            return _agg_kernel_stacked(bs, as_, omega, global_b,
                                       global_a, fallback, r_max)
        def one_slice(bs_l, as_l, gb_l, ga_l):
            res = _weighted_svd(bs_l, as_l, omega, gb_l, ga_l, fallback,
                                r_max, backend)
            sig = res.sigma if res.sigma is not None else jnp.zeros((r_max,))
            return res.b_g, res.a_g, sig
        gb = global_b if global_b is not None else \
            jnp.zeros(bs.shape[1:-1] + (r_max,), jnp.float32)
        ga = global_a if global_a is not None else \
            jnp.zeros(as_.shape[1:-2] + (r_max, as_.shape[-1]), jnp.float32)
        b_g, a_g, sigma = jax.vmap(one_slice, in_axes=(1, 1, 0, 0))(
            bs, as_, gb, ga)
        return AggregationResult(b_g, a_g, sigma)
    if backend == "dense":
        dw = dense_from_weighted(bs, as_, omega, global_b, global_a, fallback)
        b_g, a_g, sigma = svd_realloc_dense(dw, r_max)
    elif backend == "factored":
        u_c, v_c = factored_from_weighted(bs, as_, omega, global_b, global_a,
                                          fallback)
        b_g, a_g, sigma = svd_realloc_factored(u_c, v_c, r_max)
    elif backend == "kernel":
        from repro.kernels import ops as kernel_ops
        u_c, v_c, g_u, g_v = kernel_ops.factored_stack_gram(
            bs, as_, omega, global_b, global_a, fallback)
        b_g, a_g, sigma = svd_realloc_gram(u_c, v_c, g_u, g_v, r_max)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return AggregationResult(b_g, a_g, sigma)


def _agg_kernel_stacked(bs, as_, omega, global_b, global_a,
                        fallback, r_max) -> AggregationResult:
    """Kernel backend for batch-stacked factors: flatten every batch axis
    into one layer axis, run the fused layer-batched Pallas grids once
    (sqrt-weighted U_c/V_c stacks + (R, R) Gram cores -- DESIGN.md §4.3,
    the Eq. 8 fallback riding as one extra client), then one batched
    Gram-core SVD realloc. dW (L, d, n) is never materialized."""
    from repro.kernels import ops as kernel_ops
    lead = bs.shape[1:-2]                     # batch axes after clients
    m, d, r = bs.shape[0], bs.shape[-2], bs.shape[-1]
    n = as_.shape[-1]
    layers = int(np.prod(lead))
    bs_l = jnp.moveaxis(bs.reshape(m, layers, d, r), 0, 1)
    as_l = jnp.moveaxis(as_.reshape(m, layers, r, n), 0, 1)
    gb = None if global_b is None else global_b.reshape(layers, d, r_max)
    ga = None if global_a is None else global_a.reshape(layers, r_max, n)
    u_c, v_c, g_u, g_v = kernel_ops.factored_stack_gram_layered(
        bs_l, as_l, omega, gb, ga, fallback)
    b_g, a_g, sigma = jax.vmap(
        functools.partial(svd_realloc_gram, r_max=r_max))(u_c, v_c, g_u, g_v)
    return AggregationResult(b_g.reshape(lead + (d, r_max)),
                             a_g.reshape(lead + (r_max, n)),
                             sigma.reshape(lead + (r_max,)))


# ---------------------------------------------------------------------------
# method registry + per-adapter driver
# ---------------------------------------------------------------------------

METHODS = ("fedavg", "hetlora", "flora", "flexlora", "raflora", "ffa")


def aggregate_ffa(bs, as_, ranks, n_k, *, global_b) -> AggregationResult:
    """FFA-LoRA (paper ref [9]): the random-init DOWN factor is FROZEN at
    its shared global value; only the UP factor is trained and averaged --
    removes the E[B]E[A] != E[BA] bias in the homogeneous setting.

    Layout note: the server maps model lora_a -> first factor here, so the
    FROZEN factor is ``bs``/``global_b`` and the averaged one is ``as_``.
    Heterogeneous ranks: zero-padded averaging (HetLoRA-style) on the
    trained factor.
    """
    a_g = weighted_avg(as_, jnp.asarray(_weights(n_k), dtype=as_.dtype))
    return AggregationResult(global_b, a_g, None)


# -- jitted whole-bucket pipelines (batched round engine) -------------------
#
# The sequential reference path runs the rules above eagerly, one adapter at
# a time. The batched engine instead stacks every same-shape adapter into
# one (M, P, ..., d, r) bucket and pushes the whole bucket through ONE jitted
# call -- including the stack/pad/concatenate assembly -- so per-op Python
# dispatch is paid once per bucket per round.

def _dispatch_stacked(bs, as_, warg, global_b, global_a, fallback, r_max,
                      backend, method):
    """Traced method dispatch over pre-stacked factors.

    Returns (b_g, a_g, sigma|None, merge_delta|None); ``warg`` is the
    client-weight vector (avg family) or the omega matrix (SVD family).
    """
    if method in ("fedavg", "hetlora", "ffa"):
        w = warg.astype(bs.dtype)
        a_g = weighted_avg(as_, w)
        if method == "ffa":           # frozen factor: keep the global value
            return global_b, a_g, None, None
        return weighted_avg(bs, w), a_g, None, None
    if method == "flora":
        b_g, a_g, dw = _flora_delta(bs, as_, warg)
        return b_g, a_g, None, dw
    res = _weighted_svd(bs, as_, warg, global_b, global_a, fallback,
                        r_max, backend)
    return res.b_g, res.a_g, res.sigma, None


@functools.partial(jax.jit, static_argnames=("r_max", "backend", "method"))
def _stacked_core(bs, as_, warg, global_b, global_a, fallback, *,
                  r_max, backend, method):
    return _dispatch_stacked(_dq(bs), _dq(as_), warg, global_b, global_a,
                             fallback, r_max, backend, method)


def _pad_rank(x, r_max: int, axis: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r_max - x.shape[axis])
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("r_max", "backend", "method"))
def _grouped_core(group_bs, group_as, warg, global_bs, global_as, fallback,
                  *, r_max, backend, method):
    """Assemble a shape bucket from per-rank-group factor tuples and
    aggregate it, all inside one XLA program.

    group_bs: tuple over rank groups of tuples over bucket adapters of
    (G, ..., d, r_group) arrays (group_as analogous); global_bs/global_as:
    tuples over bucket adapters of (..., d, r_max)/(..., r_max, n).
    Transport-quantized entries (QuantFactor) dequantize here, once, at
    stack-build time. The stack build is the named scope ``agg.stack``;
    the factored realloc's are ``agg.qr`` and ``agg.core_svd``.
    """
    with jax.named_scope("agg.stack"):
        bs = jnp.concatenate(
            [_pad_rank(jnp.stack([_dq(b) for b in bt], axis=1), r_max, -1)
             for bt in group_bs])                     # (M, P, ..., d, r_max)
        as_ = jnp.concatenate(
            [_pad_rank(jnp.stack([_dq(a) for a in at], axis=1), r_max, -2)
             for at in group_as])                     # (M, P, ..., r_max, n)
        gb = None if global_bs is None else jnp.stack(global_bs)
        ga = None if global_as is None else jnp.stack(global_as)
    return _dispatch_stacked(bs, as_, warg, gb, ga, fallback, r_max,
                             backend, method)


# -- sharded whole-bucket pipelines (sharded round engine) -------------------
#
# DESIGN.md §5: with clients sharded over the mesh's ``data`` axis, every
# reduction this family performs -- plain weighted factor averages, FLoRA's
# dW stacking, and the weighted-diagonal contraction behind the SVD-realloc
# methods -- becomes a per-shard partial sum followed by ONE ``jax.lax.psum``.
# The dense family all-reduces the (..., d, n) contraction; the factored
# AND kernel families all-reduce the zero-scattered (d, R) / (R, n) factor
# stack (each shard writes its own column block, so the psum is an
# all-gather in disguise and the reduced stack equals the single-device one
# up to client ordering, which the SVD does not see) -- the kernel backend
# builds its shard-local block with the layered Pallas stack grid over
# resident clients only (DESIGN.md §4.3). The SVD reallocation itself is
# the UNCHANGED single-device math (``svd_realloc_dense`` /
# ``svd_realloc_factored`` / the Pallas-Gram ``svd_realloc_gram``) applied
# to the reduced, replicated result.

def _realloc_dense_lead(dw, r_max):
    """Batched ``svd_realloc_dense`` over any leading bucket/layer axes."""
    lead, (d, n) = dw.shape[:-2], dw.shape[-2:]
    b, a, s = jax.vmap(functools.partial(svd_realloc_dense, r_max=r_max))(
        dw.reshape((-1, d, n)))
    return (b.reshape(lead + (d, r_max)), a.reshape(lead + (r_max, n)),
            s.reshape(lead + (r_max,)))


def _realloc_factored_lead(u_c, v_c, r_max):
    """Batched ``svd_realloc_factored`` over any leading bucket/layer axes."""
    lead = u_c.shape[:-2]
    d, rr = u_c.shape[-2:]
    n = v_c.shape[-1]
    b, a, s = jax.vmap(functools.partial(
        svd_realloc_factored, r_max=r_max))(
        u_c.reshape((-1, d, rr)), v_c.reshape((-1, rr, n)))
    return (b.reshape(lead + (d, r_max)), a.reshape(lead + (r_max, n)),
            s.reshape(lead + (r_max,)))


def _realloc_gram_lead(u_c, v_c, g_u, g_v, r_max):
    """Batched ``svd_realloc_gram`` over any leading bucket/layer axes."""
    lead = u_c.shape[:-2]
    d, rr = u_c.shape[-2:]
    n = v_c.shape[-1]
    b, a, s = jax.vmap(functools.partial(
        svd_realloc_gram, r_max=r_max))(
        u_c.reshape((-1, d, rr)), v_c.reshape((-1, rr, n)),
        g_u.reshape((-1, rr, rr)), g_v.reshape((-1, rr, rr)))
    return (b.reshape(lead + (d, r_max)), a.reshape(lead + (r_max, n)),
            s.reshape(lead + (r_max,)))


def _sharded_partial_quantized(group_bs, group_as, group_w, *, r_max,
                               axis, axes, axis_sizes):
    """Quantized factored/kernel partial: all-reduce the COMPRESSED bytes.

    Instead of dequantizing locally and psumming f32 stacks, each shard
    zero-scatters its raw int8/bf16 payload block into the full
    (…, d, S*width) stack -- mirroring ``factored_stack_batched``'s column
    layout exactly (column index = client*r_max + rank) -- together with a
    tiny f32 per-column weight vector folding ``scale * sqrt(omega)``.
    Disjoint blocks mean the payload psum is an all-gather in disguise
    (every position has exactly one nonzero contributor, so int8 never
    overflows), and the wire bytes drop by ~4x at int8 / 2x at bf16: the
    claim ``launch/fl_dryrun.py --transport`` verifies. Dequantization
    happens ONCE, after the reduction, so the returned (u_c, v_c) are the
    same f32 stacks the unquantized path reduces -- the Eq. 8 fallback
    append and the SVD realloc downstream are untouched, and the kernel
    backend shares this staging (its Gram grids consume the reduced,
    replicated stack exactly as in the unquantized sharded path).
    """
    qs = jnp.concatenate(
        [_pad_rank(jnp.stack([f.q for f in bt], axis=1), r_max, -1)
         for bt in group_bs])           # (m_loc, P, ..., d, r_max) payload
    sb = jnp.concatenate(
        [_pad_rank(jnp.stack([f.scale for f in bt], axis=1), r_max, -1)
         for bt in group_bs])           # (m_loc, P, ..., 1, r_max) f32
    qa = jnp.concatenate(
        [_pad_rank(jnp.stack([f.q for f in at], axis=1), r_max, -2)
         for at in group_as])           # (m_loc, P, ..., r_max, n) payload
    sa = jnp.concatenate(
        [_pad_rank(jnp.stack([f.scale for f in at], axis=1), r_max, -2)
         for at in group_as])           # (m_loc, P, ..., r_max, 1) f32
    w = jnp.concatenate(group_w)        # (m_loc, r_max) omega rows
    m, r = qs.shape[0], qs.shape[-1]
    lead = qs.shape[1:-2]
    sq = jnp.sqrt(jnp.maximum(w, 0.0)).astype(jnp.float32)
    sqr = sq.reshape((m,) + (1,) * len(lead) + (r,))
    colw_u = sb[..., 0, :] * sqr        # (m, *lead, r): scale * sqrt(omega)
    colw_v = sa[..., 0] * sqr
    # factored_stack_batched layout: column index = client*r_max + rank
    u_pay = jnp.moveaxis(qs, 0, -2).reshape(lead + (qs.shape[-2], m * r))
    v_pay = jnp.moveaxis(qa, 0, -3).reshape(lead + (m * r, qa.shape[-1]))
    cu = jnp.moveaxis(colw_u, 0, -2).reshape(lead + (m * r,))
    cv = jnp.moveaxis(colw_v, 0, -2).reshape(lead + (m * r,))
    width = m * r
    shard_idx = jnp.int32(0)            # flat shard index over the axes
    n_shards = 1
    for a, size in zip(axes, axis_sizes):
        shard_idx = shard_idx * size + jax.lax.axis_index(a)
        n_shards *= size
    off = shard_idx * width

    def scatter(x, ax):
        shape = list(x.shape)
        shape[ax] = n_shards * width
        full = jnp.zeros(tuple(shape), x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(full, x, off, axis=ax)

    u_full = jax.lax.psum(scatter(u_pay, -1), axis)
    v_full = jax.lax.psum(scatter(v_pay, -2), axis)
    cu_full = jax.lax.psum(scatter(cu, -1), axis)
    cv_full = jax.lax.psum(scatter(cv, -1), axis)
    u_c = u_full.astype(jnp.float32) * cu_full[..., None, :]
    v_c = v_full.astype(jnp.float32) * cv_full[..., :, None]
    return u_c, v_c


def _sharded_partial(group_bs, group_as, group_w, gb, ga, *, r_max,
                     backend, method, axes, axis_sizes):
    """Per-shard body (runs INSIDE shard_map): assemble the shard's local
    client block of the bucket, compute its partial reduction, psum.

    ``group_w`` carries the per-group client weight vectors (avg family) or
    omega matrix rows (SVD family) already zeroed for ghost clients, sharded
    along the client axis exactly like the factor stacks, so each shard
    weights only its resident clients. ``axes`` is the tuple of mesh axes
    the client axis is sharded over (the live engine's 1-D mesh uses
    ``("data",)``; the multi-pod dry run uses ``("pod", "data")`` so the
    pod axis shares the work instead of replicating it).
    """
    axis = axes if len(axes) > 1 else axes[0]
    quantized = any(hasattr(b, "q") for bt in group_bs for b in bt)
    svd_family = method not in ("fedavg", "hetlora", "ffa", "flora")
    if quantized and svd_family and backend in ("factored", "kernel"):
        # quantized collective: psum the raw int8/bf16 payload blocks plus
        # a tiny f32 per-column weight vector; dequantize AFTER the
        # reduction (DESIGN.md §12)
        return _sharded_partial_quantized(
            group_bs, group_as, group_w, r_max=r_max, axis=axis,
            axes=axes, axis_sizes=axis_sizes)
    if quantized:
        # avg family / flora / dense backend consume full-precision stacks
        # before their reduction -- dequantize locally (no collective-byte
        # saving on these paths; documented in DESIGN.md §12)
        group_bs = tuple(tuple(_dq(b) for b in bt) for bt in group_bs)
        group_as = tuple(tuple(_dq(a) for a in at) for at in group_as)
    bs = jnp.concatenate([_pad_rank(jnp.stack(bt, axis=1), r_max, -1)
                          for bt in group_bs])        # (m_loc, P, ..., d, r)
    as_ = jnp.concatenate([_pad_rank(jnp.stack(at, axis=1), r_max, -2)
                           for at in group_as])       # (m_loc, P, ..., r, n)
    w = jnp.concatenate(group_w)
    if method in ("fedavg", "hetlora", "ffa"):
        wc = w.astype(bs.dtype)
        a_g = jax.lax.psum(weighted_avg(as_, wc), axis)
        if method == "ffa":           # frozen factor: keep the global value
            return gb, a_g
        return jax.lax.psum(weighted_avg(bs, wc), axis), a_g
    if method == "flora":
        b_g, a_g, dw = _flora_delta(bs, as_, w)
        return b_g, a_g, jax.lax.psum(dw, axis)
    # SVD family: w is the (m_loc, r_max) omega matrix. Both low-rank
    # backends reduce the zero-scattered (d+n, R) stack -- the factored
    # backend builds its shard-local block with jnp, the kernel backend
    # with the layered Pallas stack grid over the shard's RESIDENT clients
    # only (DESIGN.md §4.3); the collective stays ONE psum per bucket.
    if backend in ("factored", "kernel"):
        if backend == "kernel":
            from repro.kernels import ops as kernel_ops
            u_loc, v_loc = kernel_ops.factored_stack_lead(bs, as_, w)
        else:
            u_loc, v_loc = factored_stack_batched(bs, as_, w)
        width = u_loc.shape[-1]
        shard_idx = jnp.int32(0)        # flat shard index over the axes
        n_shards = 1
        for a, size in zip(axes, axis_sizes):
            shard_idx = shard_idx * size + jax.lax.axis_index(a)
            n_shards *= size
        off = shard_idx * width
        u_full = jnp.zeros(u_loc.shape[:-1] + (n_shards * width,),
                           u_loc.dtype)
        v_full = jnp.zeros(v_loc.shape[:-2] + (n_shards * width,)
                           + v_loc.shape[-1:], v_loc.dtype)
        u_full = jax.lax.dynamic_update_slice_in_dim(u_full, u_loc, off,
                                                     axis=-1)
        v_full = jax.lax.dynamic_update_slice_in_dim(v_full, v_loc, off,
                                                     axis=-2)
        return jax.lax.psum(u_full, axis), jax.lax.psum(v_full, axis)
    # dense: the paper-faithful (..., d, n) all-reduce
    dw = jnp.einsum("m...dr,mr,m...rn->...dn", bs.astype(jnp.float32),
                    w.astype(jnp.float32), as_.astype(jnp.float32))
    return jax.lax.psum(dw, axis)


_SHARDED_FN_CACHE: Dict[tuple, "object"] = {}


def sharded_grouped_fn(mesh, r_max: int, backend: str, method: str,
                       axes: Tuple[str, ...] = ("data",)):
    """The jitted sharded-bucket pipeline for one (mesh, method, backend).

    Signature: fn(group_bs, group_as, group_w, global_bs, global_as,
    fallback) -> (b_g, a_g, sigma|None, merge_delta|None), mirroring
    ``_grouped_core`` but with every per-group array sharded over the
    mesh axes in ``axes`` on its leading client dimension (the live
    engine's 1-D FL mesh uses ``("data",)``; the multi-pod dry run shards
    over ``("pod", "data")``). Cached per key so repeated rounds reuse one
    compilation; also the lowering target of ``launch/fl_dryrun.py`` (the
    dry-run and the live engine share this exact program).
    """
    key = (mesh, r_max, backend, method, tuple(axes))
    if key in _SHARDED_FN_CACHE:
        return _SHARDED_FN_CACHE[key]
    from jax.sharding import PartitionSpec as P
    axes = tuple(axes)
    axis_sizes = tuple(mesh.shape[a] for a in axes)
    partial_fn = functools.partial(
        _sharded_partial, r_max=r_max, backend=backend, method=method,
        axes=axes, axis_sizes=axis_sizes)

    def fn(group_bs, group_as, group_w, global_bs, global_as, fallback):
        from repro.sharding.specs import client_spec
        check_fallback_globals(fallback, global_bs, global_as)
        gb = None if global_bs is None else jnp.stack(global_bs)
        ga = None if global_as is None else jnp.stack(global_as)
        cl = client_spec(axes)
        red = jax.shard_map(partial_fn, mesh=mesh,
                            in_specs=(cl, cl, cl, P(), P()),
                            out_specs=P(), check_vma=False)(
            group_bs, group_as, group_w, gb, ga)
        if method in ("fedavg", "hetlora", "ffa"):
            b_g, a_g = red
            return b_g, a_g, None, None
        if method == "flora":
            b_g, a_g, dw = red
            return b_g, a_g, None, dw
        if backend in ("factored", "kernel"):
            u_c, v_c = red
            if fallback is not None:
                # appended exactly once, AFTER the cross-shard reduction
                u_c, v_c = factored_append_fallback(u_c, v_c, gb, ga,
                                                    fallback)
            if backend == "kernel":
                # (R, R) Gram cores of the reduced, replicated stack via
                # the Pallas grids, then the Gram-core realloc -- the same
                # math as the single-host kernel path (DESIGN.md §4.3).
                # A Mosaic kernel cannot be partitioned by XLA, so every
                # device runs the grids on its own replicated copy.
                from repro.kernels import ops as kernel_ops
                g_u, g_v = jax.shard_map(
                    kernel_ops.factored_gram_lead, mesh=mesh,
                    in_specs=(P(), P()), out_specs=(P(), P()),
                    check_vma=False)(u_c, v_c)
                b_g, a_g, sigma = _realloc_gram_lead(u_c, v_c, g_u, g_v,
                                                     r_max)
            else:
                b_g, a_g, sigma = _realloc_factored_lead(u_c, v_c, r_max)
        else:
            dw = red
            if fallback is not None:
                dw = dw + dense_fallback_term(gb, ga, fallback)
            b_g, a_g, sigma = _realloc_dense_lead(dw, r_max)
        return b_g, a_g, sigma, None

    jitted = jax.jit(fn)
    _SHARDED_FN_CACHE[key] = jitted
    return jitted


@dataclass
class Aggregator:
    """Aggregates a round of client adapter uploads, layer by layer."""

    method: str
    rank_levels: Sequence[int]
    backend: str = "factored"
    # raFLoRA partial variants (Fig. 5a): apply effective-contributor
    # weighting only up to this boundary; higher partitions use FlexLoRA
    # weights. None = full raFLoRA.
    partial_up_to: Optional[int] = None

    def __post_init__(self):
        assert self.method in METHODS, self.method

    def aggregate_layer(self, factors, ranks, n_k, global_b=None,
                        global_a=None) -> AggregationResult:
        """factors: [(B_k (d, r_k), A_k (r_k, n))] for one adapter layer."""
        r_max = max(self.rank_levels)
        bs, as_ = pad_stack(factors, r_max)
        if self.method == "fedavg":
            return aggregate_fedavg(bs, as_, ranks, n_k)
        if self.method == "hetlora":
            return aggregate_hetlora(bs, as_, ranks, n_k)
        if self.method == "ffa":
            return aggregate_ffa(bs, as_, ranks, n_k, global_b=global_b)
        if self.method == "flora":
            return aggregate_flora(bs, as_, ranks, n_k)
        if self.method == "flexlora":
            return aggregate_flexlora(bs, as_, ranks, n_k,
                                      backend=self.backend)
        # raflora (optionally partial)
        if self.partial_up_to is None:
            return aggregate_raflora(
                bs, as_, ranks, n_k, rank_levels=self.rank_levels,
                global_b=global_b, global_a=global_a, backend=self.backend)
        return self._aggregate_partial(bs, as_, ranks, n_k, global_b, global_a)

    def _aggregate_partial(self, bs, as_, ranks, n_k, global_b, global_a
                           ) -> AggregationResult:
        """raFLoRA-a/b/c variants: rank-aware weights for partitions up to
        ``partial_up_to``; FlexLoRA weights above (Fig. 5a)."""
        omega, fallback = self._svd_weights(ranks, n_k)
        return _weighted_svd(bs, as_, jnp.asarray(omega), global_b, global_a,
                             None if fallback is None
                             else jnp.asarray(fallback),
                             max(self.rank_levels), self.backend)

    def _svd_weights(self, ranks, n_k):
        """Per-round (omega, fallback) numpy weights for the SVD-realloc
        family: flexlora, raflora, and the partial raFLoRA variants."""
        r_max = max(self.rank_levels)
        if self.method == "flexlora":
            return parts.omega_flexlora(ranks, n_k, r_max), None
        omega, fb = parts.omega_raflora(ranks, n_k, self.rank_levels)
        if self.partial_up_to is not None:
            om_flex = parts.omega_flexlora(ranks, n_k, r_max)
            cut = self.partial_up_to
            omega = np.concatenate([omega[:, :cut], om_flex[:, cut:]], axis=1)
            fb = np.concatenate([fb[:cut], np.zeros(r_max - cut)])
        return omega, (fb if fb.any() else None)

    def _weight_args(self, ranks, n_k):
        """(warg, fallback) inputs for ``_dispatch_stacked``.

        Returned as NUMPY: the jitted bucket pipelines transfer them at
        dispatch. Eager ``jnp.asarray`` here would synchronize with
        in-flight device work on the CPU client and stall the async round
        engine's dispatch pipeline."""
        if self.method == "fedavg":
            ranks_arr = np.asarray(ranks)
            assert (ranks_arr == ranks_arr[0]).all(), \
                "fedavg requires homogeneous ranks"
        if self.method in ("fedavg", "hetlora", "ffa", "flora"):
            return np.asarray(_weights(n_k), np.float32), None
        omega, fallback = self._svd_weights(ranks, n_k)
        return (np.asarray(omega),
                None if fallback is None else np.asarray(fallback))

    def aggregate_stack(self, bs, as_, ranks, n_k, global_b=None,
                        global_a=None) -> AggregationResult:
        """First-class batched API: aggregate a pre-stacked shape bucket.

        bs (M, *batch, d, r_max); as_ (M, *batch, r_max, n) with any batch
        axes (adapter bucket, scan-stacked layers, ...); global factors, if
        given, carry the same batch axes without the client axis. One jitted
        call per bucket. Returns an AggregationResult whose fields keep the
        batch axes.
        """
        warg, fallback = self._weight_args(ranks, n_k)
        b_g, a_g, sigma, dw = _stacked_core(
            bs, as_, warg, global_b, global_a, fallback,
            r_max=max(self.rank_levels), backend=self.backend,
            method=self.method)
        return AggregationResult(b_g, a_g, sigma, merge_delta=dw)

    def _present_weight_args(self, ranks, n_arr, present):
        """(warg, fallback) with only ``present`` clients participating.

        The event-driven engine aggregates PARTIAL cohorts (whoever has
        arrived when the trigger fires); absent clients must contribute
        exactly nothing AND stay out of every membership-derived quantity
        (raFLoRA effective-contributor sets, the Eq. 8 fallback mask, the
        fedavg homogeneity check) -- so weights are computed on the present
        subset only and scattered back with zeros, exactly the ghost-client
        rule of the sharded path. When every client is present this is
        bit-identical to the unfiltered path (same inputs, same arrays),
        which is what keeps the unit-latency event run equal to the
        cadence engine.
        """
        n_arr = np.where(np.asarray(present, dtype=bool), n_arr, 0.0)
        real = np.flatnonzero(n_arr > 0)
        assert real.size > 0, "an aggregation fired with no present client"
        warg_real, fallback = self._weight_args(
            [ranks[i] for i in real], n_arr[real])
        warg_np = np.asarray(warg_real)
        warg = np.zeros((len(n_arr),) + warg_np.shape[1:], warg_np.dtype)
        warg[real] = warg_np
        return warg, fallback

    def aggregate_grouped(self, group_bs, group_as, ranks, n_k,
                          global_bs=None, global_as=None,
                          staleness=None, gamma: float = 1.0,
                          present=None) -> AggregationResult:
        """Batched round engine hot path: aggregate a shape bucket straight
        from per-rank-group factor stacks.

        group_bs/group_as: sequences over rank groups of per-adapter factor
        sequences ((G, ..., d, r_group) / (G, ..., r_group, n)); ranks/n_k
        in concatenated group-client order; global_bs/global_as: per-adapter
        global factors. Bucket assembly (stack adapters, pad ranks,
        concatenate groups) AND aggregation run in one jitted dispatch.
        Returns an AggregationResult with a leading bucket-adapter axis.

        ``staleness``/``gamma``: the async round engine's staleness-
        discounted weighting (``staleness_discount``) -- per-client
        aggregation ages folded into the n_k-derived weights.

        ``present``: optional per-client participation mask (event-driven
        engine): absent clients get zero weight and are excluded from
        membership-derived weighting (``_present_weight_args``).
        """
        n_arr = staleness_discount(n_k, staleness, gamma)
        if present is not None:
            warg, fallback = self._present_weight_args(ranks, n_arr, present)
        else:
            warg, fallback = self._weight_args(ranks, n_arr)
        b_g, a_g, sigma, dw = _grouped_core(
            tuple(tuple(bt) for bt in group_bs),
            tuple(tuple(at) for at in group_as),
            warg,
            None if global_bs is None else tuple(global_bs),
            None if global_as is None else tuple(global_as),
            fallback, r_max=max(self.rank_levels), backend=self.backend,
            method=self.method)
        return AggregationResult(b_g, a_g, sigma, merge_delta=dw)

    def aggregate_grouped_sharded(self, group_bs, group_as, ranks, n_k,
                                  mesh, global_bs=None, global_as=None,
                                  staleness=None, gamma: float = 1.0,
                                  present=None) -> AggregationResult:
        """Sharded round engine hot path: ``aggregate_grouped`` with the
        client axis sharded over the mesh's ``data`` axis and every
        reduction backed by one ``jax.lax.psum`` (DESIGN.md §5).

        Inputs mirror ``aggregate_grouped`` except that each group's client
        axis must be padded to a multiple of the data-axis size and
        ``n_k[j] == 0`` marks a ghost (padding) client: weights and omega
        rows are computed from the REAL clients only and scattered with
        zeros at ghost positions, so ghosts contribute exactly nothing to
        any reduction AND leave the raFLoRA effective-contributor counts /
        Eq. 8 fallback untouched. ``staleness``/``gamma`` discount exactly
        as in ``aggregate_grouped`` (a ghost's discounted count is still 0);
        ``present`` additionally zeroes not-yet-arrived clients (the
        event-driven engine's partial cohorts ride the same ghost rule).
        """
        n_shards = mesh.shape["data"]
        sizes = [_leading(bt[0]) for bt in group_bs]
        assert all(g % n_shards == 0 for g in sizes), (sizes, n_shards)
        n_arr = staleness_discount(n_k, staleness, gamma)
        # ghosts and absent clients share ONE masking rule
        # (_present_weight_args): subset weights, scattered back with zeros
        warg, fallback = self._present_weight_args(
            ranks, n_arr,
            np.ones(len(n_arr), dtype=bool) if present is None else present)
        group_w = tuple(np.split(warg, np.cumsum(sizes)[:-1]))
        fn = sharded_grouped_fn(mesh, max(self.rank_levels), self.backend,
                                self.method)
        b_g, a_g, sigma, dw = fn(
            tuple(tuple(bt) for bt in group_bs),
            tuple(tuple(at) for at in group_as),
            group_w,
            None if global_bs is None else tuple(global_bs),
            None if global_as is None else tuple(global_as),
            fallback)
        return AggregationResult(b_g, a_g, sigma, merge_delta=dw)
