"""Fused LoRA-dense matmul Pallas kernels.

Single-adapter (training-side):  y = x @ W + s * (x @ A^T) @ B^T.
Multi-adapter  (serving-side):   y[m] = x[m] @ W
                                        + s_p * (x[m] @ A_p^T) @ B_p^T,
                                 p = page_of_block(m) -- each request row
                                 gathers its own (A, B, scale) from a paged
                                 adapter cache via scalar-prefetched page
                                 indices (DESIGN.md §11).

TPU rationale (DESIGN.md §4.3): the naive three-matmul composition streams
``x`` from HBM twice and materializes ``z = x A^T`` in HBM. Fusing lets one
pass over x feed both the MXU main matmul and the (tall-skinny) adapter
matmul; the rank-r bottleneck z lives entirely in a VMEM scratch
(bm x r <= 512 x 256 floats), and the adapter correction is applied to the
output tile while it is still resident. Block sizes default to MXU-aligned
(512, 512, 512).

Both wrappers follow the PR-4 pad-to-tile-and-slice convention: non-tile
extents are zero-padded up to the block grid and the result is sliced back,
so callers never need divisible shapes (zero rows/columns are inert in
every product). Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"
semantics) so the f32 accumulator and z scratch carry across the K loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rank_partition_agg import _pad_axis

_HI = jax.lax.Precision.HIGHEST
# (M, N, K) grid: output tiles are independent, K carries the accumulators
_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
# contract the last dims of both operands: x (m, k) . a (r, k) -> (m, r)
_NT = (((1,), (1,)), ((), ()))


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, z_ref, *,
            scale: float, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    w = w_ref[...].astype(jnp.float32)          # (bk, bn)
    a = a_ref[...].astype(jnp.float32)          # (r, bk)
    acc_ref[...] += jax.lax.dot(x, w, precision=_HI)
    z_ref[...] += jax.lax.dot_general(x, a, _NT, precision=_HI)

    @pl.when(k == k_steps - 1)
    def _finalize():
        b = b_ref[...].astype(jnp.float32)      # (bn, r)
        out = acc_ref[...] + scale * jax.lax.dot_general(
            z_ref[...], b, _NT, precision=_HI)
        o_ref[...] = out.astype(o_ref.dtype)


def lora_apply_pallas(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                      b: jnp.ndarray, scale: float = 1.0, *,
                      block_m: int = 512, block_n: int = 512,
                      block_k: int = 512,
                      interpret: bool = True) -> jnp.ndarray:
    """x (M, K); w (K, N); a (r, K); b (N, r). Returns (M, N) in x.dtype.

    Extents need NOT divide the block sizes: the wrapper zero-pads every
    dim (m/n/k to its tile, r to the 8-sublane tile) and slices the
    result back -- zero rows of x contribute nothing, zero columns of
    a/b are spectrum-inert (the omega-style padding convention).
    """
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    xp = _pad_axis(_pad_axis(x, 0, bm), 1, bk)
    wp = _pad_axis(_pad_axis(w, 0, bk), 1, bn)
    ap = _pad_axis(_pad_axis(a, 0, 8), 1, bk)
    bp = _pad_axis(_pad_axis(b, 0, bn), 1, 8)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    r = ap.shape[0]
    k_steps = kp // bk
    grid = (mp // bm, np_ // bn, k_steps)

    scratch_shapes = [pltpu.VMEM((bm, bn), jnp.float32),
                      pltpu.VMEM((bm, r), jnp.float32)]

    kernel = functools.partial(_kernel, scale=scale, k_steps=k_steps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((r, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((bn, r), lambda i, j, kk: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=scratch_shapes,
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(xp, wp, ap, bp)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# batched multi-adapter kernel (serving path, DESIGN.md §11)
# ---------------------------------------------------------------------------

def _batched_kernel(pages_ref, scales_ref, x_ref, w_ref, a_ref, b_ref, o_ref,
                    acc_ref, z_ref, *, k_steps: int):
    """One (row-block, n-block) output tile whose rows all share the page
    selected by the scalar-prefetched ``pages_ref`` -- the A/B BlockSpec
    index maps gather that page's factors straight from the cache, so the
    rank-r bottleneck z stays VMEM-resident per tile exactly as in the
    single-adapter kernel. The page scales are prefetched scalars too."""
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bk)
    w = w_ref[...].astype(jnp.float32)          # (bk, bn)
    a = a_ref[0].astype(jnp.float32)            # (r, bk): this block's page
    acc_ref[...] += jax.lax.dot(x, w, precision=_HI)
    z_ref[...] += jax.lax.dot_general(x, a, _NT, precision=_HI)

    @pl.when(k == k_steps - 1)
    def _finalize():
        b = b_ref[0].astype(jnp.float32)        # (bn, r)
        out = acc_ref[...] + scales_ref[pages_ref[i]] * jax.lax.dot_general(
            z_ref[...], b, _NT, precision=_HI)
        o_ref[...] = out.astype(o_ref.dtype)


def batched_lora_apply_pallas(x: jnp.ndarray, w: jnp.ndarray,
                              a_pages: jnp.ndarray, b_pages: jnp.ndarray,
                              scales: jnp.ndarray,
                              block_pages: jnp.ndarray, *,
                              block_m: int = 8, block_n: int = 512,
                              block_k: int = 512,
                              interpret: bool = True) -> jnp.ndarray:
    """Paged multi-adapter fused apply.

    x (M, K) with M a multiple of ``block_m`` and every ``block_m`` row
    block single-adapter by construction (the ops wrapper's SGMV grouping
    guarantees this); w (K, N); a_pages (P, r, K); b_pages (P, N, r);
    scales (P,) f32; block_pages (M / block_m,) int32 page index per row
    block. Returns (M, N) in x.dtype.

    n / k / r are padded to tiles here (pad-to-tile-and-slice); padded
    rank columns are zero (omega-style) and therefore inert.
    """
    m, k = x.shape
    _, n = w.shape
    p = a_pages.shape[0]
    bm = block_m
    assert m % bm == 0 and block_pages.shape == (m // bm,), \
        (m, bm, block_pages.shape)
    bn, bk = min(block_n, n), min(block_k, k)
    xp = _pad_axis(x, 1, bk)
    wp = _pad_axis(_pad_axis(w, 0, bk), 1, bn)
    ap = _pad_axis(_pad_axis(a_pages, 1, 8), 2, bk)
    bp = _pad_axis(_pad_axis(b_pages, 1, bn), 2, 8)
    kp = xp.shape[1]
    np_ = wp.shape[1]
    r = ap.shape[1]
    k_steps = kp // bk
    grid = (m // bm, np_ // bn, k_steps)

    kernel = functools.partial(_batched_kernel, k_steps=k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, pg, sc: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk, pg, sc: (kk, j)),
            pl.BlockSpec((1, r, bk), lambda i, j, kk, pg, sc: (pg[i], 0, kk)),
            pl.BlockSpec((1, bn, r), lambda i, j, kk, pg, sc: (pg[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, pg, sc: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, np_), x.dtype),
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(block_pages.astype(jnp.int32), scales.astype(jnp.float32), xp, wp,
      ap, bp)
    return out[:, :n]
