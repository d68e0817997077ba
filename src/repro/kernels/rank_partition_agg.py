"""Rank-partitioned aggregation Pallas kernels (the paper's Eq. 8 / Alg. 1
lines 6-10 as TPU contractions) -- dense-output AND fused-factored variants.

``rank_partition_agg_pallas`` computes dW = sum_m B_m diag(omega_m) A_m over
M clients, where ``omega`` encodes EITHER FlexLoRA's rank-agnostic weights
or raFLoRA's rank-partitioned effective-contributor weights (see
core/partitions.py) -- the aggregation-rule difference is data, not code.
The per-client diagonal scaling is folded into the B tile while it is
VMEM-resident, so each (d-tile, n-tile) output block is an M-step
accumulation of (bd x r) @ (r x bn) MXU matmuls with zero extra HBM traffic
for the weighting. Grid (d/bd, n/bn, M), client loop innermost
("arbitrary"), f32 accumulator in VMEM scratch. The empty-partition
fallback slice (Eq. 8 case 2) enters as client M+1 with omega = the
fallback indicator (handled by ops.py).

``rank_partition_agg_layered_pallas`` is the batched-round-engine variant:
the server stacks every same-shape adapter of the model into one
(L, M, d, r) bucket and the whole bucket lowers through a single grid with
the layer axis outermost -- one kernel launch per round per shape bucket
instead of one per adapter. omega is shared across layers (the aggregation
weights depend only on the round's client ranks/sample counts, not on the
adapter), so the weight tile stays resident across the layer loop.

The FUSED FACTORED path (DESIGN.md §4.3) never materializes dW at all.
The aggregate is always U_c @ V_c with U_c (d, M r) the sqrt(omega)-weighted
client B columns and V_c (M r, n) the matching A rows (DESIGN.md §4.2), so
the kernels below emit only O((d+n) R) HBM bytes:

* ``weighted_stack_{b,a}_layered_pallas`` build the sqrt-weighted column
  stacks U_c / V_c on-chip (grid (L, M, tiles): one weighted copy per
  client tile -- the omega diagonal is applied while the factor tile is
  VMEM-resident, exactly as in the dense kernel).
* ``gram_left_layered_pallas`` / ``gram_right_layered_pallas`` compute the
  (R x R) Gram cores G_u = U_c^T U_c and G_v = V_c V_c^T as d-/n-step MXU
  accumulations (grid (L, R/br, R/br, tiles), f32 scratch accumulator) --
  the O((d+n) R^2) heavy lifting of the factored SVD realloc, on the MXU,
  with the (R x R) eigen/SVD core left to ``core/svd.svd_realloc_gram``.

All kernels pad non-tile-divisible d / n extents to the block size with
zeros (zero rows/columns contribute nothing to any contraction; callers
slice the valid extent back), so odd adapter shapes (e.g. d=300, n=520)
lower instead of tripping divisibility asserts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _pad_axis(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    """Zero-pad ``axis`` up to a multiple of ``mult`` (pad-to-tile)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _acc_scratch(shape):
    return [pltpu.VMEM(shape, jnp.float32)]


def _omega_rows(omega: jnp.ndarray) -> jnp.ndarray:
    """(M, r) -> (M, 1, r): each client's weights as one (1, r) row block.

    A TPU block's last two dims must tile by (8, 128) or span the array;
    a (1, r) block of the (M, r) matrix does neither, a (1, 1, r) block of
    the (M, 1, r) view spans both."""
    return omega[:, None, :]


def _omega_cols(omega: jnp.ndarray) -> jnp.ndarray:
    """(M, r) -> (M, r, 1): the column view, for scaling the rows of A."""
    return omega[:, :, None]


# widest Gram core held as ONE (R, R) block when R does not tile by 128:
# its f32 accumulator, double-buffered output and (256, R) input blocks
# then stay near 5 MB of VMEM; wider cores must be padded to 128
GRAM_SINGLE_BLOCK_MAX = 512


def _gram_block(rr: int, preferred: int) -> int:
    """Core tile for an R-wide Gram: ``preferred`` (a multiple of 128)
    when it divides R, else the whole R -- a TPU block's lane dim must
    tile by 128 or span the array."""
    if rr % preferred == 0:
        return preferred
    assert rr <= GRAM_SINGLE_BLOCK_MAX, (rr, preferred)
    return rr


# ---------------------------------------------------------------------------
# dense-output kernels (materialize dW -- the paper-faithful contraction)
# ---------------------------------------------------------------------------

def _kernel(bs_ref, as_ref, om_ref, o_ref, acc_ref, *, m_steps: int):
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = bs_ref[0].astype(jnp.float32)            # (bd, r)
    a = as_ref[0].astype(jnp.float32)            # (r, bn)
    om = om_ref[0].astype(jnp.float32)           # (1, r)
    acc_ref[...] += jax.lax.dot(b * om, a, precision=_HI)

    @pl.when(m == m_steps - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rank_partition_agg_pallas(bs: jnp.ndarray, as_: jnp.ndarray,
                              omega: jnp.ndarray, *,
                              block_d: int = 256, block_n: int = 256,
                              interpret: bool = True) -> jnp.ndarray:
    """bs (M, d, r); as_ (M, r, n); omega (M, r) -> dW (d, n) f32."""
    m, d, r = bs.shape
    n = as_.shape[-1]
    bd, bn = min(block_d, d), min(block_n, n)
    bs = _pad_axis(bs, 1, bd)
    as_ = _pad_axis(as_, 2, bn)
    dp, np_ = bs.shape[1], as_.shape[2]
    grid = (dp // bd, np_ // bn, m)

    kernel = functools.partial(_kernel, m_steps=m)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd, r), lambda i, j, mm: (mm, i, 0)),
            pl.BlockSpec((1, r, bn), lambda i, j, mm: (mm, 0, j)),
            pl.BlockSpec((1, 1, r), lambda i, j, mm: (mm, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bd, bn), lambda i, j, mm: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, np_), jnp.float32),
        scratch_shapes=_acc_scratch((bd, bn)),
        interpret=interpret,
    )(bs, as_, _omega_rows(omega))
    return out[:d, :n]


def _layered_kernel(bs_ref, as_ref, om_ref, o_ref, acc_ref, *, m_steps: int):
    m = pl.program_id(3)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b = bs_ref[0, 0].astype(jnp.float32)         # (bd, r)
    a = as_ref[0, 0].astype(jnp.float32)         # (r, bn)
    om = om_ref[0].astype(jnp.float32)           # (1, r)
    acc_ref[...] += jax.lax.dot(b * om, a, precision=_HI)

    @pl.when(m == m_steps - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def rank_partition_agg_layered_pallas(bs: jnp.ndarray, as_: jnp.ndarray,
                                      omega: jnp.ndarray, *,
                                      block_d: int = 256, block_n: int = 256,
                                      interpret: bool = True) -> jnp.ndarray:
    """bs (L, M, d, r); as_ (L, M, r, n); omega (M, r) -> dW (L, d, n) f32.

    Layer axis outermost in the grid so each layer's accumulator lives its
    full client loop before the next layer starts (same scratch reuse
    pattern as the single-layer kernel)."""
    l, m, d, r = bs.shape
    n = as_.shape[-1]
    bd, bn = min(block_d, d), min(block_n, n)
    bs = _pad_axis(bs, 2, bd)
    as_ = _pad_axis(as_, 3, bn)
    dp, np_ = bs.shape[2], as_.shape[3]
    grid = (l, dp // bd, np_ // bn, m)

    kernel = functools.partial(_layered_kernel, m_steps=m)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bd, r), lambda ll, i, j, mm: (ll, mm, i, 0)),
            pl.BlockSpec((1, 1, r, bn), lambda ll, i, j, mm: (ll, mm, 0, j)),
            pl.BlockSpec((1, 1, r), lambda ll, i, j, mm: (mm, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bd, bn), lambda ll, i, j, mm: (ll, i, j)),
        out_shape=jax.ShapeDtypeStruct((l, dp, np_), jnp.float32),
        scratch_shapes=_acc_scratch((bd, bn)),
        interpret=interpret,
    )(bs, as_, _omega_rows(omega))
    return out[:, :d, :n]


# ---------------------------------------------------------------------------
# fused factored kernels: sqrt-weighted stacks + (R x R) Gram cores
# ---------------------------------------------------------------------------

def _stack_b_kernel(bs_ref, om_ref, u_ref):
    b = bs_ref[0, 0].astype(jnp.float32)                        # (bd, r)
    sq = jnp.sqrt(jnp.maximum(om_ref[0].astype(jnp.float32), 0.0))  # (1, r)
    u_ref[0, 0] = (b * sq).astype(u_ref.dtype)


def weighted_stack_b_layered_pallas(bs: jnp.ndarray, omega: jnp.ndarray, *,
                                    block_d: int = 256,
                                    interpret: bool = True) -> jnp.ndarray:
    """bs (L, M, d, r); omega (M, r) -> U_c (L, d, M*r) f32.

    Client m's weighted columns B_m diag(sqrt(omega_m)) land in column
    block m -- the left factor of DESIGN.md §4.2's U_c V_c form, built
    on-chip so dW is never needed. The grid writes (L, M, d, r) -- an
    (bd, r) block inside the M*r-wide stack would be a lane-unaligned
    block -- and one XLA transpose interleaves the clients into columns."""
    l, m, d, r = bs.shape
    bd = min(block_d, d)
    bs = _pad_axis(bs, 2, bd)
    dp = bs.shape[2]
    grid = (l, m, dp // bd)
    out = pl.pallas_call(
        _stack_b_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bd, r), lambda ll, mm, t: (ll, mm, t, 0)),
            pl.BlockSpec((1, 1, r), lambda ll, mm, t: (mm, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bd, r),
                               lambda ll, mm, t: (ll, mm, t, 0)),
        out_shape=jax.ShapeDtypeStruct((l, m, dp, r), jnp.float32),
        interpret=interpret,
    )(bs, _omega_rows(omega))
    return jnp.moveaxis(out[:, :, :d], 1, 2).reshape(l, d, m * r)


def _stack_a_kernel(as_ref, om_ref, v_ref):
    a = as_ref[0, 0].astype(jnp.float32)                        # (r, bn)
    sq = jnp.sqrt(jnp.maximum(om_ref[0].astype(jnp.float32), 0.0))  # (r, 1)
    v_ref[0] = (a * sq).astype(v_ref.dtype)


def weighted_stack_a_layered_pallas(as_: jnp.ndarray, omega: jnp.ndarray, *,
                                    block_n: int = 256,
                                    interpret: bool = True) -> jnp.ndarray:
    """as_ (L, M, r, n); omega (M, r) -> V_c (L, M*r, n) f32."""
    l, m, r, n = as_.shape
    bn = min(block_n, n)
    as_ = _pad_axis(as_, 3, bn)
    np_ = as_.shape[3]
    grid = (l, m, np_ // bn)
    out = pl.pallas_call(
        _stack_a_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, r, bn), lambda ll, mm, t: (ll, mm, 0, t)),
            pl.BlockSpec((1, r, 1), lambda ll, mm, t: (mm, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, bn), lambda ll, mm, t: (ll, mm, t)),
        out_shape=jax.ShapeDtypeStruct((l, m * r, np_), jnp.float32),
        interpret=interpret,
    )(as_, _omega_cols(omega))
    return out[..., :n]


def _gram_kernel(xi_ref, xj_ref, g_ref, acc_ref, *, t_steps: int,
                 contract_axis: int):
    i, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the Gram matrix is symmetric: accumulate only the upper-triangle
    # blocks (j >= i); the strictly-lower blocks finalize as zeros and the
    # wrapper mirrors them with one elementwise select
    @pl.when(j >= i)
    def _accumulate():
        xi = xi_ref[0].astype(jnp.float32)
        xj = xj_ref[0].astype(jnp.float32)
        dims = (((contract_axis,), (contract_axis,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(xi, xj, dims, precision=_HI)

    @pl.when(t == t_steps - 1)
    def _finalize():
        g_ref[0] = acc_ref[...].astype(g_ref.dtype)


def _mirror_lower(g: jnp.ndarray, br: int) -> jnp.ndarray:
    """Fill the zero strictly-lower-triangle BLOCKS of a block-upper Gram
    output with the transposed upper triangle (diagonal blocks were
    computed whole, so only whole blocks below the diagonal mirror)."""
    rr = g.shape[-1]
    rb = jnp.arange(rr) // br
    lower = rb[:, None] > rb[None, :]
    return jnp.where(lower, jnp.swapaxes(g, -1, -2), g)


def gram_left_layered_pallas(u_c: jnp.ndarray, *, block_d: int = 256,
                             block_r: int = 128,
                             interpret: bool = True) -> jnp.ndarray:
    """u_c (L, d, R) -> G_u = U_c^T U_c (L, R, R) f32.

    Grid (L, R/br, R/br, d/bd): each (br x br) core block accumulates a
    d-step sum of (bd x br)^T @ (bd x br) MXU products in f32 scratch --
    upper-triangle blocks only (the Gram matrix is symmetric; the lower
    half is mirrored with one elementwise select, halving the MXU work).
    R must tile by 8, and by 128 beyond ``GRAM_SINGLE_BLOCK_MAX`` (ops.py
    pads)."""
    l, d, rr = u_c.shape
    bd = min(block_d, d)
    br = _gram_block(rr, block_r)
    u_c = _pad_axis(u_c, 1, bd)
    dp = u_c.shape[1]
    grid = (l, rr // br, rr // br, dp // bd)
    kernel = functools.partial(_gram_kernel, t_steps=dp // bd,
                               contract_axis=0)
    g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bd, br), lambda ll, i, j, t: (ll, t, i)),
            pl.BlockSpec((1, bd, br), lambda ll, i, j, t: (ll, t, j)),
        ],
        out_specs=pl.BlockSpec((1, br, br), lambda ll, i, j, t: (ll, i, j)),
        out_shape=jax.ShapeDtypeStruct((l, rr, rr), jnp.float32),
        scratch_shapes=_acc_scratch((br, br)),
        interpret=interpret,
    )(u_c, u_c)
    return _mirror_lower(g, br)


def gram_right_layered_pallas(v_c: jnp.ndarray, *, block_n: int = 256,
                              block_r: int = 128,
                              interpret: bool = True) -> jnp.ndarray:
    """v_c (L, R, n) -> G_v = V_c V_c^T (L, R, R) f32."""
    l, rr, n = v_c.shape
    bn = min(block_n, n)
    br = _gram_block(rr, block_r)
    v_c = _pad_axis(v_c, 2, bn)
    np_ = v_c.shape[2]
    grid = (l, rr // br, rr // br, np_ // bn)
    kernel = functools.partial(_gram_kernel, t_steps=np_ // bn,
                               contract_axis=1)
    g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, bn), lambda ll, i, j, t: (ll, i, t)),
            pl.BlockSpec((1, br, bn), lambda ll, i, j, t: (ll, j, t)),
        ],
        out_specs=pl.BlockSpec((1, br, br), lambda ll, i, j, t: (ll, i, j)),
        out_shape=jax.ShapeDtypeStruct((l, rr, rr), jnp.float32),
        scratch_shapes=_acc_scratch((br, br)),
        interpret=interpret,
    )(v_c, v_c)
    return _mirror_lower(g, br)
