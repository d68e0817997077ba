"""Mamba-2 SSD chunked-scan Pallas kernel.

TPU rationale (DESIGN.md §4.3): the SSD *dual form* turns the selective-scan
recurrence into per-chunk matmuls -- exactly what the MXU wants -- plus a
tiny sequential inter-chunk state update. A GPU implementation leans on
warp-level associative scans; on TPU the right decomposition is:

  grid = (batch, heads, chunks), chunk axis innermost & sequential;
  per step:   cb   = C_q B_q^T             (Q x Q matmul, MXU)
              y    = (cb * Lmat) X + (C state^T) * decay   (MXU)
              state = chunk_decay * state + (decayed X)^T B  (MXU)

One grid step holds one head's chunk as plain 2-D tiles, so every block's
last two dims span the array (Q x P, Q x N, P x N) and every product is a
2-D MXU matmul. The (P x N) state lives in VMEM scratch across the chunk
loop; nothing recurrent ever round-trips HBM.

Layout expected by the kernel (pre-arranged head-major by ops.py):
  x   (B, H, nc, Q, P)        dt (B, H, nc, Q, 1)
  cum (B, H, nc, Q, 1) and its row view (B, H, nc, 1, Q): the inclusive
      in-chunk cumsum of dt * A (A = -exp(a_log) < 0)
  b,c (B, H, nc, Q, N)        -- groups already expanded to heads
  init_state (B, H, P, N)
Outputs: y (B, H, nc, Q, P) f32 WITHOUT the D-skip term (ops.py adds it);
final_state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))      # a (m, k) . b (n, k) -> (m, n)
_TN = (((0,), (0,)), ((), ()))      # a (k, m) . b (k, n) -> (m, n)


def _kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, init_ref,
            y_ref, final_ref, state_ref, *, nc: int, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)    # (P, N)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (Q, 1)
    cum = cum_ref[0, 0, 0]                        # (Q, 1)
    cum_row = cumr_ref[0, 0, 0]                   # (1, Q)
    bq = b_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    cq = c_ref[0, 0, 0].astype(jnp.float32)       # (Q, N)
    dtx = x * dt                                  # (Q, P)

    # intra-chunk: Lmat_ij = exp(cum_i - cum_j), i >= j (mask before exp --
    # see models/layers/ssd.py for the where-NaN rationale)
    idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.exp(jnp.where(idx >= jdx, cum - cum_row, -1e30))   # (Q, Q)
    cb = jax.lax.dot_general(cq, bq, _NT)                          # (Q, Q)
    y_intra = jnp.dot(cb * lmat, dtx)                              # (Q, P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                                         # (P, N)
    y_inter = jax.lax.dot_general(cq, state, _NT) * jnp.exp(cum)   # (Q, P)

    # state update
    # the chunk total cum[q-1], read by a masked lane reduction (a (1, 1)
    # slice at lane q-1 gets a layout Mosaic cannot broadcast from)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    last = jnp.sum(jnp.where(lane == q - 1, cum_row, 0.0), axis=1,
                   keepdims=True)                                  # (1, 1)
    decayed = dtx * jnp.exp(last - cum)                            # (Q, P)
    state_ref[...] = (state * jnp.exp(last)
                      + jax.lax.dot_general(decayed, bq, _TN))     # (P, N)

    y_ref[0, 0, 0] = y_intra + y_inter

    @pl.when(ci == nc - 1)
    def _final():
        final_ref[0, 0] = state_ref[...]


def ssd_scan_pallas(x, dt, cum, b, c, init_state, *,
                    interpret: bool = True):
    """Inputs pre-chunked, head-major & group-expanded (module docstring)."""
    B_, h, nc, q, p = x.shape
    n = b.shape[-1]
    grid = (B_, h, nc)
    chunk = lambda bi, hi, ci: (bi, hi, ci, 0, 0)
    state = lambda bi, hi, ci: (bi, hi, 0, 0)

    kernel = functools.partial(_kernel, nc=nc, q=q)
    y, final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), chunk),
            pl.BlockSpec((1, 1, 1, q, 1), chunk),
            pl.BlockSpec((1, 1, 1, q, 1), chunk),
            pl.BlockSpec((1, 1, 1, 1, q), chunk),
            pl.BlockSpec((1, 1, 1, q, n), chunk),
            pl.BlockSpec((1, 1, 1, q, n), chunk),
            pl.BlockSpec((1, 1, p, n), state),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), chunk),
            pl.BlockSpec((1, 1, p, n), state),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_, h, nc, q, p), jnp.float32),
            jax.ShapeDtypeStruct((B_, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, cum, jnp.swapaxes(cum, -1, -2), b, c, init_state)
    return y, final
