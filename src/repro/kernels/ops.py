"""jit'd public wrappers for the Pallas kernels.

Each op pads to hardware-friendly shapes, dispatches to the kernel (interpret
mode off the TPU -- the kernel body runs in Python for correctness
validation; compiled Mosaic on a TPU backend, never interpreted there), and
slices back. Oracles in ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.svd import check_fallback_globals
from repro.kernels.lora_apply import (batched_lora_apply_pallas,
                                      lora_apply_pallas)
from repro.kernels.rank_partition_agg import (GRAM_SINGLE_BLOCK_MAX,
                                              gram_left_layered_pallas,
                                              gram_right_layered_pallas,
                                              rank_partition_agg_layered_pallas,
                                              rank_partition_agg_pallas,
                                              weighted_stack_a_layered_pallas,
                                              weighted_stack_b_layered_pallas)
from repro.kernels.ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    """Interpret the kernels unless the backend is a TPU. Asked when an op
    TRACES, never at import: importing this module must not start a
    backend (that would take the chip), and one process may trace for
    more than one backend."""
    return jax.default_backend() != "tpu"


# pad-to-multiple: the ONE zero-pad helper, shared with the kernel grids
from repro.kernels.rank_partition_agg import _pad_axis as _pad_to


def _tile_block(padded: int, preferred: int = 256, lane: int = 128) -> int:
    """Largest tile <= preferred that divides the (lane-padded) dim --
    e.g. a 384-padded dim tiles at 128, not the non-divisor 256."""
    return preferred if padded % preferred == 0 else lane


@functools.partial(jax.jit, static_argnames=("scale",))
def lora_apply(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
               b: jnp.ndarray, scale: float = 1.0) -> jnp.ndarray:
    """Fused y = x @ w + scale * (x @ a.T) @ b.T; x (..., K)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # pad every dim to the kernel's tiling granularity
    bm = 256 if m >= 256 else max(8, m)
    x2 = _pad_to(x2, 0, bm)
    xp = _pad_to(x2, 1, 128)
    wp = _pad_to(_pad_to(w, 0, 128), 1, 128)
    ap = _pad_to(_pad_to(a, 0, 8), 1, 128)
    bp = _pad_to(_pad_to(b, 0, 128), 1, 8)
    y = lora_apply_pallas(xp, wp, ap, bp, scale,
                          block_m=min(256, xp.shape[0]),
                          block_n=min(512, wp.shape[1]),
                          block_k=min(512, xp.shape[1]),
                          interpret=_interpret())
    return y[:m, :n].reshape(lead + (n,)).astype(x.dtype)


@jax.jit
def batched_lora_apply(x: jnp.ndarray, w: jnp.ndarray,
                       a_pages: jnp.ndarray, b_pages: jnp.ndarray,
                       scales: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Multi-adapter fused apply: row t of x (..., K) uses adapter page
    ``ids[t]`` from a_pages (P, r, K) / b_pages (P, N, r) / scales (P,).

    SGMV-style grouping (DESIGN.md §11): rows are sorted by page id and
    each group is padded to the ``bm`` row-block boundary, so every kernel
    row block is single-adapter and the paged kernel gathers its (A, B,
    scale) once per tile via scalar-prefetched block->page indices. All
    shapes stay static under jit: the padded row count is bounded by
    ceil(M/bm) + P blocks, zero filler rows are inert, and the scatter
    back drops them.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    idf = ids.reshape(-1).astype(jnp.int32)
    m = x2.shape[0]
    p = a_pages.shape[0]
    bm = 8
    # group rows by page: sorted order, per-page extents, block-aligned
    # destination offsets (group g starts at a bm multiple)
    order = jnp.argsort(idf, stable=True)
    ids_sorted = idf[order]
    counts = jnp.bincount(idf, length=p)
    blocks_per = (counts + bm - 1) // bm
    padded = blocks_per * bm
    group_start = jnp.cumsum(padded) - padded
    cum_before = jnp.cumsum(counts) - counts
    dest = group_start[ids_sorted] + (jnp.arange(m) - cum_before[ids_sorted])
    m_pad = ((m + bm - 1) // bm + p) * bm           # static worst case
    x_g = jnp.zeros((m_pad, k), x.dtype).at[dest].set(x2[order])
    # page of each row block: invert the block-aligned group layout
    # (trailing unused blocks clip to page P-1; their rows are zero)
    bounds = jnp.cumsum(blocks_per)
    block_page = jnp.minimum(
        jnp.searchsorted(bounds, jnp.arange(m_pad // bm), side="right"),
        p - 1).astype(jnp.int32)
    # pad every dim to the kernel's tiling granularity (as in lora_apply)
    xp = _pad_to(x_g, 1, 128)
    wp = _pad_to(_pad_to(w, 0, 128), 1, 128)
    ap = _pad_to(_pad_to(a_pages, 1, 8), 2, 128)
    bp = _pad_to(_pad_to(b_pages, 1, 128), 2, 8)
    y_g = batched_lora_apply_pallas(
        xp, wp, ap, bp, scales, block_page,
        block_m=bm, block_n=min(512, wp.shape[1]),
        block_k=min(512, xp.shape[1]), interpret=_interpret())
    y2 = jnp.zeros((m, n), x.dtype).at[order].set(y_g[dest, :n])
    return y2.reshape(lead + (n,))


@jax.jit
def rank_partition_agg(bs: jnp.ndarray, as_: jnp.ndarray, omega: jnp.ndarray,
                       global_b: Optional[jnp.ndarray] = None,
                       global_a: Optional[jnp.ndarray] = None,
                       fallback: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """dW = sum_m B_m diag(omega_m) A_m (+ fallback global slices).

    bs (M, d, r); as_ (M, r, n); omega (M, r); optional global factors enter
    as one extra "client" carrying the empty-partition fallback (Eq. 8).
    """
    bs, as_, omega = _append_fallback_client(bs, as_, omega, global_b,
                                             global_a, fallback,
                                             layer_axes=0)
    # only r needs padding (to the 8-sublane tile); the kernel pads and
    # re-slices non-divisible d / n extents itself
    bsp = _pad_to(bs, 2, 8)
    asp = _pad_to(as_, 1, 8)
    omp = _pad_to(omega, 1, 8)
    return rank_partition_agg_pallas(
        bsp, asp, omp,
        block_d=_tile_block(bsp.shape[1]), block_n=_tile_block(asp.shape[2]),
        interpret=_interpret())


@jax.jit
def rank_partition_agg_layered(bs: jnp.ndarray, as_: jnp.ndarray,
                               omega: jnp.ndarray,
                               global_b: Optional[jnp.ndarray] = None,
                               global_a: Optional[jnp.ndarray] = None,
                               fallback: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Layer-batched dW: one kernel launch for a whole adapter bucket.

    bs (L, M, d, r); as_ (L, M, r, n); omega (M, r) shared across layers;
    optional global factors (L, d, r)/(L, r, n) enter as one extra "client"
    per layer carrying the empty-partition fallback (Eq. 8).
    Returns dW (L, d, n) f32.
    """
    bs, as_, omega = _append_fallback_client(bs, as_, omega, global_b,
                                             global_a, fallback,
                                             layer_axes=1)
    # only r needs padding (to the 8-sublane tile); the kernel pads and
    # re-slices non-divisible d / n extents itself
    bsp = _pad_to(bs, 3, 8)
    asp = _pad_to(as_, 2, 8)
    omp = _pad_to(omega, 1, 8)
    return rank_partition_agg_layered_pallas(
        bsp, asp, omp,
        block_d=_tile_block(bsp.shape[2]), block_n=_tile_block(asp.shape[3]),
        interpret=_interpret())


# -- fused factored aggregation (DESIGN.md §4.3): O((d+n)R) memory ----------
#
# The kernel backend's hot path: build the sqrt(omega)-weighted column
# stacks U_c / V_c and their (R x R) Gram cores with Pallas kernels, then
# SVD-realloc via core/svd.svd_realloc_gram -- dW (d, n) is NEVER formed.
# The Eq. 8 empty-partition fallback enters as one extra "client" whose
# omega row is the fallback indicator, exactly as on the dense kernel path.
# These helpers are plain traced functions (no own jit) so the aggregation
# pipelines can call them inside their jitted / shard_map'd bodies.

def _dequant(x):
    """Accept the compressed-transport layout (QuantFactor: int8/bf16
    payload + f32 per-column scales, DESIGN.md §12) at every factor-stack
    entry point. Duck-typed so kernels/ never imports repro.federation;
    plain f32 stacks pass through untouched. The payload->f32 multiply is
    elementwise staging the Pallas grids consume directly -- the grids
    themselves stay layout-agnostic."""
    if hasattr(x, "q") and hasattr(x, "scale"):
        return x.q.astype(jnp.float32) * x.scale
    return x


def _append_fallback_client(bs, as_, omega, global_b, global_a, fallback,
                            *, layer_axes: int):
    """Concatenate the global factors as client M+1 carrying ``fallback``.

    ``layer_axes`` leading axes precede the client axis (0 for (M, d, r),
    1 for (L, M, d, r)); the global factors carry those axes without the
    client axis."""
    check_fallback_globals(fallback, global_b, global_a)
    if fallback is None:
        return bs, as_, omega
    ax = layer_axes
    bs = jnp.concatenate(
        [bs, jnp.expand_dims(global_b, ax).astype(bs.dtype)], axis=ax)
    as_ = jnp.concatenate(
        [as_, jnp.expand_dims(global_a, ax).astype(as_.dtype)], axis=ax)
    omega = jnp.concatenate([omega, fallback[None].astype(omega.dtype)],
                            axis=0)
    return bs, as_, omega


def factored_stack_layered(bs: jnp.ndarray, as_: jnp.ndarray,
                           omega: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """bs (L, M, d, r); as_ (L, M, r, n); omega (M, r) ->
    U_c (L, d, M*r8), V_c (L, M*r8, n) f32 (r zero-padded to a multiple of
    8 -- zero columns are spectrum-inert and keep the R width tile-able;
    the stack grids pad and re-slice d / n themselves)."""
    bsp = _pad_to(bs, 3, 8)
    asp = _pad_to(as_, 2, 8)
    omp = _pad_to(omega, 1, 8)
    u_c = weighted_stack_b_layered_pallas(
        bsp, omp, block_d=_tile_block(bsp.shape[2]), interpret=_interpret())
    v_c = weighted_stack_a_layered_pallas(
        asp, omp, block_n=_tile_block(asp.shape[3]), interpret=_interpret())
    return u_c, v_c


def factored_gram_layered(u_c: jnp.ndarray, v_c: jnp.ndarray
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """u_c (L, d, R); v_c (L, R, n) -> Gram cores (L, R, R) x2. R is padded
    to 8 so the core tiles (to 128 past ``GRAM_SINGLE_BLOCK_MAX``, where the
    core no longer fits one block); callers slice back to the incoming
    width. Zero columns are spectrum-inert."""
    rr = u_c.shape[-1]
    mult = 8 if rr <= GRAM_SINGLE_BLOCK_MAX else 128
    up = _pad_to(u_c, 2, mult)
    vp = _pad_to(v_c, 1, mult)
    g_u = gram_left_layered_pallas(up, block_d=_tile_block(up.shape[1]),
                                   interpret=_interpret())
    g_v = gram_right_layered_pallas(vp, block_n=_tile_block(vp.shape[2]),
                                    interpret=_interpret())
    return g_u[:, :rr, :rr], g_v[:, :rr, :rr]


def factored_stack_lead(bs: jnp.ndarray, as_: jnp.ndarray,
                        omega: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                     jnp.ndarray]:
    """``svd.factored_stack_batched`` on the Pallas kernels, for factor
    stacks with ANY batch axes between the client and matrix axes.

    bs (M, *B, d, r); as_ (M, *B, r, n); omega (M, r). Returns
    u_c (*B, d, M*r8), v_c (*B, M*r8, n) -- the layout the sharded round
    engine zero-scatters and psums (DESIGN.md §5), built on-chip."""
    bs, as_ = _dequant(bs), _dequant(as_)
    m, r = bs.shape[0], bs.shape[-1]
    d, n = bs.shape[-2], as_.shape[-1]
    lead = bs.shape[1:-2]
    layers = 1
    for s in lead:
        layers *= s
    bs_l = jnp.moveaxis(bs.reshape(m, layers, d, r), 0, 1)
    as_l = jnp.moveaxis(as_.reshape(m, layers, r, n), 0, 1)
    u_c, v_c = factored_stack_layered(bs_l, as_l, omega)
    width = u_c.shape[-1]
    return (u_c.reshape(lead + (d, width)),
            v_c.reshape(lead + (width, n)))


def factored_gram_lead(u_c: jnp.ndarray, v_c: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``factored_gram_layered`` over ANY leading batch axes (or none)."""
    lead = u_c.shape[:-2]
    d, rr = u_c.shape[-2:]
    n = v_c.shape[-1]
    layers = 1
    for s in lead:
        layers *= s
    g_u, g_v = factored_gram_layered(u_c.reshape(layers, d, rr),
                                     v_c.reshape(layers, rr, n))
    return g_u.reshape(lead + (rr, rr)), g_v.reshape(lead + (rr, rr))


@jax.jit
def factored_stack_gram(bs: jnp.ndarray, as_: jnp.ndarray,
                        omega: jnp.ndarray,
                        global_b: Optional[jnp.ndarray] = None,
                        global_a: Optional[jnp.ndarray] = None,
                        fallback: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray, jnp.ndarray]:
    """The whole fused factored front half for ONE adapter: (u_c, v_c,
    g_u, g_v) for svd_realloc_gram.

    bs (M, d, r); as_ (M, r, n); omega (M, r); optional global factors
    enter as one extra "client" carrying the Eq. 8 fallback indicator.
    """
    bs, as_ = _dequant(bs), _dequant(as_)
    bs, as_, omega = _append_fallback_client(bs, as_, omega, global_b,
                                             global_a, fallback,
                                             layer_axes=0)
    u_c, v_c = factored_stack_layered(bs[None], as_[None], omega)
    g_u, g_v = factored_gram_layered(u_c, v_c)
    return u_c[0], v_c[0], g_u[0], g_v[0]


@jax.jit
def factored_stack_gram_layered(bs: jnp.ndarray, as_: jnp.ndarray,
                                omega: jnp.ndarray,
                                global_b: Optional[jnp.ndarray] = None,
                                global_a: Optional[jnp.ndarray] = None,
                                fallback: Optional[jnp.ndarray] = None
                                ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                           jnp.ndarray, jnp.ndarray]:
    """Layer-batched ``factored_stack_gram``: one kernel launch per shape
    bucket. bs (L, M, d, r); as_ (L, M, r, n); omega (M, r) shared across
    layers; global factors (L, d, r)/(L, r, n)."""
    bs, as_ = _dequant(bs), _dequant(as_)
    bs, as_, omega = _append_fallback_client(bs, as_, omega, global_b,
                                             global_a, fallback,
                                             layer_axes=1)
    u_c, v_c = factored_stack_layered(bs, as_, omega)
    g_u, g_v = factored_gram_layered(u_c, v_c)
    return u_c, v_c, g_u, g_v


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, d_skip: jnp.ndarray,
             chunk: int, init_state: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan. Shapes as in models/layers/ssd.ssd_scan_chunked."""
    B_, L, H, P = x.shape
    G, N = b.shape[-2:]
    chunk = min(chunk, L)
    assert L % chunk == 0
    nc = L // chunk
    reps = H // G

    def head_major(t):   # (B, L, H, F) -> (B, H, nc, chunk, F)
        return jnp.moveaxis(t.reshape(B_, nc, chunk, H, t.shape[-1]), 3, 1)

    dtf = dt.astype(jnp.float32)[..., None]                       # (B,L,H,1)
    a_neg = -jnp.exp(a_log.astype(jnp.float32))                   # (H,)
    cum = jnp.cumsum(head_major(dtf * a_neg[:, None]), axis=3)    # in-chunk
    init = (jnp.zeros((B_, H, P, N), jnp.float32)
            if init_state is None else init_state.astype(jnp.float32))
    y, final = ssd_scan_pallas(
        head_major(x), head_major(dtf), cum,
        head_major(jnp.repeat(b, reps, axis=2)),
        head_major(jnp.repeat(c, reps, axis=2)), init,
        interpret=_interpret())
    y = jnp.moveaxis(y, 1, 3).reshape(B_, L, H, P)
    skip = x.astype(jnp.float32) * d_skip.astype(jnp.float32)[:, None]
    return (y + skip).astype(x.dtype), final


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0) -> jnp.ndarray:
    """Fused flash attention; pads sequence lengths to block multiples."""
    from repro.kernels.flash_attention import flash_attention_pallas
    b, lq, h, d = q.shape
    lkv = k.shape[1]
    bq = min(128, max(8, lq))
    bk = min(128, max(8, lkv))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=bq, block_kv=bk,
                                 interpret=_interpret())
    return out[:, :lq]
