"""SVD-based rank reallocation (FlexLoRA Eq. 3-4) -- dense and factored.

``svd_realloc_dense`` is the paper-faithful path: materialize the d x n
aggregate, full SVD, truncate to r_max. O(d*n*min(d,n)) flops, O(d*n) memory.

``svd_realloc_factored`` is our beyond-paper path (DESIGN.md §4.2): the
aggregate is ALWAYS of the form U_c @ V_c with U_c (d, R), V_c (R, n),
R = sum_k r_k << min(d, n), because it is a weighted sum of client low-rank
products. QR-reduce both sides, SVD only the (R x R) core:

    U_c = Q_u R_u,  V_c^T = Q_v R_v
    U_c V_c = Q_u (R_u R_v^T) Q_v^T = Q_u (U_s S V_s^T) Q_v^T

=> singular values of the aggregate are exactly those of the small core.
O((d+n) R^2 + R^3) flops, O((d+n) R) memory -- for nemotron's FFN layer
(18432 x 73728, R ~ 168*... per-round stack) this is ~60x less compute and
~260x less memory than the dense path, with IDENTICAL results up to float
round-off (validated in tests/test_svd.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def check_fallback_globals(fallback, global_b, global_a) -> None:
    """A non-None Eq. 8 fallback REQUIRES both global factors.

    Silently dropping the fallback (the old behaviour when ``global_b`` was
    None) degrades raFLoRA's empty-partition case to FlexLoRA-style zeroing,
    so we fail loudly instead."""
    if fallback is None:
        return
    missing = [name for name, g in (("global_b", global_b),
                                    ("global_a", global_a)) if g is None]
    if missing:
        raise ValueError(
            "Eq. 8 empty-partition fallback is set but "
            f"{' and '.join(missing)} {'is' if len(missing) == 1 else 'are'}"
            " missing; pass the current global adapter factors so the "
            "uncovered rank partitions can retain their global slices")


def _whole(x: jnp.ndarray) -> jnp.ndarray:
    """Gather an operand whose matrix axes carry an explicit sharding:
    the decompositions (QR, SVD) take their matrix axes whole."""
    sharding = jax.typeof(x).sharding
    if any(ax is not None for ax in sharding.spec):
        return jax.sharding.reshard(
            x, sharding.update(spec=jax.sharding.PartitionSpec()))
    return x


def svd_realloc_dense(dw: jnp.ndarray, r_max: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paper-faithful: SVD the dense aggregate. Returns (B_g, A_g, sigma).

    B_g = U[:, :r] * sigma (d, r_max); A_g = V^T[:r] (r_max, n).
    """
    u, s, vt = jnp.linalg.svd(dw.astype(jnp.float32), full_matrices=False)
    u, s, vt = u[:, :r_max], s[:r_max], vt[:r_max]
    return u * s[None, :], vt, s


def svd_realloc_factored(u_c: jnp.ndarray, v_c: jnp.ndarray, r_max: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Factored: SVD of U_c @ V_c without materializing it.

    u_c (d, R); v_c (R, n). Returns (B_g (d, r_max), A_g (r_max, n), sigma).
    If R < r_max the trailing singular values are exactly zero and the
    factors are zero-padded (the aggregate has algebraic rank <= R).
    Stacks sharded on an explicit mesh axis are gathered first.
    """
    u_c = _whole(u_c.astype(jnp.float32))
    v_c = _whole(v_c.astype(jnp.float32))
    with jax.named_scope("agg.qr"):
        q_u, r_u = jnp.linalg.qr(u_c)        # (d, R), (R, R)
        q_v, r_v = jnp.linalg.qr(v_c.T)      # (n, R), (R, R)
    with jax.named_scope("agg.core_svd"):
        core = r_u @ r_v.T                    # (R, R)
        u_s, s, vt_s = jnp.linalg.svd(core, full_matrices=False)
        u_full = q_u @ u_s                    # (d, R)
        vt_full = vt_s @ q_v.T                # (R, n)
    r = u_c.shape[1]
    if r >= r_max:
        u_full, s, vt_full = u_full[:, :r_max], s[:r_max], vt_full[:r_max]
    else:
        pad = r_max - r
        u_full = jnp.pad(u_full, ((0, 0), (0, pad)))
        vt_full = jnp.pad(vt_full, ((0, pad), (0, 0)))
        s = jnp.pad(s, (0, pad))
    return u_full * s[None, :], vt_full, s


def svd_realloc_gram(u_c: jnp.ndarray, v_c: jnp.ndarray,
                     g_u: jnp.ndarray, g_v: jnp.ndarray, r_max: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Factored SVD realloc from precomputed (R, R) Gram cores
    (DESIGN.md §4.3 -- the kernel backend's route).

    u_c (d, R); v_c (R, n); g_u = U_c^T U_c; g_v = V_c V_c^T. The Pallas
    kernels compute the two Gram accumulations on the MXU; everything here
    is (R x R)-sized except the two final (d, R) @ (R, r_max) /
    (r_max, R) @ (R, n) projections:

        G_u = P_u diag(lam_u) P_u^T   =>   U_c = Q_u S_u P_u^T,
        G_v = P_v diag(lam_v) P_v^T   =>   V_c = P_v S_v Q_v^T,
        U_c V_c = Q_u [S_u (P_u^T P_v) S_v] Q_v^T,

    with S = sqrt(lam) and Q_u = U_c P_u S_u^+ orthonormal on the numerical
    range. SVD of the bracketed (R x R) core gives the spectrum; the
    truncated factors fold Q_u / Q_v back through ONE matmul per side.

    vs the QR route (``svd_realloc_factored``): no (d, R)/(n, R)
    orthogonalization at all -- but the Gram squaring halves the attainable
    precision (singular values below ~sqrt(eps) * sigma_max sit under the
    eigensolver's noise floor). Rank is cut at lam > R * eps * lam_max;
    zero-padded client columns land exactly there and contribute nothing.
    """
    u_c = u_c.astype(jnp.float32)
    v_c = v_c.astype(jnp.float32)
    eps = jnp.finfo(jnp.float32).eps
    rr = u_c.shape[-1]

    def _whiten(gram):
        lam, p = jnp.linalg.eigh(gram.astype(jnp.float32))
        lam = jnp.maximum(lam, 0.0)
        keep = lam > rr * eps * jnp.max(lam)
        s = jnp.where(keep, jnp.sqrt(lam), 0.0)
        inv = jnp.where(keep, 1.0 / jnp.where(keep, jnp.sqrt(lam), 1.0), 0.0)
        return s, inv, p

    with jax.named_scope("agg.core_svd"):
        s_u, inv_u, p_u = _whiten(g_u)
        s_v, inv_v, p_v = _whiten(g_v)
        core = (s_u[:, None] * (p_u.T @ p_v)) * s_v[None, :]  # (R, R)
        w1, s, w2t = jnp.linalg.svd(core, full_matrices=False)
        left = p_u @ (inv_u[:, None] * w1)                    # (R, R)
        right = (w2t * inv_v[None, :]) @ p_v.T                # (R, R)
    k = min(rr, r_max)
    b_g = (u_c @ left[:, :k]) * s[None, :k]                   # (d, k)
    a_g = right[:k] @ v_c                                     # (k, n)
    s = s[:k]
    if k < r_max:
        pad = r_max - k
        b_g = jnp.pad(b_g, ((0, 0), (0, pad)))
        a_g = jnp.pad(a_g, ((0, pad), (0, 0)))
        s = jnp.pad(s, (0, pad))
    return b_g, a_g, s


def factored_from_weighted(bs: jnp.ndarray, as_: jnp.ndarray,
                           omega: jnp.ndarray,
                           global_b: Optional[jnp.ndarray] = None,
                           global_a: Optional[jnp.ndarray] = None,
                           fallback: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Build the stacked factors of sum_k B_k diag(omega_k) A_k [+ fallback].

    bs (M, d, r_max); as_ (M, r_max, n); omega (M, r_max).
    The per-client diagonal is split sqrt-symmetrically between the two
    factors so the stack stays well-conditioned for QR.
    Returns u_c (d, M*r_max [+ r_max]), v_c (matching, n).
    """
    check_fallback_globals(fallback, global_b, global_a)
    m, d, r = bs.shape
    n = as_.shape[-1]
    sq = jnp.sqrt(jnp.maximum(omega, 0.0)).astype(jnp.float32)  # (M, r)
    u_parts = (bs.astype(jnp.float32) * sq[:, None, :])          # (M, d, r)
    v_parts = (as_.astype(jnp.float32) * sq[:, :, None])         # (M, r, n)
    u_c = jnp.moveaxis(u_parts, 0, 1).reshape(d, m * r)
    v_c = v_parts.reshape(m * r, n)
    if fallback is not None:
        fb = jnp.sqrt(jnp.maximum(fallback, 0.0)).astype(jnp.float32)
        u_c = jnp.concatenate([u_c, global_b.astype(jnp.float32) * fb[None, :]],
                              axis=1)
        v_c = jnp.concatenate([v_c, global_a.astype(jnp.float32) * fb[:, None]],
                              axis=0)
    return u_c, v_c


def factored_stack_batched(bs: jnp.ndarray, as_: jnp.ndarray,
                           omega: jnp.ndarray
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``factored_from_weighted``'s client stack for ANY number of batch
    axes between the client axis and the matrix axes.

    bs (M, *B, d, r); as_ (M, *B, r, n); omega (M, r). Returns
    u_c (*B, d, M*r), v_c (*B, M*r, n) -- the per-client sqrt-split diagonal
    weighting of the 3-D path, applied bucket-wide. The sharded round engine
    builds each mesh shard's LOCAL stack with this and all-reduces the
    result (DESIGN.md §5); no fallback handling here because the Eq. 8
    fallback columns must be appended exactly once, AFTER the cross-shard
    reduction.
    """
    m, r = bs.shape[0], bs.shape[-1]
    d, n = bs.shape[-2], as_.shape[-1]
    lead = bs.shape[1:-2]
    sq = jnp.sqrt(jnp.maximum(omega, 0.0)).astype(jnp.float32)   # (M, r)
    sq_b = sq.reshape((m,) + (1,) * len(lead) + (1, r))
    sq_a = sq.reshape((m,) + (1,) * len(lead) + (r, 1))
    u_parts = bs.astype(jnp.float32) * sq_b                      # (M, *B, d, r)
    v_parts = as_.astype(jnp.float32) * sq_a                     # (M, *B, r, n)
    u_c = jnp.moveaxis(u_parts, 0, -2).reshape(lead + (d, m * r))
    v_c = jnp.moveaxis(v_parts, 0, -3).reshape(lead + (m * r, n))
    return u_c, v_c


def factored_append_fallback(u_c: jnp.ndarray, v_c: jnp.ndarray,
                             global_b: jnp.ndarray, global_a: jnp.ndarray,
                             fallback: jnp.ndarray
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append the Eq. 8 empty-partition fallback columns to a (possibly
    batch-stacked) factored stack: u_c (*B, d, R), global_b (*B, d, r_max)."""
    fb = jnp.sqrt(jnp.maximum(fallback, 0.0)).astype(jnp.float32)
    u_c = jnp.concatenate(
        [u_c, global_b.astype(jnp.float32) * fb[None, :]], axis=-1)
    v_c = jnp.concatenate(
        [v_c, global_a.astype(jnp.float32) * fb[:, None]], axis=-2)
    return u_c, v_c


def dense_fallback_term(global_b: jnp.ndarray, global_a: jnp.ndarray,
                        fallback: jnp.ndarray) -> jnp.ndarray:
    """The Eq. 8 empty-partition term G_B diag(fallback) G_A, for global
    factors with any leading batch axes. The single implementation behind
    the dense path's fallback, eager AND sharded."""
    return jnp.einsum("...dr,r,...rn->...dn", global_b.astype(jnp.float32),
                      fallback.astype(jnp.float32),
                      global_a.astype(jnp.float32))


def dense_from_weighted(bs: jnp.ndarray, as_: jnp.ndarray, omega: jnp.ndarray,
                        global_b: Optional[jnp.ndarray] = None,
                        global_a: Optional[jnp.ndarray] = None,
                        fallback: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Materialize sum_k B_k diag(omega_k) A_k (+ global fallback slices)."""
    check_fallback_globals(fallback, global_b, global_a)
    dw = jnp.einsum("mdr,mr,mrn->dn", bs.astype(jnp.float32),
                    omega.astype(jnp.float32), as_.astype(jnp.float32))
    if fallback is not None:
        dw = dw + dense_fallback_term(global_b, global_a, fallback)
    return dw
