"""Flash-style causal attention Pallas kernel (TPU).

The fourth perf-critical hot-spot: every assigned architecture except
mamba2 spends most of its prefill/train flops here. The pure-XLA blockwise
path (models/layers/attention.py) streams KV blocks through lax.scan with
f32 online-softmax state in HLO; on TPU each scan step round-trips its
block through HBM and (under TP) the f32 boundary values inflate collective
traffic (measured in EXPERIMENTS.md §Perf C). The kernel keeps the running
max / denominator / accumulator strictly in VMEM scratch.

Layout: grid (batch*kv_heads, q_blocks, kv_blocks); kv innermost
("arbitrary") so the online-softmax state carries in scratch; q/k/v blocks
are MXU-aligned; GQA handled by folding the group dim into the q rows
(q block (G*bq, D) vs kv block (bk, D)).

Masking supports full-causal and sliding-window (static window) -- the
same modes the model uses. Oracle: ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: int, block_q: int, block_kv: int,
            n_kv: int, kv_len: int, groups: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)       # (G*bq, D)
    k = k_ref[0, 0].astype(jnp.float32)       # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)       # (bk, D)
    d = q.shape[-1]
    s = jax.lax.dot(q, k.T, precision=jax.lax.Precision.HIGHEST)
    s = s * (d ** -0.5)                        # (G*bq, bk)

    # absolute positions: q rows are G groups x bq positions
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    q_pos = qi * block_q + row % block_q
    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kv_pos < kv_len
    if causal:
        mask &= kv_pos <= q_pos
    if window > 0:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
        p, v, precision=jax.lax.Precision.HIGHEST)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_kv: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q (B, Lq, H, D); k, v (B, Lkv, KVH, D) -> (B, Lq, H, D).

    Lq/Lkv padded to block multiples by the ops wrapper; H = G * KVH.
    """
    b, lq, h, d = q.shape
    _, lkv, kvh, _ = k.shape
    g = h // kvh
    assert lq % block_q == 0 and lkv % block_kv == 0
    n_q = lq // block_q
    n_kv = lkv // block_kv

    # (B, Lq, KVH, G, D) -> (B*KVH, n_q, G*bq, D)
    qg = q.reshape(b, n_q, block_q, kvh, g, d)
    qg = qg.transpose(0, 3, 1, 4, 2, 5).reshape(b * kvh, n_q,
                                                g * block_q, d)
    kb = k.reshape(b, n_kv, block_kv, kvh, d).transpose(0, 3, 1, 2, 4)
    kb = kb.reshape(b * kvh, n_kv, block_kv, d)
    vb = v.reshape(b, n_kv, block_kv, kvh, d).transpose(0, 3, 1, 2, 4)
    vb = vb.reshape(b * kvh, n_kv, block_kv, d)

    grid = (b * kvh, n_q, n_kv)
    rows = g * block_q
    scratch = [pltpu.VMEM((rows, d), jnp.float32),
               pltpu.VMEM((rows,), jnp.float32),
               pltpu.VMEM((rows,), jnp.float32)]
    kernel = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, n_kv=n_kv, kv_len=lkv, groups=g)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda bh, qi, ki: (bh, qi, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, qi, ki: (bh, ki, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, qi, ki: (bh, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bh, qi, ki: (bh, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, n_q, rows, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qg, kb, vb)
    # (B*KVH, n_q, G*bq, D) -> (B, Lq, H, D)
    out = out.reshape(b, kvh, n_q, g, block_q, d)
    out = out.transpose(0, 2, 4, 1, 3, 5).reshape(b, lq, h, d)
    return out
