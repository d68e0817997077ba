"""Attention: blockwise (flash-style) softmax attention in pure JAX.

TPU adaptation notes (DESIGN.md §4): rather than materializing (Lq, Lkv)
score matrices -- which at prefill_32k would be terabytes -- we stream KV
blocks through an online-softmax ``lax.scan``, the standard TPU formulation
(compute lives in MXU matmuls; running max/denominator live in VREGs).
Sequences of at most ``ONE_TILE_MAX`` positions, whose scores fit
comfortably, run as one tile instead. The same code serves:

  * full causal attention          (train / prefill)
  * sliding-window causal          (long-context variants, hymba, llama4)
  * bidirectional                  (hubert, vit, roberta encoders)
  * single-token decode            (serve_step; q length 1 vs KV cache)

GQA/MQA is handled by grouping query heads over shared KV heads.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import tracing

NEG_INF = -1e30

# Sequences of at most this many query and key positions take one tile.
# Its f32 score tensor is b*h*lq*lkv*4 bytes: 5 vmapped clients x 32
# items x 12 heads at 197 tokens is 298 MB a layer (ViT rounds), 16 slots
# x 32 heads at 256 is 134 MB (Granite prefill), and 16 x 32 heads at 512
# is 537 MB. Longer sequences stream tiles to bound it; below the bound
# tiling only adds loop overhead, padding and quarter-filled MXU tiles.
ONE_TILE_MAX = 512


def _gqa_group(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """(B, L, H, D) -> (B, L, KVH, G, D) with G = H // KVH."""
    b, l, h, d = q.shape
    return q.reshape(b, l, num_kv_heads, h // num_kv_heads, d)


def _uses_window(sliding_window) -> bool:
    return sliding_window is not None and not (
        isinstance(sliding_window, int) and sliding_window == 0)


def _masked_scores(q_blk, k_blk, q_pos, kv_pos, *, scale, causal: bool,
                   sliding_window, softcap: float, bf16_scores: bool,
                   kv_len: Optional[int] = None):
    """f32 scores (B, KVH, G, q, k) of a q tile (B, KVH, G, q, D) against a
    kv tile (B, KVH, k, D), soft-capped and masked to ``NEG_INF``.

    ``q_pos``/``kv_pos`` are the tiles' absolute positions; ``kv_len``
    masks kv padding at and beyond it."""
    # inputs stay bf16 (collectives/copies move half the bytes); the MXU
    # accumulates in f32 via preferred_element_type. bf16_scores: emit the
    # dot in bf16 so its VJP dots are bf16 too -- an f32 dot here poisons
    # every backward collective upstream (§Perf; the Pallas kernel is the
    # lossless fix).
    if bf16_scores:
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk,
                       k_blk).astype(jnp.float32) * scale
    else:
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if _uses_window(sliding_window):
        mask &= kv_pos[None, :] > q_pos[:, None] - sliding_window
    if kv_len is not None:
        mask &= (kv_pos < kv_len)[None, :]
    return jnp.where(mask, s, NEG_INF)


def _one_tile_attention(q, k, v, *, causal: bool, sliding_window=0,
                        q_offset: int = 0, softcap: float = 0.0,
                        bf16_scores: bool = False) -> jnp.ndarray:
    """Attention over the whole sequence as one tile: one score product,
    one softmax, one PV product; no scan, no padding. Arguments and
    result as ``blockwise_attention``'s, less the block sizes."""
    b, lq, h, d = q.shape
    _, lkv, kvh, _ = k.shape
    qg = _gqa_group(q, kvh).transpose(0, 2, 3, 1, 4)   # (B, KVH, G, Lq, D)
    kt = k.transpose(0, 2, 1, 3)                       # (B, KVH, Lkv, D)
    vt = v.transpose(0, 2, 1, 3)
    s = _masked_scores(qg, kt, q_offset + jnp.arange(lq), jnp.arange(lkv),
                       scale=d ** -0.5, causal=causal,
                       sliding_window=sliding_window, softcap=softcap,
                       bf16_scores=bf16_scores)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    denom = p.sum(axis=-1)
    # p in the compute dtype for the MXU; f32 accumulator
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
    out = (out / jnp.maximum(denom[..., None], 1e-30)).astype(q.dtype)
    # (B, KVH, G, Lq, D) -> (B, Lq, H, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, lq, h, d)


def _tiled_attention(q, k, v, *, causal: bool, sliding_window=0,
                     q_offset: int = 0, block_q: int = 1024,
                     block_kv: int = 1024, softcap: float = 0.0,
                     bf16_scores: bool = False) -> jnp.ndarray:
    """Online-softmax attention streaming (block_q, block_kv) tiles through
    a nested ``lax.scan``; sequences are padded to block multiples."""
    b, lq, h, d = q.shape
    _, lkv, kvh, _ = k.shape
    scale = d ** -0.5

    block_q = min(block_q, lq)
    block_kv = min(block_kv, lkv)
    # pad to block multiples
    pad_q = (-lq) % block_q
    pad_kv = (-lkv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq = (lq + pad_q) // block_q
    nkv = (lkv + pad_kv) // block_kv

    qg = _gqa_group(q, kvh)                      # (B, Lq, KVH, G, D)
    g = qg.shape[3]
    qg = qg.reshape(b, nq, block_q, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, KVH, G, bq, D)
    kb = k.reshape(b, nkv, block_kv, kvh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nkv, block_kv, kvh, d).transpose(1, 0, 3, 2, 4)
    # (nkv, B, KVH, bkv, D)

    q_pos_base = jnp.arange(nq) * block_q        # per q block
    kv_pos_base = jnp.arange(nkv) * block_kv

    def q_block_body(_, qi):
        q_blk, q_idx = qi                        # (B, KVH, G, bq, D), scalar
        q_pos = q_offset + q_idx + jnp.arange(block_q)  # absolute positions

        def kv_block_body(carry, kvi):
            acc, m, denom = carry
            k_blk, v_blk, kv_idx = kvi
            s = _masked_scores(q_blk, k_blk, q_pos,
                               kv_idx + jnp.arange(block_kv), scale=scale,
                               causal=causal, sliding_window=sliding_window,
                               softcap=softcap, bf16_scores=bf16_scores,
                               kv_len=lkv if pad_kv else None)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            denom = denom * alpha + p.sum(axis=-1)
            # p in the compute dtype for the MXU; f32 accumulator
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((b, kvh, g, block_q, d), jnp.float32)
        m0 = jnp.full((b, kvh, g, block_q), NEG_INF, jnp.float32)
        d0 = jnp.zeros((b, kvh, g, block_q), jnp.float32)
        (acc, m, denom), _ = jax.lax.scan(
            kv_block_body, (acc0, m0, d0), (kb, vb, kv_pos_base))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, out = jax.lax.scan(q_block_body, None, (qg, q_pos_base))
    # out: (nq, B, KVH, G, bq, D) -> (B, Lq, H, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * block_q, h, d)
    return out[:, :lq]


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool, sliding_window=0,
                        q_offset: int = 0,
                        block_q: int = 1024, block_kv: int = 1024,
                        softcap: float = 0.0,
                        bf16_scores: bool = False) -> jnp.ndarray:
    """Softmax attention, as one tile when both lengths are at most
    ``ONE_TILE_MAX``, else online-softmax over (block_q, block_kv) tiles.

    q: (B, Lq, H, D); k, v: (B, Lkv, KVH, D). Returns (B, Lq, H, D).
    ``q_offset`` is the absolute position of q[0] (prefill continuation /
    decode). ``sliding_window``: 0/None = unlimited; may be a traced scalar
    (per-layer global-vs-window selection under lax.scan). Each trace
    counts the path it took (``attn.one_tile`` / ``attn.tiled``).
    """
    kw = dict(causal=causal, sliding_window=sliding_window,
              q_offset=q_offset, softcap=softcap, bf16_scores=bf16_scores)
    if max(q.shape[1], k.shape[1]) <= ONE_TILE_MAX:
        tracing.count("attn.one_tile")
        return _one_tile_attention(q, k, v, **kw)
    tracing.count("attn.tiled")
    return _tiled_attention(q, k, v, block_q=block_q, block_kv=block_kv,
                            **kw)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     *, softcap: float = 0.0) -> jnp.ndarray:
    """Single-token decode: q (B, 1, H, D) vs cache (B, S, KVH, D).

    ``cache_len`` (scalar or (B,)) masks cache positions >= len.
    """
    b, _, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    scale = d ** -0.5
    qg = _gqa_group(q, kvh)[:, 0]                # (B, KVH, G, D)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache.astype(qg.dtype),
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(jnp.asarray(cache_len), (-1, 1))  # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)
