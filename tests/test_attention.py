"""Blockwise attention: equivalence with naive softmax attention across
masking modes on both the tiled and the one-tile path, the one-tile
path's equivalence with the tiled one (forward and gradient), the rule
that picks between them, plus decode-path invariants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline: deterministic fixed-grid shim
    from _hypothesis_compat import given, settings, strategies as st

from repro import tracing
from repro.models.layers.attention import (ONE_TILE_MAX, _one_tile_attention,
                                           _tiled_attention,
                                           blockwise_attention,
                                           decode_attention)

# the tiled path itself, and the dispatcher (one tile at these lengths)
PATHS = pytest.mark.parametrize("attend", [_tiled_attention,
                                           blockwise_attention],
                                ids=["tiled", "dispatch"])


def naive_attention(q, k, v, causal, window=0, q_offset=0, softcap=0.0):
    b, lq, h, d = q.shape
    _, lkv, kvh, _ = k.shape
    g = h // kvh
    qg = q.reshape(b, lq, kvh, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * d ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = q_offset + jnp.arange(lq)
    kpos = jnp.arange(lkv)
    mask = jnp.ones((lq, lkv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if not isinstance(window, int) or window:     # int or traced scalar
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, lq, h, d).astype(q.dtype)


def make_qkv(key, b=2, l=48, h=4, kvh=2, d=16):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, l, h, d))
    k = jax.random.normal(ks[1], (b, l, kvh, d))
    v = jax.random.normal(ks[2], (b, l, kvh, d))
    return q, k, v


class TestBlockwise:
    @PATHS
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("bq,bkv", [(16, 16), (48, 48), (8, 24)])
    def test_matches_naive(self, attend, causal, bq, bkv):
        q, k, v = make_qkv(jax.random.PRNGKey(0))
        got = attend(q, k, v, causal=causal, block_q=bq, block_kv=bkv)
        want = naive_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @PATHS
    @pytest.mark.parametrize("window", [1, 8, 17, 48])
    def test_sliding_window(self, attend, window):
        q, k, v = make_qkv(jax.random.PRNGKey(1))
        got = attend(q, k, v, causal=True, sliding_window=window,
                     block_q=16, block_kv=16)
        want = naive_attention(q, k, v, True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @PATHS
    def test_traced_window(self, attend):
        """Window as a traced scalar (hymba per-layer global selection)."""
        q, k, v = make_qkv(jax.random.PRNGKey(2))

        @jax.jit
        def f(q, k, v, w):
            return attend(q, k, v, causal=True, sliding_window=w,
                          block_q=16, block_kv=16)

        got = f(q, k, v, jnp.int32(8))
        want = naive_attention(q, k, v, True, window=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @PATHS
    @given(l=st.sampled_from([3, 7, 15, 16, 17, 31, 33, 47, 50]),
           seed=st.integers(0, 100))
    @settings(max_examples=25, deadline=None)
    def test_ragged_lengths(self, attend, l, seed):
        """Non-block-multiple sequence lengths pad correctly.

        Lengths are drawn from a fixed set spanning below/at/above block
        boundaries: every DISTINCT length compiles a fresh attention
        program, so a free-range integer strategy made this the single
        slowest cold-run test while adding no extra padding coverage."""
        key = jax.random.PRNGKey(seed)
        q, k, v = make_qkv(key, l=l)
        got = attend(q, k, v, causal=True, block_q=16, block_kv=16)
        want = naive_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)

    @PATHS
    def test_mqa_grouping(self, attend):
        q, k, v = make_qkv(jax.random.PRNGKey(3), h=8, kvh=1)
        got = attend(q, k, v, causal=True, block_q=16, block_kv=16)
        want = naive_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @PATHS
    def test_softcap(self, attend):
        q, k, v = make_qkv(jax.random.PRNGKey(4))
        got = attend(q, k, v, causal=True, softcap=5.0, block_q=16,
                     block_kv=16)
        assert bool(jnp.isfinite(got).all())
        want = naive_attention(q, k, v, True, softcap=5.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# (name, lq, lkv, keyword arguments of the attention call)
ONE_TILE_CASES = [
    ("causal-197", 197, 197, dict(causal=True)),
    ("bidir-197", 197, 197, dict(causal=False)),
    ("causal-256", 256, 256, dict(causal=True)),
    ("bidir-256", 256, 256, dict(causal=False)),
    ("window-int", 197, 197, dict(causal=True, sliding_window=40)),
    ("window-traced", 256, 256, dict(causal=True, sliding_window="traced")),
    ("offset", 61, 197, dict(causal=True, q_offset=136)),
    ("offset-window", 61, 256, dict(causal=True, q_offset=195,
                                    sliding_window=70)),
    ("softcap", 197, 197, dict(causal=True, softcap=5.0)),
    ("bf16-scores", 197, 197, dict(causal=True, bf16_scores=True)),
    ("bf16", 256, 256, dict(causal=True)),
    ("bf16-bidir", 197, 197, dict(causal=False)),
]


class TestOneTile:
    """The one-tile path against the tiled path (blocks of 64, so 197
    pads) and naive attention, forward and ``jax.grad``, GQA 8 over 2."""

    @pytest.mark.parametrize("name,lq,lkv,kw", ONE_TILE_CASES,
                             ids=[c[0] for c in ONE_TILE_CASES])
    def test_matches_tiled_and_naive(self, name, lq, lkv, kw):
        dtype = jnp.bfloat16 if name.startswith("bf16") else jnp.float32
        ks = jax.random.split(jax.random.PRNGKey(lq + lkv), 4)
        q = jax.random.normal(ks[0], (2, lq, 8, 16)).astype(dtype)
        k = jax.random.normal(ks[1], (2, lkv, 2, 16)).astype(dtype)
        v = jax.random.normal(ks[2], (2, lkv, 2, 16)).astype(dtype)
        ct = jax.random.normal(ks[3], (2, lq, 8, 16))
        traced = kw.get("sliding_window") == "traced"

        def loss(attend, q, k, v, w):
            # a traced window is a jit argument, an int stays static
            out = attend(q, k, v, **(dict(kw, sliding_window=w) if traced
                                     else kw))
            return (out.astype(jnp.float32) * ct).sum(), out

        def run(attend):
            f = jax.jit(jax.value_and_grad(
                functools.partial(loss, attend), argnums=(0, 1, 2),
                has_aux=True))
            (_, out), grads = f(q, k, v, jnp.int32(50))
            return [np.asarray(x, np.float32) for x in (out, *grads)]

        tiled = functools.partial(_tiled_attention, block_q=64, block_kv=64)

        def naive(q, k, v, *, causal, sliding_window=0, q_offset=0,
                  softcap=0.0, bf16_scores=False):
            return naive_attention(q, k, v, causal, window=sliding_window,
                                   q_offset=q_offset, softcap=softcap)

        with jax.default_matmul_precision("highest"):
            got, want, exact = run(_one_tile_attention), run(tiled), \
                run(naive)
        # bf16 rounds p (and the output) at different points on each path
        tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
        for a, b, c in zip(got, want, exact):
            np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
            np.testing.assert_allclose(a, c, atol=tol, rtol=tol)
        assert got[0].dtype == np.float32 and all(
            np.isfinite(x).all() for x in got)


def _loops(fn, *args):
    """Primitive names of ``scan``/``while`` in fn's jaxpr, and the
    ``attn.*`` counts its trace recorded."""
    before = tracing.counters()
    text = str(jax.make_jaxpr(fn)(*args))
    after = tracing.counters()
    counts = {key: after.get(key, 0) - before.get(key, 0)
              for key in ("attn.one_tile", "attn.tiled")}
    return [p for p in ("scan[", "while[") if p in text], counts


class TestDispatch:
    @pytest.mark.parametrize("l", [197, 256, ONE_TILE_MAX])
    @pytest.mark.parametrize("block", [64, 32])
    def test_short_sequences_take_one_tile(self, l, block):
        q, k, v = make_qkv(jax.random.PRNGKey(7), b=1, l=l, h=4, kvh=2,
                           d=8)
        loops, counts = _loops(functools.partial(
            blockwise_attention, causal=True, block_q=block,
            block_kv=block), q, k, v)
        assert loops == []
        assert counts == {"attn.one_tile": 1, "attn.tiled": 0}

    @pytest.mark.parametrize("lq,lkv", [(520, 520), (1, 520), (520, 64)])
    def test_longer_sequences_stay_tiled(self, lq, lkv):
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (1, lq, 4, 8))
        k = jax.random.normal(ks[1], (1, lkv, 2, 8))
        v = jax.random.normal(ks[2], (1, lkv, 2, 8))
        loops, counts = _loops(functools.partial(
            blockwise_attention, causal=False, block_q=64, block_kv=64),
            q, k, v)
        assert "scan[" in loops
        assert counts == {"attn.one_tile": 0, "attn.tiled": 1}

    def test_vit_client_step_traces_one_tile(self):
        """A ViT-shaped client step (197 tokens, blocks of 64, remat, as
        build_experiment sets them) takes the one-tile path."""
        from repro.configs.base import LoRAConfig
        from repro.federation.experiment import fedvit_config
        from repro.models.transformer import Model
        model = Model(fedvit_config(d_model=16, num_layers=1, num_classes=4,
                                    patches=197),
                      LoRAConfig(rank_levels=(4,), rank_probs=(1.0,)),
                      remat=True, block_q=64, block_kv=64)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"embeds": jnp.zeros((2, 197, 16)),
                 "targets": jnp.zeros((2, 197), jnp.int32)}
        _, counts = _loops(jax.grad(
            lambda p: model.train_loss(p, batch)[0]), params)
        assert counts["attn.one_tile"] >= 1 and counts["attn.tiled"] == 0


class TestDecode:
    def test_matches_last_row_of_full(self):
        key = jax.random.PRNGKey(5)
        q, k, v = make_qkv(key, l=20)
        full = naive_attention(q, k, v, True)
        got = decode_attention(q[:, -1:], k, v, cache_len=20)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(full[:, -1]), atol=1e-5)

    def test_cache_len_masks_tail(self):
        """Entries beyond cache_len must not influence the output."""
        key = jax.random.PRNGKey(6)
        q, k, v = make_qkv(key, l=32)
        out1 = decode_attention(q[:, -1:], k, v, cache_len=16)
        k_garbage = k.at[:, 16:].set(99.0)
        v_garbage = v.at[:, 16:].set(-99.0)
        out2 = decode_attention(q[:, -1:], k_garbage, v_garbage, cache_len=16)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)
