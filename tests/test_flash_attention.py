"""Flash-attention Pallas kernel: shape/mode sweeps vs the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


class TestFlashAttention:
    @pytest.mark.parametrize("B,L,H,KVH,D", [
        (2, 48, 4, 2, 16), (1, 64, 8, 1, 32), (2, 64, 6, 6, 16),
        (1, 128, 4, 4, 64),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep(self, B, L, H, KVH, D, causal):
        key = jax.random.PRNGKey(B * 100 + L + H)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, L, H, D))
        k = jax.random.normal(ks[1], (B, L, KVH, D))
        v = jax.random.normal(ks[2], (B, L, KVH, D))
        got = ops.flash_attention(q, k, v, causal=causal)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("window", [4, 16, 40])
    def test_sliding_window(self, window):
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 40, 4, 16))
        k = jax.random.normal(ks[1], (1, 40, 2, 16))
        v = jax.random.normal(ks[2], (1, 40, 2, 16))
        got = ops.flash_attention(q, k, v, causal=True, window=window)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_ragged_length_padding(self):
        key = jax.random.PRNGKey(9)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, 33, 4, 16))
        k = jax.random.normal(ks[1], (2, 33, 4, 16))
        v = jax.random.normal(ks[2], (2, 33, 4, 16))
        got = ops.flash_attention(q, k, v, causal=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)

    def test_matches_model_blockwise_path(self):
        """The kernel and the model's lax.scan blockwise attention agree
        (the tiled path: at 64 tokens the model itself takes one tile)."""
        from repro.models.layers.attention import _tiled_attention
        key = jax.random.PRNGKey(11)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, 64, 8, 32))
        k = jax.random.normal(ks[1], (2, 64, 2, 32))
        v = jax.random.normal(ks[2], (2, 64, 2, 32))
        got = ops.flash_attention(q, k, v, causal=True)
        want = _tiled_attention(q, k, v, causal=True, block_q=16,
                                block_kv=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)
