"""Operation and byte counts against hand counts, and the peak table."""
import pytest

from harness import counts, env
from harness.peaks import peak

VIT = env.load("configs", "vit-base")
GRANITE = env.load("configs", "granite-3-8b-pp2")


def test_vit_base_parameters():
    # per layer: q/k/v/o 4 x 768^2 + 3 x 768 biases, MLP 2 x 768 x 3072,
    # two norm scales of 768
    layer = 4 * 768 * 768 + 3 * 768 + 2 * 768 * 3072 + 2 * 768
    assert counts.layer_params(VIT) == layer == 7_081_728
    assert counts.stack_params(VIT) == 84_980_736          # ~85 M


def test_granite_stage_parameters():
    # q, o 4096^2; k, v 4096 x 1024 (8 KV heads of 128); SwiGLU 3 x 4096 x
    # 12800; two norms
    layer = 2 * 4096 ** 2 + 2 * 4096 * 1024 + 3 * 4096 * 12800 + 2 * 4096
    assert counts.layer_params(GRANITE) == layer == 199_237_632
    assert counts.stack_params(GRANITE) == 3_984_752_640   # 3.98 B
    assert counts.embed_params(GRANITE) == 201_338_880     # 0.20 B
    # 8.37 GB of bf16 weights with the final norm
    assert counts.weight_bytes(GRANITE) == 2 * (3_984_752_640
                                                + 201_338_880 + 4096)


def test_granite_kv_bytes_per_token():
    # k and v, 20 layers, 8 heads of 128, 2 bytes
    assert counts.kv_bytes_per_token(GRANITE) == 2 * 20 * 8 * 128 * 2 == 81_920


def test_vit_train_ops_per_token():
    r = 16
    base = 4 * 84_980_736                 # forward + activation gradients
    frontend = 2 * 768 * 768
    attn = 12 * 197 * 768 * 12            # QK^T and PV, forward and 2x back
    lora = 6 * r * (4 * 1536 + 2 * 3840) * 12
    head = 4 * 768 * 100 / 197
    want = base + frontend + attn + lora + head
    assert counts.train_ops_per_token(VIT, r) == pytest.approx(want)
    assert 0.36e9 < want < 0.39e9
    # 5 clients x 2 steps x 32 items = 63,040 tokens a round
    assert counts.round_ops(VIT, [r] * 5, 64) == pytest.approx(
        want * 5 * 64 * 197)


def test_granite_forward_ops():
    ctx, r = 300, 8
    want = (2 * 3_984_752_640 + 4 * ctx * 4096 * 20
            + 2 * r * (8192 + 5120 + 5120 + 8192) * 20 + 2 * 201_338_880)
    assert counts.forward_ops(GRANITE, ctx, r) == pytest.approx(want)
    assert counts.prompt_ops(GRANITE, 3, r) == pytest.approx(
        sum(counts.forward_ops(GRANITE, c, r) for c in (1, 2, 3)))


def test_decode_bound_is_bandwidth_at_small_batch():
    pk = peak("TPU v5 lite")
    contexts, ranks = [300] * 16, [8] * 16
    t = counts.decode_step_bound(GRANITE, contexts, ranks, [8, 16], pk)
    nbytes = (counts.weight_bytes(GRANITE) + 81_920 * 300 * 16
              + counts.adapter_bytes(GRANITE, 8)
              + counts.adapter_bytes(GRANITE, 16))
    assert t == pytest.approx(nbytes / 819e9)
    assert 0.0102 < t < 0.0115


def test_peak_table():
    pk = peak("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak("TPU v4")
