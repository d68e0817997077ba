"""Compile the main-path Pallas kernels for a described TPU v5e, at real
widths, with the TPU's own compiler (no chip needed).

Interpret mode accepts block shapes that Mosaic refuses (a block's last two
dims must tile by (8, 128) or span the array) and cannot see VMEM limits or
device memory; these compiles can. Each test checks that the compiled
program holds the kernel as a ``tpu_custom_call`` and that the program
fits one chip's 16 GB.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import this
file. The ops wrappers pick interpret mode from the backend they trace on,
which here is the CPU; the ``chip_compile`` fixture steers that choice to
compiled Mosaic for the duration of a test.

Widths: the federated aggregation at llama3.2-3b ``q_proj`` (28 layers,
d = n = 3072, r_max 32, 8 clients plus the Eq. 8 fallback client), the
serving LoRA-apply and attention at llama3.2-3b, the SSD scan at
mamba2-1.3b.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops

HBM_BYTES = 16 * 10**9                   # one v5e chip

LLAMA = get_config("llama3.2-3b")
MAMBA = get_config("mamba2-1.3b")
LAYERS, D, R, CLIENTS = LLAMA.num_layers, LLAMA.d_model, 32, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def chip_compile(topo, monkeypatch):
    """Compile for one described chip, kernels as Mosaic, no persistent
    cache (a TPU entry written here could not be read back without a
    chip). Returns ``compile(fn, *shapes, **static)``."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()                   # drop interpret-mode traces
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes, **static):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                if s is not None else None for s in shapes]
        return fn.lower(*args, **static).compile()

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _assert_on_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used


def _aggregation_shapes():
    return (_sds((LAYERS, CLIENTS, D, R)), _sds((LAYERS, CLIENTS, R, D)),
            _sds((CLIENTS, R)), _sds((LAYERS, D, R)), _sds((LAYERS, R, D)),
            _sds((R,)))


def test_factored_stack_gram_layered(chip_compile):
    _assert_on_chip(chip_compile(ops.factored_stack_gram_layered,
                                 *_aggregation_shapes()))


def test_rank_partition_agg_layered(chip_compile):
    _assert_on_chip(chip_compile(ops.rank_partition_agg_layered,
                                 *_aggregation_shapes()))


def test_lora_apply(chip_compile):
    tokens = 4 * 512
    _assert_on_chip(chip_compile(
        ops.lora_apply, _sds((tokens, D)), _sds((D, D)), _sds((R, D)),
        _sds((D, R)), scale=2.0))


def test_batched_lora_apply(chip_compile):
    slots, prompt, pages, r = 4, 32, 3, 16
    bf = jnp.bfloat16
    _assert_on_chip(chip_compile(
        ops.batched_lora_apply, _sds((slots, prompt, D), bf),
        _sds((D, D), bf), _sds((pages, r, D), bf), _sds((pages, D, r), bf),
        _sds((pages,)), _sds((slots, prompt), jnp.int32)))


def test_flash_attention(chip_compile):
    seq, hd = 2048, LLAMA.resolved_head_dim
    bf = jnp.bfloat16
    _assert_on_chip(chip_compile(
        ops.flash_attention, _sds((1, seq, LLAMA.num_heads, hd), bf),
        _sds((1, seq, LLAMA.num_kv_heads, hd), bf),
        _sds((1, seq, LLAMA.num_kv_heads, hd), bf)))


def test_ssd_scan(chip_compile):
    ssm = MAMBA.ssm
    heads = ssm.expand * MAMBA.d_model // ssm.head_dim
    seq = 4 * ssm.chunk_size
    _assert_on_chip(chip_compile(
        ops.ssd_scan, _sds((1, seq, heads, ssm.head_dim)),
        _sds((1, seq, heads)), _sds((heads,)),
        _sds((1, seq, ssm.ngroups, ssm.state_dim)),
        _sds((1, seq, ssm.ngroups, ssm.state_dim)), _sds((heads,)),
        chunk=ssm.chunk_size))


def test_interpret_follows_the_backend():
    """Off the TPU the ops interpret; the choice is made per trace."""
    assert ops._interpret() is (jax.default_backend() != "tpu")
    assert np.isfinite(float(ops.lora_apply(
        jnp.ones((8, 128)), jnp.ones((128, 128)), jnp.ones((8, 128)),
        jnp.ones((128, 8)))[0, 0]))
