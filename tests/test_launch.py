"""Entry points: registered-config wiring of the federated task, the
``--arch`` switch of the trainer, the shared compile-cache helper, and
``chip_smoke.py`` refusing to run anywhere but on a TPU."""
import importlib.util
import os
import shutil

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.federation import experiment
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_build_experiment_wires_a_registered_config():
    """vit-base's width, patch count and class count shape both the data
    and the model; its client step recomputes layers (remat). Two items
    per class keep the host data at ~121 MB."""
    cfg = get_config("vit-base")
    exp = experiment.build_experiment(
        "raflora", arch="vit-base", samples_per_class=2,
        fl_overrides={"num_clients": 4, "participation": 0.5})
    assert exp.model.cfg is cfg
    assert exp.model.remat
    patches, dim = cfg.frontend.tokens_per_item, cfg.frontend.embed_dim
    assert (patches, dim) == (197, 768)
    assert exp.test_batch["embeds"].shape[1:] == (patches, dim)
    assert exp.test_batch["targets"].shape[1] == patches
    assert exp.test_batch["targets"].max() < cfg.vocab_size == 100
    shard = exp.registry.shards[0]
    (batch,) = exp.server.batch_fn(0, np.random.default_rng(0))[:1]
    assert batch["embeds"].shape == (min(32, len(shard)), patches, dim)


def test_tiny_proxy_stays_the_default():
    exp = experiment.build_experiment("raflora", num_classes=4, d_model=32,
                                      samples_per_class=8)
    assert exp.model.cfg.name == "fedvit-tiny"
    assert exp.model.cfg.d_model == 32 and not exp.model.remat
    assert exp.test_batch["embeds"].shape[1:] == (8, 32)


def test_host_data_is_capped_near_one_gigabyte():
    cfg = get_config("vit-base")
    patches, dim = cfg.frontend.tokens_per_item, cfg.frontend.embed_dim
    per_class = experiment._samples_per_class(cfg.vocab_size, patches, dim)
    assert per_class == 17
    assert per_class * cfg.vocab_size * patches * dim * 4 \
        <= experiment.HOST_DATA_BYTES
    assert experiment._samples_per_class(20, 8, 128) == 100


def test_train_passes_arch_through(monkeypatch):
    from repro.launch import train
    seen = {}

    class Built(Exception):
        pass

    def fake_build(method, **kw):
        seen.update(kw, method=method)
        raise Built

    monkeypatch.setattr(experiment, "build_experiment", fake_build)
    with pytest.raises(Built):
        train.main(["--arch", "vit-base", "--backend", "kernel",
                    "--rounds", "3"])
    assert seen["arch"] == "vit-base" and seen["backend"] == "kernel"
    with pytest.raises(Built):
        train.main([])
    assert seen["arch"] is None


@pytest.mark.parametrize("env, want_dir, want", [
    (None, str(compile_cache.CHECKOUT_CACHE),
     str(compile_cache.CHECKOUT_CACHE)),
    ("/elsewhere/cache", None, "/elsewhere/cache"),
    ("", None, None),
], ids=["checkout", "environment", "off"])
def test_compile_cache_location(monkeypatch, env, want_dir, want):
    """The environment's directory when set (JAX reads it itself, so the
    helper sets none), the checkout's ``.jax_cache`` otherwise, off when
    set empty."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    assert compile_cache.enable_compile_cache() == want
    assert updates.get("jax_compilation_cache_dir") == want_dir
    assert str(compile_cache.CHECKOUT_CACHE) == os.path.join(ROOT,
                                                             ".jax_cache")


def _load_chip_smoke(path):
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_chip_smoke_refuses_the_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    smoke = _load_chip_smoke(os.path.join(ROOT, "chip_smoke.py"))
    with pytest.raises(SystemExit) as ended:
        smoke.main([])
    assert ended.value.code != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


def test_chip_smoke_refuses_a_bare_directory(tmp_path, capsys):
    """Copied out of the checkout, the script finds no program to run."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    smoke = _load_chip_smoke(str(tmp_path / "chip_smoke.py"))
    with pytest.raises(SystemExit) as ended:
        smoke.main([])
    assert ended.value.code != 0
    out, err = capsys.readouterr()
    assert "no repro package" in err and '"ok"' not in out
