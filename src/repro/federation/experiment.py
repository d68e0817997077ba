"""Reusable end-to-end FedLoRA experiment setup (paper Section 6 proxy).

Builds the synthetic-classification federated task: a ViT-style encoder
(patch-embedding frontend, class logit read from position 0), non-IID
client shards, heterogeneous ranks, and a FederatedLoRA server for any
aggregation method. The encoder is either the tiny CPU-scale proxy
(default) or a registered vision config at its published widths
(``arch="vit-base"``), whose width, patch count and class count then shape
the synthetic data too. All the accuracy/energy benchmarks and the
integration tests run through this single harness, mirroring how every
paper experiment shares one training pipeline.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import (ACT_GELU, ATTN_BIDIR, FLConfig,
                                FrontendConfig, LoRAConfig, ModelConfig)
from repro.data import ClusterClassification, batches, make_partition
from repro.federation.server import FederatedLoRA
from repro.federation.topology import ClientRegistry
from repro.models.transformer import Model


def fedvit_config(d_model: int = 128, num_layers: int = 2,
                  num_classes: int = 20, patches: int = 8) -> ModelConfig:
    """Tiny ViT-family encoder for the CPU-scale paper experiments."""
    return ModelConfig(
        name="fedvit-tiny",
        kind="vlm",
        num_layers=num_layers,
        d_model=d_model,
        num_heads=4,
        num_kv_heads=4,
        head_dim=d_model // 4,
        d_ff=d_model * 4,
        vocab_size=num_classes,
        activation=ACT_GELU,
        attn_type=ATTN_BIDIR,
        rope_type="none",
        qkv_bias=True,
        frontend=FrontendConfig(kind="vision", embed_dim=d_model,
                                tokens_per_item=patches),
        lora_targets=("q_proj", "k_proj", "v_proj", "o_proj",
                      "up_proj", "down_proj"),
        source="paper-proxy: ViT-base downscaled for CPU federated runs",
    )


# host bytes the synthetic training set may take (float32 patch embeddings)
HOST_DATA_BYTES = 2**30


def _samples_per_class(num_classes: int, patches: int, dim: int) -> int:
    """100 per class, or fewer where that would pass ``HOST_DATA_BYTES``
    (vit-base widths: 17 per class, 1.03 GB)."""
    return max(1, min(100, HOST_DATA_BYTES // (num_classes * patches * dim
                                               * 4)))


def _to_batch(x: np.ndarray, y: np.ndarray, num_positions: int) -> dict:
    """Classification batch: label read out at position 0.

    Returned as NUMPY arrays deliberately: batch building is the host-side
    data pipeline, and on jax's CPU client any eager device touch (even a
    transfer) synchronizes with in-flight computations. Keeping batches in
    host memory until the training dispatch transfers them is what lets the
    async round engine overlap round t+1's data pipeline with round t's
    device execution."""
    b = x.shape[0]
    targets = np.zeros((b, num_positions), np.int32)
    targets[:, 0] = y
    mask = np.zeros((b, num_positions), np.float32)
    mask[:, 0] = 1.0
    return {"embeds": np.asarray(x, np.float32), "targets": targets,
            "loss_mask": mask}


@dataclass
class FLExperiment:
    server: FederatedLoRA
    model: Model
    test_batch: dict
    registry: ClientRegistry

    def eval_accuracy(self) -> float:
        return self.server.evaluate(self.test_batch)["accuracy"]


def build_experiment(method: str = "raflora", *,
                     arch: Optional[str] = None,
                     fl_overrides: Optional[dict] = None,
                     lora_overrides: Optional[dict] = None,
                     num_classes: int = 20,
                     d_model: int = 128,
                     modes_per_class: int = 4,
                     noise: float = 0.6,
                     samples_per_class: Optional[int] = None,
                     batches_per_round: int = 2,
                     backend: str = "factored",
                     partial_up_to: Optional[int] = None,
                     noisy_low_rank_std: float = 0.0,
                     server_momentum_beta: float = 0.0,
                     round_engine: str = "batched",
                     mesh=None,
                     pipeline_depth: int = 1,
                     staleness_gamma: float = 1.0,
                     event_scheduler=None,
                     transport=None,
                     data_seed: int = 0) -> FLExperiment:
    """``arch``: a registered vision config (e.g. ``"vit-base"``) trained
    at its published widths: the data takes the config's embedding width,
    patch count and class count (``num_classes``/``d_model`` are then
    ignored), and the client step recomputes each layer in the backward
    pass (remat) -- without it a vit-base round of 5 clients x 32 items
    needs ~80 GB of device memory. None: the tiny CPU-scale proxy.

    ``samples_per_class``: None takes ``_samples_per_class``.

    ``event_scheduler``: an ``events.EventScheduler`` switching the
    async engine from the fixed ``pipeline_depth`` cadence to arrival-event
    buffer triggers on the virtual clock (DESIGN.md §7).

    ``transport``: a ``transport.UpdateTransport``/``TransportConfig``
    compressing client factor uploads (int8/bf16 + error feedback,
    DESIGN.md §12); None ships f32."""
    if arch is None:
        cfg = fedvit_config(d_model=d_model, num_classes=num_classes)
    else:
        cfg = get_config(arch)
        assert cfg.frontend.kind == "vision", \
            f"{arch}: the federated task needs a vision frontend"
    patches, dim = cfg.frontend.tokens_per_item, cfg.frontend.embed_dim
    num_classes = cfg.vocab_size
    fl = FLConfig(aggregator=method, num_clients=20, participation=0.25,
                  num_rounds=40, local_batch_size=32, learning_rate=2e-3,
                  partition="pathological", dirichlet_alpha=1.0,
                  labels_per_client=max(num_classes // 4, 2))
    if fl_overrides:
        fl = dataclasses.replace(fl, **fl_overrides)
    lora = LoRAConfig(rank_levels=(4, 8, 16, 24, 32),
                      rank_probs=(0.2, 0.2, 0.2, 0.2, 0.2))
    if lora_overrides:
        lora = dataclasses.replace(lora, **lora_overrides)

    if samples_per_class is None:
        samples_per_class = _samples_per_class(num_classes, patches, dim)
    data = ClusterClassification(
        num_classes=num_classes, dim=dim, patches=patches,
        modes_per_class=modes_per_class, noise=noise,
        samples_per_class=samples_per_class, seed=data_seed)
    (x_tr, y_tr), (x_te, y_te) = data.train_test_split()
    shards = make_partition(fl.partition, y_tr, fl.num_clients,
                            alpha=fl.dirichlet_alpha,
                            labels_per_client=fl.labels_per_client,
                            seed=fl.seed)
    model = Model(cfg, lora, dtype=jnp.float32, remat=arch is not None,
                  block_q=64, block_kv=64)
    registry = ClientRegistry.create(fl, lora, shards)

    # optional: degrade low-rank clients' data (Table 4 extension)
    x_noisy = x_tr
    if noisy_low_rank_std > 0:
        rng = np.random.default_rng(123)
        x_noisy = x_tr.copy()
        min_rank = min(lora.rank_levels)
        for cid in range(fl.num_clients):
            if registry.ranks[cid] == min_rank:
                idx = registry.shards[cid]
                x_noisy[idx] = x_tr[idx] + noisy_low_rank_std * rng.normal(
                    size=x_tr[idx].shape).astype(np.float32)

    def batch_fn(client_id: int, rng: np.random.Generator) -> list:
        idx = registry.shards[client_id]
        xs, ys = x_noisy[idx], y_tr[idx]
        out = []
        for bx, by in batches(xs, ys, fl.local_batch_size, rng,
                              epochs=fl.local_epochs):
            out.append(_to_batch(bx, by, data.patches))
            if len(out) >= batches_per_round:
                break
        return out

    server_momentum = None
    if server_momentum_beta > 0:
        from repro.core.server_opt import FactoredServerMomentum
        server_momentum = FactoredServerMomentum(beta=server_momentum_beta)
    server = FederatedLoRA(model, fl, lora, registry, batch_fn,
                           backend=backend, partial_up_to=partial_up_to,
                           server_momentum=server_momentum,
                           round_engine=round_engine, mesh=mesh,
                           pipeline_depth=pipeline_depth,
                           staleness_gamma=staleness_gamma,
                           event_scheduler=event_scheduler,
                           transport=transport)
    test_batch = _to_batch(x_te[:512], y_te[:512], data.patches)
    return FLExperiment(server=server, model=model, test_batch=test_batch,
                        registry=registry)
