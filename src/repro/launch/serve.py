"""Serving driver: multi-tenant batched greedy decoding with the
fine-tuned adapters (DESIGN.md §11).

Runs the serving subsystem end to end at the architecture's published
widths (bf16 weights, every layer) -- adapters are staged in an
``AdapterStore`` (paged, rank-bucketed, versioned) and a ``ServingEngine``
prefills the KV/SSM cache up front at full ``max_len`` via
``Model.init_cache`` (path-aware seeding; SSM ``conv``/``ssm`` states
transfer correctly), then decodes token-by-token. llama3.2-3b is the
default: ~6.4 GB of bf16 weights, which one 16 GB chip holds.

The serving rank is DERIVED from the LoRA config (``r_max``) -- never
hardcoded -- so train-side rank-level changes cannot desync serving.

  PYTHONPATH=src python -m repro.launch.serve --tokens 32
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

RANK_LEVELS = (4, 8, 16)


def build(cfg, *, tenants: int, slots: int, max_len: int, seed: int):
    """(model, params, store, engine) for ``cfg``: random bf16 weights from
    ``seed``, one tenant per rank level (cycled), published once."""
    from repro.configs import LoRAConfig
    from repro.core.lora import split_lora
    from repro.models import build_model
    from repro.serving import AdapterStore, ServingEngine

    lora = LoRAConfig(rank_levels=RANK_LEVELS)
    model = build_model(cfg, lora, dtype=jnp.bfloat16, remat=False,
                        block_q=32, block_kv=32)
    # independent streams: params and tenants must never share a key
    k_init, k_tenants = jax.random.split(jax.random.PRNGKey(seed))
    # one jitted init: eager init would hold the per-layer arrays and
    # their stacked copy at once (2x the weights)
    params = jax.jit(model.init)(k_init)
    _, lora_tree = split_lora(params)

    # highest level = the config's serving rank r_max -- derived, never
    # hardcoded; each tenant's factors are a seeded perturbation
    store = AdapterStore(lora.rank_levels, scaling_fn=lora.scaling)
    levels = sorted(lora.rank_levels, reverse=True)
    for t in range(max(1, tenants)):
        key_t = jax.random.fold_in(k_tenants, t)
        leaves, treedef = jax.tree.flatten(lora_tree)
        perturbed = [
            x + (0.01 * jax.random.normal(jax.random.fold_in(key_t, i),
                                          x.shape)).astype(x.dtype)
            for i, x in enumerate(leaves)]
        store.put(f"tenant{t}", jax.tree.unflatten(treedef, perturbed),
                  levels[t % len(levels)])
    store.publish()
    engine = ServingEngine(model, params, store, max_len=max_len,
                           slots=slots)
    return model, params, store, engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--tenants", type=int, default=3,
                    help="number of adapter pages to serve across the batch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not cfg.supports_decode:
        print(f"{args.arch} is encoder-only; no decode path")
        return 1
    b, lp = args.batch, args.prompt_len
    _, _, store, engine = build(cfg, tenants=args.tenants, slots=b,
                                max_len=lp + args.tokens, seed=args.seed)
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, size=(b, lp), dtype=np.int32)
    tenant_of = [f"tenant{i % max(1, args.tenants)}" for i in range(b)]

    t0 = time.time()   # host-clock: ok (CLI wall phase timing, off the round path)
    first = engine.admit(range(b), prompts, tenant_of)
    jax.block_until_ready(first)
    t_prefill = time.time() - t0   # host-clock: ok (CLI wall phase timing)

    generated = [np.asarray(first)]
    active = jnp.ones((b,), bool)
    t0 = time.time()   # host-clock: ok (CLI wall phase timing)
    for _ in range(args.tokens - 1):
        generated.append(np.asarray(engine.decode(active)))
    seqs = np.stack(generated, axis=1)
    t_decode = time.time() - t0   # host-clock: ok (CLI wall phase timing)
    device = jax.devices()[0]
    print(f"arch={cfg.name} device={device.platform}/{device.device_kind} "
          f"batch={b} tenants={store.published.num_pages} "
          f"ranks={store.published.ranks} adapter_v{store.published.version} "
          f"prefill({lp} toks)={t_prefill:.2f}s "
          f"decode({args.tokens} toks)={t_decode:.2f}s "
          "(wall clock, compilation included)")
    for i in range(min(b, 2)):
        print(f"  req{i} [{tenant_of[i]}]: {seqs[i].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
