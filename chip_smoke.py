#!/usr/bin/env python3
"""On-chip smoke check of the two jobs this repository runs, at published
widths, through their normal entry points. Needs a TPU and a checkout
around it (the script puts the checkout's ``src/`` on the path):

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded engine, four chips

Phases (one process holds the chip for all of them):

a) device report; anything but a TPU stops the script (exit 1).
b) federated rounds at vit-base width (12 layers, d 768, 197 patches,
   100 classes; 5 clients x 2 local steps x 32 items, ranks 4..32):
   one raFLoRA round on the ``kernel`` backend and one on the ``dense``
   reference, from identical seeds and state, compared on the adapter
   products B @ A and on the higher-rank energy ratio; then two more
   ``kernel`` rounds, whose losses must be finite. The compiled
   aggregation program must hold the Pallas kernels (``tpu_custom_call``).
c) adapter serving at llama3.2-3b width (28 layers, bf16 weights): three
   tenants at ranks 16, 8 and 4, four requests admitted, eight tokens
   decoded; the logits of prefill and of every cached decode step are
   compared with the same model's full forward over the same tokens and
   tenants.
d) compile seconds, steady seconds per phase (a smoke reading, not a
   benchmark) and the device's peak memory.
e) ``--four-chips`` only: one ``kernel`` round on the sharded engine over
   a ("data",) mesh of four chips against the batched engine on one chip,
   compared on the adapter products; nothing else runs.

The last line of standard output is the JSON result; it is printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ROUND_ARCH = "vit-base"
# 16 clients, 5 sampled per round: at vit-base width the host data holds
# 17 items per class, so every client's shard (73..86 items) fills two
# local batches of 32 -- each round trains ONE group of 5 clients x 2
# steps, and all rounds reuse one training and one aggregation program
ROUND_FL = {"num_rounds": 3, "num_clients": 16, "participation": 5 / 16}
SERVE_ARCH = "llama3.2-3b"
PROMPT_LEN, NEW_TOKENS, SLOTS, TENANTS = 32, 8, 4, 3

# Kernel vs dense round: both rounds train identically (same program, same
# data, under "highest" matmul precision), so the products differ only by
# aggregation. The kernel path squares the stack into (R, R) Gram cores,
# which halves the attainable precision: agreement is ~sqrt(eps_f32)
# (3.5e-4) of the product's largest singular value, not eps. 2e-3 sigma_max
# is the bound the CPU property tests hold the kernel path to
# (tests/test_kernels.py, TestFusedFactoredProperty).
ROUND_PRODUCT_RTOL = 2e-3
# The energy ratio is a quotient of sums of squared singular values: the
# same ~sqrt(eps) relative noise, on a value in [0, 1].
ENERGY_ATOL = 2e-3
# Sharded vs batched, both on the kernel backend: the sharded engine
# assembles the client stack shard by shard, so its columns come in
# another order and the Gram cores round differently -- the same
# ~sqrt(eps) noise floor, hence the same bound.
SHARDED_PRODUCT_RTOL = ROUND_PRODUCT_RTOL
# Served logits vs the full forward, both bf16: a cached decode step takes
# one softmax over the whole cache while the full forward runs blockwise
# (online-softmax) attention, so the two round differently in every one
# of the 28 layers (prefill and forward share one path). bf16 keeps
# 8 significant bits (relative step 2^-8 = 3.9e-3); 28 layers of residual
# adds of such rounding stay within 2^-4 = 6.25e-2 of the largest logit.
SERVE_LOGIT_RTOL = 2.0 ** -4


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Seconds XLA spends compiling (or fetching from the persistent
    cache), and how many programs came from that cache, read from JAX's
    own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.programs


def adapter_factors(jax, np, server) -> dict:
    """{adapter path: (lora_b (L, out, r), lora_a (L, r, in))} in f32."""
    flat = jax.tree_util.tree_flatten_with_path(server.global_lora)[0]
    leaves = {tuple(str(getattr(p, "key", p)) for p in path):
              np.asarray(leaf, np.float32) for path, leaf in flat}
    parents = sorted({k[:-1] for k in leaves if k[-1] == "lora_b"})
    return {k: (leaves[k + ("lora_b",)], leaves[k + ("lora_a",)])
            for k in parents}


def product_error(np, got: dict, want: dict) -> float:
    """Largest entry of |B A - B' A'| over every adapter and layer, in
    units of that product's largest singular value. Products, not factors:
    the SVD realloc's sign and rotation freedom cancels in the product."""
    assert sorted(got) == sorted(want)
    worst = 0.0
    for k, (b_w, a_w) in want.items():
        b_g, a_g = got[k]
        diff = np.abs(b_g @ a_g - b_w @ a_w).max(axis=(-2, -1))
        # sigma_max(B A) from the (r, r) core of the two QR factors
        core = (np.linalg.qr(b_w)[1]
                @ np.swapaxes(np.linalg.qr(np.swapaxes(a_w, -1, -2))[1],
                              -1, -2))
        smax = np.linalg.svd(core, compute_uv=False)[..., 0]
        worst = max(worst, float((diff / np.maximum(smax, 1e-30)).max()))
    return worst


def timed(jax, fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def round_phase(jax, np, clock, report, *, arch=ROUND_ARCH, **exp_kw):
    """b) kernel vs dense round, then two more kernel rounds."""
    from repro.core import aggregation
    from repro.federation.experiment import build_experiment

    def build(backend):
        return build_experiment("raflora", arch=arch, backend=backend,
                                fl_overrides=ROUND_FL, **exp_kw)

    kern, dense = build("kernel"), build("dense")
    # record the kernel round's aggregation call to inspect its program
    core, calls = aggregation._grouped_core, []

    def recording_core(*args, **kwargs):
        calls.append((args, kwargs))
        return core(*args, **kwargs)

    c0 = clock.mark()
    # every round runs at "highest": the dense reference needs it, and one
    # precision keeps rounds 2-3 on the programs round 1 compiled
    with jax.default_matmul_precision("highest"):
        aggregation._grouped_core = recording_core
        st_k, t_k = timed(jax, kern.server.run_round)
        aggregation._grouped_core = core
        jax.block_until_ready(kern.server.global_lora)
        st_d, t_d = timed(jax, dense.server.run_round)
        jax.block_until_ready(dense.server.global_lora)
        check(st_k.clients == st_d.clients and st_k.ranks == st_d.ranks,
              "kernel and dense rounds sampled different clients")
        # one training program on one input: identical up to the last bit
        # unless XLA reorders a reduction between the two compilations
        check(math.isfinite(st_k.mean_client_loss)
              and abs(st_k.mean_client_loss - st_d.mean_client_loss)
              <= 1e-6 * abs(st_d.mean_client_loss),
              f"client training differs between the backends: "
              f"{st_k.mean_client_loss} vs {st_d.mean_client_loss}")
        err = product_error(np, adapter_factors(jax, np, kern.server),
                            adapter_factors(jax, np, dense.server))
        e_k = float(kern.server.energy.higher_rank_ratio[-1])
        e_d = float(dense.server.energy.higher_rank_ratio[-1])
        print(f"round kernel-vs-dense: clients={st_k.clients} "
              f"ranks={st_k.ranks} loss={st_k.mean_client_loss:.6f} "
              f"product_err/sigma_max={err:.3e} (tol {ROUND_PRODUCT_RTOL:g}) "
              f"energy kernel={e_k:.6f} dense={e_d:.6f} "
              f"(tol {ENERGY_ATOL:g})", flush=True)
        check(err <= ROUND_PRODUCT_RTOL,
              f"kernel-vs-dense products differ by {err:.3e}")
        check(abs(e_k - e_d) <= ENERGY_ATOL,
              f"higher-rank energy differs: {e_k} vs {e_d}")

        check(bool(calls), "the kernel round made no grouped aggregation call")
        args, kwargs = calls[0]
        text = core.lower(*args, **kwargs).compile().as_text()
        kernels = text.count("tpu_custom_call")
        print(f"round aggregation program: tpu_custom_call x{kernels}",
              flush=True)
        check(kernels > 0, "the kernel aggregation program holds no "
                           "tpu_custom_call: the Pallas grids did not compile")
        del dense, calls, args, kwargs
        gc.collect()

        losses, walls = [], []
        for _ in range(2):
            st, t = timed(jax, kern.server.run_round)
            jax.block_until_ready(kern.server.global_lora)
            losses.append(st.mean_client_loss)
            walls.append(t)
    print(f"round kernel rounds 2-3: losses={losses} "
          f"energy={[float(e) for e in kern.server.energy.higher_rank_ratio]}",
          flush=True)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    c1 = clock.mark()
    report["round"] = {
        "compile_s": c1[0] - c0[0], "programs": c1[1] - c0[1],
        "first_round_s": t_k, "dense_round_s": t_d,
        "steady_round_s": walls[-1]}
    del kern
    gc.collect()


def serve_phase(jax, np, clock, report, *, cfg=None):
    """c) admit 4 requests over 3 tenants, decode 8 tokens, compare every
    step's logits with the full forward."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import build
    from repro.serving.engine import substitute_pages

    cfg = cfg or get_config(SERVE_ARCH)
    c0 = clock.mark()
    model, _, store, engine = build(
        cfg, tenants=TENANTS, slots=SLOTS,
        max_len=PROMPT_LEN + NEW_TOKENS + 1, seed=0)
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, size=(SLOTS, PROMPT_LEN), dtype=np.int32)
    tenant_of = [f"tenant{i % TENANTS}" for i in range(SLOTS)]
    _, t_admit = timed(jax, lambda: engine.admit(range(SLOTS), prompts,
                                                 tenant_of))
    # the same admit again: now compiled, and it leaves the same state
    _, t_admit_steady = timed(jax, lambda: engine.admit(range(SLOTS), prompts,
                                                        tenant_of))
    served, fed, decode_s = [engine.logits], [], []
    active = jnp.ones((SLOTS,), bool)
    for _ in range(NEW_TOKENS):
        fed.append(np.asarray(engine.tokens))
        _, t = timed(jax, lambda: engine.decode(active))
        served.append(engine.logits)
        decode_s.append(t)
    snap = store.published
    seq = np.concatenate([prompts, np.stack(fed, axis=1)], axis=1)
    forward = jax.jit(lambda base, pages, ids, tokens: model.forward_seq(
        substitute_pages(base, pages, ids), {"tokens": tokens})[0])
    ref = forward(engine.base, snap.pages, engine.slot_pages, seq)
    ref = np.asarray(ref[:, PROMPT_LEN - 1:], np.float32)
    got = np.stack([np.asarray(x, np.float32) for x in served], axis=1)
    check(np.isfinite(got).all(), "non-finite served logits")
    scale = float(np.abs(ref).max())
    per_step = np.abs(got - ref).max(axis=(0, 2)) / scale
    print(f"serve {cfg.name}: ranks={snap.ranks} tenants={tenant_of} "
          f"logits max|ref|={scale:.4f} rel_err prefill={per_step[0]:.3e} "
          f"decode max={per_step[1:].max():.3e} (tol {SERVE_LOGIT_RTOL:g}) "
          f"argmax agree={float((got.argmax(-1) == ref.argmax(-1)).mean()):.3f}",
          flush=True)
    check(float(per_step.max()) <= SERVE_LOGIT_RTOL,
          f"served logits differ from the full forward by {per_step.max():.3e}")
    c1 = clock.mark()
    report["serve"] = {
        "compile_s": c1[0] - c0[0], "programs": c1[1] - c0[1],
        "first_admit_s": t_admit, "steady_admit_s": t_admit_steady,
        "first_decode_s": decode_s[0],
        "steady_decode_s": float(np.mean(decode_s[1:]))}
    del model, store, engine, ref, got, served
    gc.collect()


def four_chip_phase(jax, np, clock, report, *, arch=ROUND_ARCH, **exp_kw):
    """e) the sharded engine over 4 chips vs the batched engine on one."""
    from repro.federation.experiment import build_experiment
    from repro.launch.mesh import make_fl_mesh

    mesh = make_fl_mesh()
    ids = sorted(d.id for d in mesh.devices.flat)
    print(f"four-chip mesh: axes={mesh.axis_names} devices={ids}", flush=True)
    check(len(set(ids)) == 4, f"the mesh spans {len(set(ids))} devices")

    def build(engine, **kw):
        return build_experiment("raflora", arch=arch, backend="kernel",
                                round_engine=engine, fl_overrides=ROUND_FL,
                                **kw, **exp_kw)

    sharded, batched = build("sharded", mesh=mesh), build("batched")
    trainer, placed = sharded.server.trainer, []
    dispatch = trainer.dispatch_group_masked

    def recording_dispatch(*args, **kwargs):
        lora_g, loss = dispatch(*args, **kwargs)
        placed.extend(leaf.sharding for leaf in jax.tree.leaves(lora_g))
        return lora_g, loss

    trainer.dispatch_group_masked = recording_dispatch
    c0 = clock.mark()
    with jax.default_matmul_precision("highest"):
        st_s, t_s = timed(jax, sharded.server.run_round)
        jax.block_until_ready(sharded.server.global_lora)
        st_b, t_b = timed(jax, batched.server.run_round)
        jax.block_until_ready(batched.server.global_lora)
    spans = {len(s.device_set) for s in placed}
    print(f"four-chip client stacks: {len(placed)} leaves, devices per "
          f"leaf={sorted(spans)}", flush=True)
    check(spans == {4}, f"client stacks span {spans} devices, not 4")
    check(st_s.clients == st_b.clients, "the engines sampled differently")
    err = product_error(np, adapter_factors(jax, np, sharded.server),
                        adapter_factors(jax, np, batched.server))
    print(f"four-chip sharded-vs-batched: clients={st_s.clients} "
          f"loss sharded={st_s.mean_client_loss:.6f} "
          f"batched={st_b.mean_client_loss:.6f} product_err/sigma_max={err:.3e} "
          f"(tol {SHARDED_PRODUCT_RTOL:g})", flush=True)
    check(math.isfinite(st_s.mean_client_loss), "non-finite sharded loss")
    check(abs(st_s.mean_client_loss - st_b.mean_client_loss)
          <= 1e-4 * abs(st_b.mean_client_loss),
          "sharded and batched client losses differ")
    check(err <= SHARDED_PRODUCT_RTOL,
          f"sharded-vs-batched products differ by {err:.3e}")
    c1 = clock.mark()
    report["four_chips"] = {"compile_s": c1[0] - c0[0],
                            "programs": c1[1] - c0[1],
                            "sharded_round_s": t_s, "batched_round_s": t_b}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on 4 chips vs the "
                         "batched engine on one")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)

    import jax
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this check "
             "runs on the chip only")
    if args.four_chips:
        check(len(devices) >= 4, f"--four-chips needs 4 chips, found "
                                 f"{len(devices)}")

    clock = CompileClock(jax)
    report: dict = {}
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(jax, np, clock, report)
    else:
        round_phase(jax, np, clock, report)
        serve_phase(jax, np, clock, report)
    stats = dev.memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["compile_s"], report["programs"] = clock.mark()
    report["cache_hits"] = clock.cache_hits
    report["total_s"] = time.perf_counter() - t0
    print("smoke reading, not a benchmark (wall seconds on the host clock "
          "around block_until_ready; compile_s = XLA compile or "
          "persistent-cache fetch): " + json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
