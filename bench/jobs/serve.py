"""Multi-tenant adapter serving through the program's engine and batcher.

Set-up makes the weights (bf16, on the device, one jitted call) in the
program's parameter layout and every tenant's adapter, stages the tenants
in the program's ``AdapterStore``, builds its ``ServingEngine`` and warms
every shape the window uses: an admit of each batch size 1..slots and the
decode step. The window drives the program's ``ContinuousBatcher`` with a
wall clock (zero virtual costs): requests from the traffic file arrive
open-loop at their due times, the batcher admits them into free slots and
decodes every active slot once per step. The benchmark wraps the engine's
``admit`` and ``decode`` to stamp when each token is ready, and keeps the
engine's own logits row of every token it serves to the sampled requests.

The sample is drawn from the seed before the window (the request with the
longest output and others, up to the traffic's ``sample_tokens``); every
request due in the window finishes when the traffic says ``drain``. After
the window the float32 reference runs over each sampled prompt and its
served tokens, and two numbers are compared: ``logit_err``, the widest
root-mean-square gap between the engine's logits (prefill and cached
decode) and the reference's, over the reference's spread; and
``served_gap``, the widest gap by which a served token's logit lies below
the reference's best.
"""
from __future__ import annotations

import dataclasses
import gc
import statistics
import time

import numpy as np

from harness.clock import now
from refs.decoder import ATTN, pick_gap, rel_err, served_logits


def model_config(cfg: dict):
    from repro.configs import get_config
    heads = cfg["num_attention_heads"]
    return dataclasses.replace(
        get_config(cfg["program"]["arch"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        lora_targets=tuple(cfg["lora"]["targets"]))


def make_weights(jax, shapes, key):
    """The program's parameter tree from ``key`` in one jitted call:
    weights N(0, 1/fan_in), norm scales 1 + N(0, 0.1^2), the embedding
    N(0, 1/hidden); the tree's own LoRA leaves are zero (tenants are
    served from the adapter store)."""
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(path, s, k):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("lora_a", "lora_b"):
            return jnp.zeros(s.shape, s.dtype)
        z = jax.random.normal(k, s.shape, jnp.float32)
        if name == "w":
            z = z * s.shape[-2] ** -0.5
        elif name == "scale":
            z = 1.0 + 0.1 * z
        else:
            z = z * s.shape[-1] ** -0.5
        return z.astype(s.dtype)

    @jax.jit
    def gen(key):
        return [leaf(p, s, jax.random.fold_in(key, i))
                for i, (p, s) in enumerate(flat)]

    return jax.tree_util.tree_unflatten(treedef, gen(key))


def make_tenants(jax, lora_shapes, key, ranks):
    """One adapter per tenant at r_max width, zero beyond its rank:
    A N(0, 1/fan_in), B N(0, 0.01/r), in the program's lora-tree layout."""
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        lora_shapes, is_leaf=lambda x: x is None)

    @jax.jit
    def gen(key):
        out = []
        for t, rank in enumerate(ranks):
            leaves = []
            for i, (path, s) in enumerate(flat):
                if s is None:
                    leaves.append(None)
                    continue
                k = jax.random.fold_in(jax.random.fold_in(key, t), i)
                z = jax.random.normal(k, s.shape, jnp.float32)
                name = str(path[-1].key)
                if name == "lora_a":
                    z = z * s.shape[-1] ** -0.5 * (
                        jnp.arange(s.shape[-2]) < rank)[:, None]
                else:
                    z = z * (0.01 / rank) ** 0.5 * (
                        jnp.arange(s.shape[-1]) < rank)
                leaves.append(z.astype(s.dtype))
            out.append(leaves)
        return out

    return [jax.tree_util.tree_unflatten(treedef, leaves)
            for leaves in gen(key)]


def plain_factors(tree, targets):
    lay = tree["layers"]["attn"]
    return {t: (lay[ATTN[t]]["lora_a"], lay[ATTN[t]]["lora_b"])
            for t in targets}


def make_requests(ctx, tenant_ranks):
    """Due times, prompts, output lengths and tenants of the requests due
    in the window. Gaps (exponential quantiles), output lengths (lognormal
    quantiles, clipped) and tenant counts (Zipf shares) are one fixed
    multiset that the seed only orders, so every seed does the same
    work; prompts are random token ids from the seed."""
    tr, cfg = ctx.traffic, ctx.cfg
    rate, seconds = tr["rate_per_s"], ctx.seconds
    n = max(1, int(round(rate * seconds)))
    rng = ctx.rng(5)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    nd = statistics.NormalDist()
    lens = np.clip(np.round(tr["output_median"] * np.exp(
        tr["output_sigma"] * np.array([nd.inv_cdf(x) for x in q]))),
        tr["output_min"], tr["output_max"]).astype(int)
    rng.shuffle(lens)
    k = len(tenant_ranks)
    share = 1.0 / np.arange(1, k + 1) ** tr["zipf_s"]
    share = share / share.sum() * n
    counts = np.floor(share).astype(int)
    counts[np.argsort(counts - share)[:n - counts.sum()]] += 1
    tenants = np.repeat(np.arange(k), counts)
    rng.shuffle(tenants)
    prompts = rng.integers(0, cfg["vocab_size"], (n, tr["prompt_len"]),
                           dtype=np.int32)
    return [{"due": float(due[i]), "out": int(lens[i]),
             "tenant": int(tenants[i]), "prompt": prompts[i]}
            for i in range(n)]


class WallClock:
    """The batcher's clock on the host's wall: seconds since ``t0``."""

    def __init__(self):
        self.t0 = now()

    @property
    def now(self):
        return now() - self.t0

    def advance(self, t):
        pass


def run(ctx) -> dict:
    jax = ctx.jax
    import jax.numpy as jnp
    from repro.configs import LoRAConfig
    from repro.core.lora import split_lora
    from repro.models import build_model
    from repro.serving import AdapterStore, ServingEngine
    from repro.serving.scheduler import ContinuousBatcher, ServeRequest

    cfg, tr = ctx.cfg, ctx.traffic
    targets = cfg["lora"]["targets"]
    levels = tuple(cfg["lora"]["rank_levels"])
    slots, plen = tr["slots"], tr["prompt_len"]
    t0 = now()
    lora_cfg = LoRAConfig(rank_levels=levels)
    model = build_model(model_config(cfg), lora_cfg, dtype=jnp.bfloat16,
                        remat=False, block_q=cfg["program"]["block_q"],
                        block_kv=cfg["program"]["block_kv"])
    shapes = model.param_shapes()
    params = make_weights(jax, shapes, ctx.key(3))
    tenant_ranks = [levels[t % len(levels)] for t in range(tr["tenants"])]
    trees = make_tenants(jax, split_lora(shapes)[1], ctx.key(4),
                         tenant_ranks)
    store = AdapterStore(levels, scaling_fn=lora_cfg.scaling)
    for t, (tree, rank) in enumerate(zip(trees, tenant_ranks)):
        store.put(f"tenant{t}", tree, rank)
    store.publish()
    engine = ServingEngine(model, params, store,
                           max_len=plen + tr["output_max"] + 1, slots=slots)
    jax.block_until_ready((params, store.published.pages))
    requests = make_requests(ctx, tenant_ranks)
    sample = plan_sample(ctx, requests) if ctx.mode != "sweep" else set()
    ctx.setup["data_weights_s"] = now() - t0
    take_row = jax.jit(lambda logits, i: logits[i])

    # warm every shape: one admit of each size, then decode steps
    wrng = ctx.rng(6)
    for m in range(1, slots + 1):
        ids = wrng.integers(0, cfg["vocab_size"], (m, plen), dtype=np.int32)
        np.asarray(engine.admit(range(m), ids,
                                [f"tenant{i % len(trees)}"
                                 for i in range(m)]))
    np.asarray(take_row(engine.logits, np.int32(0)))
    for _ in range(2):
        np.asarray(engine.decode(np.ones(slots, bool)))
    np.asarray(take_row(engine.logits, np.int32(0)))

    clock = WallClock()
    batcher = ContinuousBatcher(engine, clock=clock, step_cost=0.0,
                                prefill_cost=0.0)
    reqs = [ServeRequest(rid=i, prompt=r["prompt"],
                         adapter_id=f"tenant{r['tenant']}",
                         max_new_tokens=r["out"], arrival=r["due"])
            for i, r in enumerate(requests)]
    times = {i: [] for i in range(len(reqs))}
    rows = {i: [] for i in sample}      # the engine's logits, on the device
    steps = []
    admit, decode = engine.admit, engine.decode

    def timed_admit(slot_idx, prompts, adapter_ids):
        t_a = clock.now
        with ctx.spans("bench.admit"):
            out = admit(slot_idx, prompts, adapter_ids)
            jax.block_until_ready(out)
        t_b = clock.now
        ranks = []
        for s in slot_idx:
            r = batcher.slots[s]
            times[r.rid].append(t_b)
            ranks.append(tenant_ranks[requests[r.rid]["tenant"]])
            if r.rid in rows:
                rows[r.rid].append(take_row(engine.logits, np.int32(s)))
        steps.append({"kind": "admit", "t0": t_a, "t1": t_b,
                      "ranks": ranks})
        return out

    def timed_decode(mask):
        live = [i for i, on in enumerate(mask) if on]
        ctxs = [plen + len(batcher.slots[i].tokens) for i in live]
        ranks = [tenant_ranks[requests[batcher.slots[i].rid]["tenant"]]
                 for i in live]
        pages = {requests[batcher.slots[i].rid]["tenant"] for i in live}
        t_a = clock.now
        with ctx.spans("bench.decode"):
            out = decode(mask)
            jax.block_until_ready(out)
        t_b = clock.now
        for i in live:
            rid = batcher.slots[i].rid
            times[rid].append(t_b)
            if rid in rows:
                rows[rid].append(take_row(engine.logits, np.int32(i)))
        steps.append({"kind": "decode", "t0": t_a, "t1": t_b,
                      "contexts": ctxs, "ranks": ranks,
                      "pages": [tenant_ranks[p] for p in pages]})
        return out

    engine.admit, engine.decode = timed_admit, timed_decode
    for r in reqs:
        batcher.submit(r)
    seconds, drain = ctx.seconds, tr["drain"]
    backlog = None
    with ctx.window() as w:
        clock.t0 = w.window_start
        while True:
            t = clock.now
            busy = any(s is not None for s in batcher.slots)
            if t >= seconds and backlog is None:
                backlog = sum(1 for i, r in enumerate(requests)
                              if not times[i])
            if t >= seconds and (not drain or not (busy or batcher.queue)):
                break
            if not busy:
                nxt = batcher.queue[0].arrival if batcher.queue else seconds
                if nxt > t:
                    with ctx.spans("bench.idle"):
                        time.sleep(nxt - t)
                    continue
            with ctx.spans("bench.sched"):
                batcher.step()
        window_s = clock.now
    # a sampled request still running at the close is waited for, up to a
    # minute, outside the window
    waited = now()
    while (sample - {r.rid for r in batcher.done}
           and now() - waited < 60.0
           and (batcher.queue or any(batcher.slots))):
        batcher.step()
    mem = ctx.memory_peak()
    engine.admit, engine.decode = admit, decode
    done = list(batcher.done)
    del batcher, engine, store
    gc.collect()
    records = [{"due": r["due"], "times": times[i], "tenant": r["tenant"],
                "out": r["out"]} for i, r in enumerate(requests)]
    finished = sum(1 for i, r in enumerate(requests)
                   if len(times[i]) >= r["out"])
    record = {
        "job": "serve", "window_s": window_s, "seconds": seconds,
        "prompt_len": plen, "requests": records, "steps": steps,
        "memory_peak_bytes": mem, "attempted": len(requests),
        "failed": 0 if not drain else len(requests) - finished,
        "complete": finished > 0,
        "readings": {"finished": finished, "backlog_at_close": backlog},
        "checks": {}}
    if ctx.mode == "sweep":
        return record

    # -- reference over the sampled requests ----------------------------
    t_ref = now()
    by_rid = {r.rid: r for r in done}
    lost = [rid for rid in sorted(sample) if rid not in by_rid]
    picked = [by_rid[rid] for rid in sorted(sample) if rid in by_rid]
    got = [np.asarray(jnp.stack(rows[r.rid]), np.float32) for r in picked]
    del rows
    tenants = {t: plain_factors(tree, targets) for t, tree in enumerate(trees)}
    served = [(np.asarray(r.prompt), np.asarray(r.tokens),
               requests[r.rid]["tenant"]) for r in picked]
    ref = served_logits(cfg, params, tenants, served) if served else []
    for g, (_, toks, _) in zip(got, served):
        if g.shape[0] != len(toks):
            raise RuntimeError(f"{g.shape[0]} logits rows kept for "
                               f"{len(toks)} served tokens")
    readings = record["readings"]
    readings.update(compare(got, ref, [s_ for _, s_, _ in served]))
    readings["lost"] = len(lost)
    ctx.info["sampled"] = (f"{len(picked)}req/"
                           f"{sum(len(s_) for _, s_, _ in served)}tok")
    if ctx.mode == "calibrate":
        # the control: the reference computed in int8 (weights per output
        # channel, inputs per token), in the program's place; and the
        # faults it stands in for: every request on the next tenant's
        # adapter, and on no adapter
        k = len(trees)
        zero = {tg: (np.zeros_like(a), np.zeros_like(b))
                for tg, (a, b) in tenants[0].items()}
        alts = {
            "control": dict(quant="w8a8"),
            "w8": dict(quant="w8"),
            "fp8": dict(quant="fp8"),
            "wrong_adapter": dict(served=[(p, s_, (t + 1) % k)
                                          for p, s_, t in served]),
            "no_adapter": dict(tenants={t: zero for t in tenants})}
        for name, kw in alts.items():
            alt = served_logits(cfg, params, kw.get("tenants", tenants),
                                kw.get("served", served),
                                quant=kw.get("quant"))
            picks = [a.argmax(-1) for a in alt]
            readings.update({f"{name}_{key}": v for key, v in
                             compare(alt, ref, picks).items()})
    ctx.info["reference_s"] = now() - t_ref
    limits = ctx.cell["limits"]
    record["complete"] = record["complete"] and not lost and bool(picked)
    record["checks"] = {
        name: {"value": readings.get(name, float("nan")),
               "limit": limits[name]}
        for name in ("logit_err", "served_gap")}
    return record


def compare(got, ref, picks) -> dict:
    """The widest logits gap (``rel_err``) and the widest gap of the
    picked tokens below the reference's best, over every position."""
    if not ref:
        return {}
    return {"logit_err": float(max(rel_err(g, r).max()
                                   for g, r in zip(got, ref))),
            "served_gap": float(max(pick_gap(r, p).max()
                                    for r, p in zip(ref, picks)))}


def plan_sample(ctx, requests) -> set:
    """Ids of the sampled requests: the one with the longest output and
    others drawn from the seed, until the traffic's ``sample_tokens``
    served tokens (or ``sample_max`` requests) are reached."""
    tr = ctx.traffic
    order = sorted(range(len(requests)), key=lambda i: -requests[i]["out"])
    rest = order[1:]
    ctx.rng(7).shuffle(rest)
    sample, tokens = set(), 0
    for i in [order[0]] + rest:
        if tokens >= tr["sample_tokens"] or len(sample) >= tr["sample_max"]:
            break
        sample.add(i)
        tokens += requests[i]["out"]
    return sample
