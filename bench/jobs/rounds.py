"""Federated raFLoRA rounds through the program's round engine.

Set-up makes the data (a class-clustered patch-embedding task, on the
device from the seed, then held on the host as the program's data pipeline
expects) and the weights (on the device, one jitted call), builds ONE
``FederatedLoRA`` server on the program's default round engine and
aggregation backend, and drives it through its first rounds (the rounds
the reference follows). A second server from the same factors runs one
round of the other aggregation kind (every sampled client below r_max, so
the uncovered levels keep the global's components, or every client at
r_max). The window then runs whole rounds on the first server until
``--seconds`` have passed; each round ends with ``block_until_ready`` on
the landed global factors.

After the window the reference re-runs the recorded rounds (same clients,
ranks, sample counts and batches) from the same initial factors. Per
followed round it reads the relative gap of the mean client loss and, by
the worst adapter (target x layer), the gap between the program's and the
reference's norm of the adapter product's change from the initial
factors, over the larger of the reference's norm of that adapter and of
the median adapter. Compared (``loss_gap``, ``change_gap``): the larger
of the first followed round's and the other kind's. The later rounds'
are readings only: after each truncated SVD, near-equal singular values
at the r_max cut turn round-off into another subspace, so their gaps
grow from round to round and swing from seed to seed (PERF.md).
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

from harness.clock import now
from refs.vit_rounds import TARGETS, run_rounds, unpack

LORA_B_STD = 0.005


def model_config(cfg: dict):
    """The program's ModelConfig for this configuration file."""
    from repro.configs import get_config
    from repro.configs.base import FrontendConfig
    base = get_config(cfg["program"]["arch"])
    heads = cfg["num_attention_heads"]
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=heads, num_kv_heads=heads,
        head_dim=cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["num_labels"],
        qkv_bias=cfg["qkv_bias"], rms_norm_eps=cfg["rms_norm_eps"],
        lora_targets=tuple(cfg["lora"]["targets"]),
        frontend=FrontendConfig(kind="vision", embed_dim=cfg["hidden_size"],
                                tokens_per_item=cfg["tokens_per_item"]))


def make_weights(jax, shapes, key):
    """Every leaf of the program's parameter tree from ``key``, in one
    jitted call: weights N(0, 1/fan_in), biases N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2), LoRA A N(0, 1/r) and LoRA B N(0, LORA_B_STD^2): a
    global adapter that earlier rounds have landed, so that the levels no
    sampled client reaches keep components that are not zero."""
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(path, s, k):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(k, s.shape, jnp.float32)
        if name == "w":
            z = z * s.shape[-2] ** -0.5
        elif name == "b":
            z = 0.02 * z
        elif name == "scale":
            z = 1.0 + 0.1 * z
        elif name == "lora_a":
            z = z * s.shape[-2] ** -0.5
        elif name == "lora_b":
            z = LORA_B_STD * z
        else:
            z = z * s.shape[-1] ** -0.5
        return z.astype(s.dtype)

    @jax.jit
    def gen(key):
        return [leaf(p, s, jax.random.fold_in(key, i))
                for i, (p, s) in enumerate(flat)]

    return jax.tree_util.tree_unflatten(treedef, gen(key))


def make_clients(ctx):
    """Ranks, sample counts, labels and items of every client. The sizes
    and ranks are one fixed multiset, which the seed only shuffles, so that
    every seed does the same work."""
    jax = ctx.jax
    import jax.numpy as jnp
    cfg, tr = ctx.cfg, ctx.traffic
    k = tr["num_clients"]
    levels = cfg["lora"]["rank_levels"]
    rng = ctx.rng(1)
    ranks = np.array([levels[i % len(levels)] for i in range(k)])
    rng.shuffle(ranks)
    lo, hi = tr["items_per_client"]
    sizes = np.round(np.linspace(lo, hi, k)).astype(int)
    rng.shuffle(sizes)
    classes = cfg["num_labels"]
    labels, modes = [], []
    for c in range(k):
        own = rng.choice(classes, size=tr["labels_per_client"],
                         replace=False)
        labels.append(own[np.arange(sizes[c]) % len(own)])
        modes.append(rng.integers(0, tr["modes_per_class"], sizes[c]))
    labels, modes = np.concatenate(labels), np.concatenate(modes)
    t, d = cfg["tokens_per_item"], cfg["hidden_size"]

    @jax.jit
    def items(key, lab, mod):
        k1, k2 = jax.random.split(key)
        centers = jax.random.normal(
            k1, (classes, tr["modes_per_class"], t, d), jnp.float32)
        return (centers[lab, mod]
                + tr["noise"] * jax.random.normal(k2, (len(lab), t, d)))

    x = np.asarray(items(ctx.key(2), jnp.asarray(labels), jnp.asarray(modes)))
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    shards = [np.arange(o, o + n) for o, n in zip(offsets, sizes)]
    return ranks, shards, x, labels.astype(np.int32)


def to_batch(x, labels, ids, t):
    """The program's classification batch: label read at position 0."""
    b = len(ids)
    targets = np.zeros((b, t), np.int32)
    targets[:, 0] = labels[ids]
    mask = np.zeros((b, t), np.float32)
    mask[:, 0] = 1.0
    return {"embeds": x[ids], "targets": targets, "loss_mask": mask}


def _host(tree):
    import jax
    return jax.tree.map(lambda a: None if a is None else np.asarray(a),
                        tree, is_leaf=lambda a: a is None)


def lora_config(cfg: dict):
    from repro.configs.base import LoRAConfig
    levels = tuple(cfg["lora"]["rank_levels"])
    return LoRAConfig(rank_levels=levels,
                      rank_probs=tuple([1 / len(levels)] * len(levels)))


def make_model(cfg: dict):
    from repro.models.transformer import Model
    prog = cfg["program"]
    return Model(model_config(cfg), lora_config(cfg), dtype=np.float32,
                 remat=prog["remat"], block_q=prog["block_q"],
                 block_kv=prog["block_kv"])


def build(ctx, model, ranks, shards, x, labels, params, record=None):
    """One server on the program's default round engine and backend."""
    from repro.configs.base import FLConfig
    from repro.federation.server import FederatedLoRA
    from repro.federation.topology import ClientRegistry
    cfg, tr = ctx.cfg, ctx.traffic
    levels = tuple(cfg["lora"]["rank_levels"])
    steps, bsz = tr["local_steps"], tr["batch_size"]
    t = cfg["tokens_per_item"]
    fl = FLConfig(aggregator="raflora", num_clients=len(ranks),
                  participation=tr["clients_per_round"] / len(ranks),
                  num_rounds=10 ** 9, local_batch_size=bsz,
                  learning_rate=tr["learning_rate"], lr_schedule="constant",
                  weight_decay=0.0, seed=ctx.seed)
    registry = ClientRegistry(ranks=np.asarray(ranks, int),
                              shards=list(shards), rank_levels=levels)

    def batch_fn(cid, rng):
        with ctx.spans("bench.batch"):
            ids = shards[cid][rng.permutation(len(shards[cid]))[
                :steps * bsz]]
            if record is not None:
                record.append((int(cid), ids))
            return [to_batch(x, labels, ids[s * bsz:(s + 1) * bsz], t)
                    for s in range(steps)]

    return FederatedLoRA(model, fl, lora_config(cfg), registry, batch_fn,
                         base_params=params)


def adapter_factors(lora_tree, targets):
    """{target: (A (L, r, in), B (L, out, r))} from a program lora tree."""
    lay = lora_tree["layers"]
    return {t_: (np.asarray(lay[TARGETS[t_][0]][TARGETS[t_][1]]["lora_a"]),
                 np.asarray(lay[TARGETS[t_][0]][TARGETS[t_][1]]["lora_b"]))
            for t_ in targets}


def change_norms(f0, f1):
    """{(target, layer): ||B1 A1 - B0 A0||_F} in float64."""
    out = {}
    for t_ in f0:
        a0, b0 = (np.asarray(z, np.float64) for z in f0[t_])
        a1, b1 = (np.asarray(z, np.float64) for z in f1[t_])
        for layer in range(a0.shape[0]):
            d = b1[layer] @ a1[layer] - b0[layer] @ a0[layer]
            out[(t_, layer)] = float(np.linalg.norm(d))
    return out


def norm_gap(got: dict, want: dict, keep=None) -> float:
    """Worst |got - want| over max(want, median want), by leaf."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keys)


def gaps(losses, landed, ref, lora0, keep) -> dict:
    """Per followed round: the loss's relative gap, and the change gap of
    the worst and of the median adapter."""
    out = {"loss_gaps": [], "change_gaps": [], "median_change_gaps": []}
    for loss, got, r in zip(losses, landed, ref):
        out["loss_gaps"].append(abs(loss - r["loss"]) / abs(r["loss"]))
        g, w = change_norms(lora0, got), change_norms(lora0, r["global"])
        out["change_gaps"].append(norm_gap(g, w, keep))
        med = float(np.median([w[k] for k in keep]))
        out["median_change_gaps"].append(float(np.median(
            [abs(g[k] - w[k]) / max(w[k], med) for k in keep])))
    return out


def run(ctx) -> dict:
    jax = ctx.jax
    cfg, tr = ctx.cfg, ctx.traffic
    targets = cfg["lora"]["targets"]
    levels = cfg["lora"]["rank_levels"]
    r_max = max(levels)
    precision = jax.default_matmul_precision(cfg["matmul_precision"])
    t0 = now()
    ranks, shards, x, labels = make_clients(ctx)
    model = make_model(cfg)
    # the program's parameter layout, filled by the benchmark's generator
    params = make_weights(jax, model.param_shapes(), ctx.key(3))
    jax.block_until_ready(params)
    calls: list = []
    server = build(ctx, model, ranks, shards, x, labels, params, calls)
    ctx.setup["data_weights_s"] = now() - t0

    from repro.core.lora import split_lora
    lora0 = adapter_factors(_host(split_lora(params)[1]), targets)
    follow = tr["reference_rounds"]
    losses, followed, landed = [], [], []
    with precision:
        for _ in range(follow):
            st = server.run_round()
            jax.block_until_ready(server.global_lora)
            losses.append(st.mean_client_loss)
            followed.append((list(st.ranks), list(st.clients)))
            landed.append(adapter_factors(_host(server.global_lora),
                                          targets))
    # the other aggregation program: a round whose sampled clients all
    # stop below r_max keeps the uncovered levels' global components, one
    # whose clients reach r_max keeps none. One round of the kind the first
    # followed round was not, on a second server from the same factors, so
    # that both programs are compiled before the window and both are
    # compared, and every seed's set-up does the same work.
    covered = max(followed[0][0]) >= r_max
    aux_calls: list = []
    aux = build(ctx, model, [min(levels) if covered else r_max]
                * len(ranks), shards, x, labels, params, aux_calls)
    with precision:
        st = aux.run_round()
        jax.block_until_ready(aux.global_lora)
    other = {"loss": st.mean_client_loss,
             "round": (list(st.ranks), list(st.clients)),
             "landed": adapter_factors(_host(aux.global_lora), targets)}
    del aux
    gc.collect()

    rounds = []
    with precision, ctx.window() as w:
        while True:
            with ctx.spans("bench.round"):
                st = server.run_round()
                jax.block_until_ready(server.global_lora)
            t1 = now()
            rounds.append({"end": t1 - w.window_start,
                           "ranks": [int(r) for r in st.ranks],
                           "loss": st.mean_client_loss})
            if t1 - w.window_start >= ctx.seconds:
                break
    mem = ctx.memory_peak()
    del server, model
    gc.collect()

    # -- reference over the followed rounds ------------------------------
    t_ref = now()
    base, _ = unpack(_host(params), targets)
    plan = plan_rounds(tr, shards, followed, calls)
    plan_other = plan_rounds(tr, shards, [other["round"]], aux_calls)

    def follow_from(rounds, **kw):
        return run_rounds(cfg, base, lora0, rounds,
                          lambda ids: (x[ids], labels[ids]),
                          lr=tr["learning_rate"], levels=levels,
                          targets=targets, **kw)

    def follow_both(**kw):
        return follow_from(plan, **kw), follow_from(plan_other, **kw)[0]

    ref, ref_other = follow_both(precision=cfg["matmul_precision"])
    # leaves the reference's first round leaves (all but) unmoved are
    # left out: under a thousandth of the median leaf's change
    first = change_norms(lora0, ref[0]["global"])
    med = float(np.median(list(first.values())))
    keep = {k for k, v in first.items() if v >= 1e-3 * med}
    got = gaps(losses + [other["loss"]], landed + [other["landed"]],
               ref + [ref_other], lora0, keep)
    ctx.info["reference_s"] = now() - t_ref
    readings = {"losses": losses, "ref_losses": [r["loss"] for r in ref],
                "other_kind": "kept" if covered else "covered",
                "left_out": len(first) - len(keep), **got}
    if ctx.mode == "calibrate":
        # the control (the reference one precision step down) and the
        # half-batch fault, both put in the program's place
        for name, kw in (("control", {"precision": "high"}),
                         ("half_batch", {"batch_frac": 0.5,
                                         "precision":
                                             cfg["matmul_precision"]})):
            alt, alt_other = follow_both(**kw)
            alt = alt + [alt_other]
            alt_gaps = gaps([a["loss"] for a in alt],
                            [a["global"] for a in alt], ref + [ref_other],
                            lora0, keep)
            readings.update({f"{name}_{k}": v for k, v in alt_gaps.items()})
    limits = ctx.cell["limits"]
    return {
        "job": "rounds",
        "window_s": rounds[-1]["end"],
        "rounds": rounds,
        "round_ops": [_round_ops(cfg, tr, r["ranks"]) for r in rounds],
        "memory_peak_bytes": mem,
        "attempted": len(rounds),
        "failed": sum(1 for r in rounds if not np.isfinite(r["loss"])),
        "complete": True,
        "readings": readings,
        "checks": {
            "loss_gap": {"value": max(got["loss_gaps"][0],
                                      got["loss_gaps"][-1]),
                         "limit": limits["loss_gap"]},
            "change_gap": {"value": max(got["change_gaps"][0],
                                        got["change_gaps"][-1]),
                           "limit": limits["change_gap"]}},
    }


def plan_rounds(tr, shards, followed, calls):
    """The reference's plan of the followed rounds: per client its rank,
    sample count and the batches the program drew (in ``calls``)."""
    m, bsz = tr["clients_per_round"], tr["batch_size"]
    plan = []
    for r_i, (rks, clients) in enumerate(followed):
        entries = calls[r_i * m:(r_i + 1) * m]
        if [c for c, _ in entries] != clients:
            raise RuntimeError(f"round {r_i}: batches drawn for "
                               f"{[c for c, _ in entries]}, not {clients}")
        plan.append({"clients": [
            (rk, len(shards[c]), [ids[s * bsz:(s + 1) * bsz]
                                  for s in range(tr["local_steps"])])
            for rk, (c, ids) in zip(rks, entries)]})
    return plan


def _round_ops(cfg, tr, ranks):
    from harness.counts import round_ops
    return round_ops(cfg, ranks, tr["local_steps"] * tr["batch_size"])
