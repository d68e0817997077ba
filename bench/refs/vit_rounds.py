"""Plain reference of raFLoRA rounds on a ViT-style encoder with LoRA.

The model, as the configuration runs it (departures from the published
ViT are listed in the configuration file): a linear input projection of
precomputed patch embeddings, ``num_hidden_layers`` pre-norm blocks
(RMSNorm; bidirectional multi-head attention with q/k/v biases; a GELU
MLP in its tanh form), a final RMSNorm and a linear head read at position
0. LoRA (alpha = rank, so scale 1) on the configured targets: a target
y = x W + b becomes y + (x A^T) B^T with A (r, in) and B (out, r).

A round (raFLoRA, arXiv:2602.13486 Eq. 8 and Algorithm 1): every sampled
client k starts from the global factors truncated to its rank r_k, runs
AdamW (b1 0.9, b2 0.999, eps 1e-8, no weight decay) on its batches, and
uploads its factors; the server forms, per adapter and layer,

    dW = sum_k B_k diag(w_k) A_k  (+ sum over uncovered i of B_g[:, i] A_g[i])

with w_k[i] = n_k / N_h(i) if r_k >= h(i) else 0, h(i) the smallest rank
level >= i + 1 and N_h the samples of the clients with r_k >= h; indices
of a level that no sampled client reaches keep the global's component.
The new global factors are the rank-r_max truncated SVD of dW:
B = U[:, :r], A = S[:r] V[:r]^T, in float64 on the host
(``truncated_svd``).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from refs.precision import einsum

TARGETS = {"q_proj": ("attn", "q"), "k_proj": ("attn", "k"),
           "v_proj": ("attn", "v"), "o_proj": ("attn", "o"),
           "up_proj": ("mlp", "up"), "down_proj": ("mlp", "down"),
           "gate_proj": ("mlp", "gate")}


def unpack(params: dict, targets) -> tuple:
    """(base, lora) from the benchmark-made weight tree (the layout the
    program consumes): base holds plain arrays, lora {target: (A, B)}
    stacked over layers, A (L, r, in), B (L, out, r)."""
    lay = params["layers"]
    base = {"frontend": params["frontend_proj"]["w"],
            "head": params["lm_head"]["w"],
            "final": params["final_norm"]["scale"],
            "ln1": lay["norm1"]["scale"], "ln2": lay["norm2"]["scale"]}
    for name in ("q", "k", "v", "o"):
        base[name + "_w"] = lay["attn"][name]["w"]
        if "b" in lay["attn"][name]:
            base[name + "_b"] = lay["attn"][name]["b"]
    for name in ("up", "down"):
        base[name + "_w"] = lay["mlp"][name]["w"]
    lora = {t: (lay[TARGETS[t][0]][TARGETS[t][1]]["lora_a"],
                lay[TARGETS[t][0]][TARGETS[t][1]]["lora_b"])
            for t in targets}
    return base, lora


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def make_loss(cfg: dict, precision: str, targets):
    mm = einsum(precision)
    heads = cfg["num_attention_heads"]
    eps = cfg["rms_norm_eps"]
    short = {t: TARGETS[t][1] for t in targets}

    def lin(x, p, lp, name):
        y = mm("btd,de->bte", x, p[name + "_w"])
        if name + "_b" in p:
            y = y + p[name + "_b"]
        if name in lp:
            a, b = lp[name]
            y = y + mm("btr,er->bte", mm("btd,rd->btr", x, a), b)
        return y

    def loss(lora, base, x, labels):
        h = mm("btd,de->bte", x, base["frontend"])
        n_b, t, d = h.shape
        hd = d // heads
        stacked = {k: v for k, v in base.items()
                   if k not in ("frontend", "head", "final")}
        stacked["lora"] = {short[k]: v for k, v in lora.items()}

        def layer(h, p):
            lp = p["lora"]
            n = _rms(h, p["ln1"], eps)
            q, k, v = (lin(n, p, lp, s).reshape(n_b, t, heads, hd)
                       for s in ("q", "k", "v"))
            s = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            h = h + lin(o.reshape(n_b, t, d), p, lp, "o")
            n = _rms(h, p["ln2"], eps)
            h = h + lin(_gelu_tanh(lin(n, p, lp, "up")), p, lp, "down")
            return h, None

        # recompute each layer in the backward pass: the same numbers, and
        # the batch's activations fit beside the data on one chip
        h, _ = jax.lax.scan(jax.checkpoint(layer), h, stacked)
        z = _rms(h[:, 0], base["final"], eps)
        logits = mm("bd,dc->bc", z, base["head"])
        gold = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)

    return loss


def make_step(cfg: dict, precision: str, targets, lr: float):
    loss = make_loss(cfg, precision, targets)
    b1, b2, eps = 0.9, 0.999, 1e-8

    # the step number is static, so the bias corrections 1 - b^t are
    # worked out in double precision on the host: in float32 one rounding
    # of b2^t (near 1) is already 6e-5 of 1 - b2^t
    @functools.partial(jax.jit, static_argnums=(3,))
    def step(lora, m, v, t, base, x, labels):
        val, g = jax.value_and_grad(loss)(lora, base, x, labels)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        lora = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t))
            / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), lora, m, v)
        return lora, m, v, val

    return step


def truncate(lora, rank):
    """Zero every component at or beyond ``rank``."""
    return {k: (a * (jnp.arange(a.shape[-2]) < rank)[:, None],
                b * (jnp.arange(b.shape[-1]) < rank))
            for k, (a, b) in lora.items()}


def omega(ranks, n_k, levels):
    levels = sorted(levels)
    r_max = levels[-1]
    ranks, n_k = np.asarray(ranks), np.asarray(n_k, np.float64)
    w = np.zeros((len(ranks), r_max))
    uncovered = np.zeros(r_max)
    for i in range(r_max):
        h = min(lv for lv in levels if lv >= i + 1)
        members = ranks >= h
        if n_k[members].sum() > 0:
            w[members, i] = n_k[members] / n_k[members].sum()
        else:
            uncovered[i] = 1.0
    return w, uncovered


def truncated_svd(u_c, v_c, r):
    """Rank-``r`` truncated SVD of ``u_c @ v_c`` (u_c (out, K), v_c (K, in),
    float64), as (A (r, in), B (out, r)) with B = U[:, :r] and
    A = S[:r] V[:r]^T. The product is never formed: with u_c = Q1 R1 and
    v_c^T = Q2 R2, u_c v_c = Q1 (R1 R2^T) Q2^T, and the SVD of the (K, K)
    core gives the spectrum. Exact in exact arithmetic, and in float64 far
    below the float32 round-off the comparison reads."""
    if not (np.isfinite(u_c).all() and np.isfinite(v_c).all()):
        raise FloatingPointError("non-finite client factors in the "
                                 "reference's aggregation")
    q1, r1 = np.linalg.qr(u_c)
    q2, r2 = np.linalg.qr(v_c.T)
    w, s, zt = np.linalg.svd(r1 @ r2.T)
    b = q1 @ w[:, :r]
    a = s[:r, None] * (q2 @ zt[:r].T).T
    if len(s) < r:                      # fewer columns than r: pad with 0
        b = np.pad(b, ((0, 0), (0, r - len(s))))
        a = np.pad(a, ((0, r - len(s)), (0, 0)))
    return a, b


def aggregate(client_loras, glob, ranks, n_k, levels):
    """New global {target: (A, B)} from client factors (host float64)."""
    w, uncovered = omega(ranks, n_k, levels)
    r = max(levels)
    out = {}
    for tgt in glob:
        a_s = np.stack([np.asarray(c[tgt][0], np.float64)
                        for c in client_loras])          # (M, L, r, in)
        b_s = np.stack([np.asarray(c[tgt][1], np.float64)
                        for c in client_loras])          # (M, L, out, r)
        g_a, g_b = (np.asarray(x, np.float64) for x in glob[tgt])
        a_new, b_new = [], []
        for layer in range(a_s.shape[1]):
            # dW = sum_k B_k diag(w_k) A_k + sum_uncovered B_g[:, i] A_g[i]
            u_c = np.concatenate([b_s[k, layer] * w[k]
                                  for k in range(len(b_s))]
                                 + [g_b[layer] * uncovered], axis=1)
            v_c = np.concatenate([a_s[k, layer] for k in range(len(a_s))]
                                 + [g_a[layer]], axis=0)
            a, b = truncated_svd(u_c, v_c, r)
            a_new.append(a)
            b_new.append(b)
        out[tgt] = (np.stack(a_new).astype(np.float32),
                    np.stack(b_new).astype(np.float32))
    return out


_STEPS: dict = {}        # one jitted step per configuration and precision


def run_rounds(cfg, base, lora0, rounds, fetch, *, lr, levels, targets,
               precision="highest", batch_frac=1.0):
    """Follow ``rounds`` (each {"clients": [(rank, n_k, [batch ids...])]})
    from the global factors ``lora0``. ``fetch(ids)`` returns the batch
    (x, labels) as host arrays. ``batch_frac`` < 1 keeps only that share
    of each batch (a fault read in the reference).

    Returns per round: mean client loss, the clients' first-round factors
    and the global factors after the round."""
    key = (json.dumps(cfg, sort_keys=True), precision, tuple(targets), lr)
    if key not in _STEPS:
        _STEPS[key] = make_step(cfg, precision, targets, lr)
    step = _STEPS[key]
    base = jax.tree.map(jnp.asarray, base)
    glob = {k: (np.asarray(a, np.float32), np.asarray(b, np.float32))
            for k, (a, b) in lora0.items()}
    out = []
    for rnd in rounds:
        client_loras, losses = [], []
        g_dev = {k: (jnp.asarray(a), jnp.asarray(b))
                 for k, (a, b) in glob.items()}
        for rank, _, batches in rnd["clients"]:
            lora = truncate(g_dev, rank)
            m = jax.tree.map(jnp.zeros_like, lora)
            v = jax.tree.map(jnp.zeros_like, lora)
            t = 0
            val = None
            for ids in batches:
                x, y = fetch(ids)
                keep = max(1, int(round(len(ids) * batch_frac)))
                t += 1
                lora, m, v, val = step(lora, m, v, t, base,
                                          jnp.asarray(x[:keep]),
                                          jnp.asarray(y[:keep]))
            client_loras.append(jax.tree.map(np.asarray, lora))
            losses.append(float(val))
        glob = aggregate(client_loras, glob, [c[0] for c in rnd["clients"]],
                         [c[1] for c in rnd["clients"]], levels)
        out.append({"loss": float(np.mean(losses)),
                    "clients": client_loras, "global": glob})
    return out
