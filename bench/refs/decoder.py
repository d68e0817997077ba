"""Plain reference of a dense GQA decoder with per-request LoRA adapters.

Per layer, as the configuration runs it: RMSNorm; q/k/v projections (with
each request's own adapter y += (x A^T) B^T on the LoRA targets); RoPE on
q and k (rotate-half, theta from the configuration, positions 0..L-1);
causal softmax attention, query head h reading KV head h // (H / KVH);
the output projection; the residual; RMSNorm; a SwiGLU MLP
(silu(x Wg) * (x Wu)) Wd; the residual. Then a final RMSNorm and logits
against the tied embedding. Float32, products at HIGHEST, one layer at a
time (the bf16 weights are widened inside the layer), so that a stage of
a large model fits beside its own weights.

``served_logits`` runs it over each request's prompt and served tokens and
returns the logits at every served position. With ``quant="w8a8"`` it is
the control, the computation one precision step below the configuration's
bf16: every projection computed in int8, its weights per output channel
and its inputs per token (readings: ``"w8"``, the weights alone in int8;
``"fp8"``, weights and inputs in float8 e4m3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from refs.precision import einsum

ATTN = {"q_proj": "q", "k_proj": "k", "v_proj": "v", "o_proj": "o"}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (B, T, H, D), positions 0..T-1."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def quantize_int8(w, axis):
    """Symmetric int8 with one scale per slice along ``axis`` (the axis
    the scale is taken over: the input axis of a weight, the feature axis
    of an activation), returned dequantized in float32."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0,
                    1e-12)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def quantize_fp8(w, axis):
    """float8 e4m3 with one scale per slice along ``axis`` (the largest
    magnitude maps to 448, the format's largest), returned in float32."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0,
                    1e-30)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"w8a8": quantize_int8, "w8": quantize_int8, "fp8": quantize_fp8}


def _act(h, quant):
    return QUANT[quant](h, -1) if quant in ("w8a8", "fp8") else h


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "quant"))
def _layer(x, p, lora, *, heads, kv_heads, eps, theta, quant):
    mm = einsum("highest")
    b, t, d = x.shape
    hd = d // heads

    def w(name):
        m = p[name]
        return QUANT[quant](m, 0) if quant else m.astype(jnp.float32)

    def lin(h, name, target=None):
        y = mm("btd,de->bte", _act(h, quant), w(name))
        if target in lora:
            a, bb = lora[target]
            y = y + mm("btr,ber->bte", mm("btd,brd->btr", h, a), bb)
        return y

    n = _rms(x, p["ln1"], eps)
    q = _rope(lin(n, "q", "q_proj").reshape(b, t, heads, hd), theta)
    k = _rope(lin(n, "k", "k_proj").reshape(b, t, kv_heads, hd), theta)
    v = lin(n, "v", "v_proj").reshape(b, t, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v).reshape(b, t, d)
    x = x + lin(o, "o", "o_proj")
    n = _rms(x, p["ln2"], eps)
    h = jax.nn.silu(lin(n, "gate")) * lin(n, "up")
    return x + lin(h, "down")


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, final, embed, *, eps, quant):
    e = QUANT[quant](embed, 1) if quant else embed.astype(jnp.float32)
    return einsum("highest")("btd,vd->btv",
                             _act(_rms(x, final, eps), quant), e)


def forward(cfg, params, lora_rows, seqs, *, start=0, quant=None):
    """Logits (B, T - start, V) of ``seqs`` (B, T) int32 at positions from
    ``start``; ``lora_rows`` {target: (A (B, L, r, in), B (B, L, out, r))}
    in float32, one row a request. The embedding is looked up unquantized
    (a lookup is no product); the tied projection to the logits is."""
    lay = params["layers"]
    embed = params["embed"]
    x = embed.astype(jnp.float32)[jnp.asarray(seqs)]
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"], eps=cfg["rms_norm_eps"],
              theta=float(cfg["rope_theta"]), quant=quant)
    for layer in range(cfg["num_hidden_layers"]):
        p = {"ln1": lay["norm1"]["scale"][layer],
             "ln2": lay["norm2"]["scale"][layer]}
        for name in ("q", "k", "v", "o"):
            p[name] = lay["attn"][name]["w"][layer]
        for name in ("gate", "up", "down"):
            p[name] = lay["mlp"][name]["w"][layer]
        lr = {t_: (a[:, layer], b[:, layer]) for t_, (a, b)
              in lora_rows.items()}
        x = _layer(x, p, lr, **kw)
    return _logits(x[:, start:], params["final_norm"]["scale"], embed,
                   eps=cfg["rms_norm_eps"], quant=quant)


def served_logits(cfg, params, tenants, requests, *, quant=None):
    """``tenants`` {id: {target: (A (L, r, in), B (L, out, r))}} at the
    tenant's true rank; ``requests`` [(prompt, served tokens, tenant)].

    Returns one float32 array (served tokens, V) per request: the logits
    at each position that produced a served token, the prompt's last and
    each served token's but the last, teacher-forced on the served
    tokens."""
    lens = [len(p) + len(s) - 1 for p, s, _ in requests]
    t = max(lens)
    seqs = np.zeros((len(requests), t), np.int32)
    for i, (p, s, _) in enumerate(requests):
        full = np.concatenate([p, s[:-1]])
        seqs[i, :len(full)] = full
    rows = {tg: tuple(jnp.stack([jnp.asarray(tenants[r[2]][tg][j],
                                             jnp.float32)
                                 for r in requests]) for j in (0, 1))
            for tg in cfg["lora"]["targets"]}
    start = min(len(p) for p, _, _ in requests) - 1
    logits = np.asarray(forward(cfg, params, rows, seqs, start=start,
                                quant=quant))
    out = []
    for i, (p, s, _) in enumerate(requests):
        pos = np.arange(len(p) - 1, len(p) - 1 + len(s)) - start
        out.append(logits[i, pos])
    return out


def rel_err(got, ref):
    """Per position: the root mean square of ``got - ref`` over the
    vocabulary, over the spread (standard deviation) of ``ref``."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return (np.sqrt(np.mean((got - ref) ** 2, -1)) / np.std(ref, -1))


def pick_gap(ref, picked):
    """Per position: the reference's best logit minus its logit of the
    token ``picked``."""
    ref = np.asarray(ref, np.float64)
    return ref.max(-1) - ref[np.arange(len(picked)), np.asarray(picked)]
