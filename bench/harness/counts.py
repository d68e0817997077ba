"""Operation and byte counts from a configuration's shapes, kept with the
benchmark so that every PR counts the same way. Configurations are the
files under ``bench/configs`` (Hugging Face key names).

Conventions:
- a matrix product of (m, k) by (k, n) is 2*m*k*n operations;
- training counts what the algorithm requires: the frozen base's forward
  (2 per weight per token) and its activation gradients (2 more), the
  LoRA factors' forward, activation and weight gradients at each client's
  own rank, and attention; recomputation under remat is not counted;
- serving counts a forward per token at that token's context length, at
  the request's own adapter rank;
- bytes are what a decode step needs to read once: the weights, the live
  KV prefix of each active slot and the adapter columns in use.
"""
from __future__ import annotations

ADAPTER_SHAPES = {
    # target -> (fan in, fan out) as functions of (d, heads*hd, kv*hd, d_ff)
    "q_proj": lambda d, q, kv, f: (d, q),
    "k_proj": lambda d, q, kv, f: (d, kv),
    "v_proj": lambda d, q, kv, f: (d, kv),
    "o_proj": lambda d, q, kv, f: (q, d),
    "up_proj": lambda d, q, kv, f: (d, f),
    "gate_proj": lambda d, q, kv, f: (d, f),
    "down_proj": lambda d, q, kv, f: (f, d),
}


def _dims(c: dict):
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    hd = c.get("head_dim") or d // heads
    kvh = c.get("num_key_value_heads", heads)
    return d, heads * hd, kvh * hd, c["intermediate_size"]


def layer_params(c: dict) -> int:
    """Weights of one transformer layer (biases and norm scales included)."""
    d, q, kv, f = _dims(c)
    attn = d * q + 2 * d * kv + q * d
    if c.get("qkv_bias"):
        attn += q + 2 * kv
    gated = c.get("hidden_act") in ("silu", "swiglu")
    mlp = (3 if gated else 2) * d * f
    return attn + mlp + 2 * d


def stack_params(c: dict) -> int:
    return c["num_hidden_layers"] * layer_params(c)


def embed_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def adapter_columns(c: dict) -> int:
    """sum over LoRA targets of (fan in + fan out), one layer."""
    dims = _dims(c)
    return sum(sum(ADAPTER_SHAPES[t](*dims)) for t in c["lora"]["targets"])


# -- rounds (encoder fine-tuning) -------------------------------------------

def train_ops_per_token(c: dict, rank: int) -> float:
    """Required operations per training token for a client of ``rank``."""
    d, q, _, _ = _dims(c)
    t, layers = c["tokens_per_item"], c["num_hidden_layers"]
    base = 4.0 * stack_params(c)                 # forward + activation grads
    frontend = 2.0 * d * d                       # input projection, forward
    attn = 12.0 * t * q * layers                 # QK^T and PV, fwd + 2x bwd
    lora = 6.0 * rank * adapter_columns(c) * layers
    head = 4.0 * d * c["num_labels"] / t         # logits at position 0 only
    return base + frontend + attn + lora + head


def round_ops(c: dict, ranks, items_per_client: int) -> float:
    """Required operations of one round's local training."""
    t = c["tokens_per_item"]
    return sum(items_per_client * t * train_ops_per_token(c, r)
               for r in ranks)


# -- serving (decoder forward) ----------------------------------------------

def forward_ops(c: dict, context: int, rank: int) -> float:
    """Forward operations for one token attending to ``context`` keys."""
    _, q, _, _ = _dims(c)
    layers = c["num_hidden_layers"]
    return (2.0 * stack_params(c) + 4.0 * context * q * layers
            + 2.0 * rank * adapter_columns(c) * layers
            + 2.0 * embed_params(c))


def prompt_ops(c: dict, prompt_len: int, rank: int) -> float:
    """Forward operations of a causal prefill of ``prompt_len`` tokens."""
    return sum(forward_ops(c, i + 1, rank) for i in range(prompt_len))


def kv_bytes_per_token(c: dict) -> int:
    _, _, kv, _ = _dims(c)
    return 2 * c["num_hidden_layers"] * kv * c["bytes_per_param"]


def weight_bytes(c: dict) -> int:
    return (stack_params(c) + embed_params(c)
            + c["hidden_size"]) * c["bytes_per_param"]


def adapter_bytes(c: dict, rank: int) -> int:
    return (rank * adapter_columns(c) * c["num_hidden_layers"]
            * c["bytes_per_param"])


def decode_step_bound(c: dict, contexts, ranks, pages, peak: dict) -> float:
    """Least seconds one decode step can take on a chip with ``peak``:
    ``contexts``/``ranks`` per active slot (keys attended, adapter rank),
    ``pages`` the ranks of the distinct adapters those slots use."""
    ops = sum(forward_ops(c, ctx, r) for ctx, r in zip(contexts, ranks))
    nbytes = (weight_bytes(c) + kv_bytes_per_token(c) * sum(contexts)
              + sum(adapter_bytes(c, r) for r in pages))
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def served_ops(c: dict, steps, prompt_len: int) -> float:
    """Operations of the tokens a serving window produced: each admitted
    prompt's causal prefill and each generated token's forward at its own
    context and adapter rank. Padded slots do not count."""
    per_rank: dict = {}
    ops = 0.0
    for s in steps:
        if s["kind"] == "admit":
            for r in s["ranks"]:
                if r not in per_rank:
                    per_rank[r] = prompt_ops(c, prompt_len, r)
                ops += per_rank[r]
        else:
            ops += sum(forward_ops(c, x, r)
                       for x, r in zip(s["contexts"], s["ranks"]))
    return ops
