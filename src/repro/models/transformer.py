"""Unified model assembly for all assigned architectures.

One code path builds dense / moe / ssm / hybrid / vlm / audio models from a
``ModelConfig``: per-layer parameters are stacked on a leading axis and the
layer stack runs under ``jax.lax.scan`` (with configurable remat policy), so
96-layer 340B-class graphs compile with bounded HLO size.

Entry points
  Model.init(key)                     -> params pytree (LoRA factors inline)
  Model.train_loss(params, batch)     -> (loss, metrics)
  Model.prefill(params, batch)        -> (logits, cache)
  Model.decode_step(params, batch, cache) -> (logits, cache)
  Model.init_cache(batch, max_len)    -> zeroed cache pytree
  Model.param_shapes() / cache_shapes -> ShapeDtypeStructs (no allocation)

LoRA: adapters sized r_max live inline in the params ( ``lora_a``/``lora_b``
leaves); a client of rank r_k runs with ``lora_rank=r_k`` which statically
truncates the factors (Algorithm 1 line 4 of the paper).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_BIDIR, ATTN_SLIDING, LoRAConfig,
                                ModelConfig)
from repro.models.layers.attention import blockwise_attention, decode_attention
from repro.models.layers.dense import dense_apply, dense_init, lora_init
from repro.models.layers.mla import mla_attention, mla_decode, mla_init
from repro.models.layers.mlp import mlp_apply, mlp_init
from repro.models.layers.moe import moe_apply, moe_init
from repro.models.layers.norms import rms_norm, rms_norm_init
from repro.models.layers.rope import apply_mrope, apply_rope
from repro.models.layers.ssd import (ssd_dims, ssd_init, ssd_mixer_apply,
                                     ssd_mixer_decode)

Params = Dict[str, Any]


def _lora_ranks_for(cfg: ModelConfig, lora: Optional[LoRAConfig]) -> dict:
    if lora is None:
        return {}
    return {t: lora.r_max for t in cfg.lora_targets}


# ---------------------------------------------------------------------------
# Per-layer blocks
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig, lora: Optional[LoRAConfig] = None,
                 *, dtype=jnp.float32, remat: bool = True,
                 use_kernels: bool = False,
                 block_q: int = 512, block_kv: int = 1024,
                 moe_impl: str = "tp", mesh=None, batch_axes=("data",),
                 residual_sharding=None, logits_sharding=None,
                 attn_q_sharding=None, moe_capacity_factor: float = 0.0,
                 attn_repeat_kv: bool = False, bf16_scores: bool = False):
        self.cfg = cfg
        self.lora = lora
        self.dtype = dtype
        self.remat = remat
        self.use_kernels = use_kernels
        self.block_q = block_q
        self.block_kv = block_kv
        # distribution hooks (launch/dryrun wires these; None on CPU)
        self.moe_impl = moe_impl          # "tp" (GSPMD) | "ep" (shard_map)
        self.mesh = mesh
        self.batch_axes = batch_axes
        self.residual_sharding = residual_sharding  # NamedSharding | None
        self.logits_sharding = logits_sharding      # NamedSharding | None
        # Megatron-style: shard q heads over "model" so scores never psum
        self.attn_q_sharding = attn_q_sharding      # NamedSharding | None
        # >0: capacity-grouped EP dispatch (§Perf iteration A)
        self.moe_capacity_factor = moe_capacity_factor
        # repeat KV heads to full MHA so the head axis shards cleanly when
        # num_heads doesn't tile the model axis (§Perf: kills score psums)
        self.attn_repeat_kv = attn_repeat_kv
        self.bf16_scores = bf16_scores
        self.lora_ranks = _lora_ranks_for(cfg, lora)
        # layer grouping for scan: llama4 interleaves dense/moe with period 2
        moe = cfg.moe
        self.group_size = moe.moe_layer_period if (moe and moe.moe_layer_period > 1) else 1
        assert cfg.num_layers % self.group_size == 0
        self.num_groups = cfg.num_layers // self.group_size

    # -- init ---------------------------------------------------------------

    def _layer_init(self, key, layer_idx: int) -> Params:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 8)
        p: Params = {"norm1": rms_norm_init(cfg.d_model, dtype=dt)}
        lr = self.lora_ranks
        if cfg.kind == "ssm":
            p["ssm"] = ssd_init(ks[0], cfg.d_model, cfg.ssm, lora_ranks=lr,
                                dtype=dt)
            return p  # mamba2 block: norm + mixer + residual only
        # attention mixer
        if cfg.mla is not None:
            p["attn"] = mla_init(ks[0], cfg.d_model, cfg.num_heads, cfg.mla,
                                 lora_ranks=lr, dtype=dt)
        else:
            hd = cfg.resolved_head_dim
            q_out = cfg.num_heads * hd
            kv_out = cfg.num_kv_heads * hd
            p["attn"] = {
                "q": dense_init(ks[0], cfg.d_model, q_out, bias=cfg.qkv_bias,
                                dtype=dt, lora_rank=lr.get("q_proj", 0)),
                "k": dense_init(ks[1], cfg.d_model, kv_out, bias=cfg.qkv_bias,
                                dtype=dt, lora_rank=lr.get("k_proj", 0)),
                "v": dense_init(ks[2], cfg.d_model, kv_out, bias=cfg.qkv_bias,
                                dtype=dt, lora_rank=lr.get("v_proj", 0)),
                "o": dense_init(ks[3], q_out, cfg.d_model, dtype=dt,
                                lora_rank=lr.get("o_proj", 0)),
            }
        if cfg.kind == "hybrid":
            p["ssm"] = ssd_init(ks[4], cfg.d_model, cfg.ssm, lora_ranks=lr,
                                dtype=dt)
        # FFN
        p["norm2"] = rms_norm_init(cfg.d_model, dtype=dt)
        if cfg.moe is not None and cfg.moe.is_moe_layer(layer_idx):
            p["moe"] = moe_init(ks[5], cfg.d_model, cfg.moe, cfg.activation,
                                lora_ranks=lr, dtype=dt)
        else:
            d_ff = cfg.d_ff
            if cfg.moe is not None:  # llama4 dense layers: 2x expert width
                d_ff = cfg.moe.expert_d_ff * 2
            p["mlp"] = mlp_init(ks[5], cfg.d_model, d_ff, cfg.activation,
                                lora_ranks=lr, dtype=dt)
        return p

    def _group_init(self, key, group_idx: int) -> Params:
        if self.group_size == 1:
            return self._layer_init(key, group_idx)
        ks = jax.random.split(key, self.group_size)
        return {f"sub{i}": self._layer_init(ks[i], group_idx * self.group_size + i)
                for i in range(self.group_size)}

    def init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        params: Params = {
            "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                      * cfg.d_model ** -0.5).astype(dt),
            "final_norm": rms_norm_init(cfg.d_model, dtype=dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                           dtype=dt)
        gks = jax.random.split(k_layers, self.num_groups)
        # groups share one structure (the layer scan stacks them), so one
        # group's init vmapped over the group keys gives the same values as
        # initialising group by group, and a jitted init compiles one group
        # instead of num_groups (minutes saved at 28+ layers on a TPU)
        params["layers"] = jax.vmap(lambda k: self._group_init(k, 0))(gks)
        if self.lora is not None and self.lora.variant != "lora":
            params = self._apply_peft_variant(params)
        if cfg.frontend.kind != "none":
            # projector stub: precomputed embeddings enter at embed_dim ->
            # identity-shaped projector kept trainable-frozen
            params["frontend_proj"] = dense_init(
                jax.random.fold_in(key, 11), cfg.frontend.embed_dim,
                cfg.d_model, dtype=dt)
        return params

    def _apply_peft_variant(self, params: Params) -> Params:
        """Table 5 variants: DoRA adds trainable magnitudes next to every
        adapter; QLoRA fake-quantizes the frozen base of adapted layers."""
        from repro.models.layers.dense import (dora_magnitude_init,
                                               quantize_dequantize)
        variant = self.lora.variant
        bits = self.lora.quant_bits

        def walk(node):
            if not isinstance(node, dict):
                return node
            out = {k: walk(v) for k, v in node.items()}
            if "w" in out and "lora_a" in out:
                if variant == "dora":
                    out["lora_m"] = dora_magnitude_init(out["w"])
                elif variant == "qlora":
                    out["w"] = quantize_dequantize(out["w"], bits)
            return out

        return walk(params)

    def param_shapes(self) -> Params:
        """ShapeDtypeStructs for the full config -- no allocation."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # -- forward pieces -------------------------------------------------------

    def _apply_rope(self, t: jnp.ndarray, positions) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.rope_type == "none":
            return t
        if cfg.rope_type == "mrope":
            return apply_mrope(t, positions, cfg.rope_theta, cfg.mrope_sections)
        return apply_rope(t, positions, cfg.rope_theta)

    def _attn_seq(self, p: Params, x: jnp.ndarray, positions, *,
                  lora_rank: int, lora_scale: float, is_global,
                  q_offset: int = 0):
        """Full-sequence attention; returns (out, (k, v)) for cache fill."""
        cfg = self.cfg
        b, l = x.shape[:2]
        hd = cfg.resolved_head_dim
        lk = dict(lora_rank=lora_rank, lora_scale=lora_scale,
                  use_kernel=self.use_kernels)
        q_flat = dense_apply(p["q"], x, **lk)
        if self.attn_q_sharding is not None and not self.attn_repeat_kv:
            # constrain the FLAT (B, L, H*hd) projection: always evenly
            # divisible; GSPMD maps it onto (heads, hd) subgroups itself
            q_flat = jax.lax.with_sharding_constraint(q_flat,
                                                      self.attn_q_sharding)
        q = q_flat.reshape(b, l, cfg.num_heads, hd)
        k = dense_apply(p["k"], x, **lk).reshape(b, l, cfg.num_kv_heads, hd)
        v = dense_apply(p["v"], x, **lk).reshape(b, l, cfg.num_kv_heads, hd)
        q = self._apply_rope(q, positions)
        k = self._apply_rope(k, positions)
        if self.attn_repeat_kv and cfg.num_kv_heads < cfg.num_heads:
            reps = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        if self.attn_repeat_kv and self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            head_sh = NamedSharding(self.mesh, PartitionSpec(
                self.batch_axes, None, "model", None))
            q = jax.lax.with_sharding_constraint(q, head_sh)
            k = jax.lax.with_sharding_constraint(k, head_sh)
            v = jax.lax.with_sharding_constraint(v, head_sh)
        causal = cfg.attn_type != ATTN_BIDIR
        window = 0
        if cfg.attn_type == ATTN_SLIDING and cfg.sliding_window:
            # global layers (is_global) disable the window via a huge value
            window = jnp.where(is_global, jnp.int32(2**30),
                               jnp.int32(cfg.sliding_window))
        out = blockwise_attention(
            q, k, v, causal=causal, sliding_window=window, q_offset=q_offset,
            block_q=self.block_q, block_kv=self.block_kv,
            softcap=cfg.logit_softcap, bf16_scores=self.bf16_scores)
        out = out.reshape(b, l, cfg.num_heads * hd)
        return dense_apply(p["o"], out, **lk), (k, v)

    def _attn_decode(self, p: Params, x: jnp.ndarray, cache_l, cache_len,
                     positions, *, lora_rank: int, lora_scale: float,
                     is_global):
        cfg = self.cfg
        b = x.shape[0]
        hd = cfg.resolved_head_dim
        lk = dict(lora_rank=lora_rank, lora_scale=lora_scale,
                  use_kernel=self.use_kernels)
        q = dense_apply(p["q"], x, **lk).reshape(b, 1, cfg.num_heads, hd)
        k = dense_apply(p["k"], x, **lk).reshape(b, 1, cfg.num_kv_heads, hd)
        v = dense_apply(p["v"], x, **lk).reshape(b, 1, cfg.num_kv_heads, hd)
        q = self._apply_rope(q, positions)
        k = self._apply_rope(k, positions)
        s_cache = cache_l["k"].shape[1]
        write_idx = cache_len % s_cache          # ring buffer when S < max_len
        if jnp.ndim(cache_len) == 0:
            k_cache = jax.lax.dynamic_update_slice(
                cache_l["k"], k.astype(cache_l["k"].dtype),
                (0, write_idx, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache_l["v"], v.astype(cache_l["v"].dtype),
                (0, write_idx, 0, 0))
        else:
            # per-slot cache lengths (continuous-batching serving): each
            # batch row writes its own ring position
            rows = jnp.arange(b)
            k_cache = cache_l["k"].at[rows, write_idx].set(
                k[:, 0].astype(cache_l["k"].dtype))
            v_cache = cache_l["v"].at[rows, write_idx].set(
                v[:, 0].astype(cache_l["v"].dtype))
        window = None
        if (cfg.attn_type == ATTN_SLIDING and cfg.sliding_window
                and s_cache > cfg.sliding_window):
            # full-size cache: apply the window by masking
            window = jnp.where(is_global, jnp.int32(2**30),
                               jnp.int32(cfg.sliding_window))
        eff_len = jnp.minimum(cache_len, s_cache - 1)
        out = self._masked_decode_attn(q, k_cache, v_cache, eff_len, window)
        out = out.reshape(b, 1, cfg.num_heads * hd)
        return dense_apply(p["o"], out, **lk), {"k": k_cache, "v": v_cache}

    def _masked_decode_attn(self, q, k_cache, v_cache, cache_len, window):
        s = k_cache.shape[1]
        total = cache_len + 1
        if window is None:
            return decode_attention(q, k_cache, v_cache, total,
                                    softcap=self.cfg.logit_softcap)
        # sliding window: valid positions in (total - window, total)
        pos = jnp.arange(s)
        lo = total - window
        # emulate via cache_len mask + explicit lower bound: push invalid
        # keys out by masking scores through a large-negative v trick is
        # fragile; instead reuse decode_attention's upper mask and add the
        # lower mask by zeroing keys' contribution via a second mask pass.
        out = _decode_attention_windowed(q, k_cache, v_cache, total, lo,
                                         softcap=self.cfg.logit_softcap)
        return out

    def _mrope_decode_positions(self, cache_len, b):
        # decode: all three mrope streams advance with the token index
        pos = jnp.full((b,), cache_len, jnp.int32)
        if self.cfg.rope_type == "mrope":
            return jnp.broadcast_to(pos, (3, b))[:, :, None] * jnp.ones(
                (3, b, 1), jnp.int32)
        return pos[:, None]

    # -- block application ----------------------------------------------------

    def _block_seq(self, p: Params, x, positions, aux, *, layer_idx,
                   lora_rank, lora_scale, mode):
        """One layer, full sequence. Returns (x, aux, cache_entry)."""
        cfg = self.cfg
        lk = dict(lora_rank=lora_rank, lora_scale=lora_scale)
        is_global = self._is_global(layer_idx)
        cache_entry = {}
        h = rms_norm(p["norm1"], x, eps=cfg.rms_norm_eps)
        if cfg.kind == "ssm":
            mixed, (conv_s, ssm_s) = ssd_mixer_apply(
                p["ssm"], h, cfg.d_model, cfg.ssm, use_kernel=self.use_kernels,
                **lk)
            if mode == "prefill":
                cache_entry = {"conv": conv_s, "ssm": ssm_s}
            return x + mixed, aux, cache_entry
        if cfg.mla is not None:
            attn_out, (ckv, krope) = mla_attention(
                p["attn"], h, positions, cfg.num_heads, cfg.mla,
                rope_theta=cfg.rope_theta,
                causal=cfg.attn_type != ATTN_BIDIR,
                sliding_window=cfg.sliding_window if cfg.attn_type == ATTN_SLIDING else 0,
                **lk)
            if mode == "prefill":
                cache_entry["ckv"] = ckv
                cache_entry["krope"] = krope
        else:
            attn_out, (k, v) = self._attn_seq(
                p["attn"], h, positions, is_global=is_global, **lk)
            if mode == "prefill":
                cache_entry["k"] = k
                cache_entry["v"] = v
        if cfg.kind == "hybrid":
            ssm_out, (conv_s, ssm_s) = ssd_mixer_apply(
                p["ssm"], h, cfg.d_model, cfg.ssm, use_kernel=self.use_kernels,
                **lk)
            r = cfg.hybrid_attn_ratio
            mixed = r * attn_out + (1.0 - r) * ssm_out
            if mode == "prefill":
                cache_entry["conv"] = conv_s
                cache_entry["ssm"] = ssm_s
        else:
            mixed = attn_out
        x = x + mixed
        h2 = rms_norm(p["norm2"], x, eps=cfg.rms_norm_eps)
        if "moe" in p:
            ffn_out, moe_aux = self._moe(p["moe"], h2, **lk)
            aux = aux + moe_aux * cfg.moe.router_aux_loss_coef
        else:
            ffn_out = mlp_apply(p["mlp"], h2, cfg.activation, **lk)
        x = x + ffn_out
        if self.residual_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, self.residual_sharding)
        return x, aux, cache_entry

    def _moe(self, p_moe, h2, **lk):
        if self.moe_impl == "ep":
            from repro.models.layers.moe import moe_apply_ep
            return moe_apply_ep(p_moe, h2, self.cfg.moe, self.cfg.activation,
                                self.mesh, batch_axes=self.batch_axes,
                                capacity_factor=self.moe_capacity_factor,
                                **lk)
        return moe_apply(p_moe, h2, self.cfg.moe, self.cfg.activation, **lk)

    def _block_decode(self, p: Params, x, cache_l, cache_len, positions, *,
                      layer_idx, lora_rank, lora_scale):
        cfg = self.cfg
        lk = dict(lora_rank=lora_rank, lora_scale=lora_scale)
        is_global = self._is_global(layer_idx)
        new_cache = dict(cache_l)
        h = rms_norm(p["norm1"], x, eps=cfg.rms_norm_eps)
        if cfg.kind == "ssm":
            mixed, (conv_s, ssm_s) = ssd_mixer_decode(
                p["ssm"], h, cfg.d_model, cfg.ssm, cache_l["conv"],
                cache_l["ssm"], **lk)
            new_cache.update(conv=conv_s, ssm=ssm_s)
            return x + mixed, new_cache
        if cfg.mla is not None:
            # cache_len may be scalar (single-sequence decode) or (B,)
            # per-slot lengths (continuous-batching serving): mla_decode
            # vectorizes the cache write, decode_attention the mask
            s_cache = cache_l["ckv"].shape[1]
            attn_out, (ckv, krope) = mla_decode(
                p["attn"], h, positions[:, 0] if positions.ndim > 1 else positions,
                cache_l["ckv"], cache_l["krope"],
                jnp.minimum(cache_len, s_cache - 1), cfg.num_heads,
                cfg.mla, rope_theta=cfg.rope_theta,
                write_idx=cache_len % s_cache, **lk)
            new_cache.update(ckv=ckv, krope=krope)
        else:
            attn_out, kv = self._attn_decode(
                p["attn"], h, cache_l, cache_len, positions,
                is_global=is_global, **lk)
            new_cache.update(kv)
        if cfg.kind == "hybrid":
            ssm_out, (conv_s, ssm_s) = ssd_mixer_decode(
                p["ssm"], h, cfg.d_model, cfg.ssm, cache_l["conv"],
                cache_l["ssm"], **lk)
            r = cfg.hybrid_attn_ratio
            mixed = r * attn_out + (1.0 - r) * ssm_out
            new_cache.update(conv=conv_s, ssm=ssm_s)
        else:
            mixed = attn_out
        x = x + mixed
        h2 = rms_norm(p["norm2"], x, eps=cfg.rms_norm_eps)
        if "moe" in p:
            ffn_out, _ = self._moe(p["moe"], h2, **lk)
        else:
            ffn_out = mlp_apply(p["mlp"], h2, cfg.activation, **lk)
        return x + ffn_out, new_cache

    def _is_global(self, layer_idx) -> jnp.ndarray:
        if self.cfg.global_attn_every:
            return (layer_idx % self.cfg.global_attn_every) == 0
        return jnp.asarray(False)

    # -- embeddings / head ----------------------------------------------------

    def _embed_inputs(self, params: Params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        parts = []
        if cfg.frontend.kind != "none" and "embeds" in batch:
            fe = dense_apply(params["frontend_proj"],
                             batch["embeds"].astype(self.dtype))
            parts.append(fe)
        if "tokens" in batch:
            tok = jnp.take(params["embed"], batch["tokens"], axis=0)
            if self.mesh is not None and self.residual_sharding is not None:
                # pin the gather output to batch-only sharding: GSPMD must
                # not back-propagate feature sharding into the lookup table
                # (XLA mis-partitions jvp-of-gather on feature-sharded
                # tables -- see DESIGN.md §5)
                from jax.sharding import NamedSharding, PartitionSpec
                tok = jax.lax.with_sharding_constraint(
                    tok, NamedSharding(self.mesh, PartitionSpec(
                        self.batch_axes, None, None)))
            if cfg.kind == "dense" and cfg.name.startswith("gemma"):
                tok = tok * jnp.asarray(cfg.d_model ** 0.5, tok.dtype)
            parts.append(tok.astype(self.dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def _logits(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        x = rms_norm(params["final_norm"], x, eps=self.cfg.rms_norm_eps)
        if self.cfg.tie_embeddings:
            if (self.mesh is not None and self.residual_sharding is not None
                    and self.logits_sharding is None):
                # odd-vocab tied head: keep x feature-replicated so GSPMD
                # never feature-shards the (gathered) embedding table
                from jax.sharding import NamedSharding, PartitionSpec
                x = jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, PartitionSpec(
                        self.batch_axes, None, None)))
            logits = x @ params["embed"].astype(x.dtype).T
        else:
            logits = dense_apply(params["lm_head"], x)
        if self.logits_sharding is not None:
            # keep logits vocab-sharded: a (B, L, 256k) f32 tensor must never
            # materialize unsharded (loss reductions psum over the shards)
            logits = jax.lax.with_sharding_constraint(logits,
                                                      self.logits_sharding)
        return logits

    def _default_positions(self, batch: dict, b: int, l: int):
        if "positions" in batch:
            return batch["positions"]
        pos = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
        if self.cfg.rope_type == "mrope":
            return jnp.broadcast_to(pos, (3, b, l))
        return pos

    # -- public entry points ---------------------------------------------------

    def forward_seq(self, params: Params, batch: dict, *, mode: str = "train",
                    lora_rank: int = -1, lora_scale: float = 1.0):
        """Full-sequence forward. mode: "train" (no cache) | "prefill"."""
        x = self._embed_inputs(params, batch)
        b, l = x.shape[:2]
        positions = self._default_positions(batch, b, l)
        aux0 = jnp.zeros((), jnp.float32)

        def group_body(carry, inp):
            x, aux = carry
            p_group, group_idx = inp
            caches = {}
            for i in range(self.group_size):
                p_l = p_group[f"sub{i}"] if self.group_size > 1 else p_group
                layer_idx = group_idx * self.group_size + i
                x, aux, cache_entry = self._block_seq(
                    p_l, x, positions, aux, layer_idx=layer_idx,
                    lora_rank=lora_rank, lora_scale=lora_scale, mode=mode)
                if self.group_size > 1:
                    caches[f"sub{i}"] = cache_entry
                else:
                    caches = cache_entry
            return (x, aux), caches

        body = group_body
        if self.remat:
            body = jax.checkpoint(
                group_body,
                policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), caches = jax.lax.scan(
            body, (x, aux0),
            (params["layers"], jnp.arange(self.num_groups)))
        logits = self._logits(params, x)
        return logits, aux, caches

    def train_loss(self, params: Params, batch: dict, *, lora_rank: int = -1,
                   lora_scale: float = 1.0):
        logits, aux, _ = self.forward_seq(
            params, batch, mode="train", lora_rank=lora_rank,
            lora_scale=lora_scale)
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        logits_f = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits_f, axis=-1)
        # gold logit via one-hot contraction: reduction over the (possibly
        # model-sharded) vocab dim lowers to a psum instead of a cross-shard
        # gather (take_along_axis would all-gather the logits)
        vocab = logits_f.shape[-1]
        onehot = jax.nn.one_hot(targets, vocab, dtype=logits_f.dtype)
        gold = jnp.sum(logits_f * onehot, axis=-1)
        nll = (logz - gold) * mask
        loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
        metrics = {"loss": loss, "aux_loss": aux,
                   "accuracy": (jnp.argmax(logits_f, -1) == targets)
                   .astype(jnp.float32).__mul__(mask).sum()
                   / jnp.maximum(mask.sum(), 1.0)}
        return loss + aux, metrics

    def prefill(self, params: Params, batch: dict, *, lora_rank: int = -1,
                lora_scale: float = 1.0):
        logits, _, caches = self.forward_seq(
            params, batch, mode="prefill", lora_rank=lora_rank,
            lora_scale=lora_scale)
        return logits, caches

    def decode_step(self, params: Params, batch: dict, cache: dict, *,
                    lora_rank: int = -1, lora_scale: float = 1.0):
        """One decode step. batch: {"token": (B, 1)} [+ modality stubs].

        cache: {"layers": stacked per-layer cache, "len": scalar int32}.
        Returns (logits (B, 1, V), new cache).
        """
        assert self.cfg.supports_decode, f"{self.cfg.name} is encoder-only"
        cache_len = cache["len"]
        tok = batch["token"]
        x = jnp.take(params["embed"], tok, axis=0).astype(self.dtype)
        if self.cfg.kind == "dense" and self.cfg.name.startswith("gemma"):
            x = x * jnp.asarray(self.cfg.d_model ** 0.5, x.dtype)
        b = x.shape[0]
        # cache["len"] is a scalar (lock-step decode) or (B,) vector
        # (per-slot lengths under continuous batching) -- both reshape to
        # one position column
        pos_col = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1,)), (b,))[:, None]
        if self.cfg.rope_type == "mrope":
            positions = jnp.broadcast_to(pos_col, (3, b, 1))
        else:
            positions = pos_col

        def group_body(x, inp):
            p_group, cache_group, group_idx = inp
            new_group = {}
            for i in range(self.group_size):
                p_l = p_group[f"sub{i}"] if self.group_size > 1 else p_group
                c_l = cache_group[f"sub{i}"] if self.group_size > 1 else cache_group
                layer_idx = group_idx * self.group_size + i
                x, c_new = self._block_decode(
                    p_l, x, c_l, cache_len, positions, layer_idx=layer_idx,
                    lora_rank=lora_rank, lora_scale=lora_scale)
                if self.group_size > 1:
                    new_group[f"sub{i}"] = c_new
                else:
                    new_group = c_new
            return x, new_group

        x, new_layer_caches = jax.lax.scan(
            group_body, x,
            (params["layers"], cache["layers"], jnp.arange(self.num_groups)))
        logits = self._logits(params, x)
        return logits, {"layers": new_layer_caches, "len": cache_len + 1}

    # -- cache construction ----------------------------------------------------

    def _layer_cache_shape(self, batch_size: int, max_len: int) -> dict:
        cfg, dt = self.cfg, self.dtype
        entry: dict = {}
        if cfg.kind == "ssm" or cfg.kind == "hybrid":
            dims = ssd_dims(cfg.d_model, cfg.ssm)
            entry["conv"] = jax.ShapeDtypeStruct(
                (batch_size, cfg.ssm.conv_dim - 1, dims["conv_ch"]), dt)
            entry["ssm"] = jax.ShapeDtypeStruct(
                (batch_size, dims["nheads"], dims["head_dim"],
                 cfg.ssm.state_dim), jnp.float32)
        if cfg.kind == "ssm":
            return entry
        s = self.cache_seq_len(max_len)
        if cfg.mla is not None:
            entry["ckv"] = jax.ShapeDtypeStruct(
                (batch_size, s, cfg.mla.kv_lora_rank), dt)
            entry["krope"] = jax.ShapeDtypeStruct(
                (batch_size, s, cfg.mla.qk_rope_head_dim), dt)
        else:
            hd = cfg.resolved_head_dim
            entry["k"] = jax.ShapeDtypeStruct(
                (batch_size, s, cfg.num_kv_heads, hd), dt)
            entry["v"] = jax.ShapeDtypeStruct(
                (batch_size, s, cfg.num_kv_heads, hd), dt)
        return entry

    def cache_seq_len(self, max_len: int) -> int:
        """Ring-buffer length: pure sliding-window archs only ever need the
        last ``window`` positions (what makes long_500k decode O(window))."""
        cfg = self.cfg
        if (cfg.attn_type == ATTN_SLIDING and cfg.sliding_window
                and not cfg.global_attn_every):
            return min(max_len, cfg.sliding_window)
        return max_len

    def cache_shapes(self, batch_size: int, max_len: int) -> dict:
        per_layer = self._layer_cache_shape(batch_size, max_len)
        if self.group_size > 1:
            per_layer = {f"sub{i}": per_layer for i in range(self.group_size)}
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.num_groups,) + s.shape,
                                           s.dtype), per_layer)
        return {"layers": stacked,
                "len": jax.ShapeDtypeStruct((), jnp.int32)}

    def init_cache(self, batch_size: int, max_len: int) -> dict:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch_size, max_len))


# ---------------------------------------------------------------------------
# windowed decode attention helper
# ---------------------------------------------------------------------------

def _decode_attention_windowed(q, k_cache, v_cache, total, lo, *,
                               softcap: float = 0.0):
    """decode attention with validity window [lo, total)."""
    b, _, h, d = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    scale = d ** -0.5
    qg = q.reshape(b, kvh, h // kvh, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = jnp.arange(s)
    # total / lo are scalars or (B,) per-slot lengths; broadcast over rows
    total_b = jnp.reshape(jnp.asarray(total), (-1, 1))
    lo_b = jnp.reshape(jnp.asarray(lo), (-1, 1))
    valid = (pos[None, :] < total_b) & (pos[None, :] >= jnp.maximum(lo_b, 0))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def build_model(cfg: ModelConfig, lora: Optional[LoRAConfig] = None,
                **kw) -> Model:
    return Model(cfg, lora, **kw)
