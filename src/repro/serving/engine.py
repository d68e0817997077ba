"""Batched multi-adapter inference engine (DESIGN.md §11).

``ServingEngine`` runs a fixed number of request SLOTS over one jitted
prefill and one jitted decode program. Per-request adapters enter by LEAF
SUBSTITUTION: the published pages (leading page axis P) are gathered by
the slots' page ids into per-slot factors -- lora_a (P, G, r, in) ->
(G, S, r, in) -- and merged over the base params, so the batched leaves
ride the layer ``lax.scan`` exactly like the training-side factors and
``dense_apply`` dispatches to its per-request branch (the paged Pallas
kernel under ``use_kernel``, the batched einsum oracle otherwise).

Version atomicity: every public engine call captures ``store.published``
EXACTLY ONCE at entry; the whole jitted step runs on that snapshot and its
version is appended to ``version_log``. A hot-swap between two steps is
therefore the only place a version change can land -- no request mixes
versions within one step.

Per-slot KV state: one full-``max_len`` cache allocated up front via
``Model.init_cache`` with a VECTOR ``len`` (one length per slot, the
continuous-batching shape the transformer decode path supports), seeded
path-aware from prefill caches by ``seed_cache`` -- SSM ``conv``/``ssm``
states transfer as-is; attention ``k``/``v``/``ckv``/``krope`` leaves
merge on their sequence axis (ring-scattered when the prompt exceeds the
ring length). This replaces the old shape-matching ``grow`` hack that
silently skipped SSM states and mis-padded coincidental dims.
"""
from __future__ import annotations

from typing import Any, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.lora import merge_lora, split_lora
from repro.serving.adapter_store import AdapterStore

_SEQ_KEYS = ("k", "v", "ckv", "krope")   # per-token cache leaves (seq axis 2)
_STATE_KEYS = ("conv", "ssm")            # positionless SSM states


def _leaf_key(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def seed_cache(cache, prefill_caches, prompt_len: int, slot_mask):
    """Merge prefill caches into a full-length cache, path-aware.

    cache: the engine's persistent ``init_cache`` pytree (vector ``len``);
    prefill_caches: ``Model.prefill``'s per-layer caches (seq len =
    prompt_len); slot_mask: (S,) bool -- only masked slots are (re)seeded.

    Leaves are merged BY PATH KEY, not by shape: ``conv``/``ssm`` states
    transfer unchanged, sequence leaves pad (or ring-scatter, when
    prompt_len exceeds the ring length S_c) on axis 2 of their stacked
    (G, S, S_c, ...) layout. A dim coincidentally equal to prompt_len is
    never touched.
    """
    mask = jnp.asarray(slot_mask, bool)

    def merge(path, full, got):
        key = _leaf_key(path)
        if key == "len":
            return jnp.where(mask, jnp.int32(prompt_len), full)
        got = got.astype(full.dtype)
        if key in _SEQ_KEYS:
            s_c = full.shape[2]              # stacked leaves: (G, S, S_c, ..)
            if prompt_len <= s_c:
                pad = [(0, 0)] * got.ndim
                pad[2] = (0, s_c - prompt_len)
                new = jnp.pad(got, pad)
            else:
                # ring discipline: token t lives at slot t % S_c; the last
                # S_c prompt positions land on a permutation of 0..S_c-1
                idx = jnp.arange(prompt_len - s_c, prompt_len) % s_c
                new = jnp.zeros_like(full).at[:, :, idx].set(
                    got[:, :, prompt_len - s_c:])
        elif key in _STATE_KEYS:
            new = got
        else:
            raise ValueError(f"unknown cache leaf {key!r} at {path}")
        sel = mask.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(sel, new, full)

    flat = {"layers": cache["layers"], "len": cache["len"]}
    got = {"layers": prefill_caches, "len": cache["len"]}
    return jax.tree_util.tree_map_with_path(merge, flat, got)


def substitute_pages(base, pages, page_ids):
    """Merge page-gathered per-slot factors over the base params: leaf
    (P, G, ...) -> (S, G, ...) -> (G, S, ...), so the layer scan strips G
    and ``dense_apply`` sees per-slot (S, ...) batched leaves."""
    def gather(leaf):
        if leaf is None:
            return None
        return jnp.moveaxis(leaf[page_ids], 0, 1)
    lora = jax.tree.map(gather, pages, is_leaf=lambda x: x is None)
    return merge_lora(base, lora)


class ServingEngine:
    """Fixed-slot multi-tenant engine over a published adapter snapshot.

    ``logits`` holds the (S, V) next-token logits of the last ``admit`` or
    ``decode`` call, for checking the served path against a full forward.
    """

    def __init__(self, model, params, store: AdapterStore, *,
                 max_len: int, slots: int):
        if store.published is None:
            raise ValueError("AdapterStore has no published snapshot; "
                             "stage adapters and publish() first")
        if model.lora is not None and model.lora.variant != "lora":
            raise NotImplementedError(
                "serving supports plain LoRA adapters only")
        self.model = model
        self.store = store
        self.max_len = int(max_len)
        self.slots = int(slots)
        self.base, _ = split_lora(params)
        # persistent per-slot state
        self.cache = model.init_cache(self.slots, self.max_len)
        self.cache["len"] = jnp.zeros((self.slots,), jnp.int32)
        self.tokens = jnp.zeros((self.slots,), jnp.int32)
        self.slot_pages = jnp.zeros((self.slots,), jnp.int32)
        self.logits = None
        self.version_log: List[int] = []     # one snapshot version per step

        # named scopes for the trace: the adapter page gather
        # (``serve.pages``), the model (``serve.model``) and, in decode, the
        # select that freezes inactive slots (``serve.select``)
        def prefill_impl(base, pages, page_ids, prompts):
            with jax.named_scope("serve.pages"):
                merged = substitute_pages(base, pages, page_ids)
            with jax.named_scope("serve.model"):
                logits, caches = model.prefill(merged, {"tokens": prompts})
                logits = logits[:, -1, :]
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, logits, caches

        def decode_impl(base, pages, page_ids, tokens, cache, active):
            with jax.named_scope("serve.pages"):
                merged = substitute_pages(base, pages, page_ids)
            with jax.named_scope("serve.model"):
                logits, new_cache = model.decode_step(
                    merged, {"token": tokens[:, None]}, cache)
                logits = logits[:, -1, :]
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # inactive slots are frozen: token, length and SSM states hold
            with jax.named_scope("serve.select"):
                next_tok = jnp.where(active, next_tok, tokens)
                sel = lambda n, o: jax.tree.map(
                    lambda a, b: jnp.where(
                        jnp.reshape(active, (1, -1) + (1,) * (a.ndim - 2)),
                        a, b),
                    n, o)
                new_cache["layers"] = sel(new_cache["layers"],
                                          cache["layers"])
                new_cache["len"] = jnp.where(active, new_cache["len"],
                                             cache["len"])
            return next_tok, logits, new_cache

        self._prefill = jax.jit(prefill_impl)
        self._decode = jax.jit(decode_impl)

    # -- public steps (one snapshot capture per call) ------------------------

    def admit(self, slot_idx: Sequence[int], prompts,
              adapter_ids: Sequence[Any]) -> jnp.ndarray:
        """Prefill ``prompts`` ((n, L) int32) into slots ``slot_idx`` with
        per-request tenants ``adapter_ids``; returns the first greedy token
        per admitted request. One adapter snapshot for the whole call.
        Counts the rows prefilled (``serve.prefill_rows``, every slot) and
        the requests admitted (``serve.admitted``), inside the span
        ``serve.engine.admit``."""
        with tracing.span("serve.engine.admit"):
            snap = self.store.published        # THE capture
            self.version_log.append(snap.version)
            slot_idx = list(slot_idx)
            prompts = jnp.asarray(prompts, jnp.int32)
            n, lp = prompts.shape
            assert len(slot_idx) == n == len(list(adapter_ids))
            tracing.count("serve.prefill_rows", self.slots)
            tracing.count("serve.admitted", n)
            # full-width prefill: inactive rows run on zeros and are
            # discarded
            idx = jnp.asarray(slot_idx)
            full_prompts = jnp.zeros((self.slots, lp), jnp.int32)
            full_prompts = full_prompts.at[idx].set(prompts)
            new_pages = self.slot_pages.at[idx].set(
                snap.page_ids(adapter_ids))
            next_tok, self.logits, caches = self._prefill(
                self.base, snap.pages, new_pages, full_prompts)
            mask = jnp.zeros((self.slots,), bool).at[idx].set(True)
            self.cache = seed_cache(self.cache, caches, lp, mask)
            self.tokens = jnp.where(mask, next_tok, self.tokens)
            self.slot_pages = new_pages
            return next_tok[idx]

    def decode(self, active_mask) -> jnp.ndarray:
        """One greedy decode step for every active slot; returns the (S,)
        token vector. One adapter snapshot for the whole step. Counts the
        rows decoded (``serve.decode_rows``, every slot) and the live ones
        (``serve.decode_live``, read from the mask as given), inside the
        span ``serve.engine.decode``."""
        with tracing.span("serve.engine.decode"):
            tracing.count("serve.decode_rows", self.slots)
            tracing.count("serve.decode_live",
                          np.count_nonzero(np.asarray(active_mask)))
            snap = self.store.published        # THE capture
            self.version_log.append(snap.version)
            active = jnp.asarray(active_mask, bool)
            self.tokens, self.logits, self.cache = self._decode(
                self.base, snap.pages, self.slot_pages, self.tokens,
                self.cache, active)
            return self.tokens

    # -- introspection -------------------------------------------------------

    def slot_len(self) -> jnp.ndarray:
        return self.cache["len"]
