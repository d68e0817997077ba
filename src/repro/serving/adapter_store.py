"""Paged adapter cache with atomic, versioned hot-swap (DESIGN.md §11).

``AdapterStore`` holds one LoRA adapter tree per tenant, bucketed by rank
level exactly like the aggregation side buckets clients: every staged
adapter belongs to the rank-level bucket of its true rank, and pages are
packed bucket-by-bucket (ascending rank level, insertion order within a
bucket) so same-rank tenants are contiguous in the page axis. Factors are
stored at ``r_max`` width with omega-style zero columns beyond the true
rank -- zero columns are spectrum-inert, so padded pages apply exactly as
their truncated originals (the same convention the aggregators use).

Publishing is ATOMIC: ``publish()`` packs the staged adapters into an
immutable :class:`PublishedAdapters` snapshot under a strictly monotonic
version and flips one reference. Readers (``ServingEngine``) capture the
snapshot once per decode step, so an in-flight step finishes entirely on
the version it started with and no request ever mixes versions within a
step; the next step observes the new version. (CPython reference
assignment is atomic; there is a single writer -- the federation hook or
the operator -- by construction.)

``bind_server`` attaches the store to a :class:`FederatedLoRA` server's
post-aggregation hook: every round landing (sync engines at round
finalize, async/event engines whenever their buffer fires, including
``drain_pending``) re-stages the designated tenant with the new global
factors and publishes under the server's adapter version.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core.lora import _is_lora_path

Pages = Any  # lora-tree-shaped pytree; leaves carry a leading page axis


@dataclasses.dataclass(frozen=True)
class PublishedAdapters:
    """Immutable snapshot of the packed adapter pages.

    ``pages`` mirrors the model's lora tree (None at non-lora leaves);
    every array leaf carries a leading page axis P: lora_a (P, ..., r_max,
    in), lora_b (P, ..., out, r_max). ``page_of`` maps tenant id -> page
    index; ``ranks[p]`` is page p's true rank (its rank-level bucket);
    ``scales[p]`` is the LoRA scaling already FOLDED into that page's
    lora_b at packing time, recorded here for introspection only.
    """
    version: int
    pages: Pages
    page_of: Mapping[Any, int]
    ranks: Tuple[int, ...]
    scales: Tuple[float, ...]

    @property
    def num_pages(self) -> int:
        return len(self.ranks)

    def page_ids(self, adapter_ids) -> jnp.ndarray:
        """Map tenant ids -> int32 page indices (host-side)."""
        return jnp.asarray([self.page_of[i] for i in adapter_ids],
                           jnp.int32)


def _mask_and_pad(path, leaf, rank: int, r_max: int):
    """Zero columns >= rank, pad the rank dim to r_max (omega-style)."""
    key = path[-1].key
    if key == "lora_m":
        raise ValueError("DoRA magnitudes are not servable via the paged "
                         "adapter cache (serving supports plain LoRA)")
    ax = leaf.ndim - 2 if key == "lora_a" else leaf.ndim - 1
    r_in = leaf.shape[ax]
    assert r_in <= r_max, (r_in, r_max)
    col = jnp.arange(r_in)
    shape = [1] * leaf.ndim
    shape[ax] = r_in
    leaf = leaf * (col < rank).reshape(shape).astype(leaf.dtype)
    if r_in < r_max:
        pad = [(0, 0)] * leaf.ndim
        pad[ax] = (0, r_max - r_in)
        leaf = jnp.pad(leaf, pad)
    return leaf


class AdapterStore:
    """Rank-level-bucketed tenant adapter store with atomic publish."""

    def __init__(self, rank_levels: Tuple[int, ...],
                 scaling_fn=None):
        self.rank_levels = tuple(sorted(rank_levels))
        self.r_max = max(self.rank_levels)
        # staged: tenant id -> (rank, lora_tree); insertion order preserved
        self._staged: Dict[Any, Tuple[int, Any]] = {}
        self._scaling_fn = scaling_fn or (lambda rank: 1.0)
        self._published: Optional[PublishedAdapters] = None
        self._version = 0

    # -- staging -------------------------------------------------------------

    def put(self, adapter_id, lora_tree, rank: int) -> None:
        """Stage (or replace) a tenant's adapter at its true rank. Takes
        effect only at the next ``publish()``."""
        if rank not in self.rank_levels:
            raise ValueError(f"rank {rank} not in levels {self.rank_levels}")
        self._staged[adapter_id] = (rank, lora_tree)

    def buckets(self) -> Dict[int, list]:
        """rank level -> staged tenant ids (the aggregation-side bucket
        discipline: group by rank level, insertion order within)."""
        out: Dict[int, list] = {lvl: [] for lvl in self.rank_levels}
        for aid, (rank, _) in self._staged.items():
            out[rank].append(aid)
        return out

    # -- publish / read ------------------------------------------------------

    @property
    def published(self) -> Optional[PublishedAdapters]:
        """The live snapshot. Capture ONCE per step; never re-read
        mid-step."""
        return self._published

    @property
    def version(self) -> int:
        return self._version

    def publish(self, version: Optional[int] = None) -> PublishedAdapters:
        """Pack the staged adapters and atomically flip the live snapshot.

        ``version`` defaults to the next monotonic value; an explicit
        version (e.g. the federation server's adapter version) must be
        strictly greater than the current one.
        """
        if not self._staged:
            raise ValueError("publish() with no staged adapters")
        version = self._version + 1 if version is None else int(version)
        if version <= self._version:
            raise ValueError(
                f"version must be monotonic: {version} <= {self._version}")
        with tracing.span("serve.publish", version=version):
            order = [aid for lvl in self.rank_levels
                     for aid in self.buckets()[lvl]]
            page_of = {aid: p for p, aid in enumerate(order)}
            ranks = tuple(self._staged[aid][0] for aid in order)
            scales = tuple(float(self._scaling_fn(r)) for r in ranks)
            trees = []
            for aid in order:
                rank, tree = self._staged[aid]
                s = self._scaling_fn(rank)

                def pack(path, leaf):
                    if leaf is None or not _is_lora_path(path):
                        return leaf
                    leaf = _mask_and_pad(path, leaf, rank, self.r_max)
                    if path[-1].key == "lora_b" and s != 1.0:
                        # fold the per-tenant scaling into B so the
                        # engine can run every page at unit scale
                        leaf = leaf * jnp.asarray(s, leaf.dtype)
                    return leaf

                trees.append(jax.tree_util.tree_map_with_path(
                    pack, tree, is_leaf=lambda x: x is None))
            pages = jax.tree.map(
                lambda *leaves: (None if leaves[0] is None
                                 else jnp.stack(leaves)),
                *trees, is_leaf=lambda x: x is None)
        snap = PublishedAdapters(version=version, pages=pages,
                                 page_of=page_of, ranks=ranks,
                                 scales=scales)
        self._published = snap          # the atomic flip
        self._version = version
        return snap

    # -- federation hook -----------------------------------------------------

    def bind_server(self, server, adapter_id="global",
                    rank: Optional[int] = None) -> None:
        """Attach to ``FederatedLoRA.add_post_aggregate_hook``: every round
        landing re-stages ``adapter_id`` with the freshly aggregated global
        factors and publishes under the server's adapter version."""
        rank = self.r_max if rank is None else rank

        def on_round_landing(version: int, global_lora) -> None:
            self.put(adapter_id, global_lora, rank)
            self.publish(version)

        server.add_post_aggregate_hook(on_round_landing)
