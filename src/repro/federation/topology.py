"""Client registry: heterogeneous rank assignment + data shard bookkeeping."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.configs.base import FLConfig, LoRAConfig


@dataclass
class ClientRegistry:
    """K clients, each with a LoRA rank drawn from the configured levels
    (paper: uniform over {8,16,32,48,64} by default) and a data shard."""

    ranks: np.ndarray                 # (K,) int
    shards: List[np.ndarray]          # per-client sample indices
    rank_levels: Sequence[int]

    @classmethod
    def create(cls, fl: FLConfig, lora: LoRAConfig,
               shards: List[np.ndarray],
               rng: Optional[np.random.Generator] = None) -> "ClientRegistry":
        rng = rng or np.random.default_rng(fl.seed)
        k = fl.num_clients
        assert len(shards) == k, (len(shards), k)
        ranks = rng.choice(lora.rank_levels, size=k, p=lora.rank_probs)
        return cls(ranks=ranks.astype(int), shards=shards,
                   rank_levels=tuple(lora.rank_levels))

    @property
    def num_clients(self) -> int:
        return len(self.ranks)

    def num_samples(self, k: int) -> int:
        return len(self.shards[k])

    def add_client(self, rank: int, shard: np.ndarray) -> int:
        """Register a NEW client mid-run (event-driven "join" lifecycle
        event) and return its id. Ids are append-only so plans and shards
        recorded before the join stay valid."""
        cid = self.num_clients
        # np.append copies the whole (K,) rank vector -- an O(K) cost per
        # JOIN event (not per round); the host-cost shim records it
        tracing.count("registry/add_client")
        self.ranks = np.append(self.ranks, int(rank)).astype(int)
        self.shards.append(np.asarray(shard, dtype=np.int64))
        return cid

    def inflate(self, total_clients: int,
                rng: Optional[np.random.Generator] = None) -> None:
        """Grow the registry to ``total_clients`` with synthetic clients
        for scale testing: ranks drawn from the configured levels, data
        shards ALIASED round-robin onto the existing shard arrays (no
        data copies -- a million-client registry stays a rank vector plus
        a list of references). Ids are append-only, so existing plans and
        the rng sampling stream stay valid."""
        k = self.num_clients
        extra = int(total_clients) - k
        if extra <= 0:
            return
        rng = rng or np.random.default_rng(0)
        new_ranks = rng.choice(list(self.rank_levels), size=extra)
        self.ranks = np.concatenate(
            [self.ranks, new_ranks.astype(int)])
        self.shards.extend(self.shards[i % k] for i in range(extra))

    def sample_round(self, m: int, rng: np.random.Generator,
                     active: Optional[np.ndarray] = None) -> np.ndarray:
        """Uniform sampling without replacement (Alg. 1 line 3).

        ``active`` (event-driven engine): restrict sampling to this client
        pool -- dropouts leave it, rejoined/joined clients enter it. A
        round never samples more clients than are active. ``active=None``
        keeps the exact historical rng consumption, so scenarios without
        lifecycle events reproduce cadence-engine sampling bit-for-bit."""
        if active is None:
            tracing.count("registry/sample", m)
            return rng.choice(self.num_clients, size=m, replace=False)
        active = np.asarray(active)
        tracing.count("registry/active_pool", active.size)
        m = min(int(m), active.size)
        return active[rng.choice(active.size, size=m, replace=False)]

    def coverage(self) -> np.ndarray:
        from repro.core.partitions import coverage
        return coverage(self.rank_levels, self.ranks)
