"""Federated server: Algorithm 1 round loop with pluggable aggregation.

Per round: uniform client sampling -> broadcast (rank-truncated adapters) ->
parallel local training -> rank-partitioned (or baseline) aggregation ->
SVD reallocation -> energy bookkeeping. The server state is checkpointable
and the whole loop is architecture-agnostic: it sees only adapter factor
trees from ``repro.core.lora``.

Two round engines (DESIGN.md "Batched round engine"):

* ``round_engine="batched"`` (default): ALL sampled clients train as ONE
  vmapped, jitted multi-client step over stacked LoRA trees -- each
  client's factors rank-masked and its lora scale vmapped, which is exact
  (client.py) -- and aggregation stacks every same-shape adapter into one
  (M, P, ..., d, r) bucket and runs one jitted weighted-contraction +
  batched QR/SVD realloc per bucket (the "kernel" backend lowers a bucket
  through the fused layer-batched Pallas grids -- sqrt-weighted factor
  stacks + (R, R) Gram cores feeding the Gram-core SVD realloc, so dW is
  never materialized; DESIGN.md §4.3).
* ``round_engine="sequential"``: the original per-client / per-adapter
  reference loop, kept for bit-level comparison (tests assert the two match
  to float tolerance) and for debugging.

* ``round_engine="sharded"`` (DESIGN.md §5): the batched engine's
  dispatches as shard_map programs over a mesh's ``data`` axis. Sampled
  clients are partitioned round-robin across shards (padded to equal
  per-shard counts with zero-weight ghost clients), local training runs
  the IDENTICAL masked vmapped step body on each shard's client block, and
  the stacked-factor contraction sum_k B_k diag(omega_k) A_k is computed
  as per-shard partials reduced by ONE ``jax.lax.psum`` per bucket before
  the unchanged SVD reallocation (launch/fl_dryrun.py lowers the very same
  program on the mocked production pod mesh). Every backend is
  engine-complete here, including "kernel": each shard builds its local
  zero-scattered (d+n, R) factor-stack partial with the layered Pallas
  grid over its resident clients only, the psum stays one (d+n, R)
  all-reduce, and the Gram-core realloc runs on the reduced stack
  (DESIGN.md §4.3 -- no silent einsum downgrade).

* ``round_engine="async"`` (DESIGN.md §6): the round as explicit
  plan -> train -> aggregate STAGES with FedBuff-style BUFFERED
  aggregation. Every round plans and dispatches one ``RoundPlan``'s masked
  vmapped local training as non-blocking jax handles
  (``client.dispatch_group_masked``) into a ``pipeline_depth``-deep buffer;
  when the buffer fills, ONE staleness-discounted bucketed aggregation +
  SVD realloc consumes every pending plan. Plan age in rounds is its
  staleness (mixed 0..depth-1 inside each aggregation); clients'
  aggregation weights are discounted by ``gamma**staleness`` folded into
  the n_k-derived weights (``core.aggregation.staleness_discount`` --
  ghost-client zero-weighting and the Eq. 8 fallback untouched).
  Aggregation, SVD, momentum and the global write-back amortize over depth
  rounds, and the host path between dispatches is deliberately jax-free
  (numpy batches/weights, flush-time-only device reads) so training
  dispatches pipeline against in-flight aggregation work instead of
  synchronizing with it. ``pipeline_depth=1`` reduces exactly to the
  batched engine (zero staleness is an arithmetic no-op); an optional mesh
  routes both stages through the sharded dispatches instead.

* ``round_engine="async"`` + ``event_scheduler=`` (DESIGN.md §7): the
  buffered aggregation driven by ARRIVAL EVENTS on a deterministic virtual
  clock instead of the fixed cadence. Each dispatched client's update
  arrives after a seeded per-client latency draw
  (``federation/events.py``); pluggable buffer triggers (count / virtual
  timeout / staleness bound) decide when the buffered aggregation fires,
  consuming exactly the updates that have arrived -- partial cohorts ride
  the ghost-client zero-weight rule (``present`` mask), staleness is
  arrival-time-derived (``floor(wait / round_interval)``), and client
  lifecycle events (dropout / rejoin / mid-run join) reshape the sampling
  pool between rounds. The count trigger under the unit-latency trace is
  bit-equal to the ``pipeline_depth=k`` cadence path
  (tests/test_events.py).

Every engine's stages are spans of ``repro.tracing``: ``fl.round`` holds
``fl.plan`` (sampling and the data pipeline), ``fl.train`` (host stacking,
``fl.stack``, and the training dispatch), ``fl.aggregate``, ``fl.write``
(the write-back dispatch) and ``fl.sync`` (the host reading losses and
probes back).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import FLConfig, LoRAConfig
from repro.core.aggregation import Aggregator, cohort_weights, weighted_avg
from repro.core.energy import EnergyTrace
from repro.core.lora import merge_lora, split_lora
from repro.federation.client import LocalTrainer, _stack_steps
from repro.federation.topology import ClientRegistry
from repro.federation.transport import (QuantFactor, TransportConfig,
                                        UpdateTransport)
from repro.models.transformer import Model
from repro.optim import get_schedule


@dataclass
class RoundStats:
    round: int
    clients: List[int]
    ranks: List[int]
    lr: float
    mean_client_loss: float
    sigma_probe: Optional[np.ndarray]  # singular values of probe adapter
    wall_time_s: float
    # event-driven engine: the virtual-clock time at the round's window end
    virtual_time: Optional[float] = None


@dataclass
class BucketedUpdate:
    """Aggregation output of the grouped engines, kept STACKED per shape
    bucket: ``buckets`` entries are (adapter parents, B stack (P, …, d, r),
    A stack (P, …, r, n)); ``mags`` holds DoRA magnitudes. Never unstacked
    per adapter on the hot path -- the write-back slices inside ONE jitted
    program (``_write_bucketed``), because every eager slice is a separate
    computation against jax's bounded CPU in-flight queue and would stall
    the async engine's dispatch pipeline."""

    buckets: List[tuple] = field(default_factory=list)
    mags: Dict = field(default_factory=dict)


@functools.partial(jax.jit, static_argnames=("bucket_parents",))
def _write_bucketed(lora_tree, bucket_stacks, mags, *, bucket_parents):
    """Write a ``BucketedUpdate`` back into the model-layout lora tree as
    one XLA program (swapaxes/slice/astype plumbing included)."""
    from repro.core.lora import _is_lora_path
    lookup = {p: (bi, j) for bi, group in enumerate(bucket_parents)
              for j, p in enumerate(group)}

    def rebuild(path, x):
        if x is None or not _is_lora_path(path):
            return x
        parent = tuple(str(getattr(p, "key", p)) for p in path[:-1])
        if path[-1].key == "lora_m":
            m_new = mags.get((parent, "m"))
            return x if m_new is None else m_new.astype(x.dtype)
        bi, j = lookup[parent]
        b_g, a_g = bucket_stacks[bi]
        if path[-1].key == "lora_a":
            return jnp.swapaxes(b_g[j], -2, -1).astype(x.dtype)
        return jnp.swapaxes(a_g[j], -2, -1).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(rebuild, lora_tree,
                                            is_leaf=lambda x: x is None)


def flatten_cohort(members, ranks, n_k, staleness=None, present=None,
                   r_min: int = 1):
    """Permute per-sampled-client vectors into stacked group-member order.

    ``members[j]`` is the sampled-client index at stacked position j, or -1
    for a GHOST (shard padding): ghosts take rank ``r_min``, zero samples,
    zero staleness and are never present, so every weight they receive is
    identically zero. This is the single member-rebase rule shared by the
    grouped engines (``_aggregate_grouped``) and the protocol checker's
    ghost-rule invariant (``analysis/protocol.py``) -- the checker verifies
    the very arrays the aggregation consumes."""
    ranks_o = [ranks[i] if i >= 0 else r_min for i in members]
    n_k_o = [n_k[i] if i >= 0 else 0 for i in members]
    stal_o = (None if staleness is None else
              [staleness[i] if i >= 0 else 0 for i in members])
    pres_o = (None if present is None else
              [bool(present[i]) if i >= 0 else False for i in members])
    return ranks_o, n_k_o, stal_o, pres_o


@dataclass
class RoundPlan:
    """One round's sampled work order, carried between the round stages.

    Everything rng-dependent (client sample, data batches) is fixed at PLAN
    time, so the sampling stream is identical across engines and pipeline
    depths. After the train stage the plan carries the dispatched group
    factor stacks and per-group loss handles -- unmaterialized jax arrays
    (``client.dispatch_group_masked``), which is what lets the async engine
    buffer trained-but-not-yet-aggregated rounds without blocking.
    """

    round: int                 # the logical round this plan aggregates into
    version: int               # global model version when training dispatched
    clients: List[int]
    ranks: List[int]
    n_k: List[int]
    lr: float
    client_batches: Optional[list] = None   # dropped once training dispatched
    # grouped engines: [(members, r_max, {adapter_path: stacked factors})]
    group_factors: Optional[list] = None
    loss_parts: Optional[list] = None       # [(members, loss handle | None)]
    # sequential engine: per-client factor dicts + eager float losses
    client_factors: Optional[list] = None
    losses: Optional[list] = None


class FederatedLoRA:
    """End-to-end heterogeneous-rank FedLoRA driver."""

    def __init__(self, model: Model, fl: FLConfig, lora: LoRAConfig,
                 registry: ClientRegistry,
                 batch_fn: Callable[[int, np.random.Generator], list],
                 *, base_params=None, seed: Optional[int] = None,
                 backend: str = "factored",
                 partial_up_to: Optional[int] = None,
                 server_momentum=None,
                 round_engine: str = "batched",
                 mesh=None,
                 pipeline_depth: int = 1,
                 staleness_gamma: float = 1.0,
                 event_scheduler=None,
                 transport=None):
        """batch_fn(client_id, rng) -> list of training batches (dicts).

        ``round_engine="sharded"`` runs the batched engine's dispatches as
        shard_map programs over ``mesh``'s ``data`` axis (defaults to a
        1-D mesh over every visible device, ``launch/mesh.py::make_fl_mesh``).

        ``round_engine="async"`` buffers rounds: up to ``pipeline_depth``
        trained plans are in flight (training dispatched, aggregation
        pending), one buffered aggregation consumes them all, and stale
        contributions are discounted by ``staleness_gamma**staleness``
        (gamma=1: no discount). ``pipeline_depth=1`` IS the batched engine.
        An explicit ``mesh`` routes the async stages through the sharded
        dispatches.

        ``event_scheduler`` (requires ``round_engine="async"``): an
        ``events.EventScheduler`` replacing the fixed cadence with
        arrival-event buffer triggers on the virtual clock (see module
        docstring / DESIGN.md §7).

        ``transport``: a ``transport.UpdateTransport`` (or
        ``TransportConfig``) compressing client->server factor uploads:
        int8/bf16 per-column quantization with per-client error-feedback
        accumulators, dequantized once at aggregation stack-build time
        (DESIGN.md §12). None ships f32 factors unchanged.
        """
        assert round_engine in ("batched", "sequential", "sharded",
                                "async"), round_engine
        assert pipeline_depth >= 1, pipeline_depth
        assert 0.0 < staleness_gamma <= 1.0, staleness_gamma
        assert event_scheduler is None or round_engine == "async", \
            "event_scheduler rides round_engine='async'"
        self.round_engine = round_engine
        self.pipeline_depth = pipeline_depth if round_engine == "async" else 1
        self.staleness_gamma = staleness_gamma
        if round_engine == "sharded" and mesh is None:
            from repro.launch.mesh import make_fl_mesh
            mesh = make_fl_mesh()
        if mesh is not None:
            assert "data" in mesh.axis_names, mesh.axis_names
        self.mesh = mesh
        self.model = model
        self.fl = fl
        self.lora_cfg = lora
        self.registry = registry
        self.batch_fn = batch_fn
        self.rng = np.random.default_rng(fl.seed if seed is None else seed)
        params = base_params if base_params is not None else model.init(
            jax.random.PRNGKey(fl.seed))
        self.base, self.global_lora = split_lora(params)
        self.trainer = LocalTrainer(model, weight_decay=fl.weight_decay,
                                    freeze_a=(fl.aggregator == "ffa"))
        if isinstance(transport, TransportConfig):
            transport = UpdateTransport(transport)
        assert transport is None or isinstance(transport, UpdateTransport), \
            transport
        self.transport = transport
        self.server_momentum = server_momentum  # FactoredServerMomentum|None
        self.aggregator = Aggregator(fl.aggregator, lora.rank_levels,
                                     backend=backend,
                                     partial_up_to=partial_up_to)
        self.schedule = get_schedule(fl.lr_schedule, fl.learning_rate,
                                     fl.num_rounds)
        self.round_idx = 0
        # serving hot-swap (DESIGN.md §11): every aggregation landing bumps
        # the adapter version and fires the post-aggregate hooks with the
        # fresh global factors -- sync engines at round finalize, async /
        # event engines whenever their buffer fires (incl. drain_pending)
        self.adapter_version = 0
        self._post_aggregate_hooks: List[Callable] = []
        self.energy = EnergyTrace(lora.rank_levels)
        self.history: List[RoundStats] = []
        self._extract_jit = None   # lazily-built jitted factor extractor
        # async engine state: FIFO of trained-but-unaggregated plans
        # (their rounds are already counted) and the next round to plan
        self._pending: "deque[RoundPlan]" = deque()
        self._plan_idx = 0
        # finalized rounds whose stats still hold unmaterialized handles
        self._stat_queue: deque = deque()
        # event-driven async engine: arrival-event scheduler on the
        # virtual clock; "join" lifecycle events grow the client registry
        self.event_scheduler = None
        if event_scheduler is not None:
            self.set_event_scheduler(event_scheduler)

    def set_event_scheduler(self, scheduler) -> None:
        """Attach an event scheduler before the first round -- lets callers
        inspect the built registry first (e.g. pick the high-rank clients
        as the straggler set) and then wire the scenario."""
        assert self.round_engine == "async", self.round_engine
        assert self.round_idx == 0 and not self._pending, \
            "attach the event scheduler before running rounds"
        self.event_scheduler = scheduler
        scheduler.bind_join_hook(self._apply_join)

    def _apply_join(self, ev) -> None:
        """Apply a "join" lifecycle event to the registry. Idempotent: the
        event declares the id it creates, so replaying the lifecycle prefix
        after a checkpoint restore cannot double-register."""
        if ev.client < self.registry.num_clients:
            return                      # already applied (restore replay)
        assert ev.client == self.registry.num_clients, \
            (ev.client, self.registry.num_clients)
        assert ev.rank is not None and ev.shard is not None, ev
        self.registry.add_client(ev.rank, ev.shard)

    # -- adapter plumbing ---------------------------------------------------

    def _extract_factors(self, lora_tree, rank: int) -> Dict[tuple, tuple]:
        """{adapter_path: (B (…, d_in, r_k), A (…, r_k, d_out))}.

        Model layout: lora_a (…, r_max, in), lora_b (…, out, r_max).
        Paper layout: B = lora_a^T restricted to r_k, A = lora_b^T.
        """
        from repro.core.lora import _is_lora_path
        pairs: Dict[tuple, dict] = {}

        def collect(path, x):
            if x is not None and _is_lora_path(path):
                parent = tuple(str(getattr(p, "key", p)) for p in path[:-1])
                kind = {"lora_a": "a", "lora_b": "b",
                        "lora_m": "m"}[path[-1].key]
                pairs.setdefault(parent, {})[kind] = x
            return x

        jax.tree_util.tree_map_with_path(collect, lora_tree,
                                         is_leaf=lambda x: x is None)
        out = {}
        for parent, ab in pairs.items():
            a_model = ab["a"]           # (…, r_max, in)
            b_model = ab["b"]           # (…, out, r_max)
            b_paper = jnp.swapaxes(a_model, -2, -1)[..., :rank]   # (…, in, r_k)
            a_paper = jnp.swapaxes(b_model, -2, -1)[..., :rank, :]  # (…, r_k, out)
            out[parent] = (b_paper, a_paper)
            if "m" in ab:               # DoRA magnitude: FedAvg'd separately
                out[(parent, "m")] = ab["m"]
        return out

    def _extract_factors_batched(self, lora_tree, rank: int
                                 ) -> Dict[tuple, tuple]:
        """Jitted ``_extract_factors`` (batched engine): the whole tree's
        swapaxes/slice plumbing is one XLA dispatch. Adapter pairs and DoRA
        magnitudes are returned as separate jit outputs because their dict
        keys don't sort against each other (pytree flattening sorts keys)."""
        if self._extract_jit is None:
            def ex(tree, r):
                out = self._extract_factors(tree, r)
                pairs = {k: v for k, v in out.items()
                         if not self._is_magnitude(k)}
                mags = {k: v for k, v in out.items()
                        if self._is_magnitude(k)}
                return pairs, mags
            self._extract_jit = jax.jit(ex, static_argnums=(1,))
        pairs, mags = self._extract_jit(lora_tree, rank)
        return {**pairs, **mags}

    def _write_factors(self, results) -> None:
        """Write aggregated (b_g, a_g) back into the global lora tree.

        ``BucketedUpdate`` (grouped engines) writes in ONE jitted dispatch;
        a per-adapter dict (sequential reference) writes eagerly."""
        with tracing.span("fl.write"):
            if isinstance(results, BucketedUpdate):
                self.global_lora = _write_bucketed(
                    self.global_lora,
                    tuple((b, a) for _, b, a in results.buckets),
                    results.mags,
                    bucket_parents=tuple(
                        parents for parents, _, _ in results.buckets))
            else:
                from repro.core.lora import _is_lora_path

                def rebuild(path, x):
                    if x is None or not _is_lora_path(path):
                        return x
                    parent = tuple(str(getattr(p, "key", p))
                                   for p in path[:-1])
                    if path[-1].key == "lora_m":
                        m_new = results.get((parent, "m"))
                        return x if m_new is None else m_new.astype(x.dtype)
                    b_g, a_g = results[parent]
                    if path[-1].key == "lora_a":
                        return jnp.swapaxes(b_g, -2, -1).astype(x.dtype)
                    return jnp.swapaxes(a_g, -2, -1).astype(x.dtype)

                self.global_lora = jax.tree_util.tree_map_with_path(
                    rebuild, self.global_lora, is_leaf=lambda x: x is None)
        # round landing: bump the serving adapter version and notify
        # subscribers (AdapterStore hot-swap) with the new global factors.
        # Hooks degrade to skip-and-warn: a run whose adapters are not
        # servable (DoRA magnitudes rejected by the AdapterStore, non-LoRA
        # variants refused by the serving engine) must not take down the
        # round loop from inside its own landing notification.
        self.adapter_version += 1
        for hook in self._post_aggregate_hooks:
            try:
                hook(self.adapter_version, self.global_lora)
            except Exception as e:  # noqa: BLE001 -- hooks are best-effort
                warnings.warn(
                    f"post-aggregate hook {hook!r} failed at adapter "
                    f"version {self.adapter_version} ({e}); skipping -- "
                    "the round loop continues, the subscriber keeps its "
                    "previous snapshot", RuntimeWarning, stacklevel=2)

    def add_post_aggregate_hook(self, hook) -> None:
        """Register ``hook(adapter_version, global_lora)`` to fire at every
        aggregation landing, across ALL round engines (the single choke
        point is ``_write_factors``)."""
        self._post_aggregate_hooks.append(hook)

    def _merge_flora_delta(self, deltas: Dict[tuple, jnp.ndarray]) -> None:
        """FLoRA: fold dW into the base dense weights (cold-start restart)."""
        def apply(path, x):
            if x is None:
                return x
            key = getattr(path[-1], "key", None)
            if key != "w":
                return x
            parent = tuple(str(getattr(p, "key", p)) for p in path[:-1])
            if parent in deltas:
                return (x.astype(jnp.float32)
                        + deltas[parent].astype(jnp.float32)).astype(x.dtype)
            return x

        self.base = jax.tree_util.tree_map_with_path(
            apply, self.base, is_leaf=lambda x: x is None)

    # -- local training (both engines) --------------------------------------

    def _train_sequential(self, client_batches, ranks, lr, clients):
        """Reference path: one ``trainer.train`` call per sampled client."""
        client_factors: List[Dict[tuple, tuple]] = []
        losses = []
        for batches, rank, cid in zip(client_batches, ranks, clients):
            trained, metrics = self.trainer.train(
                self.base, self.global_lora, rank, batches, lr)
            factors = self._extract_factors(trained, rank)
            if self.transport is not None:
                factors = self.transport.encode_client(cid, factors)
            client_factors.append(factors)
            losses.append(float(metrics.get("loss", jnp.nan)))
        return client_factors, losses

    def _train_grouped(self, client_batches, ranks, lr, clients, *,
                       sharded: bool):
        """Batched AND sharded engines: ONE vmapped, jitted multi-client
        dispatch per step-count group trains every sampled client
        regardless of rank (``train_group_masked``: factors zero-masked
        beyond each client's rank, per-client lora scale vmapped -- exact,
        see client.py). Step counts are homogeneous in the common case.
        Factors stay stacked over each group's client axis -- the grouped
        aggregation consumes them stacked, so nothing is unstacked per
        client.

        ``sharded=True`` additionally pads each group's client axis to a
        multiple of the mesh shard count with GHOST clients and partitions
        it round-robin across shards (stacked position j -> shard j % S, so
        ghosts spread evenly instead of piling onto the last shard) before
        dispatching through the shard_map runner. Ghosts clone the group's
        first member's batches -- any finite data works because their
        aggregation weight is identically zero (n_k=0 => omega=0), and
        cloning keeps their losses/gradients finite so 0-weighted NaNs can
        never poison the cross-shard psum.

        Returns (group_factors, loss_parts): group_factors entries are
        (members, r_max, {adapter_path: stacked factors}) where members[j]
        is the sampled-client index at stacked position j, or -1 for a
        ghost; loss_parts entries are (members, loss handle) with the loss
        handle an UNMATERIALIZED jax array (or None for a zero-step group)
        -- nothing in this function blocks on device execution, so the
        async engine can buffer the whole round as in-flight handles
        (``_losses_from_parts`` materializes them at finalize time)."""
        groups: Dict[int, List[int]] = {}
        for i, batches in enumerate(client_batches):
            groups.setdefault(len(batches), []).append(i)
        tracing.count("server/train_groups", len(groups))
        group_factors = []
        loss_parts = []
        r_max = self.lora_cfg.r_max
        r_min = min(self.lora_cfg.rank_levels)
        for steps, idxs in sorted(groups.items()):
            members = idxs
            tracing.count("server/train_stack_steps", steps * len(idxs))
            if sharded:
                n_shards = self.mesh.shape["data"]
                members = idxs + [-1] * ((-len(idxs)) % n_shards)
                # round-robin -> contiguous shard blocks: shard s's block
                # holds stacked positions {j : j % S == s} of the original
                # order
                order = sorted(range(len(members)),
                               key=lambda j: (j % n_shards, j // n_shards))
                members = [members[j] for j in order]
            g_ranks = [ranks[i] if i >= 0 else r_min for i in members]
            # stack on the HOST (numpy) -- an eager jnp.stack would
            # synchronize with in-flight device work on the CPU client and
            # break the async engine's overlap; the training dispatch
            # transfers the stacked batches
            with tracing.span("fl.stack"):
                stacks = [
                    jax.tree.map(lambda *xs: _stack_steps(xs),
                                 *[client_batches[i if i >= 0 else idxs[0]][t]
                                   for i in members])
                    for t in range(steps)]
            lora_g, loss_g = self.trainer.dispatch_group_masked(
                self.base, self.global_lora, g_ranks, stacks, lr,
                mesh=self.mesh if sharded else None)
            # masked training leaves zeros beyond each client's rank, which
            # is exactly the zero-padded (G, ..., d, r_max) stack layout the
            # grouped aggregation expects; _extract_factors is shape-
            # agnostic in the leading axes
            factors = self._extract_factors_batched(lora_g, r_max)
            if self.transport is not None:
                # compress the group's upload: error-feedback accumulators
                # are keyed by GLOBAL client id (the same client carries
                # its residual across rounds); ghosts (-1) get zeros in and
                # their residual out is discarded. Quantization preserves
                # the zero columns beyond each client's rank (absmax 0 ->
                # scale 0), so the grouped stack layout is unchanged.
                gids = [clients[i] if i >= 0 else -1 for i in members]
                factors = self.transport.encode_group(gids, factors)
            group_factors.append((members, r_max, factors))
            loss_parts.append((members, loss_g))
        return group_factors, loss_parts

    @staticmethod
    def _losses_from_parts(loss_parts, num_clients: int) -> List[float]:
        """Materialize per-group loss handles into sampled-client-order
        floats (ghost losses dropped). The one host transfer of the train
        stage, deferred to round finalize so pipelined rounds never block
        on it early."""
        losses = [float("nan")] * num_clients
        for members, loss_g in loss_parts:
            arr = (np.asarray(loss_g) if loss_g is not None
                   else np.full((len(members),), np.nan))
            for j, i in enumerate(members):
                if i >= 0:
                    losses[i] = float(arr[j])
        return losses

    # -- aggregation (both engines) ------------------------------------------

    @staticmethod
    def _is_magnitude(parent) -> bool:
        return (isinstance(parent, tuple) and len(parent) == 2
                and parent[1] == "m")

    def _aggregate_magnitudes(self, client_factors, parents, w_clients,
                              results) -> None:
        """DoRA magnitudes: weighted FedAvg (not rank-structured)."""
        for parent in parents:
            ms = jnp.stack([cf[parent] for cf in client_factors])
            results[parent] = weighted_avg(ms, w_clients)

    def _aggregate_sequential(self, client_factors, ranks, n_k):
        """Reference path: one ``aggregate_layer`` call per adapter."""
        results, deltas, sigmas = {}, {}, {}
        global_factors = self._extract_factors(self.global_lora,
                                               self.lora_cfg.r_max)
        w_clients = jnp.asarray(np.asarray(n_k) / np.sum(n_k))
        parents = list(client_factors[0])
        self._aggregate_magnitudes(
            client_factors, [p for p in parents if self._is_magnitude(p)],
            w_clients, results)
        for parent in parents:
            if self._is_magnitude(parent):
                continue
            factors = [cf[parent] for cf in client_factors]
            g_b, g_a = global_factors[parent]
            res = self.aggregator.aggregate_layer(factors, ranks, n_k,
                                                  global_b=g_b, global_a=g_a)
            self._record_result(parent, (g_b, g_a), res, results, deltas,
                                sigmas)
        return results, deltas, self._sigma_probe(parents, sigmas)

    def _aggregate_grouped(self, group_factors, ranks, n_k, *,
                           sharded: bool, staleness=None, present=None):
        """Batched, sharded AND async engines: bucket adapters by factor
        shape and aggregate each bucket with ONE jitted call.

        The client axis is assembled group-by-group (clients stay in rank-
        group order, with ranks/n_k permuted to match), so each bucket needs
        only one pad + one concatenate per training group instead of
        per-client restacking. ``sharded=True`` routes each bucket through
        ``aggregate_grouped_sharded`` (client axis left sharded over the
        mesh, one psum per bucket); ghost members (-1) ride along with
        n_k=0 so every weight they receive -- including the DoRA magnitude
        FedAvg weights -- is exactly zero.

        ``staleness``: per-sampled-client aggregation ages (async engine);
        folded into every n_k-derived weight via
        ``aggregation.staleness_discount`` with ``self.staleness_gamma``.
        ``present``: per-sampled-client participation mask (event-driven
        engine): not-yet-arrived clients get exactly zero weight everywhere
        -- including the DoRA magnitude FedAvg -- and are excluded from
        membership-derived weighting (``Aggregator._present_weight_args``).
        Server momentum, when configured, applies per bucket in ONE jitted
        dispatch (``FactoredServerMomentum.apply_bucket``) instead of an
        unjitted per-adapter host loop. Returns a ``BucketedUpdate`` (plus
        flora deltas and the lazy sigma probe) -- per-adapter unstacking is
        deferred into the jitted write-back."""
        update = BucketedUpdate()
        deltas = {}
        sigma_probe = None
        r_max = self.lora_cfg.r_max
        r_min = min(self.lora_cfg.rank_levels)
        gamma = self.staleness_gamma
        global_factors = self._extract_factors_batched(self.global_lora,
                                                       r_max)
        # group-order permutation of the client axis (ghosts: rank r_min,
        # zero samples, zero staleness, never present)
        members = [i for mem, _, _ in group_factors for i in mem]
        tracing.count("server/agg_members", len(members))
        ranks_o, n_k_o, stal_o, pres_o = flatten_cohort(
            members, ranks, n_k, staleness, present, r_min)
        w_clients = jnp.asarray(cohort_weights(n_k_o, stal_o, pres_o, gamma))
        parents = list(group_factors[0][2])
        for parent in [p for p in parents if self._is_magnitude(p)]:
            # DoRA magnitudes: weighted FedAvg (not rank-structured)
            ms = jnp.concatenate([fg[parent] for _, _, fg in group_factors])
            update.mags[parent] = weighted_avg(ms, w_clients)
        buckets: Dict[tuple, List] = {}
        for parent in parents:
            if self._is_magnitude(parent):
                continue
            gb0, ga0 = global_factors[parent]
            buckets.setdefault((gb0.shape, ga0.shape), []).append(parent)
        tracing.count("server/agg_buckets", len(buckets))
        for group in buckets.values():
            args = (
                [[fg[p][0] for p in group] for _, _, fg in group_factors],
                [[fg[p][1] for p in group] for _, _, fg in group_factors],
                ranks_o, n_k_o)
            kwargs = dict(
                global_bs=[global_factors[p][0] for p in group],
                global_as=[global_factors[p][1] for p in group],
                staleness=stal_o, gamma=gamma, present=pres_o)
            if sharded:
                res = self.aggregator.aggregate_grouped_sharded(
                    *args, self.mesh, **kwargs)
            else:
                res = self.aggregator.aggregate_grouped(*args, **kwargs)
            if self.server_momentum is not None:
                # whole-bucket momentum: one jitted stacked-QR-SVD dispatch
                b_new, a_new = self.server_momentum.apply_bucket(
                    tuple(group), [global_factors[p] for p in group],
                    res.b_g, res.a_g, r_max)
            else:
                b_new, a_new = res.b_g, res.a_g
            update.buckets.append((tuple(group), b_new, a_new))
            if res.merge_delta is not None:
                for j, parent in enumerate(group):
                    deltas[parent] = res.merge_delta[j]
            if res.sigma is not None and sigma_probe is None:
                # energy probe = the FIRST adapter's spectrum (bucket order
                # preserves first-seen parent order). Kept as the UNSLICED
                # bucket stack handle -- even an eager slice would
                # synchronize with the device; flush_stats slices/averages
                # in numpy after the one d2h transfer.
                sigma_probe = ("bucket_stack", res.sigma)
        return update, deltas, sigma_probe

    def _record_result(self, parent, global_pair, res, results, deltas,
                       sigmas) -> None:
        if self.server_momentum is not None:
            results[parent] = self.server_momentum.apply(
                parent, global_pair, (res.b_g, res.a_g), self.lora_cfg.r_max)
        else:
            results[parent] = (res.b_g, res.a_g)
        if res.merge_delta is not None:
            deltas[parent] = res.merge_delta
        if res.sigma is not None:
            sigmas[parent] = res.sigma

    @staticmethod
    def _sigma_probe(parents, sigmas) -> Optional[jnp.ndarray]:
        """First adapter's spectrum (layer-averaged) as the energy probe.

        Returned UNMATERIALIZED (a lazy jax array): reading it is the round's
        device-sync point, so it happens at stat-materialization time, not
        inside the aggregate stage."""
        for parent in parents:
            if parent in sigmas:
                sig = jnp.asarray(sigmas[parent])
                return sig if sig.ndim == 1 else sig.mean(axis=0)
        return None

    # -- the round: plan -> train -> aggregate stages ------------------------

    def _now(self) -> float:
        """The round-stat clock. With an event scheduler this is the
        VIRTUAL clock -- the event-driven round path must not read the
        host clock (runs would stop being a pure function of the seed;
        the rng/determinism lint bans ``time.time()`` there), so its
        ``wall_time_s`` is virtual seconds. The wall-clock engines keep
        real wall time."""
        if self.event_scheduler is not None:
            return self.event_scheduler.clock.now
        return time.time()  # host-clock: ok (wall-clock engines only)

    @property
    def _sharded_dispatch(self) -> bool:
        """Whether the grouped stages run through the shard_map dispatches
        (the sharded engine always; the async engine iff given a mesh)."""
        return (self.round_engine == "sharded"
                or (self.round_engine == "async" and self.mesh is not None))

    def _plan_round(self) -> RoundPlan:
        """PLAN stage: sample clients/ranks/n_k/lr and draw data batches.

        Consumes the rng in strict round order (one ``sample_round`` + one
        ``batch_fn`` per client), so the sampling stream is identical across
        engines AND pipeline depths -- a resumed or re-depth'd run sees the
        same clients.

        With an event scheduler the sample is drawn from the ACTIVE client
        pool (dropouts excluded, joined clients included); scenarios with
        no lifecycle events keep ``active=None`` and therefore the exact
        historical rng stream."""
        with tracing.span("fl.plan"):
            fl = self.fl
            active = (None if self.event_scheduler is None else
                      self.event_scheduler.active_clients(
                          self.registry.num_clients))
            clients = self.registry.sample_round(fl.clients_per_round,
                                                 self.rng,
                                                 active=active).tolist()
            tracing.count("server/plan_clients", len(clients))
            plan = RoundPlan(
                round=self._plan_idx, version=self.round_idx,
                clients=clients,
                ranks=[int(self.registry.ranks[c]) for c in clients],
                n_k=[max(self.registry.num_samples(c), 1) for c in clients],
                lr=self.schedule(self._plan_idx),
                client_batches=[self.batch_fn(cid, self.rng)
                                for cid in clients])
            self._plan_idx += 1
            return plan

    def _train_stage(self, plan: RoundPlan) -> None:
        """TRAIN stage: dispatch the plan's local training. Grouped engines
        are non-blocking (jax handles stay enqueued); the sequential
        reference trains eagerly."""
        with tracing.span("fl.train", clients=len(plan.clients)):
            if self.round_engine == "sequential":
                plan.client_factors, plan.losses = self._train_sequential(
                    plan.client_batches, plan.ranks, plan.lr, plan.clients)
            else:
                plan.group_factors, plan.loss_parts = self._train_grouped(
                    plan.client_batches, plan.ranks, plan.lr, plan.clients,
                    sharded=self._sharded_dispatch)
        plan.client_batches = None     # free the host-side batch copies

    def _aggregate_stage(self, plan: RoundPlan, staleness: int = 0):
        """AGGREGATE stage: bucketed aggregation + SVD realloc (+ bucketed
        server momentum) of one trained plan against the CURRENT global
        adapters, discounting by the plan's staleness."""
        with tracing.span("fl.aggregate"):
            if self.round_engine == "sequential":
                return self._aggregate_sequential(plan.client_factors,
                                                  plan.ranks, plan.n_k)
            return self._aggregate_grouped(
                plan.group_factors, plan.ranks, plan.n_k,
                sharded=self._sharded_dispatch,
                staleness=[staleness] * len(plan.clients))

    def _finalize_round(self, plan: RoundPlan, results, deltas, sigma_probe,
                        t0: float) -> RoundStats:
        """Write back the aggregate (``results=None`` on async buffer-fill
        rounds: the global model is unchanged), record energy/stats,
        advance the round counter.

        All host sync points (loss materialization, sigma probe) are
        deferred through the stat queue: the synchronous engines flush it
        immediately (keep=0 -- identical behavior to before), while the
        async engine keeps up to ``pipeline_depth - 1`` rounds' stats as
        unmaterialized handles so the host never waits for the device
        inside the pipelined window. The returned RoundStats object is
        patched IN PLACE when its handles materialize; ``run()``, ``save``
        and ``drain_pending`` flush, so histories read after any of those
        are always complete."""
        if results is not None:
            self._write_factors(results)
        if deltas:
            self._merge_flora_delta(deltas)
        stats = RoundStats(
            round=plan.round, clients=plan.clients, ranks=plan.ranks,
            lr=plan.lr, mean_client_loss=float("nan"),
            sigma_probe=None, wall_time_s=self._now() - t0)
        self.history.append(stats)
        self.round_idx += 1
        self._stat_queue.append((stats, plan, sigma_probe))
        keep = (self.pipeline_depth - 1
                if self.round_engine == "async" else 0)
        self.flush_stats(keep=keep)
        return stats

    @staticmethod
    def _materialize_probe(sigma_probe) -> Optional[np.ndarray]:
        """One d2h transfer + numpy slice/average of a probe handle."""
        if sigma_probe is None:
            return None
        if (isinstance(sigma_probe, tuple)
                and sigma_probe[0] == "bucket_stack"):
            arr = np.asarray(sigma_probe[1])[0]
        else:
            arr = np.asarray(sigma_probe)
        return arr if arr.ndim == 1 else arr.mean(axis=0)

    def flush_stats(self, keep: int = 0) -> None:
        """Materialize queued round stats (oldest first) until at most
        ``keep`` remain pending: loss handles -> mean client loss, sigma
        probe -> energy trace + history entry. The event-driven engine can
        fire several aggregations inside one round's window, so an entry
        may carry a LIST of probe handles -- each is recorded in the energy
        trace; the round's stats keep the last."""
        with tracing.span("fl.sync"):
            while len(self._stat_queue) > keep:
                stats, plan, sigma_probe = self._stat_queue.popleft()
                probes = (sigma_probe if isinstance(sigma_probe, list)
                          else [sigma_probe])
                for handle in probes:
                    probe = self._materialize_probe(handle)
                    if probe is not None:
                        self.energy.record(probe)
                        stats.sigma_probe = probe
                losses = (plan.losses if plan.losses is not None
                          else self._losses_from_parts(plan.loss_parts,
                                                       len(plan.ranks)))
                # nanmean: a zero-batch client trains 0 steps and reports
                # NaN -- a per-client condition that must not poison the
                # round stat
                loss_arr = np.asarray(losses, dtype=np.float64)
                stats.mean_client_loss = (
                    float(np.nanmean(loss_arr))
                    if not np.all(np.isnan(loss_arr)) else float("nan"))

    def run_round(self) -> RoundStats:
        """One round of the configured engine, as the span ``fl.round``."""
        with tracing.span("fl.round", round=self.round_idx):
            if self.round_engine == "async":
                return self._run_round_async()
            t0 = self._now()
            plan = self._plan_round()
            self._train_stage(plan)
            results, deltas, sigma_probe = self._aggregate_stage(plan)
            return self._finalize_round(plan, results, deltas, sigma_probe,
                                        t0)

    def _run_round_async(self) -> RoundStats:
        """One async round: plan + dispatch this round's training
        (non-blocking -- nothing here waits on the device), buffer the
        plan, and run ONE buffered aggregation when ``pipeline_depth``
        plans are pending.

        This is FedBuff-style buffered aggregation on a deterministic
        cadence: the server applies one staleness-discounted aggregation
        per ``pipeline_depth`` training rounds, consuming the whole buffer
        in one bucketed dispatch. Plan age in rounds IS the staleness
        (mixed 0..depth-1 within every aggregation), so
        ``staleness_gamma`` shifts relative weight toward fresher rounds.
        The wins: (a) aggregation + SVD realloc + global write-back +
        momentum amortize over depth rounds (fewer server steps for the
        same training throughput -- measurable even on a serial host), and
        (b) training dispatches never wait for aggregation, so on parallel
        hardware round t+1's local training overlaps the buffered
        aggregation's device time. ``pipeline_depth=1`` aggregates every
        round with zero staleness -- exactly the batched engine.

        Buffer-fill rounds report their training losses; sigma_probe (and
        an energy-trace entry) appears on aggregation rounds only.

        With an ``event_scheduler`` the cadence is replaced by arrival
        events on the virtual clock (``_run_round_event``).
        """
        if self.event_scheduler is not None:
            return self._run_round_event()
        t0 = self._now()
        plan = self._plan_round()
        self._train_stage(plan)
        self._pending.append(plan)
        results, deltas, sigma_probe = None, None, None
        if len(self._pending) >= self.pipeline_depth:
            results, deltas, sigma_probe = self._aggregate_buffer(plan.round)
        return self._finalize_round(plan, results, deltas, sigma_probe, t0)

    # -- event-driven async rounds (DESIGN.md §7) ----------------------------

    def _run_round_event(self) -> RoundStats:
        """One event-driven round: plan + dispatch training at the current
        virtual time, register per-client arrival events, then advance the
        clock one ``round_interval`` processing arrivals / lifecycle events
        in order. Every trigger firing runs ONE buffered aggregation over
        exactly the arrived-but-unaggregated updates (partial cohorts ride
        the ghost zero-weight rule) and applies it immediately, so later
        fires in the same window see the updated global adapters."""
        t0 = self._now()
        sched = self.event_scheduler
        plan = self._plan_round()
        self._train_stage(plan)
        self._pending.append(plan)
        sched.dispatch(plan.round, plan.clients)
        probes = []
        for fire_time in sched.advance_window():
            probe = self._fire_aggregation(fire_time)
            if probe is not None:
                probes.append(probe)
        self._retire_completed()
        stats = self._finalize_round(plan, None, None, probes or None, t0)
        stats.virtual_time = sched.clock.now
        return stats

    def _fire_aggregation(self, fire_time: float):
        """Aggregate every arrived-but-unaggregated client update at one
        trigger firing and apply it to the global adapters. Returns the
        (lazy) sigma probe handle, or None if nothing was buffered."""
        results, deltas, sigma_probe = self._aggregate_arrivals(fire_time)
        if results is None:
            return None
        self._write_factors(results)
        if deltas:
            self._merge_flora_delta(deltas)
        return sigma_probe

    def _aggregate_arrivals(self, fire_time: float):
        """The event-driven buffered aggregation: merge the pending plans
        that have ready (arrived, unconsumed) members into one bucketed
        step -- full factor stacks with a ``present`` mask, so a plan can
        be consumed across several fires, each member exactly once.
        Staleness is arrival-time-derived (``EventScheduler.staleness_of``).
        """
        sched = self.event_scheduler
        ready = sched.take_ready()
        plans = [p for p in self._pending if p.round in ready]
        if not plans:
            return None, None, None
        ranks, n_k, group_factors = self._merge_plan_groups(plans)
        staleness, present = [], []
        for p in plans:
            arrived = ready[p.round]
            for j in range(len(p.clients)):
                present.append(j in arrived)
                staleness.append(
                    sched.staleness_of(fire_time, arrived[j])
                    if j in arrived else 0)
        with tracing.span("fl.aggregate"):
            return self._aggregate_grouped(
                group_factors, ranks, n_k, sharded=self._sharded_dispatch,
                staleness=staleness, present=present)

    def _retire_completed(self) -> None:
        """Drop pending plans whose every member has been aggregated or
        lost to a dropout -- their factor stacks are no longer needed
        (loss handles stay on the stat queue until flushed)."""
        done = set(self.event_scheduler.completed_plans())
        if not done:
            return
        for p in self._pending:
            if p.round in done:
                p.group_factors = None
                self.event_scheduler.forget_plan(p.round)
        self._pending = deque(p for p in self._pending
                              if p.round not in done)

    @staticmethod
    def _merge_plan_groups(plans):
        """Merge pending plans' rank-group factor stacks onto ONE sampled-
        client axis: member indices rebase by each plan's offset (ghosts
        stay -1). The single rebase rule shared by the cadence buffer and
        the event-driven arrival aggregation -- their bit-equivalence
        depends on it."""
        ranks = [r for p in plans for r in p.ranks]
        n_k = [n for p in plans for n in p.n_k]
        group_factors, off = [], 0
        for p in plans:
            group_factors += [
                ([m + off if m >= 0 else -1 for m in mem], r_max, fg)
                for mem, r_max, fg in p.group_factors]
            off += len(p.clients)
        return ranks, n_k, group_factors

    def _aggregate_buffer(self, as_of_round: int):
        """Aggregate EVERY pending plan in one buffered, staleness-
        discounted bucketed step (plan age in rounds = staleness). Member
        indices are offset into the merged sampled-client axis; the merged
        client set runs through the SAME grouped bucket pipeline as a
        single round's."""
        plans = list(self._pending)
        self._pending.clear()
        ranks, n_k, group_factors = self._merge_plan_groups(plans)
        staleness = [as_of_round - p.round
                     for p in plans for _ in p.clients]
        with tracing.span("fl.aggregate"):
            out = self._aggregate_grouped(
                group_factors, ranks, n_k,
                sharded=self._sharded_dispatch, staleness=staleness)
        for p in plans:
            # consumed by the aggregation dispatch; only loss_parts are
            # still needed (stat flush) -- dropping the factor-stack refs
            # caps retained memory at the buffer itself, not depth extra
            # rounds of trained factors riding the stat queue
            p.group_factors = None
        return out

    def drain_pending(self) -> Optional[np.ndarray]:
        """Flush a partially filled aggregation buffer early: run the
        buffered aggregation now instead of waiting for the cadence (e.g.
        before a final evaluation). No new round is recorded -- the
        pending plans' rounds already reported their stats -- but the
        aggregate updates the global model, the energy trace, and the last
        history entry's sigma probe. Returns the probe (None if nothing
        was pending).

        Event-driven engine: the remaining arrival events are played out
        (triggers still fire where due), then whatever is left buffered is
        force-aggregated at the final virtual time -- in-flight updates of
        dropped-out clients stay lost, by design."""
        if self.event_scheduler is not None:
            self.flush_stats()   # queued probes precede the drain's fires
            probe = None
            for fire_time in self.event_scheduler.drain():
                handle = self._fire_aggregation(fire_time)
                p = self._materialize_probe(handle)
                if p is not None:
                    self.energy.record(p)
                    probe = p
            self._retire_completed()
            if probe is not None and self.history:
                self.history[-1].sigma_probe = probe
            return probe
        if not self._pending:
            return None
        as_of = self._pending[-1].round
        results, deltas, sigma_probe = self._aggregate_buffer(as_of)
        self._write_factors(results)
        if deltas:
            self._merge_flora_delta(deltas)
        self.flush_stats()
        probe = self._materialize_probe(sigma_probe)
        if probe is not None:
            self.energy.record(probe)
            if self.history:
                self.history[-1].sigma_probe = probe
        return probe

    def run(self, rounds: Optional[int] = None,
            eval_fn: Optional[Callable] = None,
            eval_every: int = 10) -> List[RoundStats]:
        rounds = rounds if rounds is not None else self.fl.num_rounds
        for _ in range(rounds):
            self.run_round()
            if eval_fn is not None and self.round_idx % eval_every == 0:
                self.flush_stats()      # eval callbacks see complete history
                eval_fn(self)
        self.flush_stats()
        return self.history

    # -- evaluation / state --------------------------------------------------

    def global_params(self):
        return merge_lora(self.base, self.global_lora)

    def evaluate(self, batch: dict) -> dict:
        params = self.global_params()
        _, metrics = self.model.train_loss(params, batch,
                                           lora_rank=self.lora_cfg.r_max)
        return {k: float(v) for k, v in metrics.items()}

    @staticmethod
    def _stats_to_meta(s: RoundStats) -> dict:
        d = dataclasses.asdict(s)
        if d["sigma_probe"] is not None:
            d["sigma_probe"] = np.asarray(d["sigma_probe"]).tolist()
        return d

    @staticmethod
    def _stats_from_meta(d: dict) -> RoundStats:
        d = dict(d)
        if d.get("sigma_probe") is not None:
            d["sigma_probe"] = np.asarray(d["sigma_probe"], np.float32)
        return RoundStats(**d)

    # -- pending-plan (de)serialization: the async engine's in-flight buffer
    #
    # A pending plan's training was dispatched against global adapters that
    # may no longer exist by save time, so re-planning from the rng on
    # restore could NOT reproduce it -- the trained factor stacks themselves
    # are checkpointed (flat arrays, no pytree template needed on load).
    # Key encoding: "g{gi}/P/{adapter path}/b|a" for factor pairs,
    # "g{gi}/M/{adapter path}" for DoRA magnitudes, "g{gi}/loss" for the
    # per-group loss vector. Transport-quantized pairs store payload and
    # scale separately ("bq"/"bs" and "aq"/"as" leaves) so a mid-buffer
    # checkpoint round-trips the COMPRESSED plan bit-exactly (int8 payload
    # + f32 scales) instead of a dequantized approximation.

    @staticmethod
    def _factor_arrays(arrays: Dict[str, np.ndarray], key: str, val,
                       leaf: str) -> None:
        if isinstance(val, QuantFactor) or hasattr(val, "q"):
            arrays[f"{key}/{leaf}q"] = np.asarray(val.q)
            arrays[f"{key}/{leaf}s"] = np.asarray(val.scale)
        else:
            arrays[f"{key}/{leaf}"] = np.asarray(val)

    @staticmethod
    def _plan_arrays(plan: RoundPlan) -> Dict[str, np.ndarray]:
        arrays: Dict[str, np.ndarray] = {}
        for gi, (members, r_max, factors) in enumerate(plan.group_factors):
            for parent, val in factors.items():
                if FederatedLoRA._is_magnitude(parent):
                    arrays[f"g{gi}/M/" + "/".join(parent[0])] = \
                        np.asarray(val)
                else:
                    b, a = val
                    key = f"g{gi}/P/" + "/".join(parent)
                    FederatedLoRA._factor_arrays(arrays, key, b, "b")
                    FederatedLoRA._factor_arrays(arrays, key, a, "a")
        for gi, (_, loss_g) in enumerate(plan.loss_parts):
            if loss_g is not None:
                arrays[f"g{gi}/loss"] = np.asarray(loss_g)
        return arrays

    @staticmethod
    def _plan_meta(plan: RoundPlan) -> dict:
        return {"round": plan.round, "version": plan.version,
                "clients": plan.clients, "ranks": plan.ranks,
                "n_k": plan.n_k, "lr": plan.lr,
                "groups": [{"members": list(members), "r_max": r_max}
                           for members, r_max, _ in plan.group_factors]}

    @staticmethod
    def _plan_from_arrays(meta: dict, arrays: Dict[str, np.ndarray]
                          ) -> RoundPlan:
        group_factors, loss_parts = [], []
        for gi, g in enumerate(meta["groups"]):
            factors: Dict[tuple, object] = {}
            prefix = f"g{gi}/"
            pairs: Dict[tuple, dict] = {}
            for key, arr in arrays.items():
                if not key.startswith(prefix):
                    continue
                rest = key[len(prefix):]
                if rest.startswith("M/"):
                    factors[(tuple(rest[2:].split("/")), "m")] = \
                        jnp.asarray(arr)
                elif rest.startswith("P/"):
                    path, leaf = rest[2:].rsplit("/", 1)
                    pairs.setdefault(tuple(path.split("/")), {})[leaf] = \
                        jnp.asarray(arr)
            for parent, ba in pairs.items():
                factors[parent] = (
                    QuantFactor(ba["bq"], ba["bs"]) if "bq" in ba
                    else ba["b"],
                    QuantFactor(ba["aq"], ba["as"]) if "aq" in ba
                    else ba["a"])
            members = [int(m) for m in g["members"]]
            group_factors.append((members, int(g["r_max"]), factors))
            loss = arrays.get(prefix + "loss")
            loss_parts.append((members,
                               None if loss is None else jnp.asarray(loss)))
        return RoundPlan(
            round=int(meta["round"]), version=int(meta["version"]),
            clients=[int(c) for c in meta["clients"]],
            ranks=[int(r) for r in meta["ranks"]],
            n_k=[int(n) for n in meta["n_k"]], lr=float(meta["lr"]),
            group_factors=group_factors, loss_parts=loss_parts)

    def save(self, path: str) -> None:
        from repro.checkpointing.checkpoint import save_flat, save_pytree
        self.flush_stats()      # checkpointed history/energy are complete
        save_pytree(path + ".base", self.base)
        # full server state rides in the metadata: rng stream, energy trace,
        # and round history -- without them a resumed run samples a
        # DIFFERENT client sequence and judges collapse on a truncated trace
        meta = {"round": self.round_idx,
                "adapter_version": self.adapter_version,
                "method": self.fl.aggregator,
                "rng_state": self.rng.bit_generator.state,
                "energy": self.energy.state_dict(),
                "history": [self._stats_to_meta(s) for s in self.history]}
        # server momentum: without its (B_m, A_m) pairs a resumed
        # beta > 0 run silently restarts momentum from zero and diverges
        # from the uninterrupted run
        if self.server_momentum is not None and self.server_momentum.state:
            save_flat(path + ".momentum",
                      self.server_momentum.state_arrays())
            meta["momentum"] = True
        # compressed transport: per-client error-feedback accumulators ride
        # as flat f32 arrays (bit-exact) -- without them a resumed run
        # re-quantizes from zero residual and diverges from the
        # uninterrupted compressed run
        if self.transport is not None:
            save_flat(path + ".transport", self.transport.state_arrays())
            meta["transport"] = True
        # async engine: dispatched-but-unaggregated plans ride along so a
        # resumed run aggregates the SAME trained factors the uninterrupted
        # run would have
        if self._pending:
            meta["pending"] = [self._plan_meta(p) for p in self._pending]
            for i, plan in enumerate(self._pending):
                save_flat(path + f".pending{i}", self._plan_arrays(plan))
        # event-driven engine: the virtual clock, the in-flight arrival
        # queue, per-plan arrival/consumption bookkeeping and the latency
        # models' rng streams -- without them a resumed run re-draws
        # latencies and fires triggers at different virtual times
        if self.event_scheduler is not None:
            meta["events"] = self.event_scheduler.state_dict()
        save_pytree(path + ".lora", self.global_lora, metadata=meta)

    def restore(self, path: str) -> None:
        from repro.checkpointing.checkpoint import (load_flat, load_metadata,
                                                    load_pytree)
        self.base = load_pytree(path + ".base", self.base)
        self.global_lora = load_pytree(path + ".lora", self.global_lora)
        # in-flight state always resets to the CHECKPOINT's -- restoring
        # onto a server that has already run rounds (a mid-experiment
        # rollback) must not leak its pre-restore stat handles, pending
        # plans, or momentum into the restored run
        self._stat_queue.clear()
        self._pending.clear()
        if self.server_momentum is not None:
            self.server_momentum.state = None
        if self.transport is not None:
            self.transport.reset()
        meta = load_metadata(path + ".lora")
        if meta:
            self.round_idx = meta.get("round", self.round_idx)
            self.adapter_version = meta.get("adapter_version",
                                            self.adapter_version)
            if meta.get("rng_state") is not None:
                # restore IN PLACE on the server's seeded stream: no fresh
                # unseeded generator is ever constructed on the round path
                # (the checkpointed state overwrites whatever the stream
                # has drawn, which is the whole point of restore)
                self.rng.bit_generator.state = meta["rng_state"]
            if meta.get("energy") is not None:
                self.energy = EnergyTrace.from_state(meta["energy"])
            if meta.get("history") is not None:
                self.history = [self._stats_from_meta(d)
                                for d in meta["history"]]
            if meta.get("momentum") and self.server_momentum is not None:
                self.server_momentum.load_state_arrays(
                    load_flat(path + ".momentum"))
            if self.transport is not None:
                if meta.get("transport"):
                    self.transport.load_state_arrays(
                        load_flat(path + ".transport"))
                else:
                    # back-compat: a checkpoint written before the
                    # compressed transport existed carries no accumulator
                    # state -- resume with zero residuals instead of
                    # KeyError'ing (the telescoping restarts at e_0 = 0)
                    warnings.warn(
                        "checkpoint predates the compressed update "
                        "transport; error-feedback accumulators "
                        "initialize to zero", RuntimeWarning,
                        stacklevel=2)
            for i, pm in enumerate(meta.get("pending") or []):
                self._pending.append(self._plan_from_arrays(
                    pm, load_flat(path + f".pending{i}")))
            if self.event_scheduler is not None:
                # resets to the CHECKPOINT's event state (pristine when the
                # checkpoint was not event-driven); replays applied "join"
                # events so the registry matches the restored round
                self.event_scheduler.load_state_dict(meta.get("events"))
            else:
                # an event-driven checkpoint resumed without a scheduler
                # would re-draw latencies and fire on the wrong cadence --
                # refuse instead of silently diverging
                assert meta.get("events") is None, \
                    ("checkpoint carries event-scheduler state; attach an "
                     "EventScheduler before restore()")
        # pending plans belong to ALREADY-COUNTED rounds (the buffered-
        # aggregation cadence), so planning resumes at round_idx itself
        self._plan_idx = self.round_idx
