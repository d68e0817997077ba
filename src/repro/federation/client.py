"""Client-side local fine-tuning: LoRA-only gradients, AdamW, jitted per rank.

The client receives the (conceptually truncated) global adapters; we keep
the r_max-sized factors resident and run with static ``lora_rank=r_k``, which
slices the factors inside the forward -- mathematically identical to
truncate-then-train (gradients outside the slice are exactly zero) while
keeping one params pytree shape for all clients. The jit cache keys on r_k,
so there are at most |rank_levels| compilations.

``train_group`` is the batched round engine's per-rank-group entry point:
clients of one rank level train as ONE ``jax.vmap``-ed, jitted multi-client
step over the client axis of stacked LoRA trees -- same per-client math as
``train`` (the vmap wraps the exact same step function), one XLA dispatch
per group instead of one per client per step.

``train_group_masked`` goes further and batches ALL rank levels into a
single dispatch: every client runs at static ``lora_rank=r_max`` with its
adapter factors zero-masked beyond its own rank r_k and its own
``lora_scale`` vmapped in. This is EXACT, not an approximation: the masked
slices contribute zero to the forward, their gradients are identically zero
(each is a product with the other, zeroed, factor), so AdamW leaves them at
zero -- bit-for-bit the state sequential training leaves OUTSIDE its r_k
slice, which aggregation zero-pads anyway. One compilation and one XLA
dispatch cover the whole heterogeneous round.

``dispatch_group_masked`` wraps either masked runner as a NON-BLOCKING
handle pair (factor stacks, loss array) for the async round engine: jax's
async dispatch returns enqueued arrays immediately, so the server can
pipeline the next round's training against the current round's aggregation
without any ``jax.block_until_ready``/host-transfer synchronization point.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.lora import merge_lora, split_lora
from repro.models.transformer import Model
from repro.optim import AdamW


def _stack_steps(xs) -> "np.ndarray":
    """Batch-leaf stacking on the HOST when the leaves are numpy (the
    data-pipeline common case): an eager ``jnp.stack`` would synchronize
    with in-flight device work on jax's CPU client, serializing the async
    round engine's pipeline. Device-array leaves fall back to jnp.stack.
    Shared by the trainers' step-axis stacking here and the server's
    client-axis stacking (``federation/server.py``)."""
    if all(isinstance(x, np.ndarray) for x in xs):
        return np.stack(xs)
    return jnp.stack(xs)


def _stack_step_axis(batch_stacks):
    """The (T, G, ...) step-major stacks of a group's per-step batches,
    stacked on the host as the span ``fl.stack``."""
    if not batch_stacks:
        return ()
    with tracing.span("fl.stack"):
        return jax.tree.map(lambda *xs: _stack_steps(xs), *batch_stacks)


class LocalTrainer:
    def __init__(self, model: Model, *, weight_decay: float = 0.0,
                 freeze_a: bool = False):
        self.model = model
        self.opt = AdamW(weight_decay=weight_decay)
        self.freeze_a = freeze_a   # FFA-LoRA: train only the B factors
        self._step_cache: Dict[int, Callable] = {}
        self._vstep_cache: Dict[Tuple[int, int], Callable] = {}

    def _zero_frozen(self, grads):
        """FFA-LoRA: zero the A-factor gradients."""
        import jax.tree_util as jtu
        return jtu.tree_map_with_path(
            lambda p, g: (jnp.zeros_like(g)
                          if g is not None
                          and getattr(p[-1], "key", "") == "lora_a"
                          else g),
            grads, is_leaf=lambda x: x is None)

    def _make_raw_step(self, rank: int) -> Callable:
        """The un-jitted single-client step; shared by ``step_fn`` (jit) and
        ``group_runner`` (jit(vmap)) so both engines run identical math."""
        model, opt = self.model, self.opt
        scale = (self.model.lora.scaling(rank)
                 if self.model.lora is not None else 1.0)

        def loss_fn(lora, base, batch):
            params = merge_lora(base, lora)
            loss, metrics = model.train_loss(params, batch, lora_rank=rank,
                                             lora_scale=scale)
            return loss, metrics

        freeze_a = self.freeze_a

        def step(lora, opt_state, base, batch, lr):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(lora, base, batch)
            if freeze_a:
                grads = self._zero_frozen(grads)
            lora, opt_state = opt.update(grads, opt_state, lora, lr)
            return lora, opt_state, metrics

        return step

    def _make_raw_step_scaled(self) -> Callable:
        """Like ``_make_raw_step`` but at static ``lora_rank=r_max`` with a
        TRACED per-client ``lora_scale`` -- the all-rank masked runner vmaps
        over it. Named scopes mark the step's stages in the trace: under
        ``client.grad``, the forward as ``jvp(client.forward)`` and the
        backward as ``transpose(jvp(client.forward))``; then the optimizer
        update, ``client.update``."""
        model, opt = self.model, self.opt
        r_max = model.lora.r_max

        def loss_fn(lora, base, batch, scale):
            with jax.named_scope("client.forward"):
                params = merge_lora(base, lora)
                loss, metrics = model.train_loss(
                    params, batch, lora_rank=r_max, lora_scale=scale)
            return loss, metrics

        freeze_a = self.freeze_a

        def step(lora, opt_state, base, batch, lr, scale):
            with jax.named_scope("client.grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(lora, base, batch, scale)
            with jax.named_scope("client.update"):
                if freeze_a:
                    grads = self._zero_frozen(grads)
                lora, opt_state = opt.update(grads, opt_state, lora, lr)
            return lora, opt_state, metrics

        return step

    def step_fn(self, rank: int) -> Callable:
        if rank not in self._step_cache:
            self._step_cache[rank] = jax.jit(self._make_raw_step(rank))
        return self._step_cache[rank]

    def group_runner(self, rank: int, steps: int) -> Callable:
        """One jitted call running ALL ``steps`` local steps of a rank
        group: a vmap of the per-client step over the client axis, unrolled
        over the (small, static) local step count so the whole group's local
        training is a single XLA dispatch. Cache keys on (rank, steps);
        jit re-specializes per group size via the stacked shapes."""
        key = (rank, steps)
        if key not in self._vstep_cache:
            raw = self._make_raw_step(rank)
            vstep = jax.vmap(raw, in_axes=(0, 0, None, 0, None))

            def run(lora, opt_state, base, stacks, lr):
                metrics = {}
                for t in range(steps):     # static unroll (1-2 typically)
                    batch = jax.tree.map(lambda x: x[t], stacks)
                    lora, opt_state, metrics = vstep(lora, opt_state, base,
                                                     batch, lr)
                return lora, metrics

            self._vstep_cache[key] = jax.jit(run)
        return self._vstep_cache[key]

    def _masked_run_fn(self, steps: int) -> Callable:
        """The un-jitted all-rank masked group body. The client axis size is
        read from ``mask`` at trace time, so the SAME function serves the
        whole-round jit (``masked_runner``) and the per-shard body of the
        sharded round engine (``masked_runner_sharded``), which hands it the
        local client block of each mesh shard."""
        raw = self._make_raw_step_scaled()
        vstep = jax.vmap(raw, in_axes=(0, 0, None, 0, None, 0))
        opt = self.opt

        def run(global_lora, base, stacks, lr, mask, scales):
            size = mask.shape[0]

            def tile_mask(path, x):
                if x is None:
                    return None
                t = jnp.repeat(x[None], size, axis=0)
                key_ = getattr(path[-1], "key", "")
                lead = (1,) * (x.ndim - 2)
                if key_ == "lora_a":   # (M, ..., r_max, in): mask rows
                    return t * mask.reshape(
                        (size,) + lead + (mask.shape[1], 1)).astype(t.dtype)
                if key_ == "lora_b":   # (M, ..., out, r_max): mask cols
                    return t * mask.reshape(
                        (size,) + lead + (1, mask.shape[1])).astype(t.dtype)
                return t               # lora_m and anything else
            lora = jax.tree_util.tree_map_with_path(
                tile_mask, global_lora, is_leaf=lambda x: x is None)
            opt_state = opt.init(lora)
            opt_state = opt_state._replace(
                step=jnp.zeros((size,), jnp.int32))
            metrics = {}
            for t in range(steps):     # static unroll (1-2 typically)
                batch = jax.tree.map(lambda x: x[t], stacks)
                lora, opt_state, metrics = vstep(lora, opt_state, base,
                                                 batch, lr, scales)
            return lora, metrics

        return run

    def masked_runner(self, steps: int) -> Callable:
        """One jitted call training ALL clients of a round regardless of
        rank: tile + rank-mask the global adapters inside the program, then
        unrolled vmapped steps at static r_max with per-client scales.
        Cache keys on steps; jit re-specializes per round size."""
        key = ("masked", steps)
        if key not in self._vstep_cache:
            self._vstep_cache[key] = jax.jit(self._masked_run_fn(steps))
        return self._vstep_cache[key]

    def masked_runner_sharded(self, steps: int, mesh) -> Callable:
        """The all-rank masked runner as a ``shard_map`` over the mesh's
        ``data`` axis (DESIGN.md §5): each shard runs the IDENTICAL masked
        vmapped step body on its contiguous block of the client axis, with
        base weights and global adapters replicated. Per-client training is
        independent, so device placement changes nothing mathematically --
        batched == sharded up to XLA scheduling round-off.

        Cache keys on (steps, mesh); jit re-specializes per shard size."""
        key = ("sharded", steps, mesh)
        if key not in self._vstep_cache:
            from repro.sharding.specs import round_engine_specs
            run = self._masked_run_fn(steps)
            spec = round_engine_specs()
            sharded = jax.shard_map(
                run, mesh=mesh,
                in_specs=(spec.replicated, spec.replicated, spec.batch_stack,
                          spec.replicated, spec.clients, spec.clients),
                out_specs=(spec.clients, spec.clients),
                check_vma=False)
            self._vstep_cache[key] = jax.jit(sharded)
        return self._vstep_cache[key]

    def dispatch_group_masked(self, base, global_lora, ranks: Sequence[int],
                              batch_stacks: List[dict], lr: float,
                              mesh=None) -> Tuple[dict, object]:
        """Non-blocking all-rank group dispatch: (factor stacks, loss handle).

        The async round engine's entry point. Both returns are plain jax
        arrays produced by the jitted (or shard_mapped, when ``mesh`` is
        given) masked runner -- jax's async dispatch means this function
        returns as soon as the computation is ENQUEUED; nothing here (and
        nothing the caller does short of ``np.asarray``/item reads) blocks
        on device execution, so round t+1's training can be in flight while
        round t's aggregation is still running. The loss handle is
        ``metrics["loss"]`` unmaterialized (None when the group ran zero
        steps); callers convert it to floats only at finalize time.
        """
        if mesh is not None:
            lora_g, metrics = self.train_group_masked_sharded(
                base, global_lora, ranks, batch_stacks, lr, mesh)
        else:
            lora_g, metrics = self.train_group_masked(
                base, global_lora, ranks, batch_stacks, lr)
        return lora_g, metrics.get("loss")

    def train(self, base, global_lora, rank: int,
              batch_iter: Iterable[dict], lr: float) -> Tuple[dict, dict]:
        """Run local epochs; returns (trained lora tree, last metrics)."""
        step = self.step_fn(int(rank))
        opt_state = self.opt.init(global_lora)
        lora = global_lora
        metrics = {}
        for batch in batch_iter:
            lora, opt_state, metrics = step(lora, opt_state, base, batch,
                                            jnp.float32(lr))
        return lora, metrics

    def train_group(self, base, global_lora, rank: int,
                    batch_stacks: List[dict], lr: float,
                    size: int) -> Tuple[dict, dict]:
        """Train ``size`` same-rank clients as one vmapped step sequence.

        ``batch_stacks``: list over local steps of batch pytrees with a
        leading client axis of length ``size`` (step t holds client i's t-th
        batch at index i). Returns (lora tree with leading client axis,
        last-step metrics with leading client axis).
        """
        lora = jax.tree.map(
            lambda x: jnp.repeat(x[None], size, axis=0), global_lora)
        if not batch_stacks:
            return lora, {}
        runner = self.group_runner(int(rank), len(batch_stacks))
        # (T, G, ...) step-major stacks so the runner slices per step
        stacks = jax.tree.map(lambda *xs: jnp.stack(xs), *batch_stacks)
        opt_state = self.opt.init(lora)
        # per-client step counters: AdamW's bias correction must see the
        # same step index as the sequential engine
        opt_state = opt_state._replace(step=jnp.zeros((size,), jnp.int32))
        return runner(lora, opt_state, base, stacks, jnp.float32(lr))

    def train_group_masked(self, base, global_lora, ranks: Sequence[int],
                           batch_stacks: List[dict],
                           lr: float) -> Tuple[dict, dict]:
        """Train a mixed-rank client group in ONE jitted dispatch.

        Exact equivalence with per-rank training (see module docstring):
        client k's factors are zero-masked beyond rank r_k, runs at static
        r_max with its own lora_scale. Returned factor stacks carry zeros
        beyond each client's rank -- exactly the zero-padded layout
        ``pad_stack``/aggregation expect, so no per-rank re-slicing is
        needed downstream.

        ``batch_stacks``: list over local steps of batch pytrees with a
        leading client axis of length ``len(ranks)``.
        """
        r_max = self.model.lora.r_max
        mask = (np.arange(r_max)[None, :]
                < np.asarray(ranks)[:, None]).astype(np.float32)
        scales = np.asarray([self.model.lora.scaling(int(r))
                             for r in ranks], np.float32)
        runner = self.masked_runner(len(batch_stacks))
        stacks = _stack_step_axis(batch_stacks)
        return runner(global_lora, base, stacks, np.float32(lr),
                      mask, scales)

    def train_group_masked_sharded(self, base, global_lora,
                                   ranks: Sequence[int],
                                   batch_stacks: List[dict], lr: float,
                                   mesh) -> Tuple[dict, dict]:
        """``train_group_masked`` with the client axis sharded over the
        mesh's ``data`` axis (one shard_map dispatch for the whole group).

        The caller must have padded the client axis to a multiple of the
        data-axis size (``federation/server.py`` does this with zero-weight
        ghost clients); each shard trains its contiguous block. Returned
        factor stacks (and metrics) come back as globally-addressable arrays
        sharded over the client axis, ready for the sharded aggregation.
        """
        r_max = self.model.lora.r_max
        n_shards = mesh.shape["data"]
        assert len(ranks) % n_shards == 0, (len(ranks), n_shards)
        mask = (np.arange(r_max)[None, :]
                < np.asarray(ranks)[:, None]).astype(np.float32)
        scales = np.asarray([self.model.lora.scaling(int(r))
                             for r in ranks], np.float32)
        runner = self.masked_runner_sharded(len(batch_stacks), mesh)
        stacks = _stack_step_axis(batch_stacks)
        return runner(global_lora, base, stacks, np.float32(lr),
                      mask, scales)
