"""Serving subsystem tests (DESIGN.md §11): adapter store semantics,
path-aware cache seeding (the SSM ``grow`` regression), end-to-end greedy
prefill+decode equivalence against the full-sequence forward, hot-swap
atomicity at a round landing, scheduler-vs-isolated equality, and the
federation post-aggregation hook (sync and async/drain paths)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import LoRAConfig, get_config
from repro.core.lora import merge_lora, split_lora
from repro.models import build_model
from repro.serving import (AdapterStore, ContinuousBatcher, ServeRequest,
                           ServingEngine, seed_cache)

LORA = LoRAConfig(rank_levels=(4, 8, 16))


def _reduced(name, lora=LORA, **replace):
    cfg = get_config(name).reduced(**replace.pop("reduced_kw", {}))
    if replace:
        cfg = dataclasses.replace(cfg, **replace)
    model = build_model(cfg, lora, dtype=jnp.float32, remat=False,
                        block_q=16, block_kv=16)
    return cfg, model


def _rand_lora(lora_tree, key, scale=0.05):
    """Random nonzero factors (init has B=0, which would test nothing)."""
    leaves = [i for i, _ in enumerate(jax.tree.leaves(
        lora_tree, is_leaf=lambda x: x is None))]
    counter = iter(leaves)

    def rand(x):
        if x is None:
            return None
        k = jax.random.fold_in(key, next(counter))
        return scale * jax.random.normal(k, x.shape, x.dtype)
    return jax.tree.map(rand, lora_tree, is_leaf=lambda x: x is None)


def _mask_rank(lora_tree, rank):
    """Zero factor columns >= rank (the store's omega-style convention)."""
    def mask(path, x):
        if x is None:
            return None
        ax = x.ndim - 2 if path[-1].key == "lora_a" else x.ndim - 1
        col = jnp.arange(x.shape[ax])
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        return x * (col < rank).reshape(shape).astype(x.dtype)
    return jax.tree_util.tree_map_with_path(
        mask, lora_tree, is_leaf=lambda x: x is None)


@pytest.fixture(scope="module")
def attn_setup():
    cfg, model = _reduced("gemma-2b")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def ssm_setup():
    cfg, model = _reduced("mamba2-1.3b")
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


@pytest.fixture(scope="module")
def mla_setup():
    cfg, model = _reduced("deepseek-v2-236b")
    params = model.init(jax.random.PRNGKey(5))
    return cfg, model, params


# ---------------------------------------------------------------------------
# AdapterStore
# ---------------------------------------------------------------------------

def _toy_tree(r=16, d_in=8, d_out=6, val=1.0):
    return {"proj": {"lora_a": jnp.full((r, d_in), val),
                     "lora_b": jnp.full((d_out, r), val)}}


class TestAdapterStore:
    def test_bucket_order_and_page_ids(self):
        store = AdapterStore((4, 8, 16))
        store.put("c", _toy_tree(), 16)
        store.put("a", _toy_tree(), 4)
        store.put("b", _toy_tree(), 4)
        snap = store.publish()
        # ascending rank level, insertion order within a bucket
        assert snap.page_of == {"a": 0, "b": 1, "c": 2}
        assert snap.ranks == (4, 4, 16)
        np.testing.assert_array_equal(
            np.asarray(snap.page_ids(["c", "a", "c"])), [2, 0, 2])
        assert snap.pages["proj"]["lora_a"].shape[0] == 3

    def test_monotonic_version(self):
        store = AdapterStore((4, 8, 16))
        store.put("t", _toy_tree(), 8)
        assert store.publish().version == 1
        with pytest.raises(ValueError, match="monotonic"):
            store.publish(1)
        assert store.publish(5).version == 5
        assert store.publish().version == 6

    def test_masking_and_padding(self):
        store = AdapterStore((4, 8, 16))
        store.put("t", _toy_tree(r=8), 4)     # true rank 4, staged at r=8
        snap = store.publish()
        a = np.asarray(snap.pages["proj"]["lora_a"][0])   # (16, 8)
        b = np.asarray(snap.pages["proj"]["lora_b"][0])   # (6, 16)
        assert a.shape == (16, 8) and b.shape == (6, 16)
        assert (a[:4] == 1.0).all() and (a[4:] == 0.0).all()
        assert (b[:, :4] == 1.0).all() and (b[:, 4:] == 0.0).all()

    def test_scale_folded_into_b(self):
        store = AdapterStore((4, 8, 16), scaling_fn=lambda r: 32.0 / r)
        store.put("t", _toy_tree(r=16), 16)
        snap = store.publish()
        assert snap.scales == (2.0,)
        np.testing.assert_allclose(
            np.asarray(snap.pages["proj"]["lora_b"][0]), 2.0)
        np.testing.assert_allclose(
            np.asarray(snap.pages["proj"]["lora_a"][0]), 1.0)

    def test_unknown_rank_and_empty_publish_raise(self):
        store = AdapterStore((4, 8, 16))
        with pytest.raises(ValueError, match="not in levels"):
            store.put("t", _toy_tree(), 5)
        with pytest.raises(ValueError, match="no staged"):
            store.publish()

    def test_dora_magnitudes_rejected(self):
        store = AdapterStore((4, 8, 16))
        tree = _toy_tree()
        tree["proj"]["lora_m"] = jnp.ones((6,))
        store.put("t", tree, 16)
        with pytest.raises(ValueError, match="DoRA"):
            store.publish()


# ---------------------------------------------------------------------------
# seed_cache: path-aware merge (the old `grow` shape-matching regression)
# ---------------------------------------------------------------------------

class TestSeedCache:
    def test_ssm_state_with_coincidental_prompt_len_dim(self):
        """The old serve.py `grow` padded ANY axis-2 dim equal to the
        prompt length -- an SSM conv state of width == prompt_len was
        silently grown (and ssm/conv states never transferred at all).
        seed_cache merges by PATH KEY: states transfer unchanged."""
        lp, s_full, slots = 4, 10, 3
        cache = {"layers": {"conv": jnp.zeros((2, slots, lp, 5)),
                            "ssm": jnp.zeros((2, slots, 7, 5)),
                            "k": jnp.zeros((2, slots, s_full, 2, 2))},
                 "len": jnp.zeros((slots,), jnp.int32)}
        got = {"conv": jnp.ones((2, slots, lp, 5)),
               "ssm": 2.0 * jnp.ones((2, slots, 7, 5)),
               "k": 3.0 * jnp.ones((2, slots, lp, 2, 2))}
        out = seed_cache(cache, got, lp, jnp.array([True, True, True]))
        # conv axis-2 == prompt_len is a coincidence: NOT padded, NOT lost
        np.testing.assert_array_equal(np.asarray(out["layers"]["conv"]), 1.0)
        np.testing.assert_array_equal(np.asarray(out["layers"]["ssm"]), 2.0)
        k = np.asarray(out["layers"]["k"])
        assert (k[:, :, :lp] == 3.0).all() and (k[:, :, lp:] == 0.0).all()
        np.testing.assert_array_equal(np.asarray(out["len"]), lp)

    def test_mask_reseeds_only_selected_slots(self):
        lp, s_full, slots = 2, 6, 3
        cache = {"layers": {"k": jnp.zeros((1, slots, s_full, 2))},
                 "len": jnp.full((slots,), 5, jnp.int32)}
        got = {"k": jnp.ones((1, slots, lp, 2))}
        out = seed_cache(cache, got, lp, jnp.array([False, True, False]))
        k = np.asarray(out["layers"]["k"])
        assert (k[:, 0] == 0.0).all() and (k[:, 2] == 0.0).all()
        assert (k[:, 1, :lp] == 1.0).all()
        np.testing.assert_array_equal(np.asarray(out["len"]), [5, lp, 5])

    def test_unknown_leaf_key_raises(self):
        cache = {"layers": {"mystery": jnp.zeros((1, 2, 3))},
                 "len": jnp.zeros((2,), jnp.int32)}
        with pytest.raises(ValueError, match="unknown cache leaf"):
            seed_cache(cache, {"mystery": jnp.ones((1, 2, 3))}, 3,
                       jnp.array([True, True]))


# ---------------------------------------------------------------------------
# end-to-end greedy equivalence (attention + SSM archs)
# ---------------------------------------------------------------------------

def _greedy_reference(model, params, prompt, n_tokens):
    """Greedy continuation via repeated FULL-sequence forwards."""
    toks = list(np.asarray(prompt))
    out = []
    for _ in range(n_tokens):
        seq = jnp.asarray(toks, jnp.int32)[None, :]
        logits, _, _ = model.forward_seq(params, {"tokens": seq},
                                         mode="train")
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


@pytest.mark.parametrize("setup_name", ["attn_setup", "ssm_setup"])
def test_e2e_greedy_matches_full_forward(setup_name, request):
    """Prefill + token-by-token decode through the serving engine must
    reproduce the full-sequence forward's greedy argmax -- per slot, with
    HETEROGENEOUS per-slot adapter ranks (16 and 4). The SSM arch is the
    regression for the old `grow` bug (conv/ssm states never transferred:
    decode ran from zero state and diverged)."""
    cfg, model, params = request.getfixturevalue(setup_name)
    base, lora_tree = split_lora(params)
    tree_hi = _rand_lora(lora_tree, jax.random.PRNGKey(7))
    tree_lo = _rand_lora(lora_tree, jax.random.PRNGKey(8))

    store = AdapterStore(LORA.rank_levels)
    store.put("hi", tree_hi, 16)
    store.put("lo", tree_lo, 4)
    store.publish()

    lp, n_new = 8, 4
    prompts = jax.random.randint(jax.random.PRNGKey(9), (2, lp), 0,
                                 cfg.vocab_size)
    engine = ServingEngine(model, params, store, max_len=lp + n_new + 1,
                           slots=2)
    first = engine.admit([0, 1], prompts, ["hi", "lo"])
    gen = [np.asarray(first)]
    for _ in range(n_new - 1):
        gen.append(np.asarray(engine.decode(jnp.array([True, True]))))
    gen = np.stack(gen, axis=1)                       # (2, n_new)

    for row, (tree, rank) in enumerate([(tree_hi, 16), (tree_lo, 4)]):
        merged = merge_lora(base, _mask_rank(tree, rank))
        want = _greedy_reference(model, merged, prompts[row], n_new)
        np.testing.assert_array_equal(gen[row], want,
                                      err_msg=f"slot {row} rank {rank}")


def test_e2e_mla_ragged_slots_matches_full_forward(mla_setup):
    """deepseek-style MLA serving with RAGGED per-slot cache lengths: slot 1
    admits mid-stream with a shorter prompt, so ``cache["len"]`` is a
    heterogeneous vector when both slots decode together -- the shape that
    used to raise NotImplementedError in the MLA decode path. Every slot's
    greedy continuation must still match the full-sequence forward."""
    cfg, model, params = mla_setup
    base, lora_tree = split_lora(params)
    tree_hi = _rand_lora(lora_tree, jax.random.PRNGKey(7))
    tree_lo = _rand_lora(lora_tree, jax.random.PRNGKey(8))

    store = AdapterStore(LORA.rank_levels)
    store.put("hi", tree_hi, 16)
    store.put("lo", tree_lo, 4)
    store.publish()

    lp0, lp1 = 8, 5
    key = jax.random.PRNGKey(9)
    prompt0 = jax.random.randint(key, (1, lp0), 0, cfg.vocab_size)
    prompt1 = jax.random.randint(jax.random.fold_in(key, 1), (1, lp1), 0,
                                 cfg.vocab_size)
    engine = ServingEngine(model, params, store, max_len=lp0 + 6, slots=2)
    gen0 = [int(engine.admit([0], prompt0, ["hi"])[0])]
    gen0.append(int(engine.decode(jnp.array([True, False]))[0]))
    gen1 = [int(engine.admit([1], prompt1, ["lo"])[0])]
    lens = np.asarray(engine.slot_len())
    assert lens[0] != lens[1], "slots must be genuinely ragged"
    for _ in range(2):
        toks = engine.decode(jnp.array([True, True]))
        gen0.append(int(toks[0]))
        gen1.append(int(toks[1]))

    for row, (tree, rank, prompt, gen) in enumerate(
            [(tree_hi, 16, prompt0, gen0), (tree_lo, 4, prompt1, gen1)]):
        merged = merge_lora(base, _mask_rank(tree, rank))
        want = _greedy_reference(model, merged, prompt[0], len(gen))
        np.testing.assert_array_equal(
            gen, want, err_msg=f"slot {row} rank {rank} (ragged decode)")


# ---------------------------------------------------------------------------
# hot-swap atomicity at a round landing
# ---------------------------------------------------------------------------

def test_hot_swap_atomic_no_version_mixing():
    """Mid-stream publish: (a) every engine step runs on exactly one
    snapshot version and the version log flips once; (b) post-flip tokens
    are BIT-EQUAL to a fresh engine started on the new adapters that
    teacher-forces the same prefix. Single layer + cache-neutral targets
    (q/o projections feed nothing that is cached), so the cache depends
    only on the token sequence, never the adapter version."""
    cfg, model = _reduced("gemma-2b", lora_targets=("q_proj", "o_proj"),
                          reduced_kw={"num_layers": 1})
    params = model.init(jax.random.PRNGKey(2))
    _, lora_tree = split_lora(params)
    tree_v1 = _rand_lora(lora_tree, jax.random.PRNGKey(3))
    tree_v2 = _rand_lora(lora_tree, jax.random.PRNGKey(4))

    store = AdapterStore(LORA.rank_levels)
    store.put("t", tree_v1, 16)
    store.publish()

    lp, pre_flip, post_flip = 8, 3, 4
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, lp), 0,
                                 cfg.vocab_size)
    engine = ServingEngine(model, params, store,
                           max_len=lp + pre_flip + post_flip + 2, slots=2)
    seq = [np.asarray(engine.admit([0, 1], prompts, ["t", "t"]))]
    active = jnp.array([True, True])
    for _ in range(pre_flip):
        seq.append(np.asarray(engine.decode(active)))
    # the round landing: in-flight stream, new factors, bumped version
    store.put("t", tree_v2, 16)
    store.publish()
    for _ in range(post_flip):
        seq.append(np.asarray(engine.decode(active)))
    seq = np.stack(seq, axis=1)                 # (2, 1 + pre + post)

    # (a) one version per step, exactly one flip, no interleaving
    log = engine.version_log
    assert log == [1] * (1 + pre_flip) + [2] * post_flip, log

    # (b) fresh engine on v2 only, teacher-forced through the prefix
    store2 = AdapterStore(LORA.rank_levels)
    store2.put("t", tree_v2, 16)
    store2.publish()
    fresh = ServingEngine(model, params, store2,
                          max_len=lp + pre_flip + post_flip + 2, slots=2)
    fresh.admit([0, 1], prompts, ["t", "t"])
    # force the v1-generated prefix (cache is version-independent here)
    replay = []
    for t in range(pre_flip + post_flip):
        fresh.tokens = jnp.asarray(seq[:, t], jnp.int32)
        replay.append(np.asarray(fresh.decode(active)))
    replay = np.stack(replay, axis=1)
    # free-running tail under v2 == original's post-flip tokens, bit-equal
    np.testing.assert_array_equal(replay[:, pre_flip:],
                                  seq[:, 1 + pre_flip:])


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_matches_isolated_requests(attn_setup):
    """Continuous batching (slot recycling, interleaved tenants) must not
    change any request's tokens vs running it alone in its own engine."""
    cfg, model, params = attn_setup
    _, lora_tree = split_lora(params)
    store = AdapterStore(LORA.rank_levels)
    store.put("hi", _rand_lora(lora_tree, jax.random.PRNGKey(11)), 16)
    store.put("lo", _rand_lora(lora_tree, jax.random.PRNGKey(12)), 4)
    store.publish()

    lp, n_new, slots = 8, 4, 2
    rng = np.random.default_rng(13)
    reqs = [ServeRequest(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                                    size=lp),
                         adapter_id=("hi", "lo")[i % 2],
                         max_new_tokens=n_new, arrival=0.01 * i)
            for i in range(5)]

    engine = ServingEngine(model, params, store, max_len=lp + n_new + 1,
                           slots=slots)
    batcher = ContinuousBatcher(engine, step_cost=0.01, prefill_cost=0.05)
    for r in reqs:
        batcher.submit(r)
    batcher.run()
    assert len(batcher.done) == len(reqs)
    stats = batcher.stats()
    assert stats["completed"] == len(reqs)
    assert stats["tokens"] == len(reqs) * n_new
    assert stats["virtual_p95_s"] >= stats["virtual_p50_s"] > 0

    for req in batcher.done:
        iso = ServingEngine(model, params, store, max_len=lp + n_new + 1,
                            slots=slots)
        toks = [int(np.asarray(iso.admit(
            [0], np.asarray(req.prompt)[None], [req.adapter_id]))[0])]
        for _ in range(n_new - 1):
            toks.append(int(np.asarray(
                iso.decode(jnp.array([True, False])))[0]))
        assert req.tokens == toks, req.rid


def test_scheduler_latency_draws_are_deterministic(attn_setup):
    """Same scenario twice -> bit-identical virtual stats (the property
    bench_trend relies on to gate serving rows)."""
    from repro.federation.events import LognormalLatency
    cfg, model, params = attn_setup
    _, lora_tree = split_lora(params)

    def run_once():
        store = AdapterStore(LORA.rank_levels)
        store.put("t", _rand_lora(lora_tree, jax.random.PRNGKey(21)), 8)
        store.publish()
        engine = ServingEngine(model, params, store, max_len=12, slots=2)
        batcher = ContinuousBatcher(
            engine, latency=LognormalLatency(0.02, 0.3, seed=0),
            step_cost=0.01, prefill_cost=0.05)
        rng = np.random.default_rng(22)
        for i in range(4):
            batcher.submit(ServeRequest(
                rid=i, prompt=rng.integers(0, cfg.vocab_size, size=8),
                adapter_id="t", max_new_tokens=3, arrival=0.02 * i))
        batcher.run()
        return batcher.stats()

    assert run_once() == run_once()


def test_t_admit_stamped_when_the_slot_is_taken(attn_setup):
    """``t_admit`` is stamped when a request takes its slot, before the
    engine's admit call: on a clock that moves while the call runs, it
    lies before ``t_first``, and no request is stamped before it
    arrived."""
    from repro.federation.events import VirtualClock
    cfg, model, params = attn_setup
    _, lora_tree = split_lora(params)
    store = AdapterStore(LORA.rank_levels)
    store.put("t", _rand_lora(lora_tree, jax.random.PRNGKey(31)), 8)
    store.publish()
    engine = ServingEngine(model, params, store, max_len=12, slots=2)
    clock = VirtualClock()
    admit = engine.admit

    def slow_admit(*args):
        clock.advance(clock.now + 0.5)   # time passes inside the call
        return admit(*args)

    engine.admit = slow_admit
    batcher = ContinuousBatcher(engine, clock=clock, step_cost=0.0,
                                prefill_cost=0.0)
    rng = np.random.default_rng(32)
    for i in range(3):
        batcher.submit(ServeRequest(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, size=8),
            adapter_id="t", max_new_tokens=2, arrival=0.1 * i))
    batcher.run()
    assert len(batcher.done) == 3
    for r in batcher.done:
        assert r.arrival <= r.t_admit < r.t_first <= r.t_done
        assert r.t_first - r.t_admit >= 0.5


# ---------------------------------------------------------------------------
# federation round-landing hook
# ---------------------------------------------------------------------------

def _tiny_experiment(**kw):
    from repro.federation.experiment import build_experiment
    fl = {"num_clients": 4, "participation": 1.0, "num_rounds": 8,
          "local_batch_size": 4}
    fl.update(kw.pop("fl_overrides", {}))
    lora = {"rank_levels": (4, 8), "rank_probs": (0.5, 0.5)}
    lora.update(kw.pop("lora_overrides", {}))
    return build_experiment(
        "raflora", fl_overrides=fl, lora_overrides=lora,
        num_classes=4, d_model=32, samples_per_class=8,
        batches_per_round=1, **kw)


class TestRoundLandingHook:
    def test_sync_engine_fires_hook_every_round(self):
        exp = _tiny_experiment(round_engine="batched")
        seen = []
        exp.server.add_post_aggregate_hook(
            lambda v, tree: seen.append(v))
        store = AdapterStore((4, 8))
        store.bind_server(exp.server)
        exp.server.run(3)
        assert seen == [1, 2, 3]
        assert exp.server.adapter_version == 3
        assert store.version == 3
        snap = store.published
        assert snap.ranks == (8,) and snap.page_of == {"global": 0}

    def test_async_engine_fires_on_buffer_and_drain(self):
        exp = _tiny_experiment(round_engine="async", pipeline_depth=2)
        store = AdapterStore((4, 8))
        store.bind_server(exp.server)
        exp.server.run(3)          # depth 2: not every round aggregates
        mid = store.version
        exp.server.drain_pending()  # mid-buffer leftovers must also land
        assert store.version == exp.server.adapter_version >= mid
        assert store.version >= 1
        log = store.published
        assert log is not None and log.version == store.version

    def test_unservable_adapters_skip_and_warn(self):
        """A DoRA run with a bound AdapterStore: the store rejects DoRA
        magnitudes at publish(), so the post-aggregate hook raises inside
        the round loop. The hook must degrade to skip-and-warn -- training
        continues, the store simply never publishes -- instead of taking
        down the round. (Regression: the hook exception used to propagate
        out of ``_write_factors`` and abort ``run()``.)"""
        exp = _tiny_experiment(round_engine="batched",
                               lora_overrides={"variant": "dora"})
        store = AdapterStore((4, 8))
        store.bind_server(exp.server)
        with pytest.warns(RuntimeWarning, match="post-aggregate hook"):
            exp.server.run(2)
        assert exp.server.adapter_version == 2    # the round loop survived
        assert store.published is None            # nothing ever servable

    def test_served_factors_track_global(self):
        exp = _tiny_experiment(round_engine="batched")
        store = AdapterStore((4, 8))
        store.bind_server(exp.server)
        exp.server.run(2)
        want = {p: np.asarray(l) for p, l in
                jax.tree_util.tree_flatten_with_path(
                    exp.server.global_lora)[0]}
        got_tree = store.published.pages
        for path, leaf in jax.tree_util.tree_flatten_with_path(got_tree)[0]:
            np.testing.assert_allclose(np.asarray(leaf[0]), want[path],
                                       atol=1e-7)


# ---------------------------------------------------------------------------
# engine guards
# ---------------------------------------------------------------------------

def test_engine_rejects_unpublished_store(attn_setup):
    cfg, model, params = attn_setup
    with pytest.raises(ValueError, match="publish"):
        ServingEngine(model, params, AdapterStore(LORA.rank_levels),
                      max_len=8, slots=1)
