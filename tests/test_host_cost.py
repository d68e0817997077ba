"""Unit tests for ``analysis/host_cost`` (the host-side half of the
complexity certifier): the tracing shim's lifecycle and accounting, the
instrumented federation hooks, and the registry-independence regression
test -- per-round host cost must not move when the registry grows from
1k to 100k registered clients at a fixed cohort (the ROADMAP
million-client tripwire, gated as a contract by tools/certify_scaling.py
and pinned here as a plain assertion).
"""
import numpy as np
import pytest

from repro import tracing
from repro.analysis import host_cost
from repro.analysis.host_cost import HostCostMonitor, measure_rounds


class TestShim:
    def test_inactive_hooks_are_noops(self):
        """Counts made outside a monitor -- before it opens and after it
        closes -- are not its loop counts; only the deltas inside it are.
        Allocations are recorded only while it is open."""
        tracing.count("nobody/listening", 100)
        host_cost.alloc("nobody/listening", 1 << 20)
        mon = HostCostMonitor()
        assert mon.total_loop_iters == 0
        assert mon.total_alloc_bytes == 0
        with mon:
            tracing.count("nobody/listening", 3)
        tracing.count("nobody/listening", 100)
        assert mon.loop_iters == {"nobody/listening": 3}
        assert mon.total_alloc_bytes == 0

    def test_tick_and_alloc_accumulate_under_monitor(self):
        with HostCostMonitor() as mon:
            tracing.count("loop/a", 5)
            tracing.count("loop/a", 3)
            host_cost.alloc("buf", 64)
        assert mon.loop_iters == {"loop/a": 8}
        assert mon.alloc_bytes == {"buf": 64}

    def test_numpy_constructors_traced_and_restored(self):
        orig_zeros = np.zeros
        with HostCostMonitor() as mon:
            np.zeros((16,), np.float32)          # 64 B
            np.asarray([1.0, 2.0])               # 16 B
        assert np.zeros is orig_zeros            # patch undone on exit
        assert mon.alloc_bytes["np.zeros"] == 64
        assert mon.alloc_bytes["np.asarray"] == 16
        before = mon.total_alloc_bytes
        np.zeros((1024,))                        # monitor closed: unseen
        assert mon.total_alloc_bytes == before

    def test_mark_isolates_phase_deltas(self):
        with HostCostMonitor() as mon:
            tracing.count("x", 2)
            mon.mark("round0")
            tracing.count("x", 7)
            host_cost.alloc("y", 10)
            mon.mark("round1")
        p0, p1 = mon.phases
        assert (p0.loop_iters, p0.alloc_bytes) == (2, 0)
        assert (p1.loop_iters, p1.alloc_bytes) == (7, 10)
        assert p1.loop_detail == {"x": 7}

    def test_nesting_raises(self):
        with HostCostMonitor():
            with pytest.raises(RuntimeError, match="nested"):
                with HostCostMonitor():
                    pass


class TestRegistryHooks:
    def test_sample_round_preserves_rng_stream(self):
        """The loop counter must not consume rng draws: sampling through the
        instrumented registry is bit-exact with a direct rng.choice."""
        from repro.configs.base import FLConfig, LoRAConfig
        from repro.federation.topology import ClientRegistry
        fl = FLConfig(num_clients=12)
        lora = LoRAConfig(rank_levels=(4, 8), rank_probs=(0.5, 0.5))
        shards = [np.arange(i, i + 3) for i in range(12)]
        reg = ClientRegistry.create(fl, lora, shards)
        expected = np.random.default_rng(7).choice(12, size=5,
                                                   replace=False)
        got = reg.sample_round(5, np.random.default_rng(7))
        np.testing.assert_array_equal(got, expected)

    def test_inflate_appends_aliased_shards(self):
        from repro.configs.base import FLConfig, LoRAConfig
        from repro.federation.topology import ClientRegistry
        fl = FLConfig(num_clients=4)
        lora = LoRAConfig(rank_levels=(4, 8), rank_probs=(0.5, 0.5))
        shards = [np.arange(i, i + 3) for i in range(4)]
        reg = ClientRegistry.create(fl, lora, shards)
        reg.inflate(1000)
        assert reg.num_clients == 1000
        assert set(np.unique(reg.ranks)) <= {4, 8}
        # shards are references onto the original arrays, not copies
        assert reg.shards[4] is reg.shards[0]
        assert reg.shards[999] is reg.shards[999 % 4]
        reg.inflate(10)                          # shrink request: no-op
        assert reg.num_clients == 1000


def _tiny_experiment():
    from repro.federation.experiment import build_experiment
    return build_experiment(
        "raflora",
        fl_overrides={"num_rounds": 60, "num_clients": 16,
                      "participation": 0.5, "partition": "iid"},
        lora_overrides={"rank_levels": (8,), "rank_probs": (1.0,)},
        num_classes=4, d_model=32, samples_per_class=20,
        batches_per_round=1, backend="factored")


@pytest.mark.slow
class TestRoundCostIndependentOfRegistry:
    def test_1k_vs_100k_registered_clients(self):
        """Satellite regression test: growing the registry 100x at a
        fixed cohort must leave per-round loop iterations EXACTLY equal
        and per-round allocated bytes within noise (rng-dependent
        sampling can shuffle which equal-size shards are touched)."""
        exp = _tiny_experiment()
        exp.registry.inflate(1_000)
        small = measure_rounds(exp.server, rounds=3, warmup=1)
        exp.registry.inflate(100_000)
        large = measure_rounds(exp.server, rounds=3, warmup=1)
        assert large["loop_iters"] == small["loop_iters"]
        assert large["alloc_bytes"] == pytest.approx(
            small["alloc_bytes"], rel=0.01)
        # the hooks themselves are alive: every phase saw the planner,
        # the sampler and the aggregator loops
        detail = large["phases"][-1]["loop_detail"]
        for label in ("registry/sample", "server/plan_clients",
                      "server/agg_members"):
            assert detail.get(label, 0) > 0, detail
