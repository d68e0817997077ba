"""Trace reduction on a hand-made trace, on a trace recorded live on the
CPU, and on a small trace recorded on a TPU v5e (tests/data)."""
import os
import time

import pytest

from harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_reduce_by_hand():
    ns = 1
    t = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0, 10, None], ["fusion.2", 5, 15, None],
                ["dot.3", 30, 10, None], ["fusion.1", 60, 5, None]],
        "programs": [["jit_train", 0, 20], ["jit_agg", 30, 10],
                     ["jit_train", 60, 5]]}},
        "host": [["bench.window", 0, 50], ["bench.round", 0, 25],
                 ["bench.batch", 22, 8], ["bench.round", 28, 22]]}
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(50e-9 * ns)
    # busy: [0, 20] and [30, 40]; the op at 60 lies outside the window
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["programs"] == pytest.approx({"jit_train": 20e-9,
                                           "jit_agg": 10e-9})
    # gap [20, 30] (mid 25: inside bench.batch, the innermost span) and
    # [40, 50] (inside the second bench.round)
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.batch": 10e-9,
                                                 "bench.round": 10e-9})
    assert dict(r["device_ops"])["jit_train/fusion.1"] == pytest.approx(
        10e-9)
    assert r["spans"]["bench.round"]["count"] == 2
    assert r["spans"]["bench.round"]["busy_s"] == pytest.approx(30e-9)
    assert tr.idle_pct(r) == pytest.approx(40.0)


def test_reduce_live_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_probe(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    bench_probe(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                bench_probe(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    r = tr.reduce(tr.load(tr.find_xplane(str(tmp_path))))
    assert 0 < r["busy_s"] < r["window_s"] < 1.0
    assert r["programs"].get("jit_bench_probe", 0) > 0
    labels = dict(r["idle_gaps"])
    assert labels.get("bench.idle", 0) >= 0.05
    assert r["spans"]["bench.round"]["count"] == 3


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "probe.xplane.pb")),
                    reason="no recorded TPU trace")
def test_reduce_recorded_tpu_trace():
    """Three bench.round spans, each running one jitted step on the chip,
    with 10 ms of bench.idle after each, inside bench.window."""
    t = tr.load(os.path.join(DATA, "probe.xplane.pb"))
    assert "/device:TPU:0" in t["devices"]
    r = tr.reduce(t)
    assert r["programs"].get("jit_step", 0) > 0
    assert r["spans"]["bench.round"]["count"] == 3
    assert dict(r["idle_gaps"]).get("bench.idle", 0) >= 0.03
    assert 0 < r["busy_s"] < r["window_s"]
