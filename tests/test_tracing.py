"""The program's spans, counters and samples (``repro.tracing``): nesting,
parents and self time; counters and samples read across a window; a ring
that has dropped what a window needs reads None; the compile counter; the
``fl.*`` spans of a round engine and the ``serve.*`` spans, samples and
row counters of the batcher and engine; and the spans on the host plane of
a live profiler trace."""
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.tracing import Recorder

STAGES = ["fl.plan", "fl.train", "fl.aggregate", "fl.write", "fl.sync"]


def _window(fn):
    """Run ``fn`` and return its result with the window it ran in."""
    t0 = time.perf_counter_ns()
    out = fn()
    return out, t0, time.perf_counter_ns() + 1


def test_nesting_parents_and_self_time():
    rec = Recorder()
    t0 = time.perf_counter_ns()
    with rec.span("a", k=1):
        with rec.span("b"):
            with rec.span("d"):
                time.sleep(0.002)
        with rec.span("c"):
            time.sleep(0.001)
    spans = {s.name: s for s in rec.between(t0, time.perf_counter_ns(),
                                            "span")}
    a, b, c, d = (spans[k] for k in "abcd")
    assert a.parent == -1 and a.attrs == {"k": 1}
    assert b.parent == c.parent == a.index and d.parent == b.index
    dur = {k: s.end_ns - s.start_ns for k, s in spans.items()}
    assert tracing.self_ns(a) == dur["a"] - dur["b"] - dur["c"]
    assert tracing.self_ns(b) == dur["b"] - dur["d"]
    assert tracing.self_ns(d) == dur["d"] >= 2_000_000
    assert a.start_ns <= b.start_ns < b.end_ns <= c.start_ns < c.end_ns \
        <= a.end_ns


def test_counters_and_samples_across_a_window():
    rec = Recorder()
    rec.count("rows", 5)
    rec.observe("wait_s", 9.0, rid=0)
    t0 = time.perf_counter_ns()
    rec.count("rows", 2)
    rec.count("rows", 3)
    rec.count("live")
    rec.observe("wait_s", 0.25, rid=1)
    t1 = time.perf_counter_ns()
    rec.count("rows", 100)
    rec.observe("wait_s", 7.0, rid=2)
    assert rec.counters() == {"rows": 110, "live": 1}
    got = rec.between(t0, t1, "count")
    assert sum(c.n for c in got if c.name == "rows") == 5
    assert [(s.value, s.attrs) for s in rec.between(t0, t1, "sample")] \
        == [(0.25, {"rid": 1})]


def test_module_readers_share_one_recorder():
    def body():
        with tracing.span("test.outer"):
            tracing.count("test.rows", 4)
            tracing.observe("test.wait_s", 0.5, rid="r")
    _, t0, t1 = _window(body)
    assert [s.name for s in tracing.spans_between(t0, t1)] == ["test.outer"]
    assert tracing.counts_between(t0, t1) == {"test.rows": 4}
    assert [s.value for s in tracing.samples_between(t0, t1)] == [0.5]
    assert tracing.counters()["test.rows"] >= 4


def test_ring_overflow_reads_none_not_a_partial_value():
    rec = Recorder(maxlen=8)
    t0 = time.perf_counter_ns()
    for _ in range(3):
        with rec.span("early"):
            pass
    for _ in range(20):
        rec.count("late")
    t1 = time.perf_counter_ns()
    assert rec.dropped == 15
    # the window reaches back past the oldest entry still held
    assert rec.between(t0, t1, "span") is None
    assert rec.between(t0, t1, "count") is None
    # a window inside what is held reads in full
    oldest = rec.ring[0][3]
    assert sum(c.n for c in rec.between(oldest + 1, t1, "count")) == 7
    # totals are kept apart from the ring and lose nothing
    assert rec.counters() == {"late": 20}


def test_threads_keep_their_own_parents_and_lose_no_count():
    rec = Recorder()
    n_threads, n_iter = 12, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(tid):
            for _ in range(n_iter):
                with rec.span("outer", tid=tid):
                    with rec.span("inner", tid=tid):
                        rec.count("hits")
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        t0 = time.perf_counter_ns()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert rec.counters() == {"hits": n_threads * n_iter}
    spans = rec.between(t0, time.perf_counter_ns(), "span")
    by_index = {s.index: s for s in spans}
    inner = [s for s in spans if s.name == "inner"]
    assert len(inner) == n_threads * n_iter
    for s in inner:
        parent = by_index[s.parent]
        assert parent.name == "outer" and parent.attrs == s.attrs


def test_compile_counter_attributes_a_fresh_jit_to_its_span():
    x = jnp.arange(13.0)
    key = tracing.COMPILES + "test.compile"
    before = tracing.counters().get(key, 0)

    @jax.jit
    def fresh(v):
        return jnp.cumsum(v * 3.0) - 1.0

    with tracing.span("test.outer"):
        with tracing.span("test.compile"):
            fresh(x).block_until_ready()
        fresh(x).block_until_ready()       # cached: no second program
    assert tracing.counters()[key] == before + 1
    assert tracing.counters().get(tracing.COMPILES + "test.outer", 0) == 0


# -- the round engine --------------------------------------------------------

@pytest.fixture(scope="module")
def fl_exp():
    from repro.federation.experiment import build_experiment
    return build_experiment(
        "raflora", round_engine="batched",
        fl_overrides={"num_clients": 4, "participation": 0.5,
                      "num_rounds": 8, "local_batch_size": 4},
        lora_overrides={"rank_levels": (4, 8), "rank_probs": (0.5, 0.5)},
        num_classes=4, d_model=32, samples_per_class=8,
        batches_per_round=1)


def test_round_spans_in_order(fl_exp):
    server = fl_exp.server
    _, t0, t1 = _window(lambda: [server.run_round() for _ in range(2)])
    spans = [s for s in tracing.spans_between(t0, t1)
             if s.name.startswith("fl.")]
    rounds = sorted((s for s in spans if s.name == "fl.round"),
                    key=lambda s: s.start_ns)
    assert len(rounds) == 2 and all(r.parent == -1 for r in rounds)
    for r in rounds:
        kids = sorted((s for s in spans if s.parent == r.index),
                      key=lambda s: s.start_ns)
        assert [s.name for s in kids] == STAGES
        train = kids[1]
        stacks = [s for s in spans if s.parent == train.index]
        # the client axis (server) and the step axis (trainer)
        assert [s.name for s in stacks] == ["fl.stack", "fl.stack"]
        assert r.start_ns <= kids[0].start_ns and kids[-1].end_ns <= r.end_ns
    assert rounds[0].end_ns <= rounds[1].start_ns


# -- the serving batcher and engine ------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import LoRAConfig, get_config
    from repro.core.lora import split_lora
    from repro.models import build_model
    from repro.serving import AdapterStore
    lora = LoRAConfig(rank_levels=(4, 8))
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg, lora, dtype=jnp.float32, remat=False,
                        block_q=16, block_kv=16)
    params = model.init(jax.random.PRNGKey(0))
    _, lora_tree = split_lora(params)
    store = AdapterStore(lora.rank_levels)
    store.put("t", jax.tree.map(lambda x: None if x is None else 0.05 * x,
                                lora_tree, is_leaf=lambda x: x is None), 8)
    store.publish()
    return cfg, model, params, store


def _serve(serve_setup, n_req=3, slots=2):
    from repro.serving import ContinuousBatcher, ServeRequest, ServingEngine
    cfg, model, params, store = serve_setup
    engine = ServingEngine(model, params, store, max_len=12, slots=slots)
    batcher = ContinuousBatcher(engine, step_cost=0.01, prefill_cost=0.05)
    rng = np.random.default_rng(3)
    for i in range(n_req):
        batcher.submit(ServeRequest(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, size=8),
            adapter_id="t", max_new_tokens=3, arrival=0.02 * i))
    batcher.run()
    return batcher


def test_serve_spans_samples_and_row_counters(serve_setup):
    slots, n_req = 2, 3
    batcher, t0, t1 = _window(lambda: _serve(serve_setup, n_req, slots))
    spans = [s for s in tracing.spans_between(t0, t1)
             if s.name.startswith("serve.") and s.name != "serve.publish"]
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == batcher.steps and all(s.parent == -1
                                               for s in steps)
    step_ids = {s.index for s in steps}
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    for name in ("serve.admit", "serve.decode", "serve.wait"):
        assert by_name[name] and all(s.parent in step_ids
                                     for s in by_name[name])
    admits, decodes = by_name["serve.admit"], by_name["serve.decode"]
    assert len(by_name["serve.wait"]) == len(admits) + len(decodes)
    # the engine's spans sit inside the batcher's calls
    inner = {s.parent for s in by_name["serve.engine.admit"]}
    assert inner == {s.index for s in admits}
    assert sorted(r for s in admits for r in s.attrs["rids"]) \
        == list(range(n_req))
    waits = tracing.samples_between(t0, t1)
    waits = [w for w in waits if w.name == "serve.queue_wait_s"]
    assert sorted(w.attrs["rid"] for w in waits) == list(range(n_req))
    assert all(w.value >= 0 for w in waits)
    for r in batcher.done:
        w = next(w for w in waits if w.attrs["rid"] == r.rid)
        assert w.value == pytest.approx(r.t_admit - r.arrival)
    counts = tracing.counts_between(t0, t1)
    assert counts["serve.admitted"] == n_req
    assert counts["serve.prefill_rows"] == slots * len(admits)
    assert counts["serve.decode_rows"] == slots * len(decodes)
    assert counts["serve.decode_live"] == sum(s.attrs["live"]
                                              for s in decodes)
    assert counts["serve.decode_live"] < counts["serve.decode_rows"]


def test_spans_on_the_host_plane_of_a_live_trace(fl_exp, serve_setup,
                                                 tmp_path):
    from jax.profiler import ProfileData
    fl_exp.server.run_round()
    _serve(serve_setup, n_req=1)             # warm: the trace holds steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        fl_exp.server.run_round()
        _serve(serve_setup, n_req=1)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert set(STAGES) | {"fl.round", "fl.stack"} <= names
    assert {"serve.step", "serve.admit", "serve.decode", "serve.wait",
            "serve.engine.admit", "serve.engine.decode"} <= names
