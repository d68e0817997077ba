"""The per-layer metrics that read the program's own spans and counters,
on the CPU: the small rounds and serving cells of ``test_correctness``
run traced, and each of those metrics comes out, in range. The programs
that the device-time metrics find by jitted name are still in the trace
under those names, and their metrics still report."""
import time

import pytest

from harness.cell import run_cell
from test_correctness import ROUNDS, SERVE, SMALL_GRANITE, SMALL_VIT


def traced(workload, small):
    keep = {}
    res = run_cell(workload, 2 ** 33 + 7, 2.0, True,
                   t_start=time.perf_counter(), require_tpu=False,
                   overrides=small, keep=keep)
    return res, keep["record"]


@pytest.fixture(scope="module")
def rounds():
    return traced(ROUNDS, SMALL_VIT)


@pytest.fixture(scope="module")
def serve():
    return traced(SERVE, SMALL_GRANITE)


def values(res):
    return {k: v["value"] for k, v in res["metrics"].items()}


def test_rounds_program_metrics(rounds):
    res, record = rounds
    m = values(res)
    assert res["correct"], res["checks"]
    round_s = record["window_s"] / len(record["rounds"])
    assert 0 < m["round_prep_ms"] < 1e3 * round_s
    assert m["setup_programs"] >= 1
    assert "jit_run" in record["trace"]["programs"]
    for name in ("train_ms", "agg_ms", "round_idle_pct", "round_mfu"):
        assert name in m, name


def test_serve_program_metrics(serve):
    res, record = serve
    m = values(res)
    assert res["correct"], res["checks"]
    assert m["queue_wait_p90_ms"] >= 0
    assert m["sched_host_ms"] > 0
    assert 0 < m["prefill_useful_pct"] <= 100
    assert 0 < m["decode_useful_pct"] <= 100
    assert m["setup_programs"] >= 1
    programs = record["trace"]["programs"]
    assert "jit_decode_impl" in programs and "jit_prefill_impl" in programs
    for name in ("admit_ms", "decode_ms", "decode_roofline",
                 "serve_idle_pct.knee", "serve_mfu.knee"):
        assert name in m, name

