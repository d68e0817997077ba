"""BENCHMARK.json against the shape the harness relies on: every cell,
configuration, traffic mix and metric is found by its name."""
import json
import os
import re

import pytest

from harness import env

SPEC = env.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_configs_and_cells_resolve():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(env.ROOT, c["file"]))
        data = env.load("configs", c["name"])
        assert data["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(env.BENCH, "jobs",
                                           data["job"] + ".py"))
    pairs = set()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = env.load("workloads", w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        assert w["config"] in configs
        env.load("traffic", w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_metrics_have_readers(group):
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC[group]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(env.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_what_it_needs():
    e2e = {m["name"]: set(m.get("workloads", [w["name"] for w in
                                              SPEC["workloads"]]))
           for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for w in SPEC["workloads"]:
        name = w["name"]
        assert sum(name in cells for cells in e2e.values()) >= 2
        layer = [m for m in SPEC["per_layer"] if name in m["workloads"]]
        assert layer
        for m in layer:
            assert name in e2e[m["moves"]]
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
