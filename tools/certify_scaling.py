#!/usr/bin/env python
"""Complexity-certifier sweep (DESIGN.md §9): lower every engine x
backend x method program at a geometric ladder of problem sizes, fit
log-log scaling exponents per axis, gate them against the declared
contract catalog (``analysis/complexity.CONTRACTS``) and write the
tracked ``AUDIT_scaling.json``.

    PYTHONPATH=src python tools/certify_scaling.py [--out PATH] [--fast]
        [--vmem-target v5e] [--with-lint [--lint-out PATH]
        [--lint-skip-dispatch]]

Axes and ladders (geometric; sizes are 128-lane-aligned so the kernel
backend's pad-to-tile never bends a fit):

  dn        d = n = s together -- the axis that separates O(d*n) from
            O((d+n)R): dense slope ~2, factored/kernel ~1. All engines.
  d, n      single-axis ladders (batched engine rows).
  m         clients per rank group (batched + sharded rows).
  r         r_max via single-level rank_levels=(r,) (batched rows).
  shards    mesh size (sharded rows; needs the forced 8-device CPU
            platform, see tools/ci.sh).
  registry  registered-client count at FIXED cohort, measured as host
            counters over real tiny rounds (``analysis/host_cost``) on
            the batched AND event engines.
  (host) m  sampled-cohort ladder of the same host counters.

Every lowering goes through the shared ``analysis/lowering`` cache, so
the base point of each row is compiled once and reused by every axis
(and by the lint sweep when run in the same process via ``--with-lint``).

Positive controls (the sweep FAILS if any does NOT trip): the dense
backend must certify O(d*n) against the low-rank contracts
(``dense-dn-superlinear``), and an injected O(registry) host scan must
trip the registry contract (``host-registry-scan``). A control that
RAISES fails the report the same way (report.run_control).

Exit status: 0 all contracts hold + controls tripped, 1 otherwise, 2 on
usage errors. ``tools/ci.sh certify`` runs the full sweep; ``tools/ci.sh
lint-fast`` runs ``--fast --with-lint`` on reduced ladders for the smoke
tier.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

# ladders: lane-aligned dn/d/n; m/r geometric; the base point (first
# entry of each ladder) is shared across axes through the lowering cache
DN_LADDER = (128, 256, 512)
M_LADDER = (2, 4, 8)
R_LADDER = (8, 16)
SHARD_LADDER = (2, 4, 8)
HOST_K_LADDER = (1_000, 10_000, 100_000)
HOST_M_LADDER = (4, 8, 16)
HOST_NUM_CLIENTS = 32
HOST_ROUNDS, HOST_WARMUP = 3, 1
EVENT_ROUNDS, EVENT_WARMUP = 4, 2

FAST_DN_LADDER = (128, 256)
FAST_M_LADDER = (2, 4)
FAST_SHARD_LADDER = (2, 4)
FAST_HOST_K_LADDER = (1_000, 10_000)
FAST_HOST_M_LADDER = (4, 8)


def _device_rows(fast: bool):
    """(engine, method, backend_label) rows; '-' = avg family (lowered
    with the factored default, backend-independent)."""
    from repro.analysis.lowering import BACKENDS, ENGINES, SVD_METHODS
    engines = ("batched", "sharded") if fast else ENGINES
    svd = ("raflora",) if fast else SVD_METHODS
    avg = ("fedavg",) if fast else ("fedavg", "hetlora", "ffa", "flora")
    rows = []
    for engine in engines:
        for method in avg:
            rows.append((engine, method, "-"))
        for method in svd:
            for backend in BACKENDS:
                rows.append((engine, method, backend))
    return rows


def _measure_device_row(engine: str, method: str, label: str,
                        fast: bool):
    """ScalingRow of one program: lower at every ladder point of every
    axis that applies to its engine, extract the device cost vector."""
    from repro.analysis.complexity import Measurement, ScalingRow, \
        device_costs
    from repro.analysis.lowering import ProgramPoint, lower_program

    backend = "factored" if label == "-" else label
    depth = 2 if engine == "async" else 1
    base = ProgramPoint(engine=engine, method=method, backend=backend,
                        d=DN_LADDER[0], n=DN_LADDER[0], rank_levels=(8,),
                        m_per_group=M_LADDER[0], p_bucket=1, depth=depth,
                        shards=0)
    dn = FAST_DN_LADDER if fast else DN_LADDER
    ms = FAST_M_LADDER if fast else M_LADDER
    sh = FAST_SHARD_LADDER if fast else SHARD_LADDER

    meas = []

    def probe(axis, x, pt):
        meas.append(Measurement(axis, float(x),
                                device_costs(lower_program(pt))))

    for s in dn:
        probe("dn", s, base.scaled(d=s, n=s))
    if engine == "batched" and not fast:
        for s in dn[1:]:
            probe("d", s, base.scaled(d=s))
            probe("n", s, base.scaled(n=s))
        probe("d", dn[0], base)
        probe("n", dn[0], base)
    if engine == "batched":
        # the sharded engine has no cohort axis to measure: its stack
        # width is device-count-bound (one slot per shard), m_per_group
        # never reaches the lowered shapes
        for m in ms:
            probe("m", m * depth, base.scaled(m_per_group=m))
    if engine == "batched" and not fast:
        for r in R_LADDER:
            probe("r", r, base.scaled(rank_levels=(r,)))
    if engine == "sharded":
        for s in sh:
            probe("shards", s, base.scaled(shards=s))
    return ScalingRow(program=f"{engine}/{method}/{label}", engine=engine,
                      method=method, backend=label if label != "-"
                      else "factored", measurements=meas)


# -- host round path --------------------------------------------------------

def _build_host_experiment(event: bool):
    """Tiny real federation whose registry can be inflated between
    measurements: iid partition (equal shard sizes keep per-round alloc
    byte counts shape-stable), a single rank level (one train group, so
    loop counters are a deterministic function of cohort size only)."""
    from repro.federation.experiment import build_experiment
    kwargs = {}
    if event:
        from repro.federation.events import (ConstantLatency,
                                             CountTrigger, EventScheduler)
        cohort = HOST_NUM_CLIENTS // 4
        kwargs = dict(round_engine="async", pipeline_depth=1,
                      event_scheduler=EventScheduler(
                          ConstantLatency(1.0), CountTrigger(cohort)))
    else:
        kwargs = dict(round_engine="batched")
    return build_experiment(
        "raflora",
        fl_overrides={"num_rounds": 200, "num_clients": HOST_NUM_CLIENTS,
                      "participation": 0.25, "partition": "iid"},
        lora_overrides={"rank_levels": (8,), "rank_probs": (1.0,)},
        num_classes=4, d_model=32, samples_per_class=40,
        batches_per_round=1, backend="factored", **kwargs)


def _host_costs(server, rounds: int, warmup: int) -> dict:
    from repro.analysis import host_cost
    cost = host_cost.measure_rounds(server, rounds=rounds, warmup=warmup)
    return {"host_loop_iters": cost["loop_iters"],
            "host_alloc_bytes": cost["alloc_bytes"]}


def _measure_host_rows(fast: bool, verbose: bool):
    """Host-counter ScalingRows: registry ladder on the batched and
    event engines, cohort ladder on the batched engine."""
    from repro.analysis.complexity import Measurement, ScalingRow
    ks = FAST_HOST_K_LADDER if fast else HOST_K_LADDER
    cohorts = FAST_HOST_M_LADDER if fast else HOST_M_LADDER
    rows = []

    exp = _build_host_experiment(event=False)
    meas = []
    for k in ks:
        exp.registry.inflate(k)
        costs = _host_costs(exp.server, HOST_ROUNDS, HOST_WARMUP)
        meas.append(Measurement("registry", float(k), costs))
        if verbose:
            print(f"  [host] batched registry={k}: {costs}")
    fl0 = exp.server.fl
    for m in cohorts:
        exp.server.fl = dataclasses.replace(
            fl0, participation=m / HOST_NUM_CLIENTS)
        costs = _host_costs(exp.server, HOST_ROUNDS, HOST_WARMUP)
        meas.append(Measurement("m", float(m), costs))
        if verbose:
            print(f"  [host] batched cohort={m}: {costs}")
    exp.server.fl = fl0
    rows.append(ScalingRow(program="host/batched-round", engine="host",
                           method="round", backend="-",
                           measurements=meas))

    exp_ev = _build_host_experiment(event=True)
    meas_ev = []
    for k in ks:
        exp_ev.registry.inflate(k)
        costs = _host_costs(exp_ev.server, EVENT_ROUNDS, EVENT_WARMUP)
        meas_ev.append(Measurement("registry", float(k), costs))
        if verbose:
            print(f"  [host] event registry={k}: {costs}")
    rows.append(ScalingRow(program="host/event-round", engine="host",
                           method="round", backend="-",
                           measurements=meas_ev))
    return rows


# -- controls ---------------------------------------------------------------

def _add_controls(report, rows):
    from repro import tracing
    from repro.analysis import complexity, host_cost
    from repro.analysis.complexity import Measurement, ScalingRow

    def dense_control():
        findings = []
        for row in rows:
            if row.backend != "dense":
                continue
            findings.extend(complexity.evaluate_row(
                row, complexity.dense_control_contracts()))
        return findings

    report.run_control(
        "dense-dn-superlinear", "scaling-contract", dense_control,
        "dense rows violate every low-rank dn contract: the ladder "
        "certifies O(d*n) and the fits can see it")

    def host_scan_control():
        meas = []
        for k in HOST_K_LADDER:
            with host_cost.HostCostMonitor() as mon:
                # the injected regression: a per-round O(registry) scan
                tracing.count("control/registry_scan", k)
                host_cost.alloc("control/pool_copy", 8 * k)
                mon.mark("round0")
            ph = mon.phases[0]
            meas.append(Measurement("registry", float(k), {
                "host_loop_iters": float(ph.loop_iters),
                "host_alloc_bytes": float(ph.alloc_bytes)}))
        row = ScalingRow(program="control/host-linear-scan",
                         engine="host", method="round", backend="-",
                         measurements=meas)
        return complexity.evaluate_row(row)

    report.run_control(
        "host-registry-scan", "scaling-contract", host_scan_control,
        "an injected per-round O(registry) scan trips the registry "
        "contracts")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="AUDIT_scaling.json")
    ap.add_argument("--fast", action="store_true",
                    help="reduced ladders + engine subset (smoke tier)")
    ap.add_argument("--with-lint", action="store_true",
                    help="run the program-lint sweep first in the same "
                         "process (shares the lowering cache + jax init)")
    ap.add_argument("--lint-out", default="AUDIT_program_lint.json")
    ap.add_argument("--lint-skip-dispatch", action="store_true")
    ap.add_argument("--vmem-target", default=None,
                    help="pallas VMEM budget table entry for --with-lint "
                         "(v4/v5e/v5p/v6e; default v5e)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    lint_rc = 0
    if args.with_lint:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import lint_programs
        lint_argv = ["--out", args.lint_out]
        if args.lint_skip_dispatch:
            lint_argv.append("--skip-dispatch")
        if args.vmem_target:
            lint_argv += ["--vmem-target", args.vmem_target]
        lint_rc = lint_programs.main(lint_argv)

    import jax
    from repro.analysis import complexity, lowering
    from repro.analysis.report import AuditReport, ProgramAudit

    dn = FAST_DN_LADDER if args.fast else DN_LADDER
    report = AuditReport(matrix={
        "fast": args.fast,
        "devices": jax.device_count(),
        "ladders": {
            "dn": list(dn),
            "m": list(FAST_M_LADDER if args.fast else M_LADDER),
            "r": [] if args.fast else list(R_LADDER),
            "shards": list(FAST_SHARD_LADDER if args.fast
                           else SHARD_LADDER),
            "registry": list(FAST_HOST_K_LADDER if args.fast
                             else HOST_K_LADDER),
            "host_m": list(FAST_HOST_M_LADDER if args.fast
                           else HOST_M_LADDER),
        },
        "contracts": [
            {"name": c.name, "metric": c.metric, "axis": c.axis,
             "max_slope": c.max_slope, "min_slope": c.min_slope,
             "engines": list(c.engines) if c.engines else None,
             "methods": list(c.methods) if c.methods else None,
             "backends": list(c.backends) if c.backends else None}
            for c in complexity.CONTRACTS],
    })

    rows = []
    for engine, method, label in _device_rows(args.fast):
        row = _measure_device_row(engine, method, label, args.fast)
        rows.append(row)
        findings = complexity.evaluate_row(row)
        stats = row.stats()
        base = min((m for m in row.measurements if m.axis == "dn"),
                   key=lambda m: m.x)
        stats["base_costs"] = {k: int(v) for k, v in base.costs.items()}
        audit = ProgramAudit(row.program, "scaling", findings, stats)
        report.add(audit)
        if args.verbose or not audit.ok:
            for f in findings:
                print(f"  {f}")
        dn_flops = stats["slopes"].get("dn/dot_flops")
        print(f"[scal] {row.program:28s} "
              f"{'ok' if audit.ok else 'FAIL'} "
              f"(dn flops^{dn_flops})")

    for row in _measure_host_rows(args.fast, args.verbose):
        rows.append(row)
        findings = complexity.evaluate_row(row)
        audit = ProgramAudit(row.program, "scaling", findings,
                             row.stats())
        report.add(audit)
        if args.verbose or not audit.ok:
            for f in findings:
                print(f"  {f}")
        reg = row.stats()["slopes"].get("registry/host_loop_iters")
        print(f"[scal] {row.program:28s} "
              f"{'ok' if audit.ok else 'FAIL'} "
              f"(registry iters^{reg})")

    _add_controls(report, rows)

    report.write(args.out)
    s = report.summary()
    cache = lowering.cache_info()
    print(f"[scal] {s['programs']} programs, {s['errors']} errors, "
          f"{s['controls']} controls ({len(s['controls_failed'])} dead), "
          f"{cache['entries']} unique lowerings -> {args.out}")
    if not report.ok:
        for p in report.failed_programs:
            print(f"[scal] FAIL {p.program}: "
                  + "; ".join(str(f) for f in p.errors[:3]))
        for name in report.failed_controls:
            ctl = report.controls[name]
            why = ctl.error or "did not trip"
            print(f"[scal] DEAD CONTROL {name}: rule {ctl.rule} {why}")
        return 1
    print("[scal] OK" + (" (lint FAILED)" if lint_rc else ""))
    return 1 if lint_rc else 0


if __name__ == "__main__":
    sys.exit(main())
