"""One run of one cell: the device check, the cell's job, its metrics and
the result line. ``run.py`` is the command-line entry; tests call
``run_cell`` directly with ``require_tpu=False`` and small overrides."""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import math
import os
import shutil
import sys

from harness import env
from harness.clock import CompileClock, Spans, now


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _module(kind: str, name: str):
    path = os.path.join(env.BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a job gets: the parsed files, the seed, the clocks, the device
    and ``window()``, which marks (and with ``--trace 1`` traces) the
    measured window."""

    def __init__(self, jax, workload, cell, cfg, traffic, seed, seconds,
                 trace, t_start, devices, peak):
        self.jax = jax
        self.workload, self.cell = workload, cell
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        self.devices, self.peak = devices, peak
        self.clock = CompileClock(jax)
        self.spans = Spans()
        self.setup: dict = {}
        self.info: dict = {}
        self.window_start = None
        self.trace_result: dict = {}
        # "run" (the benchmark), "calibrate" (also read the control and the
        # faults the reference can stand in for) or "sweep" (no reference)
        self.mode = "run"

    def key(self, stream: int):
        return env.prng_key(self.seed, stream)

    def rng(self, stream: int):
        return env.np_rng(self.seed, stream)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts."""
        jax = self.jax
        gc.collect()
        self.window_start = now()
        self.setup["total_s"] = self.window_start - self.t_start
        self.setup["compile_s"] = self.clock.seconds
        self.setup["programs"] = self.clock.programs
        self.setup["cache_hits"] = self.clock.cache_hits
        trace_dir = os.path.join(env.TRACE_DIR,
                                 f"{self.workload}-{self.seed}")
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            self.spans.tracing = True
        self.clock.window = True
        try:
            with self.spans("bench.window"):
                yield self
        finally:
            self.clock.window = False
            if self.trace:
                self.spans.tracing = False
                jax.profiler.stop_trace()

    def reduce_trace(self) -> None:
        """Read and reduce the window's trace (after the window)."""
        from harness import trace as tr
        trace_dir = os.path.join(env.TRACE_DIR,
                                 f"{self.workload}-{self.seed}")
        self.trace_result = tr.reduce(tr.load(tr.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def _metrics_for(spec: dict, workload: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: dict | None = None, patch=None, mode: str = "run",
             keep: dict | None = None):
    """Run one cell once; returns the result dict (last stdout line).

    ``overrides``: {"config": {...}, "traffic": {...}, "cell": {...}}
    merged over the files (tests shrink a cell with it). ``patch(ctx)``
    runs before the job and may break the program underneath (tests)."""
    import jax
    spec = env.spec()
    entry = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if entry is None:
        raise KeyError(f"{workload!r} is not a workload of BENCHMARK.json")
    cell = env.load("workloads", workload)
    cfg = env.load("configs", cell["config"])
    traffic = env.load("traffic", cell["traffic"])
    for name, d in (("config", cfg), ("traffic", traffic), ("cell", cell)):
        d.update((overrides or {}).get(name, {}))
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
        if len(devices) < entry["chips"]:
            raise NoChip(f"{workload} needs {entry['chips']} chips, "
                         f"found {len(devices)}")
    from harness.peaks import peak
    pk = peak(dev.device_kind) if require_tpu else peak("TPU v5 lite")
    ctx = Context(jax, workload, cell, cfg, traffic, seed, seconds, trace,
                  t_start, devices[:entry["chips"]], pk)
    ctx.mode = mode
    if trace and traffic.get("trace_seconds"):
        # a traced run of a mix whose full window makes a trace too large
        # to read in time measures a shorter window of the same traffic
        ctx.seconds = min(seconds, traffic["trace_seconds"])
        log(f"trace: window of {ctx.seconds} s (the mix's trace_seconds)")
    if patch is not None:
        patch(ctx)
    job = _module("jobs", cfg["job"])
    record = job.run(ctx)
    if trace:
        t = now()
        ctx.reduce_trace()
        log(f"trace: read and reduced in {now() - t:.1f} s")
    record["trace"] = ctx.trace_result
    record["setup"] = ctx.setup
    log("setup: " + " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                             else f"{k}={v}" for k, v in ctx.setup.items()))
    log(f"window: compiles_inside={ctx.clock.in_window}")
    log("info: " + " ".join(f"{k}={v}" for k, v in ctx.info.items()))
    log("readings: " + repr(record.get("readings")))

    metrics = {}
    for m in _metrics_for(spec, workload, trace):
        value = _module("metrics", m["name"]).read(record, ctx)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = record["checks"]
    correct = (record["complete"]
               and all(_finite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if trace and ctx.trace_result:
        device["busy_s"] = ctx.trace_result["busy_s"]
        device["window_s"] = ctx.trace_result["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace_result["device_ops"],
            "idle_gaps": ctx.trace_result["idle_gaps"]}
    result["checks"] = checks
    if keep is not None:
        keep["record"], keep["ctx"] = record, ctx
    return result
