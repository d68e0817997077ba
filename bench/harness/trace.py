"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a plain dict (also what
the recorded test trace holds):

    {"devices": {name: {"ops": [[op, start_ns, dur_ns, program], ...],
                        "programs": [[program, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...]}

On a TPU the device planes are ``/device:TPU:<n>`` with the lines
"XLA Ops" and "XLA Modules"; on the CPU backend (tests) the operations sit
on host threads and carry ``hlo_op``/``hlo_module`` stats. Host spans are
the benchmark's own ``TraceAnnotation``s, whose names start with
``bench.``.

``reduce`` computes, over the window that the span ``bench.window``
covers: device busy seconds (the union of operation intervals, averaged
over devices), device seconds per program (jitted name, id stripped), the
operations that took most time, the idle gaps summed by the innermost host
span that covered each gap, and device busy seconds inside each host span.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
_ID = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return _ID.sub("", name).strip()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    host = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices.setdefault(plane.name, {"ops": [], "programs": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        dev["ops"].append([e.name, int(e.start_ns),
                                           int(e.duration_ns),
                                           stats.get("hlo_module")])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        dev["programs"].append([program_name(e.name),
                                                int(e.start_ns),
                                                int(e.duration_ns)])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
                        continue
                    stats = dict(e.stats)
                    if "hlo_op" in stats and "hlo_module" in stats:
                        dev = devices.setdefault(
                            f"cpu:{stats.get('device_ordinal', 0)}",
                            {"ops": [], "programs": []})
                        dev["ops"].append([e.name, int(e.start_ns),
                                           int(e.duration_ns),
                                           str(stats["hlo_module"])])
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(busy, lo, hi) -> int:
    return sum(e - s for s, e in _clip(busy, lo, hi))


def _program_of_ops(dev: dict) -> list:
    """Each operation's program: its own stat, else the program event on
    the same device whose interval holds the operation's start."""
    progs = sorted(dev["programs"], key=lambda p: p[1])
    out = []
    j = 0
    for name, s, d, prog in sorted(dev["ops"], key=lambda o: o[1]):
        if prog is None:
            while j + 1 < len(progs) and progs[j + 1][1] <= s:
                j += 1
            if progs and progs[j][1] <= s < progs[j][1] + progs[j][2]:
                prog = progs[j][0]
        out.append([name, s, d, program_name(prog) if prog else "?"])
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    devices = [d for d in tr["devices"].values() if d["ops"]]
    if not devices:
        return {}
    host = tr["host"]
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        lo = min(o[1] for d in devices for o in d["ops"])
        hi = max(o[1] + o[2] for d in devices for o in d["ops"])
    busy_per_dev, programs, ops = [], defaultdict(float), defaultdict(float)
    for dev in devices:
        ops_p = [o for o in _program_of_ops(dev)
                 if o[1] + o[2] > lo and o[1] < hi]
        busy_per_dev.append(_union(_clip([[o[1], o[1] + o[2]]
                                          for o in ops_p], lo, hi)))
        for name, s, d, prog in ops_p:
            ops[f"{prog}/{name}"] += d / len(devices)
        prog_events = [p for p in dev["programs"]
                       if p[1] + p[2] > lo and p[1] < hi]
        if prog_events:
            for name, s, d in prog_events:
                programs[name] += (min(s + d, hi) - max(s, lo)) / len(devices)
        else:
            for name, s, d, prog in ops_p:
                programs[prog] += d / len(devices)
    busy = busy_per_dev[0]
    spans = [(n, s, s + d) for n, s, d in host if n != WINDOW_SPAN]
    gaps = defaultdict(float)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        around = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = (min(around, key=lambda sp: sp[2] - sp[1])[0]
                 if around else "none")
        gaps[label] += g1 - g0
    span_stats: dict = {}
    for n, s, e in spans:
        st = span_stats.setdefault(n, {"count": 0, "wall_s": 0.0,
                                       "busy_s": 0.0})
        st["count"] += 1
        st["wall_s"] += (e - s) * 1e-9
        st["busy_s"] += _overlap(busy, s, e) * 1e-9
    ns = 1e-9
    return {
        "busy_s": sum(sum(e - s for s, e in b) for b in busy_per_dev)
        / len(busy_per_dev) * ns,
        "window_s": (hi - lo) * ns,
        "programs": {k: v * ns for k, v in programs.items()},
        "device_ops": [[k, v * ns] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "spans": span_stats,
    }


def idle_pct(reduced: dict):
    """Percent of the traced window with no operation on the device."""
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
