"""The program's own spans, counters and samples (``repro.tracing``),
read over a run's measured window on the clock they were recorded on
(``time.perf_counter``, the clock of ``harness.clock.now``). A program
without them reads None, and so does a window the program's ring no
longer holds: a metric that reads them is then left out of the line."""
from __future__ import annotations


def tracing():
    """The program's tracing module, or None where it has none."""
    try:
        from repro import tracing as tr
    except ImportError:
        return None
    return tr


def window_ns(run, ctx) -> tuple:
    """The measured window, [start, end) in nanoseconds."""
    t0 = ctx.window_start
    return int(t0 * 1e9), int((t0 + run["window_s"]) * 1e9)


def spans(run, ctx, name: str):
    """The window's spans named ``name``, with every span of the window
    by index (for parents and children); None where there are none."""
    tr = tracing()
    if tr is None:
        return None
    got = tr.spans_between(*window_ns(run, ctx))
    if not got:
        return None
    named = [s for s in got if s.name == name]
    return (named, {s.index: s for s in got}) if named else None


def counts(run, ctx, t0_ns=None, t1_ns=None):
    """Each counter's change over the window (or over [t0_ns, t1_ns))."""
    tr = tracing()
    if tr is None:
        return None
    w0, w1 = window_ns(run, ctx)
    return tr.counts_between(w0 if t0_ns is None else t0_ns,
                             w1 if t1_ns is None else t1_ns)


def ratio_pct(run, ctx, part: str, whole: str):
    """100 x the window's change of counter ``part`` over ``whole``."""
    got = counts(run, ctx)
    if not got or not got.get(whole):
        return None
    return 100.0 * got.get(part, 0) / got[whole]
