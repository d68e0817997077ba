"""Programs compiled or fetched from the persistent cache in set-up under
one of the program's spans (``fl.*``, ``serve.*``): the change of the
program's ``compiles:<span>`` counters from the process's start to the
window's. The benchmark's own programs (weights, data) run under no
program span and are not counted."""
from harness import program

PREFIXES = ("compiles:fl.", "compiles:serve.")


def read(run, ctx):
    start, _ = program.window_ns(run, ctx)
    got = program.counts(run, ctx, int(ctx.t_start * 1e9), start)
    if got is None:
        return None
    return float(sum(n for k, n in got.items() if k.startswith(PREFIXES)))
