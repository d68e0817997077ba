"""Time a request waited for a slot, 90th percentile, in ms: the program's
``serve.queue_wait_s`` samples (the batcher's clock at the slot take less
the request's arrival) stamped in the window."""
import numpy as np

from harness import program


def read(run, ctx):
    tr = program.tracing()
    if run["job"] != "serve" or tr is None:
        return None
    got = tr.samples_between(*program.window_ns(run, ctx))
    waits = [s.value for s in got or () if s.name == "serve.queue_wait_s"]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
