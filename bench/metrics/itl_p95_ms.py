"""Gap between successive output tokens of a request, 95th percentile, in
ms, over every gap of every request in the window."""
import numpy as np


def read(run, ctx):
    if run["job"] != "serve":
        return None
    gaps = [b - a for r in run["requests"]
            for a, b in zip(r["times"], r["times"][1:])]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
