"""Time to first token, 90th percentile, in ms: from each request's due
time in the open loop to its first token being ready, over every request
due in the window."""
import numpy as np


def read(run, ctx):
    if run["job"] != "serve":
        return None
    ttft = [r["times"][0] - r["due"] for r in run["requests"] if r["times"]]
    return 1e3 * float(np.percentile(ttft, 90)) if ttft else None
