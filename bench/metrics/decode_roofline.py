"""Decode step's share of its roofline, in percent: the least time each
traced decode step could take (harness.counts.decode_step_bound: the
larger of its operations over peak FLOP/s and the bytes it needs -- the
weights, the live KV prefix of its active slots and the adapter columns in
use -- over peak HBM bytes/s) summed, over the decode program's device
time."""
from harness.counts import decode_step_bound

DECODE = "jit_decode_impl"


def read(run, ctx):
    t = run["trace"]
    if run["job"] != "serve" or not t or not t["programs"].get(DECODE):
        return None
    bound = sum(decode_step_bound(ctx.cfg, s["contexts"], s["ranks"],
                                  s["pages"], ctx.peak)
                for s in run["steps"] if s["kind"] == "decode")
    return 100.0 * bound / t["programs"][DECODE]
