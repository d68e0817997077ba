"""Device milliseconds per decode step (``jit_decode_impl``)."""

DECODE = "jit_decode_impl"


def read(run, ctx):
    t = run["trace"]
    n = sum(1 for s in run["steps"] if s["kind"] == "decode")
    if run["job"] != "serve" or not t or DECODE not in t["programs"] or not n:
        return None
    return 1e3 * t["programs"][DECODE] / n
