"""Device milliseconds per admit: the prefill program (``jit_prefill_impl``)
and the engine's eager cache-seeding and slot updates, i.e. every program
of the window but the decode step, over the admits."""

DECODE = "jit_decode_impl"


def read(run, ctx):
    t = run["trace"]
    admits = sum(1 for s in run["steps"] if s["kind"] == "admit")
    if run["job"] != "serve" or not t or not admits:
        return None
    return 1e3 * sum(v for k, v in t["programs"].items()
                     if k != DECODE) / admits
