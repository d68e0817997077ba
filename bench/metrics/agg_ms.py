"""Device milliseconds per round of every program of the round but client
training: factor extraction, the grouped aggregation and SVD realloc per
shape bucket, and the jitted write-back."""

TRAIN_PROGRAM = "jit_run"


def read(run, ctx):
    t = run["trace"]
    if run["job"] != "rounds" or not t:
        return None
    other = sum(v for k, v in t["programs"].items() if k != TRAIN_PROGRAM)
    return 1e3 * other / len(run["rounds"])
