"""Device milliseconds per round of the client training program (the
round engine's masked, vmapped local-training jit, named ``jit_run``)."""

TRAIN_PROGRAM = "jit_run"


def read(run, ctx):
    t = run["trace"]
    if run["job"] != "rounds" or not t or TRAIN_PROGRAM not in t["programs"]:
        return None
    return 1e3 * t["programs"][TRAIN_PROGRAM] / len(run["rounds"])
