"""Host milliseconds of a scheduler step outside the engine: the mean self
time of the program's ``serve.step`` spans in the window, i.e. each step
less its calls into the engine (``serve.admit``, ``serve.decode``) and its
waits for their tokens (``serve.wait``)."""
from harness import program


def read(run, ctx):
    if run["job"] != "serve":
        return None
    got = program.spans(run, ctx, "serve.step")
    if got is None:
        return None
    tr = program.tracing()
    steps = got[0]
    return 1e-6 * sum(tr.self_ns(s) for s in steps) / len(steps)
