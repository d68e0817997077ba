"""Share of the prefilled rows that held a request, in percent: the
window's change of the program's ``serve.admitted`` counter over that of
``serve.prefill_rows`` (every admit prefills all slots)."""
from harness import program


def read(run, ctx):
    if run["job"] != "serve":
        return None
    return program.ratio_pct(run, ctx, "serve.admitted",
                             "serve.prefill_rows")
