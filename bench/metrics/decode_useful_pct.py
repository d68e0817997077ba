"""Share of the decoded rows that were live, in percent: the window's
change of the program's ``serve.decode_live`` counter over that of
``serve.decode_rows`` (every decode step runs all slots)."""
from harness import program


def read(run, ctx):
    if run["job"] != "serve":
        return None
    return program.ratio_pct(run, ctx, "serve.decode_live",
                             "serve.decode_rows")
