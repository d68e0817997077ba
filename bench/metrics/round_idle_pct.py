"""Share of the traced window in which no operation ran on the device."""
from harness.trace import idle_pct


def read(run, ctx):
    return idle_pct(run["trace"]) if run["job"] == "rounds" else None
