"""Set-up seconds: process start to the first timed step (loading, data
and weights, compilation or cache fetches, warm-up and the rounds or
requests that set-up drives), on the host clock."""


def read(run, ctx):
    return run["setup"]["total_s"]
