"""Wall seconds per federated round: the window, which ends when the last
round's landed global factors are ready, over the rounds it completed."""


def read(run, ctx):
    if run["job"] != "rounds" or not run["rounds"]:
        return None
    return run["window_s"] / len(run["rounds"])
