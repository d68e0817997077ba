"""Host milliseconds before the device has any of a round's work: per
round in the window, from the start of the program's ``fl.round`` span to
the end of its ``fl.train`` span (sampling, the data pipeline's batches,
host stacking and the training program's dispatch with its host-to-device
copy), averaged over the rounds."""
from harness import program


def read(run, ctx):
    if run["job"] != "rounds":
        return None
    got = program.spans(run, ctx, "fl.train")
    if got is None:
        return None
    trains, by_index = got
    preps = [t.end_ns - by_index[t.parent].start_ns for t in trains
             if t.parent in by_index
             and by_index[t.parent].name == "fl.round"]
    return 1e-6 * sum(preps) / len(preps) if preps else None
