"""Process start to the first measured instant: imports, weights, engine,
compile or cache load, warm-up (and the lead-in of a mix that has one), in
seconds."""


def read(run):
    return run.setup_s
