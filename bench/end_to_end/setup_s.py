"""Process start to the first due request: imports, weights, engine,
warm-up and, in a checkout's first run, compilation."""


def read(run):
    return run.setup_s
