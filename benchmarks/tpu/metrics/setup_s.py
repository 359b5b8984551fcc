"""Process start to window start: imports, weights, warm-up, compiles."""


def read(run):
    return run.setup_s
