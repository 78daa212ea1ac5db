"""Process start to window start: imports, weights, engine, warm-up."""


def read(run):
    return run.setup_s
