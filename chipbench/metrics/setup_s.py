"""Process start to window start: params and pool from the seed, engine
construction, warm-up (compilation or persistent-cache loads)."""


def read(run):
    return run.setup_s
