"""Set-up: imports, weights made from the seed, engine, warm-up, compiles."""


def read(run):
    return run.setup_s
