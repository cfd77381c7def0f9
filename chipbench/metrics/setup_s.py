"""Process start to the first timed round: imports, weights from the seed,
compilation or the compile cache, and one warm call of each program."""


def read(run):
    return run.setup_s
